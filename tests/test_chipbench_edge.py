"""`chipbench/readers/idle_by_edge`: the C++ edge's stamps, carried in the
metadata of the `pump.admit` profiler events, rebuilt on the trace's clock and
set against the device's idle time.  Held to hand-made rows, to a real
`jax.profiler` trace of a daemon on the CPU backend, and to the known answer of
`chipbench/data/recorded_edge.json` (a cut of a traced chip run)."""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench.readers import idle_by_edge, idle_by_phase, phase_ms_seen  # noqa: E402
from gubernator_tpu import native, saturation, wire  # noqa: E402

ORDER = [p for p, _ in saturation.WATERFALL]
NO_REQUEST = ["epoll.wait", "pump.take", "window.idle"]


def _admit(thread, start, mono_ns, take, edge="", sends=""):
    return [thread, float(start), {"mono_ns": str(mono_ns), "take": str(take), "edge": edge, "sends": sends}]


def test_a_gap_half_under_a_take_alone_and_half_under_an_edge_read_gives_half():
    rows = [["t0", "pump.take", 0, 1000]]
    edge_rows = [["edge.recv", 500, 1200]]
    got = idle_by_edge.attribute([(0, 1000)], rows, ORDER, NO_REQUEST, edge_rows)
    assert got == {"idle": pytest.approx(1000e-9), "no_request": pytest.approx(1000e-9),
                   "edge_io": pytest.approx(500e-9), "edge.recv": pytest.approx(500e-9),
                   "edge.handoff": 0.0, "edge.send": 0.0}
    assert 100.0 * got["edge_io"] / got["idle"] == pytest.approx(50.0)


def test_an_edge_interval_counts_only_where_nothing_but_waiting_is_under_way():
    """Idle under `dispatch.commit` is the commit's whatever the acceptor does;
    idle under no phase at all is unattributed, not the edge's."""
    rows = [["t0", "pump.take", 0, 400], ["t1", "dispatch.commit", 100, 200]]
    edge_rows = [["edge.send", 0, 300], ["edge.recv", 250, 600]]
    got = idle_by_edge.attribute([(0, 600)], rows, ORDER, NO_REQUEST, edge_rows)
    assert got["no_request"] == pytest.approx(300e-9)  # 0-100 and 200-400
    assert got["edge_io"] == pytest.approx(300e-9)
    assert got["edge.send"] == pytest.approx(200e-9)   # 0-100, 200-300
    assert got["edge.recv"] == pytest.approx(150e-9)   # 250-400; 400-600 has no phase
    assert got["edge_io"] <= got["no_request"] <= got["idle"]


def test_rebuild_shifts_the_stamps_by_the_median_offset_of_the_anchors():
    admits = [
        _admit("t0", 1_000_100, 100, 40, edge="7:-900:-500:-300", sends="6:-2000:-1500"),
        _admit("t0", 2_000_200, 1_000_200, 50, edge="8:-800:-400:-200;9:-700:-300:-100"),
        _admit("t1", 3_009_000, 2_000_000, 60),  # an anchor read late: 9,000 where the others say 1,000,000
    ]
    edge_rows, takes, offset = idle_by_edge.rebuild(admits)
    assert offset == 1_000_000
    assert edge_rows[:3] == [["edge.recv", 999_200, 999_600], ["edge.handoff", 999_600, 999_800],
                             ["edge.send", 998_100, 998_600]]
    assert [r[0] for r in edge_rows].count("edge.recv") == 3 and len(edge_rows) == 7
    assert takes == [["t0", 1_000_100.0, 1_000_140], ["t0", 2_000_200.0, 2_000_250], ["t1", 3_009_000.0, 3_000_060]]
    with pytest.raises(ValueError):
        idle_by_edge.rebuild([_admit("t0", 1, 0, 0, edge="7:1:2")])
    # A `pump.account` event carries drained answers and no take.
    account = ["t2", 1_000_500.0, {"mono_ns": "500", "sends": "7:-300:-100"}]
    edge_rows, takes, offset = idle_by_edge.rebuild(admits[:2] + [account])
    assert offset == 1_000_000 and len(takes) == 2
    assert edge_rows[-1] == ["edge.send", 1_000_200, 1_000_400]


def test_the_take_is_held_to_the_end_of_its_pump_take_event():
    takes = [["t0", 1000.0, 900], ["t0", 3000.0, 2950], ["t1", 500.0, 100]]
    rows = [["t0", "pump.take", 0, 940], ["t0", "pump.take", 1500, 2900], ["t0", "pump.take", 3100, 3200],
            ["t0", "pump.admit", 1000, 1400]]
    assert idle_by_edge.take_distances_ns(takes, rows) == [40, 50]  # t1 has no pump.take: no pair


def test_a_program_is_paired_with_the_launch_nearest_to_it():
    dev, mod = "/device:TPU:0", "XLA Modules"
    programs = [[dev, mod, "jit__rounds_packed_mesh(1)", 1300.0, 50.0], [dev, mod, "jit__rounds_packed_mesh(1)", 10_950.0, 50.0],
                [dev, mod, "jit__sync_body(2)", 5000.0, 50.0],      # left out by name
                [dev, mod, "jit__rounds_packed_mesh(1)", 26_100.0, 50.0],  # no launch within half a spacing
                ["/device:TPU:1", mod, "jit__rounds_packed_mesh(1)", 0.0, 50.0]]  # not the first device
    rows = [["t0", "dispatch.launch", 1000, 1200], ["t0", "dispatch.launch", 11_000, 11_200],
            ["t0", "dispatch.launch", 21_000, 21_200]]
    assert idle_by_edge.launch_to_program_ns(programs, rows, ["sync_body"]) == [-50.0, 125.0, 1, 2]
    assert idle_by_edge.launch_to_program_ns(programs, rows[:1], ["sync_body"]) is None


def test_without_stamps_the_reader_reads_nothing():
    with open(os.path.join(REPO, "chipbench", "data", "recorded_phases.json")) as f:
        recorded = json.load(f)  # a program from before the stamps: phases, no `mono_ns`
    c = recorded["cuts"][0]
    assert idle_by_edge.from_rows(
        [tuple(g) for g in c["gaps"]], c["thread_rows"], [], [], recorded["order"],
        recorded["no_request"], recorded["exclude"]) is None
    ctx = {"after": {"latency": {"phases": {}}}, "trace": {"xplane": "/nonexistent"}, "device": {"platform": "tpu"}}
    assert idle_by_edge.read(ctx, {"no_request": NO_REQUEST}) is None
    # And the three phase metrics against a daemon that has never observed them.
    seen = {"before": {"latency": {"phases": {}}}, "after": {"latency": {"phases": {"ingress.parse": {"sum_ms": 1, "count": 1}}}}}
    for name in ("edge.recv", "edge.handoff", "edge.send"):
        with open(os.path.join(REPO, "chipbench", "layer_metrics", f"{name}_ms_per_req.json")) as f:
            assert phase_ms_seen.read(seen, json.load(f)["params"]) is None


def test_the_recorded_chip_trace_gives_its_known_answer():
    with open(os.path.join(REPO, "chipbench", "data", "recorded_edge.json")) as f:
        rec = json.load(f)
    got = idle_by_edge.from_rows(
        [tuple(g) for g in rec["gaps"]], rec["thread_rows"], rec["admits"], rec["program_rows"],
        rec["order"], rec["no_request"], rec["exclude"])
    want = rec["answer"]
    assert set(got["seconds"]) == set(want["seconds"])
    for key, seconds in want["seconds"].items():
        assert got["seconds"][key] == pytest.approx(seconds, abs=1e-9), key
    assert got["takes"] == want["takes"] and got["intervals"] == want["intervals"]
    assert got["offset_ns"] == pytest.approx(want["offset_ns"])
    assert list(got["take_distance_ns"]) == pytest.approx(want["take_distance_ns"])
    assert list(got["launch_to_program_ns"]) == pytest.approx(want["launch_to_program_ns"])
    # What the cut shows: the chip run's stamps lie within 0.2 ms of the
    # trace's own events, some of the "no request" idle time is the edge's,
    # and never more than all of it.
    assert want["take_distance_ns"][0] < 200_000 and want["take_distance_ns"][1] >= 10
    s = want["seconds"]
    assert 0 < s["edge_io"] <= s["no_request"] <= s["idle"]
    assert sum(hi - lo for lo, hi in rec["gaps"]) / 1e9 == pytest.approx(s["idle"])
    # The same gaps and threads through the reader it builds on.
    by_phase = idle_by_phase.attribute(
        [tuple(g) for g in rec["gaps"]], rec["thread_rows"], rec["order"], rec["no_request"])
    assert sum(by_phase.get(p, 0.0) for p in rec["no_request"]) == pytest.approx(s["no_request"])


@pytest.mark.skipif(not native.available(), reason="native runtime unavailable")
def test_under_a_profiler_session_the_stamps_ride_the_takes_and_rebuild_on_its_clock(tmp_path):
    """A daemon on the CPU backend under a real `jax.profiler` trace: every
    take's `pump.admit` event carries its frames' stamps, the `pump.account`
    events the answers drained in them, and the rebuilt take time
    lies inside that take's `pump.take` event, a fraction of a millisecond
    before its end."""
    import jax

    from gubernator_tpu.cluster import fast_test_behaviors
    from gubernator_tpu.config import DaemonConfig
    from gubernator_tpu.daemon import Daemon
    from gubernator_tpu.utils.clock import Clock

    behaviors = fast_test_behaviors()
    behaviors.global_sync_wait_s = 3600.0
    behaviors.multi_region_sync_wait_s = 3600.0
    behaviors.native_ingress = True
    d = Daemon(
        DaemonConfig(
            listen_address="127.0.0.1:0", grpc_listen_address="127.0.0.1:0",
            cache_size=4096, global_cache_size=256, behaviors=behaviors,
            peer_discovery_type="static", native_http=True, warmup_shapes=[],
        ),
        clock=Clock(),
    ).start()
    n, frames = 64, 40
    try:
        d.set_peers([d.peer_info])
        frame = wire.encode_ingress_frame((
            ["traced"] * n, [f"{i}k" for i in range(n)], np.zeros(n, np.int32), np.zeros(n, np.int32),
            np.ones(n, np.int64), np.full(n, 1_000_000, np.int64), np.full(n, 3_600_000, np.int64),
        ))
        req = urllib.request.Request(
            f"http://{d.gateway.address}/v1/GetRateLimits", data=frame,
            headers={"Content-Type": wire.COLUMNS_CONTENT_TYPE})

        def ask():
            with urllib.request.urlopen(req, timeout=30) as resp:
                assert resp.status == 200

        ask()  # the bucket's programs are loaded before the trace
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            for _ in range(frames):
                ask()
        finally:
            jax.profiler.stop_trace()
    finally:
        d.close()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    admits = idle_by_edge.load_stamps(path)
    assert sum("take" in stats for _, _, stats in admits) == frames  # a `pump.admit` a take
    assert all(("take" in stats) == ("edge" in stats) and ("take" in stats or "sends" in stats)
               for _, _, stats in admits)  # the others: `pump.account` events that drained answers
    edge_rows, takes, _ = idle_by_edge.rebuild(admits)
    assert len(takes) == frames
    by_name = {name: [r for r in edge_rows if r[0] == name] for name in idle_by_edge.EDGE_PHASES}
    assert len(by_name["edge.recv"]) == len(by_name["edge.handoff"]) == frames
    assert frames - 1 <= len(by_name["edge.send"]) <= frames + 1  # the ask before the trace; the last answer
    assert all(0 <= hi - lo < 1e9 for _, lo, hi in edge_rows)
    thread_rows = idle_by_phase.load_threads(path, set(ORDER))
    distances = idle_by_edge.take_distances_ns(takes, thread_rows)
    assert len(distances) >= frames - 2  # a take under way when the trace began has no pump.take event
    # The reading is taken in C++ as the take wakes, the event ends once Python
    # has built the batch's views: 0.2 ms of this sandbox's CPU under the
    # profiler (0.19 at the least, 0.24 at the median), so the bound here is
    # twice the chip run's 0.2 ms, and the reading must lie INSIDE its event.
    assert statistics.median(distances) < 400_000, sorted(distances)
    spans = [(lo, hi) for _, name, lo, hi in thread_rows if name == "pump.take"]
    assert sum(any(lo <= t <= hi for lo, hi in spans) for _, _, t in takes) >= frames - 2
    # The intervals lie where the trace's own events say a request was on its
    # way in: a frame's hand-off ends inside the `epoll.wait` event of the
    # worker that submitted it (edge.next holds both native calls).
    waits = [(lo, hi) for _, name, lo, hi in thread_rows if name == "epoll.wait"]
    inside = sum(any(lo <= end <= hi for lo, hi in waits) for _, _, end in by_name["edge.handoff"])
    assert inside >= frames - 2, (inside, frames)
