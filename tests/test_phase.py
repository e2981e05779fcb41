"""`saturation.phase`, the one span primitive of the waterfall: one clock
reading that lands in the always-on reservoir, the host sampler's tag, the
sampled span (with links and the take's ticket) and — while a profiler
session runs — the profiler's own trace.  And the path it is used on: a
daemon with sampling on still serves frames on the native lane."""

from __future__ import annotations

import glob
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from gubernator_tpu import native, profiling, saturation, telemetry, tracing, wire
from gubernator_tpu.cluster import fast_test_behaviors
from gubernator_tpu.config import DaemonConfig
from gubernator_tpu.daemon import Daemon
from gubernator_tpu.saturation import phase
from gubernator_tpu.service import ServiceConfig, V1Service
from gubernator_tpu.types import Behavior, PeerInfo, RateLimitRequest
from gubernator_tpu.utils.clock import Clock

from .conftest import one_device_store


@pytest.fixture(autouse=True)
def _clean():
    saturation.reset()
    tracing.reset()
    yield
    saturation.reset()
    tracing.reset()


@pytest.fixture
def sampled():
    prev = tracing.sample_rate()
    tracing.set_sample_rate(1.0)
    yield
    tracing.set_sample_rate(prev)


def _stats(name):
    return saturation.phase_snapshot().get(name)


# ---------------------------------------------------------------------
# Off (no profiler session, batch not sampled): reservoir and tag only
# ---------------------------------------------------------------------
def test_phase_feeds_reservoir_and_reports_its_reading():
    with phase("dispatch.stage") as ph:
        pass
    snap = _stats("dispatch.stage")
    assert snap["count"] == 1
    assert ph.dt_s >= 0.0
    assert snap["sum_ms"] == pytest.approx(ph.dt_s * 1e3, abs=1e-3)
    assert tracing.spans_snapshot() == []  # bt is None: no span


def test_phase_tags_the_thread_for_the_sampler():
    ident = threading.get_ident()
    with phase("dispatch.launch"):
        assert profiling._scopes[ident] == "dispatch.launch"
    assert ident not in profiling._scopes


def test_phases_nest_each_observed_once_inner_inside_outer():
    ident = threading.get_ident()
    with phase("dispatch.prepare") as outer:
        with phase("dispatch.plan_wait") as inner:
            assert profiling._scopes[ident] == "dispatch.plan_wait"
        assert profiling._scopes[ident] == "dispatch.prepare"
    assert _stats("dispatch.prepare")["count"] == 1
    assert _stats("dispatch.plan_wait")["count"] == 1
    assert inner.dt_s <= outer.dt_s


def test_exception_inside_still_closes_the_phase(sampled):
    ident = threading.get_ident()
    bt = tracing.new_batch([tracing.SpanContext(7, 9)])
    with pytest.raises(RuntimeError):
        with phase("dispatch.fetch", bt, ticket=3):
            raise RuntimeError("boom")
    assert ident not in profiling._scopes
    assert _stats("dispatch.fetch")["count"] == 1
    (span,) = tracing.spans_snapshot()
    assert span["name"] == "dispatch.fetch"
    assert span["attrs"] == {"ticket": 3, "error": "boom"}


def test_name_reassigned_inside_goes_to_the_reservoir_it_names():
    with phase("global.sync_drain") as ph:
        ph.name = "global.tick_idle"
    assert _stats("global.sync_drain") is None
    assert _stats("global.tick_idle")["count"] == 1


# ---------------------------------------------------------------------
# Sampled batch: the span, its links, the shared ticket
# ---------------------------------------------------------------------
def test_sampled_phase_records_a_linked_span_with_the_ticket(sampled):
    lane = tracing.SpanContext(0xABC, 0xDEF)
    bt = tracing.new_batch([lane])
    with phase("dispatch.prepare", bt) as ph:
        ph.note(ticket=41, lanes=8)  # learned inside, under the plan lock
    with phase("dispatch.launch", bt, ticket=41, fused=1):
        pass
    spans = tracing.spans_snapshot(lane.trace_hex)  # found through the link
    assert [s["name"] for s in spans] == ["dispatch.prepare", "dispatch.launch"]
    for s in spans:
        assert s["trace_id"] == bt.ctx.trace_hex
        assert s["parent_id"] == bt.ctx.span_hex
        assert s["links"] == [{"trace_id": lane.trace_hex, "span_id": lane.span_hex}]
        assert s["attrs"]["ticket"] == 41
    assert spans[0]["attrs"]["lanes"] == 8 and spans[1]["attrs"]["fused"] == 1


def test_new_batch_rolls_for_a_batch_without_member_contexts(sampled):
    assert tracing.new_batch() is None  # nothing to link, no roll asked
    bt = tracing.new_batch(roll=True)  # a native take at sample rate 1
    assert bt is not None and bt.links == ()
    tracing.set_sample_rate(0.0)
    assert tracing.new_batch(roll=True) is None


# ---------------------------------------------------------------------
# On: a profiler session is running
# ---------------------------------------------------------------------
def test_with_a_profiler_session_the_phase_lies_in_its_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    assert not saturation._profiler_session_on()
    with phase("dispatch.commit") as ph:
        assert ph._ann is None  # (d) cost one check
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        assert saturation._profiler_session_on()
        with phase("dispatch.prepare") as ph:
            with phase("dispatch.plan_wait"):
                pass
            ph.note(ticket=5)
        with pytest.raises(ValueError):
            with phase("dispatch.stage", ticket=5):
                raise ValueError("closes in the trace too")
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("dispatch."):
                    found[ev.name] = (ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
    assert set(found) == {"dispatch.prepare", "dispatch.plan_wait", "dispatch.stage"}
    lo, hi, stats = found["dispatch.prepare"]
    assert str(stats["ticket"]) == "5"
    inner_lo, inner_hi, _ = found["dispatch.plan_wait"]
    assert lo <= inner_lo and inner_hi <= hi  # same clock, nested
    assert str(found["dispatch.stage"][2]["ticket"]) == "5"
    assert _stats("dispatch.stage")["count"] == 1


# ---------------------------------------------------------------------
# The sites
# ---------------------------------------------------------------------
def test_an_idle_global_tick_is_counted_on_its_own():
    svc = V1Service(ServiceConfig(cache_size=512))
    try:
        svc.set_peers([PeerInfo(grpc_address="127.0.0.1:1", is_owner=True)])
        svc.global_mgr._interval.stop()  # the ticks counted here are this test's own
        res = svc.store.sync_globals(svc.clock.now_ms())
        assert res.did_work is False
        assert _stats("global.tick_idle")["count"] == 1
        assert _stats("global.sync_drain") is None and _stats("global.sync") is None
        # Warm-up runs the one pass that loads the sync program and leaves
        # its `__warmup__` gslot active; the ticks after it are idle still.
        svc.store.warmup(svc.clock.now_ms())
        assert svc.store.gtable.active_gslots()
        assert _stats("global.sync_drain")["count"] == 1
        assert _stats("global.sync")["count"] == 1
        res = svc.store.sync_globals(svc.clock.now_ms())
        assert res.did_work is False
        assert _stats("global.tick_idle")["count"] == 2
        assert _stats("global.sync_drain")["count"] == 1
        assert _stats("global.sync")["count"] == 1
    finally:
        svc.close()


@pytest.mark.skipif(not native.available(), reason="native runtime unavailable")
def test_a_daemon_without_global_traffic_runs_no_sync_pass_until_one_arrives():
    """Plain frames across many ticks: every tick is `global.tick_idle`
    and `mesh:global_sync` does not run.  One GLOBAL request: answered,
    then exactly one pass on the next tick, then idle ticks again."""
    behaviors = fast_test_behaviors()  # a tick every 50 ms
    behaviors.native_ingress = True
    d = Daemon(
        DaemonConfig(
            listen_address="127.0.0.1:0", grpc_listen_address="127.0.0.1:0",
            cache_size=4096, global_cache_size=256, behaviors=behaviors,
            peer_discovery_type="static", native_http=True,
        ),
    ).start()
    base = f"http://{d.gateway.address}"

    def fetch(path, data=None, content_type="application/json"):
        req = urllib.request.Request(
            base + path, data=data, headers={"Content-Type": content_type}
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.read()

    def count(name):
        phases = json.loads(fetch("/debug/latency"))["phases"]
        return (phases.get(name) or {"count": 0})["count"]

    def passes():
        """Runs of the sync program, and the two phases only a pass observes."""
        runs = json.loads(fetch("/debug/device"))["programRuns"]["mesh:global_sync"]
        return runs["count"], count("global.sync"), count("global.sync_drain")

    n = 8
    frame = wire.encode_ingress_frame((
        ["plain"] * n, [f"k{i}" for i in range(n)],
        np.zeros(n, np.int32), np.zeros(n, np.int32),
        np.ones(n, np.int64), np.full(n, 100, np.int64),
        np.full(n, 3_600_000, np.int64),
    ))

    def serve_frames_for_ticks(ticks):
        until = count("global.tick_idle") + ticks
        deadline = time.monotonic() + 30
        while count("global.tick_idle") < until:
            assert time.monotonic() < deadline, "the GLOBAL tick stopped"
            fetch("/v1/GetRateLimits", frame, wire.COLUMNS_CONTENT_TYPE)

    try:
        d.set_peers([d.peer_info])
        before = passes()
        assert before[0] >= 1  # warm-up's own pass
        serve_frames_for_ticks(6)
        assert passes() == before

        body = json.dumps({"requests": [{
            "name": "g", "uniqueKey": "k", "hits": 3, "limit": 10,
            "duration": 60_000, "behavior": "GLOBAL",
        }]}).encode()
        (ans,) = json.loads(fetch("/v1/GetRateLimits", body))["responses"]
        assert int(ans["remaining"]) == 7 and not ans.get("error")
        deadline = time.monotonic() + 30
        while passes() == before:
            assert time.monotonic() < deadline, "the GLOBAL request was never synced"
            time.sleep(0.01)
        serve_frames_for_ticks(6)
        assert passes() == tuple(x + 1 for x in before)
        assert not d.service.store._global_pending
    finally:
        d.close()


def test_a_daemons_start_runs_one_sync_pass_and_feeds_the_tuner_nothing():
    """Warm-up syncs its own GLOBAL key; the manager, ticking since the
    service was built, is held off meanwhile.  So a start runs ONE pass, on
    the starting thread, and the tuner's window stays at its fall-back: the
    tick no longer races warm-up for the pass that loads the sync program."""
    behaviors = fast_test_behaviors()
    behaviors.global_sync_wait_s = None  # the auto-tuned window, as a daemon's
    d = Daemon(
        DaemonConfig(
            listen_address="127.0.0.1:0", grpc_listen_address="127.0.0.1:0",
            cache_size=4096, global_cache_size=256, behaviors=behaviors,
            peer_discovery_type="static", warmup_shapes=[],
        ),
    ).start()
    try:
        mgr = d.service.global_mgr
        deadline = time.monotonic() + 30
        while (_stats("global.tick_idle") or {"count": 0})["count"] < 3:
            assert time.monotonic() < deadline, "the GLOBAL tick never came back"
            time.sleep(0.02)
        assert _stats("global.sync")["count"] == 1
        assert mgr.measured_sync_cost_s is None
        assert mgr.sync_wait_s == mgr.SYNC_WAIT_FALLBACK_S
    finally:
        d.close()


def test_a_held_tick_lock_keeps_the_manager_from_the_pass_until_released():
    clock = Clock()
    clock.freeze(1_790_000_000_000)
    behaviors = fast_test_behaviors()  # a tick every 50 ms
    svc = V1Service(ServiceConfig(
        cache_size=256, global_cache_size=64, behaviors=behaviors, clock=clock,
    ))
    try:
        svc.set_peers([PeerInfo(grpc_address="127.0.0.1:1", is_owner=True)])
        req = RateLimitRequest(
            name="g", unique_key="held", hits=1, limit=10, duration=60_000,
            behavior=Behavior.GLOBAL,
        )
        with svc.global_mgr.tick_lock:
            svc.store.apply([req], clock.now_ms())
            assert svc.store._global_pending
            time.sleep(0.3)  # six tick periods
            assert _stats("global.sync") is None
        deadline = time.monotonic() + 30
        while _stats("global.sync") is None:
            assert time.monotonic() < deadline, "the released tick never ran the pass"
            time.sleep(0.01)
        assert not svc.store._global_pending
    finally:
        svc.close()


def test_waterfall_lists_every_phase_once_and_nests_under_a_parent():
    names = [p for p, _ in saturation.WATERFALL]
    assert len(names) == len(set(names)) and tuple(names) == saturation.PHASES
    for i, (name, depth) in enumerate(saturation.WATERFALL):
        assert depth in (0, 1)
        if depth:
            assert i > 0 and name.split(".")[0] == saturation.WATERFALL[i - 1][0].split(".")[0]


@pytest.mark.skipif(not native.available(), reason="native runtime unavailable")
@pytest.mark.parametrize("store", ["mesh", "one-device"])
def test_the_native_plan_is_a_phase_inside_every_prepare(store):
    """`dispatch.plan_native` (the C++ slot-table plan alone) is a depth-1
    phase listed after `dispatch.plan_wait`, and the store observes it once
    a prepare, inside `dispatch.prepare`'s time, over 8 shards and over one."""
    from gubernator_tpu.parallel.mesh import MeshBucketStore

    names = [p for p, _ in saturation.WATERFALL]
    at = names.index("dispatch.plan_native")
    assert saturation.WATERFALL[at] == ("dispatch.plan_native", 1)
    assert names[at - 2:at] == ["dispatch.prepare", "dispatch.plan_wait"]
    st = MeshBucketStore(capacity_per_shard=64) if store == "mesh" else one_device_store(64)
    n = 12
    for t in range(5):
        st.apply_columns(
            [f"pn{i % 7}" for i in range(n)], np.zeros(n, np.int32), np.zeros(n, np.int32),
            np.ones(n, np.int64), np.full(n, 100, np.int64), np.full(n, 60_000, np.int64),
            1_700_000_000_000 + t)
    plan, prepare = _stats("dispatch.plan_native"), _stats("dispatch.prepare")
    assert plan["count"] == prepare["count"] == 5
    assert 0 < plan["sum_ms"] <= prepare["sum_ms"]


@pytest.mark.skipif(not native.available(), reason="native runtime unavailable")
def test_the_tier_moves_are_a_phase_inside_the_launch():
    """`dispatch.moves` (the two-tier table's move launches) is a depth-1
    phase whose parent is `dispatch.launch`; a store with a back tier
    observes it inside the launch's time, and only for a launch that had
    moves to apply; a store without one never enters it."""
    names = [p for p, _ in saturation.WATERFALL]
    at = names.index("dispatch.moves")
    assert saturation.WATERFALL[at] == ("dispatch.moves", 1)
    assert next(p for p, d in reversed(saturation.WATERFALL[:at]) if d == 0) == "dispatch.launch"

    def frame(st, keys, t):
        n = len(keys)
        st.apply_columns(
            keys, np.zeros(n, np.int32), np.zeros(n, np.int32), np.ones(n, np.int64),
            np.full(n, 100, np.int64), np.full(n, 60_000, np.int64), 1_700_000_000_000 + t)

    plain = one_device_store(16)
    for t in range(3):
        frame(plain, [f"mv{t}_{i}" for i in range(8)], t)
    assert _stats("dispatch.moves") is None
    tiered = one_device_store(16, back_capacity_per_shard=64)
    frame(tiered, [f"mv0_{i}" for i in range(8)], 10)  # fits the front: nothing moves
    assert _stats("dispatch.moves") is None
    for t in range(1, 4):  # each frame's 8 creates demote 8 rows (the first: none, 16 slots)
        frame(tiered, [f"mv{t}_{i}" for i in range(8)], 10 + t)
    moves, launch = _stats("dispatch.moves"), _stats("dispatch.launch")
    assert moves["count"] == 2 and launch["count"] == 7
    assert 0 < moves["sum_ms"] < launch["sum_ms"]
    assert tiered.tables[0].tier_stats[2] == 16  # demotions


@pytest.mark.skipif(not native.available(), reason="native runtime unavailable")
def test_sampling_daemon_serves_frames_on_the_native_lane(sampled):
    """GUBER_TRACE_SAMPLE=1 no longer switches the native lane off: the
    frame is served by it (the ingress counters show it) and
    /debug/traces holds the take's batch spans, one ticket through them."""
    behaviors = fast_test_behaviors()
    behaviors.global_sync_wait_s = 3600.0
    behaviors.native_ingress = True
    behaviors.trace_sample = 1.0
    clock = Clock()
    d = Daemon(
        DaemonConfig(
            listen_address="127.0.0.1:0", grpc_listen_address="127.0.0.1:0",
            cache_size=4096, global_cache_size=256, behaviors=behaviors,
            peer_discovery_type="static", native_http=True,
        ),
        clock=clock,
    ).start()
    try:
        d.set_peers([d.peer_info])
        assert tracing.enabled() and d.gateway.pump.active
        n = 8
        frame = wire.encode_ingress_frame((
            ["traced"] * n, [f"k{i}" for i in range(n)],
            np.zeros(n, np.int32), np.zeros(n, np.int32),
            np.ones(n, np.int64), np.full(n, 100, np.int64),
            np.full(n, 3_600_000, np.int64),
        ))
        base = f"http://{d.gateway.address}"
        req = urllib.request.Request(
            base + "/v1/GetRateLimits", data=frame,
            headers={"Content-Type": wire.COLUMNS_CONTENT_TYPE},
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.status == 200
        stats = d.gateway.pump.stats()
        assert stats["frames"] >= 1 and stats["lanes"] >= n  # the native lane took it
        with urllib.request.urlopen(base + "/debug/traces", timeout=30) as resp:
            spans = json.loads(resp.read())["spans"]
        native_root = [s for s in spans if s["name"] == "batch.window"
                       and s["attrs"].get("lane") == "native"]
        assert native_root, [s["name"] for s in spans]
        trace_id = native_root[-1]["trace_id"]
        names = {s["name"] for s in spans if s["trace_id"] == trace_id}
        assert {"pump.admit", "dispatch.prepare", "dispatch.stage", "dispatch.launch",
                "dispatch.fetch", "dispatch.commit", "pump.outcome",
                "response.encode"} <= names, names
        tickets = {s["attrs"]["ticket"] for s in spans
                   if s["trace_id"] == trace_id and s["name"].startswith("dispatch.")
                   and "ticket" in s["attrs"]}
        assert len(tickets) == 1
        with urllib.request.urlopen(base + "/debug/device", timeout=30) as resp:
            startup = json.loads(resp.read())["startup"]
        assert {"backend", "table", "warmup", "listen"} <= set(startup["parts_s"])
        assert any(row["calls"] for row in startup["programs"].values())
    finally:
        d.close()
