"""The cell `v5e1-1m-keylimits.frames` at test size, on the CPU: 8,000 keys
of `chipbench/population.py` with the configuration's own `population` block
(a limit a key, log-uniform from 100 to 1,000,000) in 8,192 slots, loaded,
asked for and read back in 512-lane frames drawn by its scrambled Zipfian
0.99.  Every such frame carries more than 256 distinct configurations, so
every dispatch leaves the dictionary wire for the per-lane wire
(`MeshBucketStore._stage_columns`), which no cell ran before this one.

Held here: every lane of the load, the traffic and the read-back equals the
sequential oracle on S = 1 and S = 4 and through a served daemon's native
lane; the same frames on either wire answer alike; 256 configurations ride
the dictionary and 257 the lanes, by the counters of the `mesh` block; the
counters add up; a per-lane launch has a label and the uploads a phase of
their own; nothing compiles after warm-up; and the cell's files say what the
issue says.  Everything is made from SEED."""

from __future__ import annotations

import functools
import json
import os
import sys

import jax
import numpy as np
import pytest

from gubernator_tpu import native, saturation, telemetry, tracing
from gubernator_tpu.models.shard import make_columns
from gubernator_tpu.ops import buckets
from gubernator_tpu.parallel.mesh import MeshBucketStore
from gubernator_tpu.types import Algorithm, RateLimitRequest

from . import oracle as orc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench.daemon import Http  # noqa: E402
from chipbench.generators import frames as gen_frames  # noqa: E402
from chipbench.population import Population  # noqa: E402
from chipbench.readers import mesh_tally, phase_ms_per  # noqa: E402

pytestmark = pytest.mark.skipif(
    not native.available(), reason="the columnar path needs the native host runtime")

SEED = 33
KEYS = 8_000
SLOTS = 8_192
LANES = 512
NAME = "bench"
T0 = 1_790_000_000_000
TRAFFIC_FRAMES = 12
DRAIN_FRAMES = 2  # Zipfian frames of DRAIN_HITS a lane: the hot buckets run dry
DRAIN_HITS = 60_000
READBACK_FRAMES = 3
CELL = "v5e1-1m-keylimits.frames"
TWIN = "v5e1-1m.frames"
SHARDS = [1, 4]
WIRE_COUNTERS = ("dispatches", "lanes", "laneWireDispatches", "laneWireLanes", "configRows", "uploads")
# One packed buffer, one device_put: the per-lane wire's stage makes the one
# transfer call the dictionary wire's makes (tests/test_lane_wire.py counts
# the real calls).
LANE_WIRE_UPLOADS = 1
LABEL_LANES = "mesh:dispatch:solo:lanes"
LABEL_DICT = "mesh:dispatch:solo:narrow"


def _cell_json(*parts):
    with open(os.path.join(REPO, "chipbench", *parts)) as f:
        return json.load(f)


def _population(config: str) -> Population:
    return Population(_cell_json("configs", config + ".json")["population"], KEYS, SEED)


@pytest.fixture(scope="module")
def pop():
    assert _cell_json("traffic", "frames.json")["lanes_per_request"] == 4096  # the cell's; 512 here
    return _population("v5e1-1m-keylimits")


def _takes(pop):
    """[(key indices, hits, now_ms)]: the load (every key once, one hit, the
    tail frame filled with hits=0 re-reads of loaded token keys, as the
    harness fills it), the traffic (Zipfian frames, one hit a lane, seconds
    apart so that leaky buckets leak; then, beyond what the cell sends, two
    frames of 60,000 hits a lane, since a limit a key leaves no bucket of
    8,000 dry after twelve frames), the read-back (hits=0)."""
    rng = np.random.default_rng([SEED, 0x6B6C696D])
    fill = np.flatnonzero(pop.algo[: 4 * LANES] == 0)[:LANES]
    out, now = [], T0
    for lo in range(0, pop.n, LANES):
        hi = min(lo + LANES, pop.n)
        idx = np.concatenate([np.arange(lo, hi), fill[: LANES - (hi - lo)]])
        hits = np.concatenate([np.ones(hi - lo, np.int64), np.zeros(LANES - (hi - lo), np.int64)])
        out.append((idx, hits, now))
        now += int(rng.integers(1, 20))
    for t in range(TRAFFIC_FRAMES + DRAIN_FRAMES):
        now += int(rng.integers(0, 4000))
        hits = 1 if t < TRAFFIC_FRAMES else DRAIN_HITS
        out.append((pop.draw(rng, LANES), np.full(LANES, hits, np.int64), now))
    for _ in range(READBACK_FRAMES):
        now += int(rng.integers(1, 20))
        out.append((pop.draw(rng, LANES), np.zeros(LANES, np.int64), now))
    return out


def _oracle(pop, takes):
    """What upstream's sequential algorithm answers, lane by lane."""
    cache = orc.OracleCache()
    answers = []
    for idx, hits, now in takes:
        rows = np.empty((len(idx), 4), np.int64)
        for lane, (i, h) in enumerate(zip(idx.tolist(), hits.tolist())):
            r = orc.apply(cache, RateLimitRequest(
                name=NAME, unique_key=pop.unique_key(i), hits=h, limit=int(pop.limit[i]),
                duration=pop.duration_ms, algorithm=Algorithm(int(pop.algo[i]))), now)
            rows[lane] = (int(r.status), r.limit, r.remaining, r.reset_time)
        answers.append(rows)
    return answers


@pytest.fixture(scope="module")
def takes(pop):
    return _takes(pop)


@pytest.fixture(scope="module")
def expected(pop, takes):
    return _oracle(pop, takes)


def _configurations(pop, idx, hits) -> int:
    """Distinct (algorithm, hits, limit) rows of a frame (one duration, no
    behaviour bit), reckoned apart from the program."""
    return len(np.unique(np.stack([pop.algo[idx], hits, pop.limit[idx]]), axis=1).T)


def _serve(pop, takes, shards: int, force_wire=None, warm: bool = False) -> dict:
    """The takes through a fresh `MeshBucketStore`; what it answered and what
    the process counted on the way."""
    keys = [f"{NAME}_{pop.unique_key(i)}" for i in range(pop.n)]
    telemetry.set_enabled(True)
    telemetry.reset()
    saturation.reset()
    # Four shards get 4,096 slots each: keys are owned by hash, so a shard of
    # 2,048 would evict what its uneven share overfills.
    store = MeshBucketStore(
        capacity_per_shard=SLOTS // min(shards, 2), devices=jax.devices()[:shards])
    if warm:
        store.warmup(T0 - 60_000, warm_shapes=[LANES])
        telemetry.mark_steady()
    out = {"store": store, "before": saturation.mesh_tally.snapshot(),
           "phases_before": saturation.phase_snapshot(),
           "runs_before": dict(telemetry.snapshot()["programRuns"])}
    answers = []
    for idx, hits, now in takes:
        r = store.apply_columns(
            [keys[i] for i in idx.tolist()], pop.algo[idx], np.zeros(len(idx), np.int32), hits,
            pop.limit[idx], np.full(len(idx), pop.duration_ms, np.int64), now,
            force_wire=force_wire)
        answers.append(np.stack([r["status"], r["limit"], r["remaining"], r["reset_time"]], axis=1))
    out.update(
        answers=answers, after=saturation.mesh_tally.snapshot(),
        phases=saturation.phase_snapshot(), runs=telemetry.snapshot()["programRuns"],
        steady_recompiles=telemetry.steady_recompile_count())
    return out


@pytest.fixture(scope="module")
def served(pop, takes):
    """shards -> the cell's run on that many devices (S = 1 warmed up first,
    as the daemon is); each is driven once."""

    @functools.cache
    def run(shards: int):
        return _serve(pop, takes, shards, warm=shards == 1)

    yield run
    telemetry.reset()
    saturation.reset()


def _grown(run, key):
    return run["after"][key] - run["before"][key]


def _runs(run, label) -> int:
    return (run["runs"].get(label, {"count": 0})["count"]
            - run["runs_before"].get(label, {"count": 0})["count"])


def _wrong(answers, expected):
    return [(t, np.flatnonzero((got != want).any(axis=1))[:5].tolist())
            for t, (got, want) in enumerate(zip(answers, expected)) if (got != want).any()]


# ---------------------------------------------------------------------
# The frames are the cell's, and every one of them leaves the dictionary
# ---------------------------------------------------------------------
def test_every_frame_of_load_traffic_and_readback_passes_256_configurations(pop, takes):
    counts = [_configurations(pop, idx, hits) for idx, hits, _ in takes]
    assert min(counts) > buckets.DICT_TABLE_ROWS, counts
    load = -(-KEYS // LANES)
    assert len(takes) == load + TRAFFIC_FRAMES + DRAIN_FRAMES + READBACK_FRAMES
    for idx, hits, _ in takes[load:load + TRAFFIC_FRAMES]:
        assert np.bincount(idx).max() >= 10  # the hottest key, many times in one frame
        assert set(pop.algo[idx].tolist()) == {0, 1}
    # The twin's population, the same keys in 16 plan tiers, stays far under it.
    twin = _population("v5e1-1m")
    assert (twin.key_bytes == pop.key_bytes).all() and (twin.algo == pop.algo).all()
    assert max(_configurations(twin, idx, hits) for idx, hits, _ in takes) <= 2 * 16 + 2 * 16


@pytest.mark.parametrize("shards", SHARDS)
def test_every_lane_equals_the_sequential_oracle(served, expected, shards):
    """Status, limit, remaining and reset of every lane of load, traffic and
    read-back, on one device and on four shards."""
    run = served(shards)
    assert _wrong(run["answers"], expected) == []
    assert sum(int((want[:, 0] == 1).sum()) for want in expected) > 0  # some bucket ran dry
    assert run["store"].size() >= KEYS
    run["store"].check_consistency()


@pytest.mark.parametrize("shards", SHARDS)
def test_every_dispatch_took_the_per_lane_wire_and_the_counters_add_up(served, pop, takes, shards):
    run = served(shards)
    n = len(takes)
    assert _grown(run, "dispatches") == _grown(run, "laneWireDispatches") == n
    assert _grown(run, "lanes") == _grown(run, "laneWireLanes") == n * LANES
    assert _grown(run, "uploads") == n * LANE_WIRE_UPLOADS
    # The program counted the configurations the frames hold, reckoned here.
    assert _grown(run, "configRows") == sum(_configurations(pop, idx, hits) for idx, hits, _ in takes)
    assert _runs(run, LABEL_LANES) == n and _runs(run, LABEL_DICT) == 0


@pytest.mark.parametrize("shards", SHARDS)
def test_the_uploads_are_a_phase_inside_the_stage(served, takes, shards):
    run = served(shards)
    before, after = run["phases_before"], run["phases"]

    def grown(name, field):
        return after[name][field] - before.get(name, {}).get(field, 0)

    assert grown("dispatch.upload", "count") == grown("dispatch.stage", "count") == len(takes)
    assert 0 < grown("dispatch.upload", "sum_ms") < grown("dispatch.stage", "sum_ms")


def test_no_compile_after_warm_up_on_the_cells_one_device(served):
    """Warm-up compiles the per-lane program of the warm bucket beside the
    dictionary's (`force_wire="narrow"`), under the per-lane label; the load,
    the traffic and the read-back then compile nothing."""
    run = served(1)
    assert run["steady_recompiles"] == 0
    assert run["runs_before"][LABEL_LANES]["count"] == 2  # distinct keys, then one key
    assert run["runs_before"][LABEL_DICT]["count"] == 2
    assert LABEL_LANES in telemetry.snapshot()["startup"]["programs"]


# ---------------------------------------------------------------------
# The two wires answer alike
# ---------------------------------------------------------------------
@pytest.mark.parametrize("config", [
    "v5e1-1m",  # 16 tiers: the dictionary by count, then the lanes forced
    "v5e1-1m-keylimits",  # a limit a key: the lanes by count, then forced
])
def test_the_same_frames_on_each_wire_answer_alike(config):
    """`force_wire` None lets the count choose; "narrow" and "wide" force the
    per-lane wire with the i32 and the i64 answer.  Lane for lane the same,
    and the oracle's."""
    pop = _population(config)
    takes = _takes(pop)
    want = _oracle(pop, takes)
    for wire in (None, "narrow", "wide"):
        run = _serve(pop, takes, 1, force_wire=wire)
        assert _wrong(run["answers"], want) == [], wire
        by_count_dict = wire is None and config == "v5e1-1m"
        assert _grown(run, "laneWireDispatches") == (0 if by_count_dict else len(takes)), wire
        assert _grown(run, "uploads") == len(takes) * (1 if by_count_dict else LANE_WIRE_UPLOADS)
        # A forced wire never asks the dictionary, so it counts no configuration.
        assert (_grown(run, "configRows") > 0) == (wire is None)
        label = {None: LABEL_DICT if by_count_dict else LABEL_LANES,
                 "narrow": LABEL_LANES, "wide": LABEL_LANES + "64"}[wire]
        assert _runs(run, label) == len(takes), (wire, sorted(run["runs"]))
    telemetry.reset()
    saturation.reset()


@pytest.mark.parametrize("configs,lane_wire", [(256, False), (257, True)])
def test_256_configurations_ride_the_dictionary_and_257_the_lanes(configs, lane_wire):
    """The edge of `DICT_TABLE_ROWS`, by the counters, and both are right."""
    n = 300
    limits = 1_000 + np.arange(n) % configs  # `configs` distinct limits over 300 keys
    keys = [f"edge_{i}" for i in range(n)]
    cols = make_columns(np.zeros(n, np.int32), np.zeros(n, np.int32), np.ones(n, np.int64),
                        limits.astype(np.int64), np.full(n, 60_000, np.int64), n)
    rows, enc = buckets.build_config_dict(cols, T0)
    assert rows == configs and (enc is None) == lane_wire
    telemetry.set_enabled(True)
    store = MeshBucketStore(capacity_per_shard=1024, devices=jax.devices()[:1])
    before = saturation.mesh_tally.snapshot()
    runs_before = dict(telemetry.snapshot()["programRuns"])
    cache = orc.OracleCache()
    for t in range(3):
        got = store.apply_columns(
            keys, np.zeros(n, np.int32), np.zeros(n, np.int32), np.ones(n, np.int64),
            limits.astype(np.int64), np.full(n, 60_000, np.int64), T0 + t)
        for lane in range(n):
            want = orc.apply(cache, RateLimitRequest(
                name="", unique_key=keys[lane], hits=1, limit=int(limits[lane]), duration=60_000), T0 + t)
            assert (int(got["status"][lane]), int(got["remaining"][lane]), int(got["reset_time"][lane])) == (
                int(want.status), want.remaining, want.reset_time), (t, lane)
    after = saturation.mesh_tally.snapshot()
    grown = {k: after[k] - before[k] for k in WIRE_COUNTERS}
    assert grown == {
        "dispatches": 3, "lanes": 3 * n, "configRows": 3 * configs,
        "laneWireDispatches": 3 * lane_wire, "laneWireLanes": 3 * n * lane_wire,
        "uploads": 3 * (LANE_WIRE_UPLOADS if lane_wire else 1)}
    label = LABEL_LANES if lane_wire else LABEL_DICT
    runs = telemetry.snapshot()["programRuns"]
    assert runs[label]["count"] - runs_before.get(label, {"count": 0})["count"] == 3


def test_a_sampled_takes_upload_span_names_its_wire(pop, takes):
    """`dispatch.upload` of a sampled take is a span beside `dispatch.stage`,
    and says which wire it uploaded."""
    keys = [f"{NAME}_{pop.unique_key(i)}" for i in range(pop.n)]
    store = MeshBucketStore(capacity_per_shard=SLOTS, devices=jax.devices()[:1])
    idx, hits, now = takes[-READBACK_FRAMES - 1]
    prev = tracing.sample_rate()
    tracing.set_sample_rate(1.0)
    try:
        spans = {}
        for wire, limit in (("lanes", pop.limit[idx]), ("dict", np.full(LANES, 100, np.int64))):
            bt = tracing.new_batch(roll=True)
            tracing.stage_batch_trace(bt)
            store.apply_columns(
                [keys[i] for i in idx.tolist()], pop.algo[idx], np.zeros(LANES, np.int32), hits,
                limit, np.full(LANES, pop.duration_ms, np.int64), now)
            spans[wire] = {s["name"]: s["attrs"] for s in tracing.spans_snapshot(bt.ctx.trace_hex)}
    finally:
        tracing.set_sample_rate(prev)
    for wire, found in spans.items():
        assert "dispatch.stage" in found and found["dispatch.upload"]["wire"] == wire


# ---------------------------------------------------------------------
# Through a served daemon's native lane
# ---------------------------------------------------------------------
def test_a_served_daemons_native_lane_answers_the_oracle_and_serves_the_counters(pop, takes, expected):
    """The cell's path at test size: `Daemon` with the native edge on one
    device, the harness's own frames and client, a frozen clock moved to each
    take's instant."""
    from gubernator_tpu.cluster import fast_test_behaviors
    from gubernator_tpu.config import DaemonConfig
    from gubernator_tpu.daemon import Daemon
    from gubernator_tpu.utils.clock import Clock

    behaviors = fast_test_behaviors()
    behaviors.global_sync_wait_s = 3600.0
    behaviors.multi_region_sync_wait_s = 3600.0
    clock = Clock()
    clock.freeze(T0 - 60_000)
    telemetry.set_enabled(True)
    daemon = Daemon(DaemonConfig(
        listen_address="127.0.0.1:0", grpc_listen_address="127.0.0.1:0", cache_size=SLOTS,
        global_cache_size=256, behaviors=behaviors, peer_discovery_type="static",
        native_http=True, devices=jax.devices()[:1], warmup_shapes=[LANES]), clock=clock).start()
    http = None
    try:
        daemon.set_peers([daemon.peer_info])
        address = f"127.0.0.1:{daemon.gateway._edge.port}"
        http = Http(address, timeout_s=60.0)
        before = http.get_json("/debug/device")["mesh"]
        frames_before = daemon.gateway.pump.stats()["frames"]
        answers = []
        for idx, hits, now in takes:
            clock.freeze(now)
            body = http.roundtrip(gen_frames.frame_payload(pop, idx, hits, address))
            answers.append(np.stack(gen_frames.decode(body, LANES), axis=1))
        assert _wrong(answers, expected) == []
        assert daemon.gateway.pump.stats()["frames"] - frames_before == len(takes)  # the native lane
        device = http.get_json("/debug/device")
        grown = {k: device["mesh"][k] - before[k] for k in WIRE_COUNTERS}
        assert grown["dispatches"] == grown["laneWireDispatches"] == len(takes)
        assert grown["lanes"] == grown["laneWireLanes"] == len(takes) * LANES
        assert grown["uploads"] == len(takes) * LANE_WIRE_UPLOADS
        assert grown["configRows"] > len(takes) * buckets.DICT_TABLE_ROWS
        assert device["steadyRecompiles"] == 0
        assert LABEL_LANES in device["startup"]["programs"]
        status = http.get_json("/debug/status")
        assert {k: status["wire"][k] for k in WIRE_COUNTERS} == {k: device["mesh"][k] for k in WIRE_COUNTERS}
        latency = http.get_json("/debug/latency")
        assert {"phase": "dispatch.upload", "depth": 1} in latency["waterfall"]
        assert latency["phases"]["dispatch.upload"]["count"] == latency["phases"]["dispatch.stage"]["count"]
        assert http.get_json("/debug/audit")["violationTotal"] == 0
    finally:
        if http is not None:
            http.close()
        daemon.close()
        telemetry.reset()
        saturation.reset()


# ---------------------------------------------------------------------
# The names, and the cell's files
# ---------------------------------------------------------------------
def test_the_upload_is_listed_inside_the_stage():
    names = [p for p, _ in saturation.WATERFALL]
    at = names.index("dispatch.upload")
    assert saturation.WATERFALL[at] == ("dispatch.upload", 1)
    assert saturation.WATERFALL[at - 1] == ("dispatch.stage", 0)


def test_the_cells_files_say_what_the_issue_says():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("v5e1-1m-keylimits", "frames", 1)
    entry = next(c for c in bench["configs"] if c["name"] == "v5e1-1m-keylimits")
    assert entry["file"] == "chipbench/configs/v5e1-1m-keylimits.json" and entry["reduced"] == []
    assert len(entry["source"]) <= 200 and "README.md" in entry["source"] and "RateLimitReq" in entry["source"]
    config, twin = _cell_json("configs", "v5e1-1m-keylimits.json"), _cell_json("configs", "v5e1-1m.json")
    assert config["source"] == entry["source"] and config["name"] == entry["name"]
    assert config["population"] == dict(twin["population"], limit_tiers=1_000_000)
    for same in ("chips", "env", "reduced", "guarantees", "control"):
        assert config[same] == twin[same], same
    assert config["assumed"][1:] == twin["assumed"][1:] and config["assumed"][0].startswith("limits: one a key")
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"] if CELL in m.get("workloads", [])}
    assert listed >= {
        "batcher.wait_ms", "batcher.lanes_per_dispatch", "kernel.us_per_dispatch", "kernel.apply_roofline",
        "kernel.rounds_us_per_dispatch", "plan.lock_wait_ms", "plan.native_ms_per_dispatch",
        "launch.lock_wait_ms", "batcher.pump_ms_per_take", "edge.unattributed_ms_per_req",
        "device.idle_unattributed_share", "device.idle_no_request_share", "xla.program_load_s",
        "mesh.stage_ms_per_dispatch", "wire.lane_share", "wire.configs_per_dispatch",
        "wire.uploads_per_dispatch", "wire.upload_ms_per_dispatch"}
    assert not listed & {"launch.sync_stall_ms", "batcher.queue_p99_ms", "mesh.pad_fill", "mesh.shard_skew"}
    for metric in bench["per_layer"]:
        if metric["name"].startswith("wire.") and metric["name"] != "wire.wide_share":  # PR 39's
            assert metric["workloads"] == [CELL, TWIN, "greg-10m.frames", "v5e1-1m-mixed.frames",
                                           "v5e1-1m-gw4.frames"]  # PRs 39, 41 and 45 appended theirs
            assert metric["moves"] == "req_p50_ms"
            spec = _cell_json("layer_metrics", metric["name"] + ".json")
            assert spec["reader"] in ("mesh_tally", "phase_ms_per")
            assert (spec["layer"], spec["unit"], spec["source"]) == (
                metric["layer"], metric["unit"], metric["source"])


# ---------------------------------------------------------------------
# The readers of the four wire.* metrics, on snapshots written out here
# ---------------------------------------------------------------------
def _snap(mesh=None, upload=None):
    device = {} if mesh is None else {"mesh": mesh}
    phases = {} if upload is None else {"dispatch.upload": {"count": upload[0], "sum_ms": upload[1]}}
    return {"device": device, "latency": {"phases": phases}}


def _read(name, ctx):
    spec = _cell_json("layer_metrics", name + ".json")
    return {"mesh_tally": mesh_tally, "phase_ms_per": phase_ms_per}[spec["reader"]].read(ctx, spec["params"])


LOADED = {"shards": 1, "dispatches": 253, "lanes": 1_003_520, "laneWireDispatches": 253,
          "laneWireLanes": 1_003_520, "configRows": 1_000_900, "uploads": 6_072}
# 2,000 frames more, of which 1,500 left the dictionary.
WINDOW = {"shards": 1, "dispatches": 2_253, "lanes": 1_003_520 + 2_000 * 4096,
          "laneWireDispatches": 1_753, "laneWireLanes": 1_003_520 + 1_500 * 4096,
          "configRows": 1_000_900 + 1_500 * 2_507 + 500 * 32, "uploads": 6_072 + 1_500 * 24 + 500}


@pytest.mark.parametrize("name,want", [
    ("wire.lane_share", 75.0),
    ("wire.configs_per_dispatch", (1_500 * 2_507 + 500 * 32) / 2_000),
    ("wire.uploads_per_dispatch", (1_500 * 24 + 500) / 2_000),
    ("wire.upload_ms_per_dispatch", 3.5),
])
def test_the_wire_readers_give_the_values_reckoned_by_hand(name, want):
    ctx = {"before": _snap(LOADED, (253, 800.0)), "after": _snap(WINDOW, (2_253, 7_800.0)), "requests": 2_000}
    assert _read(name, ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "wire.lane_share", "wire.configs_per_dispatch", "wire.uploads_per_dispatch", "wire.upload_ms_per_dispatch"])
def test_the_wire_readers_read_nothing_where_nothing_was_dispatched(name):
    """No `mesh` block, no `dispatch.upload` phase, or nothing between the
    snapshots: None, and no exception."""
    assert _read(name, {"before": _snap(), "after": _snap(), "requests": 0}) is None
    same = _snap(WINDOW, (2_253, 7_800.0))
    assert _read(name, {"before": same, "after": same, "requests": 0}) is None
