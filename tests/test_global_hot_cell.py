"""The cell `v5e4-mesh-1m-global.frames` at test size, on the CPU: 8,000 keys of
`chipbench/population.py` with the configuration's own `population` block in
32,768 slots, loaded, asked for and read back in 512-lane frames of the cell's
own generator (`chipbench/generators/frames_global_hot.py`) with a hot set of
16: every check of the 16 hottest keys carries GLOBAL, the bit a key's.

Held here: every lane of load, traffic and read-back equals the sequential
oracle on a mesh of S = 1, 2 and 4 (one served run a shard count); a take is
ONE dispatch of one round; the gslot table holds exactly the hot keys, each
with the shard `shard_of_key` gives; CONVERGENCE: after `sync_globals()` every
shard's replica row of every hot key holds the owner's status, which is the
oracle's, and a program whose broadcast psum drops every shard's rows but
shard 0's fails that; a real daemon on four devices keeps every such frame on
its native lane (`fallbacks` 0); a seed's frames hold `frames-1k`'s keys and
differ in the behaviour column alone; 31% of the pool's lanes carry the bit at
the cell's own size; the two counters and the readers of the six new metrics
give values reckoned by hand and read nothing on a program from before them;
the cell's files say what ISSUE 48 says.  Everything is made from SEED."""

from __future__ import annotations

import json
import os
import struct
import sys

import jax
import numpy as np
import pytest

from gubernator_tpu import native, saturation, telemetry
from gubernator_tpu.ops import global_ops
from gubernator_tpu.parallel import mesh as mesh_mod
from gubernator_tpu.parallel.mesh import SYNC_WIDTH, MeshBucketStore, shard_of_key
from gubernator_tpu.types import Algorithm, Behavior, RateLimitRequest

from . import oracle as orc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import gubc, trace_reduce  # noqa: E402
from chipbench.daemon import Http, metric_sum  # noqa: E402
from chipbench.generators import frames as gen_frames  # noqa: E402
from chipbench.generators import frames_global_hot as gen_hot  # noqa: E402
from chipbench.population import Population  # noqa: E402
from chipbench.readers import mesh_counted, mesh_tally, phase_ms_per, program_us_per_launch  # noqa: E402

pytestmark = pytest.mark.skipif(
    not native.available(), reason="the columnar path needs the native host runtime")

SEED = 48
KEYS = 8_000
SLOTS = 32_768
LANES = 512
HOT = 16
NAME = "bench"
T0 = 1_790_000_000_000
GLOBAL = int(Behavior.GLOBAL)
TRAFFIC_FRAMES = 12
READBACK_FRAMES = 2
SHARDS = [1, 2, 4]
CELL = "v5e4-mesh-1m-global.frames"
CONFIG = "v5e4-mesh-1m-global"
TRAFFIC = "frames-1k-global-hot"
BYPASS = "v5e4-mesh-1m.frames"
MIXED = "v5e1-1m-mixed.frames"
NEW_METRICS = ("global.lanes_per_take", "global.keys_per_take", "global.note_ms_per_take",
               "global.keys_per_pass", "global.sync_device_us_per_pass",
               "global.sync_collective_us_per_pass")
ALSO_LISTED = ("behavior.flagged_lane_share", "behavior.handle_ms_per_dispatch",
               "global.sync_hold_ms_per_pass", "global.sync_ms_per_req", "global.sync_rows_per_pass")
NATIVE_INGRESS = "gubernator_native_ingress_batches_total"


def _cell_json(*parts):
    with open(os.path.join(REPO, "chipbench", *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    return dict(_cell_json("traffic", TRAFFIC + ".json"), global_hot_keys=HOT)


@pytest.fixture(scope="module")
def pop():
    pop = Population(_cell_json("configs", CONFIG + ".json")["population"], KEYS, SEED)
    assert not pop.behavior.any() and set(pop.algo.tolist()) == {0, 1}
    return pop


@pytest.fixture(scope="module")
def hot(pop, traffic):
    """hash key -> key index of the hot set."""
    idx = gen_hot.hot_keys(pop, traffic)
    assert len(idx) == HOT == len(set(idx.tolist()))
    return {f"{NAME}_{pop.unique_key(int(i))}": int(i) for i in idx}


@pytest.fixture(scope="module")
def takes(pop, traffic):
    """[(key indices, behaviour a lane, hits, now_ms)]: the load (every key
    once, one hit, behaviour 0, the tail frame filled as the harness fills
    it), the traffic (the generator's own: `frames`' draws, GLOBAL on the hot
    keys' lanes; seconds apart so that leaky buckets leak; the last frames
    take 60,000 hits a lane so that buckets run dry), the read-back (hits=0,
    behaviour 0)."""
    rng = np.random.default_rng([SEED, 0x706F6F6C])
    plain = np.zeros(LANES, np.int32)
    fill = np.flatnonzero(pop.algo[: 4 * LANES] == 0)[:LANES]
    out, now = [], T0
    for lo in range(0, pop.n, LANES):
        hi = min(lo + LANES, pop.n)
        idx = np.concatenate([np.arange(lo, hi), fill[: LANES - (hi - lo)]])
        hits = np.concatenate([np.ones(hi - lo, np.int64), np.zeros(LANES - (hi - lo), np.int64)])
        out.append((idx, plain, hits, now))
        now += 7
    keys = [pop.draw(rng, LANES) for _ in range(TRAFFIC_FRAMES)]
    for t, (idx, behavior) in enumerate(zip(keys, gen_hot.lane_behaviors(pop, traffic, keys))):
        now += 1_500
        hits = 1 if t < TRAFFIC_FRAMES - 2 else 60_000
        out.append((idx, behavior, np.full(LANES, hits, np.int64), now))
    for _ in range(READBACK_FRAMES):
        now += 11
        out.append((pop.draw(rng, LANES), plain, np.zeros(LANES, np.int64), now))
    return out


def _oracle_rows(cache, pop, idx, behavior, hits, now) -> np.ndarray:
    rows = np.empty((len(idx), 4), np.int64)
    for lane, i in enumerate(np.asarray(idx).tolist()):
        r = orc.apply(cache, RateLimitRequest(
            name=NAME, unique_key=pop.unique_key(i), hits=int(hits[lane]), limit=int(pop.limit[i]),
            duration=int(pop.duration[i]), algorithm=Algorithm(int(pop.algo[i])),
            behavior=int(behavior[lane])), now)
        rows[lane] = (int(r.status), r.limit, r.remaining, r.reset_time)
    return rows


@pytest.fixture(scope="module")
def oracle(pop, takes):
    """(the expected rows of every take, the oracle's cache after the last)."""
    cache = orc.OracleCache()
    return [_oracle_rows(cache, pop, *take) for take in takes], cache


def _wrong(answers, expected):
    return [(t, np.flatnonzero((got != want).any(axis=1))[:5].tolist())
            for t, (got, want) in enumerate(zip(answers, expected)) if (got != want).any()]


def _global_lanes(takes) -> int:
    return sum(int(np.count_nonzero(behavior & GLOBAL)) for _, behavior, _, _ in takes)


def _global_keys(takes) -> int:
    """Distinct GLOBAL keys, summed a take."""
    return sum(len(np.unique(idx[(behavior & GLOBAL) != 0])) for idx, behavior, _, _ in takes)


# ---------------------------------------------------------------------
# The frames are the cell's
# ---------------------------------------------------------------------
def _behavior_column_at(payload: bytes, pop) -> "tuple[int, int]":
    """(byte offset, lanes) of the behaviour column of one request frame, from
    the byte layout (`gubc.encode_frame`): magic, header, two fixed-width
    string columns, algorithm i32[n], behaviour i32[n]."""
    body_at = payload.index(b"\r\n\r\n") + 4
    body = payload[body_at:]
    assert body[:4] == gubc.MAGIC
    _, kind, n = struct.unpack_from("<BBI", body, 4)
    assert kind == gubc.KIND_REQUEST
    at = 10
    for width in (len(pop.name), pop.key_width):
        (blob,) = struct.unpack_from("<I", body, at)
        assert blob == n * width
        at += 4 + 4 * (n + 1) + blob
    return body_at + at + 4 * n, n


def test_a_seeds_frames_hold_frames_1ks_keys_and_differ_in_the_behaviour_column_alone(pop):
    """The cell's own traffic file but for the pool's size: the key draws are
    `frames-1k`'s, every lane of a hot key carries GLOBAL and no other lane
    does, and no other byte of a payload differs."""
    params = dict(_cell_json("traffic", TRAFFIC + ".json"), pool_requests=6)
    plain_params = dict(_cell_json("traffic", "frames-1k.json"), pool_requests=6)
    host = "127.0.0.1:1"
    pool = gen_hot.build_pool(pop, params, np.random.default_rng([SEED, 0x706F6F6C]), host)
    plain = gen_frames.build_pool(pop, plain_params, np.random.default_rng([SEED, 0x706F6F6C]), host)
    is_hot = np.zeros(pop.n, bool)
    is_hot[pop.key_of_rank[:64]] = True
    assert gen_hot.decode is gen_frames.decode and len(pool) == len(plain) == 6
    for a, b in zip(pool, plain):
        assert (a.keys == b.keys).all() and a.hits == b.hits == 1 and len(a.keys) == 1028
        assert len(a.payload) == len(b.payload)
        at, n = _behavior_column_at(a.payload, pop)
        assert n == 1028
        differ = np.flatnonzero(np.frombuffer(a.payload, np.uint8) != np.frombuffer(b.payload, np.uint8))
        assert differ.size and at <= differ.min() and differ.max() < at + 4 * n
        sent = np.frombuffer(a.payload, np.int32, n, at)
        assert (np.frombuffer(b.payload, np.int32, n, at) == 0).all()
        assert (sent == np.where(is_hot[a.keys], GLOBAL, 0)).all()
        # The bit is a key's: a key's lanes all carry it or none does.
        for key in np.unique(a.keys[sent != 0]).tolist():
            assert (sent[a.keys == key] == GLOBAL).all()


def test_31_per_cent_of_the_lanes_carry_the_bit_at_the_cells_own_size():
    """1,000,000 keys, Zipfian 0.99, the 64 hottest ranks: 31.4% of the checks
    by the population's own weights, and of the lanes of 64 of its frames."""
    config = _cell_json("configs", CONFIG + ".json")
    params = _cell_json("traffic", TRAFFIC + ".json")
    pop = Population(config["population"], config["population"]["resident_keys"], SEED)
    weights = np.arange(1, pop.n + 1, dtype=np.float64) ** -config["population"]["zipf_theta"]
    assert weights[:64].sum() / weights.sum() == pytest.approx(0.314, abs=0.001)
    rng = np.random.default_rng([SEED, 0x706F6F6C])
    keys = [pop.draw(rng, params["lanes_per_request"]) for _ in range(64)]
    columns = np.stack(gen_hot.lane_behaviors(pop, params, keys))
    assert set(np.unique(columns).tolist()) == {0, GLOBAL}
    assert 0.29 < (columns != 0).mean() < 0.33
    per_frame = (columns != 0).sum(axis=1)
    assert 280 < per_frame.mean() < 360 and per_frame.min() > 200
    assert max(len(np.unique(idx[col != 0])) for idx, col in zip(keys, columns)) <= 64


# ---------------------------------------------------------------------
# The store: one dispatch, one round, the gslot table, convergence
# ---------------------------------------------------------------------
def _apply_all(store, pop, takes) -> list:
    """Every take through `apply_columns`, one dispatch each: [lanes, 4] a take."""
    keys = [f"{NAME}_{pop.unique_key(i)}" for i in range(pop.n)]
    answers = []
    for idx, behavior, hits, now in takes:
        r = store.apply_columns(
            [keys[i] for i in idx.tolist()], pop.algo[idx], behavior, hits, pop.limit[idx],
            pop.duration[idx], now)
        answers.append(np.stack([r["status"], r["limit"], r["remaining"], r["reset_time"]], axis=1))
    return answers


def _serve(pop, takes, shards: int) -> dict:
    saturation.reset()
    store = MeshBucketStore(
        capacity_per_shard=SLOTS // min(shards, 2), devices=jax.devices()[:shards])
    before = saturation.mesh_tally.snapshot()
    answers = _apply_all(store, pop, takes)
    return {"store": store, "answers": answers, "before": before,
            "after": saturation.mesh_tally.snapshot()}


@pytest.fixture(scope="module")
def served(pop, takes):
    runs = {}

    def run(shards: int):
        if shards not in runs:
            runs[shards] = _serve(pop, takes, shards)
        return runs[shards]

    yield run
    saturation.reset()


@pytest.mark.parametrize("shards", SHARDS)
def test_every_lane_of_the_cells_frames_equals_the_sequential_oracle(served, oracle, shards):
    run = served(shards)
    assert _wrong(run["answers"], oracle[0]) == []
    run["store"].check_consistency()


@pytest.mark.parametrize("shards", SHARDS)
def test_a_take_is_one_dispatch_of_one_round_and_the_note_counts_its_lanes_and_keys(served, takes, shards):
    """A hot key's lanes all carry the bit, so its group is uniform with or
    without `split_routing_bits`: round 0, closed form.  The two counters:
    every GLOBAL lane, and the distinct keys among a take's."""
    run = served(shards)
    grown = {k: run["after"][k] - run["before"][k] for k in run["after"] if k != "shards"}
    assert grown["dispatches"] == grown["rounds"] == len(takes)
    assert grown["lanes"] == len(takes) * LANES
    assert grown["flaggedLanes"] == grown["globalLanes"] == _global_lanes(takes) > TRAFFIC_FRAMES * 100
    assert grown["globalKeys"] == _global_keys(takes) <= TRAFFIC_FRAMES * HOT
    assert grown["globalKeys"] > TRAFFIC_FRAMES * HOT // 2
    assert grown["laneWireDispatches"] == 0


def _replica_rows(store, g: int) -> np.ndarray:
    """[S, 4]: every shard's replica row of gslot `g` (status, limit,
    remaining, reset_time)."""
    gcols = store.gcols
    return np.stack([np.asarray(col)[:, g] for col in (
        gcols.rep_status, gcols.rep_limit, gcols.rep_remaining, gcols.rep_reset)], axis=1)


def _not_converged(store, pop, hot: dict, cache, now: int) -> dict:
    """hash key -> the shards whose replica row of it is not the owner's
    status, which is the oracle's bucket read with zero hits at `now`."""
    out = {}
    for key, i in hot.items():
        (want,) = _oracle_rows(cache, pop, [i], [0], [0], now)
        rows = _replica_rows(store, store.gtable.get(key))
        off = np.flatnonzero((rows != want).any(axis=1)).tolist()
        if off:
            out[key] = off
    return out


@pytest.mark.parametrize("shards", SHARDS)
def test_the_gslot_table_holds_exactly_the_hot_keys_and_a_pass_converges_every_shards_replica(
        served, pop, takes, oracle, hot, shards):
    run = served(shards)
    store, table = run["store"], run["store"].gtable
    assert len(table) == HOT and sorted(table.key_of(g) for g in table.active_gslots()) == sorted(hot)
    for key, i in hot.items():
        g = table.get(key)
        owner = shard_of_key(key, shards)
        assert table.owner_shard[g] == owner and store.dirty[owner, g], key
        assert (table.algorithm[g], table.limit[g], table.duration[g], table.behavior[g]) == (
            pop.algo[i], pop.limit[i], pop.duration[i], 0)
    assert int(store.dirty.sum()) == HOT and store._global_pending
    if shards > 1:  # the hot set lies on more than one shard: the broadcast crosses shards
        assert len({shard_of_key(key, shards) for key in hot}) > 1
    # Before the pass no replica row holds anything: every shard is behind.
    now = takes[-1][3] + 5
    assert len(_not_converged(store, pop, hot, oracle[1], now)) == HOT
    tally = saturation.mesh_tally.snapshot()
    res = store.sync_globals(now)
    assert _not_converged(store, pop, hot, oracle[1], now) == {}
    # What the pass says it would broadcast is what the replicas now hold.
    cols = res.broadcast_cols
    assert sorted(cols.keys) == sorted(hot)
    for lane, key in enumerate(cols.keys):
        want = (cols.status[lane], cols.limit[lane], cols.remaining[lane], cols.reset_time[lane])
        assert (_replica_rows(store, table.get(key)) == want).all(), key
    assert not store.dirty.any() and not store._global_pending
    grown = {k: v - tally[k] for k, v in saturation.mesh_tally.snapshot().items()}
    assert (grown["syncPasses"], grown["syncRows"], grown["syncTouched"]) == (
        1, min(store.g_capacity, SYNC_WIDTH), HOT)
    assert store.sync_globals(now + 1).broadcast_cols is None  # idle: nothing pending
    store.check_consistency()


def test_a_program_whose_broadcast_psum_drops_three_shards_rows_does_not_converge(
        monkeypatch, pop, takes, oracle, hot):
    """The control of the convergence test: the sync program built with a
    broadcast (the psum of the stacked status rows) that carries shard 0's
    rows alone.  The hits are still aggregated and every owner still applies,
    so every ANSWER stays right; the replica rows of the keys shards 1-3 own
    stay behind on every shard, and the test above would fail on them."""
    real = jax.lax.psum

    def psum(x, axis):
        if getattr(x, "ndim", 0) != 2:  # the hit aggregation: as it is
            return real(x, axis)
        return real(jax.numpy.where(jax.lax.axis_index(axis) == 0, x, 0), axis)

    monkeypatch.setattr(mesh_mod, "_SYNC_FN_CACHE", {})
    monkeypatch.setattr(global_ops.jax.lax, "psum", psum)
    store = MeshBucketStore(capacity_per_shard=SLOTS // 2, devices=jax.devices()[:4])
    assert _wrong(_apply_all(store, pop, takes), oracle[0]) == []
    now = takes[-1][3] + 5
    store.sync_globals(now)
    behind = _not_converged(store, pop, hot, oracle[1], now)
    elsewhere = sorted(key for key in hot if shard_of_key(key, 4) != 0)
    assert sorted(behind) == elsewhere and len(elsewhere) > HOT // 2
    assert all(shards == [0, 1, 2, 3] for shards in behind.values())


# ---------------------------------------------------------------------
# Through a served daemon's native lane, on four devices
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def daemon_at():
    from gubernator_tpu.cluster import fast_test_behaviors
    from gubernator_tpu.config import DaemonConfig
    from gubernator_tpu.daemon import Daemon
    from gubernator_tpu.utils.clock import Clock

    telemetry.set_enabled(True)
    telemetry.reset()
    saturation.reset()
    behaviors = fast_test_behaviors()
    behaviors.global_sync_wait_s = 3600.0  # the test runs the pass itself
    clock = Clock()
    clock.freeze(T0 - 60_000)
    daemon = Daemon(DaemonConfig(
        listen_address="127.0.0.1:0", grpc_listen_address="127.0.0.1:0", cache_size=SLOTS,
        global_cache_size=4096, behaviors=behaviors, peer_discovery_type="static",
        native_http=True, devices=jax.devices()[:4], warmup_shapes=[]), clock=clock).start()
    daemon.set_peers([daemon.peer_info])
    address = f"127.0.0.1:{daemon.gateway._edge.port}"
    http = Http(address, timeout_s=60.0)
    try:
        yield daemon, clock, http, address
    finally:
        http.close()
        daemon.close()
        telemetry.reset()
        saturation.reset()


def test_a_daemon_on_four_devices_keeps_every_frame_native_and_converges_on_its_own_tick(
        daemon_at, pop, takes, oracle, hot):
    daemon, clock, http, address = daemon_at
    store = daemon.service.store
    assert store.n_shards == 4
    before = http.get_json("/debug/device")["mesh"]
    stats = daemon.gateway.pump.stats()
    answers = []
    for idx, behavior, hits, now in takes:
        clock.freeze(now)
        n = len(idx)
        frame = gubc.encode_frame(
            gubc.fixed_width_column(NAME.encode() * n, n, len(NAME)),
            gubc.fixed_width_column(pop.keys_blob(idx), n, pop.key_width),
            pop.algo[idx], behavior, hits, pop.limit[idx], pop.duration[idx])
        body = http.roundtrip(gubc.http_request(address, gubc.COLUMNS_CONTENT_TYPE, frame))
        answers.append(np.stack(gen_frames.decode(body, n), axis=1))
    assert _wrong(answers, oracle[0]) == []
    after = daemon.gateway.pump.stats()
    assert after["frames"] - stats["frames"] == len(takes)
    assert after["fallbacks"] == stats["fallbacks"] == 0
    mesh = http.get_json("/debug/device")["mesh"]
    grown = {k: mesh[k] - before[k] for k in mesh}
    assert grown["dispatches"] == grown["rounds"] == len(takes) and mesh["shards"] == 4
    assert grown["globalLanes"] == _global_lanes(takes) and grown["globalKeys"] == _global_keys(takes)
    # The manager's own tick runs the pass; the replicas of all four shards then
    # hold the owner's status.
    now = takes[-1][3] + 5
    clock.freeze(now)
    assert len(store.gtable) == HOT + 1  # and warm-up's own key
    assert daemon.service.global_mgr.run_once()
    assert _not_converged(store, pop, hot, oracle[1], now) == {}
    mesh = http.get_json("/debug/device")["mesh"]
    assert mesh["syncPasses"] - before["syncPasses"] == 1
    assert mesh["syncTouched"] - before["syncTouched"] == HOT
    assert http.get_json("/debug/audit")["violationTotal"] == 0
    assert metric_sum(http.scrape(), NATIVE_INGRESS, '"fallbacks"') == 0


# ---------------------------------------------------------------------
# The cell's files
# ---------------------------------------------------------------------
def test_the_cells_files_say_what_the_issue_says():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell, entry = bench["workloads"][-1], bench["configs"][-1]
    assert cell == {
        "name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 4, "why": cell["why"]}
    assert "64 hottest keys GLOBAL" in cell["why"] and BYPASS in cell["why"] and len(cell["why"]) <= 200
    config = _cell_json("configs", CONFIG + ".json")
    twin = _cell_json("configs", "v5e4-mesh-1m.json")
    assert (entry["name"], entry["file"]) == (CONFIG, f"chipbench/configs/{CONFIG}.json")
    assert entry["source"] == config["source"] and len(config["source"]) <= 200
    for word in ("BASELINE.json configuration 4", "8-shard mesh", "hot-key skew",
                 "docs/architecture.md 'Global Behavior'", "Behavior.GLOBAL", "zipf 0.99"):
        assert word in config["source"], word
    assert entry["reduced"] == config["reduced"] == ["shards"] and config["shards"] == 4
    assert "8 -> 4" in config["reduced_why"]["shards"]
    assert config["architecture"] is None and config["name"] == CONFIG
    for same in ("env", "population", "control", "chips"):
        assert config[same] == twin[same], same
    assert config["assumed"][: len(twin["assumed"])] == twin["assumed"]
    assert len(config["assumed"]) == len(twin["assumed"]) + 2 and "64 hottest" in config["assumed"][-2]
    assert config["guarantees"] == dict(twin["guarantees"], **{"global": config["guarantees"]["global"]})
    assert "exact" in config["guarantees"]["global"] and "replica row" in config["guarantees"]["global"]
    assert "home_shard" in config["deployment"] and "second daemon" in config["deployment"]
    traffic, frames_1k = _cell_json("traffic", TRAFFIC + ".json"), _cell_json("traffic", "frames-1k.json")
    for same in ("loop", "connections", "lanes_per_request", "hits", "pool_requests",
                 "lanes_in_flight", "warm_buckets", "warm_buckets_why", "load_lanes", "readback_lanes"):
        assert traffic[same] == frames_1k[same], same
    assert (traffic["name"], traffic["kind"], traffic["global_hot_keys"], traffic["ramp_s"]) == (
        TRAFFIC, "frames_global_hot", 64, 10.0)
    assert (traffic["connections"], traffic["lanes_per_request"], traffic["warm_buckets"]) == (2, 1028, [1024])
    assert set(traffic) - set(frames_1k) == {"global_hot_keys", "global_hot_why", "ramp_why"}
    # Appended, each after PR 45's; a second four-chip cell of nine.
    assert [w["name"] for w in bench["workloads"]].index(CELL) == 8 and len(bench["configs"]) == 8
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    # Every per-layer metric that lists the bypass lists the cell, and the five
    # of the GLOBAL lanes and their pass that `v5e1-1m-mixed.frames` brought.
    for name, metric in by_name.items():
        if metric in bench["per_layer"] and (BYPASS in metric.get("workloads", ()) or name in ALSO_LISTED):
            assert metric["workloads"][-1] == CELL, name
    assert "kernel.apply_roofline" in by_name and CELL not in by_name["kernel.apply_roofline"]["workloads"]
    # Six untraced runs on the chip spread 1.5% in `req_p99_ms` (PERF.md section
    # 2), under half its bound (9%): the cell reports it, appended last.
    assert by_name["req_p99_ms"]["workloads"][-1] == CELL
    # The six new ones, last and in this order, each read in the cell and in
    # the one other cell whose window holds GLOBAL lanes and sync passes.
    assert [m["name"] for m in bench["per_layer"][-len(NEW_METRICS):]] == list(NEW_METRICS)
    readers = {"global.lanes_per_take": "mesh_counted", "global.keys_per_take": "mesh_counted",
               "global.note_ms_per_take": "phase_ms_per", "global.keys_per_pass": "mesh_tally",
               "global.sync_device_us_per_pass": "program_us_per_launch",
               "global.sync_collective_us_per_pass": "program_us_per_launch"}
    for name in NEW_METRICS:
        metric, spec = by_name[name], _cell_json("layer_metrics", name + ".json")
        assert metric["workloads"] == [CELL, MIXED] and spec["reader"] == readers[name]
        assert (spec["layer"], spec["unit"], spec["source"], spec["moves"], spec["better"]) == (
            metric["layer"], metric["unit"], metric["source"], metric["moves"], metric["better"])
        assert metric["moves"] in ("req_p50_ms", "checks_per_s")  # both reported by both cells
    # The kernel readers leave the sync program out; the new reader reads it.
    assert _cell_json("layer_metrics", "kernel.us_per_dispatch.json")["params"]["exclude"] == \
        _cell_json("layer_metrics", "global.sync_device_us_per_pass.json")["params"]["programs"]


# ---------------------------------------------------------------------
# The readers of the six new metrics, on snapshots written out here
# ---------------------------------------------------------------------
def _snap(mesh=None, phases=None):
    device = {} if mesh is None else {"mesh": mesh}
    rows = {name: {"count": c, "sum_ms": ms} for name, (c, ms) in (phases or {}).items()}
    return {"device": device, "latency": {"phases": rows}, "metrics": []}


def _read(name, ctx):
    spec = _cell_json("layer_metrics", name + ".json")
    reader = {"mesh_tally": mesh_tally, "mesh_counted": mesh_counted, "phase_ms_per": phase_ms_per,
              "program_us_per_launch": program_us_per_launch}
    return reader[spec["reader"]].read(ctx, spec["params"])


# The load: 973 one-frame dispatches, no GLOBAL lane, warm-up's one pass.
LOADED = {"shards": 4, "dispatches": 973, "lanes": 973 * 1028, "globalLanes": 0, "globalKeys": 0,
          "syncPasses": 1, "syncRows": 1024, "syncTouched": 1}
# Ramp and window: 4,000 dispatches more, 2,500 of one frame (323 GLOBAL lanes
# on 58 keys) and 1,500 of two (646 on 63), and 810 passes of 64 gslots but the
# first, which took 40.
WINDOW = {"shards": 4, "dispatches": 4_973, "lanes": 973 * 1028 + 5_500 * 1028,
          "globalLanes": 2_500 * 323 + 1_500 * 646, "globalKeys": 2_500 * 58 + 1_500 * 63,
          "syncPasses": 811, "syncRows": 811 * 1024, "syncTouched": 1 + 40 + 809 * 64}
PHASES_LOADED = {"dispatch.prepare": (973, 900.0)}
PHASES_WINDOW = {"dispatch.prepare": (4_973, 900.0 + 7_000.0), "dispatch.global_note": (4_000, 1_300.0)}


@pytest.mark.parametrize("name,want", [
    ("global.lanes_per_take", (2_500 * 323 + 1_500 * 646) / 4_000),
    ("global.keys_per_take", (2_500 * 58 + 1_500 * 63) / 4_000),
    ("global.note_ms_per_take", 1_300.0 / 4_000),
    ("global.keys_per_pass", (40 + 809 * 64) / 810),
])
def test_the_counter_and_phase_readers_give_the_values_reckoned_by_hand(name, want):
    ctx = {"before": _snap(LOADED, PHASES_LOADED), "after": _snap(WINDOW, PHASES_WINDOW),
           "requests": 5_500}
    assert _read(name, ctx) == pytest.approx(want)


def test_a_program_from_before_the_counters_reads_nothing_and_the_bypass_reads_a_true_0():
    """The parent's `mesh` block has no `globalLanes` and no `globalKeys`: the
    two read nothing, not 0, and nothing raises; so with no snapshot at all.
    The bypass (`v5e4-mesh-1m.frames` on this program) has the counters and
    they stand still: a true 0 a take, no pass to divide by."""
    old = {k: v for k, v in LOADED.items() if not k.startswith("global")}
    parent = {"before": _snap(old, PHASES_LOADED),
              "after": _snap(dict(old, dispatches=4_973), PHASES_WINDOW), "requests": 5_500}
    empty = {"before": _snap(), "after": _snap(), "requests": 0}
    for name in ("global.lanes_per_take", "global.keys_per_take"):
        assert _read(name, parent) is None and _read(name, empty) is None
    assert _read("global.keys_per_pass", empty) is None and _read("global.note_ms_per_take", empty) is None
    bypass = {"before": _snap(LOADED, PHASES_LOADED),
              "after": _snap(dict(LOADED, dispatches=4_973), {"dispatch.prepare": (4_973, 7_900.0)}),
              "requests": 5_500}
    assert _read("global.lanes_per_take", bypass) == 0.0 == _read("global.keys_per_take", bypass)
    assert _read("global.note_ms_per_take", bypass) == 0.0
    assert _read("global.keys_per_pass", bypass) is None


def _trace_rows() -> list:
    """Event rows as `trace_reduce.load_xplane` gives them, two chips, times in
    nanoseconds.  Chip 0: two launches of the sync program of 100 and 140 us;
    chip 1: the same two of 130 and 150 us (it waited longer in the collective)
    and is the busiest.  Inside chip 1's first launch: an all-reduce of 40 us,
    and an all-reduce-start / all-reduce-done pair of 10 us each with a fusion
    between them; inside its second: one all-reduce of 70 us.  A dispatch
    program runs between them, with an all-reduce of its own that no metric of
    the sync program may count."""
    us = 1_000.0
    ops, modules = trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE
    return [
        ["/device:TPU:0", modules, "jit__sync_body(77)", 0.0, 100 * us],
        ["/device:TPU:0", ops, "all-reduce.3", 10 * us, 20 * us],
        ["/device:TPU:0", modules, "jit__sync_body(77)", 1_000 * us, 140 * us],
        ["/device:TPU:0", ops, "all-reduce.3", 1_010 * us, 60 * us],
        ["/device:TPU:1", modules, "jit__sync_body(77)", 0.0, 130 * us],
        ["/device:TPU:1", ops, "all-reduce.3", 5 * us, 40 * us],
        ["/device:TPU:1", ops, "all-reduce-start.1", 50 * us, 10 * us],
        ["/device:TPU:1", ops, "fusion.9", 60 * us, 30 * us],
        ["/device:TPU:1", ops, "all-reduce-done.1", 90 * us, 10 * us],
        ["/device:TPU:1", modules, "jit_run(5)", 400 * us, 300 * us],
        ["/device:TPU:1", ops, "all-reduce.8", 410 * us, 200 * us],
        ["/device:TPU:1", modules, "jit__sync_body(77)", 1_000 * us, 150 * us],
        ["/device:TPU:1", ops, "all-reduce.3", 1_020 * us, 70 * us],
        ["/host:CPU", "python", "global.sync", 0.0, 2_000 * us],
    ]


def test_the_device_readers_read_the_sync_program_on_the_busiest_chip(monkeypatch):
    rows = _trace_rows()
    monkeypatch.setattr(trace_reduce, "load_xplane", lambda path, cpu_stand_in=False: rows)
    ctx = {"trace": {"xplane": "unread"}, "device": {"platform": "tpu"}}
    assert _read("global.sync_device_us_per_pass", ctx) == pytest.approx((130 + 150) / 2)
    assert _read("global.sync_collective_us_per_pass", ctx) == pytest.approx((40 + 10 + 10 + 70) / 2)
    # One chip, a program with no collective in it: a true 0 beside its time.
    rows = [r for r in _trace_rows() if r[0] != "/device:TPU:1" and not r[2].startswith("all-reduce")]
    ctx = {"trace": {"xplane": "unread"}, "device": {"platform": "tpu"}}
    assert _read("global.sync_device_us_per_pass", ctx) == pytest.approx(120.0)
    assert _read("global.sync_collective_us_per_pass", ctx) == 0.0
    # A trace that holds no launch of the sync program (the bypass; a program
    # from before the sync program had the name): nothing, and no raise.  So
    # with no device plane at all.
    for kept in (lambda r: "sync_body" not in r[2], lambda r: r[0].startswith("/host")):
        rows = [r for r in _trace_rows() if kept(r)]
        ctx = {"trace": {"xplane": "unread"}, "device": {"platform": "tpu"}}
        assert _read("global.sync_device_us_per_pass", ctx) is None
        assert _read("global.sync_collective_us_per_pass", ctx) is None
