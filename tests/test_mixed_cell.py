"""The cell `v5e1-1m-mixed.frames` at test size, on the CPU: 8,000 keys of
`chipbench/population.py` with the configuration's own `population` block in
32,768 slots, loaded, asked for and read back in 512-lane frames of the
cell's own generator (`chipbench/generators/frames_mixed.py`): one lane in a
hundred carries NO_BATCHING, GLOBAL or MULTI_REGION, the bit drawn a lane.

Held here: the oracle reads none of the three bits (an owner's answer is the
same with any of them); a frame that carries them stays on a served daemon's
native ingress lane in an all-self ring (`frames` +1, `fallbacks` +0) and every
lane of load, traffic and read-back equals the sequential oracle, a key that
one frame holds flagged and plain and duplicate GLOBAL lanes of one key
included; a take is ONE dispatch of the warm bucket, one kernel round, and
compiles nothing; in a two-node ring such a frame is still handed to the
Python router whole, and answered right there; the owner's duties are kept
(GLOBAL keys in the gslot table with their configuration and owner shard, the
owner rows dirty until a sync pass takes them, the pass broadcasting the
oracle's state; MULTI_REGION hits in the region queue; the audit silent); the
same on a mesh of S = 1, 2 and 4; the counters, the phases, the cell's files
and the readers of its four metrics.  Everything is made from SEED."""

from __future__ import annotations

import json
import os
import sys

import jax
import numpy as np
import pytest

from gubernator_tpu import native, saturation, telemetry
from gubernator_tpu.gateway import NativeIngressPump
from gubernator_tpu.models.shard import (
    ROUTING_BEHAVIOR,
    make_columns,
    pad_size,
    split_routing_bits,
)
from gubernator_tpu.parallel.mesh import SYNC_WIDTH, MeshBucketStore, shard_of_key
from gubernator_tpu.types import Algorithm, Behavior, RateLimitRequest

from . import oracle as orc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench.daemon import Http, metric_sum  # noqa: E402
from chipbench.generators import frames as gen_frames  # noqa: E402
from chipbench.generators import frames_mixed as gen_mixed  # noqa: E402
from chipbench import gubc  # noqa: E402
from chipbench.population import Population  # noqa: E402
from chipbench.readers import counter_share, mesh_tally, phase_ms_per  # noqa: E402

pytestmark = pytest.mark.skipif(
    not native.available(), reason="the columnar path needs the native host runtime")

SEED = 41
KEYS = 8_000
SLOTS = 32_768
LANES = 512
NAME = "bench"
T0 = 1_790_000_000_000
NO_BATCHING, GLOBAL, MULTI_REGION = (
    int(Behavior.NO_BATCHING), int(Behavior.GLOBAL), int(Behavior.MULTI_REGION))
BITS = (NO_BATCHING, GLOBAL, MULTI_REGION)
TRAFFIC_FRAMES = 24
READBACK_FRAMES = 3
CELL = "v5e1-1m-mixed.frames"
BYPASS = "v5e1-1m.frames"
SHARDS = [1, 2, 4]
NEW_METRICS = ("behavior.flagged_lane_share", "behavior.handle_ms_per_dispatch",
               "global.sync_hold_ms_per_pass", "global.sync_ms_per_req")
SYNC_ROWS = "global.sync_rows_per_pass"  # PR 42
NATIVE_INGRESS = "gubernator_native_ingress_batches_total"


def _cell_json(*parts):
    with open(os.path.join(REPO, "chipbench", *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    return _cell_json("traffic", "frames-mixed.json")


@pytest.fixture(scope="module")
def pop():
    pop = Population(_cell_json("configs", "v5e1-1m-mixed.json")["population"], KEYS, SEED)
    assert not pop.behavior.any() and set(pop.algo.tolist()) == {0, 1}
    return pop


def _takes(pop, traffic):
    """[(key indices, behaviour a lane, hits, now_ms)]: the load (every key
    once, one hit, behaviour 0, the tail frame filled with hits=0 re-reads, as
    the harness fills it), the traffic (the generator's own draws: Zipfian
    keys, then a behaviour column a frame at the cell's share; seconds apart
    so that leaky buckets leak; the last frames take 60,000 hits a lane so
    that buckets run dry), the read-back (hits=0, behaviour 0)."""
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
    for t, (idx, behavior) in enumerate(zip(keys, gen_mixed.lane_behaviors(pop, traffic, rng, keys))):
        now += 1_500
        hits = 1 if t < TRAFFIC_FRAMES - 2 else 60_000
        out.append((idx, behavior, np.full(LANES, hits, np.int64), now))
    for _ in range(READBACK_FRAMES):
        now += 11
        out.append((pop.draw(rng, LANES), plain, np.zeros(LANES, np.int64), now))
    return out


@pytest.fixture(scope="module")
def takes(pop, traffic):
    return _takes(pop, traffic)


def _oracle_rows(cache, keys, algo, behavior, hits, limit, duration, now) -> np.ndarray:
    rows = np.empty((len(keys), 4), np.int64)
    for lane, key in enumerate(keys):
        r = orc.apply(cache, RateLimitRequest(
            name=NAME, unique_key=key, hits=int(hits[lane]), limit=int(limit[lane]),
            duration=int(duration[lane]), algorithm=Algorithm(int(algo[lane])),
            behavior=int(behavior[lane])), now)
        rows[lane] = (int(r.status), r.limit, r.remaining, r.reset_time)
    return rows


def _expected(pop, takes, cache=None):
    cache = cache or orc.OracleCache()
    return [
        _oracle_rows(cache, [pop.unique_key(i) for i in idx.tolist()], pop.algo[idx], behavior,
                     hits, pop.limit[idx], pop.duration[idx], now)
        for idx, behavior, hits, now in takes
    ]


@pytest.fixture(scope="module")
def expected(pop, takes):
    return _expected(pop, takes)


def _wrong(answers, expected):
    return [(t, np.flatnonzero((got != want).any(axis=1))[:5].tolist())
            for t, (got, want) in enumerate(zip(answers, expected)) if (got != want).any()]


def _flagged(takes, bit=ROUTING_BEHAVIOR):
    return sum(int(np.count_nonzero(behavior & bit)) for _, behavior, _, _ in takes)


def _global_keys(pop, takes) -> dict:
    """hash key -> (key index, behaviour word) of its LAST GLOBAL lane."""
    last = {}
    for idx, behavior, _, _ in takes:
        for lane in np.flatnonzero(behavior & GLOBAL).tolist():
            last[f"{NAME}_{pop.unique_key(int(idx[lane]))}"] = (int(idx[lane]), int(behavior[lane]))
    return last


# ---------------------------------------------------------------------
# The reference: the three bits change no owner's answer
# ---------------------------------------------------------------------
@pytest.mark.parametrize("bits", [NO_BATCHING, GLOBAL, MULTI_REGION, GLOBAL | MULTI_REGION,
                                  NO_BATCHING | GLOBAL | MULTI_REGION])
def test_the_oracle_answers_the_same_with_or_without_a_routing_bit(pop, bits):
    """`oracle.py` says it in words; this holds it: a stream answered with the
    bits in a random third of its requests equals the same stream without
    them, request for request (token and leaky, buckets running dry, time
    passing), and the bits are exactly the three."""
    assert int(orc.ROUTING_BEHAVIOR) == ROUTING_BEHAVIOR == NO_BATCHING | GLOBAL | MULTI_REGION
    rng = np.random.default_rng([SEED, bits])
    n = 3_000
    idx = pop.draw(rng, n) % 40
    hits = rng.choice([0, 1, 1, 1, 5, 40_000], size=n)
    flagged = np.where(rng.random(n) < 0.33, bits, 0)
    now = T0 + np.cumsum(rng.integers(0, 900, size=n))
    keys = [pop.unique_key(int(i)) for i in idx]
    plain, with_bits = orc.OracleCache(), orc.OracleCache()
    for lane in range(n):
        sl = slice(lane, lane + 1)
        args = (keys[sl], pop.algo[idx[sl]])
        rest = (hits[sl], pop.limit[idx[sl]], pop.duration[idx[sl]], int(now[lane]))
        a = _oracle_rows(plain, *args, np.zeros(1, np.int32), *rest)
        b = _oracle_rows(with_bits, *args, flagged[sl], *rest)
        assert (a == b).all(), lane


# ---------------------------------------------------------------------
# The frames are the cell's
# ---------------------------------------------------------------------
def test_the_generator_flags_one_lane_in_a_hundred_with_one_bit_each(pop, traffic):
    rng = np.random.default_rng([SEED, 1])
    keys = [pop.draw(rng, 4096) for _ in range(64)]
    columns = np.stack(gen_mixed.lane_behaviors(pop, traffic, rng, keys))
    flagged = columns[columns != 0]
    assert traffic["flagged_lane_share"] == 0.01 and traffic["flagged_bits"] == [1, 2, 16]
    assert 0.009 < flagged.size / columns.size < 0.011
    assert set(flagged.tolist()) == set(BITS)  # exactly one bit a flagged lane
    for bit in BITS:
        assert 0.28 < (flagged == bit).mean() < 0.39
    # 41 flagged lanes a 4096-lane frame on average, and no frame without one.
    per_frame = (columns != 0).sum(axis=1)
    assert 36 < per_frame.mean() < 46 and per_frame.min() > 0


def test_the_frames_hold_a_key_flagged_and_plain_and_a_key_global_twice(pop, takes):
    """What the plan must not trip over is in the seeded traffic itself: one
    frame holds a key in a flagged lane and in plain lanes (a uniform group
    but for the bit), and one holds two GLOBAL lanes of one key."""
    flagged_and_plain = global_twice = 0
    for idx, behavior, _, _ in takes:
        for key in np.unique(idx[behavior != 0]).tolist():
            words = behavior[idx == key]
            flagged_and_plain += bool((words == 0).any() and (words != 0).any())
            global_twice += int(np.count_nonzero(words & GLOBAL)) >= 2
    assert flagged_and_plain >= 5 and global_twice >= 1
    assert _flagged(takes) == sum(_flagged(takes, b) for b in BITS) > 100
    assert all(_flagged(takes, b) > 20 for b in BITS)


# ---------------------------------------------------------------------
# The store: one dispatch, one round, the owner's book-keeping, on a mesh
# ---------------------------------------------------------------------
def _serve(pop, takes, shards: int) -> dict:
    keys = [f"{NAME}_{pop.unique_key(i)}" for i in range(pop.n)]
    saturation.reset()
    store = MeshBucketStore(
        capacity_per_shard=SLOTS // min(shards, 2), devices=jax.devices()[:shards])
    before = saturation.mesh_tally.snapshot()
    answers = []
    for idx, behavior, hits, now in takes:
        sent = behavior.copy()
        r = store.apply_columns(
            [keys[i] for i in idx.tolist()], pop.algo[idx], sent, hits, pop.limit[idx],
            pop.duration[idx], now)
        assert (sent == behavior).all()  # the caller's column is never written
        answers.append(np.stack([r["status"], r["limit"], r["remaining"], r["reset_time"]], axis=1))
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
def test_every_lane_equals_the_sequential_oracle_on_a_mesh(served, expected, shards):
    run = served(shards)
    assert _wrong(run["answers"], expected) == []
    run["store"].check_consistency()


@pytest.mark.parametrize("shards", SHARDS)
def test_a_take_is_one_dispatch_of_one_round_whatever_its_bits(served, takes, shards):
    """A hot key with one flagged lane among plain ones is still one uniform
    group (round 0, closed form): the routing bits are off the column the
    plan compares.  And they cost the dictionary wire no row."""
    run = served(shards)
    grown = {k: run["after"][k] - run["before"][k] for k in run["after"] if k != "shards"}
    assert grown["dispatches"] == grown["rounds"] == len(takes)
    assert grown["lanes"] == len(takes) * LANES
    assert grown["flaggedLanes"] == _flagged(takes)
    assert grown["laneWireDispatches"] == 0
    assert grown["configRows"] <= len(takes) * 16 * 2 * 2  # tiers x algorithms x (hits, or the tail's 0)


@pytest.mark.parametrize("shards", SHARDS)
def test_the_global_keys_are_in_the_gslot_table_with_their_owner_and_a_pass_takes_the_dirt(
        served, pop, takes, expected, shards):
    run = served(shards)
    store = run["store"]
    want = _global_keys(pop, takes)
    table = store.gtable
    assert len(table) == len(want) > 20  # a gslot a distinct key, duplicates and all
    assert store._global_pending
    for key, (i, word) in want.items():
        g = table.get(key)
        owner = shard_of_key(key, shards)
        assert table.owner_shard[g] == owner and store.dirty[owner, g], key
        assert (table.algorithm[g], table.limit[g], table.duration[g]) == (
            pop.algo[i], pop.limit[i], pop.duration[i])
        assert table.behavior[g] == word & ~GLOBAL
    assert int(store.dirty.sum()) == len(want)
    # The pass that is owed: it runs the collective, takes every dirty row,
    # and what it would broadcast is the owner's bucket as the oracle has it
    # (a read of zero hits at the pass's instant).
    now = takes[-1][3] + 5
    cache = orc.OracleCache()
    _expected(pop, takes, cache)
    res = store.sync_globals(now)
    assert not store.dirty.any() and not store._global_pending
    cols = res.broadcast_cols
    assert sorted(cols.keys) == sorted(want)
    for lane, key in enumerate(cols.keys):
        i = want[key][0]
        (row,) = _oracle_rows(cache, [pop.unique_key(i)], pop.algo[[i]], [0], [0],
                              pop.limit[[i]], pop.duration[[i]], now)
        assert (cols.status[lane], cols.limit[lane], cols.remaining[lane]) == tuple(row[:3]), key
    # Nothing pending: the next tick is idle, and the answers were not moved.
    assert store.sync_globals(now + 1).broadcast_cols is None
    store.check_consistency()


def test_split_routing_bits_leaves_a_plain_batch_alone_and_strips_a_flagged_one():
    n = 6
    plain = make_columns(np.zeros(n), np.array([0, 4, 8, 0, 12, 0]), np.ones(n), np.ones(n), np.ones(n), n)
    column = plain.behavior
    split_routing_bits(plain)
    assert plain.behavior is column and plain.flagged_lanes == 0 and plain.global_lanes is None
    sent = np.array([0, 2, 16 | 4, 1, 2 | 8 | 16, 0], np.int32)
    cols = make_columns(np.zeros(n), sent, np.ones(n), np.ones(n), np.ones(n), n)
    split_routing_bits(cols)
    assert cols.behavior.tolist() == [0, 0, 4, 0, 8, 0] and sent.tolist() == [0, 2, 20, 1, 26, 0]
    assert cols.flagged_lanes == 4 and cols.global_lanes.tolist() == [1, 4]
    assert cols.sent_behavior.tolist() == sent.tolist()


def test_a_full_gslot_table_evicts_and_the_answers_stay_the_oracles():
    """More GLOBAL keys than gslots: the oldest gslot is recycled (its device
    rows cleared one row a call, the shape `apply` clears), a take at a time."""
    store = MeshBucketStore(capacity_per_shard=1024, g_capacity=8, devices=jax.devices()[:1])
    cache = orc.OracleCache()
    for step in range(3):
        keys = [f"ev{step}x{i:03d}" for i in range(12)] + [f"ev{step}x000"] * 2
        n = len(keys)
        algo = np.arange(n, dtype=np.int32) % 2
        behavior = np.full(n, GLOBAL, np.int32)
        args = (np.ones(n, np.int64), np.full(n, 3, np.int64), np.full(n, 60_000, np.int64))
        got = store.apply_columns([f"{NAME}_{k}" for k in keys], algo, behavior, *args, T0 + step)
        want = _oracle_rows(cache, keys, algo, behavior, *args, T0 + step)
        assert (np.stack([got[c] for c in ("status", "limit", "remaining", "reset_time")], axis=1) == want).all()
        assert len(store.gtable) == 8
        store.sync_globals(T0 + step)
    store.check_consistency()


# ---------------------------------------------------------------------
# Through a served daemon's native lane
# ---------------------------------------------------------------------
def _daemon(peers_after=None):
    from gubernator_tpu.cluster import fast_test_behaviors
    from gubernator_tpu.config import DaemonConfig
    from gubernator_tpu.daemon import Daemon
    from gubernator_tpu.utils.clock import Clock

    behaviors = fast_test_behaviors()
    behaviors.global_sync_wait_s = 3600.0  # the test runs the passes itself
    behaviors.multi_region_sync_wait_s = 3600.0
    clock = Clock()
    clock.freeze(T0 - 60_000)
    daemon = Daemon(DaemonConfig(
        listen_address="127.0.0.1:0", grpc_listen_address="127.0.0.1:0", cache_size=SLOTS,
        global_cache_size=4096, behaviors=behaviors, peer_discovery_type="static",
        native_http=True, devices=jax.devices()[:1], warmup_shapes=[LANES]), clock=clock).start()
    daemon.set_peers([daemon.peer_info] + list(peers_after or ()))
    address = f"127.0.0.1:{daemon.gateway._edge.port}"
    return daemon, clock, Http(address, timeout_s=60.0), address


@pytest.fixture(scope="module")
def daemon_at():
    telemetry.set_enabled(True)
    telemetry.reset()
    saturation.reset()
    daemon, clock, http, address = _daemon()
    try:
        yield daemon, clock, http, address
    finally:
        http.close()
        daemon.close()
        telemetry.reset()
        saturation.reset()


def _lane_counts(daemon) -> "tuple[int, int]":
    stats = daemon.gateway.pump.stats()
    return stats["frames"], stats["fallbacks"]


def _send(http, address, pop, idx, behavior, hits) -> np.ndarray:
    """One frame over the keys `idx`, a behaviour and a hit count a lane."""
    n = len(idx)
    frame = gubc.encode_frame(
        gubc.fixed_width_column(NAME.encode() * n, n, len(NAME)),
        gubc.fixed_width_column(pop.keys_blob(idx), n, pop.key_width),
        pop.algo[idx], np.asarray(behavior, np.int32), np.asarray(hits, np.int64),
        pop.limit[idx], pop.duration[idx])
    body = http.roundtrip(gubc.http_request(address, gubc.COLUMNS_CONTENT_TYPE, frame))
    return np.stack(gen_frames.decode(body, n), axis=1)


def test_the_native_lane_keeps_every_mixed_frame_in_one_dispatch_and_keeps_the_owners_duties(
        daemon_at, pop, takes, expected):
    """The cell's path at test size: load, window, read-back, every frame kept
    by the C++ lane; then what the owner owes."""
    daemon, clock, http, address = daemon_at
    store = daemon.service.store
    region = daemon.service.multi_region_mgr
    before = http.get_json("/debug/device")
    frames_before, fallbacks_before = _lane_counts(daemon)
    phases_before = http.get_json("/debug/latency")["phases"]
    queued_before = region.queued_hits
    answers = []
    for idx, behavior, hits, now in takes:
        clock.freeze(now)
        answers.append(_send(http, address, pop, idx, behavior, hits))
    assert _wrong(answers, expected) == []
    frames, fallbacks = _lane_counts(daemon)
    assert frames - frames_before == len(takes) and fallbacks == fallbacks_before == 0

    # One dispatch a take, of the one warm bucket, one round; nothing compiled.
    device = http.get_json("/debug/device")
    grown = {k: device["mesh"][k] - before["mesh"][k] for k in device["mesh"]}
    assert grown["dispatches"] == grown["rounds"] == len(takes)
    assert grown["lanes"] == len(takes) * LANES
    assert grown["paddedLanes"] == len(takes) * pad_size(LANES)  # the one warm bucket
    assert grown["flaggedLanes"] == _flagged(takes)
    assert device["steadyRecompiles"] == 0 and device["compileTotal"] == before["compileTotal"]
    latency = http.get_json("/debug/latency")
    assert {"phase": "behavior.handle", "depth": 0} in latency["waterfall"]
    assert {"phase": "dispatch.global_note", "depth": 1} in latency["waterfall"]

    def observed(name):
        return latency["phases"].get(name, {"count": 0})["count"] - phases_before.get(
            name, {"count": 0})["count"]

    assert observed("behavior.handle") == len(takes)  # one a take, the plain ones too
    assert observed("dispatch.global_note") == sum(
        1 for _, behavior, _, _ in takes if (behavior & GLOBAL).any())

    # The owner's duties.  GLOBAL: a gslot a key, owned by shard 0, dirty.
    want = _global_keys(pop, takes)
    for key, (i, word) in want.items():
        g = store.gtable.get(key)
        assert g is not None and store.gtable.owner_shard[g] == 0 and store.dirty[0, g], key
        assert (store.gtable.limit[g], store.gtable.behavior[g]) == (pop.limit[i], word & ~GLOBAL)
    assert store._global_pending
    # MULTI_REGION: the queue holds these lanes' hits, key by key.
    mr_hits = {}
    for idx, behavior, hits, _ in takes:
        for lane in np.flatnonzero(behavior & MULTI_REGION).tolist():
            key = f"{NAME}_{pop.unique_key(int(idx[lane]))}"
            mr_hits[key] = mr_hits.get(key, 0) + int(hits[lane])
    assert region.queued_hits - queued_before == sum(mr_hits.values()) > 0
    assert {k: r.hits for k, r in region._hits.items() if k in mr_hits} == mr_hits
    assert http.get_json("/debug/status")["region"]["queuedHits"] == region.queued_hits
    # The pass that is owed runs on the manager's tick, takes the dirt and,
    # with no peer to tell, sends nothing.
    syncs_before = http.get_json("/debug/latency")["phases"].get("global.sync", {"count": 0})["count"]
    assert int(store._gtouched.sum()) == len(want)
    assert daemon.service.global_mgr.run_once()
    assert not store.dirty.any() and not store._global_pending
    latency = http.get_json("/debug/latency")
    assert latency["phases"]["global.sync"]["count"] == syncs_before + 1
    # What the pass carried: the touched gslots and no other row, in one
    # launch of the program's one width.
    mesh = http.get_json("/debug/device")["mesh"]
    assert store._sync_width == min(store.g_capacity, SYNC_WIDTH) > len(want)
    assert {k: mesh[k] - before["mesh"][k] for k in ("syncPasses", "syncRows", "syncTouched")} == {
        "syncPasses": 1, "syncRows": store._sync_width, "syncTouched": len(want)}
    assert not daemon.service.global_mgr.run_once()  # idle: nothing pending
    assert http.get_json("/debug/device")["mesh"]["syncPasses"] == mesh["syncPasses"]
    # The read-back after the pass: the pass moved no bucket.
    idx, behavior, hits, now = takes[-1]
    assert (_send(http, address, pop, idx, behavior, hits) == expected[-1]).all()
    assert http.get_json("/debug/audit")["violationTotal"] == 0
    assert metric_sum(http.scrape(), NATIVE_INGRESS, '"fallbacks"') == 0


def test_a_no_batching_lane_sends_its_frame_through_the_express_queue_and_changes_nothing(daemon_at, pop):
    daemon, clock, http, address = daemon_at
    clock.freeze(T0 + 7_200_000)
    idx = np.arange(64, 128)
    behavior = np.zeros(64, np.int32)
    behavior[[3, 40]] = NO_BATCHING
    before = daemon.gateway.pump.stats()
    plain = _send(http, address, pop, idx, np.zeros(64, np.int32), np.zeros(64, np.int64))
    flagged = _send(http, address, pop, idx, behavior, np.zeros(64, np.int64))
    stats = daemon.gateway.pump.stats()
    assert stats["frames"] - before["frames"] == 2
    assert stats["expressFrames"] - before["expressFrames"] == 1  # the flagged one, whole
    assert stats["expressLanes"] - before["expressLanes"] == 64
    assert stats["fallbacks"] == before["fallbacks"]
    assert (flagged == plain).all() and (flagged[:, 1] == pop.limit[idx]).all()


@pytest.mark.parametrize("bit", [GLOBAL, MULTI_REGION])
def test_in_a_two_node_ring_a_flagged_frame_is_still_handed_to_the_python_router(pop, bit):
    """`behavior_mask` holds GLOBAL and MULTI_REGION again once the ring has
    another node: a frame of keys this daemon owns falls back for the bit
    (as before this cell), a plain one of the same keys stays, and the Python
    router answers the flagged one right (its local-owned GLOBAL lanes ride
    the columnar dispatch there too)."""
    from gubernator_tpu.types import PeerInfo

    assert NativeIngressPump.fallback_mask(all_self=False, express=True) == GLOBAL | MULTI_REGION
    assert NativeIngressPump.fallback_mask(all_self=True, express=True) == 0
    assert NativeIngressPump.fallback_mask(all_self=True, express=False) == NO_BATCHING
    other = PeerInfo(grpc_address="127.0.0.1:9", http_address="127.0.0.1:9")
    daemon, clock, http, address = _daemon(peers_after=[other])
    try:
        clock.freeze(T0)
        mine = np.array([i for i in range(600) if daemon.service.get_peer(
            f"{NAME}_{pop.unique_key(i)}").info.is_owner][:64])
        assert len(mine) == 64
        idx = np.concatenate([mine, mine[:8]])  # eight keys twice
        behavior = np.zeros(len(idx), np.int32)
        hits = np.ones(len(idx), np.int64)
        cache = orc.OracleCache()
        keys = [pop.unique_key(int(i)) for i in idx]
        rest = (pop.limit[idx], pop.duration[idx], T0)
        assert (_send(http, address, pop, idx, behavior, hits)
                == _oracle_rows(cache, keys, pop.algo[idx], behavior, hits, *rest)).all()
        assert _lane_counts(daemon) == (1, 0)
        behavior[[2, 66, 30]] = bit  # a key flagged in one lane and plain in another
        assert (_send(http, address, pop, idx, behavior, hits)
                == _oracle_rows(cache, keys, pop.algo[idx], behavior, hits, *rest)).all()
        assert _lane_counts(daemon) == (1, 1)
        if bit == GLOBAL:
            store = daemon.service.store
            flagged = {f"{NAME}_{keys[lane]}" for lane in (2, 66, 30)}
            assert {k for k in flagged if store.gtable.get(k) is not None} == flagged
            assert int(store.dirty.sum()) == len(flagged)
        else:
            assert daemon.service.multi_region_mgr.queued_hits == 3
        assert http.get_json("/debug/audit")["violationTotal"] == 0
    finally:
        http.close()
        daemon.close()


def test_the_python_path_keeps_a_local_global_lane_columnar(daemon_at, pop):
    """`get_rate_limits_columns` (the router a fallen-back frame takes): no
    lane of a one-node ring goes lane by lane through the dataclass router."""
    from gubernator_tpu.service import IngressColumns

    daemon, clock, _, _ = daemon_at
    clock.freeze(T0 + 9_000_000)
    idx = np.arange(200, 232)
    behavior = np.zeros(32, np.int32)
    behavior[[1, 9, 17]] = (GLOBAL, MULTI_REGION, GLOBAL | MULTI_REGION)
    cols = IngressColumns(
        names=[NAME] * 32, unique_keys=[pop.unique_key(int(i)) for i in idx],
        algorithm=pop.algo[idx], behavior=behavior, hits=np.zeros(32, np.int64),
        limit=pop.limit[idx], duration=pop.duration[idx])
    queued = daemon.service.multi_region_mgr.queued_hits
    result = daemon.service.get_rate_limits_columns(cols)
    assert result.overrides == {}  # no lane left the columns
    assert (result.limit == pop.limit[idx]).all()
    store = daemon.service.store
    for lane in (1, 17):
        g = store.gtable.get(f"{NAME}_{pop.unique_key(int(idx[lane]))}")
        assert g is not None and store.dirty[0, g]
    assert daemon.service.multi_region_mgr.queued_hits == queued  # zero hits queued twice
    daemon.service.global_mgr.run_once()


# ---------------------------------------------------------------------
# The names, and the cell's files
# ---------------------------------------------------------------------
def test_the_phases_lie_where_the_waterfall_says():
    names = [p for p, _ in saturation.WATERFALL]
    at = names.index("behavior.handle")
    assert saturation.WATERFALL[at] == ("behavior.handle", 0)
    assert names.index("calendar.resolve") < at < names.index("dispatch.prepare")
    note = names.index("dispatch.global_note")
    assert saturation.WATERFALL[note] == ("dispatch.global_note", 1)
    assert names.index("dispatch.prepare") < note < names.index("dispatch.stage")
    for kept in ("global.sync_drain", "global.sync", "global.tick_idle"):
        assert (kept, 0) in saturation.WATERFALL


def test_the_cells_files_say_what_the_issue_says(traffic):
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    entry = next(c for c in bench["configs"] if c["name"] == "v5e1-1m-mixed")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("v5e1-1m-mixed", "frames-mixed", 1)
    config = _cell_json("configs", "v5e1-1m-mixed.json")
    twin = _cell_json("configs", "v5e1-1m.json")
    assert config["source"] == entry["source"] and entry["reduced"] == config["reduced"] == []
    assert "enum Behavior" in config["source"] and "README" in config["source"]
    for same in ("env", "population", "control", "chips"):
        assert config[same] == twin[same], same
    assert config["assumed"][: len(twin["assumed"])] == twin["assumed"]
    assert config["guarantees"] == dict(twin["guarantees"], behaviours=config["guarantees"]["behaviours"])
    assert "exactly" in config["guarantees"]["behaviours"]
    frames = _cell_json("traffic", "frames.json")
    for same in ("loop", "connections", "lanes_per_request", "hits", "pool_requests", "ramp_s",
                 "lanes_in_flight", "warm_buckets", "load_lanes", "readback_lanes"):
        assert traffic[same] == frames[same], same
    assert (traffic["kind"], traffic["flagged_lane_share"], traffic["flagged_bits"]) == (
        "frames_mixed", 0.01, [1, 2, 16])
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    # Every metric that lists the bypass lists the cell, `req_p99_ms` too: six
    # runs on the chip spread 3.6% (PERF.md section 2), under half its bound.
    # (The six metrics of PR 45's cell read its pipeline, and list it and the
    # bypass alone.)
    names = [m["name"] for m in bench["per_layer"]]
    later = names[names.index(SYNC_ROWS) + 1:]
    for name, metric in by_name.items():
        if BYPASS in metric.get("workloads", ()) and name not in later:
            assert CELL in metric["workloads"], name
    assert CELL in by_name["req_p99_ms"]["workloads"]
    assert by_name["ingress.native_frame_share"]["workloads"][-1] == CELL
    assert CELL in by_name["kernel.apply_roofline"]["workloads"]
    for name in NEW_METRICS:
        metric = by_name[name]
        # A pass's hold has nothing to read where no pass runs: the bypass is
        # listed by the three that read something there (where PR 45's cell,
        # which reports what its bypass reports, follows it).
        # (PR 48's cell, the hot keys GLOBAL on four chips, is appended to each.)
        listed = [w for w in metric["workloads"] if w != "v5e4-mesh-1m-global.frames"]
        assert listed[:2] == ([CELL] if name == "global.sync_hold_ms_per_pass" else [CELL, BYPASS])
        spec = _cell_json("layer_metrics", name + ".json")
        assert spec["reader"] in ("mesh_tally", "phase_ms_per")
        assert (spec["layer"], spec["unit"], spec["source"], spec["moves"], spec["better"]) == (
            metric["layer"], metric["unit"], metric["source"], metric["moves"], metric["better"])
    assert [by_name[name]["moves"] for name in NEW_METRICS] == [
        "req_p50_ms", "req_p50_ms", "req_p99_ms", "checks_per_s"]
    # Appended in PR 41's order, and PR 42's one after them: the rows a pass
    # carried, read where a pass runs.
    at = names.index(SYNC_ROWS)
    assert names[at - len(NEW_METRICS):at + 1] == [*NEW_METRICS, SYNC_ROWS]
    rows, spec = by_name[SYNC_ROWS], _cell_json("layer_metrics", SYNC_ROWS + ".json")
    assert rows["workloads"] == [CELL, "v5e4-mesh-1m-global.frames"] and spec["reader"] == "mesh_tally"
    assert (spec["layer"], spec["unit"], spec["source"], spec["moves"], spec["better"]) == (
        rows["layer"], rows["unit"], rows["source"], rows["moves"], rows["better"]) == (
        by_name["global.sync_hold_ms_per_pass"]["layer"], "rows", "program_counter", "req_p99_ms", "lower")
    # Appended, each after PR 39's.
    assert bench["workloads"].index(cell) == 6 and bench["configs"].index(entry) == 5


# ---------------------------------------------------------------------
# The readers of the four new metrics, on snapshots written out here
# ---------------------------------------------------------------------
def _snap(mesh=None, phases=None):
    device = {} if mesh is None else {"mesh": mesh}
    rows = {name: {"count": c, "sum_ms": ms} for name, (c, ms) in (phases or {}).items()}
    return {"device": device, "latency": {"phases": rows}, "metrics": []}


def _read(name, ctx):
    spec = _cell_json("layer_metrics", name + ".json")
    reader = {"mesh_tally": mesh_tally, "phase_ms_per": phase_ms_per, "counter_share": counter_share}
    return reader[spec["reader"]].read(ctx, spec["params"])


LOADED = {"shards": 1, "dispatches": 245, "lanes": 245 * 4096, "flaggedLanes": 0}
# 3,000 frames more, 41 flagged lanes each.
WINDOW = {"shards": 1, "dispatches": 3_245, "lanes": 3_245 * 4096, "flaggedLanes": 3_000 * 41}
PHASES_LOADED = {"behavior.handle": (245, 2.0), "global.sync": (1, 30.0), "global.sync_drain": (1, 1.0)}
# 3,000 takes, every one with a GLOBAL lane, and 60 passes of 3 + 33 ms.
PHASES_WINDOW = {"behavior.handle": (3_245, 2.0 + 90.0), "dispatch.global_note": (3_000, 150.0),
                 "global.sync": (61, 30.0 + 60 * 33.0), "global.sync_drain": (61, 1.0 + 60 * 3.0)}


@pytest.mark.parametrize("name,want", [
    ("behavior.flagged_lane_share", 100 * 41 / 4096),
    ("behavior.handle_ms_per_dispatch", (90.0 + 150.0) / 3_000),
    ("global.sync_hold_ms_per_pass", 36.0),
    ("global.sync_ms_per_req", 60 * 36.0 / 3_000),
])
def test_the_new_readers_give_the_values_reckoned_by_hand(name, want):
    ctx = {"before": _snap(LOADED, PHASES_LOADED), "after": _snap(WINDOW, PHASES_WINDOW),
           "requests": 3_000}
    assert _read(name, ctx) == pytest.approx(want)


def test_the_bypass_reads_the_check_alone_no_flagged_lane_and_no_pass():
    """`v5e1-1m.frames`: the phase is entered a take, no GLOBAL lane, so no
    pass between the snapshots: a share of 0, the check's microseconds, a
    true 0 of sync a request, and nothing for a pass's hold."""
    plain = dict(WINDOW, flaggedLanes=0)
    ctx = {"before": _snap(LOADED, PHASES_LOADED),
           "after": _snap(plain, dict(PHASES_LOADED, **{"behavior.handle": (3_245, 2.0 + 15.0)})),
           "requests": 3_000}
    assert _read("behavior.flagged_lane_share", ctx) == 0.0
    assert _read("behavior.handle_ms_per_dispatch", ctx) == pytest.approx(0.005)
    assert _read("global.sync_ms_per_req", ctx) == 0.0
    assert _read("global.sync_hold_ms_per_pass", ctx) is None


def test_the_rows_a_pass_carried_are_read_from_the_sync_counters_and_a_parent_reads_nothing():
    """`global.sync_rows_per_pass`: 60 passes in ramp and window, two of them
    bursts of two launches, at the width 4096.  A program from before PR 42
    counts no pass (its `mesh` block has no such counter), so there is nothing
    to divide by: None, and no raise; so with no snapshot at all."""
    loaded = dict(LOADED, syncPasses=1, syncRows=4096, syncTouched=1)
    window = dict(WINDOW, syncPasses=61, syncRows=4096 * 63, syncTouched=1 + 60 * 85)
    ctx = {"before": _snap(loaded, PHASES_LOADED), "after": _snap(window, PHASES_WINDOW), "requests": 3_000}
    assert _read(SYNC_ROWS, ctx) == pytest.approx(4096 * 62 / 60)
    ctx = {"before": _snap(loaded, PHASES_LOADED), "after": _snap(dict(window, syncRows=4096 * 61), PHASES_WINDOW),
           "requests": 3_000}
    assert _read(SYNC_ROWS, ctx) == 4096.0
    parent = {"before": _snap(LOADED, PHASES_LOADED), "after": _snap(WINDOW, PHASES_WINDOW), "requests": 3_000}
    assert _read(SYNC_ROWS, parent) is None
    assert _read(SYNC_ROWS, {"before": _snap(), "after": _snap(), "requests": 0}) is None
    # The bypass: the counters are there and no pass ran between the snapshots.
    idle = {"before": _snap(loaded, PHASES_LOADED), "after": _snap(dict(WINDOW, **{
        k: loaded[k] for k in ("syncPasses", "syncRows", "syncTouched")}), PHASES_LOADED), "requests": 3_000}
    assert _read(SYNC_ROWS, idle) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_from_before_this_cell_reads_0_or_nothing_and_does_not_raise(name):
    """The parent: a `mesh` block without `flaggedLanes` reads a share of 0
    (`mesh_tally` takes a missing counter for 0), no `behavior.handle` phase
    reads nothing; its sync phases are the ones it has always had.  No
    snapshot at all reads nothing."""
    old = {k: v for k, v in LOADED.items() if k != "flaggedLanes"}
    sync = {k: v for k, v in PHASES_WINDOW.items() if k.startswith("global.sync")}
    ctx = {"before": _snap(old, {k: PHASES_LOADED[k] for k in sync}),
           "after": _snap(dict(old, dispatches=3_245, lanes=3_245 * 4096), sync), "requests": 3_000}
    want = {"behavior.flagged_lane_share": 0.0, "behavior.handle_ms_per_dispatch": None,
            "global.sync_hold_ms_per_pass": 36.0, "global.sync_ms_per_req": 0.72}
    got = _read(name, ctx)
    assert got is None if want[name] is None else got == pytest.approx(want[name])
    empty = {"before": _snap(), "after": _snap(), "requests": 0}
    assert _read(name, empty) is None
