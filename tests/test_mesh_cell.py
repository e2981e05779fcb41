"""The four-shard cell `v5e4-mesh-1m.frames` at test size, on the CPU's
virtual devices: the cell's own frames (1028 lanes drawn by the scrambled
Zipfian 0.99 of `chipbench/population.py`, token and leaky, hot keys repeated
inside a frame, two frames coalesced into one take) through `MeshBucketStore`
on S = 1, 2 and 4 shards, held lane by lane to the sequential oracle; where
the keys live afterwards; why the cell's frames have 1028 lanes; and what the
`mesh` block of `GET /debug/device` counts, held to values reckoned here from
`shard_of_key` and `pad_size` alone.  Everything is made from SEED."""

from __future__ import annotations

import functools
import json
import os
import sys

import jax
import numpy as np
import pytest

from gubernator_tpu import native, saturation
from gubernator_tpu.models.shard import pad_size
from gubernator_tpu.parallel.mesh import MeshBucketStore, shard_of_key
from gubernator_tpu.types import Algorithm, RateLimitRequest

from . import oracle as orc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench.population import Population  # noqa: E402
from chipbench.readers import mesh_tally, phase_ms_per  # noqa: E402

pytestmark = pytest.mark.skipif(
    not native.available(), reason="the columnar path needs the native host runtime")

SEED = 27
KEYS = 20_000  # the harness's rehearsal size
SLOTS = 32_768
LANES = 1028  # frames-1k.json: lanes_per_request
NAME = "bench"
T0 = 1_790_000_000_000
# The window's takes: a frame alone, or two frames coalesced into one take.
TAKE_FRAMES = [1, 2, 1, 1, 2, 2, 1, 2, 1, 1, 2, 1]
SHARDS = [1, 2, 4]


def _cell_json(*parts):
    with open(os.path.join(REPO, "chipbench", *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def pop():
    assert _cell_json("traffic", "frames-1k.json")["lanes_per_request"] == LANES
    return Population(_cell_json("configs", "v5e4-mesh-1m.json")["population"], KEYS, SEED)


@pytest.fixture(scope="module")
def hash_keys(pop):
    return [f"{NAME}_{pop.unique_key(i)}" for i in range(pop.n)]


@pytest.fixture(scope="module")
def takes(pop):
    """[(key indices, hits, now_ms)]: the load (every key once, one hit, in
    frames of LANES; the tail frame filled with hits=0 re-reads, as the
    harness fills it), then the window's takes."""
    rng = np.random.default_rng([SEED, 0x74616B65])
    out, now = [], T0
    for lo in range(0, pop.n, LANES):
        hi = min(lo + LANES, pop.n)
        fill = np.arange(LANES - (hi - lo))
        idx = np.concatenate([np.arange(lo, hi), fill])
        hits = np.concatenate([np.ones(hi - lo, np.int64), np.zeros(len(fill), np.int64)])
        out.append((idx, hits, now))
        now += int(rng.integers(1, 20))
    for frames in TAKE_FRAMES:
        now += int(rng.integers(0, 4000))
        idx = pop.draw(rng, frames * LANES)
        out.append((idx, np.ones(len(idx), np.int64), now))
    return out


@pytest.fixture(scope="module")
def expected(pop, takes):
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
def served(pop, hash_keys, takes):
    """shards -> (the store, its answers a take, the mesh tally before and
    after the takes); each store is driven once."""

    @functools.cache
    def run(shards: int):
        store = MeshBucketStore(
            capacity_per_shard=SLOTS // shards, devices=jax.devices()[:shards])
        before = saturation.mesh_tally.snapshot()
        answers = []
        for idx, hits, now in takes:
            r = store.apply_columns(
                [hash_keys[i] for i in idx.tolist()], pop.algo[idx],
                np.zeros(len(idx), np.int32), hits, pop.limit[idx],
                np.full(len(idx), pop.duration_ms, np.int64), now)
            answers.append(np.stack(
                [r["status"], r["limit"], r["remaining"], r["reset_time"]], axis=1))
        return store, answers, before, saturation.mesh_tally.snapshot()

    return run


def test_the_takes_are_the_cells(pop, takes):
    """Hot keys repeat inside a frame, both algorithms are asked for, and some
    bucket runs dry: the duplicate groups and OVER_LIMIT are exercised."""
    window = takes[-len(TAKE_FRAMES):]
    assert {len(idx) for idx, _, _ in window} == {LANES, 2 * LANES}
    for idx, _, _ in window:
        assert np.bincount(idx).max() >= 20  # the hottest key, many times in one take
        assert set(pop.algo[idx].tolist()) == {0, 1}


@pytest.mark.parametrize("shards", SHARDS)
def test_every_lane_equals_the_sequential_oracle(served, expected, shards):
    """Status, limit, remaining and reset of every lane of every take; since
    S = 1 is held to the same answers, S = 2 and 4 equal it too."""
    _, answers, _, _ = served(shards)
    over = 0
    for t, (got, want) in enumerate(zip(answers, expected)):
        wrong = np.flatnonzero((got != want).any(axis=1))
        assert len(wrong) == 0, (t, wrong[:5], got[wrong[:5]], want[wrong[:5]])
        over += int((want[:, 0] == 1).sum())
    assert over > 0


@pytest.mark.parametrize("shards", SHARDS)
def test_every_key_lives_in_the_shard_that_owns_it_and_in_no_other(served, hash_keys, shards):
    store, _, _, _ = served(shards)
    resident = [set(t.keys()) for t in store.tables]
    assert sum(len(r) for r in resident) == len(hash_keys) == store.size()
    for key in hash_keys:
        owner = shard_of_key(key, shards)
        assert [s for s in range(shards) if key in resident[s]] == [owner], key


@pytest.mark.parametrize("shards", SHARDS)
def test_the_mesh_tally_counts_what_the_keys_shards_give(served, hash_keys, takes, shards):
    """`lanes`, `paddedLanes`, `fullestShardLanes` and `dispatches` of the
    `mesh` block, reckoned from `shard_of_key` and `pad_size`."""
    _, _, before, after = served(shards)
    owner = np.array([shard_of_key(k, shards) for k in hash_keys])
    want = {"dispatches": 0, "lanes": 0, "paddedLanes": 0, "fullestShardLanes": 0}
    for idx, _, _ in takes:
        fullest = int(np.bincount(owner[idx], minlength=shards).max())
        want["dispatches"] += 1
        want["lanes"] += len(idx)
        want["fullestShardLanes"] += fullest
        want["paddedLanes"] += shards * pad_size(fullest)
    assert after["shards"] == shards
    assert {k: after[k] - before[k] for k in want} == want
    assert after["rounds"] - before["rounds"] >= want["dispatches"]
    if shards == 1:
        assert want["fullestShardLanes"] == want["lanes"]


def test_a_frame_of_1028_lanes_keeps_four_shards_in_the_1024_bucket(pop, hash_keys):
    """frames-1k.json's argument, from `pad_size` and `shard_of_key` alone:
    over 2,000 takes of one and of two 1028-lane frames the fullest of four
    shards pads to 1024 every time; a 1024-lane frame CAN split 256/256/256/256,
    which pads to the 256 bucket that warm-up never compiles."""
    warm = _cell_json("traffic", "frames-1k.json")["warm_buckets"]
    assert warm == [1024]
    owner = np.array([shard_of_key(k, 4) for k in hash_keys])
    rng = np.random.default_rng([SEED, 0x706164])
    for t in range(2000):
        counts = np.bincount(owner[pop.draw(rng, LANES * (1 + t % 2))], minlength=4)
        assert pad_size(int(counts.max())) == 1024, (t, counts)
    # 4 x 257: the fullest shard of 1028 lanes holds 257 at the least.
    assert pad_size(-(-LANES // 4)) == 1024 and pad_size(1024 // 4) == 256
    even = np.concatenate([np.flatnonzero(owner == s)[:256] for s in range(4)])
    assert len(even) == 1024
    assert pad_size(int(np.bincount(owner[even], minlength=4).max())) == 256


def test_debug_device_serves_the_mesh_block(served):
    """`GET /debug/device` carries the tally under `mesh`, cumulative."""
    from gubernator_tpu import gateway

    served(4)
    status, _, body = gateway.handle_request(None, "GET", "/debug/device", b"")
    assert status == 200
    mesh = json.loads(body)["mesh"]
    assert mesh == saturation.mesh_tally.snapshot()
    assert set(mesh) == {
        "shards", "dispatches", "lanes", "paddedLanes", "fullestShardLanes", "rounds",
        "laneWireDispatches", "laneWireLanes", "configRows", "uploads",
        "calendarLanes", "wideDispatches", "flaggedLanes",
        "syncPasses", "syncRows", "syncTouched", "globalLanes", "globalKeys",
        "launches", "fusedDispatches", "takes", "takeFrames", "inFlightSum"}
    assert mesh["dispatches"] >= len(TAKE_FRAMES) and mesh["paddedLanes"] >= mesh["lanes"] > 0


def test_a_sampled_takes_plan_and_stage_spans_carry_the_shards_fill(pop, hash_keys):
    """`dispatch.prepare` and `dispatch.stage` of a sampled take carry
    `shards`, `fullest` and `padded` beside its ticket."""
    from gubernator_tpu import tracing

    store = MeshBucketStore(capacity_per_shard=SLOTS // 4, devices=jax.devices()[:4])
    idx = pop.draw(np.random.default_rng([SEED, 0x7370616E]), LANES)
    keys = [hash_keys[i] for i in idx.tolist()]
    fullest = int(np.bincount([shard_of_key(k, 4) for k in keys], minlength=4).max())
    prev = tracing.sample_rate()
    tracing.set_sample_rate(1.0)
    try:
        bt = tracing.new_batch(roll=True)
        tracing.stage_batch_trace(bt)
        store.apply_columns(
            keys, pop.algo[idx], np.zeros(LANES, np.int32), np.ones(LANES, np.int64),
            pop.limit[idx], np.full(LANES, pop.duration_ms, np.int64), T0)
        spans = {s["name"]: s["attrs"] for s in tracing.spans_snapshot(bt.ctx.trace_hex)}
    finally:
        tracing.set_sample_rate(prev)
    for name in ("dispatch.prepare", "dispatch.stage"):
        attrs = spans[name]
        assert (attrs["shards"], attrs["fullest"], attrs["padded"]) == (4, fullest, 4 * 1024), name
        assert attrs["ticket"] == spans["dispatch.prepare"]["ticket"]
    assert spans["dispatch.prepare"]["lanes"] == LANES


# ---------------------------------------------------------------------
# The readers of the three mesh.* metrics, on snapshots written out here
# ---------------------------------------------------------------------
def _snap(mesh=None, stage=None):
    device = {} if mesh is None else {"mesh": mesh}
    phases = {} if stage is None else {"dispatch.stage": {"count": stage[0], "sum_ms": stage[1]}}
    return {"device": device, "latency": {"phases": phases}}


BEFORE = {"shards": 4, "dispatches": 973, "lanes": 1_000_244, "paddedLanes": 3_985_408,
          "fullestShardLanes": 262_710, "rounds": 980}
# 300 takes more: 200 of one frame and 100 of two, the fullest shards 61,680 and 61,700 lanes.
AFTER = {"shards": 4, "dispatches": 1273, "lanes": 1_000_244 + 200 * 1028 + 100 * 2056,
         "paddedLanes": 3_985_408 + 300 * 4096, "fullestShardLanes": 262_710 + 61_680 + 61_700,
         "rounds": 2900}


def _spec(name):
    return _cell_json("layer_metrics", name + ".json")


def test_the_mesh_readers_give_the_values_reckoned_by_hand():
    ctx = {"before": _snap(BEFORE, (973, 1400.0)), "after": _snap(AFTER, (1273, 1790.0)),
           "requests": 400}
    fill = _spec("mesh.pad_fill")
    assert fill["reader"] == "mesh_tally"
    assert mesh_tally.read(ctx, fill["params"]) == pytest.approx(100 * 411_200 / 1_228_800)
    skew = _spec("mesh.shard_skew")
    assert skew["reader"] == "mesh_tally"
    assert mesh_tally.read(ctx, skew["params"]) == pytest.approx(4 * 123_380 / 411_200)
    stage = _spec("mesh.stage_ms_per_dispatch")
    assert stage["reader"] == "phase_ms_per"
    assert phase_ms_per.read(ctx, stage["params"]) == pytest.approx(390.0 / 300)


def test_one_shard_reads_a_skew_of_one():
    one = {"shards": 1, "dispatches": 10, "lanes": 40_960, "paddedLanes": 40_960,
           "fullestShardLanes": 40_960, "rounds": 10}
    ctx = {"before": _snap({k: 0 for k in one}), "after": _snap(one)}
    assert mesh_tally.read(ctx, _spec("mesh.shard_skew")["params"]) == 1.0
    assert mesh_tally.read(ctx, _spec("mesh.pad_fill")["params"]) == 100.0


@pytest.mark.parametrize("name", ["mesh.pad_fill", "mesh.shard_skew"])
def test_the_mesh_readers_read_nothing_from_a_program_without_the_block(name):
    """The parent of PR 27 serves no `mesh` block: None, and no exception; and
    None where nothing was dispatched between the snapshots."""
    params = _spec(name)["params"]
    assert mesh_tally.read({"before": _snap(), "after": _snap()}, params) is None
    assert mesh_tally.read({"before": _snap(), "after": _snap(AFTER)}, params) is None
    assert mesh_tally.read({"before": _snap(AFTER), "after": _snap(AFTER)}, params) is None
