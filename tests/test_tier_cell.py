"""The cell `ycsb-f-32m.frames` at test size, on the CPU, in the one tier the
configuration runs and in the two tiers it turned down: 8,000 keys of
`chipbench/population.py` (the configuration's own population: token and
leaky, 16 limit tiers) in 8,192 slots, either one table or a front of 256 and
a back tier of 8,192 - 256, loaded and then asked for in 64-lane frames drawn
by its scrambled Zipfian 0.99, through `MeshBucketStore` on one device, every
lane held to the sequential oracle, which never evicts.  Both must keep the
configuration's guarantee (`eviction: none`) and count every resident bucket.

The two-tier run goes on until the demotions have passed three times the back
tier's capacity: allocated by a ring cursor alone, the back tier overwrote
resident keys at the cursor's first lap (`back_evictions` > 0, answers wrong);
it must lose none however long the run.  Then: one shape of the move program
a warm bucket, no compile after warm-up, and the moves a phase of their own
inside the launch.

The harness's `--rehearse` cannot stand in for the two-tier run: it shrinks
`GUBER_CACHE_SIZE` to 32,768 for 20,000 keys and leaves a back tier at its
configured size, so every key stays in the front and nothing moves.
Everything is made from SEED."""

from __future__ import annotations

import json
import os
import sys

import jax
import numpy as np
import pytest

from gubernator_tpu import native, saturation, telemetry
from gubernator_tpu.parallel import mesh
from gubernator_tpu.parallel.mesh import MeshBucketStore
from gubernator_tpu.types import Algorithm, RateLimitRequest

from . import oracle as orc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench.population import Population  # noqa: E402

pytestmark = pytest.mark.skipif(
    not native.available(), reason="the two-tier table needs the native host runtime")

SEED = 31
KEYS = 8_000
FRONT = 256
BACK = 8_192 - FRONT
LANES = 64
NAME = "bench"
T0 = 1_790_000_000_000
LAPS = 3  # the demotions pass this many times the back tier's capacity
CHURN = 300  # frames after the load where nothing is demoted
CELL = "ycsb-f-32m.frames"
TIERS = {"one tier": (FRONT + BACK, 0), "two tiers": (FRONT, BACK)}


def _cell_json(*parts):
    with open(os.path.join(REPO, "chipbench", *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def pop():
    config = _cell_json("configs", "ycsb-f-32m.json")
    assert config["guarantees"]["eviction"].startswith("none")
    assert KEYS % LANES == 0 and KEYS <= FRONT + BACK
    return Population(config["population"], KEYS, SEED)


def _tier_stats(store) -> dict:
    total, back_used, demotions, promotions, back_evictions = store.tables[0].tier_stats
    return {"total": total, "back_used": back_used, "demotions": demotions,
            "promotions": promotions, "back_evictions": back_evictions}


@pytest.fixture(scope="module", params=list(TIERS))
def run(request, pop):
    """The store after warm-up, the load and the churn; the answers wrong
    against the oracle; the phases before and after the churn."""
    front, back = TIERS[request.param]
    telemetry.set_enabled(True)
    telemetry.reset()
    saturation.reset()
    keys = [f"{NAME}_{pop.unique_key(i)}" for i in range(pop.n)]
    shapes_before = mesh._moves_mesh_jit._cache_size()
    store = MeshBucketStore(
        capacity_per_shard=front, back_capacity_per_shard=back, devices=jax.devices()[:1])
    store.warmup(T0 - 60_000, warm_shapes=[LANES])
    telemetry.mark_steady()
    shapes_warm = mesh._moves_mesh_jit._cache_size()
    cache = orc.OracleCache()
    rng = np.random.default_rng([SEED, 0x74696572])
    out = {"store": store, "back": back, "wrong": 0, "frames": 0}
    now = T0

    def frame(idx):
        nonlocal now
        now += int(rng.integers(1, 50))
        got = store.apply_columns(
            [keys[i] for i in idx.tolist()], pop.algo[idx], np.zeros(len(idx), np.int32),
            np.ones(len(idx), np.int64), pop.limit[idx],
            np.full(len(idx), pop.duration_ms, np.int64), now)
        out["frames"] += 1
        for lane, i in enumerate(idx.tolist()):
            want = orc.apply(cache, RateLimitRequest(
                name=NAME, unique_key=pop.unique_key(i), hits=1, limit=int(pop.limit[i]),
                duration=pop.duration_ms, algorithm=Algorithm(int(pop.algo[i]))), now)
            out["wrong"] += (
                int(got["status"][lane]), int(got["limit"][lane]), int(got["remaining"][lane]),
                int(got["reset_time"][lane]),
            ) != (int(want.status), want.limit, want.remaining, want.reset_time)

    for lo in range(0, pop.n, LANES):  # the load: every key once
        frame(np.arange(lo, lo + LANES))
    out["loaded"] = _tier_stats(store)
    out["latency_loaded"] = saturation.phase_snapshot()
    out["load_frames"] = out["frames"]
    while (out["frames"] < out["load_frames"] + CHURN
           or 0 < back and _tier_stats(store)["demotions"] <= LAPS * back):
        frame(pop.draw(rng, LANES))
    out["after"] = _tier_stats(store)
    out["latency_after"] = saturation.phase_snapshot()
    out["shapes"] = (shapes_before, shapes_warm, mesh._moves_mesh_jit._cache_size())
    out["steady_recompiles"] = telemetry.steady_recompile_count()
    out["move_runs"] = telemetry.snapshot()["programRuns"].get("mesh:tier_moves", {"count": 0})["count"]
    yield out
    telemetry.reset()
    saturation.reset()


two_tiers_only = pytest.mark.parametrize("run", ["two tiers"], indirect=True)


def test_every_lane_equals_the_oracle_that_never_evicts(run):
    assert run["frames"] >= run["load_frames"] + CHURN
    assert run["wrong"] == 0


def test_every_key_is_resident_and_counted_once(run):
    store = run["store"]
    # Warm-up's 1 ms keys: two tiers have long dropped them (an expired row is
    # not demoted); one tier keeps those whose slots nobody needed.
    spare = 0 if run["back"] else FRONT + BACK - KEYS
    assert KEYS <= store.size() <= KEYS + spare
    store.check_consistency()


def test_the_gauge_counts_every_resident_bucket(run):
    from gubernator_tpu.metrics import Metrics

    m = Metrics()
    m.observe_cache(run["store"])
    assert m.cache_size._value.get() == run["store"].size() >= KEYS  # noqa: SLF001


def test_no_compile_after_warm_up(run):
    assert run["steady_recompiles"] == 0
    if not run["back"]:  # no move program, no phase
        assert run["shapes"][2] == run["shapes"][0]
        assert "dispatch.moves" not in run["latency_after"] and run["move_runs"] == 0


@two_tiers_only
def test_nothing_is_lost_after_three_laps_of_the_back_tier(run):
    tiered = run
    loaded, after = tiered["loaded"], tiered["after"]
    # The load: 8,000 creates through 256 slots demote all but the last 256
    # (warm-up's keys, 1 ms long, are dropped and not demoted); nothing promoted.
    assert (loaded["promotions"], loaded["demotions"], loaded["back_used"]) == (
        0, KEYS - FRONT, KEYS - FRONT)
    # The churn: every bucket stays resident, so a promotion (a row out of the
    # back) is paid for by a demotion (a row into it).
    assert after["demotions"] > LAPS * BACK
    assert after["promotions"] == after["demotions"] - loaded["demotions"]
    assert after["back_evictions"] == 0
    assert len(tiered["store"].tables[0]) == FRONT and after["back_used"] == KEYS - FRONT


@two_tiers_only
def test_one_move_program_a_warm_bucket(run):
    tiered = run
    before, warm, after = tiered["shapes"]
    assert tiered["store"]._move_buckets == [LANES]
    assert warm == before + 1  # warm-up compiled the one shape
    assert after == warm  # ... and load and churn launched no other
    assert "mesh:tier_moves" in telemetry.compile_snapshot()


@two_tiers_only
def test_the_moves_are_a_phase_inside_the_launch(run):
    tiered = run
    before, after = tiered["latency_loaded"], tiered["latency_after"]
    moves = after["dispatch.moves"]["count"] - before["dispatch.moves"]["count"]
    assert 0 < moves <= tiered["frames"] - tiered["load_frames"]  # entered only with moves queued
    launch_ms = after["dispatch.launch"]["sum_ms"] - before["dispatch.launch"]["sum_ms"]
    assert 0 < after["dispatch.moves"]["sum_ms"] - before["dispatch.moves"]["sum_ms"] < launch_ms
    # One launch of the move program a phase entered, and warm-up's; under a
    # label that `batcher.lanes_per_dispatch` does not count.
    assert tiered["move_runs"] == after["dispatch.moves"]["count"] + 1
    assert not any(label.startswith("mesh:dispatch:") and "moves" in label
                   for label in telemetry.snapshot()["programRuns"])


def test_the_cell_is_the_one_tier_and_its_files_say_so():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("ycsb-f-32m", "frames-pool4k", 1)
    config = _cell_json("configs", "ycsb-f-32m.json")
    assert config["env"] == {"GUBER_NATIVE_HTTP": "1", "GUBER_CACHE_SIZE": str(2**25)}
    assert config["population"]["resident_keys"] == 32_000_000 <= 2**25 < config["control"]["resident_keys"]
    assert config["reduced"] == []
    traffic = _cell_json("traffic", "frames-pool4k.json")
    assert (traffic["pool_requests"], traffic["ramp_s"], traffic["warm_buckets"]) == (4096, 10.0, [4096])
    # Every metric that lists its cells and names this one is read by files that exist.
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", []) and metric in bench["per_layer"]:
            spec = _cell_json("layer_metrics", metric["name"] + ".json")
            assert os.path.exists(os.path.join(REPO, "chipbench", "readers", spec["reader"] + ".py"))
