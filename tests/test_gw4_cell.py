"""The cell `v5e1-1m-gw4.frames` at test size, on the CPU: four gateway
connections, each with one frame in flight, in front of ONE daemon whose
warm-up compiled ONE pad bucket (1024 lanes a shard here, where the cell warms
4096).  8,000 keys of `chipbench/population.py` with the configuration's own
`population` block in 32,768 slots.

Held here: a take of the native ingress pump never outgrows what warm-up
compiled (`NativeIngressPump.take_bound`: the shards' share of the widest
warmed bucket, `TAKE_LANES` where warm-up was given no shape), so with four
frames waiting every dispatch still pads to the warmed bucket and nothing
compiles, on S = 1, 2 and 4 shards; every answer of four frames in flight
together equals the sequential oracle for SOME order of those frames (which
is all a client may ask of frames it sent at once) and the read-back equals it
exactly; frames that fit the bound together are still taken together (two
1028-lane frames on four shards: the mesh cell's coalescing); the same daemon
on the bound the program had before (64,000 lanes, by the constructor's
argument) pads past the warmed bucket and compiles inside a request: the
control that shows this file sees the mechanism; the counters of the takes and
the launches, `/debug/status` `ingress.takeLanes`, the cell's files, the
payloads (`v5e1-1m`'s population and `frames`' own frames from one seed), and
the readers of the cell's six metrics on counters written out by hand.
Everything is made from SEED."""

from __future__ import annotations

import copy
import json
import os
import sys
import threading
import time

import jax
import numpy as np
import pytest

from gubernator_tpu import gateway, native, saturation, telemetry
from gubernator_tpu.gateway import NativeIngressPump
from gubernator_tpu.models.shard import pad_size
from gubernator_tpu.parallel.mesh import MeshBucketStore
from gubernator_tpu.types import Algorithm, RateLimitRequest

from . import oracle as orc
from .conftest import one_device_store

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import gubc, harness  # noqa: E402
from chipbench.daemon import Http  # noqa: E402
from chipbench.generators import frames as gen_frames  # noqa: E402
from chipbench.population import Population  # noqa: E402
from chipbench.readers import (  # noqa: E402
    kernel_take_roofline,
    kernel_us_per_take,
    mesh_counted,
    mesh_tally,
    phase_ms_seen,
)

pytestmark = pytest.mark.skipif(
    not native.available(), reason="the columnar path needs the native host runtime")

SEED = 45
KEYS = 8_000
SLOTS = 32_768
BUCKET = 1024  # the one per-shard pad bucket the daemons here warm (the cell: 4096)
CONNECTIONS = 4
# A frame's lanes by the shards: the bucket itself on one shard (the cell's
# shape: a frame fills the bucket, so a take is one frame); on S shards 257 a
# shard, the mesh cell's argument: a lone frame's fullest shard holds at least
# 257 lanes and pads to 1024, and two frames taken together still do.
LANES = {1: BUCKET, 2: 2 * 257, 4: 4 * 257}
NAME = "bench"
T0 = 1_790_000_000_000
CELL = "v5e1-1m-gw4.frames"
BYPASS = "v5e1-1m.frames"
NEW_METRICS = ("pump.frames_per_take", "pipeline.takes_in_flight", "launch.fused_share",
               "launch.gate_wait_ms", "kernel.us_per_take", "kernel.take_roofline")
PER_LAUNCH = ("kernel.us_per_dispatch", "kernel.apply_roofline", "kernel.rounds_us_per_dispatch")


def _cell_json(*parts):
    with open(os.path.join(REPO, "chipbench", *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def pop():
    return Population(_cell_json("configs", "v5e1-1m-gw4.json")["population"], KEYS, SEED)


# ---------------------------------------------------------------------
# The bound
# ---------------------------------------------------------------------
def test_the_bound_is_what_warm_up_was_asked_for_and_take_lanes_where_it_was_asked_nothing():
    """No warm-up, or a warm-up that was given no shape (it compiles the
    smallest bucket for itself): the pump's bound is the constant.  A warm-up
    that was given shapes records the widest bucket they pad to."""
    store = one_device_store(1024)
    assert store.warm_bucket == 0
    assert NativeIngressPump.take_bound(store) == NativeIngressPump.TAKE_LANES == 64_000
    store.warmup(T0, warm_shapes=[])
    assert store.warm_bucket == 0 and NativeIngressPump.take_bound(store) == 64_000
    store.warmup(T0, warm_shapes=[1, 40])
    assert store.warm_bucket == 64 == pad_size(40) and NativeIngressPump.take_bound(store) == 64


@pytest.mark.parametrize("shards,shapes,lanes", [
    (1, [4096], 4096),  # the one-chip frames cells: a frame a take
    (4, [1024], 4096),  # v5e4-mesh-1m.frames: over the 2,056 lanes it has in flight
    (1, [1, 250, 1000], 1024),  # the shipped default
    (8, [1, 250, 1000], 8192),
])
def test_the_bound_is_the_shards_share_of_the_widest_warmed_bucket(shards, shapes, lanes):
    """What `MeshBucketStore.warmup` records for these shapes is
    `pad_size` of the widest (its identical-keys leg puts every lane on one
    shard); the served daemons below hold the recording itself."""
    class Store:
        warm_bucket, n_shards = pad_size(max(shapes)), shards

    assert NativeIngressPump.take_bound(Store) == lanes


def test_the_bound_never_passes_take_lanes():
    class Wide:
        warm_bucket, n_shards = 1 << 20, 4

    assert NativeIngressPump.take_bound(Wide) == NativeIngressPump.TAKE_LANES


# ---------------------------------------------------------------------
# Four connections through a served daemon's front door
# ---------------------------------------------------------------------
class _ParentBoundPump(NativeIngressPump):
    """The pump on the bound it had before: whatever is queued, to 64,000 lanes."""

    def __init__(self, service):
        super().__init__(service, take_lanes=NativeIngressPump.TAKE_LANES)


def _daemon(shards: int):
    from gubernator_tpu.cluster import fast_test_behaviors
    from gubernator_tpu.config import DaemonConfig
    from gubernator_tpu.daemon import Daemon
    from gubernator_tpu.utils.clock import Clock

    behaviors = fast_test_behaviors()
    behaviors.global_sync_wait_s = 3600.0
    clock = Clock()
    clock.freeze(T0 - 60_000)
    daemon = Daemon(DaemonConfig(
        listen_address="127.0.0.1:0", grpc_listen_address="127.0.0.1:0", cache_size=SLOTS,
        behaviors=behaviors, peer_discovery_type="static", native_http=True,
        devices=jax.devices()[:shards], warmup_shapes=[BUCKET]), clock=clock).start()
    daemon.set_peers([daemon.peer_info])
    return daemon, clock, f"127.0.0.1:{daemon.gateway._edge.port}"


class _Served:
    """A daemon, four connections and the sequential oracle beside it."""

    def __init__(self, pop, shards: int):
        telemetry.set_enabled(True)
        telemetry.reset()
        saturation.reset()
        self.pop, self.shards, self.lanes = pop, shards, LANES[shards]
        self.daemon, self.clock, self.address = _daemon(shards)
        self.https = [Http(self.address, timeout_s=60.0) for _ in range(CONNECTIONS)]
        self.cache = orc.OracleCache()
        self.rng = np.random.default_rng([SEED, 0x677734, shards])
        self.now = T0

    def close(self):
        for h in self.https:
            h.close()
        self.daemon.close()
        telemetry.reset()
        saturation.reset()

    @property
    def pump(self):
        return self.daemon.gateway.pump

    def device(self) -> dict:
        return self.https[0].get_json("/debug/device")

    def send(self, http, idx, hits) -> np.ndarray:
        n = len(idx)
        frame = gubc.encode_frame(
            gubc.fixed_width_column(NAME.encode() * n, n, len(NAME)),
            gubc.fixed_width_column(self.pop.keys_blob(idx), n, self.pop.key_width),
            self.pop.algo[idx], np.zeros(n, np.int32), np.full(n, hits, np.int64),
            self.pop.limit[idx], self.pop.duration[idx])
        body = http.roundtrip(gubc.http_request(self.address, gubc.COLUMNS_CONTENT_TYPE, frame))
        return np.stack(gen_frames.decode(body, n), axis=1)

    def oracle_rows(self, idx, hits) -> np.ndarray:
        rows = np.empty((len(idx), 4), np.int64)
        for lane, i in enumerate(idx.tolist()):
            r = orc.apply(self.cache, RateLimitRequest(
                name=NAME, unique_key=self.pop.unique_key(i), hits=hits, limit=int(self.pop.limit[i]),
                duration=int(self.pop.duration[i]), algorithm=Algorithm(int(self.pop.algo[i]))), self.now)
            rows[lane] = (int(r.status), r.limit, r.remaining, r.reset_time)
        return rows

    def load(self) -> None:
        """Every key once, one hit, one frame in flight, as the harness loads."""
        for lo in range(0, self.pop.n, self.lanes):
            idx = np.arange(lo, lo + self.lanes) % self.pop.n
            self.now += 7
            self.clock.freeze(self.now)
            got = self.send(self.https[0], idx, 1 if lo + self.lanes <= self.pop.n else 0)
            want = self.oracle_rows(idx, 1 if lo + self.lanes <= self.pop.n else 0)
            assert (got == want).all(), lo

    def round(self, hits: int = 1, stall: bool = False) -> list:
        """Four frames in flight together, one a connection; returns
        [(key indices, the answer)].  With `stall` the plan lock is held
        until both pump threads sit on a take of one frame each and the other
        two frames are queued behind them: what the bound decides is then what
        the next take holds, the same in every run."""
        self.now += 1_500
        self.clock.freeze(self.now)
        frames = [self.pop.draw(self.rng, self.lanes) for _ in range(CONNECTIONS)]
        answers: list = [None] * CONNECTIONS
        errors: list = []

        def client(c):
            try:
                answers[c] = self.send(self.https[c], frames[c], hits)
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=client, args=(c,)) for c in range(CONNECTIONS)]
        store = self.daemon.service.store

        def until(key, value):
            deadline = time.monotonic() + 20.0
            while self.pump.stats()[key] != value:
                assert time.monotonic() < deadline, (key, value, self.pump.stats())
                time.sleep(0.002)

        if stall:
            store._plan_lock.acquire()
        try:
            taken = self.pump.stats()["batches"]
            for c, t in enumerate(threads):
                t.start()
                if stall and c < NativeIngressPump.N_PUMPS:  # a take of its own, held at the plan
                    until("batches", taken + c + 1)
            if stall:
                until("pendingLanes", (CONNECTIONS - NativeIngressPump.N_PUMPS) * self.lanes)
        finally:
            if stall:
                store._plan_lock.release()
        for t in threads:
            t.join(60.0)
        assert not errors, errors
        return list(zip(frames, answers))

    def hold_to_the_oracle(self, sent: list, hits: int = 1) -> None:
        """The answers of frames in flight together equal the sequential
        oracle's for some order of the frames (a take of several frames is
        those frames one after another at one clock reading)."""

        def place(left: list) -> bool:
            if not left:
                return True
            for k, (idx, got) in enumerate(left):
                touched = {self.pop.unique_key(i) for i in set(idx.tolist())}
                saved = {f"{NAME}_{u}": copy.deepcopy(self.cache.items.get(f"{NAME}_{u}"))
                         for u in touched}
                if (self.oracle_rows(idx, hits) == got).all() and place(left[:k] + left[k + 1:]):
                    return True
                for key, item in saved.items():
                    if item is None:
                        self.cache.items.pop(key, None)
                    else:
                        self.cache.items[key] = item
            return False

        assert place(list(sent)), "no order of the frames in flight gives these answers"

    def read_back(self) -> None:
        """Every key, hits=0, one frame in flight: exactly the oracle's."""
        self.now += 11
        self.clock.freeze(self.now)
        for lo in range(0, self.pop.n, self.lanes):
            idx = np.arange(lo, lo + self.lanes) % self.pop.n
            assert (self.send(self.https[0], idx, 0) == self.oracle_rows(idx, 0)).all(), lo


def _slots_given_back(pump) -> bool:
    """A take gives its slot back after its answers have left: soon."""
    deadline = time.monotonic() + 10.0
    while pump._in_flight and time.monotonic() < deadline:
        time.sleep(0.005)
    return pump._in_flight == 0


def _grown(after: dict, before: dict) -> dict:
    return {k: after["mesh"][k] - before["mesh"][k] for k in after["mesh"] if k != "shards"}


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_four_frames_in_flight_never_outgrow_the_warmed_bucket(pop, shards):
    """Load, rounds of four frames in flight (stalled: the takes are the same
    in every run; and free-running), read-back.  Every dispatch pads to the one
    warmed bucket, nothing compiles, every answer is the oracle's for some
    order of the frames in flight, the read-back is the oracle's exactly."""
    served = _Served(pop, shards)
    try:
        lanes = served.lanes
        assert served.daemon.service.store.warm_bucket == BUCKET
        assert served.pump.take_lanes == shards * BUCKET
        status = served.https[0].get_json("/debug/status")
        assert status["ingress"]["takeLanes"] == shards * BUCKET
        served.load()
        before = served.device()
        compiled = before["compileTotal"]

        # Stalled: two takes of a frame each, then whatever the bound lets the
        # next take hold of the two queued frames.
        stalled = 3
        for _ in range(stalled):
            served.hold_to_the_oracle(served.round(stall=True))
        grown = _grown(served.device(), before)
        together = 2 * lanes <= shards * BUCKET  # two queued frames fit one take
        assert together == (shards > 1)
        assert grown["takeFrames"] == CONNECTIONS * stalled
        assert grown["takes"] == grown["dispatches"] == (3 if together else 4) * stalled
        assert grown["lanes"] == CONNECTIONS * lanes * stalled
        assert grown["paddedLanes"] == grown["dispatches"] * shards * BUCKET  # the warmed bucket, each
        assert grown["launches"] + grown["fusedDispatches"] >= grown["dispatches"]
        assert grown["launches"] <= grown["dispatches"]
        assert grown["takes"] <= grown["inFlightSum"] <= NativeIngressPump.DEPTH * grown["takes"]
        assert grown["inFlightSum"] >= stalled + grown["takes"]  # a round's second take found the first

        # Free-running, buckets running dry in the last round.
        free = 4
        for r in range(free):
            hits = 1 if r < free - 1 else 60_000
            served.hold_to_the_oracle(served.round(hits=hits), hits=hits)
        after = served.device()
        grown = _grown(after, before)
        assert grown["takeFrames"] == CONNECTIONS * (stalled + free)
        assert grown["paddedLanes"] == grown["dispatches"] * shards * BUCKET
        if shards == 1:  # a frame fills the bucket: one frame a take, whatever waits
            assert grown["takes"] == grown["takeFrames"]
        assert after["steadyRecompiles"] == 0 and after["compileTotal"] == compiled
        served.read_back()
        # Every take gave its slot back (after its answers had left).
        assert _slots_given_back(served.pump)
        assert served.https[0].get_json("/debug/audit")["violationTotal"] == 0
        served.daemon.service.store.check_consistency()
    finally:
        served.close()


def test_on_the_bound_it_had_before_the_pump_pads_past_the_warmed_bucket(pop, monkeypatch):
    """The control: the same daemon, the same frames, `take_lanes=64_000`.
    The two queued frames are taken together, their dispatch pads to a bucket
    nobody warmed and compiles it inside the clients' requests; the answers
    are still right (it was never wrong, it was slow: 425-470 s a bucket on
    the chip)."""
    monkeypatch.setattr(gateway, "NativeIngressPump", _ParentBoundPump)
    served = _Served(pop, 1)
    try:
        assert served.daemon.service.store.warm_bucket == BUCKET
        assert served.pump.take_lanes == 64_000
        assert served.https[0].get_json("/debug/status")["ingress"]["takeLanes"] == 64_000
        served.load()
        before = served.device()
        served.hold_to_the_oracle(served.round(stall=True))
        after = served.device()
        grown = _grown(after, before)
        assert (grown["takes"], grown["takeFrames"], grown["dispatches"]) == (3, 4, 3)
        assert grown["paddedLanes"] == 2 * BUCKET + pad_size(2 * BUCKET) > 3 * BUCKET
        assert after["steadyRecompiles"] >= 1 and after["compileTotal"] > before["compileTotal"]
        served.read_back()
    finally:
        served.close()


# ---------------------------------------------------------------------
# The counters
# ---------------------------------------------------------------------
def test_the_mesh_block_counts_launches_and_takes():
    tally = saturation.MeshTally()
    assert {"launches", "fusedDispatches", "takes", "takeFrames", "inFlightSum"} <= set(tally.snapshot())
    tally.add_launch(1)
    tally.add_launch(2)
    tally.add_launch(4)
    tally.add_take(1, 1)
    tally.add_take(3, 4)
    snap = tally.snapshot()
    assert (snap["launches"], snap["fusedDispatches"]) == (3, 6)  # a solo launch fuses nothing
    assert (snap["takes"], snap["takeFrames"], snap["inFlightSum"]) == (2, 4, 5)


def test_a_take_that_fails_gives_its_slot_back(pop):
    """`inFlightSum` counts takes admitted and not yet committed: a take whose
    dispatch raises must leave the count, or every later take reads one more."""
    served = _Served(pop, 1)
    try:
        store = served.daemon.service.store
        real = store.apply_columns_async

        def broken(*a, **kw):
            raise RuntimeError("planted")

        store.apply_columns_async = broken
        idx = pop.draw(served.rng, 64)
        with pytest.raises(Exception):
            served.send(served.https[0], idx, 0)
        store.apply_columns_async = real
        assert served.pump._in_flight == 0
        before = served.device()
        served.clock.freeze(T0)
        served.send(served.https[0], idx, 0)
        grown = _grown(served.device(), before)
        assert (grown["takes"], grown["takeFrames"], grown["inFlightSum"]) == (1, 1, 1)
        assert _slots_given_back(served.pump)
    finally:
        served.close()


# ---------------------------------------------------------------------
# The cell's files
# ---------------------------------------------------------------------
def test_the_cells_files_say_what_the_issue_says():
    bench = harness.load_json(REPO, "BENCHMARK.json")
    cell, config, traffic = harness.find_cell(bench, CELL)
    assert cell == bench["workloads"][7] and cell["chips"] == 1  # appended by PR 45, one cell since
    assert (cell["config"], cell["traffic"]) == ("v5e1-1m-gw4", "frames-x4")
    entry = bench["configs"][6]
    assert entry["name"] == "v5e1-1m-gw4" and entry["reduced"] == [] == config["reduced"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    twin = _cell_json("configs", "v5e1-1m.json")
    assert entry["source"] != next(c for c in bench["configs"] if c["name"] == "v5e1-1m")["source"]
    for key in ("env", "population", "control", "guarantees", "chips"):
        assert config[key] == twin[key], key
    assert config["architecture"] is None
    assert config["assumed"][:len(twin["assumed"])] == twin["assumed"]
    assert "four aggregator" in config["assumed"][-1]
    # frames.json with four connections, and nothing else that a run reads.
    frames = _cell_json("traffic", "frames.json")
    assert traffic["kind"] == "frames" and traffic["loop"] == "closed"
    assert (traffic["connections"], traffic["lanes_in_flight"]) == (4, 16_384)
    unchanged = set(frames) - {"name", "why", "connections", "lanes_in_flight", "warm_buckets_why"}
    assert {k: traffic[k] for k in unchanged} == {k: frames[k] for k in unchanged}
    assert traffic["warm_buckets"] == [4096] == [pad_size(traffic["lanes_per_request"])]
    # The new metrics list the cell and its bypass; the cell reports every
    # metric its bypass reports, but the three that are per program LAUNCH.
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL, BYPASS], name
        spec = _cell_json("layer_metrics", name + ".json")
        assert {k: spec[k] for k in ("unit", "better", "layer", "source", "moves")} == {
            k: by_name[name][k] for k in ("unit", "better", "layer", "source", "moves")}, name
        assert os.path.exists(os.path.join(REPO, "chipbench", "readers", spec["reader"] + ".py"))
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW_METRICS[0])  # appended by PR 45; PR 47 appended its one after them
    assert names[at:at + len(NEW_METRICS)] == list(NEW_METRICS) and at == 39
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS or "workloads" not in m:
            continue
        listed = (CELL in m["workloads"], BYPASS in m["workloads"])
        assert listed == ((False, True) if m["name"] in PER_LAUNCH else (listed[1], listed[1])), m["name"]


@pytest.mark.parametrize("seed", [7, 2147483653])
def test_one_seed_gives_v5e1_1ms_population_and_frames_own_frames(seed):
    """The new configuration differs from `v5e1-1m` by its callers alone: the
    same seed gives the same keys, algorithms, limits and ranks, and the pool
    of `frames-x4` is `frames`' pool byte for byte (four connections walk it,
    each in an order of its own)."""
    bench = harness.load_json(REPO, "BENCHMARK.json")
    _, config, traffic = harness.find_cell(bench, CELL)
    _, twin_config, twin_traffic = harness.find_cell(bench, BYPASS)
    pop = Population(config["population"], harness.REHEARSE_KEYS, seed)
    twin = Population(twin_config["population"], harness.REHEARSE_KEYS, seed)
    for field in ("key_bytes", "algo", "limit", "key_of_rank", "behavior", "duration"):
        assert (getattr(pop, field) == getattr(twin, field)).all(), field
    host = "127.0.0.1:1"
    small = dict(traffic, pool_requests=16), dict(twin_traffic, pool_requests=16)
    pools = [gen_frames.build_pool(p, t, np.random.default_rng([seed, 0x706F6F6C]), host)
             for p, t in zip((pop, twin), small)]
    assert [r.payload for r in pools[0]] == [r.payload for r in pools[1]]
    assert all((a.keys == b.keys).all() for a, b in zip(*pools))


# ---------------------------------------------------------------------
# The readers of the six metrics, on counters written out here
# (chipbench/layer_metrics/README.pipeline.md reckons the same by hand)
# ---------------------------------------------------------------------
MESH_BEFORE = {"shards": 1, "dispatches": 245, "lanes": 1_003_520, "paddedLanes": 1_003_520,
               "launches": 245, "fusedDispatches": 0, "takes": 245, "takeFrames": 245,
               "inFlightSum": 245}
# Ramp and window: 6,000 takes of one 4096-lane frame; 5,880 solo launches, 48
# fused pairs and 6 fused fours (120 dispatches in 54 launches); 21,300 takes
# in flight summed over the admissions.
MESH_AFTER = {"shards": 1, "dispatches": 6_245, "lanes": 1_003_520 + 6_000 * 4096,
              "paddedLanes": 1_003_520 + 6_000 * 4096, "launches": 245 + 5_934,
              "fusedDispatches": 120, "takes": 6_245, "takeFrames": 6_245,
              "inFlightSum": 245 + 21_300}
OLD_KEYS = ("shards", "dispatches", "lanes", "paddedLanes")  # a program from before PR 45


def _snap(mesh, gate=None):
    phases = {}
    if gate is not None:
        phases = {"dispatch.gate_wait": {"count": gate[0], "sum_ms": gate[1]},
                  "dispatch.launch": {"count": gate[2], "sum_ms": 0.0}}
    return {"device": {} if mesh is None else {"mesh": mesh}, "latency": {"phases": phases}}


def _spec(name):
    return _cell_json("layer_metrics", name + ".json")


def _ctx(before, after, **more):
    return {"before": before, "after": after, **more}


def test_the_counter_readers_give_the_values_reckoned_by_hand():
    ctx = _ctx(_snap(MESH_BEFORE, (245, 1.0, 245)), _snap(MESH_AFTER, (6_245, 751.0, 6_179)))
    spec = _spec("pump.frames_per_take")
    assert spec["reader"] == "mesh_tally" and mesh_tally.read(ctx, spec["params"]) == 1.0
    spec = _spec("pipeline.takes_in_flight")
    assert spec["reader"] == "mesh_tally"
    assert mesh_tally.read(ctx, spec["params"]) == pytest.approx(21_300 / 6_000) == 3.55
    spec = _spec("launch.fused_share")
    assert spec["reader"] == "mesh_counted"
    assert mesh_counted.read(ctx, spec["params"]) == pytest.approx(100 * 120 / 6_000) == 2.0
    spec = _spec("launch.gate_wait_ms")
    assert spec["reader"] == "phase_ms_seen"
    assert phase_ms_seen.read(ctx, spec["params"]) == pytest.approx(750.0 / 5_934)


def test_the_counter_readers_read_nothing_from_a_program_before_the_counters():
    """The parent serves the `mesh` block without the five counters, and the
    PR 27 parent no block at all: None, not 0, and nothing raises."""
    old = [{k: m[k] for k in OLD_KEYS} for m in (MESH_BEFORE, MESH_AFTER)]
    for before, after in ((_snap(old[0]), _snap(old[1])), (_snap(None), _snap(None))):
        ctx = _ctx(before, after)
        for name, reader in (("pump.frames_per_take", mesh_tally),
                             ("pipeline.takes_in_flight", mesh_tally),
                             ("launch.fused_share", mesh_counted)):
            assert reader.read(ctx, _spec(name)["params"]) is None, name
        assert phase_ms_seen.read(ctx, _spec("launch.gate_wait_ms")["params"]) is None
    # `mesh_tally` alone would have called the missing numerator 0.
    assert mesh_tally.read(_ctx(_snap(old[0]), _snap(old[1])), _spec("launch.fused_share")["params"]) == 0.0
    # Counters that stood still (no take on the native lane): nothing.
    still = _ctx(_snap(MESH_AFTER), _snap(MESH_AFTER))
    assert mesh_counted.read(still, _spec("launch.fused_share")["params"]) is None


def _kernel_ctx(launches, platform="tpu"):
    """A context whose trace holds `launches` (the `fused` of each
    `dispatch.launch` event) without a profile file: the readers' one read of
    the file is replaced by its result."""
    programs = {"jit__rounds_packed_mesh": [5_880.0, 5_880 * 1_080e-6],
                "jit_run": [54.0, 48 * 2_100e-6 + 6 * 4_150e-6],
                "jit__sync_body": [3.0, 0.5]}
    ctx = _ctx(_snap(MESH_BEFORE), _snap(MESH_AFTER), trace={"program": programs},
               device={"platform": platform, "kind": "TPU v5 lite"}, unique_keys_per_request=2_900.0)
    ctx["_dispatches_per_launch"] = sum(launches) / len(launches) if launches else None
    return ctx


def test_the_kernel_readers_divide_by_dispatches_and_not_by_launches():
    from chipbench import roofline
    from chipbench.readers import kernel_us_per_dispatch

    # The host's events stop a little before the device's line does: the trace
    # holds 5,934 programs and the events of 5,835 of them (98 solo launches
    # and a pair missing), and the ratio is held to 5,900 / 5,835.
    launches = [1] * 5_782 + [2] * 47 + [4] * 6
    ctx = _kernel_ctx(launches)
    params = _spec("kernel.us_per_take")["params"]
    assert _spec("kernel.us_per_take")["reader"] == "kernel_us_per_take"
    seconds = 5_880 * 1_080e-6 + 48 * 2_100e-6 + 6 * 4_150e-6  # the sync program is left out
    dispatches = 5_934 * 5_900 / 5_835
    assert kernel_us_per_take.traced(ctx, params) == pytest.approx((seconds, dispatches))
    assert kernel_us_per_take.read(ctx, params) == pytest.approx(1e6 * seconds / dispatches)
    # The accepted reader divides the same seconds by the 5,934 launches, and
    # where every launch is solo the two agree exactly.
    assert kernel_us_per_dispatch.read(ctx, params) == pytest.approx(1e6 * seconds / 5_934)
    solo = _kernel_ctx([1] * 5_800)
    assert kernel_us_per_take.read(solo, params) == kernel_us_per_dispatch.read(solo, params)
    spec = _spec("kernel.take_roofline")
    assert spec["reader"] == "kernel_take_roofline" and spec["unit"] == "%"
    least = (4.0 * 7 * 4096 + 96.0 * 2_900) / 819e9
    assert roofline.least_seconds("TPU v5 lite", roofline.dict_wire_dispatch_bytes(4096, 2_900)) == \
        pytest.approx(least)
    share = kernel_take_roofline.read(ctx, spec["params"])
    assert share == pytest.approx(100 * least * dispatches / seconds) and 0 < share < 100
    # Off a TPU there is no roofline to hold it to; with no launch event, nothing.
    assert kernel_take_roofline.read(_kernel_ctx(launches, "cpu"), spec["params"]) is None
    for reader in (kernel_us_per_take, kernel_take_roofline):
        assert reader.read(_kernel_ctx([]), params) is None


def test_the_launch_events_of_a_traced_run_say_how_many_dispatches_each_carried(tmp_path):
    """`dispatches_per_launch` on a real profile: a store traced while it runs
    three solo dispatches and one fused group of two."""
    store = one_device_store(4096)

    def dispatch(tag, n=64):
        return store.apply_columns_async(
            [f"{tag}:{i}" for i in range(n)], np.zeros(n, np.int32), np.zeros(n, np.int32),
            np.ones(n, np.int64), np.full(n, 50, np.int64), np.full(n, 60_000, np.int64), T0)

    dispatch("warm").result()
    stage = store._stage_columns
    stalled = threading.Event()

    def slow_stage(prep):
        if not stalled.is_set():
            stalled.set()
            time.sleep(0.4)  # the second ticket reaches the gate meanwhile
        return stage(prep)

    before = saturation.mesh_tally.snapshot()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for tag in ("a", "b", "c"):
            dispatch(tag).result()
        store._stage_columns = slow_stage
        threads = [threading.Thread(target=lambda t=t: dispatch(t).result()) for t in ("d", "e")]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        jax.profiler.stop_trace()
    after = saturation.mesh_tally.snapshot()
    assert after["dispatches"] - before["dispatches"] == 5
    assert after["launches"] - before["launches"] == 4
    assert after["fusedDispatches"] - before["fusedDispatches"] == 2
    import glob

    (xplane,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    assert kernel_us_per_take.dispatches_per_launch({"trace": {"xplane": xplane}}) == 5 / 4
