"""Millisecond express lane (PR 14): shallow-queue bypass equivalence,
small batches' sequential semantics on the device path,
audit-ledger balance with express and batched dispatches interleaving,
the chaos DELAY-on-batched-path isolation, the GUBER_EXPRESS knobs, and
NO_BATCHING on the native hot path."""

from __future__ import annotations

import dataclasses
import random
import threading
import time

import numpy as np
import pytest

from gubernator_tpu import audit as audit_mod
from gubernator_tpu import faults, native, saturation
from gubernator_tpu.client import V1Client
from gubernator_tpu.cluster import Cluster, fast_test_behaviors
from gubernator_tpu.config import BehaviorConfig, setup_daemon_config
from gubernator_tpu.faults import FaultPlan
from gubernator_tpu.models.shard import host_readback
from gubernator_tpu.parallel.mesh import MeshBucketStore
from gubernator_tpu.service import IngressColumns, ServiceConfig, V1Service
from gubernator_tpu.types import (
    Behavior,
    GetRateLimitsRequest,
    PeerInfo,
    RateLimitRequest,
)
from gubernator_tpu.utils.batch_window import BatchWindow

from . import oracle
from .conftest import one_device_store


# ---------------------------------------------------------------------
# Window cap: GUBER_LATENCY_TARGET_MS binds
# ---------------------------------------------------------------------

def test_window_cap_clamps_effective_wait():
    w = BatchWindow(lambda b: None, wait_s=0.5, limit=1000, lazy=True,
                    cap_s=0.005)
    assert w.effective_wait_s() == 0.005
    # Adaptive sizing also yields to the cap (occupancy -> latency).
    w2 = BatchWindow(lambda b: None, wait_s=0.5, limit=1000, lazy=True,
                     adaptive=True, cap_s=0.002)
    w2._rate = 10.0  # adaptive would pick limit/rate = 100s
    assert w2.effective_wait_s() == 0.002
    # No cap = the pre-express window, untouched.
    w3 = BatchWindow(lambda b: None, wait_s=0.5, limit=1000, lazy=True)
    assert w3.effective_wait_s() == 0.5


def test_latency_target_caps_batcher_windows():
    # A deliberately wide window (500 ms) with a 10 ms target: the cap
    # (target/2 — half the budget coalesces, half pays dispatch) must
    # bind on both batchers.
    beh = BehaviorConfig(latency_target_ms=10.0, batch_wait_s=0.5)
    svc = _service(beh)
    try:
        assert svc.columnar_batcher._window.effective_wait_s() == 0.005
        assert svc.local_batcher._window.effective_wait_s() == 0.005
    finally:
        svc.close()
    # Knob off (express=0): occupancy mode keeps the window.
    svc = _service(BehaviorConfig(
        latency_target_ms=10.0, batch_wait_s=0.5, express=False
    ))
    try:
        assert svc.columnar_batcher._window.effective_wait_s() == 0.5
    finally:
        svc.close()


# ---------------------------------------------------------------------
# Bypass-vs-windowed byte identity (2 seeds, one device + 8 shards)
# ---------------------------------------------------------------------

class _FixedClock:
    """Deterministic clock: byte-identity across two services needs
    identical now_ms at every dispatch (reset_time derives from it)."""

    def __init__(self, t0: int = 1_700_000_000_000):
        self.t = t0

    def now_ms(self) -> int:
        return self.t


def _service(behaviors: BehaviorConfig, store=None, clock=None) -> V1Service:
    svc = V1Service(ServiceConfig(
        store=store, cache_size=2048, global_cache_size=256,
        behaviors=behaviors, advertise_address="127.0.0.1:9991",
        **({"clock": clock} if clock is not None else {}),
    ))
    svc.set_peers([PeerInfo(grpc_address="127.0.0.1:9991", is_owner=True)])
    return svc


def _drive_stream(svc: V1Service, seed: int):
    """One seeded request stream — singles and small column batches,
    token + leaky, occasional RESET_REMAINING and duplicate keys —
    returning every response triple in order."""
    rng = random.Random(seed)
    out = []
    for step in range(60):
        if rng.random() < 0.5:
            r = RateLimitRequest(
                name="xt", unique_key=f"k{rng.randrange(8)}", hits=1,
                limit=20, duration=60_000,
                algorithm=rng.choice([0, 1]),
            )
            resp = svc.get_rate_limits(
                GetRateLimitsRequest(requests=[r])
            ).responses[0]
            out.append((resp.status, resp.remaining, resp.reset_time))
        else:
            n = rng.choice([2, 3, 4, 8])
            ks = [f"k{rng.randrange(8)}" for _ in range(n)]
            cols = IngressColumns(
                names=["xt"] * n, unique_keys=ks,
                algorithm=np.array(
                    [rng.choice([0, 1]) for _ in range(n)], np.int32
                ),
                behavior=np.array(
                    [rng.choice([0, 0, 0, 8]) for _ in range(n)], np.int32
                ),
                hits=np.ones(n, np.int64),
                limit=np.full(n, 20, np.int64),
                duration=np.full(n, 60_000, np.int64),
            )
            rc = svc.get_rate_limits_columns(cols)
            for i in range(n):
                resp = rc.response_at(i)
                out.append((resp.status, resp.remaining, resp.reset_time))
    return out


class _HeldTicket:
    """A dispatch held between plan and launch: its stage step (after
    the ticket is taken, before the launch gate) blocks until released,
    so every younger ticket waits at the gate behind it."""

    def __init__(self, store, now_ms: int = 1_700_000_000_000):
        # 100 lanes a shard: another pad bucket than a few calls', so
        # the launch cannot fuse the two into one program.
        lanes = 100 * store.n_shards
        self.release = threading.Event()
        self.handle = None
        staged = threading.Event()
        real = store._stage_columns

        def stage(prep):
            store._stage_columns = real  # this batch alone is held
            staged.set()
            assert self.release.wait(60)
            return real(prep)

        store._stage_columns = stage
        self.reqs = [
            RateLimitRequest(name="xt", unique_key=f"held{i}", hits=1,
                             limit=20, duration=60_000)
            for i in range(lanes)
        ]

        def run():
            self.handle = store.apply_columns_async(
                [r.hash_key() for r in self.reqs],
                np.zeros(lanes, np.int32), np.zeros(lanes, np.int32),
                np.ones(lanes, np.int64), np.full(lanes, 20, np.int64),
                np.full(lanes, 60_000, np.int64), now_ms,
            )

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()
        assert staged.wait(60)

    def launch(self) -> dict:
        self.release.set()
        self._thread.join(60)
        return self.handle.result()


def _call_reqs(i: int, algo: int = 0):
    """The two checks of one classic call; key 0 is shared by every
    call, so the calls' order shows in the answers."""
    return [
        RateLimitRequest(name="xt", unique_key=k, hits=1, limit=6,
                         duration=60_000, algorithm=algo)
        for k in ("shared", f"own{i}")
    ]


def _submit_reqs(svc: V1Service, reqs):
    """One submission to the columnar batcher, as _dispatch_fast makes it."""
    n = len(reqs)
    return svc.columnar_batcher.submit(
        [r.hash_key() for r in reqs],
        np.array([r.algorithm for r in reqs], np.int32),
        np.zeros(n, np.int32),
        np.array([r.hits for r in reqs], np.int64),
        np.array([r.limit for r in reqs], np.int64),
        np.array([r.duration for r in reqs], np.int64),
        None, None,
    )


def _rows(out, lo: int = 0, hi=None):
    """(status, remaining, reset_time) of a columnar answer's lanes."""
    hi = len(out["status"]) if hi is None else hi
    return [
        (int(out["status"][i]), int(out["remaining"][i]),
         int(out["reset_time"][i]))
        for i in range(lo, hi)
    ]


def _triples(fut):
    handle, lo, hi = fut.result(timeout=60)
    return _rows(handle.result(), lo, hi)


def _wait_for(cond, what: str, timeout_s: float = 30.0) -> None:
    deadline = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.002)


def _held_burst(svc: V1Service, k: int = 5):
    """k two-check calls submitted while an older ticket is held
    between plan and launch; the futures, then the held batch's
    answers once released."""
    store = svc.store
    held = _HeldTicket(store)
    futs = [_submit_reqs(svc, _call_reqs(i, algo=i % 2)) for i in range(k)]
    # The flusher took them and waits at the launch gate (ticket 1).
    _wait_for(lambda: store._next_ticket >= 2, "the burst was never planned")
    held.launch()
    return futs


@pytest.mark.parametrize("store_kind", ["one-device", "mesh"])
@pytest.mark.parametrize("seed", [21, 22])
def test_bypass_vs_windowed_byte_identical(store_kind, seed):
    """The express bypass changes WHEN a dispatch launches, never what
    it computes: the same seeded request stream through an express-on
    and an express-off service answers identically, and so does a
    burst that arrives while a dispatch is under way (the on-service
    sends it through the window as ONE dispatch)."""
    def mk(express: bool):
        store = (
            one_device_store(512) if store_kind == "one-device"
            else MeshBucketStore(capacity_per_shard=128)
        )
        return _service(BehaviorConfig(express=express), store=store,
                        clock=_FixedClock())

    def bypassed():
        return saturation.express_snapshot()["lanes"]["bypass"]

    saturation.reset()
    on, off = mk(True), mk(False)
    try:
        got_on = _drive_stream(on, seed)
        # The on-service actually exercised the lane while the
        # off-service stayed fully classic.
        lanes_on = bypassed()
        assert lanes_on > 0
        got_off = _drive_stream(off, seed)
        assert bypassed() == lanes_on
        assert got_on == got_off
        burst_on = [_triples(f) for f in _held_burst(on)]
        burst_off = [_triples(f) for f in _held_burst(off)]
        assert burst_on == burst_off
        assert _drive_stream(on, seed + 100) == _drive_stream(off, seed + 100)
    finally:
        on.close()
        off.close()
        saturation.reset()


# ---------------------------------------------------------------------
# The admission rule: bypass only when no dispatch is under way
# ---------------------------------------------------------------------

def _oracle_triples(cache, reqs, now):
    return [
        (int(r.status), int(r.remaining), int(r.reset_time))
        for r in (oracle.apply(cache, q, now) for q in reqs)
    ]


@pytest.mark.parametrize(
    "case", ["idle", "held", "launched-unresolved", "queued", "wide"]
)
def test_admission_rule(make_store, case):
    """A submission bypasses the window only when no dispatch is under
    way and nothing is queued; answers and final rows are the
    sequential oracle's for the submission order either way."""
    saturation.reset()
    clock = _FixedClock()
    now = clock.now_ms()
    store = make_store(512)
    svc = _service(
        BehaviorConfig(batch_wait_s=0.25), store=store, clock=clock,
    )
    cache = oracle.OracleCache()
    asked = []  # every request, in the order the store must apply them

    def declined():
        return saturation.express_snapshot()["declined"]["submissions"]

    def bypassed():
        return saturation.express_snapshot()["dispatches"]["bypass"]

    try:
        assert not store.dispatch_under_way()
        if case == "idle":
            d0 = store.device_dispatches
            for i in range(3):  # a lone sequential client: every call
                reqs = _call_reqs(i)
                fut = _submit_reqs(svc, reqs)
                assert fut.done()  # dispatched on the caller's thread
                assert _triples(fut) == _oracle_triples(cache, reqs, now)
                asked += reqs
            assert bypassed() == 3 and sum(declined().values()) == 0
            assert store.device_dispatches == d0 + 3
        elif case == "held":
            k = 5
            held = _HeldTicket(store, now_ms=now)
            assert store.dispatch_under_way()
            d0 = store.device_dispatches
            calls = [_call_reqs(i, algo=i % 2) for i in range(k)]
            futs = [_submit_reqs(svc, c) for c in calls]
            assert not any(f.done() for f in futs)
            _wait_for(lambda: store._next_ticket >= 2, "never planned")
            held_out = held.launch()
            got = [f.result(timeout=60) for f in futs]
            # ONE dispatch of 2k lanes: one handle, the calls its slices.
            assert len({id(h) for h, _, _ in got}) == 1
            assert [(lo, hi) for _, lo, hi in got] == [
                (2 * i, 2 * i + 2) for i in range(k)
            ]
            assert store.device_dispatches == d0 + 2  # the held one + 1
            assert declined() == {"wide": 0, "launching": k, "queued": 0}
            assert bypassed() == 0
            want_held = _oracle_triples(cache, held.reqs, now)
            assert [
                (int(held_out["status"][i]), int(held_out["remaining"][i]),
                 int(held_out["reset_time"][i]))
                for i in range(len(held.reqs))
            ] == want_held
            for f, c in zip(futs, calls):
                assert _triples(f) == _oracle_triples(cache, c, now)
            asked += held.reqs + [r for c in calls for r in c]
        elif case == "launched-unresolved":
            # Three dispatches launched and nobody reading them back:
            # the host's dispatch section is free, so a call bypasses.
            handles, first = [], []
            for i in range(3):
                reqs = _call_reqs(100 + i)
                first.append(reqs)
                handles.append(store.apply_columns_async(
                    [r.hash_key() for r in reqs], np.zeros(2, np.int32),
                    np.zeros(2, np.int32), np.ones(2, np.int64),
                    np.full(2, 6, np.int64), np.full(2, 60_000, np.int64),
                    now,
                ))
            assert store.pipeline_depth() == 3
            assert not store.dispatch_under_way()
            reqs = _call_reqs(0)
            fut = _submit_reqs(svc, reqs)
            assert fut.done() and bypassed() == 1
            assert sum(declined().values()) == 0
            for q in first:
                _oracle_triples(cache, q, now)
            assert _triples(fut) == _oracle_triples(cache, reqs, now)
            asked += [r for q in first for r in q] + reqs
        else:
            # A wide submission takes the window; a call that arrives
            # while it waits there joins it and rides its dispatch.
            d0 = store.device_dispatches
            wide = [
                RateLimitRequest(name="xt", unique_key=f"w{i % 3}", hits=1,
                                 limit=6, duration=60_000)
                for i in range(8)
            ]
            f_wide = _submit_reqs(svc, wide)
            assert not f_wide.done()
            assert declined() == {"wide": 1, "launching": 0, "queued": 0}
            futs, calls = [f_wide], [wide]
            if case == "queued":
                calls.append(_call_reqs(0))
                futs.append(_submit_reqs(svc, calls[-1]))
                assert declined()["queued"] == 1
            got = [f.result(timeout=60) for f in futs]
            assert len({id(h) for h, _, _ in got}) == 1
            assert store.device_dispatches == d0 + 1 and bypassed() == 0
            for f, c in zip(futs, calls):
                assert _triples(f) == _oracle_triples(cache, c, now)
                asked += c
        # Final rows: a zero-hit read of every key against the oracle's.
        first = {}
        for r in asked:
            first.setdefault((r.unique_key, r.algorithm), r)
        peeks = [dataclasses.replace(r, hits=0) for r in first.values()]
        rows = svc.get_rate_limits(
            GetRateLimitsRequest(requests=peeks)
        ).responses
        assert [
            (int(r.status), int(r.remaining), int(r.reset_time)) for r in rows
        ] == _oracle_triples(cache, peeks, now)
        assert svc.auditor.check_now() == []
    finally:
        svc.close()
        saturation.reset()


def test_steady_concurrency_coalesces_and_accounts_exactly():
    """32 closed-loop callers of 2-check calls on a one-device store:
    every arrival but the first finds a dispatch under way, so calls
    ride dispatches of many lanes; every hit is accounted exactly.

    The store's stage step is given the chip's cost (a flush of
    `v5e1-1m.singles` spends 3.0 ms in dispatch.stage and 2.5 ms in
    dispatch.launch, PERF.md §5; the CPU's 0.6 ms would leave the
    callers' own Python, not the dispatch section, setting the pace):
    5 ms asleep, outside the interpreter, as an upload is."""
    threads, calls_each, keys, limit = 32, 25, 24, 40
    saturation.reset()
    store = one_device_store(512)
    stage = store._stage_columns

    def stage_at_the_chips_cost(prep):
        time.sleep(0.005)
        return stage(prep)

    store._stage_columns = stage_at_the_chips_cost
    svc = _service(BehaviorConfig(), store=store, clock=_FixedClock())
    granted = [[0] * keys for _ in range(threads)]
    errors = []
    deadline = time.monotonic() + 120.0  # this test's own time limit

    def caller(t: int):
        rng = random.Random(1000 + t)
        try:
            for _ in range(calls_each):
                assert time.monotonic() < deadline, "time limit"
                ks = rng.sample(range(keys), 2)
                cols = IngressColumns(
                    names=["st"] * 2, unique_keys=[f"k{k}" for k in ks],
                    algorithm=np.zeros(2, np.int32),
                    behavior=np.zeros(2, np.int32),
                    hits=np.ones(2, np.int64),
                    limit=np.full(2, limit, np.int64),
                    duration=np.full(2, 3_600_000, np.int64),
                )
                rc = svc.get_rate_limits_columns(cols)
                for i, k in enumerate(ks):
                    resp = rc.response_at(i)
                    assert resp.error == "" and resp.limit == limit
                    if resp.status == 0:
                        granted[t][k] += 1
                        assert 0 <= resp.remaining < limit
                    else:
                        assert resp.remaining == 0
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    try:
        d0 = store.device_dispatches
        pool = [threading.Thread(target=caller, args=(t,), daemon=True)
                for t in range(threads)]
        for th in pool:
            th.start()
        for th in pool:
            th.join(max(deadline - time.monotonic(), 0.1))
        assert not any(th.is_alive() for th in pool), "time limit"
        assert errors == []
        calls = threads * calls_each
        # The oracle's accounting of a token bucket with no refill in
        # the run (frozen clock): a key grants min(limit, asked) hits.
        asked = [0] * keys
        for t in range(threads):
            rng = random.Random(1000 + t)
            for _ in range(calls_each):
                for k in rng.sample(range(keys), 2):
                    asked[k] += 1
        total = [sum(g[k] for g in granted) for k in range(keys)]
        assert total == [min(limit, a) for a in asked]
        assert any(a > limit for a in asked)  # OVER_LIMIT was exercised
        rows = svc.get_rate_limits(GetRateLimitsRequest(requests=[
            RateLimitRequest(name="st", unique_key=f"k{k}", hits=0,
                             limit=limit, duration=3_600_000)
            for k in range(keys)
        ])).responses
        assert [r.remaining for r in rows] == [limit - g for g in total]
        assert svc.auditor.check_now() == []
        # At least 8 lanes a dispatch: a quarter as many dispatches as
        # calls.  Measured: 53-55 dispatches of 800 calls on two cores,
        # 69-70 on six; the old rule's 400-430 (most of them bypasses,
        # one for every call or two; 195-198 once the tenant ledger's
        # fold no longer throttles the callers).
        dispatches = store.device_dispatches - d0
        assert dispatches <= calls // 4, (dispatches, calls)
        snap = saturation.express_snapshot()
        assert snap["declined"]["submissions"]["launching"] > 0
    finally:
        svc.close()
        saturation.reset()


# ---------------------------------------------------------------------
# Small batches on the device path: sequential semantics, the oracle's
# ---------------------------------------------------------------------

def _small_batches(seed: int, steps: int = 150):
    """Randomized batches of 1-4 lanes: expiry edges (clock jumps past
    short durations), duplicate-heavy batches, token + leaky,
    RESET_REMAINING.  Yields (keys, algo, behavior, hits, limit,
    duration, now)."""
    rng = random.Random(seed)
    now = 1_000_000
    for step in range(steps):
        n = rng.choice([1, 1, 2, 3, 4])
        ks = [f"k{rng.randrange(6)}" for _ in range(n)]
        if rng.random() < 0.35:
            ks = [ks[0]] * n  # duplicate group
        algo = np.array([rng.choice([0, 1]) for _ in range(n)], np.int32)
        beh = np.array([rng.choice([0, 0, 0, 8]) for _ in range(n)], np.int32)
        hits = np.array([rng.choice([0, 1, 1, 2, 5, 11]) for _ in range(n)],
                        np.int64)
        limit = np.full(n, rng.choice([1, 3, 10, 30]), np.int64)
        dur = np.full(n, rng.choice([7, 50, 100, 1000]), np.int64)
        now += rng.choice([0, 0, 1, 3, 60, 120, 1500])  # expiry edges
        yield ks, algo, beh, hits, limit, dur, now


def _drive_store(store, seed: int, steps: int = 150, width: int = 4):
    """The stream's answers, a tuple a batch, from `store` fed each
    batch in calls of at most `width` lanes: 4 is the batch whole, 1 is
    one lane a call in the batch's order."""
    out = []
    for ks, algo, beh, hits, limit, dur, now in _small_batches(seed, steps):
        got = []
        for lo in range(0, len(ks), width):
            cut = slice(lo, lo + width)
            got += _rows(store.apply_columns(
                ks[cut], algo[cut], beh[cut], hits[cut], limit[cut], dur[cut],
                now,
            ))
        out.append(tuple(got))
    return out


def _drive_oracle(seed: int, steps: int = 150):
    """The same stream through tests/oracle.py, a lane at a time."""
    cache = oracle.OracleCache()
    return [
        tuple(_oracle_triples(cache, [
            RateLimitRequest(
                name="", unique_key=ks[i], algorithm=int(algo[i]),
                behavior=int(beh[i]), hits=int(hits[i]), limit=int(limit[i]),
                duration=int(dur[i]),
            )
            for i in range(len(ks))
        ], now))
        for ks, algo, beh, hits, limit, dur, now in _small_batches(seed, steps)
    ]


def _batched_and_lane_at_a_time(mk, seed: int, steps: int = 150):
    """A store fed the stream's batches and a second one fed the same
    lanes one a call; every small batch was a device program."""
    a, b = mk(), mk()
    ra = _drive_store(a, seed, steps)
    rb = _drive_store(b, seed, steps, width=1)
    assert a.device_dispatches == steps
    assert b.device_dispatches == sum(len(t) for t in rb)
    return ra, rb


@pytest.mark.parametrize("seed", [31, 32])
def test_small_batches_equal_one_lane_at_a_time_one_device(seed):
    """A batch of 1-4 lanes answers as its lanes would one after the
    other (what the kernel's rounds reproduce), and as the reference:
    six keys in 64 slots, none evicted."""
    ra, rb = _batched_and_lane_at_a_time(lambda: one_device_store(64), seed)
    assert ra == rb
    assert ra == _drive_oracle(seed)


@pytest.mark.parametrize("seed", [31, 32])
def test_small_batches_equal_one_lane_at_a_time_mesh(seed):
    ra, rb = _batched_and_lane_at_a_time(
        lambda: MeshBucketStore(capacity_per_shard=32), seed
    )
    assert ra == rb
    assert ra == _drive_oracle(seed)


def test_small_batches_under_eviction_pressure_equal_one_lane_at_a_time():
    """A tiny table forces mid-batch slot takeovers (a different key's
    create evicting into a just-written slot), which must not be
    confused with a duplicate group."""
    ra, rb = _batched_and_lane_at_a_time(
        lambda: one_device_store(4), 41, steps=120
    )
    assert ra == rb


def test_a_calendar_lane_in_a_one_lane_batch():
    """DURATION_IS_GREGORIAN lanes carry host-precomputed expiry: a
    one-lane batch selects it on the device path."""
    now = 1_700_000_000_000
    ge = np.array([now + 3_600_000], np.int64)
    gd = np.array([3_600_000], np.int64)
    store = one_device_store(16)
    out = []
    for i in range(4):
        out += _rows(store.apply_columns(
            ["gk"], np.zeros(1, np.int32),
            np.full(1, int(Behavior.DURATION_IS_GREGORIAN), np.int32),
            np.ones(1, np.int64), np.full(1, 10, np.int64),
            np.full(1, 4, np.int64),  # calendar enum, not ms
            now + i, greg_expire=ge, greg_duration=gd,
        ))
    assert store.device_dispatches == 4
    assert out == [(0, 10 - hits, now + 3_600_000) for hits in (1, 2, 3, 4)]


def test_a_one_lane_request_is_one_device_dispatch(make_store):
    """Default behaviours: the bypass answers a one-lane request with
    one device program, here as on a TPU."""
    saturation.reset()
    svc = _service(BehaviorConfig(), store=make_store(64))
    try:
        d0 = svc.store.device_dispatches
        resp = svc.get_rate_limits(GetRateLimitsRequest(requests=[
            RateLimitRequest(name="one", unique_key="k", hits=1, limit=10,
                             duration=60_000)
        ])).responses[0]
        assert (resp.error, resp.status, resp.remaining) == ("", 0, 9)
        assert svc.store.device_dispatches == d0 + 1
        snap = saturation.express_snapshot()
        assert snap["dispatches"]["bypass"] == snap["lanes"]["bypass"] == 1
    finally:
        svc.close()
        saturation.reset()


# ---------------------------------------------------------------------
# Audit ledger balanced with express interleaving batched dispatches
# ---------------------------------------------------------------------

def test_audit_balanced_with_express_interleaving():
    saturation.reset()
    svc = _service(BehaviorConfig())
    try:
        rng = random.Random(7)
        for step in range(40):
            n = rng.choice([1, 1, 2, 24])  # express singles + batched
            ks = [f"ak{rng.randrange(12)}" for _ in range(n)]
            cols = IngressColumns(
                names=["at"] * n, unique_keys=ks,
                algorithm=np.zeros(n, np.int32),
                behavior=np.zeros(n, np.int32),
                hits=np.ones(n, np.int64),
                limit=np.full(n, 1000, np.int64),
                duration=np.full(n, 60_000, np.int64),
            )
            svc.get_rate_limits_columns(cols)
        # The lane really ran, and on the device.
        assert saturation.express_snapshot()["lanes"]["bypass"] > 0
        assert svc.store.device_dispatches > 0
        violations = svc.auditor.check_now()
        assert violations == [], violations
    finally:
        svc.close()
        saturation.reset()


# ---------------------------------------------------------------------
# Chaos: DELAY on the batched (forwarded) path must not stall express
# ---------------------------------------------------------------------

@pytest.mark.chaos
def test_chaos_delay_on_batched_path_does_not_stall_express():
    """A FaultPlan DELAY on every peer forward (the batched remote leg)
    slows remote-owned keys to ~delay_s; locally-owned express singles
    riding the bypass must keep answering orders of magnitude faster —
    the lanes are independent by construction."""
    cluster = Cluster().start(2)
    try:
        d0 = cluster.daemon_at(0)
        svc = d0.service
        # One locally-owned and one remotely-owned key, seen from d0.
        # Index-FIRST keys: FNV-1 clusters suffix-varying keys into one
        # vnode gap (the documented test_hash_ring finding), which can
        # land a short probe range on a single owner (64 keys did, in
        # one tier-1 run of a tree that touched nothing here).
        local_key = remote_key = None
        for i in range(4096):
            k = f"{i}ck"
            peer = svc.get_peer(f"ct_{k}")
            if peer.info.is_owner and local_key is None:
                local_key = k
            if not peer.info.is_owner and remote_key is None:
                remote_key = k
            if local_key and remote_key:
                break
        assert local_key and remote_key

        plan = FaultPlan(seed=3)
        plan.delay("*", 1.5, op="GetPeerRateLimits")
        with faults.injected(plan):
            def one(k):
                return svc.get_rate_limits(GetRateLimitsRequest(requests=[
                    RateLimitRequest(name="ct", unique_key=k, hits=1,
                                     limit=100, duration=60_000)
                ])).responses[0]

            t0 = time.monotonic()
            slow_done = threading.Event()
            threading.Thread(
                target=lambda: (one(remote_key), slow_done.set()),
                daemon=True,
            ).start()
            fast = [one(local_key) for _ in range(5)]
            fast_elapsed = time.monotonic() - t0
            assert all(r.error == "" for r in fast)
            # 5 express rounds complete well inside ONE delayed
            # forward (the bound is HALF the injected delay: isolation
            # is the claim, with headroom for 2-core suite weather —
            # express rounds are ~2-30 ms each).
            assert fast_elapsed < 0.75, fast_elapsed
            assert not slow_done.is_set()  # the delayed leg still parked
            assert slow_done.wait(timeout=10.0)
    finally:
        cluster.stop()


# ---------------------------------------------------------------------
# Config plumbing + the GUBER_EXPRESS=0 interop switch
# ---------------------------------------------------------------------

def test_express_knobs_env_plumbing():
    conf = setup_daemon_config(env={
        "GUBER_EXPRESS": "0",
        "GUBER_EXPRESS_MAX_LANES": "8",
    })
    b = conf.behaviors
    assert b.express is False
    assert b.express_max_lanes == 8
    # Defaults: the lane ships ON.
    d = setup_daemon_config(env={})
    assert d.behaviors.express is True
    assert d.behaviors.express_max_lanes == 4


@pytest.mark.parametrize("env", [
    {"GUBER_EXPRESS_MAX_LANES": "0"},
    {"GUBER_EXPRESS_MAX_LANES": "65"},
])
def test_express_knobs_loud_validation(env):
    with pytest.raises(ValueError):
        setup_daemon_config(env=env)


def test_express_off_is_pre_express_behavior():
    """GUBER_EXPRESS=0: no bypass, windows uncapped —
    every submission waits out the coalescing window exactly as before
    the lane existed."""
    saturation.reset()
    svc = _service(BehaviorConfig(express=False, latency_target_ms=5.0))
    try:
        assert svc.columnar_batcher._express.enabled is False
        assert svc.columnar_batcher._window.cap_s is None
        for i in range(4):
            svc.get_rate_limits(GetRateLimitsRequest(requests=[
                RateLimitRequest(name="off", unique_key=f"k{i}", hits=1,
                                 limit=10, duration=60_000)
            ]))
        snap = saturation.express_snapshot()
        assert snap["lanes"]["bypass"] == 0
        assert snap["lanes"]["windowed"] > 0
        assert sorted(snap["lanes"]) == ["bypass", "native", "windowed"]
    finally:
        svc.close()
        saturation.reset()


# ---------------------------------------------------------------------
# Native hot path: NO_BATCHING rides the express queue, not Python
# ---------------------------------------------------------------------

@pytest.mark.skipif(not native.available(),
                    reason="native runtime unavailable")
@pytest.mark.parametrize("express", [True, False])
def test_native_no_batching_express_vs_fallback(express):
    """With the lane on, a NO_BATCHING kind-5 frame is served natively
    through the express queue (expressFrames counted, zero fallbacks);
    with GUBER_EXPRESS=0 it falls back to the Python path — exactly the
    PR 13 behavior — and both answer correct bytes."""
    from tests.test_native_loop import _frame, _post, _standalone
    from gubernator_tpu import wire
    from gubernator_tpu.utils.clock import Clock

    import tests.test_native_loop as tnl

    d = tnl._standalone(Clock(), native_ingress=True)
    try:
        if not express:
            d.service.conf.behaviors.express = False
            d.gateway.pump.update_ring()  # re-push the masks
        pump = d.gateway.pump
        before = pump.stats()
        frame = _frame("nb", ["k1"], behavior=int(Behavior.NO_BATCHING))
        raw, body = _post(d.gateway._edge.port, frame)
        assert raw.startswith(b"HTTP/1.1 200 OK")
        rc = wire.decode_ingress_result_frame(body)
        assert rc.n == 1 and int(rc.status[0]) == 0
        after = pump.stats()
        if express:
            assert after["expressFrames"] == before["expressFrames"] + 1
            assert after["fallbacks"] == before["fallbacks"]
        else:
            assert after["expressFrames"] == before["expressFrames"]
            assert after["fallbacks"] > before["fallbacks"]
            assert after["frames"] == before["frames"]  # never in the ring
    finally:
        d.close()


@pytest.mark.skipif(not native.available(),
                    reason="native runtime unavailable")
def test_debug_surfaces_report_express():
    import json
    import urllib.request

    from tests.test_native_loop import _frame, _post, _standalone
    from gubernator_tpu.utils.clock import Clock

    d = _standalone(Clock(), native_ingress=True)
    try:
        frame = _frame("dbg", ["k1"], behavior=int(Behavior.NO_BATCHING))
        _post(d.gateway._edge.port, frame)
        # Give the pump's stats poll a beat to fold the express delta.
        deadline = time.time() + 5.0
        port = d.gateway._edge.port
        while time.time() < deadline:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/status", timeout=5
            ) as f:
                status = json.loads(f.read())
            if status["express"]["lanes"].get("native", 0) > 0:
                break
            time.sleep(0.05)
        assert status["express"]["enabled"] is True
        assert status["express"]["lanes"]["native"] >= 1
        assert sorted(status["express"]) == [
            "declined", "dispatches", "enabled", "hitRate", "lanes",
            "maxLanes",
        ]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/latency", timeout=5
        ) as f:
            lat = json.loads(f.read())
        assert "express" in lat and "hitRate" in lat["express"]
    finally:
        d.close()


@pytest.mark.skipif(not native.available(),
                    reason="native runtime unavailable")
def test_native_take_is_express_pure():
    """An express frame queued behind bulk backlog jumps the queue AND
    its take never keeps filling from the bulk queue — otherwise the
    express response would wait out a full coalesced dispatch."""
    from tests.test_native_loop import (
        _connect, _edge_with_batcher, _frame, _http_post,
    )

    edge, b, _ring = _edge_with_batcher(["me"], "me")
    # Re-push with the express mask on (the pump's GUBER_EXPRESS shape).
    b.set_ring(
        np.zeros(0, np.uint64), np.zeros(0, np.uint8), all_self=True,
        enabled=True, cap_lanes=0, max_frame_lanes=16384,
        behavior_mask=2 | 4 | 16, express_mask=1,
    )
    socks = []
    try:
        # Two bulk frames, then one NO_BATCHING express frame — one
        # connection each (response plumbing is not what this pins).
        for i in range(2):
            s = _connect(edge.port)
            socks.append(s)
            s.sendall(_http_post(_frame("xp", [f"b{i}a", f"b{i}b"])))
            assert edge.next(timeout_ms=2000, ingress=b) is native.FAST_LANE
        s = _connect(edge.port)
        socks.append(s)
        s.sendall(_http_post(_frame(
            "xp", ["xk"], behavior=int(Behavior.NO_BATCHING)
        )))
        assert edge.next(timeout_ms=2000, ingress=b) is native.FAST_LANE
        # First take: the express frame ALONE (jumped 4 bulk lanes).
        tb = b.take(65536, timeout_ms=2000)
        assert tb is not None and tb.n == 1 and tb.n_frames == 1
        b.fail(tb, 500, "Error", "application/json", b"{}")
        # Second take: the bulk frames, coalesced.
        tb2 = b.take(65536, timeout_ms=2000)
        assert tb2 is not None and tb2.n == 4 and tb2.n_frames == 2
        b.fail(tb2, 500, "Error", "application/json", b"{}")
        assert b.stats()["expressLanes"] == 1
    finally:
        for s in socks:
            s.close()
        b.free()
        edge.shutdown()


# ---------------------------------------------------------------------
# Readback-flake quarantine (the counted single retry)
# ---------------------------------------------------------------------

def test_host_readback_retries_indexerror_once():
    from gubernator_tpu.models import shard as shard_mod

    class Flaky:
        def __init__(self, fail_times):
            self.fails = fail_times

        def __array__(self, dtype=None, copy=None):
            if self.fails:
                self.fails -= 1
                raise IndexError("list index out of range")
            return np.arange(3)

    before = shard_mod.readback_retries_total()
    out = host_readback(Flaky(1))
    assert list(out) == [0, 1, 2]
    assert shard_mod.readback_retries_total() == before + 1
    # A second consecutive failure propagates (one retry, not a loop).
    with pytest.raises(IndexError):
        host_readback(Flaky(2))
    # Non-IndexError failures propagate untouched.
    class Broken:
        def __array__(self, dtype=None, copy=None):
            raise ValueError("boom")
    with pytest.raises(ValueError):
        host_readback(Broken())
