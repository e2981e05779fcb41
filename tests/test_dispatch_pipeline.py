"""Overlapped dispatch pipeline: interleaving oracle + machinery tests.

The pipeline's contract (models/shard.py ColumnarPipeline): however
many ingress threads race `apply_columns_async`, the observable results
are BYTE-IDENTICAL to applying the same batches serially in ticket
(plan) order on a fresh store.  Staleness from commits landing after
younger plans is absorbed by the pending-write guard + device-side
expiry revalidation, and launch fusion is semantically invisible — so
any divergence here is a real ordering bug, not noise.

The oracle deliberately avoids capacity pressure: under eviction the
documented pipelined-staleness semantics allow eviction decisions to
act on slightly-old expire times, which is a legitimate (and tested
elsewhere) divergence, not an ordering violation.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.faults import DELAY, FaultPlan, FaultRule
from gubernator_tpu.parallel.mesh import MeshBucketStore

from .conftest import one_device_store

pytestmark = pytest.mark.skipif(
    not native.available(), reason="columnar pipeline needs the native runtime"
)

NOW = 1_573_430_400_000


def _make_batches(seed: int, n_batches: int, lanes: int, n_keys: int,
                  wide: bool):
    """Deterministic batches with heavy cross-batch key overlap; each
    batch owns a fixed now_ms (NOW + index) so a serial replay is
    exactly reproducible regardless of which thread dispatched it."""
    rng = np.random.RandomState(seed)
    batches = []
    for b in range(n_batches):
        ids = rng.randint(0, n_keys, size=lanes)
        batches.append(dict(
            keys=[f"orc:{i}" for i in ids],
            algorithm=(ids % 2).astype(np.int32),
            behavior=np.zeros(lanes, np.int32),
            hits=rng.randint(1, 4, size=lanes).astype(np.int64),
            # wide: limits beyond int32 push the batch off the narrow
            # output wire (models/shard.narrow_ok).
            limit=np.full(lanes, (1 << 40) if wide else 50, np.int64),
            duration=np.full(lanes, 3_600_000, np.int64),
            now=NOW + b,
        ))
    return batches


def _dispatch(store, b, force_wire):
    return store.apply_columns_async(
        b["keys"], b["algorithm"], b["behavior"], b["hits"], b["limit"],
        b["duration"], b["now"], force_wire=force_wire,
    )


def _race(store, batches, n_threads, force_wire, delay_fn=None):
    """Race the batches over n_threads dispatcher threads; returns
    [(ticket, batch_idx, result_dict)] sorted by ticket."""
    out = []
    out_mu = threading.Lock()
    errs = []

    def worker(tid):
        try:
            for bi in range(tid, len(batches), n_threads):
                if delay_fn is not None:
                    delay_fn(tid, bi)
                h = _dispatch(store, batches[bi], force_wire)
                r = h.result()
                with out_mu:
                    out.append((h.ticket, bi, r))
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [
        threading.Thread(target=worker, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    out.sort()
    assert [t for t, _, _ in out] == sorted(t for t, _, _ in out)
    return out


def _assert_matches_serial(make_store, batches, raced, force_wire):
    """Replay the raced batches serially in ticket order on a fresh
    store; every lane's status/remaining/reset must match bitwise."""
    serial = make_store()
    for ticket, bi, raced_result in raced:
        b = batches[bi]
        expect = serial.apply_columns(
            b["keys"], b["algorithm"], b["behavior"], b["hits"], b["limit"],
            b["duration"], b["now"], force_wire=force_wire,
        )
        for f in ("status", "remaining", "reset_time"):
            assert np.array_equal(
                np.asarray(raced_result[f]), np.asarray(expect[f])
            ), (
                f"field {f} diverged for batch {bi} (ticket {ticket}, "
                f"wire={force_wire})"
            )


@pytest.mark.parametrize("seed", [7, 1234])
@pytest.mark.parametrize("force_wire", [None, "wide"])
def test_one_device_interleaved_matches_serial(seed, force_wire):
    store = one_device_store(4096)
    batches = _make_batches(seed, n_batches=12, lanes=96, n_keys=64,
                            wide=force_wire == "wide")
    raced = _race(store, batches, n_threads=3, force_wire=force_wire)
    _assert_matches_serial(
        lambda: one_device_store(4096), batches, raced, force_wire
    )


@pytest.mark.parametrize("seed", [11, 4242])
@pytest.mark.parametrize("force_wire", [None, "wide"])
def test_mesh_interleaved_matches_serial(seed, force_wire):
    store = MeshBucketStore(capacity_per_shard=1024)
    batches = _make_batches(seed, n_batches=10, lanes=128, n_keys=80,
                            wide=force_wire == "wide")
    raced = _race(store, batches, n_threads=3, force_wire=force_wire)
    _assert_matches_serial(
        lambda: MeshBucketStore(capacity_per_shard=1024), batches, raced,
        force_wire,
    )


@pytest.mark.chaos
@pytest.mark.parametrize("seed", [3, 99])
def test_interleaved_matches_serial_under_fault_delays(seed):
    """Chaos variant: per-(thread, op) seeded FaultPlan DELAY rules
    jitter the dispatchers' schedules — the interleavings shift with
    the seed, the oracle verdict must not."""
    plan = FaultPlan(seed=seed)
    plan.add(FaultRule(peer="*", op="dispatch", kind=DELAY,
                       delay_s=0.004, rate=0.6))
    store = MeshBucketStore(capacity_per_shard=1024)
    batches = _make_batches(seed, n_batches=9, lanes=64, n_keys=48,
                            wide=False)

    def delay_fn(tid, bi):
        act = plan.intercept(f"t{tid}", "dispatch")
        if act is not None and act.kind == DELAY:
            time.sleep(act.delay_s)

    raced = _race(store, batches, n_threads=3, force_wire=None,
                  delay_fn=delay_fn)
    _assert_matches_serial(
        lambda: MeshBucketStore(capacity_per_shard=1024), batches, raced,
        None,
    )


def _oracle_results(batches, raced):
    """The raced batches applied by tests/oracle.py one lane after another
    in ticket order: [(status, remaining, reset_time)] a batch."""
    from gubernator_tpu.types import Algorithm, RateLimitRequest

    from . import oracle as orc

    cache = orc.OracleCache()
    out = []
    for _ticket, bi, _ in raced:
        b = batches[bi]
        rows = np.empty((len(b["keys"]), 3), np.int64)
        for lane, key in enumerate(b["keys"]):
            name, _, unique = key.partition(":")
            r = orc.apply(cache, RateLimitRequest(
                name=name, unique_key=unique, hits=int(b["hits"][lane]),
                limit=int(b["limit"][lane]), duration=int(b["duration"][lane]),
                algorithm=Algorithm(int(b["algorithm"][lane]))), b["now"])
            rows[lane] = (int(r.status), r.remaining, r.reset_time)
        out.append(rows)
    return out


# (fused group, lanes a batch): the group sizes the gate forms, at a small
# width and at the width the frames cells serve (4096 lanes, their one warm
# bucket: the fused programs every cold start compiles).
@pytest.mark.parametrize("group,lanes", [(4, 64), (2, 64), (2, 4096), (4, 4096)])
def test_launch_fusion_under_backlog(monkeypatch, group, lanes):
    """Stall ticket 0 in its STAGE step; the tickets behind it stage and
    wait at the launch gate, so ticket 0's launch fuses them all into ONE
    program, and the results still match the serial replay and, lane by
    lane, the sequential oracle: each handle's fetch reads its own rows
    of the shared answer."""
    from gubernator_tpu import saturation

    store = one_device_store(8192)
    batches = _make_batches(21, n_batches=group, lanes=lanes,
                            n_keys=lanes // 2, wide=False)
    for b in batches:
        b["keys"] = [k.replace("orc:", "orc:k") for k in b["keys"]]
        b["hits"][:] = 1  # uniform duplicate groups: one round a dispatch
        b["limit"][:] = 3  # and buckets that run dry inside the group
    orig = store._stage_columns
    stalled = threading.Event()

    def slow_stage(prep):
        if not stalled.is_set():
            stalled.set()
            # let the other tickets reach the gate
            deadline = time.monotonic() + 20.0
            while len(store._launch_gate) < group - 1 and time.monotonic() < deadline:
                time.sleep(0.005)
        return orig(prep)

    monkeypatch.setattr(store, "_stage_columns", slow_stage)
    store.take_pipeline_stats()
    tally = saturation.mesh_tally.snapshot()
    raced = _race(store, batches, n_threads=group, force_wire=None)
    stats, _depth, _hwm = store.take_pipeline_stats()
    grown = {k: v - tally[k] for k, v in saturation.mesh_tally.snapshot().items()}
    # `group` dispatches in ONE launch.
    assert stats["prepare"][0] == group
    assert stats["launch"][0] == 1, stats
    assert (grown["dispatches"], grown["launches"], grown["fusedDispatches"]) == (group, 1, group)
    _assert_matches_serial(
        lambda: one_device_store(8192), batches, raced, None
    )
    over = 0
    for (ticket, bi, got), want in zip(raced, _oracle_results(batches, raced)):
        for col, f in enumerate(("status", "remaining", "reset_time")):
            assert np.array_equal(np.asarray(got[f]), want[:, col]), (f, bi, ticket)
        over += int(want[:, 0].sum())
    assert over > 0


def test_fused_kernel_matches_solo_sequence():
    """The fused launch program (one shard of the mesh's) is
    bit-equivalent to the same wires applied by consecutive solo runs
    of the kernel it vmaps (state threading included)."""
    import jax

    from gubernator_tpu.models.shard import make_columns
    from gubernator_tpu.ops import buckets
    from gubernator_tpu.parallel import mesh

    lanes, cap = 64, 256
    slot = np.arange(lanes, dtype=np.int32)

    def wire(hits, exists, now):
        cols = make_columns(
            np.zeros(lanes, np.int32), np.zeros(lanes, np.int32),
            np.full(lanes, hits, np.int64), np.full(lanes, 100, np.int64),
            np.full(lanes, 60_000, np.int64), lanes,
        )
        _, (cfg, table) = buckets.build_config_dict(cols, NOW)
        w = buckets.pack_dict_wire(
            slot[None, :], np.full((1, lanes), exists, bool),
            np.ones((1, lanes), bool), cfg[None, :].astype(np.uint8),
            np.zeros((1, lanes), np.int32), np.zeros((1, lanes), np.int32),
            table,
        )
        # The fused program reads each wire's round count and clock
        # from its header; the solo kernel below takes them by hand.
        buckets.set_wire_header(w, 1, now)
        return w[0]

    nows = [NOW, NOW + 10, NOW + 20, NOW + 30]
    wires = [
        wire(1, False, nows[0]), wire(2, True, nows[1]),
        wire(3, True, nows[2]), wire(5, True, nows[3]),
    ]

    solo = jax.jit(buckets.apply_rounds_packed)
    solo_state = buckets.init_state(cap)
    solo_out = []
    for w, t in zip(wires, nows):
        solo_state, packed = solo(solo_state, np.array(w), 1, t)
        solo_out.append(np.asarray(packed))

    # One shard: every array carries the leading [S=1] axis.
    fused_state = jax.tree.map(lambda a: a[None], buckets.init_state(cap))
    one_device = mesh.make_mesh(jax.devices()[:1])
    fn = mesh._mesh_fused_packed_jit(one_device, 4, wide=False, donate_wires=False)
    fused_state, stacked = fn(fused_state, *[np.array(w)[None] for w in wires])
    stacked = np.asarray(stacked)  # [k, S, 4, P]
    for i in range(4):
        assert np.array_equal(stacked[i, 0], solo_out[i]), f"sub-batch {i}"
    for a, b in zip(jax.tree.leaves(solo_state), jax.tree.leaves(fused_state)):
        assert np.array_equal(np.asarray(a), np.asarray(b)[0])


def test_ingress_queue_sheds_with_429_error():
    from gubernator_tpu.config import BehaviorConfig
    from gubernator_tpu.metrics import Metrics
    from gubernator_tpu.service import ColumnarBatcher, IngressShedError, LocalBatcher
    from gubernator_tpu.types import RateLimitRequest
    from gubernator_tpu.utils.clock import DEFAULT_CLOCK

    # express=False: this test pins the WINDOWED queue's shed semantics
    # (express bypass lanes never queue, so they only shed when
    # concurrent in-flight lanes exceed the cap).
    beh = BehaviorConfig(batch_wait_s=5.0, ingress_queue_lanes=100,
                         express=False)
    metrics = Metrics()
    cb = ColumnarBatcher(object(), beh, DEFAULT_CLOCK, metrics=metrics)
    try:
        n = 60
        args = (
            [f"k{i}" for i in range(n)], np.zeros(n, np.int32),
            np.zeros(n, np.int32), np.ones(n, np.int64),
            np.full(n, 5, np.int64), np.full(n, 60_000, np.int64),
            None, None,
        )
        fut1 = cb.submit(*args)
        fut2 = cb.submit(*args)  # 60 + 60 > 100: shed
        with pytest.raises(IngressShedError) as ei:
            fut2.result(timeout=1)
        assert ei.value.http_status == 429
        assert "OVER_LIMIT" not in str(ei.value)
        assert metrics.ingress_shed._value.get() == n  # noqa: SLF001
        assert not fut1.done()  # admitted lanes still queued, not shed
    finally:
        cb.stop()

    lb = LocalBatcher(object(), BehaviorConfig(
        batch_wait_s=5.0, ingress_queue_lanes=2, express=False),
        DEFAULT_CLOCK, metrics=metrics)
    try:
        r = RateLimitRequest(name="a", unique_key="b", hits=1, limit=5,
                             duration=60_000)
        lb.submit(r)
        lb.submit(r)
        with pytest.raises(IngressShedError):
            lb.submit(r).result(timeout=1)
    finally:
        lb.stop()


def test_ingress_queue_env_knob():
    from gubernator_tpu.config import setup_daemon_config

    conf = setup_daemon_config(env={"GUBER_INGRESS_QUEUE_LANES": "123"})
    assert conf.behaviors.ingress_queue_lanes == 123
    assert setup_daemon_config(env={}).behaviors.ingress_queue_lanes == 262_144


def test_dispatch_metrics_cleared_per_scrape():
    from gubernator_tpu.metrics import Metrics

    store = one_device_store(1024)
    b = _make_batches(5, 1, 32, 16, wide=False)[0]
    _dispatch(store, b, None).result()
    m = Metrics()
    m.observe_dispatch(store)
    text = m.render().decode()
    assert "gubernator_dispatch_inflight 0.0" in text
    assert 'gubernator_dispatch_stage_seconds{stage="prepare",stat="count"} 1.0' in text
    assert 'stage="launch"' in text and 'stage="commit"' in text
    # Second scrape with no traffic since: stage series cleared (PR 1
    # breaker-gauge convention), gauges report an idle pipeline.
    m.observe_dispatch(store)
    text2 = m.render().decode()
    assert 'stage="prepare"' not in text2
    assert "gubernator_dispatch_inflight 0.0" in text2
