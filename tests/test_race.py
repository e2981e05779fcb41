"""Concurrency hardening: traffic storms racing peer churn, batcher
flushes, GLOBAL syncs, and shutdown.

The reference runs its whole suite under Go's race detector
(`Makefile:8-9`); Python has no `-race`, so these tests hammer the
lock-heavy host tier from many threads with faulthandler armed and
verify (a) nothing deadlocks or raises out of the service surface,
(b) every response is well-formed, and (c) the slot tables stay
internally consistent (MeshBucketStore.check_consistency).
"""

import faulthandler
import threading
import time

import numpy as np
import pytest

from gubernator_tpu.config import BehaviorConfig
from gubernator_tpu.service import IngressColumns, ServiceConfig, V1Service
from gubernator_tpu.types import (
    Behavior,
    GetRateLimitsRequest,
    PeerInfo,
    RateLimitRequest,
)
from gubernator_tpu.utils.clock import Clock

T0 = 1_573_430_430_000

faulthandler.enable()


def make_service(addr="127.0.0.1:9901", behaviors=None):
    clock = Clock()
    clock.freeze(T0)
    svc = V1Service(ServiceConfig(cache_size=8192, clock=clock,
                                  advertise_address=addr,
                                  behaviors=behaviors or BehaviorConfig()))
    svc.set_peers([PeerInfo(grpc_address=addr, is_owner=True)])
    return svc


def cols_for(tid, i, n=50, behavior=0):
    ids = (np.arange(n) * 131 + i * 7 + tid) % 500
    return IngressColumns(
        names=["race"] * n,
        unique_keys=[f"k{k}" for k in ids],
        algorithm=(ids % 2).astype(np.int32),
        behavior=np.full(n, behavior, np.int32),
        hits=np.ones(n, np.int64),
        limit=np.full(n, 1_000_000, np.int64),
        duration=np.full(n, 60_000, np.int64),
    )


def run_storm(svc, n_workers, iters, churn_fn=None, behaviors=(0,)):
    """Drive traffic from n_workers threads while churn_fn runs in a
    loop; returns (errors, malformed) collected across workers."""
    errors, malformed = [], []
    stop = threading.Event()
    lock = threading.Lock()

    def worker(tid):
        try:
            for i in range(iters):
                beh = behaviors[i % len(behaviors)]
                if i % 3 == 0:
                    # dataclass path incl. the LocalBatcher leg
                    resp = svc.get_rate_limits(GetRateLimitsRequest(requests=[
                        RateLimitRequest(name="race", unique_key=f"k{(i * 13 + tid) % 500}",
                                         hits=1, limit=1_000_000, duration=60_000,
                                         behavior=beh)
                    ]))
                    rls = resp.responses
                else:
                    result = svc.get_rate_limits_columns(cols_for(tid, i, behavior=beh))
                    rls = [result.response_at(j) for j in range(result.n)]
                for r in rls:
                    ok_value = r.error or (r.reset_time > 0 and r.limit > 0)
                    if not ok_value:
                        with lock:
                            malformed.append(r)
        except Exception as e:  # noqa: BLE001
            with lock:
                errors.append(e)

    def churner():
        while not stop.is_set():
            try:
                churn_fn()
            except Exception as e:  # noqa: BLE001
                with lock:
                    errors.append(e)
            time.sleep(0.002)

    # daemon=True + stop in finally: a DETECTED deadlock must fail the
    # test, not hang pytest at interpreter exit with the diagnosis lost.
    threads = [
        threading.Thread(target=worker, args=(t,), daemon=True)
        for t in range(n_workers)
    ]
    churn_thread = (
        threading.Thread(target=churner, daemon=True) if churn_fn else None
    )
    try:
        for t in threads:
            t.start()
        if churn_thread:
            churn_thread.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive(), "worker deadlocked"
    finally:
        stop.set()
    if churn_thread:
        churn_thread.join(timeout=10)
        assert not churn_thread.is_alive(), "churner deadlocked"
    return errors, malformed


def test_set_peers_storm_during_traffic():
    """Traffic from 8 threads while the peer list churns between
    self-only and self+unreachable-fakes: requests whose keys re-hash
    to fake owners error per-lane, everything else answers, nothing
    deadlocks, and the slot tables stay consistent.

    The retry back-off is cut to a tenth: at its default every lane
    forwarded to a fake sleeps out 0.02+0.04+...+0.32 s of re-picks, and
    those sleeps — not the races under test — made the storm last 128 s
    alone, past run_storm's 120 s deadlock limit under any load.  Same
    retries, same churn, same traffic; only the sleeping is shorter."""
    svc = make_service(behaviors=BehaviorConfig(
        retry_backoff_base_s=0.002, retry_backoff_max_s=0.1,
    ))
    me = PeerInfo(grpc_address="127.0.0.1:9901", is_owner=True)
    fakes = [PeerInfo(grpc_address=f"127.0.0.1:1{n}") for n in range(3)]
    state = {"flip": False}

    def churn():
        state["flip"] = not state["flip"]
        svc.set_peers([me] + (fakes if state["flip"] else []))

    try:
        errors, malformed = run_storm(svc, n_workers=8, iters=30, churn_fn=churn)
        assert errors == []
        assert malformed == []
        svc.store.check_consistency()
        # service still fully functional with the stable peer list
        svc.set_peers([me])
        r = svc.get_rate_limits(GetRateLimitsRequest(requests=[
            RateLimitRequest(name="after", unique_key="storm", hits=1,
                             limit=10, duration=60_000)
        ]))
        assert r.responses[0].error == "" and r.responses[0].remaining == 9
    finally:
        svc.close()


def test_global_sync_races_columnar_traffic():
    """GLOBAL syncs (device collective + donated-buffer swaps) racing
    columnar dispatches from many threads must serialize correctly."""
    svc = make_service("127.0.0.1:9902")

    def churn():
        svc.global_mgr.run_once()

    try:
        errors, malformed = run_storm(
            svc, n_workers=6, iters=20, churn_fn=churn,
            behaviors=(0, int(Behavior.GLOBAL)),
        )
        assert errors == []
        assert malformed == []
        svc.store.check_consistency()
    finally:
        svc.close()


def test_shutdown_races_traffic():
    """close() during a storm: every in-flight request completes with a
    result or a well-formed per-lane error — never a hang or an
    unhandled exception from the service surface."""
    svc = make_service("127.0.0.1:9903")
    started = threading.Event()
    outcome = {"errors": [], "done": 0}
    lock = threading.Lock()

    def worker(tid):
        started.set()
        for i in range(40):
            try:
                result = svc.get_rate_limits_columns(cols_for(tid, i, n=20))
                for j in range(result.n):
                    result.response_at(j)
            except Exception as e:  # noqa: BLE001
                with lock:
                    outcome["errors"].append(e)
            with lock:
                outcome["done"] += 1

    threads = [
        threading.Thread(target=worker, args=(t,), daemon=True) for t in range(4)
    ]
    for t in threads:
        t.start()
    started.wait(timeout=10)
    time.sleep(0.05)
    svc.close()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "worker hung across close()"
    # post-close requests must degrade to per-lane errors, not raise
    assert outcome["errors"] == []
    assert outcome["done"] == 4 * 40


def test_concurrent_single_key_exactness():
    """The canonical race check: many threads draining ONE key must
    admit exactly `limit` hits across every ingress path."""
    svc = make_service("127.0.0.1:9904")
    limit = 60
    admitted = []
    lock = threading.Lock()

    def worker(tid):
        got = 0
        for i in range(10):
            n = 4
            cols = IngressColumns(
                names=["exact"] * n,
                unique_keys=["one"] * n,
                algorithm=np.zeros(n, np.int32),
                behavior=np.zeros(n, np.int32),
                hits=np.ones(n, np.int64),
                limit=np.full(n, limit, np.int64),
                duration=np.full(n, 3_600_000, np.int64),
            )
            r = svc.get_rate_limits_columns(cols)
            got += sum(1 for j in range(n) if r.response_at(j).status == 0)
        with lock:
            admitted.append(got)

    try:
        threads = [threading.Thread(target=worker, args=(t,)) for t in range(5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
        assert sum(admitted) == limit  # 5*10*4=200 attempts, exactly 60 pass
        svc.store.check_consistency()
    finally:
        svc.close()
