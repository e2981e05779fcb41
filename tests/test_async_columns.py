"""Async columnar entry points (get_rate_limits_columns_async and the
PeersV1 twin): the callback-driven completion path the native epoll
edge uses must produce lane-for-lane the same responses as the
blocking entry — both share _submit_columns, so these tests pin the
completion machinery (_ColumnsJoin, _HandleDrainer): exactly-once
delivery, error conversion, shutdown behavior, and the no-blocked-
worker property (in-flight requests > worker threads)."""

import json
import functools
import threading
import time
import urllib.request

import numpy as np
import pytest

from gubernator_tpu.service import (
    ApiError,
    IngressColumns,
    ServiceConfig,
    V1Service,
)
from gubernator_tpu.types import Behavior, PeerInfo, Status
from gubernator_tpu.utils.clock import Clock

NOW = 1_573_430_400_000


def make_cols(n, name="acol", prefix="k", hits=1, limit=10, duration=60_000,
              behavior=0, algorithm=0):
    return IngressColumns(
        names=[name] * n,
        unique_keys=[f"{prefix}{i}" for i in range(n)],
        algorithm=np.full(n, algorithm, np.int32),
        behavior=np.full(n, behavior, np.int32),
        hits=np.full(n, hits, np.int64),
        limit=np.full(n, limit, np.int64),
        duration=np.full(n, duration, np.int64),
    )


@pytest.fixture
def service():
    clock = Clock()
    clock.freeze(NOW)
    svc = V1Service(ServiceConfig(cache_size=4096, clock=clock,
                                  advertise_address="127.0.0.1:9999"))
    svc.set_peers([PeerInfo(grpc_address="127.0.0.1:9999", is_owner=True)])
    yield svc
    svc.close()


def run_async(fn, cols, timeout=30.0):
    """Drive one async call to completion; asserts exactly-once."""
    done = threading.Event()
    calls = []

    def cb(result, exc):
        calls.append((result, exc))
        done.set()

    fn(cols, cb)
    assert done.wait(timeout), "async callback never fired"
    time.sleep(0.02)  # a double-call would land here
    assert len(calls) == 1, f"callback fired {len(calls)} times"
    return calls[0]


def assert_same_responses(res_a, res_b):
    assert res_a.n == res_b.n
    for i in range(res_a.n):
        a, b = res_a.response_at(i), res_b.response_at(i)
        assert (a.status, a.limit, a.remaining, a.error) == (
            b.status, b.limit, b.remaining, b.error
        ), f"lane {i} diverged"


def test_async_matches_sync(service):
    n = 64
    sync_res = service.get_rate_limits_columns(make_cols(n, hits=3))
    async_res, exc = run_async(
        service.get_rate_limits_columns_async, make_cols(n, hits=3)
    )
    assert exc is None
    # Same frozen clock: the async batch drains 3 more hits per key.
    assert async_res.n == n
    for i in range(n):
        assert async_res.response_at(i).remaining == (
            sync_res.response_at(i).remaining - 3
        )


def test_async_validation_error_lanes(service):
    cols = make_cols(8)
    cols.unique_keys[3] = ""
    cols.names[5] = ""
    res, exc = run_async(service.get_rate_limits_columns_async, cols)
    assert exc is None
    assert "unique_key" in res.response_at(3).error
    assert "namespace" in res.response_at(5).error
    assert res.response_at(0).error == ""
    assert res.response_at(0).status == int(Status.UNDER_LIMIT)


def test_async_over_batch_cap_is_api_error(service):
    cols = make_cols(2)

    class FakeLen:
        def __len__(self):
            return 1001

        def __getattr__(self, k):
            return getattr(cols, k)

    res, exc = run_async(service.get_rate_limits_columns_async, FakeLen())
    assert res is None
    assert isinstance(exc, ApiError)


def test_async_empty_batch(service):
    res, exc = run_async(service.get_rate_limits_columns_async, make_cols(0))
    assert exc is None
    assert res.n == 0


def test_async_single_lane_rides_dataclass_path(service):
    # n == 1 falls back to the (pool-run) dataclass router.
    res, exc = run_async(
        service.get_rate_limits_columns_async,
        make_cols(1, behavior=int(Behavior.NO_BATCHING)),
    )
    assert exc is None
    assert res.response_at(0).status == int(Status.UNDER_LIMIT)
    assert res.response_at(0).limit == 10


def test_async_global_lanes(service):
    # GLOBAL lanes ride the slow (dataclass) resolver inside the async
    # plan — owner-local here, so they answer authoritatively.
    n = 16
    beh = np.zeros(n, np.int32)
    beh[::2] = int(Behavior.GLOBAL)
    cols = make_cols(n)
    cols.behavior = beh
    res, exc = run_async(service.get_rate_limits_columns_async, cols)
    assert exc is None
    for i in range(n):
        assert res.response_at(i).status == int(Status.UNDER_LIMIT)
        assert res.response_at(i).remaining == 9


def test_async_mixed_no_batching(service):
    n = 12
    beh = np.zeros(n, np.int32)
    beh[:4] = int(Behavior.NO_BATCHING)
    cols = make_cols(n)
    cols.behavior = beh
    res, exc = run_async(service.get_rate_limits_columns_async, cols)
    assert exc is None
    for i in range(n):
        assert res.response_at(i).remaining == 9


def test_async_peer_columns_matches_sync(service):
    sync_res = service.get_peer_rate_limits_columns(make_cols(32, hits=2))
    async_res, exc = run_async(
        service.get_peer_rate_limits_columns_async, make_cols(32, hits=2)
    )
    assert exc is None
    for i in range(32):
        assert async_res.response_at(i).remaining == (
            sync_res.response_at(i).remaining - 2
        )


def test_async_many_inflight_few_workers(service):
    """The point of the async path: many concurrent requests in flight
    with NO per-request blocked thread.  120 requests submitted from 2
    threads all complete, and their hits all land."""
    n_reqs, lanes = 120, 8
    done = threading.Event()
    results = []
    lock = threading.Lock()

    def cb(result, exc):
        with lock:
            results.append((result, exc))
            if len(results) == n_reqs:
                done.set()

    def submit(base):
        for r in range(n_reqs // 2):
            cols = make_cols(lanes, prefix="storm", limit=100_000)
            service.get_rate_limits_columns_async(cols, cb)

    t1 = threading.Thread(target=submit, args=(0,))
    t2 = threading.Thread(target=submit, args=(1,))
    t1.start(); t2.start(); t1.join(); t2.join()
    assert done.wait(60), f"only {len(results)}/{n_reqs} completed"
    assert all(exc is None for _, exc in results)
    # Every request drained `lanes` hits off the same keys: the final
    # remaining must reflect all of them (no lost or double applies).
    final, exc = run_async(
        service.get_rate_limits_columns_async,
        make_cols(lanes, prefix="storm", limit=100_000),
    )
    assert exc is None
    assert final.response_at(0).remaining == 100_000 - (n_reqs + 1)


def test_async_single_lane_saturation_makes_progress(service):
    """More concurrent single-lane async requests than the slow pool
    has threads: they must all complete (queueing, not deadlock).  The
    round-5 review found the original fallback shared _forward_pool
    with _route's inner leaf forwards — 64 outer tasks could fill the
    pool and block forever on inner tasks queued behind them; the
    dedicated _slow_pool keeps outer and inner work on disjoint pools.
    GLOBAL|NO_BATCHING is the one single-key shape that still DECLINES
    the zero-thread fast path (sync parity: it takes store.apply with
    no window), so this pins the slow-pool route specifically."""
    n_reqs = 140  # > _slow_pool max_workers would deadlock the old way
    beh = int(Behavior.GLOBAL) | int(Behavior.NO_BATCHING)
    done = threading.Event()
    results = []
    lock = threading.Lock()

    def cb(result, exc):
        with lock:
            results.append(exc)
            if len(results) == n_reqs:
                done.set()

    for i in range(n_reqs):
        service.get_rate_limits_columns_async(
            make_cols(1, prefix=f"sat{i}", limit=1000, behavior=beh), cb
        )
    assert done.wait(60), f"only {len(results)}/{n_reqs} completed"
    assert all(e is None for e in results)


def test_async_single_lane_fast_path_no_thread_parked(service):
    """Plain single-key async requests on a standalone daemon take the
    zero-extra-thread fast path (_try_single_async): many more
    concurrent requests than ANY pool has threads all complete with
    exact accounting on a shared key."""
    n_reqs = 300
    done = threading.Event()
    results = []
    lock = threading.Lock()

    def cb(result, exc):
        with lock:
            results.append((result, exc))
            if len(results) == n_reqs:
                done.set()

    for i in range(n_reqs):
        service.get_rate_limits_columns_async(
            make_cols(1, prefix="fastone", limit=100_000), cb
        )
    assert done.wait(60), f"only {len(results)}/{n_reqs} completed"
    assert all(exc is None for _, exc in results)
    assert all(r.response_at(0).error == "" for r, _ in results)
    final, exc = run_async(
        service.get_rate_limits_columns_async,
        make_cols(1, prefix="fastone", hits=0, limit=100_000),
    )
    assert exc is None
    assert final.response_at(0).remaining == 100_000 - n_reqs


def test_async_single_lane_global_completes(service):
    """GLOBAL single-key async (owner-local): rides the LocalBatcher
    branch of the fast path — the batcher flush thread completes it."""
    res, exc = run_async(
        service.get_rate_limits_columns_async,
        make_cols(1, prefix="gfast", behavior=int(Behavior.GLOBAL)),
    )
    assert exc is None
    assert res.response_at(0).status == int(Status.UNDER_LIMIT)
    assert res.response_at(0).remaining == 9


def test_async_single_lane_empty_key_validates(service):
    """Empty unique_key declines the fast path; the sync router's exact
    validation wording must come back through the slow pool."""
    cols = make_cols(1, prefix="v")
    cols.unique_keys[0] = ""
    res, exc = run_async(service.get_rate_limits_columns_async, cols)
    assert exc is None
    assert "unique_key" in res.response_at(0).error


def test_async_after_close_reports_error(service):
    service.close()
    res, exc = run_async(service.get_rate_limits_columns_async, make_cols(4))
    # Either shape is acceptable — a hard error or per-lane errors —
    # but it must complete and must not claim success with zeroed lanes.
    if exc is None:
        assert res.response_at(0).error != ""


def test_handle_drainer_contract():
    """_HandleDrainer alone: value delivery, exception conversion,
    stop() draining already-registered work, and fail-fast on late
    registration."""
    from gubernator_tpu.peer_client import PeerError
    from gubernator_tpu.service import _HandleDrainer

    class Handle:
        def __init__(self, value=None, exc=None, delay=0.0):
            self._v, self._e, self._delay = value, exc, delay

        def result(self):
            if self._delay:
                time.sleep(self._delay)
            if self._e is not None:
                raise self._e
            return self._v

    d = _HandleDrainer()
    d.start()
    got = []
    ev = threading.Event()
    d.register(Handle(value={"x": 1}), lambda v, e: (got.append((v, e)), ev.set()))
    assert ev.wait(10) and got == [({"x": 1}, None)]

    got.clear(); ev.clear()
    boom = RuntimeError("boom")
    d.register(Handle(exc=boom), lambda v, e: (got.append((v, e)), ev.set()))
    assert ev.wait(10) and got == [(None, boom)]

    # Work registered BEFORE stop is resolved by the draining workers.
    got.clear()
    slow_done = threading.Event()
    d.register(Handle(value=7, delay=0.2),
               lambda v, e: (got.append((v, e)), slow_done.set()))
    d.stop()
    assert slow_done.wait(10) and got == [(7, None)]

    # Late registration fails fast with the closed error, still exactly
    # once, on the caller thread.
    late = []
    d.register(Handle(value=9), lambda v, e: late.append((v, e)))
    assert len(late) == 1
    v, e = late[0]
    assert v is None and isinstance(e, PeerError)


def test_handle_drainer_resolves_a_shared_handle_once():
    """k waiters of ONE handle (a coalesced dispatch) cost one
    resolution and one thread: the callbacks fire in registration
    order, a raising one does not starve the rest, and a registration
    after the handle resolved opens a resolution of its own."""
    from gubernator_tpu.service import _HandleDrainer

    class Handle:
        def __init__(self):
            self.calls = 0
            self.gate = threading.Event()

        def result(self):
            self.calls += 1
            assert self.gate.wait(10)
            return "v"

    d = _HandleDrainer()
    d.start()
    try:
        h, other = Handle(), Handle()
        got, done = [], threading.Event()
        deadline = time.monotonic() + 10
        while d._idle < d.MIN_THREADS and time.monotonic() < deadline:
            time.sleep(0.001)  # both workers parked: none is spawned below

        def cb(i, v, e):
            got.append((i, v, e, threading.current_thread().name))
            if i == 2:
                raise RuntimeError("consumer bug")
            if i == 5:
                done.set()

        for i in range(6):
            d.register(h, functools.partial(cb, i))
        other_done = threading.Event()
        d.register(other, lambda v, e: other_done.set())
        assert len(d._threads) == d.MIN_THREADS  # two handles, two workers
        other.gate.set()
        assert other_done.wait(10)  # not queued behind the shared handle
        assert not got
        h.gate.set()
        assert done.wait(10)
        assert h.calls == 1
        assert [(i, v, e) for i, v, e, _ in got] == [
            (i, "v", None) for i in range(6)
        ]
        assert len({name for *_, name in got}) == 1
        late = threading.Event()
        d.register(h, lambda v, e: late.set())
        assert late.wait(10) and h.calls == 2
    finally:
        d.stop(timeout_s=5.0)


def test_async_callback_exception_does_not_wedge(service):
    """A raising callback must not kill the drainer pool: subsequent
    requests still complete."""
    fired = threading.Event()

    def bad_cb(result, exc):
        fired.set()
        raise RuntimeError("consumer bug")

    service.get_rate_limits_columns_async(make_cols(4, prefix="bad"), bad_cb)
    assert fired.wait(30)
    res, exc = run_async(
        service.get_rate_limits_columns_async, make_cols(4, prefix="good")
    )
    assert exc is None
    assert res.response_at(0).status == int(Status.UNDER_LIMIT)
