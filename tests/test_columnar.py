"""Columnar ingress path: the zero-dataclass hot path must be
semantically identical to the dataclass router (gubernator.go:116-227
behavior), lane for lane, for every routing class — plain local lanes,
validation errors, GLOBAL lanes, and remotely-owned forwards."""

import json
import threading
import urllib.request

import numpy as np
import pytest

from gubernator_tpu.parallel.mesh import MeshBucketStore
from gubernator_tpu.service import IngressColumns, ServiceConfig, V1Service
from gubernator_tpu.types import (
    Algorithm,
    Behavior,
    GetRateLimitsRequest,
    RateLimitRequest,
    Status,
)
from gubernator_tpu.utils.clock import Clock

from .conftest import one_device_store

NOW = 1_573_430_400_000


def make_cols(n, name="col", prefix="k", hits=1, limit=10, duration=60_000,
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
    from gubernator_tpu.types import PeerInfo

    svc.set_peers([PeerInfo(grpc_address="127.0.0.1:9999", is_owner=True)])
    yield svc
    svc.close()


def test_columnar_matches_dataclass_path(service):
    cols = make_cols(64, hits=3, limit=10)
    reqs = [cols.request_at(i) for i in range(64)]

    r1 = service.get_rate_limits_columns(cols)
    r2 = service.get_rate_limits(GetRateLimitsRequest(requests=reqs))

    # Same frozen now: second call sees state the first left behind.
    for i in range(64):
        a = r1.response_at(i)
        b = r2.responses[i]
        assert a.status == Status.UNDER_LIMIT
        assert b.status == Status.UNDER_LIMIT
        assert a.remaining == 7 and b.remaining == 4
        assert a.reset_time == b.reset_time == NOW + 60_000


def test_columnar_validation_errors(service):
    cols = make_cols(4)
    cols.unique_keys[1] = ""
    cols.names[2] = ""
    r = service.get_rate_limits_columns(cols)
    assert r.response_at(0).status == Status.UNDER_LIMIT
    assert r.response_at(1).error == "field 'unique_key' cannot be empty"
    assert r.response_at(2).error == "field 'namespace' cannot be empty"
    assert r.response_at(3).remaining == 9


def test_columnar_batch_cap(service):
    from gubernator_tpu.service import ApiError

    with pytest.raises(ApiError):
        service.get_rate_limits_columns(make_cols(1001))


def test_columnar_global_lanes_mixed(service):
    """GLOBAL lanes take the replica/dataclass path while plain lanes
    stay columnar — both classes must answer in one call."""
    n = 8
    cols = make_cols(n, prefix="mix")
    beh = cols.behavior.copy()
    beh[::2] = int(Behavior.GLOBAL)
    cols.behavior = beh
    r = service.get_rate_limits_columns(cols)
    for i in range(n):
        resp = r.response_at(i)
        assert resp.error == ""
        assert resp.status == Status.UNDER_LIMIT
        assert resp.remaining == 9


def test_columnar_multi_region_queues_aggregated_hits(service):
    """MULTI_REGION lanes stay columnar when locally owned; the region
    queue receives per-key aggregated hits (multiregion.go:37-47)."""
    n = 6
    cols = IngressColumns(
        names=["mr"] * n,
        unique_keys=["a", "a", "a", "b", "b", "c"],
        algorithm=np.zeros(n, np.int32),
        behavior=np.full(n, int(Behavior.MULTI_REGION), np.int32),
        hits=np.ones(n, np.int64),
        limit=np.full(n, 10, np.int64),
        duration=np.full(n, 60_000, np.int64),
    )
    r = service.get_rate_limits_columns(cols)
    assert [r.response_at(i).remaining for i in range(n)] == [9, 8, 7, 9, 8, 9]
    with service.multi_region_mgr._lock:
        queued = dict(service.multi_region_mgr._hits)
    assert queued["mr_a"].hits == 3
    assert queued["mr_b"].hits == 2
    assert queued["mr_c"].hits == 1


def test_columnar_reset_remaining_and_leaky(service):
    n = 6
    cols = make_cols(n, prefix="rr", hits=4, limit=4,
                     algorithm=int(Algorithm.LEAKY_BUCKET))
    r1 = service.get_rate_limits_columns(cols)
    assert all(r1.response_at(i).remaining == 0 for i in range(n))
    r2 = service.get_rate_limits_columns(cols)
    assert all(r2.response_at(i).status == Status.OVER_LIMIT for i in range(n))


def test_columnar_gregorian_error_lane(service):
    cols = make_cols(3, prefix="greg")
    beh = cols.behavior.copy()
    beh[1] = int(Behavior.DURATION_IS_GREGORIAN)
    cols.behavior = beh
    dur = cols.duration.copy()
    dur[1] = 99  # not a valid Gregorian interval
    cols.duration = dur
    r = service.get_rate_limits_columns(cols)
    assert r.response_at(0).error == ""
    assert "gregorian" in r.response_at(1).error.lower() or r.response_at(1).error
    assert r.response_at(2).error == ""


def test_columnar_duplicate_keys(service):
    """Duplicate keys in one columnar batch serialize like the mutex
    would (gubernator.go:336-337): k occurrences each subtract."""
    n = 10
    cols = IngressColumns(
        names=["dup"] * n,
        unique_keys=["same"] * n,
        algorithm=np.zeros(n, np.int32),
        behavior=np.zeros(n, np.int32),
        hits=np.ones(n, np.int64),
        limit=np.full(n, 6, np.int64),
        duration=np.full(n, 60_000, np.int64),
    )
    r = service.get_rate_limits_columns(cols)
    statuses = [r.response_at(i).status for i in range(n)]
    assert statuses.count(Status.UNDER_LIMIT) == 6
    assert statuses.count(Status.OVER_LIMIT) == 4


def test_columnar_concurrent_pipelining(service):
    """Concurrent columnar callers must pipeline without corrupting
    state: total accepted across threads == limit exactly."""
    n_threads, per_batch = 8, 4
    limit = n_threads * per_batch // 2
    results = []
    lock = threading.Lock()

    def worker(t):
        cols = IngressColumns(
            names=["conc"] * per_batch,
            unique_keys=["shared"] * per_batch,
            algorithm=np.zeros(per_batch, np.int32),
            behavior=np.zeros(per_batch, np.int32),
            hits=np.ones(per_batch, np.int64),
            limit=np.full(per_batch, limit, np.int64),
            duration=np.full(per_batch, 60_000, np.int64),
        )
        r = service.get_rate_limits_columns(cols)
        with lock:
            results.extend(r.response_at(i).status for i in range(per_batch))

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results.count(Status.UNDER_LIMIT) == limit
    assert results.count(Status.OVER_LIMIT) == limit


def test_gateway_columnar_roundtrip():
    """Multi-item JSON requests flow through parse_columns /
    render_columns and must match the reference JSON shape."""
    from gubernator_tpu.daemon import Daemon, DaemonConfig

    d = Daemon(DaemonConfig(listen_address="127.0.0.1:0",
                            grpc_listen_address="127.0.0.1:0"))
    d.start()
    try:
        body = {
            "requests": [
                {"name": "gw", "uniqueKey": f"k{i}", "hits": "1",
                 "limit": "5", "duration": "60000"}
                for i in range(3)
            ]
            + [{"name": "gw", "uniqueKey": ""}]
        }
        req = urllib.request.Request(
            f"http://{d.gateway.address}/v1/GetRateLimits",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=10) as resp:
            payload = json.loads(resp.read())
        rs = payload["responses"]
        assert len(rs) == 4
        # Exact reference JSON shape (grpc-gateway camelCase, stringified
        # int64s) — pin every field.
        assert set(rs[0]) == {"status", "limit", "remaining", "resetTime"}
        assert rs[0]["status"] == "UNDER_LIMIT"
        assert rs[0]["limit"] == "5"
        assert rs[0]["remaining"] == "4"
        assert int(rs[0]["resetTime"]) > 0
        assert rs[3]["error"] == "field 'unique_key' cannot be empty"
    finally:
        d.close()


def test_columnar_fallback_without_native():
    """A store without columnar support routes the whole batch through
    the dataclass path transparently."""
    clock = Clock()
    clock.freeze(NOW)
    store = MeshBucketStore(capacity_per_shard=256, use_native=False)
    svc = V1Service(ServiceConfig(store=store, clock=clock,
                                  advertise_address="127.0.0.1:9998"))
    from gubernator_tpu.types import PeerInfo

    svc.set_peers([PeerInfo(grpc_address="127.0.0.1:9998", is_owner=True)])
    try:
        assert not store.supports_columns
        r = svc.get_rate_limits_columns(make_cols(5, prefix="nofast"))
        for i in range(5):
            assert r.response_at(i).remaining == 9
    finally:
        svc.close()


def test_service_starts_and_folds_without_the_native_build(monkeypatch):
    """A host with no compiler: `native.available()` is False, the
    store plans with the Python slot table, and the service still
    starts — the tenant ledger and the hot-key sketch it constructs
    fold a batch (40 lanes, above `topk`: the batch fold) from numpy."""
    from gubernator_tpu import native
    from gubernator_tpu.types import PeerInfo

    monkeypatch.setattr(native, "_get_lib", lambda: None)
    clock = Clock()
    clock.freeze(NOW)
    store = MeshBucketStore(capacity_per_shard=256)
    assert not store._native
    svc = V1Service(ServiceConfig(store=store, clock=clock,
                                  advertise_address="127.0.0.1:9998"))
    svc.set_peers([PeerInfo(grpc_address="127.0.0.1:9998", is_owner=True)])
    try:
        lanes0 = svc.tenants.totals()["lanes"]
        r = svc.get_rate_limits_columns(make_cols(40, prefix="nocc"))
        for i in range(40):
            assert r.response_at(i).remaining == 9
        assert svc.tenants.totals()["lanes"] - lanes0 == 40
        assert svc.tenants.batches >= 1
        hs = native.fnv1_batch([f"nocc{i % 7}" for i in range(40)])
        svc.hotkeys.update(hs, [f"nocc{i % 7}" for i in range(40)])
        assert svc.hotkeys.snapshot()["total_lanes"] >= 40
        assert svc.debug_status()["folds"]["native"] is False
    finally:
        svc.close()


def test_wide_gregorian_stays_on_dict_wire_and_matches_wide():
    """Yearly Gregorian expiries exceed the narrow wire's i32 deltas;
    the dict wire must still carry them (int64 table rows + wide-output
    kernel) and produce results identical to the forced per-lane wide
    wire (interval.go:82-146 is first-class in the reference)."""
    import numpy as np

    from gubernator_tpu.models.shard import GregResolver
    from gubernator_tpu.types import Behavior
    from gubernator_tpu.utils import gregorian

    NOW = 1_700_000_000_000
    n = 96
    greg = GregResolver(NOW)
    ge_y, gd_y = greg.resolve(gregorian.GREGORIAN_YEARS)
    ge_d, gd_d = greg.resolve(gregorian.GREGORIAN_DAYS)
    yearly = (np.arange(n) % 2).astype(bool)
    kw = dict(
        algorithm=(np.arange(n) % 2).astype(np.int32),
        behavior=np.full(n, int(Behavior.DURATION_IS_GREGORIAN), np.int32),
        hits=np.ones(n, np.int64),
        limit=np.full(n, 1000, np.int64),
        duration=np.where(
            yearly, gregorian.GREGORIAN_YEARS, gregorian.GREGORIAN_DAYS
        ).astype(np.int64),
        greg_expire=np.where(yearly, ge_y, ge_d).astype(np.int64),
        greg_duration=np.where(yearly, gd_y, gd_d).astype(np.int64),
    )
    keys = [f"wg:{k % 24}" for k in range(n)]  # duplicates too

    # Guard against a vacuous pass: this batch must be dict-encodable
    # (otherwise both stores would silently take the same wide per-lane
    # wire and the comparison proves nothing).
    from gubernator_tpu.models.shard import make_columns
    from gubernator_tpu.ops import buckets

    cols = make_columns(
        kw["algorithm"], kw["behavior"], kw["hits"], kw["limit"],
        kw["duration"], n, kw["greg_expire"], kw["greg_duration"],
    )
    assert buckets.build_config_dict(cols, NOW)[1] is not None

    a = one_device_store(256)
    b = one_device_store(256)
    for step in range(3):
        ra = a.apply_columns(keys, now_ms=NOW + step, **kw)
        rb = b.apply_columns(keys, now_ms=NOW + step, force_wire="wide", **kw)
        for f in ("status", "remaining", "reset_time", "limit"):
            np.testing.assert_array_equal(ra[f], rb[f], err_msg=f"{f} step {step}")
    # yearly lanes really do exceed the narrow delta (the point of the test)
    assert int((kw["greg_expire"] - NOW).max()) > (1 << 31) - 1


