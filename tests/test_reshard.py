"""Elastic membership unit tests (reshard.py): ring fingerprints, the
transfer wire, columnar drain/commit with O(1)-dispatch pins and
monotone merge semantics, set_peers ring-delta bookkeeping, the epoch
fence, and the bounded membership pool.

The cross-daemon legs (live handoff, double-dispatch reads, chaos,
exactly-once oracle) live in tests/test_reshard_chaos.py.
"""

import threading
import time

import numpy as np
import pytest

from gubernator_tpu import wire
from gubernator_tpu.parallel.hash_ring import ReplicatedConsistentHash
from gubernator_tpu.parallel.mesh import MeshBucketStore
from gubernator_tpu.reshard import (
    TransferColumns,
    ring_fingerprint,
)
from gubernator_tpu.service import ApiError, ServiceConfig, V1Service
from gubernator_tpu.types import (
    Algorithm,
    Behavior,
    PeerInfo,
    RateLimitRequest,
    RateLimitResponse,
    SECOND,
)
from gubernator_tpu.utils.clock import Clock

from .conftest import one_device_store

T0 = 1_573_430_430_000


@pytest.fixture
def clock():
    c = Clock()
    c.freeze(T0)
    return c


def _req(key, hits=1, limit=100, name="rs", duration=3600 * SECOND,
         algorithm=Algorithm.TOKEN_BUCKET, behavior=0):
    return RateLimitRequest(
        name=name, unique_key=key, hits=hits, limit=limit,
        duration=duration, algorithm=algorithm, behavior=behavior,
    )


def _cols(keys, remaining, limit=100, algo=0, status=0,
          duration=3600 * SECOND, stamp=T0, expire=T0 + 3600_000,
          ring_hash=0):
    n = len(keys)
    as_arr = lambda v, dt: (  # noqa: E731
        np.asarray(v, dt) if hasattr(v, "__len__")
        else np.full(n, v, dt)
    )
    return TransferColumns(
        keys=list(keys),
        algorithm=as_arr(algo, np.int32),
        status=as_arr(status, np.int32),
        limit=as_arr(limit, np.int64),
        remaining=as_arr(remaining, np.int64),
        duration=as_arr(duration, np.int64),
        stamp=as_arr(stamp, np.int64),
        expire_at=as_arr(expire, np.int64),
        ring_hash=ring_hash,
    )


# ---------------------------------------------------------------------
# Ring fingerprint (the transfer epoch fence)
# ---------------------------------------------------------------------
def test_ring_fingerprint_order_independent():
    a = ring_fingerprint(["h1:1", "h2:2", "h3:3"])
    b = ring_fingerprint(["h3:3", "h1:1", "h2:2"])
    assert a == b != 0


def test_ring_fingerprint_sensitivity():
    base = ring_fingerprint(["h1:1", "h2:2"])
    assert ring_fingerprint(["h1:1", "h2:2", "h3:3"]) != base  # join
    assert ring_fingerprint(["h1:1"]) != base  # leave
    assert ring_fingerprint(["h1:1", "h9:9"]) != base  # replace
    # A vnode-count change moves ownership without changing membership,
    # so it must change the epoch too.
    assert ring_fingerprint(["h1:1", "h2:2"], replicas=16) != base


def test_ring_fingerprint_matches_picker_method():
    ring = ReplicatedConsistentHash()
    for h in ("b:2", "a:1", "c:3"):
        ring.add(h)
    assert ring.fingerprint() == ring_fingerprint(
        sorted(["a:1", "b:2", "c:3"]), ring.replicas
    )


# ---------------------------------------------------------------------
# Transfer wire: GUBC frame kind 4 + proto columns
# ---------------------------------------------------------------------
def test_transfer_frame_roundtrip():
    cols = _cols(["rs_a", "rs_bc"], remaining=[93, 94],
                 ring_hash=0xDEAD_BEEF_CAFE_F00D)
    raw = wire.encode_transfer_frame(cols)
    assert wire.is_transfer_frame(raw)
    assert not wire.is_globals_frame(raw)  # kinds must not alias
    assert not wire.is_transfer_frame(
        wire.encode_globals_frame(
            __import__(
                "gubernator_tpu.parallel.global_mgr", fromlist=["x"]
            ).GlobalsColumns(
                keys=["k"], algorithm=np.zeros(1, np.int32),
                status=np.zeros(1, np.int32), limit=np.ones(1, np.int64),
                remaining=np.ones(1, np.int64),
                reset_time=np.ones(1, np.int64),
            )
        )
    )
    back = wire.decode_transfer_frame(raw)
    assert back.keys == ["rs_a", "rs_bc"]
    assert back.ring_hash == 0xDEAD_BEEF_CAFE_F00D
    assert list(back.remaining) == [93, 94]
    assert list(back.stamp) == [T0, T0]


def test_transfer_frame_rejects_corruption():
    raw = wire.encode_transfer_frame(_cols(["rs_a"], remaining=[1]))
    with pytest.raises(ValueError, match="length mismatch"):
        wire.decode_transfer_frame(raw + b"x")
    with pytest.raises(ValueError):
        wire.decode_transfer_frame(b"{not a frame}")


def test_transfer_pb_roundtrip():
    cols = _cols(["rs_a"], remaining=[42], ring_hash=7)
    m = wire.transfer_cols_to_pb(cols)
    back = wire.transfer_cols_from_pb(
        type(m).FromString(m.SerializeToString())
    )
    assert back.keys == ["rs_a"]
    assert back.ring_hash == 7
    assert list(back.remaining) == [42]
    assert list(back.expire_at) == [T0 + 3600_000]


# ---------------------------------------------------------------------
# Columnar drain + commit (MeshBucketStore): O(1) programs, monotone
# merge, idempotence
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def mesh_store():
    return MeshBucketStore(capacity_per_shard=128, g_capacity=32)


def test_drain_is_one_gather_and_removes(mesh_store, clock):
    st = mesh_store
    now = clock.now_ms()
    reqs = [_req(f"dk{i}", hits=5) for i in range(12)]
    st.apply(reqs, now)
    keys = [r.hash_key() for r in reqs]
    before = st.device_dispatches
    drains_before = st.transfer_drain_dispatches
    cols = st.drain_keys(keys[:8], now)
    # ONE device program for the whole drain batch, by counting.
    assert st.device_dispatches - before == 1
    assert st.transfer_drain_dispatches - drains_before == 1
    assert sorted(cols.keys) == sorted(keys[:8])
    assert (np.asarray(cols.remaining) == 95).all()
    resident = set(st.resident_keys())
    assert not (set(keys[:8]) & resident)
    assert set(keys[8:]) <= resident
    # Draining a non-resident key is a no-op, no device program.
    before = st.device_dispatches
    assert len(st.drain_keys(["rs_gone"], now)) == 0
    assert st.device_dispatches == before


def test_drain_gather_only_then_forget(mesh_store, clock):
    """The handoff protocol: gather WITHOUT removal (the old owner's
    copy stays readable — the double-dispatch peek target — while the
    transfer is in flight), then forget_keys on ACK (host-only, no
    device program)."""
    st = mesh_store
    now = clock.now_ms()
    reqs = [_req(f"ff{i}", hits=2) for i in range(4)]
    st.apply(reqs, now)
    keys = [r.hash_key() for r in reqs]
    cols = st.drain_keys(keys, now, remove=False)
    assert sorted(cols.keys) == sorted(keys)
    assert set(keys) <= set(st.resident_keys())  # still resident
    before = st.device_dispatches
    st.forget_keys(keys)
    assert st.device_dispatches == before  # no device program
    assert not (set(keys) & set(st.resident_keys()))


def test_drain_skips_global_keys(mesh_store, clock):
    st = mesh_store
    now = clock.now_ms()
    g = _req("gkey", hits=1, behavior=int(Behavior.GLOBAL))
    st.apply([g], now)
    assert len(st.drain_keys([g.hash_key()], now)) == 0
    # The GLOBAL key stays: its migration is the replication plane's
    # job (every peer already holds replica state).


def test_commit_is_o1_merge_monotone_idempotent(clock):
    st = MeshBucketStore(capacity_per_shard=128, g_capacity=32)
    now = clock.now_ms()
    # The receiver admitted traffic during the window: k0 has 10 hits
    # locally (remaining 90).
    st.apply([_req("w0", hits=10)], now)
    k0 = _req("w0").hash_key()
    incoming = _cols([k0, "rs_new"], remaining=[85, 97])
    before = st.device_dispatches
    assert st.commit_transfer(incoming, now) == 2
    assert st.device_dispatches - before == 2  # gather + scatter, O(1)
    assert st.transfer_commit_dispatches == 2
    out = st.apply([_req("w0", hits=0)], now)
    # Monotone merge: min(90, 85) — never more permissive than either.
    assert out[0].remaining == 85
    # Idempotent: re-delivering the same batch (a retried transfer)
    # must not double-count.
    st.commit_transfer(incoming, now)
    out = st.apply([_req("w0", hits=0)], now)
    assert out[0].remaining == 85
    # The fresh key landed wholesale (rs_new is its own hash key).
    out = st.apply(
        [RateLimitRequest(name="rs", unique_key="new", hits=0, limit=100,
                          duration=3600 * SECOND)], now
    )
    assert out[0].remaining == 97


def test_commit_drops_expired_and_dedupes(clock):
    st = MeshBucketStore(capacity_per_shard=64, g_capacity=32)
    now = clock.now_ms()
    cols = _cols(
        ["rs_dup", "rs_dead", "rs_dup"],
        remaining=[50, 1, 40],
        expire=[now + 1000, now - 1, now + 1000],
    )
    assert st.commit_transfer(cols, now) == 1  # dup keeps LAST, dead dropped
    out = st.apply([_req("dup", name="rs", hits=0, limit=100)], now)
    assert out[0].remaining == 40


def test_commit_algorithm_switch_takes_incoming(clock):
    """Transferred rows travel in the device's raw representation
    (leaky remaining is fixed-point scaled), so the switch test drains
    a REAL leaky row rather than hand-building one.  A resident row of
    a different algorithm is overwritten wholesale — no cross-algorithm
    merge."""
    src = MeshBucketStore(capacity_per_shard=64, g_capacity=32)
    dst = MeshBucketStore(capacity_per_shard=64, g_capacity=32)
    now = clock.now_ms()
    dst.apply([_req("alg", hits=3)], now)  # token bucket resident at dst
    src.apply(
        [_req("alg", hits=2, algorithm=Algorithm.LEAKY_BUCKET)], now
    )
    cols = src.drain_keys([_req("alg").hash_key()], now)
    assert list(cols.algorithm) == [int(Algorithm.LEAKY_BUCKET)]
    assert dst.commit_transfer(cols, now) == 1
    out = dst.apply(
        [_req("alg", hits=0, algorithm=Algorithm.LEAKY_BUCKET)], now
    )
    assert out[0].remaining == 98


def test_one_device_drain_commit_roundtrip(clock):
    """The one-device mesh (the shape of `v5e1-1m`) speaks the same
    drain/commit contract, at the same dispatch counts."""
    src, dst = one_device_store(64), one_device_store(64)
    now = clock.now_ms()
    src.apply([_req(f"ss{i}", hits=4) for i in range(6)], now)
    keys = [_req(f"ss{i}").hash_key() for i in range(6)]
    before = src.device_dispatches
    cols = src.drain_keys(keys, now)
    assert src.device_dispatches - before == 1
    assert len(cols) == 6 and not src.resident_keys()
    before = dst.device_dispatches
    assert dst.commit_transfer(cols, now) == 6
    assert dst.device_dispatches - before == 2
    out = dst.apply([_req(f"ss{i}", hits=0) for i in range(6)], now)
    assert [r.remaining for r in out] == [96] * 6


# ---------------------------------------------------------------------
# set_peers ring-delta bookkeeping + the epoch fence + bounded pool
# ---------------------------------------------------------------------
def _mk_service(clock, **beh_over):
    from gubernator_tpu.config import BehaviorConfig

    beh = BehaviorConfig(
        global_sync_wait_s=3600.0, multi_region_sync_wait_s=3600.0,
        **beh_over,
    )
    svc = V1Service(
        ServiceConfig(cache_size=512, clock=clock, behaviors=beh)
    )
    return svc


SELF = "127.0.0.1:19001"
OTHER = "127.0.0.1:19002"
THIRD = "127.0.0.1:19003"


def _info(addr, me=False):
    return PeerInfo(grpc_address=addr, http_address=addr, is_owner=me)


def test_set_peers_generation_and_noop(clock):
    svc = _mk_service(clock)
    try:
        svc.set_peers([_info(SELF, me=True)])
        assert svc.ring_generation == 1
        h1 = svc.ring_hash
        assert h1 != 0
        # Same membership re-pushed (discovery heartbeat): no bump, no
        # handoff window.
        svc.set_peers([_info(SELF, me=True)])
        assert svc.ring_generation == 1 and svc.ring_hash == h1
        assert svc._prev_picker is None
        # Membership change: bump + window opens.
        svc.set_peers([_info(SELF, me=True), _info(OTHER)])
        assert svc.ring_generation == 2 and svc.ring_hash != h1
        assert svc._prev_picker is not None
        assert svc.debug_status()["ring"]["handoffActive"] is True
    finally:
        svc.close()


def test_handoff_window_expires(clock):
    svc = _mk_service(clock, reshard_handoff_s=0.05)
    try:
        svc.set_peers([_info(SELF, me=True)])
        svc.set_peers([_info(SELF, me=True), _info(OTHER)])
        assert svc._handoff_prev_picker() is not None
        time.sleep(0.08)
        assert svc._handoff_prev_picker() is None  # window lapsed
        assert svc.debug_status()["ring"]["handoffActive"] is False
    finally:
        svc.close()


def test_transfer_ownership_fence_and_rejection(clock):
    svc = _mk_service(clock)
    try:
        svc.set_peers([_info(SELF, me=True), _info(OTHER)])
        # Wrong-epoch batch: fenced with FailedPrecondition/409.
        stale = _cols(["rs_x"], remaining=[5], ring_hash=12345)
        with pytest.raises(ApiError) as ei:
            svc.transfer_ownership(stale)
        assert ei.value.code == "FailedPrecondition"
        assert ei.value.http_status == 409
        assert svc.reshard.transfers_fenced_in == 1
        # Right-epoch batch: lanes owned by OTHER are dropped, lanes
        # owned here commit.
        ring = svc.local_picker
        mine, theirs = [], []
        for i in range(64):
            k = f"rs_f{i}"
            (mine if ring.get(k) == SELF else theirs).append(k)
        assert mine and theirs
        cols = _cols(mine + theirs, remaining=[9] * (len(mine) + len(theirs)),
                     ring_hash=svc.ring_hash)
        committed, rejected = svc.transfer_ownership(cols)
        assert committed == len(mine)
        assert rejected == len(theirs)
        assert svc.reshard.lanes_received == len(mine)
        assert svc.reshard.lanes_rejected == len(theirs)
    finally:
        svc.close()


def test_unfenced_transfer_accepted(clock):
    # ring_hash=0 (tests / tooling) commits anywhere.
    svc = _mk_service(clock)
    try:
        svc.set_peers([_info(SELF, me=True)])
        committed, rejected = svc.transfer_ownership(
            _cols(["rs_any"], remaining=[3], ring_hash=0)
        )
        assert (committed, rejected) == (1, 0)
    finally:
        svc.close()


def test_reshard_knob_off_is_metadata_only(clock):
    svc = _mk_service(clock, reshard=False)
    try:
        assert svc.serves_reshard is False
        svc.set_peers([_info(SELF, me=True)])
        svc.set_peers([_info(SELF, me=True), _info(OTHER)])
        # Generation still tracks (observability), but no handoff was
        # scheduled: the ring change is metadata-only, legacy semantics.
        assert svc.ring_generation == 2
        svc.reshard.wait_idle(5)
        assert svc.reshard.transfers_started == 0
    finally:
        svc.close()


def test_set_peers_bounded_shutdown_tracked(clock):
    svc = _mk_service(clock)
    try:
        svc.set_peers([_info(SELF, me=True), _info(OTHER), _info(THIRD)])
        dropped = [
            p for p in svc.get_peer_list()
            if p.info.grpc_address == THIRD
        ]
        assert len(dropped) == 1
        svc.set_peers([_info(SELF, me=True), _info(OTHER)])
        # The dropped client's shutdown ran on the TRACKED bounded pool
        # (no unbounded per-peer daemon threads), so wait_idle observes
        # its completion.
        assert svc.reshard.wait_idle(10)
        assert dropped[0]._shutdown.is_set()
        reshard_threads = [
            t.name for t in threading.enumerate()
            if t.name.startswith("reshard")
        ]
        assert len(reshard_threads) <= svc.reshard.POOL_WORKERS
    finally:
        svc.close()


def test_gateway_transfer_path(clock):
    """The HTTP surface: a GUBC transfer frame POSTed to
    /v1/peer.TransferOwnership commits; a fenced frame answers 409; a
    knob-off daemon serves NO handler on the path (404 — exactly what a
    pre-reshard build answers, which is the sender's version probe)."""
    import json

    from gubernator_tpu.gateway import handle_request

    svc = _mk_service(clock)
    try:
        svc.set_peers([_info(SELF, me=True)])
        raw = wire.encode_transfer_frame(
            _cols(["rs_http"], remaining=[11], ring_hash=svc.ring_hash)
        )
        status, _, body = handle_request(
            svc, "POST", "/v1/peer.TransferOwnership", raw
        )
        assert status == 200
        assert json.loads(body) == {"committed": 1, "rejected": 0}
        # Dead-epoch frame: fenced.
        stale = wire.encode_transfer_frame(
            _cols(["rs_http"], remaining=[11], ring_hash=12345)
        )
        status, _, body = handle_request(
            svc, "POST", "/v1/peer.TransferOwnership", stale
        )
        assert status == 409
        # Not a frame: 400.
        status, _, _ = handle_request(
            svc, "POST", "/v1/peer.TransferOwnership", b"{}"
        )
        assert status == 400
    finally:
        svc.close()
    off = _mk_service(clock, reshard=False)
    try:
        off.set_peers([_info(SELF, me=True)])
        status, _, _ = handle_request(
            off, "POST", "/v1/peer.TransferOwnership", raw
        )
        assert status == 404  # no handler: pre-reshard wire behavior
    finally:
        off.close()


def test_merge_handoff_monotone():
    primary = RateLimitResponse(status=0, limit=100, remaining=90,
                                reset_time=2000)
    peek = RateLimitResponse(status=1, limit=100, remaining=40,
                             reset_time=1500)
    out = V1Service._merge_handoff(primary, peek)
    assert (out.status, out.remaining, out.reset_time) == (1, 40, 2000)
    assert out.metadata["handoff"] == "true"
    # Peek failure / error answers leave the primary untouched.
    p2 = RateLimitResponse(status=0, limit=100, remaining=90)
    assert V1Service._merge_handoff(p2, None) is p2
    assert V1Service._merge_handoff(
        p2, RateLimitResponse(error="boom")
    ).remaining == 90
