"""Durability plane (snapshot.py): format goldens, rejection cases,
store dump/restore twins, and the service-level boot/shutdown wiring.

The byte-layout test follows the `test_wire_golden` discipline: the
expected bytes are PINNED — any layout change must bump
SNAPSHOT_VERSION and update the literal in the same reviewed change,
because a silently-moved field turns every deployed snapshot file into
a checksum-valid garbage restore.
"""

import os
import struct
import threading

import numpy as np
import pytest

from gubernator_tpu import snapshot as snap
from gubernator_tpu.parallel.mesh import MeshBucketStore
from gubernator_tpu.reshard import TransferColumns
from gubernator_tpu.service import ServiceConfig, V1Service
from gubernator_tpu.store import (
    CacheItem,
    LeakyBucketItem,
    MockLoader,
    TokenBucketItem,
)
from gubernator_tpu.types import (
    Algorithm,
    GetRateLimitsRequest,
    PeerInfo,
    RateLimitRequest,
)
from gubernator_tpu.utils.clock import Clock

from .conftest import one_device_store

NOW = 1_573_430_430_000


def _clock():
    c = Clock()
    c.freeze(NOW)
    return c


def _cols(keys, remaining, expire, algo=None, limit=100):
    n = len(keys)
    return TransferColumns(
        keys=list(keys),
        algorithm=np.asarray(
            algo if algo is not None else [int(Algorithm.TOKEN_BUCKET)] * n,
            np.int32,
        ),
        status=np.zeros(n, np.int32),
        limit=np.full(n, limit, np.int64),
        remaining=np.asarray(remaining, np.int64),
        duration=np.full(n, 60_000, np.int64),
        stamp=np.full(n, NOW, np.int64),
        expire_at=np.asarray(expire, np.int64),
    )


def _req(key, hits=1, limit=100, name="snap", algorithm=Algorithm.TOKEN_BUCKET):
    return RateLimitRequest(
        name=name, unique_key=key, hits=hits, limit=limit,
        duration=60_000, algorithm=algorithm,
    )


# ---------------------------------------------------------------------
# Format: golden bytes + codec roundtrip
# ---------------------------------------------------------------------
# encode_snapshot of the 2-lane batch below, saved_at_ms=
# 1_573_430_430_500, ring_hash=0xDEADBEEF12345678.  FROZEN: changing
# any byte of the layout requires a SNAPSHOT_VERSION bump.
GOLDEN_HEX = (
    "47554253010002000000240bc3576e01000078563412efbeadde030000000100"
    "000003000000616263000000000100000000000000010000000a000000000000"
    "0014000000000000000700000000000000dc0500000000000060ea0000000000"
    "00d0070000000000003009c3576e0100003109c3576e01000090f3c3576e0100"
    "000011c3576e010000e08d6f25"
)


def _golden_cols():
    return TransferColumns(
        keys=["a", "bc"],
        algorithm=np.array([0, 1], np.int32),
        status=np.array([0, 1], np.int32),
        limit=np.array([10, 20], np.int64),
        remaining=np.array([7, 1500], np.int64),
        duration=np.array([60_000, 2_000], np.int64),
        stamp=np.array([NOW, NOW + 1], np.int64),
        expire_at=np.array([NOW + 60_000, NOW + 2_000], np.int64),
    )


def test_snapshot_golden_bytes():
    raw = snap.encode_snapshot(
        _golden_cols(), saved_at_ms=1_573_430_430_500,
        ring_hash=0xDEADBEEF12345678,
    )
    assert raw == bytes.fromhex(GOLDEN_HEX)
    # Spot-pin the header fields on top of the blob compare, so a
    # failure names the moved field instead of "bytes differ".
    assert raw[:4] == b"GUBS" and raw[4] == snap.SNAPSHOT_VERSION == 1
    assert struct.unpack_from("<I", raw, 6)[0] == 2  # n
    assert struct.unpack_from("<q", raw, 10)[0] == 1_573_430_430_500
    assert struct.unpack_from("<Q", raw, 18)[0] == 0xDEADBEEF12345678


def test_codec_roundtrip_including_unicode_keys():
    cols = _cols(
        ["plain", "unié_汉", "x" * 300],
        remaining=[1, 2, 3],
        expire=[NOW + 1, NOW + 2, NOW + 3],
        algo=[0, 1, 0],
    )
    raw = snap.encode_snapshot(cols, NOW, ring_hash=42)
    got, meta = snap.decode_snapshot(raw)
    assert got.keys == cols.keys
    for f in ("algorithm", "status", "limit", "remaining", "duration",
              "stamp", "expire_at"):
        np.testing.assert_array_equal(getattr(got, f), getattr(cols, f))
    assert got.ring_hash == 42
    assert meta == {
        "version": 1, "lanes": 3, "saved_at_ms": NOW, "ring_hash": 42,
        "bytes": len(raw),
    }


def test_empty_snapshot_roundtrip():
    raw = snap.encode_snapshot(TransferColumns.empty(), NOW)
    got, meta = snap.decode_snapshot(raw)
    assert len(got) == 0 and meta["lanes"] == 0


# ---------------------------------------------------------------------
# Rejections: every defect is a SnapshotError, never a partial decode
# ---------------------------------------------------------------------
def test_rejects_truncation_at_every_class_of_cut():
    raw = snap.encode_snapshot(_golden_cols(), NOW)
    for cut in (0, 4, snap._HEADER.size - 1, snap._HEADER.size + 3,
                len(raw) // 2, len(raw) - 1):
        with pytest.raises(snap.SnapshotError, match="truncated"):
            snap.decode_snapshot(raw[:cut])
    # ...and APPENDED garbage is just as torn as missing bytes.
    with pytest.raises(snap.SnapshotError, match="truncated"):
        snap.decode_snapshot(raw + b"\x00")


def test_rejects_bit_flips_everywhere():
    raw = bytearray(snap.encode_snapshot(_golden_cols(), NOW))
    # One flip in each region: header count-independent field, key
    # blob, a column, and the CRC itself.
    for pos in (11, snap._HEADER.size + 9, len(raw) - 20, len(raw) - 1):
        flipped = bytearray(raw)
        flipped[pos] ^= 0x40
        with pytest.raises(snap.SnapshotError):
            snap.decode_snapshot(bytes(flipped))


def test_rejects_wrong_magic_and_version():
    raw = bytearray(snap.encode_snapshot(_golden_cols(), NOW))
    bad_magic = b"NOPE" + bytes(raw[4:])
    with pytest.raises(snap.SnapshotError, match="magic"):
        snap.decode_snapshot(bad_magic)
    bad_ver = bytearray(raw)
    bad_ver[4] = 99
    with pytest.raises(snap.SnapshotError, match="version"):
        snap.decode_snapshot(bytes(bad_ver))


def test_strict_ring_fencing():
    raw_fenced = snap.encode_snapshot(_golden_cols(), NOW, ring_hash=5)
    raw_unfenced = snap.encode_snapshot(_golden_cols(), NOW, ring_hash=0)
    # Matching fence passes; mismatch rejects; an UNFENCED file (ring 0)
    # is accepted under any expectation — the TransferColumns convention.
    snap.decode_snapshot(raw_fenced, expected_ring=5)
    with pytest.raises(snap.SnapshotError, match="ring fingerprint"):
        snap.decode_snapshot(raw_fenced, expected_ring=6)
    snap.decode_snapshot(raw_unfenced, expected_ring=6)


def test_rejects_invalid_utf8_keys_with_valid_crc():
    # Re-sign a corrupted key blob so ONLY the utf-8 check can catch it.
    raw = bytearray(snap.encode_snapshot(_golden_cols(), NOW))
    raw[snap._HEADER.size + 8] = 0xFF  # first key byte -> invalid utf-8
    body = bytes(raw[:-4])
    import zlib

    good = body + struct.pack("<I", zlib.crc32(body) & 0xFFFFFFFF)
    with pytest.raises(snap.SnapshotError, match="utf-8"):
        snap.decode_snapshot(good)


# ---------------------------------------------------------------------
# Crash-safe write: temp + fsync + rename
# ---------------------------------------------------------------------
def test_write_failure_leaves_previous_snapshot_intact(tmp_path, monkeypatch):
    path = str(tmp_path / "gub.snap")
    snap.write_snapshot(path, _golden_cols(), NOW)
    before = open(path, "rb").read()

    def boom(_fd):
        raise OSError("disk full")

    monkeypatch.setattr(os, "fsync", boom)
    with pytest.raises(OSError):
        snap.write_snapshot(path, _cols(["k"], [1], [NOW + 1]), NOW + 1)
    monkeypatch.undo()
    # The failed write neither tore the previous file nor leaked a temp.
    assert open(path, "rb").read() == before
    assert [f for f in os.listdir(tmp_path) if f != "gub.snap"] == []
    got, _ = snap.read_snapshot(path)
    assert got.keys == ["a", "bc"]


def test_torn_temp_file_is_not_the_snapshot(tmp_path):
    # A kill -9 between the temp write and the rename leaves a stray
    # .tmp — the snapshot PATH still reads back the previous complete
    # file (the rename is the commit point).
    path = str(tmp_path / "gub.snap")
    snap.write_snapshot(path, _golden_cols(), NOW)
    torn = snap.encode_snapshot(_cols(["z"], [9], [NOW + 9]), NOW)[:30]
    with open(str(tmp_path / ".gub.snap.tmp.9999"), "wb") as f:
        f.write(torn)
    got, _ = snap.read_snapshot(path)
    assert got.keys == ["a", "bc"]


# ---------------------------------------------------------------------
# One gather to dump, one merge-commit to restore: over one device (the
# shape of `v5e1-1m`) and over the harness's 8 shards
# ---------------------------------------------------------------------
@pytest.mark.parametrize("mk", [
    lambda: one_device_store(64, g_capacity=32),
    lambda: MeshBucketStore(capacity_per_shard=64, g_capacity=32),
], ids=["one-device", "mesh"])
def test_mesh_store_snapshot_roundtrip_o1_dispatches(mk):
    src, dst = mk(), mk()
    src.apply([_req(f"m{i}", hits=2) for i in range(12)], NOW)
    before = src.device_dispatches
    cols = src.snapshot_columns(NOW)
    assert src.device_dispatches - before == 1  # ONE mesh-wide gather
    assert sorted(cols.keys) == sorted(
        _req(f"m{i}").hash_key() for i in range(12)
    )
    # Gather-only: unlike drain_keys the table keeps every key.
    assert len(src.resident_keys()) == 12
    before = dst.device_dispatches
    assert dst.commit_transfer(cols, NOW) == 12
    assert dst.device_dispatches - before == 2  # O(1): gather + scatter
    out = dst.apply([_req(f"m{i}", hits=0) for i in range(12)], NOW)
    assert [r.remaining for r in out] == [98] * 12


def test_warmup_keys_stay_out_of_the_file():
    st = MeshBucketStore(capacity_per_shard=64, g_capacity=32)
    st.warmup(NOW)
    st.apply([_req("real", hits=1)], NOW)
    cols = st.snapshot_columns(NOW)
    assert cols.keys == [_req("real").hash_key()]


def test_restore_drops_expired_rows():
    dst = one_device_store(64)
    cols = _cols(["live", "dead"], remaining=[5, 5],
                 expire=[NOW + 1000, NOW - 1])
    assert dst.commit_transfer(cols, NOW) == 1
    assert dst.resident_keys() == ["live"]


# ---------------------------------------------------------------------
# Service wiring: boot restore, shutdown save, knob-off, Loader SPI
# ---------------------------------------------------------------------
def _service(path="", loader=None, interval_s=0.0, cache=2048):
    from gubernator_tpu.config import BehaviorConfig

    beh = BehaviorConfig(
        global_sync_wait_s=3600.0, multi_region_sync_wait_s=3600.0,
        snapshot_interval_s=interval_s,
    )
    svc = V1Service(ServiceConfig(
        cache_size=cache, clock=_clock(), behaviors=beh, loader=loader,
        advertise_address="127.0.0.1:9999", snapshot_path=path,
    ))
    svc.set_peers([PeerInfo(grpc_address="127.0.0.1:9999", is_owner=True)])
    return svc


def test_service_shutdown_save_then_boot_restore(tmp_path):
    path = str(tmp_path / "svc.snap")
    svc = _service(path)
    svc.get_rate_limits(GetRateLimitsRequest(
        requests=[_req(f"b{i}", hits=3, limit=10) for i in range(8)]
    ))
    svc.close()
    assert os.path.exists(path)
    assert svc.snapshots.saves_ok == 1 and svc.snapshots.saved_lanes == 8

    svc2 = _service(path)
    try:
        assert svc2.snapshots.restore_result == "ok"
        assert svc2.snapshots.restored_lanes == 8
        r = svc2.get_rate_limits(GetRateLimitsRequest(
            requests=[_req(f"b{i}", hits=0, limit=10) for i in range(8)]
        ))
        # Zero-downtime restart: the spend survives the process.
        assert [x.remaining for x in r.responses] == [7] * 8
        # Restore is O(1) device programs, pinned by the ledger the
        # acceptance criteria name (commit = gather + scatter).
        assert svc2.snapshots.last_restore_seconds > 0
    finally:
        svc2.close()


def test_snapshot_disabled_is_the_pre_durability_daemon(tmp_path):
    path = str(tmp_path / "off.snap")
    svc = _service(path)
    svc.get_rate_limits(GetRateLimitsRequest(
        requests=[_req("reset_me", hits=3, limit=10)]
    ))
    svc.close()
    # Restart WITHOUT the knob: full reset (the legacy failure class).
    svc2 = _service("")
    try:
        assert not svc2.snapshots.enabled
        assert svc2.snapshots.restore_result == "disabled"
        r = svc2.get_rate_limits(GetRateLimitsRequest(
            requests=[_req("reset_me", hits=0, limit=10)]
        ))
        assert r.responses[0].remaining == 10
    finally:
        svc2.close()


def test_corrupt_snapshot_is_a_loud_cold_start(tmp_path):
    path = str(tmp_path / "corrupt.snap")
    with open(path, "wb") as f:
        f.write(b"GUBS" + os.urandom(64))
    svc = _service(path)
    try:
        assert svc.snapshots.restore_result == "rejected"
        assert svc.snapshots.restored_lanes == 0
        got = svc.metrics.snapshot_restores.labels(
            result="rejected"
        )._value.get()  # noqa: SLF001
        assert got == 1
        # Cold start: fresh traffic serves normally.
        r = svc.get_rate_limits(GetRateLimitsRequest(
            requests=[_req("fresh", hits=1, limit=10)]
        ))
        assert r.responses[0].remaining == 9
    finally:
        svc.close()


def test_loader_spi_rides_the_columnar_path(tmp_path):
    # Loader.load feeds ONE merge-commit; Loader.save still receives
    # CacheItems (reference backends port unchanged) — and the monotone
    # merge means a snapshot can never un-spend what a loader already
    # admitted (lower remaining wins).
    path = str(tmp_path / "both.snap")
    key = _req("merge", limit=10).hash_key()
    snap.write_snapshot(path, _cols([key], remaining=[7], expire=[NOW + 60_000],
                                    limit=10), NOW)
    loader = MockLoader()
    loader.cache_items.append(CacheItem(
        algorithm=int(Algorithm.TOKEN_BUCKET), key=key,
        value=TokenBucketItem(limit=10, duration=60_000, remaining=2,
                              created_at=NOW),
        expire_at=NOW + 60_000,
    ))
    svc = _service(path, loader=loader)
    try:
        assert loader.called["Load()"] == 1
        r = svc.get_rate_limits(GetRateLimitsRequest(
            requests=[_req("merge", hits=0, limit=10)]
        ))
        assert r.responses[0].remaining == 2  # min wins: no un-spend
    finally:
        svc.close()
    assert loader.called["Save()"] == 1
    saved = {i.key: i for i in loader.cache_items[1:]}
    assert saved[key].value.remaining == 2


def test_loader_leaky_items_roundtrip_fixed_point():
    items = [CacheItem(
        algorithm=int(Algorithm.LEAKY_BUCKET), key="leaky",
        value=LeakyBucketItem(limit=10, duration=60_000, remaining=4.5,
                              updated_at=NOW),
        expire_at=NOW + 60_000,
    )]
    cols = snap.items_to_columns(items)
    back = snap.columns_to_items(cols)
    assert isinstance(back[0].value, LeakyBucketItem)
    assert back[0].value.remaining == pytest.approx(4.5)
    assert back[0].value.updated_at == NOW


def test_interval_cadence_writes_in_the_background(tmp_path):
    path = str(tmp_path / "cadence.snap")
    svc = _service(path, interval_s=0.05)
    try:
        svc.get_rate_limits(GetRateLimitsRequest(
            requests=[_req("tick", hits=1)]
        ))
        deadline = threading.Event()
        for _ in range(100):
            if svc.snapshots.saves_ok >= 2:
                break
            deadline.wait(0.05)
        assert svc.snapshots.saves_ok >= 2, "interval writer never fired"
        assert os.path.exists(path)
        got, _ = snap.read_snapshot(path)
        assert _req("tick").hash_key() in got.keys
    finally:
        svc.close()


def test_boot_sweeps_orphaned_temp_files(tmp_path):
    # A kill -9 mid-write orphans a pid-suffixed temp this process will
    # never name again; boot must sweep siblings or a crash-looping
    # daemon accretes one ~file-sized orphan per crash.
    path = str(tmp_path / "sweep.snap")
    snap.write_snapshot(path, _golden_cols(), NOW)
    for pid in (111, 222):
        with open(str(tmp_path / f".sweep.snap.tmp.{pid}"), "wb") as f:
            f.write(b"torn")
    with open(str(tmp_path / "unrelated.tmp"), "wb") as f:
        f.write(b"keep")
    svc = _service(path)
    try:
        assert svc.snapshots.restore_result == "ok"
        assert sorted(os.listdir(tmp_path)) == ["sweep.snap", "unrelated.tmp"]
    finally:
        svc.close()


def test_restore_violation_fires_audit_surface_directly(tmp_path):
    # The windowed Auditor is constructed AFTER the boot restore (its
    # arm() baselines the restore's ledger notes away), so a commit
    # that MINTS lanes must fire the violation metric + dump from the
    # restore path itself.
    path = str(tmp_path / "mint.snap")
    key = _req("mint").hash_key()
    snap.write_snapshot(path, _cols([key], [5], [NOW + 60_000]), NOW)
    svc = _service("")
    try:
        mgr = snap.SnapshotManager(svc, path=path)
        real = svc.store.commit_transfer
        svc.store.commit_transfer = lambda cols, now: real(cols, now) + 3
        mgr.restore()
        got = svc.metrics.audit_violations.labels(
            invariant="snapshot_restore"
        )._value.get()  # noqa: SLF001
        assert got == 1
    finally:
        svc.close()


def test_audit_ledger_snapshot_conservation(tmp_path):
    # The snapshot_restore invariant: committed lanes can never exceed
    # loaded lanes; a clean save/restore cycle reconciles silently.
    from gubernator_tpu import audit

    path = str(tmp_path / "audit.snap")
    base = audit.ledger_snapshot()
    svc = _service(path)
    svc.get_rate_limits(GetRateLimitsRequest(
        requests=[_req(f"a{i}", hits=1) for i in range(4)]
    ))
    svc.close()
    svc2 = _service(path)
    try:
        d = {
            k: v - base.get(k, 0)
            for k, v in audit.ledger_snapshot().items()
        }
        assert d["snapshot_saved_lanes"] >= 4
        assert d["snapshot_loaded_lanes"] >= 4
        assert d["snapshot_committed_lanes"] <= d["snapshot_loaded_lanes"]
        assert not svc2.auditor.check_now()  # silent on a clean cycle
    finally:
        svc2.close()
