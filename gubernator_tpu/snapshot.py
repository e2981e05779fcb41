"""Durability plane: crash-safe columnar device-state snapshots.

Every daemon restart used to zero every device-resident bucket — a
deploy or crash at production traffic was a cluster-wide rate-limit
reset (ROADMAP item 4's failure class).  This module persists the
packed device arrays across process lives:

  * DUMP — ONE mesh-wide D2H gather (`store.snapshot_columns`, the
    reshard `drain_keys` playbook's all-keys variant: resolve every
    resident key's slot host-side, gather the full bucket rows in one
    device program) produces a `reshard.TransferColumns` batch, encoded
    into a versioned + CRC-checksummed on-disk format.  The gather
    rides the dispatch pipeline's plan lock (the same drain-then-lock
    envelope every wholesale state reader uses); the encode and file
    I/O run OUTSIDE every store lock, so launches resume the moment the
    gather's readback lands.
  * CRASH SAFETY — snapshots are written to a same-directory temp
    file, fsync'd, and atomically rename(2)'d over the previous
    snapshot (then the directory entry is fsync'd).  A reader can NEVER
    observe a torn file: it sees the old complete snapshot or the new
    complete snapshot, nothing in between — `kill -9` mid-write leaves
    the previous snapshot intact and loadable (chaos-tested).
  * RESTORE — at boot, ONE H2D commit (`store.commit_transfer`, the
    reshard monotone merge) replays the snapshot into the fresh device
    state.  The merge is monotone (lower remaining wins, expired rows
    dropped), so a STALE snapshot can never un-spend hits admitted
    after it was taken, and a snapshot restored late (after traffic
    already started) can never resurrect budget — the staleness slack
    is bounded by the hits admitted between the last completed snapshot
    and the crash, exactly the contract architecture.md "Durability"
    documents.
  * RING FENCING — the header stamps the membership fingerprint
    (`reshard.ring_fingerprint`) the daemon served under when the
    snapshot was written.  When the restarted daemon's bootstrap
    membership differs, the restored keys this daemon no longer owns
    are handed off through the EXISTING reshard transfer path
    (V1Service.set_peers schedules the same drain -> transfer pass a
    live ring delta gets); a matching fingerprint means ownership is
    unchanged by construction and restore costs nothing further.
    `read_snapshot(expected_ring=...)` additionally supports strict
    fencing (reject a wrong-ring file outright) for tools and
    Store-SPI deployments that want it.

Corrupt, truncated, bit-flipped, or wrong-version files are rejected
LOUDLY at boot: counted in gubernator_snapshot_restores{result=
"rejected"}, a `snapshot-rejected` flight-recorder event (auto-dump),
and a cold start — never a partial or garbage restore.

File format v1 (little-endian; golden-pinned in tests/test_snapshot.py
— layout frozen, changing ANY byte requires a version bump):

  offset  size  field
  0       4     magic "GUBS"
  4       1     version (1)
  5       1     reserved (0)
  6       4     u32 n (lanes)
  10      8     i64 saved_at_ms (daemon clock at the gather)
  18      8     u64 ring_hash (membership fingerprint; 0 = unfenced)
  26      4     u32 key_bytes (total packed key bytes)
  30      4*n   u32[n] key END offsets into the key blob
  ..      kb    key blob (utf-8, concatenated)
  ..      4*n   i32[n] algorithm
  ..      4*n   i32[n] status
  ..      8*n   i64[n] limit
  ..      8*n   i64[n] remaining
  ..      8*n   i64[n] duration
  ..      8*n   i64[n] stamp
  ..      8*n   i64[n] expire_at
  tail    4     u32 crc32 (zlib) of every preceding byte
"""

from __future__ import annotations

import os
import struct
import threading
import time
import zlib
from typing import Optional, Tuple

import numpy as np

from . import audit
from . import tracing
from .reshard import TransferColumns
from .utils.logging import category_logger

logger = category_logger("snapshot")

SNAPSHOT_MAGIC = b"GUBS"
SNAPSHOT_VERSION = 1
_HEADER = struct.Struct("<4sBBIqQI")  # magic ver rsvd n saved_at ring kb
_CRC = struct.Struct("<I")


class SnapshotError(ValueError):
    """A snapshot file that must not be restored (corrupt, truncated,
    wrong version, checksum mismatch, or — under strict fencing — a
    wrong ring fingerprint)."""


def encode_snapshot(cols: TransferColumns, saved_at_ms: int,
                    ring_hash: int = 0) -> bytes:
    """TransferColumns -> the on-disk byte layout (checksum included)."""
    n = len(cols)
    key_bytes = [k.encode("utf-8") for k in cols.keys]
    offsets = np.cumsum(
        np.fromiter((len(b) for b in key_bytes), np.uint32, count=n),
        dtype=np.uint32,
    ) if n else np.zeros(0, np.uint32)
    blob = b"".join(key_bytes)
    parts = [
        _HEADER.pack(
            SNAPSHOT_MAGIC, SNAPSHOT_VERSION, 0, n,
            int(saved_at_ms), int(ring_hash) & 0xFFFFFFFFFFFFFFFF,
            len(blob),
        ),
        offsets.tobytes(),
        blob,
        np.ascontiguousarray(cols.algorithm, np.int32).tobytes(),
        np.ascontiguousarray(cols.status, np.int32).tobytes(),
        np.ascontiguousarray(cols.limit, np.int64).tobytes(),
        np.ascontiguousarray(cols.remaining, np.int64).tobytes(),
        np.ascontiguousarray(cols.duration, np.int64).tobytes(),
        np.ascontiguousarray(cols.stamp, np.int64).tobytes(),
        np.ascontiguousarray(cols.expire_at, np.int64).tobytes(),
    ]
    body = b"".join(parts)
    return body + _CRC.pack(zlib.crc32(body) & 0xFFFFFFFF)


def decode_snapshot(raw: bytes,
                    expected_ring: Optional[int] = None
                    ) -> Tuple[TransferColumns, dict]:
    """Bytes -> (TransferColumns, meta).  Raises SnapshotError on any
    defect; `expected_ring` (strict fencing) additionally rejects a
    FENCED file (nonzero ring_hash) whose membership fingerprint does
    not match — an unfenced file (ring_hash 0) is accepted anywhere,
    the TransferColumns convention."""
    if len(raw) < _HEADER.size + _CRC.size:
        raise SnapshotError(f"truncated snapshot ({len(raw)} bytes)")
    magic, version, _rsvd, n, saved_at, ring_hash, kb = _HEADER.unpack_from(
        raw, 0
    )
    if magic != SNAPSHOT_MAGIC:
        raise SnapshotError(f"bad magic {magic!r}")
    if version != SNAPSHOT_VERSION:
        raise SnapshotError(f"unsupported snapshot version {version}")
    total = _HEADER.size + 4 * n + kb + (4 + 4 + 8 * 5) * n + _CRC.size
    if len(raw) != total:
        raise SnapshotError(
            f"truncated snapshot ({len(raw)} bytes, expected {total})"
        )
    (crc,) = _CRC.unpack_from(raw, total - _CRC.size)
    if zlib.crc32(raw[: total - _CRC.size]) & 0xFFFFFFFF != crc:
        raise SnapshotError("checksum mismatch (bit rot or torn write)")
    if (expected_ring is not None and ring_hash != 0
            and ring_hash != (int(expected_ring) & 0xFFFFFFFFFFFFFFFF)):
        raise SnapshotError(
            f"ring fingerprint mismatch (file {ring_hash:016x}, "
            f"expected {int(expected_ring) & 0xFFFFFFFFFFFFFFFF:016x})"
        )
    pos = _HEADER.size
    offsets = np.frombuffer(raw, np.uint32, count=n, offset=pos)
    pos += 4 * n
    blob = raw[pos: pos + kb]
    if n and int(offsets[-1]) != kb:
        raise SnapshotError("key blob length mismatch")
    pos += kb

    def arr(dtype, width):
        nonlocal pos
        a = np.frombuffer(raw, dtype, count=n, offset=pos)
        pos += width * n
        return a

    algorithm = arr(np.int32, 4)
    status = arr(np.int32, 4)
    limit = arr(np.int64, 8)
    remaining = arr(np.int64, 8)
    duration = arr(np.int64, 8)
    stamp = arr(np.int64, 8)
    expire_at = arr(np.int64, 8)
    keys = []
    lo = 0
    try:
        for hi in offsets:
            keys.append(blob[lo:hi].decode("utf-8"))
            lo = int(hi)
    except UnicodeDecodeError as e:
        raise SnapshotError(f"invalid utf-8 in key blob: {e}") from None
    cols = TransferColumns(
        keys=keys,
        algorithm=algorithm.copy(),
        status=status.copy(),
        limit=limit.copy(),
        remaining=remaining.copy(),
        duration=duration.copy(),
        stamp=stamp.copy(),
        expire_at=expire_at.copy(),
        ring_hash=int(ring_hash),
    )
    meta = {
        "version": version,
        "lanes": n,
        "saved_at_ms": int(saved_at),
        "ring_hash": int(ring_hash),
        "bytes": total,
    }
    return cols, meta


def write_snapshot(path: str, cols: TransferColumns, saved_at_ms: int,
                   ring_hash: int = 0) -> int:
    """Crash-safe write: encode, write to a same-directory temp file,
    fsync, atomic rename over `path`, fsync the directory.  A reader
    (or a restart after `kill -9` at ANY instant of this sequence) sees
    either the previous complete snapshot or the new complete snapshot
    — never a torn file.  Returns the byte size written."""
    raw = encode_snapshot(cols, saved_at_ms, ring_hash)
    d = os.path.dirname(os.path.abspath(path)) or "."
    tmp = os.path.join(d, f".{os.path.basename(path)}.tmp.{os.getpid()}")
    try:
        with open(tmp, "wb") as f:
            f.write(raw)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    try:
        dirfd = os.open(d, os.O_RDONLY)
        try:
            os.fsync(dirfd)
        finally:
            os.close(dirfd)
    except OSError:  # pragma: no cover — exotic fs without dir fsync
        pass
    return len(raw)


def read_snapshot(path: str, expected_ring: Optional[int] = None
                  ) -> Tuple[TransferColumns, dict]:
    """Load + verify one snapshot file (see decode_snapshot)."""
    with open(path, "rb") as f:
        raw = f.read()
    return decode_snapshot(raw, expected_ring=expected_ring)


# ---------------------------------------------------------------------
# Loader-SPI bridge: the reference's CacheItem stream over the columnar
# path, so custom persistence backends written against store.go port
# unchanged while the device work stays O(1) programs per batch.
# ---------------------------------------------------------------------
def columns_to_items(cols: TransferColumns):
    """TransferColumns -> List[store.CacheItem] (Loader.save feed)."""
    from .models.shard import _rows_to_items
    from .ops import buckets

    rows = buckets.BucketRows(
        algo=cols.algorithm, limit=cols.limit, remaining=cols.remaining,
        duration=cols.duration, stamp=cols.stamp, expire_at=cols.expire_at,
        status=cols.status,
    )
    return _rows_to_items(cols.keys, rows)


def items_to_columns(items) -> TransferColumns:
    """Iterable[store.CacheItem] -> TransferColumns (Loader.load feed:
    the whole stream commits in ONE device program via
    store.commit_transfer instead of one row-scatter per item)."""
    from .ops.buckets import LEAKY_SCALE
    from .store import LeakyBucketItem
    from .types import Algorithm

    items = list(items)
    n = len(items)
    cols = TransferColumns.empty()
    if n == 0:
        return cols
    keys, algo, status, limit, remaining, duration, stamp, expire = (
        [], np.empty(n, np.int32), np.zeros(n, np.int32),
        np.empty(n, np.int64), np.empty(n, np.int64),
        np.empty(n, np.int64), np.empty(n, np.int64), np.empty(n, np.int64),
    )
    for i, item in enumerate(items):
        v = item.value
        keys.append(item.key)
        expire[i] = int(item.expire_at)
        if isinstance(v, LeakyBucketItem):
            algo[i] = int(Algorithm.LEAKY_BUCKET)
            remaining[i] = int(v.remaining * LEAKY_SCALE)
            stamp[i] = int(v.updated_at)
        else:
            algo[i] = int(item.algorithm)
            remaining[i] = int(v.remaining)
            stamp[i] = int(v.created_at)
            status[i] = int(v.status)
        limit[i] = int(v.limit)
        duration[i] = int(v.duration)
    return TransferColumns(
        keys=keys, algorithm=algo, status=status, limit=limit,
        remaining=remaining, duration=duration, stamp=stamp,
        expire_at=expire,
    )


class SnapshotManager:
    """Dump/restore orchestration for one V1Service: restore at boot,
    save on close()/SIGTERM and on the GUBER_SNAPSHOT_INTERVAL cadence.
    Disabled entirely (every method an early return) when no path is
    configured — GUBER_SNAPSHOT=0 is exactly the pre-durability
    daemon."""

    def __init__(self, service, path: str = "", interval_s: float = 0.0):
        self.service = service
        self.path = path or ""
        self.interval_s = max(float(interval_s or 0.0), 0.0)
        self.enabled = bool(self.path)
        # Host-side counters (exported via Metrics.observe_snapshot and
        # served raw in GET /debug/status).
        self.saves_ok = 0
        self.saves_failed = 0
        self.restored_lanes = 0
        self.saved_lanes = 0
        self.restore_result = "disabled" if not self.enabled else "pending"
        self.last_save_unix = 0.0
        self.last_save_bytes = 0
        self.last_save_seconds = 0.0
        self.last_restore_seconds = 0.0
        # Ring fingerprint the restored file was saved under (None =
        # nothing restored / unfenced): V1Service.set_peers compares it
        # against the bootstrap membership and hands off no-longer-owned
        # keys through the reshard transfer path on mismatch.
        self.restored_ring_hash: Optional[int] = None
        self._save_lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _sweep_orphan_temps(self) -> None:
        """Remove stale `.{name}.tmp.{pid}` siblings a crash mid-write
        left behind (each process writes a pid-suffixed temp and only
        unlinks its OWN on a caught exception — `kill -9` orphans it;
        a crash-looping daemon must not accrete one ~file-sized orphan
        per crash).  Boot-time only: this daemon owns the path, so any
        temp here is dead by definition."""
        d = os.path.dirname(os.path.abspath(self.path)) or "."
        prefix = f".{os.path.basename(self.path)}.tmp."
        try:
            names = os.listdir(d)
        except OSError:
            return
        for name in names:
            if name.startswith(prefix):
                try:
                    os.unlink(os.path.join(d, name))
                    logger.info("removed orphaned snapshot temp %s", name)
                except OSError:  # pragma: no cover — raced/forbidden
                    pass

    # -- restore (boot) ------------------------------------------------
    def restore(self) -> int:
        """Load + verify + ONE H2D merge-commit.  Any defect is a loud
        cold start: counted, flight-recorder `snapshot-rejected` event
        (auto-dump), logged — never a partial restore.  Returns lanes
        committed."""
        if not self.enabled:
            return 0
        self._sweep_orphan_temps()
        m = self.service.metrics
        if not os.path.exists(self.path):
            self.restore_result = "absent"
            if m is not None:
                m.snapshot_restores.labels(result="absent").inc()
            return 0
        t0 = time.perf_counter()
        try:
            cols, meta = read_snapshot(self.path)
        except (SnapshotError, OSError) as e:
            self.restore_result = "rejected"
            if m is not None:
                m.snapshot_restores.labels(result="rejected").inc()
            tracing.record_event(
                "snapshot-rejected", path=self.path, reason=str(e)
            )
            logger.warning(
                "snapshot %s REJECTED (cold start): %s", self.path, e
            )
            return 0
        audit.note("snapshot_loaded_lanes", len(cols))
        now_ms = self.service.clock.now_ms()
        committed = self.service.store.commit_transfer(cols, now_ms)
        audit.note("snapshot_committed_lanes", committed)
        if committed > len(cols):
            # The snapshot_restore conservation break (a commit minting
            # lanes) must fire HERE, not ride the windowed Auditor: the
            # auditor is constructed AFTER the boot restore (its arm()
            # baselines these notes away) and its first-pass extent
            # seeding would swallow a one-shot boot excess anyway.
            if m is not None:
                m.audit_violations.labels(invariant="snapshot_restore").inc()
            tracing.record_event(
                "audit-violation", invariant="snapshot_restore",
                excess=committed - len(cols),
            )
            logger.warning(
                "snapshot restore VIOLATION: committed %d lanes from a "
                "%d-lane file", committed, len(cols),
            )
        self.last_restore_seconds = time.perf_counter() - t0
        self.restored_lanes = committed
        self.restore_result = "ok"
        self.restored_ring_hash = meta["ring_hash"] or None
        if m is not None:
            m.snapshot_restores.labels(result="ok").inc()
            m.snapshot_lanes.labels(direction="restored").inc(committed)
        logger.info(
            "restored %d/%d snapshot lanes from %s "
            "(saved_at_ms=%d ring=%016x, %.1fms)",
            committed, meta["lanes"], self.path, meta["saved_at_ms"],
            meta["ring_hash"], self.last_restore_seconds * 1e3,
        )
        return committed

    # -- save (interval / close / SIGTERM) -----------------------------
    def save_now(self, reason: str = "interval") -> bool:
        """One dump: gather (under the store's drain-then-lock envelope,
        one device program), then encode + crash-safe write OUTSIDE
        every store lock.  Serialized against concurrent saves; returns
        success."""
        if not self.enabled:
            return False
        m = self.service.metrics
        with self._save_lock:
            t0 = time.perf_counter()
            try:
                now_ms = self.service.clock.now_ms()
                cols = self.service.store.snapshot_columns(now_ms)
                size = write_snapshot(
                    self.path, cols, now_ms,
                    ring_hash=getattr(self.service, "ring_hash", 0),
                )
            except Exception as e:  # noqa: BLE001 — a failed dump must
                # never take the serving path (or shutdown) down.
                self.saves_failed += 1
                if m is not None:
                    m.snapshot_writes.labels(result="error").inc()
                logger.warning(
                    "snapshot save (%s) to %s failed: %s",
                    reason, self.path, e,
                )
                return False
            self.last_save_seconds = time.perf_counter() - t0
            self.last_save_unix = time.time()
            self.last_save_bytes = size
            self.saves_ok += 1
            self.saved_lanes += len(cols)
            audit.note("snapshot_saved_lanes", len(cols))
            if m is not None:
                m.snapshot_writes.labels(result="ok").inc()
                m.snapshot_lanes.labels(direction="saved").inc(len(cols))
            logger.debug(
                "snapshot save (%s): %d lanes, %d bytes, %.1fms",
                reason, len(cols), size, self.last_save_seconds * 1e3,
            )
            return True

    def start(self) -> None:
        """Start the background cadence (no-op when disabled or
        interval 0 = shutdown-only snapshots)."""
        if not self.enabled or self.interval_s <= 0 or self._thread:
            return
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="snapshot-writer"
        )
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.save_now("interval")
            except Exception:  # noqa: BLE001 — the writer must never die
                logger.exception("snapshot interval save failed")

    def stop(self, timeout_s: float = 5.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=timeout_s)
            self._thread = None

    def snapshot(self) -> dict:
        """The /debug/status "snapshot" section."""
        return {
            "enabled": self.enabled,
            "path": self.path,
            "intervalS": self.interval_s,
            "savesOk": self.saves_ok,
            "savesFailed": self.saves_failed,
            "savedLanes": self.saved_lanes,
            "restore": self.restore_result,
            "restoredLanes": self.restored_lanes,
            "lastSaveUnix": self.last_save_unix,
            "lastSaveBytes": self.last_save_bytes,
            "lastSaveSeconds": round(self.last_save_seconds, 4),
            "lastRestoreSeconds": round(self.last_restore_seconds, 4),
        }
