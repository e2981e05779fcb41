"""ctypes loader for the C++ host runtime (host_runtime.cpp).

Compiles the shared library on first import with g++ (cached next to
the source, rebuilt when the source hash changes) and wraps it in
Python classes with the same interface as the pure-Python twins
(models/slot_table.py).  If no compiler is available the package
falls back to the Python implementation — `available()` reports which
path is active.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import List, Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "host_runtime.cpp")
_LIB_TMPL = os.path.join(_HERE, "_host_runtime_{digest}.so")

_lib = None
_lib_err: Optional[str] = None
_build_lock = threading.Lock()

# THE compile flags, pinned in one place: `make native`, the on-import
# rebuild and the tier-1 source-hash check all go through here, so a
# flag tweak cannot fork a differently-built .so from the one the
# hash-suffix discipline vouches for.
CXX = "g++"
CXX_FLAGS = ["-O2", "-std=c++17", "-shared", "-fPIC"]


def source_digest() -> str:
    """First 16 hex chars of sha256(host_runtime.cpp) — the .so name
    suffix (`_host_runtime_<digest>.so`).  A checked-in binary whose
    suffix does not match the current source is stale by definition
    (tests/test_native_build.py enforces this in tier-1)."""
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:16]


def lib_path() -> str:
    """Path the current source compiles to (exists or not)."""
    return _LIB_TMPL.format(digest=source_digest())


def build() -> str:
    """Compile the runtime for the current source if its .so is absent
    (the `make native` entry point); returns the .so path."""
    path = lib_path()
    if not os.path.exists(path):
        err = _compile(path)
        if err is not None:
            raise RuntimeError(err)
    return path


def _compile(lib_path: str) -> Optional[str]:
    """Compile the runtime to lib_path via unique-tmp + rename; returns
    an error string or None."""
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = [CXX, *CXX_FLAGS, _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib_path)
        return None
    except (OSError, subprocess.SubprocessError) as e:
        return f"native build failed: {e}"


def _build() -> Optional[ctypes.CDLL]:
    global _lib_err
    lib_path = _LIB_TMPL.format(digest=source_digest())
    if os.path.exists(lib_path):
        # Refresh mtime: the stale-prune below is age-based, and an
        # old-mtime .so being REUSED by this process must not look
        # prunable to a concurrently starting process (TOCTOU between
        # our exists() and CDLL()).
        try:
            os.utime(lib_path)
        except OSError:
            pass
    else:
        err = _compile(lib_path)
        if err is not None:
            _lib_err = err
            return None
    try:
        lib = ctypes.CDLL(lib_path)
    except OSError:
        # TOCTOU: between our exists()/utime() and the CDLL, another
        # process's age-based prune may have deleted an old .so.  The
        # compile is cheap and writes via unique-tmp + rename, so retry
        # once through the build path instead of falling back to the
        # slow Python slot table for this process's whole lifetime.
        err = _compile(lib_path)
        if err is not None:
            _lib_err = err
            return None
        try:
            lib = ctypes.CDLL(lib_path)
        except OSError as e:
            _lib_err = f"native load failed: {e}"
            return None

    # Prune superseded builds: each source edit leaves a hash-named .so
    # behind, which otherwise accumulates without bound.  Only delete
    # files comfortably older than any concurrently-starting process's
    # build window — a racing starter with a different source digest
    # must not lose its fresh .so between write and dlopen.
    import glob
    import time

    cutoff = time.time() - 600
    for stale in glob.glob(_LIB_TMPL.format(digest="*")):
        if stale != lib_path:
            try:
                if os.path.getmtime(stale) < cutoff:
                    os.remove(stale)
            except OSError:
                pass

    c = ctypes
    lib.gt_table_new.restype = c.c_void_p
    lib.gt_table_new.argtypes = [c.c_int64]
    lib.gt_table_new_hashed.restype = c.c_void_p
    lib.gt_table_new_hashed.argtypes = [c.c_int64, c.c_uint64, c.c_uint64]
    lib.gt_table_free.argtypes = [c.c_void_p]
    lib.gt_table_len.restype = c.c_int64
    lib.gt_table_len.argtypes = [c.c_void_p]
    lib.gt_table_stats.argtypes = [c.c_void_p, c.POINTER(c.c_int64)]
    lib.gt_table_index_stats.argtypes = [c.c_void_p, c.POINTER(c.c_int64)]
    lib.gt_table_evictions.restype = c.c_int64
    lib.gt_table_evictions.argtypes = [c.c_void_p]
    lib.gt_table_front_evictions.restype = c.c_int64
    lib.gt_table_front_evictions.argtypes = [c.c_void_p]
    lib.gt_table_generation.restype = c.c_uint64
    lib.gt_table_generation.argtypes = [c.c_void_p]
    lib.gt_table_get_slot.restype = c.c_int32
    lib.gt_table_get_slot.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
    lib.gt_table_lookup_or_assign.argtypes = [
        c.c_void_p, c.c_char_p, c.c_int64, c.c_int64,
        c.POINTER(c.c_int32), c.POINTER(c.c_uint8),
    ]
    lib.gt_table_remove.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
    lib.gt_table_set_expire.argtypes = [c.c_void_p, c.c_int32, c.c_int64]
    lib.gt_table_get_expire.argtypes = [c.c_void_p, c.c_void_p, c.c_int64, c.c_void_p]
    lib.gt_table_commit.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_int64]
    lib.gt_table_commit_keys.argtypes = [
        c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_int64,
    ]
    lib.gt_table_keys_size.argtypes = [c.c_void_p, c.POINTER(c.c_int64), c.POINTER(c.c_int64)]
    lib.gt_table_keys.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p]
    lib.gt_batch_begin.restype = c.c_void_p
    lib.gt_batch_begin.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p, c.c_int64, c.c_int64]
    lib.gt_batch_next_round.restype = c.c_int64
    lib.gt_batch_next_round.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p]
    lib.gt_batch_commit_round.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p]
    lib.gt_batch_plan.restype = c.c_int64
    lib.gt_batch_plan.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p]
    lib.gt_batch_plan_grouped.restype = c.c_int64
    lib.gt_batch_plan_grouped.argtypes = [
        c.c_void_p,  # batch
        c.c_void_p, c.c_void_p,  # algo, behavior
        c.c_void_p, c.c_void_p, c.c_void_p,  # hits, limit, duration
        c.c_void_p, c.c_void_p,  # greg_expire, greg_duration
        c.c_int32,  # RESET_REMAINING mask
        c.c_void_p, c.c_void_p, c.c_void_p,  # round_id, slots, exists
        c.c_void_p, c.c_void_p,  # occ, write
    ]
    lib.gt_batch_commit_plan.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p]
    lib.gt_batch_free.argtypes = [c.c_void_p]
    lib.gt_mesh_begin.restype = c.c_void_p
    lib.gt_mesh_begin.argtypes = [
        c.c_void_p, c.c_int64,  # tables[S], S
        c.c_void_p, c.c_void_p, c.c_int64, c.c_int64,  # keys, offsets, n, now
        c.c_void_p,  # counts[S] out
    ]
    lib.gt_mesh_plan_grouped.restype = c.c_int64
    lib.gt_mesh_plan_grouped.argtypes = [
        c.c_void_p,  # mesh plan
        c.c_void_p, c.c_void_p,  # algo, behavior
        c.c_void_p, c.c_void_p, c.c_void_p,  # hits, limit, duration
        c.c_void_p, c.c_void_p,  # greg_expire, greg_duration
        c.c_int32, c.c_int64,  # reset mask, P
        c.c_void_p, c.c_void_p, c.c_void_p,  # slot, rid, exists
        c.c_void_p, c.c_void_p, c.c_void_p,  # occ, write, pos
    ]
    lib.gt_mesh_encode_wire.restype = c.c_int32
    lib.gt_mesh_encode_wire.argtypes = [
        c.c_int64, c.c_int64, c.c_int64,  # S, P, n
        c.c_void_p, c.c_void_p, c.c_void_p,  # slot, exists, write
        c.c_void_p, c.c_void_p, c.c_void_p,  # occ, rid, pos
        c.c_void_p, c.c_void_p,  # algo, behavior
        c.c_void_p, c.c_void_p, c.c_void_p,  # hits, limit, duration
        c.c_void_p, c.c_void_p,  # greg_expire, greg_duration
        c.c_int64, c.c_int64,  # now_ms, n_rounds
        c.c_int32, c.c_int32,  # narrow, force_lanes
        c.c_int64, c.c_int64,  # dict_row, lane_row (words)
        c.c_void_p, c.c_void_p,  # wire out, config_rows out
    ]
    lib.gt_mesh_finish_narrow.argtypes = [
        c.c_void_p, c.c_void_p, c.c_int64,
        c.c_void_p, c.c_void_p, c.c_void_p,
    ]
    lib.gt_mesh_finish_wide.argtypes = [
        c.c_void_p, c.c_void_p,
        c.c_void_p, c.c_void_p, c.c_void_p,
    ]
    lib.gt_mesh_free.argtypes = [c.c_void_p]
    lib.gt_table_enable_back.argtypes = [c.c_void_p, c.c_int64]
    lib.gt_table_tier_stats.argtypes = [c.c_void_p, c.c_void_p]
    lib.gt_table_move_counts.argtypes = [
        c.c_void_p, c.POINTER(c.c_int64), c.POINTER(c.c_int64),
    ]
    lib.gt_table_take_moves.restype = c.c_int32
    lib.gt_table_take_moves.argtypes = [
        c.c_void_p, c.c_void_p, c.c_int64, c.POINTER(c.c_int64)
    ]
    lib.gt_table_back_size.argtypes = [
        c.c_void_p, c.POINTER(c.c_int64), c.POINTER(c.c_int64),
    ]
    lib.gt_table_back_keys.argtypes = [c.c_void_p] + [c.c_void_p] * 4
    lib.gt_fnv1_batch.argtypes = [c.c_void_p, c.c_void_p, c.c_int64, c.c_int32, c.c_void_p]
    lib.gt_json_parse.restype = c.c_void_p
    lib.gt_json_parse.argtypes = [c.c_char_p, c.c_int64]
    lib.gt_json_n.restype = c.c_int64
    lib.gt_json_n.argtypes = [c.c_void_p]
    lib.gt_json_hk_bytes.restype = c.c_int64
    lib.gt_json_hk_bytes.argtypes = [c.c_void_p]
    lib.gt_json_fill.argtypes = [c.c_void_p] + [c.c_void_p] * 10
    lib.gt_json_free.argtypes = [c.c_void_p]
    lib.gt_json_render.restype = c.c_int64
    lib.gt_json_render.argtypes = [
        c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_int64,
        c.c_void_p, c.c_int64, c.c_char_p, c.c_void_p, c.c_char_p,
        c.c_int64,
    ]
    lib.gt_frame_parse.restype = c.c_void_p
    lib.gt_frame_parse.argtypes = [
        c.c_char_p, c.c_int64, c.c_int32, c.c_void_p,
    ]
    lib.gt_frame_fill.argtypes = [c.c_void_p] + [c.c_void_p] * 3
    lib.gt_frame_free.argtypes = [c.c_void_p]
    lib.gt_http_start.restype = c.c_void_p
    lib.gt_http_start.argtypes = [c.c_char_p, c.c_int, c.c_int, c.c_char_p]
    lib.gt_http_port.restype = c.c_int
    lib.gt_http_port.argtypes = [c.c_void_p]
    lib.gt_http_acceptor_count.restype = c.c_int
    lib.gt_http_acceptor_count.argtypes = [c.c_void_p]
    lib.gt_http_acceptor_stats.argtypes = [c.c_void_p, c.c_void_p]
    lib.gt_http_stats.argtypes = [c.c_void_p, c.c_void_p]
    lib.gt_http_drain_sends.restype = c.c_int64
    lib.gt_http_drain_sends.argtypes = [c.c_void_p, c.c_void_p, c.c_int64]
    lib.gt_mono_ns.restype = c.c_int64
    lib.gt_mono_ns.argtypes = []
    lib.gt_http_next.restype = c.c_int
    lib.gt_http_next.argtypes = [c.c_void_p, c.c_int64, c.c_void_p]
    lib.gt_http_respond.argtypes = [
        c.c_void_p, c.c_uint64, c.c_int, c.c_char_p, c.c_char_p,
        c.c_char_p, c.c_int64,
    ]
    lib.gt_http_shutdown.argtypes = [c.c_void_p]
    lib.gt_http_free.argtypes = [c.c_void_p]
    lib.gt_ingress_new.restype = c.c_void_p
    lib.gt_ingress_new.argtypes = []
    lib.gt_ingress_set_ring.argtypes = [
        c.c_void_p, c.c_void_p, c.c_void_p, c.c_int64,  # vh, vself, nv
        c.c_int32, c.c_int32,                           # all_self, enabled
        c.c_int64, c.c_int64,                # cap_lanes, max_frame_lanes
        c.c_int32, c.c_int32,                # behavior_mask, hash_variant
        c.c_int32,                           # express_mask
    ]
    lib.gt_ingress_submit.restype = c.c_int
    lib.gt_ingress_submit.argtypes = [c.c_void_p, c.c_void_p, c.c_uint64]
    lib.gt_ingress_take.restype = c.c_int
    lib.gt_ingress_take.argtypes = [
        c.c_void_p, c.c_int64, c.c_int64,
        c.POINTER(c.c_void_p), c.c_void_p,
    ]
    lib.gt_ingress_complete.argtypes = [c.c_void_p] + [c.c_void_p] * 4
    lib.gt_ingress_fail.argtypes = [
        c.c_void_p, c.c_int, c.c_char_p, c.c_char_p, c.c_char_p, c.c_int64,
    ]
    lib.gt_ingress_stop.argtypes = [c.c_void_p]
    lib.gt_ingress_stats.argtypes = [c.c_void_p, c.c_void_p]
    lib.gt_ingress_free.argtypes = [c.c_void_p]
    lib.gt_name_groups.restype = c.c_int64
    lib.gt_name_groups.argtypes = (
        [c.c_void_p, c.c_void_p, c.c_int64] + [c.c_void_p] * 3
        + [c.c_int64, c.c_void_p]
    )
    lib.gt_cms_fold.restype = c.c_int64
    lib.gt_cms_fold.argtypes = (
        [c.c_void_p, c.c_int32, c.c_int64] + [c.c_void_p] * 3 + [c.c_int64]
        + [c.c_void_p, c.c_int64, c.c_int64, c.c_int64, c.c_void_p]
    )
    return lib


def _get_lib() -> Optional[ctypes.CDLL]:
    global _lib
    if _lib is None and _lib_err is None:
        with _build_lock:
            if _lib is None and _lib_err is None:
                _lib = _build()
    return _lib


def available() -> bool:
    return _get_lib() is not None


def build_error() -> Optional[str]:
    _get_lib()
    return _lib_err


def pack_keys(keys) -> Tuple[np.ndarray, np.ndarray]:
    """Concatenate utf-8 keys into (bytes buffer, offsets[n+1])."""
    bs = [k.encode("utf-8") if isinstance(k, str) else k for k in keys]
    offsets = np.zeros(len(bs) + 1, dtype=np.int64)
    np.cumsum([len(b) for b in bs], out=offsets[1:])
    return np.frombuffer(b"".join(bs), dtype=np.uint8), offsets


class PackedKeys:
    """Hash keys kept in PACKED form (one utf-8 buffer + offsets[n+1])
    end-to-end: the C++ JSON parser emits this, the batch planner
    consumes it, and per-lane Python strings only materialize for the
    rare slow/error lanes — the edge never pays n string objects per
    batch."""

    __slots__ = ("buf", "offsets")

    def __init__(self, buf: np.ndarray, offsets: np.ndarray):
        self.buf = buf
        self.offsets = offsets

    def __len__(self) -> int:
        return len(self.offsets) - 1

    def __getitem__(self, i: int) -> str:
        o = self.offsets
        return bytes(self.buf[o[i]:o[i + 1]]).decode("utf-8")

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    @staticmethod
    def concat(parts: "List[PackedKeys]") -> "PackedKeys":
        """Concatenate packed key batches without materializing
        strings (the ColumnarBatcher's multi-submission coalesce)."""
        bufs = [p.buf for p in parts]
        offs = [parts[0].offsets]
        base = int(parts[0].offsets[-1])
        for p in parts[1:]:
            offs.append(p.offsets[1:] + base)
            base += int(p.offsets[-1])
        return PackedKeys(np.concatenate(bufs), np.concatenate(offs))

    def subset(self, idx) -> "PackedKeys":
        """Vectorized selection of lanes `idx` (no per-lane Python)."""
        idx = np.asarray(idx, dtype=np.int64)
        o = self.offsets
        starts = o[idx]
        lens = o[idx + 1] - starts
        new_off = np.zeros(len(idx) + 1, dtype=np.int64)
        np.cumsum(lens, out=new_off[1:])
        total = int(new_off[-1])
        pos = np.repeat(starts - new_off[:-1], lens) + np.arange(total, dtype=np.int64)
        return PackedKeys(self.buf[pos], new_off)


def as_packed(keys) -> Tuple[np.ndarray, np.ndarray]:
    """(buf, offsets) for either a PackedKeys or a list of strings."""
    if isinstance(keys, PackedKeys):
        return keys.buf, keys.offsets
    return pack_keys(keys)


class ParsedJson:
    """Result of the native GetRateLimits JSON parse (gt_json_parse):
    kernel-ready columns + packed hash keys + validation codes +
    (offset, len) spans of each name/unique_key in the body."""

    __slots__ = ("n", "algo", "behavior", "hits", "limit", "duration",
                 "err", "hash_keys", "nspan", "ukspan", "body")

    def __init__(self, n, algo, behavior, hits, limit, duration, err,
                 hash_keys, nspan, ukspan, body):
        self.n = n
        self.algo = algo
        self.behavior = behavior
        self.hits = hits
        self.limit = limit
        self.duration = duration
        self.err = err
        self.hash_keys = hash_keys
        self.nspan = nspan
        self.ukspan = ukspan
        self.body = body

    def name_at(self, i: int) -> str:
        off, ln = self.nspan[2 * i], self.nspan[2 * i + 1]
        return self.body[off:off + ln].decode("utf-8")

    def unique_key_at(self, i: int) -> str:
        off, ln = self.ukspan[2 * i], self.ukspan[2 * i + 1]
        return self.body[off:off + ln].decode("utf-8")


def parse_json_batch(body: bytes) -> Optional[ParsedJson]:
    """Parse a /v1/GetRateLimits body natively; None means "use the
    Python fallback" (escape sequences in keys, floats, behavior flag
    lists, malformed JSON — anything beyond the common wire shape)."""
    lib = _get_lib()
    if lib is None:
        return None
    h = lib.gt_json_parse(body, len(body))
    if not h:
        return None
    try:
        n = int(lib.gt_json_n(h))
        hkb = int(lib.gt_json_hk_bytes(h))
        algo = np.empty(n, dtype=np.int32)
        behavior = np.empty(n, dtype=np.int32)
        hits = np.empty(n, dtype=np.int64)
        limit = np.empty(n, dtype=np.int64)
        duration = np.empty(n, dtype=np.int64)
        err = np.empty(n, dtype=np.uint8)
        hk = np.empty(hkb, dtype=np.uint8)
        hkoff = np.empty(n + 1, dtype=np.int64)
        nspan = np.empty(2 * n, dtype=np.int64)
        ukspan = np.empty(2 * n, dtype=np.int64)
        lib.gt_json_fill(
            h, algo.ctypes.data, behavior.ctypes.data, hits.ctypes.data,
            limit.ctypes.data, duration.ctypes.data, err.ctypes.data,
            hk.ctypes.data, hkoff.ctypes.data, nspan.ctypes.data,
            ukspan.ctypes.data,
        )
    finally:
        lib.gt_json_free(h)
    return ParsedJson(n, algo, behavior, hits, limit, duration, err,
                      PackedKeys(hk, hkoff), nspan, ukspan, body)


class _GtFrameInfo(ctypes.Structure):
    _fields_ = [(name, ctypes.c_int64) for name in (
        "n", "name_off_pos", "name_blob_pos", "uk_off_pos", "uk_blob_pos",
        "algo_pos", "beh_pos", "hits_pos", "limit_pos", "dur_pos",
        "trace_pos", "trace_count", "hk_bytes",
    )]


_INGRESS_FRAME_KIND = 5  # wire._FRAME_KIND_INGRESS_REQ


def parse_ingress_frame(raw: bytes):
    """Parse a public GUBC ingress frame (kind 5) natively: one
    GIL-released pass validates the frame, slices every column (numpy
    views of `raw`, zero-copy numerics), builds the packed hash keys
    and stamps per-lane validation codes — the wire.decode_ingress_frame
    fast path.  None means "use the Python decode" (no native runtime,
    or a malformed frame whose exact error wording the Python path
    owns)."""
    lib = _get_lib()
    if lib is None:
        return None
    info = _GtFrameInfo()
    h = lib.gt_frame_parse(raw, len(raw), _INGRESS_FRAME_KIND,
                           ctypes.byref(info))
    if not h:
        return None
    try:
        n = int(info.n)
        hk = np.empty(max(int(info.hk_bytes), 1), dtype=np.uint8)
        hkoff = np.empty(n + 1, dtype=np.int64)
        err = np.empty(max(n, 1), dtype=np.uint8)
        lib.gt_frame_fill(h, hk.ctypes.data, hkoff.ctypes.data,
                          err.ctypes.data)
    finally:
        lib.gt_frame_free(h)
    from .. import wire  # deferred: wire imports this package lazily

    no = np.frombuffer(raw, np.uint32, n + 1, int(info.name_off_pos))
    uo = np.frombuffer(raw, np.uint32, n + 1, int(info.uk_off_pos))
    nb = raw[int(info.name_blob_pos):int(info.name_blob_pos) + int(no[-1] if n else 0)]
    ub = raw[int(info.uk_blob_pos):int(info.uk_blob_pos) + int(uo[-1] if n else 0)]
    try:
        # Untrusted-edge parity with wire._check_utf8_blobs: invalid
        # UTF-8 must 400 here, not 500 later inside a slow-lane decode.
        nb.decode("utf-8")
        ub.decode("utf-8")
    except UnicodeDecodeError:
        return None  # the Python decode owns the exact error wording
    trace_ctx = None
    if info.trace_count > 0:
        trace_ctx, _ = wire.unpack_trace_entries(raw, int(info.trace_pos))
    return wire.FrameIngressColumns(
        n, nb, no, ub, uo,
        np.frombuffer(raw, np.int32, n, int(info.algo_pos)),
        np.frombuffer(raw, np.int32, n, int(info.beh_pos)),
        np.frombuffer(raw, np.int64, n, int(info.hits_pos)),
        np.frombuffer(raw, np.int64, n, int(info.limit_pos)),
        np.frombuffer(raw, np.int64, n, int(info.dur_pos)),
        trace_ctx=trace_ctx,
        err=err[:n],
        packed=PackedKeys(hk[:int(info.hk_bytes)], hkoff),
    )


def render_json(status, limit, remaining, reset, overrides: dict) -> Optional[bytes]:
    """Build the GetRateLimits response body natively; `overrides` maps
    lane index -> pre-rendered JSON bytes (error / forwarded lanes).
    None when the native runtime is unavailable."""
    lib = _get_lib()
    if lib is None:
        return None
    n = len(status)
    status = np.ascontiguousarray(status, dtype=np.int32)
    limit = np.ascontiguousarray(limit, dtype=np.int64)
    remaining = np.ascontiguousarray(remaining, dtype=np.int64)
    reset = np.ascontiguousarray(reset, dtype=np.int64)
    if overrides:
        items = sorted(overrides.items())
        ov_idx = np.asarray([i for i, _ in items], dtype=np.int64)
        bufs = [b for _, b in items]
        ov_off = np.zeros(len(bufs) + 1, dtype=np.int64)
        np.cumsum([len(b) for b in bufs], out=ov_off[1:])
        ov_buf = b"".join(bufs)
    else:
        ov_idx = np.empty(0, dtype=np.int64)
        ov_off = np.zeros(1, dtype=np.int64)
        ov_buf = b""
    n_ov = len(ov_idx)
    # Single-pass render into a worst-case buffer (<=129 bytes per
    # plain lane; see gt_json_render).
    cap = 32 + n * 160 + len(ov_buf) + n_ov * 2
    out = ctypes.create_string_buffer(cap)
    size = lib.gt_json_render(
        status.ctypes.data, limit.ctypes.data, remaining.ctypes.data,
        reset.ctypes.data, n, ov_idx.ctypes.data, n_ov, ov_buf,
        ov_off.ctypes.data, out, cap,
    )
    if size < 0:
        return None  # cap overflow (cannot happen by construction)
    return out.raw[:size]


def fnv1_batch(keys, variant_1a: bool = False) -> np.ndarray:
    """Batch FNV-1/1a 64 hash (replicated_hash.go:31); pure-Python
    fallback when the native build is unavailable."""
    lib = _get_lib()
    out = np.empty(len(keys), dtype=np.uint64)
    if len(keys) == 0:
        return out
    if lib is None:
        from ..utils import hashing

        fn = hashing.fnv1a_64 if variant_1a else hashing.fnv1_64
        for i, k in enumerate(keys):
            out[i] = fn(k.encode("utf-8") if isinstance(k, str) else k)
        return out
    buf, offsets = as_packed(keys)
    lib.gt_fnv1_batch(
        buf.ctypes.data, offsets.ctypes.data, len(keys),
        1 if variant_1a else 0, out.ctypes.data,
    )
    return out


def _i64(a) -> np.ndarray:
    return np.ascontiguousarray(a, dtype=np.int64)


def name_groups(names, hits, name_lens, uk_lens, lane_const: int):
    """The tenant fold's aggregation of one batch (gt_name_groups):
    `names` a list of strings or a PackedKeys, the rest one value a
    lane.  Returns (uh, first, inv, lanes, hits, bytes): the distinct
    FNV-1 name hashes ascending, each one's first lane, every lane's
    position in `uh`, and lanes / hits / ingress bytes by name (a
    lane's bytes are its two lengths plus `lane_const`), in one pass
    that holds no interpreter; numpy's unique and three sums when the
    native build is unavailable."""
    lib = _get_lib()
    hits, name_lens, uk_lens = _i64(hits), _i64(name_lens), _i64(uk_lens)
    if lib is None:
        uh, first, inv = np.unique(
            fnv1_batch(names), return_index=True, return_inverse=True
        )
        sums = np.zeros((3, len(uh)), dtype=np.int64)
        np.add.at(sums[0], inv, 1)
        np.add.at(sums[1], inv, hits)
        np.add.at(sums[2], inv, name_lens + uk_lens + int(lane_const))
        return uh, first, inv, sums[0], sums[1], sums[2]
    buf, off = as_packed(names)
    off = _i64(off)
    n = len(off) - 1
    out = np.empty((6, n), dtype=np.int64)
    m = lib.gt_name_groups(
        buf.ctypes.data, off.ctypes.data, n, hits.ctypes.data,
        name_lens.ctypes.data, uk_lens.ctypes.data, int(lane_const),
        out.ctypes.data,
    )
    return (out[0, :m].view(np.uint64), out[1, :m], out[5].view(np.intp),
            out[2, :m], out[3, :m], out[4, :m])


def cms_fold(tab: np.ndarray, salts: np.ndarray, hashes, weights, tracked,
             floor: int, topk: int):
    """Fold one batch into a count-min table (gt_cms_fold): `tab`
    i64[depth, width] and `salts` u64[depth] are the CALLER's arrays,
    written in place — the one copy of the counts, under whatever lock
    the caller guards them with.  `hashes` u64[n]; `weights` one i64 a
    hash, None for 1 each.  Returns (ud, first, est, t_idx, cand): the
    distinct hashes in order of first occurrence, each one's first lane
    and its estimate after the adds; for each hash of `tracked` (u64,
    or None) its position in `ud` or -1; and the positions of the
    untracked hashes whose estimate is above `floor` — at most `topk`,
    the largest, the later position among equals.  The interpreter is
    released for the pass; the same answer from numpy when the native
    build is unavailable."""
    if (tab.dtype != np.int64 or not tab.flags.c_contiguous
            or salts.dtype != np.uint64 or len(salts) != tab.shape[0]):
        raise ValueError("cms_fold wants i64[depth, width] and u64[depth]")
    hashes = np.ascontiguousarray(hashes, dtype=np.uint64)
    n = len(hashes)
    if weights is not None:
        weights = _i64(weights)
        if len(weights) != n:
            raise ValueError("cms_fold wants one weight a hash")
    tracked = np.ascontiguousarray(
        () if tracked is None else tracked, dtype=np.uint64
    )
    nt, topk = len(tracked), max(int(topk), 0)
    lib = _get_lib()
    if lib is None:
        return _cms_fold_numpy(tab, salts, hashes, weights, tracked,
                               floor, topk)
    out = np.empty(3 * n + nt + 1 + topk, dtype=np.int64)
    m = lib.gt_cms_fold(
        tab.ctypes.data, tab.shape[0], tab.shape[1], salts.ctypes.data,
        hashes.ctypes.data,
        None if weights is None else weights.ctypes.data, n,
        tracked.ctypes.data, nt, int(floor), topk, out.ctypes.data,
    )
    t0 = 3 * n
    c0 = t0 + nt + 1
    return (out[:m].view(np.uint64), out[n:n + m], out[2 * n:2 * n + m],
            out[t0:t0 + nt], out[c0:c0 + int(out[c0 - 1])])


def _cms_fold_numpy(tab, salts, hashes, weights, tracked, floor, topk):
    """cms_fold without the native build: the same cells, the same
    answer, position for position."""
    uh, first, inv = np.unique(
        hashes, return_index=True, return_inverse=True
    )
    order = np.argsort(first)  # np.unique sorts; the fold keeps arrival
    ud, first = uh[order], first[order].astype(np.int64)
    w = np.zeros(len(ud), dtype=np.int64)
    pos = np.empty(len(ud), dtype=np.intp)
    pos[order] = np.arange(len(ud))
    np.add.at(w, pos[inv], 1 if weights is None else weights)
    width = np.uint64(tab.shape[1])
    idx = (((ud[None, :] * salts[:, None]) >> np.uint64(17)) % width).astype(
        np.intp
    )
    for r in range(tab.shape[0]):
        np.add.at(tab[r], idx[r], w)
    est = tab[np.arange(tab.shape[0])[:, None], idx].min(axis=0)
    t_idx = np.full(len(tracked), -1, dtype=np.int64)
    if len(ud) and len(tracked):
        at = np.searchsorted(uh, tracked).clip(max=len(uh) - 1)
        hit = uh[at] == tracked
        t_idx[hit] = pos[at[hit]]
    untracked = np.ones(len(ud), dtype=bool)
    untracked[t_idx[t_idx >= 0]] = False
    cand = np.flatnonzero(untracked & (est > floor))
    if len(cand) > topk:
        cand = cand[np.lexsort((cand, est[cand]))][len(cand) - topk:]
    return ud, first, est, t_idx, cand.astype(np.int64)


class NativeSlotTable:
    """Drop-in for models.slot_table.SlotTable backed by the C++ table.

    Same semantics: strict expiry (cache.go:151), same-slot recycling on
    expiry (cache.go:138-163), LRU eviction at capacity (cache.go:115-130).
    """

    def __init__(self, capacity: int, _hash_bits: Optional[Tuple[int, int]] = None):
        """`_hash_bits` is for tests alone: (and, or) masks over every
        key's index hash, so that keys collide in the index on purpose
        (gt_table_new_hashed).  No caller in the package passes it."""
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        lib = _get_lib()
        if lib is None:
            raise RuntimeError(_lib_err or "native runtime unavailable")
        self._lib = lib
        self.capacity = capacity
        if _hash_bits is None:
            self._ptr = lib.gt_table_new(capacity)
        else:
            self._ptr = lib.gt_table_new_hashed(capacity, *_hash_bits)

    def __del__(self):
        ptr = getattr(self, "_ptr", None)
        if ptr:
            self._lib.gt_table_free(ptr)
            self._ptr = None

    def __len__(self) -> int:
        return int(self._lib.gt_table_len(self._ptr))

    # -- stats (hit/miss/eviction counters for metrics parity) ---------
    @property
    def _stats(self):
        out = (ctypes.c_int64 * 3)()
        self._lib.gt_table_stats(self._ptr, out)
        return int(out[0]), int(out[1]), int(out[2])

    @property
    def hits(self) -> int:
        return self._stats[0]

    @property
    def misses(self) -> int:
        return self._stats[1]

    @property
    def index_stats(self) -> dict:
        """The key index's health since the table was made: `lookups`,
        `probes` (index entries inspected for them; probes a lookup is
        the chain length a key pays), `refused` (entries whose hash bits
        matched and whose key did not) and `entries` (index size).  A
        degenerate key set shows here as a number, not as a slow plan."""
        out = (ctypes.c_int64 * 4)()
        self._lib.gt_table_index_stats(self._ptr, out)
        return dict(zip(("lookups", "probes", "refused", "entries"),
                        (int(v) for v in out)))

    @property
    def generation(self) -> int:
        """Key->slot mapping-change counter (Table::map_generation);
        unchanged across two reads == no mapping changed between them."""
        return int(self._lib.gt_table_generation(self._ptr))

    @property
    def evictions(self) -> int:
        """Buckets that left the table for want of room; with a back
        tier a demotion keeps the bucket and is not one."""
        return int(self._lib.gt_table_evictions(self._ptr))

    @property
    def front_evictions(self) -> int:
        """Front slots taken from their keys, demoted or dropped alike.
        Hot: plan_grouped_python reads this around every lookup, so it
        takes the single-counter FFI call, not the stats marshal."""
        return int(self._lib.gt_table_front_evictions(self._ptr))

    # ------------------------------------------------------------------
    def get_slot(self, key: str) -> Optional[int]:
        b = key.encode("utf-8")
        s = self._lib.gt_table_get_slot(self._ptr, b, len(b))
        return None if s < 0 else int(s)

    def lookup_or_assign(self, key: str, now_ms: int) -> Tuple[int, bool]:
        b = key.encode("utf-8")
        slot = ctypes.c_int32()
        exists = ctypes.c_uint8()
        self._lib.gt_table_lookup_or_assign(
            self._ptr, b, len(b), now_ms, ctypes.byref(slot), ctypes.byref(exists)
        )
        return int(slot.value), bool(exists.value)

    def remove(self, key: str) -> None:
        b = key.encode("utf-8")
        self._lib.gt_table_remove(self._ptr, b, len(b))

    def set_expire(self, slot: int, expire_ms: int) -> None:
        self._lib.gt_table_set_expire(self._ptr, slot, expire_ms)

    def get_expire_bulk(self, slots) -> np.ndarray:
        """Expiry bookkeeping for many slots at once (narrow-wire
        keep-sentinel decode, ops/buckets.py unpack_output32)."""
        slots = np.ascontiguousarray(slots, dtype=np.int32)
        out = np.empty(max(len(slots), 1), dtype=np.int64)
        self._lib.gt_table_get_expire(
            self._ptr, slots.ctypes.data, len(slots), out.ctypes.data
        )
        return out[: len(slots)]

    def commit(self, slots, new_expire_ms, removed, keys=None) -> None:
        slots = np.ascontiguousarray(slots, dtype=np.int32)
        expire = np.ascontiguousarray(new_expire_ms, dtype=np.int64)
        rm = np.ascontiguousarray(removed, dtype=np.uint8)
        if keys is not None:
            # Staleness-guarded commit (slot_table.py::commit keys check).
            buf, offsets = pack_keys(keys)
            self._lib.gt_table_commit_keys(
                self._ptr, slots.ctypes.data, expire.ctypes.data, rm.ctypes.data,
                buf.ctypes.data if len(buf) else None, offsets.ctypes.data, len(slots),
            )
            return
        self._lib.gt_table_commit(
            self._ptr, slots.ctypes.data, expire.ctypes.data, rm.ctypes.data, len(slots)
        )

    # -- two-tier back tier (front/back split, Table two-tier mode) ----
    def enable_back(self, back_capacity: int) -> None:
        """Turn on the back tier: front LRU evictions demote rows to a
        back table instead of dropping them (a freed back slot first,
        then the one under the ring cursor); lookups promote them back.  Device moves
        queue in the table until take_moves_into."""
        self._lib.gt_table_enable_back(self._ptr, back_capacity)

    @property
    def tier_stats(self):
        """(total_keys, back_keys, demotions, promotions, back_evictions)."""
        out = (ctypes.c_int64 * 5)()
        self._lib.gt_table_tier_stats(self._ptr, out)
        return tuple(int(x) for x in out)

    def move_counts(self):
        np_, nd = ctypes.c_int64(), ctypes.c_int64()
        self._lib.gt_table_move_counts(
            self._ptr, ctypes.byref(np_), ctypes.byref(nd)
        )
        return int(np_.value), int(nd.value)

    def take_moves_into(self, block: np.ndarray) -> "tuple[int, int] | None":
        """Drain the queued device moves into `block`, a C-contiguous
        i32[5, P] the caller has filled with its padding (rows: promo
        kind, promo src, promo dst, demo src, demo dst; src = -1 is a
        no-op), and close the drain window.  Returns (promotions,
        demotions) written, or None with nothing drained when either
        count is over P.  The caller MUST apply the block
        (ops/buckets.apply_moves) before any other device program
        touches the front rows."""
        counts = (ctypes.c_int64 * 2)()
        if self._lib.gt_table_take_moves(
            self._ptr, block.ctypes.data, block.shape[1], counts
        ):
            return None
        return int(counts[0]), int(counts[1])

    def back_entries(self):
        """(keys, back_slots i32, expire i64) of every back-tier row."""
        count = ctypes.c_int64()
        total = ctypes.c_int64()
        self._lib.gt_table_back_size(
            self._ptr, ctypes.byref(count), ctypes.byref(total)
        )
        n, nb = int(count.value), int(total.value)
        if n == 0:
            return [], np.empty(0, np.int32), np.empty(0, np.int64)
        slots = np.empty(n, dtype=np.int32)
        expire = np.empty(n, dtype=np.int64)
        offsets = np.empty(n + 1, dtype=np.int64)
        buf = ctypes.create_string_buffer(max(nb, 1))
        self._lib.gt_table_back_keys(
            self._ptr, slots.ctypes.data, expire.ctypes.data,
            offsets.ctypes.data, buf,
        )
        raw = buf.raw[:nb]
        keys = [
            raw[offsets[i]:offsets[i + 1]].decode("utf-8") for i in range(n)
        ]
        return keys, slots, expire

    def keys(self) -> List[str]:
        count = ctypes.c_int64()
        total = ctypes.c_int64()
        self._lib.gt_table_keys_size(self._ptr, ctypes.byref(count), ctypes.byref(total))
        n, nb = int(count.value), int(total.value)
        if n == 0:
            return []
        slots = np.empty(n, dtype=np.int32)
        offsets = np.empty(n + 1, dtype=np.int64)
        buf = ctypes.create_string_buffer(max(nb, 1))
        self._lib.gt_table_keys(self._ptr, slots.ctypes.data, offsets.ctypes.data, buf)
        raw = buf.raw[:nb]
        return [raw[offsets[i]:offsets[i + 1]].decode("utf-8") for i in range(n)]


class NativeBatchPlanner:
    """Round planner over a NativeSlotTable: resolve + split a whole key
    batch into race-free kernel rounds in C++ (shard.py::RoundPlanner).
    """

    def __init__(self, table: NativeSlotTable, keys, now_ms: int):
        self._lib = table._lib
        self._table = table
        self.n = len(keys)
        self._buf, self._offsets = as_packed(keys)
        self._ptr = self._lib.gt_batch_begin(
            table._ptr, self._buf.ctypes.data if self.n else None,
            self._offsets.ctypes.data, self.n, now_ms,
        )
        self._lane = np.empty(max(self.n, 1), dtype=np.int32)
        self._slot = np.empty(max(self.n, 1), dtype=np.int32)
        self._exists = np.empty(max(self.n, 1), dtype=np.uint8)

    def __del__(self):
        ptr = getattr(self, "_ptr", None)
        if ptr:
            self._lib.gt_batch_free(ptr)
            self._ptr = None

    def next_round(self):
        """Returns (lane_idx, slots, exists) views for the next round, or
        None when the batch is exhausted."""
        m = self._lib.gt_batch_next_round(
            self._ptr, self._lane.ctypes.data, self._slot.ctypes.data,
            self._exists.ctypes.data,
        )
        if m == 0:
            return None
        return self._lane[:m], self._slot[:m], self._exists[:m].astype(bool)

    def commit_round(self, new_expire_ms, removed) -> None:
        expire = np.ascontiguousarray(new_expire_ms, dtype=np.int64)
        rm = np.ascontiguousarray(removed, dtype=np.uint8)
        self._lib.gt_batch_commit_round(self._ptr, expire.ctypes.data, rm.ctypes.data)

    def plan(self):
        """Plan ALL rounds upfront (no interleaved commits): returns
        (round_id[n] i32, slot[n] i32, exists[n] bool, n_rounds) for the
        single-dispatch kernel path (ops/buckets.py apply_rounds)."""
        round_id = np.empty(max(self.n, 1), dtype=np.int32)
        slots = np.empty(max(self.n, 1), dtype=np.int32)
        exists = np.empty(max(self.n, 1), dtype=np.uint8)
        n_rounds = self._lib.gt_batch_plan(
            self._ptr, round_id.ctypes.data, slots.ctypes.data, exists.ctypes.data
        )
        return (
            round_id[: self.n],
            slots[: self.n],
            exists[: self.n].astype(bool),
            int(n_rounds),
        )

    def plan_grouped(self, cols, reset_mask: int):
        """Grouped full plan (gt_batch_plan_grouped): uniform duplicate
        groups collapse into round 0 with per-lane occurrence indices;
        the rest use rounds 1+.  `cols` carries contiguous algo(i32),
        behavior(i32), hits/limit/duration/greg_expire/greg_duration
        (i64) arrays aligned with the batch keys.  Returns (round_id,
        slot, exists, occ, write, n_rounds)."""
        n = max(self.n, 1)
        round_id = np.zeros(n, dtype=np.int32)
        slots = np.empty(n, dtype=np.int32)
        exists = np.empty(n, dtype=np.uint8)
        occ = np.zeros(n, dtype=np.int32)
        write = np.empty(n, dtype=np.uint8)
        n_rounds = self._lib.gt_batch_plan_grouped(
            self._ptr,
            cols.algo.ctypes.data, cols.behavior.ctypes.data,
            cols.hits.ctypes.data, cols.limit.ctypes.data,
            cols.duration.ctypes.data,
            cols.greg_expire.ctypes.data, cols.greg_duration.ctypes.data,
            reset_mask,
            round_id.ctypes.data, slots.ctypes.data, exists.ctypes.data,
            occ.ctypes.data, write.ctypes.data,
        )
        m = self.n
        return (
            round_id[:m], slots[:m], exists[:m].astype(bool),
            occ[:m], write[:m].astype(bool), int(n_rounds),
        )

    def commit_plan(self, new_expire_ms, removed) -> None:
        """Fold kernel outputs (indexed by ORIGINAL lane order) back into
        the table, last-write-per-key wins."""
        expire = np.ascontiguousarray(new_expire_ms, dtype=np.int64)
        rm = np.ascontiguousarray(removed, dtype=np.uint8)
        self._lib.gt_batch_commit_plan(self._ptr, expire.ctypes.data, rm.ctypes.data)


class NativeMeshPlanner:
    """Whole-mesh columnar planning in single C++ calls: shard-bucket
    (fnv1a % S), per-shard grouped round planning into padded [S, P]
    arrays, and post-dispatch decode + slot-table commit + original-
    order response scatter (gt_mesh_*).  Replaces the round-3 Python
    loop over shards in parallel/mesh.py's columnar dispatch.

    Lifecycle (plan under the store's `_plan_lock`; finish from the
    FIFO resolver — the per-table C++ mutex makes a finish safe
    against the NEXT batch's concurrent plan):
        mp = NativeMeshPlanner(tables, keys, now_ms)   # begin: counts
        n_rounds = mp.plan_grouped(cols, reset_mask, P)  # padded arrays
        wire, lane_wire, rows = mp.encode_wire(...)    # the upload's buffer
        ... device dispatch ...
        status, remaining, reset = mp.finish_narrow(packed_np, now_ms)
    """

    __slots__ = ("_lib", "_tables", "_ptr", "n", "counts", "padded",
                 "pos", "slot", "rid", "exists", "occ", "write",
                 "_keepalive")

    def __init__(self, tables, keys, now_ms: int):
        self._lib = tables[0]._lib
        self._tables = tables  # keep tables (and their C ptrs) alive
        S = len(tables)
        buf, offsets = as_packed(keys)
        self.n = len(offsets) - 1
        self.counts = np.zeros(S, dtype=np.int64)
        ptrs = (ctypes.c_void_p * S)(*[t._ptr for t in tables])
        self._keepalive = (buf, offsets, ptrs)
        self._ptr = self._lib.gt_mesh_begin(
            ptrs, S, buf.ctypes.data if self.n else None,
            offsets.ctypes.data, self.n, now_ms, self.counts.ctypes.data,
        )

    def __del__(self):
        ptr = getattr(self, "_ptr", None)
        if ptr:
            self._lib.gt_mesh_free(ptr)
            self._ptr = None

    def plan_grouped(self, cols, reset_mask: int, padded: int):
        """Plan every shard into padded [S, P] row-major arrays; returns
        n_rounds.  Padding lanes keep slot=-1 / zeros."""
        S = len(self.counts)
        self.padded = padded
        self.slot = np.full((S, padded), -1, dtype=np.int32)
        self.rid = np.zeros((S, padded), dtype=np.int32)
        self.exists = np.zeros((S, padded), dtype=np.uint8)
        self.occ = np.zeros((S, padded), dtype=np.int32)
        self.write = np.zeros((S, padded), dtype=np.uint8)
        self.pos = np.zeros(max(self.n, 1), dtype=np.int64)
        n_rounds = self._lib.gt_mesh_plan_grouped(
            self._ptr,
            cols.algo.ctypes.data, cols.behavior.ctypes.data,
            cols.hits.ctypes.data, cols.limit.ctypes.data,
            cols.duration.ctypes.data,
            cols.greg_expire.ctypes.data, cols.greg_duration.ctypes.data,
            reset_mask, padded,
            self.slot.ctypes.data, self.rid.ctypes.data,
            self.exists.ctypes.data, self.occ.ctypes.data,
            self.write.ctypes.data, self.pos.ctypes.data,
        )
        return int(n_rounds)

    def encode_wire(self, cols, now_ms: int, n_rounds: int, narrow: bool,
                    force_lanes: bool, dict_row: int, lane_row: int):
        """The planned batch as the ONE i32 buffer the stage uploads,
        header included, in one call (gt_mesh_encode_wire: it interns
        the lanes' configurations, counts them, picks the wire by the
        rule and fills it).  `dict_row` and `lane_row` are the words of
        a shard's row on the dictionary wire and on the per-lane wire
        of this answer width (ops/buckets.py dict_wire_words,
        lane_wire_words): the layout is buckets', and a width the
        native side would not write raises.  Returns (wire i32[S, W],
        lane_wire, config_rows): a view of a fresh buffer a call (on
        the CPU backend the device array aliases it), sized for the
        wider of the two rows."""
        S, P = self.slot.shape
        buf = np.empty(S * max(dict_row, lane_row), dtype=np.int32)
        config_rows = ctypes.c_int64()
        lane_wire = self._lib.gt_mesh_encode_wire(
            S, P, self.n,
            self.slot.ctypes.data, self.exists.ctypes.data,
            self.write.ctypes.data, self.occ.ctypes.data,
            self.rid.ctypes.data, self.pos.ctypes.data,
            cols.algo.ctypes.data, cols.behavior.ctypes.data,
            cols.hits.ctypes.data, cols.limit.ctypes.data,
            cols.duration.ctypes.data,
            cols.greg_expire.ctypes.data, cols.greg_duration.ctypes.data,
            now_ms, n_rounds, narrow, force_lanes, dict_row, lane_row,
            buf.ctypes.data, ctypes.byref(config_rows),
        )
        if lane_wire < 0:
            raise ValueError(
                f"wire rows of {dict_row} / {lane_row} words for {P} lanes "
                "are not the native encoder's layout"
            )
        row = lane_row if lane_wire else dict_row
        return buf[: S * row].reshape(S, row), bool(lane_wire), config_rows.value

    def finish_narrow(self, packed_np, now_ms: int):
        """Decode + commit a narrow i32[S, 4, P] result; returns
        (status i32[n], remaining i64[n], reset_time i64[n]) in
        ORIGINAL lane order."""
        packed_np = np.ascontiguousarray(packed_np, dtype=np.int32)
        status = np.empty(max(self.n, 1), dtype=np.int32)
        remaining = np.empty(max(self.n, 1), dtype=np.int64)
        reset = np.empty(max(self.n, 1), dtype=np.int64)
        self._lib.gt_mesh_finish_narrow(
            self._ptr, packed_np.ctypes.data, now_ms,
            status.ctypes.data, remaining.ctypes.data, reset.ctypes.data,
        )
        return status[: self.n], remaining[: self.n], reset[: self.n]

    def finish_wide(self, packed_np):
        """Decode + commit a wide result (absolute values): the
        i32[S, 8, P] lo/hi planes a wide program answers in
        (ops/buckets.py WIDE_ANSWER_ROWS), handed through as they are;
        the C++ loop composes each lane's 64 bits as it reads them."""
        if packed_np.dtype != np.int32:
            raise TypeError(f"the wide answer is i32 planes, not {packed_np.dtype}")
        packed_np = np.ascontiguousarray(packed_np)
        status = np.empty(max(self.n, 1), dtype=np.int32)
        remaining = np.empty(max(self.n, 1), dtype=np.int64)
        reset = np.empty(max(self.n, 1), dtype=np.int64)
        self._lib.gt_mesh_finish_wide(
            self._ptr, packed_np.ctypes.data,
            status.ctypes.data, remaining.ctypes.data, reset.ctypes.data,
        )
        return status[: self.n], remaining[: self.n], reset[: self.n]


class _GtHttpReq(ctypes.Structure):
    _fields_ = [
        ("token", ctypes.c_uint64),
        ("method", ctypes.c_int32),
        ("path_len", ctypes.c_int32),
        ("body_len", ctypes.c_int64),
        ("path", ctypes.c_char_p),
        ("body", ctypes.POINTER(ctypes.c_char)),
        ("t_first_byte_ns", ctypes.c_int64),
        ("t_body_ns", ctypes.c_int64),
    ]


_HTTP_METHODS = {0: "GET", 1: "POST"}


#: Sentinel next() returns when the native fast lane consumed the
#: request (gt_ingress_submit took ownership — no Python handling).
FAST_LANE = object()

_INGRESS_SNIFF = b"GUBC\x01\x05"  # magic + version + kind-5
_JSON_WS = b" \t\n\r"


class HttpEdge:
    """ctypes wrapper over the C++ epoll HTTP server (gt_http_*).

    `acceptors` native epoll threads share the TCP port via
    SO_REUSEPORT (1 = the classic single loop); `uds_path` adds an
    AF_UNIX listener speaking the same protocol.  Python workers call
    next() (GIL released while blocked in the native wait) and answer
    with respond().  See gateway.NativeGatewayServer for the worker
    loop."""

    def __init__(self, listen_address: str = "127.0.0.1:0",
                 acceptors: int = 1, uds_path: str = ""):
        lib = _get_lib()
        if lib is None:
            raise RuntimeError(f"native runtime unavailable: {build_error()}")
        self._lib = lib
        host, _, port = listen_address.partition(":")
        # gt_http_start takes a dotted-quad (AF_INET): resolve hostnames
        # here so 'localhost:1051' etc. keep working like the stdlib
        # gateway.  IPv6 listen addresses are not supported by this edge.
        import socket as _socket

        host_ip = _socket.gethostbyname(host or "127.0.0.1")
        self._ptr = lib.gt_http_start(
            host_ip.encode(), int(port or 0), int(acceptors),
            uds_path.encode(),
        )
        if not self._ptr:
            raise OSError(
                f"gt_http_start failed to bind {listen_address}"
                + (f" / uds {uds_path}" if uds_path else "")
            )
        self.port = int(lib.gt_http_port(self._ptr))
        self.acceptors = int(lib.gt_http_acceptor_count(self._ptr))
        self.uds_path = uds_path
        self.stopped = False
        self._freed = False
        self._stop_lock = threading.Lock()
        # drain_sends' one buffer, and the lock that lets any thread use it.
        self._drain_buf = np.empty(self._DRAIN_ROWS * 3, dtype=np.int64)
        self._drain_lock = threading.Lock()

    def acceptor_stats(self):
        """Per-acceptor counters: list of dicts {uds, accepted,
        requests, ingressFrames, ingressLanes, wakeups, conns} — the
        gubernator_ingress_acceptor_* metric source and the fairness
        tests' oracle.  A freed edge reads as empty, never a crash."""
        if self._ptr is None:
            return []
        n = self.acceptors
        out = np.zeros(n * 7, dtype=np.int64)
        self._lib.gt_http_acceptor_stats(self._ptr, out.ctypes.data)
        keys = ("uds", "accepted", "requests", "ingressFrames",
                "ingressLanes", "wakeups", "conns")
        return [
            dict(zip(keys, (int(v) for v in out[i * 7:(i + 1) * 7])))
            for i in range(n)
        ]

    STAT_KEYS = ("reads", "readBytes", "sends", "sendBytes", "epolloutRounds",
                 "requests", "sendRingDropped")
    _DRAIN_ROWS = 256

    def stats(self) -> dict:
        """The socket's own work, summed over the acceptors
        (`/debug/status` `edge`), all cumulative: `reads`, `readBytes`,
        `sends`, `sendBytes`, `epolloutRounds`, `requests` (reads and
        sends a request are these over `requests`), and
        `sendRingDropped`: answered requests that fell off the send
        ring's bound before `drain_sends` took them, by which many
        `edge.send` is under-observed.  A freed edge reads as zeros."""
        out = np.zeros(len(self.STAT_KEYS), dtype=np.int64)
        if self._ptr is not None:
            self._lib.gt_http_stats(self._ptr, out.ctypes.data)
        return dict(zip(self.STAT_KEYS, out.tolist()))

    def drain_sends(self) -> list:
        """[[token, t_staged_ns, t_last_byte_ns]] of the requests whose
        answer's last byte the kernel has accepted since the last call,
        oldest first, on the clock of time.monotonic_ns() (`edge.send` is
        the difference).  Each record is handed out once, to whichever
        thread asks first; an empty ring costs one native call that
        takes no lock of the server's."""
        out: list = []
        with self._drain_lock:
            buf = self._drain_buf
            while self._ptr is not None:
                n = int(self._lib.gt_http_drain_sends(
                    self._ptr, buf.ctypes.data, self._DRAIN_ROWS
                ))
                out += buf[: n * 3].reshape(-1, 3).tolist()
                if n < self._DRAIN_ROWS:
                    break
        return out

    def next(self, timeout_ms: int = 200, ingress=None):
        """Blocks up to timeout_ms for one parsed request.  Returns
        (token, method, path, body_bytes, (t_first_byte_ns, t_body_ns)),
        None (timeout/stopping), or FAST_LANE when `ingress` (an
        IngressBatcher) consumed the request natively — a POST
        /v1/GetRateLimits whose body sniffs as a kind-5 frame, or as a
        classic JSON call (`{` after JSON whitespace), goes through
        gt_ingress_submit WITHOUT copying the body into Python (the C++
        side picks the parser by the same first bytes); any fallback
        reason (malformed, exotic JSON, slow lanes, validation errors,
        remote owners, disabled) falls through to the ordinary copy-out
        so the Python path serves it unchanged.  That is two GIL-released native calls
        with the interpreter between them: gt_http_next hands the request
        over, this thread takes the interpreter back to sniff the body,
        then gt_ingress_submit parses and enqueues (`edge.handoff` runs
        from the body's last byte to that submit's entry).  The stamps
        are the C++ edge's, on the clock of time.monotonic_ns().  The
        copied body means the token may be answered from any thread at
        any later time."""
        if self.stopped:
            return None
        req = _GtHttpReq()
        rc = self._lib.gt_http_next(self._ptr, timeout_ms, ctypes.byref(req))
        if rc != 1:
            return None
        if (
            ingress is not None
            and req.method == 1
            and req.body_len >= 10
            and req.path == b"/v1/GetRateLimits"
        ):
            head = ctypes.string_at(req.body, 6)
            if (
                head == _INGRESS_SNIFF
                # A classic call: `{` after JSON whitespace (a head that
                # is all whitespace is the C++ parser's to read on).
                or head.lstrip(_JSON_WS)[:1] in (b"{", b"")
            ) and self._lib.gt_ingress_submit(
                self._ptr, ingress._ptr, req.token
            ) == 0:
                return FAST_LANE
        method = _HTTP_METHODS.get(req.method, "OTHER")
        path = req.path.decode("utf-8", "replace") if req.path else ""
        body = ctypes.string_at(req.body, req.body_len) if req.body_len else b""
        return req.token, method, path, body, (req.t_first_byte_ns, req.t_body_ns)

    def respond(self, token: int, status: int, body: bytes,
                reason: str = "OK", content_type: str = "application/json"):
        self._lib.gt_http_respond(
            self._ptr, token, status, reason.encode(), content_type.encode(),
            body, len(body),
        )

    def shutdown(self) -> None:
        """Phase 1: stop traffic (closes sockets, joins the native
        epoll thread).  The HttpServer stays ALLOCATED: workers still
        blocked in next() or about to respond() keep valid memory.
        Callers must join their workers, then call free()."""
        with self._stop_lock:
            if self.stopped:
                return
            self.stopped = True
        self._lib.gt_http_shutdown(self._ptr)

    def free(self) -> None:
        """Phase 2: release the native server.  Only safe after every
        worker thread using this edge has exited."""
        with self._stop_lock:
            if self._freed or self._ptr is None:
                return
            self._freed = True
        self._lib.gt_http_free(self._ptr)
        self._ptr = None


class _GtTakenInfo(ctypes.Structure):
    # Pointers as plain addresses (c_void_p reads as an int): a view is
    # made from the address when a column is first read.
    _fields_ = [
        ("n", ctypes.c_int64),
        ("n_frames", ctypes.c_int64),
        ("algo", ctypes.c_void_p),
        ("beh", ctypes.c_void_p),
        ("hits", ctypes.c_void_p),
        ("limit", ctypes.c_void_p),
        ("duration", ctypes.c_void_p),
        ("hk", ctypes.c_void_p),
        ("hkoff", ctypes.c_void_p),
        ("hk_bytes", ctypes.c_int64),
        ("hashes", ctypes.c_void_p),
        ("name_blob", ctypes.c_void_p),
        ("name_off", ctypes.c_void_p),
        ("name_bytes", ctypes.c_int64),
        ("uk_blob", ctypes.c_void_p),
        ("uk_off", ctypes.c_void_p),
        ("uk_bytes", ctypes.c_int64),
        ("frame_lanes", ctypes.c_void_p),
        ("frame_age_us", ctypes.c_void_p),
        ("frame_stamps", ctypes.c_void_p),
        ("parse_ns_total", ctypes.c_int64),
        ("hits_total", ctypes.c_int64),
        ("frame_body", ctypes.c_void_p),
        ("beh_or", ctypes.c_int64),
        ("frame_call", ctypes.c_void_p),
        ("n_calls", ctypes.c_int64),
    ]


# C++-owned memory at an address, as a buffer numpy can view: ONE ctypes
# type of no real length, of which `_view` reads `count` items (a type a
# length would be made anew for every take's key bytes).
_MEMORY = ctypes.c_char * (1 << 40)


def _view(addr, n, dtype):
    """Zero-copy numpy view of `n` items at a C address (no ownership)."""
    if n == 0 or not addr:
        return np.zeros(0, dtype=dtype)
    return np.frombuffer(_MEMORY.from_address(addr), dtype, n)


# A take's columns beyond the seven a dispatch reads, made when first
# read (IngressTakenBatch.__getattr__): attribute -> (pointer field,
# items as a function of the batch, dtype, row width or 0 for a flat
# column).
_LAZY_VIEWS = {
    "hashes": ("hashes", lambda tb: tb.n, np.uint64, 0),
    "_nb": ("name_blob", lambda tb: int(tb._info.name_bytes), np.uint8, 0),
    "_no": ("name_off", lambda tb: tb.n + 1, np.int64, 0),
    "_ub": ("uk_blob", lambda tb: int(tb._info.uk_bytes), np.uint8, 0),
    "_uo": ("uk_off", lambda tb: tb.n + 1, np.int64, 0),
    "frame_lanes": ("frame_lanes", lambda tb: tb.n_frames, np.int64, 0),
    "frame_age_us": ("frame_age_us", lambda tb: tb.n_frames, np.int64, 0),
    # A row a frame: (token, t_first_byte, t_body, arrival), the C++
    # edge's stamps on the clock of time.monotonic_ns(); a frame's
    # arrival plus its age is the take's own clock reading.
    "frame_stamps": ("frame_stamps", lambda tb: tb.n_frames * 4, np.int64, 4),
    # A row a frame: (address, length) of the bytes the client sent.
    "frame_body": ("frame_body", lambda tb: tb.n_frames * 2, np.int64, 2),
    # 1 where the frame is a classic JSON call, 0 a kind-5 frame.
    "frame_call": ("frame_call", lambda tb: tb.n_frames, np.uint8, 0),
}


class IngressTakenBatch:
    """One coalesced batch from the native ingress ring: contiguous
    kernel-ready column arrays spanning every taken frame, as ZERO-COPY
    numpy views of C++-owned buffers.  Valid ONLY until
    IngressBatcher.complete()/fail() releases the handle — the pump is
    the sole owner and must not let views escape the dispatch round.
    The seven columns a dispatch reads are viewed at once; the others
    when something first reads them (the folds, the tap, the outcome:
    off the take's way to its launch), under the same lifetime rule.

    Quacks like wire.FrameIngressColumns where the batch-granularity
    folds need it (len, .hits/.behavior/..., `_nb`/`_no`/`_uo` name
    columns for the tenant fold, packed hash keys + ring hashes for
    the hot-key sketch)."""

    __slots__ = ("_ptr", "_info", "n", "n_frames", "n_calls", "algorithm", "behavior",
                 "hits", "limit", "duration", "hash_keys", "parse_ns_total",
                 "hits_total", "beh_or", "trace_ctx", *_LAZY_VIEWS)

    def __init__(self, ptr, info: _GtTakenInfo):
        self._ptr = ptr
        self._info = info
        n = int(info.n)
        self.n = n
        self.n_frames = int(info.n_frames)
        # Of them, the classic JSON calls (answered as JSON).
        self.n_calls = int(info.n_calls)
        self.algorithm = _view(info.algo, n, np.int32)
        self.behavior = _view(info.beh, n, np.int32)
        self.hits = _view(info.hits, n, np.int64)
        self.limit = _view(info.limit, n, np.int64)
        self.duration = _view(info.duration, n, np.int64)
        self.hash_keys = PackedKeys(
            _view(info.hk, int(info.hk_bytes), np.uint8),
            _view(info.hkoff, n + 1, np.int64),
        )
        self.parse_ns_total = int(info.parse_ns_total)
        self.hits_total = int(info.hits_total)
        # The OR of every lane's behaviour word (gt_ingress_submit read
        # them all): what the take holds, without a pass over the column.
        self.beh_or = int(info.beh_or)
        self.trace_ctx = None  # fast lane never carries sampled frames

    def __getattr__(self, name: str):
        # Reached only for a slot not yet filled: a lazy view's first read.
        spec = _LAZY_VIEWS.get(name)
        if spec is None:
            raise AttributeError(name)
        if self._ptr is None:
            raise ValueError(f"{name}: the batch was completed, its views are dead")
        field, length, dtype, row = spec
        view = _view(getattr(self._info, field), length(self), dtype)
        if row:
            view = view.reshape(-1, row)
        setattr(self, name, view)
        return view

    def frame_bytes(self) -> "List[bytes]":
        """The kind-5 frames of the take as the clients sent them, a copy
        each (IngressFrame::body, which lives until complete()/fail());
        its classic JSON calls are left out."""
        bodies = self.frame_body
        if self.n_calls:
            bodies = bodies[self.frame_call == 0]
        return [ctypes.string_at(addr, length) for addr, length in bodies.tolist()]

    def __len__(self) -> int:
        return self.n

    def _name_at(self, i: int) -> str:
        return bytes(self._nb[self._no[i]:self._no[i + 1]]).decode("utf-8")

    def _uk_at(self, i: int) -> str:
        return bytes(self._ub[self._uo[i]:self._uo[i + 1]]).decode("utf-8")

    def request_at(self, i: int):
        """Lane i as a dataclass, a copy that outlives the batch (what
        the MULTI_REGION hit queue keeps of a lane)."""
        from ..types import RateLimitRequest

        return RateLimitRequest(
            name=self._name_at(i),
            unique_key=self._uk_at(i),
            hits=int(self.hits[i]),
            limit=int(self.limit[i]),
            duration=int(self.duration[i]),
            algorithm=int(self.algorithm[i]),
            behavior=int(self.behavior[i]),
        )


class IngressBatcher:
    """The native ingress ring (gt_ingress_*): gateway workers submit
    kind-5 frames and classic JSON calls GIL-free; the NativeIngressPump
    takes coalesced batches, dispatches them at batch granularity, and
    completes them back into native response fills (kind-6 for a frame,
    JSON for a call).  See host_runtime.cpp
    'Native ingress service loop' for the full contract."""

    STAT_KEYS = ("frames", "lanes", "batches", "shedFrames", "shedLanes",
                 "fallbacks", "pendingFrames", "pendingLanes",
                 "expressFrames", "expressLanes", "calls", "callFallbacks")

    def __init__(self):
        lib = _get_lib()
        if lib is None:
            raise RuntimeError(f"native runtime unavailable: {build_error()}")
        self._lib = lib
        self._ptr = lib.gt_ingress_new()
        self.stopped = False

    def set_ring(self, vnode_hashes, vnode_self, *, all_self: bool,
                 enabled: bool, cap_lanes: int, max_frame_lanes: int,
                 behavior_mask: int, hash_variant: int = 0,
                 express_mask: int = 0) -> None:
        vh = np.ascontiguousarray(vnode_hashes, dtype=np.uint64)
        vs = np.ascontiguousarray(vnode_self, dtype=np.uint8)
        self._lib.gt_ingress_set_ring(
            self._ptr, vh.ctypes.data, vs.ctypes.data, len(vh),
            1 if all_self else 0, 1 if enabled else 0,
            int(cap_lanes), int(max_frame_lanes), int(behavior_mask),
            int(hash_variant), int(express_mask),
        )

    def disable(self) -> None:
        """Fast path off (every submit falls back to Python) without
        touching the rest of the config."""
        self.set_ring(
            np.zeros(0, np.uint64), np.zeros(0, np.uint8),
            all_self=False, enabled=False, cap_lanes=0,
            max_frame_lanes=0, behavior_mask=0,
        )

    def take(self, max_lanes: int, timeout_ms: int = 200):
        """Block (GIL released) for one coalesced batch; None on
        timeout or shutdown (check .stopped)."""
        tb = ctypes.c_void_p()
        info = _GtTakenInfo()
        rc = self._lib.gt_ingress_take(
            self._ptr, int(max_lanes), int(timeout_ms),
            ctypes.byref(tb), ctypes.byref(info),
        )
        if rc == -1:
            self.stopped = True
            return None
        if rc != 1:
            return None
        return IngressTakenBatch(tb, info)

    def complete(self, tb: IngressTakenBatch, status, limit, remaining,
                 reset_time) -> None:
        """Native response fill: per-frame kind-6 encode + write.
        Consumes the handle — the batch's views die here.  A handle
        already consumed is a no-op (an error in post-complete
        bookkeeping must never double-answer or crash)."""
        if tb._ptr is None:
            return
        status = np.ascontiguousarray(status, dtype=np.int32)
        limit = np.ascontiguousarray(limit, dtype=np.int64)
        remaining = np.ascontiguousarray(remaining, dtype=np.int64)
        reset_time = np.ascontiguousarray(reset_time, dtype=np.int64)
        ptr, tb._ptr = tb._ptr, None
        self._lib.gt_ingress_complete(
            ptr, status.ctypes.data, limit.ctypes.data,
            remaining.ctypes.data, reset_time.ctypes.data,
        )

    def fail(self, tb: IngressTakenBatch, status: int, reason: str,
             content_type: str, body: bytes) -> None:
        """Error fill: every frame of the batch answers `body`.
        Consumes the handle; a handle already consumed is a no-op —
        passing a freed batch into the native fill would be a
        use-after-free, and its frames were already answered."""
        if tb._ptr is None:
            return
        ptr, tb._ptr = tb._ptr, None
        self._lib.gt_ingress_fail(
            ptr, int(status), reason.encode(), content_type.encode(),
            body, len(body),
        )

    def stop(self) -> None:
        """Wake the pump and 503 any still-queued frames."""
        self.stopped = True
        self._lib.gt_ingress_stop(self._ptr)

    def counters(self) -> "List[int]":
        """The ring's cumulative counters, in STAT_KEYS' order."""
        out = (ctypes.c_int64 * len(self.STAT_KEYS))()
        if self._ptr:  # freed batchers read as all-zero, never crash
            self._lib.gt_ingress_stats(self._ptr, out)
        return list(out)

    def stats(self) -> dict:
        return dict(zip(self.STAT_KEYS, self.counters()))

    def free(self) -> None:
        ptr, self._ptr = self._ptr, None
        if ptr:
            self._lib.gt_ingress_free(ptr)
