"""Host side of a device bucket store: request planning, the columnar
dispatch pipeline and the Store-SPI conversions.

The device store itself is `parallel.mesh.MeshBucketStore` (one shard
per device; one device is a one-shard mesh).  It replaces a reference
peer's `LRUCache` + mutex + per-request algorithm call
(`gubernator.go:335-354`): a whole batch of requests is resolved to
device slots host-side, then evaluated in one jitted program.

Request order within a batch is preserved for duplicate keys (the k-th
request for a key sees the state left by the (k-1)-th), matching the
reference's mutex serialization (gubernator.go:336-337).
"""

from __future__ import annotations

import datetime as _dt
import threading
from collections import deque
from dataclasses import dataclass
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from .. import audit
from .. import profiling
from .. import saturation
from ..saturation import phase
from .. import telemetry
from .. import tracing
from ..ops import buckets
from ..types import (
    Algorithm,
    Behavior,
    RateLimitRequest,
    RateLimitResponse,
    has_behavior,
)
from ..utils import gregorian
from .slot_table import SlotTable

# Batches pad to a small set of bucket sizes: each bucket is its own
# XLA program, and a program's FIRST dispatch pays a compile (or, on a
# remote device, a multi-second executable load) — so few distinct
# shapes beats tight padding.  Below 1024 buckets grow 4x (64, 256,
# 1024: padded lanes cost microseconds, and these are the sizes the
# service/peer planes hit, where a cold bucket can blow an RPC
# deadline); above 1024 they grow 2x (wasting up to half a large
# batch's scatter time would be real money).
_PAD_MIN = 64
_PAD_COARSE_MAX = 1024
_PAD_MAX = 1 << 20


def pad_size(n: int) -> int:
    p = _PAD_MIN
    while p < n and p < _PAD_COARSE_MAX:
        p <<= 2
    while p < n and p < _PAD_MAX:
        p <<= 1
    if n <= p:
        return p
    return ((n + _PAD_MAX - 1) // _PAD_MAX) * _PAD_MAX


@dataclass
class _Prepared:
    """A request resolved host-side, ready for kernel dispatch.

    gslot / cached_hint are used by the GLOBAL path (parallel/mesh.py):
    cached_hint lanes answer from the replica columns, touch no local
    bucket state, and scatter-add their hits — so they bypass the
    round-uniqueness rules entirely.
    """

    pos: int
    slot: int
    exists: bool
    req: RateLimitRequest
    key: str
    greg_expire: int = 0
    greg_duration: int = 0
    resolved: bool = False
    gslot: int = -1
    cached_hint: bool = False


class GregResolver:
    """Memoized Gregorian expiry/duration for one batch timestamp.

    now is fixed for the whole batch, so the calendar math depends only
    on req.duration — at most the 6 Gregorian interval kinds recur (the
    host analogue of algorithms.go:90-95,140-145).  `resolve` returns
    (expire_ms, duration_ms) or the GregorianError the reference would
    surface as a per-request error.
    """

    def __init__(self, now_ms: int):
        self.now_ms = now_ms
        self._now_dt: Optional[_dt.datetime] = None
        self._cache: Dict[int, object] = {}

    def resolve(self, duration: int):
        if self._now_dt is None:
            self._now_dt = _dt.datetime.fromtimestamp(
                self.now_ms / 1000.0, tz=_dt.timezone.utc
            )
        cached = self._cache.get(duration)
        if cached is None:
            try:
                cached = (
                    gregorian.gregorian_expiration(self._now_dt, duration),
                    gregorian.gregorian_duration(self._now_dt, duration),
                )
            except gregorian.GregorianError as e:
                cached = e
            self._cache[duration] = cached
        return cached


_GREG_KINDS = 6  # upstream's interval kinds, 0 (minutes) .. 5 (years)


def greg_lanes(behavior) -> np.ndarray:
    """The lanes of a behaviour column that carry DURATION_IS_GREGORIAN."""
    return (np.asarray(behavior) & int(Behavior.DURATION_IS_GREGORIAN)) != 0


def resolve_greg_columns(greg: np.ndarray, duration, now_ms: int):
    """`GregResolver` over a frame's columns at ONE clock reading.

    `greg` masks the calendar lanes (`greg_lanes` of the behaviour
    column, less whatever lanes the caller serves elsewhere).  `now` is
    one for the take and a duration has at most six kinds, so each kind
    the frame holds is resolved once (`distinct` of them) and the lanes
    gather from the six-row table: no Python loop over lanes.  Returns
    `(greg_expire, greg_duration, errors, distinct)`: two i64 columns
    (0 on every other lane) and, for the lanes upstream answers with an
    error (weeks, or a duration that is no interval kind), `(lane
    indices, message)` pairs, their columns left 0."""
    duration = np.asarray(duration)
    # Row _GREG_KINDS of the tables: a lane off the mask, or one whose
    # duration is no kind; row 3 (weeks) stays 0 too.
    kind = np.where(
        greg & (duration >= 0) & (duration < _GREG_KINDS), duration, _GREG_KINDS
    )
    held = np.bincount(kind, minlength=_GREG_KINDS + 1)
    expire = np.zeros(_GREG_KINDS + 1, np.int64)
    length = np.zeros(_GREG_KINDS + 1, np.int64)
    errors = []
    resolver = GregResolver(now_ms)
    kinds = np.flatnonzero(held[:_GREG_KINDS]).tolist()
    for k in kinds:
        cached = resolver.resolve(k)
        if isinstance(cached, gregorian.GregorianError):
            errors.append((np.flatnonzero(kind == k), str(cached)))
        else:
            expire[k], length[k] = cached
    invalid = greg & (kind == _GREG_KINDS)
    if invalid.any():
        errors.append((np.flatnonzero(invalid), gregorian.ERR_INVALID))
    return expire[kind], length[kind], errors, len(kinds)


def prepare_requests(
    requests: Sequence[RateLimitRequest],
    now_ms: int,
    responses: List[Optional[RateLimitResponse]],
    positions: Optional[Sequence[int]] = None,
) -> List[_Prepared]:
    """Precompute per-request host-side values (hash key, Gregorian
    expiry/duration).  Requests with invalid Gregorian durations get
    error responses directly (reference returns the error per-request)."""
    greg = GregResolver(now_ms)
    prepared: List[_Prepared] = []

    for i, req in enumerate(requests):
        pos = positions[i] if positions is not None else i
        p = _Prepared(pos=pos, slot=-1, exists=False, req=req, key=req.hash_key())
        if has_behavior(req.behavior, Behavior.DURATION_IS_GREGORIAN):
            cached = greg.resolve(req.duration)
            if isinstance(cached, gregorian.GregorianError):
                responses[pos] = RateLimitResponse(error=str(cached))
                continue
            p.greg_expire, p.greg_duration = cached
        prepared.append(p)
    return prepared


def plan_grouped_python(table, prepared: Sequence[_Prepared], now_ms: int):
    """Full-plan twin of the C++ gt_batch_plan_grouped over a Python
    SlotTable: uniform duplicate groups (same key, identical config, no
    RESET_REMAINING) collapse into round 0 with per-lane occurrence
    indices and a single scattering (write) lane; everything else takes
    the round scheme from round 1 with the same chaining/deferral rules
    as RoundPlanner.  Mutates each _Prepared's slot/exists; returns
    (round_id, occ, write, n_rounds) arrays aligned to `prepared`.

    Used by the mesh store's fused dispatch: ALL rounds of ALL shards
    run inside one jitted program instead of one dispatch per round.
    """
    n = len(prepared)
    round_id = np.zeros(n, dtype=np.int32)
    occ = np.zeros(n, dtype=np.int32)
    write = np.zeros(n, dtype=bool)

    groups: "Dict[str, List[int]]" = {}
    for j, p in enumerate(prepared):
        if p.cached_hint:
            # Replica-cache lane: no local state touched; hits
            # accumulate by scatter-add, so no round/uniqueness rules.
            p.slot, p.exists, p.resolved = -1, False, True
            continue
        groups.setdefault(p.key, []).append(j)

    used0: set = set()
    slow: List[int] = []
    # Last key to write each slot in scheduled device order: round-0
    # groups seed it; slow lanes consult it for BOTH exists-chaining
    # and slot-takeover detection.
    slot_owner: Dict[int, str] = {}
    for key, lanes in groups.items():
        f = prepared[lanes[0]]
        uniform = not has_behavior(f.req.behavior, Behavior.RESET_REMAINING)
        for j in lanes[1:]:
            if not uniform:
                break
            q = prepared[j]
            uniform = (
                q.req.algorithm == f.req.algorithm
                and q.req.behavior == f.req.behavior
                and q.req.hits == f.req.hits
                and q.req.limit == f.req.limit
                and q.req.duration == f.req.duration
                and q.greg_expire == f.greg_expire
                and q.greg_duration == f.greg_duration
            )
        ev_before = table.front_evictions
        slot, exists = table.lookup_or_assign(key, now_ms)
        evicted = table.front_evictions != ev_before
        for j in lanes:
            prepared[j].slot = slot
            prepared[j].exists = exists
            prepared[j].resolved = True
        # An eviction may have stolen a slot from a key with earlier
        # lanes in this batch; the slow path's deferral orders it.
        if uniform and not evicted and slot not in used0:
            used0.add(slot)
            slot_owner[slot] = key
            for o, j in enumerate(lanes):
                occ[j] = o
                write[j] = o + 1 == len(lanes)
        else:
            slow.extend(lanes)

    if not slow:
        return round_id, occ, write, 1

    slow.sort()
    rnd = 1
    pending = slow
    while pending:
        seen: set = set()
        used: set = set()
        deferred: List[int] = []
        for j in pending:
            p = prepared[j]
            if p.key in seen:
                deferred.append(j)
                continue
            owner = slot_owner.get(p.slot)
            if owner is not None and owner != p.key:
                # The captured slot was taken over by ANOTHER key's
                # create (mid-batch eviction) scheduled before this
                # lane.  Running here — with either exists value —
                # would corrupt the new owner's device state.
                # Re-resolve: the table no longer maps this key, so it
                # gets a fresh slot (or evicts a different one).
                p.slot, p.exists = table.lookup_or_assign(p.key, now_ms)
            if p.slot in used:  # eviction collision: defer as-is
                deferred.append(j)
                seen.add(p.key)
                continue
            round_id[j] = rnd
            write[j] = True
            if slot_owner.get(p.slot) == p.key:
                p.exists = True  # chained: device state authoritative
            slot_owner[p.slot] = p.key
            seen.add(p.key)
            used.add(p.slot)
        pending = deferred
        rnd += 1
    return round_id, occ, write, rnd


class RoundPlanner:
    """Splits a prepared request stream into kernel rounds.

    A round must have unique keys AND unique slots (the scatter is
    race-free only then).  Duplicates are skipped-and-deferred to a later
    round so the k-th request for a key observes the (k-1)-th's committed
    state — the vectorized equivalent of the reference's mutex
    serialization (gubernator.go:336-337).  Cross-key order is NOT
    preserved (matching the reference's arbitrary goroutine fan-out
    order, gubernator.go:131-218), which keeps hot-key batches at
    max-multiplicity rounds instead of one round per duplicate.  A slot
    collision can only happen when LRU eviction under capacity pressure
    reuses a slot already scheduled in the current round; the colliding
    request keeps its captured (slot, exists) — re-resolving after the
    round would see the stale mirror the evicted lane wrote — and runs
    next round, preserving sequential evict-then-create semantics.
    """

    def __init__(
        self,
        table: SlotTable,
        prepared: Sequence[_Prepared],
        now_ms: int,
        resolver=None,
    ):
        self.table = table
        self.queue = deque(prepared)
        self.now_ms = now_ms
        # Pluggable (slot, exists) resolution — the Store SPI path wraps
        # the table lookup with store.get / remove side effects.
        self.resolver = resolver or (lambda p: table.lookup_or_assign(p.key, now_ms))

    def next_chunk(self) -> List[_Prepared]:
        cur: List[_Prepared] = []
        seen_keys: set = set()
        used_slots: set = set()
        deferred: deque = deque()
        while self.queue:
            p = self.queue.popleft()
            if p.cached_hint:
                # Replica-cache lane: no local state touched, hit
                # accumulation is scatter-add (duplicate-safe) — exempt
                # from key/slot uniqueness.
                p.slot, p.exists, p.resolved = -1, False, True
                cur.append(p)
                continue
            if p.key in seen_keys:
                deferred.append(p)  # k-th occurrence waits for commit
                continue
            if not p.resolved:
                p.slot, p.exists = self.resolver(p)
                p.resolved = True
            if p.slot in used_slots:
                # Eviction collision: defer as-is; same-key successors
                # must stay behind it.
                deferred.append(p)
                seen_keys.add(p.key)
                continue
            cur.append(p)
            seen_keys.add(p.key)
            used_slots.add(p.slot)
        self.queue = deferred
        return cur


class _Columns:
    """Request fields as contiguous arrays (one slot per valid lane)."""

    __slots__ = ("algo", "behavior", "hits", "limit", "duration",
                 "greg_expire", "greg_duration", "calendar_lanes",
                 "flagged_lanes", "global_lanes", "sent_behavior")

    def __init__(self, n: int):
        self.algo = np.empty(n, dtype=np.int32)
        self.behavior = np.empty(n, dtype=np.int32)
        self.hits = np.empty(n, dtype=np.int64)
        self.limit = np.empty(n, dtype=np.int64)
        self.duration = np.empty(n, dtype=np.int64)
        self.greg_expire = np.zeros(n, dtype=np.int64)
        self.greg_duration = np.zeros(n, dtype=np.int64)
        self.calendar_lanes = 0
        # Routing bits (split_routing_bits): lanes that carried one, the
        # GLOBAL lanes' indices, the column as the caller sent it.
        self.flagged_lanes = 0
        self.global_lanes = None
        self.sent_behavior = None


_I32_MAX = (1 << 31) - 1


def narrow_ok(cols: "_Columns", now_ms: int) -> bool:
    """True when every value column fits the int32 wire
    (buckets.apply_rounds32 preconditions)."""
    hi = _I32_MAX
    for a in (cols.hits, cols.limit, cols.duration):
        if a.size and (int(a.min()) < 0 or int(a.max()) > hi):
            return False
    mask = cols.greg_duration != 0
    if mask.any():
        d = cols.greg_expire[mask] - now_ms
        if int(d.min()) < 0 or int(d.max()) > hi or int(cols.greg_duration.max()) > hi:
            return False
    return True


def make_columns(algorithm, behavior, hits, limit, duration, n,
                 greg_expire=None, greg_duration=None) -> "_Columns":
    """Coerce caller-provided arrays into contiguous kernel columns."""
    cols = _Columns(0)
    cols.algo = np.ascontiguousarray(algorithm, dtype=np.int32)
    cols.behavior = np.ascontiguousarray(behavior, dtype=np.int32)
    cols.hits = np.ascontiguousarray(hits, dtype=np.int64)
    cols.limit = np.ascontiguousarray(limit, dtype=np.int64)
    cols.duration = np.ascontiguousarray(duration, dtype=np.int64)
    z = np.zeros(n, dtype=np.int64)
    cols.greg_expire = (
        z if greg_expire is None else np.ascontiguousarray(greg_expire, np.int64)
    )
    cols.greg_duration = (
        z if greg_duration is None else np.ascontiguousarray(greg_duration, np.int64)
    )
    # Resolved calendar lanes (a resolved interval's length is never 0);
    # a caller that resolved none passes no column, and nothing is counted.
    cols.calendar_lanes = (
        0 if greg_duration is None else int(np.count_nonzero(cols.greg_duration))
    )
    return cols


# The behaviour bits that say WHERE and WHEN a check is applied and never
# what it answers: upstream's owner applies a GLOBAL or MULTI_REGION
# request to its own bucket like any other (gubernator.go:339-345, then
# QueueUpdate / QueueHits), and NO_BATCHING only skips a coalescing wait.
# The kernel reads DURATION_IS_GREGORIAN and RESET_REMAINING alone.
ROUTING_BEHAVIOR = (
    int(Behavior.NO_BATCHING) | int(Behavior.GLOBAL) | int(Behavior.MULTI_REGION)
)


def split_routing_bits(cols: "_Columns") -> None:
    """Take the routing bits off a batch's behaviour column before it is
    planned: the plan calls a key's lanes one uniform group (answered in
    closed form, round 0) only if their behaviour words are equal, so a
    hot key with one flagged lane among plain ones would otherwise run a
    kernel round a lane; and the dictionary wire spends a row a distinct
    word.  Leaves behind what the owner's book-keeping needs: the count
    of flagged lanes, the GLOBAL lanes, and the column as it was sent.
    A batch without such a bit is untouched; the caller's array is never
    written."""
    flagged = cols.behavior & ROUTING_BEHAVIOR
    if not flagged.any():
        return
    cols.flagged_lanes = int(np.count_nonzero(flagged))
    glob = np.flatnonzero(flagged & int(Behavior.GLOBAL))
    if glob.size:
        cols.global_lanes = glob
    cols.sent_behavior = cols.behavior
    cols.behavior = cols.behavior & ~ROUTING_BEHAVIOR


# ---------------------------------------------------------------------
# Device->host readback with the known-flake quarantine: under heavy
# suite load the jax CPU backend occasionally raised a spurious
# IndexError (seen on jax 0.4.x; whether 0.9.0 still raises it is not
# known — the retry counter says if it ever fires)
# ("list index out of range") from _copy_single_device_array_to_host_async
# inside np.asarray of a device array.  The array is intact — an
# immediate retry succeeds — so the dispatch readback sites retry ONCE
# and count, instead of failing a whole batch (and a tier-1 run) on a
# runtime race that is not ours.  Anything else (or a second failure)
# propagates unchanged.
_readback_lock = threading.Lock()
_readback_retries_total = 0


def readback_retries_total() -> int:
    """Cumulative retry count (scraped into
    gubernator_readback_retries_total)."""
    with _readback_lock:
        return _readback_retries_total


def host_readback(arr) -> np.ndarray:
    """np.asarray(device_array) with the single-retry quarantine."""
    global _readback_retries_total
    try:
        return np.asarray(arr)
    except IndexError:
        with _readback_lock:
            _readback_retries_total += 1
        return np.asarray(arr)


def _wire_donate_ok(device) -> bool:
    """Whether a freshly uploaded wire buffer is donatable on this
    device.  CPU device_put zero-copies host numpy (the device array
    ALIASES the staging buffer), so donation is unusable there and
    would warn per compile; accelerators copy on upload, so donating
    lets XLA recycle the wire's bytes into the outputs."""
    try:
        d = device if device is not None else jax.devices()[0]
        return d.platform != "cpu"
    except Exception:  # noqa: BLE001 — backend quirks: lose the optimization only
        return False


def _prefetch_async(arr) -> None:
    """Start the device->host copy of `arr` without blocking (the
    launch stage calls this right after the dispatch, so the readback
    overlaps the NEXT batch's host work instead of serializing behind
    it — on a remote device the transfer is a full network RTT)."""
    try:
        arr.copy_to_host_async()
    except (AttributeError, NotImplementedError):  # pragma: no cover
        pass  # backend without async host copies: fetch pays the wait


class _FusedFetch:
    """One shared readback for a FUSED launch group: the k batches'
    packed results ride one stacked device array, transferred ONCE
    (whichever waiter arrives first pays it); each handle reads its
    slice.  Slicing per batch keeps the commit closures unchanged."""

    __slots__ = ("_arr", "_lock", "_np")

    def __init__(self, arr):
        self._arr = arr
        self._lock = threading.Lock()
        self._np = None

    def get(self, i: int):
        with self._lock:
            if self._np is None:
                self._np = host_readback(self._arr)
                self._arr = None  # drop the device reference
            return self._np[i]


@dataclass
class _Staged:
    """A prepared batch between the stage and launch steps: the packed
    wire's H2D upload is already in flight; `solo` launches it alone,
    while same-`fuse_key` neighbors waiting at the launch gate can ride
    one fused program instead (ColumnarPipeline._launch_in_order)."""

    solo: Callable            # state -> (state, packed)
    fuse_key: object = None   # None = not fuse-eligible (fallback wire)
    wire_dev: object = None   # uploaded packed wire (dict-wire path)
    wide: bool = False        # the ANSWER: absolute lo/hi planes, not i32 deltas
    # The wire that carried it, for the mesh tally and the launch's
    # label: the per-lane wire (a word a value) or, by default, the
    # dictionary wire; the distinct configurations counted on the way
    # (0 where the dictionary was not tried); the stage's transfer
    # calls, set beside each call: one buffer, one device_put, on
    # either wire.
    lane_wire: bool = False
    config_rows: int = 0
    uploads: int = 0


class ColumnsHandle:
    """Deferred result of one pipelined columnar batch
    (MeshBucketStore.apply_columns_async).  Commits apply strictly in
    dispatch order — result() drains every older in-flight batch —
    but the device->host READBACK runs outside the ordering locks:
    concurrent waiters overlap their transfers (on a remote device each
    readback is a full network RTT, so serializing them caps the whole
    service at 1/RTT batches per second).

    The handle is created at the END of the prepare stage (its `ticket`
    is the batch's reservation in the plan-order journal) and becomes
    fetchable once the launch stage ran: `_fetch` blocks on the launch
    event, so a drain that overtakes a not-yet-launched batch simply
    waits for its dispatcher thread to reach the launch gate."""

    def __init__(self, store, commit_fn, limit_col, hits_col=None):
        self._store = store
        self._fetch_fn: "Optional[Callable]" = None  # set by the launch
        self._commit_fn = commit_fn
        self._fetched = None
        self._fetch_lock = threading.Lock()
        self._launched = threading.Event()
        self._launch_exc: "Optional[BaseException]" = None
        self._exc: "Optional[BaseException]" = None
        self._limit = limit_col
        self._hits = hits_col  # conservation-ledger twin of the decode
        self._value = None
        self.ticket = -1  # plan-order reservation (set by the pipeline)
        self.done = False
        # tracing.BatchTrace of the submitting batcher (None when the
        # batch carried no sampled lanes): stage spans for this batch
        # parent under its window span and link its member lanes.
        self._trace = None

    # -- launch side (dispatcher threads) ------------------------------
    def _launch_ok(self, fetch_fn) -> None:
        self._fetch_fn = fetch_fn
        self._launched.set()

    def _launch_fail(self, exc: BaseException) -> None:
        self._launch_exc = exc
        self._launched.set()

    # -- resolve side --------------------------------------------------
    def _fetch(self):
        """Blocking device readback; idempotent and safe to call from
        any thread (no store/drain lock held).  Returns None when the
        handle already resolved (a racing waiter's courtesy fetch)."""
        with self._fetch_lock:
            if self.done:
                return None
            if self._fetched is None:
                self._launched.wait()
                if self._launch_exc is not None:
                    raise self._launch_exc
                self._fetched = self._fetch_fn()
                self._fetch_fn = None
            return self._fetched

    def _do_resolve(self) -> None:
        store = self._store
        try:
            with phase("dispatch.fetch", self._trace, ticket=self.ticket) as ph:
                packed_np = self._fetch()
        except Exception as e:  # noqa: BLE001 — launch failure
            self._finish_exc(e)
            return
        store._observe_stage("fetch", ph.dt_s)
        try:
            with phase("dispatch.commit", self._trace, ticket=self.ticket) as ph:
                status, remaining, reset = self._commit_fn(packed_np)
        except Exception as e:  # noqa: BLE001 — surfaced at result()
            self._finish_exc(e)
            return
        store._observe_stage("commit", ph.dt_s)
        # Conservation ledger (audit.py), fed from the decode the commit
        # just produced: hits GRANTED by the device (UNDER_LIMIT lanes)
        # and the negative-remaining tripwire — two vectorized reductions
        # per batch, the applied-side twin of the dispatch-side count in
        # _submit_pipelined.
        hits = self._hits
        if hits is not None:
            st = np.asarray(status)
            n = min(len(hits), len(st))
            audit.note(
                "applied_hits",
                int(np.asarray(hits[:n])[st[:n] == 0].sum()),  # 0 = UNDER_LIMIT
            )
            rem = np.asarray(remaining)
            neg = int((rem < 0).sum())
            if neg:
                audit.note("negative_remaining", neg)
        self._value = {
            "status": status,
            "limit": self._limit,
            "remaining": remaining,
            "reset_time": reset,
        }
        # Drop the closures: they pin the planner (C++ batch + key
        # buffer), the device output array, and the padded columns.
        # done flips under the fetch lock so a racing waiter's _fetch
        # never sees half-cleared state.
        self._commit_fn = None
        with self._fetch_lock:
            self._fetched = None
            self.done = True

    def _finish_exc(self, exc: BaseException) -> None:
        """Record a launch/commit failure as this handle's outcome so
        the FIFO drain can keep resolving younger batches; result()
        re-raises."""
        self._exc = exc
        self._commit_fn = None
        with self._fetch_lock:
            self._fetched = None
            self.done = True

    def prefetch(self) -> None:
        """Nonblocking hint from the drainer's backlog path.  The
        launch stage already requested the async device->host copy, so
        there is nothing further to do without blocking; kept as an
        explicit extension point for transports whose launch-side
        prefetch is unavailable.  MUST NOT touch `_fetch_lock` — a
        resolver holds it across the blocking readback, and this hint
        fires from service threads that must never stall an RTT."""

    def result(self) -> dict:
        if not self.done:
            try:
                self._fetch()  # overlap readbacks across waiter threads
            except Exception:  # noqa: BLE001
                pass  # the ordered drain records it as this handle's outcome
            self._store._drain_until(self)
        if self._exc is not None:
            raise self._exc
        return self._value


class ColumnarPipeline:
    """Mixin: the three-stage overlapped dispatch pipeline for columnar
    batches (architecture.md "Dispatch pipeline").

    Each batch moves through:

      1. PREPARE — slot-table planning (the only table-mutating step),
         under `_plan_lock`.  The batch's position in the plan order is
         its reservation TICKET; the `_inflight` FIFO appended here is
         the reservation journal — commit order is defined at plan
         time, before any device work.
      2. STAGE — pack the wire and START the H2D upload.  No locks:
         batch N+1's packing runs while batch N computes on device.
      3. LAUNCH — ticket order, under `_lock`, reduced to the
         state-threading jit call (state and wire donated).  Consecutive
         same-shape batches already staged at the gate launch FUSED —
         one program applies them sequentially — so the fixed
         per-dispatch cost amortizes under backlog.
      4. FETCH (no locks; the launch pre-requested the async copy) and
         COMMIT (FIFO under `_drain_lock`, table writes guarded by the
         per-table native mutex + `_lock` for host mirrors).

    Locks, in acquisition order (never the reverse):
      * `_plan_lock` — serializes prepares; owns ticket assignment.
      * `_drain_lock` — serializes resolvers; held across the blocking
        device readback so results commit strictly in dispatch order.
      * `_lock` (the store mutation RLock) — guards the donated device
        buffers; taken by launches and by resolvers ONLY for the
        post-readback decode/commit.

    Batch N+1's PREPARE overlaps batch N's COMMIT: the two hold
    different Python locks, and the C++ slot tables carry their own
    per-table mutex (host_runtime.cpp), so call-level interleaving is
    safe.  The semantics are the pipelined-staleness contract unchanged:
    planning reads table expiry that may lag by the unresolved depth,
    the kernel revalidates expiry device-side, and per-slot
    pending-write counts keep in-flight slots uneviction-able.
    """

    # Launch-fusion cap: group sizes are restricted to {1, 2, 4} — each
    # (size, wire shape) is a distinct XLA program, and on a remote
    # device every program's first dispatch pays an executable load.
    MAX_FUSE = 4

    def _init_pipeline(self) -> None:
        self._inflight: "deque[ColumnsHandle]" = deque()
        self._drain_lock = threading.Lock()
        self._plan_lock = threading.Lock()
        self._launch_cv = threading.Condition()
        self._next_ticket = 0
        self._next_launch = 0
        self._launch_gate: "Dict[int, tuple]" = {}  # ticket -> (_Staged, handle)
        self._launch_aborted: set = set()  # tombstoned tickets (abort path)
        self._stage_stats: "Dict[str, list]" = {}
        self._stats_lock = threading.Lock()
        self._depth_hwm = 0
        self._seen_wire_shapes: set = set()  # (W, narrow) staged so far
        # The widest per-shard pad bucket warm-up compiled for the shapes
        # it was GIVEN (MeshBucketStore.warmup; 0: it was given none).
        # The native ingress pump keeps a take inside it.
        self.warm_bucket = 0
        # Device programs launched by this store's columnar pipeline —
        # the "telemetry adds zero device dispatches" contract is
        # pinned by COUNTING this (tests/test_observability.py), the
        # replica_commit_dispatches playbook.
        self.device_dispatches = 0

    # -- observability (metrics.observe_dispatch scrapes these) --------
    def _observe_stage(self, stage: str, dt: float) -> None:
        # The per-scrape stage gauge, fed from the reading the stage's
        # `phase()` took (which already reached the always-on reservoir).
        with self._stats_lock:
            st = self._stage_stats.setdefault(stage, [0, 0.0, 0.0])
            st[0] += 1
            st[1] += dt
            st[2] = max(st[2], dt)

    def pipeline_depth(self) -> int:
        """Batches dispatched but not yet resolved (gauge value)."""
        return len(self._inflight)

    def dispatch_under_way(self) -> bool:
        """The host's dispatch section is occupied: a batch is being
        planned, or holds a ticket and has not launched yet.  A batch
        that has launched (on the device, or being read back) does not
        count.  Read without a lock: the express admission rule
        (service._ExpressPolicy) takes it as a hint of whether a
        dispatch made now would wait its turn behind another."""
        return (self._next_ticket != self._next_launch
                or self._plan_lock.locked())

    def occupancy_stats(self) -> "List[dict]":
        """Per-shard occupancy from the HOST slot tables the dispatch
        commits already maintain — THE one occupancy read of the
        saturation plane (zero device programs; consumed by
        Metrics.observe_saturation and V1Service.debug_status): one
        row per shard table (+ the optional back tier)."""
        back_cap = self.back_capacity_per_shard
        out = []
        for s, t in enumerate(self.tables):
            row = {
                "shard": s,
                "used": len(t),
                "capacity": int(t.capacity),
                "evictions": int(t.evictions),
            }
            if hasattr(t, "index_stats"):
                # The native table's key index: lookups, probes,
                # refused hash hits, entries (probes a lookup is the
                # chain length a key pays).
                row["index"] = t.index_stats
            if back_cap:
                # `used`, `capacity` and the ratio are the FRONT's (the
                # table the kernel addresses); `evictions` counts
                # buckets lost from either tier, a demotion is not one.
                _, back_used, demotions, promotions, back_ev = t.tier_stats
                row["back_used"] = back_used
                row["back_capacity"] = back_cap
                row["demotions"] = demotions
                row["promotions"] = promotions
                row["back_evictions"] = back_ev
            out.append(row)
        return out

    def take_pipeline_stats(self):
        """Drain the per-stage timing aggregates accumulated since the
        last call: ({stage: (count, total_s, max_s)}, depth, depth_hwm).
        Cleared per scrape, like the breaker gauges (PR 1 convention)."""
        with self._stats_lock:
            out = {k: tuple(v) for k, v in self._stage_stats.items()}
            self._stage_stats.clear()
            hwm = self._depth_hwm
            self._depth_hwm = len(self._inflight)
        return out, len(self._inflight), hwm

    # -- the three-stage dispatch driver -------------------------------
    def _submit_pipelined(self, keys, cols, now_ms: int,
                          force_wire: Optional[str] = None) -> "ColumnsHandle":
        """Run prepare -> stage -> launch for one batch and return its
        enqueued handle.  Subclasses provide `_prepare_columns` (table
        planning, returns a prep object with a `.commit` closure),
        `_stage_columns` (pack + upload, returns a _Staged), and
        `_launch_group` (the locked jit call for 1..MAX_FUSE staged
        batches)."""
        bt = tracing.take_batch_trace()  # staged by the batcher (if sampled)
        # Conservation ledger (audit.py): hits entering the device
        # dispatch — the earlier-layer twin of the applied-hits count at
        # commit decode (applied <= dispatched is the device invariant).
        audit.note("dispatched_hits", int(cols.hits.sum()))
        # dispatch.prepare keeps its extent (its clock starts before the
        # plan lock); dispatch.plan_wait inside it is the lock alone.
        with phase("dispatch.prepare", bt) as ph:
            with phase("dispatch.plan_wait", bt):
                self._plan_lock.acquire()
            try:
                prep = self._prepare_columns(keys, cols, now_ms, force_wire, bt)
                handle = ColumnsHandle(self, prep.commit, cols.limit, cols.hits)
                handle._trace = bt
                handle.ticket = self._next_ticket
                self._next_ticket += 1
                self._inflight.append(handle)
                with self._stats_lock:
                    self._depth_hwm = max(self._depth_hwm, len(self._inflight))
            finally:
                self._plan_lock.release()
            # What the mesh adds: every shard pads to the fullest one's
            # bucket, so one launch scatters `shards * prep.padded` lanes.
            shards, fullest = self._shard_fill(prep)
            padded = shards * prep.padded
            ph.note(ticket=handle.ticket, lanes=prep.n, shards=shards,
                    fullest=fullest, padded=padded)
        self._observe_stage("prepare", ph.dt_s)
        # Lane utilization: real lanes vs the pow2-padded shape the
        # launch will scatter (saturation plane; drained per scrape).
        saturation.lane_util.add(prep.n, padded)
        try:
            with phase("dispatch.stage", bt, ticket=handle.ticket,
                       shards=shards, fullest=fullest, padded=padded) as ph:
                staged = self._stage_columns(prep)
            self._observe_stage("stage", ph.dt_s)
        except BaseException as e:
            self._abort_launch_turn(handle, e)
            raise
        # The same with the shards' fill and the wire the stage took,
        # cumulative (/debug/device `mesh`).
        saturation.mesh_tally.add(
            shards, prep.n, padded, fullest, prep.n_rounds,
            staged.lane_wire, staged.config_rows, staged.uploads,
            cols.calendar_lanes, staged.wide, cols.flagged_lanes,
        )
        self._launch_in_order(handle, staged)
        return handle

    def _retire_aborted_locked(self) -> None:
        """Advance past tombstoned (aborted) tickets; `_launch_cv` held."""
        while self._next_launch in self._launch_aborted:
            self._launch_aborted.discard(self._next_launch)
            self._next_launch += 1
        # Tombstones of already-passed tickets (a waiter aborted while
        # a fusing launcher swept it up) can never retire: drop them.
        self._launch_aborted = {
            t for t in self._launch_aborted if t > self._next_launch
        }

    def _abort_launch_turn(self, group_or_handle, exc: BaseException) -> None:
        """A failure after tickets were reserved — staging raised, or an
        asynchronous exception (KeyboardInterrupt) landed while waiting
        at the gate: mark the handle(s) failed and retire their launch
        turns WITHOUT blocking.  If the turn is current it advances now;
        otherwise a tombstone makes whichever launcher next advances
        skip it — so an interrupted dispatcher can never wedge younger
        tickets or the resolvers waiting on their launch events."""
        handles = (
            [h for _, h in group_or_handle]
            if isinstance(group_or_handle, list) else [group_or_handle]
        )
        for h in handles:
            h._launch_fail(exc)
        with self._launch_cv:
            for h in handles:
                self._launch_gate.pop(h.ticket, None)
                self._launch_aborted.add(h.ticket)
            self._retire_aborted_locked()
            self._launch_cv.notify_all()

    def _launch_in_order(self, handle: "ColumnsHandle",
                         staged: "_Staged") -> None:
        ticket = handle.ticket
        bt = handle._trace
        group = None
        try:
            # dispatch.gate_wait: this ticket's launch turn (an older
            # ticket is still staging or launching).
            with phase("dispatch.gate_wait", bt, ticket=ticket), self._launch_cv:
                if self._next_launch != ticket:
                    self._launch_gate[ticket] = (staged, handle)
                    while (self._next_launch != ticket
                           and not handle._launched.is_set()):
                        self._launch_cv.wait(0.1)
                    self._launch_gate.pop(ticket, None)
                    if handle._launched.is_set():
                        return  # an older launcher fused this batch into its group
                group = [(staged, handle)]
                if staged.fuse_key is not None:
                    # Collect contiguous already-staged successors of the
                    # same wire shape/kind.  Contiguity is required — the
                    # launch turn advances past exactly this group, so a
                    # gap ticket must not be skipped.
                    avail = []
                    nt = ticket + 1
                    while (len(avail) < self.MAX_FUSE - 1
                           and nt in self._launch_gate
                           and self._launch_gate[nt][0].fuse_key == staged.fuse_key):
                        avail.append(nt)
                        nt += 1
                    take = 3 if len(avail) >= 3 else (1 if avail else 0)
                    for t2 in avail[:take]:
                        group.append(self._launch_gate.pop(t2))
        except BaseException as e:  # async interrupt mid-wait/collect
            self._abort_launch_turn(group or handle, e)
            raise
        exc: "Optional[BaseException]" = None
        # dispatch.launch keeps its extent (its clock starts before the
        # store lock); dispatch.launch_wait inside it is the lock alone.
        # The span and the profiler event go to the launcher's own
        # batch; a fused group's other batches get a launch span each
        # below (each batch's trace sees the one program).
        with phase("dispatch.launch", bt, ticket=ticket, fused=len(group)) as ph:
            with phase("dispatch.launch_wait", bt, ticket=ticket):
                self._lock.acquire()
            try:
                self._launch_group(group)
            except BaseException as e:  # noqa: BLE001
                exc = e
            finally:
                self._lock.release()
        dt = ph.dt_s
        self._observe_stage("launch", dt)
        # Lane-time pool (profiling.py): these lanes rode a launch of
        # this wall cost — the tenant ledger's proportional-share
        # denominator (the per-launch timing telemetry also drains).
        profiling.note_lane_time(
            sum(len(h._limit) for _, h in group), dt
        )
        for _, h in group[1:]:
            tracing.stage_span("dispatch.launch", dt, h._trace,
                               ticket=h.ticket, fused=len(group))
        if exc is not None:
            for _, h in group:
                h._launch_fail(exc)
        with self._launch_cv:
            self._next_launch = ticket + len(group)
            self._retire_aborted_locked()
            self._launch_cv.notify_all()
        if exc is not None:
            raise exc

    def _shard_fill(self, prep) -> "Tuple[int, int]":
        """(shards a take is split over, lanes on its fullest shard);
        the mesh store overrides."""
        return 1, prep.n

    # -- launch hooks (the store's device topology) ---------------------
    def _pre_launch(self) -> None:
        """Hook: device work that must precede the group's programs
        (the mesh drains its queued tier moves here)."""

    def _fused_launch_fn(self, k: int, wide: bool):
        """Hook: the jitted K-batch fused program for this store's
        device topology."""
        raise NotImplementedError

    def _program_label(self, group) -> str:
        """XLA-telemetry program identity for one launch group: solo vs
        fused-K, then the wire and the answer's width — the axes along
        which distinct programs compile.  A dictionary-wire launch is
        named by its answer alone (`narrow`: i32[S, 4, P] deltas,
        `wide`: the absolute 64-bit answer as i32[S, 8, P] lo/hi
        planes); a per-lane launch is `lanes` (i32 columns, narrow
        answer) or `lanes64` (lo/hi pairs up, lo/hi planes down)."""
        staged = group[0][0]
        shape = "solo" if len(group) == 1 else f"fused{len(group)}"
        if staged.lane_wire:
            form = "lanes64" if staged.wide else "lanes"
        else:
            form = "wide" if staged.wide else "narrow"
        return f"mesh:dispatch:{shape}:{form}"

    def _launch_group(self, group) -> None:
        """Stage 3 (ticket order, under `_lock`): just the
        state-threading jit call, on device arrays alone (the state and
        the staged wires: no host->device transfer, which
        tests/test_wire_header.py lets JAX refuse).  A multi-batch
        group rides ONE fused program; each handle's fetch reads its
        slice of the shared stacked result, transferred once.  Either
        answer is a 32-bit array ([S, 4, P] narrow, [S, 8, P] wide;
        [k, ...] stacked): no 64-bit array leaves the device here."""
        self._pre_launch()
        # One program per group (fused or solo) — counted, not timed:
        # the zero-extra-dispatch telemetry contract asserts on this.
        self.device_dispatches += 1
        saturation.mesh_tally.add_launch(len(group))
        # lazy: the wide-answer programs warm-up deliberately defers,
        # the per-lane wire's and the fused launches', so their first
        # post-steady compile is by design, not shape churn.  The
        # dictionary wire's solo wide program is warmed (a monthly
        # calendar lane takes it): a compile of it counts.
        staged = group[0][0]
        with telemetry.program(
            self._program_label(group),
            lazy=staged.wide and (staged.lane_wire or len(group) > 1),
        ):
            if len(group) == 1:
                staged, h = group[0]
                self.state, packed = staged.solo(self.state)
                h._launch_ok(partial(host_readback, packed))
                _prefetch_async(packed)
                return
            fn = self._fused_launch_fn(len(group), group[0][0].wide)
            self.state, stacked = fn(
                self.state, *[s.wire_dev for s, _ in group]
            )
            shared = _FusedFetch(stacked)
            for i, (_, h) in enumerate(group):
                h._launch_ok(partial(shared.get, i))
            _prefetch_async(stacked)

    # -- resolve / drain ordering --------------------------------------
    def _drain_until(self, handle: "ColumnsHandle") -> None:
        with self._drain_lock:
            if handle.done:
                return  # a concurrent drain already resolved it
            while self._inflight:
                h = self._inflight.popleft()
                h._do_resolve()
                if h is handle:
                    return
            if not handle.done:  # not in the deque (already popped elsewhere)
                handle._do_resolve()

    def _drain_all(self) -> None:
        with self._drain_lock:
            while self._inflight:
                self._inflight.popleft()._do_resolve()

    def _drain_then_lock(self) -> None:
        """Acquire the plan + store locks with the pipeline empty:
        non-columnar mutators (dataclass apply, snapshot, loader,
        GLOBAL sync) must observe every older batch's table commits
        first, and must block new prepares while they hold the state.
        Release with `_unlock_drained`.  Loops defensively, though with
        `_plan_lock` held no new handle can enter the FIFO."""
        self._plan_lock.acquire()
        while True:
            self._drain_all()
            self._lock.acquire()
            if not self._inflight:
                return
            self._lock.release()

    def _unlock_drained(self) -> None:
        self._lock.release()
        self._plan_lock.release()


def build_round_arrays(chunk: Sequence[_Prepared], padded: int) -> Tuple[np.ndarray, ...]:
    """Columnize one round of prepared requests into kernel input arrays."""
    slot = np.full(padded, -1, dtype=np.int32)
    exists = np.zeros(padded, dtype=bool)
    algo = np.zeros(padded, dtype=np.int32)
    behavior = np.zeros(padded, dtype=np.int32)
    hits = np.zeros(padded, dtype=np.int64)
    limit = np.zeros(padded, dtype=np.int64)
    duration = np.zeros(padded, dtype=np.int64)
    greg_expire = np.zeros(padded, dtype=np.int64)
    greg_duration = np.zeros(padded, dtype=np.int64)
    for i, p in enumerate(chunk):
        slot[i] = p.slot
        exists[i] = p.exists
        algo[i] = int(p.req.algorithm)
        behavior[i] = int(p.req.behavior)
        hits[i] = p.req.hits
        limit[i] = p.req.limit
        duration[i] = p.req.duration
        greg_expire[i] = p.greg_expire
        greg_duration[i] = p.greg_duration
    return slot, exists, algo, behavior, hits, limit, duration, greg_expire, greg_duration


def make_store_resolver(table, algo_mirror, store, inject_fn, now_ms: int):
    """Slot resolution wrapped with the reference's Store call pattern:
    cache miss -> store.get -> inject (algorithms.go:26-33); cached item
    with switched algorithm -> store.remove + re-get
    (algorithms.go:54-62,196-204).  One resolver per shard table,
    one store."""

    def resolve(p):
        slot, exists = table.lookup_or_assign(p.key, now_ms)
        req = p.req
        if exists and algo_mirror[slot] != int(req.algorithm):
            # Algorithm switch: reference removes from cache AND store,
            # then re-reads the store on the retry pass.
            store.remove(p.key)
            item, ok = store.get(req)
            if ok and item is not None and int(item.algorithm) == int(req.algorithm):
                inject_fn(slot, item)
                return slot, True
            return slot, False
        if not exists:
            item, ok = store.get(req)
            if ok and item is not None and int(item.algorithm) != int(req.algorithm):
                # c.Add + failed type-cast -> remove both + re-get.
                store.remove(p.key)
                item, ok = store.get(req)
            if ok and item is not None:
                inject_fn(slot, item)
                # Note: an already-expired store item is recreated by the
                # kernel's expiry check rather than resurrected
                # (divergence: the reference trusts store items without
                # re-checking ExpireAt for one request).
                return slot, True
        return slot, exists

    return resolve


def item_to_rows(item) -> "buckets.BucketRows":
    """Convert one SPI CacheItem to a single-row BucketRows."""
    from ..store import LeakyBucketItem

    v = item.value
    if isinstance(v, LeakyBucketItem):
        return buckets.BucketRows(
            algo=np.array([int(Algorithm.LEAKY_BUCKET)], np.int32),
            limit=np.array([v.limit], np.int64),
            remaining=np.array([int(v.remaining * buckets.LEAKY_SCALE)], np.int64),
            duration=np.array([v.duration], np.int64),
            stamp=np.array([v.updated_at], np.int64),
            expire_at=np.array([item.expire_at], np.int64),
            status=np.array([0], np.int32),
        )
    return buckets.BucketRows(
        algo=np.array([int(Algorithm.TOKEN_BUCKET)], np.int32),
        limit=np.array([v.limit], np.int64),
        remaining=np.array([v.remaining], np.int64),
        duration=np.array([v.duration], np.int64),
        stamp=np.array([v.created_at], np.int64),
        expire_at=np.array([item.expire_at], np.int64),
        status=np.array([int(v.status)], np.int32),
    )


def _rows_to_items(keys, rows):
    """Convert gathered device rows to SPI CacheItems (store.go:11-24)."""
    from ..store import CacheItem, LeakyBucketItem, TokenBucketItem

    algo = np.asarray(rows.algo)
    limit = np.asarray(rows.limit)
    remaining = np.asarray(rows.remaining)
    duration = np.asarray(rows.duration)
    stamp = np.asarray(rows.stamp)
    expire = np.asarray(rows.expire_at)
    status = np.asarray(rows.status)
    items = []
    for i, key in enumerate(keys):
        if algo[i] == int(Algorithm.LEAKY_BUCKET):
            value = LeakyBucketItem(
                limit=int(limit[i]),
                duration=int(duration[i]),
                remaining=remaining[i] / buckets.LEAKY_SCALE,
                updated_at=int(stamp[i]),
            )
        else:
            value = TokenBucketItem(
                limit=int(limit[i]),
                duration=int(duration[i]),
                remaining=int(remaining[i]),
                created_at=int(stamp[i]),
                status=int(status[i]),
            )
        items.append(
            CacheItem(algorithm=int(algo[i]), key=key, value=value, expire_at=int(expire[i]))
        )
    return items
