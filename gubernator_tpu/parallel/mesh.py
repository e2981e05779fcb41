"""Mesh-sharded bucket store: key ownership = device shard.

The TPU-native replacement for the reference's peer cluster
(replicated_hash.go key->owner + per-peer caches): bucket state columns
get a leading shard axis laid out over a 1-D `jax.sharding.Mesh`, and
one program applies every shard's request sub-batch to its own state
slice in a single dispatch.  What the reference does with N gRPC
servers and a consistent-hash ring across processes, this does with N
devices and a static shardmap inside one XLA program — peer traffic
becomes ICI traffic.

GLOBAL behavior (Behavior.GLOBAL) is fully supported: non-owner shards
answer from replica columns and accumulate hits device-side; a periodic
`sync_globals()` runs ONE shard_map collective program (psum hit
aggregation -> owner apply -> psum status broadcast) in place of the
reference's three RPC pipelines (global.go).  See ops/global_ops.py.

Key -> shard assignment is `fnv1a(key) % n_shards` (a static shardmap;
the dynamic-membership ring remains at the host/daemon tier for
multi-process deployments, parallel/hash_ring.py).  The mesh is static
for the process lifetime — the reference drops bucket state on
membership change anyway (architecture.md:5-11), so elasticity lives at
the host tier in both designs.
"""

from __future__ import annotations

import collections
from dataclasses import dataclass
import threading
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import saturation, telemetry
from ..saturation import phase
from ..models.shard import (
    ColumnarPipeline,
    RoundPlanner,
    _rows_to_items,
    _Staged,
    _wire_donate_ok,
    build_round_arrays,
    host_readback,
    item_to_rows,
    make_columns,
    make_store_resolver,
    narrow_ok,
    pad_size,
    plan_grouped_python,
    prepare_requests,
    split_routing_bits,
)
from ..models.slot_table import SlotTable
from ..ops import buckets, global_ops
from ..types import (
    Behavior,
    RateLimitRequest,
    RateLimitResponse,
    UpdatePeerGlobal,
    has_behavior,
)
from ..utils import hashing
from .global_mgr import GlobalKeyTable, GlobalsColumns, HitColumns


def shard_of_key(key: str, n_shards: int) -> int:
    """Static shardmap: fnv1a-64 of the hash key, modulo shard count."""
    return hashing.hash_string_64(key) % n_shards


def _pad_pow2(n: int, floor: int = 8) -> int:
    """Own pow2 size buckets (>= floor) for variable-length index
    arrays handed to jitted programs: every distinct shape is its own
    XLA compile, so unpadded tick-to-tick sizes would recompile inside
    the store lock."""
    m = floor
    while m < n:
        m <<= 1
    return m


@partial(jax.jit, donate_argnums=(0, 1))
def _answer_jit(state, gcols, batch, extra, now):
    """Per-shard answer kernel with PACKED output: one i64[S, 5, B]
    array carries status/removed/cached (bit-packed), limit, remaining,
    reset_time, new_expire, so the host pays ONE device->host transfer
    per round instead of seven (each blocking readback is its own
    device round trip)."""

    def one(state_s, gcols_s, batch_s, extra_s):
        ns, ng, out, cached = global_ops.answer_batch(
            state_s, gcols_s, batch_s, extra_s, now, cold_cond=False
        )
        row0 = (
            out.status.astype(jnp.int64)
            | (out.removed.astype(jnp.int64) << 1)
            | (cached.astype(jnp.int64) << 2)
        )
        packed = jnp.stack(
            (row0, out.limit, out.remaining, out.reset_time, out.new_expire)
        )
        return ns, ng, packed

    return jax.vmap(one)(state, gcols, batch, extra)


@partial(jax.jit, donate_argnums=(0, 1))
def _answer_rounds_jit(state, gcols, batch, extra, round_id, n_rounds, now):
    """Fused multi-round answer: ALL duplicate rounds of ALL shards run
    inside one dispatch (`lax.while_loop` over rounds, like
    buckets.apply_rounds), with the same packed i64[S, 5, B] output as
    _answer_jit.  One device round-trip per batch regardless of key
    multiplicity — the thundering-herd case costs the same dispatch as
    a uniform batch.  `n_rounds` is a traced scalar: one compilation
    serves every round count at a given batch width."""

    def one(state_s, gcols_s, batch_s, extra_s, rid_s):
        B = batch_s.slot.shape[0]
        packed0 = jnp.zeros((5, B), jnp.int64)

        def cond(c):
            return c[0] < n_rounds

        def body(c):
            r, st, gc, packed = c
            active = rid_s == r
            b_r = batch_s._replace(slot=jnp.where(active, batch_s.slot, -1))
            e_r = extra_s._replace(gslot=jnp.where(active, extra_s.gslot, -1))
            st, gc, out, cached = global_ops.answer_batch(st, gc, b_r, e_r, now, cold_cond=False)
            row0 = (
                out.status.astype(jnp.int64)
                | (out.removed.astype(jnp.int64) << 1)
                | (cached.astype(jnp.int64) << 2)
            )
            newp = jnp.stack(
                (row0, out.limit, out.remaining, out.reset_time, out.new_expire)
            )
            packed = jnp.where(active[None, :], newp, packed)
            return r + 1, st, gc, packed

        _, st, gc, packed = jax.lax.while_loop(
            cond, body, (jnp.asarray(0, jnp.int32), state_s, gcols_s, packed0)
        )
        return st, gc, packed

    return jax.vmap(one)(state, gcols, batch, extra, round_id)


# The dispatch programs take (state, wire...) and nothing else: the
# round count and the clock ride the wire's header (buckets.wire_header),
# read by slices INSIDE the program, so a launch hands the runtime device
# arrays alone and uploads nothing (a Python or numpy scalar argument is
# a transfer call of its own in front of every program: two of them were
# 0.44 ms of a launch on a TPU v5e).
#
# Each runs under `shard_map` over the store's mesh (_dispatch_jit):
# every device runs the body on ITS OWN shard's rows, so the header it
# reads is its own row's copy and no device waits for another.  (Under
# a plain `jit` the partitioner turns "shard 0's word, replicated" into
# an all-reduce: the four chips would rendezvous at the start of every
# dispatch, the first waiting until the host has launched the last, and
# a dispatch program would be a collective: see _SYNC_COLLECTIVE_LOCK.)
# The header is read outside the `vmap` over the device's rows: a rounds
# loop whose bound is batched runs predicated, and selects the whole
# carried table every round.


def _rounds_over_rows(kernel, state, wire, **kw):
    n_rounds, now = buckets.wire_header(wire)

    def one(state_s, w_s):
        return kernel(state_s, w_s, n_rounds, now, cold_cond=False, **kw)

    return jax.vmap(one)(state, wire)


def _rounds_lanes_mesh(state, wire):
    """Per-lane rounds behind the single-buffer wire ([S, 11P+4] i32,
    the layout buckets.pack_lane_wire documents and the native encode
    fills): what a batch the dictionary cannot hold dispatches, and one
    sharded transfer like the dictionary's.
    One i32[S, 4, B] packed result."""
    return _rounds_over_rows(buckets.apply_rounds_lanes, state, wire)


def _rounds_lanes_wide_mesh(state, wire):
    """The per-lane wire of values exceeding int32: [S, 16P+4], lo/hi
    pairs, and the wide answer as it leaves the device: i32[S, 8, B],
    lo planes then hi planes (buckets.WIDE_ANSWER_ROWS)."""
    return _rounds_over_rows(buckets.apply_rounds_lanes, state, wire, wide=True)


def _rounds_packed_mesh(state, wire):
    """Dict-wire rounds behind the single-buffer wire ([S, 3P+1796]
    i32, the layout buckets.pack_dict_wire documents and the native
    encode fills): one sharded transfer per batch."""
    return _rounds_over_rows(buckets.apply_rounds_packed, state, wire)


def _rounds_packed_wide_mesh(state, wire):
    """Wide-output packed dict wire (values beyond int32 — monthly/
    yearly Gregorian expiries): the absolute 64-bit answer as
    i32[S, 8, B], lo planes then hi planes (buckets.WIDE_ANSWER_ROWS),
    so no 64-bit array crosses to the host."""
    return _rounds_over_rows(buckets.apply_rounds_packed_wide, state, wire)


def _per_device(mesh: Mesh, body, stacked: bool = False):
    """`body(state, *wires)` run by every device of `mesh` on its own
    shard's rows.  `stacked`: the answer carries a leading axis in front
    of the shards' (the fused programs' [k, S, 4, P]; [k, S, 8, P]
    wide).  No `check_vma`:
    the rounds loop starts its carry from constants and ends it
    per-device, which the check refuses and the loop means."""
    axis = mesh.axis_names[0]
    return shard_map(
        body, mesh=mesh, in_specs=P(axis),
        out_specs=(P(axis), P(None, axis) if stacked else P(axis)),
        check_vma=False,
    )


# One jitted program per (mesh, body, donation), module-wide: stores
# over the same devices share their compiled programs.
_DISPATCH_JIT: dict = {}


def _dispatch_jit(mesh: Mesh, body, donate_wire: bool = False):
    """The solo dispatch program `body` (one of the `_rounds_*_mesh`)
    over `mesh`, the state donated.  `donate_wire`: the twin for the
    overlapped dispatch pipeline — the wire is a fresh per-batch
    sharded upload nothing reads afterwards, so on real accelerators
    (not CPU, which zero-copies uploads) XLA can recycle its bytes into
    the outputs."""
    key = (mesh, body, donate_wire)
    fn = _DISPATCH_JIT.get(key)
    if fn is None:
        fn = _DISPATCH_JIT[key] = jax.jit(
            _per_device(mesh, body),
            donate_argnums=(0, 1) if donate_wire else 0,
        )
    return fn


# Launch-fusion programs (ColumnarPipeline._launch_group): K same-shape
# dict-wire batches applied SEQUENTIALLY inside one sharded program —
# batch i+1 sees batch i's state, exactly as K solo dispatches would,
# but the host pays one dispatch and one stacked readback for the
# group.  Cached per (mesh, k, wide, donate) module-wide.
_MESH_FUSED_JIT: dict = {}


def _mesh_fused_packed_jit(mesh: Mesh, k: int, wide: bool,
                           donate_wires: bool = True):
    key = (mesh, k, wide, donate_wires)
    fn = _MESH_FUSED_JIT.get(key)
    if fn is None:
        base = _rounds_packed_wide_mesh if wide else _rounds_packed_mesh

        def run(state, *wires):
            outs = []
            for wire in wires:
                state, packed = base(state, wire)
                outs.append(packed)
            return state, jnp.stack(outs)  # [k, S, 4, P]; wide [k, S, 8, P]

        donate = tuple(range(k + 1)) if donate_wires else (0,)
        fn = jax.jit(_per_device(mesh, run, stacked=True), donate_argnums=donate)
        _MESH_FUSED_JIT[key] = fn
        telemetry.note_program_created(
            f"mesh_fused:k{k}:{'wide' if wide else 'narrow'}"
        )
    return fn


@partial(jax.jit, donate_argnums=0)
def _set_replica_jit(gcols, gslots, status, limit, remaining, reset):
    return jax.vmap(
        global_ops.set_replica, in_axes=(0, None, None, None, None, None)
    )(gcols, gslots, status, limit, remaining, reset)


@partial(jax.jit, donate_argnums=0)
def _clear_jit(gcols, idx):
    return jax.vmap(global_ops.clear_gslots, in_axes=(0, None))(gcols, idx)


# A tier-move block: i32[S, 5, P], the five columns of one drain window
# of every shard in ONE upload.  Rows: promo kind, promo src, promo dst,
# demo src, demo dst; a record whose src is -1 is a no-op (padding, or a
# move the host cancelled).
_MOVE_SRC_ROWS = (1, 3)


def _move_block(shards: int, padded: int) -> np.ndarray:
    block = np.zeros((shards, 5, padded), dtype=np.int32)
    block[:, _MOVE_SRC_ROWS, :] = -1
    return block


@partial(jax.jit, donate_argnums=(0, 1))
def _moves_mesh_jit(state, back, moves):
    """Apply one drain window of tier moves on every shard (see
    buckets.apply_moves; `moves` is a tier-move block)."""
    return jax.vmap(lambda st, bk, mv: buckets.apply_moves(st, bk, *mv))(
        state, back, moves
    )


@partial(jax.jit, donate_argnums=0)
def _write_row_jit(state, s, slot, rows):
    # Donated single-row scatter: store-miss injection / loader placement
    # without copying the whole [S, C] state.  `rows` is a logical
    # BucketRows; decompose into the split i32 layout first.
    vals = buckets.rows_to_split(rows)
    return jax.tree.map(lambda col, val: col.at[s, slot].set(val[0]), state, vals)


@jax.jit
def _gather_rows_mesh_jit(state, slots):
    """Reshard drain/merge gather: full bucket rows for [S, P] padded
    slot arrays — ONE device program per drain batch regardless of lane
    count (padding lanes carry slot sentinels whose garbage rows the
    host masks by per-shard count)."""
    return jax.vmap(buckets.read_rows)(state, slots)


@partial(jax.jit, donate_argnums=0)
def _write_rows_mesh_jit(state, slots, rows):
    """Reshard commit scatter: [S, P] transferred rows in one donated
    program (slot -1 = padding, dropped inside buckets.write_rows)."""
    return jax.vmap(buckets.write_rows)(state, slots, rows)


_SYNC_FN_CACHE: dict = {}

# Process-wide serialization of the GLOBAL sync collective — the mesh's
# ONLY cross-device rendezvous program (psum aggregate -> owner apply ->
# psum broadcast).  Two MeshBucketStores sharing one device set (the
# multi-daemon in-process test cluster on the 8-device virtual CPU
# mesh) can otherwise enqueue their sync programs in different per-
# device orders, and two interleaved rendezvous deadlock every device
# queue behind them.  Held from dispatch through the blocking readback;
# non-collective programs never rendezvous, so they need no ordering.
# Production runs one daemon (one store) per process: zero contention.
_SYNC_COLLECTIVE_LOCK = threading.Lock()


# Gslots one launch of the sync program carries (its one width: a store
# provisioned with fewer carries them all).  A pass takes the gslots
# touched since the last one, this many a launch, so the width is a
# launch's fixed price (upload, kernel lanes, read-back) against the
# launches a burst needs.  Measured on a TPU v5e (PERF.md section 6,
# PR 42): the program takes 0.8 ms at 1024 lanes and 1.6 ms at 4096,
# a pass's hold 5.4 against 7.0 ms, where a pass of the benchmark's
# GLOBAL traffic takes ~100 gslots; under 1024 the launch's fixed
# costs are all that is left.
SYNC_WIDTH = 1024


def _get_sync_fn(mesh: Mesh, axis: str):
    """One compiled GLOBAL-sync collective program per (mesh, axis):
    `(state, gcols, wire) -> (state, gcols, answer)`, the wire and the
    answer as global_ops.pack_sync_wire / unpack_sync_answer have them,
    both replicated (every row of the answer is a psum's result)."""
    key = (mesh, axis)
    fn = _SYNC_FN_CACHE.get(key)
    if fn is None:

        @jax.named_scope(buckets.SCOPE_GLOBAL_SYNC)
        def _sync_body(state, gcols, wire):
            sq = lambda t: jax.tree.map(lambda a: a[0], t)
            ns, ngc, answer = global_ops.global_sync(
                sq(state), sq(gcols), wire, axis=axis
            )
            ex = lambda t: jax.tree.map(lambda a: a[None], t)
            return ex(ns), ex(ngc), answer

        fn = jax.jit(
            shard_map(
                _sync_body,
                mesh=mesh,
                in_specs=(P(axis), P(axis), P()),
                out_specs=(P(axis), P(axis), P()),
            ),
            donate_argnums=(0, 1),
        )
        _SYNC_FN_CACHE[key] = fn
    return fn


def make_mesh(devices: Optional[Sequence[jax.Device]] = None, axis: str = "shard") -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.array(devices), (axis,))


def _locked(fn):
    """Serialize store mutators on the instance lock (donated device
    buffers must never be used concurrently)."""

    def wrapper(self, *args, **kwargs):
        with self._lock:
            return fn(self, *args, **kwargs)

    wrapper.__name__ = fn.__name__
    wrapper.__doc__ = fn.__doc__
    return wrapper


def _drained_locked(fn):
    """_locked plus a pipeline drain first: mutators that read or commit
    the slot tables / state wholesale must observe every in-flight
    columnar batch's commits, and must hold the PLAN lock too so no new
    batch can plan against the state they are mutating
    (ColumnarPipeline._drain_then_lock)."""

    def wrapper(self, *args, **kwargs):
        self._drain_then_lock()
        try:
            return fn(self, *args, **kwargs)
        finally:
            self._unlock_drained()

    wrapper.__name__ = fn.__name__
    wrapper.__doc__ = fn.__doc__
    return wrapper


def _programmed(label, lazy=False):
    """XLA-telemetry label scope as a decorator (telemetry.program):
    applied INSIDE the lock decorators so the recorded wall time is the
    program work, not drain-wait backpressure.  `lazy` marks programs
    warmup deliberately defers (telemetry.program's lazy contract)."""

    def deco(fn):
        def wrapper(self, *args, **kwargs):
            with telemetry.program(label, lazy=lazy):
                return fn(self, *args, **kwargs)

        wrapper.__name__ = fn.__name__
        wrapper.__doc__ = fn.__doc__
        return wrapper

    return deco


@dataclass
class _MeshPrep:
    """Output of MeshBucketStore's prepare stage: the mesh plan plus
    the commit closure, handed to the unlocked stage step."""

    cols: object
    now_ms: int
    force_wire: Optional[str]
    n: int
    fullest: int  # lanes on the fullest shard: what `padded` pads
    padded: int
    n_rounds: int
    narrow: bool
    mp: object  # NativeMeshPlanner
    pos: np.ndarray
    commit: object
    bt: object = None  # the take's sampled trace, for the stage's phases


@dataclass
class SyncResult:
    """Host-tier work produced by one GLOBAL sync collective.

    Both legs come back in COLUMN form, emitted straight from the sync
    decode arrays (no per-key dataclasses): `broadcast_cols` feeds the
    encode-once UpdatePeerGlobals fan-out, `remote_hit_cols` rides the
    columnar GetPeerRateLimits forward.  The dataclass views
    (`broadcasts` / `remote_hits`) materialize lazily for tests and the
    classic legs."""

    broadcast_cols: Optional[GlobalsColumns] = None
    remote_hit_cols: Optional[HitColumns] = None
    # False only for a tick that found no GLOBAL lane pending: it never
    # ran the collective, so observers tuning windows from sync cost
    # must ignore it.
    did_work: bool = True

    @property
    def broadcasts(self) -> List[UpdatePeerGlobal]:
        if self.broadcast_cols is None:
            return []
        return self.broadcast_cols.to_updates()

    @property
    def remote_hits(self) -> List[RateLimitRequest]:
        if self.remote_hit_cols is None:
            return []
        return self.remote_hit_cols.to_requests()

    @property
    def broadcast_count(self) -> int:
        return 0 if self.broadcast_cols is None else len(self.broadcast_cols)


class MeshBucketStore(ColumnarPipeline):
    """Bucket tables for all local shards, sharded over a device mesh.

    The host keeps one SlotTable per shard; requests are bucketed by
    `shard_of_key`, each shard's stream is round-planned independently
    (duplicate keys serialize within their shard), and all shards' round
    r runs as ONE sharded program dispatch.

    `apply(..., home_shard=s)` models the reference's ingress topology:
    the request arrived at peer s, which may not own the key.  GLOBAL
    requests at a non-owner answer locally (replica cache or as-if-owner
    fallback, gubernator.go:231-255) and forward hits at the next
    `sync_globals()`.  Non-GLOBAL requests always route to the owner
    (the in-process equivalent of the BATCHING forward,
    peer_client.go:237-268).
    """

    def __init__(
        self,
        capacity_per_shard: int = 50_000,
        g_capacity: int = 4096,
        mesh: Optional[Mesh] = None,
        devices: Optional[Sequence[jax.Device]] = None,
        store=None,
        use_native: bool = True,
        back_capacity_per_shard: int = 0,
    ):
        """back_capacity_per_shard > 0 enables the two-tier table: a
        small FRONT table (capacity_per_shard) absorbs every kernel
        scatter — whose cost scales with the table it targets — while
        front LRU evictions DEMOTE rows to a big device-resident back
        tier instead of dropping them, and later lookups PROMOTE them
        back.  Total capacity = front + back per shard; a demoted row
        takes a back slot that a promotion, an expiry or a removal
        freed before any other, so state is lost only when front and
        back together are full (then the back slot under the ring cursor
        goes: by ring position, not by age).  Requires the native
        runtime; incompatible with the Store SPI (whose resolver
        injects rows synchronously mid-round).

        Sizing contract: the front must hold one BATCH's per-shard
        working set (unique keys) with room to spare — a single batch
        whose unique keys exceed the front capacity exhausts the
        pending-write eviction guard and degrades to the planner's
        all-pending fallback (reference-grade state loss, exactly as a
        single-tier table at that capacity would).  The tiering wins
        when the churn is ACROSS batches: each batch's keys fit the
        front, while the long-tail keyspace lives in the back."""
        self.store = store
        # One mutation lock: apply/sync/inject swap donated device
        # buffers, so concurrent callers (gateway handler threads, the
        # GlobalManager tick) must serialize — the role of the
        # reference's cache mutex (gubernator.go:336-337), held per
        # BATCH here instead of per request.
        self._lock = threading.RLock()
        self.mesh = mesh if mesh is not None else make_mesh(devices)
        (self.axis,) = self.mesh.axis_names
        self.n_shards = self.mesh.devices.size
        self.capacity_per_shard = capacity_per_shard
        self.g_capacity = g_capacity
        # C++ slot tables when the native runtime is available: the
        # Python scheduling loop stays (plan_grouped_python), but every
        # lookup/commit runs at C++ hash-map speed.
        from .. import native as _native

        self._native = use_native and _native.available()
        self._init_pipeline()  # FIFO of in-flight columnar batches
        _table = _native.NativeSlotTable if self._native else SlotTable
        self.tables = [_table(capacity_per_shard) for _ in range(self.n_shards)]
        self.back_capacity_per_shard = back_capacity_per_shard
        if back_capacity_per_shard > 0:
            if not self._native:
                raise RuntimeError("two-tier table requires the native runtime")
            if store is not None:
                raise ValueError("two-tier table is incompatible with a Store SPI")
            for t in self.tables:
                t.enable_back(back_capacity_per_shard)
        # One [S, C] array: per-shard views via algo_mirror[s], and the
        # columnar commit updates it with ONE vectorized scatter.
        self.algo_mirror = np.zeros(
            (self.n_shards, capacity_per_shard), dtype=np.int32
        )
        self.gtable = GlobalKeyTable(g_capacity)
        self.dirty = np.zeros((self.n_shards, g_capacity), dtype=bool)
        # The gslots the next sync pass takes, and the only rows it can
        # change: a superset of {g: some shard's ghits[g] > 0, or
        # dirty[., g]}.  Device-side ghits grow only under a lane that
        # carries its gslot (`p.gslot = g` in `apply`) and `dirty` is set
        # at two sites (`apply`, `_note_global_owners`); each marks the
        # gslot here, and the pass that zeroes both clears the mark.
        # (A gslot recycled in between keeps its mark: the pass then
        # finds no hits and the new key's configuration, as a pass over
        # every row would.)
        self._gtouched = np.zeros(g_capacity, dtype=bool)
        self._sync_width = min(SYNC_WIDTH, g_capacity)
        # A GLOBAL lane was planned since the last sync pass (owner dirt
        # OR device-side ghits).  Raised in `apply` and lowered by the
        # pass, both under the drained lock; `sync_globals` reads it
        # lock-free to skip a tick with nothing pending.
        self._global_pending = False
        # Device programs dispatched by replica-batch commits — the
        # O(1)-dispatch-per-broadcast contract is pinned by counting,
        # not timing (tests/test_global_plane.py).
        self.replica_commit_dispatches = 0
        # Same counting contract for the resharding plane
        # (tests/test_reshard.py): one gather program per drain batch,
        # gather+scatter (2) per transfer commit.
        self.transfer_drain_dispatches = 0
        self.transfer_commit_dispatches = 0

        self._sharding = NamedSharding(self.mesh, P(self.axis))
        self._replicated = NamedSharding(self.mesh, P())
        # Wire donation (launch stage): accelerators copy uploads, so
        # the wire buffer is recyclable; CPU zero-copies host numpy.
        self._wire_donate = _wire_donate_ok(self.mesh.devices.flat[0])
        self.state = self._stack_and_shard(buckets.init_state(capacity_per_shard))
        # The back tier starts all zero (buckets.init_back) and is the
        # big one (64 B a slot): zero pages go up from the host as they
        # are, where _stack_and_shard would make it on the device, fetch
        # it, copy it and put it back (20 s against 2 s for 32.5M slots).
        self.back = (
            jax.tree.map(
                lambda a: jax.device_put(
                    np.zeros((self.n_shards,) + a.shape, a.dtype), self._sharding
                ),
                jax.eval_shape(lambda: buckets.init_back(back_capacity_per_shard)),
            )
            if back_capacity_per_shard > 0
            else None
        )
        self.gcols = self._stack_and_shard(global_ops.init_global_columns(g_capacity))
        # Drain windows the plans have closed and no launch has applied
        # yet, oldest first, a tier-move block each.  A plan closes its
        # own window into a block of ITS pad bucket (a take
        # of n lanes queues at most n promotions and n demotions), so
        # the move program has one shape a pad bucket, the ones warm-up
        # compiles (`_move_buckets`), and a backlog of several plans
        # drains in as many launches.
        self._move_backlog: collections.deque = collections.deque()
        self._move_buckets: List[int] = []

        # Jitted programs are MODULE-level (or cached per mesh) so every
        # store/daemon in a process shares one XLA compilation cache —
        # per-instance closures would recompile everything per daemon.
        self._answer_fn = _answer_jit
        self._answer_rounds_fn = _answer_rounds_jit
        self._sync_fn = _get_sync_fn(self.mesh, self.axis)
        self._set_replica_fn = _set_replica_jit
        self._clear_fn = _clear_jit
        self._write_row_fn = _write_row_jit

    def _stack_and_shard(self, single):
        stacked = jax.tree.map(
            lambda c: np.broadcast_to(np.asarray(c), (self.n_shards,) + c.shape).copy(), single
        )
        return jax.tree.map(lambda c: jax.device_put(c, self._sharding), stacked)

    def _close_move_window(self, padded: int = 0) -> None:
        """Close every table's drain window into one tier-move block on
        the backlog (caller holds the plan lock, so nothing queues
        meanwhile).  `padded` is the pad bucket of the plan that queued
        the moves; without one (a mutator outside the columnar path)
        the block takes the smallest bucket warm-up compiled that holds
        them.  Nothing queued: nothing appended."""
        counts = [t.move_counts() for t in self.tables]
        need = max(max(c) for c in counts)
        if need == 0:
            return
        if padded < need:
            padded = next(
                (b for b in self._move_buckets if b >= need), pad_size(need)
            )
        block = _move_block(self.n_shards, padded)
        for s, t in enumerate(self.tables):
            if any(counts[s]):
                taken = t.take_moves_into(block[s])
                assert taken == counts[s], (taken, counts[s])
        self._move_backlog.append(block)

    def _launch_moves(self) -> None:
        """Apply the backlog's drain windows, oldest first, one launch
        of the move program each (caller holds the store lock), so the
        rows are in their new homes before any program that reads front
        rows.  No-op (no dispatch, no phase) when nothing is queued —
        the steady state for front-resident traffic."""
        if not self._move_backlog:
            return
        with phase("dispatch.moves"):
            while self._move_backlog:
                block = self._move_backlog.popleft()
                with telemetry.program("mesh:tier_moves"):
                    self.state, self.back = _moves_mesh_jit(
                        self.state, self.back,
                        jax.device_put(block, self._sharding),
                    )

    def _drain_moves(self) -> None:
        """Apply every queued tier move (caller holds the plan lock and
        the store lock: the mutators outside the columnar path, which
        queue moves in the C++ tables and need them on the device at
        once)."""
        if self.back is None:
            return
        self._close_move_window()
        self._launch_moves()

    # ------------------------------------------------------------------
    @_drained_locked
    def apply(
        self,
        requests: Sequence[RateLimitRequest],
        now_ms: int,
        home_shard: Optional[int] = None,
        remote_global: bool = False,
    ) -> List[RateLimitResponse]:
        """Evaluate a batch across all shards; responses in request order.

        remote_global=True marks every GLOBAL request's authoritative
        owner as a REMOTE daemon (V1Service sets this when the hash ring
        maps the key to another peer): the key is answered locally from
        its replica cache / fallback bucket, hits accumulate device-side,
        and sync_globals() surfaces the totals for the host to forward.
        """
        responses: List[Optional[RateLimitResponse]] = [None] * len(requests)
        prepared = prepare_requests(requests, now_ms, responses)

        by_shard: List[list] = [[] for _ in range(self.n_shards)]
        for p in prepared:
            owner = shard_of_key(p.key, self.n_shards)
            target = owner
            if has_behavior(p.req.behavior, Behavior.GLOBAL):
                # Owner dirt or non-owner ghits: either owes a sync pass.
                self._global_pending = True
                owner_mark = -1 if remote_global else owner
                g, evicted = self.gtable.lookup_or_assign(p.key, owner_mark)
                if evicted is not None:
                    self.gcols = self._clear_fn(self.gcols, np.array([evicted], np.int32))
                self.gtable.update_config(g, p.req, p.greg_expire, p.greg_duration)
                self._gtouched[g] = True
                non_owner = remote_global or (home_shard is not None and home_shard != owner)
                if non_owner:
                    # Non-owner: answer locally, forward hits at sync
                    # (gubernator.go:231-255).
                    p.gslot = g
                    target = owner if remote_global else home_shard
                    if self.gtable.rep_expire[g] >= now_ms:
                        p.cached_hint = True
                else:
                    # Owner applies directly and owes a broadcast
                    # (getRateLimit's QueueUpdate, gubernator.go:339-341).
                    self.dirty[owner, g] = True
            by_shard[target].append(p)

        if self.store is None:
            self._apply_fused(by_shard, now_ms, responses)
        else:
            # Store SPI needs per-round host callbacks (get/on_change
            # between rounds), so it keeps the interleaved loop.
            planners = [
                RoundPlanner(
                    self.tables[s],
                    by_shard[s],
                    now_ms,
                    resolver=self._store_resolver(s, now_ms),
                )
                for s in range(self.n_shards)
            ]
            while True:
                chunks = [pl.next_chunk() for pl in planners]
                if not any(chunks):
                    break
                self._run_round(chunks, now_ms, responses)

        return [r if r is not None else RateLimitResponse() for r in responses]

    # ------------------------------------------------------------------
    # Columnar bulk ingress (zero-dataclass hot path)
    # ------------------------------------------------------------------
    @property
    def supports_columns(self) -> bool:
        """True when the zero-dataclass bulk path is usable (native host
        runtime present, no synchronous Store SPI callbacks)."""
        return self._native and self.store is None

    def describe_topology(self) -> Tuple[str, str]:
        """(backend platform, mesh shape string) for the
        gubernator_build_info gauge: e.g. ("tpu", "8") for a flat
        8-device mesh."""
        try:
            platform = self.mesh.devices.flat[0].platform
        except Exception:  # noqa: BLE001
            platform = "unknown"
        return platform, "x".join(str(d) for d in self.mesh.devices.shape)

    def apply_columns(
        self, keys, algorithm, behavior, hits, limit, duration, now_ms: int,
        greg_expire=None, greg_duration=None, force_wire=None,
    ) -> dict:
        """Columnar bulk API over the whole mesh: keys bucket onto
        shards by the static shardmap (fnv1a % n_shards, batched in
        C++), each shard's stream round-plans in its own C++ table, and
        ALL shards' rounds run in ONE fused dispatch.  Returns a dict of
        numpy arrays (status/limit/remaining/reset_time) aligned with
        `keys`.

        Every lane is taken as owned by this daemon and entered at its
        owner shard, a GLOBAL lane too: it is applied to the owner's
        bucket like any other lane of the dispatch, and the plan does
        the owner's book-keeping for it (`_note_global_owners`: its
        gslot, its configuration, the owner row dirty for the next sync
        pass; upstream's getRateLimit + QueueUpdate,
        gubernator.go:339-341).  What only `apply` can say keeps the
        dataclass path: a GLOBAL lane whose owner is another daemon
        (`remote_global`: answered from the replica, hits forwarded) and
        a lane that entered at another shard than its owner's
        (`home_shard`).  NO_BATCHING and MULTI_REGION change nothing
        here (the caller queues a MULTI_REGION lane's hits)."""
        return self.apply_columns_async(
            keys, algorithm, behavior, hits, limit, duration, now_ms,
            greg_expire, greg_duration, force_wire=force_wire,
        ).result()

    def apply_columns_async(
        self, keys, algorithm, behavior, hits, limit, duration, now_ms: int,
        greg_expire=None, greg_duration=None, force_wire=None,
    ) -> ColumnsHandle:
        """Pipelined apply_columns: plans and enqueues the batch, then
        returns immediately with a ColumnsHandle; `handle.result()`
        blocks on the one packed readback.  Dispatching batch i+1
        before resolving batch i overlaps host planning and transfer
        with device compute via the ColumnarPipeline locks (the
        reference's interval-drained queues, peer_client.go:272-312,
        feeding a device instead of a socket).

        Pipelined planning reads slot-table expiry that is stale by the
        unresolved depth; the kernel revalidates expiry device-side, so
        the only observable effect is eviction under pressure acting on
        slightly old expire times."""
        if not (self._native and self.store is None):
            raise RuntimeError(
                "apply_columns requires the native host runtime and no Store SPI"
            )
        cols = make_columns(
            algorithm, behavior, hits, limit, duration, len(keys),
            greg_expire, greg_duration,
        )
        split_routing_bits(cols)
        return self._submit_pipelined(keys, cols, now_ms, force_wire)

    def _note_global_owners(self, keys, cols, pos, padded: int) -> None:
        """The owner's book-keeping of a batch's GLOBAL lanes, a batch
        at a time (caller holds the plan lock, which a sync pass holds
        too: the pass that takes this dirt has drained this batch, so
        the status it broadcasts holds these hits).  What is done a
        LANE: its key is read from `keys` and stored in a dict, so that
        a key's LAST lane wins, as a request at a time would leave it.
        What is done a DISTINCT key: its gslot is looked up or assigned,
        its configuration written, its owner row marked dirty.  The mesh
        tally counts both (`globalLanes`, `globalKeys`)."""
        last = {keys[i]: i for i in cols.global_lanes.tolist()}
        saturation.mesh_tally.add_global_note(len(cols.global_lanes), len(last))
        idx = np.fromiter(last.values(), np.int64, len(last))
        owner = pos[idx] // padded  # the shard the plan put the lane on
        g, evicted = self.gtable.assign_columns(list(last), owner)
        for ev in evicted:
            # One row a call: the shape `apply` clears, so no new program.
            with self._lock:
                self.gcols = self._clear_fn(self.gcols, np.array([ev], np.int32))
        self.gtable.update_config_columns(
            g, cols.algo[idx], cols.sent_behavior[idx] & ~int(Behavior.GLOBAL),
            cols.limit[idx], cols.duration[idx], cols.greg_expire[idx],
            cols.greg_duration[idx],
        )
        self.dirty[owner, g] = True
        self._gtouched[g] = True
        self._global_pending = True

    def _prepare_columns(self, keys, cols, now_ms: int,
                         force_wire: Optional[str] = None,
                         bt=None) -> "_MeshPrep":
        """Stage 1 of the overlapped dispatch (under `_plan_lock`): the
        slot-table work only — gt_mesh_begin + gt_mesh_plan_grouped
        (hash/bucket every key, per-shard grouped round planning,
        padded [S, P] fill).  Tier moves queued by this plan are closed
        into one block of the plan's pad bucket; the LAUNCH stage
        applies the backlog, ordered against the device program.  The commit side stays ONE C++ call
        (gt_mesh_finish_*: decode, slot-table commit, original-order
        scatter), safe against the NEXT batch's concurrent planning via
        the per-table native mutex."""
        from .. import native as _native

        n = len(keys)
        # dispatch.plan_native: the two C++ calls alone, inside
        # dispatch.prepare — what of a prepare is the slot table.
        with phase("dispatch.plan_native", bt):
            mp = _native.NativeMeshPlanner(self.tables, keys, now_ms)
            fullest = int(mp.counts.max()) if n else 0
            padded = pad_size(max(fullest, 1))
            n_rounds = mp.plan_grouped(
                cols, int(Behavior.RESET_REMAINING), padded
            )
        if self.back is not None:
            self._close_move_window(padded)
        pos = mp.pos[:n]
        if cols.global_lanes is not None:
            with phase("dispatch.global_note", bt, lanes=len(cols.global_lanes)):
                self._note_global_owners(keys, cols, pos, padded)
        narrow = narrow_ok(cols, now_ms) and force_wire != "wide"

        def commit(packed_np):
            with self._lock:
                if narrow:
                    status, rem, reset = mp.finish_narrow(packed_np, now_ms)
                else:
                    status, rem, reset = mp.finish_wide(packed_np)
                if n:
                    # Host algo mirror (Store-SPI bookkeeping parity):
                    # one vectorized 2-D scatter, no per-shard masks.
                    self.algo_mirror[
                        pos // padded, mp.slot.reshape(-1)[pos]
                    ] = cols.algo
            return status, rem, reset

        return _MeshPrep(
            cols=cols, now_ms=now_ms, force_wire=force_wire, n=n,
            fullest=fullest, padded=padded, n_rounds=n_rounds, narrow=narrow,
            mp=mp, pos=pos, commit=commit, bt=bt,
        )

    def _stage_columns(self, prep: "_MeshPrep") -> "_Staged":
        """Stage 2 (no locks): encode the wire and start the sharded
        H2D upload while older batches compute/transfer.

        Two wires carry a batch, and `prep.narrow` is neither: it is
        the width of the ANSWER (every value fits the i32 deltas), which
        picks the program on either wire.  The DICTIONARY wire holds a
        batch of at most DICT_TABLE_ROWS distinct configurations: one
        i32 buffer of 3 words a lane plus the table, ONE transfer.  A
        batch of more (a limit a key), of more than 255 rounds, with an
        `occ` past 65,535, or with `force_wire` set ("narrow" / "wide":
        the PER-LANE wire, named by the answer width it pins; warm-up
        and tests use it) rides the per-lane wire: one i32 buffer too,
        of 11 words a lane (16 with the wide answer) and no table, and
        ONE transfer.  Either wire is encoded by ONE native call beside
        the plan (NativeMeshPlanner.encode_wire: it counts the
        configurations, applies this rule and fills the buffer, header
        and all, with the interpreter released; buckets.pack_dict_wire,
        pack_lane_wire and build_config_dict are the numpy reference
        the tests hold it to, and nothing here calls them) and unpacked
        by slices inside the jitted program, and either carries the
        round count and the clock in its header: the launch passes
        device arrays alone and uploads nothing.  `dispatch.upload`
        times the one transfer call; what the stage takes beyond it is
        the encode.  The wire taken, the configurations counted and the
        transfer calls made ride the _Staged into the mesh tally."""
        padded, narrow = prep.padded, prep.narrow
        wire, lane_wire, config_rows = prep.mp.encode_wire(
            prep.cols, prep.now_ms, prep.n_rounds, narrow,
            prep.force_wire is not None,
            buckets.dict_wire_words(padded),
            buckets.lane_wire_words(padded, wide=not narrow),
        )
        if not lane_wire:
            # Values live in the dict wire's 256-row i64 table, so wide
            # batches (monthly/yearly Gregorian) stay on it too — only
            # the output width switches (apply_rounds_packed_wide).
            # Single-buffer wire: ONE sharded host->device transfer per
            # batch instead of 12 (per-call overhead dominates at
            # service batch sizes).
            with phase("dispatch.upload", prep.bt, wire="dict"):
                wire_dev = jax.device_put(wire, self._sharding)
            # (A single-round compacted scatter — commit only the write
            # lanes — measured slower on TPU; see git history.)
            fn_packed = _dispatch_jit(
                self.mesh,
                _rounds_packed_mesh if narrow else _rounds_packed_wide_mesh,
                donate_wire=self._wire_donate,
            )
            with self._stats_lock:
                self._seen_wire_shapes.add((wire.shape[1], narrow))
            return _Staged(
                solo=lambda state: fn_packed(state, wire_dev),
                fuse_key=("dict", narrow, wire.shape[1]),
                wire_dev=wire_dev,
                wide=not narrow, config_rows=config_rows, uploads=1,
            )
        # The per-lane wire: a word a value for a narrow answer, a lo/hi
        # pair for a wide one, in one buffer like the dictionary's.
        with phase("dispatch.upload", prep.bt, wire="lanes"):
            wire_dev = jax.device_put(wire, self._sharding)
        fn_lanes = _dispatch_jit(
            self.mesh, _rounds_lanes_mesh if narrow else _rounds_lanes_wide_mesh
        )
        return _Staged(
            solo=lambda state: fn_lanes(state, wire_dev),
            wide=not narrow, lane_wire=True, config_rows=config_rows,
            uploads=1,
        )

    def _shard_fill(self, prep) -> Tuple[int, int]:
        return self.n_shards, prep.fullest

    def _pre_launch(self) -> None:
        # Tier moves queued by the group's plans must land before the
        # batch programs read front rows.  The whole backlog goes:
        # moves queued by a LATER plan are safe to apply early — the
        # pending-write guard keeps every in-flight batch's slots out
        # of the mover's reach.
        self._launch_moves()

    def _fused_launch_fn(self, k: int, wide: bool):
        return _mesh_fused_packed_jit(
            self.mesh, k, wide, donate_wires=self._wire_donate
        )

    # ------------------------------------------------------------------
    def _apply_fused(self, by_shard, now_ms: int, responses) -> None:
        """One dispatch for the whole batch: every shard's rounds run
        inside _answer_rounds_jit; one packed readback; one commit."""
        if not any(by_shard):
            return  # every request failed validation: nothing to dispatch
        S = self.n_shards
        plans = []
        n_rounds = 1
        maxb = 1
        for s in range(S):
            rid, occ, wr, nr = plan_grouped_python(
                self.tables[s], by_shard[s], now_ms
            )
            plans.append((rid, occ, wr))
            n_rounds = max(n_rounds, nr)
            maxb = max(maxb, len(by_shard[s]))
        self._drain_moves()  # tier moves queued by plan_grouped_python
        padded = pad_size(maxb)
        cols = [build_round_arrays(by_shard[s], padded) for s in range(S)]
        stacked = [np.stack([c[f] for c in cols]) for f in range(9)]
        rid_a = np.zeros((S, padded), np.int32)
        occ_a = np.zeros((S, padded), np.int32)
        wr_a = np.zeros((S, padded), dtype=bool)
        gslot = np.full((S, padded), -1, dtype=np.int32)
        for s in range(S):
            m = len(by_shard[s])
            if not m:
                continue
            rid, occ, wr = plans[s]
            rid_a[s, :m] = rid
            occ_a[s, :m] = occ
            wr_a[s, :m] = wr
            for i, p in enumerate(by_shard[s]):
                gslot[s, i] = p.gslot

        batch = buckets.RequestBatch(
            *[jnp.asarray(a) for a in stacked],
            occ=jnp.asarray(occ_a),
            write=jnp.asarray(wr_a),
        )
        batch = jax.tree.map(lambda c: jax.device_put(c, self._sharding), batch)
        extra = global_ops.GlobalBatchExtra(
            gslot=jax.device_put(jnp.asarray(gslot), self._sharding)
        )
        rid_dev = jax.device_put(jnp.asarray(rid_a), self._sharding)

        self.state, self.gcols, packed = self._answer_rounds_fn(
            self.state, self.gcols, batch, extra, rid_dev, n_rounds, now_ms
        )

        # Only scattering lanes commit bookkeeping (grouped
        # intermediates' new_expire is not the final state).
        self._decode_commit_respond(packed, by_shard, responses, write=wr_a)

    def _decode_commit_respond(self, packed, chunks, responses, write=None) -> np.ndarray:
        """Shared tail of both dispatch paths: decode the packed
        [S, 5, B] device result, fill responses, and fold bookkeeping
        back into the slot tables.  `write` masks which lanes commit
        (None = every non-cached lane, the single-round case).  Returns
        the cached mask for the Store-SPI caller."""
        packed_np = host_readback(packed)  # the one blocking transfer
        row0 = packed_np[:, 0]
        out_status = (row0 & 1).astype(np.int32)
        out_removed = ((row0 >> 1) & 1).astype(bool)
        cached_np = ((row0 >> 2) & 1).astype(bool)
        out_limit = packed_np[:, 1]
        out_rem = packed_np[:, 2]
        out_reset = packed_np[:, 3]
        out_exp = packed_np[:, 4]

        for s, chunk in enumerate(chunks):
            if not chunk:
                continue
            commit_slots, commit_exp, commit_rm, commit_keys = [], [], [], []
            for i, p in enumerate(chunk):
                commits = write[s, i] if write is not None else True
                if commits and not cached_np[s, i] and p.slot >= 0:
                    commit_slots.append(p.slot)
                    commit_exp.append(out_exp[s, i])
                    commit_rm.append(out_removed[s, i])
                    commit_keys.append(p.key)
                    self.algo_mirror[s][p.slot] = int(p.req.algorithm)
                responses[p.pos] = RateLimitResponse(
                    status=int(out_status[s, i]),
                    limit=int(out_limit[s, i]) if cached_np[s, i] else int(p.req.limit),
                    remaining=int(out_rem[s, i]),
                    reset_time=int(out_reset[s, i]),
                )
            self.tables[s].commit(commit_slots, commit_exp, commit_rm, keys=commit_keys)
        return cached_np

    # ------------------------------------------------------------------
    def _run_round(self, chunks, now_ms: int, responses) -> None:
        self._drain_moves()  # tier moves queued while planning the round
        padded = pad_size(max(max((len(c) for c in chunks), default=1), 1))
        cols = [build_round_arrays(c, padded) for c in chunks]
        stacked = [np.stack([col[f] for col in cols]) for f in range(9)]
        gslot = np.full((self.n_shards, padded), -1, dtype=np.int32)
        for s, chunk in enumerate(chunks):
            for i, p in enumerate(chunk):
                gslot[s, i] = p.gslot

        batch = buckets.RequestBatch(*[jnp.asarray(a) for a in stacked])
        batch = jax.tree.map(lambda c: jax.device_put(c, self._sharding), batch)
        extra = global_ops.GlobalBatchExtra(
            gslot=jax.device_put(jnp.asarray(gslot), self._sharding)
        )

        self.state, self.gcols, packed = self._answer_fn(
            self.state, self.gcols, batch, extra, now_ms
        )

        cached_np = self._decode_commit_respond(packed, chunks, responses)
        if self.store is not None:
            removed_np = (np.asarray(packed)[:, 0] >> 1 & 1).astype(bool)
            for s, chunk in enumerate(chunks):
                if chunk:
                    self._fire_store_callbacks(s, chunk, cached_np[s], removed_np[s])

    # ------------------------------------------------------------------
    # Store SPI (persistence): get() fulfills misses, on_change()
    # observes every applied request, remove() fires on explicit
    # removals — the call pattern of algorithms.go:26-33,64-68,176-177.
    # ------------------------------------------------------------------
    def _store_resolver(self, s: int, now_ms: int):
        return make_store_resolver(
            self.tables[s],
            self.algo_mirror[s],
            self.store,
            lambda slot, item: self._inject(s, slot, item),
            now_ms,
        )

    def _inject(self, s: int, slot: int, item) -> None:
        rows = item_to_rows(item)
        self.algo_mirror[s][slot] = int(rows.algo[0])
        self.state = self._write_row_fn(
            self.state, np.int32(s), np.int32(slot), rows
        )
        self.tables[s].set_expire(slot, item.expire_at)

    def _read_shard_rows(self, s: int, slots):
        idx = np.asarray(slots, np.int32)
        shard_state = jax.tree.map(lambda col: col[s], self.state)
        return jax.tree.map(np.asarray, buckets.read_rows(shard_state, idx))

    def _fire_store_callbacks(self, s: int, chunk, cached_row, removed_row) -> None:
        live = []
        for i, p in enumerate(chunk):
            if cached_row[i] or p.slot < 0:
                continue  # replica-cache answers never touch the store
            if removed_row[i]:
                self.store.remove(p.key)
            else:
                live.append((i, p))
        if not live:
            return
        rows = self._read_shard_rows(s, [p.slot for _, p in live])
        items = _rows_to_items([p.key for _, p in live], rows)
        for (_, p), item in zip(live, items):
            self.store.on_change(p.req, item)

    @_drained_locked
    def load_item(self, item) -> None:
        """Loader.Load path (gubernator.go:78-90), routed to the owner shard."""
        s = shard_of_key(item.key, self.n_shards)
        slot, _ = self.tables[s].lookup_or_assign(item.key, 0)
        # A promotion queued by the resolve would otherwise overwrite
        # the injected row at the next drain.
        self._drain_moves()
        self._inject(s, slot, item)

    @_drained_locked
    def snapshot_items(self):
        """Loader.Save path (gubernator.go:93-111) across all shards.
        Materialized under the lock so a concurrent apply cannot swap
        state buffers mid-snapshot."""
        self._drain_moves()  # pending promotions leave front rows stale
        items = []
        for s in range(self.n_shards):
            keys = self.tables[s].keys()
            if keys:
                slots = [self.tables[s].get_slot(k) for k in keys]
                rows = self._read_shard_rows(s, slots)
                items.extend(_rows_to_items(keys, rows))
            if self.back is not None:
                bkeys, bslots, _ = self.tables[s].back_entries()
                if bkeys:
                    back_shard = jax.tree.map(lambda col: col[s], self.back)
                    rows = jax.tree.map(
                        np.asarray,
                        buckets.read_back_rows(back_shard, bslots),
                    )
                    items.extend(_rows_to_items(bkeys, rows))
        return items

    # ------------------------------------------------------------------
    # Elastic membership: columnar state handoff (reshard.py).
    # ------------------------------------------------------------------
    @_drained_locked
    def resident_keys(self) -> "List[str]":
        """Every key currently resident in the FRONT slot tables (the
        ring-delta scan input).  Back-tier rows do not migrate: they
        are the cold long tail by construction, and a stale row at the
        old owner is unreachable once routing moves — it ages out of
        the FIFO (architecture.md "Membership & resharding" documents
        the bound).  Host-only, no device programs — but it must hold
        the PLAN lock like snapshot_items: the native table's key
        enumeration is a size-then-fill marshal, and a concurrent
        batch planner growing the table between the two calls would
        overrun the fill buffer."""
        out: List[str] = []
        for t in self.tables:
            out.extend(t.keys())
        return out

    def resident_mask(self, keys) -> np.ndarray:
        """Which keys currently map to a slot — the handoff peek's
        observe-don't-create filter (a zero-hit dispatch for an absent
        key would mint a shadow bucket that later rides the transfer
        plane as noise).  Single guarded C++ lookups per key: safe
        without the plan lock, unlike the size-then-fill enumeration
        resident_keys needs it for."""
        out = np.zeros(len(keys), dtype=bool)
        for j, k in enumerate(keys):
            t = self.tables[shard_of_key(k, self.n_shards)]
            out[j] = t.get_slot(k) is not None
        return out

    @_drained_locked
    @_programmed("mesh:reshard_gather", lazy=True)
    def drain_keys(self, keys, now_ms: int, remove: bool = True):
        """Drain moved keys off the device: resolve their slots in the
        host tables and gather the full bucket rows with ONE mesh-wide
        device program (the PR 5 readback playbook in reverse) —
        atomically with respect to dispatches (the pipeline is drained
        and the plan lock held).  With remove=True the keys also leave
        the tables immediately; the resharding handoff passes
        remove=False and calls forget_keys() only after the transfer is
        ACKED, so the old owner's copy stays readable (the
        double-dispatch peek target) for the whole in-flight window and
        an aborted transfer loses nothing.  Keys no longer resident
        (evicted/expired since the ring-delta scan) and GLOBAL keys
        (they migrate through their own replication plane — every peer
        already holds replica state and the new owner's first sync
        takes over aggregation) are skipped.  Returns a
        reshard.TransferColumns."""
        return self._gather_transfer_locked(keys, now_ms, remove,
                                            skip_global=True)

    @_drained_locked
    @_programmed("mesh:snapshot_gather", lazy=True)
    def snapshot_columns(self, now_ms: int):
        """Durability dump (snapshot.py): every FRONT-resident key's
        full bucket row in ONE mesh-wide gather program — drain_keys'
        all-keys variant.  Unlike a reshard drain it KEEPS the tables
        (gather-only) and INCLUDES owner-side GLOBAL buckets (they
        restore as ordinary rows; the gslot table and replica columns
        rebuild from traffic + broadcasts).  Back-tier rows are the
        cold long tail by construction and are not snapshotted — the
        same documented bound as the reshard plane.  Warmup keys stay
        out of the file."""
        keys = [
            k for t in self.tables for k in t.keys()
            if not k.startswith("__warmup__")
        ]
        return self._gather_transfer_locked(keys, now_ms, remove=False,
                                            skip_global=False)

    def _gather_transfer_locked(self, keys, now_ms: int, remove: bool,
                                skip_global: bool):
        from ..reshard import TransferColumns

        per_slot: List[List[int]] = [[] for _ in range(self.n_shards)]
        per_keys: List[List[str]] = [[] for _ in range(self.n_shards)]
        gkeys = self.gtable._key_to_gslot  # noqa: SLF001
        for k in keys:
            if skip_global and k in gkeys:
                continue
            s = shard_of_key(k, self.n_shards)
            slot = self.tables[s].get_slot(k)
            if slot is None:
                continue
            per_slot[s].append(slot)
            per_keys[s].append(k)
        max_n = max((len(x) for x in per_slot), default=0)
        if max_n == 0:
            return TransferColumns.empty()
        # Two-tier: get_slot may have queued promotions; land them so
        # the front rows we gather are current.
        self._drain_moves()
        S = self.n_shards
        P = _pad_pow2(max_n)
        slots = np.full((S, P), -1, dtype=np.int32)
        for s in range(S):
            if per_slot[s]:
                slots[s, : len(per_slot[s])] = per_slot[s]
        rows = jax.tree.map(
            np.asarray,
            _gather_rows_mesh_jit(
                self.state, jax.device_put(slots, self._sharding)
            ),
        )
        self.transfer_drain_dispatches += 1
        self.device_dispatches += 1
        out_keys: List[str] = []
        cols = {
            name: [] for name in (
                "algo", "status", "limit", "remaining", "duration",
                "stamp", "expire_at",
            )
        }
        for s in range(S):
            n = len(per_keys[s])
            if n == 0:
                continue
            out_keys.extend(per_keys[s])
            cols["algo"].append(rows.algo[s, :n])
            cols["status"].append(rows.status[s, :n])
            cols["limit"].append(rows.limit[s, :n])
            cols["remaining"].append(rows.remaining[s, :n])
            cols["duration"].append(rows.duration[s, :n])
            cols["stamp"].append(rows.stamp[s, :n])
            cols["expire_at"].append(rows.expire_at[s, :n])
            if remove:
                for k in per_keys[s]:
                    self.tables[s].remove(k)
        cat = {k: np.concatenate(v) for k, v in cols.items()}
        # Expired rows (warmup keys, long-idle buckets) are removed
        # from the tables like everything else but carry no state worth
        # shipping: filter them out of the wire payload.
        live = np.nonzero(cat["expire_at"] >= now_ms)[0]
        return TransferColumns(
            keys=[out_keys[int(i)] for i in live],
            algorithm=cat["algo"][live].astype(np.int32),
            status=cat["status"][live].astype(np.int32),
            limit=cat["limit"][live].astype(np.int64),
            remaining=cat["remaining"][live].astype(np.int64),
            duration=cat["duration"][live].astype(np.int64),
            stamp=cat["stamp"][live].astype(np.int64),
            expire_at=cat["expire_at"][live].astype(np.int64),
        )

    @_drained_locked
    def forget_keys(self, keys) -> None:
        """Drop keys from the host tables (no device program: a freed
        slot's stale row is overwritten on reassignment, exists=False).
        The resharding handoff calls this after a transfer is ACKED —
        hits the old owner admitted between the drain gather and this
        point are the documented in-flight slack."""
        for k in keys:
            self.tables[shard_of_key(k, self.n_shards)].remove(k)

    @_drained_locked
    @_programmed("mesh:reshard_commit", lazy=True)
    def commit_transfer(self, cols, now_ms: int) -> int:
        """Receive side of an ownership transfer: assign slots for the
        whole batch in the host tables, gather the CURRENT rows for
        keys already resident (they admitted traffic during the handoff
        window), MERGE monotonically (reshard.merge_transfer_rows:
        remaining=min, status/stamp/expire=max — idempotent, so a
        re-delivered transfer cannot double-count), and scatter the
        merged rows back with ONE donated program.  O(1) device
        dispatches per batch (gather + scatter), pinned by counting
        `transfer_commit_dispatches` / `device_dispatches` — the
        set_replica_batch playbook applied to the main bucket tables.
        Returns the number of lanes committed."""
        from ..reshard import merge_transfer_rows

        n = len(cols)
        if n == 0:
            return 0
        # Dead rows (already expired in transit) are not worth a slot.
        fresh = np.nonzero(np.asarray(cols.expire_at) >= now_ms)[0]
        # Duplicate keys keep the LAST lane (dict semantics; also keeps
        # the scatter's indices unique — duplicate scatter order is
        # unspecified).
        seen: Dict[str, int] = {}
        for j in fresh:
            seen[cols.keys[int(j)]] = int(j)
        idx = np.fromiter(seen.values(), dtype=np.int64, count=len(seen))
        if not idx.size:
            return 0
        m = idx.size
        shard_ix = np.empty(m, np.int32)
        slot_ix = np.empty(m, np.int32)
        exists_ix = np.zeros(m, dtype=bool)
        for j, i in enumerate(idx):
            k = cols.keys[int(i)]
            s = shard_of_key(k, self.n_shards)
            slot, exists = self.tables[s].lookup_or_assign(k, now_ms)
            shard_ix[j] = s
            slot_ix[j] = slot
            exists_ix[j] = exists
        # Two-tier: lookup_or_assign may queue promotions for keys that
        # lived in the back tier; land them before reading front rows.
        self._drain_moves()
        S = self.n_shards
        counts = np.bincount(shard_ix, minlength=S)
        P = _pad_pow2(int(counts.max()))
        slots = np.full((S, P), -1, dtype=np.int32)
        lane_of = np.empty(m, np.int64)  # (shard, col) -> flat lane j
        fill = np.zeros(S, np.int64)
        for j in range(m):
            s = int(shard_ix[j])
            slots[s, fill[s]] = slot_ix[j]
            lane_of[j] = s * P + fill[s]
            fill[s] += 1
        slots_dev = jax.device_put(slots, self._sharding)
        cur = jax.tree.map(
            np.asarray, _gather_rows_mesh_jit(self.state, slots_dev)
        )
        flat = lambda a: a.reshape(-1)[lane_of]  # noqa: E731
        merged = merge_transfer_rows(
            {
                "algo": flat(cur.algo),
                "status": flat(cur.status),
                "limit": flat(cur.limit),
                "remaining": flat(cur.remaining),
                "stamp": flat(cur.stamp),
                "expire_at": flat(cur.expire_at),
            },
            cols, idx, now_ms, exists_ix,
        )
        pack = {}
        for name, dtype in (
            ("algo", np.int32), ("status", np.int32), ("limit", np.int64),
            ("remaining", np.int64), ("duration", np.int64),
            ("stamp", np.int64), ("expire_at", np.int64),
        ):
            buf = np.zeros((S * P,), dtype=dtype)
            buf[lane_of] = merged[name]
            pack[name] = buf.reshape(S, P)
        self.state = _write_rows_mesh_jit(
            self.state,
            slots_dev,
            buckets.BucketRows(
                algo=pack["algo"], limit=pack["limit"],
                remaining=pack["remaining"], duration=pack["duration"],
                stamp=pack["stamp"], expire_at=pack["expire_at"],
                status=pack["status"],
            ),
        )
        self.transfer_commit_dispatches += 2
        self.device_dispatches += 2
        # Host mirrors: the algo mirror feeds algorithm-switch
        # detection; the table expiry feeds planning/eviction.
        self.algo_mirror[shard_ix, slot_ix] = merged["algo"]
        for j in range(m):
            self.tables[int(shard_ix[j])].set_expire(
                int(slot_ix[j]), int(merged["expire_at"][j])
            )
        return int(m)

    # ------------------------------------------------------------------
    def set_replica(self, update, now_ms: int) -> None:
        """Receive side of UpdatePeerGlobals (gubernator.go:259-272):
        store the owner daemon's authoritative status in the replica
        columns, expiring at ResetTime.  One code path with the batch
        receive: a single update is a 1-lane batch."""
        self.set_replica_batch(GlobalsColumns.from_updates([update]), now_ms)

    @_locked
    @_programmed("mesh:replica_commit")
    def set_replica_batch(self, cols: "GlobalsColumns", now_ms: int) -> None:
        """Batched receive side of UpdatePeerGlobals: decode the WHOLE
        broadcast into arrays and commit it with ONE gather/scatter
        device program (plus one clear program when assignments evicted
        gslots) and one vectorized host-mirror update — an N-item
        broadcast costs O(1) device dispatches, not N (the pre-columns
        receiver paid a full dispatch/readback RTT per item,
        `replica_commit_dispatches` counts the programs for the tests
        that pin this)."""
        n = len(cols)
        if n == 0:
            return
        gslots = np.empty(n, dtype=np.int64)
        evicted: List[int] = []
        for i, k in enumerate(cols.keys):
            g, ev = self.gtable.lookup_or_assign(k, -1)
            if ev is not None:
                evicted.append(ev)
            gslots[i] = g
        # Keep only lanes whose key STILL maps to its gslot: a lane can
        # go stale when a later assignment in this same batch recycled
        # its gslot under capacity pressure; and duplicate keys keep the
        # LAST lane (dict semantics of the per-item loop this replaces).
        keep = np.fromiter(
            (
                self.gtable._key_to_gslot.get(k) == int(g)  # noqa: SLF001
                for k, g in zip(cols.keys, gslots)
            ),
            dtype=bool, count=n,
        )
        idx = np.nonzero(keep)[0]
        if idx.size > 1:
            g_kept = gslots[idx]
            _, last_rev = np.unique(g_kept[::-1], return_index=True)
            idx = idx[(idx.size - 1) - last_rev]
        if evicted:
            # Zero recycled rows BEFORE the scatter: a slot evicted and
            # reassigned within this batch gets its new values next.
            # Padded to pow2 buckets with out-of-range indices (clear's
            # mode="drop" ignores them) so varying eviction counts stay
            # within a handful of compiled shapes.
            ev = sorted(set(evicted))
            ev_a = np.full(_pad_pow2(len(ev)), self.g_capacity, np.int32)
            ev_a[: len(ev)] = ev
            self.gcols = self._clear_fn(self.gcols, ev_a)
            self.replica_commit_dispatches += 1
        if not idx.size:
            return
        m = idx.size
        pad = _pad_pow2(m)
        # Pad the scatter to pow2 shape buckets: gslot -1 lanes are
        # dropped inside set_replica, so broadcasts of any size share
        # ~log2(g_capacity) compiled programs instead of one per size.
        gsel = np.full(pad, -1, np.int32)
        gsel[:m] = gslots[idx]
        status = np.zeros(pad, np.int32)
        status[:m] = np.asarray(cols.status, dtype=np.int32)[idx]
        limit = np.zeros(pad, np.int64)
        limit[:m] = np.asarray(cols.limit, dtype=np.int64)[idx]
        remaining = np.zeros(pad, np.int64)
        remaining[:m] = np.asarray(cols.remaining, dtype=np.int64)[idx]
        reset = np.zeros(pad, np.int64)
        reset[:m] = np.asarray(cols.reset_time, dtype=np.int64)[idx]
        self.gcols = self._set_replica_fn(
            self.gcols, gsel, status, limit, remaining, reset
        )
        self.replica_commit_dispatches += 1
        # Vectorized host mirror (rep_expire gates the replica-cache
        # hint; algorithm keeps the broadcast's authoritative value).
        self.gtable.rep_expire[gsel[:m]] = reset[:m]
        self.gtable.algorithm[gsel[:m]] = np.asarray(
            cols.algorithm, dtype=np.int32
        )[idx]

    # ------------------------------------------------------------------
    def sync_globals(self, now_ms: int) -> "SyncResult":
        """Run one GLOBAL sync collective (the TPU-native stand-in for
        GlobalSyncWait ticks of all three global.go pipelines).

        The SyncResult carries what the HOST tier must fan out over the
        peer transport: authoritative statuses for keys this daemon owns
        (UpdatePeerGlobals broadcast) and aggregated hit totals for keys
        owned by remote daemons (GetPeerRateLimits forward).

        A tick with no GLOBAL lane planned since the last pass
        (`_global_pending` down) returns first: no drain, no lock, no
        upload, no program, no read-back — the pass would be an identity
        (ghits all zero, nothing dirty).  It is counted as
        `global.tick_idle`.  A lane planned just after the test waits
        for the next tick, as one planned just after a pass always did.

        A pending tick observes two phases: `global.sync_drain` (what
        `_drain_then_lock` waits for: every in-flight batch's commit,
        then both locks — serving-pipeline backpressure) and
        `global.sync` (locks held: dispatch, blocking read-back,
        decode/commit — the real recurring cost of a pass).  The pass
        works on the gslots TOUCHED since the last one (`_gtouched`) and
        on no other row: one upload of their configuration, one program
        of fixed width over them (again for every further `SYNC_WIDTH`
        of them), one read-back of their rows, so it costs what was
        touched and not what `g_capacity` provisions.

        Sets `last_sync_cost_s` to the `global.sync` reading.  The
        GlobalManager's window tuner reads this instead of its own wall
        clock: folding the drain into the window would inflate
        GlobalSyncWait ~10x under load (observed on the contended CPU
        host: wall-time syncs pinned the auto window at its 1s cap)."""
        if not self._global_pending:
            with phase("global.tick_idle"):
                return SyncResult(did_work=False)
        with phase("global.sync_drain"):
            self._drain_then_lock()
        try:
            with phase("global.sync") as ph, telemetry.program("mesh:global_sync"):
                res = self._sync_globals_locked(now_ms)
            # Only passes that ran the collective are valid sync-cost
            # observations (a ~0 no-work pass would pin a min-of-N
            # window estimator at its floor).
            self.last_sync_cost_s = ph.dt_s
            return res
        finally:
            self._unlock_drained()

    def _resolve_owner_slots(self, touched: np.ndarray, now_ms: int) -> None:
        """Resolve each touched GLOBAL key's slot in its owner shard's
        table (`gtable.owner_slot`), assigning one to a key that has
        none.

        A slot the table confirmed stays valid while that table's
        mapping GENERATION stands (bumped by assign/remap/evict/remove
        in both table twins; value/expire writes and in-place expiry
        reuse keep slot ownership and don't bump), so each gslot keeps
        the generation it was confirmed at (`gtable.owner_gen`) and a
        pass with no mapping churn since looks nothing up.  The
        generation is a gslot's own: a gslot no pass took for a while
        is held against the generation of ITS last confirmation, not
        the last pass's.

        Assigning one key can evict another's slot under capacity
        pressure, so iterate to a fixed point (bounded), then drop any
        still-unstable entries from this sync."""
        gt = self.gtable
        local = [
            (int(g), int(o))
            for g, o in zip(touched, gt.owner_shard[touched])
            if o >= 0  # a remote daemon's key has no local slot
        ]
        gens = [t.generation for t in self.tables]
        for _ in range(3):
            changed = False
            for g, o in local:
                if gt.owner_slot[g] >= 0 and gt.owner_gen[g] == gens[o]:
                    continue
                key = gt.key_of(g)
                slot = self.tables[o].get_slot(key)
                if slot is None:
                    slot, _ = self.tables[o].lookup_or_assign(key, now_ms)
                    gens[o] = self.tables[o].generation
                    changed = True
                gt.owner_slot[g] = slot
                gt.owner_gen[g] = gens[o]
            if not changed:
                break
        for g, o in local:
            if gt.owner_gen[g] != gens[o] and (
                self.tables[o].get_slot(gt.key_of(g)) != int(gt.owner_slot[g])
            ):
                gt.owner_slot[g] = -1

    def _sync_globals_locked(self, now_ms: int) -> "SyncResult":
        gt = self.gtable
        touched = np.flatnonzero(self._gtouched)
        self._resolve_owner_slots(touched, now_ms)
        # Owner-slot resolution above may promote demoted GLOBAL keys;
        # their rows must be in the front table before the collective
        # reads them.
        self._drain_moves()

        # The sync program has ONE width: a pass of more touched gslots
        # launches it again on the next `K`, under the same locks.
        # Launches hold disjoint gslots, hence disjoint owner slots, so
        # their order changes nothing.
        K = self._sync_width
        owner = gt.owner_shard[touched]
        owner_dirty = (owner >= 0) & self.dirty[np.maximum(owner, 0), touched]
        answers = []
        with _SYNC_COLLECTIVE_LOCK:
            for lo in range(0, max(len(touched), 1), K):
                g = touched[lo:lo + K]
                wire = global_ops.pack_sync_wire(
                    K, self.g_capacity, g,
                    global_ops.SyncConfig(
                        gt.owner_slot[g], owner[lo:lo + K], gt.algorithm[g],
                        gt.behavior[g], gt.limit[g], gt.duration[g],
                        gt.greg_expire[g], gt.greg_duration[g],
                    ),
                    owner_dirty[lo:lo + K], now_ms,
                )
                self.state, self.gcols, answer = self._sync_fn(
                    self.state, self.gcols,
                    jax.device_put(wire, self._replicated),
                )
                answers.append((answer, len(g)))
            # The blocking transfers, one a launch: i32[11, K] each.
            (applied, removed, status, new_expire, totals, limit, remaining,
             reset) = global_ops.unpack_sync_answer(
                np.concatenate(
                    [host_readback(a)[:, :m] for a, m in answers], axis=1
                )
            )
            # That was ONE shard's copy: every shard is done with the last
            # launch before the next collective may start.
            jax.block_until_ready(self.gcols.ghits)
        saturation.mesh_tally.add_sync(K * len(answers), len(touched))
        # Every array above is aligned with `touched`.  A row no shard
        # applied keeps its replica columns, so its mirror stands too.
        gt.rep_expire[touched[applied]] = reset[applied]

        result = SyncResult()
        # Remote daemons' keys with aggregated hits: sendHits payloads
        # (global.go:120-160), emitted as wire-ready COLUMNS straight
        # from the template arrays — no per-key dataclasses.
        rsel = np.flatnonzero((owner < 0) & (totals > 0))
        if rsel.size:
            rsel = rsel[gt.templated(touched[rsel])]
        if rsel.size:
            result.remote_hit_cols = gt.hit_columns(touched[rsel], totals[rsel])
        # Applied rows: their owner is a local shard and holds a slot.
        sel = np.flatnonzero(applied)
        for o in np.unique(owner[sel]):
            o = int(o)
            at = sel[owner[sel] == o]
            idx = touched[at]
            slots = gt.owner_slot[idx].tolist()
            keys = [gt.key_of(g) for g in idx.tolist()]
            if self.store is not None:
                # Store SPI parity: the owner-side apply of forwarded
                # hits fires OnChange/Remove per key in the reference
                # (algorithms.go:64-68,38-40) — keep the per-key path.
                for k, g, slot, i in zip(keys, idx.tolist(), slots, at.tolist()):
                    self.tables[o].commit(
                        [slot], [int(new_expire[i])], [bool(removed[i])], keys=[k]
                    )
                    req = gt.request_template(g, int(totals[i]))
                    if removed[i]:
                        self.store.remove(k)
                    elif req is not None:
                        rows = self._read_shard_rows(o, [slot])
                        self.store.on_change(req, _rows_to_items([k], rows)[0])
            else:
                self.tables[o].commit(
                    slots, new_expire[at].tolist(), removed[at].tolist(), keys=keys
                )
            # Commit-removals unmapped their keys: the next pass that
            # takes them resolves anew.
            gt.owner_slot[idx[removed[at]]] = -1
        # Authoritative statuses for the host broadcast leg, in column
        # form straight from the sync readback (the sender encodes
        # these ONCE and fans the same payload to every peer).
        if sel.size:
            g = touched[sel]
            result.broadcast_cols = GlobalsColumns(
                keys=[gt.key_of(x) for x in g.tolist()],
                algorithm=gt.algorithm[g].astype(np.int32),
                status=status[sel].astype(np.int32),
                limit=limit[sel],
                remaining=remaining[sel],
                reset_time=reset[sel],
            )
        self.dirty[:, touched] = False
        self._gtouched[touched] = False
        self._global_pending = False
        return result

    # ------------------------------------------------------------------
    def warmup(self, now_ms: int, warm_shapes: Optional[Sequence[int]] = None) -> None:
        """Compile the hot programs before serving traffic.  A daemon
        that starts answering RPCs cold pays the first-dispatch XLA
        compile (most of a minute per program on a TPU) inside a
        client's 500ms deadline; run it here instead, behind the same
        readiness gate as WaitForConnect (daemon.go:242-248).  Uses a
        reserved key with a 1ms duration so the slot recycles on the
        next eviction scan.  The request carries Behavior.GLOBAL so the
        sync pass finds a GLOBAL lane pending and actually dispatches
        the collective program — after a plain request it would return
        idle before compiling it."""
        req = RateLimitRequest(
            name="__warmup__", unique_key="__warmup__", hits=0, limit=1,
            duration=1, behavior=Behavior.GLOBAL,
        )
        self.apply([req], now_ms)
        self.sync_globals(now_ms)
        # Compile the batched replica-commit scatter at its smallest
        # pad bucket: the first received GLOBAL broadcast must not pay
        # the compile inside the sender's RPC deadline.  Reuses the
        # warmup key's gslot; reset_time in the past so the replica
        # row can never serve a cached answer.
        self.set_replica_batch(
            GlobalsColumns(
                keys=[req.hash_key()],
                algorithm=np.zeros(1, np.int32),
                status=np.zeros(1, np.int32),
                limit=np.ones(1, np.int64),
                remaining=np.zeros(1, np.int64),
                reset_time=np.full(1, now_ms - 1, np.int64),
            ),
            now_ms,
        )
        if self._native and self.store is None:
            # Compile the columnar ingress kernels too (the gateway/gRPC
            # hot path).  Each pad_size bucket is its own XLA program,
            # and even a compile-cache HIT pays an executable load at
            # first dispatch — so warm
            # every bucket the deployment expects (`warm_shapes`, lane
            # counts) during startup, not inside a client's deadline.
            # Warm each shape TWICE: with DISTINCT keys (spread over all
            # shards, compiling the pad_size(lanes/S) bucket even traffic
            # dispatches) AND with IDENTICAL keys (everything hashes to
            # one shard, compiling the pad_size(lanes) bucket a
            # duplicate-heavy batch dispatches — without this, a
            # hot-key storm's first dispatch pays that compile or load
            # inside a client RPC deadline).  Both wires get compiled
            # with the narrow (i32) answer here: the dictionary wire
            # and, forced by "narrow", the per-lane wire that a batch
            # of more than 256 configurations takes.  The dictionary
            # wire's wide answer (lo/hi planes) follows below; the
            # per-lane wire's stays lazy (only a batch of more than 256
            # configurations with a value past i32 reaches it).  1ms
            # duration so the slots recycle.
            for lanes in sorted(set(warm_shapes or (1,))):
                lanes = max(int(lanes), 1)
                for keys in (
                    [f"__warmup__:{i}" for i in range(lanes)],
                    ["__warmup__:0"] * lanes,
                ):
                    for wire in (None, "narrow"):
                        self.apply_columns(
                            keys,
                            np.zeros(lanes, np.int32), np.zeros(lanes, np.int32),
                            np.zeros(lanes, np.int64), np.ones(lanes, np.int64),
                            np.ones(lanes, np.int64), now_ms, force_wire=wire,
                        )
            # Compile the launch-FUSION programs for every dict-wire
            # shape the warm shapes exercised: a backlogged coalescer
            # fuses consecutive same-shape batches into one program
            # (ColumnarPipeline._launch_group), and that program's
            # first dispatch must not pay its executable load inside a
            # client deadline.  All-noop wires (slot=-1 lanes) thread
            # the state through unchanged.
            # And the dictionary wire's WIDE answer at the same shapes:
            # every frame that holds a monthly or yearly calendar lane
            # takes it (narrow_ok: such a lane's expiry and duration
            # pass i32), so a daemon that serves calendar quotas must
            # not compile it inside a client's first such request.  The
            # fused wide launches stay lazy: they need two wide takes
            # in flight at once.
            S = self.n_shards
            with self._stats_lock:
                shapes = sorted(self._seen_wire_shapes)
            if warm_shapes:
                # What the deployment said it expects, after pad_size:
                # the per-lane wire was warmed at the same buckets.  The
                # native ingress pump takes no more lanes at once than
                # S of the widest (a wider take would compile its bucket
                # inside a client's request).
                self.warm_bucket = max(
                    buckets.dict_wire_lanes(W) for W, narrow in shapes if narrow
                )
            wide_fn = _dispatch_jit(
                self.mesh, _rounds_packed_wide_mesh, donate_wire=self._wire_donate
            )
            for W, narrow in shapes:
                if not narrow:
                    continue
                noop = np.zeros((S, W), dtype=np.int32)
                # slot=-1: every lane inert
                noop[:, :buckets.dict_wire_lanes(W)] = -1
                buckets.set_wire_header(noop, 1, now_ms)
                for k in (2, 4):
                    fn = self._fused_launch_fn(k, False)
                    wires = [
                        jax.device_put(noop, self._sharding) for _ in range(k)
                    ]
                    with self._lock, telemetry.program(
                        f"mesh:dispatch:fused{k}:narrow"
                    ):
                        self.state, _ = fn(self.state, *wires)
                with self._lock, telemetry.program("mesh:dispatch:solo:wide"):
                    self.state, _ = wide_fn(
                        self.state, jax.device_put(noop, self._sharding)
                    )
            if self.back is not None:
                # Compile the tier-move program at every pad bucket the
                # warm shapes dispatch (all-noop blocks): a plan closes
                # its moves into a block of its own bucket, so these are
                # the shapes the load and the window launch, and the
                # first real demotion pays no compile inside a client's
                # deadline.
                self._move_buckets = sorted({
                    buckets.dict_wire_lanes(W) for W, _ in shapes
                })
                for padded in self._move_buckets:
                    with self._lock, telemetry.program("mesh:tier_moves"):
                        self.state, self.back = _moves_mesh_jit(
                            self.state, self.back,
                            jax.device_put(_move_block(S, padded), self._sharding),
                        )

    def size(self) -> int:
        """Every resident bucket, whichever tier it lies in (what
        `gubernator_cache_size` reports, as upstream's gauge counts every
        cached item)."""
        if self.back is None:
            return sum(len(t) for t in self.tables)
        return sum(t.tier_stats[0] for t in self.tables)

    @_drained_locked
    def check_consistency(self) -> None:
        """Test/debug invariant sweep over the host tier (the
        race-detector analogue of the reference's `-race` runs,
        Makefile:8-9): every shard's key->slot mapping must be a
        bijection onto live slots and sized consistently.  Raises
        AssertionError on corruption."""
        for s in range(self.n_shards):
            t = self.tables[s]
            keys = t.keys()
            slots = [t.get_slot(k) for k in keys]
            assert None not in slots, f"shard {s}: unmapped key in keys()"
            assert len(set(slots)) == len(slots), f"shard {s}: slot aliasing"
            assert len(keys) == len(t), (
                f"shard {s}: size {len(t)} != mapped keys {len(keys)}"
            )
            assert all(0 <= x < self.capacity_per_shard for x in slots), (
                f"shard {s}: slot out of range"
            )
            if self.back is None:
                continue
            back_keys, back_slots, _ = t.back_entries()
            assert len(back_keys) == t.tier_stats[1], (
                f"shard {s}: back size {t.tier_stats[1]} != back keys {len(back_keys)}"
            )
            assert len(set(back_slots.tolist())) == len(back_slots), (
                f"shard {s}: back slot aliasing"
            )
            assert not set(back_keys) & set(keys), (
                f"shard {s}: a key in both tiers"
            )
