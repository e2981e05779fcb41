"""GLOBAL-behavior kernels: replica caches, hit accumulators, and the
collective sync program.

Reference model (global.go, gubernator.go:231-272, architecture.md:46-74):
a GLOBAL rate limit is owned by one peer; every other peer answers from
a local cache of the owner's last broadcast status, asynchronously
forwards aggregated hits to the owner, and the owner broadcasts
authoritative status back.  Three RPC pipelines (QueueHit->sendHits,
GetPeerRateLimits, UpdatePeerGlobals) implement this.

TPU-native redesign: "peers" are mesh shards.  GLOBAL keys get a
process-wide dense id (gslot) so every shard indexes the same [G]
replica columns.  Per shard:
  * replica columns rep_* [G]      — the owner's last broadcast status
                                     (the non-owner cache of
                                     gubernator.go:263-270, ExpireAt =
                                     ResetTime)
  * hit accumulator ghits [G]      — hits answered locally, not yet
                                     forwarded (globalManager.asyncQueue
                                     aggregation, global.go:83-91)

The answer kernel (answer_batch) extends the bucket kernel: lanes whose
replica entry is live answer from it WITHOUT touching local buckets
(gubernator.go:241-249); lanes whose entry is dead fall through to a
normal local-bucket evaluation, exactly the reference's
"process as if we own it" fallback (gubernator.go:250-254).  Either
way the lane's hits scatter-add into ghits (duplicate gslots are safe:
scatter-add commutes).

The sync program (global_sync) is ONE shard_map over the mesh replacing
all three RPC pipelines with collectives, over the gslots TOUCHED since
the last pass (K of them a launch, gathered by index: the host knows
every gslot a pass can change, so a pass costs what was touched and not
what is provisioned):
  1. psum(ghits[touched])   — hit aggregation to owners
                              (replaces sendHits, global.go:120-160)
  2. owners apply the summed hits to their buckets via the bucket
     kernel (replaces GetPeerRateLimits -> getRateLimit)
  3. psum of owner-masked status — authoritative broadcast
                              (replaces broadcastPeers, global.go:198-243;
                              sum works because exactly one shard owns
                              each gslot)
  4. every shard writes the touched rows of its replica columns; their
     accumulators reset.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..types import Behavior
from . import buckets
from .buckets import BucketState, RequestBatch, BatchOutput

_I64 = jnp.int64
_I32 = jnp.int32


class GlobalColumns(NamedTuple):
    """Per-shard GLOBAL state (leading axis [G] per shard).

    rep_*: cached owner-broadcast status (the RateLimitResp cache item of
    gubernator.go:263-270).  ghits: locally-accumulated unforwarded hits.
    """

    rep_status: jax.Array  # i32[G]
    rep_limit: jax.Array  # i64[G]
    rep_remaining: jax.Array  # i64[G]
    rep_reset: jax.Array  # i64[G]
    rep_expire: jax.Array  # i64[G]
    ghits: jax.Array  # i64[G]


class GlobalBatchExtra(NamedTuple):
    """Extra per-lane request columns for GLOBAL routing.

    gslot: process-wide GLOBAL key id; -1 for non-GLOBAL lanes and for
    GLOBAL lanes evaluated at their owner shard (those take the normal
    bucket path; only the dirty flag is tracked host-side).
    """

    gslot: jax.Array  # i32[B]


class SyncConfig(NamedTuple):
    """Apply config of the gslots one sync launch carries, a row a lane,
    host-provided (the host mirrors the last-seen request config per
    GLOBAL key, standing in for the full RateLimitReq the reference
    forwards in GetPeerRateLimits).  It goes up inside the sync wire
    (pack_sync_wire)."""

    owner_slot: jax.Array  # i32[K] owner shard's local bucket slot
    owner_shard: jax.Array  # i32[K]
    algorithm: jax.Array  # i32[K]
    behavior: jax.Array  # i32[K] (GLOBAL bit stripped host-side)
    limit: jax.Array  # i64[K]
    duration: jax.Array  # i64[K]
    greg_expire: jax.Array  # i64[K]
    greg_duration: jax.Array  # i64[K]


def clear_gslots(gcols: GlobalColumns, gslots) -> GlobalColumns:
    """Zero the rows of recycled gslots (host evicted their keys).

    Run immediately at eviction so a reused gslot can never serve the
    previous key's cached status.  Unforwarded ghits for the evicted key
    are dropped — analogous to the reference losing a key's state on LRU
    eviction (cache.go:115-130).
    """
    idx = jnp.asarray(gslots, _I32)
    return GlobalColumns(
        rep_status=gcols.rep_status.at[idx].set(0, mode="drop"),
        rep_limit=gcols.rep_limit.at[idx].set(0, mode="drop"),
        rep_remaining=gcols.rep_remaining.at[idx].set(0, mode="drop"),
        rep_reset=gcols.rep_reset.at[idx].set(0, mode="drop"),
        rep_expire=gcols.rep_expire.at[idx].set(0, mode="drop"),
        ghits=gcols.ghits.at[idx].set(0, mode="drop"),
    )


def set_replica(gcols: GlobalColumns, gslots, status, limit, remaining, reset) -> GlobalColumns:
    """Write owner-broadcast statuses into replica rows — the receive
    side of UpdatePeerGlobals (gubernator.go:259-272): the cache item is
    the resp, keyed by HashKey, expiring at ResetTime."""
    G = gcols.rep_status.shape[0]
    idx = jnp.asarray(gslots, _I32)
    idx = jnp.where(idx >= 0, idx, G)  # drop invalid (negative wraps!)
    drop = dict(mode="drop")
    return GlobalColumns(
        rep_status=gcols.rep_status.at[idx].set(jnp.asarray(status, _I32), **drop),
        rep_limit=gcols.rep_limit.at[idx].set(jnp.asarray(limit, _I64), **drop),
        rep_remaining=gcols.rep_remaining.at[idx].set(jnp.asarray(remaining, _I64), **drop),
        rep_reset=gcols.rep_reset.at[idx].set(jnp.asarray(reset, _I64), **drop),
        rep_expire=gcols.rep_expire.at[idx].set(jnp.asarray(reset, _I64), **drop),
        ghits=gcols.ghits,
    )


def init_global_columns(g_capacity: int) -> GlobalColumns:
    z64 = jnp.zeros((g_capacity,), _I64)
    return GlobalColumns(
        rep_status=jnp.zeros((g_capacity,), _I32),
        rep_limit=z64,
        rep_remaining=z64,
        rep_reset=z64,
        rep_expire=z64,
        ghits=z64,
    )


def answer_batch(
    state: BucketState,
    gcols: GlobalColumns,
    req: RequestBatch,
    extra: GlobalBatchExtra,
    now_ms,
    cold_cond: bool = True,
):
    """Unified per-shard request kernel: bucket evaluation + GLOBAL
    replica-cache short-circuit + hit accumulation.

    Returns (new_state, new_gcols, out, cached) where cached[b] marks
    lanes answered from the replica cache (no local bucket mutation —
    the host must skip its slot-table commit for those lanes).
    """
    now = jnp.asarray(now_ms, _I64)
    G = gcols.rep_status.shape[0]
    has_g = extra.gslot >= 0
    g = jnp.clip(extra.gslot, 0, G - 1)

    # Live replica entry => answer from cache (gubernator.go:241-249).
    cached = has_g & (gcols.rep_expire[g] >= now)

    # Cached lanes skip local bucket evaluation entirely.
    local_req = req._replace(slot=jnp.where(cached, -1, req.slot))
    new_state, out = buckets.apply_batch(state, local_req, now, cold_cond=cold_cond)

    status = jnp.where(cached, gcols.rep_status[g], out.status)
    limit = jnp.where(cached, gcols.rep_limit[g], out.limit)
    remaining = jnp.where(cached, gcols.rep_remaining[g], out.remaining)
    reset_time = jnp.where(cached, gcols.rep_reset[g], out.reset_time)

    # Async hit forwarding: aggregate into the accumulator
    # (globalManager.QueueHit + the sum at global.go:83-91).  Non-GLOBAL
    # lanes map to G (out of bounds) so mode='drop' drops them —
    # `.at[-1]` would wrap to the last gslot.
    gs = jnp.where(has_g, extra.gslot, G)
    new_gcols = gcols._replace(ghits=gcols.ghits.at[gs].add(req.hits, mode="drop"))

    out = BatchOutput(
        status=status,
        limit=limit,
        remaining=remaining,
        reset_time=reset_time,
        new_expire=out.new_expire,
        removed=out.removed,
        pre_expire=out.pre_expire,
    )
    return new_state, new_gcols, out, cached


# The sync wire: what ONE pass launch takes up, a single i32 buffer
# [1, SYNC_WIRE_COLUMNS * K + WIRE_HEADER_WORDS], replicated over the
# mesh (every shard needs every touched gslot's configuration; one
# transfer call, as the dispatch wires make).  Column k lies at words
# [kK, (k+1)K); the clock rides the header (buckets.set_wire_header), so
# the launch uploads nothing else.
#
#   0  gslot (g_capacity in a lane no gslot fills: dropped on the device)
#   1  owner_slot    3  algorithm    5  the owner row's dirty bit
#   2  owner_shard   4  behavior     6… limit, duration, greg_expire,
#                                       greg_duration: lo then hi of each
(_SYNC_GSLOT, _SYNC_OWNER_SLOT, _SYNC_OWNER_SHARD, _SYNC_ALGO, _SYNC_BEHAVIOR,
 _SYNC_DIRTY, _SYNC_VALUES) = range(7)
SYNC_WIRE_COLUMNS = _SYNC_VALUES + 2 * 4

# And what comes back: i32[SYNC_ANSWER_ROWS, K], the same on every shard
# (each row is a psum's result), so the host fetches one copy.  Row 0 is
# applied | removed << 1 | status << 2; then new_expire, total, limit,
# remaining, reset_time as their LO planes (rows 1-5) and their HI planes
# (rows 6-10): no 64-bit array leaves the device (buckets.WIDE_ANSWER_ROWS
# says why).
SYNC_ANSWER_ROWS = 1 + 2 * 5


def pack_sync_wire(width: int, g_capacity: int, gslots, cfg: SyncConfig,
                   owner_dirty, now_ms: int):
    """Serialize one launch of the sync program into its single i32
    buffer (numpy, host side).  `gslots` are at most `width` DISTINCT
    gslots, `cfg` their SyncConfig rows and `owner_dirty[i]` whether the
    owner shard's row of `gslots[i]` is dirty (the one dirty bit the
    program reads: a non-owner's never enters `mine & dirty`)."""
    import numpy as np

    K, n = width, len(gslots)
    w = np.zeros((1, SYNC_WIRE_COLUMNS * K + buckets.WIRE_HEADER_WORDS), np.int32)

    def col(k):
        return w[0, k * K:k * K + n]

    w[0, n:K] = g_capacity
    col(_SYNC_GSLOT)[:] = gslots
    col(_SYNC_OWNER_SLOT)[:] = cfg.owner_slot
    col(_SYNC_OWNER_SHARD)[:] = cfg.owner_shard
    col(_SYNC_ALGO)[:] = cfg.algorithm
    col(_SYNC_BEHAVIOR)[:] = cfg.behavior
    col(_SYNC_DIRTY)[:] = owner_dirty
    k = _SYNC_VALUES
    for v in (cfg.limit, cfg.duration, cfg.greg_expire, cfg.greg_duration):
        v = np.asarray(v, np.int64)
        col(k)[:] = (v & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
        col(k + 1)[:] = (v >> 32).astype(np.int32)
        k += 2
    buckets.set_wire_header(w, 0, now_ms)
    return w


def unpack_sync_answer(answer):
    """Host twin of global_sync's answer (numpy i32[SYNC_ANSWER_ROWS, K]):
    (applied bool, removed bool, status i32, then new_expire, total,
    limit, remaining, reset_time as i64), a row a lane of the wire."""
    flags = answer[0]
    return ((flags & 1) != 0, (flags & 2) != 0, flags >> 2,
            *buckets.compose_wide_answer(answer[1:]))


def global_sync(state: BucketState, gcols: GlobalColumns, wire, *, axis: str):
    """One launch of the GLOBAL sync step for one shard, meant to run
    inside shard_map over `axis`, over the K gslots the wire names and
    no other row (K = the wire's width, static).  Collectives replace the
    reference's three RPC pipelines (see module docstring).

    The host names every gslot a pass can change (MeshBucketStore.
    `_gtouched`): a row changes only if some shard holds hits for it or
    its owner row is dirty, so `ghits` is zero outside the lanes of a
    pass, and zeroing them is the `zeros_like(ghits)` of a pass over
    every row."""
    G = gcols.ghits.shape[0]
    K = (wire.shape[1] - buckets.WIRE_HEADER_WORDS) // SYNC_WIRE_COLUMNS
    _, now = buckets.wire_header(wire)
    my = jax.lax.axis_index(axis).astype(_I32)

    def col(k):
        return wire[0, k * K:(k + 1) * K]

    values = [
        buckets._compose64(col(k), col(k + 1))  # noqa: SLF001
        for k in range(_SYNC_VALUES, SYNC_WIRE_COLUMNS, 2)
    ]
    gslot = col(_SYNC_GSLOT)
    cfg = SyncConfig(
        col(_SYNC_OWNER_SLOT), col(_SYNC_OWNER_SHARD), col(_SYNC_ALGO),
        col(_SYNC_BEHAVIOR), *values,
    )
    live = gslot < G  # a lane no gslot fills carries G
    # Distinct out-of-bounds rows for the lanes a scatter leaves out
    # (unique_indices promises uniqueness over the whole index vector).
    oob = G + jnp.arange(K, dtype=_I32)

    # Hit aggregation -> owners.
    total = jax.lax.psum(
        gcols.ghits.at[gslot].get(mode="fill", fill_value=0), axis
    )

    # Owners apply when there are forwarded hits or local dirt; hits==0
    # lanes are pure status reads (broadcastPeers' Hits=0 getRateLimit,
    # global.go:202-214).
    mine = cfg.owner_shard == my
    active = (total > 0) | (col(_SYNC_DIRTY) != 0)
    apply_mask = live & mine & active & (cfg.owner_slot >= 0)

    batch = RequestBatch(
        slot=jnp.where(apply_mask, cfg.owner_slot, -1),
        exists=apply_mask,  # kernel re-validates expiry device-side
        algorithm=cfg.algorithm,
        behavior=cfg.behavior,
        hits=total,
        limit=cfg.limit,
        duration=cfg.duration,
        greg_expire=cfg.greg_expire,
        greg_duration=cfg.greg_duration,
    )
    new_state, out = buckets.apply_batch(state, batch, now)

    # Authoritative broadcast: exactly one shard owns each gslot, so a
    # masked psum is the broadcast (replaces UpdatePeerGlobals), and one
    # psum carries every row of it.
    flags = (
        apply_mask.astype(_I64)
        | (out.removed.astype(_I64) << 1)
        | (out.status.astype(_I64) << 2)
    )
    sent = jax.lax.psum(
        jnp.where(
            apply_mask,
            jnp.stack(
                (flags, out.new_expire, out.limit, out.remaining, out.reset_time)
            ),
            0,
        ),
        axis,
    )
    b_flags, b_expire, b_limit, b_remaining, b_reset = sent
    applied = (b_flags & 1) != 0

    drop = dict(mode="drop", unique_indices=True)
    put = jnp.where(applied, gslot, oob)
    new_gcols = GlobalColumns(
        rep_status=gcols.rep_status.at[put].set((b_flags >> 2).astype(_I32), **drop),
        rep_limit=gcols.rep_limit.at[put].set(b_limit, **drop),
        rep_remaining=gcols.rep_remaining.at[put].set(b_remaining, **drop),
        # Non-owner cache item expires at ResetTime (gubernator.go:268).
        rep_reset=gcols.rep_reset.at[put].set(b_reset, **drop),
        rep_expire=gcols.rep_expire.at[put].set(b_reset, **drop),
        ghits=gcols.ghits.at[jnp.where(live, gslot, oob)].set(0, **drop),
    )
    # `total` goes back so the host tier can forward hits for keys whose
    # authoritative owner is a REMOTE daemon (owner_shard == -1: no
    # local shard applies, but the aggregated count must reach the owner
    # via the peer transport — the sendHits leg, global.go:120-160).
    rows = jnp.stack((b_expire, total, b_limit, b_remaining, b_reset))
    answer = jnp.concatenate(
        (
            b_flags.astype(_I32)[None],
            buckets._lo32(rows),  # noqa: SLF001
            buckets._hi32(rows),  # noqa: SLF001
        )
    )
    return new_state, new_gcols, answer
