"""Vectorized token-bucket / leaky-bucket kernels over struct-of-arrays state.

This is the TPU-native replacement for the reference's per-key, mutex-
serialized algorithm functions (`algorithms.go:24-180` tokenBucket,
`algorithms.go:183-336` leakyBucket).  Instead of one Go-map lookup and
pointer mutation per request, bucket state lives as integer columns on
device and a whole request batch is evaluated in one jitted, branchless
program: gather slot rows -> select across the reference's control-flow
paths with `jnp.where` -> scatter rows back.

Semantics preserved exactly (each cited to the reference):
  * expired slot == cache miss, recreate in place      (cache.go:138-163)
  * algorithm switch resets the bucket                 (algorithms.go:54-62,196-204)
  * RESET_REMAINING: token removes the bucket, leaky refills to limit
                                                       (algorithms.go:36-47,206-208)
  * limit hot-change adds the delta to remaining, clamped at 0
                                                       (algorithms.go:70-78)
  * token duration hot-change re-derives expiry from CreatedAt and
    recreates if already expired; stored Duration is NOT updated
                                                       (algorithms.go:87-105)
  * hits == 0 is a status query                        (algorithms.go:107-110,280-283)
  * remaining == 0  -> OVER_LIMIT (token: sticky Status update)
                                                       (algorithms.go:112-117,260-264)
  * hits == remaining -> drain to exactly 0            (algorithms.go:119-124,266-271)
  * hits >  remaining -> OVER_LIMIT without mutating   (algorithms.go:126-130,273-278)
  * first hit creates the bucket; hits > limit -> OVER_LIMIT
    (token keeps remaining=limit, leaky keeps 0)       (algorithms.go:161-166,318-323)
  * leaky leak applied only when >= 1 whole token leaked
                                                       (algorithms.go:234-241)
  * leaky remaining clamped to limit                   (algorithms.go:243-245)

Divergences (documented, deliberate):
  * leaky `remaining` is fixed-point int64 (scale 2**20) instead of Go
    float64 — TPUs have no native f64.  The leak amount
    `elapsed * limit / duration` is computed EXACTLY (128-bit integer
    muldiv) where the reference double-rounds through float64
    (`rate = duration/limit; leak = elapsed/rate`), so for rates that
    are not exactly representable in binary (e.g. duration=1000,
    limit=30) the reference can under-count a leak by one whole token
    at exact multiples; this implementation is the mathematically exact
    value.  Bounded by 1 token per leak event; pinned by
    tests/test_algorithms.py::test_leaky_nonrepresentable_rate.
  * supported magnitude domain: limit and hits up to 2**43 (the
    fixed-point scale consumes 20 bits); the reference's float64 loses
    integer exactness past 2**53 anyway.
  * the reference sets the leaky expiry to `now * duration` — an obvious
    bug (algorithms.go:287); we use `now + duration` (the create path's
    `now + duration`, algorithms.go:326, applied consistently).

Time is an explicit kernel argument (`now_ms`), which is what makes the
reference's frozen-clock test strategy (functional_test.go:108-167) work
unchanged here.

Gregorian calendar values cannot be computed on device; the host
precomputes `greg_expire` / `greg_duration` per request (as the reference
does inline at algorithms.go:90-95,140-145,216-232) and the kernel
selects them when the DURATION_IS_GREGORIAN bit is set.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..types import Algorithm, Behavior, Status

# Fixed-point scale for leaky-bucket fractional remaining.
LEAKY_SCALE_BITS = 20
LEAKY_SCALE = 1 << LEAKY_SCALE_BITS

_I64 = jnp.int64
_I32 = jnp.int32
_U64 = jnp.uint64

# Stable names of the parts of a bucket program (`jax.named_scope`): they
# ride every operation's `op_name` metadata into the compiled program and
# the device trace, so a reduction finds "the rounds loop" by name and not
# as this week's `while.52`.  They change no jitted function's name and no
# compile-cache key (debug metadata is stripped from the key), so an
# executable cached before a scope existed still loads — without it.
SCOPE_WIRE_DECODE = "wire_decode"  # single-buffer wire -> columns, table gathers
SCOPE_DUP_GROUPS = "dup_groups"    # analytic duplicate groups (occurrence j's level)
SCOPE_ROUNDS = "rounds"            # the sequential rounds loop, everything inside it
SCOPE_COMMIT = "commit"            # row scatter into the table
SCOPE_ANSWER_PACK = "answer_pack"  # per-lane outputs -> one packed array
SCOPE_GLOBAL_SYNC = "global_sync"  # the GLOBAL collective's body (parallel/mesh.py)


def _muldiv128(a, b, d):
    """Exact (floor(a*b/d), a*b mod d) for 0 <= a,b < 2**63, 1 <= d < 2**63.

    `a * b` overflows int64 for legal proto values (elapsed_ms * limit),
    so the product is formed as a 128-bit (hi, lo) pair from 32x32
    partials and divided by shift-subtract long division.  The quotient
    must fit in int64 — guaranteed by callers via a <= d (=> q <= b).
    128 data-independent iterations; vectorizes cleanly across lanes.
    """
    a = a.astype(_U64)
    b = b.astype(_U64)
    d = jnp.maximum(d.astype(_U64), jnp.uint64(1))
    mask = jnp.uint64(0xFFFFFFFF)
    a_lo, a_hi = a & mask, a >> 32
    b_lo, b_hi = b & mask, b >> 32
    ll = a_lo * b_lo
    mid = a_lo * b_hi + (ll >> 32)  # no overflow: < 2**64
    mid2 = mid + a_hi * b_lo
    carry = (mid2 < mid).astype(_U64)
    lo = (mid2 << 32) | (ll & mask)
    hi = a_hi * b_hi + (mid2 >> 32) + (carry << 32)

    def body(_, st):
        r, q, hi, lo = st
        top = hi >> 63
        hi = (hi << 1) | (lo >> 63)
        lo = lo << 1
        r = (r << 1) | top
        take = r >= d
        r = jnp.where(take, r - d, r)
        q = (q << 1) | take.astype(_U64)
        return r, q, hi, lo

    z = jnp.zeros_like(a)
    r, q, _, _ = jax.lax.fori_loop(0, 128, body, (z, z, hi, lo))
    return q.astype(_I64), r.astype(_I64)


def _leak_amounts(el_c, lim_nn, rn):
    """Exact (floor(el*lim/rn), floor((el*lim mod rn) * SCALE / rn)).

    Fast path (pure int64, no loop): decompose lim = qL*rn + rL, so
    el*lim/rn = el*qL + el*rL/rn.  el <= rn (callers clip), hence
    el*qL <= lim fits; el*rL fits whenever el <= MAX64/rL.  That covers
    every realistic config (any duration < ~24.8 days, or any
    limit%duration small); only when BOTH duration > 2**31.5 ms AND
    elapsed*remainder actually overflow does the whole batch fall back
    to the 128-bit long-division loop (_muldiv128) via lax.cond — the
    branch is data-dependent, so the loop costs nothing when unused.
    """
    qL = lim_nn // rn
    rL = lim_nn % rn
    max64 = jnp.asarray((1 << 63) - 1, _I64)
    safe_rl = jnp.maximum(rL, 1)
    ok = ((rL == 0) | (el_c <= max64 // safe_rl)) & (rn < (1 << 43))

    def fast(_):
        prod = el_c * rL
        lw = el_c * qL + prod // rn
        lr = prod % rn
        frac = (lr * LEAKY_SCALE) // rn
        return lw, frac

    def slow(_):
        lw, lr = _muldiv128(el_c, lim_nn, rn)
        frac, _ = _muldiv128(lr, jnp.full_like(lr, LEAKY_SCALE), rn)
        return lw, frac

    return jax.lax.cond(jnp.all(ok), fast, slow, None)


class BucketState(NamedTuple):
    """Bucket table for one shard (capacity C), stored as TWO row-major
    int32 arrays of shape [C, 8].

    Logically each slot holds the union of the reference's
    TokenBucketItem / LeakyBucketItem (store.go:11-24) plus CacheItem
    bookkeeping (cache.go:64-76): algo, limit, remaining (leaky scaled
    by LEAKY_SCALE), duration, stamp (CreatedAt/UpdatedAt), expire_at
    (expiry-as-miss), sticky status.  Every int64 value is a lo/hi i32
    pair; algo+status pack into one flags lane (bits 0-1 algo, bit 2
    status).

    PHYSICAL layout (measured on TPU v5e, round 3): XLA's random-index
    scatter is the kernel's whole cost, and its price is per scattered
    ROW, not per element — 11 separate [C] column scatters cost ~24ms
    per 131k batch where ONE [C,8] row scatter costs ~2.7ms (and i64
    rows cost ~6x i32 rows).  So the state is two 8-lane i32 row
    tables split by write frequency:

      hot[C, 8]  — rewritten on every hit:
        0 flags, 1 remaining_lo, 2 remaining_hi, 3 stamp_lo,
        4 stamp_hi, 5 expire_lo, 6 expire_hi, 7 spare
      cold[C, 8] — rewritten only when a lane's stored config changes
                   (create, limit/duration hot-change, algo switch):
        0 limit_lo, 1 limit_hi, 2 duration_lo, 3 duration_hi, 4-7 spare

    The cold scatter is guarded by a lax.cond on "any lane changed its
    config", so steady-state traffic pays exactly one row scatter per
    batch.  The kernel recomposes int64 after the gather and decomposes
    before the scatter, so the arithmetic (and the wire formats) are
    bit-identical to the logical layout.  Host exchange uses BucketRows.
    """

    hot: jax.Array  # i32[C, 8]
    cold: jax.Array  # i32[C, 8]


# hot lane indices
_H_FLAGS, _H_REM_LO, _H_REM_HI = 0, 1, 2
_H_STAMP_LO, _H_STAMP_HI, _H_EXP_LO, _H_EXP_HI = 3, 4, 5, 6
# cold lane indices
_C_LIM_LO, _C_LIM_HI, _C_DUR_LO, _C_DUR_HI = 0, 1, 2, 3


class BucketRows(NamedTuple):
    """Logical (composed int64) row form: the host exchange format for
    Store/Loader snapshots and row injection (read_rows/write_rows)."""

    algo: jax.Array  # i32[N]
    limit: jax.Array  # i64[N]
    remaining: jax.Array  # i64[N]
    duration: jax.Array  # i64[N]
    stamp: jax.Array  # i64[N]
    expire_at: jax.Array  # i64[N]
    status: jax.Array  # i32[N]


_MASK32 = (1 << 32) - 1


def _compose64(lo, hi):
    """Exact int64 from a lo/hi int32 pair (sign lives in hi)."""
    return (hi.astype(_I64) << 32) | (lo.astype(_I64) & _MASK32)


def _lo32(v):
    return v.astype(_I32)  # modular truncation keeps the low 32 bits


def _hi32(v):
    return (v >> 32).astype(_I32)


def _pack_hot(flags, remaining, stamp, expire) -> jax.Array:
    """Stack hot row values into [N, 8] (lane order: see BucketState)."""
    z = jnp.zeros_like(flags)
    return jnp.stack(
        (
            flags,
            _lo32(remaining), _hi32(remaining),
            _lo32(stamp), _hi32(stamp),
            _lo32(expire), _hi32(expire),
            z,
        ),
        axis=-1,
    )


def _pack_cold(limit, duration) -> jax.Array:
    """Stack cold row values into [N, 8]."""
    z = jnp.zeros_like(_lo32(limit))
    return jnp.stack(
        (
            _lo32(limit), _hi32(limit),
            _lo32(duration), _hi32(duration),
            z, z, z, z,
        ),
        axis=-1,
    )


def rows_to_split(rows: BucketRows) -> BucketState:
    """Decompose logical rows into the hot/cold row layout (same
    leading length); the write-side twin of read_rows' composition."""
    algo = jnp.asarray(rows.algo, _I32)
    status = jnp.asarray(rows.status, _I32)
    limit = jnp.asarray(rows.limit, _I64)
    remaining = jnp.asarray(rows.remaining, _I64)
    duration = jnp.asarray(rows.duration, _I64)
    stamp = jnp.asarray(rows.stamp, _I64)
    expire = jnp.asarray(rows.expire_at, _I64)
    flags = (algo & 3) | ((status & 1) << 2)
    return BucketState(
        hot=_pack_hot(flags, remaining, stamp, expire),
        cold=_pack_cold(limit, duration),
    )


class RequestBatch(NamedTuple):
    """One device-ready batch of resolved requests (length B, padded).

    `slot` indexes into the BucketState columns; -1 marks a padding lane
    (scatters drop, responses are garbage and masked host-side).
    `exists` is the host's claim that the slot currently maps this key;
    the kernel still validates expiry device-side.
    """

    slot: jax.Array  # i32[B]
    exists: jax.Array  # bool[B]
    algorithm: jax.Array  # i32[B]
    behavior: jax.Array  # i32[B]
    hits: jax.Array  # i64[B]
    limit: jax.Array  # i64[B]
    duration: jax.Array  # i64[B]
    greg_expire: jax.Array  # i64[B] (0 unless DURATION_IS_GREGORIAN)
    greg_duration: jax.Array  # i64[B] (0 unless DURATION_IS_GREGORIAN)
    # Analytic-duplicate extension (grouped planner,
    # gt_batch_plan_grouped): occurrence index within a uniform
    # duplicate group, and whether this lane scatters state (the last
    # occurrence).  None => every lane is its own group (occ=0,
    # write=valid), which is byte-identical to the pre-extension kernel.
    occ: "jax.Array | None" = None  # i32[B]
    write: "jax.Array | None" = None  # bool[B]


class BatchOutput(NamedTuple):
    """Per-lane responses plus host-mirror bookkeeping."""

    status: jax.Array  # i32[B]
    limit: jax.Array  # i64[B]
    remaining: jax.Array  # i64[B]
    reset_time: jax.Array  # i64[B]
    new_expire: jax.Array  # i64[B]  slot expire_at after this request
    removed: jax.Array  # bool[B] token RESET_REMAINING freed the slot
    # The slot's stored expiry as this lane's round GATHERED it (free:
    # the kernel reads it anyway).  The narrow wire's -2 keep-sentinel
    # detector; replaces a separate whole-batch pre-gather (measured
    # at ~1ms per 131k-lane batch on a TPU; see git history).
    pre_expire: jax.Array  # i64[B]


def init_state(capacity: int) -> BucketState:
    """Fresh all-expired bucket table (expire_at=0 => every slot is free)."""
    return BucketState(
        hot=jnp.zeros((capacity, 8), _I32),
        cold=jnp.zeros((capacity, 8), _I32),
    )


def apply_batch(
    state: BucketState, req: RequestBatch, now_ms, cold_cond: bool = True
) -> "tuple[BucketState, BatchOutput]":
    """Evaluate one batch against the bucket table.

    Pure function: returns (new_state, responses).  Slots must be unique
    within the batch (the host splits duplicate-key batches into
    flush-separated rounds; see RoundPlanner) so the gather/scatter
    is race-free.

    `cold_cond` (static) guards the cold-row scatter with a lax.cond so
    steady-state batches skip it.  Under jax.vmap (the mesh store's
    per-shard kernels) cond lowers to executing BOTH branches plus a
    select — strictly worse than scattering unconditionally — so
    vmapped callers must pass cold_cond=False.
    """
    out, new = _apply_compute(state, req, now_ms)
    with jax.named_scope(SCOPE_COMMIT):
        state = _commit_rows(state, req, new, cold_cond)
    return state, out


class _NewRows(NamedTuple):
    """Per-lane post-batch row values (the commit's input): what
    _apply_compute would store for each lane, before any scatter."""

    flags: jax.Array  # i32[B]
    rem: jax.Array  # i64[B]
    stamp: jax.Array  # i64[B]
    exp: jax.Array  # i64[B]
    limit: jax.Array  # i64[B]
    dur: jax.Array  # i64[B]
    writes: jax.Array  # bool[B] — lanes that commit state
    cold_changed: jax.Array  # bool[B] — writes whose stored config changed


def _commit_rows(state: BucketState, req, new: _NewRows, cold_cond: bool):
    """Per-lane row scatter (every lane submits a row; dropped lanes
    still pay the scatter's per-submitted-row price — the compact
    commit below avoids that when the plan allows)."""
    C = state.hot.shape[0]
    # Non-write lanes map to DISTINCT out-of-bounds indices (C + lane)
    # rather than a shared C: mode='drop' discards them either way, but
    # unique_indices=True promises uniqueness over the WHOLE index
    # vector and repeated sentinels would be undefined behavior.
    lane = jnp.arange(req.slot.shape[0], dtype=_I32)
    oob = C + lane
    scat = jnp.where(new.writes, req.slot, oob)
    drop = dict(mode="drop", unique_indices=True)
    new_hot = state.hot.at[scat].set(
        _pack_hot(new.flags, new.rem, new.stamp, new.exp), **drop
    )

    scat_cold = jnp.where(new.cold_changed, req.slot, oob)
    cold_rows = _pack_cold(new.limit, new.dur)

    if cold_cond:
        def _scatter_cold(args):
            cold, idx, rows = args
            return cold.at[idx].set(rows, **drop)

        def _keep_cold(args):
            return args[0]

        new_cold = jax.lax.cond(
            jnp.any(new.cold_changed), _scatter_cold, _keep_cold,
            (state.cold, scat_cold, cold_rows),
        )
    else:
        new_cold = state.cold.at[scat_cold].set(cold_rows, **drop)
    return BucketState(hot=new_hot, cold=new_cold)


def _apply_compute(
    state: BucketState, req: RequestBatch, now_ms
) -> "tuple[BatchOutput, _NewRows]":
    """The batch evaluation WITHOUT the state commit: returns the
    responses plus every lane's post-batch row values (see apply_batch
    for semantics; the split exists so commits can be compacted)."""
    now = jnp.asarray(now_ms, _I64)
    C = state.hot.shape[0]

    valid = req.slot >= 0
    s = jnp.clip(req.slot, 0, C - 1)

    # Two row gathers (cheap, vectorized) instead of 11 column gathers.
    hot_g = state.hot[s]  # [B, 8]
    cold_g = state.cold[s]  # [B, 8]
    g_flags = hot_g[:, _H_FLAGS]
    g_algo = g_flags & 3
    g_status = (g_flags >> 2) & 1
    g_limit = _compose64(cold_g[:, _C_LIM_LO], cold_g[:, _C_LIM_HI])
    g_rem = _compose64(hot_g[:, _H_REM_LO], hot_g[:, _H_REM_HI])
    g_dur = _compose64(cold_g[:, _C_DUR_LO], cold_g[:, _C_DUR_HI])
    g_stamp = _compose64(hot_g[:, _H_STAMP_LO], hot_g[:, _H_STAMP_HI])
    g_exp = _compose64(hot_g[:, _H_EXP_LO], hot_g[:, _H_EXP_HI])

    # Expiry-as-miss: reference expires strictly (`ExpireAt < now`,
    # cache.go:151), so a slot at exactly its expiry is still live.
    live = req.exists & (g_exp >= now)
    exist = live & (g_algo == req.algorithm)  # algo switch => recreate

    is_tok = req.algorithm == int(Algorithm.TOKEN_BUCKET)
    greg = (req.behavior & int(Behavior.DURATION_IS_GREGORIAN)) != 0
    reset_b = (req.behavior & int(Behavior.RESET_REMAINING)) != 0
    hits = req.hits
    OVER = jnp.asarray(int(Status.OVER_LIMIT), _I32)
    UNDER = jnp.asarray(int(Status.UNDER_LIMIT), _I32)

    # Analytic-duplicate support: a uniform duplicate group (same key,
    # identical config/hits, no RESET_REMAINING — enforced by the
    # grouped planner) runs entirely in one round.  Every lane reads the
    # SAME pre-group slot row; occurrence j's pre-hit remaining is
    # derived in closed form (the first j duplicates accepted
    # min(j, base // hits) hits), and only the last occurrence scatters.
    # occ=None degenerates to occ=0 everywhere: byte-identical to the
    # ungrouped kernel.
    occ64 = None if req.occ is None else req.occ.astype(_I64)
    hs = jnp.maximum(hits, 1)

    def occ_rem(base):
        if occ64 is None:
            return base
        with jax.named_scope(SCOPE_DUP_GROUPS):
            taken = jnp.minimum(occ64, base // hs)
            return jnp.where(hits > 0, base - hits * taken, base)

    # ---------------- token bucket, existing item ----------------
    # RESET_REMAINING is checked before the algorithm-switch cast in the
    # reference (algorithms.go:36 precedes :54), so it applies to any live
    # slot regardless of the stored algorithm.
    tok_reset = live & is_tok & reset_b  # algorithms.go:36-47

    # Limit hot-change: remaining += r.limit - t.limit, clamp 0 (algorithms.go:70-78)
    t_rem0 = jnp.maximum(g_rem + (req.limit - g_limit), 0)

    # Duration hot-change (algorithms.go:87-105); expiry derives from CreatedAt.
    dur_changed = g_dur != req.duration
    exp_from_cfg = jnp.where(greg, req.greg_expire, g_stamp + req.duration)
    dur_expired = dur_changed & (exp_from_cfg < now)  # => remove + recreate
    t_exp = jnp.where(dur_changed, exp_from_cfg, g_exp)

    tok_exist = exist & is_tok & ~reset_b & ~dur_expired
    do_hit = hits > 0
    t_rem0 = occ_rem(t_rem0)  # this occurrence's pre-hit remaining
    can_take = do_hit & (hits <= t_rem0)  # covers == and < ; mutates
    t_rem1 = jnp.where(can_take, t_rem0 - hits, t_rem0)
    t_resp_status = jnp.where(
        do_hit & ((t_rem0 == 0) | (hits > t_rem0)), OVER, g_status
    )
    # Sticky status persists only via the remaining==0 path (algorithms.go:112-117)
    t_new_status = jnp.where(do_hit & (t_rem0 == 0), OVER, g_status)

    # ---------------- token bucket, fresh create ----------------
    # (selected in sel() as the fallback for token lanes that are neither
    # tok_reset nor tok_exist: plain miss, algo switch, or dur_expired)
    # Occurrence j applies to the remaining the first lane's create left
    # behind; hits > pre-hit remaining covers the hits > limit case of
    # lane 0 (algorithms.go:161-166) and every later over/at-zero lane.
    c_exp_tok = jnp.where(greg, req.greg_expire, now + req.duration)
    remc = occ_rem(req.limit)
    c_over = hits > remc
    c_rem_tok = jnp.where(c_over, remc, remc - hits)
    # Sticky for grouped creates: a later occurrence that found the
    # fresh bucket already drained sets OVER exactly as the exist path
    # would have in its sequential round (do_hit & pre-rem == 0).
    if occ64 is None:
        c_status_store = UNDER * jnp.ones_like(g_status)
    else:
        c_status_store = jnp.where(
            (occ64 > 0) & do_hit & (remc == 0), OVER, UNDER
        )

    # ---------------- leaky bucket, existing item ----------------
    lky_exist = exist & ~is_tok
    l_rem = jnp.where(lky_exist & reset_b, req.limit * LEAKY_SCALE, g_rem)  # :206-208

    rate_num = jnp.where(greg, req.greg_duration, req.duration)
    dur_eff = jnp.where(greg, req.greg_expire - now, req.duration)
    lim_safe = jnp.maximum(req.limit, 1)

    elapsed = now - g_stamp
    rn = jnp.maximum(rate_num, 1)  # duration<=0 degenerates to instant refill
    el_c = jnp.clip(elapsed, 0, rn)  # leak can't exceed one full refill
    lim_nn = jnp.maximum(req.limit, 0)
    # leak = elapsed * limit / duration, exact + overflow-safe.
    leak_whole, leak_frac = _leak_amounts(el_c, lim_nn, rn)
    leak_s = leak_whole * LEAKY_SCALE + leak_frac
    do_leak = leak_whole > 0  # only whole tokens trigger (algorithms.go:238-241)
    l_rem = jnp.where(do_leak, l_rem + leak_s, l_rem)
    l_stamp = jnp.where(do_leak, now, g_stamp)
    l_rem = jnp.where(l_rem // LEAKY_SCALE > req.limit, req.limit * LEAKY_SCALE, l_rem)

    rem_int0 = l_rem // LEAKY_SCALE
    l_reset = now + rate_num // lim_safe  # now + int64(rate) (algorithms.go:251)

    # Occurrence offset: earlier duplicates consumed whole tokens only
    # (the fractional part never changes within one `now`).
    rem_int = occ_rem(rem_int0)
    l_rem_base = l_rem - (rem_int0 - rem_int) * LEAKY_SCALE

    at_zero = rem_int == 0  # algorithms.go:260-264 (OVER even for hits==0)
    exact = ~at_zero & (rem_int == hits)  # algorithms.go:266-271
    overflow = ~at_zero & ~exact & (hits > rem_int)  # algorithms.go:273-278
    take = exact | (~at_zero & ~overflow & (hits > 0))
    l_rem_f = jnp.where(take, l_rem_base - hits * LEAKY_SCALE, l_rem_base)
    l_resp_rem = jnp.where(exact, 0, jnp.where(take, l_rem_f // LEAKY_SCALE, rem_int))
    l_resp_status = jnp.where(at_zero | overflow, OVER, UNDER)
    # Expiry refresh only on the plain-subtract path (algorithms.go:287):
    # for a group, "any accepted occurrence so far was a plain subtract".
    taken_cnt = jnp.where(hits > 0, (rem_int0 - rem_int) // hs, 0) + take.astype(_I64)
    drained_exactly = (hits > 0) & (taken_cnt > 0) & (rem_int - hits * take.astype(_I64) == 0)
    any_plain = (taken_cnt - drained_exactly.astype(_I64)) >= 1
    l_exp = jnp.where(any_plain, now + dur_eff, g_exp)

    # ---------------- leaky bucket, fresh create ----------------
    # Over-create clamps stored remaining to 0 (algorithms.go:318-323),
    # so later occurrences of an over-create group see 0, not limit.
    lky_create = ~is_tok & ~exist
    lc_over_all = hits > req.limit
    remlc = occ_rem(req.limit)
    if occ64 is not None:
        remlc = jnp.where(lc_over_all & (occ64 > 0), 0, remlc)
    lc_take = (hits > 0) & (hits <= remlc)
    lc_over = hits > remlc  # covers lane 0's hits > limit and drained lanes
    lc_rem = jnp.where(lc_over_all, 0, (remlc - hits * lc_take) * LEAKY_SCALE)
    lc_resp_rem = jnp.where(lc_take, remlc - hits, jnp.where(lc_over_all, 0, remlc))
    lc_exp = now + dur_eff
    lc_reset = now + dur_eff // lim_safe  # algorithms.go:315 (integer div)

    # ---------------- merge the five paths ----------------
    def sel(tok_reset_v, tok_exist_v, tok_create_v, lky_exist_v, lky_create_v):
        out = jnp.where(
            is_tok,
            jnp.where(
                tok_reset,
                tok_reset_v,
                jnp.where(tok_exist, tok_exist_v, tok_create_v),
            ),
            jnp.where(lky_exist, lky_exist_v, lky_create_v),
        )
        return out

    z64 = jnp.zeros_like(hits)
    resp_status = sel(
        UNDER * jnp.ones_like(g_status),
        t_resp_status,
        jnp.where(c_over, OVER, UNDER),
        l_resp_status,
        jnp.where(lc_over, OVER, UNDER),
    )
    resp_rem = sel(
        req.limit,
        jnp.where(can_take, t_rem1, t_rem0),
        c_rem_tok,
        l_resp_rem,
        lc_resp_rem,
    )
    resp_reset = sel(z64, t_exp, c_exp_tok, l_reset, lc_reset)

    n_algo = jnp.where(valid, req.algorithm, g_algo)
    n_limit = sel(g_limit, req.limit, req.limit, req.limit, req.limit)
    n_rem = sel(g_rem, t_rem1, c_rem_tok, l_rem_f, lc_rem)
    # Token stored Duration only set at create (algorithms.go:87-105 never
    # writes t.Duration); leaky existing stores the raw request duration
    # (algorithms.go:212), leaky create stores the adjusted one (:307).
    n_dur = sel(g_dur, g_dur, req.duration, req.duration, dur_eff)
    n_stamp = sel(g_stamp, g_stamp, now, l_stamp, now)
    n_exp = sel(z64, t_exp, c_exp_tok, l_exp, lc_exp)
    n_status = sel(
        UNDER * jnp.ones_like(g_status), t_new_status, c_status_store, UNDER, UNDER
    )

    removed = tok_reset & valid

    # Padding lanes (slot=-1) must NOT write; in grouped mode only the
    # LAST occurrence of each duplicate group writes.  The cold row is
    # rewritten only when a write lane actually changed its stored
    # config (create, limit or duration hot-change, algo switch).
    writes = valid if req.write is None else (valid & req.write)
    n_flags = (n_algo & 3) | ((n_status & 1) << 2)
    cold_changed = writes & ((n_limit != g_limit) | (n_dur != g_dur))

    out = BatchOutput(
        status=jnp.where(valid, resp_status, UNDER),
        limit=jnp.where(valid, req.limit, z64),
        remaining=jnp.where(valid, resp_rem, z64),
        reset_time=jnp.where(valid, resp_reset, z64),
        new_expire=jnp.where(valid, n_exp, z64),
        removed=removed,
        pre_expire=jnp.where(valid, g_exp, z64),
    )
    new = _NewRows(
        flags=n_flags, rem=n_rem, stamp=n_stamp, exp=n_exp,
        limit=n_limit, dur=n_dur, writes=writes, cold_changed=cold_changed,
    )
    return out, new


def _pack_output(out: BatchOutput, with_pre: bool = False) -> jax.Array:
    """Fuse the per-lane outputs into ONE i64[4, B] array so the host
    pays a single device->host transfer per batch instead of five (each
    blocking readback is its own device round trip).  Row 0 packs status
    (bit 0) and removed (bit 1); rows 1-3 are remaining / reset_time / new_expire.
    `limit` is an echo of the request and never leaves the device.
    `with_pre` appends pre_expire as row 4 (narrow-wire sentinel input,
    consumed on device — it never reaches the host wire)."""
    with jax.named_scope(SCOPE_ANSWER_PACK):
        row0 = out.status.astype(_I64) | (out.removed.astype(_I64) << 1)
        rows = (row0, out.remaining, out.reset_time, out.new_expire)
        if with_pre:
            rows = rows + (out.pre_expire,)
        return jnp.stack(rows)


def apply_rounds(
    state: BucketState, req: RequestBatch, round_id, n_rounds, now_ms,
    cold_cond: bool = True,
) -> "tuple[BucketState, jax.Array]":
    """Evaluate a whole duplicate-key batch in ONE dispatch.

    `round_id[i]` assigns each lane to a sequential round (computed by
    the host planner: unique keys+slots per round); the loop applies
    round r's lanes while masking the rest, so the k-th request for a
    key observes the (k-1)-th's state — the reference's mutex
    serialization (gubernator.go:336-337) — without a host round-trip
    between rounds.  `n_rounds` is a traced scalar: one compilation
    serves every round count at a given batch width.

    Returns (new_state, packed_output i64[4, B]): the layout of
    _pack_output, decoded host-side by gt_mesh_finish_wide.
    """
    return _apply_rounds_impl(
        state, req, round_id, n_rounds, now_ms, cold_cond, with_pre=False
    )


def _apply_rounds_impl(
    state, req, round_id, n_rounds, now_ms, cold_cond, with_pre
):
    """Shared rounds loop; with_pre=True carries pre_expire as row 4
    (the narrow wire's on-device sentinel input)."""
    B = req.slot.shape[0]
    packed0 = jnp.zeros((5 if with_pre else 4, B), _I64)

    def cond(c):
        return c[0] < n_rounds

    def body(c):
        r, st, packed = c
        active = round_id == r
        req_r = req._replace(slot=jnp.where(active, req.slot, -1))
        st, out = apply_batch(st, req_r, now_ms, cold_cond=cold_cond)
        packed = jnp.where(
            active[None, :], _pack_output(out, with_pre=with_pre), packed
        )
        return r + 1, st, packed

    with jax.named_scope(SCOPE_ROUNDS):
        _, state, packed = jax.lax.while_loop(
            cond, body, (jnp.asarray(0, _I32), state, packed0)
        )
    return state, packed


class RequestBatch32(NamedTuple):
    """Narrow-wire twin of RequestBatch: i32 value columns, Gregorian
    expiry as a delta from `now_ms`.  Halves host->device bytes and is
    usable whenever the batch's values fit (the common case: hits,
    limit, duration < 2**31 and no monthly/yearly Gregorian resets).
    The kernel computes in int64 regardless — only the WIRE narrows,
    which is what matters when the device sits across a thin link.

    It exists on the device only: the host never builds one.  Both
    wires decode into it inside the jitted program — the dictionary
    wire by table gathers (apply_rounds_dict), the per-lane wire by
    slices of its one buffer (unpack_lane_wire)."""

    slot: jax.Array  # i32[B]
    exists: jax.Array  # bool[B]
    algorithm: jax.Array  # i32[B]
    behavior: jax.Array  # i32[B]
    hits: jax.Array  # i32[B]
    limit: jax.Array  # i32[B]
    duration: jax.Array  # i32[B]
    greg_expire_delta: jax.Array  # i32[B] (greg_expire - now; 0 if unused)
    greg_duration: jax.Array  # i32[B]
    occ: "jax.Array | None" = None  # i32[B]
    write: "jax.Array | None" = None  # bool[B]


def apply_rounds32(
    state: BucketState, req32: RequestBatch32, round_id, n_rounds, now_ms,
    cold_cond: bool = True,
) -> "tuple[BucketState, jax.Array]":
    """apply_rounds with an int32 wire on BOTH directions.

    Input columns upcast on device; the packed result narrows to
    i32[4, B] (row 0 bit-packs status/removed; rows 1-3 are remaining,
    reset_time - now, new_expire - now).  Callers must guarantee the
    narrow preconditions (narrow_ok checks them host-side):
    limit/hits/duration in [0, 2**31) and Gregorian deltas in range.
    Those bound every value the kernel COMPUTES this batch; a time the
    kernel merely passes through unchanged (a live bucket's stored
    expiry, which may lie arbitrarily far in the future from a wide
    batch) is encoded as the sentinel -2 ("unchanged") and reconstructed
    host-side from the slot table (gt_mesh_finish_narrow), never clipped.
    """
    now = jnp.asarray(now_ms, _I64)
    req = RequestBatch(
        slot=req32.slot,
        exists=req32.exists,
        algorithm=req32.algorithm,
        behavior=req32.behavior,
        hits=req32.hits.astype(_I64),
        limit=req32.limit.astype(_I64),
        duration=req32.duration.astype(_I64),
        greg_expire=now + req32.greg_expire_delta.astype(_I64),
        greg_duration=req32.greg_duration.astype(_I64),
        occ=req32.occ,
        write=req32.write,
    )
    # The -2 pass-through detector rides the packed output as row 4:
    # each lane's stored expiry as its OWN round gathered it.  (Round 4
    # replaced a separate whole-batch pre-gather measured at ~1ms per
    # 131k batch; the per-round value is equivalent for the sentinel
    # because -2 fires only for values unrepresentable on this wire,
    # which no round of a narrow batch can have WRITTEN — any such
    # value predates the batch, so pre-round == pre-batch.)
    state, packed64 = _apply_rounds_impl(
        state, req, round_id, n_rounds, now_ms, cold_cond, with_pre=True
    )
    pre_exp = packed64[4]
    hi = jnp.asarray((1 << 31) - 1, _I64)

    def delta(v):
        # -1: absolute 0 (removed slot / no reset) — restore exact 0.
        # -2: UNREPRESENTABLE pass-through (a live bucket's far-future
        #     stored time, only reachable unchanged from pre-batch
        #     state) — host reconstructs the absolute value.  The
        #     sentinel must fire ONLY when the delta would clip: a
        #     representable value always rides the wire verbatim, so a
        #     coincidental v == pre_exp (e.g. an eviction-recycled slot
        #     recreated at the same expiry) still commits correctly.
        d = v - now
        fits = (d >= 0) & (d <= hi)
        return jnp.where(
            v == 0, -1, jnp.where(fits, d, jnp.where(v == pre_exp, -2, jnp.clip(d, 0, hi)))
        )

    with jax.named_scope(SCOPE_ANSWER_PACK):
        packed32 = jnp.stack(
            (
                packed64[0],
                jnp.clip(packed64[1], 0, hi),
                delta(packed64[2]),
                delta(packed64[3]),
            )
        ).astype(_I32)
    return state, packed32


class RequestBatchDict(NamedTuple):
    """Config-dictionary wire: the narrowest host->device encoding.

    Realistic traffic shares a handful of (algorithm, behavior, hits,
    limit, duration, gregorian) configurations across a batch, so the
    wire carries a K<=256-row config TABLE plus one u8 index per lane
    instead of seven full value columns.  Per-lane payload: slot i32 +
    flags u8 (bit0 exists, bit1 write) + cfg u8 + occ u16 = 8 bytes,
    ~5x less than RequestBatch32's 42 — and on a thin link the batch
    bytes ARE the throughput ceiling.  The kernel expands via table
    gathers (K-sized, trivially cached on device) and delegates to
    apply_rounds32, so semantics and the packed i32 output are
    byte-identical to the narrow wire."""

    slot: jax.Array  # i32[B]
    flags: jax.Array  # u8[B]: bit0 exists, bit1 write
    cfg: jax.Array  # u8[B] index into the table rows
    occ: jax.Array  # u16[B]
    t_algorithm: jax.Array  # i32[K]
    t_behavior: jax.Array  # i32[K]
    t_hits: jax.Array  # i32[K]
    t_limit: jax.Array  # i32[K]
    t_duration: jax.Array  # i32[K]
    t_greg_expire_delta: jax.Array  # i32[K]
    t_greg_duration: jax.Array  # i32[K]


DICT_TABLE_ROWS = 256  # fixed so K never forces a recompile


def apply_rounds_dict(
    state: BucketState, reqd: RequestBatchDict, round_id8, n_rounds, now_ms,
    cold_cond: bool = True,
) -> "tuple[BucketState, jax.Array]":
    """apply_rounds32 behind the config-dictionary wire.  round_id8 is
    u8 (planner guarantees n_rounds <= 255 or falls back)."""
    with jax.named_scope(SCOPE_WIRE_DECODE):
        cfg = reqd.cfg.astype(_I32)
        req32 = RequestBatch32(
            slot=reqd.slot,
            exists=(reqd.flags & 1) != 0,
            algorithm=reqd.t_algorithm[cfg],
            behavior=reqd.t_behavior[cfg],
            hits=reqd.t_hits[cfg],
            limit=reqd.t_limit[cfg],
            duration=reqd.t_duration[cfg],
            greg_expire_delta=reqd.t_greg_expire_delta[cfg],
            greg_duration=reqd.t_greg_duration[cfg],
            occ=reqd.occ.astype(_I32),
            write=(reqd.flags & 2) != 0,
        )
    return apply_rounds32(
        state, req32, round_id8.astype(_I32), n_rounds, now_ms,
        cold_cond=cold_cond,
    )


DICT_WIRE_TABLE_WORDS = 2 * DICT_TABLE_ROWS + 5 * 2 * DICT_TABLE_ROWS

# The wire's header: the last words of every shard's row, on either
# wire (written by the native encode with the rest of the buffer;
# set_wire_header is its numpy twin, for the reference packers).  What
# a dispatch program reads besides the state and the lanes
# rides the ONE buffer the stage uploads, so a launch hands the runtime
# device arrays alone and makes no host->device transfer of its own (a
# Python or numpy scalar argument is a transfer call each, 0.2 ms on a
# TPU whatever it carries).  At the row's END, so the lanes' columns
# and the table keep their offsets (multiples of P and of 256).
#
#   word 0  n_rounds          word 2  now_ms, high word
#   word 1  now_ms, low word  word 3  spare, zero
#
# The same in every shard's row (the buffer stays rectangular and
# sharded as it is), so every device reads its own copy.  A packer
# leaves it zero, and zero rounds answer nothing: `set_wire_header`
# fills it.
WIRE_HEADER_WORDS = 4


def set_wire_header(w, n_rounds: int, now_ms: int) -> None:
    """Fill the header of a packed [S, W] wire (either wire), in place."""
    lo = now_ms & _MASK32
    h = w[:, w.shape[1] - WIRE_HEADER_WORDS:]
    h[:, 0] = n_rounds
    h[:, 1] = lo - ((lo >> 31) << 32)  # the low word's bits as an i32
    h[:, 2] = now_ms >> 32


def wire_header(wire):
    """Device-side twin of set_wire_header: (n_rounds i32, now_ms i64)
    as unbatched scalars, read from the first of the [s, W] rows it is
    handed (under the mesh's `shard_map` a device holds its own shard's
    row, so that is its own copy).  Call it outside the `vmap` over
    the rows: a rounds loop whose bound is batched runs predicated,
    and selects the whole carried table every round."""
    h = wire[0, wire.shape[1] - WIRE_HEADER_WORDS:]
    return h[0], _compose64(h[1], h[2])


def dict_wire_words(P: int) -> int:
    """Words of a shard's row on the dictionary wire of P lanes."""
    return 3 * P + DICT_WIRE_TABLE_WORDS + WIRE_HEADER_WORDS


def dict_wire_lanes(words: int) -> int:
    """P of a dictionary wire whose shard row is `words` long."""
    return (words - DICT_WIRE_TABLE_WORDS - WIRE_HEADER_WORDS) // 3


def pack_dict_wire(slot, exists, write, cfg, occ, round_id, table) -> "jax.Array":
    """Serialize one dict-wire batch into a SINGLE i32 buffer.

    The numpy REFERENCE of the layout: a dispatch's wire is filled by
    the native encode beside the plan (host_runtime.cpp
    gt_mesh_encode_wire, one call for count, rule, buffer and header),
    which tests/test_native_encode.py holds to this function; the
    served path does not call it.

    The dict wire's 12 separate arrays cost 12 host->device transfers
    per dispatch; at service batch sizes (<=4096 lanes) the per-call
    overhead dwarfs the bytes, so everything rides one
    [S, 3P + DICT_WIRE_TABLE_WORDS + WIRE_HEADER_WORDS] i32 array
    instead (host packs with numpy views, device unpacks with free
    slices/shifts inside the jit):

      words [0,P)    slot (i32)
      words [P,2P)   occ | flags<<16 | cfg<<24   (flags: bit0 exists,
                                                  bit1 write)
      words [2P,3P)  round_id
      words [3P,..)  config-table rows: algo(256), behavior(256), then
                     hits/limit/duration/greg_expire_delta/
                     greg_duration as i64 lo/hi word pairs (512 each)
      last 4 words   the header (WIRE_HEADER_WORDS: n_rounds, now_ms),
                     zero here; set_wire_header fills it

    The value rows are 64-bit so ANY magnitude (monthly Gregorian
    expiries, >2^31 limits) rides the dict wire — per-lane bytes are
    unchanged because values live in the 256-row table.

    Inputs are [S, P] arrays plus the 7-row table as [rows][256]
    (shared across shards — the wire carries it once per shard row
    only to keep the buffer rectangular).
    """
    import numpy as np

    S, P = slot.shape
    w = np.empty((S, dict_wire_words(P)), dtype=np.int32)
    w[:, :P] = slot
    meta = occ.astype(np.int32) & 0xFFFF
    meta |= (exists.astype(np.int32) | (write.astype(np.int32) << 1)) << 16
    meta |= cfg.astype(np.int32) << 24
    w[:, P:2 * P] = meta
    w[:, 2 * P:3 * P] = round_id
    pos = 3 * P
    for k in range(2):  # algo, behavior: i32
        w[:, pos:pos + DICT_TABLE_ROWS] = table[k].astype(np.int32)
        pos += DICT_TABLE_ROWS
    for k in range(2, 7):  # value rows: i64 as lo/hi
        v = table[k].astype(np.int64)
        w[:, pos:pos + DICT_TABLE_ROWS] = (v & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
        pos += DICT_TABLE_ROWS
        w[:, pos:pos + DICT_TABLE_ROWS] = (v >> 32).astype(np.int32)
        pos += DICT_TABLE_ROWS
    w[:, pos:] = 0
    return w


@jax.named_scope(SCOPE_WIRE_DECODE)
def unpack_dict_wire(w, P: int):
    """Device-side twin of pack_dict_wire for ONE shard row: returns
    (slot, flags, cfg u8, occ, rid, [7 table value arrays — value rows
    composed to i64]).  Pure slicing/shifting — fuses into the kernel
    for free."""
    slot = w[:P]
    meta = w[P:2 * P]
    occ = (meta & 0xFFFF).astype(jnp.uint16)
    fl = (meta >> 16) & 0xFF
    cfg = ((meta >> 24) & 0xFF).astype(jnp.uint8)
    rid = w[2 * P:3 * P]
    pos = 3 * P
    rows = []
    for k in range(2):
        rows.append(w[pos:pos + DICT_TABLE_ROWS])
        pos += DICT_TABLE_ROWS
    for k in range(5):
        lo = w[pos:pos + DICT_TABLE_ROWS]
        pos += DICT_TABLE_ROWS
        hi = w[pos:pos + DICT_TABLE_ROWS]
        pos += DICT_TABLE_ROWS
        rows.append(_compose64(lo, hi))
    return slot, fl, cfg, occ, rid, rows


def apply_rounds_packed(
    state: BucketState, wire, n_rounds, now_ms, cold_cond: bool = True
) -> "tuple[BucketState, jax.Array]":
    """Narrow-output dict kernel behind the single-buffer wire.  Host
    precondition (narrow_ok): every value and every time the kernel
    computes fits the i32 output deltas."""
    P = dict_wire_lanes(wire.shape[0])
    slot, fl, cfg, occ, rid, rows = unpack_dict_wire(wire, P)
    reqd = RequestBatchDict(
        slot=slot,
        flags=fl.astype(jnp.uint8),
        cfg=cfg,
        occ=occ,
        t_algorithm=rows[0],
        t_behavior=rows[1],
        t_hits=rows[2].astype(_I32),
        t_limit=rows[3].astype(_I32),
        t_duration=rows[4].astype(_I32),
        t_greg_expire_delta=rows[5].astype(_I32),
        t_greg_duration=rows[6].astype(_I32),
    )
    return apply_rounds_dict(state, reqd, rid, n_rounds, now_ms, cold_cond=cold_cond)


# The wide answer's container.  A dispatch program hands the host 32-bit
# words in both directions: the wide wires go up as lo/hi pairs in one
# i32 buffer, and the wide answer comes down as i32[8, B], the four rows
# of _pack_output as their LO planes (rows 0-3) and then their HI planes
# (rows 4-7), the lanes the minor dimension.  Nothing is clipped or
# turned into a delta: the same 64 bits a lane and row.  (A TPU keeps an
# s64 array as two u32 halves and its runtime rebuilds the i64 on the
# host, fetch by fetch: a millisecond of a 4096-lane read-back.)
WIDE_ANSWER_ROWS = 8


def apply_rounds_planes(
    state: BucketState, req: RequestBatch, round_id, n_rounds, now_ms,
    cold_cond: bool = True,
) -> "tuple[BucketState, jax.Array]":
    """apply_rounds with its i64[4, B] answer as i32[8, B]: lo planes,
    then hi planes.  What the wide dispatch bodies of both wires end in."""
    state, packed = apply_rounds(
        state, req, round_id, n_rounds, now_ms, cold_cond=cold_cond
    )
    with jax.named_scope(SCOPE_ANSWER_PACK):
        return state, jnp.concatenate((_lo32(packed), _hi32(packed)))


def split_wide_answer(packed64):
    """Host twin of apply_rounds_planes' split, over numpy i64[..., 4, B]."""
    import numpy as np

    return np.concatenate(
        (packed64.astype(np.int32), (packed64 >> 32).astype(np.int32)), axis=-2
    )


def compose_wide_answer(planes):
    """The wide answer's i32[..., 8, B] planes back to _pack_output's
    i64[..., 4, B], in numpy: what gt_mesh_finish_wide does lane by
    lane (the lo word is unsigned: its top bit is bit 31 of the value,
    not a sign).  Any even count of rows, lo planes then hi planes (the
    sync answer's ten, global_ops.unpack_sync_answer)."""
    import numpy as np

    half = planes.shape[-2] // 2
    lo, hi = planes[..., :half, :], planes[..., half:, :]
    return (hi.astype(np.int64) << 32) | (lo.astype(np.int64) & 0xFFFFFFFF)


def apply_rounds_packed_wide(
    state: BucketState, wire, n_rounds, now_ms, cold_cond: bool = True
) -> "tuple[BucketState, jax.Array]":
    """Wide-output twin of apply_rounds_packed: same single-buffer wire,
    int64 compute and the packed result of _pack_output as i32[8, B]
    lo/hi planes (apply_rounds_planes).  This is what keeps
    monthly/yearly Gregorian batches on the dict wire: their far-future
    expiries exceed the narrow output's i32 deltas, but per-lane bytes
    are identical — only the readback doubles.  Matches
    interval.go:82-146 being first-class in the reference."""
    now = jnp.asarray(now_ms, _I64)
    P = dict_wire_lanes(wire.shape[0])
    slot, fl, cfg, occ, rid, rows = unpack_dict_wire(wire, P)
    with jax.named_scope(SCOPE_WIRE_DECODE):
        cfg = cfg.astype(_I32)
        delta = rows[5][cfg]
        greg_dur = rows[6][cfg]
        req = RequestBatch(
            slot=slot,
            exists=(fl & 1) != 0,
            algorithm=rows[0][cfg],
            behavior=rows[1][cfg],
            hits=rows[2][cfg],
            limit=rows[3][cfg],
            duration=rows[4][cfg],
            greg_expire=jnp.where(greg_dur != 0, now + delta, 0),
            greg_duration=greg_dur,
            occ=occ.astype(_I32),
            write=(fl & 2) != 0,
        )
    return apply_rounds_planes(
        state, req, rid, n_rounds, now_ms, cold_cond=cold_cond
    )


# The per-lane wire: what a batch the dictionary cannot hold rides (more
# than DICT_TABLE_ROWS configurations: a limit a key; more than 255
# rounds; an `occ` past 65,535).  Six words a lane that are what they
# are on either answer width, then the five values: a word each for the
# narrow answer (narrow_ok: they fit), a lo/hi pair each for the wide.
_LANE_SLOT, _LANE_FLAGS, _LANE_ALGO, _LANE_BEHAVIOR, _LANE_OCC, _LANE_RID = range(6)
_LANE_VALUES = 6  # hits, limit, duration, greg_expire(_delta), greg_duration
LANE_WIRE_WORDS = _LANE_VALUES + 5
LANE_WIRE_WORDS_WIDE = _LANE_VALUES + 2 * 5


def lane_wire_words(P: int, wide: bool) -> int:
    """Words of a shard's row on the per-lane wire of P lanes."""
    return (LANE_WIRE_WORDS_WIDE if wide else LANE_WIRE_WORDS) * P + WIRE_HEADER_WORDS


def pack_lane_wire(slot, exists, write, occ, round_id, pos, values, wide: bool):
    """Serialize one per-lane batch into a SINGLE i32 buffer, as
    pack_dict_wire does for the dictionary wire and for the same
    reason: a transfer call costs the host more than its bytes.  Like
    it, the numpy REFERENCE of the layout: gt_mesh_encode_wire fills a
    dispatch's buffer and is held to this byte for byte.  The
    buffer is [S, words * P + WIRE_HEADER_WORDS], column k of a shard
    at words [kP, (k+1)P), the header (n_rounds, now_ms: zero here,
    set_wire_header fills it) in the row's last four:

      0  slot                     4  occ       (a whole word each: this
      1  exists | write << 1      5  round id   wire is also the one for
      2  algorithm                              occ > 65,535 and for
      3  behavior                               more than 255 rounds)
      6… hits, limit, duration, greg_expire, greg_duration: one word
         each (LANE_WIRE_WORDS = 11) or, `wide`, lo then hi of each
         (LANE_WIRE_WORDS_WIDE = 16)

    `slot` … `round_id` are the plan's [S, P] arrays.  `values` are the
    seven columns algorithm … greg_duration in REQUEST order and `pos`
    each request's place in the flattened [S, P] plan; they are written
    straight into the buffer's slices.  greg_expire is the delta from
    now on the narrow wire (RequestBatch32) and the absolute time on
    the wide one (RequestBatch).  Every value rides exactly; lanes no
    request fills (slot -1) read zero."""
    import numpy as np

    S, P = slot.shape
    row = lane_wire_words(P, wide)
    w = np.zeros((S, row), dtype=np.int32)

    def col(k):
        return w[:, k * P:(k + 1) * P]

    col(_LANE_SLOT)[:] = slot
    col(_LANE_FLAGS)[:] = exists | (write << 1)
    col(_LANE_OCC)[:] = occ
    col(_LANE_RID)[:] = round_id
    # Request i lies at word pos[i] of column 0 of ITS shard's row; a
    # row is `words` columns and the header long, so later shards shift
    # by the rest.
    flat = w.reshape(-1)
    base = pos + (pos // P) * (row - P)

    def scatter(k, v):
        flat[k * P:][base] = v

    scatter(_LANE_ALGO, values[0])
    scatter(_LANE_BEHAVIOR, values[1])
    k = _LANE_VALUES
    for v in values[2:]:
        if wide:
            scatter(k, (v & 0xFFFFFFFF).astype(np.uint32).view(np.int32))
            scatter(k + 1, (v >> 32).astype(np.int32))
            k += 2
        else:
            scatter(k, v)
            k += 1
    return w


@jax.named_scope(SCOPE_WIRE_DECODE)
def unpack_lane_wire(w, wide: bool):
    """Device-side twin of pack_lane_wire for ONE shard row: returns
    (RequestBatch32, round ids), or (RequestBatch, round ids) from the
    wide buffer, its values composed to i64.  Slices and shifts only."""
    P = (w.shape[0] - WIRE_HEADER_WORDS) // (
        LANE_WIRE_WORDS_WIDE if wide else LANE_WIRE_WORDS
    )

    def col(k):
        return w[k * P:(k + 1) * P]

    if wide:
        values = [
            _compose64(col(k), col(k + 1))
            for k in range(_LANE_VALUES, LANE_WIRE_WORDS_WIDE, 2)
        ]
    else:
        values = [col(k) for k in range(_LANE_VALUES, LANE_WIRE_WORDS)]
    flags = col(_LANE_FLAGS)
    make = RequestBatch if wide else RequestBatch32
    req = make(
        col(_LANE_SLOT), (flags & 1) != 0, col(_LANE_ALGO), col(_LANE_BEHAVIOR),
        *values, occ=col(_LANE_OCC), write=(flags & 2) != 0,
    )
    return req, col(_LANE_RID)


def apply_rounds_lanes(
    state: BucketState, wire, n_rounds, now_ms, wide: bool = False,
    cold_cond: bool = True,
) -> "tuple[BucketState, jax.Array]":
    """The rounds kernel behind the single-buffer per-lane wire:
    apply_rounds32 and its packed i32[4, B] answer (host precondition:
    narrow_ok) or, `wide`, apply_rounds_planes and i32[8, B]."""
    req, rid = unpack_lane_wire(wire, wide)
    rounds = apply_rounds_planes if wide else apply_rounds32
    return rounds(state, req, rid, n_rounds, now_ms, cold_cond=cold_cond)


def build_config_dict(cols, now_ms: int):
    """Host half of the dict wire: map each lane's 7 value columns to a
    row index in a <=256-row table.  Returns (rows, enc): `rows` the
    distinct configs counted, `enc` = (cfg_idx u8[B], table 7x
    i64[DICT_TABLE_ROWS]) or None when the batch has too many (the
    per-lane wire's case).  The numpy REFERENCE of the count and the
    rule: a dispatch's are the native encode's (gt_mesh_encode_wire,
    which interns the seven values themselves, so a collision costs it
    nothing), held to this in tests/test_native_encode.py; the served
    path does not call it.  Exact by construction: lanes group
    by a 64-bit polynomial mix of the columns, then every lane is
    VERIFIED equal to its group representative — a hash collision
    degrades to fallback, never to a wrong config."""
    import numpy as np

    greg_delta = np.where(
        cols.greg_duration != 0, cols.greg_expire - now_ms, 0
    ).astype(np.int64)
    arrays = (
        cols.algo, cols.behavior, cols.hits, cols.limit, cols.duration,
        greg_delta, cols.greg_duration,
    )
    n = len(cols.algo)
    if n == 0:
        return 0, None
    with np.errstate(over="ignore"):
        h = np.zeros(n, np.int64)
        for c in arrays:
            h = h * np.int64(1000003) + c.astype(np.int64)
    uq, idx_first, inv = np.unique(h, return_index=True, return_inverse=True)
    if len(uq) > DICT_TABLE_ROWS:
        return len(uq), None
    for c in arrays:
        if not np.array_equal(c[idx_first][inv], c):
            return len(uq), None  # collision: correctness over compactness
    table = []
    for c in arrays:
        # i64 rows: the table is 256 entries, so wide values (monthly/
        # yearly Gregorian expiries, >2^31 limits) cost nothing per
        # lane — the whole batch stays on the dict wire.
        row = np.zeros(DICT_TABLE_ROWS, np.int64)
        row[: len(uq)] = c[idx_first]
        table.append(row)
    return len(uq), (inv.astype(np.uint8), tuple(table))


@jax.jit
def read_rows(state: BucketState, slots) -> BucketRows:
    """Gather full bucket rows for the given slots (host-bound: Store
    OnChange callbacks and Loader snapshots need the item state the way
    the reference passes CacheItems, store.go:29-45)."""
    s = jnp.asarray(slots, _I32)
    hot = state.hot[s]
    cold = state.cold[s]
    flags = hot[:, _H_FLAGS]
    return BucketRows(
        algo=flags & 3,
        limit=_compose64(cold[:, _C_LIM_LO], cold[:, _C_LIM_HI]),
        remaining=_compose64(hot[:, _H_REM_LO], hot[:, _H_REM_HI]),
        duration=_compose64(cold[:, _C_DUR_LO], cold[:, _C_DUR_HI]),
        stamp=_compose64(hot[:, _H_STAMP_LO], hot[:, _H_STAMP_HI]),
        expire_at=_compose64(hot[:, _H_EXP_LO], hot[:, _H_EXP_HI]),
        status=(flags >> 2) & 1,
    )


@partial(jax.jit, donate_argnums=0)
def write_rows(state: BucketState, slots, rows: BucketRows) -> BucketState:
    """Scatter full bucket rows (Store.Get results / Loader.Load items).
    Negative slots are mapped out of bounds and dropped."""
    C = state.hot.shape[0]
    s = jnp.asarray(slots, _I32)
    s = jnp.where(s >= 0, s, C)
    vals = rows_to_split(rows)
    return BucketState(
        hot=state.hot.at[s].set(vals.hot, mode="drop"),
        cold=state.cold.at[s].set(vals.cold, mode="drop"),
    )


class BackState(NamedTuple):
    """Back tier of the two-tier bucket table (same [Cb, 8] i32 hot/cold
    row layout as BucketState).

    Kernel lanes only ever address the FRONT table; rows move between
    tiers via `apply_moves` (host-planned promotions/demotions, see
    native Table two-tier mode).  The split exists because the hot
    scatter's cost scales with the table it targets (~2.4ns/slot
    measured on TPU v5e) — a 2M-slot table prices every batch ~5.9ms
    where a 262k front prices ~2.7ms, while the back tier is touched
    only by the (batched, usually empty) move program."""

    hot: jax.Array  # i32[Cb, 8]
    cold: jax.Array  # i32[Cb, 8]


def init_back(capacity: int) -> BackState:
    return BackState(
        hot=jnp.zeros((capacity, 8), _I32),
        cold=jnp.zeros((capacity, 8), _I32),
    )


def apply_moves(
    state: BucketState, back: BackState,
    promo_kind, promo_src, promo_dst, demo_src, demo_dst,
) -> "tuple[BucketState, BackState]":
    """Apply one drain window of tier moves.

    Demotions gather PRE-promotion front rows and scatter them into the
    back tier; promotions gather from the back tier (kind 0) or from
    the front (kind 1 — a row demoted and re-promoted inside the same
    window, which never reached the back table; the host rewrites those
    sources, gt_table_take_moves contract).  src=-1 marks a padding or
    cancelled record (dropped via out-of-bounds destinations).  The
    host guarantees destination uniqueness within a window
    (unique_indices) — see the native Table's cancel_pending_demo.
    """
    Cf = state.hot.shape[0]
    Cb = back.hot.shape[0]
    drop = dict(mode="drop", unique_indices=True)

    nd = demo_src.shape[0]
    dsrc = jnp.clip(demo_src, 0, Cf - 1)
    lane_d = jnp.arange(nd, dtype=_I32)
    ddst = jnp.where(demo_src >= 0, demo_dst, Cb + lane_d)
    new_back = BackState(
        hot=back.hot.at[ddst].set(state.hot[dsrc], **drop),
        cold=back.cold.at[ddst].set(state.cold[dsrc], **drop),
    )

    np_ = promo_src.shape[0]
    from_front = (promo_kind == 1)[:, None]
    psrc_b = jnp.clip(promo_src, 0, Cb - 1)
    psrc_f = jnp.clip(promo_src, 0, Cf - 1)
    # kind 0 reads the PRE-demo back rows (input `back`, never
    # `new_back`): the host hands a promotion's freed source slot to a
    # demotion of the same window (a swap), so a promo source may be a
    # same-window demo destination, and the row read is the one that
    # was there before the window.
    ph = jnp.where(from_front, state.hot[psrc_f], back.hot[psrc_b])
    pc = jnp.where(from_front, state.cold[psrc_f], back.cold[psrc_b])
    lane_p = jnp.arange(np_, dtype=_I32)
    pdst = jnp.where(promo_src >= 0, promo_dst, Cf + lane_p)
    new_state = BucketState(
        hot=state.hot.at[pdst].set(ph, **drop),
        cold=state.cold.at[pdst].set(pc, **drop),
    )
    return new_state, new_back


def read_back_rows(back: BackState, slots) -> BucketRows:
    """Gather full logical rows from the back tier (snapshot path)."""
    s = jnp.asarray(slots, _I32)
    hot = back.hot[s]
    cold = back.cold[s]
    flags = hot[:, _H_FLAGS]
    return BucketRows(
        algo=flags & 3,
        limit=_compose64(cold[:, _C_LIM_LO], cold[:, _C_LIM_HI]),
        remaining=_compose64(hot[:, _H_REM_LO], hot[:, _H_REM_HI]),
        duration=_compose64(cold[:, _C_DUR_LO], cold[:, _C_DUR_HI]),
        stamp=_compose64(hot[:, _H_STAMP_LO], hot[:, _H_STAMP_HI]),
        expire_at=_compose64(hot[:, _H_EXP_LO], hot[:, _H_EXP_HI]),
        status=(flags >> 2) & 1,
    )
