"""Round-4 probe #3: WHICH narrowing piece costs the 5.2ms?

probe_r4_bisect found apply_rounds32 (narrow wire) at 5770us/batch vs
apply_rounds (wide) at 515us — the narrowing layer dominates the
production kernel ~11x.  This probe prices the layer's pieces by
building apply_rounds32 variants with parts disabled:

  A   full apply_rounds32                      (baseline)
  A1  no -2 sentinel: skip the pre-batch row gather + pre_exp compare
      (delta clips instead of passing through)
  A2  narrow INPUT only: upcast i32 inputs, return the wide i64 packed
      output untouched (isolates the input upcast cost)
  A3  output delta+cast WITHOUT the stack reorder: subtract/clip rows
      in-place on the i64[4,B] then astype (isolates jnp.stack)
  B   wide apply_rounds                        (floor, re-measured)

Each measured by the same differential chained-K method.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import gubernator_tpu  # noqa: F401
import jax
import jax.numpy as jnp
import numpy as np

from gubernator_tpu.cmd import place_compile_cache

place_compile_cache()

from gubernator_tpu.ops import buckets

B = 131_072
C = 262_144
K_LO, K_HI = 4, 20
NOW = 1_700_000_000_000

rng = np.random.RandomState(7)
_ = np.asarray(jnp.zeros((1,), jnp.int32))  # honest mode

_I64 = jnp.int64
_I32 = jnp.int32


def measure(name, make_fn, state, *args):
    ts = {}
    for K in (K_LO, K_HI):
        fn = make_fn(K)
        st, out = fn(state, *args)
        np.asarray(jax.tree_util.tree_leaves(out)[0].ravel()[:1])
        best = float("inf")
        for _ in range(4):
            t0 = time.perf_counter()
            st, out = fn(st, *args)
            np.asarray(jax.tree_util.tree_leaves(out)[0].ravel()[:1])
            best = min(best, time.perf_counter() - t0)
        ts[K] = best
        del st, out
    us = (ts[K_HI] - ts[K_LO]) / (K_HI - K_LO) * 1e6
    print(f"{name:58s} {us:9.1f} us/batch", flush=True)
    return us


def chain(body):
    def make(K):
        @jax.jit
        def run(state, *args):
            def f(i, c):
                st, _ = c
                st, out = body(st, i, *args)
                return jax.lax.optimization_barrier((st, out))

            st0, out0 = body(state, jnp.asarray(0, jnp.int32), *args)
            return jax.lax.fori_loop(1, K, f, (st0, out0))

        return run

    return make


def upcast(req32, now):
    return buckets.RequestBatch(
        slot=req32.slot, exists=req32.exists, algorithm=req32.algorithm,
        behavior=req32.behavior, hits=req32.hits.astype(_I64),
        limit=req32.limit.astype(_I64), duration=req32.duration.astype(_I64),
        greg_expire=now + req32.greg_expire_delta.astype(_I64),
        greg_duration=req32.greg_duration.astype(_I64),
        occ=req32.occ, write=req32.write,
    )


def main():
    one = jnp.asarray(1, jnp.int32)
    slot = rng.permutation(C)[:B].astype(np.int32)
    n = B
    b32 = jax.device_put(buckets.make_batch32(
        slot, np.ones(n, bool), (slot % 2).astype(np.int32),
        np.zeros(n, np.int32), np.ones(n, np.int32),
        np.full(n, 1 << 30, np.int32), np.full(n, 3_600_000, np.int32),
    ))
    b64 = jax.device_put(buckets.make_batch(
        slot, np.ones(n, bool), (slot % 2).astype(np.int32),
        np.zeros(n, np.int32), np.ones(n, np.int64),
        np.full(n, 1 << 30, np.int64), np.full(n, 3_600_000, np.int64),
    ))
    rid = jax.device_put(np.zeros(n, np.int32))

    state = buckets.init_state(C)
    create = jax.device_put(
        buckets.make_batch(
            slot, np.zeros(n, bool), (slot % 2).astype(np.int32),
            np.zeros(n, np.int32), np.ones(n, np.int64),
            np.full(n, 1 << 30, np.int64), np.full(n, 3_600_000, np.int64),
        )
    )
    state, _p = buckets.apply_rounds_jit(state, create, rid, one, NOW)
    np.asarray(_p[:1, :1])

    now_dev = jnp.asarray(NOW, _I64)

    def a_body(st, i, b, r):
        return buckets.apply_rounds32(st, b, r, one, now_dev + i.astype(_I64))

    measure("A  apply_rounds32 full", chain(a_body), state, b32, rid)

    # A1: no -2 sentinel (no pre-batch gather; deltas clip)
    def a1_body(st, i, b, r):
        now = now_dev + i.astype(_I64)
        req = upcast(b, now)
        st, packed64 = buckets.apply_rounds(st, req, r, one, now)
        hi = jnp.asarray((1 << 31) - 1, _I64)

        def delta(v):
            d = v - now
            return jnp.where(v == 0, -1, jnp.clip(d, 0, hi))

        packed32 = jnp.stack(
            (packed64[0], jnp.clip(packed64[1], 0, hi),
             delta(packed64[2]), delta(packed64[3]))
        ).astype(_I32)
        return st, packed32

    measure("A1 no sentinel pre-gather", chain(a1_body), state, b32, rid)

    # A2: narrow input only, wide output
    def a2_body(st, i, b, r):
        now = now_dev + i.astype(_I64)
        return buckets.apply_rounds(st, upcast(b, now), r, one, now)

    measure("A2 narrow input, wide output", chain(a2_body), state, b32, rid)

    # A3: delta on rows without restacking (subtract a row-constant
    # offset vector, then one astype)
    def a3_body(st, i, b, r):
        now = now_dev + i.astype(_I64)
        req = upcast(b, now)
        st, packed64 = buckets.apply_rounds(st, req, r, one, now)
        off = jnp.stack(
            (jnp.zeros((), _I64), jnp.zeros((), _I64), now, now)
        )[:, None]
        return st, (packed64 - off).astype(_I32)

    measure("A3 row-offset subtract + cast", chain(a3_body), state, b32, rid)

    def b_body(st, i, b, r):
        return buckets.apply_rounds(st, b, r, one, now_dev + i.astype(_I64))

    measure("B  apply_rounds wide (floor)", chain(b_body), state, b64, rid)

    # B2: wide kernel + plain i32 cast of all four rows (no deltas)
    def b2_body(st, i, b, r):
        st, packed64 = buckets.apply_rounds(st, b, r, one, now_dev + i.astype(_I64))
        return st, packed64.astype(_I32)

    measure("B2 wide + bare i32 cast", chain(b2_body), state, b64, rid)


if __name__ == "__main__":
    main()
