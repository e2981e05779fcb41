"""Round-3 probe #5: per-index vs per-element scatter cost (honest mode).

Decides the state layout: 11 i32 columns (current) vs row-major
[C,16]/[C,128].  Also: gather vs scatter split, sorted indices, and
on-device sort cost.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import gubernator_tpu  # noqa: F401  (x64)
import jax
import jax.numpy as jnp
import numpy as np

from gubernator_tpu.cmd import place_compile_cache

place_compile_cache()

C = 262_144
B = 131_072
K1, K2 = 4, 20

rng = np.random.RandomState(7)
idx_np = rng.choice(C, size=B, replace=False).astype(np.int32)
idx_sorted_np = np.sort(idx_np)

_ = np.asarray(jnp.zeros((1,), jnp.int32))  # honest mode


def first_leaf(tree):
    return jax.tree_util.tree_leaves(tree)[0]


def bench(name, make_run, *args):
    runs = {k: make_run(k) for k in (K1, K2)}
    ts = {}
    for k, fn in runs.items():
        out = fn(*args)
        np.asarray(first_leaf(out).ravel()[:1])
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            out = fn(*args)
            np.asarray(first_leaf(out).ravel()[:1])
            best = min(best, time.perf_counter() - t0)
        ts[k] = best
    c = (ts[K2] - ts[K1]) / (K2 - K1)
    print(f"{name:40s} {c*1e6:10.1f} us/iter", flush=True)
    return c


def chain(body, K):
    @jax.jit
    def run(state, *rest):
        def f(i, st):
            return body(st, i, *rest)

        return jax.lax.fori_loop(0, K, f, state)

    return run


def main():
    cols = [
        jnp.asarray(rng.randint(0, 1 << 20, size=C, dtype=np.int32))
        for _ in range(11)
    ]
    idx = jnp.asarray(idx_np)
    idx_s = jnp.asarray(idx_sorted_np)

    # gather-only: fold gathers into a B-sized carry
    def gath(carry, i, st, ix):
        acc = carry
        for c in st:
            acc = acc + c[ix + (i & 0)]
        return acc

    bench("gather-only 11 cols", lambda K: chain(gath, K), jnp.zeros((B,), jnp.int32), cols, idx)
    bench("gather-only 11 cols sorted", lambda K: chain(gath, K), jnp.zeros((B,), jnp.int32), cols, idx_s)

    # scatter-only: values derived from carry scalar to defeat DCE-free motion
    def scat(st, i, ix):
        v = st[0][0] + jnp.int32(1)
        return [c.at[ix].set(v, mode="drop", unique_indices=True) for c in st]

    bench("scatter-only 11 cols", lambda K: chain(scat, K), cols, idx)
    bench("scatter-only 11 cols sorted", lambda K: chain(scat, K), cols, idx_s)

    def rmw_cols(st, i, ix):
        gs = [c[ix] for c in st]
        return [
            c.at[ix].set(g + 1, mode="drop", unique_indices=True)
            for c, g in zip(st, gs)
        ]

    bench("rmw 11 cols sorted", lambda K: chain(rmw_cols, K), cols, idx_s)

    # row-major
    for W in (16, 128):
        rows = jnp.asarray(rng.randint(0, 1 << 20, size=(C, W), dtype=np.int32))

        def rmw_rows(st, i, ix):
            g = st[ix]
            return st.at[ix].set(g + 1, mode="drop", unique_indices=True)

        bench(f"rmw rows [C,{W}] random", lambda K: chain(rmw_rows, K), rows, idx)
        bench(f"rmw rows [C,{W}] sorted", lambda K: chain(rmw_rows, K), rows, idx_s)
        del rows

    # 8-col-packed rows: [C, 8] (one 32B row per slot)
    rows8 = jnp.asarray(rng.randint(0, 1 << 20, size=(C, 8), dtype=np.int32))

    def rmw_rows8(st, i, ix):
        g = st[ix]
        return st.at[ix].set(g + 1, mode="drop", unique_indices=True)

    bench("rmw rows [C,8] random", lambda K: chain(rmw_rows8, K), rows8, idx)

    # on-device sort / argsort of the slot column
    def sortb(carry, i, v):
        return jnp.sort(v + carry[0]).astype(jnp.int32)

    bench("sort 131k i32", lambda K: chain(sortb, K), idx, idx)

    def argsortb(carry, i, v):
        return jnp.argsort(v + carry[0]).astype(jnp.int32)

    bench("argsort 131k i32", lambda K: chain(argsortb, K), idx, idx)


if __name__ == "__main__":
    main()
