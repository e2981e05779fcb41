"""Round-3 probe #6: confirm row-scatter wins at production capacity."""

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

B = 131_072
K1, K2 = 4, 20

rng = np.random.RandomState(7)
_ = np.asarray(jnp.zeros((1,), jnp.int32))


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
    print(f"{name:44s} {c*1e6:10.1f} us/iter", flush=True)
    return c


def chain(body, K):
    @jax.jit
    def run(state, *rest):
        def f(i, st):
            return body(st, i, *rest)

        return jax.lax.fori_loop(0, K, f, state)

    return run


def rmw_rows(st, i, ix):
    g = st[ix]
    return st.at[ix].set(g + 1, mode="drop", unique_indices=True)


def main():
    for C in (262_144, 2_097_152):
        idx = np.sort(rng.choice(C, size=B, replace=False).astype(np.int32))
        idx = jnp.asarray(idx)
        rows = jnp.asarray(rng.randint(0, 1 << 20, size=(C, 16), dtype=np.int32))
        bench(f"rmw rows [{C},16] sorted", lambda K: chain(rmw_rows, K), rows, idx)
        del rows

    C = 262_144
    idxs = np.sort(rng.choice(C, size=B, replace=False).astype(np.int32))
    idx = jnp.asarray(idxs)

    rows8 = jnp.asarray(rng.randint(0, 1 << 20, size=(C, 8), dtype=np.int32))

    def rmw2(st, i, ix):
        a, b = st
        return (
            a.at[ix].set(a[ix] + 1, mode="drop", unique_indices=True),
            b.at[ix].set(b[ix] + 1, mode="drop", unique_indices=True),
        )

    bench("rmw 2x rows [C,8] sorted", lambda K: chain(rmw2, K), (rows8, rows8 + 1), idx)

    # gather rows honest (random idx), fold into carry
    ridx = jnp.asarray(rng.choice(C, size=B, replace=False).astype(np.int32))
    rows = jnp.asarray(rng.randint(0, 1 << 20, size=(C, 16), dtype=np.int32))

    def gath_rows(carry, i, st, ix):
        return carry + st[ix + (carry[0, 0] & 0)]

    bench("gather rows [C,16] random", lambda K: chain(gath_rows, K),
          jnp.zeros((B, 16), jnp.int32), rows, ridx)

    # in-batch argsort+permute+scatter end-to-end (unsorted input slots)
    def full_commit(st, i, ix):
        g = st[ix]  # gather random
        perm = jnp.argsort(ix)
        return st.at[ix[perm]].set(g[perm] + 1, mode="drop", unique_indices=True)

    bench("gather+argsort+perm+scatter [C,16]", lambda K: chain(full_commit, K), rows, ridx)


if __name__ == "__main__":
    main()
