"""Round-3 probe #3: which arithmetic op burns the 30ms?

Times individual vector ops over B=131072 lanes, chained in one jit.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from gubernator_tpu.cmd import place_compile_cache

place_compile_cache()
jax.config.update("jax_enable_x64", True)

B = 131_072
ITERS = 16

rng = np.random.RandomState(7)
a64 = jnp.asarray(rng.randint(1, 1 << 40, size=B).astype(np.int64))
b64 = jnp.asarray(rng.randint(1, 1 << 20, size=B).astype(np.int64))
a32 = jnp.asarray(rng.randint(1, 1 << 30, size=B, dtype=np.int32))
b32 = jnp.asarray(rng.randint(1, 1 << 15, size=B, dtype=np.int32))


def bench(name, fn, *args):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    dt = (time.perf_counter() - t0) / ITERS
    del out
    print(f"{name:36s} {dt*1e6:10.1f} us/iter", flush=True)


def chain(body):
    @jax.jit
    def run(x, y):
        def f(i, x):
            return body(x, y)

        return jax.lax.fori_loop(0, ITERS, f, x)

    return run


def main():
    bench("i64 add", chain(lambda x, y: x + y), a64, b64)
    bench("i64 mul", chain(lambda x, y: x * y), a64, b64)
    bench("i64 div", chain(lambda x, y: x // y), a64, b64)
    bench("i64 mod", chain(lambda x, y: x % y), a64, b64)
    bench("i64 divmod pow2", chain(lambda x, y: x // (1 << 20)), a64, b64)
    bench("i32 add", chain(lambda x, y: x + y), a32, b32)
    bench("i32 mul", chain(lambda x, y: x * y), a32, b32)
    bench("i32 div", chain(lambda x, y: x // y), a32, b32)
    bench("i32 mod", chain(lambda x, y: x % y), a32, b32)
    bench("i64 where", chain(lambda x, y: jnp.where(x > y, x, y)), a64, b64)
    bench("i64 cmp+sel x5", chain(
        lambda x, y: jnp.where(x > y, x, jnp.where(x < y, y, jnp.where(x == y, x + 1, jnp.where(x > 0, y + 1, jnp.where(y > 0, x - 1, y)))))
    ), a64, b64)
    bench("f32 div", chain(lambda x, y: x / y),
          a32.astype(jnp.float32), b32.astype(jnp.float32))

    from gubernator_tpu.ops.buckets import _muldiv128, _leak_amounts

    bench("muldiv128", chain(lambda x, y: _muldiv128(x, y, y + 3)[0]), a64, b64)
    bench("leak_amounts", chain(lambda x, y: _leak_amounts(jnp.minimum(x, y), x, y)[0]), a64, b64)


if __name__ == "__main__":
    main()
