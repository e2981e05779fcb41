"""Round-4 measurement: mesh scaling shape at 1/2/4/8 shards.

The round-3 north-star claim multiplied one chip's device rate by 8 —
an unmeasured projection (VERDICT r3).  Real multi-chip hardware is not
available here, but the virtual CPU mesh runs REAL sharded programs
(one fused dispatch over S devices; real psum collectives in the GLOBAL
sync), so the SCALING SHAPE — how fixed total work behaves as the shard
count grows — is measurable.  Absolute numbers are CPU-bound and mean
nothing vs the TPU rows; the ratio columns are the result.

For S in {1, 2, 4, 8}: one child process pinned to S virtual devices
(xla_force_host_platform_device_count, exactly how tests/conftest.py
provisions the suite) runs

  * columnar ingress: the SAME fixed workload (131072-lane Zipf batch
    over 100k keys, mixed token+leaky, 262144 total slots split over
    the shards) through MeshBucketStore.apply_columns_async, depth-1
    pipelined, best-of-3 epochs; and
  * GLOBAL sync: measure_sync_cost_s on a 512-gslot table (64 active
    keys), the collective whose cost sets the GlobalSyncWait window.

Usage:
    python benchmarks/mesh_scaling.py          # parent: all S, table
    python benchmarks/mesh_scaling.py --child S  # one measurement
"""

import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

B = 131_072
N_KEYS = 100_000
TOTAL_SLOTS = 262_144
NOW = 1_700_000_000_000


def child(S: int) -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    from gubernator_tpu.cmd import place_compile_cache

    place_compile_cache()

    import numpy as np

    from gubernator_tpu.parallel.mesh import MeshBucketStore, make_mesh

    devices = jax.devices()[:S]
    assert len(devices) == S, (S, jax.devices())
    mesh = make_mesh(devices)
    store = MeshBucketStore(
        capacity_per_shard=TOTAL_SLOTS // S, g_capacity=512, mesh=mesh
    )

    rng = np.random.RandomState(42)
    hot = rng.randint(0, N_KEYS // 10, size=B)
    cold = rng.randint(0, N_KEYS, size=B)
    key_ids = np.where(rng.random(B) < 0.8, hot, cold)
    keys = [f"scale_account:{k}" for k in key_ids]
    algo = (key_ids % 2).astype(np.int32)
    behavior = np.zeros(B, np.int32)
    hits = np.ones(B, np.int64)
    limit = np.full(B, 1_000_000, np.int64)
    duration = np.full(B, 3_600_000, np.int64)

    def pump(ks, al, bh, ht, lm, dr, nb):
        def dispatch(i):
            return store.apply_columns_async(
                ks, al, bh, ht, lm, dr, NOW + i
            )

        dispatch(0).result()  # compile + fill
        dispatch(1).result()
        iters, best = 4, 0.0
        step = 2
        for _ in range(3):
            t0 = time.perf_counter()
            pending = None
            for i in range(iters):
                h = dispatch(step + i)
                if pending is not None:
                    pending.result()
                pending = h
            pending.result()
            dt = time.perf_counter() - t0
            step += iters
            best = max(best, nb * iters / dt)
        return best

    best = pump(keys, algo, behavior, hits, limit, duration, B)

    # Weak scaling: per-shard work CONSTANT (16384 lanes x S), so a
    # flat per-batch time across S means the fused program really runs
    # the shards concurrently.
    BW = 16_384 * S
    wk_ids = key_ids[:BW]
    weak = pump(
        [f"scale_account:{k}" for k in wk_ids],
        (wk_ids % 2).astype(np.int32), np.zeros(BW, np.int32),
        np.ones(BW, np.int64), np.full(BW, 1_000_000, np.int64),
        np.full(BW, 3_600_000, np.int64), BW,
    )

    # GLOBAL sync collective cost on a fresh store (measure_sync_cost_s
    # refuses live GLOBAL traffic).
    gstore = MeshBucketStore(
        capacity_per_shard=4096, g_capacity=512, mesh=mesh
    )
    from gubernator_tpu.types import Behavior, RateLimitRequest

    for i in range(64):
        gstore.apply(
            [
                RateLimitRequest(
                    name="gs", unique_key=f"g{i}", hits=1, limit=1000,
                    duration=60_000, behavior=Behavior.GLOBAL,
                )
            ],
            NOW,
        )
    gstore.sync_globals(NOW + 1)
    # measure raw sync cost via the same chained method the store's
    # tuner uses, but on this store WITH its 64 live keys: time real
    # sync_globals passes (host legs included — the serving cost).
    t0 = time.perf_counter()
    n_sync = 10
    for i in range(n_sync):
        gstore.sync_globals(NOW + 2 + i)
    sync_s = (time.perf_counter() - t0) / n_sync

    print(json.dumps({
        "S": S, "columnar_cps": best, "weak_cps": weak,
        "sync_ms": sync_s * 1e3,
    }))


def main() -> None:
    if len(sys.argv) > 2 and sys.argv[1] == "--child":
        child(int(sys.argv[2]))
        return
    rows = []
    for S in (1, 2, 4, 8):
        env = dict(os.environ)
        xla = re.sub(
            r"--xla_force_host_platform_device_count=\d+", "",
            env.get("XLA_FLAGS", ""),
        )
        env["XLA_FLAGS"] = f"{xla} --xla_force_host_platform_device_count={S}".strip()
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child", str(S)],
            env=env, cwd=REPO, check=True, capture_output=True, text=True,
            timeout=1800,
        )
        line = [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
        rows.append(json.loads(line))
        print(line, flush=True)
    base = rows[0]
    print(f"\n{'S':>2} {'fixed-work cps':>15} {'vs S=1':>7} "
          f"{'weak cps':>12} {'vs S=1':>7} {'sync ms':>8} {'vs S=1':>7}")
    for r in rows:
        print(
            f"{r['S']:>2} {r['columnar_cps']:>15,.0f} "
            f"{r['columnar_cps'] / base['columnar_cps']:>6.2f}x "
            f"{r['weak_cps']:>12,.0f} "
            f"{r['weak_cps'] / base['weak_cps']:>6.2f}x "
            f"{r['sync_ms']:>8.2f} {r['sync_ms'] / base['sync_ms']:>6.2f}x"
        )


if __name__ == "__main__":
    main()
