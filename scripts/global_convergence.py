#!/usr/bin/env python3
"""GLOBAL convergence of `v5e4-mesh-1m-global` on the devices this is started
on, once: after the cell's traffic, does every shard's replica row of every hot
key hold the owner's status?

    python3 scripts/global_convergence.py --seed N [--seconds S] [--rehearse]

The daemon of the configuration's file (`chipbench/configs/
v5e4-mesh-1m-global.json`: its `env`, the traffic file's warm bucket) is
started IN THIS PROCESS, as `python -m gubernator_tpu.cmd.server` starts it,
because only the process that holds the chips can read their replica rows; the
harness's own load, pool and load generator (`chipbench/`) drive it over its
HTTP front door, and its own sync ticks run the passes.  When the traffic has
stopped and no GLOBAL lane is pending any more, the script reads, for each hot
key, the replica row (status, limit, remaining, reset_time) of its gslot on
every shard (`store.gcols`, what `tests/test_global_hot_cell.py` reads) and
compares:

- every shard's row with the owner shard's row: equal, field for field;
- the owner shard's row with the owner's BUCKET, read over HTTP with hits=0:
  a token bucket equal in status, limit and remaining; a leaky bucket equal in
  limit, its remaining between the row's and the row's plus what leaked since
  the traffic stopped (the wall clock runs on).

It is not the benchmark and gives no rate or time.  One JSON line, last;
exit 0 if converged, 1 if not, 3 for a rehearsal (20,000 keys on the CPU's
virtual devices: never a pass)."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
CONFIG = "v5e4-mesh-1m-global"
TRAFFIC = "frames-1k-global-hot"
QUIET_LIMIT_S = 10.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from chipbench import harness
    from chipbench.daemon import Http, free_port

    config = harness.load_json(harness.BENCH_DIR, "configs", CONFIG + ".json")
    traffic = harness.load_json(harness.BENCH_DIR, "traffic", TRAFFIC + ".json")
    chips = int(config["chips"])
    address = f"127.0.0.1:{free_port()}"
    env = dict(config["env"], GUBER_HTTP_ADDRESS=address, GUBER_GRPC_ADDRESS=f"127.0.0.1:{free_port()}",
               GUBER_WARMUP_SHAPES=",".join(str(b) for b in traffic["warm_buckets"]))
    n_keys = int(config["population"]["resident_keys"])
    if args.rehearse:
        n_keys = harness.REHEARSE_KEYS
        env["GUBER_CACHE_SIZE"] = str(harness.REHEARSE_SLOTS)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    os.environ.update(env)  # before jax is imported

    import importlib

    import numpy as np

    from chipbench.generators import frames
    from chipbench.loadgen import LoadGen, wall_ceil_ms, wall_floor_ms
    from chipbench.population import Population
    from gubernator_tpu.cmd import place_compile_cache

    place_compile_cache()
    from gubernator_tpu.config import setup_daemon_config
    from gubernator_tpu.daemon import spawn_daemon
    from gubernator_tpu.parallel.mesh import shard_of_key

    pop = Population(config["population"], n_keys, args.seed)
    generator = importlib.import_module(f"chipbench.generators.{traffic['kind']}")
    pool = generator.build_pool(pop, traffic, np.random.default_rng([args.seed, 0x706F6F6C]), address)
    daemon = spawn_daemon(setup_daemon_config())
    http = gen = None
    try:
        store = daemon.service.store
        http = Http(address)
        device = harness.device_of(http.get_json("/debug/device"))
        if device["count"] != chips or (device["platform"] != "tpu" and not args.rehearse):
            print(f"FAILED: the daemon holds {device}, the configuration asks for {chips} TPU chips", flush=True)
            return 1
        _, _, load_wrong = harness.load_population(http, pop, int(traffic["load_lanes"]), address)
        before = http.get_json("/debug/device")["mesh"]
        gen = LoadGen(address, pool, traffic, args.seed)
        gen.run(2.0, args.seconds)
        stopped_ms = wall_floor_ms()
        failed = sum(d.status != 200 for d in gen.done)
        # The daemon's own ticks take the last dirt; then every tick is idle.
        deadline = time.monotonic() + QUIET_LIMIT_S
        while store._global_pending and time.monotonic() < deadline:
            time.sleep(0.05)
        quiet = not store._global_pending
        mesh = http.get_json("/debug/device")["mesh"]

        hot = generator.hot_keys(pop, traffic)
        names = [f"{pop.name}_{pop.unique_key(int(i))}" for i in hot]
        gslots = [store.gtable.get(k) for k in names]
        missing = sum(g is None for g in gslots)
        g = np.array([-1 if x is None else x for x in gslots])
        with store._lock:
            gcols = store.gcols
            rows = np.stack([np.asarray(col)[:, g] for col in (
                gcols.rep_status, gcols.rep_limit, gcols.rep_remaining, gcols.rep_reset)], axis=2)  # [S, hot, 4]
        owner = np.array([shard_of_key(k, store.n_shards) for k in names])
        owner_rows = rows[owner, np.arange(len(hot))]  # [hot, 4]
        differ = int((rows != owner_rows[None]).any(axis=2).sum())  # (shard, key) pairs
        table_owner_wrong = int((store.gtable.owner_shard[g] != owner).sum())

        # The owner's bucket, through the front door, hits=0 (padded to a load frame).
        lanes = int(traffic["load_lanes"])
        idx = np.resize(hot, lanes)
        body = http.roundtrip(frames.frame_payload(pop, idx, 0, address))
        read_ms = wall_ceil_ms()
        status, limit, remaining, _ = (col[: len(hot)] for col in frames.decode(body, lanes))
        token = pop.algo[hot] == 0
        # A second of room: the last pass may start before the last answer is read.
        leaked = np.ceil(pop.limit[hot] * ((read_ms - stopped_ms + 1e3) / pop.duration[hot])) + 1
        token_wrong = int((token & (
            (owner_rows[:, 0] != status) | (owner_rows[:, 1] != limit) | (owner_rows[:, 2] != remaining))).sum())
        leaky_wrong = int((~token & (
            (owner_rows[:, 1] != limit) | (remaining < owner_rows[:, 2])
            | (remaining > np.minimum(owner_rows[:, 2] + leaked, limit)))).sum())
        audit = http.get_json("/debug/audit").get("violationTotal")
        ok = (quiet and not missing and not differ and not table_owner_wrong and not token_wrong
              and not leaky_wrong and not load_wrong and not failed and audit == 0
              and len(store.gtable) == len(hot) + 1)  # and warm-up's own key
        line = {
            "converged": bool(ok and not args.rehearse), "rehearsal": args.rehearse, "seed": args.seed,
            "device": device, "shards": int(store.n_shards), "hot_keys": int(len(hot)),
            "token_keys": int(token.sum()), "leaky_keys": int((~token).sum()),
            "owner_shards_of_the_hot_keys": np.bincount(owner, minlength=store.n_shards).tolist(),
            "requests": len(gen.done), "requests_failed": int(failed), "load_answers_wrong": int(load_wrong),
            "passes": int(mesh["syncPasses"] - before["syncPasses"]),
            "gslots_a_pass": (mesh["syncTouched"] - before["syncTouched"]) / max(
                1, mesh["syncPasses"] - before["syncPasses"]),
            "quiet_after_traffic": bool(quiet), "gslot_table_keys": len(store.gtable),
            "hot_keys_without_gslot": int(missing), "gslots_with_another_owner_shard": table_owner_wrong,
            "replica_rows_compared": int(rows.shape[0] * rows.shape[1]),
            "replica_rows_that_differ_from_the_owner_shards": differ,
            "token_owner_rows_that_differ_from_the_bucket": token_wrong,
            "leaky_owner_rows_outside_the_leak_bracket": leaky_wrong,
            "ms_from_traffic_stop_to_bucket_read": int(read_ms - stopped_ms),
            "audit_violations": audit,
        }
        print(json.dumps(line), flush=True)
        return 3 if args.rehearse else (0 if ok else 1)
    finally:
        if gen is not None:
            gen.close()
        if http is not None:
            http.close()
        daemon.close()


if __name__ == "__main__":
    raise SystemExit(main())
