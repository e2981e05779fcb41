#!/usr/bin/env python3
"""Several runs of run.py in one call, one after another (one process holds
the chip at a time), their result lines gathered into one JSON-lines file.
For the builder's measurements; the driver calls run.py itself.

    python3 chipbench/many.py OUT.jsonl -- --workload W --seconds S [--trace 1] -- SEED [SEED ...]
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv) -> int:
    out_path, rest = argv[0], argv[1:]
    first = rest.index("--")
    second = rest.index("--", first + 1)
    run_args, seeds = rest[first + 1:second], rest[second + 1:]
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    worst = 0
    for seed in seeds:
        t = time.monotonic()
        proc = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), *run_args, "--seed", seed],
            capture_output=True, text=True,
        )
        took = time.monotonic() - t
        lines = proc.stdout.strip().splitlines()
        print("\n".join(lines[:-1]), flush=True)
        record = {"seed": int(seed), "args": run_args, "rc": proc.returncode, "took_s": round(took, 1)}
        try:
            record["line"] = json.loads(lines[-1])
        except (IndexError, ValueError):
            record["tail"] = lines[-3:] + proc.stderr.strip().splitlines()[-5:]
        print("RESULT " + json.dumps(record), flush=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(record) + "\n")
        worst = max(worst, proc.returncode)
    return worst


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
