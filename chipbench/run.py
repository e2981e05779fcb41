#!/usr/bin/env python3
"""One run of one benchmark cell on the machine this is started on.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result (see chipbench/README.md); its
last key, `compared`, and the last lines of standard error give every number
compared beside its limit.  It
exits non-zero, with no result line, when the daemon's device is not a TPU or
there are fewer chips than the cell asks for.  `--rehearse` runs the cell at
20,000 keys on whatever backend JAX finds and always ends `correct: false`,
exit status 3; `--control` loads the configuration's control population.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import harness  # noqa: E402
from chipbench.daemon import BenchFailure  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--log-pads", action="store_true",
                    help="log the pad of every dispatch (through traced_daemon.py)")
    args = ap.parse_args(argv)
    try:
        bench = harness.load_json(REPO, "BENCHMARK.json")
        line, status = harness.run_cell(
            bench, args.workload, args.seed, args.seconds, bool(args.trace),
            rehearse=args.rehearse, control=args.control, t_process_start=T_PROCESS_START,
            log_pads=args.log_pads,
        )
    except BenchFailure as e:
        print(f"FAILED: {e}", flush=True)
        return 1
    except Exception:  # noqa: BLE001 — no result line on any fault
        print(f"FAILED:\n{traceback.format_exc()}", flush=True)
        return 1
    print(json.dumps(line), flush=True)
    for name, c in line["compared"].items():  # and as the last lines of standard error
        print(f"compared: {name} {c['value']:g} (limit {c['limit']:g}) {'ok' if c['ok'] else 'WRONG'}",
              file=sys.stderr, flush=True)
    return status


if __name__ == "__main__":
    raise SystemExit(main())
