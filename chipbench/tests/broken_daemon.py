#!/usr/bin/env python3
"""The daemon with its timed path broken underneath, for test_broken_path.py
and test_control.py: the same `gubernator_tpu.cmd.server` entry with one fault,
named by CHIPBENCH_BROKEN:

- `remaining` (the default): every 20th columnar dispatch reports one admitted
  check's `remaining` one too high, as a daemon that lost the hit would;
- `calendar`: the DURATION_IS_GREGORIAN bit is dropped from every lane before
  the plan and the lane's quota answered as an hour's, as a program that knows
  no calendar would serve it: nothing resets at its interval's end."""

from __future__ import annotations

import itertools
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

GREGORIAN = 4
HOUR_MS = 3_600_000


def main() -> int:
    from gubernator_tpu.cmd import server
    from gubernator_tpu.parallel import mesh

    fault = os.environ.get("CHIPBENCH_BROKEN", "remaining")
    if fault not in ("remaining", "calendar"):
        raise SystemExit(f"CHIPBENCH_BROKEN={fault!r}: no such fault")
    inner = mesh.MeshBucketStore._prepare_columns
    counter = itertools.count(1)

    def broken(self, keys, cols, now_ms, force_wire=None, bt=None):
        if fault == "calendar":
            quota = (cols.behavior & GREGORIAN) != 0
            cols.behavior = (cols.behavior & ~GREGORIAN).astype(np.int32)
            cols.duration = np.where(quota, HOUR_MS, cols.duration).astype(np.int64)
            cols.greg_expire = np.zeros_like(cols.greg_expire)
            cols.greg_duration = np.zeros_like(cols.greg_duration)
        prep = inner(self, keys, cols, now_ms, force_wire, bt)
        if fault == "remaining" and next(counter) % 20 == 0 and prep.n and int(cols.hits[0]) == 1:
            commit = prep.commit

            def altered(packed):
                status, remaining, reset = commit(packed)
                remaining = np.array(remaining)
                if status[0] == 0:
                    remaining[0] += 1
                return status, remaining, reset

            prep.commit = altered
        return prep

    mesh.MeshBucketStore._prepare_columns = broken
    return server.main([])


if __name__ == "__main__":
    raise SystemExit(main())
