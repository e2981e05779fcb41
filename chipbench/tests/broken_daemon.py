#!/usr/bin/env python3
"""The daemon with its timed path broken underneath, for test_broken_path.py:
the same `gubernator_tpu.cmd.server` entry, but every 20th columnar dispatch
reports one admitted check's `remaining` one too high, as a daemon that lost
the hit would."""

from __future__ import annotations

import itertools
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def main() -> int:
    from gubernator_tpu.cmd import server
    from gubernator_tpu.parallel import mesh

    inner = mesh.MeshBucketStore._prepare_columns
    counter = itertools.count(1)

    def broken(self, keys, cols, now_ms, force_wire=None):
        prep = inner(self, keys, cols, now_ms, force_wire)
        if next(counter) % 20 == 0 and prep.n and int(cols.hits[0]) == 1:
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
