#!/usr/bin/env python3
"""The daemon with the guarantee of `v5e1-1m-mixed` broken underneath, for
test_flagged_control.py: the same `gubernator_tpu.cmd.server` entry, but a
lane that carries NO_BATCHING, GLOBAL or MULTI_REGION is ANSWERED and NOT
APPLIED: its hit is taken out of the dispatch (the bucket never sees it) and
its answer is made up as if it had been (an admitted lane's `remaining` one
lower than the bucket's), as a daemon would that did a flagged lane's
book-keeping and then routed its hit nowhere.  Plain lanes are served as they
are, so only a cell that sends flagged lanes can catch it."""

from __future__ import annotations

import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

ROUTING_BITS = 1 | 2 | 16


def main() -> int:
    from gubernator_tpu.cmd import server
    from gubernator_tpu.parallel import mesh

    split = mesh.split_routing_bits
    prepare = mesh.MeshBucketStore._prepare_columns
    lost = {}  # id(cols) -> the lanes whose hit was dropped

    def split_and_drop(cols):
        flagged = ((cols.behavior & ROUTING_BITS) != 0) & (cols.hits == 1)
        split(cols)
        if flagged.any():
            cols.hits = np.where(flagged, 0, cols.hits).astype(np.int64)
            lost[id(cols)] = flagged

    def broken(self, keys, cols, now_ms, force_wire=None, bt=None):
        prep = prepare(self, keys, cols, now_ms, force_wire, bt)
        flagged = lost.pop(id(cols), None)
        if flagged is not None:
            commit = prep.commit

            def altered(packed):
                status, remaining, reset = commit(packed)
                remaining = np.array(remaining)
                admitted = flagged & (np.asarray(status) == 0) & (remaining > 0)
                remaining[admitted] -= 1
                return status, remaining, reset

            prep.commit = altered
        return prep

    mesh.split_routing_bits = split_and_drop
    mesh.MeshBucketStore._prepare_columns = broken
    return server.main([])


if __name__ == "__main__":
    raise SystemExit(main())
