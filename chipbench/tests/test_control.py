"""The control, at a size a test run can hold: the cell's rehearsal (20,000
keys, CPU backend) passes every comparison, and the same run with the
configuration's control population (twice the table's slots, so acknowledged
buckets are evicted) fails the accounting, the read-back and the cache count.
`greg-10m.frames`, the candidate of `chipbench/candidates/`, is rehearsed among
them: every lane of its load, window and read-back a calendar quota.  Its
calendar guarantee no population can break; the run that must fail for it is
`test_broken_path.py`'s, on a daemon that drops the bit.

    python3 -m pytest chipbench/tests -q        (about a minute a test)
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def rehearse(workload: str, seed: int, *flags: str) -> "tuple[dict, str]":
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", "3", "--trace", "0", "--rehearse", *flags],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600,
    )
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.mark.parametrize("workload", ["v5e1-1m.frames", "v5e1-1m.singles", "v5e4-mesh-1m.frames",
                                      "greg-10m.frames"])
def test_sound_rehearsal_passes_every_comparison_and_is_never_correct(workload):
    line, out = rehearse(workload, 7)
    assert line["checks_ok"] is True, out[-3000:]
    assert line["correct"] is False and line["rehearsal"] is True
    assert line["failed"] == 0


@pytest.mark.parametrize("workload,seed", [("v5e1-1m.frames", 11), ("v5e1-1m.frames", 2**31 + 5),
                                           ("v5e1-1m.frames", 13), ("greg-10m.frames", 2**31 + 7)])
def test_control_population_fails(workload, seed):
    line, out = rehearse(workload, seed, "--control")
    assert line["checks_ok"] is False and line["correct"] is False
    failing = [row.split()[1] for row in out.splitlines() if row.endswith("WRONG")]
    assert "daemon.cache_rows_missing" in failing  # (c) nothing evicted
    assert "readback.token_keys_wrong" in failing  # (b) the read-back
    assert any(name.startswith("accounting.token_keys") for name in failing)  # (a)
