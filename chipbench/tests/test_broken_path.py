"""The whole of a run with the timed path broken underneath: the harness's
look for a chip is skipped (a rehearsal), the daemon is `broken_daemon.py`,
and `correct` must come out false by the comparison that guards the fault:

- one admitted check in every 20th dispatch reported with a `remaining` one
  too high: the accounting alone;
- the candidate `greg-10m.frames` on a daemon that drops the calendar bit and
  answers every quota as an hour's (the control of the configuration's calendar
  guarantee, which no population can break): a token bucket's reset time, and
  nothing of the accounting, which such a daemon keeps exact."""

from __future__ import annotations

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import harness  # noqa: E402


@pytest.mark.parametrize("workload,fault,caught_by,untouched", [
    ("v5e1-1m.frames", "remaining", "accounting.token_keys_wrong_remaining_sum", "readback.token_keys_born_outside_load"),
    ("greg-10m.frames", "calendar", "readback.token_keys_born_outside_load", "accounting.token_keys_wrong_remaining_sum"),
])
def test_a_broken_daemon_makes_the_run_incorrect(workload, fault, caught_by, untouched, capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS", "cpu"))
    monkeypatch.setenv("CHIPBENCH_BROKEN", fault)
    bench = harness.load_json(REPO, "BENCHMARK.json")
    line, status = harness.run_cell(
        bench, workload, seed=2**31 + 17, seconds=3.0, trace=False, rehearse=True,
        daemon_argv=[sys.executable, os.path.join(REPO, "chipbench", "tests", "broken_daemon.py")],
    )
    out = capsys.readouterr().out
    failing = [row.split()[1] for row in out.splitlines() if row.endswith("WRONG")]
    assert line["correct"] is False and line["checks_ok"] is False and status == 3
    assert caught_by in failing and untouched not in failing, out[-3000:]
    assert line["failed"] > 0
