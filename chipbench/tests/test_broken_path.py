"""The whole of a run with the timed path broken underneath: the harness's
look for a chip is skipped (a rehearsal), the daemon is `broken_daemon.py`,
which reports one admitted check in every 20th dispatch with a `remaining` one
too high, and `correct` must come out false by the accounting alone."""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import harness  # noqa: E402


def test_an_altered_answer_makes_the_run_incorrect(capsys):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    bench = harness.load_json(REPO, "BENCHMARK.json")
    line, status = harness.run_cell(
        bench, "v5e1-1m.frames", seed=2**31 + 17, seconds=3.0, trace=False, rehearse=True,
        daemon_argv=[sys.executable, os.path.join(REPO, "chipbench", "tests", "broken_daemon.py")],
    )
    out = capsys.readouterr().out
    failing = [row.split()[1] for row in out.splitlines() if row.endswith("WRONG")]
    assert line["correct"] is False and line["checks_ok"] is False and status == 3
    assert "accounting.token_keys_wrong_remaining_sum" in failing, out[-3000:]
    assert line["failed"] > 0
