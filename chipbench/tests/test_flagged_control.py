"""The control of `v5e1-1m-mixed`'s guarantee ("a GLOBAL or MULTI_REGION lane
whose owner is this daemon is answered from the owner's bucket, exactly;
NO_BATCHING changes no answer"), which no population can break: the cell's
rehearsal (20,000 keys, CPU backend) on `broken_daemon_flagged.py`, which
answers a flagged lane and does not apply its hit, must come out not correct by
a row of the accounting or of the read-back; the sound daemon's rehearsal
passes every comparison, keeps every frame on the native lane and pads every
dispatch to the one warm bucket.

    python3 -m pytest chipbench/tests/test_flagged_control.py -q   (about a minute a test)
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import harness  # noqa: E402

CELL = "v5e1-1m-mixed.frames"


def test_the_sound_rehearsal_passes_stays_native_and_pads_to_the_one_bucket():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 41), "--seconds", "3", "--trace", "0", "--rehearse", "--log-pads"],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO, timeout=600)
    out = proc.stdout
    assert proc.returncode == 3, out[-3000:] + proc.stderr[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert line["checks_ok"] is True and line["correct"] is False and line["rehearsal"] is True
    assert line["failed"] == 0
    assert not [row for row in out.splitlines() if row.endswith("WRONG")]
    (pads,) = re.findall(r"pads of every dispatch \(per shard\): (.*)", out)
    assert re.fullmatch(r"4096: \d+ dispatches of 4096\.\.4096 lanes, at most 1 rounds", pads), pads
    (frames, fallbacks) = map(int, re.search(
        r"after the read-back: frames (\d+), fallbacks (\d+)", out).groups())
    assert frames > 100 and fallbacks == 0
    assert "xla.compiles_in_window 0" in out


def test_a_daemon_that_answers_a_flagged_lane_and_does_not_apply_it_is_caught(capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS", "cpu"))
    bench = harness.load_json(REPO, "BENCHMARK.json")
    line, status = harness.run_cell(
        bench, CELL, seed=2**31 + 43, seconds=3.0, trace=False, rehearse=True,
        daemon_argv=[sys.executable, os.path.join(REPO, "chipbench", "tests", "broken_daemon_flagged.py")],
    )
    out = capsys.readouterr().out
    failing = [row.split()[1] for row in out.splitlines() if row.endswith("WRONG")]
    assert line["correct"] is False and line["checks_ok"] is False and status == 3
    assert [name for name in failing if name.startswith(("accounting.", "readback."))], out[-3000:]
    # The fault is the flagged lanes' alone: the load (behaviour 0) is answered right.
    assert "load.first_hit_answers_wrong" not in failing
    assert line["failed"] > 0


def test_the_same_daemon_serves_the_bypass_right(capsys, monkeypatch):
    """`v5e1-1m.frames` sends no flagged lane, so the broken daemon passes it:
    the fault is caught in this cell and nowhere else."""
    monkeypatch.setenv("JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS", "cpu"))
    bench = harness.load_json(REPO, "BENCHMARK.json")
    line, status = harness.run_cell(
        bench, "v5e1-1m.frames", seed=2**31 + 43, seconds=3.0, trace=False, rehearse=True,
        daemon_argv=[sys.executable, os.path.join(REPO, "chipbench", "tests", "broken_daemon_flagged.py")],
    )
    assert line["checks_ok"] is True and status == 3, capsys.readouterr().out[-3000:]
