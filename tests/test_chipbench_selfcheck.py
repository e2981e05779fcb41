"""`chipbench/selfcheck.py` in tier 1: the harness's own check of its wire,
its reference and its planted faults, so that a wire change cannot break the
benchmark unseen."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_selfcheck_exits_0():
    done = subprocess.run([sys.executable, os.path.join(REPO, "chipbench", "selfcheck.py")],
                          cwd=REPO, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
