"""chip_smoke.py cannot pass without a TPU, its parent stays off jax's
backends, and the compile cache is placed from outside."""

import json
import os
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env():
    """The caller's environment on one CPU device (conftest asks for eight)."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_without_a_tpu_it_fails_and_says_so():
    """Against a CPU child and without --rehearse: non-zero, `"ok": false` last."""
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO, env=_env(), capture_output=True,
        text=True, timeout=600,
    )
    assert out.returncode not in (0, 3), out.stdout[-2000:] + out.stderr[-2000:]
    verdict = json.loads(out.stdout.strip().splitlines()[-1])
    assert verdict == {"ok": False, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert "not a TPU" in out.stdout


def test_parent_builds_its_traffic_without_a_backend():
    """Importing chip_smoke, making its keys and frames and running the
    oracle leaves jax's backends untouched: the parent can never hold the chip."""
    code = (
        "import chip_smoke as cs\n"
        "from jax._src import xla_bridge\n"
        "pop = cs.Population(5000, 23, 4096)\n"
        "frame = cs.frame_of([pop.request(i, 1) for i in range(4096)])\n"
        "both = cs.TwoOracles()\n"
        "both.expect(pop.request(0, 1), 1000, 1001)\n"
        "assert cs.wire.is_ingress_frame(frame)\n"
        "print(xla_bridge.backends_are_initialized())\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=_env(), capture_output=True, text=True,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "False"


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    from gubernator_tpu import cmd

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cmd.place_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was  # jax reads the variable itself
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cmd.place_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_the_comparison_with_the_oracle_can_fail():
    """A checker that accepts everything would make the smoke vacuous."""
    import chip_smoke as cs

    pop = cs.Population(8, 23, 4096)
    both = cs.TwoOracles()
    req = pop.request(0, 1)
    limit = int(pop.limit[0])
    step = cs.HOUR_MS if pop.algo[0] == 0 else cs.HOUR_MS // limit
    both.check("first hit", req, 1000, 1002, 0, limit, limit - 1, 1001 + step)
    assert both.mismatches == []
    # The second hit answered as if it were the first: one hit was lost.
    both.check("second hit", req, 1003, 1004, 0, limit, limit - 1, 1003 + step)
    assert len(both.mismatches) == 1
