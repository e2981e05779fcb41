"""A traced rehearsal of each benchmark cell on the CPU (seconds long): its
`.xplane.pb` holds every phase its path crosses, as events on the host
threads' lines, and its result line carries the per-layer metrics that read
them."""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The dispatch pipeline and the GLOBAL tick, crossed by every path.  No cell
# sends a GLOBAL lane, so every tick of the traced seconds is an idle one.
PIPELINE = {
    "dispatch.prepare", "dispatch.plan_wait", "dispatch.plan_native", "dispatch.stage", "dispatch.gate_wait",
    "dispatch.launch", "dispatch.launch_wait", "dispatch.fetch", "dispatch.commit",
    "response.encode", "epoll.wait", "global.tick_idle",
}
SYNC_PASS = {"global.sync_drain", "global.sync"}
PUMP = {"pump.take", "pump.depth_wait", "pump.admit", "pump.outcome", "pump.account"}
CROSSED = {
    # One connection, GUBC frames: the native ingress lane.
    "v5e1-1m.frames": PIPELINE | PUMP,
    # 32 connections, classic JSON calls: since PR 47 the same lane (until
    # then gateway workers, the express bypass and the batcher's window:
    # `ingress.parse`, `express.submit`, which a plain call no longer enters).
    "v5e1-1m.singles": PIPELINE | PUMP,
}
# The route every classic call took before PR 47, and a fallback's since.
PYTHON_ROUTE = {"ingress.parse", "express.submit"}
METRICS = {
    "v5e1-1m.frames": {
        "plan.lock_wait_ms", "plan.native_ms_per_dispatch", "launch.lock_wait_ms",
        "batcher.pump_ms_per_take", "edge.unattributed_ms_per_req",
        "device.idle_unattributed_share", "device.idle_no_request_share", "xla.program_load_s",
        "edge.recv_ms_per_req", "edge.handoff_ms_per_req", "edge.send_ms_per_req", "device.idle_edge_io_share"},
    # Every call is a take's on the native lane (`ingress.native_call_share`),
    # where the edge's phases are observed too; `device.idle_edge_io_share`
    # and the pump's metric do not list the cell.
    "v5e1-1m.singles": {
        "plan.lock_wait_ms", "plan.native_ms_per_dispatch", "launch.lock_wait_ms", "device.idle_unattributed_share",
        "device.idle_no_request_share", "xla.program_load_s", "ingress.native_call_share",
        "edge.recv_ms_per_req", "edge.handoff_ms_per_req", "edge.send_ms_per_req"},
}


@pytest.mark.parametrize("cell", sorted(CROSSED))
def test_traced_rehearsal_holds_the_phases_its_path_crosses(cell):
    from jax.profiler import ProfileData

    seed = 2_147_483_700 + len(cell)  # past 2**31: the driver's seeds are large
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"), "--workload", cell,
         "--seed", str(seed), "--seconds", "4", "--trace", "1", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]  # a rehearsal is never a pass
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["checks_ok"] is True
    assert METRICS[cell] <= set(line["metrics"]), sorted(line["metrics"])
    # No pass ran between the snapshots, so the tick's stall has no reading.
    assert "launch.sync_stall_ms" not in line["metrics"]
    assert line["metrics"]["device.idle_unattributed_share"]["value"] < 50.0
    named = {name for name, _ in line["breakdown"]["idle_gaps"]}
    assert any(n.startswith("host: dispatch.") or n == "host: epoll.wait" for n in named), named
    assert "idle seconds of the first device by phase" in proc.stdout
    if cell == "v5e1-1m.frames":
        assert "idle seconds of the first device by edge phase" in proc.stdout
        assert (line["metrics"]["device.idle_edge_io_share"]["value"]
                <= line["metrics"]["device.idle_no_request_share"]["value"])
    else:
        assert "device.idle_edge_io_share" not in line["metrics"]
        assert line["metrics"]["ingress.native_call_share"]["value"] == 100.0  # every call kept
    (path,) = glob.glob(os.path.join(
        REPO, "chipbench", "out", f"{cell}.seed{seed}.trace1.trace", "plugins", "profile", "*", "*.xplane.pb"))
    found = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                found.update(ev.name for ev in ln.events)
    assert CROSSED[cell] <= found, sorted(CROSSED[cell] - found)
    assert not PYTHON_ROUTE & found, sorted(PYTHON_ROUTE & found)  # no request left the lane
    assert not SYNC_PASS & found, sorted(SYNC_PASS & found)
