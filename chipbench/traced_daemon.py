#!/usr/bin/env python3
"""The daemon of a `--trace 1` run: the same `gubernator_tpu.cmd.server` entry,
called in this process, plus one thread that on SIGUSR1 records a device trace
of CHIPBENCH_TRACE_SECONDS into CHIPBENCH_TRACE_DIR.  Only the process that
holds the chip can trace it.  The daemon's own `POST /debug/profile` would not
do: it needs GUBER_TRACE_SAMPLE > 0 (sampled spans that the timed runs do not
pay; the native ingress lane stays on under it), and it writes its dump into a
directory of `mkdtemp`'s choosing, outside the checkout, where a run may write
only inside it.  With CHIPBENCH_LOG_PADS=<file> it also appends the pad of
every columnar dispatch to that file (rehearsals)."""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _tracer(go: threading.Event, out_dir: str, seconds: float) -> None:
    go.wait()
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(out_dir, profiler_options=options)
    start_ns = time.time_ns()
    time.sleep(seconds)
    stop_ns = time.time_ns()
    jax.profiler.stop_trace()
    with open(os.path.join(out_dir, "span.json.tmp"), "w") as f:
        json.dump({"start_ns": start_ns, "stop_ns": stop_ns, "written_ns": time.time_ns()}, f)
    os.replace(os.path.join(out_dir, "span.json.tmp"), os.path.join(out_dir, "span.json"))


def _log_pads(path: str) -> None:
    from gubernator_tpu.parallel import mesh

    inner = mesh.MeshBucketStore._prepare_columns

    def logged(self, keys, cols, now_ms, force_wire=None, bt=None):
        prep = inner(self, keys, cols, now_ms, force_wire, bt)
        with open(path, "a") as f:
            f.write(f"{prep.n} {prep.padded} {prep.n_rounds}\n")
        return prep

    mesh.MeshBucketStore._prepare_columns = logged


def main() -> int:
    out_dir = os.environ.get("CHIPBENCH_TRACE_DIR")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        go = threading.Event()
        signal.signal(signal.SIGUSR1, lambda *_: go.set())
        threading.Thread(
            target=_tracer, args=(go, out_dir, float(os.environ["CHIPBENCH_TRACE_SECONDS"])),
            daemon=True, name="chipbench-tracer",
        ).start()
    if os.environ.get("CHIPBENCH_LOG_PADS"):
        _log_pads(os.environ["CHIPBENCH_LOG_PADS"])
    from gubernator_tpu.cmd import server

    return server.main([])


if __name__ == "__main__":
    raise SystemExit(main())
