"""From a profiler trace to numbers: device busy and idle time, time per
program and per operation, and the longest idle gaps named by what the host
was doing in them.  `load_xplane` reads the `.xplane.pb` the JAX profiler
writes (with nothing but `jax.profiler.ProfileData`; no backend comes up);
`reduce` works on plain event rows, so that `selfcheck.py` can hold it to the
known answer of the recorded rows in `data/recorded_trace.json`."""

from __future__ import annotations

import glob
import json
import os
import re
import time

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_WAIT_S = 240.0


def load_xplane(path: str, cpu_stand_in: bool = False) -> list:
    """Event rows [plane, line, name, start_ns, duration_ns] of the device
    planes' operation and module lines and of every host thread.  With
    `cpu_stand_in` (rehearsals only: the CPU backend has no device plane) the
    host events that carry an `hlo_op` stand in for device 0's operations."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        on_device = DEVICE_PLANE.match(plane.name) is not None
        if not on_device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if on_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if ev.duration_ns <= 0:
                    continue
                # A device operation's name is its whole HLO text: keep `while.52` of
                # `%while.52 = (s32[], ...) while(...)`.
                name = ev.name.split(" = ", 1)[0].lstrip("%") if on_device else ev.name
                row = [plane.name, line.name, name, float(ev.start_ns), float(ev.duration_ns)]
                stats = dict(ev.stats) if cpu_stand_in else {}
                if "hlo_op" in stats:
                    rows.append(["/device:TPU:0", OPS_LINE, *row[2:]])
                    rows.append(["/device:TPU:0", MODULES_LINE, str(stats.get("hlo_module")), *row[3:]])
                else:
                    rows.append(row)
    return rows


def _union(intervals: list) -> list:
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def program_of(module_event: str) -> str:
    """`jit_name(1234567)` -> `jit_name`."""
    return module_event.split("(", 1)[0]


def reduce(rows: list, chips: int) -> dict:
    device = {}
    host = []
    for plane, line, name, start, dur in rows:
        if DEVICE_PLANE.match(plane):
            device.setdefault(plane, {OPS_LINE: [], MODULES_LINE: []})[line].append((name, start, dur))
        else:
            host.append((name, start, start + dur))
    if len(device) != chips or not all(d[OPS_LINE] for d in device.values()):
        raise ValueError(f"the trace holds operations of {sorted(device)}; the cell has {chips} chips")
    t_lo = min(start for _, _, _, start, _ in rows)
    t_hi = max(start + dur for _, _, _, start, dur in rows)
    busy = {}
    op_s: dict = {}
    program: dict = {}
    for plane, lines in device.items():
        merged = _union([(s, s + d) for _, s, d in lines[OPS_LINE]])
        busy[plane] = merged
        for name, _, d in lines[OPS_LINE]:
            op_s[name] = op_s.get(name, 0.0) + d / 1e9 / chips
        for name, _, d in lines[MODULES_LINE]:
            row = program.setdefault(program_of(name), [0.0, 0.0])
            row[0] += 1.0 / chips
            row[1] += d / 1e9 / chips
    busy_s = sum(hi - lo for m in busy.values() for lo, hi in m) / 1e9 / chips
    # Idle gaps of the first device, by what the host was doing in them: for
    # each traced host event name, the seconds it was under way while the device
    # sat idle (nested events each count), and the idle seconds no host event covers.
    first = busy[sorted(busy)[0]]
    edges = np.array([t_lo] + [x for lo, hi in first for x in (lo, hi)] + [t_hi])
    gap_lo, gap_hi = edges[0::2], edges[1::2]
    named: dict = {}
    if host:
        ids = {name: i for i, name in enumerate(sorted({h[0] for h in host}))}
        h_id = np.array([ids[h[0]] for h in host])
        h_lo = np.array([h[1] for h in host])
        h_hi = np.array([h[2] for h in host])
        covered = np.zeros(len(ids))
        for lo, hi in zip(gap_lo, gap_hi):
            covered += np.bincount(
                h_id, weights=np.clip(np.minimum(h_hi, hi) - np.maximum(h_lo, lo), 0.0, None),
                minlength=len(ids))
        named = {"host: " + name: covered[i] / 1e9 for name, i in ids.items() if covered[i] > 0}
        merged = np.array(_union([(h[1], h[2]) for h in host]))
        in_gaps = sum(
            float(np.clip(np.minimum(merged[:, 1], hi) - np.maximum(merged[:, 0], lo), 0.0, None).sum())
            for lo, hi in zip(gap_lo, gap_hi))
        named["host: no traced event"] = (float((gap_hi - gap_lo).sum()) - in_gaps) / 1e9
    longest = float((gap_hi - gap_lo).max()) / 1e9
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]  # noqa: E731
    return {
        "busy_s": busy_s, "window_s": (t_hi - t_lo) / 1e9, "program": program,
        "breakdown": {"device_ops": top(op_s), "idle_gaps": top(named)},
        "longest_gap_s": longest,
    }


def wait_for_span(trace_dir: str) -> None:
    """Wait until the traced daemon has written its trace (it must still be
    running: the tracer is one of its threads)."""
    span_path = os.path.join(trace_dir, "span.json")
    deadline = time.monotonic() + SPAN_WAIT_S
    while not os.path.exists(span_path):
        if time.monotonic() > deadline:
            raise RuntimeError(f"no {span_path} {SPAN_WAIT_S:.0f} s after the window: the trace was not written")
        time.sleep(0.2)


def cut(rows: list, lo_ns: float, hi_ns: float, chips: int) -> dict:
    """The rows that lie wholly inside [lo_ns, hi_ns], with their known answer:
    how `data/recorded_trace.json` was made from the first traced chip run."""
    kept = [r for r in rows if r[3] >= lo_ns and r[3] + r[4] <= hi_ns]
    got = reduce(kept, chips)
    return {
        "chips": chips, "first_device": sorted({r[0] for r in kept if DEVICE_PLANE.match(r[0])})[0],
        "busy_share": got["busy_s"] / got["window_s"], "programs": sorted(got["program"]),
        "rows": kept,
    }


def read_and_reduce(trace_dir: str, chips: int, cpu_stand_in: bool = False) -> dict:
    with open(os.path.join(trace_dir, "span.json")) as f:
        span = json.load(f)
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"{len(found)} .xplane.pb files under {trace_dir}")
    out = reduce(load_xplane(found[0], cpu_stand_in), 1 if cpu_stand_in else chips)
    # The traced span on this process's perf_counter clock, through the wall clock.
    offset = time.perf_counter() - time.time_ns() / 1e9
    out["span_perf"] = (span["start_ns"] / 1e9 + offset, span["stop_ns"] / 1e9 + offset)
    out["xplane"] = found[0]
    return out


if __name__ == "__main__":  # python3 chipbench/trace_reduce.py X.xplane.pb CHIPS [LO_MS HI_MS OUT.json]
    import sys

    rows_ = load_xplane(sys.argv[1])
    if len(sys.argv) > 3:
        t0_ = min(r[3] for r in rows_)
        with open(sys.argv[5], "w") as f_:
            json.dump(cut(rows_, t0_ + 1e6 * float(sys.argv[3]), t0_ + 1e6 * float(sys.argv[4]),
                          int(sys.argv[2])), f_, separators=(",", ":"))
    else:
        print(json.dumps(reduce(rows_, int(sys.argv[2])), indent=1))
