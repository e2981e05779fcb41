"""Device microseconds of the programs whose name holds one of `programs` per
launch, on the busiest chip's line: what the kernel readers leave out (they
`exclude` the GLOBAL sync program, `sync_body`; this reads it).  Of the
device planes that ran such a program, the one that spent the longest in it
is read: a collective makes the chips wait for one another, so the busiest
line is the one that held the pass longest.

With `ops` (prefixes of operation names, as the trace's operation line has
them: `all-reduce` also finds `all-reduce-start` and `all-reduce-done`) the
microseconds are those of the union of such operations inside the programs'
runs alone, per launch: what the collective costs a launch.  That is a true 0
where the programs ran and held no such operation (a mesh of one chip).

None where no device plane ran such a program in the traced span: a cell
whose window runs no sync pass, or a trace with no device plane."""

from __future__ import annotations

from .. import trace_reduce
from .scope_us_per_dispatch import under_scope_us


def device_lines(ctx) -> dict:
    """{device plane: {line: [(name, start_ns, end_ns)]}}, read once a run."""
    if "_device_lines" not in ctx:
        rows = trace_reduce.load_xplane(
            ctx["trace"]["xplane"], cpu_stand_in=ctx["device"]["platform"] == "cpu")
        lines: dict = {}
        for plane, line, name, start, dur in rows:
            if trace_reduce.DEVICE_PLANE.match(plane):
                lines.setdefault(plane, {}).setdefault(line, []).append((name, start, start + dur))
        ctx["_device_lines"] = lines
    return ctx["_device_lines"]


def read(ctx, params):
    busiest = None  # (nanoseconds in the programs, their runs, the plane's operations)
    for lines in device_lines(ctx).values():
        runs = [(lo, hi) for name, lo, hi in lines.get(trace_reduce.MODULES_LINE, ())
                if any(p in name for p in params["programs"])]
        spent = sum(hi - lo for lo, hi in runs)
        if runs and (busiest is None or spent > busiest[0]):
            busiest = (spent, runs, lines.get(trace_reduce.OPS_LINE, ()))
    if busiest is None:
        return None
    spent, runs, ops = busiest
    if "ops" not in params:
        return spent / 1e3 / len(runs)
    picked = [(lo, hi) for name, lo, hi in ops if name.startswith(tuple(params["ops"]))]
    return under_scope_us(picked, runs) or 0.0
