"""Device microseconds of the dispatch programs per DISPATCH, from the trace:
the seconds of every bucket program on the module line (fused programs
included) over the dispatches those programs carried.  The program writes each
launch as a `dispatch.launch` event on its thread's line of the trace, with
`fused` = the dispatches that rode it (1 a solo launch; 2 or 4 a fused one,
which is ONE program): the mean of `fused` over those events is the dispatches
a launch carried in the traced span, and the module line's launches times it
the dispatches.  (The events give the RATIO and the module line the count: a
host thread's events stop some tens of milliseconds before the device's line
does, so the two counts differ by 1-2% at the trace's edges.)
`kernel_us_per_dispatch` divides the same seconds by program launches, which is
a dispatch's only while every launch is solo; there the two agree exactly.
None where the trace holds no such event (a program without `phase()`, or one
that launched nothing)."""

from __future__ import annotations

from .kernel_us_per_dispatch import bucket_programs

LAUNCH = "dispatch.launch"


def dispatches_per_launch(ctx) -> "float | None":
    """Mean `fused` of the trace's launch events, read once a run."""
    if "_dispatches_per_launch" not in ctx:
        from jax.profiler import ProfileData

        events, carried = 0, 0.0
        for plane in ProfileData.from_file(ctx["trace"]["xplane"]).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == LAUNCH and ev.duration_ns > 0:
                        events += 1
                        carried += float(dict(ev.stats).get("fused", 1))
        ctx["_dispatches_per_launch"] = carried / events if events else None
    return ctx["_dispatches_per_launch"]


def traced(ctx, params) -> "tuple[float, float] | None":
    """(device seconds of the bucket programs, the dispatches they carried)."""
    programs = bucket_programs(ctx, params)
    launches = sum(row[0] for row in programs.values())
    per_launch = dispatches_per_launch(ctx) if launches else None
    if per_launch is None:
        return None
    return sum(row[1] for row in programs.values()), launches * per_launch


def read(ctx, params):
    got = traced(ctx, params)
    return 1e6 * got[0] / got[1] if got else None
