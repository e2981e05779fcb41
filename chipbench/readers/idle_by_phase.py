"""The first device's idle time in the traced window, by the program phase
under way on the host: what the chip was waiting for.

The daemon's `phase()` writes each phase as an event on its thread's line of
the profiler's trace, on the device operations' clock.  On one thread phases
nest, and the innermost counts.  Across threads an idle instant goes to the
DEEPEST phase under way: the one latest in the `waterfall` that
`/debug/latency` serves, with `no_request` phases (a thread blocked waiting
for a request) shallowest of all, so that an instant is theirs only when
nothing else is under way.  Instants with no phase on any thread are
`unattributed`.

`read` gives one share of the idle time (`params["share"]`):
`unattributed`, or `no_request` (only no-request phases under way: the chip
waits for the client, not for the host).  The first call of a run also prints
the idle seconds under each phase, which is the table a `perf_opt` issue is
written from.  None where the daemon serves no `waterfall` or the trace holds
no phase event (a program without `phase()`)."""

from __future__ import annotations

import json

from .. import trace_reduce

UNATTRIBUTED = "unattributed"


def load_threads(path: str, names: set) -> list:
    """[thread, name, start_ns, end_ns] of every host event called one of
    `names`; a thread is a (plane, line index) pair, since lines share names."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}#{i}"
            for ev in line.events:
                if ev.duration_ns > 0 and ev.name in names:
                    rows.append([thread, ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)])
    return rows


def innermost(events: list) -> list:
    """One thread's properly nested (name, lo, hi) events -> disjoint
    (name, lo, hi) pieces, each named by the innermost event covering it."""
    pieces = []
    stack: list = []  # (name, hi) of the open events, outermost first

    def close_until(t):
        nonlocal cursor
        while stack and stack[-1][1] <= t:
            name, hi = stack.pop()
            if hi > cursor:
                pieces.append((name, cursor, hi))
                cursor = hi

    cursor = 0.0
    for name, lo, hi in sorted(events, key=lambda e: (e[1], -e[2])):
        close_until(lo)
        if stack and lo > cursor:
            pieces.append((stack[-1][0], cursor, lo))
        cursor = max(cursor, lo)
        stack.append((name, min(hi, stack[-1][1]) if stack else hi))
    close_until(float("inf"))
    return pieces


def attribute(gaps: list, thread_rows: list, order: list, no_request: list) -> dict:
    """Idle seconds by phase.  `gaps`: the device's idle (lo, hi) intervals;
    `thread_rows`: `load_threads` rows; `order`: phase names, shallowest
    first."""
    names = list(no_request) + [p for p in order if p not in no_request]  # shallowest first
    rank = {name: i for i, name in enumerate(names)}
    by_thread: dict = {}
    for thread, name, lo, hi in thread_rows:
        if name in rank:
            by_thread.setdefault(thread, []).append((name, lo, hi))
    # +1/-1 per rank at each piece's edges, and the gaps' edges as rank -1.
    edges = []
    for events in by_thread.values():
        for name, lo, hi in innermost(events):
            edges.append((lo, 1, rank[name]))
            edges.append((hi, -1, rank[name]))
    for lo, hi in gaps:
        edges.append((lo, 1, -1))
        edges.append((hi, -1, -1))
    edges.sort()
    under_way = [0] * len(names)
    in_gap = 0
    out = {UNATTRIBUTED: 0.0}
    prev = None
    for t, step, r in edges:
        if in_gap and prev is not None and t > prev:
            deepest = next((i for i in range(len(names) - 1, -1, -1) if under_way[i]), None)
            key = UNATTRIBUTED if deepest is None else names[deepest]
            out[key] = out.get(key, 0.0) + (t - prev) / 1e9
        prev = t
        if r < 0:
            in_gap += step
        else:
            under_way[r] += step
    return out


def idle_gaps(device_rows: list) -> list:
    """The idle intervals of the first device inside the window its rows and
    every other row span, as `trace_reduce.reduce` takes them."""
    first = sorted({r[0] for r in device_rows if trace_reduce.DEVICE_PLANE.match(r[0])})[0]
    t_lo = min(r[3] for r in device_rows)
    t_hi = max(r[3] + r[4] for r in device_rows)
    busy = trace_reduce._union([
        (r[3], r[3] + r[4]) for r in device_rows if r[0] == first and r[1] == trace_reduce.OPS_LINE
    ])
    edges = [t_lo] + [x for lo, hi in busy for x in (lo, hi)] + [t_hi]
    return [(lo, hi) for lo, hi in zip(edges[0::2], edges[1::2]) if hi > lo]


def by_phase(ctx, params):
    """{phase: idle seconds} of this run, computed once; None as in the
    module's text."""
    if "_idle_by_phase" in ctx:
        return ctx["_idle_by_phase"]
    waterfall = ctx["after"]["latency"].get("waterfall")
    out = None
    if waterfall:
        order = [row["phase"] for row in waterfall]
        path = ctx["trace"]["xplane"]
        thread_rows = load_threads(path, set(order))
        if thread_rows:
            rows = trace_reduce.load_xplane(path, cpu_stand_in=ctx["device"]["platform"] == "cpu")
            out = attribute(idle_gaps(rows), thread_rows, order, params["no_request"])
            print("  idle seconds of the first device by phase: " + json.dumps(
                {k: round(v, 4) for k, v in sorted(out.items(), key=lambda kv: -kv[1])}), flush=True)
    ctx["_idle_by_phase"] = out
    return out


def read(ctx, params):
    seconds = by_phase(ctx, params)
    if seconds is None:
        return None
    idle = sum(seconds.values())
    if idle <= 0:
        return None
    if params["share"] == "unattributed":
        part = seconds[UNATTRIBUTED]
    else:
        part = sum(seconds.get(p, 0.0) for p in params["no_request"])
    return 100.0 * part / idle
