"""The first device's idle time that the daemon's own C++ edge was busy in:
of the idle instants `idle_by_phase` books as `no_request` (on the Python
threads only a thread blocked waiting for a request is under way: "the chip
waits for the client"), the share in which an acceptor thread was reading a
request off its socket (`edge.recv`), handing it to a worker (`edge.handoff`)
or writing an answer (`edge.send`).

An acceptor thread runs no Python and writes no event into the profiler's
trace.  The daemon stamps those three intervals in C++ on CLOCK_MONOTONIC and,
while a profiler session runs, puts the stamps of each native take into the
metadata of its `pump.admit` event and the answers drained after it into that
of its `pump.account` event (`gubernator_tpu/saturation.py` `edge_trace_note`;
`../layer_metrics/README.edge.md` has the format), each with one anchor
`mono_ns` read beside the event's start.  `rebuild` takes an event's own start
less its anchor as the offset between the two clocks, the median of that over
the trace's carriers, and shifts every stamp onto the trace's clock.

The first call of a run prints the idle seconds under each of the three, how
far the rebuilt take times lie from the `pump.take` events' ends (what holds
the anchor to the trace's clock), and the distance from a `dispatch.launch`
event's start to the device program it launched: the smallest, the median and
how many lie below zero (there the device's clock runs behind the host's).  None where the
trace carries no stamps (a program from before them, or a cell that never takes
on the native lane)."""

from __future__ import annotations

import bisect
import json
import statistics

from .. import trace_reduce
from .idle_by_phase import attribute as attribute_by_phase
from .idle_by_phase import idle_gaps, load_threads

CARRIERS = ("pump.admit", "pump.account")
TAKE = "pump.take"
LAUNCH = "dispatch.launch"
EDGE_PHASES = ("edge.recv", "edge.handoff", "edge.send")


def load_stamps(path: str) -> list:
    """[thread, start_ns, stats] of every host event of `CARRIERS` that carries
    the edge's stamps; a thread is named as `idle_by_phase.load_threads` names it."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in CARRIERS:
                    stats = dict(ev.stats)
                    if "mono_ns" in stats:
                        rows.append([f"{plane.name}#{i}", float(ev.start_ns), stats])
    return rows


def _records(text, width: int) -> list:
    """`a:b:c;d:e:f` -> [[a, b, c], [d, e, f]] as ints; [] for an empty field."""
    out = []
    for rec in str(text or "").split(";"):
        if rec:
            fields = [int(x) for x in rec.split(":")]
            if len(fields) != width:
                raise ValueError(f"an edge record of {len(fields)} fields where {width} belong: {rec!r}")
            out.append(fields)
    return out


def rebuild(admits: list) -> "tuple[list, list, float]":
    """(`[name, lo, hi]` of every stamped edge interval, `[thread, admit start,
    take time]` of every take, the clock offset), all on the trace's clock.
    A carrier holds a take (`take`, `edge`), drained answers (`sends`) or both."""
    offset = statistics.median(start - int(stats["mono_ns"]) for _, start, stats in admits)
    edge_rows, takes = [], []
    for thread, start, stats in admits:
        anchor = int(stats["mono_ns"]) + offset
        for _token, first_byte, body, arrival in _records(stats.get("edge"), 4):
            edge_rows.append(["edge.recv", anchor + first_byte, anchor + body])
            edge_rows.append(["edge.handoff", anchor + body, anchor + arrival])
        for _token, staged, last_byte in _records(stats.get("sends"), 3):
            edge_rows.append(["edge.send", anchor + staged, anchor + last_byte])
        if "take" in stats:
            takes.append([thread, start, anchor + int(stats["take"])])
    return edge_rows, takes, offset


def _clip(gaps: list, intervals: list) -> list:
    """The parts of `gaps` (disjoint, sorted) that lie under any of `intervals`."""
    cover: list = []
    for lo, hi in sorted((lo, hi) for lo, hi in intervals if hi > lo):
        if cover and lo <= cover[-1][1]:
            cover[-1][1] = max(cover[-1][1], hi)
        else:
            cover.append([lo, hi])
    out = []
    i = 0
    for g_lo, g_hi in gaps:
        while i < len(cover) and cover[i][1] <= g_lo:
            i += 1
        j = i
        while j < len(cover) and cover[j][0] < g_hi:
            out.append((max(cover[j][0], g_lo), min(cover[j][1], g_hi)))
            j += 1
    return out


def attribute(gaps: list, thread_rows: list, order: list, no_request: list, edge_rows: list) -> dict:
    """Idle seconds: `idle` in all, `no_request` with only no-request phases
    under way on the Python threads, `edge_io` the part of that under any
    rebuilt edge interval, and the part under each edge phase by its name
    (they may overlap: an answer leaves while the next request arrives)."""
    def waiting_for_a_request(some_gaps):
        seconds = attribute_by_phase(some_gaps, thread_rows, order, no_request)
        return sum(seconds.get(p, 0.0) for p in no_request)

    out = {
        "idle": sum(hi - lo for lo, hi in gaps) / 1e9,
        "no_request": waiting_for_a_request(gaps),
        "edge_io": waiting_for_a_request(_clip(gaps, [(lo, hi) for _, lo, hi in edge_rows])),
    }
    for name in EDGE_PHASES:
        out[name] = waiting_for_a_request(_clip(gaps, [(lo, hi) for n, lo, hi in edge_rows if n == name]))
    return out


def take_distances_ns(takes: list, thread_rows: list) -> list:
    """For each take, |its rebuilt clock reading - the end of the `pump.take`
    event that returned it| (the last one on its thread to end before its
    `pump.admit` starts).  The reading is taken in C++ when the take wakes, the
    event ends when Python has the batch: a sound offset leaves 0.1 ms or so."""
    ends: dict = {}
    for thread, name, _lo, hi in thread_rows:
        if name == TAKE:
            ends.setdefault(thread, []).append(hi)
    out = []
    for thread, admit_start, take_time in takes:
        before = [hi for hi in ends.get(thread, ()) if hi <= admit_start]
        if before:
            out.append(abs(take_time - max(before)))
    return out


def launch_to_program_ns(device_rows: list, thread_rows: list, exclude: list) -> "list | None":
    """[smallest, median, how many below zero, how many] of (device program
    start - start of the `dispatch.launch` event that launched it), over the
    first device's programs.  A program is paired with the launch event that
    starts nearest to it, and only where that is nearer than half the median
    distance between launches: a program no launch event explains (the GLOBAL
    tick's, or one whose launch began before the trace) pairs with nothing."""
    first = sorted({r[0] for r in device_rows if trace_reduce.DEVICE_PLANE.match(r[0])})[0]
    programs = [r[3] for r in device_rows if r[0] == first and r[1] == trace_reduce.MODULES_LINE
                and not any(x in r[2] for x in exclude)]
    launches = sorted(lo for _, name, lo, _hi in thread_rows if name == LAUNCH)
    if not programs or len(launches) < 2:
        return None
    near = statistics.median(b - a for a, b in zip(launches, launches[1:])) / 2
    pairs = []
    for p in programs:
        i = bisect.bisect_left(launches, p)
        d = min((p - lo for lo in launches[max(i - 1, 0):i + 1]), key=abs)
        if abs(d) < near:
            pairs.append(d)
    return [min(pairs), statistics.median(pairs), sum(d < 0 for d in pairs), len(pairs)] if pairs else None


def from_rows(gaps: list, thread_rows: list, admits: list, program_rows: list, order: list,
              no_request: list, exclude: list) -> "dict | None":
    """Everything this reader says of one trace, from plain rows (so that a
    test can hold it to the known answer of `data/recorded_edge.json`):
    `attribute`'s seconds under `seconds`, the median of `take_distances_ns`,
    `launch_to_program_ns`, and what `rebuild` found.  None without stamps."""
    if not admits:
        return None
    edge_rows, takes, offset = rebuild(admits)
    distances = take_distances_ns(takes, thread_rows)
    return {
        "seconds": attribute(gaps, thread_rows, order, no_request, edge_rows),
        "takes": len(takes), "intervals": len(edge_rows), "offset_ns": offset,
        "take_distance_ns": [statistics.median(distances), len(distances)] if distances else None,
        "launch_to_program_ns": launch_to_program_ns(program_rows, thread_rows, exclude),
    }


def by_edge(ctx, params):
    """`from_rows`' `seconds` for this run, computed (and printed) once; None
    as in the module's text."""
    if "_idle_by_edge" in ctx:
        return ctx["_idle_by_edge"]
    waterfall = ctx["after"]["latency"].get("waterfall")
    out = None
    if waterfall:
        path = ctx["trace"]["xplane"]
        admits = load_stamps(path)
        if admits:
            order = [row["phase"] for row in waterfall]
            device_rows = trace_reduce.load_xplane(path, cpu_stand_in=ctx["device"]["platform"] == "cpu")
            got = from_rows(idle_gaps(device_rows), load_threads(path, set(order)), admits, device_rows,
                            order, params["no_request"], params.get("exclude", ()))
            out = got["seconds"]
            takes, launch = got["take_distance_ns"], got["launch_to_program_ns"]
            print("  idle seconds of the first device by edge phase: " + json.dumps(
                {k: round(v, 4) for k, v in out.items()}), flush=True)
            print(f"  edge stamps on the trace's clock: {got['takes']} takes, {got['intervals']} intervals, "
                  f"offset {got['offset_ns']:.0f} ns; rebuilt take time to the end of its pump.take event: "
                  + ("no pair" if takes is None else f"median {takes[0] / 1e6:.4f} ms over {takes[1]} takes")
                  + "; dispatch.launch start to its device program's start: "
                  + ("no pair" if launch is None else
                     f"smallest {launch[0] / 1e6:.4f} ms, median {launch[1] / 1e6:.4f} ms, {launch[2]} of "
                     f"{launch[3]} programs below zero"), flush=True)
    ctx["_idle_by_edge"] = out
    return out


def read(ctx, params):
    seconds = by_edge(ctx, params)
    if seconds is None or seconds["idle"] <= 0:
        return None
    return 100.0 * seconds["edge_io"] / seconds["idle"]
