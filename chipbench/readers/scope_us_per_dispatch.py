"""Device microseconds under one `jax.named_scope` of the bucket programs per
launch: the union of the first device's operations whose `op_name` holds the
scope (so an operation and the operations nested under it count once), inside
the runs of the bucket programs, over those runs.

The scope rides an operation's metadata into the compiled program.  The TPU's
trace keeps it as the `tf_op` stat of the operation's EVENT METADATA, which
`jax.profiler.ProfileData` does not show, so this module reads the few fields
it needs from the `.xplane.pb` itself (protobuf wire format; field numbers of
tsl/profiler/protobuf/xplane.proto).

The compile cache's key leaves debug metadata out, so an executable cached
before the scopes existed still loads, without them: a check that runs the
parent commit first on a cold cache leaves such executables for the change.
Where no operation of the trace carries the scope, `params["unscoped"]` (a
substring of the `op_name` the same operations have without the scope: for
the rounds loop, everything of a `while`) finds them instead, and the run
says so on a line of its own.  None where neither finds anything: the CPU's
trace, which has no `op_name`."""

from __future__ import annotations

import re

from .. import trace_reduce

OP_NAME_STAT = "tf_op"


def _fields(buf: memoryview):
    """(field number, wire type, value) of one message; a length-delimited
    value is a memoryview of its bytes, a varint an int."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        number, wire = key >> 3, key & 7
        if wire == 0:
            value = shift = 0
            while True:
                b = buf[i]
                i += 1
                value |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
        elif wire == 2:
            size = shift = 0
            while True:
                b = buf[i]
                i += 1
                size |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = buf[i:i + 8]
            i += 8
        elif wire == 5:
            value = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield number, wire, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def first_device_plane(path: str) -> "memoryview | None":
    with open(path, "rb") as f:
        space = memoryview(f.read())
    found = {}
    for number, wire, plane in _fields(space):
        if number == 1 and wire == 2:  # XSpace.planes
            name = next((_text(v) for n, w, v in _fields(plane) if n == 2 and w == 2), "")
            if trace_reduce.DEVICE_PLANE.match(name):
                found[name] = plane
    return found[min(found)] if found else None


def device_events(plane: memoryview) -> dict:
    """{line name: [(name, op_name, start_ns, end_ns)]} of the plane's
    operation and module lines."""
    stat_names, metadata, lines = {}, {}, []
    for number, wire, value in _fields(plane):
        if wire != 2:
            continue
        if number == 5:  # stat_metadata: map<int64, XStatMetadata{id=1, name=2}>
            entry = dict((n, v) for n, _, v in _fields(value))
            row = dict((n, v) for n, _, v in _fields(entry[2]))
            stat_names[entry[1]] = _text(row.get(2, b""))
        elif number == 4:  # event_metadata: map<int64, XEventMetadata{id=1, name=2, stats=5}>
            entry = dict((n, v) for n, _, v in _fields(value))
            metadata[entry[1]] = entry[2]
        elif number == 3:  # lines
            lines.append(value)
    op_stat = {i for i, name in stat_names.items() if name == OP_NAME_STAT}

    def describe(meta: memoryview) -> "tuple[str, str]":
        name, op_name = "", ""
        for n, w, v in _fields(meta):
            if n == 2 and w == 2:
                name = _text(v)
            elif n == 5 and w == 2:  # XStat{metadata_id=1, str_value=5, ref_value=7}
                stat = dict((sn, sv) for sn, _, sv in _fields(v))
                if stat.get(1) in op_stat:
                    op_name = _text(stat[5]) if 5 in stat else stat_names.get(stat.get(7), "")
        return name, op_name

    described = {i: describe(m) for i, m in metadata.items()}
    out = {}
    for line in lines:
        name, t0_ns, events = "", 0, []
        for n, w, v in _fields(line):
            if n == 2 and w == 2:
                name = _text(v)
            elif n == 3 and w == 0:
                t0_ns = v  # XLine.timestamp_ns
            elif n == 4 and w == 2:
                events.append(v)
        if name not in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE):
            continue
        rows = []
        for ev in events:  # XEvent{metadata_id=1, offset_ps=2, duration_ps=3}
            f = dict((n, v) for n, w, v in _fields(ev) if w == 0)
            if f.get(3, 0) > 0:
                start = t0_ns + f.get(2, 0) / 1e3
                rows.append((*described.get(f.get(1), ("", "")), start, start + f[3] / 1e3))
        out[name] = rows
    return out


def bucket_runs(events: dict, exclude) -> list:
    """(lo, hi) of the runs of the programs whose name holds none of `exclude`."""
    return [(lo, hi) for name, _, lo, hi in events.get(trace_reduce.MODULES_LINE, ())
            if not any(x in name for x in exclude)]


def ops_where(events: dict, matches) -> list:
    """(lo, hi) of the operations whose `op_name` satisfies `matches`."""
    return [(lo, hi) for _, op_name, lo, hi in events.get(trace_reduce.OPS_LINE, ()) if matches(op_name)]


def scoped(events: dict, scope: str) -> list:
    """The operations under `scope`: `jit(f)/vmap(rounds)/while/body/...`
    and `jit(f)/rounds/while/...`, not `jit(f)/round/...`."""
    return ops_where(events, re.compile(r"[/(]" + re.escape(scope) + r"(?=[/)]|$)").search)


def under_scope_us(ops: list, runs: list) -> "float | None":
    """Microseconds of the union of `ops` that lie inside `runs`, per run."""
    if not ops or not runs:
        return None
    merged_runs = trace_reduce._union(runs)
    total = 0.0
    j = 0
    for lo, hi in trace_reduce._union(ops):
        while j < len(merged_runs) and merged_runs[j][1] <= lo:
            j += 1
        k = j
        while k < len(merged_runs) and merged_runs[k][0] < hi:
            total += max(0.0, min(hi, merged_runs[k][1]) - max(lo, merged_runs[k][0]))
            k += 1
    return total / 1e3 / len(runs)


def read(ctx, params):
    plane = first_device_plane(ctx["trace"]["xplane"])
    if plane is None:
        return None
    events = device_events(plane)
    ops = scoped(events, params["scope"])
    stale = params.get("unscoped")
    if not ops and stale:  # executables from before the scope existed
        ops = ops_where(events, lambda op_name: stale in op_name)
        if ops:
            print(f"  scope {params['scope']!r}: no operation of the trace carries it (executables from a compile "
                  f"cache filled before the scope existed); read by {stale!r} in op_name", flush=True)
    return under_scope_us(ops, bucket_runs(events, params.get("exclude", ())))
