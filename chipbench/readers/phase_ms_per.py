"""Growth of the summed milliseconds of some `/debug/latency` phases, per
request or per observation of some phases."""


def _phases(snap):
    return snap["latency"].get("phases") or {}


def _grown(ctx, names, field):
    before, after = _phases(ctx["before"]), _phases(ctx["after"])
    return sum(
        float(after.get(p, {}).get(field, 0)) - float(before.get(p, {}).get(field, 0))
        for p in names
    )


def read(ctx, params):
    ms = _grown(ctx, params["phases"], "sum_ms")
    per = ctx["requests"] if params["per"] == "requests" else _grown(ctx, params["count_phases"], "count")
    return ms / per if per > 0 else None
