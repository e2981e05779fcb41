"""The client's mean round trip less what the daemon's top-level phases add
up to, per request: the milliseconds of a request that no phase names.

Top-level is depth 0 of the `waterfall` that `/debug/latency` serves (a
nested phase's time is already inside its parent's), less `off_request`:
phases under way while no request waits on them, and whole-request sums.
None where the daemon serves no `waterfall`.  Only sound where one request
is one dispatch and one is in flight (`v5e1-1m.frames`): phases observed per
dispatch are then per request, and none overlap."""

from .phase_ms_per import _grown


def top_level_phases(ctx, params):
    waterfall = ctx["after"]["latency"].get("waterfall")
    if not waterfall:
        return None
    off = set(params.get("off_request", ()))
    return [row["phase"] for row in waterfall if row["depth"] == 0 and row["phase"] not in off]


def read(ctx, params):
    phases = top_level_phases(ctx, params)
    lat = ctx["window_latencies_ms"]
    if phases is None or not lat or ctx["requests"] <= 0:
        return None
    return sum(lat) / len(lat) - _grown(ctx, phases, "sum_ms") / ctx["requests"]
