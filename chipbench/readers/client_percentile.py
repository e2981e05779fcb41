"""A percentile (nearest rank) of the window's request round trips, as the
client saw them: for a cell where that tail is no end-to-end metric."""

from ..harness import percentile


def read(ctx, params):
    lat = ctx["window_latencies_ms"]  # sorted
    return percentile(lat, params["q"]) if len(lat) >= int(params.get("min_samples", 1)) else None
