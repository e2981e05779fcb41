"""Checks answered per device program launched: `/debug/device` counts the
runs of each program label since the /metrics scrape that ended the snapshot
before the ramp."""


def read(ctx, params):
    runs = ctx["after"]["device"].get("programRuns") or {}
    launched = sum(
        int(row.get("count", 0)) for label, row in runs.items()
        if any(label.startswith(p) for p in params["programs"])
    )
    return ctx["checks"] / launched if launched else None
