"""Seconds the daemon's start spent on its device programs before it said
`listening`: per program label the cache load, the compile and the first
runs that `/debug/device` sums under `startup`.  Also prints the whole
`startup` document (seconds by part and by program) on a line of its own.
None where the daemon serves no `startup`."""

import json


def read(ctx, params):
    startup = ctx["after"]["device"].get("startup")
    if not startup or not startup.get("programs"):
        return None
    print("  daemon start by part and by program: " + json.dumps(startup), flush=True)
    return float(sum(row.get(field, 0.0) for row in startup["programs"].values()
                     for field in params["fields"]))
