"""`phase_ms_per` over phases that a daemon may not have: None where its
`/debug/latency` has observed none of `phases` (a program from before they
existed), where the plain reader would give a 0 that nothing measured."""

from . import phase_ms_per


def read(ctx, params):
    seen = ctx["after"]["latency"].get("phases") or {}
    if not any(p in seen for p in params["phases"]):
        return None
    return phase_ms_per.read(ctx, params)
