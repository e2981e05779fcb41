"""`mesh_tally` for a counter that a program may lack while it serves the
block: None where the daemon's `mesh` block does not hold the numerator (a
program from before the counter existed), where `mesh_tally` would take the
missing counter for 0 and report a share that nothing measured."""

from . import mesh_tally


def read(ctx, params):
    after = ctx["after"]["device"].get("mesh") or {}
    if params["numerator"] not in after:
        return None
    return mesh_tally.read(ctx, params)
