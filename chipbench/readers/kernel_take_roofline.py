"""The least time the chip's memory bandwidth allows for the bytes the traced
dispatches must move, over the device time of every dispatch program, fused
ones included: `kernel_roofline` per DISPATCH.  That reader holds ONE
dispatch's bytes against each program LAUNCH, so a fused launch of K
dispatches reads K times too slow there; this one counts the dispatches the
trace's programs carried (`kernel_us_per_take.traced`) and a dispatch's lanes
from the `mesh` block's own counters."""

from .. import roofline
from . import mesh_tally
from .kernel_us_per_take import traced


def read(ctx, params):
    if ctx["device"]["platform"] != "tpu":
        return None  # a rehearsal on the CPU backend: no roofline to hold it to
    got = traced(ctx, params)
    lanes = mesh_tally.read(ctx, {"numerator": "lanes", "denominator": "dispatches"})
    if got is None or got[0] <= 0 or lanes is None:
        return None
    kernel_s, dispatches = got
    # A request's distinct keys (the pool): a dispatch of several requests
    # has at least as many, so the share errs low.
    unique = min(lanes, ctx["unique_keys_per_request"])
    least = roofline.least_seconds(
        ctx["device"]["kind"], roofline.dict_wire_dispatch_bytes(lanes, unique)
    )
    return 100.0 * least * dispatches / kernel_s
