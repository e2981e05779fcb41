"""The least time the chip's memory bandwidth allows for the bytes the traced
dispatches must move, over their kernel time.  Bound by bandwidth, not by
arithmetic: a check is a row gathered, a few integer operations, a row
scattered."""

from .. import roofline
from . import lanes_per_dispatch
from .kernel_us_per_dispatch import bucket_programs


def read(ctx, params):
    if ctx["device"]["platform"] != "tpu":
        return None  # a rehearsal on the CPU backend: no roofline to hold it to
    programs = bucket_programs(ctx, params)
    launches = sum(row[0] for row in programs.values())
    kernel_s = sum(row[1] for row in programs.values())
    if not launches or kernel_s <= 0:
        return None
    # Lanes a launch over the whole run (counters), and a request's distinct
    # keys (the pool): a launch of several requests has at least as many.
    lanes = lanes_per_dispatch.read(ctx, {"programs": ["mesh:dispatch:"]})
    if lanes is None:
        return None
    unique = min(lanes, ctx["unique_keys_per_request"])
    least = roofline.least_seconds(
        ctx["device"]["kind"], roofline.dict_wire_dispatch_bytes(lanes, unique)
    )
    return 100.0 * least * launches / kernel_s
