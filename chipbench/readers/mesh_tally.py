"""Growth of one counter of `/debug/device` `mesh` over the growth of another.
The block is cumulative (every columnar dispatch since the daemon started:
`dispatches`, `lanes`, `paddedLanes`, `fullestShardLanes`, `rounds`, and
`shards`, the mesh's size).  `times` scales the ratio: a number, or "shards".
None where the daemon serves no such block (a program from before it
existed) or the denominator did not grow."""


def read(ctx, params):
    before, after = ctx["before"]["device"].get("mesh"), ctx["after"]["device"].get("mesh")
    if not before or not after:
        return None
    grown = {k: float(after.get(k, 0)) - float(before.get(k, 0))
             for k in (params["numerator"], params["denominator"])}
    if grown[params["denominator"]] <= 0:
        return None
    times = params.get("times", 1)
    if times == "shards":
        times = float(after.get("shards", 0))
    return times * grown[params["numerator"]] / grown[params["denominator"]]
