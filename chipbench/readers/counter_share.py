"""Growth of some samples of one Prometheus counter as a share of the growth
of those and others: `params["metric"]` names the counter, `numerator` and
`others` are lists of label fragments (`"frames"` matches `{stat="frames"}`),
`times` scales the share.  None where none of them grew between the two
scrapes (a program from before the counter among them)."""

from ..daemon import metric_sum


def read(ctx, params):
    before, after = ctx["before"]["metrics"], ctx["after"]["metrics"]
    kept, rest = (
        sum(metric_sum(after, params["metric"], f) - metric_sum(before, params["metric"], f)
            for f in params[side])
        for side in ("numerator", "others")
    )
    return params.get("times", 1) * kept / (kept + rest) if kept + rest > 0 else None
