"""Programs compiled after warm-up, between the two snapshots."""


def read(ctx, params):
    return float(
        int(ctx["after"]["device"].get("steadyRecompiles", 0))
        - int(ctx["before"]["device"].get("steadyRecompiles", 0))
    )
