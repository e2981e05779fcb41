"""Device microseconds of the bucket programs per launch, from the trace's
module line.  Programs whose name holds one of `exclude` (the GLOBAL sync)
are not bucket programs."""


def bucket_programs(ctx, params):
    return {
        name: row for name, row in ctx["trace"]["program"].items()
        if not any(x in name for x in params.get("exclude", ()))
    }


def read(ctx, params):
    programs = bucket_programs(ctx, params)
    launches = sum(row[0] for row in programs.values())
    return 1e6 * sum(row[1] for row in programs.values()) / launches if launches else None
