"""A counter the driver read over its scope as (count, sum): the
mean, times `scale`. Nothing where the run has no such counter (a
program without it) or it never counted."""


def read(trace, run, args, ctx):
    count, total = run.get(args["counter"]) or (0, 0.0)
    if not count:
        return None
    return float(args.get("scale", 1.0)) * total / count
