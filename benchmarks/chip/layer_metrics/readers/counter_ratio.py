"""A counter the driver read over the window as (count, total): the
mean, as a percentage."""


def read(trace, run, args, ctx):
    count, total = run.get(args["counter"], (0, 0.0))
    if not count:
        return None
    return 100.0 * total / count
