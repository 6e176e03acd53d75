"""Median device duration of a compiled program's executions, in ms."""

import statistics


def read(trace, run, args, ctx):
    if trace is None:
        return None
    durs = [d for _, d in trace.executions(args["program_match"])]
    if not durs:
        return None
    return 1e3 * statistics.median(durs)
