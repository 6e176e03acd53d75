"""Device time of the matching programs / device busy time."""


def read(trace, run, args, ctx):
    if trace is None:
        return None
    durs = [d for _, d in trace.executions(args["program_match"])]
    if not durs or not trace.busy_s:
        return None
    return 100.0 * sum(durs) / trace.busy_s
