"""Share of the traced window in which no operation ran on the chip."""


def read(trace, run, args, ctx):
    if trace is None:
        return None
    window = run["traced_window_s"]
    if not window:
        return None
    return 100.0 * (1.0 - trace.busy_s / window)
