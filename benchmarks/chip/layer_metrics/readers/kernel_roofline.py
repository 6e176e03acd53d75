"""A kernel's share of its roofline over the traced steps.

Least time: for every traced step of the matching program, the cost
function `<cost>_cost(shape, contexts, block_tokens)` of lib/costs.py
gives the operations and bytes of one call from the contexts the
driver logged for that step; there is one call per layer. Device
time: the kernel's events inside those programs' executions. The
steps the driver logged inside the traced interval and the executions
in the trace are the same steps, give or take one on the span's edge;
where their numbers differ by more the share is not reported.
"""

from lib import costs


def read(trace, run, args, ctx):
    if ctx.peaks is None:
        return None
    if trace is None or not run.get("traced"):
        return None
    t0, t1 = run["traced"]
    seconds, events, per_run = trace.op_seconds(args["op_match"],
                                                args["program_match"])
    steps = [c for t, c, _, _ in run["steps"] if t0 < t <= t1 and c]
    # a step on the edge of the span may be on one side only: up to two
    # (or 2 %) are tolerated, the least time scaled to the executions seen
    if not events or not steps or \
            abs(len(steps) - len(per_run)) > max(2, len(steps) // 50):
        ctx.log("kernel_roofline: %d logged steps, %d traced executions "
                "with the kernel: nothing read" % (len(steps), len(per_run)))
        return None
    shape, layers = run["shape"], run["shape"]["layers"]
    cost = getattr(costs, args["cost"] + "_cost")
    least, bound = 0.0, {}
    for contexts in steps:
        flops, nbytes = cost(shape, contexts, run["block_tokens"])
        t, which = costs.roofline_seconds(flops, nbytes, ctx.peaks)
        least += layers * t
        bound[which] = bound.get(which, 0) + 1
    least *= len(per_run) / len(steps)
    ctx.log("kernel_roofline: %d steps, %d kernel events, %.6f s on the "
            "device, least %.6f s, bound by %r" % (len(steps), events,
                                                  seconds, least, bound))
    return 100.0 * least / seconds
