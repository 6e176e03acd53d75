"""A kernel's share of its roofline over the traced steps, where a
step makes several kinds of call to it.

As `kernel_roofline`, with the cost module and the calls a step taken
from `args`: `<cost>_cost(shape, contexts, block_tokens)` of
`lib/<cost_module>.py` gives, from the contexts the driver logged for
a step, a list of (calls a step, operations, bytes of one call); the
least time of a step is the sum over that list of calls x the larger
of operations / peak and bytes / bandwidth. Device time: the events of
the operations matching `op_match` inside the executions of the
programs matching `program_match`. Steps logged inside the traced
interval and executions in the trace are the same steps, give or take
one on the span's edge; where their numbers differ by more, or the
program has no such kernel (a parent commit without it), nothing is
read.
"""

import importlib

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
    if not events or not steps or \
            abs(len(steps) - len(per_run)) > max(2, len(steps) // 50):
        ctx.log("kernel_roofline_calls: %d logged steps, %d traced "
                "executions with the kernel: nothing read"
                % (len(steps), len(per_run)))
        return None
    cost = getattr(importlib.import_module("lib." + args["cost_module"]),
                   args["cost"] + "_cost")
    least, bound, calls_a_step = 0.0, {}, 0
    for contexts in steps:
        calls_a_step = 0
        for calls, flops, nbytes in cost(run["shape"], contexts,
                                         run["block_tokens"]):
            t, which = costs.roofline_seconds(flops, nbytes, ctx.peaks)
            least += calls * t
            calls_a_step += calls
            bound[which] = bound.get(which, 0) + calls
    least *= len(per_run) / len(steps)
    ctx.log("kernel_roofline_calls(%s): %d steps, %d kernel events (%d "
            "calls a step by the cost), %.6f s on the device, least %.6f s, "
            "bound by %r" % (args["cost"], len(steps), events, calls_a_step,
                             seconds, least, bound))
    return 100.0 * least / seconds
