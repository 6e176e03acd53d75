"""The routed experts' grouped product's share of its roofline over
the traced decode steps.

Least time: for every decode step the driver logged inside the traced
interval, `<cost>_cost(shape, rows, experts_hit)` of
`lib/<cost_module>.py` gives the operations and bytes of the step's
grouped products from the two numbers the driver logged beside it
(`run["moe_steps"]`: the (token, choice) pairs of the step's live
slots over the expert layers, and the engine's `moe_experts_hit`
counter for the step: the experts REACHED, not the experts held); the
least time of a step is the larger of operations / peak and bytes /
bandwidth. Device time: the events of the operations matching
`op_match` inside the executions of the programs matching
`program_match`. Logged steps and traced executions are the same
steps, give or take one on the span's edge; where their numbers differ
by more, where the program has no such kernel, or where the driver
logged no such steps (a cell of another family, a parent commit),
nothing is read.
"""

import importlib

from lib import costs


def read(trace, run, args, ctx):
    if ctx.peaks is None:
        return None
    if trace is None or not run.get("traced") or not run.get("moe_steps"):
        return None
    t0, t1 = run["traced"]
    seconds, events, per_run = trace.op_seconds(args["op_match"],
                                                args["program_match"])
    steps = [(rows, hit) for t, rows, hit in run["moe_steps"]
             if t0 < t <= t1 and rows]
    if not events or not steps or \
            abs(len(steps) - len(per_run)) > max(2, len(steps) // 50):
        ctx.log("moe_roofline: %d logged steps, %d traced executions with "
                "the kernel: nothing read" % (len(steps), len(per_run)))
        return None
    cost = getattr(importlib.import_module("lib." + args["cost_module"]),
                   args["cost"] + "_cost")
    least, bound = 0.0, {}
    for rows, hit in steps:
        flops, nbytes = cost(run["shape"], rows, hit)
        t, which = costs.roofline_seconds(flops, nbytes, ctx.peaks)
        least += t
        bound[which] = bound.get(which, 0) + 1
    least *= len(per_run) / len(steps)
    ctx.log("moe_roofline(%s): %d steps, %d kernel events, %.6f s on the "
            "device, least %.6f s, bound by %r; experts reached a step "
            "%.1f, pairs a step %.1f"
            % (args["cost"], len(steps), events, seconds, least, bound,
               sum(h for _, h in steps) / len(steps),
               sum(r for r, _ in steps) / len(steps)))
    return 100.0 * least / seconds
