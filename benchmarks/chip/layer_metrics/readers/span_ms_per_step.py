"""Host-span milliseconds per scheduler step, from the program's own
spans in the trace.

Over the whole `root` spans of the slice that hold at least one
`holds` span: the summed length of the `of` spans beneath the root
(the root's own length where `of` is not given), less the summed
length of the `less` spans beneath it; the `stat` (median or mean)
over those steps. `None` where the slice holds no such step — as on a
program that does not annotate its scheduler.

Also logged, for PERF.md: the median milliseconds such a step spends
in each phase beneath the root, and the median share of the number
read that no child span covers (the root's own Python).
"""

import statistics

from lib import spans


def read(trace, run, args, ctx):
    if trace is None:
        return None
    steps = [r for r in spans.find(spans.nest(trace.host_spans),
                                   args["root"])
             if any(n.name == args["holds"] for n in r.walk())]
    if not steps:
        return None
    less, of = args.get("less"), args.get("of")
    values = [(r.inside(of) if of else r.seconds)
              - (r.inside(less) if less else 0.0) for r in steps]
    by_phase = {}
    for i, r in enumerate(steps):
        for n in r.walk():
            by_phase.setdefault(n.name, [0.0] * len(steps))[i] += n.seconds
    ctx.log("span_ms_per_step %r: %d steps; median ms a step by phase %r; "
            "of the number read, the share that is the root's own time "
            "(no child covers it): median %.2f %%"
            % (args, len(steps),
               {k: round(1e3 * statistics.median(v), 4)
                for k, v in sorted(by_phase.items())},
               100.0 * statistics.median(
                   r.self_seconds / v for r, v in zip(steps, values) if v)))
    return 1e3 * getattr(statistics, args["stat"])(values)
