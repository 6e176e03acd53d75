"""Share of the traced window in which no operation ran on the chip
WHILE the host was inside one of the program's `root` spans.

Each idle stretch of the first chip, between the edges of the traced
span, is split over the host spans that overlap it, innermost first
(`lib/spans.py`); what falls beneath a whole `root` span is the
program's, the rest the harness's. The whole table goes to the log:
idle seconds by innermost span beneath the root, `outside_engine` in
all and by the harness's own spans; the idle time of the `wait` spans
(the host blocked on the device) apart where the device had not
started yet and where it had already finished. `None` where the slice
holds no `root` span.
"""

from lib import spans


def read(trace, run, args, ctx):
    if trace is None:
        return None
    window = [(s, e) for s, e, n in trace.host_spans
              if n == run.get("traced_span")]
    forest = spans.nest(trace.host_spans)
    roots = spans.find(forest, args["root"])
    if not window or not roots:
        return None
    lo, hi = window[0]
    gaps = spans.idle_intervals(trace.ops[trace.chips[0]], lo, hi)
    inside, outside = spans.split_idle(gaps, forest, args["root"],
                                       args["wait"])
    idle = sum(e - s for s, e in gaps)
    table = dict(inside, outside_engine=sum(outside.values()))
    ctx.log("engine_idle_share: window %.6f s, %d %s spans among %d host "
            "spans, idle %.6f s in %d gaps; "
            "idle seconds by innermost overlapping span %r; "
            "outside_engine by the harness's spans %r"
            % (hi - lo, len(roots), args["root"], len(trace.host_spans),
               idle, len(gaps),
               {k: round(v, 6) for k, v in
                sorted(table.items(), key=lambda kv: -kv[1])},
               {k: round(v, 6) for k, v in
                sorted(outside.items(), key=lambda kv: -kv[1])}))
    return 100.0 * sum(inside.values()) / (hi - lo)
