"""Device milliseconds of a compiled step under the program's own
scope names (`lib/scopes.py` says what carries them).

Over the executions of the programs matching `program_match` that lie
whole inside the traced slice: the device seconds of the "XLA Ops"
events whose scope path's OUTERMOST element of `vocabulary` is one of
`scopes` ("unscoped": no element is); the median over executions, in
ms — or with `share`, that sum over the execution's summed operation
time, in %. `None` where the slice holds no such execution or no
operation of one carries a name of the vocabulary (a program that
names nothing, as a parent commit before the scopes), and on a run
with no device plane.

Also logged, once a run, for PERF.md: the median ms an execution by
scope with the five largest operation kinds beneath each, every
unscoped kind, the operations' sum against the execution's length,
and the same for the programs matching `chunk_match` where the slice
holds one whole.
"""

import statistics
import time

from lib import scopes


def _rows(run, ctx, args):
    """The account of `program_match`, made (and logged, with the
    chunk programs') once a run: the drivers' `run` carries the parsed
    trace and the accounts from one metric's reader to the next."""
    vocabulary = tuple(args["vocabulary"])
    key = (args["program_match"], vocabulary)
    made = run.setdefault("scope_accounts", {})
    if key in made:
        return made[key]
    t0 = time.monotonic()
    if "scope_raw" not in run:
        run["scope_raw"] = scopes.load(ctx.trace_dir(),
                                       run.get("traced_span"))
    for match in (args["program_match"], args.get("chunk_match")):
        if match is None:
            continue
        rows = scopes.account(run["scope_raw"], match, vocabulary)
        made.setdefault(key, rows)  # the first is `program_match`'s
        if rows:
            ctx.log("scope_ms_per_step %r: median ms an execution by scope "
                    "| op kinds beneath it, mean ms an execution\n%s"
                    % (match, "\n".join(scopes.table(rows, vocabulary))))
    ctx.log("scope_ms_per_step: the second parse of the trace and its "
            "account took %.1f s" % (time.monotonic() - t0))
    return made[key]


def read(trace, run, args, ctx):
    if trace is None:
        return None
    rows = _rows(run, ctx, args)
    vocabulary = args["vocabulary"]
    if not rows or not any(s in r["by_scope"] for r in rows
                           for s in vocabulary):
        return None
    sums = scopes.scope_seconds(rows, args["scopes"])
    if args.get("share"):
        return 100.0 * statistics.median(
            s / r["total"] for s, r in zip(sums, rows))
    return 1e3 * statistics.median(sums)
