"""A device trace read by the program's own scope names.

The program under test wraps the parts of its compiled steps in
`jax.named_scope`s; a scope rides an operation's metadata (`op_name`)
into the compiled program and the profiler's trace. On a TPU v5e
under JAX 0.9 (read by hand from a recorded trace, PR 35) the carrier
is a stat of the event's METADATA on the plane "/device:TPU:<i>":
`tf_op`, e.g. "jit(_decode)/lm_attention/dot_general:", which XProf
shows as the framework op name. `jax.profiler.ProfileData` yields an
event's own stats (offset, duration) and not its metadata's, so the
same `.xplane.pb` is parsed here a second time with the generated
protobuf module that ships with tensorflow. A fusion carries ONE
path, its root instruction's; `copy-start` / `copy-done` (parameter
copies, cross-program prefetch) carry none.

Two steps, so that the second can be checked without a chip:

  load(trace_dir, clip_span) -> raw: {"ops": [[start_s, dur_s, name,
      path], ...], "programs": [[start_s, dur_s, name], ...],
      "span": [lo_s, hi_s] or None}: the first chip's "XLA Ops" and
      "XLA Modules" that start inside the host span `clip_span` (as
      `xplane.Trace` clips), seconds from the span's start
  account(raw, program_match, vocabulary) -> per execution of the
      matching programs that lies WHOLE inside the span, its
      operations' device seconds by outermost vocabulary scope
"""

from __future__ import annotations

import importlib.util
import os
import re
import statistics
import sys

from lib.xplane import (DEVICE_PLANE, HOST_PLANE, MODULES_LINE, OPS_LINE,
                        find_xplane, op_kind)

UNSCOPED = "unscoped"
_PB2 = "tensorflow.tsl.profiler.protobuf.xplane_pb2"


def _xplane_pb2():
    """The generated module of the profiler's format, loaded from its
    file: it needs google.protobuf alone, where importing the
    `tensorflow` package around it costs 14-16 s."""
    if _PB2 in sys.modules:
        return sys.modules[_PB2]
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        raise RuntimeError("no tensorflow here: nothing parses a trace's "
                           "event metadata")
    path = os.path.join(list(spec.submodule_search_locations)[0],
                        *(_PB2.split(".")[1:-1] + ["xplane_pb2.py"]))
    spec = importlib.util.spec_from_file_location("chipbench_xplane_pb2",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _events(line, t0_ns):
    """(start, duration) in whole picoseconds from `t0_ns`, and the
    metadata id, of a line's events."""
    base = (line.timestamp_ns - t0_ns) * 1000
    return [(base + e.offset_ps, e.duration_ps, e.metadata_id)
            for e in line.events]


def _scope_paths(plane):
    """metadata id -> the framework op name its `tf_op` stat holds,
    '' where it has none."""
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    paths = {}
    for mid, md in plane.event_metadata.items():
        for st in md.stats:
            if names.get(st.metadata_id) == "tf_op":
                paths[mid] = (st.str_value
                              or names.get(st.ref_value, "")).rstrip(":")
    return paths


def load(trace_dir, clip_span=None):
    pb2 = _xplane_pb2()
    space = pb2.XSpace()
    with open(find_xplane(trace_dir), "rb") as f:
        space.ParseFromString(f.read())
    chips = sorted((int(DEVICE_PLANE.match(p.name).group(1)), i)
                   for i, p in enumerate(space.planes)
                   if DEVICE_PLANE.match(p.name)
                   and any(ln.name == OPS_LINE and ln.events
                           for ln in p.lines))
    if not chips:
        raise RuntimeError("no operation ran on a device in the trace")
    plane = space.planes[chips[0][1]]
    lines = {ln.name: ln for ln in plane.lines}
    t0_ns = lines[OPS_LINE].timestamp_ns
    lo, hi = None, None
    if clip_span is not None:
        inside = []
        for p in space.planes:
            if p.name != HOST_PLANE:
                continue
            ids = {k for k, v in p.event_metadata.items()
                   if v.name == clip_span}
            inside += [(s, s + d) for ln in p.lines
                       for s, d, mid in _events(ln, t0_ns) if mid in ids]
        if not inside:
            raise RuntimeError("no host span %r in the trace" % clip_span)
        lo, hi = min(inside)
    names = {k: v.name for k, v in plane.event_metadata.items()}
    paths = _scope_paths(plane)
    zero = lo if lo is not None else 0

    def clipped(line):
        return sorted((s, d, mid) for s, d, mid in _events(line, t0_ns)
                      if lo is None or lo <= s < hi)

    ps = 1e-12
    return {
        "ops": [[(s - zero) * ps, d * ps, names[mid], paths.get(mid, "")]
                for s, d, mid in clipped(lines[OPS_LINE])],
        "programs": [[(s - zero) * ps, d * ps, names[mid]]
                     for s, d, mid in (clipped(lines[MODULES_LINE])
                                       if MODULES_LINE in lines else ())],
        "span": None if lo is None else [0.0, (hi - lo) * ps],
    }


def outermost(path, vocabulary):
    """The first element of a '/'-separated scope path that is in the
    vocabulary, or "unscoped"."""
    for part in path.split("/"):
        if part in vocabulary:
            return part
    return UNSCOPED


def self_seconds(ops):
    """The durations of `ops` (sorted by start), each less what the
    events nested inside it take: a loop's event spans its body's."""
    own = [op[1] for op in ops]
    open_ = []  # indices of the events that hold the current one
    for i, op in enumerate(ops):
        # an event that starts as another ends (to a nanosecond: the
        # times are floats) follows it and is not inside it
        while open_ and (ops[open_[-1]][0] + ops[open_[-1]][1]
                         <= op[0] + 1e-9):
            open_.pop()
        if open_:
            own[open_[-1]] -= op[1]
        open_.append(i)
    return own


def account(raw, program_match, vocabulary):
    """One row an execution of the matching programs that lies whole
    inside the span: {"start", "duration", "total" (its operations'
    summed device seconds), "by_scope": {scope: seconds}, "by_kind":
    {(scope, op kind): seconds}}."""
    rx = re.compile(program_match)
    span = raw.get("span")
    ops, rows, i = raw["ops"], [], 0
    filed = {}  # (name, path) -> (scope, op kind): a step repeats them
    for s, d, name in raw["programs"]:
        if not rx.search(name):
            continue
        if span is not None and not (span[0] <= s and s + d <= span[1]):
            continue
        while i < len(ops) and ops[i][0] < s:
            i += 1
        j = i
        while j < len(ops) and ops[j][0] < s + d:
            j += 1
        row = {"start": s, "duration": d, "total": 0.0, "by_scope": {},
               "by_kind": {}}
        for op, own in zip(ops[i:j], self_seconds(ops[i:j])):
            kind = filed.get((op[2], op[3]))
            if kind is None:
                kind = filed[op[2], op[3]] = (outermost(op[3], vocabulary),
                                              op_kind(op[2]))
            scope = kind[0]
            row["total"] += own
            row["by_scope"][scope] = row["by_scope"].get(scope, 0.0) + own
            row["by_kind"][kind] = row["by_kind"].get(kind, 0.0) + own
        if j > i:
            rows.append(row)
        i = j
    return rows


def scope_seconds(rows, scopes):
    """Per execution, the device seconds under the given scopes."""
    return [sum(r["by_scope"].get(s, 0.0) for s in scopes) for r in rows]


def table(rows, vocabulary, top=5):
    """For PERF.md: median ms an execution by scope with the largest
    op kinds beneath each (mean ms an execution), every unscoped op
    kind, and the operations' sum against the executions' length."""
    n = len(rows)
    kinds = {}
    for r in rows:
        for (scope, kind), sec in r["by_kind"].items():
            under = kinds.setdefault(scope, {})
            under[kind] = under.get(kind, 0.0) + sec
    out = []
    for scope in list(vocabulary) + [UNSCOPED]:
        if scope not in kinds:
            continue
        ranked = sorted(kinds[scope].items(), key=lambda kv: -kv[1])
        if scope != UNSCOPED:
            ranked = ranked[:top]
        out.append("  %-13s %8.4f ms | %s" % (
            scope,
            1e3 * statistics.median(scope_seconds(rows, [scope])),
            "; ".join("%s %.4f" % (k, 1e3 * sec / n) for k, sec in ranked)))
    total = statistics.median(r["total"] for r in rows)
    length = statistics.median(r["duration"] for r in rows)
    out.append("  %d executions; operations %.4f ms of an execution's "
               "%.4f ms (%.2f %%)" % (n, 1e3 * total, 1e3 * length,
                                      100.0 * total / length))
    return out
