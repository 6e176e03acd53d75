"""From the profiler's `.xplane.pb` to what the per-layer readers read.

Two steps, so that the second can be checked without a chip:

  load_xplane(path) -> raw: {"lines": {"<plane>|<line>": [[name, start_s, dur_s], ...]}}
  Trace(raw, chips)  -> busy seconds, device ops, program executions,
                        host spans, idle gaps by what the host was doing

What the trace looks like on a TPU v5e under JAX 0.9 (read by hand from
a recorded one): each chip is a plane "/device:TPU:<i>" whose line
"XLA Modules" holds one event per execution of a compiled program
("jit__decode(<fingerprint>)") and whose line "XLA Ops" holds the
operations, named by their HLO text ("%fusion.12 = bf16[...] fusion(");
"Async XLA Ops" are DMAs that overlap them and are not counted as busy
time. Host threads are lines of the plane "/host:CPU"; annotations
made with `jax.profiler.TraceAnnotation` appear there under their own
names, on the same clock as the device lines.

    python3 benchmarks/chip/lib/xplane.py <in.xplane.pb> <out.json.gz> <seconds>
writes the first `seconds` of a trace in the raw form (for selfcheck/).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib.stats import union_seconds  # noqa: E402

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# the benchmark's own host spans: "sched.step", "loadgen.wait_due", ...
OWN_SPAN = re.compile(r"^[a-z_]+\.[a-z_.]+$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def load_xplane(path):
    """Every line of every device plane, and the host lines that carry
    at least one of the benchmark's own spans."""
    from jax.profiler import ProfileData

    lines = {}
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = [[e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                   for e in line.events]
            if not device:
                evs = [e for e in evs if OWN_SPAN.match(e[0])]
            if evs:
                lines["%s|%s" % (plane.name, line.name)] = evs
    return {"lines": lines}


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError("the profiler wrote no .xplane.pb under %s"
                           % trace_dir)
    return paths[-1]


def op_kind(hlo_text):
    """'%x.1 = bf16[8,128]{1,0:T(8,128)} fusion(...)' -> 'fusion bf16[8,128]'"""
    _, _, rest = hlo_text.partition(" = ")
    if not rest:
        return hlo_text[:80]
    shape = rest.split(" ", 1)[0].split("{", 1)[0]
    m = _OPCODE.search(rest)
    return "%s %s" % (m.group(1) if m else "op", shape[:60])


class Trace(object):
    def __init__(self, raw, chips, clip_span=None):
        """`clip_span`: keep only what starts inside the (first) host
        span of that name; its length is then `window_s`."""
        self.ops, self.programs = {}, {}
        self.host_spans = []
        self.window_s = None
        if clip_span is not None:
            inside = [(s, s + d) for k, evs in raw["lines"].items()
                      if k.startswith(HOST_PLANE) for n, s, d in evs
                      if n == clip_span]
            if not inside:
                raise RuntimeError("no host span %r in the trace" % clip_span)
            lo, hi = min(inside)
            self.window_s = hi - lo
            raw = {"lines": {k: [e for e in evs if lo <= e[1] < hi]
                             for k, evs in raw["lines"].items()}}
        for key, evs in raw["lines"].items():
            plane, _, line = key.partition("|")
            device = DEVICE_PLANE.match(plane)
            if device:
                chip = int(device.group(1))
                into = self.ops if line == OPS_LINE else self.programs
                into[chip] = sorted((s, d, n) for n, s, d in evs)
            else:
                self.host_spans.extend((s, s + d, n) for n, s, d in evs)
        self.host_spans.sort()
        self.chips = sorted(self.ops)[:chips]
        if not self.chips:
            raise RuntimeError("no operation ran on a device in the trace")
        busy = [union_seconds((s, s + d) for s, d, _ in self.ops[c])
                for c in self.chips]
        self.busy_s = sum(busy) / len(busy)

    # --- programs and their operations ------------------------------------
    def executions(self, program_match, chip=None):
        """(start, duration) of each execution of the programs whose
        name matches, on the first chip."""
        rx = re.compile(program_match)
        chip = self.chips[0] if chip is None else chip
        return [(s, d) for s, d, n in self.programs.get(chip, ())
                if rx.search(n)]

    def op_seconds(self, op_match, program_match=None, chip=None):
        """Device seconds of the operations whose HLO text matches,
        inside executions of the matching programs if given
        -> (seconds, events, [(program start, seconds in it), ...])."""
        rx = re.compile(op_match)
        chip = self.chips[0] if chip is None else chip
        runs = (self.executions(program_match, chip)
                if program_match else None)
        total, count, per_run, i = 0.0, 0, {}, 0
        for s, d, n in self.ops[chip]:
            if not rx.search(n):
                continue
            if runs is not None:
                while i < len(runs) and runs[i][0] + runs[i][1] < s:
                    i += 1
                if i == len(runs) or runs[i][0] > s:
                    continue
                per_run[runs[i][0]] = per_run.get(runs[i][0], 0.0) + d
            total += d
            count += 1
        return total, count, sorted(per_run.items())

    # --- the breakdown ----------------------------------------------------
    def top_ops(self, n=10):
        tot = {}
        for s, d, name in self.ops[self.chips[0]]:
            k = op_kind(name)
            a = tot.setdefault(k, [0, 0.0])
            a[0] += 1
            a[1] += d
        top = sorted(tot.items(), key=lambda kv: -kv[1][1])[:n]
        return [["%s x%d" % (k, c), t] for k, (c, t) in top]

    def idle_gaps(self, n=10):
        """Idle seconds on the first chip between its first and last
        operation: gaps inside a program's execution under
        "inside_program", the others by the benchmark's innermost host
        span open when the gap began."""
        chip = self.chips[0]
        iv = sorted((s, s + d) for s, d, _ in self.ops[chip])
        runs = [(s, s + d) for s, d, _ in self.programs.get(chip, ())]
        tot, end, i = {}, None, 0
        for s, e in iv:
            if end is not None and s > end:
                while i < len(runs) and runs[i][1] <= end:
                    i += 1
                inside = i < len(runs) and runs[i][0] <= end and s <= runs[i][1]
                name = "inside_program" if inside else self._span_at(end)
                tot[name] = tot.get(name, 0.0) + (s - end)
            end = e if end is None else max(end, e)
        return [[k, t] for k, t in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def _span_at(self, t):
        best = None
        for s, e, name in self.host_spans:
            if s > t:
                break
            if e >= t and (best is None or e - s < best[0]):
                best = (e - s, name)
        return best[1] if best else "no_span_open"

    def breakdown(self):
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def reduce(trace_dir, chips, clip_span=None):
    return Trace(load_xplane(find_xplane(trace_dir)), chips, clip_span)


def dump_raw(raw, path, seconds=None):
    """The raw form as gzip JSON; with `seconds`, only what starts in
    the first `seconds` after the first device event."""
    if seconds is not None:
        t0 = min(e[1] for k, evs in raw["lines"].items()
                 if DEVICE_PLANE.match(k.partition("|")[0]) for e in evs)
        raw = {"lines": {k: [e for e in evs if t0 <= e[1] < t0 + seconds]
                         for k, evs in raw["lines"].items()}}
    with gzip.open(path, "wt") as f:
        json.dump(raw, f, separators=(",", ":"))


def load_raw(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


if __name__ == "__main__":
    dump_raw(load_xplane(sys.argv[1]), sys.argv[2], float(sys.argv[3]))
