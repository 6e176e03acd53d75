"""Profiler (reference python/paddle/v2/fluid/profiler.py:33 cuda_profiler,
:76 profiler; C++ platform/profiler.cc RecordEvent/EnableProfiler:142,
ParseEvents:198).

Two layers on TPU:

* XLA traces via jax.profiler (TensorBoard/XProf) — the deep-dive path.
* A per-op COST TABLE (reference ParseEvents parity): inside a
  ``with profiler(...)`` block the Executor switches to an interpret-mode
  timed run — each forward op executes eagerly on the device and is
  synchronised + wall-clock timed; a training program's backward+update
  then runs once through the normal fused path (one row) so update
  semantics are unchanged. On exit the sorted table prints and is
  available programmatically via ``last_profile()``.
"""

from __future__ import annotations

import contextlib
import os
import time

import jax

__all__ = [
    "cuda_profiler", "reset_profiler", "profiler", "record_event",
    "get_events", "last_profile", "active_op_collector",
]

_events = []
_last_profile = []
_active_collector = None


class OpCostCollector(object):
    """op type -> (calls, total, min, max) wall-clock seconds."""

    def __init__(self):
        self.rows = {}

    def record(self, op_type: str, seconds: float):
        row = self.rows.get(op_type)
        if row is None:
            self.rows[op_type] = [1, seconds, seconds, seconds]
        else:
            row[0] += 1
            row[1] += seconds
            row[2] = min(row[2], seconds)
            row[3] = max(row[3], seconds)

    def table(self, sorted_key=None):
        """[{Event, Calls, Total, Min, Max, Ave}] in ms, sorted like the
        reference (profiler.py sorted_key in calls/total/max/min/ave)."""
        out = [
            {
                "Event": op,
                "Calls": calls,
                "Total": total * 1e3,
                "Min": mn * 1e3,
                "Max": mx * 1e3,
                "Ave": total / calls * 1e3,
            }
            for op, (calls, total, mn, mx) in self.rows.items()
        ]
        key = {
            "calls": "Calls", "total": "Total", "max": "Max",
            "min": "Min", "ave": "Ave",
        }.get(sorted_key)
        if key:
            out.sort(key=lambda r: r[key], reverse=True)
        return out


def active_op_collector():
    """The executor checks this each run; non-None switches it to the
    interpret-mode timed path."""
    return _active_collector


def last_profile():
    """The table from the most recent profiler() block."""
    return list(_last_profile)


@contextlib.contextmanager
def cuda_profiler(output_file, output_mode=None, config=None):
    """Kept for API parity; records an XLA trace to the given directory."""
    with profiler("All", profile_path=output_file):
        yield


def reset_profiler():
    _events.clear()
    del _last_profile[:]


def _print_table(table, elapsed):
    print("\n------------------------->     Profiling Report     "
          "<-------------------------\n")
    print("Place: TPU    Total time span: %.4fs" % elapsed)
    hdr = "%-32s %8s %12s %12s %12s %12s" % (
        "Event", "Calls", "Total(ms)", "Min(ms)", "Max(ms)", "Ave(ms)")
    print(hdr)
    for r in table:
        print("%-32s %8d %12.4f %12.4f %12.4f %12.4f" % (
            r["Event"][:32], r["Calls"], r["Total"], r["Min"], r["Max"],
            r["Ave"]))


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile"):
    """Reference fluid.profiler.profiler parity: times every executor run
    in the block per-op and prints the sorted cost table on exit."""
    global _active_collector
    if state not in ["CPU", "GPU", "All", "TPU"]:
        raise ValueError("state must be 'CPU', 'GPU', 'TPU' or 'All'")
    if sorted_key not in (None, "default", "calls", "total", "max", "min",
                          "ave"):
        raise ValueError("unsupported sorted_key %r" % sorted_key)
    trace_dir = (
        profile_path if os.path.isdir(profile_path)
        else os.path.dirname(profile_path) or "/tmp"
    )
    started = False
    # XLA trace capture defaults ON, matching the behavior of this API
    # before the per-op table existed (rounds 1-2 always started a
    # trace); PADDLE_TPU_XLA_TRACE=0 opts out for op-table-only CI runs
    if os.environ.get("PADDLE_TPU_XLA_TRACE", "1") != "0":
        try:
            jax.profiler.start_trace(trace_dir)
            started = True
        except Exception:
            pass  # a trace may already be running
    prev = _active_collector
    collector = OpCostCollector()
    _active_collector = collector
    t0 = time.time()
    try:
        yield
    finally:
        elapsed = time.time() - t0
        _active_collector = prev
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
        _events.append(("profiler_span", elapsed))
        table = collector.table(
            sorted_key if sorted_key != "default" else None
        )
        del _last_profile[:]
        _last_profile.extend(table)
        _print_table(table, elapsed)


@contextlib.contextmanager
def record_event(name):
    """RAII timing (reference platform/profiler.h RecordEvent)."""
    t0 = time.time()
    try:
        yield
    finally:
        _events.append((name, time.time() - t0))


def get_events():
    return list(_events)


def device_memory_stats(device=None):
    """Per-device memory counters (bytes_in_use, peak_bytes_in_use,
    bytes_limit, ...) straight from the runtime — the observability the
    reference exposed through its allocator stats
    (memory/detail/buddy_allocator). Returns {} when the backend does
    not report memory (e.g. the CPU test fixture)."""
    import jax

    d = device if device is not None else jax.local_devices()[0]
    stats = getattr(d, "memory_stats", None)
    if stats is None:
        return {}
    try:
        return dict(stats() or {})
    except Exception:
        return {}


__all__.append("device_memory_stats")


# ---------------------------------------------------------------------
# compiled-step per-op profiling (r4): the interpret-mode table above
# times ops EAGERLY; this path reads the truth of the FUSED program —
# every scheduled HLO instruction of the compiled step is attributed
# back to the fluid op that produced it via the `op:<type>` named-scope
# tags lowering stamps into HLO metadata (core/lowering.py run_op), and
# the measured compiled-step wall time is distributed over ops by each
# instruction's roofline time — max(HBM time from operand+output bytes,
# MXU time from conv/dot FLOPs). Backward instructions (op_name carries
# XLA's transpose(...) wrapper) land on "<op>_grad" rows, mirroring the
# reference's per-grad-op rows (platform/profiler.cc:198 ParseEvents).
# ---------------------------------------------------------------------

import re as _re

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}
_SHAPE_RE = _re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_INST_RE = _re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OPNAME_RE = _re.compile(r'op_name="([^"]*)"')
_TAG_RE = _re.compile(r"op:([\w.]+)")


def _shape_bytes(type_str):
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def _shape_elems(type_str):
    """Element count of the FIRST shape in an HLO type string."""
    m = _SHAPE_RE.search(type_str)
    if not m:
        return 0
    n = 1
    if m.group(2):
        for d in m.group(2).split(","):
            n *= int(d)
    return n


# v5e ridge point (peak bf16 flops / HBM bytes per second ~= 197e12 /
# 819e9). Only the RATIO enters the modeled per-op shares below; override
# for other parts.
RIDGE_FLOPS_PER_BYTE = float(
    os.environ.get("PADDLE_TPU_RIDGE_FLOPS_PER_BYTE", "240.5")
)

_WINDOW_RE = _re.compile(r"window=\{([^}]*)\}")
_DIMLABEL_RE = _re.compile(r"dim_labels=([\w?]+_[\w?]+->[\w?]+)")
_LHS_CONTRACT_RE = _re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def _window_fields(window_str):
    """{'size': [..], 'stride': [..], 'pad_lo'/'pad_hi': [..],
    'lhs_dilate'/'rhs_dilate': [..]} from an HLO window attribute body
    ('size=56x56 pad=55_55x55_55 lhs_dilate=2x2 rhs_reversal=1x1')."""
    out = {}
    for field in window_str.split():
        if "=" not in field:
            continue
        k, v = field.split("=", 1)
        parts = v.split("x")
        if k == "pad":
            out["pad_lo"] = [int(p.split("_")[0]) for p in parts]
            out["pad_hi"] = [int(p.split("_")[1]) for p in parts]
        elif k in ("size", "stride", "lhs_dilate", "rhs_dilate"):
            out[k] = [int(p) for p in parts]
    return out


def _conv_valid_taps(out_size, w, stride, pad_lo, pad_hi, lhs_dil, rhs_dil):
    """Sum over output positions of IN-BOUNDS, non-dilation-zero kernel
    taps along one spatial dim — the real MAC count per (batch, feature,
    contracted-channel) triple, matching XLA's cost analysis: a backward
    conv with a 56x56 window and pad=55 mostly multiplies padding and
    would otherwise be overcounted ~8x."""
    win_dil = (w - 1) * rhs_dil + 1
    base_dil = (out_size - 1) * stride + win_dil - pad_lo - pad_hi
    total = 0
    for o in range(out_size):
        start = o * stride - pad_lo
        for k in range(w):
            loc = start + k * rhs_dil
            if 0 <= loc < base_dil and loc % lhs_dil == 0:
                total += 1
    return total


def _instr_flops(name, rest, types):
    """Estimated FLOPs of one HLO instruction (convolution/dot; 0 for
    everything else — elementwise flops are noise next to HBM traffic).
    `types` is the enclosing computation's {instr: result type} table
    (operands are referenced by name, their shapes live there).

    convolution: 2 * non-spatial out elems * valid window taps *
    per-group contracted input-feature dim (read off the rhs operand
    shape via dim_labels — works for forward, grad-input (dilated) and
    grad-filter convs alike).
    dot: 2 * out_elems * prod(lhs contracting dim sizes)."""
    if " convolution(" in rest or rest.startswith("convolution("):
        dl = _DIMLABEL_RE.search(rest)
        wm = _WINDOW_RE.search(rest)
        sm_out = _SHAPE_RE.search(rest.split(" ")[0])
        if not (dl and sm_out and sm_out.group(2)):
            return 0.0
        out_dims = [int(d) for d in sm_out.group(2).split(",")]
        out_labels = dl.group(1).split("->")[1]
        spatial_pos = [i for i, c in enumerate(out_labels) if c.isdigit()]
        nonspatial = 1
        for i, d in enumerate(out_dims):
            if i not in spatial_pos:
                nonspatial *= d
        w = _window_fields(wm.group(1)) if wm else {}
        sizes = w.get("size", [1] * len(spatial_pos))
        strides = w.get("stride", [1] * len(sizes))
        pad_lo = w.get("pad_lo", [0] * len(sizes))
        pad_hi = w.get("pad_hi", [0] * len(sizes))
        lhs_dil = w.get("lhs_dilate", [1] * len(sizes))
        rhs_dil = w.get("rhs_dilate", [1] * len(sizes))
        taps = 1.0
        for j, pos in enumerate(spatial_pos):
            if j >= len(sizes):
                break
            taps *= _conv_valid_taps(
                out_dims[pos], sizes[j], strides[j], pad_lo[j], pad_hi[j],
                lhs_dil[j], rhs_dil[j],
            )
        contracted = 1
        ops = _re.findall(r"%([\w.\-]+)", rest.split("(", 1)[1])
        if len(ops) >= 2 and ops[1] in types:
            rhs_labels = dl.group(1).split("_")[1].split("->")[0]
            i_pos = rhs_labels.find("i")
            sm = _SHAPE_RE.search(types[ops[1]])
            if i_pos >= 0 and sm and sm.group(2):
                dims = [int(d) for d in sm.group(2).split(",")]
                if i_pos < len(dims):
                    contracted = dims[i_pos]
        return 2.0 * nonspatial * taps * contracted
    if " dot(" in rest or rest.startswith("dot("):
        out_elems = _shape_elems(rest.split(" ")[0])
        contracted = 1
        cm = _LHS_CONTRACT_RE.search(rest)
        ops = _re.findall(r"%([\w.\-]+)", rest.split("(", 1)[1])
        if cm and ops and ops[0] in types:
            sm = _SHAPE_RE.search(types[ops[0]])
            if sm and sm.group(2):
                dims = [int(d) for d in sm.group(2).split(",")]
                for ix in (int(x) for x in cm.group(1).split(",") if x):
                    if ix < len(dims):
                        contracted *= dims[ix]
        return 2.0 * out_elems * contracted
    return 0.0


def _computation_flops(hlo_text):
    """{computation_name: total conv/dot FLOPs} over every non-entry
    computation — so an entry `fusion(...) calls=%comp` instruction can
    be charged for the matmul work hidden inside its fused computation
    (transformer steps fuse dots; ResNet convs stay at entry level)."""
    comps = {}
    cur, types, lines = None, {}, []
    for line in hlo_text.splitlines():
        if (not line.startswith(" ") and line.rstrip().endswith("{")
                and "=" not in line.split("{")[0]):
            if line.lstrip().startswith("ENTRY"):
                # entry instructions are walked by parse_hlo_op_costs
                # itself; parsing them here would double the flops work
                cur = None
                continue
            nm = _re.match(r"\s*%?([\w.\-]+)", line)
            cur = nm.group(1) if nm else None
            types, lines = {}, []
            if cur:
                comps[cur] = {"types": types, "lines": lines}
            continue
        if cur and line.startswith(" "):
            im = _INST_RE.match(line)
            if im:
                types[im.group(1)] = im.group(2).split(" ")[0]
                lines.append((im.group(1), im.group(2)))
    out = {}
    for cname, c in comps.items():
        fl = 0.0
        for name, rest in c["lines"]:
            fl += _instr_flops(name, rest, c["types"])
        if fl:
            out[cname] = fl
    return out


def _entry_lines(hlo_text):
    """The ENTRY computation's lines only — a computation printed AFTER
    the entry must never leak rows."""
    lines = []
    in_entry = False
    depth = 0
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            in_entry = True
            depth = line.count("{") - line.count("}")
            continue
        if in_entry:
            depth += line.count("{") - line.count("}")
            if depth <= 0:
                break
            lines.append(line)
    return lines


def _line_tag(line):
    """Op provenance tag of one HLO line ('[xla]' when untagged);
    backward instructions (op_name carries XLA's transpose(...) wrapper)
    land on '<op>_grad' rows."""
    onm = _OPNAME_RE.search(line)
    if onm:
        t = _TAG_RE.search(onm.group(1))
        if t:
            tag = t.group(1)
            if "transpose(" in onm.group(1):
                tag += "_grad"  # cotangent-pass instruction
            return tag
    return "[xla]"


_CALLS_RE = _re.compile(r"calls=%?([\w.\-]+)")
_OPCODE_RE = _re.compile(r"\b([a-z][a-z0-9\-]*)\(")

# Overlapped memory-movement / bookkeeping instructions: XLA hides them
# behind compute (async weight-prefetch slices, aliasing bitcasts), so
# they carry bytes but ~zero serial time — billing them serially made
# the '[xla]' row claim far more of the modeled step than an earlier
# round's on-chip trace gave it. Synchronous VMEM staging
# `copy`/`copy-done` instructions are NOT here: that trace showed they
# DO serialize; `copy-start` alone stays free so the start/done pair is
# billed once.
_OVERLAPPED_OPCODES = {
    "copy-start", "async-start", "async-done",
    "slice-start", "slice-done", "bitcast", "bitcast-convert",
}


def _opcode(rest):
    """HLO opcode of an instruction body ('bf16[...]{...} fusion(%a)' ->
    'fusion'). Tuple-typed async instructions bury the opcode mid-line;
    the first lowercase identifier followed by '(' is it (dtype tokens
    carry digits/brackets, layout T()/S() tokens are uppercase)."""
    m = _OPCODE_RE.search(rest)
    return m.group(1) if m else ""


def parse_hlo_op_costs(hlo_text):
    """{op_row: {'instructions': n, 'bytes': b, 'flops': f, 'teq': t}}
    from scheduled HLO text. Only the ENTRY computation's instructions
    count (fusions are single scheduled instructions; their internals are
    not separately scheduled) — but conv/dot FLOPs hiding inside a fused
    computation are charged to the entry `fusion` instruction that
    `calls=` it (XLA:TPU fuses BN stats into convs, dots into transformer
    blocks). Instructions with no op tag pool under '[xla]'.

    'teq' is the roofline time proxy in byte-equivalents:
    max(bytes, flops / RIDGE_FLOPS_PER_BYTE) — a compute-bound conv is
    weighted by MXU time, a bandwidth-bound fusion by HBM time. Shares
    of `teq` are the modeled per-op time split."""
    entry_lines = _entry_lines(hlo_text)
    comp_flops = _computation_flops(hlo_text)

    # symbol table: instruction name -> result type string
    types = {}
    for line in entry_lines:
        m = _INST_RE.match(line)
        if m:
            types[m.group(1)] = m.group(2).split(" ")[0]

    rows = {}
    for line in entry_lines:
        m = _INST_RE.match(line)
        if not m:
            continue
        name, rest = m.groups()
        opcode = _opcode(rest)
        if opcode in ("parameter", "constant", "tuple", "get-tuple-element"):
            continue
        byts = _shape_bytes(types.get(name, ""))
        for ref in _re.findall(r"%([\w.\-]+)", rest):
            if ref in types and ref != name:
                byts += _shape_bytes(types[ref])
        flops = _instr_flops(name, rest, types)
        if opcode == "fusion":
            cm = _CALLS_RE.search(rest)
            if cm:
                flops += comp_flops.get(cm.group(1), 0.0)
        overlapped = opcode in _OVERLAPPED_OPCODES or (
            opcode == "custom-call"
            and ("Bitcast" in rest or "Sharding" in rest)
        )
        row = rows.setdefault(
            _line_tag(line), {"instructions": 0, "bytes": 0, "flops": 0.0,
                              "teq": 0.0}
        )
        row["instructions"] += 1
        row["bytes"] += byts
        row["flops"] += flops
        if not overlapped:
            row["teq"] += max(byts, flops / RIDGE_FLOPS_PER_BYTE)
    return rows


def compiled_profile(exe, program, feed, fetch_list, runs=3,
                     sorted_key="total"):
    """Per-op cost table of the COMPILED training step.

    Runs the program once to compile (and prime the executor cache),
    re-lowers the cached signature to read the scheduled HLO, times
    `runs` steps wall-clock, and splits the measured per-step time over
    op rows by attributed memory traffic. Returns (table, meta) where
    table rows follow OpCostCollector.table() ({'Event', 'Calls',
    'Total', ...} — Total in ms) and meta carries the raw bytes and the
    XLA cost-analysis flops for the step."""
    import numpy as _np

    exe._capture_avals = True
    try:
        exe.run(program, feed=feed, fetch_list=fetch_list)
        entry, avals, host_args = exe._last_exec
    finally:
        exe._capture_avals = False
        # the host snapshot is a full copy of every param: don't park it
        # on the executor past this call
        exe._last_exec = None
    lowered = entry.lower(*avals)
    compiled = lowered.compile()
    rows = parse_hlo_op_costs(compiled.as_text())

    # pure device time: fresh device args per run (the entry donates its
    # buffers), timed around the cached jitted entry with
    # block_until_ready — host feed upload / numpy fetch conversion stay
    # OUT of the op rows (ADVICE r4, profiler.py:309). Bare device_put
    # would fight a mesh-jitted entry's in_shardings, so sharded
    # executors fall back to end-to-end timing.
    dev_s = None
    if exe._resolve_mesh() is None:
        dev_s = 0.0
        for _ in range(runs):
            dev_args = jax.tree_util.tree_map(
                lambda a: jax.device_put(a) if hasattr(a, "shape") else a,
                host_args,
            )
            jax.block_until_ready(dev_args)
            t0 = time.time()
            out_dev = entry(*dev_args)
            jax.block_until_ready(out_dev)
            dev_s += time.time() - t0
        dev_s /= runs

    # end-to-end wall time (host feed + fetch included) for the meta row
    t0 = time.time()
    for _ in range(runs):
        out = exe.run(program, feed=feed, fetch_list=fetch_list)
    _np.asarray(out[0])  # sync
    e2e_s = (time.time() - t0) / runs
    step_s = dev_s if dev_s is not None else e2e_s

    # roofline-time split: each row's share is max(HBM time, MXU time) in
    # byte-equivalents (teq) — a bytes-only split under-weights the
    # compute-bound backward convs
    total_teq = sum(r["teq"] for r in rows.values()) or 1
    table = [
        {
            "Event": tag,
            "Calls": r["instructions"],
            "Total": step_s * 1e3 * r["teq"] / total_teq,
            "Min": 0.0,
            "Max": 0.0,
            "Ave": step_s * 1e3 * r["teq"] / total_teq
            / max(r["instructions"], 1),
            "Bytes": r["bytes"],
            "Flops": r["flops"],
        }
        for tag, r in rows.items()
    ]
    key = {"calls": "Calls", "total": "Total", "ave": "Ave"}.get(
        sorted_key, "Total"
    )
    table.sort(key=lambda r: r[key], reverse=True)
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    meta = {
        # device-only when timing_mode == "device"; end-to-end otherwise
        "step_seconds": step_s,
        "e2e_seconds": e2e_s,         # exe.run incl. host feed/fetch
        "host_overhead_seconds": (
            max(e2e_s - step_s, 0.0) if dev_s is not None else None
        ),
        "timing_mode": "device" if dev_s is not None else "e2e",
        "flops": float((ca or {}).get("flops", 0.0)),
        "bytes_attributed": sum(r["bytes"] for r in rows.values()),
        "teq_attributed": total_teq,
    }
    _print_table(table, step_s * runs)
    return table, meta


__all__ += ["compiled_profile", "parse_hlo_op_costs"]


def parse_hlo_instr_tags(hlo_text):
    """{instruction_name: op_tag} over the ENTRY computation — the join
    key between a device profiler trace (events named per HLO
    instruction) and the lowering's op provenance metadata. Shares the
    entry walk and tag extraction with parse_hlo_op_costs so the
    modeled and measured tables can never disagree about ownership."""
    tags = {}
    for line in _entry_lines(hlo_text):
        m = _INST_RE.match(line)
        if m:
            tags[m.group(1)] = _line_tag(line)
    return tags


def _parse_trace_durations(trace_dir):
    """Per-plane sums of per-event durations (us) from a
    jax.profiler.trace output directory: {pid: {event_name: us}}. Events
    carry the HLO instruction name verbatim ('fusion.123',
    'dot_general.1') on the device plane; host planes carry Python /
    runtime spans that must never pollute the device accounting — the
    caller picks the plane that actually holds the compiled step's
    instructions."""
    import glob
    import gzip
    import json as _json

    planes = {}
    for p in glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True
    ):
        tr = _json.loads(gzip.open(p).read())
        for e in tr.get("traceEvents", []):
            if e.get("ph") != "X" or "dur" not in e:
                continue
            name = e.get("name", "")
            if name.startswith("end: "):
                continue
            durs = planes.setdefault(e.get("pid", 0), {})
            durs[name] = durs.get(name, 0.0) + float(e["dur"])
    return planes


def trace_profile(exe, program, feed, fetch_list, runs=3):
    """Reconcile the traffic-MODELED per-op attribution against
    MEASURED per-instruction device times from a real `jax.profiler`
    trace (r4 verdict #4; the reference measured per-op times with CUDA
    events, platform/profiler.cc:142,198 — this is the TPU equivalent:
    XLA instruction events joined back to op provenance through the HLO
    metadata tags lowering stamps).

    Returns (table, meta): rows {'Event', 'measured_ms',
    'modeled_ms', 'disagreement'} sorted by measured time;
    meta['top5_max_disagreement'] is the reconciliation verdict — the
    share-of-step disagreement between the two attributions over the
    five biggest measured rows. Works on any backend with profiler
    support (CPU validates the machinery; TPU gives real device
    times)."""
    import tempfile

    import jax
    import numpy as _np

    exe._capture_avals = True
    try:
        exe.run(program, feed=feed, fetch_list=fetch_list)
        entry, avals, host_args = exe._last_exec
    finally:
        exe._capture_avals = False
        exe._last_exec = None
    compiled = entry.lower(*avals).compile()
    txt = compiled.as_text()
    tags = parse_hlo_instr_tags(txt)
    model_rows = parse_hlo_op_costs(txt)

    import shutil

    trace_dir = tempfile.mkdtemp(prefix="ptpu_trace_")
    try:
        with jax.profiler.trace(trace_dir):
            for _ in range(runs):
                out = exe.run(program, feed=feed, fetch_list=fetch_list)
            _np.asarray(out[0])  # sync inside the trace window
        planes = _parse_trace_durations(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    # join: instruction event -> op tag, on the DEVICE plane only. The
    # trace holds one plane per pid — host Python/runtime threads, the
    # dispatch queue, and the device's instruction track. Joining every
    # plane inflated unmatched_ms ~100x (host spans nest device events;
    # r5 on-chip capture). The device plane is identified, not assumed:
    # the pid whose events best match the entry's instruction names.
    # module-level / bookkeeping spans on the device plane (the whole
    # 'jit_step(...)' execution span, numeric queue ids) nest the
    # instruction events — counting them as unmatched instruction time
    # double-bills the entire step
    _instr_name = _re.compile(r"^[a-z][\w.\-]*$")

    def _match(durs):
        meas, unmatched = {}, 0.0
        for name, us in durs.items():
            tag = tags.get(name)
            if tag is None:
                tag = tags.get(name.split(" ")[0])
            if tag is None:
                base = name.split(" ")[0]
                if _instr_name.match(base) and not base.startswith("jit_"):
                    unmatched += us
                continue
            meas[tag] = meas.get(tag, 0.0) + us
        return meas, unmatched

    best = ({}, 0.0)
    for durs in planes.values():
        cand = _match(durs)
        if sum(cand[0].values()) > sum(best[0].values()):
            best = cand
    measured, unmatched_us = best
    if not measured:
        # no plane matched a single instruction tag (renamed events,
        # empty trace): surface the largest instruction-like residue
        # instead of reporting a silently-clean 0.0 join
        unmatched_us = max(
            (_match(d)[1] for d in planes.values()), default=0.0
        )
    total_meas = sum(measured.values()) or 1.0
    total_teq = sum(r["teq"] for r in model_rows.values()) or 1

    table = []
    for tag in sorted(set(measured) | set(model_rows)):
        m_us = measured.get(tag, 0.0)
        t = model_rows.get(tag, {}).get("teq", 0)
        meas_share = m_us / total_meas
        model_share = t / total_teq
        table.append({
            "Event": tag,
            "measured_ms": round(m_us / 1e3 / runs, 4),
            "measured_share": round(meas_share, 4),
            "modeled_share": round(model_share, 4),
            "disagreement": round(abs(meas_share - model_share), 4),
        })
    table.sort(key=lambda r: -r["measured_ms"])
    top5 = table[:5]
    meta = {
        "runs": runs,
        "measured_total_ms": round(total_meas / 1e3 / runs, 3),
        # leftover time on the DEVICE plane only (infeed, runtime ops)
        "unmatched_ms": round(unmatched_us / 1e3 / runs, 3),
        "top5_max_disagreement": max(
            (r["disagreement"] for r in top5), default=0.0
        ),
        "backend": jax.default_backend(),
    }
    return table, meta


__all__ += ["trace_profile", "parse_hlo_instr_tags"]
