"""The readers of the program's own spans, on a hand-made raw trace
whose answers can be worked out on paper (milliseconds below).

The traced span runs 0 -> 100. Two scheduler steps:

  sched.step 1-31 > engine.step 2-30 > engine.decode 3-29 >
      alloc_blocks 3-4, dispatch 5-7, device_wait 7-25, emit 26-29
  sched.step 35-75 > engine.step 36-74 >
      engine.admit 37-38,
      engine.prefill_chunk 38-50 > dispatch 39-41, device_wait 41-49,
      engine.decode 50-73 > dispatch 51-52, device_wait 52-70, emit 70-73
  loadgen.wait_due 80-95

and an orphan: an engine.step that began before the slice (-5 -> 0.9)
is clipped away with its sched.step, its child engine.emit 0.2-0.8 is
not. The device runs 6-24, 40-48 and 48.5-69: idle 0-6, 24-40, 48-48.5
and 69-100, 53.5 in all.
"""

import pytest

from lib import spans, xplane

MS = 1e-3
HOST = [
    ("bench.traced", 0, 100),
    ("sched.step", -6, 7), ("engine.step", -5, 5.9), ("engine.emit", 0.2, 0.6),
    ("sched.step", 1, 30), ("engine.step", 2, 28), ("engine.decode", 3, 26),
    ("engine.alloc_blocks", 3, 1), ("engine.dispatch", 5, 2),
    ("engine.device_wait", 7, 18), ("engine.emit", 26, 3),
    ("sched.step", 35, 40), ("engine.step", 36, 38), ("engine.admit", 37, 1),
    ("engine.prefill_chunk", 38, 12), ("engine.dispatch", 39, 2),
    ("engine.device_wait", 41, 8), ("engine.decode", 50, 23),
    ("engine.dispatch", 51, 1), ("engine.device_wait", 52, 18),
    ("engine.emit", 70, 3),
    ("loadgen.wait_due", 80, 15),
]
OPS = [("%k.1 = bf16[4,2,1,8]{3,2,1,0} custom-call(s32[4] %t)", 6, 18),
       ("%c.1 = bf16[1,2,64,8]{3,2,1,0} custom-call(s32[4] %t)", 40, 8),
       ("%k.1 = bf16[4,2,1,8]{3,2,1,0} custom-call(s32[4] %t)", 48.5, 20.5)]
RUN = {"traced_span": "bench.traced"}


class Ctx(object):
    def __init__(self):
        self.lines = []

    def log(self, *a):
        self.lines.append(" ".join(str(x) for x in a))


def trace_of(host):
    raw = {"lines": {
        "/device:TPU:0|XLA Ops": [[n, s * MS, d * MS] for n, s, d in OPS],
        "/device:TPU:0|XLA Modules": [["jit__decode(1)", 6 * MS, 18 * MS]],
        "/host:CPU|python3": [[n, s * MS, d * MS] for n, s, d in host],
    }}
    return xplane.Trace(raw, 1, clip_span="bench.traced")


def metric(name):
    import run as harness

    spec = harness.load_json("layer_metrics", name + ".json")
    reader = harness.load_module("layer_metrics/readers", spec["reader"])
    return lambda trace, ctx: reader.read(trace, RUN, spec.get("args", {}),
                                          ctx)


def test_host_ms_per_step_is_the_step_less_its_device_waits():
    # step 1: 28 - 18 = 10; step 2: 38 - (8 + 18) = 12; the orphan
    # engine.emit has no engine.step above it and is no step
    ctx = Ctx()
    value = metric("sched_host_ms_per_step")(trace_of(HOST), ctx)
    assert value == pytest.approx(11.0)
    # the log names every phase and the root's own share: step 1 has
    # 2-3 and 29-30 of 10 to itself, step 2 36-37 and 73-74 of 12
    assert "'engine.emit': 3.0" in ctx.lines[-1]
    assert "median 18.33 %" in ctx.lines[-1]


def test_prefill_hold_counts_only_the_steps_with_a_chunk():
    assert metric("prefill_hold_ms_per_step")(trace_of(HOST), Ctx()) \
        == pytest.approx(12.0)


def test_idle_is_split_over_the_spans_that_overlap_it():
    ctx = Ctx()
    value = metric("device_idle_share.engine")(trace_of(HOST), ctx)
    # beneath whole engine.step spans: the step's own time 4, alloc 1,
    # decode's own 2, dispatch 2, device_wait 2.5, emit 6, admit 1,
    # the chunk's own 1 = 19.5 of 100
    assert value == pytest.approx(19.5)
    trace = trace_of(HOST)
    forest = spans.nest(trace.host_spans)
    gaps = spans.idle_intervals(trace.ops[0], 0.0, 100 * MS)
    assert sum(e - s for s, e in gaps) == pytest.approx(53.5 * MS)
    inside, outside = spans.split_idle(gaps, forest, "engine.step",
                                       "engine.device_wait")
    # of the waits' 2.5: the device was idle as none of them began,
    # as all three ended (24-25, 48-49 less the op from 48.5: 0, 69-70)
    # and in between (48-48.5)
    want_in = {"engine.step": 4, "engine.alloc_blocks": 1,
               "engine.decode": 2, "engine.dispatch": 2,
               "engine.device_wait:after_end": 2.0,
               "engine.device_wait": 0.5, "engine.emit": 6,
               "engine.admit": 1, "engine.prefill_chunk": 1}
    # the orphan's 0.6 is the harness's, under its own name
    want_out = {"bench.traced": 14.4, "sched.step": 4, "engine.emit": 0.6,
                "loadgen.wait_due": 15}
    assert {k: pytest.approx(v * MS) for k, v in want_in.items()} == inside
    assert {k: pytest.approx(v * MS) for k, v in want_out.items()} == outside
    # the table sums to the idle time of device_idle_share.serve
    assert sum(inside.values()) + sum(outside.values()) \
        == pytest.approx(100 * MS - trace.busy_s)
    assert "'outside_engine': 0.034" in ctx.lines[-1]
    # the old rule (between the first and the last operation) files
    # all of 24-40 under the span that was open at 24
    assert dict(trace.idle_gaps()) \
        == {"engine.device_wait": pytest.approx(16.5 * MS)}


@pytest.mark.parametrize("name", ["sched_host_ms_per_step",
                                  "device_idle_share.engine",
                                  "prefill_hold_ms_per_step"])
def test_a_slice_without_the_programs_spans_reads_none(name):
    """A program that does not annotate its scheduler (the parent of
    the PR that brought the spans): nothing to read, never 0."""
    bare = [h for h in HOST if not h[0].startswith("engine.")]
    assert metric(name)(trace_of(bare), Ctx()) is None
    assert metric(name)(None, Ctx()) is None  # a CPU rehearsal: no trace


def test_a_step_without_a_chunk_reads_no_prefill_hold():
    only_first = [h for h in HOST if h[1] < 35 or h[0] == "bench.traced"]
    assert metric("prefill_hold_ms_per_step")(trace_of(only_first),
                                              Ctx()) is None
    assert metric("sched_host_ms_per_step")(trace_of(only_first), Ctx()) \
        == pytest.approx(10.0)


def test_a_child_that_ends_with_its_parent_is_its_child():
    # starts and lengths are whole nanoseconds read into floats: the
    # sums may differ in the last bit
    top = spans.nest([(0.1, 0.1 + 0.2, "engine.step"),
                      (0.25, 0.25 + 0.05 + 1e-17, "engine.emit")])
    assert [n.name for n in top] == ["engine.step"]
    assert [c.name for c in top[0].children] == ["engine.emit"]


@pytest.mark.parametrize("span,head,tail,between", [
    ((1, 10), 1, 1, 3),          # idle on entering and on leaving
    ((5.5, 5.8), 0.3, 0, 0.3),   # one gap holds the whole span: once
    ((3, 4), 0, 0, 0),           # the device ran throughout
    ((2, 9.5), 0, 0.5, 1.5),     # busy on entering
])
def test_idle_at_the_edges_of_a_wait(span, head, tail, between):
    idle = spans._Idle([(0, 2), (5, 6), (9, 12)])
    assert idle.edges(*span) == (pytest.approx(head), pytest.approx(tail))
    assert idle.between(*span) == pytest.approx(between)
