"""`run.py --selfcheck`: the yardstick checked against hand counts.

No chip: the trace reduction runs on a hand-made trace whose answers
can be worked out on paper and on a small trace recorded on a v5e
(three decode steps of the 24-layer LM at 32 slots); the cost
functions and the metric arithmetic run at one small shape each.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from lib import costs, stats, traffic, xplane  # noqa: E402

KERNEL = r"= bf16\[\d+,\d+,1,\d+\]\S* custom-call\("
DECODE = r"^jit__decode\("


def near(a, b, rel=1e-9):
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-30)


def check_synthetic():
    # chip 0: two runs of program p; ops a (1 ms, twice), k (2.5 ms, once,
    # overlapping a by 0.5 ms in the first run); host idles 3 ms between
    ms = 1e-3
    raw = {"lines": {
        "/device:TPU:0|XLA Modules": [["jit_p(1)", 0.0, 3 * ms],
                                      ["jit_p(1)", 6 * ms, 1 * ms]],
        "/device:TPU:0|XLA Ops": [
            ["%a.1 = f32[8]{0} fusion(f32[8]{0} %x)", 0.0, 1 * ms],
            ["%k.1 = bf16[4,2,1,8]{3,2,1,0} custom-call(s32[4] %t)",
             0.5 * ms, 2.5 * ms],
            ["%a.1 = f32[8]{0} fusion(f32[8]{0} %x)", 6 * ms, 1 * ms]],
        "/host:CPU|python3": [["sched.step", -1 * ms, 4.5 * ms],
                              ["loadgen.wait_due", 3.6 * ms, 2 * ms]],
    }}
    t = xplane.Trace(raw, 1)
    assert near(t.busy_s, 4 * ms), t.busy_s            # [0, 3] + [6, 7]
    assert t.executions(r"^jit_p\(") == [(0.0, 3 * ms), (6 * ms, 1 * ms)]
    sec, n, per = t.op_seconds(r"custom-call\(", r"^jit_p\(")
    assert near(sec, 2.5 * ms) and n == 1 and len(per) == 1
    gaps = dict(t.idle_gaps())
    # one gap, 3 -> 6 ms, began while sched.step was the only span open
    assert list(gaps) == ["sched.step"] and near(gaps["sched.step"], 3 * ms)
    assert t.top_ops(1)[0][0] == "custom-call bf16[4,2,1,8] x1"


def check_recorded():
    t = xplane.Trace(xplane.load_raw(
        os.path.join(HERE, "v5e_decode_3steps.json.gz")), 1)
    runs = t.executions(DECODE)
    assert len(runs) == 3
    assert all(0.0180 < d < 0.0182 for _, d in runs), runs
    # the operations tile the program executions
    assert abs(t.busy_s - sum(d for _, d in runs)) < 1e-3 * t.busy_s
    sec, n, per = t.op_seconds(KERNEL, DECODE)
    assert n == 3 * 24 and len(per) == 3        # one call per layer
    assert 0.85 < sec / t.busy_s < 0.87         # read by hand: 86 %
    # 32 slots at ~330 cached positions: 24 calls a step, K/V bytes
    # the tables name over 819 GB/s, against 15.5 ms on the device
    shape = {"vocab": 50257, "dim": 2048, "heads": 16, "layers": 24,
             "mlp_mult": 4}
    fl, by = costs.paged_decode_attention_cost(shape, [330] * 32, 16)
    least, bound = costs.roofline_seconds(
        fl, by, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "hbm"
    assert 0.15 < 3 * 24 * least / sec < 0.18


def check_costs():
    s = {"vocab": 10, "dim": 4, "heads": 2, "layers": 2, "mlp_mult": 4}
    # per layer: 4 d^2 + 2 d (4 d) = 64 + 128; two layers
    assert costs.lm_block_matmul_params(s) == 384
    assert costs.lm_head_params(s) == 40
    # 2 (384 + 40) + layers 2 x 4 x context 3 x d 4
    assert costs.lm_decode_flops(s, 3) == 848 + 96
    # 3 rows: 2 x 3 x 384, head once 2 x 40, attention over 1 + 2 + 3 rows
    assert costs.lm_prefill_flops(s, 3) == 2304 + 80 + 2 * 4 * 6 * 4
    # contexts 3 and 17, 16-token blocks: 1 and 2 blocks named
    fl, by = costs.paged_decode_attention_cost(s, [3, 17], 16)
    assert fl == 4 * 3 * 4 + 4 * 17 * 4
    assert by == 2 * 48 * 4 * 2 + 2 * 2 * 4 * 2
    t, which = costs.roofline_seconds(
        100.0, 10.0, {"bf16_flops_per_s": 50.0, "hbm_bytes_per_s": 10.0})
    assert (t, which) == (2.0, "flops")


def check_arithmetic():
    assert stats.percentile([1, 2, 3, 4, 5], 95) == 4.8
    assert stats.percentile([], 95) is None
    assert near(stats.spread([10, 10, 11, 12, 12, 13]), 2.25 / 11.5)
    assert stats.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    tr = {"loop": "open", "rate_per_s": 4.0, "lead_in_s": 1.0,
          "prompt_tokens": {"dist": "uniform", "lo": 4, "hi": 12},
          "output_tokens": {"dist": "uniform", "lo": 3, "hi": 3},
          "temperatures": [0.0, 0.8]}
    a = traffic.generate(tr, 50, 1, 4.0)
    b = traffic.generate(tr, 50, 2 ** 31 + 7, 4.0)
    assert len(a) == len(b) == 20
    # every seed: the same sizes and arrival gaps, in another order
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    gaps = lambda rs: sorted(round(y["due_s"] - x["due_s"], 9)
                             for x, y in zip(rs, rs[1:]))
    assert a[0]["due_s"] == 0.0 and gaps(a) != [] \
        and abs(sum(gaps(a)) - sum(gaps(b))) < 0.5
    assert [r["prompt"].tolist() for r in a] != \
        [r["prompt"].tolist() for r in b]
    assert traffic.prompt_buckets(tr, 8, 64, 4.0) == [8, 16]
    # a block of 8 arrivals at 4 a second lasts 2 s exactly, whatever
    # the seed: request 8 opens the second block
    c = traffic.generate(dict(tr, block=8), 50, 3, 4.0)
    assert near(c[8]["due_s"], 2.0) and near(c[16]["due_s"], 4.0)


def main():
    for fn in (check_synthetic, check_recorded, check_costs,
               check_arithmetic):
        fn()
        print("selfcheck: %s ok" % fn.__name__)
    return 0
