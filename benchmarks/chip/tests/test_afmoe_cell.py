"""The sparse-expert cell (`trinitymini_reason_closed`) at rehearsal
sizes: `correct` reads true for a sound run and false for the int8
control, for a garbled token stream and for a router that ignores its
bias or its scale; its cost functions hold their hand counts; its
readers read nothing, and do not raise, from a program that lacks
what they read.

Two numbers decide `correct` here (`drivers/serve_engine_afmoe.py`):
`flip_gap_mean_sq`, the harness's, and `gap_mean`. At these sizes (a
few hundred tokens compared) the first does not tell the program from
the int8 control — a served token that is not the reference's first
mostly follows from an expert chosen otherwise at a near-tie, and the
SIZE of such a gap is what an expert weighs, whatever the precision —
so its rehearsal limit only catches gross faults; the second, which
also counts how OFTEN that happens, does: program 0.013-0.018,
control 0.040-0.045 over ten seeds (CPU readings of what is counted,
not of a time). The chip's limits are in PERF.md section 6."""

import numpy as np
import pytest

import run as harness
from lib import costs_afmoe as costs

CELL = "trinitymini_reason_closed"
PUBLISHED = harness.load_json("configs", "trinity_mini.json")["shape"]


def drive(seed, seconds=6.0, tweak=None):
    return harness.drive(["--workload", CELL, "--seed", str(seed),
                          "--seconds", str(seconds), "--rehearse"],
                         tweak=tweak)


def test_sound_run_is_correct_and_the_controls_are_not():
    """Prefill in one and two chunks, window release, rotated window
    keys beside position-free full ones, sorted grouped experts: every
    finished greedy request against the reference's full forward (every
    expert over every row, its own top-k). The same prompts and tokens
    judged with the reference computed in int8, and the served tokens
    garbled, fail a limit the program passes."""
    for seed in (11, 2 ** 31 + 12):
        driver, run = drive(seed)
        assert run["failed"] == 0
        notes = run["notes"]
        assert notes["state_slots_reset"] == 0  # no state to reset
        assert notes["window_blocks_released"] > 0
        assert notes["steps_with_chunk"] > 0
        assert set(notes["cache_bytes_in_use"]) == {"full", "window"}
        # 4 live rows x top-4 over 16 experts: 4-16 reached a layer
        assert 4 <= notes["moe_experts_hit_a_layer_step"] <= 16
        assert 1 <= notes["moe_rows_max"] <= 4
        count, total = run["cache_bytes_per_slot"]
        assert count > 0 and total > 0
        assert run["model_flops"] > 0
        pairs = [rows for _, rows, _ in run["moe_steps"]]
        assert pairs and max(pairs) == 4 * 4 * 4  # slots x top_k x layers
        assert all(0 < hit <= 4 * 16 for _, _, hit in run["moe_steps"])
        ok, compared = driver.check()
        assert ok, compared
        assert set(compared) == {"flip_gap_mean_sq", "tokens_compared",
                                 "gap_mean"}
        bad, control = driver.check(control="int8")
        assert not bad, control
        assert control["gap_mean"]["value"] > control["gap_mean"]["limit"]
    rng = np.random.default_rng(0)
    for r in driver.sample():  # one token in four is another id
        hit = rng.random(len(r.tokens)) < 0.25
        r.tokens = np.where(hit, (r.tokens + 1 + rng.integers(
            0, 1000, len(r.tokens))) % 8192, r.tokens).astype(np.int32)
    garbled, compared = driver.check()
    assert not garbled, compared


@pytest.mark.parametrize("fault", ["no_bias", "no_scale"])
def test_a_router_without_its_bias_or_its_scale_is_not_correct(monkeypatch,
                                                               fault):
    """The planted fault: the program's router ignores its bias (other
    experts chosen) or its scale (the routed sum a third of its size
    beside the shared expert). Nothing fails, every request finishes,
    and `correct` reads false."""
    from paddle_tpu.parallel import routed_experts

    sound = routed_experts.route

    def faulty(u32, router_w, bias, top_k, route_scale=1.0, route_norm=True):
        if fault == "no_bias":
            bias = bias * 0
        else:
            route_scale = 1.0
        return sound(u32, router_w, bias, top_k, route_scale, route_norm)

    monkeypatch.setattr(routed_experts, "route", faulty)
    driver, run = drive(21)
    assert run["failed"] == 0 and run["attempted"] > 0
    ok, compared = driver.check()
    assert not ok, compared


def test_costs_hold_their_hand_counts():
    assert costs.selfcheck()
    s = PUBLISHED
    n, p = costs.layer_counts(s), costs.matmul_params(s)
    assert n == {"window": 4, "full": 1, "dense": 1, "expert": 4}
    assert p["attention"] == 27_262_976 and p["expert"] == 6_291_456
    assert p["dense"] == 37_748_736 and p["router"] == 262_144
    # ACTIVE parameters a token: 8 routed + 1 shared expert, not 128
    act = (5 * p["attention"] + p["dense"] + 4 * (9 * p["expert"]
                                                  + p["router"])
           + p["head"])
    assert costs.active_params(s) == act
    # the published depth: 2 dense + 30 expert layers, 3.06 B active
    whole = dict(s, layer_types=["sliding_attention"] * 24
                 + ["full_attention"] * 8, num_dense_layers=2)
    assert 3.0e9 < costs.active_params(whole) < 3.1e9
    assert costs.decode_flops(s, 1) == 2 * act + 5 * 4 * 4096
    # context 5,000: the full layer attends it all, a window layer 2,048
    assert costs.decode_flops(s, 5000) == (
        2 * act + 4 * 4096 * 5000 + 4 * 4 * 4096 * 2048)
    # a position is 2,048 B a layer (4 heads of 128, K and V, bf16)
    (wc, _, wb), (fc, _, fb) = costs.swa_decode_attention_cost(s, [4097], 32)
    assert (wc, fc) == (4, 1)
    assert wb == 2048 * 2048 + 2 * 4096 * 2
    assert fb == 4128 * 2048 + 2 * 4096 * 2
    # a full step: 64 slots x 8 choices x 4 layers, 456 experts reached
    flops, nbytes = costs.moe_grouped_matmul_cost(s, 2048, 456)
    assert flops == 2 * 2048 * 6_291_456
    assert nbytes == 456 * 12_582_912 + 2048 * (5120 * 2 + 8192)
    # where routing concentrates the reached experts' bytes fall, and a
    # count of all 512 held would read over the device's time
    assert costs.moe_grouped_matmul_cost(s, 2048, 512)[1] > nbytes


class _Ctx(object):
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    def __init__(self):
        self.logged = []

    def log(self, *a):
        self.logged.append(a)


class _NoKernelTrace(object):
    """A trace of a program without the kernels (the parent commit)."""

    def op_seconds(self, op_match, program_match=None, chip=None):
        return 0.0, 0, []


@pytest.mark.parametrize("metric", ["moe_expert_roofline",
                                    "swa_attn_roofline"])
def test_new_readers_read_nothing_where_there_is_nothing(metric):
    spec = harness.load_json("layer_metrics", metric + ".json")
    reader = harness.load_module("layer_metrics/readers", spec["reader"])
    run = {"traced": (0.0, 1.0), "steps": [(0.5, [100, 200], 0, 0.04)],
           "moe_steps": [(0.5, 64, 40)], "shape": PUBLISHED,
           "block_tokens": 32}
    assert reader.read(_NoKernelTrace(), run, spec.get("args", {}),
                       _Ctx()) is None
    assert reader.read(None, {}, spec.get("args", {}), _Ctx()) is None
    # a cell of another family: the driver logged no routed steps
    del run["moe_steps"]

    class Trace(object):
        def op_seconds(self, op_match, program_match=None, chip=None):
            return 0.01, 8, [(0.0, 0.01)]

    if metric == "moe_expert_roofline":
        assert reader.read(Trace(), run, spec["args"], _Ctx()) is None


@pytest.mark.parametrize("metric,seconds,calls", [
    ("moe_expert_roofline", 0.0085, 8), ("swa_attn_roofline", 0.0025, 5)])
def test_roofline_readers_add_up_the_calls_of_a_step(metric, seconds, calls):
    """One step, contexts [4096] x 64: eight grouped products over 456
    reached experts against HBM bandwidth in 8.5 ms, four window reads
    and one full read in 2.5 ms."""
    spec = harness.load_json("layer_metrics", metric + ".json")
    reader = harness.load_module("layer_metrics/readers", spec["reader"])

    class Trace(object):
        def op_seconds(self, op_match, program_match=None, chip=None):
            return seconds, calls, [(0.0, seconds)]

    run = {"traced": (0.0, 1.0), "steps": [(0.5, [4096] * 64, 0, 0.04)],
           "moe_steps": [(0.5, 2048, 456)], "shape": PUBLISHED,
           "block_tokens": 32}
    got = reader.read(Trace(), run, spec["args"], _Ctx())
    if metric == "moe_expert_roofline":
        least = (456 * 12_582_912 + 2048 * 18432) / 819e9
    else:
        io = 64 * 2 * 4096 * 2
        least = (4 * (64 * 2048 * 2048 + io)
                 + (64 * 4096 * 2048 + io)) / 819e9
    assert abs(got - 100 * least / seconds) < 1e-6
    assert 50 < got < 100
