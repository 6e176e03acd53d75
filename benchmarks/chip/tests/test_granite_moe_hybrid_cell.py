"""The state-space hybrid cell with routed experts
(`granitehsmall_reason_closed`) at rehearsal sizes: `correct` reads
true for a sound run and false for the int8 control, for the reference
with a sigmoid router, with a softmax over all the logits or without
its shared MLP, for a garbled token stream and for a program whose
router scores by sigmoids or whose shared MLP is dropped; its cost
functions hold their hand counts at the published shape; its reader
reads nothing, and does not raise, where there is nothing.

Three numbers decide `correct`
(`drivers/serve_engine_granite_moe_hybrid.py`): the median
`logit_err_median` of the program's logits through its caches against
the reference's, `served_not_top2_share` (the served tokens that are
neither the first nor the second of those logits) and
`state_err_first_layer_max`, the first Mamba-2 layer's state after the
last judged token. CPU readings of what is counted, not of a time, at
these sizes over three seeds: program 0.0257-0.0258, 0 and
0.0081-0.0088; int8 control 0.098-0.107, 0.060-0.077 and 0.028-0.034;
a sigmoid router 0.210-0.224 and 0.23-0.25 (its state 0: the first
layer lies before the first expert layer); limits 0.05, 0.0005 and
0.016, between the program's and int8's ends. A state rounded to
bfloat16 reads 0.0005-0.0007 and 0.0070-0.0088 on the logits and the
state, as the program here (a rehearsal's contexts are under 110
tokens, too few for a slow head to sum the rounding:
`tests/test_granite_moe_hybrid.py` shows the mechanism over 400
tokens; the chip's readings are in PERF.md), and fails by the served
tokens alone, 0.005-0.015."""

import numpy as np
import pytest

import run as harness
from lib import costs_granite_hybrid as mixers
from lib import costs_granite_moe_hybrid as costs

CELL = "granitehsmall_reason_closed"
PUBLISHED = harness.load_json("configs", "granite_4_0_h_small.json")["shape"]


def drive(seed, seconds=4.0, tweak=None):
    return harness.drive(["--workload", CELL, "--seed", str(seed),
                          "--seconds", str(seconds), "--rehearse"],
                         tweak=tweak)


def test_sound_run_is_correct_and_the_controls_are_not():
    """Prefill in one and two chunks, decode through the one-head pool
    and the state, 8 of 16 experts held: every finished greedy request
    against the reference's full forward (every held expert over every
    row, its own top-k softmax, the sequential recurrence). The same
    prompts and tokens judged with the reference computed in int8, with
    a sigmoid router, a softmax over all the logits or no shared MLP,
    and the served tokens garbled, fail a limit the program passes."""
    for seed in (11, 2 ** 31 + 12):
        driver, run = drive(seed)
        assert run["failed"] == 0
        notes = run["notes"]
        assert notes["steps_with_chunk"] > 0
        assert set(notes["cache_bytes_in_use"]) == {"full", "state"}
        # 4 live rows x top-4 over 16 experts, 8 held: 0-8 a layer
        assert 0 < notes["moe_experts_hit_a_layer_step"] <= 8
        count, total = run["cache_bytes_per_slot"]
        assert count > 0 and total > 0
        assert run["model_flops"] > 0
        # slots x top_k x held share x layers: 4 x 4 x 1/2 x 10
        pairs = [rows for _, rows, _ in run["moe_steps"]]
        assert pairs and max(pairs) == 80
        assert all(0 <= hit <= 10 * 8 for _, _, hit in run["moe_steps"])
        ok, compared = driver.check()
        assert ok, compared
        assert set(compared) == {"served_not_top2_share",
                                 "tokens_compared", "logit_err_median",
                                 "state_err_first_layer_max"}
        # the engine served what its own logits put first or second
        assert compared["served_not_top2_share"]["value"] == 0.0
        for control in ("int8", "sigmoid_router", "softmax_all",
                        "no_shared_expert"):
            bad, ctrl = driver.check(control=control)
            assert not bad, (control, ctrl)
            for key in ("served_not_top2_share", "logit_err_median"):
                assert ctrl[key]["value"] > ctrl[key]["limit"], (control, key)
        # the routing faults leave the first layer's state as it is (it
        # lies before the first expert layer); the int8 control does not
        _, ctrl = driver.check(control="int8")
        first = ctrl["state_err_first_layer_max"]
        assert first["value"] > first["limit"]
    rng = np.random.default_rng(0)
    for r in driver.sample():  # one token in four is another id
        hit = rng.random(len(r.tokens)) < 0.25
        r.tokens = np.where(hit, (r.tokens + 1 + rng.integers(
            0, 1000, len(r.tokens))) % 8192, r.tokens).astype(np.int32)
    garbled, compared = driver.check()
    assert not garbled, compared


@pytest.mark.parametrize("fault", ["sigmoid_router", "no_shared_expert"])
def test_a_program_with_a_planted_fault_is_not_correct(monkeypatch, fault):
    """The program itself gets it wrong: its router weights the chosen
    experts by their sigmoids normalised over the k (the scoring the
    other expert families have), or its shared MLP is left out of the
    expert layers. Nothing fails, every request finishes, and `correct`
    reads false."""
    import jax.numpy as jnp

    from paddle_tpu.models import granite_hybrid as gh
    from paddle_tpu.parallel import routed_experts

    if fault == "sigmoid_router":
        sound = routed_experts.route

        def faulty(u32, router_w, bias, top_k, **kw):
            zero = jnp.zeros(router_w.shape[-1], jnp.float32)
            return sound(u32, router_w, zero, top_k, scoring="sigmoid")

        monkeypatch.setattr(routed_experts, "route", faulty)
    else:
        monkeypatch.setattr(gh, "_mlp", lambda u, blk: jnp.zeros_like(u))
    driver, run = drive(21)
    assert run["failed"] == 0 and run["attempted"] > 0
    ok, compared = driver.check()
    assert not ok, compared


def test_costs_hold_their_hand_counts():
    """At the published shape: 9,437,184 weights an expert; a Mamba-2
    layer 461.2 M and the attention layer 400.9 M with 36 experts held
    (the reference's whole count: matrices, conv, norms); a token's
    routed work 10 x 36/72 = 5 experts a layer; and the two mixer
    calls' costs of `costs_granite_hybrid.py` read this shape as they
    read h-micro's: 4 MiB of float32 state a slot and layer, in and
    out, nine calls; 4,096 B of K and V a position, one call."""
    assert costs.selfcheck()
    ref = harness.load_module("references", "granite_moe_hybrid_plain")
    s = PUBLISHED
    p = costs.matmul_params(s)
    assert p["expert"] == 9_437_184
    assert costs.layer_counts(s) == {"mamba": 9, "attention": 1,
                                     "expert": 10}

    def one_layer(kind):
        return ref.param_count(dict(s, layers=1, layer_types=[kind],
                                    vocab=0)) - 4096  # the final norm

    assert one_layer("mamba") == 461_203_072
    assert one_layer("attention") == 400_859_136
    assert ref.param_count(s) == 4_757_211_776
    act = (9 * p["mamba"] + p["attention"]
           + 10 * (5 * p["expert"] + p["shared"] + p["router"]) + p["head"])
    assert costs.active_params(s) == act
    ((calls, fl, by),) = mixers.ssd_state_update_cost(s, [3600] * 64, 32)
    assert calls == 9 and fl == 64 * 6 * 128 * 8192
    assert by == 64 * (2 * 128 * 8192 * 4 + 2 * 8192 * 4 + (128 + 256) * 4)
    ((calls, fl, by),) = mixers.gqa_decode_attention_cost(s, [3600] * 64, 32)
    assert calls == 1 and fl == 64 * 4 * 32 * 128 * 3600
    assert by == 64 * 3616 * 4096 + 64 * 2 * 32 * 128 * 2


class _Ctx(object):
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    def __init__(self):
        self.logged = []

    def log(self, *a):
        self.logged.append(a)


class _NoKernelTrace(object):
    """A trace of a program without the kernel (the parent commit)."""

    def op_seconds(self, op_match, program_match=None, chip=None):
        return 0.0, 0, []


def test_the_new_reader_reads_nothing_where_there_is_nothing():
    spec = harness.load_json("layer_metrics", "moe_expert_roofline.json")
    reader = harness.load_module("layer_metrics/readers", spec["reader"])
    run = {"traced": (0.0, 1.0), "moe_steps": [(0.5, 320, 340)],
           "shape": PUBLISHED}
    assert reader.read(_NoKernelTrace(), run, spec["args"], _Ctx()) is None
    assert reader.read(None, {}, spec["args"], _Ctx()) is None
    # a parent's driver logs no expert steps
    assert reader.read(_NoKernelTrace(), {"traced": (0.0, 1.0)},
                       spec["args"], _Ctx()) is None


def test_the_roofline_reader_adds_up_a_steps_products():
    """One step: 64 slots x 5 held choices x 10 layers = 3,200 pairs
    reaching 355 experts (35.5 of 36 a layer): their weights, 6.70 GB,
    against HBM bandwidth (the FLOPs' 0.31 ms are a 27th of the bytes')."""
    spec = harness.load_json("layer_metrics", "moe_expert_roofline.json")
    reader = harness.load_module("layer_metrics/readers", spec["reader"])
    seconds = 0.010

    class Trace(object):
        def op_seconds(self, op_match, program_match=None, chip=None):
            return seconds, 20, [(0.0, seconds)]

    run = {"traced": (0.0, 1.0), "moe_steps": [(0.5, 3200, 355)],
           "shape": PUBLISHED}
    got = reader.read(Trace(), run, spec["args"], _Ctx())
    nbytes = 355 * 9_437_184 * 2 + 3200 * ((4096 + 3 * 768) * 2 + 4096 * 4)
    assert abs(got - 100 * nbytes / 819e9 / seconds) < 1e-6
    assert 80 < got < 85
