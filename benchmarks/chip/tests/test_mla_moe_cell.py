"""The latent-attention cell (`kanana2_reason128_closed`) at rehearsal
sizes: `correct` reads true for a sound run and false for the int8
control, for a garbled token stream and for a rotary key written to the
cache unrotated; its cost functions hold their hand counts; its
reader reads nothing, and does not raise, from a program that lacks
the kernel.

Two numbers decide `correct` here (`drivers/serve_engine_afmoe.py`,
whose `check()` this cell's driver keeps): `flip_gap_mean_sq`, the
harness's, and `gap_mean`. As in the sparse-expert cell the first does
not tell the program from the int8 control at these sizes (a flip
mostly follows from an expert chosen otherwise at a near-tie); the
second does: program 0.0018-0.0041, control 0.0100-0.0161 over four
seeds (CPU readings of what is counted, not of a time), limit 0.0065.
The chip's limits are in PERF.md."""

import numpy as np
import pytest

import run as harness
from lib import costs_mla_moe as costs

CELL = "kanana2_reason128_closed"
PUBLISHED = harness.load_json("configs", "kanana_2_30b_a3b.json")["shape"]


def drive(seed, seconds=4.0, tweak=None):
    return harness.drive(["--workload", CELL, "--seed", str(seed),
                          "--seconds", str(seconds), "--rehearse"],
                         tweak=tweak)


def test_sound_run_is_correct_and_the_controls_are_not():
    """Prefill in one and two chunks (expanded), absorbed decode through
    the latent pools, 8 of 16 experts held: every finished greedy
    request against the reference's expanded full forward (every held
    expert over every row, its own top-k). The same prompts and tokens
    judged with the reference computed in int8, and the served tokens
    garbled, fail a limit the program passes."""
    for seed in (11, 2 ** 31 + 12):
        driver, run = drive(seed)
        assert run["failed"] == 0
        notes = run["notes"]
        assert notes["steps_with_chunk"] > 0
        assert set(notes["cache_bytes_in_use"]) == {"full"}
        # 4 live rows x top-4 over 16 experts, 8 held: 0-8 a layer
        assert 0 < notes["moe_experts_hit_a_layer_step"] <= 8
        count, total = run["cache_bytes_per_slot"]
        assert count > 0 and total > 0
        assert run["model_flops"] > 0
        # slots x top_k x held share x expert layers: 4 x 4 x 1/2 x 2
        pairs = [rows for _, rows, _ in run["moe_steps"]]
        assert pairs and max(pairs) == 16
        assert all(0 <= hit <= 2 * 8 for _, _, hit in run["moe_steps"])
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


def test_an_unrotated_cached_key_is_not_correct(monkeypatch):
    """The planted fault: the program caches k_r as it leaves W_kva,
    unrotated, while the query is rotated (the whole-sequence oracle is
    untouched; only what the cache holds changes). Nothing fails, every
    request finishes, and `correct` reads false."""
    import jax.numpy as jnp

    from paddle_tpu.models import mla_moe

    sound = mla_moe._project

    def faulty(h, p, pos, cfg):
        q_n, q_r, row = sound(h, p, pos, cfg)
        r, dr = cfg.kv_rank, cfg.rope_dim
        raw = (h @ p["wkva"])[..., r:r + dr].astype(row.dtype)
        return q_n, q_r, jnp.concatenate(
            [row[..., :r], raw, row[..., r + dr:]], -1)

    monkeypatch.setattr(mla_moe, "_project", faulty)
    driver, run = drive(21)
    assert run["failed"] == 0 and run["attempted"] > 0
    ok, compared = driver.check()
    assert not ok, compared


def test_costs_hold_their_hand_counts():
    assert costs.selfcheck()
    s = PUBLISHED
    n, p = costs.layer_counts(s), costs.matmul_params(s)
    assert n == {"attention": 8, "dense": 1, "expert": 7}
    assert p["attention"] == 26_345_472 and p["expert"] == 4_718_592
    assert p["shared"] == 9_437_184 and p["router"] == 262_144
    assert p["dense"] == 37_748_736 and p["head"] == 128256 * 2048
    # a token's routed experts HERE: 6 x 16/128 = 0.75 of one
    act = (8 * p["attention"] + p["dense"]
           + 7 * (0.75 * p["expert"] + p["shared"] + p["router"])
           + p["head"])
    assert costs.active_params(s) == act
    # absorbed decode: 2 x 32 x (576 + 512) a position and layer
    assert costs.decode_flops(s, 3600) == 2 * act + 8 * 69_632 * 3600
    # expanded prefill: 2 x 32 x (192 + 128) a pair and layer
    assert costs.prefill_flops(s, 2) == (2 * 2 * (act - p["head"])
                                         + 2 * p["head"] + 8 * 20_480 * 3)
    # a position is 1,152 B a layer, read once; 128 slots at 3,600
    ((calls, fl, by),) = costs.mla_decode_attention_cost(s, [3600] * 128, 32)
    assert calls == 8 and fl == 128 * 69_632 * 3600
    assert by == 128 * 3616 * 1152 + 128 * 32 * 1088 * 2


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
    spec = harness.load_json("layer_metrics", "mla_decode_roofline.json")
    reader = harness.load_module("layer_metrics/readers", spec["reader"])
    run = {"traced": (0.0, 1.0), "steps": [(0.5, [100, 200], 0, 0.04)],
           "shape": PUBLISHED, "block_tokens": 32}
    assert reader.read(_NoKernelTrace(), run, spec["args"], _Ctx()) is None
    assert reader.read(None, {}, spec["args"], _Ctx()) is None


@pytest.mark.parametrize("seconds", [0.0075, 0.006])
def test_the_roofline_reader_adds_up_a_steps_calls(seconds):
    """One step, contexts [3600] x 128: eight latent calls against HBM
    bandwidth (the FLOPs' time is a quarter of the bytes')."""
    spec = harness.load_json("layer_metrics", "mla_decode_roofline.json")
    reader = harness.load_module("layer_metrics/readers", spec["reader"])

    class Trace(object):
        def op_seconds(self, op_match, program_match=None, chip=None):
            return seconds, 8, [(0.0, seconds)]

    run = {"traced": (0.0, 1.0), "steps": [(0.5, [3600] * 128, 0, 0.04)],
           "shape": PUBLISHED, "block_tokens": 32}
    got = reader.read(Trace(), run, spec["args"], _Ctx())
    least = 8 * (128 * 3616 * 1152 + 128 * 32 * 1088 * 2) / 819e9
    assert abs(got - 100 * least / seconds) < 1e-6
    assert 60 < got < 100
