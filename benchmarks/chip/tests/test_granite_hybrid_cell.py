"""The Mamba-2 / grouped-query cell (`granite4hmicro_reason_closed`) at
rehearsal sizes: `correct` reads true for a sound run and false for
the int8 control, for a garbled token stream and for a planted fault
in what the cell exists to exercise; its cost functions hold their
hand counts; its readers read nothing, and do not raise, from a
program that lacks what they read."""

import numpy as np
import pytest

import run as harness
from lib import costs_granite_hybrid as costs

CELL = "granite4hmicro_reason_closed"
PUBLISHED = {
    "vocab": 100352, "dim": 2048, "heads": 32, "kv_heads": 8, "head_dim": 64,
    "layers": 40,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "mlp_mult": 4, "mamba_heads": 64, "mamba_head_dim": 64, "d_state": 128,
    "d_conv": 4, "chunk": 256}


def drive(seed, seconds=8.0):
    return harness.drive(["--workload", CELL, "--seed", str(seed),
                          "--seconds", str(seconds), "--rehearse"])


def test_sound_run_is_correct_and_the_controls_are_not():
    """Prefill in chunks with the state carried, the blocked scan, the
    one-token update, state reset at admission, the four pools on one
    table: every finished greedy request against the reference's full
    forward (sequential recurrence). The same prompts and tokens judged
    with the reference computed in int8, and the served tokens garbled,
    fail the limit the program passes."""
    for seed in (11, 2 ** 31 + 12):
        driver, run = drive(seed)
        assert run["failed"] == 0
        notes = run["notes"]
        assert notes["state_slots_reset"] > 4  # slots were re-used
        assert notes["steps_with_chunk"] > 0
        assert set(notes["cache_bytes_in_use"]) == {"full", "state"}
        count, total = run["cache_bytes_per_slot"]
        assert count > 0 and total > 0
        assert run["model_flops"] > 0
        ok, compared = driver.check()
        assert ok, compared
        bad, control = driver.check(control="int8")
        assert not bad, control
    rng = np.random.default_rng(0)
    for r in driver.sample():  # one token in four is another id
        hit = rng.random(len(r.tokens)) < 0.25
        r.tokens = np.where(hit, (r.tokens + 1 + rng.integers(
            0, 1000, len(r.tokens))) % 8192, r.tokens).astype(np.int32)
    garbled, compared = driver.check()
    assert not garbled, compared


def test_state_not_reset_at_admission_is_not_correct(monkeypatch):
    """The planted fault: a slot's recurrent state is left as its last
    tenant had it. Nothing fails, every request finishes, and `correct`
    reads false."""
    from paddle_tpu.serving import ServingEngine

    monkeypatch.setattr(ServingEngine, "_reset_slot_state",
                        lambda self, s: None)
    driver, run = drive(21)
    assert run["failed"] == 0 and run["attempted"] > 0
    ok, compared = driver.check()
    assert not ok, compared


def test_costs_hold_their_hand_counts():
    assert costs.selfcheck()
    # the published shape: 3.19 B parameters in matrices, 36 + 4 layers
    s = PUBLISHED
    n, p = costs.layer_counts(s), costs.matmul_params(s)
    assert n == {"mamba": 36, "attention": 4}
    total = (40 * p["mlp"] + 36 * p["mamba"] + 4 * p["attention"]
             + p["head"])
    assert 3.18e9 < total < 3.20e9
    # a token: 2 x 3.19 G for the matrices, 0.11 G for the recurrence
    assert 6.45e9 < costs.decode_flops(s, 1) < 6.55e9
    # a slot's state: 2 MB in and 2 MB out a layer; 36 calls a step
    ((calls, flops, nbytes),) = costs.ssd_state_update_cost(s, [4096], 32)
    assert calls == 36 and flops == 6 * 128 * 4096
    assert nbytes == 2 * 2097152 + 2 * 16384 + (64 + 256) * 4
    # a position is 2,048 B a layer (8 heads of 64, K and V, bf16)
    ((calls, _, nbytes),) = costs.gqa_decode_attention_cost(s, [4097], 32)
    assert calls == 4 and nbytes == 4128 * 2048 + 2 * 2048 * 2
    # a 2,048-row chunk's scan: 8 blocks of 256
    flops, nbytes = costs.ssd_chunk_scan_cost(s, 2048)
    assert flops == 8 * (2 * 65536 * 4224 + 4 * 256 * 128 * 4096)
    assert nbytes == 2048 * (8192 + 256 + 64) * 4 + 2 * 2097152


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


@pytest.mark.parametrize("metric", ["ssd_decode_roofline",
                                    "gqa_attn_roofline"])
def test_new_readers_read_nothing_where_there_is_nothing(metric):
    spec = harness.load_json("layer_metrics", metric + ".json")
    reader = harness.load_module("layer_metrics/readers", spec["reader"])
    run = {"traced": (0.0, 1.0), "steps": [(0.5, [100, 200], 0, 0.04)],
           "shape": PUBLISHED, "block_tokens": 32}
    assert reader.read(_NoKernelTrace(), run, spec.get("args", {}),
                       _Ctx()) is None
    assert reader.read(None, {}, spec.get("args", {}), _Ctx()) is None


@pytest.mark.parametrize("metric,seconds,calls", [
    ("ssd_decode_roofline", 0.014, 36), ("gqa_attn_roofline", 0.004, 4)])
def test_roofline_readers_add_up_the_calls_of_a_step(metric, seconds, calls):
    """One step, contexts [4096] x 64: 36 state updates against HBM
    bandwidth in 14 ms, four K/V reads in 4 ms."""
    spec = harness.load_json("layer_metrics", metric + ".json")
    reader = harness.load_module("layer_metrics/readers", spec["reader"])

    class Trace(object):
        def op_seconds(self, op_match, program_match=None, chip=None):
            return seconds, calls, [(0.0, seconds)]

    run = {"traced": (0.0, 1.0), "steps": [(0.5, [4096] * 64, 0, 0.04)],
           "shape": PUBLISHED, "block_tokens": 32}
    got = reader.read(Trace(), run, spec["args"], _Ctx())
    if metric == "ssd_decode_roofline":
        least = 36 * 64 * (4 * 1048576 + 2 * 16384 + 1280) / 819e9
    else:
        least = 4 * 64 * (4096 * 2048 + 2 * 2048 * 2) / 819e9
    assert abs(got - 100 * least / seconds) < 1e-6
    assert 50 < got < 100
