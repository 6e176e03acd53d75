"""The hybrid cell (`phi4flash_reason_closed`) at rehearsal sizes:
`correct` reads true for a sound run and false for the int8 control
and for a planted fault in what the cell exists to exercise; its cost
functions hold their hand counts; its readers read nothing, and do not
raise, from a program that lacks what they read."""

import pytest

import run as harness
from lib import costs_sambay

CELL = "phi4flash_reason_closed"


def drive(seed, seconds=8.0):
    return harness.drive(["--workload", CELL, "--seed", str(seed),
                          "--seconds", str(seconds), "--rehearse"])


def test_sound_run_is_correct_and_the_int8_control_is_not():
    """Prefill in chunks, window release, state carried and reset, the
    shared pool: every finished greedy request against the reference's
    full forward. The same prompts and tokens judged with the
    reference computed in int8 fail the limit the program passes."""
    for seed in (11, 2 ** 31 + 12):
        driver, run = drive(seed)
        assert run["failed"] == 0
        notes = run["notes"]
        assert notes["window_blocks_released"] > 0
        assert notes["state_slots_reset"] > 4  # slots were re-used
        assert set(notes["cache_bytes_in_use"]) == {"full", "window", "state"}
        count, total = run["cache_bytes_per_slot"]
        assert count > 0 and total > 0
        assert run["model_flops"] > 0
        ok, compared = driver.check()
        assert ok, compared
        bad, control = driver.check(control="int8")
        assert not bad, control


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
    assert costs_sambay.selfcheck()
    # the published shape: 3.85 B parameters, 9 + 8 + 1 + 7 + 7 layers
    s = {"vocab": 200064, "dim": 2560, "heads": 40, "kv_heads": 20,
         "layers": 32, "mlp_mult": 4, "window": 512}
    n, p = costs_sambay.layer_counts(s), costs_sambay.matmul_params(s)
    assert sum(n.values()) == 32
    total = 32 * p["mlp"] + sum(n[k] * p[k] for k in n) + p["head"]
    assert 3.84e9 < total < 3.86e9
    # a window call never reads more than 512 positions, a shared-pool
    # call the whole context: 5,120 B a position
    (cw, _, bw), (cf, _, bf) = costs_sambay.hybrid_decode_attention_cost(
        s, [4096], 16)
    assert (cw, cf) == (8, 8)
    assert bw == 512 * 5120 + 3 * 2560 * 2
    assert bf == 4096 * 5120 + 3 * 2560 * 2


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


@pytest.mark.parametrize("metric", ["hybrid_attn_roofline",
                                    "ssm_decode_roofline",
                                    "cache_mb_per_slot"])
def test_new_readers_read_nothing_where_there_is_nothing(metric):
    spec = harness.load_json("layer_metrics", metric + ".json")
    reader = harness.load_module("layer_metrics/readers", spec["reader"])
    run = {"traced": (0.0, 1.0), "steps": [(0.5, [100, 200], 0, 0.04)],
           "shape": {}, "block_tokens": 16}
    assert reader.read(_NoKernelTrace(), run, spec.get("args", {}),
                       _Ctx()) is None
    assert reader.read(None, {}, spec.get("args", {}), _Ctx()) is None


def test_roofline_reader_adds_up_the_calls_of_a_step():
    """One step, contexts [4096] x 64, the kernel 20 ms on the device:
    8 window calls + 8 shared-pool calls against HBM bandwidth."""
    spec = harness.load_json("layer_metrics", "hybrid_attn_roofline.json")
    reader = harness.load_module("layer_metrics/readers", spec["reader"])
    s = {"vocab": 200064, "dim": 2560, "heads": 40, "kv_heads": 20,
         "layers": 32, "mlp_mult": 4, "window": 512}

    class Trace(object):
        def op_seconds(self, op_match, program_match=None, chip=None):
            return 0.020, 16, [(0.0, 0.020)]

    run = {"traced": (0.0, 1.0), "steps": [(0.5, [4096] * 64, 0, 0.04)],
           "shape": s, "block_tokens": 16}
    got = reader.read(Trace(), run, spec["args"], _Ctx())
    io = 64 * 3 * 2560 * 2
    least = 8 * (64 * 512 * 5120 + io) / 819e9 \
        + 8 * (64 * 4096 * 5120 + io) / 819e9
    assert abs(got - 100 * least / 0.020) < 1e-6
