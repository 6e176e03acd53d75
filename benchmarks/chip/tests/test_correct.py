"""`correct` has to come out false for the control and for a broken
timed path, at a size a test run can hold (the rehearse/ files)."""

import pytest

import run as harness

CELLS = ["gpt1p3b_decode_closed", "gpt1p3b_chat_open"]


def drive(cell, seed, seconds=3.0, **engine_kw):
    """A run without the harness's look for a chip: set-up, window,
    release, and the driver ready for its check."""
    return harness.drive(
        ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
         "--rehearse"],
        lambda ctx, driver: driver.engine_kw.update(engine_kw))


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_the_int8_control_is_not(cell):
    """The plain reference computed in int8 in the program's place, on
    the same prompts and tokens, has to fail the limit the program
    passes (three seeds; 8 s, so that some hundred served tokens
    differ from the reference's first and their mean gap is steady)."""
    for seed in (11, 2 ** 31 + 12, 13):
        driver, run = drive(cell, seed, seconds=8.0)
        assert run["failed"] == 0
        ok, compared = driver.check()
        assert ok, compared
        bad, control = driver.check(control="int8")
        assert not bad, control


@pytest.mark.parametrize("cell", CELLS)
def test_a_token_altered_where_it_is_produced_is_not_correct(cell):
    """The engine's own garble drill shifts every emitted token to
    another id: the run completes, and `correct` reads false."""
    from paddle_tpu.distributed.fault_injection import FaultInjector

    driver, run = drive(cell, 21, fault_injector=FaultInjector("garble@1"))
    assert run["attempted"] > 0
    ok, compared = driver.check()
    assert not ok, compared


def test_too_few_tokens_compared_is_not_correct():
    """A window that finishes nothing to compare proves nothing."""
    driver, run = drive(CELLS[0], 31)
    driver.window_reqs = driver.window_reqs[:0]
    ok, compared = driver.check()
    assert not ok and compared["tokens_compared"]["value"] == 0
