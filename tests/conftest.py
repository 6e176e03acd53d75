"""Test harness: run everything on a virtual 8-device CPU mesh so the
distributed paths are CI-testable without TPU hardware (SURVEY.md §4.4
lesson: the reference's multi-process distributed tests were excluded from
CI; we make ours single-process)."""

import os

# XLA_FLAGS must be set before backend init
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np
import pytest

import jax

# kernels run at the platform's fast default precision (bf16 passes on the
# TPU MXU); numeric comparison tests need full f32 accumulation
jax.config.update("jax_default_matmul_precision", "float32")


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'` (ROADMAP); slow marks the long
    # elasticity drills that exceed that budget
    config.addinivalue_line(
        "markers", "slow: long end-to-end runs excluded from tier-1"
    )


# tier-1 budget guard: the ROADMAP's 870 s timeout is a shared budget;
# any single test taking >= this many seconds is visibly flagged at the
# end of the run so a creeping drill can't silently eat the suite
SLOW_TEST_SECONDS = 10.0


def pytest_terminal_summary(terminalreporter):
    slow = []
    for reports in terminalreporter.stats.values():
        for rep in reports:
            if (
                getattr(rep, "when", None) == "call"
                and getattr(rep, "duration", 0.0) >= SLOW_TEST_SECONDS
            ):
                slow.append((rep.duration, rep.nodeid))
    if not slow:
        return
    terminalreporter.write_sep(
        "=", "tier-1 budget guard: tests >= %.0fs" % SLOW_TEST_SECONDS
    )
    for dur, nodeid in sorted(slow, reverse=True):
        terminalreporter.write_line("%8.1fs  %s" % (dur, nodeid))
    terminalreporter.write_line(
        "(mark non-essential end-to-end drills @pytest.mark.slow to "
        "keep tier-1 under the ROADMAP timeout)"
    )


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Each test gets fresh default programs, a fresh scope, and no
    leaked default mesh (a test that sets one would silently change how
    later tests execute)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.core.program import (
        Program,
        switch_main_program,
        switch_startup_program,
    )
    from paddle_tpu.fluid.executor import Scope, switch_scope
    from paddle_tpu.parallel import mesh as mesh_mod

    prev_main = switch_main_program(Program())
    prev_startup = switch_startup_program(Program())
    prev_scope = switch_scope(Scope())
    prev_mesh = mesh_mod.get_default_mesh()
    yield
    switch_main_program(prev_main)
    switch_startup_program(prev_startup)
    switch_scope(prev_scope)
    mesh_mod.set_default_mesh(prev_mesh)


class _EngineWatch(object):
    """Which spans are open, and under which span each device-to-host
    read (`np.asarray` of a jax.Array in serving/engine.py) is made."""

    def __init__(self, eng, monkeypatch):
        from paddle_tpu.serving import engine as engine_mod

        self.open, self.reads, self.opened = [], [], []
        real_phase, watch = eng.metrics.phase, self

        class _Tracked(object):
            def __init__(self, name, ph):
                self.name, self.ph = name, ph

            def __enter__(self):
                watch.opened.append((self.name, tuple(watch.open)))
                watch.open.append(self.name)
                return self.ph.__enter__()

            def __exit__(self, *exc):
                watch.open.pop()
                return self.ph.__exit__(*exc)

        class _Numpy(object):
            def __getattr__(self, name):
                return getattr(np, name)

            @staticmethod
            def asarray(x, *a, **kw):
                if isinstance(x, jax.Array):
                    watch.reads.append(watch.open[-1] if watch.open
                                       else None)
                return np.asarray(x, *a, **kw)

        monkeypatch.setattr(
            eng.metrics, "phase",
            lambda name, row=None, **kw: _Tracked(
                name, real_phase(name, row, **kw)))
        monkeypatch.setattr(engine_mod, "np", _Numpy())
        self.undo = monkeypatch.undo


@pytest.fixture
def watch_engine(monkeypatch):
    """`watch_engine(eng)` -> an `_EngineWatch` on that ServingEngine
    for the rest of the test (or until its `.undo()`)."""
    return lambda eng: _EngineWatch(eng, monkeypatch)
