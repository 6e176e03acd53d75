"""The scheduler's own spans (ISSUE 25): one primitive on
`ServingMetrics`, two sinks — the profiler's trace and the profiler-
style rows — and the record a slow step leaves behind."""

import logging
import os
import re
import sys

import numpy as np
import pytest

import jax

from paddle_tpu.distributed.fault_injection import FaultInjector
from paddle_tpu.models import transformer as T
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.metrics import ServingMetrics

CHIPBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip")

# the dozen names, and which may sit directly beneath engine.step
NAMES = {"engine.step", "engine.expire", "engine.admit",
         "engine.prefill_chunk", "engine.decode", "engine.alloc_blocks",
         "engine.upload", "engine.dispatch", "engine.device_wait",
         "engine.integrity", "engine.emit", "engine.publish"}
TOP = ("engine.expire", "engine.admit", "engine.prefill_chunk",
       "engine.decode")


@pytest.fixture(scope="module")
def model():
    cfg = T.TransformerConfig(vocab=64, dim=32, heads=2, layers=2,
                              max_len=64)
    return cfg, T.init_params(cfg, jax.random.PRNGKey(25))


def _load(eng, n=3, max_new=6, lo=4):
    rng = np.random.RandomState(eng.metrics.steps)
    return [eng.submit(rng.randint(0, 64, (lo + 3 * i,)).astype(np.int32),
                       max_new) for i in range(n)]


def _calls(eng):
    return {name: row[0] for name, row in eng.metrics.ops.rows.items()}


@pytest.mark.parametrize("async_dispatch", [None, False],
                         ids=["default", "lockstep"])
def test_every_phase_row_has_the_calls_it_should(model, async_dispatch):
    cfg, params = model
    eng = ServingEngine(params, cfg, max_slots=2, prefix_cache_tokens=64,
                        prefix_block_tokens=4,
                        async_dispatch=async_dispatch)
    _load(eng)
    eng.run()
    m, calls = eng.metrics, _calls(eng)
    n_dec, n_chunk = m.decode_steps, m.prefill_chunks
    assert calls["engine.step"] == m.steps > n_dec
    assert calls["engine.decode"] == calls["decode_step"]
    assert calls["engine.prefill_chunk"] == n_chunk == 3
    assert sum(n for k, n in calls.items()
               if k.startswith("prefill_T")) == n_chunk
    assert calls["engine.admit"] >= 3  # a starved head retries a step
    assert calls["engine.dispatch"] == n_dec + n_chunk
    # every step that is read is read ONCE, judged, then emitted. The
    # engine that runs ahead (the default) leaves unread the step it
    # had dispatched past a retirement that emptied every slot (parked
    # lanes only): here once while the third request waits for blocks,
    # once at the end
    n_read = calls["engine.device_wait"] - m.prefills
    assert n_dec - n_read == (2 if eng.async_dispatch else 0)
    assert n_read <= calls["engine.decode"] <= n_dec
    assert calls["engine.integrity"] == n_read + m.prefills
    assert calls["engine.emit"] == n_read + m.prefills
    assert calls["engine.alloc_blocks"] == n_dec + n_chunk
    assert calls["engine.publish"] >= 1
    assert 1 <= calls["engine.upload"] <= n_dec + n_chunk
    # a dozen names and the three older rows: nothing per slot or token
    names = {k for k in calls if k.startswith("engine.")}
    assert names <= NAMES and "engine.expire" not in names
    assert set(calls) - names == {"decode_step", "prefill_T8",
                                  "prefill_T16"}
    assert max(calls.values()) <= 2 * m.steps
    # the per-step dict is the LAST step's, reset at each step's top
    assert m.step_phases["engine.step"] == \
        pytest.approx(m.ops.rows["engine.step"][2], abs=1.0) \
        and set(m.step_phases) <= NAMES
    rep = m.report()
    assert rep["steps"] == m.steps and isinstance(rep["slow_steps"], list)


def test_a_deadline_opens_the_expire_span(model):
    cfg, params = model
    eng = ServingEngine(params, cfg, max_slots=2)
    eng.submit(np.arange(5, dtype=np.int32), 4, deadline_at=0.0)
    eng.run()
    assert _calls(eng)["engine.expire"] == 1 and eng.metrics.expired == 1


def test_spans_reach_the_profilers_trace_and_nest(model, tmp_path):
    """Under a running profiler the names come back through the
    benchmark's own loader — so they pass its filter for host spans —
    on the CPU backend too, and every child lies inside its step."""
    sys.path.insert(0, CHIPBENCH)
    try:
        from lib import spans, xplane
    finally:
        sys.path.remove(CHIPBENCH)
    cfg, params = model
    eng = ServingEngine(params, cfg, max_slots=2)
    _load(eng, n=2)
    eng.run()  # compiled: the traced steps below are ordinary ones
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        step0 = eng.metrics.steps
        _load(eng, n=2)
        eng.run()
    finally:
        jax.profiler.stop_trace()
    raw = xplane.load_xplane(xplane.find_xplane(str(tmp_path)))
    host = [(s, s + d, n) for key, evs in raw["lines"].items()
            if key.startswith(xplane.HOST_PLANE) for n, s, d in evs]
    assert host and all(xplane.OWN_SPAN.match(n) for _, _, n in host)
    names = {n for _, _, n in host}
    assert {"engine.step", "engine.admit", "engine.prefill_chunk",
            "engine.decode", "engine.alloc_blocks", "engine.dispatch",
            "engine.device_wait", "engine.integrity",
            "engine.emit"} <= names <= NAMES
    forest = spans.nest(host)
    # nothing of the engine lies outside a step, and only the four
    # scheduler phases sit directly beneath one
    assert {n.name for n in forest} == {"engine.step"}
    assert len(forest) == eng.metrics.steps - step0
    for step in forest:
        assert {c.name for c in step.children} <= set(TOP)
        for node in step.walk():
            assert step.start <= node.start and node.end <= step.end + 1e-9


def test_the_steps_own_time_is_a_small_share(model):
    """What `engine.step` keeps to itself — the queue loop, the
    fragmentation count, the injector's tick — stays under a fifth of
    the step on a model this small (on the chip: PERF.md)."""
    cfg, params = model
    eng = ServingEngine(params, cfg, max_slots=2)
    _load(eng)
    eng.run()
    before = {k: row[1] for k, row in eng.metrics.ops.rows.items()}
    _load(eng, n=4, max_new=12)
    eng.run()
    spent = {k: row[1] - before.get(k, 0.0)
             for k, row in eng.metrics.ops.rows.items()}
    own = spent["engine.step"] - sum(spent.get(k, 0.0) for k in TOP)
    assert 0.0 <= own < 0.2 * spent["engine.step"], spent


def test_a_slow_step_is_kept_with_its_phases(model, caplog):
    """`delay@N:dur` sleeps in the injector's tick, inside `step()`:
    the step lands in the record, the sleep showing as time of
    `engine.step` that no phase beneath it covers."""
    cfg, params = model
    inj = FaultInjector("")
    eng = ServingEngine(params, cfg, max_slots=2, fault_injector=inj)
    _load(eng)
    eng.run()  # the compiling steps are slow too, and are logged
    first = list(eng.metrics.slow_steps)
    assert first and all(
        r["phases"]["engine.dispatch"] > 0.5 * r["seconds"] for r in first)
    eng.metrics.slow_steps.clear()
    inj.arm("delay@3:0.6")
    target = eng.metrics.steps + 3
    _load(eng)
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.serving"):
        eng.run()
    (rec,) = eng.metrics.slow_steps  # the ordinary steps are not kept
    assert rec["step"] == target and rec["seconds"] >= 0.6
    ph = rec["phases"]
    assert ph["engine.step"] == pytest.approx(rec["seconds"])
    assert ph["engine.step"] - sum(ph.get(k, 0.0) for k in TOP) >= 0.6
    assert eng.metrics.report()["slow_steps"] == [rec]
    (line,) = [r.getMessage() for r in caplog.records
               if "slow engine step" in r.getMessage()]
    assert "'step': %d" % target in line and "engine.decode" in line


def test_only_a_step_over_the_threshold_is_kept():
    m = ServingMetrics(max_slots=2)
    for i in range(12):
        m.steps += 1
        m.observe_step(0.4)
        m.observe_step(0.6 + i)
    assert len(m.slow_steps) == 8  # bounded: the last eight
    assert [r["seconds"] for r in m.slow_steps] == \
        [0.6 + i for i in range(4, 12)]


def test_phase_folds_rows_attrs_and_the_older_name():
    m = ServingMetrics(max_slots=2)
    with m.phase("engine.prefill_chunk", row="prefill_T8", rid=7, bucket=8):
        with m.phase("engine.dispatch") as inner:
            pass
    with pytest.raises(KeyError):
        with m.phase("engine.dispatch"):
            raise KeyError("a failing step still closes its span")
    assert {k: r[0] for k, r in m.ops.rows.items()} == {
        "engine.dispatch": 2, "engine.prefill_chunk": 1, "prefill_T8": 1}
    assert m.ops.rows["prefill_T8"] == m.ops.rows["engine.prefill_chunk"]
    assert inner.t1 >= inner.t0 and m.wall_s >= inner.t1 - inner.t0
    assert set(m.step_phases) == {"engine.dispatch", "engine.prefill_chunk"}
    assert all(re.match(r"^[a-z_]+\.[a-z_.]+$", k) for k in m.step_phases)


@pytest.mark.parametrize("kw,row", [
    ({"async_dispatch": False}, "decode_step"),
    ({"spec_draft_len": 3}, "spec_verify"),
])
def test_the_other_decode_paths_emit_the_same_names(model, kw, row):
    cfg, params = model
    base = ServingEngine(params, cfg, max_slots=2)
    eng = ServingEngine(params, cfg, max_slots=2, **kw)
    for e in (base, eng):
        _load(e)
        e.run()
    calls, plain = _calls(eng), _calls(base)
    engine = lambda c: {k for k in c if k.startswith("engine.")}
    assert engine(calls) == engine(plain)
    assert calls[row] == calls["engine.decode"] >= eng.metrics.decode_steps
    assert calls["engine.dispatch"] == \
        eng.metrics.decode_steps + eng.metrics.prefill_chunks
    assert calls["engine.device_wait"] == calls["engine.emit"]
    assert max(calls.values()) <= 2 * eng.metrics.steps + 2
