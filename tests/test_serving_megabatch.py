"""Megabatch decode window + async dispatch (ISSUE 19,
paddle_tpu/serving — engine.py `decode_window`/`async_dispatch`,
models/transformer.py `decode_window_retire`, metrics.py per-token
EWMA + device-busy union, fleet.py autoscaler headroom clamp):

* Token-identity sweep — every K in {1, 2, 4, 8}, sync and async,
  greedy AND sampled, bit-identical to sequential generate() (or to
  the K=1 sync engine where quantization moves outputs off the f32
  oracle); decode traced exactly ONCE per engine lifetime whatever K.
* Hard paths under the window — prefix-aliased/COW admissions,
  per-tenant LoRA adapters, int8/fp8 KV quantization, EOS retiring a
  slot mid-window (out-of-range parking), integrity traps tripping
  mid-window (iteration j poisons ONLY tokens >= j), speculative
  decode composition refused loudly.
* Window-granularity SLO — a request expiring mid-window expires at
  the window boundary with its pre-window tokens kept (async inflight
  lanes discarded); the fleet autoscaler's deadline headroom clamps to
  the widest live window; the step-latency EWMA is normalized PER
  TOKEN so a K=8 replica is not 8x "slower" than a K=1 peer.
* Failover mid-window — a replica killed between dispatch and sync
  resumes on the survivor token-identically; the journal's progress
  DELTAS concatenate exactly to each request's final token list (no
  lane duplicated, none lost).
* Gray-failure drill at K=8 (slow) — the per-token normalization in
  action: a slow@ replica in a K=8 fleet is demoted, and ONLY it.
* One step ahead by default (ISSUE 28) — a default-constructed engine
  (`async_dispatch=None`) rides the same sweeps as a case of its own:
  token-identical to the lock-step engine and to generate() under
  staggered arrivals, EOS, cancel and expiry, greedy and sampled; a
  steady step makes ONE blocking device-to-host read; nearly every
  step is dispatched before its predecessor is read; a trap at step N
  with N+1 in flight emits nothing of either.
"""

import json
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.distributed.fault_injection import FaultInjector
from paddle_tpu.models import transformer as T
from paddle_tpu.serving import (
    AdapterRegistry,
    IntegrityError,
    RequestJournal,
    ServingEngine,
    ServingFleet,
    make_adapter,
)

_HAS_FP8 = hasattr(jnp, "float8_e4m3fn")
_KVQS = ["int8", "fp8"] if _HAS_FP8 else ["int8"]


def _cfg(**kw):
    kw.setdefault("vocab", 50)
    kw.setdefault("dim", 32)
    kw.setdefault("heads", 4)
    kw.setdefault("layers", 2)
    kw.setdefault("max_len", 64)
    return T.TransformerConfig(**kw)


def _mk(seed=0, **kw):
    cfg = _cfg(**kw)
    return cfg, T.init_params(cfg, jax.random.PRNGKey(seed))


def _oracle(params, cfg, prompt, max_new):
    return np.asarray(
        T.generate(params, jnp.asarray(prompt)[None], cfg, max_new)
    )[0]


def _full(h):
    return np.concatenate([h.prompt, np.asarray(h.tokens, np.int32)])


def _plant_trap(eng, packed, j, s):
    """The packed result of a dispatched step with slot s's trap flag
    of iteration j forged on (layout: `ServingEngine._unpack`)."""
    flat = np.asarray(packed).copy()
    S = eng.max_slots
    flat[j * (2 * S + 1) + S + s] = 1
    return flat


@pytest.fixture(scope="module")
def model():
    return _mk(0)


@pytest.fixture(scope="module")
def workload(model):
    cfg, params = model
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, cfg.vocab, (t,)).astype(np.int32)
               for t in (3, 7, 12, 5, 9, 17)]
    budgets = [6, 9, 5, 11, 4, 7]  # deliberately NOT multiples of K:
    # every variant retires slots mid-window (the parking path)
    oracle = [_oracle(params, cfg, p, n)
              for p, n in zip(prompts, budgets)]
    return prompts, budgets, oracle


# ---------------------------------------------------------------------------
# token-identity sweep: K x async x {greedy, sampled}
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("async_on", [False, True, None])
@pytest.mark.parametrize("K", [1, 2, 4, 8])
def test_greedy_identity_every_window(model, workload, K, async_on):
    """The ISSUE 19 acceptance bar: for every K (and with async
    dispatch on top; None = what a default-constructed engine does,
    ISSUE 28) the engine is bit-identical to sequential generate()
    under staggered arrivals, and decode is compiled exactly once."""
    cfg, params = model
    prompts, budgets, oracle = workload
    eng = ServingEngine(params, cfg, max_slots=2, decode_window=K,
                        async_dispatch=async_on)
    hs = []
    for i, (p, n) in enumerate(zip(prompts, budgets)):
        hs.append(eng.submit(p, n))
        if i % 2 == 1:
            eng.step()  # arrivals keep landing while others decode
    eng.run()
    for h, want in zip(hs, oracle):
        np.testing.assert_array_equal(_full(h), want)
    assert eng.metrics.decode_trace_count() == 1
    assert eng.metrics.prefill_trace_count() <= 3


@pytest.mark.parametrize("K,async_on", [(2, True), (4, True), (8, True),
                                        (1, None), (4, None)])
def test_sampled_identity_window_vs_sequential(model, K, async_on):
    """Sampling must be window-invariant: the fold_in(key, count)
    schedule depends on each slot's emitted-token COUNT, not on how
    many iterations one compiled step covers — a K-window async
    engine's sampled outputs equal the K=1 sync engine's exactly."""
    cfg, params = model
    rng = np.random.RandomState(13)
    reqs = [(rng.randint(0, cfg.vocab, (t,)).astype(np.int32), n, temp)
            for t, n, temp in ((5, 9, 0.8), (11, 7, 1.2), (4, 10, 0.8),
                               (8, 6, 0.0))]  # greedy rides along
    base = ServingEngine(params, cfg, max_slots=2, async_dispatch=False)
    want = []
    for i, (p, n, temp) in enumerate(reqs):
        h = base.submit(p, n, temperature=temp, seed=100 + i)
        h.result()  # drives the engine; returns prompt + tokens
        want.append(list(h.tokens))
    eng = ServingEngine(params, cfg, max_slots=2, decode_window=K,
                        async_dispatch=async_on)
    assert eng.async_dispatch
    hs = [eng.submit(p, n, temperature=temp, seed=100 + i)
          for i, (p, n, temp) in enumerate(reqs)]
    eng.run()
    for h, w in zip(hs, want):
        assert list(h.tokens) == w
    assert eng.metrics.decode_trace_count() == 1


@pytest.mark.parametrize("kw", [
    {"decode_window": 4, "async_dispatch": False},
    {"decode_window": 4, "async_dispatch": True}, {}],
    ids=["K4_sync", "K4_async", "default"])
def test_eos_mid_window_identity(model, kw):
    """A slot hitting EOS at a window-interior iteration — or, for the
    default engine, at a step whose successor is already in flight —
    retires on the device (same rule) and parks its remaining lanes;
    output equals the lock-step engine with the same eos_id,
    finish_reason included."""
    cfg, params = model
    p = np.arange(2, 9, dtype=np.int32)
    base = ServingEngine(params, cfg, max_slots=1, async_dispatch=False)
    hf = base.submit(p, 12)
    hf.result()
    eos = int(hf.tokens[2])  # EOS lands at generated index 2: mid-window
    hb = ServingEngine(params, cfg, max_slots=1, async_dispatch=False) \
        .submit(p, 12, eos_id=eos)
    hb.result()
    want = list(hb.tokens)
    assert want[-1] == eos and len(want) < 12
    eng = ServingEngine(params, cfg, max_slots=1, **kw)
    h = eng.submit(p, 12, eos_id=eos)
    eng.run()
    assert list(h.tokens) == want
    assert h.finish_reason == "eos"
    # the slot and its blocks are free again: nothing of the step
    # that ran past the EOS leaked into the next tenant
    h2 = eng.submit(p, 5)
    eng.run()
    assert list(h2.tokens) == list(hf.tokens[:5])


def test_spec_decode_composition_refused(model):
    """ISSUE 19 allows composing spec decode with the window or
    refusing loudly; this build refuses — both knobs, not just one."""
    cfg, params = model
    with pytest.raises(ValueError, match="spec_draft_len composes"):
        ServingEngine(params, cfg, max_slots=2, spec_draft_len=3,
                      decode_window=4)
    with pytest.raises(ValueError, match="spec_draft_len composes"):
        ServingEngine(params, cfg, max_slots=2, spec_draft_len=3,
                      async_dispatch=True)


def test_compile_count_regression_window(model):
    """A K=8 async session over mixed prompt lengths traces prefill
    <= #buckets and decode EXACTLY once; a second wave on the same
    engine retraces nothing (window size and dispatch depth must not
    leak into compiled shapes)."""
    cfg, params = model
    rng = np.random.RandomState(3)
    lengths = [3, 5, 8, 12, 16, 20, 4, 9]
    eng = ServingEngine(params, cfg, max_slots=4, decode_window=8,
                        async_dispatch=True)
    for t in lengths:
        eng.submit(rng.randint(0, cfg.vocab, (t,)).astype(np.int32), 5)
    eng.run()
    buckets = {eng._bucket(t) for t in lengths}
    assert eng.metrics.prefill_trace_count() <= len(buckets)
    assert eng.metrics.decode_trace_count() == 1
    before = dict(eng.metrics.trace_counts)
    for t in lengths:
        eng.submit(rng.randint(0, cfg.vocab, (t,)).astype(np.int32), 6)
    eng.run()
    assert eng.metrics.trace_counts == before


# ---------------------------------------------------------------------------
# hard paths: prefix/COW, adapters, quantization, traps
# ---------------------------------------------------------------------------

def test_prefix_alias_and_cow_identity_under_window(model):
    """Paged scatter writes inside the scan must respect the aliasing
    discipline: the COW drill from test_serving_engine (whole-prompt
    re-admit privatises the shared tail block) run at K=4 async —
    same counters, outputs oracle-identical."""
    cfg, params = _mk(21)
    rng = np.random.RandomState(21)
    p = rng.randint(0, cfg.vocab, (8,)).astype(np.int32)  # 2 x Bt=4
    want = _oracle(params, cfg, p, 5)
    eng = ServingEngine(params, cfg, max_slots=2, kv_block_tokens=4,
                        prefix_cache_tokens=64, decode_window=4,
                        async_dispatch=True)
    h1 = eng.submit(p, 5)
    eng.run()
    assert eng.metrics.cow_blocks == 0  # cold publish: nothing shared
    h2 = eng.submit(p, 5)
    eng.run()
    assert eng.metrics.cow_blocks == 1  # tail block privatised
    h3 = eng.submit(p, 5)
    eng.run()
    assert eng.metrics.cow_blocks == 2
    for h in (h1, h2, h3):
        np.testing.assert_array_equal(_full(h), want)
    assert eng.prefix_cache.stats()["hits"] >= 2
    assert eng.metrics.decode_trace_count() == 1


def test_adapter_identity_under_window(model):
    """Per-slot LoRA gathers ride the window's compiled step: a K=4
    async multi-tenant batch decodes exactly what per-request K=1 sync
    engines decode, zero-adapter rows included."""
    cfg, params = model
    reg = AdapterRegistry()
    reg.register("ad_a", make_adapter(cfg, rank=4, seed=1))
    reg.register("ad_b", make_adapter(cfg, rank=4, seed=2))
    rng = np.random.RandomState(5)
    plan = [("ad_a", rng.randint(0, cfg.vocab, (6,)).astype(np.int32)),
            ("ad_b", rng.randint(0, cfg.vocab, (9,)).astype(np.int32)),
            (None, rng.randint(0, cfg.vocab, (4,)).astype(np.int32))]
    want = []
    for a, p in plan:
        seq = ServingEngine(params, cfg, max_slots=1,
                            adapter_registry=reg, adapter_slots=3)
        sh = seq.submit(p, 6, adapter=a)
        sh.result()
        want.append(list(sh.tokens))
    eng = ServingEngine(params, cfg, max_slots=3, adapter_registry=reg,
                        adapter_slots=3, decode_window=4,
                        async_dispatch=True)
    hs = [eng.submit(p, 6, adapter=a) for a, p in plan]
    eng.run()
    for h, w in zip(hs, want):
        assert list(h.tokens) == w
    assert eng.metrics.decode_trace_count() == 1


@pytest.mark.parametrize("kvq", _KVQS)
def test_kv_quant_identity_under_window(model, kvq):
    """Quantized blocks commit scales at open and round-trip through
    the scan's per-iteration writes: a K=4 async engine matches the
    K=1 sync engine under the SAME storage dtype (quantization moves
    outputs off the f32 oracle, so the bar is engine-vs-engine)."""
    cfg, params = model
    rng = np.random.RandomState(9)
    reqs = [(rng.randint(0, cfg.vocab, (t,)).astype(np.int32), n)
            for t, n in ((5, 8), (12, 6), (7, 9))]
    base = ServingEngine(params, cfg, max_slots=2, kv_quant=kvq)
    want = []
    for p, n in reqs:
        bh = base.submit(p, n)
        bh.result()
        want.append(list(bh.tokens))
    eng = ServingEngine(params, cfg, max_slots=2, kv_quant=kvq,
                        decode_window=4, async_dispatch=True)
    hs = [eng.submit(p, n) for p, n in reqs]
    eng.run()
    for h, w in zip(hs, want):
        assert list(h.tokens) == w
    assert eng.metrics.decode_trace_count() == 1


def test_trap_in_first_window_emits_nothing(model):
    """Poisoned params trip the trap at iteration 0 of the first
    window: the request's handle carries the IntegrityError and ZERO
    tokens — no token from a poisoned window reaches a handle."""
    cfg, params = model
    prompt = np.arange(1, 6, dtype=np.int32)
    bad = jax.tree_util.tree_map(lambda x: x, params)
    bad["embed"] = params["embed"].at[int(prompt[-1])].set(jnp.nan)
    eng = ServingEngine(bad, cfg, max_slots=2, decode_window=4,
                        async_dispatch=True)
    h = eng.submit(prompt, 8)
    with pytest.raises(IntegrityError) as ei:
        h.result()
    assert ei.value.kind == "trap"
    assert h.tokens == []


def test_trap_mid_window_poisons_only_the_tail(model):
    """The tentpole's trap-accumulation rule, white-box: integrity
    rows are judged in iteration order BEFORE their tokens emit, so a
    trip forged at iteration j=2 of a real dispatched window lets
    j=0,1 emit (still oracle-identical) and poisons tokens >= j."""
    cfg, params = model
    p = np.arange(1, 8, dtype=np.int32)
    want = list(_oracle(params, cfg, p, 16)[len(p):])
    eng = ServingEngine(params, cfg, max_slots=2, decode_window=4)
    h = eng.submit(p, 16)
    while not h.tokens:
        eng.step()
    n0 = len(h.tokens)
    s = next(i for i, hh in enumerate(eng._slot_req) if hh is h)
    rec = eng._dispatch_window()  # a REAL window off current state
    rec["packed"] = _plant_trap(eng, rec["packed"], 2, s)
    with pytest.raises(IntegrityError) as ei:
        eng._sync_window(rec)
    assert ei.value.kind == "trap"
    assert len(h.tokens) == n0 + 2  # iterations 0,1 emitted; >=2 poisoned
    assert list(h.tokens) == want[:n0 + 2]


# ---------------------------------------------------------------------------
# window-granularity SLO: expiry at the boundary, autoscaler clamp,
# per-token health gauges
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K,async_on", [(4, False), (4, True), (1, None)])
def test_expiry_at_window_boundary_keeps_pre_window_tokens(model, K,
                                                           async_on):
    """The documented enforcement granularity: a deadline dying
    mid-window expires the request at the NEXT window boundary — every
    token already synced is kept (always a whole number of windows
    past the prefill token), nothing from a discarded inflight window
    leaks in, and the engine keeps serving."""
    cfg, params = model
    p = np.arange(3, 10, dtype=np.int32)
    want = list(_oracle(params, cfg, p, 24)[len(p):])
    eng = ServingEngine(params, cfg, max_slots=2, decode_window=K,
                        async_dispatch=async_on)
    h = eng.submit(p, 24, deadline_at=time.monotonic() + 3600.0)
    while len(h.tokens) < 5:
        eng.step()
    n0 = len(h.tokens)
    assert (n0 - 1) % K == 0  # prefill token + whole windows only
    h.deadline_at = time.monotonic() - 1.0  # dies mid-window
    eng.step()
    assert h.done and h.finish_reason == "expired"
    assert len(h.tokens) == n0  # pre-window tokens kept, nothing more
    assert list(h.tokens) == want[:n0]
    assert eng.metrics.expired == 1
    h2 = eng.submit(p, 6)  # discarded lanes freed the slot cleanly
    eng.run()
    assert list(h2.tokens) == want[:6]


@pytest.mark.parametrize("async_on", [None, False])
def test_cancel_mid_decode_identity(model, async_on):
    """A request cancelled between steps — for the default engine with
    a step in flight that still decodes it — keeps a prefix of its
    oracle tokens and nothing of the step in flight; its neighbour and
    the slot's next tenant are oracle-identical."""
    cfg, params = model
    pa_, pb = np.arange(3, 10, dtype=np.int32), np.arange(5, 16,
                                                          dtype=np.int32)
    want_a = list(_oracle(params, cfg, pa_, 20)[len(pa_):])
    want_b = list(_oracle(params, cfg, pb, 14)[len(pb):])
    eng = ServingEngine(params, cfg, max_slots=2, async_dispatch=async_on)
    ha, hb = eng.submit(pa_, 20), eng.submit(pb, 14)
    while len(ha.tokens) < 6:
        eng.step()
    n0 = len(ha.tokens)
    assert eng.cancel(ha.rid)
    hc = eng.submit(pa_, 9)  # re-tenants the cancelled slot
    eng.run()
    assert ha.finish_reason == "cancelled"
    assert list(ha.tokens) == want_a[:n0]
    assert list(hb.tokens) == want_b
    assert list(hc.tokens) == want_a[:9]
    assert eng.metrics.decode_trace_count() == 1


def _steady_engine(model, n_new=40):
    """A default engine past its admissions: two slots decoding, no
    host event to come for `n_new` steps."""
    cfg, params = model
    eng = ServingEngine(params, cfg, max_slots=2)
    hs = [eng.submit(np.arange(2, 2 + t, dtype=np.int32), n_new)
          for t in (5, 9)]
    while min(len(h.tokens) for h in hs) < 3:
        eng.step()
    return eng, hs


def test_steady_step_makes_one_blocking_read(model, monkeypatch):
    """ISSUE 28's second half: everything the host needs of a step —
    tokens, trap flags, magnitude, the advanced bands — comes back in
    ONE array. Every device-to-host read the engine makes goes through
    `np.asarray`; counted here by the phase open around it, a steady
    step makes exactly one, under `engine.device_wait` —
    `engine.integrity` judges host values and reads nothing."""
    from paddle_tpu.serving import engine as engine_mod

    eng, hs = _steady_engine(model)
    open_phases, reads = [], []
    real_phase = eng.metrics.phase

    class _Tracked(object):
        def __init__(self, name, ph):
            self.name, self.ph = name, ph

        def __enter__(self):
            open_phases.append(self.name)
            return self.ph.__enter__()

        def __exit__(self, *exc):
            open_phases.pop()
            return self.ph.__exit__(*exc)

    class _Numpy(object):
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(x, *a, **kw):
            if isinstance(x, jax.Array):
                reads.append(open_phases[-1] if open_phases else None)
            return np.asarray(x, *a, **kw)

    monkeypatch.setattr(
        eng.metrics, "phase",
        lambda name, row=None, **kw: _Tracked(name,
                                              real_phase(name, row, **kw)))
    monkeypatch.setattr(engine_mod, "np", _Numpy())
    rows = eng.metrics.ops.rows
    waits0, integ0 = rows["engine.device_wait"][0], \
        rows["engine.integrity"][0]
    n = 12
    for _ in range(n):
        before = len(reads)
        eng.step()
        assert reads[before:] == ["engine.device_wait"]
    assert rows["engine.device_wait"][0] - waits0 == n
    assert rows["engine.integrity"][0] - integ0 == n  # judged, not read
    monkeypatch.undo()
    eng.run()
    cfg, params = model
    for h in hs:
        np.testing.assert_array_equal(
            _full(h), _oracle(params, cfg, h.prompt, 40))


def test_steady_run_is_dispatched_ahead(model):
    """With no host event after the admissions, every decode step but
    the pipeline's first is dispatched before its predecessor is read:
    the counters say so, in `report()` too."""
    eng, hs = _steady_engine(model)
    m = eng.metrics
    steps0, ahead0, breaks0 = (m.decode_steps, m.decode_dispatched_ahead,
                               m.decode_chain_breaks)
    for _ in range(20):
        eng.step()
    assert m.decode_steps - steps0 == 20
    assert m.decode_dispatched_ahead - ahead0 == 20
    assert m.decode_chain_breaks == breaks0
    eng.run()
    rep = m.report()
    assert rep["decode_dispatched_ahead"] == m.decode_dispatched_ahead
    assert rep["decode_chain_breaks"] == m.decode_chain_breaks
    # admissions and the last step included, the share stays high
    assert m.decode_dispatched_ahead / m.decode_steps >= 0.8
    assert m.decode_trace_count() == 1


def test_trap_with_next_step_in_flight_emits_neither(model):
    """The integrity rule one step ahead: a step's trap flags are
    judged before any of its tokens reach a handle. A trap planted in
    step N, read while N+1 is already in flight, emits no token of N
    or N+1 and latches the engine."""
    from paddle_tpu.serving import EngineFailed

    eng, hs = _steady_engine(model)
    n0 = [len(h.tokens) for h in hs]
    steps0 = eng.metrics.decode_steps
    rec = eng._inflight  # step N, dispatched and not yet read
    s = rec["slots"][0][0]
    rec["packed"] = _plant_trap(eng, rec["packed"], 0, s)
    with pytest.raises(IntegrityError) as ei:
        eng.step()
    assert ei.value.kind == "trap"
    assert eng.metrics.decode_steps == steps0 + 1  # N+1 was in flight
    assert [len(h.tokens) for h in hs] == n0
    assert eng._inflight is None  # N+1 is never read
    with pytest.raises(EngineFailed):
        eng.step()
    for h in hs:
        assert isinstance(h.error, EngineFailed)
    assert [len(h.tokens) for h in hs] == n0


def test_step_ewma_normalized_per_token():
    """metrics.observe_step(dt, tokens=K) folds dt/K: a K=8 window
    engine's 0.8s step scores exactly like a K=1 engine's 0.1s step
    (the fleet's gray-failure factor compares replicas across K)."""
    from paddle_tpu.serving.metrics import ServingMetrics
    a = ServingMetrics(2)
    a.observe_step(0.8, tokens=8)
    assert a.step_ewma_s == pytest.approx(0.1)
    a.observe_step(0.8, tokens=8)
    assert a.step_ewma_s == pytest.approx(0.1)
    b = ServingMetrics(2)
    b.observe_step(0.1)  # K=1 default: original per-step semantics
    assert b.step_ewma_s == pytest.approx(a.step_ewma_s)


def test_device_busy_union_never_double_counts():
    """observe_device_interval folds dispatch->sync spans as a UNION:
    async windows overlapping their predecessor accrue only the time
    past the watermark, so host_overhead_frac stays in [0, 1]."""
    from paddle_tpu.serving.metrics import ServingMetrics
    m = ServingMetrics(2)
    m.observe_device_interval(10.0, 11.0)
    m.observe_device_interval(10.5, 11.5)  # overlaps: +0.5 only
    m.observe_device_interval(10.0, 11.2)  # fully covered: +0
    m.observe_device_interval(12.0, 12.25)
    assert m.device_busy_s == pytest.approx(1.75)


def test_autoscaler_headroom_clamps_to_window_time(model, tmp_path):
    """Satellite 2: deadline-pressure scale-up must not fire on
    lateness the window itself guarantees — the clamp is K times the
    per-token EWMA of the widest live replica, and exactly 0.0 for a
    K=1 fleet (pre-window behavior untouched)."""
    cfg, params = model
    fleet = ServingFleet(params, cfg, n_replicas=1,
                         heartbeat_timeout_s=60.0,
                         journal_path=str(tmp_path / "j.jsonl"),
                         engine_kw={"max_slots": 2,
                                    "decode_window": 4})
    try:
        fleet.submit(np.arange(1, 8, dtype=np.int32), 8).result(
            timeout=120)
        with fleet._cond:
            w = fleet._window_headroom_s()
            ewma = float(fleet._rep_stats[0]["step_ewma_s"])
        assert w == pytest.approx(4.0 * ewma) and w > 0.0
    finally:
        fleet.close()
    plain = ServingFleet(params, cfg, n_replicas=1,
                         heartbeat_timeout_s=60.0,
                         journal_path=str(tmp_path / "j2.jsonl"),
                         engine_kw={"max_slots": 2})
    try:
        plain.submit(np.arange(1, 8, dtype=np.int32), 4).result(
            timeout=120)
        with plain._cond:
            assert plain._window_headroom_s() == 0.0
    finally:
        plain.close()


# ---------------------------------------------------------------------------
# fleet: failover mid-window, gray drill at K=8
# ---------------------------------------------------------------------------

def test_failover_mid_window_journal_deltas_concatenate(model,
                                                        tmp_path):
    """Resume-mid-window drill: r0 dies between windows of its first
    batch (exc@3); every request completes on the survivor
    token-identical to generate(), and each rid's journal progress
    DELTAS — emitted in K-token window batches, spliced across the
    failover — concatenate EXACTLY to its final token list (no lane
    duplicated at the resume point, none lost)."""
    cfg, params = model
    rng = np.random.RandomState(17)
    reqs = [(rng.randint(0, cfg.vocab, (int(rng.randint(4, 13)),)
                         ).astype(np.int32), int(rng.randint(9, 14)))
            for _ in range(4)]
    oracle = [_oracle(params, cfg, p, n) for p, n in reqs]
    journal = str(tmp_path / "journal.jsonl")
    inj = FaultInjector("exc@3")
    fleet = ServingFleet(
        params, cfg, n_replicas=2, heartbeat_timeout_s=60.0,
        journal_path=journal,
        engine_kw={"max_slots": 2, "decode_window": 4},
        engine_kw_for=lambda i: (
            {"fault_injector": inj} if i == 0 else {}))
    try:
        hs = [fleet.submit(p, n) for p, n in reqs]
        for h, want in zip(hs, oracle):
            np.testing.assert_array_equal(h.result(timeout=180), want)
        st = fleet.stats()
        assert st["failovers"] == 1 and st["lost"] == 0, st
        assert st["completed"] == 4, st
        lines = [json.loads(l) for l in open(journal)]
        done = sorted(r["rid"] for r in lines if r["kind"] == "done")
        assert done == [h.rid for h in hs]
        assert RequestJournal.recover(journal) == []
        for h in hs:
            deltas = [t for r in lines
                      if r["kind"] == "progress" and r["rid"] == h.rid
                      for t in r["tokens"]]
            assert deltas == list(h.tokens), (h.rid, deltas, h.tokens)
    finally:
        fleet.close()


def _warm_all_buckets(fleet, n_replicas=2):
    # compile every drill shape on every replica BEFORE arming any
    # fault (first-compile latency is the documented false-demotion
    # hazard), then let the EWMAs settle. A K=8 engine needs a DEEPER
    # warm than the K=1 drill: one compiled window covers 8 tokens, so
    # a small budget is only 1-2 steps and the per-token EWMA would
    # still carry the compile spike into the health judgement — two
    # 24-token waves per bucket give every replica ~8 healthy folds
    for _ in range(2):
        for L in (8, 16):
            ws = [fleet.submit(np.arange(1, L + 1, dtype=np.int32),
                               24, seed=k) for k in range(n_replicas)]
            for h in ws:
                h.result(timeout=180)
    time.sleep(0.3)


@pytest.mark.slow  # real gray window (1.6s slow@), like the K=1 drill
def test_gray_slow_replica_demoted_at_k8(model):
    """Satellite 1 regression: in a decode_window=8 fleet the health
    score still singles out the genuinely slow replica — the EWMA is
    per-token, so r1's legitimate 8-token steps never look like
    stalls. slow@ r0 is demoted (and ONLY r0), its work completes on
    the survivor token-identically, and it is probed back live."""
    cfg, params = model
    rng = np.random.RandomState(23)
    reqs = [(rng.randint(0, cfg.vocab, (int(rng.randint(4, 13)),)
                         ).astype(np.int32), 40) for _ in range(4)]
    inj = FaultInjector("")  # inert until armed post-warm-up
    fleet = ServingFleet(
        params, cfg, n_replicas=2, heartbeat_timeout_s=60.0,
        monitor_interval_s=0.05, slow_replica_factor=4.0,
        slow_min_duration_s=0.3, probe_interval_s=0.15,
        engine_kw={"max_slots": 2, "decode_window": 8},
        engine_kw_for=lambda i: (
            {"fault_injector": inj} if i == 0 else {}))
    try:
        _warm_all_buckets(fleet)
        inj.arm("slow@2:1.6/0.2")  # gray window: 1.6s of 0.2s steps
        hs = [fleet.submit(p, n) for p, n in reqs]
        for h in hs:
            h.result(timeout=120)
        st = fleet.stats()
        assert st["demotions"] == 1, st  # ONLY the slow replica
        assert st["replicas"][1]["state"] == "live", st
        assert st["lost"] == 0 and st["failovers"] == 0, st
        for h, (p, n) in zip(hs, reqs):
            np.testing.assert_array_equal(
                np.asarray(h.tokens, np.int32),
                _oracle(params, cfg, p, n)[len(p):])
        deadline = time.monotonic() + 60
        while fleet.stats()["replicas"][0]["state"] != "live":
            assert time.monotonic() < deadline, fleet.stats()
            time.sleep(0.05)
        assert fleet.stats()["restores"] == 1
        assert fleet.stats()["replicas"][0]["incarnation"] == 1
    finally:
        fleet.close()
