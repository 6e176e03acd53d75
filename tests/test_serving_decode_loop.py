"""The engine's one decode program and one decode loop (ISSUE 29;
paddle_tpu/serving/engine.py `_make_decode` / `_decode_phase` /
`_dispatch_decode` / `_read_decode`, models/transformer.py
`decode_retire`). The loop has two depths, and every drill here runs
at both: `async_dispatch=None` (what a default engine does — one step
ahead of the host, ISSUE 28) and `async_dispatch=False` (lock-step:
dispatch and read in the same `step()`, the tests' reference).

* Token identity — greedy AND sampled, bit-identical to sequential
  generate() (or to the lock-step engine where quantization moves
  outputs off the f32 oracle) under staggered arrivals, EOS retiring
  a slot on the device, cancel and expiry; decode traced exactly ONCE
  per engine lifetime at either depth, and the same program.
* Hard paths — prefix-aliased/COW admissions, per-tenant LoRA
  adapters, int8/fp8 KV quantization, integrity traps (a tripped step
  emits nothing; a trap at step N with N+1 in flight emits nothing of
  either), speculative decode composition refused loudly.
* The one read — a steady step makes ONE blocking device-to-host
  read, of one packed array whose layout `_unpack` inverts; ahead,
  nearly every step is dispatched before its predecessor is read;
  lock-step, none is and none is left in flight.
* Failover between dispatch and read — a replica killed mid-decode
  resumes on the survivor token-identically; the journal's progress
  DELTAS concatenate exactly to each request's final token list (no
  lane duplicated, none lost).
* What went with the K-token window (`decode_window`) is gone by name:
  an unknown keyword is Python's TypeError.
"""

import json
import re
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


def _plant_trap(eng, packed, s):
    """The packed result of a dispatched step with slot s's trap flag
    forged on (layout: `ServingEngine._unpack`)."""
    flat = np.asarray(packed).copy()
    flat[eng.max_slots + s] = 1
    return flat


DEPTHS = pytest.mark.parametrize("async_on", [None, False],
                                 ids=["ahead", "lockstep"])


@pytest.fixture(scope="module")
def model():
    return _mk(0)


@pytest.fixture(scope="module")
def workload(model):
    cfg, params = model
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, cfg.vocab, (t,)).astype(np.int32)
               for t in (3, 7, 12, 5, 9, 17)]
    budgets = [6, 9, 5, 11, 4, 7]  # uneven: slots retire (and park
    # on the device) while their neighbours keep decoding
    oracle = [_oracle(params, cfg, p, n)
              for p, n in zip(prompts, budgets)]
    return prompts, budgets, oracle


# ---------------------------------------------------------------------------
# token-identity sweep: depth x {greedy, sampled}
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("async_on", [False, True, None])
def test_greedy_identity_every_depth(model, workload, async_on):
    """At either depth of the loop (None = what a default-constructed
    engine does, ISSUE 28) the engine is bit-identical to sequential
    generate() under staggered arrivals, and decode is compiled
    exactly once."""
    cfg, params = model
    prompts, budgets, oracle = workload
    eng = ServingEngine(params, cfg, max_slots=2,
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


@pytest.mark.parametrize("async_on", [True, None])
def test_sampled_identity_ahead_vs_lockstep(model, async_on):
    """Sampling must not depend on the loop's depth: the
    fold_in(key, count) schedule depends on each slot's emitted-token
    COUNT, advanced on the device — an engine running ahead samples
    exactly what the lock-step engine samples."""
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
    eng = ServingEngine(params, cfg, max_slots=2,
                        async_dispatch=async_on)
    assert eng.async_dispatch
    hs = [eng.submit(p, n, temperature=temp, seed=100 + i)
          for i, (p, n, temp) in enumerate(reqs)]
    eng.run()
    for h, w in zip(hs, want):
        assert list(h.tokens) == w
    assert eng.metrics.decode_trace_count() == 1


@pytest.mark.parametrize("kw", [{"async_dispatch": True}, {}],
                         ids=["ahead", "default"])
def test_eos_with_next_step_in_flight_identity(model, kw):
    """A slot hitting EOS at a step whose successor is already in
    flight retires on the device (same rule as the host's) and parks
    its lane of that successor; output equals the lock-step engine
    with the same eos_id, finish_reason included."""
    cfg, params = model
    p = np.arange(2, 9, dtype=np.int32)
    base = ServingEngine(params, cfg, max_slots=1, async_dispatch=False)
    hf = base.submit(p, 12)
    hf.result()
    eos = int(hf.tokens[2])  # EOS lands at generated index 2
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
    """Speculative acceptance is a host decision after every verify:
    asking for it one step ahead is refused loudly; left to itself
    (None) a speculative engine is lock-step."""
    cfg, params = model
    with pytest.raises(ValueError,
                       match="spec_draft_len does not compose"):
        ServingEngine(params, cfg, max_slots=2, spec_draft_len=3,
                      async_dispatch=True)
    assert not ServingEngine(params, cfg, max_slots=2,
                             spec_draft_len=3).async_dispatch


@pytest.mark.parametrize("value", [4, None])
def test_the_window_option_is_gone(model, value):
    """`decode_window` went with the K-token window (ISSUE 29): an
    unknown keyword is Python's TypeError, whatever its value — it is
    not accepted and ignored."""
    cfg, params = model
    with pytest.raises(TypeError, match="decode_window"):
        ServingEngine(params, cfg, max_slots=2, decode_window=value)


def test_one_builder_of_the_plain_decode_program_and_27_options():
    """Exactly one method of ServingEngine builds a plain decode
    program, and the constructor takes 27 options."""
    import inspect

    builders = [n for n, f in vars(ServingEngine).items()
                if callable(f) and n.startswith("_make_decode")]
    assert builders == ["_make_decode"]
    # and one loop: a phase, a dispatch and a read, nothing else that
    # dispatches or reads a plain decode step
    loop = sorted(n for n, f in vars(ServingEngine).items() if callable(f)
                  and re.match(r"_(decode|dispatch|read|sync|window)_", n))
    assert loop == ["_decode_phase", "_dispatch_decode", "_read_decode"]
    opts = [p for p in inspect.signature(
        ServingEngine.__init__).parameters.values()
        if p.default is not inspect.Parameter.empty]
    assert len(opts) == 27 and "decode_window" not in [p.name for p in opts]


@DEPTHS
def test_compile_count_regression(model, async_on):
    """A session over mixed prompt lengths traces prefill <= #buckets
    and decode EXACTLY once; a second wave on the same engine retraces
    nothing (the loop's depth must not leak into compiled shapes)."""
    cfg, params = model
    rng = np.random.RandomState(3)
    lengths = [3, 5, 8, 12, 16, 20, 4, 9]
    eng = ServingEngine(params, cfg, max_slots=4,
                        async_dispatch=async_on)
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

@DEPTHS
def test_prefix_alias_and_cow_identity(model, async_on):
    """The decode step's paged scatter writes must respect the
    aliasing discipline whether or not a step is in flight when the
    next tenant is admitted: the COW drill from test_serving_engine
    (whole-prompt re-admit privatises the shared tail block) at either
    depth — same counters, outputs oracle-identical."""
    cfg, params = _mk(21)
    rng = np.random.RandomState(21)
    p = rng.randint(0, cfg.vocab, (8,)).astype(np.int32)  # 2 x Bt=4
    want = _oracle(params, cfg, p, 5)
    eng = ServingEngine(params, cfg, max_slots=2, kv_block_tokens=4,
                        prefix_cache_tokens=64,
                        async_dispatch=async_on)
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


@DEPTHS
def test_adapter_identity(model, async_on):
    """Per-slot LoRA gathers ride the one compiled step: a
    multi-tenant batch at either depth decodes exactly what
    per-request lock-step engines decode, zero-adapter rows
    included."""
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
                            adapter_registry=reg, adapter_slots=3,
                            async_dispatch=False)
        sh = seq.submit(p, 6, adapter=a)
        sh.result()
        want.append(list(sh.tokens))
    eng = ServingEngine(params, cfg, max_slots=3, adapter_registry=reg,
                        adapter_slots=3, async_dispatch=async_on)
    hs = [eng.submit(p, 6, adapter=a) for a, p in plan]
    eng.run()
    for h, w in zip(hs, want):
        assert list(h.tokens) == w
    assert eng.metrics.decode_trace_count() == 1


@DEPTHS
@pytest.mark.parametrize("kvq", _KVQS)
def test_kv_quant_identity(model, kvq, async_on):
    """Quantized blocks commit scales at open and round-trip through
    the step's writes: two slots at either depth match a one-slot
    lock-step engine under the SAME storage dtype (quantization moves
    outputs off the f32 oracle, so the bar is engine-vs-engine)."""
    cfg, params = model
    rng = np.random.RandomState(9)
    reqs = [(rng.randint(0, cfg.vocab, (t,)).astype(np.int32), n)
            for t, n in ((5, 8), (12, 6), (7, 9))]
    base = ServingEngine(params, cfg, max_slots=1, kv_quant=kvq,
                         async_dispatch=False)
    want = []
    for p, n in reqs:
        bh = base.submit(p, n)
        bh.result()
        want.append(list(bh.tokens))
    eng = ServingEngine(params, cfg, max_slots=2, kv_quant=kvq,
                        async_dispatch=async_on)
    hs = [eng.submit(p, n) for p, n in reqs]
    eng.run()
    for h, w in zip(hs, want):
        assert list(h.tokens) == w
    assert eng.metrics.decode_trace_count() == 1


@DEPTHS
def test_trap_in_first_step_emits_nothing(model, async_on):
    """Poisoned params trip the trap in the first compiled step: the
    request's handle carries the IntegrityError and ZERO tokens — no
    token from a poisoned step reaches a handle."""
    cfg, params = model
    prompt = np.arange(1, 6, dtype=np.int32)
    bad = jax.tree_util.tree_map(lambda x: x, params)
    bad["embed"] = params["embed"].at[int(prompt[-1])].set(jnp.nan)
    eng = ServingEngine(bad, cfg, max_slots=2, async_dispatch=async_on)
    h = eng.submit(prompt, 8)
    with pytest.raises(IntegrityError) as ei:
        h.result()
    assert ei.value.kind == "trap"
    assert h.tokens == []


def test_a_trapped_lockstep_step_emits_nothing_of_itself(model):
    """The integrity rule at the lock-step depth, white-box: the trap
    flags of a step are judged from the host values of its one read
    BEFORE any of its tokens emit. A trap forged into a real
    dispatched step raises and leaves the handles as they were."""
    cfg, params = model
    p = np.arange(1, 8, dtype=np.int32)
    want = list(_oracle(params, cfg, p, 16)[len(p):])
    eng = ServingEngine(params, cfg, max_slots=2, async_dispatch=False)
    h = eng.submit(p, 16)
    while len(h.tokens) < 3:
        eng.step()
    n0 = len(h.tokens)
    s = next(i for i, hh in enumerate(eng._slot_req) if hh is h)
    rec = eng._dispatch_decode()  # a REAL step off current state
    rec["packed"] = _plant_trap(eng, rec["packed"], s)
    with pytest.raises(IntegrityError) as ei:
        eng._read_decode(rec)
    assert ei.value.kind == "trap"
    assert list(h.tokens) == want[:n0]  # nothing of the trapped step


def test_packed_result_carries_what_the_program_returns(model):
    """`_unpack` is the inverse of the program's one packed output:
    the tokens of live lanes and -1 for parked ones, no trap, a finite
    magnitude, and the four advanced bands equal to the device arrays
    the next step would chain off — with the slot that just spent its
    budget retired ON THE DEVICE — and, last, the counters of a family
    whose step computes some (none here)."""
    cfg, params = model
    eng = ServingEngine(params, cfg, max_slots=3, async_dispatch=False)
    ha = eng.submit(np.arange(2, 9, dtype=np.int32), 3)  # budget: 3
    hb = eng.submit(np.arange(4, 9, dtype=np.int32), 9)
    while len(ha.tokens) < 2:
        eng.step()  # both prefilled, one decode step read
    rec = eng._dispatch_decode()
    toks, traps, scale, bands, stats = eng._unpack(rec["packed"])
    assert len(stats) == 0  # this family's step computes no counters
    slots = {h.rid: s for s, h in rec["slots"]}
    sa, sb = slots[ha.rid], slots[hb.rid]
    dead = [s for s in range(3) if s not in (sa, sb)]
    assert toks[sa] >= 0 and toks[sb] >= 0 and (toks[dead] == -1).all()
    assert not traps.any() and np.isfinite(scale) and scale > 0
    for band, dev in zip(bands, rec["bands"]):
        np.testing.assert_array_equal(band, np.asarray(dev))
    tok, pos, alive, counts = bands
    assert not alive[sa] and alive[sb]  # a's third token was its last
    assert pos[sb] == eng._pos[sb] + 1 and tok[sb] == toks[sb]
    eng._read_decode(rec)
    assert ha.done and ha.finish_reason == "budget" and not hb.done
    eng.run()
    np.testing.assert_array_equal(_full(ha), _oracle(params, cfg,
                                                     ha.prompt, 3))
    np.testing.assert_array_equal(_full(hb), _oracle(params, cfg,
                                                     hb.prompt, 9))


# ---------------------------------------------------------------------------
# SLO: expiry and cancel between steps, the health gauge
# ---------------------------------------------------------------------------

@DEPTHS
def test_expiry_keeps_the_tokens_already_read(model, async_on):
    """A deadline dying between two steps expires the request at the
    next step(): every token already read is kept, nothing of a
    discarded step in flight leaks in, and the engine keeps
    serving."""
    cfg, params = model
    p = np.arange(3, 10, dtype=np.int32)
    want = list(_oracle(params, cfg, p, 24)[len(p):])
    eng = ServingEngine(params, cfg, max_slots=2,
                        async_dispatch=async_on)
    h = eng.submit(p, 24, deadline_at=time.monotonic() + 3600.0)
    while len(h.tokens) < 5:
        eng.step()
    n0 = len(h.tokens)
    h.deadline_at = time.monotonic() - 1.0  # dies between steps
    eng.step()
    assert h.done and h.finish_reason == "expired"
    assert len(h.tokens) == n0  # tokens already read kept, no more
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


def _steady_engine(model, n_new=40, async_on=None):
    """An engine past its admissions: two slots decoding, no host
    event to come for `n_new` steps."""
    cfg, params = model
    eng = ServingEngine(params, cfg, max_slots=2, async_dispatch=async_on)
    hs = [eng.submit(np.arange(2, 2 + t, dtype=np.int32), n_new)
          for t in (5, 9)]
    while min(len(h.tokens) for h in hs) < 3:
        eng.step()
    return eng, hs


@DEPTHS
def test_steady_step_makes_one_blocking_read(model, watch_engine, async_on):
    """Everything the host needs of a step — tokens, trap flags,
    magnitude, the advanced bands — comes back in ONE array, at either
    depth (the lock-step step made three reads before ISSUE 29).
    Every device-to-host read the engine makes goes through
    `np.asarray`; counted by the phase open around it
    (conftest `watch_engine`), a steady step makes exactly one, under
    `engine.device_wait` — `engine.integrity` judges host values and
    reads nothing."""
    eng, hs = _steady_engine(model, async_on=async_on)
    watch = watch_engine(eng)
    rows = eng.metrics.ops.rows
    waits0, integ0 = rows["engine.device_wait"][0], \
        rows["engine.integrity"][0]
    n = 12
    for _ in range(n):
        before = len(watch.reads)
        eng.step()
        assert watch.reads[before:] == ["engine.device_wait"]
    assert rows["engine.device_wait"][0] - waits0 == n
    assert rows["engine.integrity"][0] - integ0 == n  # judged, not read
    watch.undo()
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
    rec["packed"] = _plant_trap(eng, rec["packed"], s)
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


def test_lockstep_leaves_no_step_in_flight(model):
    """The other depth of the same loop: with `async_dispatch=False`
    a step is read in the step() that dispatched it — nothing is left
    in flight between two step() calls, no step is counted as
    dispatched ahead or as a broken chain, and a token leaves the
    engine in the step() that computed it."""
    eng, hs = _steady_engine(model, async_on=False)
    m = eng.metrics
    steps0, n0 = m.decode_steps, [len(h.tokens) for h in hs]
    for k in range(1, 11):
        eng.step()
        assert eng._inflight is None
        assert [len(h.tokens) for h in hs] == [n + k for n in n0]
    assert m.decode_steps - steps0 == 10
    assert m.decode_dispatched_ahead == 0 and m.decode_chain_breaks == 0
    eng.run()
    assert m.decode_trace_count() == 1


def test_step_ewma_folds_whole_steps():
    """metrics.observe_step(dt) folds the step's wall time as it is
    (the per-token normalisation went with the K-token window): the
    first observation seeds the gauge, later ones decay at
    STEP_EWMA_ALPHA, and the report carries it — and none of the
    host-clock device-busy columns that only a removed bench row
    read."""
    from paddle_tpu.serving.metrics import ServingMetrics
    a = ServingMetrics(2)
    a.observe_step(0.8)
    assert a.step_ewma_s == pytest.approx(0.8)
    a.observe_step(0.4)
    al = ServingMetrics.STEP_EWMA_ALPHA
    assert a.step_ewma_s == pytest.approx(al * 0.4 + (1 - al) * 0.8)
    with pytest.raises(TypeError):
        a.observe_step(0.8, tokens=8)
    rep = a.report()
    assert rep["step_ewma_s"] == pytest.approx(a.step_ewma_s, abs=1e-6)
    assert not [k for k in rep if "busy" in k or "overhead" in k]


# ---------------------------------------------------------------------------
# fleet: failover between dispatch and read
# ---------------------------------------------------------------------------

@DEPTHS
def test_failover_mid_decode_journal_deltas_concatenate(model, tmp_path,
                                                        async_on):
    """Resume-mid-decode drill: r0 dies at its fourth step (exc@3) —
    running ahead, with a step dispatched and not yet read; every
    request completes on the survivor token-identical to generate(),
    and each rid's journal progress DELTAS, spliced across the
    failover, concatenate EXACTLY to its final token list (no lane
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
        engine_kw={"max_slots": 2, "async_dispatch": async_on},
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
