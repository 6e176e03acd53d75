"""Fused paged-attention kernel oracle suite (ISSUE 13).

* Per-primitive oracle — fused (Pallas table-walk,
  parallel/paged_attention.py) vs gather (`_paged_view`) logits agree
  to a PINNED float tolerance for all three paged primitives (online
  softmax reorders the reduction, so the bar is atol, not bit); cache
  writes land outside the kernel, so they agree to the same tolerance
  (layer l>0 writes inherit layer l-1's attention drift).
* Garbage-row invariant — a slot whose block table holds `-1`
  (unallocated) entries produces BIT-identical output to the same slot
  over a fully-allocated table at the same positions, with adapters
  active, on BOTH `paged_kernel` settings (the `_paged_view` docstring
  contract, pinned directly for the first time).
* End-to-end — greedy outputs through `ServingEngine` with
  paged_kernel="fused" are token-identical to the gather engine AND to
  sequential `generate()` on the prefix-aliased, copy-on-write,
  spec-decode, and zero-adapter paths.
* Compile-count regression — the fused decode and spec-verify steps
  trace exactly once, and NO `_paged_view` gather is reachable from
  the fused steps (monkeypatch-raises if one runs).
* Slot-count sweep (slow) — fused identity across engine widths.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import transformer as T
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.adapters import AdapterRegistry, make_adapter

# fused-vs-gather logits tolerance, PINNED: the two paths differ only
# in reduction order (one-shot softmax vs online (max, sum, acc)), a
# few float32 ulps at these magnitudes — loosening this means the
# kernel's numerics drifted, not that the bar was wrong
_ATOL = 2e-5
_RTOL = 2e-5


@pytest.fixture(autouse=True)
def _fresh_ring_call():
    """The merged-pool call traces once a geometry (`_ring_call` is
    jitted): a test that patches what its kernel calls must neither
    meet nor leave behind a body traced without the patch."""
    from paddle_tpu.parallel import paged_attention as pa

    pa._ring_call.clear_cache()
    yield
    pa._ring_call.clear_cache()


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
    return np.concatenate([h.full_prompt, np.asarray(h.tokens, np.int32)])


def _rand_pool(cfg, NB, Bt, seed=0):
    """A paged cache whose blocks hold random content — stronger than
    zeros for the oracle comparison (every unmasked tap matters)."""
    rng = np.random.RandomState(seed)
    dh = cfg.dim // cfg.heads
    return [
        {"k": jnp.asarray(
            rng.randn(NB, Bt, cfg.heads, dh).astype(np.float32)),
         "v": jnp.asarray(
             rng.randn(NB, Bt, cfg.heads, dh).astype(np.float32))}
        for _ in range(cfg.layers)
    ]


def _assert_caches_equal(ca, cb, exact=True):
    """exact=True for same-kernel comparisons (identical activations
    => identical writes). Fused-vs-gather comparisons use the pinned
    tolerance instead: layer 0's writes are bit-equal (they happen
    before any attention), but layer l>0 writes project activations
    that already carry layer l-1's attention drift."""
    for la, lb in zip(ca, cb):
        for band in ("k", "v"):
            a, b = np.asarray(la[band]), np.asarray(lb[band])
            if exact:
                np.testing.assert_array_equal(a, b)
            else:
                np.testing.assert_allclose(a, b, rtol=_RTOL, atol=_ATOL)


# a geometry whose decode call walks SEVERAL table groups: 8-token
# blocks group 16 to a grid step of the head-loop body (W = 128
# tokens) and 32 to a group of the ring body (`_bytes_group`, capped
# by the table), so 48 table entries make three groups of the one and
# two of the other (a 384-token span)
_BT, _MAXB = 8, 48
_W, _SPAN = 128, _BT * _MAXB
# positions on the edges of the head-loop body's groups: a context of
# one token, one token short of a group, exactly one, one more, and
# the span's last two
_EDGES = [0, _W - 2, _W - 1, _W, _SPAN - 2, _SPAN - 1, 200, 77]


def _edge_batch(S, parked=0, seed=0):
    """-> (pos [S], tables [S, _MAXB], NB): S rows cycling through
    `_EDGES`, the last `parked` of them parked at the span's end with
    an all -1 table row (what the engine's decode step hands a dead
    slot); a live row names exactly the blocks its context needs."""
    rng = np.random.RandomState(seed)
    pos = [_EDGES[i % len(_EDGES)] for i in range(S - parked)]
    need = [p // _BT + 1 for p in pos]
    NB = sum(need) + 3
    order = rng.permutation(NB)
    tables = np.full((S, _MAXB), -1, np.int32)
    at = 0
    for i, n in enumerate(need):
        tables[i, :n] = order[at:at + n]
        at += n
    return (jnp.asarray(pos + [_SPAN] * parked, jnp.int32),
            jnp.asarray(tables), NB)


def _quant_pool(cfg, NB, Bt, kv_quant, seed=0):
    """`_rand_pool` for a quantized cache: random codes in the storage
    dtype and positive per-(block, head) scales."""
    rng = np.random.RandomState(seed)
    dh = cfg.dim // cfg.heads
    st = T.kv_storage_dtype(kv_quant)

    def codes():
        c = rng.randint(-127, 128, (NB, Bt, cfg.heads, dh))
        if kv_quant == "fp8":
            c = c / 32.0
        return jnp.asarray(c, jnp.float32).astype(st)

    def scales():
        return jnp.asarray(
            (rng.rand(NB, cfg.heads) + 0.5).astype(np.float32) / 64)

    return [{"k": codes(), "v": codes(),
             "k_scale": scales(), "v_scale": scales()}
            for _ in range(cfg.layers)]


@pytest.mark.parametrize("kv_quant", ["none", "int8", "fp8"])
@pytest.mark.parametrize("S", [3, 1, 8, 32])
def test_fused_vs_gather_logits_decode(S, kv_quant):
    """S == 3 is the one-group geometry the suite began with. The
    others run the ring body (an unquantized pool) or the head-loop
    body (a quantized one) over several table groups, contexts on
    every edge of a group and of the span, and —
    from 8 rows up — two parked rows, whose garbage the gather form
    returns too and nothing reads: only LIVE rows are compared."""
    if S == 3:
        cfg, params = _mk(0)
        NB, Bt, live = 10, 8, 3
        tables = jnp.asarray([[0, 1, -1, -1], [2, 3, 4, -1],
                              [5, -1, -1, -1]], jnp.int32)
        pos = jnp.asarray([9, 20, 3], jnp.int32)
    else:
        cfg, params = _mk(0, max_len=_SPAN)
        Bt, live = _BT, S - (2 if S >= 8 else 0)
        pos, tables, NB = _edge_batch(S, parked=S - live)
    tok = jnp.asarray(np.random.RandomState(5).randint(0, 50, S),
                      jnp.int32)

    def pool():
        if kv_quant == "none":
            return _rand_pool(cfg, NB, Bt)
        return _quant_pool(cfg, NB, Bt, kv_quant)

    lg, cg = T.paged_decode_step(params, tok, pos, tables, pool(), cfg,
                                 kernel="gather", kv_quant=kv_quant)
    lf, cf = T.paged_decode_step(params, tok, pos, tables, pool(), cfg,
                                 kernel="fused", kv_quant=kv_quant)
    assert np.isfinite(np.asarray(lf)).all()  # parked rows: zeros in
    np.testing.assert_allclose(np.asarray(lf)[:live],
                               np.asarray(lg)[:live],
                               rtol=_RTOL, atol=_ATOL)
    if kv_quant == "none":
        _assert_caches_equal(cf, cg, exact=False)


def test_fused_vs_gather_logits_verify():
    cfg, params = _mk(1)
    NB, Bt, K = 10, 8, 3
    tables = jnp.asarray([[0, 1, 2, -1], [3, 4, -1, -1]], jnp.int32)
    pos = jnp.asarray([17, 9], jnp.int32)
    window = jnp.asarray([[5, 6, 7], [8, 9, 10]], jnp.int32)
    wpos = pos[:, None] + jnp.arange(K)[None, :]
    lg, cg = T.paged_verify_step(params, _rand_pool(cfg, NB, Bt),
                                 window, pos, wpos, tables, cfg,
                                 kernel="gather")
    lf, cf = T.paged_verify_step(params, _rand_pool(cfg, NB, Bt),
                                 window, pos, wpos, tables, cfg,
                                 kernel="fused")
    np.testing.assert_allclose(np.asarray(lf), np.asarray(lg),
                               rtol=_RTOL, atol=_ATOL)
    _assert_caches_equal(cf, cg, exact=False)


def test_fused_vs_gather_logits_prefill_chunk():
    cfg, params = _mk(2)
    NB, Bt = 10, 8
    table_row = jnp.asarray([0, 1, 2, -1], jnp.int32)
    chunk = jnp.asarray([3, 1, 4, 1, 5, 9, 2, 6], jnp.int32)
    lg, cg = T.paged_prefill_chunk(params, _rand_pool(cfg, NB, Bt),
                                   chunk, jnp.int32(10), table_row, cfg,
                                   true_len=jnp.int32(5),
                                   kernel="gather")
    lf, cf = T.paged_prefill_chunk(params, _rand_pool(cfg, NB, Bt),
                                   chunk, jnp.int32(10), table_row, cfg,
                                   true_len=jnp.int32(5),
                                   kernel="fused")
    np.testing.assert_allclose(np.asarray(lf), np.asarray(lg),
                               rtol=_RTOL, atol=_ATOL)
    _assert_caches_equal(cf, cg, exact=False)


def _toy_adapters(cfg, seed=7, P=2, rank=2):
    """A stacked adapter pool shaped like serving/adapters.py's device
    arrays: slot 0 the exact-zero adapter, slot 1 a random delta."""
    rng = np.random.RandomState(seed)
    d, L = cfg.dim, cfg.layers

    def stack(shape):
        a = np.zeros((P,) + shape, np.float32)
        a[1] = 0.1 * rng.randn(*shape)
        return jnp.asarray(a)

    return {
        "a_q": stack((L, d, rank)), "b_q": stack((L, rank, d)),
        "a_v": stack((L, d, rank)), "b_v": stack((L, rank, d)),
        "scale": jnp.asarray(np.array([0.0, 0.5], np.float32)),
    }


@pytest.mark.parametrize("groups", [1, 3])
@pytest.mark.parametrize("kernel", ["gather", "fused"])
def test_garbage_row_invariant_bit_identical_with_adapters(kernel, groups):
    """ISSUE 13 satellite: a slot's `-1` table entries must change
    NOTHING — bit-identical logits and cache vs a fully-allocated table
    at the same positions, adapters active, on BOTH kernel settings.
    Until now this invariant lived only in `_paged_view`'s docstring;
    the fused kernel must honor it too (its -1 clamp streams block 0's
    garbage, which the position mask must erase EXACTLY). With several
    table groups the decode call copies no block past a context,
    whatever the table's entries name, and a short last group's stale
    ring rows are masked like any depth past `pos`."""
    if groups == 1:
        cfg, params = _mk(3)
        NB, Bt = 12, 8
        # depths in use: slot0 -> 2 blocks (pos 9), slot1 -> 1 (pos 5)
        partial = jnp.asarray([[0, 1, -1, -1], [2, -1, -1, -1]], jnp.int32)
        full = jnp.asarray([[0, 1, 8, 9], [2, 10, 11, 7]], jnp.int32)
        pos = jnp.asarray([9, 5], jnp.int32)
    else:
        cfg, params = _mk(3, max_len=_SPAN)
        Bt = _BT
        pos, partial, used = _edge_batch(2, seed=3)
        pos = pos.at[0].set(_W + 5)  # ends inside the second group
        partial = partial.at[0, 0:_W // _BT + 1].set(
            jnp.arange(used, used + _W // _BT + 1))
        NB = used + _W // _BT + 1 + 2 * _MAXB
        spare = jnp.arange(NB - 2 * _MAXB, NB, dtype=jnp.int32)
        full = jnp.where(partial >= 0, partial,
                         spare.reshape(2, _MAXB))
    tok = jnp.asarray([13, 21], jnp.int32)
    adapters = _toy_adapters(cfg)
    aidx = jnp.asarray([1, 0], jnp.int32)  # live adapter + zero adapter
    la, ca = T.paged_decode_step(params, tok, pos, partial,
                                 _rand_pool(cfg, NB, Bt, seed=3), cfg,
                                 adapters=adapters, adapter_idx=aidx,
                                 kernel=kernel)
    lb, cb = T.paged_decode_step(params, tok, pos, full,
                                 _rand_pool(cfg, NB, Bt, seed=3), cfg,
                                 adapters=adapters, adapter_idx=aidx,
                                 kernel=kernel)
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    # the write landed in the same physical block either way; the
    # untouched pool blocks are bit-equal by construction
    _assert_caches_equal(ca, cb)


@pytest.mark.parametrize("pool", ["f32", "bf16"])
@pytest.mark.parametrize("S", [1, 8, 32])
def test_parked_rows_cost_the_live_rows_nothing(S, pool):
    """The decode call on a batch with parked rows (`pos` at the
    table's span, what the engine hands a dead slot) among the live
    ones: every LIVE row is bit-identical to the same rows called
    without them, and a parked row's output is zeros — nothing is
    copied or folded for it, whatever its table row names. The live
    rows are held to plain softmax attention through the table at the
    pinned tolerance; on a 16-bit pool that bar is what P's hi + lo
    split is for (P rounded once to bf16 misses it by 30x)."""
    from paddle_tpu.parallel import paged_attention as pa

    H, dh = 4, 8
    dt = jnp.float32 if pool == "f32" else jnp.bfloat16
    pos, tables, NB = _edge_batch(S, seed=S)
    rng = np.random.RandomState(S)
    k = jnp.asarray(rng.randn(NB, _BT, H, dh), dt)
    v = jnp.asarray(rng.randn(NB, _BT, H, dh), dt)
    # a query the pool's dtype holds exactly, so the kernel's cast of q
    # to it loses nothing the reference keeps
    q = jnp.asarray(rng.randn(S, H, dh), dt).astype(jnp.float32)
    alone = pa.paged_decode_attention(q, k, v, tables, pos,
                                      interpret=True)
    view_k = T._paged_view(k.astype(jnp.float32), tables)
    view_v = T._paged_view(v.astype(jnp.float32), tables)
    sc = jnp.einsum("shd,sthd->sht", q, view_k) / np.sqrt(dh)
    seen = jnp.arange(_SPAN)[None, None, :] <= pos[:, None, None]
    want = jnp.einsum("sht,sthd->shd",
                      jax.nn.softmax(jnp.where(seen, sc, -1e30), axis=-1),
                      view_v)
    np.testing.assert_allclose(np.asarray(alone), np.asarray(want),
                               rtol=_RTOL, atol=_ATOL)
    # parked rows first, in the middle and last; one names real blocks
    at = sorted({0, S // 2, S})
    qp, pp, tp = np.asarray(q), np.asarray(pos), np.asarray(tables)
    for n, i in enumerate(at):
        row = tp[0] if n == 0 else np.full(_MAXB, -1, np.int32)
        qp = np.insert(qp, i + n, rng.randn(H, dh), axis=0)
        pp = np.insert(pp, i + n, _SPAN)
        tp = np.insert(tp, i + n, row, axis=0)
    mixed = np.asarray(pa.paged_decode_attention(
        jnp.asarray(qp, jnp.float32), k, v, jnp.asarray(tp),
        jnp.asarray(pp), interpret=True))
    parked = pp >= _SPAN
    assert parked.sum() == len(at)
    np.testing.assert_array_equal(mixed[~parked], np.asarray(alone))
    np.testing.assert_array_equal(mixed[parked], 0.0)


def test_fused_engine_identity_aliased_and_cow_paths():
    """Greedy token identity fused vs gather vs generate() through the
    prefix pool: cold miss, aliased hit, and the maximal-reuse
    copy-on-write resubmit."""
    cfg, params = _mk(4)
    rng = np.random.RandomState(4)
    header = rng.randint(0, cfg.vocab, 16).astype(np.int32)
    prompts = [
        np.concatenate([header, rng.randint(0, cfg.vocab, t).astype(
            np.int32)]) for t in (3, 5)
    ]
    # whole-block prompt for the COW path: published in full, its
    # resubmit is the maximal-reuse case (every block cached, the last
    # one privatised so the final token's logits can be recomputed)
    cow_prompt = rng.randint(0, cfg.vocab, 24).astype(np.int32)
    budgets = [6, 7]

    def run(pk):
        eng = ServingEngine(params, cfg, max_slots=2,
                            kv_block_tokens=8,
                            prefix_cache_tokens=256, paged_kernel=pk)
        hs = [eng.submit(p, n, publish_len=len(header))
              for p, n in zip(prompts, budgets)]
        eng.run()
        hs.append(eng.submit(cow_prompt, 5))  # publishes all 3 blocks
        eng.run()
        h3 = eng.submit(cow_prompt, 5)  # maximal reuse -> COW
        eng.run()
        assert eng.prefix_cache.stats()["hits"] >= 1
        assert eng.metrics.cow_blocks >= 1
        return [_full(h) for h in hs + [h3]], eng

    out_f, eng_f = run("fused")
    out_g, _ = run("gather")
    assert eng_f.paged_kernel == "fused"
    assert eng_f.metrics.report()["paged_kernel"] == "fused"
    for a, b in zip(out_f, out_g):
        np.testing.assert_array_equal(a, b)
    specs = list(zip(prompts, budgets)) + [(cow_prompt, 5)] * 2
    for seq, (p, n) in zip(out_f, specs):
        np.testing.assert_array_equal(seq, _oracle(params, cfg, p, n))


def test_fused_engine_identity_spec_decode():
    """Speculative decoding over the fused verify kernel: greedy
    outputs identical to the gather spec engine and to generate()."""
    cfg, params = _mk(5)
    rng = np.random.RandomState(5)
    # repetitive prompts so the self-drafting lookup actually proposes
    base = rng.randint(0, cfg.vocab, 4).astype(np.int32)
    prompts = [np.tile(base, 3), rng.randint(0, cfg.vocab, 7).astype(
        np.int32)]
    budgets = [8, 6]

    def run(pk):
        eng = ServingEngine(params, cfg, max_slots=2, kv_block_tokens=8,
                            spec_draft_len=4, paged_kernel=pk)
        hs = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
        eng.run()
        assert eng.metrics.trace_counts.get("spec_verify", 0) == 1
        return [_full(h) for h in hs]

    out_f = run("fused")
    out_g = run("gather")
    for a, b in zip(out_f, out_g):
        np.testing.assert_array_equal(a, b)
    for seq, p, n in zip(out_f, prompts, budgets):
        np.testing.assert_array_equal(seq, _oracle(params, cfg, p, n))


def test_fused_engine_identity_zero_and_live_adapter():
    """Adapter side-band through the fused kernels: a request with NO
    adapter is token-identical to generate() (the zero-adapter slot is
    an exact no-op), and an adapter-carrying request is token-identical
    between the fused and gather engines."""
    cfg, params = _mk(6)
    reg = AdapterRegistry()
    reg.register("tenant-a", make_adapter(cfg, rank=2, seed=11))
    rng = np.random.RandomState(6)
    prompt = rng.randint(0, cfg.vocab, 9).astype(np.int32)

    def run(pk):
        eng = ServingEngine(params, cfg, max_slots=2, kv_block_tokens=8,
                            adapter_registry=reg, adapter_slots=2,
                            paged_kernel=pk)
        h0 = eng.submit(prompt, 7)  # zero adapter
        h1 = eng.submit(prompt, 7, adapter="tenant-a")
        eng.run()
        return _full(h0), _full(h1)

    base_f, ad_f = run("fused")
    base_g, ad_g = run("gather")
    np.testing.assert_array_equal(base_f, base_g)
    np.testing.assert_array_equal(base_f, _oracle(params, cfg, prompt, 7))
    np.testing.assert_array_equal(ad_f, ad_g)
    # the live adapter must actually change the continuation here —
    # otherwise the identity above proved nothing about the side-band
    assert list(ad_f) != list(base_f)


def test_fused_compile_counts_and_zero_paged_view_gathers(monkeypatch):
    """The fused steps keep the one-compiled-step discipline — the
    fused decode traced exactly once on a plain engine, the fused
    spec-verify exactly once on a spec engine (spec REPLACES the plain
    decode, so one engine can never trace both), chunks <= #pow-2
    buckets — and NEVER reach `_paged_view`: the gather helper is
    monkeypatched to raise for both engines' whole lifetime."""
    cfg, params = _mk(7)

    def _no_gather(*a, **kw):
        raise AssertionError(
            "_paged_view reached from a paged_kernel='fused' step")

    monkeypatch.setattr(T, "_paged_view", _no_gather)
    rng = np.random.RandomState(7)
    lengths = [3, 7, 12, 5, 9]

    def drive(spec):
        eng = ServingEngine(params, cfg, max_slots=3, kv_block_tokens=8,
                            spec_draft_len=spec,
                            prefix_cache_tokens=256,
                            paged_kernel="fused")
        hs = [eng.submit(rng.randint(0, cfg.vocab, t).astype(np.int32),
                         5, publish_len=4)
              for t in lengths]
        eng.run()
        # wave 2 retraces nothing
        hs += [eng.submit(rng.randint(0, cfg.vocab, t).astype(np.int32),
                          4) for t in (6, 13)]
        eng.run()
        assert all(h.done for h in hs)
        buckets = {eng._bucket(t) for t in lengths + [6, 13]}
        assert eng.metrics.prefill_trace_count() <= len(buckets)
        return eng

    eng = drive(None)
    assert eng.metrics.trace_counts.get("decode_step", 0) == 1
    eng = drive(4)
    assert eng.metrics.trace_counts.get("spec_verify", 0) == 1
    assert eng.metrics.trace_counts.get("decode_step", 0) == 0


def test_paged_kernel_knob_resolution_and_validation():
    cfg, params = _mk(8)
    # the explicit arg wins over the backend default, either way…
    for kernel in ("fused", "gather"):
        eng = ServingEngine(params, cfg, max_slots=1, paged_kernel=kernel)
        assert eng.paged_kernel == kernel
    # …and a kernel that does not exist is refused
    with pytest.raises(ValueError):
        ServingEngine(params, cfg, max_slots=1, paged_kernel="mosaic")
    # the backend default on this CI host (CPU) is the gather form —
    # fused would run interpreted; accelerator backends default fused
    eng = ServingEngine(params, cfg, max_slots=1)
    assert eng.paged_kernel == (
        "gather" if jax.default_backend() == "cpu" else "fused")
    with pytest.raises(ValueError):
        T.paged_decode_step(params, jnp.asarray([1]), jnp.asarray([0]),
                            jnp.asarray([[0]]),
                            T.init_paged_kv_cache(cfg, 2, 8), cfg,
                            kernel="mosaic")


@pytest.mark.slow
def test_fused_slot_count_sweep_token_identity():
    """Fused greedy identity vs generate() across engine widths — the
    batched kernel's slot dim must never leak into any row's tokens."""
    cfg, params = _mk(9)
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, cfg.vocab, t).astype(np.int32)
               for t in (3, 8, 13, 6)]
    budgets = [5, 7, 4, 6]
    oracle = [_oracle(params, cfg, p, n)
              for p, n in zip(prompts, budgets)]
    for slots in (1, 2, 4):
        eng = ServingEngine(params, cfg, max_slots=slots,
                            kv_block_tokens=8, paged_kernel="fused")
        hs = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
        eng.run()
        for h, want in zip(hs, oracle):
            np.testing.assert_array_equal(_full(h), want)


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
def test_prefill_taller_than_one_row_tile_matches_the_gather_form(quant):
    """A chunk taller than the kernel's row tile runs as several row
    tiles on a grid axis of their own (at real widths a 512+-row chunk
    as ONE q block is more VMEM than the chip lets a program scope —
    tests/test_tpu_aot_compile.py). Each tile must attend from its own
    base position and keep its own softmax state, padded rows of the
    last tile must stay out of the result, and on a quantized pool
    every table group must read ITS blocks' scales from the flat
    per-slot scale rows."""
    from paddle_tpu.parallel import paged_attention as pa

    H, dh, Bt, C, start = 2, 16, 16, 600, 40
    tile = pa._row_tile(H, dh, 128, 4, 1 if quant else 4)
    assert tile < C and C % tile  # several tiles, the last one padded
    maxb = -(-(start + C) // Bt) + 1
    NB = maxb + 3
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(C, H, dh).astype(np.float32))
    table = rng.permutation(NB)[:maxb].astype(np.int32)
    table[-1] = -1  # the unallocated tail entry
    table = jnp.asarray(table)
    if quant:
        k = jnp.asarray(rng.randint(-127, 128, (NB, Bt, H, dh)), jnp.int8)
        v = jnp.asarray(rng.randint(-127, 128, (NB, Bt, H, dh)), jnp.int8)
        ks = jnp.asarray(rng.rand(NB, H).astype(np.float32) / 64)
        vs = jnp.asarray(rng.rand(NB, H).astype(np.float32) / 64)
        view_k = T._paged_deq_view(k, ks, table[None])[0]
        view_v = T._paged_deq_view(v, vs, table[None])[0]
        scales = {"k_scale": ks, "v_scale": vs}
    else:
        k = jnp.asarray(rng.randn(NB, Bt, H, dh).astype(np.float32))
        v = jnp.asarray(rng.randn(NB, Bt, H, dh).astype(np.float32))
        view_k = T._paged_view(k, table[None])[0]
        view_v = T._paged_view(v, table[None])[0]
        scales = {}
    got = pa.paged_prefill_attention(q, k, v, table, start,
                                     interpret=True, **scales)
    s = jnp.einsum("thd,shd->hts", q * dh ** -0.5, view_k)
    seen = (jnp.arange(maxb * Bt)[None, :]
            <= start + jnp.arange(C)[:, None])
    p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
    want = jnp.einsum("hts,shd->thd", p, view_v)
    assert got.shape == (C, H, dh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=_RTOL, atol=_ATOL)


def test_fused_engine_refuses_a_geometry_past_scalar_memory():
    """The kernels keep every slot's block table — and on a quantized
    pool the named blocks' scales — in the core's scalar memory. A
    geometry that cannot fit is refused when the engine is built, with
    the arithmetic, not by the first step's compile on the chip."""
    cfg = _cfg(dim=2048, heads=16, max_len=2048)
    params = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    with pytest.raises(ValueError) as ei:
        ServingEngine(params, cfg, max_slots=64, kv_block_tokens=16,
                      kv_quant="int8", paged_kernel="fused")
    msg = str(ei.value)
    assert "64 slots x 128 table entries" in msg
    assert "2 x 16 heads" in msg and "bytes" in msg
    # the tables alone: 2,048 slots x 128 entries are 1 MiB of words,
    # past what the core leaves the prefetch operands
    with pytest.raises(ValueError) as ei:
        ServingEngine(params, cfg, max_slots=2048, kv_block_tokens=16,
                      paged_kernel="fused")
    msg = str(ei.value)
    assert "2048 slots x 128 table entries need" in msg
    assert "heads" not in msg
    # the same geometry fits without the scales, and on the XLA form
    for kw in ({"paged_kernel": "fused"},
               {"paged_kernel": "gather", "kv_quant": "int8"}):
        ServingEngine(params, cfg, max_slots=64, kv_block_tokens=16,
                      kv_pool_blocks=2, **kw)


# ---------------------------------------------------------------------
# the merged-pool decode call (`hybrid_decode_attention`, ISSUEs 27 and
# 32): grouped queries over a 3-D pool whose grid step is sized by the
# bytes it moves
# ---------------------------------------------------------------------

# 8-token blocks: `_group` gives 16 to a step (128 tokens), the byte
# rule — its target set to 32 of these small blocks — 32 (256 tokens);
# 96 table entries make six groups of the one, three of the other
_M_BT, _M_MAXB, _M_D, _M_WIN = 8, 96, 16, 100
_M_SPAN = _M_BT * _M_MAXB
# contexts of one token, on both sides of a block's edge, of BOTH
# group sizes' edges, of the window's (first = 0, 0, 1), the span's
# last two, and windows whose first position opens a group of either
# size (first = 128, 255, 256)
_M_EDGES = [0, 7, 8, 126, 127, 128, 254, 255, 256, 98, 99, 100,
            _M_SPAN - 2, _M_SPAN - 1, 227, 354, 355, 511, 512, 301]


def _merged_case(hk, rep, windowed, pool, seed=0, garbage=False):
    """-> (q, k_pool, v_pool, tables, pos, first): one live row for
    every edge and two parked ones (first and last) whose table rows
    name real blocks; a live row names exactly the blocks it attends
    (a window row none behind its window). With `garbage` every -1 of
    the tables names some allocated block instead."""
    rng = np.random.RandomState(seed)
    dt = jnp.float32 if pool == "f32" else jnp.bfloat16
    pos = np.array([_M_SPAN] + _M_EDGES + [_M_SPAN], np.int32)
    S = len(pos)
    first = np.maximum(pos - _M_WIN + 1, 0).astype(np.int32)
    live = pos < _M_SPAN
    lo = (first // _M_BT if windowed else np.zeros(S, np.int64)) * live
    hi = np.where(live, pos // _M_BT + 1, 3)
    NB = int((hi - lo).sum()) + 2
    order = rng.permutation(NB - 1)  # the last block stays nobody's
    tables = np.full((S, _M_MAXB), -1, np.int32)
    at = 0
    for s in range(S):
        n = hi[s] - lo[s]
        tables[s, lo[s]:hi[s]] = order[at:at + n]
        at += n
    k = jnp.asarray(rng.randn(NB, _M_BT * hk, _M_D), dt)
    v = jnp.asarray(rng.randn(NB, _M_BT * hk, _M_D), dt)
    # a query the pool's dtype holds exactly (see the 4-D test above)
    q = jnp.asarray(rng.randn(S, hk, rep, _M_D), dt).astype(jnp.float32)
    if garbage:
        fill = rng.randint(0, NB, tables.shape).astype(np.int32)
        tables = np.where(tables < 0, fill, tables)
    return (q, k, v, jnp.asarray(tables), jnp.asarray(pos),
            jnp.asarray(first) if windowed else None)


def _merged_oracle(q, k, v, tables, pos, first, hk, bt=_M_BT):
    """Plain softmax attention through the table, in float64; zeros
    for a parked slot."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    tables, pos = np.asarray(tables), np.asarray(pos)
    D = q.shape[-1]
    out = np.zeros(q.shape)
    for s in range(len(pos)):
        if pos[s] >= tables.shape[1] * bt:
            continue
        ps = np.arange(0 if first is None else int(first[s]), pos[s] + 1)
        blk = tables[s, ps // bt]
        assert (blk >= 0).all()
        rows = k[blk].reshape(len(ps), bt, hk, D)[
            np.arange(len(ps)), ps % bt]  # [n, hk, D]
        vals = v[blk].reshape(len(ps), bt, hk, D)[
            np.arange(len(ps)), ps % bt]
        sc = np.einsum("grd,ngd->grn", q[s], rows) / np.sqrt(D)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        out[s] = np.einsum("grn,ngd->grd", pr / pr.sum(-1, keepdims=True),
                           vals)
    return out


def _merged_call(case, monkeypatch, by):
    """The call with its group taken from `_group` ("tokens": the byte
    target set to nothing) or from the byte rule ("bytes": the target
    set to 32 of this geometry's blocks) -> (out, G)."""
    from paddle_tpu.parallel import paged_attention as pa

    q, k, v, tables, pos, first = case
    block_bytes = 2 * k.shape[1] * k.shape[2] * k.dtype.itemsize
    monkeypatch.setattr(pa, "_STEP_BYTES",
                        32 * block_bytes if by == "bytes" else 0)
    G = pa._bytes_group(_M_BT, _M_MAXB, block_bytes)
    assert pa._group(_M_BT, _M_MAXB) == 16
    assert G == {"tokens": 16, "bytes": 32}[by]
    out = pa.paged_decode_attention(
        q, k, v, tables, pos, interpret=True, first=first)
    return np.asarray(out), G


@pytest.mark.parametrize("by", ["tokens", "bytes"])
@pytest.mark.parametrize("pool", ["f32", "bf16"])
@pytest.mark.parametrize("windowed", [False, True], ids=["full", "window"])
@pytest.mark.parametrize("hk,rep", [(4, 8), (10, 4), (1, 2)])
def test_merged_pool_decode_call_is_plain_softmax_at_either_group(
        hk, rep, windowed, pool, by, monkeypatch):
    """The grouped-query call over a merged 3-D pool at granite's,
    SambaY's and a one-row geometry, over the whole context or a
    window of it, on both pool dtypes, with its grid step sized by 128
    tokens or by bytes: every live row is plain softmax attention
    through the table at the pinned tolerance on every edge a context
    can sit on; a parked row is zeros whatever its table names; and a
    table whose unallocated entries (-1) name real blocks instead
    gives the same bits (the garbage-row invariant: nothing is read
    from a block the context does not name)."""
    case = _merged_case(hk, rep, windowed, pool, seed=hk)
    got, G = _merged_call(case, monkeypatch, by)
    want = _merged_oracle(*case, hk)
    parked = np.asarray(case[4]) >= _M_SPAN
    assert parked.sum() == 2
    np.testing.assert_allclose(got[~parked], want[~parked],
                               rtol=_RTOL, atol=_ATOL)
    np.testing.assert_array_equal(got[parked], 0.0)
    filled = _merged_case(hk, rep, windowed, pool, seed=hk, garbage=True)
    assert (np.asarray(filled[3]) >= 0).all()
    again, _ = _merged_call(filled, monkeypatch, by)
    np.testing.assert_array_equal(again, got)


def test_group_rule_reads_the_pools_block_and_nothing_else():
    """The merged-pool call's group is the fewest blocks, `_group`'s
    doubled, whose K + V reach `_STEP_BYTES`: granite's and Trinity's
    32-token block of 4 rows a token (64 KiB) -> 32, SambaY's of 10
    (160 KiB) -> 8, a block of 20 (four of them are the target) ->
    `_group`'s own; never past the table; and `check_paged_smem`
    counts the tables, which the call's kernel reads as they are."""
    from paddle_tpu.parallel import paged_attention as pa

    bt, maxb = 32, 256
    kv = lambda rows, item=2: 2 * bt * rows * 128 * item  # noqa: E731
    assert pa._group(bt, maxb) == 4
    assert pa._bytes_group(bt, maxb, kv(4)) == 32
    assert pa._bytes_group(bt, maxb, kv(10)) == 8
    assert pa._bytes_group(bt, maxb, kv(20)) == 4 == pa._group(bt, maxb)
    assert pa._bytes_group(bt, maxb, kv(4, item=4)) == 16  # the dtype counts
    assert pa._bytes_group(bt, 8, kv(1)) == 8  # capped at the table
    assert pa._bytes_group(bt, 6, kv(1)) == 4  # `_group`'s, doubled 0 times
    # the latent call, bound by its fold: its one 40 KiB block a group of
    # 64 (2.5 MiB), where K/V calls would stop at 32
    assert pa._bytes_group(bt, maxb, bt * 640 * 2, True) == 64
    assert pa._bytes_group(bt, maxb, bt * 640 * 2) == 32
    for rows in (4, 10, 20):
        G = pa._bytes_group(bt, maxb, kv(rows))
        assert kv(rows) * G >= pa._STEP_BYTES > kv(rows) * G // 2 \
            or G == pa._group(bt, maxb)

    # the scalar memory the call is refused by is what it prefetches:
    # the tables as they are, `pos` and a window layer's `first` —
    # whatever the group
    def need(slots):
        return pa._smem_padded(slots, maxb) + 2 * pa._smem_padded(1, slots)

    room = pa._SMEM_BYTES - pa._SMEM_RESERVE
    fits = max(s for s in range(8, 4096, 8) if need(s) <= room)
    for rows in (4, 10):
        pa.check_paged_smem(fits, maxb, bt, 32, False,
                            block_bytes=kv(rows))
        with pytest.raises(ValueError, match="scalar memory"):
            pa.check_paged_smem(fits + 8, maxb, bt, 32, False,
                                block_bytes=kv(rows))


def test_rung_rule_folds_a_last_group_in_whole_tiles():
    """`_rungs`, the blocks a slot's last group may fold. A rung is the
    fewest blocks, at least G // 8 (G // 4 for a call bound by its
    copies), whose rows fill whole 128-row score tiles, or the group.
    A call bound by its fold (the latent call: its 32-row block in
    groups of 64) climbs a ladder of rungs of 8 (256 rows) to G, and a
    last group that names n <= G blocks folds never fewer than n and
    less than a rung more. A call bound by its copies folds one rung or
    the group: granite's and Trinity's 128-row block 8 of 32, SambaY's
    320-row block 2 of 8 — the two-rung rule it had. On the latent
    cell's contexts (1,551-7,168 tokens) the blocks folded over those
    named are ~1.07 with two rungs of a 32-block group, ~1.13 with two
    rungs of 64, under 1.03 with the ladder of 64."""
    from paddle_tpu.parallel import paged_attention as pa

    assert pa._rungs(64, 32, True) == tuple(range(8, 65, 8))
    assert pa._rungs(32, 32, True) == tuple(range(4, 33, 4))
    assert pa._rungs(32, 128, False) == (8, 32)
    assert pa._rungs(8, 320, False) == (2, 8)
    for G in (1, 2, 3, 4, 8, 12, 16, 32, 64):
        for rows in (8, 16, 32, 40, 64, 80, 96, 128, 320, 640):
            for fold_bound in (True, False):
                rungs = pa._rungs(G, rows, fold_bound)
                least = max(1, G // (8 if fold_bound else 4))
                tiles = [b for b in range(least, G + 1)
                         if b * rows % 128 == 0]
                r = tiles[0] if tiles else G
                assert rungs == ((tuple(range(r, G, r)) if fold_bound
                                  else (r,) if r < G else ()) + (G,))
                assert all(b * rows % 128 == 0 for b in rungs[:-1])
                for n in range(1, G + 1):
                    fold = next(b for b in rungs if b >= n)
                    assert fold >= n and (fold < n + r or not fold_bound)

    def share(rungs):
        ctx = np.arange(1551, 7169)
        n = -(-ctx // 32)
        last = (n - 1) % rungs[-1] + 1
        fold = [next(b for b in rungs if b >= k) for k in last]
        return (n - last + fold).sum() / n.sum()

    assert 1.06 < share((8, 32)) < 1.09
    assert 1.11 < share((16, 64)) < 1.15
    assert share(pa._rungs(64, 32, True)) < 1.03


def test_only_a_call_bound_by_its_fold_cuts_it_in_halves(monkeypatch):
    """`_half_cut`: a fold is cut at the block nearest its middle where
    the rows before it fill whole 128-row score tiles, and a fold of one
    such tile or less is not cut — the latent cell's group of 64 blocks
    of 32 rows halves at 32, each rung of 8 at 4. Only the call bound
    by its fold (one pool feeds both products: `fold_bound`) asks: a
    call whose copies hide its fold has the body it had, whatever the
    cut rule says (its rungs of 16 blocks of 32 rows could be cut),
    while the latent call's body follows the rule — `_half_cut` giving
    None is its serial fold, one `_fold_tile` a rung."""
    from paddle_tpu.parallel import paged_attention as pa

    assert pa._half_cut(64, 32) == 32
    assert pa._half_cut(8, 32) == 4
    assert pa._half_cut(4, 32) is None  # one tile
    assert pa._half_cut(12, 32) in (4, 8)
    assert pa._half_cut(2, 320) is None  # 320 rows: no tile edge
    assert pa._half_cut(32, 128) == 16
    assert pa._half_cut(3, 64) == 2  # the rest need not fill a tile

    sds = jax.ShapeDtypeStruct
    S, maxb = 4, 64
    tables, pos = sds((S, maxb), jnp.int32), sds((S,), jnp.int32)
    merged = (sds((S, 4, 8, 128), jnp.bfloat16),
              sds((200, 32, 128), jnp.bfloat16))
    latent = (sds((S, 32, 640), jnp.bfloat16),
              sds((200, 32, 640), jnp.bfloat16))

    def body(which):
        pa._ring_call.clear_cache()
        if which == "merged":
            fn = lambda q, k, t, p: pa.paged_decode_attention(  # noqa: E731
                q, k, k, t, p, interpret=False, scale=0.125)
            args = merged + (tables, pos)
            assert pa._rungs(pa._bytes_group(8, maxb, 2 * 32 * 128 * 2),
                             32, False) == (16, 64)
        else:
            fn = lambda q, k, t, p: pa.mla_decode_attention(  # noqa: E731
                q, k, t, p, 512, 0.1, interpret=False)
            args = latent + (tables, pos)
        with jax.default_matmul_precision(None):
            return str(jax.make_jaxpr(fn)(*args))

    rule = {w: body(w) for w in ("merged", "latent")}
    monkeypatch.setattr(pa, "_half_cut", lambda blocks, rows: None)
    serial = {w: body(w) for w in ("merged", "latent")}
    # a cut after the first 128-row tile of every fold that has two
    monkeypatch.setattr(pa, "_half_cut",
                        lambda blocks, rows: 4 if blocks > 4 else None)
    every = {w: body(w) for w in ("merged", "latent")}
    assert rule["merged"] == serial["merged"] == every["merged"]
    assert len({rule["latent"], serial["latent"], every["latent"]}) == 3
    # the serial fold has one P . V product a rung, the halves two
    assert (rule["latent"].count("dot_general")
            == serial["latent"].count("dot_general")
            + 2 * len(pa._rungs(64, 32, True)))


# the GPT block's 4-D pool on the ring body: its geometry
# (16 heads of 128, 16-token blocks, bf16: 128 KiB of K + V a block, so
# groups of 16 blocks = 256 tokens and rungs of 4), 48 table entries =
# three groups
_GPT_H, _GPT_DH, _GPT_BT, _GPT_MAXB = 16, 128, 16, 48
_GPT_SPAN, _GPT_W = _GPT_BT * _GPT_MAXB, 16 * _GPT_BT
# name: positions — a context of one token; contexts one short of, at
# and one past the edge of a rung and of each group; the span's last
# position beside a parked slot; contexts drawn at random
_GPT_CASES = {
    "pos_zero": [0],
    "group_edges": [62, 63, 64, _GPT_W - 2, _GPT_W - 1, _GPT_W,
                    2 * _GPT_W - 2, 2 * _GPT_W - 1, 2 * _GPT_W],
    "span_end_beside_parked": [_GPT_SPAN - 1, _GPT_SPAN],
    "random": np.random.default_rng(42).integers(
        0, _GPT_SPAN, 6).tolist(),
}


@pytest.mark.parametrize("case", sorted(_GPT_CASES))
def test_gpt_pool_decode_call_on_the_ring_is_the_gather_form(
        case, monkeypatch):
    """The decode call on a 4-D pool `[NB, Bt, H, Dh]` (the GPT
    block's) is the ring body over the pool's merged view: against
    `_cached_attention` over `_paged_view` (the gather form) at the
    pinned tolerance, a parked slot zeros though its table row names
    blocks, every block a live context names copied once as K and once
    as V and no other, and a slot's last group folding the first rung
    that covers it (4 blocks or the group's 16)."""
    from paddle_tpu.parallel import paged_attention as pa

    H, dh, bt, maxb = _GPT_H, _GPT_DH, _GPT_BT, _GPT_MAXB
    assert pa._bytes_group(bt, maxb, 2 * bt * H * dh * 2) == 16
    assert pa._rungs(16, bt * H, False) == (4, 16)
    pos = np.asarray(_GPT_CASES[case], np.int32)
    S, live = len(pos), pos < _GPT_SPAN
    rng = np.random.RandomState(S)
    need = np.where(live, pos // bt + 1, 2)  # a parked row names 2
    NB = int(need.sum()) + 2
    order = rng.permutation(NB)
    tables, at = np.full((S, maxb), -1, np.int32), 0
    for s in range(S):
        tables[s, :need[s]] = order[at:at + need[s]]
        at += need[s]
    k = jnp.asarray(rng.randn(NB, bt, H, dh), jnp.bfloat16)
    v = jnp.asarray(rng.randn(NB, bt, H, dh), jnp.bfloat16)
    # a query the pool's dtype holds exactly: the kernel's cast of q to
    # it loses nothing the gather form keeps
    q = jnp.asarray(rng.randn(S, H, dh), jnp.bfloat16).astype(jnp.float32)
    log = _watch_copies(monkeypatch)
    got = pa.paged_decode_attention(q, k, v, jnp.asarray(tables),
                                    jnp.asarray(pos), interpret=True)
    got = np.asarray(jax.block_until_ready(got))
    jax.effects_barrier()
    want = np.asarray(T._cached_attention(
        q, T._paged_view(k.astype(jnp.float32), jnp.asarray(tables)),
        T._paged_view(v.astype(jnp.float32), jnp.asarray(tables)),
        jnp.asarray(pos)))
    assert got.shape == (S, H, dh)
    np.testing.assert_allclose(got[live], want[live], rtol=_RTOL,
                               atol=_ATOL)
    np.testing.assert_array_equal(got[~live], 0.0)
    tables[~live] = -1
    _assert_copies_are_the_named_blocks(log, tables, 16, bt * H)
    folded = sum(e[1] for e in log if e[0] == "fold") // (bt * H)
    assert folded == sum(_folded(n, 16, (4, 16))
                         for n in (pos // bt + 1)[live])


# ---------------------------------------------------------------------
# the merged-pool call's own copies (ISSUE 36): the kernel walks a
# slot's table row and copies the blocks its context names, no other,
# into a two-deep ring. Interpreted, a copy descriptor can be watched.
# ---------------------------------------------------------------------


def _watch_copies(monkeypatch):
    """-> log: every copy the kernel starts as ("start", pool block,
    ring place, ring row) and every wait as ("wait", place, row), through
    a wrapper around `pltpu.make_async_copy`, and every fold that runs
    as ("fold", columns of its score tile), through one around
    `_fold_tile` (callbacks of an interpreted kernel; their order is
    not promised)."""
    from paddle_tpu.parallel import paged_attention as pa

    log, real = [], pa.pltpu.make_async_copy

    def watched(src, dst, sem):
        dma = real(src, dst, sem)
        blk = src.transforms[0].indices[0]
        half, at = dst.transforms[0].indices[:2]

        class Watched:
            def start(self):
                jax.debug.callback(
                    lambda b, h, r: log.append(("start", int(b), int(h),
                                                int(r))),
                    blk, half, at.start)
                dma.start()

            def wait(self):
                jax.debug.callback(
                    lambda h, r: log.append(("wait", int(h), int(r))),
                    half, at.start)
                dma.wait()

        return Watched()

    real_fold = pa._fold_tile

    def fold(s, v, *state):  # ("fold", columns) a fold that runs
        jax.debug.callback(lambda: log.append(("fold", s.shape[1])))
        real_fold(s, v, *state)

    monkeypatch.setattr(pa.pltpu, "make_async_copy", watched)
    monkeypatch.setattr(pa, "_fold_tile", fold)
    pa._ring_call.clear_cache()  # a body traced with THIS log
    return log


def _walk_call(pos, first, hk, rep, bt, maxb, G, monkeypatch, seed=0,
               dtype=jnp.float32, D=8):
    """The merged-pool call over tables that name distinct blocks for
    exactly what each slot attends (-1 elsewhere), its group set to G
    blocks -> (out, float64 oracle, live, tables, the copies' log)."""
    from paddle_tpu.parallel import paged_attention as pa

    rng = np.random.RandomState(seed)
    pos = np.asarray(pos, np.int32)
    S, span = len(pos), maxb * bt
    live = pos < span
    lo = np.zeros(S, np.int64) if first is None else np.asarray(first) // bt
    hi = np.where(live, pos // bt + 1, lo)
    NB = int((hi - lo).sum()) + 2
    order = rng.permutation(NB - 1) + 1  # block 0 is nobody's either
    tables, at = np.full((S, maxb), -1, np.int32), 0
    for s in range(S):
        n = hi[s] - lo[s]
        tables[s, lo[s]:hi[s]] = order[at:at + n]
        at += n
    k = jnp.asarray(rng.randn(NB, bt * hk, D), dtype)
    v = jnp.asarray(rng.randn(NB, bt * hk, D), dtype)
    q = jnp.asarray(rng.randn(S, hk, rep, D), dtype).astype(jnp.float32)
    monkeypatch.setattr(pa, "_bytes_group", lambda *a: G)
    log = _watch_copies(monkeypatch)
    got = pa.paged_decode_attention(
        q, k, v, jnp.asarray(tables), jnp.asarray(pos), interpret=True,
        first=None if first is None else jnp.asarray(first, jnp.int32))
    got = np.asarray(jax.block_until_ready(got))
    jax.effects_barrier()
    want = _merged_oracle(q, k, v, tables, pos, first, hk, bt)
    return got, want, live, tables, log


def _assert_copies_are_the_named_blocks(log, tables, G, rows):
    """Every block a table names is copied exactly twice (its K, its
    V) and nothing else is; every copy lands on a block's row of a
    ring place and is waited for there, once."""
    from paddle_tpu.parallel import paged_attention as pa

    starts = [e for e in log if e[0] == "start"]
    waits = [e for e in log if e[0] == "wait"]
    named = sorted(int(b) for b in tables[tables >= 0])
    assert sorted(e[1] for e in starts) == sorted(named * 2)
    assert all(0 <= e[2] < pa._RING and e[3] % rows == 0
               and e[3] < G * rows for e in starts)
    assert sorted(e[2:] for e in starts) == sorted(e[1:] for e in waits)


_WALK_BT, _WALK_MAXB, _WALK_G = 8, 40, 4  # 32 tokens a group, 10 groups
_WALK_SPAN = _WALK_BT * _WALK_MAXB
# name: (pos [S], window or None): the edges the walk has that the
# work list did not — a walk that starts inside a block, on a block
# but inside a group, a context of one token and of exactly one
# block, a last group of one block (Trinity's 65th), parked slots
# first, last and between live ones, nothing but parked slots. At 2
# K/V heads a block is 16 rows: no rung short of a group of 4 fills a
# 128-row score tile, so every group folds all 4 blocks
_WALKS = {
    "first_inside_a_block": ([150, 201, 77], 61),
    "first_on_a_block_inside_a_group": ([167, 103, 319], 96),
    "one_token_and_one_block": ([0, 7, 8, 15], None),
    "window_longer_than_the_context": ([5, 31, 40], 64),
    "last_group_of_one_block": ([135, 263, 39], 129),
    "parked_between_two_live": ([100, _WALK_SPAN, 37, _WALK_SPAN, 250],
                                None),
    "parked_first_and_last_window": ([_WALK_SPAN, 180, 66, _WALK_SPAN], 50),
    "nothing_but_parked": ([_WALK_SPAN, _WALK_SPAN], None),
}
# the rungs (`_rungs`): at 4 K/V heads a block is 32 rows and a group
# of 8 folds 4 blocks (one 128-row tile) or 8; the last group of each
# slot (of one, two and four groups) names one block, a rung, a rung
# and a block, all but one block, all 8
_RUNG_HK, _RUNG_G, _RUNGS = 4, 8, (4, 8)
for _kind, _last in (("one", 1), ("rung", 4), ("rung_plus_one", 5),
                     ("G_minus_one", _RUNG_G - 1), ("G", _RUNG_G)):
    _WALKS["last_group_" + _kind] = (
        [_WALK_BT * _last - 3, _WALK_BT * (_RUNG_G + _last) - _WALK_BT,
         _WALK_BT * (3 * _RUNG_G + _last) - 1], None, _last)


def _folded(n, G, rungs):
    """Blocks a slot that names n folds: its whole groups, then the
    first of `rungs` that covers the rest."""
    whole = (n - 1) // G
    return whole * G + next(b for b in rungs if b >= n - whole * G)


@pytest.mark.parametrize("case", sorted(_WALKS))
def test_table_walk_copies_what_the_context_names_on_every_edge(
        case, monkeypatch):
    """The kernel's walk on its own edges, each against float64 softmax
    through the table at the pinned tolerance, a parked slot zeros, and
    the copies counted: the blocks the tables name, K and V, and no
    other — a window's walk starts at the block of `first` wherever
    that lies in a group, so 65 blocks cost 65 blocks' copies. The
    folds counted too: a slot's last group folds the first rung that
    covers what it names, never a block less."""
    pos, win, *last = _WALKS[case]
    hk, G, rungs = ((_RUNG_HK, _RUNG_G, _RUNGS) if last
                    else (2, _WALK_G, (_WALK_G,)))
    pos = np.asarray(pos, np.int32)
    first = None if win is None else np.maximum(pos - win + 1, 0)
    got, want, live, tables, log = _walk_call(
        pos, first, hk, 2, _WALK_BT, _WALK_MAXB, G, monkeypatch)
    np.testing.assert_allclose(got[live], want[live], rtol=_RTOL,
                               atol=_ATOL)
    np.testing.assert_array_equal(got[~live], 0.0)
    tables[~live] = -1
    _assert_copies_are_the_named_blocks(log, tables, G, _WALK_BT * hk)
    blocks = (pos // _WALK_BT + 1 - (0 if first is None
                                     else first // _WALK_BT))[live]
    folded = sum(e[1] for e in log if e[0] == "fold") // (_WALK_BT * hk)
    assert folded == sum(_folded(n, G, rungs) for n in blocks)
    if last:
        assert ((blocks - 1) % G + 1).tolist() == last * 3
    if case == "last_group_of_one_block":
        assert blocks.tolist() == [17, 17, 5]  # 4 groups and one block


@pytest.mark.parametrize("windowed", [False, True], ids=["full", "window"])
def test_table_walk_at_the_cells_table_shape(windowed, monkeypatch):
    """What the work list's windowed cases held, held for the walk that
    took the list's place: 64 slots, 256 table entries of 32 tokens,
    groups of 16 blocks (granite's and Trinity's call; a 512-token
    window as SambaY's), positions on the list's old edges and at
    random — the call is plain softmax through the tables and copies
    exactly the blocks they name."""
    slots, maxb, bt, G, win = 64, 256, 32, 16, 512
    rng = np.random.default_rng(7)
    span = maxb * bt
    pos = rng.integers(0, span, slots).astype(np.int32)
    pos[:6] = [0, G * bt - 1, G * bt, span - 1, span, win - 1]
    first = np.maximum(pos - win + 1, 0) if windowed else None
    got, want, live, tables, log = _walk_call(
        pos, first, 1, 1, bt, maxb, G, monkeypatch, seed=7)
    assert (~live).sum() == 1
    np.testing.assert_allclose(got[live], want[live], rtol=_RTOL,
                               atol=_ATOL)
    np.testing.assert_array_equal(got[~live], 0.0)
    _assert_copies_are_the_named_blocks(log, tables, G, bt)
    # 32 rows a block: a last group folds a quarter of the group (4
    # blocks, 128 rows) or all of it
    blocks = (pos // bt + 1 - (0 if first is None else first // bt))[live]
    folded = sum(e[1] for e in log if e[0] == "fold") // bt
    assert folded == sum(_folded(n, G, (4, G)) for n in blocks)


@pytest.mark.parametrize("pool", ["f32", "bf16"])
def test_ring_rows_no_copy_wrote_cannot_reach_the_result(pool, monkeypatch):
    """A trap the BlockSpec form did not have: a ring row that no copy
    has written holds whatever the memory held, and 0 x NaN is NaN in
    P . V. Interpreted, a kernel's scratch starts as NaN bit patterns:
    with the ring's zeroing taken out, a call whose first group is
    short returns NaN — and the kernel as it is returns plain softmax,
    on contexts whose every group is short."""
    from jax._src.pallas import primitives
    from paddle_tpu.parallel import paged_attention as pa

    dt = jnp.float32 if pool == "f32" else jnp.bfloat16
    assert np.isnan(np.asarray(
        primitives.uninitialized_value((2,), dt), np.float32)).all()
    pos = np.asarray([3, 20, 9], np.int32)  # 1, 3 and 2 blocks of a 4-group
    args = (pos, None, 2, 2, _WALK_BT, _WALK_MAXB, _WALK_G, monkeypatch)
    got, want, live, _, _ = _walk_call(*args, dtype=dt)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=_RTOL, atol=_ATOL)
    monkeypatch.setattr(pa, "_zero_ring", lambda *bufs: None)
    bare, _, _, _, _ = _walk_call(*args, dtype=dt)
    assert np.isnan(bare).any()
