"""Continuous-batching serving engine (paddle_tpu/serving):

* Correctness bar — greedy engine output per request is BIT-IDENTICAL
  to sequential models/transformer.generate() at every slot count and
  admission order (three configurations below).
* Compile-count regression — a session over N requests with mixed
  prompt lengths traces prefill <= #buckets times and the decode step
  exactly once (the static-shape discipline the engine depends on).
* Slot lifecycle edge cases — queueing when full, EOS on the
  budget-exhausting step, refill right after retirement mid-flight,
  W>1 requests landing in non-contiguous free slots.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import transformer as T
from paddle_tpu.serving import ServingEngine


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


def test_greedy_bit_identical_across_slot_counts_and_orders():
    """Acceptance: three slot-count/arrival-order configurations, every
    request bit-identical to the sequential generate() oracle."""
    cfg, params = _mk(0)
    rng = np.random.RandomState(0)
    prompts = [
        rng.randint(0, cfg.vocab, (t,)).astype(np.int32)
        for t in (3, 7, 12, 5, 9, 17)
    ]
    budgets = [6, 8, 5, 10, 4, 7]
    oracle = [
        _oracle(params, cfg, p, n) for p, n in zip(prompts, budgets)
    ]

    # config 1: single slot (fully sequential through the engine)
    eng = ServingEngine(params, cfg, max_slots=1)
    hs = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
    eng.run()
    for h, want in zip(hs, oracle):
        np.testing.assert_array_equal(_full(h), want)

    # config 2: more slots than requests, all submitted upfront
    eng = ServingEngine(params, cfg, max_slots=8)
    hs = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
    eng.run()
    for h, want in zip(hs, oracle):
        np.testing.assert_array_equal(_full(h), want)

    # config 3: staggered arrivals mid-decode, latency-biased admission
    # (one prefill per step), reversed submission order
    eng = ServingEngine(params, cfg, max_slots=2, max_prefills_per_step=1)
    order = [5, 4, 3, 2, 1, 0]
    hs = {}
    for j, i in enumerate(order):
        hs[i] = eng.submit(prompts[i], budgets[i])
        if j % 2 == 1:
            eng.step()  # requests keep arriving while others decode
    eng.run()
    for i in order:
        np.testing.assert_array_equal(_full(hs[i]), oracle[i])


def test_compile_count_regression():
    """One engine lifetime over N requests with mixed prompt lengths:
    prefill traces <= #buckets and the decode step traces EXACTLY once
    (iteration count, slot churn, and admission order must not leak
    into compiled shapes)."""
    cfg, params = _mk(1)
    rng = np.random.RandomState(1)
    lengths = [3, 5, 8, 9, 12, 16, 20, 25, 4, 11]  # buckets: 8, 16, 32
    eng = ServingEngine(params, cfg, max_slots=4)
    hs = [
        eng.submit(rng.randint(0, cfg.vocab, (t,)).astype(np.int32), 5)
        for t in lengths
    ]
    eng.run()
    buckets = {eng._bucket(t) for t in lengths}
    assert eng.metrics.prefill_trace_count() <= len(buckets)
    assert eng.metrics.decode_trace_count() == 1

    # a second wave on the same engine must not retrace anything
    hs2 = [
        eng.submit(rng.randint(0, cfg.vocab, (t,)).astype(np.int32), 4)
        for t in (6, 13, 30)
    ]
    eng.run()
    assert eng.metrics.prefill_trace_count() <= len(buckets)
    assert eng.metrics.decode_trace_count() == 1
    assert all(h.done for h in hs + hs2)


def test_admission_queues_when_all_slots_busy():
    cfg, params = _mk(2)
    rng = np.random.RandomState(2)
    prompts = [
        rng.randint(0, cfg.vocab, (t,)).astype(np.int32)
        for t in (4, 6, 5, 7, 3)
    ]
    oracle = [_oracle(params, cfg, p, 6) for p in prompts]
    eng = ServingEngine(params, cfg, max_slots=2)
    hs = [eng.submit(p, 6) for p in prompts]
    eng.step()
    # two slots filled, three requests wait; the waiters have produced
    # nothing yet (admission is FCFS, not speculative)
    assert eng.live_slots == 2
    assert eng.queue_depth == 3
    assert hs[2].tokens == [] and not hs[2].done
    eng.run()
    for h, want in zip(hs, oracle):
        np.testing.assert_array_equal(_full(h), want)


def test_eos_on_same_step_as_budget_exhaustion():
    """A request whose EOS lands exactly on the budget-exhausting token
    retires ONCE (reason 'eos'), emits exactly max_new tokens, and the
    slot is immediately reusable. EOS is taken to be the token the
    oracle emits first, so the premise holds by construction whatever
    weights the seed draws."""
    cfg, params = _mk(3, vocab=8)
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, cfg.vocab, (4,)).astype(np.int32)
    eos = int(_oracle(params, cfg, prompt, 1)[-1])
    eng = ServingEngine(params, cfg, max_slots=1)
    h = eng.submit(prompt, 1, eos_id=eos)
    eng.run()
    assert h.done and h.finish_reason == "eos"
    assert h.tokens == [eos] and len(h.tokens) == 1
    # the same last token under another EOS id is the budget's verdict
    hb = eng.submit(prompt, 1, eos_id=(eos + 1) % cfg.vocab)
    eng.run()
    assert hb.tokens == [eos] and hb.finish_reason == "budget"
    # slot freed exactly once: a follow-up request runs clean
    p2 = rng.randint(0, cfg.vocab, (5,)).astype(np.int32)
    h2 = eng.submit(p2, 3)
    eng.run()
    assert h2.done
    np.testing.assert_array_equal(_full(h2), _oracle(params, cfg, p2, 3))


def test_eos_mid_budget_stops_early():
    # seed chosen so the 50x embed bias makes eos the argmax on the
    # THIRD generated token: genuinely mid-budget, not at-prefill
    cfg, params = _mk(5, vocab=8)
    eos = cfg.vocab - 1
    params["embed"] = params["embed"].at[eos].mul(50.0)
    rng = np.random.RandomState(6)
    eng = ServingEngine(params, cfg, max_slots=2)
    h = eng.submit(rng.randint(0, eos, (4,)), 10, eos_id=eos)
    eng.run()
    assert h.finish_reason == "eos"
    assert len(h.tokens) < 10 and h.tokens[-1] == eos
    # prefix agreement with the eos-aware sequential path
    want = np.asarray(T.generate(
        params, jnp.asarray(h.prompt)[None], cfg, 10, eos_id=eos
    ))[0]
    np.testing.assert_array_equal(_full(h), want[: 4 + len(h.tokens)])


def test_refill_on_retirement_mid_flight():
    """A queued request is admitted into a just-retired slot while the
    other slot is mid-decode; both the long-running neighbor and the
    refilled request stay bit-identical to the oracle."""
    cfg, params = _mk(5)
    rng = np.random.RandomState(5)
    long_p = rng.randint(0, cfg.vocab, (6,)).astype(np.int32)
    short_p = rng.randint(0, cfg.vocab, (4,)).astype(np.int32)
    late_p = rng.randint(0, cfg.vocab, (9,)).astype(np.int32)
    eng = ServingEngine(params, cfg, max_slots=2)
    h_long = eng.submit(long_p, 12)
    h_short = eng.submit(short_p, 2)   # retires after one decode
    h_late = eng.submit(late_p, 5)     # queued until short retires
    eng.step()
    assert h_late.tokens == []  # both slots busy
    eng.step()  # short's budget exhausts here...
    assert h_short.done
    eng.step()  # ...freeing its slot for late's admission
    assert h_late.tokens != [] and not h_long.done
    eng.run()
    np.testing.assert_array_equal(
        _full(h_long), _oracle(params, cfg, long_p, 12))
    np.testing.assert_array_equal(
        _full(h_short), _oracle(params, cfg, short_p, 2))
    np.testing.assert_array_equal(
        _full(h_late), _oracle(params, cfg, late_p, 5))


def test_multiple_requests_land_in_noncontiguous_free_slots():
    """W=2 requests admitted into slot holes (0 and 2) left by early
    retirements, with live neighbors in slots 1 and 3."""
    cfg, params = _mk(6)
    rng = np.random.RandomState(6)
    prompts = [
        rng.randint(0, cfg.vocab, (t,)).astype(np.int32)
        for t in (4, 5, 6, 7, 8, 10)
    ]
    budgets = [2, 12, 2, 12, 6, 6]  # slots 0 and 2 retire first
    oracle = [
        _oracle(params, cfg, p, n) for p, n in zip(prompts, budgets)
    ]
    eng = ServingEngine(params, cfg, max_slots=4)
    hs = [eng.submit(p, n) for p, n in zip(prompts[:4], budgets[:4])]
    # admit 4, decode once: the short ones hit budget 2 — one step()
    # later for the default engine, which reads a step behind
    eng.step()
    if eng.async_dispatch:
        eng.step()
    assert hs[0].done and hs[2].done
    assert not hs[1].done and not hs[3].done
    hs.append(eng.submit(prompts[4], budgets[4]))
    hs.append(eng.submit(prompts[5], budgets[5]))
    eng.step()   # both land in the holes at slots 0 and 2
    assert eng._slot_req[0] is hs[4] and eng._slot_req[2] is hs[5]
    assert eng._slot_req[1] is hs[1] and eng._slot_req[3] is hs[3]
    eng.run()
    for h, want in zip(hs, oracle):
        np.testing.assert_array_equal(_full(h), want)


def test_submit_validation_and_handle_result():
    cfg, params = _mk(7)
    eng = ServingEngine(params, cfg, max_slots=2)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(np.zeros(10, np.int32), cfg.max_len)
    with pytest.raises(ValueError, match="empty"):
        eng.submit(np.zeros(0, np.int32), 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.zeros(4, np.int32), 0)
    rng = np.random.RandomState(7)
    p = rng.randint(0, cfg.vocab, (5,)).astype(np.int32)
    h = eng.submit(p, 6)
    out = h.result()  # drives the engine itself
    np.testing.assert_array_equal(out, _oracle(params, cfg, p, 6))


def test_sampled_requests_deterministic_and_slot_independent():
    """temperature>0 uses a per-request fold_in(key, token_index)
    schedule: the same (prompt, seed) reproduces the same tokens no
    matter the slot count or what shares the batch."""
    cfg, params = _mk(8)
    rng = np.random.RandomState(8)
    p = rng.randint(0, cfg.vocab, (6,)).astype(np.int32)

    eng = ServingEngine(params, cfg, max_slots=1)
    h1 = eng.submit(p, 8, temperature=0.7, seed=13)
    eng.run()

    eng2 = ServingEngine(params, cfg, max_slots=4)
    others = [
        eng2.submit(rng.randint(0, cfg.vocab, (4,)), 8) for _ in range(3)
    ]
    h2 = eng2.submit(p, 8, temperature=0.7, seed=13)
    eng2.run()
    assert h1.tokens == h2.tokens
    assert all(o.done for o in others)
    assert all(0 <= t < cfg.vocab for t in h1.tokens)


def test_metrics_report_and_profiler_table(capsys):
    cfg, params = _mk(9)
    rng = np.random.RandomState(9)
    eng = ServingEngine(params, cfg, max_slots=2)
    for t in (4, 9, 5, 12):
        eng.submit(rng.randint(0, cfg.vocab, (t,)).astype(np.int32), 5)
    eng.run()
    rep = eng.metrics.report()
    assert rep["tokens_out"] == 4 * 5
    assert rep["prefills"] == 4
    assert 0.0 < rep["mean_occupancy"] <= 1.0
    assert rep["decode_traces"] == 1
    assert rep["tokens_per_sec"] > 0
    assert rep["mean_ttft_s"] >= rep["mean_queue_wait_s"] >= 0.0
    # profiler-style table: prefill buckets + decode rows, ms columns
    rows = {r["Event"]: r for r in eng.metrics.table("total")}
    assert "decode_step" in rows
    assert any(e.startswith("prefill_T") for e in rows)
    assert rows["decode_step"]["Calls"] == rep["decode_steps"]
    eng.metrics.print_report()
    out = capsys.readouterr().out
    assert "Profiling Report" in out and "decode_step" in out


@pytest.mark.slow  # ~18s: the broad 2-config sweep; tier-1 keeps the
# fast hit/evict/cold drill below + the bench contract test
def test_prefix_reuse_bit_identical_hit_and_partial_hit():
    """ISSUE 4 acceptance: header-sharing prompts across slot counts
    and admission orders — cold miss (the publisher), header hit, and
    full-prompt re-admit all bit-identical to sequential generate()."""
    cfg, params = _mk(11)
    rng = np.random.RandomState(11)
    header = rng.randint(0, cfg.vocab, (8,)).astype(np.int32)
    tails = [rng.randint(0, cfg.vocab, (t,)).astype(np.int32)
             for t in (3, 6, 2)]
    prompts = [np.concatenate([header, t]) for t in tails]
    budgets = [5, 4, 6]
    oracle = [
        _oracle(params, cfg, p, n) for p, n in zip(prompts, budgets)
    ]
    for max_slots, order in ((1, (0, 1, 2)), (3, (2, 1, 0))):
        eng = ServingEngine(params, cfg, max_slots=max_slots,
                            prefix_cache_tokens=64,
                            prefix_block_tokens=4)
        # wave 1: the publisher runs alone (cold miss, publishes the
        # header blocks)
        h0 = eng.submit(prompts[order[0]], budgets[order[0]])
        eng.run()
        # wave 2: the others hit the shared header; one is an exact
        # re-submit of the publisher (longest-chain full hit)
        hs = [eng.submit(prompts[i], budgets[i]) for i in order[1:]]
        h_again = eng.submit(prompts[order[0]], budgets[order[0]])
        eng.run()
        np.testing.assert_array_equal(_full(h0), oracle[order[0]])
        for i, h in zip(order[1:], hs):
            np.testing.assert_array_equal(_full(h), oracle[i])
        np.testing.assert_array_equal(_full(h_again), oracle[order[0]])
        st = eng.prefix_cache.stats()
        assert st["hits"] >= 3 and st["tokens_saved"] >= 3 * 8
        assert eng.metrics.report()["prefix_cache"]["hits"] == st["hits"]
    # maximal-reuse edge: a prompt whose first T0-1 tokens are all
    # cached — admission copies everything and computes a single-token
    # suffix chunk (the zero-recompute extreme of the partial-hit path)
    p_edge = np.concatenate([header, header[:1]])  # T0 = 9, 2 blocks cached
    h_edge = eng.submit(p_edge, 4)
    eng.run()
    assert eng.metrics.prefix_hit_tokens.max >= 8
    np.testing.assert_array_equal(
        _full(h_edge), _oracle(params, cfg, p_edge, 4))


def test_prefix_post_eviction_readmit_bit_identical():
    """A tiny pool budget forces the first prompt's blocks out; its
    re-admission is an honest cold miss and still matches the oracle.
    Chunking is ON so this tier-1 drill pins the chunked+cached
    admission path's bit-identity (cold, hit, and post-eviction)."""
    cfg, params = _mk(12)
    rng = np.random.RandomState(12)
    p1 = rng.randint(0, cfg.vocab, (12,)).astype(np.int32)
    filler = rng.randint(0, cfg.vocab, (12,)).astype(np.int32)
    want1 = _oracle(params, cfg, p1, 4)
    eng = ServingEngine(params, cfg, max_slots=1,
                        prefill_chunk_tokens=4,
                        prefix_cache_tokens=8, prefix_block_tokens=4)
    h = eng.submit(p1, 4)
    eng.run()
    np.testing.assert_array_equal(_full(h), want1)
    eng.submit(filler, 4)
    eng.run()  # filler's publish evicts p1's LRU blocks
    assert eng.prefix_cache.stats()["evictions"] >= 2
    h2 = eng.submit(p1, 4)
    eng.run()
    np.testing.assert_array_equal(_full(h2), want1)
    assert eng.prefix_cache.stats()["size_tokens"] <= 8


@pytest.mark.slow  # ~14s: step-cadence drill; the tier-1 compile-count
# and post-eviction tests cover the chunked path's correctness
def test_chunked_prefill_interleaves_with_decodes():
    """Sarathi-style chunking: a long prompt prefills in bounded chunks
    while the neighbor's decode advances EVERY step (no TTFT cliff for
    in-flight requests), and both stay bit-identical to the oracle."""
    cfg, params = _mk(13)
    rng = np.random.RandomState(13)
    short_p = rng.randint(0, cfg.vocab, (4,)).astype(np.int32)
    long_p = rng.randint(0, cfg.vocab, (33,)).astype(np.int32)
    eng = ServingEngine(params, cfg, max_slots=2,
                        prefill_chunk_tokens=8, max_prefills_per_step=1)
    h_short = eng.submit(short_p, 12)
    eng.step()  # short prefills (1 chunk) and starts decoding
    h_long = eng.submit(long_p, 5)
    eng.step()  # long admitted: chunk 1 of ceil(33/8)=5
    assert eng.prefilling_slots == 1 and not h_short.done
    n0 = len(h_short.tokens)
    eng.step()
    eng.step()  # chunks 2 and 3: long still prefilling...
    assert eng.prefilling_slots == 1
    # ...yet the neighbor decoded on BOTH steps (the interleave win)
    assert len(h_short.tokens) == n0 + 2
    eng.run()
    np.testing.assert_array_equal(
        _full(h_short), _oracle(params, cfg, short_p, 12))
    np.testing.assert_array_equal(
        _full(h_long), _oracle(params, cfg, long_p, 5))
    # 5 chunks for the long prompt, 1 for the short
    assert eng.metrics.prefill_chunks == 6
    assert eng.metrics.prefill_tokens_computed == 33 + 4


def test_compile_counts_bounded_with_chunking_and_cache():
    """Chunked + prefix-cached admission keeps the static-shape
    discipline: prefill/chunk traces <= #pow-2 buckets, decode EXACTLY
    once — and a second wave of pure aliased hits retraces nothing but
    (at most once) the fixed-block-shape copy-on-write helper. Block
    aliasing itself is a host table write: NO compiled copy/extract
    step exists on the reuse path anymore (ISSUE 7)."""
    cfg, params = _mk(14)
    rng = np.random.RandomState(14)
    lengths = [5, 9, 16, 23, 11]
    eng = ServingEngine(params, cfg, max_slots=2,
                        prefill_chunk_tokens=8,
                        prefix_cache_tokens=128, prefix_block_tokens=4)
    prompts = [rng.randint(0, cfg.vocab, (t,)).astype(np.int32)
               for t in lengths]
    for p in prompts:
        eng.submit(p, 3)
    eng.run()
    # every chunk is <= 8 tokens -> a single T8 bucket
    assert eng.metrics.prefill_trace_count() <= 2
    assert eng.metrics.decode_trace_count() == 1
    assert "prefix_copy" not in eng.metrics.trace_counts
    assert "prefix_extract" not in eng.metrics.trace_counts
    snapshot = dict(eng.metrics.trace_counts)
    for p in prompts:  # second wave: pure aliased hits + suffix chunks
        eng.submit(p, 3)
    eng.run()
    # wave 1 had no hits, so wave 2 may trace the (single-shape)
    # copy-on-write fn once — the maximal-match re-admits (T0 a block
    # multiple, whole prompt cached) privatise one block each;
    # everything else must be compile-free
    counts = dict(eng.metrics.trace_counts)
    assert counts.pop("cow_copy", 1) == 1
    snapshot.pop("cow_copy", None)
    assert counts == snapshot
    assert eng.metrics.cow_blocks >= 1  # the T0=16 maximal re-admit
    assert eng.prefix_cache.stats()["hits"] >= len(lengths)


def test_side_bands_stay_device_resident_on_steady_decode():
    """Satellite: the six per-slot side-band arrays upload to device
    only when a scheduler event dirties them — an admission-free decode
    loop does zero h2d band traffic."""
    cfg, params = _mk(15)
    rng = np.random.RandomState(15)
    eng = ServingEngine(params, cfg, max_slots=2)
    h = eng.submit(rng.randint(0, cfg.vocab, (6,)).astype(np.int32), 20)
    eng.step()  # admission dirties every band; first decode uploads
    u1 = eng.metrics.band_uploads
    assert u1 >= len(eng._dirty.union({"tok"}))  # at least one upload
    for _ in range(6):
        eng.step()
    assert eng.metrics.band_uploads == u1  # steady decode: no re-upload
    eng.run()
    assert h.done
    np.testing.assert_array_equal(
        _full(h), _oracle(params, cfg, h.prompt, 20))


def test_slot_decode_step_vector_pos_matches_scalar_rows():
    """The slotted per-row pos path of decode_step is bit-identical,
    row by row, to the scalar-pos path generate() uses."""
    cfg, params = _mk(10)
    rng = np.random.RandomState(10)
    seqs = [rng.randint(0, cfg.vocab, (t,)) for t in (5, 9)]
    caches, toks, poss, want = [], [], [], []
    for s in seqs:
        _, cache = T.prefill(params, jnp.asarray(s[:-1])[None], cfg)
        lg, c2 = T.decode_step(
            params, jnp.asarray(s[-1:]), len(s) - 1, cache, cfg
        )
        caches.append(cache)
        want.append((np.asarray(lg)[0], c2))
        toks.append(s[-1])
        poss.append(len(s) - 1)
    # stack the two independent rows into one slotted batch
    batched = [
        {
            "k": jnp.concatenate([a["k"], b["k"]]),
            "v": jnp.concatenate([a["v"], b["v"]]),
        }
        for a, b in zip(*caches)
    ]
    lg, new_cache = T.decode_step(
        params,
        jnp.asarray(np.asarray(toks, np.int32)),
        jnp.asarray(np.asarray(poss, np.int32)),
        batched,
        cfg,
    )
    lg = np.asarray(lg)
    for row in range(2):
        np.testing.assert_array_equal(lg[row], want[row][0])
        for li in range(cfg.layers):
            np.testing.assert_array_equal(
                np.asarray(new_cache[li]["k"][row]),
                np.asarray(want[row][1][li]["k"][0]),
            )


def test_moe_config_rejected_loudly():
    # reference_moe's capacity cutoff couples rows, so padded/chunked
    # prefill is not bit-stable for MoE — the engine refuses instead of
    # silently serving wrong tokens (PR 5 review hardening)
    cfg, params = _mk(moe_experts=2)
    with pytest.raises(ValueError, match="capacity cutoff that couples rows"):
        ServingEngine(params, cfg, max_slots=2)


# ---------------------------------------------------------------------
# ISSUE 7: paged KV block pool + speculative decoding
# ---------------------------------------------------------------------


def test_copy_on_write_on_shared_prefix_block():
    """A re-admit whose WHOLE prompt is cached (T0 a block multiple)
    aliases every block but must recompute the last token's logits —
    the write into the final shared block privatises it first
    (copy-on-write), and the publisher's cached chain plus a third
    admission stay intact and oracle-identical."""
    cfg, params = _mk(21)
    rng = np.random.RandomState(21)
    p = rng.randint(0, cfg.vocab, (8,)).astype(np.int32)  # 2 x Bt=4
    want = _oracle(params, cfg, p, 5)
    eng = ServingEngine(params, cfg, max_slots=2, kv_block_tokens=4,
                        prefix_cache_tokens=64)
    h1 = eng.submit(p, 5)
    eng.run()
    assert eng.metrics.cow_blocks == 0  # cold publish: nothing shared
    h2 = eng.submit(p, 5)
    eng.run()
    assert eng.metrics.cow_blocks == 1  # block 1 privatised pre-write
    h3 = eng.submit(p, 5)  # the shared chain survived the COW unharmed
    eng.run()
    assert eng.metrics.cow_blocks == 2
    for h in (h1, h2, h3):
        np.testing.assert_array_equal(_full(h), want)
    assert eng.prefix_cache.stats()["hits"] >= 2


def test_retirement_frees_exactly_the_unreached_tail():
    """Admission reserves ceil((T0+max_new)/Bt) blocks worst case; an
    early-EOS request only ever materialises the blocks its tokens
    reached, and retirement returns allocated + unreached-tail capacity
    that sums exactly to the reservation — the pool ends empty."""
    cfg, params = _mk(22, vocab=8)
    eos = cfg.vocab - 1
    params["embed"] = params["embed"].at[eos].mul(50.0)  # eos early
    rng = np.random.RandomState(22)
    prompt = rng.randint(0, eos, (5,)).astype(np.int32)
    eng = ServingEngine(params, cfg, max_slots=1, kv_block_tokens=4)
    h = eng.submit(prompt, 40, eos_id=eos)  # worst case: 45 tokens
    eng.run()
    assert h.finish_reason == "eos" and len(h.tokens) < 40
    need_total = -(-(5 + 40) // 4)
    m = eng.metrics
    assert m.kv_blocks_freed_at_retire + m.kv_tail_blocks_freed \
        == need_total
    # the tail is REAL: far more reserved than the few tokens reached
    written = 5 + len(h.tokens) - 1  # the last emitted token is unwritten
    assert m.kv_blocks_freed_at_retire == -(-written // 4)
    assert m.kv_tail_blocks_freed == need_total - -(-written // 4)
    assert eng.kv_blocks_in_use == 0  # everything back in the pool


def test_pool_exhaustion_queues_then_admits_after_retire():
    """Block-budget backpressure (ISSUE 7 satellite): a pool that can
    only cover one request's reservation QUEUES the second (slots are
    free — blocks are not) instead of raising, then admits it the
    moment the first retirement frees its blocks; both outputs match
    the oracle."""
    cfg, params = _mk(23)
    rng = np.random.RandomState(23)
    p = rng.randint(0, cfg.vocab, (5,)).astype(np.int32)
    want = _oracle(params, cfg, p, 6)
    # 4 blocks of 4 = 16 tokens; each request needs ceil(11/4)=3 blocks
    eng = ServingEngine(params, cfg, max_slots=4, kv_block_tokens=4,
                        kv_pool_blocks=4)
    a = eng.submit(p, 6)
    b = eng.submit(p, 6)
    eng.step()
    # slots were free, blocks were not: b waits in the queue
    assert sum(x is not None for x in eng._slot_req) == 1
    assert eng.queue_depth == 1 and not b.done
    eng.run()
    assert a.done and b.done
    np.testing.assert_array_equal(_full(a), want)
    np.testing.assert_array_equal(_full(b), want)
    # a request that can NEVER fit the pool still raises at submit
    with pytest.raises(ValueError, match="whole KV pool"):
        eng.submit(rng.randint(0, cfg.vocab, (20,)).astype(np.int32), 10)


def test_fully_cached_prompt_at_exact_pool_capacity_does_not_deadlock():
    """Review regression: a re-admit whose WHOLE prompt is cached and
    whose worst case exactly fills the pool must not deadlock — the
    held match pins the trie chain reclaim would need, so the engine
    drops the alias plan and admits as a cold miss (reclaiming the
    now-unpinned chain) instead of queueing forever."""
    cfg, params = _mk(27)
    rng = np.random.RandomState(27)
    p = rng.randint(0, cfg.vocab, (8,)).astype(np.int32)  # 2 x Bt=4
    want = _oracle(params, cfg, p, 8)
    eng = ServingEngine(params, cfg, max_slots=2, kv_block_tokens=4,
                        kv_pool_blocks=4, prefix_cache_tokens=64)
    h1 = eng.submit(p, 8)  # need_total = ceil(16/4) = 4 = whole pool
    eng.run()
    assert eng.prefix_cache.stats()["blocks"] == 2  # prompt published
    h2 = eng.submit(p, 8)  # full-prompt match + COW would need 4+1-ish
    h2.result()            # raises "no progress" if admission wedges
    np.testing.assert_array_equal(_full(h1), want)
    np.testing.assert_array_equal(_full(h2), want)
    # the fallback was a COLD miss: no COW happened, chain was evicted
    assert eng.metrics.cow_blocks == 0


def test_starved_admission_retries_leave_trie_and_stats_intact():
    """Review regression: a block-starved request retries admission
    every scheduler step. Those retries must not evict shareable trie
    chains (reclaim only runs when it can actually bridge the gap) and
    must not inflate hit/miss/tokens-saved stats (the match is a pure
    probe; stats record once, when the admission resolves)."""
    cfg, params = _mk(28)
    rng = np.random.RandomState(28)
    p8 = rng.randint(0, cfg.vocab, (8,)).astype(np.int32)   # 2 x Bt=4
    hog = rng.randint(0, cfg.vocab, (12,)).astype(np.int32)
    eng = ServingEngine(params, cfg, max_slots=3, kv_block_tokens=4,
                        kv_pool_blocks=7, prefix_cache_tokens=64)
    h1 = eng.submit(p8, 4)        # 3 blocks; publishes 2 to the trie
    eng.run()
    assert eng.prefix_cache.stats()["blocks"] == 2
    ha = eng.submit(hog, 8, publish_len=0)  # 20 tokens = 5 blocks: hogs
    eng.step()                              # the rest of the pool
    hb = eng.submit(p8, 4)        # needs 2 new blocks; 0 available
    for _ in range(3):
        eng.step()                # b retries and stays queued…
    assert not hb.done and eng.queue_depth == 1
    st = eng.prefix_cache.stats()
    # …without wiping the chain it will alias, and without phantom
    # stats: one miss each for the two cold admissions, nothing since
    assert st["blocks"] == 2 and st["evictions"] == 0
    assert st["hits"] == 0 and st["misses"] == 2
    eng.run()                     # hog retires -> b admits via alias
    assert hb.done
    st = eng.prefix_cache.stats()
    assert st["hits"] == 1 and st["misses"] == 2
    assert st["tokens_saved"] == 8  # credited once, for the real use
    want = _oracle(params, cfg, p8, 4)
    np.testing.assert_array_equal(_full(h1), want)
    np.testing.assert_array_equal(_full(hb), want)
    np.testing.assert_array_equal(_full(ha), _oracle(params, cfg, hog, 8))


def test_spec_decode_identity_single_trace_and_multi_token_steps():
    """Self-drafting speculative decoding: greedy outputs are identical
    to the oracle (acceptance only changes WHEN tokens appear, never
    WHICH), the verify step traces EXACTLY once per engine lifetime
    (second wave retraces nothing), and accepted drafts make some steps
    emit more than one token."""
    cfg, params = _mk(24)
    rng = np.random.RandomState(24)
    prompts = [rng.randint(0, cfg.vocab, (t,)).astype(np.int32)
               for t in (4, 9, 6)]
    budgets = [12, 8, 10]
    oracle = [_oracle(params, cfg, p, n)
              for p, n in zip(prompts, budgets)]
    eng = ServingEngine(params, cfg, max_slots=2, spec_draft_len=4)
    hs = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
    eng.run()
    for h, want in zip(hs, oracle):
        np.testing.assert_array_equal(_full(h), want)
    assert eng.metrics.trace_counts.get("spec_verify") == 1
    assert "decode_step" not in eng.metrics.trace_counts
    assert eng.metrics.spec_drafted > 0
    snapshot = dict(eng.metrics.trace_counts)
    hs2 = [eng.submit(p, 5) for p in prompts]  # wave 2: no retrace
    eng.run()
    assert dict(eng.metrics.trace_counts) == snapshot
    for h, p in zip(hs2, prompts):
        np.testing.assert_array_equal(_full(h), _oracle(params, cfg, p, 5))


@pytest.mark.slow  # ~13s (two engine builds); the tier-1 greedy
# identity + report drills already pin the spec path's correctness
def test_spec_decode_sampled_schedule_is_spec_invariant():
    """temperature>0 under speculative decoding keeps the per-request
    fold_in(key, token_index) schedule (verify position i samples index
    counts+i), so sampled outputs match the spec-off engine exactly."""
    cfg, params = _mk(25)
    rng = np.random.RandomState(25)
    p = rng.randint(0, cfg.vocab, (6,)).astype(np.int32)
    eng_plain = ServingEngine(params, cfg, max_slots=2)
    h1 = eng_plain.submit(p, 10, temperature=0.7, seed=13)
    eng_plain.run()
    eng_spec = ServingEngine(params, cfg, max_slots=2, spec_draft_len=3)
    h2 = eng_spec.submit(p, 10, temperature=0.7, seed=13)
    eng_spec.run()
    assert h1.tokens == h2.tokens


def test_paged_report_surfaces_block_and_spec_counters():
    cfg, params = _mk(26)
    rng = np.random.RandomState(26)
    eng = ServingEngine(params, cfg, max_slots=2, kv_block_tokens=8,
                        spec_draft_len=3)
    for t in (4, 9):
        eng.submit(rng.randint(0, cfg.vocab, (t,)).astype(np.int32), 6)
    eng.run()
    rep = eng.metrics.report()
    assert rep["kv_blocks_total"] == eng.num_kv_blocks
    assert rep["kv_blocks_in_use"] == 0  # all retired
    assert rep["kv_blocks_freed_at_retire"] + rep["kv_tail_blocks_freed"] \
        == sum(-(-(t + 6) // 8) for t in (4, 9))
    assert rep["spec_windows"] > 0
    # spec_drafted counts only drafts actually PROPOSED (empty lookup
    # lanes are not rejections) — this short random trace may propose
    # none; the identity drill above pins the drafted>0 case
    if rep["spec_drafted"]:
        assert 0.0 <= rep["spec_accept_rate"] <= 1.0
    else:
        assert rep["spec_accept_rate"] is None
