"""The SambaY hybrid family (ISSUE 27): the model module against its
plain reference, its three caches, its kernels, and the engine seam.

Small on the CPU, seeded random weights from the reference's own
initialiser (`benchmarks/chip/references/sambay_plain.py`, which
imports nothing of the program). Tolerances: the program and the
reference are both float32 here (conftest pins float32 matmuls), so
they differ by summation order alone: a few 1e-6 on logits of size
~2 through 8 layers. `TOL` = 5e-5 leaves ten times that room and is
still 400 times below the 2e-2 the int8 control moves the same logits
by (`test_tolerance_is_tight_enough_to_fail_the_int8_control`).
"""

import functools
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import sambay as sb
from paddle_tpu.parallel import paged_attention as pa
from paddle_tpu.parallel.ssm_update import (ssm_state_update,
                                            ssm_state_update_reference)
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.kv_blocks import WindowBlockTables

TOL = 5e-5
SHAPE = {"vocab": 300, "dim": 64, "heads": 8, "kv_heads": 4, "layers": 8,
         "mlp_mult": 4, "window": 12}
BT, SLOTS, MAXB = 4, 3, 16


def _reference():
    path = (pathlib.Path(__file__).resolve().parent.parent / "benchmarks"
            / "chip" / "references" / "sambay_plain.py")
    spec = importlib.util.spec_from_file_location("sambay_plain", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _reference()


@pytest.fixture(scope="module")
def cfg():
    return sb.SambaYConfig(
        **{k: SHAPE[k] for k in ("vocab", "dim", "heads", "kv_heads",
                                 "layers", "mlp_mult", "window")},
        max_len=BT * MAXB, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(ref):
    return ref.init_weights(SHAPE, BT * MAXB, 3, dtype="float32")


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, SHAPE["vocab"], 40).astype(
        np.int32)


@pytest.fixture(scope="module")
def ref_logits(ref, params, tokens):
    return np.asarray(ref.logits(params, tokens, SHAPE))


def test_layer_kinds_of_the_published_depth():
    kinds = sb.layer_kinds(32)
    assert [l for l, k in enumerate(kinds) if k == "mamba"] == list(
        range(0, 17, 2))
    assert [l for l, k in enumerate(kinds) if k == "window"] == list(
        range(1, 16, 2))
    assert kinds[17] == "full"
    assert [l for l, k in enumerate(kinds) if k == "gmu"] == list(
        range(18, 32, 2))
    assert [l for l, k in enumerate(kinds) if k == "cross"] == list(
        range(19, 32, 2))


def test_parameter_count_is_the_published_3p8_billion():
    cfg = sb.SambaYConfig(vocab=200064, dim=2560, heads=40, kv_heads=20,
                          layers=32, window=512, max_len=8192,
                          dtype=jnp.bfloat16)
    tree = jax.eval_shape(lambda: sb.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    assert 3.84e9 < n < 3.86e9


def test_init_params_has_the_references_tree(cfg, params):
    mine = sb.init_params(cfg, jax.random.PRNGKey(0))
    assert (jax.tree_util.tree_structure(mine)
            == jax.tree_util.tree_structure(params))
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape


def test_full_forward_equals_the_reference(cfg, params, tokens, ref_logits):
    got = sb.forward(params, jnp.asarray(tokens), cfg)
    assert np.abs(np.asarray(got) - ref_logits).max() < TOL


def test_tolerance_is_tight_enough_to_fail_the_int8_control(
        ref, params, tokens, ref_logits):
    ctrl = np.asarray(ref.logits(params, tokens, SHAPE, quant="int8"))
    assert np.abs(ctrl - ref_logits).max() > 100 * TOL


@functools.lru_cache(maxsize=None)
def _jitted(fn, cfg, kernel):
    """The model's step compiled once a kernel, as the engine does."""
    return jax.jit(functools.partial(fn, cfg=cfg, kernel=kernel))


class _Slot(object):
    """One slot's host bookkeeping, as the engine keeps it."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.cache = sb.SERVING.init_cache(cfg, 40, BT, SLOTS)
        self.win = WindowBlockTables(SLOTS, MAXB, BT, cfg.window)
        self.ftab = np.full((SLOTS, MAXB), -1, np.int32)
        self.next_block = 0

    def admit(self, s):
        assert self.win.admit(s, BT * MAXB)
        self.cache = sb.reset_slot_state(self.cache, s)

    def _ensure(self, s, lo, hi):
        for b in range(lo // BT, (hi - 1) // BT + 1):
            if self.ftab[s, b] < 0:
                self.ftab[s, b] = self.next_block
                self.next_block += 1

    def chunk(self, params, s, toks, cursor, c, bucket, kernel):
        self._ensure(s, cursor, cursor + c)
        wread = self.win.tables[s].copy()
        self.win.advance(s, cursor, cursor + c)
        assert self.win.held(s) <= self.win.per_slot
        rows = np.stack([self.ftab[s], wread, self.win.tables[s],
                         np.full(MAXB, s, np.int32)])
        padded = np.full(bucket, 7, np.int32)  # padding is not token 0
        padded[:c] = toks[cursor:cursor + c]
        logits, self.cache = _jitted(sb.paged_prefill_chunk, self.cfg, kernel)(
            params, self.cache, jnp.asarray(padded), jnp.int32(cursor),
            jnp.asarray(rows), true_len=jnp.int32(c))
        return np.asarray(logits)

    def decode(self, params, toks_at, kernel):
        """`toks_at`: {slot: (token, position)}; the others are parked."""
        pos = np.full(SLOTS, MAXB * BT, np.int32)
        tok = np.zeros(SLOTS, np.int32)
        for s, (t, p) in toks_at.items():
            self._ensure(s, p, p + 1)
            self.win.advance(s, p, p + 1)
            assert self.win.held(s) <= self.win.per_slot
            pos[s], tok[s] = p, t
        logits, self.cache = _jitted(sb.paged_decode_step, self.cfg, kernel)(
            params, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(np.stack([self.ftab, self.win.tables])), self.cache)
        return np.asarray(logits)


@pytest.mark.parametrize("kernel", ["gather", "fused"])
def test_chunked_prefill_then_decode_equals_the_full_forward(
        cfg, params, tokens, ref_logits, kernel):
    """Prefill in chunks of uneven buckets (5 of 8, 14 of 16, 8 of 8:
    padded and unpadded, the second crossing the 12-token window), then
    decode to position 39: the logits at every chunk's last row and at
    every decoded position are the reference's full forward's, with
    the window release running all the way (blocks freed, the bound
    held)."""
    st = _Slot(cfg)
    st.admit(1)
    cursor = 0
    for c, bucket in ((5, 8), (14, 16), (8, 8)):
        got = st.chunk(params, 1, tokens, cursor, c, bucket, kernel)
        cursor += c
        assert np.abs(got - ref_logits[cursor - 1]).max() < TOL
    for p in range(cursor, 40):
        got = st.decode(params, {1: (tokens[p], p)}, kernel)
        assert np.abs(got[1] - ref_logits[p]).max() < TOL
    assert st.win.released_total >= 6 and st.win.held(1) <= 4


@pytest.mark.parametrize("kernel", ["gather", "fused"])
def test_parked_slots_state_is_bit_identical_after_other_slots_steps(
        cfg, params, tokens, kernel):
    st = _Slot(cfg)
    for s in (0, 2):
        st.admit(s)
        st.chunk(params, s, tokens, 0, 8, 8, kernel)
    before = jax.tree_util.tree_map(lambda a: np.asarray(a[2]).copy(),
                                    st.cache["ssm"])
    for p in range(8, 14):  # slot 2 parked: only slot 0 steps
        st.decode(params, {0: (tokens[p], p)}, kernel)
    after = jax.tree_util.tree_map(lambda a: np.asarray(a[2]),
                                   st.cache["ssm"])
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(after)):
        assert np.array_equal(a, b)
    moved = jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a: float(np.abs(np.asarray(a[0])).max()), st.cache["ssm"]))
    assert min(moved) > 0  # slot 0's did advance


def test_padded_bucket_rows_do_not_advance_state(cfg, params, tokens):
    """The same 5 rows in a bucket of 8 and in a bucket of 16 (other
    padding): the state and the conv window they leave are the same,
    and are those of 5 rows, not of the bucket."""
    left = []
    for bucket in (8, 16):
        st = _Slot(cfg)
        st.admit(1)
        st.chunk(params, 1, tokens, 0, 5, bucket, "gather")
        left.append(jax.tree_util.tree_map(lambda a: np.asarray(a[1]),
                                           st.cache["ssm"]))
    for a, b in zip(jax.tree_util.tree_leaves(left[0]),
                    jax.tree_util.tree_leaves(left[1])):
        assert np.abs(a - b).max() < 1e-6
    st = _Slot(cfg)
    st.admit(1)
    st.chunk(params, 1, tokens, 0, 8, 8, "gather")  # 8 true rows: differs
    other = jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a: np.asarray(a[1]), st.cache["ssm"]))
    assert max(np.abs(a - b).max() for a, b in zip(
        jax.tree_util.tree_leaves(left[0]), other)) > 1e-3


def _engine(params, cfg, **kw):
    kw.setdefault("paged_kernel", "gather")
    return ServingEngine(params, cfg, max_slots=SLOTS, kv_block_tokens=BT,
                         kv_pool_blocks=40, **kw)


@pytest.mark.parametrize("kernel", ["gather", "fused"])
def test_engine_serves_the_references_greedy_tokens(ref, cfg, params,
                                                    kernel):
    """Five requests over three slots (so slots are re-used), prompts
    chunked at 16 into uneven buckets, contexts crossing the window:
    every greedy token is the reference's argmax at its position, and
    every block and reservation is back when the engine drains."""
    eng = _engine(params, cfg, prefill_chunk_tokens=16, paged_kernel=kernel)
    rng = np.random.default_rng(1)
    reqs = []
    for n, k in ((27, 10), (5, 20), (33, 8), (18, 14), (9, 30)):
        prompt = rng.integers(0, SHAPE["vocab"], n).astype(np.int32)
        reqs.append((prompt, eng.submit(prompt, k)))
    eng.run()
    for prompt, h in reqs:
        served = np.asarray(h.tokens, np.int32)
        want = np.asarray(ref.logits(
            params, np.concatenate([prompt, served]), SHAPE))
        assert np.array_equal(want[len(prompt) - 1:-1].argmax(-1), served)
    m = eng.metrics
    assert m.state_slots_reset == 5 and m.window_blocks_released > 0
    assert set(m.cache_bytes_in_use) == {"full", "window", "state"}
    assert m.decode_trace_count() == 1
    assert eng._alloc.blocks_in_use == 0 and eng._alloc.reserved == 0
    assert eng._win.alloc.blocks_in_use == 0 and eng._win.alloc.reserved == 0


def test_a_reused_slot_starts_from_zero_state(ref, cfg, params):
    """One slot, two requests one after the other: the second's tokens
    are the reference's, which they are not when the reset at
    admission is taken out (the planted fault the benchmark's test
    plants too)."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, SHAPE["vocab"], n).astype(np.int32)
               for n in (20, 11)]

    def serve(break_reset):
        eng = ServingEngine(params, cfg, max_slots=1, kv_block_tokens=BT,
                            kv_pool_blocks=40, paged_kernel="gather")
        if break_reset:
            eng._reset_slot_state = lambda s: None
        out = []
        for prompt in prompts:
            h = eng.submit(prompt, 12)
            eng.run()
            out.append(np.asarray(h.tokens, np.int32))
        return out

    def first_tokens_gap(served):
        want = np.asarray(ref.logits(
            params, np.concatenate([prompts[1], served]), SHAPE))
        rows = want[len(prompts[1]) - 1:-1]
        return float((rows.max(-1) - rows[np.arange(12), served]).max())

    assert first_tokens_gap(serve(False)[1]) == 0.0
    assert first_tokens_gap(serve(True)[1]) > 0.0


@pytest.mark.parametrize("option,value", [
    ("prefix_cache_tokens", 64), ("kv_store", object()),
    ("spec_draft_len", 4), ("kv_quant", "int8"), ("weight_quant", "int8"),
    ("adapter_registry", object()), ("kv_fingerprints", True)])
def test_each_unsupported_option_is_refused_by_name(cfg, params, option,
                                                    value):
    with pytest.raises(ValueError, match=option):
        _engine(params, cfg, **{option: value})


# ---------------------------------------------------------------------
# the family on the engine's one decode loop (ISSUE 29), at both of its
# depths (ISSUE 30): one step ahead of the host (what None resolves to,
# as for the GPT block) and lock-step
# ---------------------------------------------------------------------

DEPTHS = pytest.mark.parametrize("depth", [None, False],
                                 ids=["ahead", "lockstep"])


def _assert_reference_greedy(ref, params, prompt, served):
    """Every served token is the reference's argmax at its position."""
    served = np.asarray(served, np.int32)
    want = np.asarray(ref.logits(
        params, np.concatenate([prompt, served]), SHAPE))
    assert np.array_equal(
        want[len(prompt) - 1:len(prompt) - 1 + len(served)].argmax(-1),
        served)


def _prompts(seed, *lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, SHAPE["vocab"], n).astype(np.int32)
            for n in lengths]


def _slot_of(eng, h):
    return next(s for s, hh in enumerate(eng._slot_req) if hh is h)


def _pools_are_whole(eng):
    """Every block and reservation of the full layer's pool and of the
    window pools is back."""
    return all(a.blocks_in_use == 0 and a.reserved == 0
               for a in (eng._alloc, eng._win.alloc))


@DEPTHS
def test_the_family_rides_the_shared_loop_at_both_depths(ref, cfg, params,
                                                         depth):
    """No second program or loop for the family: its decode program is
    the engine's one `_make_decode`, and the loop's depth is the GPT
    block's — None resolves to one step ahead, because the window
    tables advance for the position the dispatched step writes (the
    seam refuses only what re-uses cached blocks; `decode_window` is
    no option of any engine). Over the 12-token window and a dozen
    block edges a slot, at either depth: the reference's greedy
    tokens, at most ceil(W / Bt) + 1 window blocks a slot at every
    step, nothing drawn past a slot's reservation, all of it back."""
    eng = _engine(params, cfg, async_dispatch=depth)
    assert eng.async_dispatch == (depth is None) and eng._inflight is None
    assert "async_dispatch" not in sb.SERVING.refused
    assert "spec_draft_len" in sb.SERVING.refused
    assert "decode_window" not in sb.SERVING.refused
    with pytest.raises(TypeError, match="decode_window"):
        _engine(params, cfg, decode_window=4)
    prompts, budgets = _prompts(10, 9, 21, 14), (40, 30, 50)
    hs = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
    win, bound = eng._win, 12 // BT + 1
    assert win.per_slot == bound
    while eng.step():
        assert all(win.held(s) <= bound for s in range(SLOTS))
        assert (win._tail >= 0).all()
        if depth is False:
            assert eng._inflight is None  # read in the step that dispatched
    m = eng.metrics
    if depth is None:
        # every step but the one after each admission wave and the
        # retirements' was dispatched before its predecessor was read
        assert m.decode_dispatched_ahead > 0.8 * m.decode_steps
    else:
        assert m.decode_dispatched_ahead == 0 and m.decode_chain_breaks == 0
    for prompt, h, n in zip(prompts, hs, budgets):
        assert len(h.tokens) == n and h.finish_reason == "budget"
        _assert_reference_greedy(ref, params, prompt, h.tokens)
    assert m.decode_trace_count() == 1 and m.window_blocks_released > 0
    assert _pools_are_whole(eng)


@DEPTHS
def test_eos_mid_run_serves_the_references_tokens(ref, cfg, params, depth):
    """A request whose EOS lands mid-run is retired ON THE DEVICE by
    the shared program (the lock-step program left it to the host) —
    one step ahead, while the step chained off it is already queued
    with the slot's window advanced one position further: its tokens
    are the reference's up to and including the EOS, its neighbour's
    and the slot's next tenant's are the reference's, and every block
    of the three caches is back at the end."""
    pa_, pb, pc = _prompts(11, 19, 30, 7)
    probe = _engine(params, cfg, prefill_chunk_tokens=16)
    hp = probe.submit(pa_, 16)
    probe.run()
    eos = int(hp.tokens[5])
    n_eos = list(hp.tokens).index(eos) + 1
    eng = _engine(params, cfg, prefill_chunk_tokens=16, async_dispatch=depth)
    ha, hb = eng.submit(pa_, 16, eos_id=eos), eng.submit(pb, 20)
    while not ha.done:
        eng.step()
    hc = eng.submit(pc, 9)  # re-tenants the slot the device retired
    eng.run()
    assert ha.finish_reason == "eos" and len(ha.tokens) == n_eos < 16
    assert list(ha.tokens) == list(hp.tokens[:n_eos])
    for prompt, h in ((pa_, ha), (pb, hb), (pc, hc)):
        _assert_reference_greedy(ref, params, prompt, h.tokens)
    assert (len(hb.tokens), len(hc.tokens)) == (20, 9)
    assert eng.metrics.decode_trace_count() == 1
    assert _pools_are_whole(eng)


@DEPTHS
@pytest.mark.parametrize("how", ["cancel", "expire"])
def test_cancel_and_expiry_mid_decode_keep_the_references_tokens(
        ref, cfg, params, how, depth):
    """A request cancelled, or expired, between two decode steps keeps
    the tokens already read — a prefix of the reference's; one step
    ahead the lane of the step in flight is discarded — and its window
    blocks and recurrent state do not leak into the neighbour or into
    the slot's next tenant: the chain breaks, the step in flight is
    read, and the fresh dispatch advances the tables at the mirrors'
    now-current positions."""
    import time

    pa_, pb, pc = _prompts(12, 17, 26, 11)
    eng = _engine(params, cfg, async_dispatch=depth)
    ha = eng.submit(pa_, 30, deadline_at=time.monotonic() + 3600.0)
    hb = eng.submit(pb, 18)
    while len(ha.tokens) < 7:
        eng.step()
    n0 = len(ha.tokens)
    if how == "cancel":
        assert eng.cancel(ha.rid)
    else:
        ha.deadline_at = time.monotonic() - 1.0
        eng.step()
    assert ha.done and ha.finish_reason == (
        "cancelled" if how == "cancel" else "expired")
    assert len(ha.tokens) == n0
    hc = eng.submit(pc, 10)
    eng.run()
    for prompt, h in ((pa_, ha), (pb, hb), (pc, hc)):
        _assert_reference_greedy(ref, params, prompt, h.tokens)
    assert (len(hb.tokens), len(hc.tokens)) == (18, 10)
    if depth is None:
        assert eng.metrics.decode_chain_breaks > 0
    assert _pools_are_whole(eng)


def _steady(params, cfg, n_new=40):
    """Two requests past their prefills, decoding across the window."""
    eng = _engine(params, cfg)
    prompts = _prompts(13, 10, 15)
    hs = [eng.submit(p, n_new) for p in prompts]
    while min(len(h.tokens) for h in hs) < 3:
        eng.step()
    return eng, prompts, hs


def test_a_steady_hybrid_step_makes_one_blocking_read(ref, cfg, params,
                                                      watch_engine):
    """The family's decode step made three device-to-host reads on the
    lock-step program (the tokens, then the trap flags and the
    magnitude); on the shared one it makes ONE, of the packed result,
    under `engine.device_wait` — `engine.integrity` judges host
    values."""
    eng, prompts, hs = _steady(params, cfg)
    watch = watch_engine(eng)
    for _ in range(12):
        before = len(watch.reads)
        eng.step()
        assert watch.reads[before:] == ["engine.device_wait"]
    watch.undo()
    eng.run()
    for prompt, h in zip(prompts, hs):
        _assert_reference_greedy(ref, params, prompt, h.tokens)


def test_window_release_still_lies_inside_alloc_blocks(cfg, params,
                                                       watch_engine):
    """The window tables advance where they did — beneath
    `engine.alloc_blocks`, now the shared dispatch's — and only on the
    steps whose write opens a block or whose window's tail leaves
    one."""
    eng, _, hs = _steady(params, cfg)
    watch = watch_engine(eng)
    released0 = eng.metrics.window_blocks_released
    n = 16  # crosses the 12-token window and four block edges a slot
    for _ in range(n):
        eng.step()
    rel = [stack for name, stack in watch.opened
           if name == "engine.window_release"]
    assert rel and all(stack == ("engine.step", "engine.decode",
                                 "engine.alloc_blocks") for stack in rel)
    assert len(rel) < n  # not every step: only at an edge
    assert eng.metrics.window_blocks_released > released0
    assert all(eng._win.held(_slot_of(eng, h)) <= 12 // BT + 1 for h in hs)


def test_a_trapped_hybrid_step_emits_nothing_of_itself(ref, cfg, params):
    """The trap flags ride the packed result and are judged from the
    host values BEFORE any token of the step emits: a trap forged into
    a real dispatched hybrid step raises and leaves every handle as it
    was."""
    from paddle_tpu.serving import IntegrityError

    eng, prompts, hs = _steady(params, cfg)
    n0 = [len(h.tokens) for h in hs]
    rec = eng._dispatch_decode()
    flat = np.asarray(rec["packed"]).copy()
    flat[eng.max_slots + _slot_of(eng, hs[1])] = 1  # `_unpack`'s layout
    rec["packed"] = flat
    with pytest.raises(IntegrityError) as ei:
        eng._read_decode(rec)
    assert ei.value.kind == "trap"
    assert [len(h.tokens) for h in hs] == n0
    for prompt, h in zip(prompts, hs):
        _assert_reference_greedy(ref, params, prompt, h.tokens)


RETIRE_AT = 9  # the token a request is retired at, one way or another


def _serve_and_retire(params, cfg, kind, depth, eos=None):
    """A request retired at its RETIRE_AT-th token — by its EOS or its
    budget (on the device) or by a cancel (on the host) — beside a
    neighbour that lives on -> (finish reason, what the slot and the
    neighbour were left as five steps later). The engine is then
    drained and every pool must be whole."""
    pn, pa_ = _prompts(14, 22, 13)
    n = RETIRE_AT
    eng = _engine(params, cfg, async_dispatch=depth)
    hn = eng.submit(pn, 40)  # the neighbour: alive throughout
    kw = {"eos_id": eos} if kind == "eos" else {}
    ha = eng.submit(pa_, n if kind == "budget" else 30, **kw)
    s = None
    while not ha.done:
        eng.step()
        s = _slot_of(eng, ha) if not ha.done else s
        if kind == "cancel" and len(ha.tokens) == n:
            eng.cancel(ha.rid)
    for _ in range(5):
        eng.step()  # the neighbour steps on; the slot stays parked
    assert not hn.done
    state = [np.asarray(a[s]).copy() for a in
             jax.tree_util.tree_leaves(eng._cache["ssm"])]
    win = eng._win
    # all the window pool holds is the neighbour's, within its bound
    assert win.alloc.blocks_in_use == win.held(_slot_of(eng, hn)) \
        <= win.per_slot
    left = (list(ha.tokens), state, win.tables[s].copy(), win.held(s),
            list(hn.tokens))
    eng.run()
    assert _pools_are_whole(eng)
    return ha.finish_reason, left


@pytest.fixture(scope="module")
def host_retired(cfg, params):
    """-> (an EOS id whose first occurrence is the request's
    RETIRE_AT-th token, the request's tokens left alone, what a
    lock-step cancel at that token leaves)."""
    probe = _engine(params, cfg, async_dispatch=False)
    hp = probe.submit(_prompts(14, 22, 13)[1], 30)
    probe.run()
    n = RETIRE_AT
    assert hp.tokens[n - 1] not in hp.tokens[:n - 1]
    return (int(hp.tokens[n - 1]), list(hp.tokens),
            _serve_and_retire(params, cfg, "cancel", False))


@DEPTHS
@pytest.mark.parametrize("how", ["eos", "budget"])
def test_a_device_retired_slot_leaves_what_a_host_retired_one_did(
        cfg, params, host_retired, how, depth):
    """EOS and budget are decided on the device. The slot such a
    request leaves — its recurrent state, its conv window, its window
    table and pool blocks — is bit for bit what the same request
    leaves when the HOST retires it at the same token (a lock-step
    cancel), also after its neighbour has stepped on past it. One step
    ahead the slot is retired on the device while the step chained off
    it is already queued — its window advanced one position further
    (not at all on a budget's last write: the clamp), its lane parked
    there — and every window block and reservation still comes back
    when the retiring step is read."""
    eos, alone, (h_reason, host_left) = host_retired
    reason, left = _serve_and_retire(params, cfg, how, depth, eos=eos)
    toks, state, table, held, neighbour = left
    h_toks, h_state, h_table, h_held, h_neighbour = host_left
    assert reason == how and h_reason == "cancelled"
    assert toks == h_toks == alone[:RETIRE_AT]
    assert len(state) == len(h_state) > 0
    for a, b in zip(state, h_state):
        assert np.array_equal(a, b)
    assert np.abs(state[0]).max() > 0  # the state of a served request
    assert (table == -1).all() and np.array_equal(table, h_table)
    assert held == h_held == 0
    assert neighbour == h_neighbour


def test_hybrid_decode_is_traced_once_across_waves(cfg, params):
    """One decode program per engine lifetime on the shared loop too:
    a second wave — other lengths, an EOS, a cancel — retraces
    nothing."""
    eng = _engine(params, cfg, prefill_chunk_tokens=16)
    for p in _prompts(15, 6, 19, 33, 12):
        eng.submit(p, 9)
    eng.run()
    assert eng.metrics.decode_trace_count() == 1
    before = dict(eng.metrics.trace_counts)
    hs = [eng.submit(p, 12, eos_id=7) for p in _prompts(16, 6, 19, 33, 12)]
    eng.step()
    eng.cancel(hs[0].rid)
    eng.run()
    assert eng.metrics.trace_counts == before
    assert all(h.done for h in hs)


def test_handoff_import_is_refused_by_name(cfg, params):
    eng = _engine(params, cfg)
    with pytest.raises(ValueError, match="handoff"):
        eng.submit(np.arange(5, dtype=np.int32), 4, handoff=[{"key": 1}])


def test_gpt_engines_go_through_the_same_seam():
    from paddle_tpu.models import transformer as tlm

    cfg = tlm.TransformerConfig(vocab=64, dim=32, heads=2, layers=2,
                                max_len=32)
    assert cfg.serving is tlm.SERVING and not tlm.SERVING.refused
    params = tlm.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, max_slots=2, kv_block_tokens=4)
    assert eng._family is tlm.SERVING and eng._win is None
    h = eng.submit(np.arange(5, dtype=np.int32), 4)
    eng.run()
    assert np.array_equal(
        h.result(),
        np.asarray(tlm.generate(params, jnp.arange(5)[None], cfg, 4))[0])


@pytest.mark.parametrize("window,bt", [(12, 4), (512, 16), (10, 4), (7, 8),
                                       (33, 16)])
def test_window_tables_never_hold_more_than_the_bound(window, bt):
    """Whatever the chunks and however long the decode, a slot holds at
    most ceil(W / Bt) + 1 blocks a window layer, every position a later
    query can attend is still held, and all of it comes back."""
    rng = np.random.default_rng(window * 100 + bt)
    total = 40 * bt
    win = WindowBlockTables(2, -(-total // bt), bt, window)
    bound = -(-window // bt) + 1
    assert win.alloc.num_blocks == 2 * bound
    for s in (0, 1):
        assert win.admit(s, total)
    pos = [0, 0]
    while min(pos) < total:
        s = int(rng.integers(0, 2))
        if pos[s] >= total:
            continue
        step = int(rng.choice([1, 1, 1, bt, 3 * bt + 1, 2 * window]))
        lo, hi = pos[s], min(total, pos[s] + step)
        win.advance(s, lo, hi)
        pos[s] = hi
        assert win.held(s) <= bound
        for p in range(max(0, hi - window), hi):
            assert win.tables[s, p // bt] >= 0
        assert (win.tables[s, :max(0, hi - window) // bt] < 0).all()
    assert win.released_total > 0
    for s in (0, 1):
        win.free(s)
    assert win.alloc.blocks_in_use == 0 and win.alloc.reserved == 0


def _one_ahead(win, s, pos, limit):
    """The engine's rule for a chained dispatch (`_dispatch_decode`
    with `prev`): the step in flight writes at `pos`, this one at
    pos + 1, and nothing is written past limit - 2."""
    q = pos + 1
    if q < limit - 1:
        win.advance(s, q, q + 1)


@pytest.mark.parametrize("window,bt", [(12, 4), (33, 16), (10, 4), (7, 8)])
def test_an_advance_ahead_of_a_commit_that_never_comes_is_returned(window,
                                                                    bt):
    """One step ahead the tables are advanced for position pos + 1
    while the step that writes pos is in flight; if that step retires
    the slot (an EOS), pos + 1 is never written. Wherever in a block
    or a window the retirement falls, the slot has held no more than
    its bound, drawn nothing past its reservation, and everything is
    back after `free`."""
    total = 4 * max(window, bt)
    win = WindowBlockTables(2, -(-total // bt), bt, window)
    bound = -(-window // bt) + 1
    for stop in range(1, total - 1, max(1, bt // 4)):
        assert win.admit(0, total) and win.admit(1, total)
        win.advance(0, 0, 1)  # the fresh dispatch at the mirror's pos
        win.advance(1, 0, total // 2)  # a neighbour's chunk: its own blocks
        for pos in range(stop):
            _one_ahead(win, 0, pos, total)
            assert win.held(0) <= bound and win._tail[0] >= 0
            # what the step in flight and the queued one attend is held
            for p in range(max(0, pos + 2 - window), pos + 2):
                assert win.tables[0, p // bt] >= 0
        # the step at `stop - 1` retired the slot; `stop` stays unwritten
        win.free(0)
        assert win.held(0) == 0 and win._tail[0] == 0
        assert win.alloc.blocks_in_use == win.held(1) <= bound
        win.free(1)
        assert win.alloc.blocks_in_use == 0 and win.alloc.reserved == 0


@pytest.mark.parametrize("total", [5, 8, 9, 16, 17, 40])
def test_a_slots_last_write_draws_nothing_past_its_reservation(total):
    """A slot on its last write (position limit - 2) in the step in
    flight has no position in the step chained off it: the rule skips
    it, as `_ensure_blocks` does for the full pool, so a request
    shorter than a window — whose reservation is its own
    ceil(total / Bt) blocks, not the window's — never draws on a
    neighbour's reservation, whichever way its length falls on a
    block edge."""
    bt, window = 4, 12
    win = WindowBlockTables(2, 16, bt, window)
    assert win.admit(0, total) and win.admit(1, 64)
    other = int(win._tail[1])
    reserved = int(win._tail[0])
    assert reserved == min(win.per_slot, -(-total // bt))
    win.advance(0, 0, 1)
    for pos in range(total - 1):  # the writes: 0 .. limit - 2
        _one_ahead(win, 0, pos, total)
        assert 0 <= win._tail[0] and win.held(0) + win._tail[0] == reserved
        assert win._tail[1] == other and win.held(1) == 0
    # the last position any dispatch advanced for is limit - 2
    assert win.tables[0, (total - 2) // bt] >= 0
    assert (win.tables[0, (total - 2) // bt + 1:] < 0).all()
    win.free(0)
    win.free(1)
    assert win.alloc.blocks_in_use == 0 and win.alloc.reserved == 0


def test_a_block_released_ahead_may_go_to_another_slot_in_the_same_call():
    """The dispatch of step N+1 releases the block slot 0's window
    leaves and, in the same pass over the live slots, hands that very
    block to slot 1, whose write opens one — while step N, in flight,
    still names it in slot 0's row of ITS table. Safe on the device
    (each dispatch carries its own snapshot; N+1's write is queued
    behind N); on the host the tables never name a block twice, the
    bound holds and the pool is whole at the end."""
    bt, window = 4, 12
    win = WindowBlockTables(2, 16, bt, window)
    assert win.admit(0, 64) and win.admit(1, 64)
    win.advance(0, 0, 14)  # a chunk: positions 0 .. 13
    win.advance(1, 0, 8)   # positions 0 .. 7: the next write opens block 2
    in_flight = win.tables.copy()  # step N's snapshot: slot 0 writes at 14
    leaving = int(win.tables[0, 0])
    assert leaving >= 0
    released = win.released_total
    # step N+1, one position ahead: slot 0 at 15, whose window (4 .. 15)
    # leaves block 0; slot 1 at 8
    assert win.advance(0, 15, 16) and win.advance(1, 8, 9)
    assert win.released_total == released + 1
    assert win.tables[0, 0] == -1 and win.tables[1, 2] == leaving
    assert in_flight[0, 0] == leaving  # still named by the step in flight
    live = win.tables[win.tables >= 0]
    assert len(set(live.tolist())) == len(live) == win.alloc.blocks_in_use
    assert max(win.held(0), win.held(1)) <= win.per_slot
    assert not win.advance(0, 15, 16)  # visited twice: idempotent
    win.free(0)
    win.free(1)
    assert win.alloc.blocks_in_use == 0 and win.alloc.reserved == 0


def _attention_oracle(q, kp, vp, tables, pos, first, bt, scale):
    """The grouped decode attention in plain numpy, from the pool."""
    S, hk, rep, D = q.shape
    out = np.zeros((S, hk, rep, D), np.float32)
    for s in range(S):
        if pos[s] >= tables.shape[1] * bt:
            continue
        lo = 0 if first is None else first[s]
        ps = np.arange(lo, pos[s] + 1)
        rows = np.stack([kp[tables[s, p // bt]].reshape(bt, hk, D)[p % bt]
                         for p in ps])  # [n, hk, D]
        vals = np.stack([vp[tables[s, p // bt]].reshape(bt, hk, D)[p % bt]
                         for p in ps])
        sc = np.einsum("grd,ngd->grn", q[s], rows) * scale
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        out[s] = np.einsum("grn,ngd->grd", pr, vals)
    return out


@pytest.mark.parametrize("windowed", [False, True])
def test_grouped_decode_kernel_equals_its_oracle(windowed):
    """4 queries a K/V head over the merged 3-D pool, one slot parked,
    one context inside a single block; with `first`, a walk that
    starts mid-table over entries already freed (-1) behind it."""
    rng = np.random.default_rng(5)
    S, hk, rep, D, bt, maxb, nb, W = 4, 2, 4, 16, 4, 12, 60, 10
    kp = rng.normal(size=(nb, bt * hk, D)).astype(np.float32)
    vp = rng.normal(size=(nb, bt * hk, D)).astype(np.float32)
    q = rng.normal(size=(S, hk, rep, D)).astype(np.float32)
    pos = np.array([37, 2, maxb * bt, 21], np.int32)
    tables = rng.permutation(nb)[:S * maxb].reshape(S, maxb).astype(np.int32)
    first = None
    if windowed:
        first = np.maximum(pos - W + 1, 0).astype(np.int32)
        for s in range(S):
            tables[s, :first[s] // bt] = -1
    got = pa.paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(pos), interpret=True,
        first=None if first is None else jnp.asarray(first), scale=0.25)
    want = _attention_oracle(q, kp, vp, tables, pos, first, bt, 0.25)
    live = pos < maxb * bt
    assert np.abs(np.asarray(got)[live] - want[live]).max() < 1e-5


def test_state_update_kernel_equals_its_reference():
    rng = np.random.default_rng(3)
    S, N, di = 5, 16, 256
    f = lambda *shp: jnp.asarray(rng.normal(size=shp).astype(np.float32))
    state, du, b, c = f(S, N, di), f(S, di), f(S, N), f(S, N)
    delta = jnp.abs(f(S, di)) * 0.1
    a_t = -jnp.exp(f(N, di))
    live = jnp.asarray([True, False, True, True, False])
    want_s, want_y = ssm_state_update_reference(state, delta, du, a_t, b, c,
                                                live)
    got_s, got_y = ssm_state_update(state, delta, du, a_t, b, c, live,
                                    interpret=True)
    assert np.abs(np.asarray(got_s) - np.asarray(want_s)).max() < 1e-6
    assert np.abs(np.asarray(got_y)[np.asarray(live)]
                  - np.asarray(want_y)[np.asarray(live)]).max() < 1e-5
    # a parked slot's state comes through bit for bit
    assert np.array_equal(np.asarray(got_s)[1], np.asarray(state)[1])
