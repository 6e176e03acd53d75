"""The Mamba-2 / grouped-query hybrid family (ISSUE 31): the model
module against its plain reference, its two caches, its kernel and
blocked scan, and the engine seam.

Small on the CPU: one 10-layer period with both kinds of layer (five
Mamba-2, one attention, four Mamba-2), seeded random weights from the
reference's own initialiser
(`benchmarks/chip/references/granite_hybrid_plain.py`, which imports
nothing of the program and runs the SEQUENTIAL recurrence).
Tolerances: the program and the reference are both float32 here
(conftest pins float32 matmuls), so they differ by summation order
alone — the blocked scan against the row-by-row recurrence included —
a few 1e-7 on logits of size ~1 through 10 layers. `TOL` = 2e-5
leaves fifty times that room and is still 300 times below the 7e-3
the int8 control moves the same logits by (the `int8` case of
`test_full_forward_against_the_reference`).
"""

import functools
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import granite_hybrid as gh
from paddle_tpu.models import parts
from paddle_tpu.parallel.ssd_update import (ssd_chunk_scan,
                                            ssd_chunk_scan_reference,
                                            ssd_state_update,
                                            ssd_state_update_reference)
from paddle_tpu.serving import ServingEngine

TOL = 2e-5
SHAPE = {"vocab": 300, "dim": 64, "heads": 8, "kv_heads": 4, "head_dim": 16,
         "layers": 10,
         "layer_types": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
         "mlp_mult": 2, "mamba_heads": 8, "mamba_head_dim": 16,
         "d_state": 32, "d_conv": 4, "chunk": 8,
         "embedding_multiplier": 2.0, "residual_multiplier": 0.22,
         "attention_multiplier": 0.0625, "logits_scaling": 8.0}
BT, SLOTS, MAXB = 4, 3, 16
PUBLISHED = dict(
    vocab=100352, dim=2048, heads=32, kv_heads=8, head_dim=64, layers=40,
    layer_types=(["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    mlp_mult=4, mamba_heads=64, mamba_head_dim=64, d_state=128, d_conv=4,
    chunk=256)


def _reference():
    path = (pathlib.Path(__file__).resolve().parent.parent / "benchmarks"
            / "chip" / "references" / "granite_hybrid_plain.py")
    spec = importlib.util.spec_from_file_location("granite_hybrid_plain",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _reference()


@pytest.fixture(scope="module")
def cfg():
    return gh.GraniteHybridConfig(max_len=BT * MAXB, dtype=jnp.float32,
                                  **SHAPE)


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


def test_parameter_count_is_the_published_3p19_billion(ref):
    """Shapes only, no arrays: 36 Mamba-2 layers of 25.9 M + 50.3 M,
    4 attention layers of 10.5 M + 50.3 M, the tied embedding 205.5 M;
    the program's tree and the reference's count the same."""
    cfg = gh.GraniteHybridConfig(**PUBLISHED)
    assert cfg.kinds.count("mamba") == 36
    assert [l for l, k in enumerate(cfg.kinds) if k == "attention"] == [
        5, 15, 25, 35]
    n = parts.param_count(gh.param_shapes(cfg))
    assert n == 3_191_396_096 == ref.param_count(PUBLISHED)
    mamba = 2048 * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * 2048
    attn = 2048 * 3072 + 2048 * 2048
    mlp = 3 * 2048 * 8192 + 2 * 2048
    assert n == 36 * (mamba + mlp) + 4 * (attn + mlp) + 100352 * 2048 + 2048
    # the state of one slot: 2 MB a layer in float32, 75.5 MB + conv rows
    assert gh.cache_bytes(cfg, 32) == {
        "full": 4 * 2 * 32 * 8 * 64 * 4,
        "call_block": 2 * 32 * 8 * 64 * 4,  # one pool's block, K + V
        "state": 36 * (128 * 4096 * 4 + 3 * 4352 * 4)}


def test_init_params_has_the_references_tree(cfg, params):
    mine = gh.init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape


@pytest.mark.parametrize("who", ["program", "int8"])
def test_full_forward_against_the_reference(ref, cfg, params, tokens,
                                            ref_logits, who):
    """The program's full forward lies within TOL of the reference's
    logits; the reference itself computed in int8 does not, by far."""
    if who == "program":
        got = np.asarray(gh.forward(params, jnp.asarray(tokens), cfg))
        assert np.abs(got - ref_logits).max() < TOL
    else:
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
        self.cache = gh.SERVING.init_cache(cfg, 40, BT, SLOTS)
        self.tab = np.full((SLOTS, MAXB), -1, np.int32)
        self.next_block = 0

    def _ensure(self, s, lo, hi):
        for b in range(lo // BT, (hi - 1) // BT + 1):
            if self.tab[s, b] < 0:
                self.tab[s, b] = self.next_block
                self.next_block += 1

    def chunk(self, params, s, toks, cursor, c, bucket):
        self._ensure(s, cursor, cursor + c)
        rows = np.stack([self.tab[s], np.full(MAXB, s, np.int32)])
        padded = np.full(bucket, 7, np.int32)  # padding is not token 0
        padded[:c] = toks[cursor:cursor + c]
        # the chunk is XLA in either `kernel`: one compile a bucket
        logits, self.cache = _jitted(gh.paged_prefill_chunk, self.cfg,
                                     "gather")(
            params, self.cache, jnp.asarray(padded), jnp.int32(cursor),
            jnp.asarray(rows), true_len=jnp.int32(c))
        return np.asarray(logits)

    def decode(self, params, toks_at, kernel):
        """`toks_at`: {slot: (token, position)}; the others are parked."""
        pos = np.full(SLOTS, MAXB * BT, np.int32)
        tok = np.zeros(SLOTS, np.int32)
        for s, (t, p) in toks_at.items():
            self._ensure(s, p, p + 1)
            pos[s], tok[s] = p, t
        logits, self.cache = _jitted(gh.paged_decode_step, self.cfg, kernel)(
            params, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(self.tab), self.cache)
        return np.asarray(logits)

    def state(self, s):
        return [np.asarray(a[s]).copy()
                for a in jax.tree_util.tree_leaves(self.cache["ssm"])]


@pytest.mark.parametrize("kernel", ["gather", "fused"])
@pytest.mark.parametrize("plan", [
    ((8, 8), (16, 16)),            # two chunks, edges on the 8-row block
    ((8, 8), (16, 16), (8, 8)),    # three chunks, edges on the block
    ((5, 8), (14, 16), (8, 8)),    # three chunks, edges off the block
    ((16, 16), (11, 16)),          # a padded last bucket
], ids=["two_on", "three_on", "three_off", "padded_last"])
def test_chunked_prefill_then_decode_equals_the_full_forward(
        cfg, params, tokens, ref_logits, plan, kernel):
    """Prefill in chunks (the first from position 0 over its own rows,
    the later ones through the table with the state and the conv rows
    carried), then decode to position 39: the logits at every chunk's
    last row and at every decoded position are the reference's full
    forward's — the blocked scan and the one-token update against the
    reference's sequential recurrence."""
    st = _Slot(cfg)
    cursor = 0
    for c, bucket in plan:
        got = st.chunk(params, 1, tokens, cursor, c, bucket)
        cursor += c
        assert np.abs(got - ref_logits[cursor - 1]).max() < TOL
    for p in range(cursor, 40):
        got = st.decode(params, {1: (tokens[p], p)}, kernel)
        assert np.abs(got[1] - ref_logits[p]).max() < TOL


@pytest.mark.parametrize("kernel", ["gather", "fused"])
def test_parked_slots_state_is_bit_identical_after_other_slots_steps(
        cfg, params, tokens, kernel):
    st = _Slot(cfg)
    for s in (0, 2):
        st.chunk(params, s, tokens, 0, 8, 8)
    before = st.state(2)
    for p in range(8, 14):  # slot 2 parked: only slot 0 steps
        st.decode(params, {0: (tokens[p], p)}, kernel)
    for a, b in zip(before, st.state(2)):
        assert np.array_equal(a, b)
    assert min(float(np.abs(a - b).max())
               for a, b in zip(before, st.state(0))) > 0  # slot 0's moved


def test_padded_bucket_rows_do_not_advance_state(cfg, params, tokens):
    """The same 5 rows in a bucket of 8 and in a bucket of 16 (other
    padding): the state and the conv rows they leave are the same, and
    are those of 5 rows, not of the bucket."""
    left = []
    for bucket in (8, 16):
        st = _Slot(cfg)
        st.chunk(params, 1, tokens, 0, 5, bucket)
        left.append(st.state(1))
    for a, b in zip(*left):
        assert np.abs(a - b).max() < 1e-6
    st = _Slot(cfg)
    st.chunk(params, 1, tokens, 0, 8, 8)  # 8 true rows: differs
    assert max(np.abs(a - b).max()
               for a, b in zip(left[0], st.state(1))) > 1e-3


def _scan_inputs(T, H=4, P=16, N=32, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    dt = jax.nn.softplus(f(T, H) - 2.0)
    a = -jnp.exp(jnp.asarray(rng.uniform(0.0, 2.7, H), jnp.float32))
    return f(N, H * P), dt, f(T, H * P), a, f(T, N), f(T, N)


@pytest.mark.parametrize("T,block", [(32, 8), (48, 16), (8, 16), (256, 256)])
def test_blocked_scan_equals_the_sequential_recurrence(T, block):
    """From a carried (non-zero) state, over one block and over
    several: the same final state and the same outputs as the
    recurrence row after row."""
    s0, dt, x, a, b, c = _scan_inputs(T)
    want_s, want_y = ssd_chunk_scan_reference(s0, dt, x, a, b, c)
    got_s, got_y = ssd_chunk_scan(s0, dt, x, a, b, c, block=block)
    scale = float(jnp.abs(want_y).max())
    assert np.abs(np.asarray(got_y - want_y)).max() < 1e-5 * scale
    assert np.abs(np.asarray(got_s - want_s)).max() < 1e-5 * float(
        jnp.abs(want_s).max())


def test_blocked_scan_rows_with_a_zero_step_change_nothing():
    s0, dt, x, a, b, c = _scan_inputs(32, seed=1)
    dt = dt.at[20:].set(0.0)
    got_s, _ = ssd_chunk_scan(s0, dt, x, a, b, c, block=8)
    want_s, _ = ssd_chunk_scan(s0, dt[:24], x[:24], a, b[:24], c[:24],
                               block=8)
    assert np.abs(np.asarray(got_s - want_s)).max() < 1e-6
    with pytest.raises(ValueError, match="rows"):
        ssd_chunk_scan(s0, dt[:20], x[:20], a, b[:20], c[:20], block=8)


@pytest.mark.parametrize("S,N,di,batch,slots_a_batch,parked", [
    (4, 8, 256, None, 4, 1), (4, 8, 4096, None, 4, 1),
    (4, 8, 2560, None, 4, 1), (6, 16, 256, 2, 2, 1), (5, 16, 256, 2, 1, 1),
    (7, 32, 128, 1, 1, 6), (1, 8, 256, None, 1, None),
    (1, 8, 256, None, 1, 0), (3, 128, 4096, None, 3, 1),
    (8, 128, 4096, None, 4, 5), (5, 128, 4096, None, 1, 0),
], ids=["256", "4096", "2560", "two_a_batch", "five_slots_by_two",
        "one_a_batch", "one_slot", "one_slot_parked", "cell_three",
        "cell_four_a_batch", "cell_five_one_a_batch"])
def test_state_update_kernel_equals_its_reference(S, N, di, batch,
                                                  slots_a_batch, parked,
                                                  monkeypatch):
    """The Pallas kernel, interpreted, against plain jax.numpy: every
    live slot's state and output row, and a parked slot's state bit
    for bit what it was, on every branch of the batch rule
    (`_step_slots`; `batch` slots' bytes stand in for `_STEP_BYTES`
    where the small shapes need more than one batch): all the slots in
    one batch (the first three: 256 channels, 4,096, and 2,560, which
    2,048-channel tiles once refused), several batches of two, a slot
    count two does not divide (one a batch), one a batch by the bytes,
    a single slot, and the cell's [128, 4096] slot — three in one
    batch, eight in two batches of four as the cell's 64 go, five one
    at a time."""
    from paddle_tpu.parallel import ssd_update

    if batch is not None:
        monkeypatch.setattr(ssd_update, "_STEP_BYTES", batch * N * di * 4)
    assert ssd_update._step_slots(S, N * di * 4) == slots_a_batch
    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    state, dtx, b, c = f(S, N, di), f(S, di), f(S, N), f(S, N)
    da = -jnp.abs(f(S, di))
    live = np.ones(S, bool)
    if parked is not None:
        live[parked] = False
    want_s, want_y = ssd_state_update_reference(state, da, dtx, b, c,
                                                jnp.asarray(live))
    got_s, got_y = ssd_state_update(state, da, dtx, b, c, jnp.asarray(live),
                                    interpret=True)
    # 1e-6 and 1e-5 as they stand wherever float32 can meet them; at
    # the cell's 128 state columns the state's draws pass 16 (one ulp:
    # 1.9e-6) and y, a sum of 128 products, 64 (one ulp: 7.6e-6): there,
    # and only there, the bounds follow the largest value over 8
    scale = ((lambda want: 1.0) if N < 128 else
             (lambda want: max(1.0, float(jnp.abs(want).max()) / 8)))
    assert np.abs(np.asarray(got_s - want_s)).max() < 1e-6 * scale(want_s)
    if live.any():
        assert (np.abs(np.asarray(got_y - want_y))[live].max()
                < 1e-5 * scale(want_y))
    if parked is not None:
        assert np.array_equal(np.asarray(got_s[parked]),
                              np.asarray(state[parked]))


def test_state_update_kernel_refuses_what_vmem_cannot_hold():
    """Two batches and the call's rows (da, dtx, B, C and y of EVERY
    slot) lie in VMEM: a slot too large for that, or slots x channels
    too many — a bound the 2,048-channel grid did not have — is
    refused with its reason before Mosaic sees it."""
    z = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    for S, di in ((2, 1 << 17), (4096, 1024)):
        with pytest.raises(ValueError, match="VMEM"):
            jax.eval_shape(
                functools.partial(ssd_state_update, interpret=True),
                z(S, 128, di), z(S, di), z(S, di), z(S, 128), z(S, 128),
                jax.ShapeDtypeStruct((S,), jnp.bool_))


# ---------------------------------------------------------------------
# through ServingEngine: the seam, the shared decode loop at both depths
# ---------------------------------------------------------------------


def _engine(params, cfg, **kw):
    kw.setdefault("paged_kernel", "gather")
    kw.setdefault("max_slots", SLOTS)
    return ServingEngine(params, cfg, kv_block_tokens=BT, kv_pool_blocks=40,
                         min_bucket=16, prefill_chunk_tokens=16, **kw)


def _prompts(seed, *lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, SHAPE["vocab"], n).astype(np.int32)
            for n in lengths]


def _assert_reference_greedy(ref, params, prompt, served):
    """Every served token is the reference's argmax at its position."""
    served = np.asarray(served, np.int32)
    want = np.asarray(ref.logits(
        params, np.concatenate([prompt, served]), SHAPE))
    assert np.array_equal(
        want[len(prompt) - 1:len(prompt) - 1 + len(served)].argmax(-1),
        served)


@pytest.mark.parametrize("depth", [None, False], ids=["ahead", "lockstep"])
def test_engine_serves_the_references_greedy_tokens(ref, cfg, params, depth):
    """Six requests over three slots (so slots are re-used and start
    from zero state), prompts chunked at 16 (one, two and three
    chunks), one request ended by its EOS on the device and one
    cancelled mid-decode, at either depth of the one decode loop: every
    greedy token is the reference's argmax at its position, decode is
    traced once across the waves, and every block and reservation is
    back when the engine drains."""
    eng = _engine(params, cfg, async_dispatch=depth)
    assert eng.async_dispatch == (depth is None)
    assert eng._win is None and eng._has_state
    p = _prompts(1, 27, 5, 33, 18, 9, 40)
    probe = _engine(params, cfg)
    hp = probe.submit(p[1], 20)
    probe.run()
    eos = int(hp.tokens[6])
    n_eos = list(hp.tokens).index(eos) + 1
    hs = [eng.submit(p[0], 10), eng.submit(p[1], 20, eos_id=eos),
          eng.submit(p[2], 30)]
    while len(hs[2].tokens) < 7:
        eng.step()
    n_cancel = len(hs[2].tokens)
    assert eng.cancel(hs[2].rid)
    before = None
    hs += [eng.submit(p[3], 14), eng.submit(p[4], 24), eng.submit(p[5], 8)]
    while eng.step():
        if before is None and all(h.done for h in hs[:3]):
            before = dict(eng.metrics.trace_counts)  # the first wave's
    assert eng.metrics.trace_counts == before
    assert hs[1].finish_reason == "eos" and len(hs[1].tokens) == n_eos < 20
    assert hs[2].finish_reason == "cancelled"
    assert len(hs[2].tokens) == n_cancel
    assert [len(h.tokens) for h in hs[3:]] == [14, 24, 8]
    for prompt, h in zip(p, hs):
        _assert_reference_greedy(ref, params, prompt, h.tokens)
    m = eng.metrics
    assert m.state_slots_reset == 6 and m.window_blocks_released == 0
    assert set(m.cache_bytes_in_use) == {"full", "state"}
    assert m.decode_trace_count() == 1
    if depth is None:
        assert m.decode_dispatched_ahead > 0 and m.decode_chain_breaks > 0
    assert eng._alloc.blocks_in_use == 0 and eng._alloc.reserved == 0


def test_a_reused_slot_starts_from_zero_state(ref, cfg, params):
    """One slot, requests one after the other: the second's tokens are
    the reference's, which they are not when the reset at admission is
    taken out (the planted fault the benchmark's test plants too)."""
    prompts = _prompts(2, 20, 11)
    eng = _engine(params, cfg, max_slots=1)

    def serve():
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

    assert first_tokens_gap(serve()[1]) == 0.0
    eng._reset_slot_state = lambda s: None
    assert first_tokens_gap(serve()[1]) > 0.0


@pytest.mark.parametrize("option,value", [
    ("prefix_cache_tokens", 64), ("kv_store", object()),
    ("spec_draft_len", 4), ("kv_quant", "int8"), ("weight_quant", "int8"),
    ("adapter_registry", object()), ("kv_fingerprints", True),
    ("handoff", [{"key": 1}])])
def test_each_unsupported_option_is_refused_by_name(cfg, params, option,
                                                    value):
    """What re-uses or re-plays cached blocks cannot restore the
    recurrent state: refused at construction (hand-off import at
    `submit`), each by its name, as the SambaY family refuses it."""
    assert set(gh.SERVING.refused) == {
        "prefix_cache_tokens", "kv_store", "spec_draft_len", "kv_quant",
        "weight_quant", "adapter_registry", "kv_fingerprints"}
    with pytest.raises(ValueError, match=option):
        if option == "handoff":
            _engine(params, cfg).submit(np.arange(5, dtype=np.int32), 4,
                                        handoff=value)
        else:
            _engine(params, cfg, **{option: value})


@pytest.mark.parametrize("family", ["gpt", "sambay", "granite_hybrid"])
def test_an_engine_builds_the_caches_its_family_declares(family, cfg,
                                                         params):
    """The seam says which caches a family has and the engine builds
    those: window tables only for the family with window layers,
    per-slot state handling only for the two with state; the GPT and
    SambaY engines hold what they held."""
    from paddle_tpu.models import sambay as sb
    from paddle_tpu.models import transformer as tlm
    from paddle_tpu.serving.kv_blocks import WindowBlockTables

    if family == "gpt":
        c = tlm.TransformerConfig(vocab=64, dim=32, heads=2, layers=2,
                                  max_len=32)
        p = jax.eval_shape(lambda: tlm.init_params(c, jax.random.PRNGKey(0)))
        want = ("paged",)
    elif family == "sambay":
        c = sb.SambaYConfig(vocab=64, dim=64, heads=8, kv_heads=4, layers=4,
                            window=8, max_len=32)
        p = jax.eval_shape(lambda: sb.init_params(c, jax.random.PRNGKey(0)))
        want = ("paged", "window", "state")
    else:
        c, p, want = cfg, params, ("paged", "state")
    eng = ServingEngine(p, c, max_slots=2, kv_block_tokens=4,
                        paged_kernel="gather")
    assert eng._family is c.serving and eng._family.caches == want
    assert isinstance(eng._win, WindowBlockTables) == ("window" in want)
    assert eng._has_state == ("state" in want)
    assert (eng._state_bytes_per_slot > 0) == ("state" in want)
    if family != "gpt":  # the GPT block's cache is a list of layers
        assert ("window" in eng._cache) == ("window" in want)
        assert "ssm" in eng._cache
