"""The sparse-expert family (ISSUE 33): the model module against its
plain reference, the routed expert layer, its two caches and the
engine seam.

Small on the CPU: one dense layer and one period of four expert layers
(window, window, full, window after the dense window layer), 16 experts
top-4 and a shared one, seeded random weights from the reference's own
initialiser (`benchmarks/chip/references/afmoe_plain.py`, which imports
nothing of the program, evaluates every expert over every row and
routes by its own top-k). Tolerances: the program and the reference
are both float32 here (conftest pins float32 matmuls), so they differ
by summation order alone — the sorted, grouped product against one
expert at a time over all rows included — a few 1e-6 on logits of size
~4 through 5 layers. `TOL` = 5e-5 leaves ten times that room and is
still a thousand times below what the int8 control moves the same
logits by (the `int8` case of `test_full_forward_against_the_reference`).
The seeds are fixed: a router's near-tie that float32 summation order
decides would move a logit by what an expert weighs, far over `TOL`,
and none occurs on them.
"""

import functools
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import afmoe as af
from paddle_tpu.models import parts
from paddle_tpu.parallel import routed_experts as rx
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.kv_blocks import WindowBlockTables

TOL = 5e-5
SHAPE = {"vocab": 300, "dim": 64, "heads": 8, "kv_heads": 2, "head_dim": 16,
         "layers": 5,
         "layer_types": ["sliding_attention", "sliding_attention",
                         "sliding_attention", "full_attention",
                         "sliding_attention"],
         "num_dense_layers": 1, "dense_width": 96, "expert_width": 32,
         "n_experts": 16, "top_k": 4, "route_scale": 2.826,
         "route_norm": True, "window": 12, "rope_theta": 10000.0}
BT, SLOTS, MAXB = 4, 3, 16
ROOT = pathlib.Path(__file__).resolve().parent.parent


def _published():
    import json

    return json.loads((ROOT / "benchmarks" / "chip" / "configs"
                       / "trinity_mini.json").read_text())


def _reference():
    path = ROOT / "benchmarks" / "chip" / "references" / "afmoe_plain.py"
    spec = importlib.util.spec_from_file_location("afmoe_plain", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _reference()


@pytest.fixture(scope="module")
def cfg():
    return af.AfmoeConfig(max_len=BT * MAXB, dtype=jnp.float32, **SHAPE)


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


def test_parameter_counts_of_the_published_shape_and_of_the_cut(ref):
    """Shapes only, no arrays. Published whole: 2 dense + 30 expert
    layers = 26.1 B ("26B-A3B"), by formula; the cut the benchmark
    serves (1 dense + 4 expert layers, all 128 experts, the whole
    vocabulary) is the count its configuration file states; the
    program's tree and the reference's count the same."""
    conf = _published()
    cut = conf["shape"]
    pub = conf["published"]
    whole = dict(cut, layers=pub["num_hidden_layers"],
                 layer_types=pub["layer_types"],
                 num_dense_layers=pub["num_dense_layers"])
    d, m, E, V = 2048, 1024, 128, 200192
    attn = d * (2 * 4096 + 2 * 512) + 4096 * d + 2 * 128
    expert_layer = attn + 4 * d + E * 3 * d * m + 3 * d * m + d * E + E
    dense_layer = attn + 4 * d + 3 * d * 6144
    for shape, dense, expert in ((whole, 2, 30), (cut, 1, 4)):
        n = parts.param_count(af.param_shapes(af.AfmoeConfig(**shape)))
        assert n == ref.param_count(shape)
        assert n == (dense * dense_layer + expert * expert_layer
                     + 2 * V * d + d)
    assert 26.0e9 < parts.param_count(
        af.param_shapes(af.AfmoeConfig(**whole))) < 26.2e9
    assert parts.param_count(af.param_shapes(af.AfmoeConfig(**cut))) \
        == conf["parameters"] == 4_241_534_720
    # every width, all experts, top-8 and the vocabulary are the
    # published ones; only the depth and the position cap are cut
    assert (cut["dim"], cut["heads"], cut["kv_heads"], cut["head_dim"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"])
    assert (cut["n_experts"], cut["top_k"], cut["expert_width"],
            cut["dense_width"], cut["vocab"], cut["window"]) == (
        pub["num_experts"], pub["num_experts_per_tok"],
        pub["moe_intermediate_size"], pub["intermediate_size"],
        pub["vocab_size"], pub["sliding_window"])
    assert cut["layer_types"] == pub["layer_types"][:5]
    assert cut["experts_held"] == [0, 128]
    # a block of ONE pool, K + V: 32 tokens x 4 heads x 128 x 2 B x 2
    assert af.cache_bytes(af.AfmoeConfig(dtype=jnp.bfloat16, **cut), 32) == {
        "full": 65536, "call_block": 65536, "window": 4 * 65536}


def test_init_params_has_the_references_tree(cfg, params):
    mine = af.init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape


@pytest.mark.parametrize("who", ["program", "int8", "no_bias", "no_scale"])
def test_full_forward_against_the_reference(ref, cfg, params, tokens,
                                            ref_logits, who):
    """The program's full forward lies within TOL of the reference's
    logits; the reference itself computed in int8, or with a router
    that ignores its bias or its scale, does not, by far."""
    if who == "program":
        got = np.asarray(af.forward(params, jnp.asarray(tokens), cfg))
        assert np.abs(got - ref_logits).max() < TOL
    elif who == "int8":
        ctrl = np.asarray(ref.logits(params, tokens, SHAPE, quant="int8"))
        assert np.abs(ctrl - ref_logits).max() > 1000 * TOL
    else:
        bad = np.asarray(ref.logits(params, tokens, SHAPE, **{who: True}))
        assert np.abs(bad - ref_logits).max() > 1000 * TOL


# ---------------------------------------------------------------------
# the routed expert layer
# ---------------------------------------------------------------------


def _layer_inputs(params, n, seed=5):
    p = params["blocks"][2]["ffn"]
    u32 = jnp.asarray(np.random.default_rng(seed).normal(
        size=(n, SHAPE["dim"])), jnp.float32)
    return p, u32


def test_route_bias_decides_the_choice_only():
    """Scores are sigmoids of a float32 product; the bias moves the
    choice and never the weight; the weights of the chosen sum to the
    scale."""
    rng = np.random.default_rng(1)
    u = jnp.asarray(rng.normal(size=(6, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 8)) / 6, jnp.float32)
    none = jnp.zeros(8, jnp.float32)
    idx0, w0 = rx.route(u, w, none, 3, 2.5, True)
    s = np.asarray(jax.nn.sigmoid(u @ w))
    assert np.array_equal(np.sort(np.asarray(idx0), -1),
                          np.sort(np.argsort(-s, -1)[:, :3], -1))
    assert np.allclose(np.asarray(w0).sum(-1), 2.5, atol=1e-6)
    bias = jnp.asarray([0, 0, 0, 0, 0, 0, 0, 10.0], jnp.float32)
    idx1, w1 = rx.route(u, w, bias, 3, 2.5, True)
    assert (np.asarray(idx1) == 7).any(-1).all()  # the bias decided
    picked = np.take_along_axis(s, np.asarray(idx1), -1)
    assert np.allclose(np.asarray(w1),
                       2.5 * picked / picked.sum(-1, keepdims=True),
                       atol=1e-6)  # ... and left the weights alone
    _, w2 = rx.route(u, w, none, 3, 1.0, False)
    assert np.allclose(np.asarray(w2),
                       np.take_along_axis(s, np.asarray(idx0), -1), atol=1e-6)


@pytest.mark.parametrize("kernel", ["gather", "fused"])
@pytest.mark.parametrize("held", [None, (4, 8)])
def test_grouped_product_equals_every_expert_over_every_row(kernel, held):
    """Sorted rows, tile-aligned groups, the Pallas kernel interpreted
    (`fused`) or `ragged_dot` (`gather`): against a loop over rows and
    choices. Rows that are not valid and experts held elsewhere take no
    row and are not counted."""
    rng = np.random.default_rng(0)
    N, k, E, d, m = 24, 4, 16, 128, 128
    u = jnp.asarray(rng.normal(size=(N, d)), jnp.float32)
    idx = jnp.asarray(np.stack([rng.permutation(E)[:k] for _ in range(N)]),
                      jnp.int32)
    w = jnp.asarray(rng.uniform(size=(N, k)), jnp.float32)
    w_gu = rng.normal(size=(E, d, 2 * m)).astype(np.float32) / 11
    w_down = rng.normal(size=(E, m, d)).astype(np.float32) / 11
    valid = rng.uniform(size=N) > 0.2
    lo, hi = held or (0, E)
    want = np.zeros((N, d), np.float32)
    counts = np.zeros(E, int)
    for t in np.nonzero(valid)[0]:
        for j in range(k):
            e = int(idx[t, j])
            if lo <= e < hi:
                g, up = np.split(np.asarray(u[t]) @ w_gu[e], 2)
                want[t] += float(w[t, j]) * (
                    (g / (1 + np.exp(-g)) * up) @ w_down[e])
                counts[e] += 1
    p = {"w_gu": jnp.asarray(w_gu[lo:hi]), "w_down": jnp.asarray(w_down[lo:hi])}
    got, stats = rx.expert_ffn(u, idx, w, p, jnp.asarray(valid), held=held,
                               kernel=kernel)
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    assert list(np.asarray(stats)) == [(counts > 0).sum(), counts.max()]
    dead, stats = rx.expert_ffn(u, idx, w, p, jnp.zeros(N, bool), held=held,
                                kernel=kernel)
    assert np.abs(np.asarray(dead)).max() == 0 and not np.asarray(stats).any()


def test_a_rows_experts_do_not_depend_on_the_rows_beside_it(cfg, params):
    """What the engine's old refusal of experts feared: a capacity that
    couples rows. Here a row's result is the same alone, in a full
    batch and beside rows that do not count — to the last bit of the
    products' summation order (1e-6 on values of size ~1)."""
    p, u32 = _layer_inputs(params, 8)
    full, _ = parts.moe_ffn(u32, p, cfg, jnp.ones(8, bool))
    alone, _ = parts.moe_ffn(u32[3:4], p, cfg, jnp.ones(1, bool))
    beside, stats = parts.moe_ffn(
        u32, p, cfg, jnp.asarray([False] * 3 + [True] + [False] * 4))
    assert np.abs(np.asarray(full[3] - alone[0])).max() < 1e-6
    assert np.abs(np.asarray(full[3] - beside[3])).max() < 1e-6
    # ... and the rows that do not count reached nobody: the shared
    # expert is all they get
    assert int(stats[0]) == SHAPE["top_k"] and int(stats[1]) == 1
    shared = parts.mlp(u32, p["shared"])
    assert np.abs(np.asarray(beside[0] - shared[0])).max() < 1e-6


def test_the_shares_of_eight_chips_add_up_to_the_whole_layer(ref, cfg,
                                                             params):
    """`experts_held`: eight shares of 2 of the 16 experts, each routing
    over all 16 and computing its own experts' part, the shared expert
    counted once, add up to what the uncut reference gives for the
    whole layer."""
    p, u32 = _layer_inputs(params, 10)
    want = np.asarray(ref._experts(
        u32, p, 0, 16, SHAPE["top_k"], SHAPE["route_scale"], True, True,
        None, False, False))
    total = np.zeros_like(want)
    for i in range(8):
        lo, hi = 2 * i, 2 * i + 2
        share = af.AfmoeConfig(max_len=64, dtype=jnp.float32,
                               **dict(SHAPE, experts_held=(lo, hi),
                                      shared_expert_held=(i == 3)))
        mine = dict(p, experts=jax.tree_util.tree_map(
            lambda a: a[lo:hi], p["experts"]))
        part, stats = parts.moe_ffn(u32, mine, share, jnp.ones(10, bool))
        assert int(stats[0]) <= 2
        total += np.asarray(part)
    assert np.abs(total - want).max() < 1e-5
    whole, _ = parts.moe_ffn(u32, p, cfg, jnp.ones(10, bool))
    assert np.abs(np.asarray(whole) - want).max() < 1e-5
    with pytest.raises(ValueError, match="experts_held"):
        af.AfmoeConfig(**dict(SHAPE, experts_held=(8, 20)))


# ---------------------------------------------------------------------
# the two caches
# ---------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _jitted(fn, cfg, kernel):
    """The model's step compiled once a kernel, as the engine does."""
    return jax.jit(functools.partial(fn, cfg=cfg, kernel=kernel))


class _Slot(object):
    """One slot's host bookkeeping, as the engine keeps it."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.cache = af.SERVING.init_cache(cfg, 40, BT, SLOTS)
        self.win = WindowBlockTables(SLOTS, MAXB, BT, cfg.window)
        self.ftab = np.full((SLOTS, MAXB), -1, np.int32)
        self.next_block = 0
        for s in range(SLOTS):
            assert self.win.admit(s, BT * MAXB)

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
        logits, self.cache = _jitted(af.paged_prefill_chunk, self.cfg,
                                     kernel)(
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
        logits, self.cache, stats = _jitted(af.paged_decode_step, self.cfg,
                                            kernel)(
            params, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(np.stack([self.ftab, self.win.tables])), self.cache)
        return np.asarray(logits), np.asarray(stats)


@pytest.mark.parametrize("kernel", ["gather", "fused"])
@pytest.mark.parametrize("plan", [
    ((32, 32),),                   # one chunk, past the 12-token window
    ((16, 16), (16, 16)),          # two chunks, edges on the 4-row block
    ((5, 8), (14, 16), (8, 8)),    # three chunks, edges off the block
    ((16, 16), (11, 16)),          # a padded last bucket
], ids=["one", "two_on", "three_off", "padded_last"])
def test_chunked_prefill_then_decode_equals_the_full_forward(
        cfg, params, tokens, ref_logits, plan, kernel):
    """Prefill in chunks (a window layer attends its own rows and the
    window behind the chunk, read through the table as it stood before
    the chunk's release; the full layer the slot's span through its
    table), then decode to position 39, the window release running all
    the way: the logits at every chunk's last row and at every decoded
    position are the reference's full forward's — rotated window keys,
    position-free full keys, sorted grouped experts against the
    reference's every-expert-over-every-row."""
    st = _Slot(cfg)
    cursor = 0
    for c, bucket in plan:
        got = st.chunk(params, 1, tokens, cursor, c, bucket, kernel)
        cursor += c
        assert np.abs(got - ref_logits[cursor - 1]).max() < TOL
    for p in range(cursor, 40):
        got, stats = st.decode(params, {1: (tokens[p], p)}, kernel)
        assert np.abs(got[1] - ref_logits[p]).max() < TOL
        # one live row: top-4 distinct experts in each of 4 layers
        assert list(stats) == [16, 1]
    # contexts past window + one block: blocks were freed, the bound held
    assert st.win.released_total >= 2
    assert st.win.held(1) <= st.win.per_slot == 4


@pytest.mark.parametrize("kernel", ["gather", "fused"])
def test_parked_slots_blocks_are_untouched_by_other_slots_steps(
        cfg, params, tokens, kernel):
    """A parked row writes no K/V, reaches no expert and is not
    counted."""
    st = _Slot(cfg)
    for s in (0, 2):
        st.chunk(params, s, tokens, 0, 8, 8, "gather")
    mine = np.asarray([b for b in st.win.tables[2] if b >= 0])
    before = [np.asarray(pool["k"][mine]).copy()
              for pool in st.cache["window"]]
    for p in range(8, 14):  # slot 2 parked: only slot 0 steps
        _, stats = st.decode(params, {0: (tokens[p], p)}, kernel)
        assert list(stats) == [16, 1]
    for a, pool in zip(before, st.cache["window"]):
        assert np.array_equal(a, np.asarray(pool["k"][mine]))


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
    """Six requests over three slots (so slots are re-used), prompts
    chunked at 16 (one, two and three chunks), contexts crossing the
    12-token window, one request ended by its EOS on the device and one
    cancelled mid-decode, at either depth of the one decode loop: every
    greedy token is the reference's argmax at its position, decode is
    traced once across the waves, and every block and reservation of
    both tables is back when the engine drains. The family keeps no
    recurrent state: the seam builds window tables WITHOUT state
    handling — no `engine.state_reset` span, `state_slots_reset` 0 —
    and the router's counters come with every decode step read."""
    eng = _engine(params, cfg, async_dispatch=depth)
    assert eng.async_dispatch == (depth is None)
    assert isinstance(eng._win, WindowBlockTables) and not eng._has_state
    assert eng._state_bytes_per_slot == 0
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
    assert m.state_slots_reset == 0 and m.window_blocks_released > 0
    assert set(m.cache_bytes_in_use) == {"full", "window"}
    assert m.decode_trace_count() == 1
    # the router's counters: one reading a decode step read; a live row
    # reaches 4 distinct experts in each of the 4 expert layers
    assert m.moe_experts_hit.count == m.moe_rows_max.count > 0
    assert 16 <= m.moe_experts_hit.mean <= 4 * 16
    assert 1 <= m.moe_rows_max.mean <= SLOTS
    rep = m.report()
    assert rep["mean_moe_experts_hit"] == round(m.moe_experts_hit.mean, 6)
    if depth is None:
        assert m.decode_dispatched_ahead > 0 and m.decode_chain_breaks > 0
    assert eng._alloc.blocks_in_use == 0 and eng._alloc.reserved == 0
    assert eng._win.alloc.blocks_in_use == 0 and eng._win.alloc.reserved == 0


def test_no_state_reset_span_and_a_flat_window_cache(cfg, params):
    """One request decoding far past window + one block: the window
    pools' bytes stop growing at ceil(W / Bt) + 1 blocks a slot while
    the full pool's follow the context, so `cache_bytes_per_slot` rises
    by the full pool's block alone; and no span of the run is
    `engine.state_reset`."""
    eng = _engine(params, cfg, max_slots=1)
    seen = []
    phase = eng.metrics.phase

    def spy(name, *a, **kw):
        seen.append(name)
        return phase(name, *a, **kw)

    eng.metrics.phase = spy
    eng.submit(_prompts(3, 6)[0], 50)
    by_kind = []
    while eng.step():
        used = eng.metrics.cache_bytes_in_use
        if used is not None:
            by_kind.append(dict(used))
    assert "engine.state_reset" not in seen
    assert "engine.window_release" in seen and "engine.decode" in seen
    sizes = af.cache_bytes(cfg, BT)
    window = [u["window"] for u in by_kind]
    assert max(window) == eng._win.per_slot * sizes["window"]
    assert window[-1] <= max(window)  # flat once past the window
    full = [u["full"] for u in by_kind]
    assert full[-1] > full[0] and full[-1] >= 13 * sizes["full"]
    assert eng.metrics.window_blocks_released >= 9
    assert eng.metrics.state_slots_reset == 0


@pytest.mark.parametrize("option,value", [
    ("prefix_cache_tokens", 64), ("kv_store", object()),
    ("spec_draft_len", 4), ("kv_quant", "int8"), ("weight_quant", "int8"),
    ("adapter_registry", object()), ("kv_fingerprints", True),
    ("handoff", [{"key": 1}])])
def test_each_unsupported_option_is_refused_with_the_familys_reason(
        cfg, params, option, value):
    """A freed window block cannot be aliased, stored, handed on or
    re-played: refused at construction (hand-off import at `submit`),
    each by its name, with THIS family's reason — not the recurrent
    state the two hybrid families give."""
    assert set(af.SERVING.refused) == {
        "prefix_cache_tokens", "kv_store", "spec_draft_len", "kv_quant",
        "weight_quant", "adapter_registry", "kv_fingerprints"}
    with pytest.raises(ValueError, match=option) as err:
        if option == "handoff":
            _engine(params, cfg).submit(np.arange(5, dtype=np.int32), 4,
                                        handoff=value)
        else:
            _engine(params, cfg, **{option: value})
    assert "behind the window" in str(err.value)
    assert "recurrent state" not in str(err.value)


@pytest.mark.parametrize("family", ["sambay", "granite_hybrid"])
def test_the_state_families_still_give_their_own_reason(family):
    """The refusal's reason comes from the family's seam: the two
    families with recurrent state say so, as they did."""
    from paddle_tpu.models import granite_hybrid as gh
    from paddle_tpu.models import sambay as sb

    if family == "sambay":
        c = sb.SambaYConfig(vocab=64, dim=64, heads=8, kv_heads=4, layers=4,
                            window=8, max_len=32)
        p = jax.eval_shape(lambda: sb.init_params(c, jax.random.PRNGKey(0)))
    else:
        c = gh.GraniteHybridConfig(vocab=64, dim=32, max_len=32)
        p = jax.eval_shape(lambda: gh.init_params(c, jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="prefix_cache_tokens") as err:
        ServingEngine(p, c, max_slots=2, kv_block_tokens=4,
                      paged_kernel="gather", prefix_cache_tokens=64)
    assert "keeps recurrent state" in str(err.value)
    assert repr(c.serving.name) in str(err.value)
