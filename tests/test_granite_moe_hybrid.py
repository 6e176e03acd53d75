"""The Mamba-2 / grouped-query hybrid WITH routed experts:
granite-4.0-h-small's block through `models/granite_hybrid.py` against
its plain reference, the published modeling code, its routing, the
chip's share of the expert layer, its one-head-a-row pool and the
engine seam.

Small on the CPU: a Mamba-2, an attention and a Mamba-2 layer; 8
experts top-3 beside a shared MLP; heads 128 wide (so the pool holds
one K/V head a row, as at the published widths); seeded random weights
from the reference's own initialiser
(`benchmarks/chip/references/granite_moe_hybrid_plain.py`, which
imports nothing of the program). Tolerances: the program and the
reference are both float32 here (conftest pins float32 matmuls), so
they differ by summation order alone, a few 1e-7 on logits of size ~1
through three layers, the blocked scan against the sequential
recurrence and the grouped products against every expert over every
row included. `TOL` = 2e-5 leaves fifty times that room and is
thousands of times below what the int8 control and the routing faults
move the same logits by, 18 times below what a bfloat16 state does
over 40 tokens (`test_full_forward_against_the_reference`).
"""

import functools
import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import granite_hybrid as gh
from paddle_tpu.models import parts
from paddle_tpu.parallel import routed_experts as rx
from paddle_tpu.serving import ServingEngine

TOL = 2e-5
SHAPE = {"vocab": 300, "dim": 512, "heads": 4, "kv_heads": 2,
         "head_dim": 128, "layers": 3,
         "layer_types": ["mamba", "attention", "mamba"], "mlp_width": 48,
         "n_experts": 8, "top_k": 3, "expert_width": 32,
         "mamba_heads": 16, "mamba_head_dim": 64, "d_state": 16,
         "d_conv": 4, "chunk": 8, "embedding_multiplier": 2.0,
         "residual_multiplier": 0.22, "attention_multiplier": 0.0078125,
         "logits_scaling": 8.0}
BT, SLOTS, MAXB = 4, 3, 16
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
PUBLISHED = dict(
    vocab=100352, dim=4096, heads=32, kv_heads=8, head_dim=128, layers=40,
    layer_types=PERIOD * 4, mlp_width=1536, n_experts=72, top_k=10,
    expert_width=768, mamba_heads=128, mamba_head_dim=64, d_state=128,
    d_conv=4, chunk=256)
CUT = dict(PUBLISHED, vocab=50176, layers=10, layer_types=PERIOD,
           experts_held=(0, 36))


def _reference():
    path = (pathlib.Path(__file__).resolve().parent.parent / "benchmarks"
            / "chip" / "references" / "granite_moe_hybrid_plain.py")
    spec = importlib.util.spec_from_file_location("granite_moe_hybrid_plain",
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


def test_parameter_counts_of_the_published_shape_and_of_the_cut(ref):
    """Shapes only, no arrays. A Mamba-2 layer outside its FFN is
    102.3 M, the attention layer 41.95 M; an FFN is the router 0.29 M,
    the shared MLP 18.87 M and 9.44 M an expert. The published model
    (all 72 experts, the whole vocabulary) is 32.2 B; the chip's share
    (one 10-layer period, 36 experts, half the vocabulary) 4.757 B. The
    program's tree and the reference's count the same."""
    mamba = (4096 * 16768 + 8448 * 4 + 8448 + 3 * 128 + 8192
             + 8192 * 4096 + 2 * 4096)
    attn = 4096 * 6144 + 4096 * 4096 + 2 * 4096
    expert = 4096 * 1536 + 768 * 4096
    assert (mamba, attn, expert) == (102_295_168, 41_951_232, 9_437_184)
    ffn = 4096 * 72 + 3 * 4096 * 1536

    def count(E, vocab, layers):
        return (layers // 10 * (9 * mamba + attn)
                + layers * (ffn + E * expert) + vocab * 4096 + 4096)

    whole = gh.GraniteHybridConfig(**PUBLISHED)
    assert whole.experts_held == (0, 72) and whole.serving is \
        gh.SERVING_EXPERTS
    assert parts.param_count(gh.param_shapes(whole)) \
        == count(72, 100352, 40) == ref.param_count(PUBLISHED)
    assert 32.1e9 < parts.param_count(gh.param_shapes(whole)) < 32.3e9
    cut = gh.GraniteHybridConfig(**CUT)
    assert parts.param_count(gh.param_shapes(cut)) \
        == count(36, 50176, 10) == ref.param_count(CUT) == 4_757_211_776
    assert mamba + ffn + 36 * expert == 461_203_072
    assert attn + ffn + 36 * expert == 400_859_136
    # one pool row a K/V head, 128 wide; 4 MB of state a layer and slot
    assert (cut.paired, cut.groups, cut.row) == (False, 8, 128)
    assert gh.cache_bytes(cut, 32) == {
        "full": 2 * 32 * 8 * 128 * 4, "call_block": 2 * 32 * 8 * 128 * 4,
        "state": 9 * (128 * 8192 * 4 + 3 * 8448 * 4)}


def test_the_dense_config_builds_as_it_did():
    """h-micro's shape (the benchmark's file: `mlp_mult`, no expert
    key) keeps two 64-wide heads a pool row, its MLP 4 x dim wide, and
    the seam without step counters; a head too wide to pair lifts the
    even-kv_heads rule, and expert keys without experts are refused."""
    micro = gh.GraniteHybridConfig(
        vocab=100352, dim=2048, heads=32, kv_heads=8, head_dim=64,
        layer_types=PERIOD * 4, mlp_mult=4, mamba_heads=64,
        mamba_head_dim=64, d_state=128)
    assert (micro.paired, micro.groups, micro.row) == (True, 4, 128)
    assert micro.mlp_width == 8192 and micro.n_experts == 0
    assert micro.serving is gh.SERVING
    assert gh.SERVING.step_counters == ()
    assert parts.param_count(gh.param_shapes(micro)) == 3_191_396_096
    with pytest.raises(ValueError, match="kv_heads even"):
        gh.GraniteHybridConfig(heads=6, kv_heads=3, head_dim=64)
    assert gh.GraniteHybridConfig(heads=6, kv_heads=3,
                                  head_dim=128).groups == 3
    with pytest.raises(ValueError, match="n_experts is 0"):
        gh.GraniteHybridConfig(top_k=2)
    with pytest.raises(ValueError, match="experts_held"):
        gh.GraniteHybridConfig(n_experts=8, top_k=2, expert_width=4,
                               experts_held=(4, 9))
    with pytest.raises(ValueError, match="top_k"):
        gh.GraniteHybridConfig(n_experts=8, top_k=9, expert_width=4)


def test_init_params_has_the_references_tree(cfg, params):
    mine = gh.init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape
    # an expert's matrices are drawn by their own rows, not by the stack
    w = np.asarray(mine["blocks"][0]["experts"]["w_down"])
    assert abs(w.std() - 32 ** -0.5) < 0.02


@pytest.mark.parametrize("who", ["program", "int8", "bf16_state",
                                 "sigmoid_router", "softmax_all",
                                 "no_shared_expert"])
def test_full_forward_against_the_reference(ref, cfg, params, tokens,
                                            ref_logits, who):
    """The program's full forward lies within TOL of the reference's
    logits; the reference computed in int8, and each planted fault,
    does not: int8 and the three routing faults move them by 0.13-0.52
    (logits up to 1.3), a state rounded to bfloat16 by 3.7e-4 over
    these 40 tokens (18 x TOL: an error that grows with the tokens the
    state has carried, read at the cell's sizes in PERF.md)."""
    if who == "program":
        got = np.asarray(gh.forward(params, jnp.asarray(tokens), cfg))
        assert np.abs(got - ref_logits).max() < TOL
        return
    quant, fault = ("int8", None) if who == "int8" else (None, who)
    ctrl = np.asarray(ref.logits(params, tokens, SHAPE, quant, fault))
    assert np.abs(ctrl - ref_logits).max() > 10 * TOL


def test_route_softmax_over_the_top_k_is_the_published_gating():
    """`route(scoring="softmax_topk")` against a NumPy transcription of
    `GraniteMoeHybridTopKGating`: top-k of the float32 logits, a
    softmax over those k alone; no bias, no normalisation, no scale.
    The sigmoid scoring beside it chooses by its biased scores."""
    rng = np.random.default_rng(4)
    u = rng.standard_normal((37, 64)).astype(np.float32)
    w_r = (rng.standard_normal((64, 72)) / 8).astype(np.float32)
    idx, w = rx.route(jnp.asarray(u), jnp.asarray(w_r), None, 10,
                      scoring="softmax_topk")
    logits = u.astype(np.float64) @ w_r.astype(np.float64)
    want = np.argsort(-logits, axis=-1, kind="stable")[:, :10]
    assert np.array_equal(np.asarray(idx), want)
    top = np.take_along_axis(logits, want, axis=-1)
    e = np.exp(top - top.max(-1, keepdims=True))
    assert np.abs(np.asarray(w) - e / e.sum(-1, keepdims=True)).max() < 1e-6
    assert np.abs(np.asarray(w).sum(-1) - 1.0).max() < 1e-6
    bias = np.zeros(72, np.float32)
    bias[0] = 10.0  # decides the sigmoid scoring's choice only
    s_idx, _ = rx.route(jnp.asarray(u), jnp.asarray(w_r), jnp.asarray(bias),
                        10, scoring="sigmoid")
    assert (np.asarray(s_idx)[:, 0] == 0).all()
    with pytest.raises(ValueError, match="scores"):
        rx.route(jnp.asarray(u), jnp.asarray(w_r), None, 10,
                 scoring="softmax")


def _ffn(cfg, blk, u32, valid):
    return jax.jit(functools.partial(gh._moe, cfg=cfg, valid=valid,
                                     kernel="gather"))(u32, blk)


def test_the_shares_of_two_chips_add_up_to_the_whole_layer(ref, params):
    """Guide section 4's share test: each half of the experts
    (`experts_held` (0, 4), (4, 8), the leaves cut to those experts)
    gives its part of the layer with the shared MLP; the two parts,
    the shared MLP counted once, equal the uncut reference's layer —
    every expert over every row, weighted by its own top-k softmax."""
    blk = params["blocks"][0]
    rng = np.random.default_rng(5)
    u32 = jnp.asarray(rng.standard_normal((19, SHAPE["dim"])), jnp.float32)
    valid = jnp.ones(19, bool)
    shared = np.asarray(gh._mlp(u32, blk))
    parts = []
    for lo, hi in ((0, 4), (4, 8)):
        c = gh.GraniteHybridConfig(dtype=jnp.float32,
                                   **dict(SHAPE, experts_held=(lo, hi)))
        cut = dict(blk, experts={k: v[lo:hi]
                                 for k, v in blk["experts"].items()})
        out, stats = _ffn(c, cut, u32, valid)
        assert 0 < int(stats[0]) <= 4
        parts.append(np.asarray(out) - shared)
    whole = np.asarray(jax.jit(functools.partial(
        ref._ffn, lo=0, hi=8, top_k=3, quant=None, fault=None))(u32, blk))
    assert np.abs(parts[0] + parts[1] + shared - whole).max() < TOL
    assert min(np.abs(p).max() for p in parts) > 100 * TOL


def test_rows_that_do_not_count_reach_no_expert(cfg, params):
    """A dead slot or a bucket's padding row: the counters count the
    valid rows' experts alone, and such a row gets the shared MLP and
    nothing routed; a valid row's result is what it is alone."""
    blk = params["blocks"][1]
    rng = np.random.default_rng(6)
    u32 = jnp.asarray(rng.standard_normal((12, SHAPE["dim"])), jnp.float32)
    valid = np.zeros(12, bool)
    valid[[2, 7]] = True
    out, stats = _ffn(cfg, blk, u32, jnp.asarray(valid))
    alone, alone_stats = _ffn(cfg, blk, u32[np.array([2, 7])], jnp.ones(2, bool))
    assert np.array_equal(np.asarray(stats), np.asarray(alone_stats))
    assert 3 <= int(stats[0]) <= 6
    assert np.abs(np.asarray(out)[valid] - np.asarray(alone)).max() < TOL
    shared = np.asarray(gh._mlp(u32, blk))
    assert np.abs(np.asarray(out)[~valid] - shared[~valid]).max() < TOL
    _, none = _ffn(cfg, blk, u32, jnp.zeros(12, bool))
    assert np.asarray(none).tolist() == [0, 0]


@functools.lru_cache(maxsize=None)
def _jitted(fn, cfg, kernel):
    """The model's step compiled once a kernel, as the engine does."""
    return jax.jit(functools.partial(fn, cfg=cfg, kernel=kernel))


class _Slot(object):
    """One slot's host bookkeeping, as the engine keeps it."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.cache = gh.SERVING_EXPERTS.init_cache(cfg, 40, BT, SLOTS)
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
        logits, self.cache = _jitted(gh.paged_prefill_chunk, self.cfg,
                                     "gather")(
            params, self.cache, jnp.asarray(padded), jnp.int32(cursor),
            jnp.asarray(rows), true_len=jnp.int32(c))
        return np.asarray(logits)

    def decode(self, params, toks_at, kernel):
        """`toks_at`: {slot: (token, position)}; the others are parked.
        -> (logits, the step's counters)."""
        pos = np.full(SLOTS, MAXB * BT, np.int32)
        tok = np.zeros(SLOTS, np.int32)
        for s, (t, p) in toks_at.items():
            self._ensure(s, p, p + 1)
            pos[s], tok[s] = p, t
        logits, self.cache, stats = _jitted(gh.paged_decode_step, self.cfg,
                                            kernel)(
            params, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(self.tab), self.cache)
        return np.asarray(logits), np.asarray(stats)


@pytest.mark.parametrize("kernel", ["gather", "fused"])
def test_chunked_prefill_then_decode_equals_the_full_forward(
        cfg, params, tokens, ref_logits, kernel):
    """Prefill in two chunks (the second through the table, with the
    state and the conv rows carried) and a padded last bucket, then
    decode to position 24 beside a parked slot: the logits at every
    chunk's last row and at every decoded position are the reference's
    full forward's; with kernel="fused" the pool write, the decode
    attention over one-head rows, the state update and the grouped
    products are the Pallas kernels, interpreted. The parked slot
    reaches no expert: a step's counters are one row's."""
    st = _Slot(cfg)
    cursor = 0
    for c, bucket in ((8, 8), (5, 8)):
        got = st.chunk(params, 1, tokens, cursor, c, bucket)
        cursor += c
        assert np.abs(got - ref_logits[cursor - 1]).max() < TOL
    for p in range(cursor, 24):
        got, stats = st.decode(params, {1: (tokens[p], p)}, kernel)
        assert np.abs(got[1] - ref_logits[p]).max() < TOL
        # one live row, top-3 of 8 in each of 3 layers
        assert stats.tolist() == [9, 1]


def test_the_counters_ride_the_engines_step(ref, cfg, params):
    """Through ServingEngine: the seam of a config with experts carries
    `step_counters`, the decode step's two counters reach the metrics
    under their names, every greedy token is the reference's argmax at
    its position, and decode is traced once."""
    eng = ServingEngine(params, cfg, max_slots=SLOTS, kv_block_tokens=BT,
                        kv_pool_blocks=40, min_bucket=8,
                        prefill_chunk_tokens=8, paged_kernel="gather")
    assert eng._step_counters == ("moe_experts_hit", "moe_rows_max")
    assert eng._has_state and eng._win is None
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, SHAPE["vocab"], n).astype(np.int32)
               for n in (11, 5, 17, 9)]
    hs = [eng.submit(p, n) for p, n in zip(prompts, (9, 12, 6, 10))]
    eng.run()
    for prompt, h in zip(prompts, hs):
        served = np.asarray(h.tokens, np.int32)
        want = np.asarray(ref.logits(
            params, np.concatenate([prompt, served]), SHAPE))
        assert np.array_equal(
            want[len(prompt) - 1:len(prompt) - 1 + len(served)].argmax(-1),
            served)
    m = eng.metrics
    assert m.moe_experts_hit.count > 0 and m.moe_rows_max.count > 0
    # 1-3 live rows x top-3 of 8 experts, 3 layers
    assert 3 <= m.moe_experts_hit.mean <= 24
    assert 1 <= m.moe_rows_max.mean <= SLOTS
    assert m.decode_trace_count() == 1
    assert eng._alloc.blocks_in_use == 0 and eng._alloc.reserved == 0


def test_the_reference_is_the_published_modeling_code(ref, params, tokens,
                                                      ref_logits,
                                                      monkeypatch):
    """`GraniteMoeHybridForCausalLM` of the installed transformers, at
    the same small config with the same weights copied in, gives the
    reference's logits: it anchors the equations (the split of W_in,
    the gated norm, the GQA heads, the router's top-k softmax, the
    shared MLP beside the experts, the four multipliers) to the
    published code, in float32 (its gating rounds the router's product
    to the weights' dtype, float32 here). The two differ by summation
    order (the published chunked scan against the sequential one) and
    torch's float32 products: 1e-4 on logits up to 1.3."""
    # the published code's own framework only: transformers imports
    # TensorFlow where it finds one, which takes seconds and nothing here
    monkeypatch.setenv("USE_TF", "0")
    monkeypatch.setenv("USE_FLAX", "0")
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    from transformers import GraniteMoeHybridConfig
    from transformers.models.granitemoehybrid.modeling_granitemoehybrid \
        import GraniteMoeHybridForCausalLM

    s = SHAPE
    hf = GraniteMoeHybridConfig(
        vocab_size=s["vocab"], hidden_size=s["dim"],
        intermediate_size=s["expert_width"],
        shared_intermediate_size=s["mlp_width"],
        num_hidden_layers=s["layers"], layer_types=s["layer_types"],
        num_attention_heads=s["heads"], num_key_value_heads=s["kv_heads"],
        num_local_experts=s["n_experts"], num_experts_per_tok=s["top_k"],
        mamba_n_heads=s["mamba_heads"], mamba_d_head=s["mamba_head_dim"],
        mamba_d_state=s["d_state"], mamba_d_conv=s["d_conv"],
        mamba_chunk_size=s["chunk"], mamba_expand=2, mamba_n_groups=1,
        mamba_conv_bias=True, mamba_proj_bias=False,
        embedding_multiplier=s["embedding_multiplier"],
        residual_multiplier=s["residual_multiplier"],
        attention_multiplier=s["attention_multiplier"],
        logits_scaling=s["logits_scaling"], rms_norm_eps=1e-5,
        position_embedding_type="nope", tie_word_embeddings=True,
        attention_bias=False)
    model = GraniteMoeHybridForCausalLM(hf).float().eval()

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))

    nq = s["heads"] * s["head_dim"]
    nk = s["kv_heads"] * s["head_dim"]
    with torch.no_grad():
        model.model.embed_tokens.weight.copy_(t(params["embed"]))
        model.model.norm.weight.copy_(t(params["norm_f"]))
        for layer, blk in zip(model.model.layers, params["blocks"]):
            p = blk["mixer"]
            layer.input_layernorm.weight.copy_(t(blk["norm1"]))
            layer.post_attention_layernorm.weight.copy_(t(blk["norm2"]))
            if layer.mamba is not None:
                mb = layer.mamba
                mb.in_proj.weight.copy_(t(p["in_proj"]).T)
                mb.conv1d.weight.copy_(t(p["conv_w"])[:, None, :])
                mb.conv1d.bias.copy_(t(p["conv_b"]))
                mb.dt_bias.copy_(t(p["dt_bias"]))
                mb.A_log.copy_(t(p["A_log"]))
                mb.D.copy_(t(p["D"]))
                mb.norm.weight.copy_(t(p["norm"]))
                mb.out_proj.weight.copy_(t(p["out_proj"]).T)
            else:
                at = layer.self_attn
                w = t(p["wqkv"])
                at.q_proj.weight.copy_(w[:, :nq].T)
                at.k_proj.weight.copy_(w[:, nq:nq + nk].T)
                at.v_proj.weight.copy_(w[:, nq + nk:].T)
                at.o_proj.weight.copy_(t(p["wo"]).T)
            moe = layer.block_sparse_moe
            moe.router.layer.weight.copy_(t(blk["router"]).T)
            moe.input_linear.weight.copy_(
                t(blk["experts"]["w_gu"]).transpose(1, 2))
            moe.output_linear.weight.copy_(
                t(blk["experts"]["w_down"]).transpose(1, 2))
            layer.shared_mlp.input_linear.weight.copy_(t(blk["w_gu"]).T)
            layer.shared_mlp.output_linear.weight.copy_(t(blk["w_down"]).T)
        out = model(torch.tensor(tokens[None, :24].astype(np.int64)),
                    use_cache=False).logits[0].numpy()
    assert np.abs(out - ref_logits[:24]).max() < 1e-4


def test_the_first_layers_state_tells_a_bfloat16_state_from_bf16_compute(
        ref):
    """What the cell's `state_err_first_layer_max` rests on, with the
    cell's dtype and state width: bfloat16 weights and activations, a
    64-token prompt in one chunk, then 400 tokens decoded one at a time
    through the state cache. The first Mamba-2 layer's state, head by
    head, against the float32 reference's after the same tokens: the
    program's own departure there is the rounding of its in-projection
    (its inputs are embedding rows in both), ~2^-9 a value, which a
    decaying sum does not grow: 0.0060 at most over the 4 heads. The
    same program with its state rounded to bfloat16 after every step
    (what keeping the state in bfloat16 would do) reads 0.0293, and the
    float32 reference with a bfloat16 state 0.0291: a slow head sums
    the rounding over the tokens it remembers. Deeper layers carry every
    earlier layer's bf16 rounding, and there a bf16 state lies inside
    it, as it does on the logits."""
    shape = dict(SHAPE, layers=2, layer_types=["mamba", "attention"],
                 mamba_heads=4, d_state=128, embedding_multiplier=12.0)
    bt, n0, n1 = 16, 64, 400
    maxb = -(-(n0 + n1) // bt)
    cfg = gh.GraniteHybridConfig(max_len=maxb * bt, dtype=jnp.bfloat16,
                                 **shape)
    params = ref.init_weights(shape, maxb * bt, 5, dtype="bfloat16")
    # head 0 at the slow corner of the published initialisers' range
    # (A = 1, a step of 1e-3: it remembers ~1,000 tokens), which the
    # cell's 128 heads a layer reach and this file's 16 need not
    mix = params["blocks"][0]["mixer"]
    mix["A_log"] = mix["A_log"].at[0].set(0.0)
    mix["dt_bias"] = mix["dt_bias"].at[0].set(math.log(math.expm1(1e-3)))
    seq = np.random.default_rng(5).integers(0, shape["vocab"],
                                            n0 + n1).astype(np.int32)
    _, want = ref.hidden_and_states(params, seq, shape, n0 + n1 - 1)
    _, faulty = ref.hidden_and_states(params, seq, shape, n0 + n1 - 1,
                                      fault="bf16_state")
    chunk = jax.jit(functools.partial(gh.paged_prefill_chunk, cfg=cfg))
    step = jax.jit(lambda p, t, q, tab, c: gh.paged_decode_step(
        p, t, q, tab, c, cfg)[1])
    tab = np.arange(maxb, dtype=np.int32)[None]

    def first_state(rounded):
        cache = gh.init_cache(cfg, maxb, bt, 1)

        def keep(c):
            return c if not rounded else dict(c, ssm=[
                dict(st, s=st["s"].astype(jnp.bfloat16).astype(jnp.float32))
                for st in c["ssm"]])

        _, cache = chunk(params, cache, jnp.asarray(seq[:n0]), jnp.int32(0),
                         jnp.asarray(np.stack([tab[0], 0 * tab[0]])))
        cache = keep(cache)
        for p in range(n0, n0 + n1 - 1):
            cache = keep(step(params, jnp.asarray(seq[p:p + 1]),
                              jnp.asarray([p], np.int32), jnp.asarray(tab),
                              cache))
        return np.asarray(cache["ssm"][0]["s"][0])

    def worst(got):
        return ref.state_err(got, want[0], shape["mamba_head_dim"]).max()

    sound, rounded = worst(first_state(False)), worst(first_state(True))
    assert sound < 0.02 < min(rounded, worst(faulty[0]))
