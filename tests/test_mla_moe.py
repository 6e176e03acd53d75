"""The latent-attention sparse-expert family (ISSUE 37): the model
module against its plain reference, the latent cache, the absorbed
decode call and the engine seam.

Small on the CPU: one dense layer and two expert layers, 4 heads of
nope 16 + rope 8 against a 32-wide latent (a 128-lane stored row), 8
experts top-2 of which the chip holds all or a share, a 2-expert-wide
shared expert, seeded random weights from the reference's own
initialiser (`benchmarks/chip/references/mla_moe_plain.py`, which
imports nothing of the program, computes the published EXPANDED form,
evaluates every expert over every row and routes by its own top-k).
Tolerances: the program and the reference are both float32 here
(conftest pins float32 matmuls), so they differ by summation order
alone — the absorbed products' re-association, the online softmax of
the kernel and the key-tiled chunk, the sorted grouped product against
one expert at a time over all rows — a few 1e-6 on logits of size ~4
through 3 layers. `TOL` = 5e-5 leaves ten times that room and is still
a thousand times below what the int8 control moves the same logits by
(the `int8` case of `test_full_forward_against_the_reference`). The
seeds are fixed: a router's near-tie that float32 summation order
decides would move a logit by what an expert weighs, far over `TOL`,
and none occurs on them.
"""

import functools
import importlib.util
import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import mla_moe as ml
from paddle_tpu.models import parts
from paddle_tpu.parallel import paged_attention as pa
from paddle_tpu.serving import ServingEngine

TOL = 5e-5
SHAPE = {"vocab": 300, "dim": 64, "heads": 4, "nope_dim": 16, "rope_dim": 8,
         "v_dim": 16, "kv_rank": 32, "layers": 3, "num_dense_layers": 1,
         "dense_width": 96, "expert_width": 16, "n_shared_experts": 2,
         "n_experts": 8, "top_k": 2, "route_scale": 2.448,
         "route_norm": True, "rope_theta": 10000.0}
BT, SLOTS, MAXB = 4, 3, 16
ROOT = pathlib.Path(__file__).resolve().parent.parent


def _published():
    return json.loads((ROOT / "benchmarks" / "chip" / "configs"
                       / "kanana_2_30b_a3b.json").read_text())


def _reference():
    path = ROOT / "benchmarks" / "chip" / "references" / "mla_moe_plain.py"
    spec = importlib.util.spec_from_file_location("mla_moe_plain", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _reference()


@pytest.fixture(scope="module")
def cfg():
    return ml.MlaMoeConfig(max_len=BT * MAXB, dtype=jnp.float32, **SHAPE)


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
    """Shapes only, no arrays. Published whole: 1 dense + 47 expert
    layers of 128 experts = 30.67 B ("30B-A3B"), by formula; the cut the
    benchmark serves (1 dense + 7 expert layers, 16 of the 128 experts,
    the whole vocabulary) is the count its configuration file states,
    1,370.3 M; the program's tree and the reference's count the same.
    The latent cache stores 640 values a token and layer (576 of data),
    1,280 B as stored."""
    conf = _published()
    cut, pub = conf["shape"], conf["published"]
    whole = dict(cut, layers=pub["num_hidden_layers"],
                 experts_held=[0, pub["n_routed_experts"]])
    d, V, E, m = 2048, 128256, 128, 768
    attn = (d * 32 * 192 + d * 576 + 512 * 32 * 256 + 32 * 128 * d)
    assert attn == 26_345_472
    norms = 2 * d + 512

    def expert_layer(held):
        return (attn + norms + d * E + E + 3 * d * 2 * m
                + held * 3 * d * m)

    dense_layer = attn + norms + 3 * d * 6144
    for shape, held, expert in ((whole, 128, 47), (cut, 16, 7)):
        n = parts.param_count(ml.param_shapes(ml.MlaMoeConfig(**shape)))
        assert n == ref.param_count(shape)
        assert n == (dense_layer + expert * expert_layer(held)
                     + 2 * V * d + d)
    assert parts.param_count(ml.param_shapes(ml.MlaMoeConfig(**whole))) == \
        conf["parameters_published"] == 30_670_815_104
    assert parts.param_count(ml.param_shapes(ml.MlaMoeConfig(**cut))) \
        == conf["parameters"] == 1_370_266_496
    # every width, the router's 128 outputs, top-6 and the vocabulary
    # are the published ones; the depth, the experts held and the
    # position cap are the cut
    assert (cut["dim"], cut["heads"], cut["nope_dim"], cut["rope_dim"],
            cut["v_dim"], cut["kv_rank"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["qk_nope_head_dim"], pub["qk_rope_head_dim"],
        pub["v_head_dim"], pub["kv_lora_rank"])
    assert (cut["n_experts"], cut["top_k"], cut["expert_width"],
            cut["dense_width"], cut["n_shared_experts"], cut["vocab"],
            cut["route_scale"], cut["rope_theta"]) == (
        pub["n_routed_experts"], pub["num_experts_per_tok"],
        pub["moe_intermediate_size"], pub["intermediate_size"],
        pub["n_shared_experts"], pub["vocab_size"],
        pub["routed_scaling_factor"], pub["rope_theta"])
    assert cut["experts_held"] == [0, conf["n_routed_experts"]] == [0, 16]
    c = ml.MlaMoeConfig(dtype=jnp.bfloat16, **cut)
    assert c.latent_row == 640
    assert c.attention_multiplier == pytest.approx(192 ** -0.5, rel=1e-12)
    # a block of ONE pool: 32 tokens x 640 lanes x 2 B
    assert ml.cache_bytes(c, 32) == {"full": 8 * 40960, "call_block": 40960}


def test_init_params_has_the_references_tree(cfg, params):
    mine = ml.init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape


@pytest.mark.parametrize("who", ["program", "int8", "no_bias", "no_rope"])
def test_full_forward_against_the_reference(ref, cfg, params, tokens,
                                            ref_logits, who):
    """The program's full forward (the pairs rotated in place, read
    de-interleaved) lies within TOL of the reference's logits (the
    published code's transpose-then-rotate-half); the reference itself
    computed in int8, with a router that ignores its bias, or with the
    rotary key left unrotated, does not, by far."""
    if who == "program":
        got = np.asarray(ml.forward(params, jnp.asarray(tokens), cfg))
        assert np.abs(got - ref_logits).max() < TOL
    elif who == "int8":
        ctrl = np.asarray(ref.logits(params, tokens, SHAPE, quant="int8"))
        assert np.abs(ctrl - ref_logits).max() > 1000 * TOL
    else:
        bad = np.asarray(ref.logits(params, tokens, SHAPE, **{who: True}))
        assert np.abs(bad - ref_logits).max() > 1000 * TOL


def test_absorbed_attention_equals_expanded_for_one_layer(cfg, params):
    """One layer's attention for every position of a 24-token sequence:
    W_kvb's key half folded into the query and its value half applied
    after, the query read against the latent rows themselves (the
    decode step's form, its XLA and its kernel), against the expanded
    keys and values of every head (the published form)."""
    p = params["blocks"][1]["attn"]
    T = 24
    h = jnp.asarray(np.random.default_rng(4).normal(size=(T, 64)),
                    jnp.float32)
    pos = jnp.arange(T)
    q_n, q_r, lat = ml._project(h, p, pos, cfg)
    k, v = ml._expand(lat, p, cfg)
    want = ml._attend(jnp.concatenate([q_n, q_r], -1), k, v, pos, pos, cfg)
    q = ml._absorbed_query(q_n, q_r, p, cfg)  # [T, heads, row]
    s = jnp.einsum("thw,kw->thk", q, lat) * cfg.attention_multiplier
    s = jnp.where((pos[None, :] <= pos[:, None])[:, None], s, -1e30)
    o_lat = jnp.einsum("thk,kr->thr", jax.nn.softmax(s, -1),
                       lat[:, :cfg.kv_rank])
    got = ml._absorbed_out(o_lat, p, cfg)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    # ... and through the kernel: T slots, slot t at position t, one
    # table over the same rows
    pool = lat.reshape(T // BT, BT, -1)
    tables = jnp.tile(jnp.arange(MAXB, dtype=jnp.int32) % (T // BT), (T, 1))
    o_k = pa.mla_decode_attention(q, pool, tables, pos, cfg.kv_rank,
                                  cfg.attention_multiplier)
    got_k = ml._absorbed_out(o_k, p, cfg)
    assert np.abs(np.asarray(got_k - want)).max() < 1e-5


def test_the_shares_of_eight_chips_add_up_to_the_whole_layer(ref, cfg,
                                                             params):
    """`experts_held`: eight shares of 1 of the 8 experts, each routing
    over all 8 and computing its own experts' part, the shared expert
    counted once, add up to what the uncut reference gives for the
    whole layer — attention being data-parallel, each chip computes the
    whole of it for its own rows, and it is not part of the sum."""
    p = params["blocks"][2]["ffn"]
    u32 = jnp.asarray(np.random.default_rng(5).normal(size=(10, 64)),
                      jnp.float32)
    want = np.asarray(ref._experts(
        u32, p, 0, 8, SHAPE["top_k"], SHAPE["route_scale"], True, True,
        None, False))
    total = np.zeros_like(want)
    for i in range(8):
        share = ml.MlaMoeConfig(max_len=64, dtype=jnp.float32,
                                **dict(SHAPE, experts_held=(i, i + 1),
                                       shared_expert_held=(i == 5)))
        mine = dict(p, experts=jax.tree_util.tree_map(
            lambda a: a[i:i + 1], p["experts"]))
        part, stats = parts.moe_ffn(u32, mine, share, jnp.ones(10, bool))
        assert int(stats[0]) <= 1
        total += np.asarray(part)
    assert np.abs(total - want).max() < 1e-5
    with pytest.raises(ValueError, match="experts_held"):
        ml.MlaMoeConfig(**dict(SHAPE, experts_held=(4, 12)))


# ---------------------------------------------------------------------
# the latent cache
# ---------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _jitted(fn, cfg, kernel):
    """The model's step compiled once a kernel, as the engine does."""
    return jax.jit(functools.partial(fn, cfg=cfg, kernel=kernel))


class _Slots(object):
    """The slots' host bookkeeping, as the engine keeps it."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.cache = ml.SERVING.init_cache(cfg, 40, BT, SLOTS)
        self.tab = np.full((SLOTS, MAXB), -1, np.int32)
        self.next_block = 0

    def _ensure(self, s, lo, hi):
        for b in range(lo // BT, (hi - 1) // BT + 1):
            if self.tab[s, b] < 0:
                self.tab[s, b] = self.next_block
                self.next_block += 1

    def chunk(self, params, s, toks, cursor, c, bucket, kernel):
        self._ensure(s, cursor, cursor + c)
        padded = np.full(bucket, 7, np.int32)  # padding is not token 0
        padded[:c] = toks[cursor:cursor + c]
        logits, self.cache = _jitted(ml.paged_prefill_chunk, self.cfg,
                                     kernel)(
            params, self.cache, jnp.asarray(padded), jnp.int32(cursor),
            jnp.asarray(self.tab[s]), true_len=jnp.int32(c))
        return np.asarray(logits)

    def decode(self, params, toks_at, kernel):
        """`toks_at`: {slot: (token, position)}; the others are parked."""
        pos = np.full(SLOTS, MAXB * BT, np.int32)
        tok = np.zeros(SLOTS, np.int32)
        for s, (t, p) in toks_at.items():
            self._ensure(s, p, p + 1)
            pos[s], tok[s] = p, t
        logits, self.cache, stats = _jitted(ml.paged_decode_step, self.cfg,
                                            kernel)(
            params, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(self.tab), self.cache)
        return np.asarray(logits), np.asarray(stats)


@pytest.mark.parametrize("kernel", ["gather", "fused"])
@pytest.mark.parametrize("plan", [
    ((32, 32),),                   # one chunk: its own expanded rows
    ((16, 16), (11, 16)),          # two chunks, the second padded
], ids=["one", "two"])
def test_chunked_prefill_then_decode_equals_the_full_forward(
        cfg, params, tokens, ref_logits, plan, kernel):
    """Prefill in chunks (the first attends its own expanded rows, a
    later one the slot's cached latents read through the table and
    expanded), then decode absorbed to position 39 (the XLA form, or
    the latent write and `mla_decode_attention` interpreted): the
    logits at every chunk's last row and at every decoded position are
    the reference's expanded full forward's."""
    st = _Slots(cfg)
    cursor = 0
    for c, bucket in plan:
        got = st.chunk(params, 1, tokens, cursor, c, bucket, kernel)
        cursor += c
        assert np.abs(got - ref_logits[cursor - 1]).max() < TOL
    for p in range(cursor, 40):
        got, stats = st.decode(params, {1: (tokens[p], p)}, kernel)
        assert np.abs(got[1] - ref_logits[p]).max() < TOL
        # one live row: top-2 distinct experts in each of 2 layers
        assert list(stats) == [4, 1]


@pytest.mark.parametrize("kernel", ["gather", "fused"])
def test_parked_slots_latent_blocks_are_untouched(cfg, params, tokens,
                                                  kernel):
    """A parked row writes no latent and reaches no expert: slot 2's
    blocks are bit for bit what its prefill left while slot 0 steps."""
    st = _Slots(cfg)
    for s in (0, 2):
        st.chunk(params, s, tokens, 0, 8, 8, "gather")
    mine = np.asarray([b for b in st.tab[2] if b >= 0])
    before = [np.asarray(pool[mine]).copy() for pool in st.cache["latent"]]
    for p in range(8, 14):  # slot 2 parked: only slot 0 steps
        _, stats = st.decode(params, {0: (tokens[p], p)}, kernel)
        assert list(stats) == [4, 1]
    for a, pool in zip(before, st.cache["latent"]):
        assert np.array_equal(a, np.asarray(pool[mine]))


# the blocks a slot's last group names, by the kind of rung it lands on
# (`pa._rungs`), at 32 rows a block (the cell's: a rung is 4 blocks,
# 128 rows) in groups of 32: one block, a rung (the first rung: one
# 128-row tile, folded whole), a rung and a block (a middle rung, 8
# blocks, folded in two halves), all but one block (the last rung, the
# group: halves of 16), all 32. (At 4 or 8 rows a block in one group of
# 12, no rung short of the group fills a 128-row tile, and no fold is
# cut in two.)
_LAST = {"one": lambda G, r: 1, "rung": lambda G, r: r,
         "rung_plus_one": lambda G, r: r + 1,
         "G_minus_one": lambda G, r: G - 1, "G": lambda G, r: G}


@pytest.mark.parametrize("heads,W,r,Bt,last", [
    (4, 128, 32, 4, None), (8, 256, 160, 8, None)]
    + [(4, 640, 512, 32, k) for k in _LAST])
def test_latent_decode_call_equals_plain_attention(heads, W, r, Bt, last):
    """`mla_decode_attention` interpreted, over ragged contexts (one
    token, a block edge, a short last group, a parked slot; with
    `last`, slots of one, two and four groups whose last group lands on
    that kind of rung, every fold of 8 blocks or more cut in two
    overlapped halves, `_fold_halves`) through tables that name blocks
    out of order and end in -1, against float64 softmax over the rows
    each table names: scores over the whole row, values its first r
    lanes. A parked slot gives zeros; no row past `pos` counts,
    whatever finite values the block holds there, and no block a table
    does not name is read: those hold NaN (0 x NaN would be NaN in
    P . V — a masked column must contribute an exact 0, and the ring
    starts as zeros, `_zero_ring`)."""
    rng = np.random.default_rng(heads)
    S, maxb, NB = 5, 12, 40
    pos = [0, Bt - 1, 5 * Bt + 2, maxb * Bt, 11 * Bt + 1]
    if last is not None:
        maxb, NB = 128, 240
        G = pa._bytes_group(Bt, maxb, Bt * W * 4, True)
        rung = pa._rungs(G, Bt, True)[0]
        assert (G, rung) == (32, 4)
        n = G + _LAST[last](G, rung)  # blocks named: a group and the last
        S, pos = 7, pos[:3] + [maxb * Bt, (n - 1) * Bt + Bt // 2,
                               (n - G - 1) * Bt + 1,
                               (n + 2 * G - 1) * Bt + Bt - 1]
    pool = rng.normal(size=(NB, Bt, W)).astype(np.float32)
    q = rng.normal(size=(S, heads, W)).astype(np.float32)
    pos = np.array(pos, np.int32)
    tables = np.full((S, maxb), -1, np.int32)
    perm, at = rng.permutation(NB - 1), 0  # no block is two slots'
    for s in range(S):
        n = min(pos[s] // Bt + 1, maxb) if pos[s] < maxb * Bt else 0
        tables[s, :n] = perm[at:at + n]
        at += n
        if n and pos[s] % Bt != Bt - 1:  # what lies past pos is garbage
            pool[tables[s, n - 1], pos[s] % Bt + 1:] = 1e4
    pool[np.setdiff1d(np.arange(NB), tables[tables >= 0])] = np.nan
    scale = 1 / math.sqrt(W)
    got = np.asarray(pa.mla_decode_attention(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(tables),
        jnp.asarray(pos), r, scale))
    assert got.shape == (S, heads, r)
    if last is not None:  # one, two and four groups
        assert sorted(-(-(pos[pos < maxb * Bt] // Bt + 1) // G)) == \
            [1, 1, 1, 1, 2, 4]
    for s in range(S):
        if pos[s] >= maxb * Bt:
            assert not got[s].any()
            continue
        rows = pool[tables[s]].reshape(-1, W)[:pos[s] + 1].astype(np.float64)
        sc = q[s].astype(np.float64) @ rows.T * scale
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        assert np.abs(got[s] - pr @ rows[:, :r]).max() < 1e-4


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


@pytest.mark.parametrize("depth", [None, False], ids=["ahead", "lockstep"])
def test_engine_serves_the_references_greedy_tokens(ref, cfg, params, depth):
    """Five requests over three slots (so slots are re-used), prompts
    chunked at 16 (one, two and three chunks), one request cancelled
    mid-decode, at either depth of the one decode loop: every greedy
    token is the reference's argmax at its position, decode is traced
    once, the one latent table is the engine's only one (no window, no
    state), the cache is counted by its one kind, and every block and
    reservation is back when the engine drains."""
    eng = _engine(params, cfg, async_dispatch=depth)
    assert eng.async_dispatch == (depth is None)
    assert eng._win is None and not eng._has_state
    assert eng.kv_block_bytes == ml.cache_bytes(cfg, BT)["full"]
    p = _prompts(1, 27, 5, 33, 18, 40)
    hs = [eng.submit(p[0], 10), eng.submit(p[1], 20), eng.submit(p[2], 30)]
    while len(hs[2].tokens) < 7:
        eng.step()
    n_cancel = len(hs[2].tokens)
    assert eng.cancel(hs[2].rid)
    hs += [eng.submit(p[3], 14), eng.submit(p[4], 8)]
    used = []
    while eng.step():
        if eng.metrics.cache_bytes_in_use is not None:
            used.append(dict(eng.metrics.cache_bytes_in_use))
    assert hs[2].finish_reason == "cancelled"
    assert len(hs[2].tokens) == n_cancel
    assert [len(h.tokens) for i, h in enumerate(hs) if i != 2] == \
        [10, 20, 14, 8]
    for prompt, h in zip(p, hs):
        served = np.asarray(h.tokens, np.int32)
        want = np.asarray(ref.logits(
            params, np.concatenate([prompt, served]), SHAPE))
        assert np.array_equal(
            want[len(prompt) - 1:len(prompt) - 1 + len(served)].argmax(-1),
            served)
    m = eng.metrics
    assert m.decode_trace_count() == 1 and m.state_slots_reset == 0
    assert used and all(set(u) == {"full"} for u in used)
    assert m.cache_bytes_per_slot.count > 0
    assert m.moe_experts_hit.count == m.moe_rows_max.count > 0
    assert 4 <= m.moe_experts_hit.mean <= 2 * 8
    if depth is None:
        assert m.decode_dispatched_ahead > 0
    assert eng._alloc.blocks_in_use == 0 and eng._alloc.reserved == 0


@pytest.mark.parametrize("option,value", [
    ("prefix_cache_tokens", 64), ("kv_store", object()),
    ("spec_draft_len", 4), ("kv_quant", "int8"), ("weight_quant", "int8"),
    ("adapter_registry", object()), ("kv_fingerprints", True),
    ("handoff", [{"key": 1}])])
def test_each_unsupported_option_is_refused_with_the_familys_reason(
        cfg, params, option, value):
    """A latent block is not a K/V block: what aliases, stores, hands on
    or verifies cached blocks is refused at construction (hand-off
    import at `submit`), each by its name, with THIS family's reason."""
    assert set(ml.SERVING.refused) == {
        "prefix_cache_tokens", "kv_store", "spec_draft_len", "kv_quant",
        "weight_quant", "adapter_registry", "kv_fingerprints"}
    with pytest.raises(ValueError, match=option) as err:
        if option == "handoff":
            _engine(params, cfg).submit(np.arange(5, dtype=np.int32), 4,
                                        handoff=value)
        else:
            _engine(params, cfg, **{option: value})
    assert "one latent a token and layer" in str(err.value)
    assert "'mla_moe'" in str(err.value)
