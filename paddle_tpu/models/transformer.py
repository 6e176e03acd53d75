"""Decoder-only transformer LM built on the parallel stack — the
long-context flagship (beyond-2018 capability; SURVEY §2.2 marks SP/ring
attention absent in the reference, first-class here).

With `moe_experts > 0` every `moe_every`-th block's MLP becomes a
Switch-Transformer top-1 MoE FFN (parallel/moe.py) whose experts shard
over the 'expert' mesh axis — the Switch-LM flagship of the
expert-parallel path.

Pure-JAX param-pytree model designed for a ('data', 'seq', 'model') mesh:
  * token embedding row-sharded over 'model' (parallel.sharded_lookup)
  * attention via parallel.sequence_parallel_attention (ring or Ulysses)
    over the 'seq' axis — O(T/n) activation memory per chip
  * MLP/attention weights column/row-sharded over 'model' by PartitionSpec
  * losses/gradients exact vs the single-device oracle (tested)

Use `init_params` + `loss_fn`/`train_step` under jax.jit with the
shardings from `param_specs`.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.attention import sequence_parallel_attention
from .parts import NEG_INF, Seam, paged_kernel_check, phys_rows
from .scopes import scope

__all__ = ["TransformerConfig", "init_params", "param_specs", "forward",
           "loss_fn", "make_train_step"]


class TransformerConfig:
    def __init__(self, vocab=256, dim=128, heads=4, layers=2, mlp_mult=4,
                 max_len=1024, dtype=jnp.float32, moe_experts=0,
                 moe_every=2, moe_capacity_factor=1.25):
        self.vocab = vocab
        self.dim = dim
        self.heads = heads
        self.layers = layers
        self.mlp_mult = mlp_mult
        self.max_len = max_len
        self.dtype = dtype
        # Switch-Transformer MoE: with moe_experts > 0, every
        # `moe_every`-th block's MLP becomes a top-1 MoE FFN
        # (parallel/moe.py) — experts shard over the 'expert' mesh axis
        self.moe_experts = moe_experts
        self.moe_every = moe_every
        self.moe_capacity_factor = moe_capacity_factor
        self.serving = SERVING  # what ServingEngine asks this family for

    def is_moe_block(self, i: int) -> bool:
        return self.moe_experts > 0 and (i % self.moe_every
                                         == self.moe_every - 1)


def init_params(cfg: TransformerConfig, key) -> Dict[str, Any]:
    ks = jax.random.split(key, cfg.layers + 2)
    d, h = cfg.dim, cfg.heads
    scale = 1.0 / math.sqrt(d)

    def dense(k, shape):
        return scale * jax.random.normal(k, shape, cfg.dtype)

    params = {
        "embed": dense(ks[0], (cfg.vocab, d)),
        "pos": dense(ks[1], (cfg.max_len, d)),
        "blocks": [],
        "ln_f": {"g": jnp.ones((d,), cfg.dtype), "b": jnp.zeros((d,), cfg.dtype)},
    }
    for i in range(cfg.layers):
        kq, kk, kv, ko, k1, k2 = jax.random.split(ks[2 + i], 6)
        # gate key derived separately so dense-model init stays
        # bit-identical to pre-MoE checkpoints for the same seed
        kg = jax.random.fold_in(ks[2 + i], 7)
        blk = {
            "ln1": {"g": jnp.ones((d,), cfg.dtype), "b": jnp.zeros((d,), cfg.dtype)},
            "wq": dense(kq, (d, d)),
            "wk": dense(kk, (d, d)),
            "wv": dense(kv, (d, d)),
            "wo": dense(ko, (d, d)),
            "ln2": {"g": jnp.ones((d,), cfg.dtype), "b": jnp.zeros((d,), cfg.dtype)},
        }
        if cfg.is_moe_block(i):
            E, m = cfg.moe_experts, cfg.mlp_mult * d
            blk["moe"] = {
                "gate_w": dense(kg, (d, E)),
                "w1": dense(k1, (E, d, m)),
                "b1": jnp.zeros((E, m), cfg.dtype),
                "w2": dense(k2, (E, m, d)),
                "b2": jnp.zeros((E, d), cfg.dtype),
            }
        else:
            blk["w1"] = dense(k1, (d, cfg.mlp_mult * d))
            blk["w2"] = dense(k2, (cfg.mlp_mult * d, d))
        params["blocks"].append(blk)
    return params


def param_specs(cfg: TransformerConfig, mesh=None) -> Dict[str, Any]:
    """PartitionSpecs for tensor parallelism over 'model' + row-sharded
    vocab + expert-sharded MoE FFNs. Megatron-style: qkv/w1
    column-parallel, wo/w2 row-parallel. Pass `mesh` to drop axes the
    mesh does not have (e.g. MoE params replicate on a mesh without an
    'expert' axis, matching forward()'s reference_moe fallback)."""
    rep = P()

    def fit(spec):
        if mesh is None:
            return spec
        return P(*(a if a in mesh.axis_names else None for a in spec))

    def block(i):
        b = {
            "ln1": {"g": rep, "b": rep},
            "wq": fit(P(None, "model")),
            "wk": fit(P(None, "model")),
            "wv": fit(P(None, "model")),
            "wo": fit(P("model", None)),
            "ln2": {"g": rep, "b": rep},
        }
        if cfg.is_moe_block(i):
            # experts shard over their leading E dim on 'expert'
            b["moe"] = {
                "gate_w": rep,
                "w1": fit(P("expert", None, None)),
                "b1": fit(P("expert", None)),
                "w2": fit(P("expert", None, None)),
                "b2": fit(P("expert", None)),
            }
        else:
            b["w1"] = fit(P(None, "model"))
            b["w2"] = fit(P("model", None))
        return b

    return {
        "embed": fit(P("model", None)),
        "pos": rep,
        "blocks": [block(i) for i in range(cfg.layers)],
        "ln_f": {"g": rep, "b": rep},
    }


def _ln(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * p["g"] + p["b"]


def forward(params, tokens, cfg: TransformerConfig,
            mesh: Optional[Mesh] = None, attn_impl: str = "ring",
            kv_sink: Optional[list] = None, last_only: bool = False,
            last_index=None):
    """tokens [B, T] int -> logits [B, T, vocab] (or [B, vocab] of just
    the final position with last_only — prefill skips the O(T x vocab)
    head it would discard). `last_index` is the dynamic counterpart: a
    traced scalar position whose single row feeds the head (the bucketed
    serving prefill pads T to a power-of-two bucket, so the true last
    prompt position is an argument, not the static T-1). With `kv_sink`
    (a list), each block appends its (k, v) [B, T, H, Dh] — the prefill
    hook for cached decoding, so serving reuses THIS block math."""
    B, T = tokens.shape
    if mesh is not None and "model" in mesh.axis_names:
        from ..parallel.embedding import sharded_lookup

        x = sharded_lookup(params["embed"], tokens, mesh, "model")
    else:
        x = params["embed"][tokens]
    x = x + params["pos"][:T][None]

    for blk in params["blocks"]:
        h = _ln(x, blk["ln1"])
        q = (h @ blk["wq"]).reshape(B, T, cfg.heads, cfg.dim // cfg.heads)
        k = (h @ blk["wk"]).reshape(B, T, cfg.heads, cfg.dim // cfg.heads)
        v = (h @ blk["wv"]).reshape(B, T, cfg.heads, cfg.dim // cfg.heads)
        if kv_sink is not None:
            kv_sink.append((k, v))
        o = sequence_parallel_attention(
            q, k, v, mesh=mesh, axis="seq", impl=attn_impl, causal=True
        )
        x = x + o.reshape(B, T, cfg.dim) @ blk["wo"]
        h = _ln(x, blk["ln2"])
        if "moe" in blk:
            from ..parallel.moe import expert_parallel_moe, reference_moe

            mp = blk["moe"]
            flat = h.reshape(B * T, cfg.dim)
            if mesh is not None and "expert" in mesh.axis_names and \
                    mesh.shape["expert"] > 1:
                y = expert_parallel_moe(
                    flat, mp["gate_w"], mp["w1"], mp["b1"], mp["w2"],
                    mp["b2"], mesh=mesh,
                    capacity_factor=cfg.moe_capacity_factor,
                )
            else:
                y = reference_moe(flat, mp["gate_w"], mp["w1"], mp["b1"],
                                  mp["w2"], mp["b2"])
            x = x + y.reshape(B, T, cfg.dim)
        else:
            x = x + jax.nn.gelu(h @ blk["w1"]) @ blk["w2"]

    if last_index is not None:
        x = jax.lax.dynamic_index_in_dim(x, last_index, axis=1,
                                         keepdims=False)
    elif last_only:
        x = x[:, -1]
    x = _ln(x, params["ln_f"])
    return x @ params["embed"].T  # weight-tied output head


def loss_fn(params, tokens, cfg: TransformerConfig,
            mesh: Optional[Mesh] = None, attn_impl: str = "ring"):
    """Next-token cross entropy over tokens [B, T+1] (input/target split)."""
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    logits = forward(params, inp, cfg, mesh=mesh, attn_impl=attn_impl)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return nll.mean()


def make_train_step(cfg: TransformerConfig, lr=1e-2,
                    mesh: Optional[Mesh] = None, attn_impl: str = "ring"):
    """SGD train step; jit it with in_shardings from param_specs when a
    mesh is used."""

    def step(params, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, tokens, cfg, mesh=mesh, attn_impl=attn_impl
        )
        params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
        return params, loss

    return step


# ---------------------------------------------------------------------
# incremental decoding (serving): per-layer KV cache + one-token steps.
# The reference era served RNN generation through beam search
# (RecurrentGradientMachine.h:307); the transformer-equivalent serving
# primitive is cached autoregressive decode — prefill computes the
# prompt's K/V once, then each new token attends over the cache instead
# of re-running the whole prefix (O(T) per token, not O(T^2)).
# ---------------------------------------------------------------------


def init_kv_cache(cfg: TransformerConfig, batch: int, max_len=None,
                  dtype=None):
    """Per-layer K/V buffers [B, L, H, Dh], zero-initialised."""
    L = int(max_len or cfg.max_len)
    dh = cfg.dim // cfg.heads
    shape = (batch, L, cfg.heads, dh)
    dt = dtype or cfg.dtype
    return [
        {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
        for _ in range(cfg.layers)
    ]


def _cached_attention(q, cache_k, cache_v, pos):
    """q [B,H,Dh] against the cache [B,L,H,Dh]; positions > pos masked.
    `pos` is a scalar (one shared decode position — generate's path) or
    a [B] vector of PER-ROW positions (the slotted serving cache, where
    every row is an independent request at its own depth). Masked
    positions contribute exactly 0 (exp(-inf) == 0, 0 * finite == 0),
    so stale/dead-slot cache rows cannot perturb live rows."""
    B, L, H, dh = cache_k.shape
    scores = jnp.einsum("bhd,blhd->bhl", q, cache_k) / math.sqrt(dh)
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        mask = (jnp.arange(L) <= pos)[None, None, :]
    else:
        mask = (jnp.arange(L)[None, :] <= pos[:, None])[:, None, :]
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhl,blhd->bhd", probs, cache_v)


def _write_kv(buf, new, pos):
    """Write one new K or V row [B, H, Dh] into the cache [B, L, H, Dh]
    at position `pos`: a contiguous dynamic_update_slice for the scalar
    case (generate — every row at the same depth), a per-row scatter for
    vector pos [B] (slotted serving — each slot at its own depth).
    Out-of-range vector positions are DROPPED by scatter semantics, so a
    retired slot parked at the clamp boundary never corrupts neighbors."""
    if jnp.ndim(pos) == 0:
        return jax.lax.dynamic_update_slice_in_dim(
            buf, new[:, None].astype(buf.dtype), pos, axis=1
        )
    B = buf.shape[0]
    return buf.at[jnp.arange(B), pos].set(new.astype(buf.dtype))


def decode_step(params, token, pos, cache, cfg: TransformerConfig):
    """One decode step: token [B] int at position `pos` -> (logits
    [B, vocab], updated cache). `pos` is a scalar (generate: all rows at
    the same depth) or a [B] vector of per-row positions (the slotted
    serving cache — many independent requests in one batched step); the
    per-row math is identical either way, so the serving engine's
    decode is bit-identical to generate's row by row."""
    B = token.shape[0]
    dh = cfg.dim // cfg.heads
    x = params["embed"][token] + params["pos"][pos]
    new_cache = []
    for blk, kv in zip(params["blocks"], cache):
        h = _ln(x, blk["ln1"])
        q = (h @ blk["wq"]).reshape(B, cfg.heads, dh)
        k = (h @ blk["wk"]).reshape(B, cfg.heads, dh)
        v = (h @ blk["wv"]).reshape(B, cfg.heads, dh)
        ck = _write_kv(kv["k"], k, pos)
        cv = _write_kv(kv["v"], v, pos)
        new_cache.append({"k": ck, "v": cv})
        o = _cached_attention(q, ck, cv, pos).reshape(B, cfg.dim)
        x = x + o @ blk["wo"]
        h = _ln(x, blk["ln2"])
        if "moe" in blk:
            from ..parallel.moe import reference_moe

            mp = blk["moe"]
            x = x + reference_moe(
                h, mp["gate_w"], mp["w1"], mp["b1"], mp["w2"], mp["b2"]
            )
        else:
            x = x + jax.nn.gelu(h @ blk["w1"]) @ blk["w2"]
    x = _ln(x, params["ln_f"])
    return x @ params["embed"].T, new_cache


def prefill(params, tokens, cfg: TransformerConfig, max_len=None):
    """Run the prompt [B, T0] once through forward() (kv_sink hook),
    filling the cache; returns (logits of the LAST prompt position
    [B, vocab], cache). Reuses forward's block math exactly — no
    duplicated transformer loop to drift."""
    B, T0 = tokens.shape
    cache = init_kv_cache(cfg, B, max_len=max_len)
    sink: list = []
    logits = forward(
        params, tokens, cfg, mesh=None, attn_impl="reference",
        kv_sink=sink, last_only=True,
    )
    for i, (k, v) in enumerate(sink):
        cache[i] = {
            "k": jax.lax.dynamic_update_slice_in_dim(
                cache[i]["k"], k.astype(cache[i]["k"].dtype), 0, axis=1
            ),
            "v": jax.lax.dynamic_update_slice_in_dim(
                cache[i]["v"], v.astype(cache[i]["v"].dtype), 0, axis=1
            ),
        }
    return logits, cache


# ---------------------------------------------------------------------
# paged KV cache (serving, ISSUE 7): the cache is a pool of fixed-size
# token BLOCKS ([NB, Bt, H, Dh] per layer) and each slot owns a block
# TABLE (row of physical block ids) instead of a contiguous cache row —
# PagedAttention (Kwon et al., SOSP '23) in static-shape JAX idiom. HBM
# residency scales with blocks actually written, not MAX_SLOTS*max_len;
# prefix reuse becomes table aliasing (two slots naming the same
# physical block) instead of device copies.
#
# Each primitive takes kernel="gather"|"fused" (ISSUE 13):
#   gather — attend over a contiguous per-slot view `_paged_view`
#            materialises as TRANSIENT activation scratch
#            [S, MAXB*Bt, H, Dh] per layer (freed after the step, but
#            an HBM write+read of the whole gathered context per step);
#   fused  — the Pallas kernels in parallel/paged_attention.py walk
#            the block table INSIDE the kernel (scalar-prefetch index
#            maps), streaming K/V blocks from the pool with online
#            softmax — no view ever exists. Fused-vs-gather logits
#            agree to float tolerance (online softmax reorders the
#            reduction), token-identically in greedy decode — the same
#            low-bit class as the padded-prefill drift (PR 2).
#
# Each primitive also takes kv_quant="none"|"int8"|"fp8" (ISSUE 14):
# the pool stores quantized codes with per-(physical block, head)
# absmax scale side-bands (k_scale/v_scale [NB, H] per layer), writes
# quantize at the scatter (_quant_scatter's commit-at-open rule), and
# reads dequantize in-kernel (fused) or on the gather view. "none" is
# byte-identical to the pre-quant code path.
# ---------------------------------------------------------------------


# ---------------------------------------------------------------------
# per-block KV quantization (ISSUE 14): the pool stores int8/fp8 with a
# per-(physical block, head) absmax scale side-band [NB, H] per layer
# and band. Scales are keyed by PHYSICAL block id, so prefix aliasing
# (two tables naming one block) shares the scale for free and
# copy-on-write copies payload+scale in the same compiled op. qmax is
# the storage format's largest representable magnitude: 127 for int8,
# 448 for float8_e4m3fn (no inf — casts past it would garbage, so
# writes clip to it explicitly).
# ---------------------------------------------------------------------

_KV_QMAX = {"int8": 127.0, "fp8": 448.0}


def _kv_quant_check(kv_quant: str):
    if kv_quant not in ("none", "int8", "fp8"):
        raise ValueError(
            "kv_quant must be 'none', 'int8', or 'fp8' (got %r)"
            % (kv_quant,))


def kv_block_bytes(layers_n: int, heads: int, dh: int,
                   block_tokens: int, kv_quant: str = "none",
                   act_itemsize: int = 4) -> int:
    """One physical KV block's HBM cost at a storage dtype: K+V
    payload rows over all layers, plus the per-(block, head) f32
    scale side-bands when quantized. THE one formula — the engine's
    allocator accounting (ServingEngine.kv_block_bytes), bench.py's
    fixed-byte-budget pool sizing, and bench_offline's roofline all
    call it, so the three can never drift. Per payload byte the
    int8/fp8 scale overhead is 4 / (block_tokens x dh) — ~0.4% at
    the Bt=16, dh=64 defaults."""
    _kv_quant_check(kv_quant)
    item = 1 if kv_quant != "none" else int(act_itemsize)
    b = 2 * layers_n * block_tokens * heads * dh * item
    if kv_quant != "none":
        b += 2 * layers_n * heads * 4
    return b


def kv_storage_dtype(kv_quant: str):
    """Pool storage dtype for a kv_quant setting; None = the model
    dtype (unquantized)."""
    _kv_quant_check(kv_quant)
    return {"none": None, "int8": jnp.int8,
            "fp8": jnp.float8_e4m3fn}[kv_quant]


def init_paged_kv_cache(cfg: TransformerConfig, num_blocks: int,
                        block_tokens: int, dtype=None,
                        kv_quant: str = "none"):
    """Per-layer pooled K/V block buffers [NB, Bt, H, Dh]. With
    `kv_quant` ('int8' | 'fp8') the payload stores the quantized code
    and each layer gains per-(block, head) f32 absmax-scale side-bands
    'k_scale'/'v_scale' [NB, H] (committed at block fill — see
    `_quant_scatter`). kv_quant='none' returns the exact pre-quant
    structure, so default engines stay trace-identical."""
    dh = cfg.dim // cfg.heads
    NB, Bt = int(num_blocks), int(block_tokens)
    shape = (NB, Bt, cfg.heads, dh)
    st = kv_storage_dtype(kv_quant)
    if st is None:
        dt = dtype or cfg.dtype
        return [
            {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
            for _ in range(cfg.layers)
        ]
    return [
        {"k": jnp.zeros(shape, st), "v": jnp.zeros(shape, st),
         "k_scale": jnp.zeros((NB, cfg.heads), jnp.float32),
         "v_scale": jnp.zeros((NB, cfg.heads), jnp.float32)}
        for _ in range(cfg.layers)
    ]


def _quant_scatter(buf, scale, pk, off, vals, qmax,
                   commit_from_call=False):
    """Quantize rows `vals` [..., H, Dh] and scatter them into the
    int8/fp8 pool `buf` [NB, Bt, H, Dh] at (pk, off) [...]; returns
    (new buf, new scale [NB, H]).

    Scale discipline (the absmax commit-at-open rule): a block is
    OPENED when some row of THIS call writes its in-block offset 0 —
    opened blocks (re)commit their per-head scale (erasing the stale
    scale a recycled pool block carries from its previous tenant).
    The commit source is the opening ROW's absmax by default; with
    `commit_from_call` it is the absmax over every row this call
    writes into the block. Chunk prefill uses call-commit (the whole
    fill is deterministic — prompt blocks are never re-opened);
    decode and verify MUST use row-commit: a verify window's extra
    rows are speculative drafts, and folding a rejected draft into
    the scale would make the committed scale — and every later
    clipped write — depend on drafts that never became tokens,
    breaking the spec-invariance guarantee (rejected positions are
    re-written by later windows, and the off-0 re-write re-commits,
    so the QUIESCENT cache is bit-identical to the plain decode
    path's). Rows landing in a block this call did NOT open re-use
    the committed scale and CLIP to it (decode appends mid-block,
    continuation chunks, draft re-writes) — the LLM.int8-style absmax
    trade: later outliers saturate rather than re-scaling rows
    already stored. Parked rows (pk == NB, the engine's
    dead-slot/padded sentinel) drop payload, scale commit, AND open
    marker alike — out-of-range scatters drop, so parking stays exact
    on the quant path and a sentinel-parked write can never dirty a
    block or its scale.

    Known limit (the absmax trade's extreme): a block OPENED by an
    all-zero row commits scale 0, and every row later appended to it
    dequantizes to exactly 0 for the block's lifetime — total loss,
    not clipping. No invariance-safe rescue exists inside per-block
    scales (a re-commit on the first nonzero append would let verify
    windows leak rejected-draft magnitudes back into the scale, and
    an epsilon floor still clips appends to ~0). It is accepted
    because an exactly-zero per-head projection requires h @ wk == 0
    in every lane through a LayerNormed activation — unreachable for
    real checkpoints short of hand-zeroed weight/embedding rows —
    and the serving_quant agreement gate is the arbiter if a model
    ever gets near it."""
    NB = buf.shape[0]
    H, dh = vals.shape[-2], vals.shape[-1]
    n = math.prod(vals.shape[:-2])
    fpk = jnp.reshape(pk, (n,))
    foff = jnp.reshape(off, (n,))
    fv = jnp.reshape(vals, (n, H, dh)).astype(jnp.float32)
    amax = jnp.abs(fv).max(axis=-1)  # [n, H]
    # commit-source rows scatter-max into the candidate scales
    # (duplicate pk rows combine by max; parked rows at NB drop, and
    # in row-commit mode non-opening rows park themselves)
    src_pk = fpk if commit_from_call else jnp.where(
        foff == 0, fpk, jnp.int32(NB))
    cand = jnp.zeros((NB, H), jnp.float32).at[src_pk].max(amax / qmax)
    opened = jnp.zeros((NB, 1), jnp.float32).at[fpk].max(
        (foff == 0).astype(jnp.float32)[:, None]) > 0
    new_scale = jnp.where(opened, cand, scale)
    # quantize each row with the post-commit scale of ITS block; a
    # zero scale (an all-zero fill, or a never-opened block nothing
    # will read) divides by 1 instead — codes stay finite and exact 0
    # round-trips to exact 0
    s_rows = new_scale[jnp.clip(fpk, 0, NB - 1)][..., None]  # [n, H, 1]
    safe = jnp.where(s_rows > 0, s_rows, 1.0)
    scaled = fv / safe
    if buf.dtype == jnp.int8:
        q = jnp.clip(jnp.round(scaled), -qmax, qmax).astype(jnp.int8)
    else:  # fp8: clip to the format's finite max BEFORE the cast
        q = jnp.clip(scaled, -qmax, qmax).astype(buf.dtype)
    return buf.at[fpk, foff].set(q), new_scale


def _paged_deq_view(buf, scale, tables):
    """Dequantized gather view: `_paged_view` of the quantized pool,
    upcast to f32 and multiplied by each block's per-head scale
    (broadcast over the block's Bt rows) — the gather fallback's read
    path, running the SAME numerics the fused kernel applies in VMEM
    so CPU CI interprets identical math. Unallocated (-1) entries
    clamp like `_paged_view`; their garbage codes times their garbage
    (finite) scales are position-masked to exactly 0 by every
    caller."""
    NB, Bt, H, dh = buf.shape
    v = _paged_view(buf, tables).astype(jnp.float32)
    s = scale[jnp.clip(tables, 0, NB - 1)]  # [..., MAXB, H]
    s = jnp.repeat(s, Bt, axis=-2)          # [..., MAXB*Bt, H]
    return v * s[..., None]


def _paged_view(buf, tables):
    """Gather a contiguous per-slot view [S, MAXB*Bt, H, Dh] out of the
    block pool [NB, Bt, H, Dh] through block tables [S, MAXB].
    Unallocated table entries (-1) clamp to block 0 — the rows they
    surface are garbage, but every caller masks attention by position,
    and position masks always exclude unwritten depths, so garbage
    rows contribute exactly 0 (finite * zero-prob)."""
    NB, Bt, H, dh = buf.shape
    v = buf[jnp.clip(tables, 0, NB - 1)]
    lead = tables.shape[:-1] + (tables.shape[-1] * Bt, H, dh)
    return v.reshape(lead)


def _adapter_delta(h, a, b, scale):
    """LoRA-style low-rank delta for one projection: h @ A @ B * scale
    with PER-SLOT adapter gathers (ISSUE 12 — Punica/S-LoRA batching:
    N tenants' deltas over one base model in one compiled step). `a`
    is [d, r] (one slot's adapter — the prefill-chunk case) or
    [S, d, r] (per-slot gathered — decode [S, d] and verify [S, K, d]
    activations); `b`/`scale` match. The ZERO adapter (A = B = 0,
    scale = 0) contributes exact float zeros, so a request with no
    adapter decodes token-identically to the base model — anything @ 0
    is 0, 0 * 0 is 0, and x + 0 never moves an argmax (the engine's
    zero-adapter identity test pins it)."""
    if a.ndim == 2:  # one slot (the prefill chunk's scalar index)
        return (h @ a) @ b * scale
    if h.ndim == 2:  # decode: [S, d] x [S, d, r]
        t = jnp.einsum("sd,sdr->sr", h, a)
        return jnp.einsum("sr,srd->sd", t, b) * scale[:, None]
    # verify: [S, K, d] x [S, d, r]
    t = jnp.einsum("skd,sdr->skr", h, a)
    return jnp.einsum("skr,srd->skd", t, b) * scale[:, None, None]


def _adapter_qv(h, blk, li, adapters, idx):
    """q/v projections with the per-slot adapter delta folded in —
    shared by the three paged steps so the adapter math cannot drift
    between decode, verify, and prefill chunks. `idx` is the per-slot
    adapter-index side-band ([] for the chunk's single slot, [S]
    otherwise); `adapters` holds the stacked device pool
    ([P, layers, ...] — serving/adapters.py). Returns (q, v) UNshaped
    (the callers reshape to heads)."""
    q = h @ blk["wq"]
    v = h @ blk["wv"]
    if adapters is not None:
        sc = adapters["scale"][idx]
        # cast the (f32 pool) delta back to the activation dtype
        # BEFORE adding: on bf16 configs an uncast add would promote
        # q/v to f32 and change downstream attention precision even
        # for the zero adapter — the token-identity invariant must
        # hold at the base model's own precision
        dq = _adapter_delta(h, adapters["a_q"][idx, li],
                            adapters["b_q"][idx, li], sc)
        dv = _adapter_delta(h, adapters["a_v"][idx, li],
                            adapters["b_v"][idx, li], sc)
        q = q + dq.astype(q.dtype)
        v = v + dv.astype(v.dtype)
    return q, v


def _paged_lm(params, cache, tokens, positions, tables, wpos, attend, cfg,
              adapters, adapter_idx, kv_quant, true_len=None):
    """The layer loop of the three paged steps -> (logits, updated
    cache): the embedding at `positions`; per layer the K/V write at
    `wpos` through `tables` and the step's own `attend(q, k_pool,
    v_pool, k_scale, v_scale, dtype)`; the head. Rows are [S] (decode)
    or [S, K] (verify); with `true_len` (the chunk) one slot's [1, C],
    whose head reads row true_len - 1."""
    one = true_len is not None
    quant = kv_quant != "none"
    qmax = _KV_QMAX.get(kv_quant)
    NB, Bt = cache[0]["k"].shape[0], cache[0]["k"].shape[1]
    with scope("lm_embed"):
        if one:
            x = params["embed"][tokens][None] + params["pos"][positions][None]
        else:
            x = params["embed"][tokens] + params["pos"][positions]
    lead = x.shape[:-1]
    heads = lead + (cfg.heads, cfg.dim // cfg.heads)
    new_cache = []
    for li, (blk, kv) in enumerate(zip(params["blocks"], cache)):
        with scope("lm_attention"):
            h = _ln(x, blk["ln1"])
            q, v = _adapter_qv(h, blk, li, adapters, adapter_idx)
            q = q.reshape(heads)
            k = (h @ blk["wk"]).reshape(heads)
            v = v.reshape(heads)
            pk, off = phys_rows(tables, wpos, NB, Bt)
            if quant:  # a chunk commits from all its rows (never drafts)
                ck, ksc = _quant_scatter(kv["k"], kv["k_scale"], pk, off,
                                         k[0] if one else k, qmax,
                                         commit_from_call=one)
                cv, vsc = _quant_scatter(kv["v"], kv["v_scale"], pk, off,
                                         v[0] if one else v, qmax,
                                         commit_from_call=one)
                new_cache.append({"k": ck, "v": cv,
                                  "k_scale": ksc, "v_scale": vsc})
            else:
                ksc = vsc = None
                ck = kv["k"].at[pk, off].set(
                    (k[0] if one else k).astype(kv["k"].dtype))
                cv = kv["v"].at[pk, off].set(
                    (v[0] if one else v).astype(kv["v"].dtype))
                new_cache.append({"k": ck, "v": cv})
            o = attend(q, ck, cv, ksc, vsc, x.dtype)
            x = x + o.reshape(lead + (cfg.dim,)) @ blk["wo"]
        with scope("lm_mlp"):
            h = _ln(x, blk["ln2"])
            x = x + jax.nn.gelu(h @ blk["w1"]) @ blk["w2"]
    with scope("lm_head"):
        if one:
            xl = jax.lax.dynamic_index_in_dim(x, true_len - 1, axis=1,
                                              keepdims=False)  # [1, dim]
            return (_ln(xl, params["ln_f"]) @ params["embed"].T)[0], \
                new_cache
        return _ln(x, params["ln_f"]) @ params["embed"].T, new_cache


def _kv_view(buf, scale, tables):
    """The gather form's view of one pool through `tables` [S, MAXB],
    or one slot's row [MAXB] as [1, MAXB]: `_paged_view`, dequantized
    (`_paged_deq_view`) where the pool carries scales."""
    if tables.ndim == 1:
        tables = tables[None]
    if scale is None:
        return _paged_view(buf, tables)
    return _paged_deq_view(buf, scale, tables)


def _masked_attention(q, ck, cv, ksc, vsc, tables, positions, dtype):
    """The chunk's and verify's attention on the gathered views:
    reference_attention's numerics (scale folded into q BEFORE the
    matmul, the -1e30 mask, softmax in the score dtype). q [B, T, H,
    dh]; `positions` [T] (one slot's chunk, `tables` its row) or
    [B, T]."""
    kview = _kv_view(ck, ksc, tables)
    vview = _kv_view(cv, vsc, tables)
    s = jnp.einsum("bthd,bshd->bhts", q * (1.0 / math.sqrt(q.shape[-1])),
                   kview)
    lv = jnp.arange(kview.shape[1])
    if positions.ndim == 1:
        mask = (lv[None, :] <= positions[:, None])[None, None]
    else:
        mask = (lv[None, None, :] <= positions[:, :, None])[:, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, vview).astype(dtype)


def paged_decode_step(params, token, pos, tables, cache,
                      cfg: TransformerConfig, adapters=None,
                      adapter_idx=None, kernel="gather",
                      kv_quant="none"):
    """One decode step over the paged pool: token [S] at per-row
    positions `pos` [S], block tables [S, MAXB] -> (logits [S, vocab],
    updated cache), with decode_step's numerics (_cached_attention) on
    the gathered view, or through the block table inside the Pallas
    kernel (kernel="fused", parallel/paged_attention.py), so a paged
    row decodes to generate()'s tokens. A parked row (pos >= MAXB*Bt)
    writes nothing; its logits are garbage nothing reads. With
    `adapters`/`adapter_idx` [S], each slot's q/v projections gain its
    tenant's LoRA delta gathered from the stacked adapter pool (index
    0 is the zero adapter, an exact no-op); the adapter gather is
    INSIDE this one compiled step, so N tenants retrace nothing.
    With `kv_quant` ('int8' | 'fp8'), writes quantize at the scatter
    (`_quant_scatter`: a block-opening row commits the block's scale,
    appends re-use it) and reads dequantize inside the fused kernel
    (scales ride as scalar-prefetch operands) or on the gather view —
    'none' is byte-identical to the pre-quant step."""
    paged_kernel_check(kernel)
    _kv_quant_check(kv_quant)

    def attend(q, ck, cv, ksc, vsc, dtype):
        if kernel == "fused":
            from ..parallel.paged_attention import paged_decode_attention

            return paged_decode_attention(q, ck, cv, tables, pos,
                                          k_scale=ksc, v_scale=vsc)
        o = _cached_attention(q, _kv_view(ck, ksc, tables),
                              _kv_view(cv, vsc, tables), pos)
        # a dequantized view is f32: cast the output back so that
        # quantization never promotes a bf16 model's residual stream
        # (the fused kernel's out dtype is q's already)
        return o if ksc is None else o.astype(dtype)

    return _paged_lm(params, cache, token, pos, tables, pos, attend, cfg,
                     adapters, adapter_idx, kv_quant)


def paged_prefill_chunk(params, cache, chunk, start_pos, table_row,
                        cfg: TransformerConfig, true_len=None,
                        adapters=None, adapter_idx=None,
                        kernel="gather", kv_quant="none"):
    """Extend the slot whose block table is `table_row` [MAXB] by a
    [C]-token chunk starting at `start_pos` (the positions before it
    cached) -> (logits [vocab] of row true_len - 1, meaningful on a
    prompt's last chunk; updated cache). A row attends the cache and
    the chunk's causal prefix with forward()'s numerics
    (reference_attention's scale-into-q einsum and -1e30 mask, not
    _cached_attention's: they differ in low bits), gathered or through
    the in-kernel table walk (kernel="fused"), so consecutive chunks
    are the monolithic prefill. Padded rows (past `true_len`) park
    their writes past the table span. `adapters`/`adapter_idx`
    (a SCALAR here — one slot prefills per chunk call) fold the slot's
    tenant LoRA delta into q/v exactly like paged_decode_step, so the
    cached K/V a chunk writes are the adapted model's. `kv_quant`
    quantizes at the scatter — a chunk COMMITS the scale of every
    block it opens (absmax over the chunk's rows in that block) and
    clips into blocks earlier chunks committed — and dequantizes on
    the read, fused or gathered, like paged_decode_step."""
    paged_kernel_check(kernel)
    _kv_quant_check(kv_quant)
    (C,) = chunk.shape
    if true_len is None:
        true_len = C
    offs = jnp.arange(C)
    positions = start_pos + offs  # [C] global rows of the chunk
    wpos = jnp.where(offs < true_len, positions,
                     jnp.int32(table_row.shape[0] * cache[0]["k"].shape[1]))

    def attend(q, ck, cv, ksc, vsc, dtype):
        if kernel == "fused":
            from ..parallel.paged_attention import paged_prefill_attention

            return paged_prefill_attention(
                q[0], ck, cv, table_row, start_pos,
                k_scale=ksc, v_scale=vsc)[None]
        return _masked_attention(q, ck, cv, ksc, vsc, table_row, positions,
                                 dtype)

    return _paged_lm(params, cache, chunk, positions, table_row, wpos,
                     attend, cfg, adapters, adapter_idx, kv_quant,
                     true_len=true_len)


def paged_verify_step(params, cache, window, pos, wpos, tables,
                      cfg: TransformerConfig, adapters=None,
                      adapter_idx=None, kernel="gather",
                      kv_quant="none"):
    """Speculative-decoding verify: run a K-token `window` [S, K] per
    slot (the pending token followed by K-1 drafted tokens) through the
    paged cache in ONE batched step, returning logits for every window
    position [S, K, vocab]. Row (s, i) sits at global position
    pos[s] + i and attends the slot's cache up to and including itself
    (the intra-window causal prefix falls out of the position mask,
    because earlier window rows were just written at earlier
    positions). `wpos` [S, K] are the WRITE positions, precomputed by
    the caller so dead slots and rows past a request's token budget
    park (>= MAXB*Bt -> dropped); the mask/embedding positions are
    always pos[s] + i. logits[s, i] is "the next token after
    window[s, :i+1]" — exactly decode_step's answer when drafts
    0..i match what the model would have produced, which is what the
    engine's acceptance rule checks. The chunk's numerics, gathered or
    fused (`paged_prefill_chunk`). `kv_quant` quantizes the window's
    writes at the scatter (a window
    row opening a fresh block commits its scale; re-writes of rejected
    draft positions clip to the committed scale until the block is
    re-opened) and dequantizes the reads, fused or gathered."""
    paged_kernel_check(kernel)
    _kv_quant_check(kv_quant)
    positions = pos[:, None] + jnp.arange(window.shape[1])[None, :]

    def attend(q, ck, cv, ksc, vsc, dtype):
        if kernel == "fused":
            from ..parallel.paged_attention import paged_verify_attention

            return paged_verify_attention(q, ck, cv, tables, pos,
                                          k_scale=ksc, v_scale=vsc)
        return _masked_attention(q, ck, cv, ksc, vsc, tables, positions,
                                 dtype)

    return _paged_lm(params, cache, window, positions, tables, wpos, attend,
                     cfg, adapters, adapter_idx, kv_quant)


def logits_trap(logits):
    """Per-row non-finite TRAP over final logits (ISSUE 15): True where
    a row's logits contain any NaN/Inf, or its softmax denominator is
    non-finite or non-positive (an all-`-inf` row would sample from a
    zero-mass distribution — as corrupt as a NaN, and invisible to a
    plain isfinite check on the argmax path). A few extra reductions
    FOLDED into the caller's already-compiled step — never a second
    trace, never a second pass over the activations. `logits` is
    [..., V]; the result drops the vocab axis."""
    finite = jnp.isfinite(logits).all(axis=-1)
    # softmax denominator at the sampling dtype: max-subtracted like
    # jax.random.categorical itself, so the reduction traps exactly
    # the distribution the sampler would draw from
    f32 = logits.astype(jnp.float32)
    denom = jnp.sum(jnp.exp(f32 - jnp.max(f32, axis=-1, keepdims=True)),
                    axis=-1)
    return ~finite | ~jnp.isfinite(denom) | (denom <= 0.0)


def logit_amax(logits, mask=None):
    """Scalar max-|logit| over the (optionally masked) rows — the
    serving sentinel's EWMA signal (ISSUE 15): wrong-but-FINITE compute
    (a flipped exponent bit, a corrupted weight tile) usually shows as
    a magnitude excursion long before anything goes NaN. Masked rows
    (dead slots) contribute 0. Folded into the compiled step like
    `logits_trap`."""
    a = jnp.max(jnp.abs(logits.astype(jnp.float32)), axis=-1)
    if mask is not None:
        while mask.ndim < a.ndim:
            mask = mask[..., None]
        a = jnp.where(mask, a, 0.0)
    return jnp.max(a)


def decode_retire(alive, nxt, pos, limits, eos_ids):
    """Device-side retirement for the serving engine's decode step
    (ISSUE 19) — the branch-free device mirror of the host scheduler's
    `_emit` rule, applied after the step's token is chosen so a step
    chained off this one's outputs retires slots exactly where the
    host loop would:

      * a slot that samples its EOS token this step emits that token
        and goes dead (EOS itself is kept — same as the host, which
        appends then retires);
      * a slot whose advanced position reaches ``limits - 1`` (i.e. it
        has now emitted ``max_new_tokens`` tokens, the host's
        ``len(tokens) >= max_new_tokens`` budget rule at the decode
        invariant ``pos = T0 + len(tokens) - 1``) emits that final
        token and parks;
      * dead slots do not advance — their position is frozen so the
        caller's ``where(alive, pos, out_of_range)`` parking keeps all
        of their later scatter writes out of range, and their emitted
        lane carries the ``-1`` padding the host discards.

    ``eos_ids`` is a per-slot int32 band with ``-1`` meaning "no EOS
    configured" (the ``>= 0`` guard below), so a vocab-less sentinel
    never matches a real token. Pure element-wise jnp, no
    data-dependent Python branching."""
    live = alive.astype(jnp.int32)
    npos = pos + live
    hit_eos = (eos_ids >= 0) & (nxt == eos_ids)
    nalive = alive & ~hit_eos & (npos < limits - 1)
    return nalive, npos


def paged_block_fingerprint(cache, bid):
    """Folded-f32 checksum of ONE physical KV block across every layer
    and cache band (payload rows AND, on a quantized pool, the
    per-head scale side-bands) — the ISSUE 15 fingerprint op. Rides
    the block-id addressing exactly like PR 14's quant scales: the
    caller hands a physical block id, the reduction reads
    `buf[bid]` per band. Position-weighted (element index mod a small
    prime) so a transposition inside the block moves the sum, and
    per-band/per-layer folded with distinct multipliers so a value
    migrating between K and V (or between layers) cannot cancel.
    Deterministic for fixed shapes on a fixed backend — the engine
    compares a recomputed fingerprint against the one committed when
    the block closed, so only run-to-run determinism matters, never
    cross-backend bit equality. Cheap: one pass over a single block's
    bytes, jitted ONCE by the engine (a new trace would violate the
    one-compiled-step discipline the serving tests pin)."""
    acc = jnp.float32(0.0)
    for li, kv in enumerate(cache):
        for bi, band in enumerate(sorted(kv)):
            x = kv[band][bid].astype(jnp.float32).reshape(-1)
            w = (jnp.arange(x.shape[0], dtype=jnp.float32) % 97.0) + 1.0
            fold = jnp.float32(1.0 + 0.013 * (li * 7 + bi + 1))
            acc = acc + jnp.sum(x * w) * fold
    return acc


__all__ += ["init_paged_kv_cache", "paged_decode_step",
            "paged_prefill_chunk", "paged_verify_step",
            "kv_storage_dtype", "kv_block_bytes",
            "logits_trap", "logit_amax", "paged_block_fingerprint",
            "decode_retire"]


def generate(params, prompt, cfg: TransformerConfig, max_new_tokens,
             temperature=0.0, key=None, max_len=None, eos_id=None):
    """Autoregressive generation: prefill the prompt [B, T0], then
    `max_new_tokens` cached decode steps inside ONE compiled loop (the
    host never re-enters it). temperature<=0 is greedy; otherwise
    softmax sampling with `key`. Returns [B, T0+max_new].

    `eos_id` opts into the reference's end-of-sequence semantics
    (RecurrentGradientMachine.h:309): a row that emits eos_id freezes
    (keeps re-emitting eos), and the loop EXITS EARLY once every row is
    done — a lax.while_loop instead of the fixed-trip scan, with the
    unwritten tail back-filled with eos (identical to what the frozen
    rows would have produced). Default None keeps the fixed-trip
    free-running behavior."""
    B, T0 = prompt.shape
    L = int(max_len or cfg.max_len)
    # the positional table bounds every position regardless of cache
    # size — JAX gather would silently clamp out-of-range indices
    L = min(L, int(params["pos"].shape[0]))
    if T0 + max_new_tokens > L:
        raise ValueError(
            "generate needs T0+max_new <= max_len (%d + %d > %d, "
            "positional table %d)"
            % (T0, max_new_tokens, L, int(params["pos"].shape[0]))
        )
    if temperature > 0 and key is None:
        raise ValueError("sampling (temperature > 0) requires `key`")
    logits, cache = prefill(params, prompt, cfg, max_len=L)
    key = key if key is not None else jax.random.PRNGKey(0)

    def pick(logits, k):
        if temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(prompt.dtype)
        return jax.random.categorical(
            k, logits.astype(jnp.float32) / temperature, axis=-1
        ).astype(prompt.dtype)

    def body(carry, i):
        logits, cache, k = carry
        k, sub = jax.random.split(k)
        tok = pick(logits, sub)
        logits, cache = decode_step(params, tok, T0 + i, cache, cfg)
        return (logits, cache, k), tok

    if eos_id is None:
        (_, _, _), toks = jax.lax.scan(
            body, (logits, cache, key), jnp.arange(max_new_tokens)
        )
        return jnp.concatenate([prompt, toks.T], axis=1)

    # eos semantics + early exit: buffer writes under lax.while_loop
    eos = jnp.asarray(eos_id, prompt.dtype)
    buf0 = jnp.zeros((B, max_new_tokens), prompt.dtype)

    def w_cond(state):
        i, alive, _, _, _, _ = state
        return (i < max_new_tokens) & jnp.any(alive)

    def w_body(state):
        i, alive, buf, logits, cache, k = state
        k, sub = jax.random.split(k)
        tok = pick(logits, sub)
        tok = jnp.where(alive, tok, eos)  # frozen rows re-emit eos
        buf = jax.lax.dynamic_update_index_in_dim(buf, tok, i, axis=1)
        alive = alive & (tok != eos)
        logits, cache = decode_step(params, tok, T0 + i, cache, cfg)
        return i + 1, alive, buf, logits, cache, k

    state = (
        jnp.asarray(0),
        jnp.ones((B,), bool),
        buf0,
        logits,
        cache,
        key,
    )
    steps_done, alive, buf, _, _, _ = jax.lax.while_loop(
        w_cond, w_body, state
    )
    # unwritten tail (all rows were done): exactly eos
    fill = jnp.arange(max_new_tokens)[None, :] >= steps_done
    buf = jnp.where(fill, eos, buf)
    if not isinstance(steps_done, jax.core.Tracer):
        LAST_DECODE_STATS["greedy_steps_executed"] = int(steps_done)
        LAST_DECODE_STATS["greedy_max_steps"] = int(max_new_tokens)
    return jnp.concatenate([prompt, buf], axis=1)


__all__ += ["init_kv_cache", "decode_step", "prefill", "generate"]


# diagnostics of the last eager beam_search_generate call: executed vs
# maximum decode steps (early exit stops at all-beams-dead)
LAST_DECODE_STATS = {}


def beam_search_generate(params, prompt, cfg: TransformerConfig,
                         max_new_tokens, beam_size=4, alpha=0.0,
                         max_len=None):
    """Beam-search generation over the KV cache (the transformer
    counterpart of the legacy RecurrentGradientMachine beam decode,
    RecurrentGradientMachine.h:309, kernels_control.py beam_search).

    Beams live flattened on the batch dim ([B*W, ...]) so every decode
    step is the SAME cached computation greedy uses; after top-k the
    caches gather along the beam dim by parent index. Finished beams
    (emitted eos) freeze: they re-emit eos with their frozen score.
    Returns (tokens [B, W, T0+max_new], scores [B, W]) sorted best
    first; alpha applies GNMT length normalisation at the final sort.
    eos is cfg.vocab - 1 by convention of this toy-vocab family.
    """
    B, T0 = prompt.shape
    W = int(beam_size)
    if max_new_tokens < 1:
        raise ValueError("beam_search_generate needs max_new_tokens >= 1")
    L = min(int(max_len or cfg.max_len), int(params["pos"].shape[0]))
    if T0 + max_new_tokens > L:
        raise ValueError(
            "beam_search_generate needs T0+max_new <= max_len "
            "(%d + %d > %d)" % (T0, max_new_tokens, L)
        )
    eos = cfg.vocab - 1

    logits, cache = prefill(params, prompt, cfg, max_len=L)  # [B, V]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    # seed beams from the prompt's top-W first tokens
    top_lp, top_tok = jax.lax.top_k(logp, W)  # [B, W]

    def tile_beam(x):
        return jnp.repeat(x, W, axis=0)  # [B*W, ...]

    cache = jax.tree_util.tree_map(tile_beam, cache)
    # fixed-size token buffer [B, W, T0+max_new]: scan carries must keep
    # their shape, so steps write in place instead of concatenating
    T_out = T0 + max_new_tokens
    tokens = jnp.zeros((B, W, T_out), prompt.dtype)
    tokens = tokens.at[:, :, :T0].set(tile_beam(prompt).reshape(B, W, T0))
    tokens = tokens.at[:, :, T0].set(top_tok)
    scores = top_lp  # [B, W] cumulative logprob
    alive = top_tok != eos  # [B, W]
    V = cfg.vocab

    def body(carry, i):
        tokens, scores, alive, cache = carry
        pos = T0 + i  # position of the newest written token
        last = jax.lax.dynamic_index_in_dim(
            tokens, pos, axis=2, keepdims=False
        ).reshape(B * W)
        lg, cache = decode_step(params, last, pos, cache, cfg)
        lp = jax.nn.log_softmax(lg.astype(jnp.float32), -1).reshape(B, W, V)
        # frozen beams contribute exactly one continuation: eos at zero
        # added cost (their score must not change or multiply)
        frozen_row = jnp.full((V,), -jnp.inf).at[eos].set(0.0)
        lp = jnp.where(alive[..., None], lp, frozen_row[None, None])
        cand = scores[..., None] + lp  # [B, W, V]
        flat = cand.reshape(B, W * V)
        new_scores, idx = jax.lax.top_k(flat, W)  # [B, W]
        parent = idx // V  # [B, W] which beam it extends
        tok = idx % V
        # reorder histories + caches by parent beam, write the new token
        tokens = jnp.take_along_axis(
            tokens, parent[..., None], axis=1
        )
        tokens = jax.lax.dynamic_update_index_in_dim(
            tokens, tok, pos + 1, axis=2
        )
        alive = (
            jnp.take_along_axis(alive, parent, axis=1) & (tok != eos)
        )
        gather = (
            parent + jnp.arange(B)[:, None] * W
        ).reshape(B * W)  # flat indices into [B*W]

        def reorder(c):
            return c[gather]

        cache = jax.tree_util.tree_map(reorder, cache)
        return (tokens, new_scores, alive, cache), None

    # early exit (reference RecurrentGradientMachine.h:309): stop the
    # moment every beam of every source has emitted eos. lax.while_loop
    # instead of a fixed-trip scan; positions past the exit step are
    # back-filled with eos — exactly what the skipped iterations would
    # have written (dead beams re-emit eos at frozen score), so the
    # result is bit-identical to the full schedule.
    def w_cond(state):
        i, carry = state
        _, _, alive_c, _ = carry
        return (i < max_new_tokens - 1) & jnp.any(alive_c)

    def w_body(state):
        i, carry = state
        carry, _ = body(carry, i)
        return i + 1, carry

    steps_done, (tokens, scores, alive, _) = jax.lax.while_loop(
        w_cond, w_body, (jnp.asarray(0), (tokens, scores, alive, cache))
    )
    # positions beyond the last written token (T0 + steps_done) hold the
    # zero-init; the skipped all-dead steps would have written eos
    fill = jnp.arange(T_out) > (T0 + steps_done)
    tokens = jnp.where(fill[None, None, :], jnp.asarray(eos, tokens.dtype),
                       tokens)
    if not isinstance(steps_done, jax.core.Tracer):
        LAST_DECODE_STATS["steps_executed"] = int(steps_done)
        LAST_DECODE_STATS["max_steps"] = int(max_new_tokens - 1)
    # GNMT length penalty: ((5 + len) / 6)^alpha
    lens = (tokens[:, :, T0:] != eos).sum(-1) + 1
    penal = jnp.power((5.0 + lens.astype(jnp.float32)) / 6.0, alpha)
    final = scores / penal  # penal > 0 always (lens >= 1)
    order = jnp.argsort(-final, axis=1)
    tokens = jnp.take_along_axis(tokens, order[..., None], axis=1)
    final = jnp.take_along_axis(final, order, axis=1)
    return tokens, final


__all__ += ["beam_search_generate"]


class _Serving(Seam):
    """The GPT block keeps the pool alone and honours every option."""
    name = "gpt"
    refused = ()
    decode_step = staticmethod(paged_decode_step)
    prefill_chunk = staticmethod(paged_prefill_chunk)

    @staticmethod
    def init_cache(cfg, num_blocks, block_tokens, slots, kv_quant="none"):
        return init_paged_kv_cache(cfg, num_blocks, block_tokens,
                                   kv_quant=kv_quant)


SERVING = _Serving()
