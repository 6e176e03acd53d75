"""A sparse-expert language model with latent attention (ISSUE 37).

The block family of `model_type` `deepseek_v3` without a query LoRA
(Kanana-2-30B-A3B, "30B-A3B"): two RMSNorms a layer; multi-head latent
attention (MLA), whose keys and values are ONE latent a token — a
normed `kv_rank`-wide vector c and a `rope_dim`-wide rotary key k_r
that every head shares — expanded per head by W_kvb; a dense SwiGLU in
the first `num_dense_layers` layers, and in every other layer
`n_experts` routed SwiGLU experts (sigmoid scores, a bias that decides
the choice only, top-k, normalised and scaled weights) beside a shared
SwiGLU `n_shared_experts` experts wide; an untied head. With d the
width and eps 1e-6, no bias on any matrix:

    x0     = E[token]
    x     <- x + Attn(RMS(x; g1))
    x     <- x + FFN(RMS(x; g2))
    logits = W_head RMS(x_L; g_f)            (float32)

    Attn(u): q = u W_q [heads, nope + rope] -> q_n, q_r;
             a = u W_kva [kv_rank + rope] -> c = RMS(a[:kv_rank]; g_kv),
             k_r = a[kv_rank:]; q_r and k_r rotated (theta, the pairs
             (2i, 2i + 1), read de-interleaved as the published
             modeling code's `apply_rotary_pos_emb_interleave` leaves
             them: [rotated evens | rotated odds] — q and k alike, so
             every product is the pairs' own);
             [k_n | v] = c W_kvb [heads, nope + v_dim];
             o = softmax((q_n k_n + q_r k_r) / sqrt(nope + rope),
             causal) v; W_o o
    FFN(u):  layer < num_dense_layers: W_down(silu(g) * up)
             else Shared(u) + sum_{e in S} w_e Expert_e(u)
             (`parts.moe_ffn` over `parallel/routed_experts.py`: the
             router in float32 from the float32 normed row)

One stack (`parts.expert_stack`, afmoe's too) runs every mode; a
mode is the `attn` it hands the stack:

  forward               whole sequence, no cache, every head's keys
                        and values expanded (the cached modes' oracle)
  paged_decode_step     one token a slot, ABSORBED: W_kvb's key half
                        is folded into the query (q_lat[h] = W_uk[h]
                        q_n[h], [kv_rank]) and its value half applied
                        after the attention (o[h] = o_lat[h] W_uv[h]),
                        so a query [heads, kv_rank + rope] reads the
                        latent rows themselves, once, for both products
                        (`parallel/paged_attention.mla_decode_attention`)
  paged_prefill_chunk   a [C]-token chunk of ONE slot, EXPANDED: the
                        latents through W_kvb to per-head k_n and v,
                        key-tiled causal attention
                        (`parts.chunk_attend`)

The one cache (`init_cache`), served by ServingEngine through
`SERVING` (`caches = ("paged",)`: the engine's one table, no window,
no state): a pool a layer, [NB + 1, Bt, row], one row a token —
c (normed) in lanes [0, kv_rank), the ROTATED k_r in [kv_rank,
kv_rank + rope), zeros to `row`, the lanes rounded up to whole 128-lane
tiles (576 -> 640 at the published widths: what the chip's tiled
layout stores anyway). Written once, when the token is.

`experts_held = (lo, hi)` names the experts whose weights this chip
has (the leaves under "experts" carry hi - lo of them): the layer
routes over all `n_experts` and computes the part its own experts
give; `shared_expert_held` says whether the shared expert is computed
here. The defaults hold everything.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..parallel.routed_experts import held_range
from .parts import (NEG_INF, Seam, chunk_attend, embed, expert_stack,
                    init_tree, paged_kernel_check, pool_view, rms32,
                    scatter_chunk, scatter_rows, untied_head)
from .scopes import scope

__all__ = ["MlaMoeConfig", "init_params", "param_shapes", "forward",
           "init_cache", "cache_bytes", "paged_decode_step",
           "paged_prefill_chunk", "SERVING"]


class MlaMoeConfig:
    def __init__(self, vocab=256, dim=64, heads=4, nope_dim=16, rope_dim=8,
                 v_dim=16, kv_rank=32, layers=3, num_dense_layers=1,
                 dense_width=128, expert_width=32, n_shared_experts=2,
                 n_experts=8, top_k=2, route_scale=1.0, route_norm=True,
                 rope_theta=10000.0, experts_held=None,
                 shared_expert_held=True, eps=1e-6, max_len=1024,
                 dtype=jnp.float32):
        if rope_dim % 2:
            raise ValueError("rope_dim rotates pairs: even (got %d)"
                             % rope_dim)
        self.vocab, self.dim, self.heads = vocab, dim, heads
        self.nope_dim, self.rope_dim, self.v_dim = nope_dim, rope_dim, v_dim
        self.kv_rank = kv_rank
        self.layers = int(layers)
        self.num_dense_layers = int(num_dense_layers)
        self.dense_width, self.expert_width = dense_width, expert_width
        self.n_shared_experts = int(n_shared_experts)
        self.n_experts, self.top_k = int(n_experts), int(top_k)
        self.experts_held = held_range(experts_held, self.n_experts)
        self.shared_expert_held = bool(shared_expert_held)
        self.route_scale = float(route_scale)
        self.route_norm = bool(route_norm)
        self.rope_theta = float(rope_theta)
        # a latent row as stored: c and k_r, in whole 128-lane tiles
        self.latent_row = -(-(kv_rank + rope_dim) // 128) * 128
        self.groups = 1  # pool rows a token (parts.scatter_rows)
        # the scores' scale, under the name `parts.chunk_attend`
        # reads it by
        self.attention_multiplier = 1.0 / math.sqrt(nope_dim + rope_dim)
        self.eps, self.max_len, self.dtype = eps, max_len, dtype
        self.serving = SERVING


def param_shapes(cfg: MlaMoeConfig):
    d, H, r = cfg.dim, cfg.heads, cfg.kv_rank
    Eh = cfg.experts_held[1] - cfg.experts_held[0]
    attn = {"wq": (d, H * (cfg.nope_dim + cfg.rope_dim)),
            "wkva": (d, r + cfg.rope_dim), "kv_norm": (r,),
            "wkvb": (r, H * (cfg.nope_dim + cfg.v_dim)),
            "wo": (H * cfg.v_dim, d)}

    def ffn(l):
        if l < cfg.num_dense_layers:
            return {"w_gu": (d, 2 * cfg.dense_width),
                    "w_down": (cfg.dense_width, d)}
        m, ms = cfg.expert_width, cfg.n_shared_experts * cfg.expert_width
        return {"router": (d, cfg.n_experts), "router_bias": (cfg.n_experts,),
                "experts": {"w_gu": (Eh, d, 2 * m), "w_down": (Eh, m, d)},
                "shared": {"w_gu": (d, 2 * ms), "w_down": (ms, d)}}

    return {"embed": (cfg.vocab, d), "norm_f": (d,), "head": (cfg.vocab, d),
            "blocks": [{"norm1": (d,), "norm2": (d,), "attn": dict(attn),
                        "ffn": ffn(l)} for l in range(cfg.layers)]}


def init_params(cfg: MlaMoeConfig, key) -> Dict[str, Any]:
    """Seeded random weights in `cfg.dtype`: the embedding N(0, 1) (no
    multiplier follows it: its rows enter the residual at unit scale),
    every other matrix N(0, 1 / the contraction's length), norm gains
    1 + N(0, 0.1), the router's bias N(0, 0.05)."""
    def leaf(k, name, shp):
        n = jax.random.normal(k, shp, jnp.float32)
        if "norm" in name:
            return 1.0 + 0.1 * n
        if name == "router_bias":
            return 0.05 * n
        if name == "embed":
            return n
        return n / math.sqrt(shp[-1] if name == "head" else shp[-2])

    return init_tree(param_shapes(cfg), key, cfg.dtype, leaf)


# ---------------------------------------------------------------------
# pieces every mode shares
# ---------------------------------------------------------------------


def _rope(x, pos, theta):
    """Rotate x [..., rope] (float32) to positions `pos`, which
    broadcasts against x's leading dims: the pairs (2i, 2i + 1) by
    pos * theta^(-2i / rope), returned de-interleaved [rotated evens |
    rotated odds]."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _project(h, p, pos, cfg):
    """h [.., d] at positions `pos` [..] -> q_n [.., heads, nope] (the
    dtype), q_r [.., heads, rope] (float32, rotated), and the token's
    latent row [.., row] in the dtype: c after its norm, then the
    rotated k_r, then zeros — what the cache stores."""
    f32 = jnp.float32
    lead = h.shape[:-1]
    q = (h @ p["wq"]).reshape(lead + (cfg.heads, cfg.nope_dim + cfg.rope_dim))
    a = h @ p["wkva"]
    c = rms32(a[..., :cfg.kv_rank], p["kv_norm"], cfg.eps)
    k_r = _rope(a[..., cfg.kv_rank:].astype(f32), pos, cfg.rope_theta)
    q_r = _rope(q[..., cfg.nope_dim:].astype(f32), pos[..., None],
                cfg.rope_theta)
    pad = jnp.zeros(lead + (cfg.latent_row - cfg.kv_rank - cfg.rope_dim,),
                    f32)
    row = jnp.concatenate([c, k_r, pad], -1).astype(h.dtype)
    return q[..., :cfg.nope_dim], q_r, row


def _expand(lat, p, cfg):
    """Latent rows [K, row] -> keys [K, heads, nope + rope] (k_n, then
    the one k_r every head shares) and values [K, heads, v_dim]."""
    K = lat.shape[0]
    H, dn = cfg.heads, cfg.nope_dim
    kv = (lat[:, :cfg.kv_rank] @ p["wkvb"]).reshape(K, H, dn + cfg.v_dim)
    k_r = lat[:, None, cfg.kv_rank:cfg.kv_rank + cfg.rope_dim]
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (K, H, cfg.rope_dim))], -1)
    return k, kv[..., dn:]


def _attend(q, k, v, qpos, kpos, cfg):
    """q [Q, heads, nope + rope] at positions qpos [Q] over k [K, heads,
    nope + rope], v [K, heads, v_dim] at positions kpos [K] -> [Q,
    heads * v_dim]: causal, in one softmax (the oracle's form)."""
    f32 = jnp.float32
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   preferred_element_type=f32) * cfg.attention_multiplier
    ok = kpos[None, :] <= qpos[:, None]
    prob = jax.nn.softmax(jnp.where(ok[None], s, NEG_INF), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", prob.astype(v.dtype), v,
                   preferred_element_type=f32).astype(q.dtype)
    return o.reshape(o.shape[0], -1)


def _absorbed_query(q_n, q_r, p, cfg):
    """q_n [S, heads, nope], q_r [S, heads, rope] -> the query a slot
    puts to its latent rows [S, heads, row]: q_n folded through W_kvb's
    key half (float32 accumulation), then q_r, then zeros."""
    H, dn = cfg.heads, cfg.nope_dim
    w_uk = p["wkvb"].reshape(cfg.kv_rank, H, dn + cfg.v_dim)[..., :dn]
    q_lat = jnp.einsum("shn,rhn->shr", q_n, w_uk,
                       preferred_element_type=jnp.float32)
    pad = jnp.zeros(q_r.shape[:-1] + (cfg.latent_row - cfg.kv_rank
                                      - cfg.rope_dim,), jnp.float32)
    return jnp.concatenate([q_lat, q_r, pad], -1).astype(q_n.dtype)


def _absorbed_out(o_lat, p, cfg):
    """o_lat [S, heads, kv_rank] -> [S, heads * v_dim]: W_kvb's value
    half, after the attention."""
    H, dn = cfg.heads, cfg.nope_dim
    w_uv = p["wkvb"].reshape(cfg.kv_rank, H, dn + cfg.v_dim)[..., dn:]
    o = jnp.einsum("shr,rhv->shv", o_lat.astype(w_uv.dtype), w_uv,
                   preferred_element_type=jnp.float32)
    return o.reshape(o.shape[0], -1).astype(w_uv.dtype)


# ---------------------------------------------------------------------
# whole sequence, no cache
# ---------------------------------------------------------------------


def forward(params, tokens, cfg: MlaMoeConfig):
    """tokens [T] -> float32 logits [T, vocab]: the whole sequence at
    once, every head's keys and values expanded, no cache, no kernel."""
    pos = jnp.arange(tokens.shape[0])

    def attn(l, h, p):
        q_n, q_r, lat = _project(h, p, pos, cfg)
        k, v = _expand(lat, p, cfg)
        q = jnp.concatenate([q_n, q_r.astype(q_n.dtype)], -1)
        return _attend(q, k, v, pos, pos, cfg) @ p["wo"]

    x, _ = expert_stack(params, embed(params, tokens), cfg, attn,
                        jnp.ones(tokens.shape, bool), "gather",
                        sandwich=False)
    return untied_head(params, x)


# ---------------------------------------------------------------------
# the latent cache
# ---------------------------------------------------------------------


def init_cache(cfg: MlaMoeConfig, num_blocks: int, block_tokens: int):
    # one block more than the allocator hands out: where the fused
    # decode write sends a parked slot's row (paged_kv_write)
    shape = (int(num_blocks) + 1, int(block_tokens), cfg.latent_row)
    return {"latent": [jnp.zeros(shape, cfg.dtype)
                       for _ in range(cfg.layers)]}


def cache_bytes(cfg: MlaMoeConfig, block_tokens: int) -> Dict[str, int]:
    """Bytes of one block over every layer's pool as STORED (they share
    the engine's table, so an allocated block is one in each), padding
    lanes included; `call_block` is one block of ONE pool: what a
    decode attention call moves a table entry, once for both of its
    products."""
    blk = block_tokens * cfg.latent_row * jnp.dtype(cfg.dtype).itemsize
    return {"full": cfg.layers * blk, "call_block": blk}


# ---------------------------------------------------------------------
# decode: one token a slot, absorbed
# ---------------------------------------------------------------------


def paged_decode_step(params, token, pos, tables, cache, cfg: MlaMoeConfig,
                      kernel="gather"):
    """One decode step through the latent cache: token [S] at per-row
    positions `pos` [S], `tables` [S, MAXB] -> (float32 logits [S,
    vocab], updated cache, int32 [2]: experts reached summed over the
    expert layers, and the fullest expert's rows). A parked row (pos >=
    MAXB * Bt) writes no latent and reaches no expert; its logits are
    garbage nothing reads. With kernel="fused" the latent write, the
    attention and the grouped products are Pallas kernels
    (parallel/paged_attention.py: `paged_kv_write` on one pool,
    `mla_decode_attention`; parallel/routed_experts.py); "gather" is the
    same absorbed arithmetic in XLA."""
    from ..parallel.paged_attention import (mla_decode_attention,
                                            paged_kv_write)

    paged_kernel_check(kernel)
    S, maxb = tables.shape
    Bt = cache["latent"][0].shape[1]
    live = pos < maxb * Bt
    r = cfg.kv_rank
    pools = iter(cache["latent"])
    new = []

    def attn(l, h, p):
        pool = next(pools)
        q_n, q_r, lat = _project(h, p, pos, cfg)
        q = _absorbed_query(q_n, q_r, p, cfg)  # [S, heads, row]
        if kernel == "fused":
            (pool,) = paged_kv_write(pool, None, lat[:, None], None,
                                     tables, pos)
            o_lat = mla_decode_attention(q, pool, tables, pos, r,
                                         cfg.attention_multiplier)
        else:
            pool = scatter_rows(pool, tables, pos, lat[:, None], cfg, Bt)
            view = pool_view(pool, tables, cfg, Bt)[:, :, 0]  # [S, K, row]
            s = jnp.einsum("shw,skw->shk", q, view,
                           preferred_element_type=jnp.float32
                           ) * cfg.attention_multiplier
            ok = jnp.arange(maxb * Bt)[None, :] <= pos[:, None]
            prob = jax.nn.softmax(jnp.where(ok[:, None], s, NEG_INF),
                                  axis=-1)
            o_lat = jnp.einsum("shk,skr->shr", prob.astype(view.dtype),
                               view[..., :r],
                               preferred_element_type=jnp.float32)
        new.append(pool)
        return _absorbed_out(o_lat, p, cfg) @ p["wo"]

    x, stats = expert_stack(params, embed(params, token), cfg, attn, live,
                            kernel, sandwich=False)
    return untied_head(params, x), {"latent": new}, stats


# ---------------------------------------------------------------------
# prefill: a chunk of one slot, expanded
# ---------------------------------------------------------------------


def paged_prefill_chunk(params, cache, chunk, start_pos, table_row,
                        cfg: MlaMoeConfig, true_len=None, kernel="gather"):
    """Extend ONE slot by a [C]-token chunk whose first row sits at
    `start_pos` -> (float32 logits of row true_len - 1 [vocab],
    updated cache). `table_row` [MAXB]: the slot's row of the block
    table. Rows past `true_len` pad the bucket: they reach no expert
    and their latent is parked or overwritten before anything attends
    it.

    The attention is XLA in either `kernel`, expanded: a chunk that
    starts a prompt attends its own rows' keys and values, expanded
    from the latents it computed; a later chunk attends the slot's
    span, read through the table after the chunk's rows are written
    and expanded whole — the same keys and values, since the cache
    holds exactly the latents the chunk computed. Key-tiled and causal
    (`parts.chunk_attend`). The experts' grouped products
    are the decode step's."""
    paged_kernel_check(kernel)
    (C,) = chunk.shape
    maxb = table_row.shape[0]
    Bt = cache["latent"][0].shape[1]
    if true_len is None:
        true_len = C
    offs = jnp.arange(C)
    valid = offs < true_len
    positions = start_pos + offs
    wpos = jnp.where(valid, positions, jnp.int32(maxb * Bt))
    pools = iter(cache["latent"])
    new = []

    def attn(l, h, p):
        pool = next(pools)
        q_n, q_r, lat = _project(h, p, positions, cfg)
        q = jnp.concatenate([q_n, q_r.astype(q_n.dtype)], -1)[:, :, None]
        pool = scatter_chunk(pool, table_row, start_pos, wpos, true_len,
                             lat[:, None], cfg, Bt)
        new.append(pool)

        def own(pool):
            return chunk_attend(q, *_expand(lat, p, cfg), start_pos, cfg)

        def span(pool):
            view = pool_view(pool, table_row, cfg, Bt)[:, 0]  # [K, row]
            return chunk_attend(q, *_expand(view, p, cfg), start_pos, cfg)

        return jax.lax.cond(start_pos == 0, own, span, pool) @ p["wo"]

    x, _ = expert_stack(params, embed(params, chunk), cfg, attn, valid,
                        kernel, sandwich=False)
    with scope("lm_head"):
        xl = jax.lax.dynamic_index_in_dim(x, true_len - 1, axis=0,
                                          keepdims=False)
    return untied_head(params, xl), {"latent": new}


class _Serving(Seam):
    """One latent pool a layer on the engine's one table: nothing is
    freed behind a window and no slot is reset at admission."""
    name = "mla_moe"
    refusal = ("its cache holds one latent a token and layer, not the "
               "per-head K/V blocks that a cached prefix, a stored or "
               "handed-on block or a verified draft are built on; "
               "quantization, adapters and fingerprints are not built "
               "for it")
    step_counters = ("moe_experts_hit", "moe_rows_max")
    cache_bytes = staticmethod(cache_bytes)
    decode = staticmethod(paged_decode_step)
    prefill = staticmethod(paged_prefill_chunk)

    @staticmethod
    def cache(cfg, num_blocks, block_tokens, slots):
        return init_cache(cfg, num_blocks, block_tokens)


SERVING = _Serving()
