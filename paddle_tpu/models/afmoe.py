"""A sparse-expert language model with gated grouped-query attention
over mixed window and full layers (ISSUE 33).

The block family of `model_type` `afmoe` (Trinity-Mini, "26B-A3B"):
four RMSNorms a layer, the branch's OUTPUT normed before it joins the
residual; grouped-query attention with a per-head RMSNorm on q and k
and a sigmoid gate on its output; by `layer_types`, sliding-window
layers whose q and k are rotated (RoPE, half-split) and full layers
with NO position; a dense SwiGLU in the first `num_dense_layers`
layers, and in every other layer `n_experts` routed SwiGLU experts
(sigmoid scores, a bias that decides the choice only, top-k,
normalised and scaled weights) beside one shared expert; an untied
head. With d the width and eps 1e-5, no bias on any matrix:

    x0     = E[token] * sqrt(d)
    a      = Attn(RMS(x; g1));   x <- x + RMS(a; g2)
    m      = FFN(RMS(x; g3));    x <- x + RMS(m; g4)
    logits = W_head RMS(x_L; g_f)            (float32)

    Attn(u): q, k, v, gate = u W_qkvg; q, k <- RMS_dh(.; g_q / g_k)
             per head; window layers rotate q and k and see key j from
             query i iff i - window < j <= i, full layers see j <= i;
             o = softmax(q k^T / sqrt(dh)) v * sigmoid(gate); W_o o
    FFN(u):  layer < num_dense_layers: W_down(silu(g) * up)
             else Shared(u) + sum_{e in S} w_e Expert_e(u)
             (`parallel/routed_experts.py`: the router in float32 from
             the float32 normed row)

One stack (`parts.expert_stack`) runs every mode; a mode is the
`attn` it hands the stack:

  forward               whole sequence, no cache (the cached modes'
                        oracle)
  paged_decode_step     one token a slot through the two caches
  paged_prefill_chunk   a [C]-token chunk of ONE slot through them

The two caches (`init_cache`), served by ServingEngine through
`SERVING` (`caches = ("paged", "window")`: window tables, no
recurrent state):

  full    a paged pool a full layer, all on the engine's ONE table:
          {"k", "v"} [NB, Bt * Hk, dh]; the keys position-free
  window  a paged pool a window layer, on one shared table whose
          entries behind the window the engine frees (at most
          ceil(window / Bt) + 1 blocks a slot); the keys ROTATED:
          K is written after the q/k norm and the rotation

A block's rows are (token, K/V head), the bytes of a `[Bt, Hk, dh]`
block, 3-D so that the device's 16 x 128 tiles are full (`sambay.py`
says why); dh = 128 fills a row, so no two heads share one.

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
from .parts import (NEG_INF, Seam, chunk_attend, expert_stack, head_view,
                    init_tree, kv_pool, paged_kernel_check, rms32,
                    scatter_chunk, scatter_rows, untied_head)
from .scopes import scope

__all__ = ["AfmoeConfig", "init_params", "param_shapes", "forward",
           "init_cache", "cache_bytes", "paged_decode_step",
           "paged_prefill_chunk", "SERVING"]

_KINDS = {"sliding_attention": "window", "full_attention": "full"}


class AfmoeConfig:
    def __init__(self, vocab=256, dim=64, heads=4, kv_heads=2, head_dim=16,
                 layer_types=("sliding_attention", "full_attention"),
                 layers=None, num_dense_layers=1, dense_width=128,
                 expert_width=32, n_experts=8, top_k=2, route_scale=1.0,
                 route_norm=True, window=32, rope_theta=10000.0,
                 experts_held=None, shared_expert_held=True, eps=1e-5,
                 max_len=1024, dtype=jnp.float32):
        if heads % kv_heads:
            raise ValueError("grouped queries share a K/V head: heads %% "
                             "kv_heads == 0 (got %d, %d)" % (heads, kv_heads))
        bad = set(layer_types) - set(_KINDS)
        if bad:
            raise ValueError("layer_types holds %r" % sorted(bad))
        self.vocab, self.dim, self.heads = vocab, dim, heads
        self.kv_heads, self.dh = kv_heads, head_dim
        self.rep = heads // kv_heads
        self.groups = kv_heads  # pool rows a token: one K/V head each
        self.kinds = tuple(_KINDS[t] for t in layer_types)
        self.layers = len(self.kinds)
        if layers is not None and int(layers) != self.layers:
            raise ValueError("layers %d, layer_types names %d"
                             % (layers, self.layers))
        self.num_dense_layers = int(num_dense_layers)
        self.dense_width, self.expert_width = dense_width, expert_width
        self.n_experts, self.top_k = int(n_experts), int(top_k)
        self.experts_held = held_range(experts_held, self.n_experts)
        self.shared_expert_held = bool(shared_expert_held)
        self.route_scale = float(route_scale)
        self.route_norm = bool(route_norm)
        self.window, self.rope_theta = int(window), float(rope_theta)
        # the scores' scale, under the name `parts.chunk_attend`
        # reads it by
        self.attention_multiplier = 1.0 / math.sqrt(head_dim)
        self.eps, self.max_len, self.dtype = eps, max_len, dtype
        self.serving = SERVING


def param_shapes(cfg: AfmoeConfig):
    d, dh = cfg.dim, cfg.dh
    nq, nk = cfg.heads * dh, cfg.kv_heads * dh
    Eh = cfg.experts_held[1] - cfg.experts_held[0]
    attn = {"wqkvg": (d, 2 * nq + 2 * nk), "q_norm": (dh,), "k_norm": (dh,),
            "wo": (nq, d)}

    def ffn(l):
        if l < cfg.num_dense_layers:
            return {"w_gu": (d, 2 * cfg.dense_width),
                    "w_down": (cfg.dense_width, d)}
        m = cfg.expert_width
        return {"router": (d, cfg.n_experts), "router_bias": (cfg.n_experts,),
                "experts": {"w_gu": (Eh, d, 2 * m), "w_down": (Eh, m, d)},
                "shared": {"w_gu": (d, 2 * m), "w_down": (m, d)}}

    return {"embed": (cfg.vocab, d), "norm_f": (d,), "head": (cfg.vocab, d),
            "blocks": [{"norm1": (d,), "norm2": (d,), "norm3": (d,),
                        "norm4": (d,), "attn": dict(attn), "ffn": ffn(l)}
                       for l in range(cfg.layers)]}


def init_params(cfg: AfmoeConfig, key) -> Dict[str, Any]:
    """Seeded random weights in `cfg.dtype`: matrices N(0, 1 / the
    contraction's length) (so the embedding's rows come out of the
    sqrt(d) multiplier at unit scale), norm gains 1 + N(0, 0.1), the
    router's bias N(0, 0.05): small against the scores' spread, large
    enough that the choice and the weights differ."""
    def leaf(k, name, shp):
        n = jax.random.normal(k, shp, jnp.float32)
        if name.endswith("norm") or name.startswith("norm"):
            return 1.0 + 0.1 * n
        if name == "router_bias":
            return 0.05 * n
        rows = shp[-1] if name in ("embed", "head") else shp[-2]
        return n / math.sqrt(rows)

    return init_tree(param_shapes(cfg), key, cfg.dtype, leaf)


# ---------------------------------------------------------------------
# pieces every mode shares
# ---------------------------------------------------------------------


def _rope(x, pos, theta):
    """Rotate x [..., dh] (float32) to positions `pos`, which
    broadcasts against x's leading dims: the half-split convention,
    x cos + rotate_half(x) sin over all dh dims."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _qkvg(h, p, pos, kind, cfg):
    """-> q [.., Hk, rep, dh], k and v [.., Hk, dh], the gate
    [.., heads * dh]: q and k normed per head and, in a window layer,
    rotated to `pos` [..]."""
    f32 = jnp.float32
    y = h @ p["wqkvg"]
    nq, nk = cfg.heads * cfg.dh, cfg.kv_heads * cfg.dh
    lead = h.shape[:-1]
    q = rms32(y[..., :nq].reshape(lead + (cfg.kv_heads, cfg.rep, cfg.dh)),
              p["q_norm"], cfg.eps)
    k = rms32(y[..., nq:nq + nk].reshape(lead + (cfg.kv_heads, cfg.dh)),
              p["k_norm"], cfg.eps)
    if kind == "window":
        q = _rope(q, pos[..., None, None], cfg.rope_theta)
        k = _rope(k, pos[..., None], cfg.rope_theta)
    v = y[..., nq + nk:nq + 2 * nk].reshape(lead + (cfg.kv_heads, cfg.dh))
    return q.astype(h.dtype), k.astype(h.dtype), v, y[..., nq + 2 * nk:]


def _attn_out(o, gate, p):
    """o [.., heads * dh] -> the layer's output: the sigmoid gate, W_o."""
    o = o.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))
    return o.astype(gate.dtype) @ p["wo"]


def _attend(q, k, v, qpos, kpos, window, cfg):
    """q [Q, Hk, rep, dh] at positions qpos [Q] over k, v [K, Hk, dh]
    at positions kpos [K] -> [Q, heads * dh]: causal, banded where
    `window`; a key at a negative position is nobody's."""
    f32 = jnp.float32
    s = jnp.einsum("qhrd,khd->hrqk", q, k,
                   preferred_element_type=f32) * cfg.attention_multiplier
    ok = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] >= 0)
    if window:
        ok = ok & (kpos[None, :] > qpos[:, None] - window)
    prob = jax.nn.softmax(jnp.where(ok[None, None], s, NEG_INF), axis=-1)
    o = jnp.einsum("hrqk,khd->qhrd", prob.astype(v.dtype), v,
                   preferred_element_type=f32).astype(q.dtype)
    return o.reshape(o.shape[0], -1)


def _embed(params, tokens, cfg):
    with scope("lm_embed"):
        return params["embed"][tokens] * jnp.asarray(math.sqrt(cfg.dim),
                                                     cfg.dtype)


# ---------------------------------------------------------------------
# whole sequence, no cache
# ---------------------------------------------------------------------


def forward(params, tokens, cfg: AfmoeConfig):
    """tokens [T] -> float32 logits [T, vocab]: the whole sequence at
    once, no cache, no kernel."""
    pos = jnp.arange(tokens.shape[0])

    def attn(l, h, p):
        kind = cfg.kinds[l]
        q, k, v, gate = _qkvg(h, p, pos, kind, cfg)
        o = _attend(q, k, v, pos, pos,
                    cfg.window if kind == "window" else 0, cfg)
        return _attn_out(o, gate, p)

    x, _ = expert_stack(params, _embed(params, tokens, cfg), cfg, attn,
                        jnp.ones(tokens.shape, bool), "gather",
                        sandwich=True)
    return untied_head(params, x)


# ---------------------------------------------------------------------
# the two caches
# ---------------------------------------------------------------------


def init_cache(cfg: AfmoeConfig, num_blocks: int, block_tokens: int,
               window_blocks: int):
    rows = int(block_tokens) * cfg.groups
    return {"full": [kv_pool(num_blocks, rows, cfg.dh, cfg.dtype)
                     for k in cfg.kinds if k == "full"],
            "window": [kv_pool(window_blocks, rows, cfg.dh, cfg.dtype)
                       for k in cfg.kinds if k == "window"]}


def cache_bytes(cfg: AfmoeConfig, block_tokens: int) -> Dict[str, int]:
    """Bytes of one block over all the full layers' pools (they share
    the engine's table, so an allocated block is one in each) and over
    all the window layers' pools; `call_block` is one block of ONE
    pool, K + V: what a decode attention call moves a table entry."""
    item = jnp.dtype(cfg.dtype).itemsize
    blk = 2 * block_tokens * cfg.kv_heads * cfg.dh * item
    return {"full": cfg.kinds.count("full") * blk, "call_block": blk,
            "window": cfg.kinds.count("window") * blk}


# ---------------------------------------------------------------------
# decode: one token a slot
# ---------------------------------------------------------------------


def paged_decode_step(params, token, pos, tables, cache, cfg: AfmoeConfig,
                      kernel="gather"):
    """One decode step through the two caches: token [S] at per-row
    positions `pos` [S], `tables` [2, S, MAXB] (the full layers', the
    window layers') -> (float32 logits [S, vocab], updated cache,
    int32 [2]: experts reached summed over the expert layers, and the
    fullest expert's rows). A parked row (pos >= MAXB * Bt) writes no
    K/V and reaches no expert; its logits are garbage nothing reads.
    With kernel="fused" the attention reads and writes and the grouped
    products are Pallas kernels (parallel/paged_attention.py: the
    grouped-query decode call, a window call walking only the window's
    blocks; parallel/routed_experts.py); "gather" is the same
    arithmetic in XLA."""
    from ..parallel.paged_attention import (paged_decode_attention,
                                            paged_kv_write)

    paged_kernel_check(kernel)
    ftab, wtab = tables[0], tables[1]
    S, maxb = ftab.shape
    Bt = cache["window"][0]["k"].shape[1] // cfg.groups
    live = pos < maxb * Bt
    first = jnp.maximum(pos - cfg.window + 1, 0)
    new = {"full": [], "window": []}
    it = {"full": iter(cache["full"]), "window": iter(cache["window"])}

    def attn(l, h, p):
        kind = cfg.kinds[l]
        kv = next(it[kind])
        tab = wtab if kind == "window" else ftab
        w = cfg.window if kind == "window" else 0
        q, k, v, gate = _qkvg(h, p, pos, kind, cfg)
        if kernel == "fused":
            kv = dict(zip("kv", paged_kv_write(kv["k"], kv["v"], k, v,
                                               tab, pos)))
            o = paged_decode_attention(
                q, kv["k"], kv["v"], tab, pos, first=first if w else None)
            o = o.reshape(S, -1)
        else:
            kv = {"k": scatter_rows(kv["k"], tab, pos, k, cfg, Bt),
                  "v": scatter_rows(kv["v"], tab, pos, v, cfg, Bt)}
            kpos = jnp.arange(maxb * Bt)
            o = jax.vmap(
                lambda q1, k1, v1, p1: _attend(q1[None], k1, v1, p1[None],
                                               kpos, w, cfg)[0]
            )(q, head_view(kv["k"], tab, cfg, Bt),
              head_view(kv["v"], tab, cfg, Bt), pos)
        new[kind].append(kv)
        return _attn_out(o, gate, p)

    x, stats = expert_stack(params, _embed(params, token, cfg), cfg, attn,
                            live, kernel, sandwich=True)
    return untied_head(params, x), new, stats


# ---------------------------------------------------------------------
# prefill: a chunk of one slot
# ---------------------------------------------------------------------


def _band_attend(q, k, v, start_pos, cfg):
    """A window layer's chunk: q [C, Hk, rep, dh], row r at position
    start_pos + r, over k, v [W + C, Hk, dh] — the W positions behind
    the chunk, then the chunk's own rows: index n sits at position
    start_pos - W + n — -> [C, heads * dh]. Query tile i reads keys
    i .. i + tile + W of that array and no others, a key tile at a
    time with the running (max, sum, acc) of an online softmax (one
    softmax over the whole span takes the contraction over dh off the
    matrix unit: `parts.chunk_attend`). Every bound is
    static."""
    f32 = jnp.float32
    C, Hk, rep, dh = q.shape
    W = k.shape[0] - C
    tile = min(512, C)
    span = -(-(tile + W) // tile) * tile
    pad = jnp.zeros((span - (tile + W),) + k.shape[1:], k.dtype)
    kh = jnp.concatenate([k, pad]).transpose(1, 0, 2)  # [Hk, W + C + pad, dh]
    vh = jnp.concatenate([v, pad]).transpose(1, 0, 2)
    outs = []
    for i in range(0, C, tile):
        qh = q[i:i + tile].transpose(1, 2, 0, 3).reshape(Hk, rep * tile, dh)
        row = jnp.tile(i + jnp.arange(tile), rep)  # the query's chunk row
        m = jnp.full((Hk, rep * tile, 1), NEG_INF, f32)
        l = jnp.zeros_like(m)
        acc = jnp.zeros((Hk, rep * tile, dh), f32)
        for j in range(i, i + span, tile):
            s = jax.lax.dot_general(
                qh, kh[:, j:j + tile], (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=f32) * cfg.attention_multiplier
            n = j + jnp.arange(tile)
            ok = ((n[None, :] <= row[:, None] + W) & (n[None, :] > row[:, None])
                  & (n[None, :] + start_pos >= W))
            s = jnp.where(ok[None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            # a tile with no visible key leaves (m, l, acc) as they were
            p = jnp.where(ok[None], jnp.exp(s - m_new), 0.0)
            a = jnp.exp(m - m_new)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), vh[:, j:j + tile],
                (((2,), (1,)), ((0,), (0,))), preferred_element_type=f32)
            m, l, acc = m_new, l * a + p.sum(-1, keepdims=True), acc * a + pv
        o = (acc / l).reshape(Hk, rep, tile, dh).transpose(2, 0, 1, 3)
        outs.append(o.reshape(tile, Hk * rep * dh).astype(q.dtype))
    return jnp.concatenate(outs)


def paged_prefill_chunk(params, cache, chunk, start_pos, table_rows,
                        cfg: AfmoeConfig, true_len=None, kernel="gather"):
    """Extend ONE slot by a [C]-token chunk whose first row sits at
    `start_pos` -> (float32 logits of row true_len - 1 [vocab],
    updated cache). `table_rows` [4, MAXB]: the slot's row of the full
    table; of the window table as it stood before this chunk (read:
    the window behind the chunk); of the window table as it stands
    after it (written: what the positions after the chunk will still
    see); and a row nothing here reads (the slot's index, for families
    with per-slot state). Rows past `true_len` pad the bucket: they
    reach no expert and their K/V is parked or overwritten before
    anything attends it.

    The attention is XLA in either `kernel`: a full layer attends the
    slot's span gathered through the table after the chunk's own rows
    are written (`parts.chunk_attend`); a window layer its
    own rows and the window behind the chunk (`_band_attend`). The
    experts' grouped products are the decode step's."""
    paged_kernel_check(kernel)
    (C,) = chunk.shape
    ftab, wread, wwrite = table_rows[0], table_rows[1], table_rows[2]
    maxb = ftab.shape[0]
    Bt = cache["window"][0]["k"].shape[1] // cfg.groups
    W = cfg.window
    if true_len is None:
        true_len = C
    offs = jnp.arange(C)
    valid = offs < true_len
    positions = start_pos + offs
    wpos = jnp.where(valid, positions, jnp.int32(maxb * Bt))
    new = {"full": [], "window": []}
    it = {"full": iter(cache["full"]), "window": iter(cache["window"])}

    def behind(pool):
        # the W positions behind the chunk, through the table as it
        # was before this chunk's release
        back = start_pos - W + jnp.arange(W)
        blk = jnp.clip(wread[jnp.clip(back // Bt, 0, maxb - 1)], 0,
                       pool.shape[0] - 1)
        row = (back % Bt)[:, None] * cfg.groups + jnp.arange(cfg.groups)
        return pool[blk[:, None], row]

    def nothing(pool):
        return jnp.zeros((W, cfg.groups, cfg.dh), pool.dtype)

    def attn(l, h, p):
        kind = cfg.kinds[l]
        kv = next(it[kind])
        q, k, v, gate = _qkvg(h, p, positions, kind, cfg)
        if kind == "window":
            kk = jnp.concatenate([jax.lax.cond(
                start_pos == 0, nothing, behind, kv["k"]), k])
            vv = jnp.concatenate([jax.lax.cond(
                start_pos == 0, nothing, behind, kv["v"]), v])
            o = _band_attend(q, kk, vv, start_pos, cfg)
            tab = wwrite
        else:
            tab = ftab
        kv = {"k": scatter_chunk(kv["k"], tab, start_pos, wpos, true_len,
                                 k, cfg, Bt),
              "v": scatter_chunk(kv["v"], tab, start_pos, wpos, true_len,
                                 v, cfg, Bt)}
        new[kind].append(kv)
        if kind == "full":
            o = chunk_attend(q, head_view(kv["k"], tab, cfg, Bt),
                             head_view(kv["v"], tab, cfg, Bt), start_pos, cfg)
        return _attn_out(o, gate, p)

    x, _ = expert_stack(params, _embed(params, chunk, cfg), cfg, attn,
                        valid, kernel, sandwich=True)
    with scope("lm_head"):
        xl = jax.lax.dynamic_index_in_dim(x, true_len - 1, axis=0,
                                          keepdims=False)
    return untied_head(params, xl), new


class _Serving(Seam):
    """The full layers' pools on the engine's one table, the window
    layers' on a window table freed behind the window; no state, so no
    slot is reset at admission."""
    name = "afmoe"
    caches = ("paged", "window")
    refusal = ("its window layers free the blocks behind the window, "
               "which a cached prefix, a stored or handed-on block or a "
               "re-played draft would still name; quantization, adapters "
               "and fingerprints are not built for it")
    step_counters = ("moe_experts_hit", "moe_rows_max")
    cache_bytes = staticmethod(cache_bytes)
    decode = staticmethod(paged_decode_step)
    prefill = staticmethod(paged_prefill_chunk)

    @staticmethod
    def cache(cfg, num_blocks, block_tokens, slots):
        per_slot = -(-cfg.window // int(block_tokens)) + 1
        return init_cache(cfg, num_blocks, block_tokens, slots * per_slot)


SERVING = _Serving()
