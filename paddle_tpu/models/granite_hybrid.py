"""A Mamba-2 / grouped-query hybrid language model.

The block family of `model_type` `granitemoehybrid`: pre-RMSNorm
residual layers whose mixer is a Mamba-2 (SSD) layer or, at the depths
`layer_types` names, plain grouped-query attention; in every layer a
shared SwiGLU MLP (`mlp_width` wide) and, where `n_experts` > 0,
routed SwiGLU experts beside it; a tied head; no positional encoding of
any kind; and four published constants where other families have
none:

    x0     = embedding_multiplier * E[token]
    x     <- x + residual_multiplier * mixer(RMSNorm(x))
    x     <- x + residual_multiplier * FFN(RMSNorm(x))
    logits = RMSNorm(x) E^T / logits_scaling
    attention scores scaled by attention_multiplier (not 1/sqrt(dh))

    FFN(u) = MLP(u)                                    (granite-4.0-h-micro)
           = sum_{e in S} w_e Expert_e(u) + MLP(u)     (granite-4.0-h-small)
             S = the top_k largest router logits u W_r, w = softmax over
             those k alone (`parallel/routed_experts.route`,
             scoring "softmax_topk": float32 from the float32 normed row);
             an expert is a SwiGLU `expert_width` wide

`experts_held = (lo, hi)` names the experts whose weights this chip
has (the leaves under "experts" carry hi - lo of them): the router
scores all `n_experts` and the layer computes the part its own experts
give; the shared MLP is computed on every chip. The default holds all.
The expert branch (router to combine, and the shared MLP) runs under
the device scope `lm_experts`, the dense MLP under `lm_mlp`.

Mamba-2 layer (H heads of P channels, di = H P, N state columns, one
B/C group): [z | xBC | dt] = h W_in (di | di + 2N | H); xBC through a
causal depthwise conv of `d_conv` taps with bias, then silu, then
split x [H, P], B [N], C [N]; dt = softplus(dt + dt_bias) [H];
A = -exp(A_log) [H]; the recurrence of `parallel/ssd_update.py`;
y += D[h] x; out = RMSNorm(y * silu(z)) W_out (the gate BEFORE the
norm, which runs over all di channels).

One stack (`_stack`) runs every mode; a mode is the `mixer` it hands
the stack, dispatched on the layer's kind, as `sambay._stack` is:

  forward               whole sequence, no cache, the SEQUENTIAL
                        recurrence (the oracle of the cached modes)
  paged_decode_step     one token a slot through the two caches
  paged_prefill_chunk   a [C]-token chunk of ONE slot through them,
                        the recurrence in its blocked matrix form

The two caches (`init_cache`), served by ServingEngine through
`SERVING` (`caches = ("paged", "state")`: no window tables):

  kv    a paged pool an attention layer, all on the engine's ONE
        block table: {"k", "v"} [NB, Bt * groups, row]
  ssm   per-slot state, no position axis: "s" [S, N, di] float32 (2 MB
        a layer and slot for h-micro's 64 heads, 4 MB for h-small's
        128: the largest cache of the family) and the last d_conv - 1
        rows of the conv's input "conv" [S, d_conv - 1, di + 2N]

The pool's row width follows the head width (`cfg.paired`). A block's
rows are (token, row), the bytes of a `[Bt, Hk, dh]` block in that
order, 3-D so that the device holds them in 16 x 128 bf16 tiles
(`sambay.py` says the same of its pools):

  2 dh <= 128 (h-micro, dh 64): a row holds TWO K (or V) heads side by
        side, groups = Hk / 2, row = 2 dh = 128: the tiles are full
        where 64-wide rows would fill half of each (twice the bytes
        and the DMA). The decode kernel scores a query against a whole
        row: it sits in the half its K head occupies and is zero in
        the other, and of the 2 dh-wide value read its own head's half
        is kept.
  2 dh > 128 (h-small, dh 128): ONE head a row, groups = Hk, row = dh:
        a 128-wide head fills a tile's lanes alone, and pairing would
        make 256-lane rows whose every query is zero in half of them:
        twice the score products and the ring's VMEM for nothing (the
        layout of `afmoe.py`'s pools).
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..parallel.routed_experts import held_range
from .parts import (NEG_INF, Seam, chunk_attend, conv, head_view, init_tree,
                    kv_pool, mlp, paged_kernel_check, reset_slot_state,
                    rms32, scatter_chunk, scatter_rows)
from .scopes import scope

__all__ = ["GraniteHybridConfig", "init_params", "param_shapes", "forward",
           "init_cache", "cache_bytes", "paged_decode_step",
           "paged_prefill_chunk", "reset_slot_state",
           "SERVING", "SERVING_EXPERTS"]


class GraniteHybridConfig:
    def __init__(self, vocab=256, dim=64, heads=4, kv_heads=2, head_dim=16,
                 layer_types=("mamba", "attention"), layers=None, mlp_mult=4,
                 mlp_width=None, n_experts=0, top_k=0, expert_width=0,
                 experts_held=None, mamba_heads=8, mamba_head_dim=16,
                 d_state=16, d_conv=4, chunk=256, embedding_multiplier=12.0,
                 residual_multiplier=0.22, attention_multiplier=0.015625,
                 logits_scaling=8.0, eps=1e-5, max_len=1024,
                 dtype=jnp.float32):
        # the pool's row: two K/V heads where two fit 128 lanes, else one
        self.paired = 2 * head_dim <= 128
        if heads % kv_heads:
            raise ValueError("grouped queries share a K/V head: heads %% "
                             "kv_heads == 0 (got %d, %d)" % (heads, kv_heads))
        if self.paired and kv_heads % 2:
            raise ValueError(
                "a pool row holds two K/V heads of %d (2 x head_dim <= "
                "128 lanes): kv_heads even (got %d)" % (head_dim, kv_heads))
        self.n_experts = int(n_experts)
        self.top_k, self.expert_width = int(top_k), int(expert_width)
        if self.n_experts:
            if not 0 < self.top_k <= self.n_experts or self.expert_width < 1:
                raise ValueError(
                    "routed experts need 0 < top_k <= n_experts and an "
                    "expert_width (got top_k %d of %d, width %d)"
                    % (self.top_k, self.n_experts, self.expert_width))
            self.experts_held = held_range(experts_held, self.n_experts)
        elif experts_held is not None or self.top_k or self.expert_width:
            raise ValueError("top_k, expert_width and experts_held name "
                             "routed experts: n_experts is 0")
        bad = set(layer_types) - {"mamba", "attention"}
        if bad:
            raise ValueError("layer_types holds %r" % sorted(bad))
        self.vocab, self.dim, self.heads = vocab, dim, heads
        self.kv_heads, self.dh = kv_heads, head_dim
        self.rep = heads // kv_heads
        self.kinds = tuple(layer_types)
        self.layers = len(self.kinds)
        if layers is not None and int(layers) != self.layers:
            raise ValueError("layers %d, layer_types names %d"
                             % (layers, self.layers))
        self.mlp_mult = mlp_mult
        # the shared MLP's width (h-small's 1,536 is 0.375 x dim)
        self.mlp_width = int(mlp_width) if mlp_width else mlp_mult * dim
        self.mamba_heads, self.mamba_head_dim = mamba_heads, mamba_head_dim
        self.d_inner = mamba_heads * mamba_head_dim
        self.d_state, self.d_conv, self.chunk = d_state, d_conv, chunk
        self.conv_dim = self.d_inner + 2 * d_state
        self.embedding_multiplier = float(embedding_multiplier)
        self.residual_multiplier = float(residual_multiplier)
        self.attention_multiplier = float(attention_multiplier)
        self.logits_scaling = float(logits_scaling)
        self.eps, self.max_len, self.dtype = eps, max_len, dtype
        # pool rows a token, and a row's width
        self.groups = kv_heads // 2 if self.paired else kv_heads
        self.row = 2 * head_dim if self.paired else head_dim
        self.serving = SERVING_EXPERTS if self.n_experts else SERVING


def _mixer_shapes(cfg, kind):
    d, di, N, H = cfg.dim, cfg.d_inner, cfg.d_state, cfg.mamba_heads
    if kind == "mamba":
        return {"in_proj": (d, 2 * di + 2 * N + H),
                "conv_w": (cfg.conv_dim, cfg.d_conv),
                "conv_b": (cfg.conv_dim,), "dt_bias": (H,), "A_log": (H,),
                "D": (H,), "norm": (di,), "out_proj": (di, d)}
    return {"wqkv": (d, (cfg.heads + 2 * cfg.kv_heads) * cfg.dh),
            "wo": (cfg.heads * cfg.dh, d)}


def param_shapes(cfg: GraniteHybridConfig):
    d, m = cfg.dim, cfg.mlp_width

    def block(kind):
        blk = {"norm1": (d,), "mixer": _mixer_shapes(cfg, kind),
               "norm2": (d,), "w_gu": (d, 2 * m), "w_down": (m, d)}
        if cfg.n_experts:
            Eh, me = cfg.experts_held[1] - cfg.experts_held[0], \
                cfg.expert_width
            blk["router"] = (d, cfg.n_experts)
            blk["experts"] = {"w_gu": (Eh, d, 2 * me), "w_down": (Eh, me, d)}
        return blk

    return {"embed": (cfg.vocab, d), "norm_f": (d,),
            "blocks": [block(kind) for kind in cfg.kinds]}


def init_params(cfg: GraniteHybridConfig, key) -> Dict[str, Any]:
    """Seeded random weights in `cfg.dtype`: matrices N(0, 1/rows), an
    expert's by its own rows (the tied embedding N(0, 1/vocab): a
    larger one makes every token
    predict itself through the tie), norm gains near 1, the conv uniform
    +-d_conv^-1/2 with a bias near 0, and the Mamba-2 leaves by the
    published initialisers (A uniform in [1, 16], dt bias the inverse
    softplus of a log-uniform step in [1e-3, 1e-1], D = 1)."""
    def leaf(k, name, shp):
        if name == "A_log":
            return jnp.log(jax.random.uniform(k, shp, jnp.float32, 1.0, 16.0))
        if name == "D":
            return jnp.ones(shp, jnp.float32)
        if name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shp) * math.log(100.0)
                         + math.log(1e-3))
            return dt + jnp.log(-jnp.expm1(-dt))
        if name == "conv_w":
            bound = shp[1] ** -0.5
            return jax.random.uniform(k, shp, jnp.float32, -bound, bound)
        n = jax.random.normal(k, shp, jnp.float32)
        if name.startswith("norm"):
            return 1.0 + 0.1 * n
        if len(shp) == 1:
            return 0.1 * n
        return n / math.sqrt(shp[-2])

    return init_tree(param_shapes(cfg), key, cfg.dtype, leaf)


# ---------------------------------------------------------------------
# pieces every mode shares
# ---------------------------------------------------------------------


def _rms(x, w, eps):
    return rms32(x, w, eps).astype(x.dtype)


# the shared MLP, a module global that `_moe` looks up when it is
# traced: the benchmark's planted fault replaces it
_mlp = mlp


def _moe(u32, blk, cfg, valid, kernel):
    """An expert layer's FFN over the float32 normed rows u32 [N, d]
    -> (float32 [N, d], stats int32 [2]): the router over all
    `n_experts`, the experts held here, and the shared MLP. A row's
    result depends on that row alone; a row that is not `valid`
    reaches no expert."""
    from ..parallel.routed_experts import expert_ffn, route

    u = u32.astype(cfg.dtype)
    idx, w = route(u32, blk["router"], None, cfg.top_k,
                   scoring="softmax_topk")
    out, stats = expert_ffn(u, idx, w, blk["experts"], valid,
                            held=cfg.experts_held, kernel=kernel)
    return out + _mlp(u, blk).astype(jnp.float32), stats


_MIXER_SCOPE = {"mamba": "lm_state", "attention": "lm_attention"}


def _stack(params, x, cfg, mixer, valid=None, kernel="gather"):
    """Every layer in its residual form, each part under its device
    scope (`scopes.py`); `mixer(kind, h, p)` is the mode's (it owns
    whatever cache the mode has); `valid` [rows]: the rows that reach
    experts. -> (the final norm's output, and for a config with routed
    experts their stats summed / maxed over the layers, int32 [2]:
    experts reached, the fullest one's rows; else None)."""
    r = cfg.residual_multiplier
    stats = None
    for blk, kind in zip(params["blocks"], cfg.kinds):
        with scope(_MIXER_SCOPE[kind]):
            x = x + r * mixer(kind, _rms(x, blk["norm1"], cfg.eps),
                              blk["mixer"])
        if not cfg.n_experts:
            with scope("lm_mlp"):
                x = x + r * _mlp(_rms(x, blk["norm2"], cfg.eps), blk)
            continue
        with scope("lm_experts"):
            m, st = _moe(rms32(x, blk["norm2"], cfg.eps), blk, cfg, valid,
                         kernel)
            x = x + (r * m).astype(x.dtype)
            stats = st if stats is None else jnp.stack(
                [stats[0] + st[0], jnp.maximum(stats[1], st[1])])
    with scope("lm_head"):
        return _rms(x, params["norm_f"], cfg.eps), stats


def _embed(params, tokens, cfg):
    with scope("lm_embed"):
        return params["embed"][tokens] * cfg.embedding_multiplier


def _head(params, x, cfg):
    with scope("lm_head"):
        return jnp.matmul(
            x, params["embed"].T,
            preferred_element_type=jnp.float32) / cfg.logits_scaling


def _split_in(h, p, cfg):
    """h -> (z [.., di], xBC [.., di + 2N] before the conv, dt [.., H])."""
    zxd = h @ p["in_proj"]
    di, c = cfg.d_inner, cfg.conv_dim
    return zxd[..., :di], zxd[..., di:di + c], zxd[..., di + c:]


def _ssd_inputs(xbc, dt, p, cfg):
    """The conv's output and the raw step -> what the recurrence
    reads, in float32: (x [.., di], dt [.., H], A [H], B, C [.., N])."""
    f32 = jnp.float32
    di, N = cfg.d_inner, cfg.d_state
    xbc = xbc.astype(f32)
    dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
    return (xbc[..., :di], dt, -jnp.exp(p["A_log"].astype(f32)),
            xbc[..., di:di + N], xbc[..., di + N:])


def _heads(v, cfg):  # [.., H] -> [.., di]: a head's value at its channels
    return jnp.repeat(v, cfg.mamba_head_dim, axis=-1)


def _mamba_out(y, x, z, p, cfg):
    """The recurrence's output y (float32) -> the mixer's output: the
    skip D x, the gate, the norm over all di channels, W_out."""
    y = y + _heads(p["D"].astype(jnp.float32), cfg) * x
    y = y * jax.nn.silu(z.astype(jnp.float32))
    return _rms(y, p["norm"], cfg.eps).astype(z.dtype) @ p["out_proj"]


def _split_qkv(h, p, cfg):
    """-> q [.., Hk, rep, dh], k and v [.., Hk, dh]."""
    qkv = h @ p["wqkv"]
    nq, nk = cfg.heads * cfg.dh, cfg.kv_heads * cfg.dh
    lead = h.shape[:-1]
    return (qkv[..., :nq].reshape(lead + (cfg.kv_heads, cfg.rep, cfg.dh)),
            qkv[..., nq:nq + nk].reshape(lead + (cfg.kv_heads, cfg.dh)),
            qkv[..., nq + nk:].reshape(lead + (cfg.kv_heads, cfg.dh)))


def _pairs(kv, cfg):  # [.., Hk, dh] -> [.., groups, row], the pool's rows
    return kv.reshape(kv.shape[:-2] + (cfg.groups, cfg.row))


def _attend(q, k, v, qpos, kpos, cfg):
    """q [Q, Hk, rep, dh] at positions qpos [Q] over k, v [K, Hk, dh]
    at positions kpos [K] -> [Q, Hk * rep * dh]: causal; a key at a
    negative position is nobody's."""
    f32 = jnp.float32
    s = jnp.einsum("qhrd,khd->hrqk", q, k,
                   preferred_element_type=f32) * cfg.attention_multiplier
    ok = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] >= 0)
    prob = jax.nn.softmax(jnp.where(ok[None, None], s, NEG_INF), axis=-1)
    o = jnp.einsum("hrqk,khd->qhrd", prob.astype(v.dtype), v,
                   preferred_element_type=f32).astype(q.dtype)
    return o.reshape(o.shape[0], -1)


# ---------------------------------------------------------------------
# whole sequence, no cache
# ---------------------------------------------------------------------


def forward(params, tokens, cfg: GraniteHybridConfig):
    """tokens [T] -> float32 logits [T, vocab]: the whole sequence at
    once, no cache, no kernel, the recurrence row after row."""
    from ..parallel.ssd_update import ssd_chunk_scan_reference

    pos = jnp.arange(tokens.shape[0])

    def mixer(kind, h, p):
        if kind == "mamba":
            z, xbc, dt = _split_in(h, p, cfg)
            rows = jnp.concatenate(
                [jnp.zeros((cfg.d_conv - 1, cfg.conv_dim), xbc.dtype), xbc])
            x, dt, a, B, C = _ssd_inputs(conv(rows, p), dt, p, cfg)
            s0 = jnp.zeros((cfg.d_state, cfg.d_inner), jnp.float32)
            _, y = ssd_chunk_scan_reference(s0, dt, x, a, B, C)
            return _mamba_out(y, x, z, p, cfg)
        q, k, v = _split_qkv(h, p, cfg)
        return _attend(q, k, v, pos, pos, cfg) @ p["wo"]

    x, _ = _stack(params, _embed(params, tokens, cfg), cfg, mixer,
                  jnp.ones(tokens.shape, bool))
    return _head(params, x, cfg)


# ---------------------------------------------------------------------
# the two caches
# ---------------------------------------------------------------------


def init_cache(cfg: GraniteHybridConfig, num_blocks: int, block_tokens: int,
               slots: int):
    dt = cfg.dtype
    rows = int(block_tokens) * cfg.groups
    return {
        "kv": [kv_pool(num_blocks, rows, cfg.row, dt)
               for k in cfg.kinds if k == "attention"],
        "ssm": [{"s": jnp.zeros((slots, cfg.d_state, cfg.d_inner),
                                jnp.float32),
                 "conv": jnp.zeros((slots, cfg.d_conv - 1, cfg.conv_dim), dt)}
                for k in cfg.kinds if k == "mamba"],
    }


def cache_bytes(cfg: GraniteHybridConfig, block_tokens: int) -> Dict[str, int]:
    """Bytes of one block over all the attention layers' pools (they
    share the table, so an allocated block is one in each), and of one
    slot's state over all the Mamba-2 layers; `call_block` is one
    block of ONE pool, K + V: what a decode attention call moves a
    table entry (its grid step is sized by it,
    `parallel/paged_attention.py`)."""
    item = jnp.dtype(cfg.dtype).itemsize
    kinds = cfg.kinds
    blk = 2 * block_tokens * cfg.kv_heads * cfg.dh * item
    return {"full": kinds.count("attention") * blk, "call_block": blk,
            "state": kinds.count("mamba") * (
                4 * cfg.d_state * cfg.d_inner
                + (cfg.d_conv - 1) * cfg.conv_dim * item)}


# ---------------------------------------------------------------------
# decode: one token a slot
# ---------------------------------------------------------------------


def _place_queries(q, cfg):
    """q [S, Hk, rep, dh] -> [S, Hk/2, 2 rep, 2 dh]: K/V head 2g + j is
    half j of pair g's row, so its `rep` queries sit in that half and
    are zero in the other: one 2 dh-wide product against the row is
    q . k of the query's own head. Unpaired rows take q as it is."""
    if not cfg.paired:
        return q
    S = q.shape[0]
    q = q.reshape(S, cfg.groups, 2, cfg.rep, 1, cfg.dh)
    eye = jnp.eye(2, dtype=q.dtype)[None, None, :, None, :, None]
    return (q * eye).reshape(S, cfg.groups, 2 * cfg.rep, 2 * cfg.dh)


def _own_halves(o, cfg):
    """o [S, Hk/2, 2 rep, 2 dh] (P V over the pair's row, every query)
    -> [S, heads * dh]: of each read, the half that is the query's own
    V head (unpaired: the read as it is)."""
    S = o.shape[0]
    if not cfg.paired:
        return o.reshape(S, cfg.heads * cfg.dh)
    o = o.reshape(S, cfg.groups, 2, cfg.rep, 2, cfg.dh)
    o = jnp.stack([o[:, :, 0, :, 0], o[:, :, 1, :, 1]], axis=2)
    return o.reshape(S, cfg.heads * cfg.dh)


def paged_decode_step(params, token, pos, tables, cache,
                      cfg: GraniteHybridConfig, kernel="gather"):
    """One decode step through the two caches: token [S] at per-row
    positions `pos` [S], `tables` [S, MAXB] -> (float32 logits
    [S, vocab], updated cache) and, for a config with routed experts,
    int32 [2]: experts reached summed over the layers, and the fullest
    expert's rows. A parked row (pos >= MAXB * Bt) writes no K/V,
    leaves its slot's state bit-identical and reaches no expert; its
    logits are garbage nothing reads. With kernel="fused" the attention
    reads and writes, the state updates and the experts' grouped
    products are Pallas kernels (parallel/paged_attention.py: the
    grouped-query decode call with `scale` = attention_multiplier;
    parallel/ssd_update.py; parallel/routed_experts.py); "gather" is
    the same arithmetic in XLA."""
    from ..parallel.paged_attention import (paged_decode_attention,
                                            paged_kv_write)
    from ..parallel.ssd_update import (ssd_state_update,
                                       ssd_state_update_reference)

    paged_kernel_check(kernel)
    S, maxb = tables.shape
    Bt = cache["kv"][0]["k"].shape[1] // cfg.groups
    live = pos < maxb * Bt
    new = {"kv": [], "ssm": []}
    it = {"kv": iter(cache["kv"]), "ssm": iter(cache["ssm"])}
    update = (ssd_state_update if kernel == "fused"
              else ssd_state_update_reference)

    def mixer(kind, h, p):
        if kind == "mamba":
            st = next(it["ssm"])
            z, xbc, dt = _split_in(h, p, cfg)
            rows = jnp.concatenate([st["conv"], xbc[:, None]], axis=1)
            xbc = jax.vmap(lambda r: conv(r, p)[0])(rows)
            x, dt, a, B, C = _ssd_inputs(xbc, dt, p, cfg)
            s, y = update(st["s"], _heads(dt * a, cfg), _heads(dt, cfg) * x,
                          B, C, live)
            new["ssm"].append({"s": s, "conv": jnp.where(
                live[:, None, None], rows[:, 1:], st["conv"])})
            return _mamba_out(y, x, z, p, cfg)
        kv = next(it["kv"])
        q, k, v = _split_qkv(h, p, cfg)
        k, v = _pairs(k, cfg), _pairs(v, cfg)
        if kernel == "fused":
            kv = dict(zip("kv", paged_kv_write(kv["k"], kv["v"], k, v,
                                               tables, pos)))
            o = _own_halves(paged_decode_attention(
                _place_queries(q, cfg), kv["k"], kv["v"], tables, pos,
                scale=cfg.attention_multiplier), cfg)
        else:
            kv = {"k": scatter_rows(kv["k"], tables, pos, k, cfg, Bt),
                  "v": scatter_rows(kv["v"], tables, pos, v, cfg, Bt)}
            kpos = jnp.arange(maxb * Bt)
            o = jax.vmap(
                lambda q1, k1, v1, p1: _attend(q1[None], k1, v1, p1[None],
                                               kpos, cfg)[0]
            )(q, head_view(kv["k"], tables, cfg, Bt),
              head_view(kv["v"], tables, cfg, Bt), pos)
        new["kv"].append(kv)
        return o @ p["wo"]

    x, stats = _stack(params, _embed(params, token, cfg), cfg, mixer, live,
                      kernel)
    out = (_head(params, x, cfg), new)
    return out if stats is None else out + (stats,)


# ---------------------------------------------------------------------
# prefill: a chunk of one slot
# ---------------------------------------------------------------------


def paged_prefill_chunk(params, cache, chunk, start_pos, table_rows,
                        cfg: GraniteHybridConfig, true_len=None,
                        kernel="gather"):
    """Extend ONE slot by a [C]-token chunk whose first row sits at
    `start_pos` -> (float32 logits of row true_len - 1 [vocab],
    updated cache). `table_rows` [2, MAXB]: the slot's row of the
    block table, and a row whose first entry is the slot's index. Rows
    past `true_len` pad the bucket: they do not advance the state
    (dt = 0: a row that changes nothing), which leaves the chunk as
    the state after row true_len - 1, carried from where the last
    chunk left it, as the conv's last d_conv - 1 input rows are; nor do
    they reach an expert.

    The mixers are XLA in either `kernel`: the recurrence in its
    blocked matrix form (`ssd_chunk_scan`, cfg.chunk rows a block), and
    the attention over the slot's span gathered through the table after
    the chunk's own rows are written (`chunk_attend`), whatever
    position the chunk starts at. The experts' grouped products are the
    decode step's."""
    from ..parallel.ssd_update import ssd_chunk_scan

    paged_kernel_check(kernel)
    (C,) = chunk.shape
    tab, slot = table_rows[0], table_rows[1, 0]
    maxb = tab.shape[0]
    Bt = cache["kv"][0]["k"].shape[1] // cfg.groups
    if true_len is None:
        true_len = C
    offs = jnp.arange(C)
    valid = offs < true_len
    wpos = jnp.where(valid, start_pos + offs, jnp.int32(maxb * Bt))
    new = {"kv": [], "ssm": []}
    it = {"kv": iter(cache["kv"]), "ssm": iter(cache["ssm"])}

    def mixer(kind, h, p):
        if kind == "mamba":
            st = next(it["ssm"])
            z, xbc, dt = _split_in(h, p, cfg)
            rows = jnp.concatenate([st["conv"][slot], xbc])
            x, dt, a, B, C_ = _ssd_inputs(conv(rows, p), dt, p, cfg)
            dt = jnp.where(valid[:, None], dt, 0.0)
            s, y = ssd_chunk_scan(st["s"][slot], dt, x, a, B, C_,
                                  block=cfg.chunk)
            # the conv's next window: the d_conv - 1 rows up to true_len
            tail = jax.lax.dynamic_slice_in_dim(rows, true_len,
                                                cfg.d_conv - 1)
            new["ssm"].append({"s": st["s"].at[slot].set(s),
                               "conv": st["conv"].at[slot].set(tail)})
            return _mamba_out(y, x, z, p, cfg)
        kv = next(it["kv"])
        q, k, v = _split_qkv(h, p, cfg)
        kv = {"k": scatter_chunk(kv["k"], tab, start_pos, wpos, true_len,
                                 _pairs(k, cfg), cfg, Bt),
              "v": scatter_chunk(kv["v"], tab, start_pos, wpos, true_len,
                                 _pairs(v, cfg), cfg, Bt)}
        new["kv"].append(kv)
        o = chunk_attend(q, head_view(kv["k"], tab, cfg, Bt),
                         head_view(kv["v"], tab, cfg, Bt), start_pos, cfg)
        return o @ p["wo"]

    x, _ = _stack(params, _embed(params, chunk, cfg), cfg, mixer, valid,
                  kernel)
    with scope("lm_head"):
        xl = jax.lax.dynamic_index_in_dim(x, true_len - 1, axis=0,
                                          keepdims=False)
    return _head(params, xl, cfg), new


class _Serving(Seam):
    """The pools on the engine's one table and per-slot recurrent
    state, no window tables; refused as the SambaY family refuses
    (ROADMAP B.I.5 keeps the snapshots)."""
    name = "granite_hybrid"
    caches = ("paged", "state")
    refusal = "it keeps recurrent state beside its K/V blocks"
    cache_bytes = staticmethod(cache_bytes)
    reset_slot_state = staticmethod(reset_slot_state)
    decode = staticmethod(paged_decode_step)
    prefill = staticmethod(paged_prefill_chunk)
    cache = staticmethod(init_cache)


class _ExpertServing(_Serving):
    """The seam of a config with routed experts: the same caches and
    refusals, and the decode step hands the engine its `step_counters`
    beside the logits; they ride the step's one packed result."""
    step_counters = ("moe_experts_hit", "moe_rows_max")


SERVING = _Serving()
SERVING_EXPERTS = _ExpertServing()
