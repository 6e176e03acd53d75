"""SambaY: a decoder-hybrid-decoder language model (ISSUE 27).

The block family of Phi-4-mini-flash-reasoning (arXiv:2507.06607):
pre-LayerNorm residual layers whose mixer is one of five kinds, a
SwiGLU MLP in every layer, a tied head, and no positional encoding of
any kind. By `layer_kinds(layers)`, for 32 layers:

  mamba   layers 0..16 even: Mamba-1 (d_state 16, d_conv 4, dt_rank
          dim/16, d_inner 2 dim); layer 16 also hands its scan output
          y_t (before the gate) up as the memory M_t
  window  layers 1..15 odd: differential attention over the last
          `window` positions
  full    layer 17: differential attention, full causal; its K/V are
          the ones every `cross` layer reads
  gmu     layers 18..30 even: gated memory unit (M_t * silu(h W_1)) W_2
  cross   layers 19..31 odd: a query only, onto layer 17's K/V

Differential attention (2H query heads, H key and H value heads of
width dh): pair p = 0..H-1 belongs to K/V group g = p // 2; with
j in {0, 1}, P_{p,j} = softmax(q_{2p+j} . k_{2g+j} / sqrt(dh)),
V_g = [v_{2g}; v_{2g+1}], a_p = P_{p,0} V_g - lambda P_{p,1} V_g,
o_p = RMSNorm(a_p) (1 - lambda_init).

One stack (`_stack`) runs every mode; a mode is the `mixer` it hands
the stack, dispatched on the layer's kind:

  forward               whole sequence, no cache (training-free oracle)
  paged_decode_step     one token a slot through the three caches
  paged_prefill_chunk   a [C]-token chunk of ONE slot through them

The three caches (`init_cache`), all served by ServingEngine through
`SERVING` (the seam `models/transformer.py` fills for the GPT block):

  full    ONE paged pool, the full layer's, which the cross layers
          read too: {"k", "v"} [NB, Bt * H/2, 2 dh]
  window  a paged pool a window layer, on one shared table whose
          entries behind the window the engine frees:
          [NBw, Bt * H/2, 2 dh]
  ssm     per-slot state, no position axis: the scan state "s"
          [S, N, di] float32 and the last d_conv - 1 rows of the
          conv's input "conv" [S, d_conv - 1, di]

A pool row holds one token's K (or V) heads of one group side by
side, 2 dh wide, and a block's rows are (token, group): 3-D on
purpose. The device tiles an array's two minor dimensions (16 x 128
for bf16): `[.., 20, 64]` would pad to `[.., 32, 128]`, 3.2 times the
bytes, where `[Bt * 10, 128]` is dense. The state is [N, di] for the
same reason (parallel/ssm_update.py).
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .parts import (NEG_INF, Seam, conv, embed, init_tree, kv_pool, mlp,
                    paged_kernel_check, pool_view, reset_slot_state,
                    scatter_chunk, scatter_rows)
from .scopes import scope

__all__ = ["SambaYConfig", "layer_kinds", "init_params", "forward",
           "init_cache", "paged_decode_step", "paged_prefill_chunk",
           "reset_slot_state", "SERVING"]


def layer_kinds(layers: int):
    """The mixer of each layer, from the depth alone: the first half
    alternates mamba / window, the second half opens with the memory's
    Mamba layer and the one full-attention layer, then alternates
    gmu / cross."""
    if layers < 4 or layers % 4:
        raise ValueError("layers must be a multiple of 4 (got %d)" % layers)
    half = layers // 2
    return tuple(
        ("mamba" if l % 2 == 0 else "window") if l <= half
        else "full" if l == half + 1
        else ("gmu" if l % 2 == 0 else "cross")
        for l in range(layers))


class SambaYConfig:
    def __init__(self, vocab=256, dim=64, heads=8, kv_heads=4, layers=4,
                 mlp_mult=4, window=512, max_len=1024, dtype=jnp.float32,
                 d_state=16, d_conv=4):
        if heads != 2 * kv_heads or kv_heads % 2 or dim % heads:
            raise ValueError(
                "differential attention pairs two query heads a K/V head "
                "and two K/V heads a value group: heads == 2 * kv_heads, "
                "kv_heads even, dim %% heads == 0 (got %d, %d, %d)"
                % (heads, kv_heads, dim))
        self.vocab, self.dim, self.heads = vocab, dim, heads
        self.kv_heads, self.layers, self.mlp_mult = kv_heads, layers, mlp_mult
        self.window, self.max_len, self.dtype = window, max_len, dtype
        self.d_state, self.d_conv = d_state, d_conv
        self.kinds = layer_kinds(layers)
        self.dh = dim // heads
        self.groups = kv_heads // 2     # value groups, 2 dh wide
        self.d_inner = 2 * dim
        self.dt_rank = dim // 16
        self.serving = SERVING

    def lambda_init(self, l: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * l)


def _mixer_shapes(cfg, kind):
    d, di, dh = cfg.dim, cfg.d_inner, cfg.dh
    R, N = cfg.dt_rank, cfg.d_state
    lam = {"lam_q1": (dh,), "lam_k1": (dh,), "lam_q2": (dh,),
           "lam_k2": (dh,), "subln": (2 * dh,)}
    if kind == "mamba":
        return {"in_proj": (d, 2 * di), "conv_w": (di, cfg.d_conv),
                "conv_b": (di,), "x_proj": (di, R + 2 * N),
                "dt_proj": (R, di), "dt_bias": (di,), "A_log": (di, N),
                "D": (di,), "out_proj": (di, d)}
    if kind == "gmu":
        return {"w1": (d, di), "w2": (di, d)}
    if kind == "cross":
        return dict(lam, wq=(d, d), bq=(d,), wo=(d, d), bo=(d,))
    n = (cfg.heads + 2 * cfg.kv_heads) * dh
    return dict(lam, wqkv=(d, n), bqkv=(n,), wo=(d, d), bo=(d,))


def init_params(cfg: SambaYConfig, key) -> Dict[str, Any]:
    """Seeded random weights in `cfg.dtype`: matrices N(0, 1/fan_in),
    gains near 1, biases near 0, the Mamba and lambda leaves by the
    family's published initialisers (A_log = log 1..N, dt bias the
    inverse softplus of a log-uniform step in [1e-3, 1e-1], D = 1)."""
    d, m = cfg.dim, cfg.mlp_mult * cfg.dim
    ln = {"g": (d,), "b": (d,)}
    shapes = {"embed": (cfg.vocab, d), "ln_f": ln, "blocks": [
        {"ln1": ln, "mixer": _mixer_shapes(cfg, kind), "ln2": ln,
         "w_gu": (d, 2 * m), "w_down": (m, d)} for kind in cfg.kinds]}
    def leaf(k, name, shp):
        if name == "A_log":
            return jnp.broadcast_to(
                jnp.log(jnp.arange(1, shp[1] + 1, dtype=jnp.float32)), shp)
        if name == "D":
            return jnp.ones(shp, jnp.float32)
        if name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shp) * math.log(100.0)
                         + math.log(1e-3))
            return dt + jnp.log(-jnp.expm1(-dt))
        if name in ("dt_proj", "conv_w"):
            bound = (shp[0] if name == "dt_proj" else shp[1]) ** -0.5
            return jax.random.uniform(k, shp, jnp.float32, -bound, bound)
        n = jax.random.normal(k, shp, jnp.float32)
        if name in ("g", "subln"):
            return 1.0 + 0.1 * n
        if len(shp) == 1:
            return 0.1 * n
        return n / math.sqrt(shp[0])

    return init_tree(shapes, key, cfg.dtype, leaf)


# ---------------------------------------------------------------------
# pieces every mode shares
# ---------------------------------------------------------------------


def _ln(x, p):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
    return (y * p["g"].astype(jnp.float32)
            + p["b"].astype(jnp.float32)).astype(x.dtype)


def _stack(params, x, cfg, mixer):
    """Every layer in its residual form, each part under its device
    scope (`scopes.py`: the Mamba layers and the gated memory units
    are `lm_state`, every kind of attention `lm_attention`);
    `mixer(l, kind, h, p)` is the mode's (it owns whatever cache the
    mode has)."""
    for l, (blk, kind) in enumerate(zip(params["blocks"], cfg.kinds)):
        with scope("lm_state" if kind in ("mamba", "gmu")
                   else "lm_attention"):
            x = x + mixer(l, kind, _ln(x, blk["ln1"]), blk["mixer"])
        with scope("lm_mlp"):
            x = x + mlp(_ln(x, blk["ln2"]), blk)
    with scope("lm_head"):
        return _ln(x, params["ln_f"])


def _head(params, x):
    with scope("lm_head"):
        return x @ params["embed"].T


def _place_queries(q, cfg):
    """q [..., 2H * dh] -> [..., groups, 4, 2 dh]: query head h = 4g + r
    scores against key head 2g + (r % 2), which is half r % 2 of group
    g's row: the query sits in that half and is zero in the other, so
    one 2 dh-wide product against the group's row is q_h . k_{2g+j}."""
    lead = q.shape[:-1]
    q = q.reshape(lead + (cfg.kv_heads, 2, cfg.dh))  # (pair, j, dh)
    eye = jnp.eye(2, dtype=q.dtype)
    q = q[..., :, None, :] * eye[:, :, None]  # (pair, j, half, dh)
    return q.reshape(lead + (cfg.groups, 4, 2 * cfg.dh))


def _diff_combine(o, p, l, cfg):
    """o [..., groups, 4, 2 dh] (P_h V_g of every query head) ->
    [..., dim]: a_p = o_{2p} - lambda o_{2p+1}, RMSNorm over 2 dh,
    (1 - lambda_init), pairs side by side."""
    f32 = jnp.float32
    lam0 = cfg.lambda_init(l)
    lam = (jnp.exp(jnp.sum(p["lam_q1"].astype(f32) * p["lam_k1"].astype(f32)))
           - jnp.exp(jnp.sum(p["lam_q2"].astype(f32)
                             * p["lam_k2"].astype(f32))) + lam0)
    lead = o.shape[:-3]
    o = o.astype(f32).reshape(lead + (cfg.kv_heads, 2, 2 * cfg.dh))
    a = o[..., 0, :] - lam * o[..., 1, :]
    a = a * jax.lax.rsqrt((a * a).mean(-1, keepdims=True) + 1e-5)
    a = a * p["subln"].astype(f32) * (1.0 - lam0)
    return a.reshape(lead + (cfg.dim,))


def _attend(q, k, v, qpos, kpos, window, cfg):
    """q [Q, groups, 4, D] at positions qpos [Q] over k, v
    [K, groups, D] at positions kpos [K] -> [Q, groups, 4, D]: causal,
    banded where `window`; a key at a negative position is nobody's."""
    f32 = jnp.float32
    s = jnp.einsum("qgrd,kgd->grqk", q, k,
                   preferred_element_type=f32) / math.sqrt(cfg.dh)
    ok = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] >= 0)
    if window:
        ok = ok & (kpos[None, :] > qpos[:, None] - window)
    prob = jax.nn.softmax(jnp.where(ok[None, None], s, NEG_INF), axis=-1)
    return jnp.einsum("grqk,kgd->qgrd", prob.astype(v.dtype), v,
                      preferred_element_type=f32).astype(q.dtype)


def _mamba_out(y, u, z, p, carry):
    """The scan's output y (float32) -> the mixer's output; y + D u is
    also the memory the gated memory units read (the LAST Mamba
    layer's is the one still in `carry` when they run)."""
    y = (y + p["D"].astype(jnp.float32) * u).astype(z.dtype)
    carry["mem"] = y
    return (y * jax.nn.silu(z)) @ p["out_proj"]


def _gmu(h, p, carry):
    return (carry["mem"] * jax.nn.silu(h @ p["w1"])) @ p["w2"]


def _attn_out(o, p, l, cfg, dtype):
    return _diff_combine(o, p, l, cfg).astype(dtype) @ p["wo"] + p["bo"]


def _split_qkv(h, p, cfg):
    """-> (q placed [..., groups, 4, 2 dh], k and v [..., groups, 2 dh])."""
    qkv = h @ p["wqkv"] + p["bqkv"]
    nq, nk = cfg.heads * cfg.dh, cfg.kv_heads * cfg.dh
    lead = h.shape[:-1]
    return (_place_queries(qkv[..., :nq], cfg),
            qkv[..., nq:nq + nk].reshape(lead + (cfg.groups, 2 * cfg.dh)),
            qkv[..., nq + nk:].reshape(lead + (cfg.groups, 2 * cfg.dh)))


def _mamba_inputs(u, p, cfg):
    """The conv's output u [..., di] -> what the recurrence reads, in
    float32: (u' , delta, B, C)."""
    f32 = jnp.float32
    R, N = cfg.dt_rank, cfg.d_state
    xp = u @ p["x_proj"]
    delta = jax.nn.softplus(
        (xp[..., :R] @ p["dt_proj"]).astype(f32) + p["dt_bias"].astype(f32))
    return (u.astype(f32), delta, xp[..., R:R + N].astype(f32),
            xp[..., R + N:].astype(f32))


# ---------------------------------------------------------------------
# whole sequence, no cache
# ---------------------------------------------------------------------


def forward(params, tokens, cfg: SambaYConfig):
    """tokens [T] -> logits [T, vocab]: the whole sequence at once, no
    cache and no kernel (the oracle of the cached modes)."""
    from ..parallel.ssm_update import ssm_chunk_scan_reference

    T = tokens.shape[0]
    pos = jnp.arange(T)
    carry = {}

    def mixer(l, kind, h, p):
        if kind == "mamba":
            uz = h @ p["in_proj"]
            u, z = uz[:, :cfg.d_inner], uz[:, cfg.d_inner:]
            rows = jnp.concatenate(
                [jnp.zeros((cfg.d_conv - 1, cfg.d_inner), u.dtype), u])
            u, delta, B, C = _mamba_inputs(conv(rows, p), p, cfg)
            a_t = -jnp.exp(p["A_log"].astype(jnp.float32)).T
            _, y = ssm_chunk_scan_reference(jnp.zeros_like(a_t), delta,
                                            delta * u, a_t, B, C)
            return _mamba_out(y, u, z, p, carry)
        if kind == "gmu":
            return _gmu(h, p, carry)
        if kind == "cross":
            q = _place_queries(h @ p["wq"] + p["bq"], cfg)
            k, v = carry["kv"]
        else:
            q, k, v = _split_qkv(h, p, cfg)
            if kind == "full":
                carry["kv"] = (k, v)
        o = _attend(q, k, v, pos, pos,
                    cfg.window if kind == "window" else 0, cfg)
        return _attn_out(o, p, l, cfg, h.dtype)

    x = _stack(params, embed(params, tokens), cfg, mixer)
    return _head(params, x)


# ---------------------------------------------------------------------
# the three caches
# ---------------------------------------------------------------------


def init_cache(cfg: SambaYConfig, num_blocks: int, block_tokens: int,
               slots: int, window_blocks: int):
    dt = cfg.dtype
    rows, D = int(block_tokens) * cfg.groups, 2 * cfg.dh
    return {
        "full": kv_pool(num_blocks, rows, D, dt),
        "window": [kv_pool(window_blocks, rows, D, dt)
                   for k in cfg.kinds if k == "window"],
        "ssm": [{"s": jnp.zeros((slots, cfg.d_state, cfg.d_inner),
                                jnp.float32),
                 "conv": jnp.zeros((slots, cfg.d_conv - 1, cfg.d_inner), dt)}
                for k in cfg.kinds if k == "mamba"],
    }


def cache_bytes(cfg: SambaYConfig, block_tokens: int) -> Dict[str, int]:
    """Bytes of one block of the full pool, of one block over all the
    window pools, and of one slot's state over all the Mamba layers;
    `call_block` is one block of ONE pool, K + V: what a decode
    attention call moves a table entry (its grid step is sized by it,
    `parallel/paged_attention.py`)."""
    item = jnp.dtype(cfg.dtype).itemsize
    blk = 2 * block_tokens * cfg.kv_heads * cfg.dh * item
    n_m = sum(k == "mamba" for k in cfg.kinds)
    return {"full": blk, "call_block": blk,
            "window": blk * sum(k == "window" for k in cfg.kinds),
            "state": n_m * cfg.d_inner * (4 * cfg.d_state
                                          + (cfg.d_conv - 1) * item)}


# ---------------------------------------------------------------------
# decode: one token a slot
# ---------------------------------------------------------------------


def paged_decode_step(params, token, pos, tables, cache, cfg: SambaYConfig,
                      kernel="gather"):
    """One decode step through the three caches: token [S] at per-row
    positions `pos` [S], `tables` [2, S, MAXB] (the full layer's, the
    window layers') -> (logits [S, vocab], updated cache). A parked
    row (pos >= MAXB * Bt) writes no K/V and leaves its slot's state
    bit-identical; its logits are garbage nothing reads. With
    kernel="fused" the 16 attention reads and the nine state updates
    are Pallas kernels (parallel/paged_attention.py: a window call
    walks only the window's blocks, a cross call writes nothing;
    parallel/ssm_update.py); "gather" is the same arithmetic in XLA."""
    from ..parallel.paged_attention import (paged_decode_attention,
                                            paged_kv_write)
    from ..parallel.ssm_update import (ssm_state_update,
                                       ssm_state_update_reference)

    paged_kernel_check(kernel)
    ftab, wtab = tables[0], tables[1]
    S, maxb = ftab.shape
    Bt = cache["full"]["k"].shape[1] // cfg.groups
    live = pos < maxb * Bt
    first = jnp.maximum(pos - cfg.window + 1, 0)
    new = {"full": cache["full"], "window": [], "ssm": []}
    it = {"window": iter(cache["window"]), "ssm": iter(cache["ssm"])}
    carry = {}
    scale = 1.0 / math.sqrt(cfg.dh)

    def attend(q, kv, tab, lo):
        """`lo`: the first position attended (a window layer's), or
        None for all of the context."""
        w = cfg.window if lo is not None else 0
        if kernel == "fused":
            return paged_decode_attention(
                q, kv["k"], kv["v"], tab, pos, first=lo, scale=scale)
        kpos = jnp.arange(maxb * Bt)
        return jax.vmap(
            lambda q1, k1, v1, p1: _attend(q1[None], k1, v1, p1[None],
                                           kpos, w, cfg)[0]
        )(q, pool_view(kv["k"], tab, cfg, Bt),
          pool_view(kv["v"], tab, cfg, Bt), pos)

    def mixer(l, kind, h, p):
        if kind == "mamba":
            st = next(it["ssm"])
            uz = h @ p["in_proj"]
            u, z = uz[:, :cfg.d_inner], uz[:, cfg.d_inner:]
            rows = jnp.concatenate([st["conv"], u[:, None]], axis=1)
            u = jax.vmap(lambda r: conv(r, p)[0])(rows)
            u, delta, B, C = _mamba_inputs(u, p, cfg)
            a_t = -jnp.exp(p["A_log"].astype(jnp.float32)).T
            update = (ssm_state_update if kernel == "fused"
                      else ssm_state_update_reference)
            s, y = update(st["s"], delta, delta * u, a_t, B, C, live)
            new["ssm"].append({"s": s, "conv": jnp.where(
                live[:, None, None], rows[:, 1:], st["conv"])})
            return _mamba_out(y, u, z, p, carry)
        if kind == "gmu":
            return _gmu(h, p, carry)
        if kind == "cross":
            q = _place_queries(h @ p["wq"] + p["bq"], cfg)
            o = attend(q, new["full"], ftab, None)
        else:
            q, k, v = _split_qkv(h, p, cfg)
            tab = wtab if kind == "window" else ftab
            kv = next(it["window"]) if kind == "window" else cache["full"]
            if kernel == "fused":
                kv = dict(zip("kv", paged_kv_write(kv["k"], kv["v"], k, v,
                                                   tab, pos)))
            else:
                kv = {"k": scatter_rows(kv["k"], tab, pos, k, cfg, Bt),
                      "v": scatter_rows(kv["v"], tab, pos, v, cfg, Bt)}
            if kind == "window":
                new["window"].append(kv)
                o = attend(q, kv, tab, first)
            else:
                new["full"] = kv
                o = attend(q, kv, tab, None)
        return _attn_out(o, p, l, cfg, h.dtype)

    x = _stack(params, embed(params, token), cfg, mixer)
    return _head(params, x), new


# ---------------------------------------------------------------------
# prefill: a chunk of one slot
# ---------------------------------------------------------------------


def _tiled(fn, C, tile):
    """fn(first row of a tile) -> that tile's rows, over the C rows of
    a chunk, one tile live at a time."""
    tile = min(tile, C)
    out = jax.lax.map(fn, jnp.arange(C // tile) * tile)
    return out.reshape((C,) + out.shape[2:])


def paged_prefill_chunk(params, cache, chunk, start_pos, table_rows,
                        cfg: SambaYConfig, true_len=None, kernel="gather"):
    """Extend ONE slot by a [C]-token chunk whose first row sits at
    `start_pos` -> (logits of row true_len - 1 [vocab], updated cache).
    `table_rows` [4, MAXB]: the slot's row of the full table; of the
    window table as it stood before this chunk (read: the window
    behind the chunk); of the window table as it stands after it
    (written: what the positions after the chunk will still see); and
    a row whose first entry is the slot's index. Rows past `true_len`
    pad the bucket: they do not advance the state (delta = 0: a scan
    row that changes nothing), which leaves the chunk as the state
    after row true_len - 1, carried from where the last chunk left it.
    With kernel="fused" the scan is a Pallas kernel
    (parallel/ssm_update.py); "gather" is `lax.scan` of the same row.

    The attention here is XLA's in either `kernel`, queries a tile at
    a time so that the score temporaries stay bounded whatever the
    bucket. A chunk that starts at position 0 (a whole prompt, or its
    first chunk) attends its own rows and nothing cached: the full and
    cross layers' tile i then reads keys 0 .. (i + 1) x 512 of the
    chunk itself, no gathered view and no masked half; a later chunk
    reads 256 rows at a time against the slot's whole table span. A
    window layer reads 512 rows against their own 512 + window keys,
    the window behind the chunk gathered through the table (nothing,
    at position 0)."""
    from ..parallel.ssm_update import (ssm_chunk_scan,
                                       ssm_chunk_scan_reference)

    paged_kernel_check(kernel)
    scan = ssm_chunk_scan if kernel == "fused" else ssm_chunk_scan_reference
    (C,) = chunk.shape
    ftab, wread, wwrite = table_rows[0], table_rows[1], table_rows[2]
    slot = table_rows[3, 0]
    maxb = ftab.shape[0]
    Bt = cache["full"]["k"].shape[1] // cfg.groups
    W = cfg.window
    if true_len is None:
        true_len = C
    offs = jnp.arange(C)
    valid = offs < true_len
    positions = start_pos + offs
    wpos = jnp.where(valid, positions, jnp.int32(maxb * Bt))
    new = {"full": cache["full"], "window": [], "ssm": []}
    it = {"window": iter(cache["window"]), "ssm": iter(cache["ssm"])}
    carry = {}

    def full_attend(q):
        def from_zero(q):
            k, v = carry["kv"]  # the chunk's own rows
            tile = min(512, C)
            return jnp.concatenate([
                _attend(q[i:i + tile], k[:i + tile], v[:i + tile],
                        i + jnp.arange(tile), jnp.arange(i + tile), 0, cfg)
                for i in range(0, C, tile)])

        def later(q):
            # one gathered view of the slot's span, through the table
            k = pool_view(new["full"]["k"], ftab, cfg, Bt)
            v = pool_view(new["full"]["v"], ftab, cfg, Bt)
            kpos = jnp.arange(maxb * Bt)
            return _tiled(lambda t: _attend(
                jax.lax.dynamic_slice_in_dim(q, t, min(256, C)), k, v,
                start_pos + t + jnp.arange(min(256, C)), kpos, 0, cfg),
                C, 256)

        return jax.lax.cond(start_pos == 0, from_zero, later, q)

    def window_attend(q, k, v, kv):
        def behind(pool):
            # the W positions behind the chunk, through the table as
            # it was before this chunk's release
            back = start_pos - W + jnp.arange(W)
            blk = jnp.clip(wread[jnp.clip(back // Bt, 0, maxb - 1)], 0,
                           pool.shape[0] - 1)
            row = (back % Bt)[:, None] * cfg.groups + jnp.arange(cfg.groups)
            return pool[blk[:, None], row]

        def nothing(pool):
            return jnp.zeros((W,) + k.shape[1:], pool.dtype)

        k = jnp.concatenate([jax.lax.cond(start_pos == 0, nothing, behind,
                                          kv["k"]).astype(k.dtype), k])
        v = jnp.concatenate([jax.lax.cond(start_pos == 0, nothing, behind,
                                          kv["v"]).astype(v.dtype), v])
        tile = min(512, C)

        def one(t):
            # key i of the concatenation sits at position start - W + i
            ks = jax.lax.dynamic_slice_in_dim(k, t, tile + W)
            vs = jax.lax.dynamic_slice_in_dim(v, t, tile + W)
            return _attend(jax.lax.dynamic_slice_in_dim(q, t, tile), ks, vs,
                           start_pos + t + jnp.arange(tile),
                           start_pos - W + t + jnp.arange(tile + W), W, cfg)

        return _tiled(one, C, tile)

    def mixer(l, kind, h, p):
        if kind == "mamba":
            st = next(it["ssm"])
            uz = h @ p["in_proj"]
            u_in, z = uz[:, :cfg.d_inner], uz[:, cfg.d_inner:]
            rows = jnp.concatenate([st["conv"][slot], u_in])
            u, delta, B, C_ = _mamba_inputs(conv(rows, p), p, cfg)
            delta = jnp.where(valid[:, None], delta, 0.0)
            a_t = -jnp.exp(p["A_log"].astype(jnp.float32)).T
            s, y = scan(st["s"][slot], delta, delta * u, a_t, B, C_)
            # the conv's next window: the d_conv - 1 rows up to true_len
            tail = jax.lax.dynamic_slice_in_dim(rows, true_len,
                                                cfg.d_conv - 1)
            new["ssm"].append({"s": st["s"].at[slot].set(s),
                               "conv": st["conv"].at[slot].set(tail)})
            return _mamba_out(y, u, z, p, carry)
        if kind == "gmu":
            return _gmu(h, p, carry)
        if kind == "cross":
            o = full_attend(_place_queries(h @ p["wq"] + p["bq"], cfg))
        else:
            q, k, v = _split_qkv(h, p, cfg)
            if kind == "window":
                kv = next(it["window"])
                o = window_attend(q, k, v, kv)
                new["window"].append({
                    "k": scatter_chunk(kv["k"], wwrite, start_pos, wpos,
                                       true_len, k, cfg, Bt),
                    "v": scatter_chunk(kv["v"], wwrite, start_pos, wpos,
                                       true_len, v, cfg, Bt)})
            else:
                kv = cache["full"]
                kv = {"k": scatter_chunk(kv["k"], ftab, start_pos, wpos,
                                         true_len, k, cfg, Bt),
                      "v": scatter_chunk(kv["v"], ftab, start_pos, wpos,
                                         true_len, v, cfg, Bt)}
                new["full"] = kv
                carry["kv"] = (k, v)  # read by the cross layers above too
                o = full_attend(q)
        return _attn_out(o, p, l, cfg, h.dtype)

    x = _stack(params, embed(params, chunk), cfg, mixer)
    with scope("lm_head"):
        xl = jax.lax.dynamic_index_in_dim(x, true_len - 1, axis=0,
                                          keepdims=False)
    return _head(params, xl), new


class _Serving(Seam):
    """Recurrent state, which block aliasing cannot restore, beside the
    pools: what re-uses or re-plays cached blocks is refused (ROADMAP
    B.I.5 keeps the snapshots). The window's first position is read
    off the device's `pos` band, so the engine runs this family one
    step ahead of the host like the GPT block."""
    name = "sambay"
    caches = ("paged", "window", "state")
    refusal = "it keeps recurrent state beside its K/V blocks"
    cache_bytes = staticmethod(cache_bytes)
    reset_slot_state = staticmethod(reset_slot_state)
    decode = staticmethod(paged_decode_step)
    prefill = staticmethod(paged_prefill_chunk)

    @staticmethod
    def cache(cfg, num_blocks, block_tokens, slots):
        per_slot = -(-cfg.window // int(block_tokens)) + 1
        return init_cache(cfg, num_blocks, block_tokens, slots,
                          slots * per_slot)


SERVING = _Serving()
