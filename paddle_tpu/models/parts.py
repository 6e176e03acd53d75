"""The parts the serving model families share, and their seam's base.

A family (`transformer.py`'s GPT block, `sambay.py`,
`granite_hybrid.py`, `afmoe.py`, `mla_moe.py`) is its own mixers,
caches and steps over these parts, and imports nothing from another
family. What runs inside a compiled step is moved here verbatim: the
operation order is what `tests/test_tpu_aot_compile.py`'s digests pin.
`Seam` is what ServingEngine asks a family for.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..parallel.kernel_utils import NEG_INF
from .scopes import scope

__all__ = ["NEG_INF", "param_count", "init_tree", "paged_kernel_check",
           "phys_rows", "rms32", "mlp", "conv", "reset_slot_state",
           "embed", "untied_head", "kv_pool", "scatter_rows",
           "scatter_chunk", "pool_view", "head_view", "chunk_attend",
           "moe_ffn", "expert_stack", "Seam"]


def param_count(shapes) -> int:
    """Parameters of a tree of shape tuples (a family's
    `param_shapes(cfg)`), from shapes alone."""
    return sum(math.prod(s) for s in jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))


def init_tree(shapes, key, dtype, leaf):
    """Seeded weights in `dtype` for a tree of shape tuples: the i-th
    leaf is `leaf(fold_in(key, i), name, shape)`, drawn in float32 by
    its name (the last key of its path)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    return jax.tree_util.tree_unflatten(treedef, [
        leaf(jax.random.fold_in(key, i), str(getattr(path[-1], "key", "w")),
             shp).astype(dtype)
        for i, (path, shp) in enumerate(flat)])


def paged_kernel_check(kernel: str):
    if kernel not in ("gather", "fused"):
        raise ValueError(
            "paged kernel must be 'gather' or 'fused' (got %r)"
            % (kernel,))


def phys_rows(tables, wpos, NB, Bt):
    """Map global write positions to (physical block, in-block offset).
    A position past the table span (the engine parks dead/padded rows
    at MAXB*Bt, the paged analogue of the slab's position-L trick) or
    landing on an unallocated (-1) entry resolves to block NB — out of
    range, so the scatter DROPS the write."""
    maxb = tables.shape[-1]
    bi = wpos // Bt
    safe = jnp.clip(bi, 0, maxb - 1)
    if tables.ndim == 1:
        phys = tables[safe]
    elif safe.ndim == tables.ndim:
        phys = jnp.take_along_axis(tables, safe, axis=-1)
    else:  # one position per table row (the decode step's [S] case)
        phys = jnp.take_along_axis(tables, safe[..., None], axis=-1)[..., 0]
    phys = jnp.where((bi < maxb) & (phys >= 0), phys, jnp.int32(NB))
    return phys, wpos % Bt


def rms32(x, w, eps):  # float32, before any rounding
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
            * w.astype(jnp.float32))


def mlp(h, blk):
    gu = h @ blk["w_gu"]
    m = gu.shape[-1] // 2
    return (jax.nn.silu(gu[..., :m]) * gu[..., m:]) @ blk["w_down"]


def conv(window_rows, p):
    """Causal depthwise conv over the rows [T + d_conv - 1, di] (the
    d_conv - 1 rows before the first one in front) -> silu, [T, di]."""
    K = p["conv_w"].shape[1]
    T = window_rows.shape[0] - (K - 1)
    acc = sum(window_rows[i:i + T].astype(jnp.float32)
              * p["conv_w"][:, i].astype(jnp.float32) for i in range(K))
    return jax.nn.silu(acc + p["conv_b"].astype(jnp.float32)).astype(
        window_rows.dtype)


def reset_slot_state(cache, slot):
    """Zero one slot's recurrent state in every state layer of
    `cache["ssm"]`: what a request admitted to the slot must start
    from."""
    return dict(cache, ssm=[
        {"s": st["s"].at[slot].set(0.0), "conv": st["conv"].at[slot].set(0)}
        for st in cache["ssm"]])


def embed(params, tokens):
    with scope("lm_embed"):
        return params["embed"][tokens]


def untied_head(params, x):  # float32 logits off the untied head
    with scope("lm_head"):
        return jnp.matmul(x, params["head"].T,
                          preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------
# a merged 3-D pool: [NB, Bt * cfg.groups, row], a block's rows
# (token, group), so the device holds them in whole 16 x 128 tiles
# ---------------------------------------------------------------------


def kv_pool(num_blocks, rows, width, dtype):
    """A K/V pool pair [NB + 1, rows, width]: one block more than the
    allocator hands out, where the fused decode write sends a parked
    slot's rows (paged_kv_write)."""
    shape = (int(num_blocks) + 1, rows, width)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def scatter_rows(pool, tab, wpos, rows, cfg, Bt):
    """Write rows [n, groups, D] at positions wpos [n] through the
    table `tab` ([n, MAXB], or [MAXB] for one slot): a position past
    the table span or on an unallocated (-1) entry is dropped (block
    NB is out of range), the parking rule of the GPT pool."""
    phys, off = phys_rows(tab, wpos, pool.shape[0], Bt)
    idx = jnp.stack([phys, off * cfg.groups], axis=-1)
    dn = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2), inserted_window_dims=(0,),
        scatter_dims_to_operand_dims=(0, 1))
    return jax.lax.scatter(pool, idx, rows.astype(pool.dtype), dn,
                           mode=jax.lax.GatherScatterMode.FILL_OR_DROP)


def scatter_chunk(pool, tab, start, wpos, true_len, rows, cfg, Bt):
    """A chunk's rows [C, groups, D] from position `start` through one
    slot's table `tab` [MAXB]. Where the chunk starts on a block's
    first row (every chunk of a prompt prefilled from position 0 in
    chunks of whole blocks) it is written a BLOCK at a time: XLA lowers
    a scatter to a loop of one small update an index, 3 us each on the
    v5e, and 4,096 rows a pool, 18 pools a chunk, would be 0.2 s of
    it. A block's rows past `true_len` are then written too: padding,
    at positions nothing attends before the decode step that owns them
    has written them again. Anywhere else, row by row (`wpos` parks
    the padded rows)."""
    C = rows.shape[0]
    if C % Bt:
        return scatter_rows(pool, tab, wpos, rows, cfg, Bt)
    NB, maxb = pool.shape[0], tab.shape[0]

    def by_block(pool):
        i = jnp.arange(C // Bt)
        bi = start // Bt + i
        phys = tab[jnp.clip(bi, 0, maxb - 1)]
        phys = jnp.where((bi < maxb) & (phys >= 0) & (i * Bt < true_len),
                         phys, jnp.int32(NB))
        return pool.at[phys].set(
            rows.astype(pool.dtype).reshape(C // Bt, Bt * cfg.groups, -1),
            mode="drop")

    return jax.lax.cond(
        start % Bt == 0, by_block,
        lambda pool: scatter_rows(pool, tab, wpos, rows, cfg, Bt), pool)


def pool_view(pool, tab, cfg, Bt):
    """The rows the table `tab` [..., MAXB] names, as positions:
    [..., MAXB * Bt, groups, D] (-1 clamps to block 0: garbage the
    position mask excludes)."""
    v = pool[jnp.clip(tab, 0, pool.shape[0] - 1)]
    return v.reshape(tab.shape[:-1] + (tab.shape[-1] * Bt, cfg.groups,
                                       pool.shape[-1]))


def head_view(pool, tab, cfg, Bt):
    """The rows the table names, as positions and K/V heads:
    [..., MAXB * Bt, Hk, dh]."""
    v = pool_view(pool, tab, cfg, Bt)
    return v.reshape(v.shape[:-2] + (cfg.kv_heads, cfg.dh))


def chunk_attend(q, k, v, start_pos, cfg):
    """q [C, Hk, rep, dh], row i at position start_pos + i, over the
    slot's span k [K, Hk, dh], v [K, Hk, dv] (index = position; dv is
    dh but in the latent family, whose values are narrower than its
    keys) -> [C, heads * dv], causal, the scores scaled by
    `cfg.attention_multiplier`. Queries 512 rows at a time, keys
    1,024 at a time with the running (max, sum, acc) of an online
    softmax, and only the key tiles up to a query tile's last position
    are walked (a trip count
    read off `start_pos`), so the work follows the context and not
    the table's span. One softmax over the whole span instead ran 130
    times slower on the v5e at 8,192 positions (0.196 s a layer
    against 1.5 ms): its reductions took the contraction over dh off
    the matrix unit."""
    f32 = jnp.float32
    C, Hk, rep, dh = q.shape
    K, dv = k.shape[0], v.shape[-1]
    tile = min(512, C)
    KT = 1024 if K % 1024 == 0 else K
    kh, vh = k.transpose(1, 0, 2), v.transpose(1, 0, 2)  # [Hk, K, dh]
    batched = (((2,), (2,)), ((0,), (0,)))
    outs = []
    for i in range(0, C, tile):
        qh = q[i:i + tile].transpose(1, 2, 0, 3).reshape(Hk, rep * tile, dh)
        qpos = jnp.tile(start_pos + i + jnp.arange(tile), rep)

        def body(j, state, qh=qh, qpos=qpos):
            m, l, acc = state
            ks = jax.lax.dynamic_slice_in_dim(kh, j * KT, KT, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(vh, j * KT, KT, axis=1)
            s = jax.lax.dot_general(
                qh, ks, batched,
                preferred_element_type=f32) * cfg.attention_multiplier
            kpos = j * KT + jnp.arange(KT)
            s = jnp.where((kpos[None, :] <= qpos[:, None])[None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            p = jnp.exp(s - m_new)
            a = jnp.exp(m - m_new)
            pv = jax.lax.dot_general(
                p.astype(vs.dtype), vs, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=f32)
            return m_new, l * a + p.sum(-1, keepdims=True), acc * a + pv

        m0 = jnp.full((Hk, rep * tile, 1), NEG_INF, f32)
        _, l, acc = jax.lax.fori_loop(
            0, jnp.minimum((start_pos + i + tile + KT - 1) // KT, K // KT),
            body, (m0, jnp.zeros_like(m0),
                   jnp.zeros((Hk, rep * tile, dv), f32)))
        o = (acc / l).reshape(Hk, rep, tile, dv).transpose(2, 0, 1, 3)
        outs.append(o.reshape(tile, Hk * rep * dv).astype(q.dtype))
    return jnp.concatenate(outs)


def moe_ffn(u32, p, cfg, valid, kernel="gather"):
    """An expert layer over the float32 normed rows u32 [N, d] ->
    (float32 [N, d], stats int32 [2]): the router (sigmoid scores,
    `cfg.route_scale` / `cfg.route_norm`), the experts this chip holds
    (`cfg.experts_held`), and the shared expert if it is held here. A
    row's result depends on that row alone. `route` is looked up when
    the layer is traced."""
    from ..parallel.routed_experts import expert_ffn, route

    u = u32.astype(cfg.dtype)
    idx, w = route(u32, p["router"], p["router_bias"], cfg.top_k,
                   cfg.route_scale, cfg.route_norm)
    out, stats = expert_ffn(u, idx, w, p["experts"], valid,
                            held=cfg.experts_held, kernel=kernel)
    if cfg.shared_expert_held:
        out = out + mlp(u, p["shared"]).astype(jnp.float32)
    return out, stats


def expert_stack(params, x, cfg, attn, valid, kernel, sandwich):
    """The residual stack of a family whose layers after the first
    `cfg.num_dense_layers` are `moe_ffn` layers, each part under its
    device scope with its norms and residual add (`scopes.py`);
    `attn(l, h, p)` is the mode's. A branch is pre-normed or, with
    `sandwich`, pre- and post-normed. `valid` [rows]: the rows that
    reach experts. -> (the final norm's output, the expert layers'
    stats summed / maxed: int32 [2])."""
    dt, eps = x.dtype, cfg.eps
    hit, fullest = jnp.int32(0), jnp.int32(0)
    for l, blk in enumerate(params["blocks"]):
        with scope("lm_attention"):
            a = attn(l, rms32(x, blk["norm1"], eps).astype(dt), blk["attn"])
            if sandwich:
                a = rms32(a, blk["norm2"], eps)
            x = x + a.astype(dt)
        dense = l < cfg.num_dense_layers
        with scope("lm_mlp" if dense else "lm_experts"):
            u32 = rms32(x, blk["norm3" if sandwich else "norm2"], eps)
            if dense:
                m = mlp(u32.astype(dt), blk["ffn"])
            else:
                m, stats = moe_ffn(u32, blk["ffn"], cfg, valid, kernel)
                hit = hit + stats[0]
                fullest = jnp.maximum(fullest, stats[1])
            if sandwich:
                m = rms32(m, blk["norm4"], eps)
            x = x + m.astype(dt)
    with scope("lm_head"):
        x = rms32(x, params["norm_f"], eps).astype(dt)
    return x, jnp.stack([hit, fullest])


class Seam(object):
    """What ServingEngine asks a model family for: its `name`; its
    `caches` ("paged": the block pool on the engine's one table;
    "window": window tables freed behind the window; "state": per-slot
    recurrent state, zeroed at admission by `reset_slot_state`); the
    options it cannot honour (`refused`, by default what only the GPT
    block's per-head K/V blocks support) and why (`refusal`); the
    counters its decode step returns beside the logits
    (`step_counters`); its caches' bytes by kind (`cache_bytes(cfg,
    block_tokens)`, or None for the engine's own formula). A family
    states `decode`, `prefill` and `cache`; the engine hands every
    family the same keywords, and the shims below drop the ones a
    refusing family only ever receives as their defaults."""
    name = ""
    caches = ("paged",)
    refused = ("prefix_cache_tokens", "kv_store", "spec_draft_len",
               "kv_quant", "weight_quant", "adapter_registry",
               "kv_fingerprints")
    refusal = ""
    step_counters = ()
    cache_bytes = None

    def decode_step(self, params, token, pos, tables, cache, cfg,
                    adapters=None, adapter_idx=None, kernel="gather",
                    kv_quant="none"):
        return self.decode(params, token, pos, tables, cache, cfg,
                           kernel=kernel)

    def prefill_chunk(self, params, cache, chunk, start_pos, table_rows,
                      cfg, true_len=None, adapters=None, adapter_idx=None,
                      kernel="gather", kv_quant="none"):
        return self.prefill(params, cache, chunk, start_pos, table_rows,
                            cfg, true_len=true_len, kernel=kernel)

    def init_cache(self, cfg, num_blocks, block_tokens, slots,
                   kv_quant="none"):
        return self.cache(cfg, num_blocks, block_tokens, slots)
