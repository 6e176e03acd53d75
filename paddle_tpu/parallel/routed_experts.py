"""Routed experts for serving (ISSUE 33): per-token top-k routing with
no capacity, and ONE grouped matrix product over the experts held.

`parallel/moe.py` is the training-side Switch layer: top-1, a capacity
cut-off that couples the rows of a batch (a token's result depends on
which other tokens reached its expert first), an all-to-all over a
mesh axis. None of that serves: a served row must give the same
result alone, in a full batch and beside padded rows. Here

  * `route`: the router's product, its scores, the bias that decides
    the choice (and nothing else), top-k, the normalisation and the
    scale — or, for Granite's router, top-k of the logits and a softmax
    over the k chosen — all in float32 from the float32 row, so that a
    16-bit rounding of a score never picks another expert;
  * `plan_rows`: the (row, choice) pairs sorted by expert, each
    expert's rows padded to whole row tiles, so that a tile belongs to
    ONE expert. Rows that do not count (a prefill bucket's padding, a
    decode step's dead slots) and choices of experts held elsewhere
    sort into a null group, take no row and are not counted. No
    scatter anywhere: two sorts and gathers;
  * `grouped_matmul`: `[rows, K] x [E_held, K, N]`, a Pallas kernel
    (`moe_grouped_matmul`) whose grid is (output tiles, the row tiles
    that exist) — the second bound is data — and whose weight block is
    picked through the scalar-prefetched tile -> expert map: an expert
    no row reached is never read, and consecutive tiles of one expert
    keep its block. bf16 operands, float32 accumulation. On the CPU
    (`kernel="gather"`) the same layout goes through
    `jax.lax.ragged_dot`;
  * `expert_ffn`: gather rows in, gate-up product, silu(g) * u, down
    product, the combine weighted in float32.

`held = (lo, hi)` is the range of experts whose weights this chip has
(`w_gu`, `w_down` carry hi - lo experts): the router still scores all
of them, and what the absent experts would have added is left out.
On one chip there is no exchange and no code that stands in for one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel_utils import resolve_interpret

__all__ = ["route", "plan_rows", "grouped_matmul", "expert_ffn", "row_tile",
           "held_range"]

# a weight block a grid step, at most: 4 MiB and 1,024 columns (my chip
# runs, PR 33: 512 rows over 128 experts 2,201 us at 1,024 columns,
# 2,207 at 512, 2,389 at 256; 32,768 rows 5,075 / 5,431)
_RHS_BLOCK_BYTES = 4 << 20
_RHS_BLOCK_COLS = 1024


def held_range(experts_held, n_experts):
    """A configuration's `experts_held` (None: all of them) -> the
    (lo, hi) range of the `n_experts` whose weights this chip has."""
    lo, hi = experts_held or (0, n_experts)
    if not 0 <= lo < hi <= n_experts:
        raise ValueError("experts_held %r of %d experts"
                         % ((lo, hi), n_experts))
    return int(lo), int(hi)


def route(u32, router_w, bias, top_k, route_scale=1.0, route_norm=True,
          scoring="sigmoid"):
    """u32 [N, d] float32 -> (experts [N, k] int32, weights [N, k]
    float32). `scoring`:

      "sigmoid"        scores are sigmoids of the logits; `bias` [E] is
                       added for the CHOICE only; the weights are the
                       chosen experts' own scores, normalised over the
                       k chosen (`route_norm`) and scaled
      "softmax_topk"   the k largest logits are chosen and their
                       weights are a softmax over those k alone
                       (Granite's `GraniteMoeHybridTopKGating`): no
                       bias, no normalisation beyond it, no scale"""
    f32 = jnp.float32
    logits = jnp.matmul(u32.astype(f32), router_w.astype(f32),
                        precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax_topk":
        top, idx = jax.lax.top_k(logits, top_k)
        return idx.astype(jnp.int32), jax.nn.softmax(top, axis=-1)
    if scoring != "sigmoid":
        raise ValueError("route scores by 'sigmoid' or 'softmax_topk', "
                         "not %r" % (scoring,))
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + bias.astype(f32), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if route_norm:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * route_scale


def row_tile(pairs: int, experts: int) -> int:
    """The row tile of a call, read off the rows it has: the rows an
    expert sees on average, as a power of two between 16 (a 16-bit
    tile's sublanes) and 128 (the matrix unit's side): 16 for a decode
    step of 64 slots x 8 choices over 128 experts, 128 for a chunk."""
    mean = max(1, pairs // max(1, experts))
    return min(128, max(16, 1 << (mean - 1).bit_length()))


def plan_rows(idx, valid, held, tm):
    """Where every (row, choice) pair goes. idx [N, k] expert ids,
    valid [N] the rows that count, held = (lo, hi) the experts whose
    weights are here -> dict:

      src [M]     the input row each row of the sorted layout copies
                  (0 on a tile's padding: finite, read by nobody)
      dest [N, k] the sorted row that holds a pair's result, -1 where
                  the pair takes none (row not valid, expert not held)
      tile_expert [T], n_tiles   the expert of each row tile, and how
                  many tiles exist
      counts [hi - lo]           pairs an expert held here received

    M = the pairs + (tm - 1) rows of padding an expert, in whole
    tiles: static."""
    lo, hi = held
    Eh = hi - lo
    N, k = idx.shape
    A = N * k
    M = -(-(A + Eh * (tm - 1)) // tm) * tm
    T = M // tm
    local = idx - lo
    ok = valid[:, None] & (local >= 0) & (local < Eh)
    key = jnp.where(ok, local, Eh).reshape(A).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)       # sorted place -> pair
    place = jnp.argsort(order)                  # pair -> sorted place
    counts = (key[:, None] == jnp.arange(Eh)[None, :]).sum(
        0, dtype=jnp.int32)
    padded = -(-counts // tm) * tm
    end_pad = jnp.cumsum(padded)
    start_pad = end_pad - padded
    start = jnp.cumsum(counts) - counts
    e = jnp.minimum(key, Eh - 1)
    dest = jnp.where(key < Eh, start_pad[e] + place - start[e], -1)
    n_tiles = end_pad[-1] // tm
    t = jnp.arange(T)
    tile_expert = jnp.minimum(
        (t[:, None] >= (end_pad // tm)[None, :]).sum(1, dtype=jnp.int32),
        Eh - 1)
    m = jnp.arange(M)
    te = tile_expert[m // tm]
    r = m - start_pad[te]
    real = (r < counts[te]) & (m // tm < n_tiles)
    pair = order[jnp.clip(start[te] + r, 0, A - 1)]
    return {"src": jnp.where(real, pair // k, 0).astype(jnp.int32),
            "dest": dest.reshape(N, k).astype(jnp.int32),
            "tile_expert": tile_expert,
            # an all-dead call still runs one tile (of expert 0's
            # block, over rows nobody reads): a grid is never empty
            "n_tiles": jnp.maximum(n_tiles, 1).astype(jnp.int32),
            "padded": padded, "counts": counts}


def _col_tile(K: int, N: int, itemsize: int) -> int:
    """Output columns a grid step: the widest divisor of N (in whole
    128-lane tiles) whose [K, tn] weight block stays within
    `_RHS_BLOCK_BYTES` and `_RHS_BLOCK_COLS`."""
    tn = N
    while tn % 256 == 0 and (tn > _RHS_BLOCK_COLS
                             or K * tn * itemsize > _RHS_BLOCK_BYTES):
        tn //= 2
    return tn


def _gmm_kernel(te_ref, x_ref, w_ref, o_ref):
    # the precision is said here: a process-wide "highest" (the test
    # suite's) is a float32 contraction Mosaic refuses on 16-bit tiles,
    # whose products are exact under float32 accumulation anyway
    o_ref[...] = jnp.dot(x_ref[...], w_ref[...],
                         preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.DEFAULT
                         ).astype(o_ref.dtype)


def grouped_matmul(x, w, plan, tm, out_dtype=None, kernel="fused",
                   interpret=None, tn=None):
    """x [M, K] in `plan_rows`' layout (row tile i belongs to expert
    plan["tile_expert"][i]) times w [E_held, K, N] -> [M, N]; rows of
    tiles that do not exist are not written."""
    M, K = x.shape
    _, _, N = w.shape
    out_dtype = out_dtype or x.dtype
    if kernel != "fused":
        sizes = plan["padded"]
        return jax.lax.ragged_dot(
            x, w, sizes, preferred_element_type=jnp.float32
        ).astype(out_dtype)
    tn = tn or _col_tile(K, N, w.dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N // tn, plan["n_tiles"]),
        in_specs=[pl.BlockSpec((tm, K), lambda j, t, te: (t, 0)),
                  pl.BlockSpec((None, K, tn),
                               lambda j, t, te: (te[t], 0, j))],
        out_specs=pl.BlockSpec((tm, tn), lambda j, t, te: (t, j)),
    )
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=resolve_interpret(interpret),
        name="moe_grouped_matmul",
        metadata={"kernel": "moe_grouped_matmul"},
    )(plan["tile_expert"], x, w)


def expert_ffn(u, idx, weights, p, valid, held=None, kernel="gather",
               interpret=None):
    """The routed experts' part of a layer: u [N, d] through the
    SwiGLU experts p["w_gu"] [E_held, d, 2 m], p["w_down"]
    [E_held, m, d] that `idx` [N, k] names, weighted by `weights`
    [N, k] (float32) -> (float32 [N, d], stats int32 [2]: the experts
    held here that a row reached, and the fullest one's rows). A row
    that is not `valid` gets zeros and reaches nobody."""
    f32 = jnp.float32
    Eh = p["w_gu"].shape[0]
    lo = held[0] if held is not None else 0  # default: all are here
    N, k = idx.shape
    tm = row_tile(N * k, Eh)
    plan = plan_rows(idx, valid, (lo, lo + Eh), tm)
    gmm = functools.partial(grouped_matmul, plan=plan, tm=tm, kernel=kernel,
                            interpret=interpret)
    gu = gmm(u[plan["src"]], p["w_gu"])
    m = gu.shape[-1] // 2
    h = (jax.nn.silu(gu[:, :m].astype(f32)) * gu[:, m:].astype(f32)
         ).astype(u.dtype)
    y = gmm(h, p["w_down"], out_dtype=f32)
    dest = plan["dest"]
    picked = y[jnp.maximum(dest, 0)]  # [N, k, d]
    out = jnp.where((dest >= 0)[..., None],
                    picked * weights[..., None].astype(f32), 0.0).sum(1)
    counts = plan["counts"]
    stats = jnp.stack([(counts > 0).sum(dtype=jnp.int32),
                       counts.max().astype(jnp.int32)])
    return out, stats
