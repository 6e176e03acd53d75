"""Pallas TPU flash attention: blockwise online-softmax attention that
never materialises the [T, T] score matrix.

The hot-op kernel story (SURVEY §7.1: "pallas for kernels XLA can't
express"): XLA fuses elementwise chains into matmuls but still allocates
the full attention score matrix; flash attention tiles Q into VMEM-sized
blocks and streams K/V blocks through the MXU with a running
(max, sum, accumulator) — O(T) memory instead of O(T^2), the same
algorithm the ring-attention path uses ACROSS chips
(parallel/attention.py), here applied WITHIN a chip.

Forward is a single `pl.pallas_call` over a (batch*heads, q_blocks,
k_blocks) grid with the k axis innermost (grid-reduction pattern:
initialise at k==0, accumulate, finalise at the last k step), emitting
the per-row log-sum-exp as a residual. Backward (jax.custom_vjp) is
two pallas passes that rebuild each probability tile from the lse —
dk/dv over a (bh, k_blocks, q_blocks) grid, dq over the forward's grid
— so every matmul stays a VMEM-tiled MXU op and memory stays O(T)
(r5; the previous XLA blockwise-recompute scan materialised
[block_q, S] f32 score tiles in HBM).

`interpret=True` runs the kernel on CPU for CI (tests/conftest runs on
a CPU mesh); on TPU the same kernel compiles to Mosaic.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel_utils import NEG_INF, causal_fill, resolve_interpret

__all__ = ["flash_attention", "resolve_interpret"]

# back-compat alias: the mask fill + interpret resolution now live in
# kernel_utils.py, shared with the paged-attention kernels (ISSUE 13)
_NEG_INF = NEG_INF

# backward tile cap: the bwd kernels hold ~3 extra [block_q, block_k]
# f32 intermediates vs the forward, so 1024-wide blocks that fit the
# forward would exceed the 16 MB scoped-VMEM budget here
_BWD_BLOCK_CAP = 512


def _block_needed(qi, kj, block_q, block_k, causal):
    """Whole-block causal skip: a k block strictly above this q block's
    last row is fully masked — skip its matmuls entirely."""
    return kj * block_k <= qi * block_q + block_q - 1 if causal else True


# the shared causal tile mask (kernel_utils.causal_fill) under its
# historical module-local name — forward and backward both use it
_causal_fill = causal_fill


def _bwd_block(block, length):
    """Backward tile size: cap at _BWD_BLOCK_CAP, halve until it
    divides — but never below the 8-row minimum the forward refuses;
    awkward lengths (e.g. prime T<=1024 that the forward runs as one
    whole-sequence block) fall back to a whole-length block instead of
    degrading to a per-row grid."""
    b = min(block, _BWD_BLOCK_CAP)
    while b > 1 and length % b:
        b //= 2
    return b if b >= 8 else length


def _out_struct(shape, dtype, like):
    """ShapeDtypeStruct for a pallas output, propagating the input's
    varying-mesh-axes type (vma) so the kernel is callable inside
    shard_map (ulysses runs it per shard) under JAX's check_vma."""
    try:
        vma = jax.typeof(like).vma
    except Exception:
        vma = None
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
               l_ref, *, scale: float, causal: bool, block_q: int,
               block_k: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(_block_needed(qi, kj, block_q, block_k, causal))
    def _accumulate():
        q = q_ref[0]  # [block_q, D], input dtype (bf16 stays on the MXU
        k = k_ref[0]  # bf16 path; accumulation is f32 via
        v = v_ref[0]  # preferred_element_type)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k]
        if causal:
            s = _causal_fill(s, qi, kj, block_q, block_k)

        m_prev = m_ref[...]  # [block_q, 1]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows (causal upper blocks): exp(-inf - -inf)
        p = jnp.exp(s - m_new)  # [block_q, block_k]
        p = jnp.where(s <= _NEG_INF, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        alpha = jnp.where(m_prev <= _NEG_INF, 0.0, alpha)

        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _finalise():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        # per-row log-sum-exp residual for the pallas backward:
        # p = exp(s - lse) reconstructs the normalised softmax directly
        lse_ref[0] = m_ref[...] + jnp.log(denom)


def _fa_forward(q, k, v, scale: float, causal: bool, block_q: int,
                block_k: int, interpret: bool):
    BH, T, D = q.shape
    S = k.shape[1]
    nq = pl.cdiv(T, block_q)
    nk = pl.cdiv(S, block_k)
    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k,
    )
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _out_struct((BH, T, D), q.dtype, q),
            _out_struct((BH, T, 1), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def _reference(q, k, v, scale, causal):
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        T, S = s.shape[1], s.shape[2]
        mask = jnp.tril(jnp.ones((T, S), bool), k=S - T)
        s = jnp.where(mask[None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(
        q.dtype
    )


def _bwd_scores(q, k, lse, qi, kj, *, scale, causal, block_q, block_k):
    """Rebuild one normalised probability tile p = exp(s*scale - lse)
    inside a backward kernel. Masked taps reconstruct to exact 0 via
    exp(-inf); no separate mask needed beyond the causal score fill."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    if causal:
        s = _causal_fill(s, qi, kj, block_q, block_k)
    return jnp.exp(s - lse)


def _fa_bwd_kv_kernel(q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                      block_q, block_k):
    """dk/dv pass: grid (BH, k_blocks, q_blocks), q innermost — each k
    block accumulates over the q blocks that attend to it."""
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_block_needed(qi, kj, block_q, block_k, causal))
    def _accumulate():
        q = q_ref[0]
        g = g_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        p = _bwd_scores(q, k, lse_ref[0], qi, kj, scale=scale,
                        causal=causal, block_q=block_q, block_k=block_k)
        # dv += p^T g   (contract the q rows)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # ds = p * (g v^T - delta) * scale
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0]) * scale
        # dk += ds^T q
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _finalise():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _fa_bwd_q_kernel(q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref,
                     dq_ref, dq_acc, *, scale, causal, block_q,
                     block_k):
    """dq pass: grid (BH, q_blocks, k_blocks), k innermost — mirrors the
    forward's grid-reduction shape."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(_block_needed(qi, kj, block_q, block_k, causal))
    def _accumulate():
        q = q_ref[0]
        g = g_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        p = _bwd_scores(q, k, lse_ref[0], qi, kj, scale=scale,
                        causal=causal, block_q=block_q, block_k=block_k)
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0]) * scale
        # dq += ds k
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kj == nk - 1)
    def _finalise():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret):
    out, _ = _fa_forward(q, k, v, scale, causal, block_q, block_k,
                         interpret)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    out, lse = _fa_forward(q, k, v, scale, causal, block_q, block_k,
                           interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, g):
    """Pallas flash backward (r5; previously an XLA blockwise-recompute
    scan that materialised [block_q, S] f32 score tiles in HBM): two
    tiled passes that rebuild each probability block from the saved
    log-sum-exp — dk/dv with q innermost, dq with k innermost. Memory
    stays O(T), all matmuls hit the MXU with f32 accumulation."""
    q, k, v, out, lse = res
    BH, T, D = q.shape
    S = k.shape[1]
    bq = _bwd_block(block_q, T)
    bk = _bwd_block(block_k, S)
    nq = T // bq
    nk = S // bk
    # delta_i = rowsum(g * o): the p·dp row-dot every ds tile needs
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
        keepdims=True,
    )
    lse = lse.reshape(BH, T, 1)

    q_spec = pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0))
    kv_spec = pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0))
    row_spec = pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_kv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        grid=(BH, nk, nq),
        in_specs=[q_spec, q_spec, kv_spec, kv_spec, row_spec, row_spec],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            _out_struct((BH, S, D), k.dtype, k),
            _out_struct((BH, S, D), v.dtype, k),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, g, k, v, lse, delta)

    q_spec2 = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))
    kv_spec2 = pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0))
    row_spec2 = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_q_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        grid=(BH, nq, nk),
        in_specs=[q_spec2, q_spec2, kv_spec2, kv_spec2, row_spec2,
                  row_spec2],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=_out_struct((BH, T, D), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(q, g, k, v, lse, delta)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, block_q: int = 1024,
                    block_k: int = 1024, interpret: bool = False):
    """Blockwise attention for [B, T, H, D] tensors (same layout as
    parallel/attention.py). Block sizes clamp to the sequence lengths
    and halve until they divide them. The 1024x1024 default came out
    of an earlier round's block sweep on a v5e whose records are gone;
    its speed is not measured on this tree. What the compiler says:
    2048-wide q or k blocks exceed the 16 MB scoped-VMEM budget and
    fail to compile, and small blocks (128x128) make a grid of tens of
    thousands of tiny matmuls."""
    B, T, H, D = q.shape
    S = k.shape[1]
    if scale is None:
        scale = D ** -0.5
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    while block_q > 1 and T % block_q:
        block_q //= 2
    while block_k > 1 and S % block_k:
        block_k //= 2
    if block_q < 8 or block_k < 8:
        # odd lengths would degrade to a per-row grid (T^2 steps of 1-row
        # matmuls) — refuse instead; pad the sequence to a multiple of 8
        raise ValueError(
            "sequence lengths (%d, %d) have no usable block split (need "
            "a multiple of 8); pad the sequence" % (T, S)
        )
    if causal and T != S:
        raise ValueError(
            "causal flash attention requires matching q/k lengths "
            "(got %d vs %d)" % (T, S)
        )

    def bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)

    out = _flash(bh(q), bh(k), bh(v), float(scale), bool(causal),
                 int(block_q), int(block_k), bool(interpret))
    return out.reshape(B, H, T, D).transpose(0, 2, 1, 3)
