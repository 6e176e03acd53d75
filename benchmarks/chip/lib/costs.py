"""Operations and bytes the algorithm needs, from shapes alone.

Useful work only: padded rows of a prefill bucket, dead slots of a
decode batch and recomputed operations do not count. A multiply-add
is two operations.

`shape` is a configuration's "shape" group: vocab, dim, heads, layers,
mlp_mult (a GPT-2 style block with a weight-tied head).
"""

from __future__ import annotations


def lm_block_matmul_params(shape):
    """Matrix parameters of all blocks: wq, wk, wv, wo, w1, w2."""
    d, m = shape["dim"], shape["mlp_mult"] * shape["dim"]
    return shape["layers"] * (4 * d * d + 2 * d * m)


def lm_head_params(shape):
    return shape["vocab"] * shape["dim"]


def lm_decode_flops(shape, context):
    """One generated token whose query attends over `context` cached
    positions (its own included): every matrix once, the head once,
    QK^T and PV over the context in every layer."""
    return (2 * (lm_block_matmul_params(shape) + lm_head_params(shape))
            + shape["layers"] * 4 * context * shape["dim"])


def lm_prefill_flops(shape, tokens):
    """`tokens` prompt rows from position 0: every block matrix per
    row, causal attention per row, and the head for the last row only
    (the served path samples one token per prompt)."""
    attended = tokens * (tokens + 1) // 2
    return (2 * tokens * lm_block_matmul_params(shape)
            + 2 * lm_head_params(shape)
            + shape["layers"] * 4 * attended * shape["dim"])


def paged_decode_attention_cost(shape, contexts, block_tokens):
    """One layer's paged decode-attention call over the live slots, in
    the bf16 the cells serve (2 bytes an element).

    `contexts`: cached positions each live slot attends over. FLOPs are
    QK^T and PV; bytes are the K and V blocks the tables name (whole
    blocks: the pool is read at block granularity) plus q in and out.
    -> (flops, bytes)
    """
    d = shape["dim"]
    flops = sum(4 * c * d for c in contexts)
    named = sum(-(-c // block_tokens) * block_tokens for c in contexts)
    kv_bytes = 2 * named * d * 2
    io_bytes = 2 * len(contexts) * d * 2
    return flops, kv_bytes + io_bytes


def roofline_seconds(flops, nbytes, peaks):
    """Least time the chip could take, and which bound sets it."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "hbm")
