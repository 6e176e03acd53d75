"""Operations and bytes the Mamba-2 / grouped-query hybrid needs, from
shapes alone.

Useful work only, as in `costs.py`: padded rows of a prefill bucket,
dead slots of a decode batch and recomputed operations do not count;
a multiply-add is two operations. `shape` is the configuration's
"shape" group: vocab, dim, heads, kv_heads, head_dim, layer_types,
mlp_mult, mamba_heads, mamba_head_dim, d_state, d_conv, chunk.
"""

from __future__ import annotations


def layer_counts(shape):
    kinds = list(shape["layer_types"])
    return {"mamba": kinds.count("mamba"),
            "attention": kinds.count("attention")}


def _dims(shape):
    di = shape["mamba_heads"] * shape["mamba_head_dim"]
    return shape["dim"], di, shape["d_state"], shape["mamba_heads"]


def matmul_params(shape):
    """Matrix parameters one token passes through, by kind of layer
    (the MLP's three matrices are in every layer) and for the head."""
    d, di, N, H = _dims(shape)
    hq, hkv, dh = shape["heads"], shape["kv_heads"], shape["head_dim"]
    return {
        "mlp": 3 * d * shape["mlp_mult"] * d,
        "mamba": d * (2 * di + 2 * N + H) + di * d,
        "attention": d * (hq + 2 * hkv) * dh + hq * dh * d,
        "head": shape["vocab"] * d,
    }


def _token_flops(shape):
    """Everything of one token but the attention reads: the matrices,
    the conv (2 d_conv a channel of xBC) and the recurrence (6 a state
    element: the decay's product, the input's outer product and sum,
    the output's product and sum)."""
    n, p = layer_counts(shape), matmul_params(shape)
    d, di, N, H = _dims(shape)
    return (2 * (len(shape["layer_types"]) * p["mlp"]
                 + n["mamba"] * p["mamba"] + n["attention"] * p["attention"])
            + n["mamba"] * (2 * shape["d_conv"] * (di + 2 * N) + 6 * N * di))


def _attn_flops(shape, attended):
    """QK^T and PV over head_dim, every query head: 4 heads head_dim an
    attended position."""
    return 4 * shape["heads"] * shape["head_dim"] * attended


def decode_flops(shape, context):
    """One generated token whose query attends over `context` cached
    positions (its own included) in every attention layer."""
    return (_token_flops(shape) + 2 * matmul_params(shape)["head"]
            + layer_counts(shape)["attention"] * _attn_flops(shape, context))


def prefill_flops(shape, tokens):
    """`tokens` prompt rows from position 0, the head for the last row
    only (the served path samples one token per prompt)."""
    causal = tokens * (tokens + 1) // 2
    return (tokens * _token_flops(shape) + 2 * matmul_params(shape)["head"]
            + layer_counts(shape)["attention"] * _attn_flops(shape, causal))


def gqa_decode_attention_cost(shape, contexts, block_tokens):
    """The decode step's attention calls over the live slots, in the
    bf16 the cell serves -> [(calls a step, flops, bytes of one call)]:
    one call an attention layer, each reading the whole context of its
    own pool through the one table, in whole blocks. A position is
    kv_heads x head_dim of K and as much of V; q is heads x head_dim in
    and as much out, a slot."""
    row = 2 * shape["kv_heads"] * shape["head_dim"] * 2
    io = len(contexts) * 2 * shape["heads"] * shape["head_dim"] * 2
    flops = sum(_attn_flops(shape, c) for c in contexts)
    named = sum(-(-c // block_tokens) * block_tokens for c in contexts)
    return [(layer_counts(shape)["attention"], flops, named * row + io)]


def ssd_state_update_cost(shape, contexts, block_tokens):
    """The decode step's one-token state updates -> [(calls a step,
    flops, bytes of one call)]: a live slot's float32 state
    [mamba_heads, mamba_head_dim, d_state] in and out, its x in and its
    y out (a float32 row of di each), its step (a value a head) and
    its B and C."""
    d, di, N, H = _dims(shape)
    slots = len(contexts)
    nbytes = slots * (2 * N * di * 4 + 2 * di * 4 + (H + 2 * N) * 4)
    return [(layer_counts(shape)["mamba"], slots * 6 * N * di, nbytes)]


def ssd_chunk_scan_cost(shape, tokens):
    """One layer's blocked scan over `tokens` rows of one slot (whole
    blocks of `chunk` rows) -> (flops, bytes): a block's C B^T, the
    masked product with the block's inputs batched over the heads, the
    read of the carried state and the block's contribution to it; x in
    and y out as float32 rows, dt, B and C, and the state in and out
    once."""
    d, di, N, H = _dims(shape)
    Q = min(shape["chunk"], tokens)
    blocks = -(-tokens // Q)
    flops = blocks * (2 * Q * Q * (N + di) + 4 * Q * N * di)
    nbytes = blocks * Q * (2 * di + 2 * N + H) * 4 + 2 * N * di * 4
    return flops, nbytes


def selfcheck():
    """Hand counts at one small shape (benchmarks/chip/tests runs this;
    `run.py --selfcheck` names its checks in a file this PR may not
    edit)."""
    s = {"vocab": 10, "dim": 16, "heads": 4, "kv_heads": 2, "head_dim": 8,
         "layer_types": ["mamba", "mamba", "attention"], "mlp_mult": 4,
         "mamba_heads": 4, "mamba_head_dim": 8, "d_state": 16, "d_conv": 4,
         "chunk": 4}
    assert layer_counts(s) == {"mamba": 2, "attention": 1}
    p = matmul_params(s)
    # d 16, di 32, N 16, H 4: in_proj 16 x (64 + 32 + 4), out 32 x 16
    assert p["mlp"] == 3 * 16 * 64 and p["head"] == 160
    assert p["mamba"] == 16 * 100 + 512
    assert p["attention"] == 16 * (4 + 4) * 8 + 32 * 16
    tok = (2 * (3 * 3072 + 2 * 2112 + 1536)
           + 2 * (2 * 4 * 64 + 6 * 16 * 32))
    assert _token_flops(s) == tok
    # context 5: 4 heads x 8 wide, QK^T and PV
    assert decode_flops(s, 5) == tok + 320 + 4 * 32 * 5
    # 4 rows: causal 1 + 2 + 3 + 4
    assert prefill_flops(s, 4) == 4 * tok + 320 + 128 * 10
    # contexts 2 and 20, 16-token blocks: one block and two; a position
    # is 2 x 2 x 8 x 2 B = 64 B; q and out 2 slots x 2 x 32 x 2 B
    ((calls, fl, by),) = gqa_decode_attention_cost(s, [2, 20], 16)
    assert calls == 1 and fl == 128 * 22 and by == 48 * 64 + 256
    ((calls, fl, by),) = ssd_state_update_cost(s, [2, 20], 16)
    assert calls == 2 and fl == 2 * 6 * 16 * 32
    assert by == 2 * (2 * 2048 + 2 * 128 + 36 * 4)
    # 8 rows in two blocks of 4
    fl, by = ssd_chunk_scan_cost(s, 8)
    assert fl == 2 * (2 * 16 * 48 + 4 * 4 * 16 * 32)
    assert by == 8 * (64 + 32 + 4) * 4 + 2 * 2048
    return True
