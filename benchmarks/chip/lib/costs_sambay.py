"""Operations and bytes the SambaY hybrid needs, from shapes alone.

Useful work only, as in `costs.py`: padded rows of a prefill bucket,
dead slots of a decode batch and recomputed operations do not count;
a multiply-add is two operations. `shape` is the configuration's
"shape" group: vocab, dim, heads, kv_heads, layers, mlp_mult, window.

Layers by kind for L layers (L a multiple of 4): L/4 + 1 Mamba, L/4
window attention, one full attention, L/4 - 1 gated memory units and
L/4 - 1 cross-attention; 32 layers: 9, 8, 1, 7, 7.
"""

from __future__ import annotations

D_STATE, D_CONV = 16, 4


def layer_counts(shape):
    q = shape["layers"] // 4
    return {"mamba": q + 1, "window": q, "full": 1, "gmu": q - 1,
            "cross": q - 1}


def _dims(shape):
    d = shape["dim"]
    return d, shape["mlp_mult"] * d, 2 * d, d // 16, d // shape["heads"]


def matmul_params(shape):
    """Matrix parameters one token passes through, by kind of layer
    (the MLP's three matrices are in every layer) and for the head."""
    d, m, di, rank, dh = _dims(shape)
    kv = shape["kv_heads"] * dh
    return {
        "mlp": 3 * d * m,
        "mamba": d * 2 * di + di * (rank + 2 * D_STATE) + rank * di + di * d,
        "window": d * (d + 2 * kv) + d * d,
        "full": d * (d + 2 * kv) + d * d,
        "cross": 2 * d * d,
        "gmu": 2 * d * di,
        "head": shape["vocab"] * d,
    }


def _token_flops(shape):
    """Everything of one token but the attention reads: the matrices,
    the conv (2 d_conv a channel) and the recurrence (6 a state
    element: the decay, its product, the input's outer product and
    sum, the output's product and sum), the memory unit's gate."""
    n, p = layer_counts(shape), matmul_params(shape)
    di = 2 * shape["dim"]
    return (2 * (shape["layers"] * p["mlp"]
                 + sum(n[k] * p[k] for k in n))
            + n["mamba"] * di * (2 * D_CONV + 6 * D_STATE)
            + n["gmu"] * di)


def _attn_flops(shape, attended):
    """A query head's QK^T over dh and PV over the pair's 2 dh, for
    every query head: 6 dh a head and attended position."""
    return 6 * shape["dim"] * attended


def decode_flops(shape, context):
    """One generated token whose query attends over `context` cached
    positions (its own included): window layers over min(context,
    window), the full layer and the cross layers over all of it."""
    n = layer_counts(shape)
    return (_token_flops(shape) + 2 * matmul_params(shape)["head"]
            + n["window"] * _attn_flops(shape, min(context, shape["window"]))
            + (1 + n["cross"]) * _attn_flops(shape, context))


def prefill_flops(shape, tokens):
    """`tokens` prompt rows from position 0, the head for the last row
    only (the served path samples one token per prompt)."""
    n, w = layer_counts(shape), shape["window"]
    causal = tokens * (tokens + 1) // 2
    k = min(tokens, w)
    banded = k * (k + 1) // 2 + (tokens - k) * w
    return (tokens * _token_flops(shape) + 2 * matmul_params(shape)["head"]
            + n["window"] * _attn_flops(shape, banded)
            + (1 + n["cross"]) * _attn_flops(shape, causal))


def _blocks(c, block_tokens):
    return -(-c // block_tokens) * block_tokens


def hybrid_decode_attention_cost(shape, contexts, block_tokens):
    """The decode step's attention calls over the live slots, in the
    bf16 the cell serves -> [(calls a step, flops, bytes of one call)]:
    the window layers' (each reads min(context, window) positions of
    its own pool, in whole blocks) and the full layer's with the cross
    layers' (each reads the whole context of the ONE shared pool). A
    position is kv_heads x dh of K and as much of V; q is heads x dh in
    and the pairs' reads heads x 2 dh out, a slot."""
    n = layer_counts(shape)
    d, _, _, _, dh = _dims(shape)
    row = 2 * shape["kv_heads"] * dh * 2
    io = len(contexts) * (d + 2 * d) * 2
    out = []
    for calls, cs in ((n["window"], [min(c, shape["window"])
                                     for c in contexts]),
                      (1 + n["cross"], contexts)):
        flops = sum(_attn_flops(shape, c) for c in cs)
        named = sum(_blocks(c, block_tokens) for c in cs)
        out.append((calls, flops, named * row + io))
    return out


def ssm_state_update_cost(shape, contexts, block_tokens):
    """The decode step's one-token state updates -> [(calls a step,
    flops, bytes of one call)]: a live slot's float32 state [16, 2 dim]
    in and out, its step and input rows in, its output row out, its B
    and C, and the layer's A once."""
    di = 2 * shape["dim"]
    slots = len(contexts)
    state = D_STATE * di * 4
    nbytes = slots * (2 * state + 3 * di * 4 + 2 * D_STATE * 4) + state
    return [(layer_counts(shape)["mamba"], slots * 6 * D_STATE * di, nbytes)]


def selfcheck():
    """Hand counts at one small shape (tests/test_hybrid_cell.py runs
    this; `run.py --selfcheck` names its checks in a file this PR may
    not edit)."""
    s = {"vocab": 10, "dim": 16, "heads": 4, "kv_heads": 2, "layers": 4,
         "mlp_mult": 4, "window": 3}
    assert layer_counts(s) == {"mamba": 2, "window": 1, "full": 1,
                               "gmu": 0, "cross": 0}
    p = matmul_params(s)
    # d 16, m 64, di 32, rank 1, dh 4, kv 8
    assert p["mlp"] == 3 * 16 * 64 and p["head"] == 160
    assert p["mamba"] == 16 * 64 + 32 * 33 + 32 + 32 * 16
    assert p["window"] == p["full"] == 16 * 32 + 256
    tok = (2 * (4 * 3072 + 2 * p["mamba"] + 2 * 768)
           + 2 * 32 * (8 + 96))
    assert _token_flops(s) == tok
    # context 5: the window layer reads 3 positions, the full layer 5
    assert decode_flops(s, 5) == tok + 320 + 6 * 16 * 3 + 6 * 16 * 5
    # 4 rows: banded 1 + 2 + 3 + 3, causal 1 + 2 + 3 + 4
    assert prefill_flops(s, 4) == 4 * tok + 320 + 96 * 9 + 96 * 10
    # contexts 2 and 20, 16-token blocks: the window reads 2 and 3
    # positions (one block each), the full layer one block and two
    (cw, fw, bw), (cf, ff, bf) = hybrid_decode_attention_cost(s, [2, 20], 16)
    assert (cw, cf) == (1, 1)
    assert fw == 96 * 5 and ff == 96 * 22
    assert bw == 32 * 32 + 2 * 48 * 2 and bf == 48 * 32 + 2 * 48 * 2
    ((calls, fl, by),) = ssm_state_update_cost(s, [2, 20], 16)
    assert calls == 2 and fl == 2 * 6 * 16 * 32
    assert by == 2 * (2 * 2048 + 3 * 128 + 128) + 2048
    return True
