"""Operations and bytes the sparse-expert model needs, from shapes
alone.

Useful work only, as in `costs.py`: padded rows of a prefill bucket,
dead slots of a decode batch, a row tile's padding and recomputed
operations do not count; a multiply-add is two operations. A token
passes through the experts it is ROUTED to and the shared one — top_k +
1 of them, not n_experts. `shape` is the configuration's "shape" group:
vocab, dim, heads, kv_heads, head_dim, layer_types, num_dense_layers,
dense_width, expert_width, n_experts, top_k, window.
"""

from __future__ import annotations


def layer_counts(shape):
    kinds = list(shape["layer_types"])
    dense = int(shape["num_dense_layers"])
    return {"window": kinds.count("sliding_attention"),
            "full": kinds.count("full_attention"),
            "dense": dense, "expert": len(kinds) - dense}


def matmul_params(shape):
    """Matrix parameters one token passes through: an attention layer
    (q, k, v, the gate and the output projection), a dense FFN, ONE
    expert (routed or shared: the same three matrices), the router,
    the head."""
    d, dh = shape["dim"], shape["head_dim"]
    nq, nk = shape["heads"] * dh, shape["kv_heads"] * dh
    return {"attention": d * (2 * nq + 2 * nk) + nq * d,
            "dense": 3 * d * shape["dense_width"],
            "expert": 3 * d * shape["expert_width"],
            "router": d * shape["n_experts"],
            "head": shape["vocab"] * d}


def active_params(shape):
    """Matrix parameters ONE token is multiplied by, embedding row
    aside: top_k routed experts and the shared one in an expert layer."""
    n, p = layer_counts(shape), matmul_params(shape)
    return ((n["window"] + n["full"]) * p["attention"]
            + n["dense"] * p["dense"]
            + n["expert"] * ((shape["top_k"] + 1) * p["expert"]
                             + p["router"])
            + p["head"])


def _attn_flops(shape, attended):
    """QK^T and PV over head_dim, every query head: 4 heads head_dim an
    attended position."""
    return 4 * shape["heads"] * shape["head_dim"] * attended


def decode_flops(shape, context):
    """One generated token whose query attends over `context` cached
    positions (its own included) in a full layer, over the last
    `window` of them in a window layer."""
    n = layer_counts(shape)
    return (2 * active_params(shape)
            + n["full"] * _attn_flops(shape, context)
            + n["window"] * _attn_flops(shape, min(context,
                                                   shape["window"])))


def prefill_flops(shape, tokens):
    """`tokens` prompt rows from position 0, the head for the last row
    only (the served path samples one token per prompt); row i of a
    window layer attends min(i + 1, window) positions."""
    n, W = layer_counts(shape), shape["window"]
    causal = tokens * (tokens + 1) // 2
    ramp = min(tokens, W)
    banded = ramp * (ramp + 1) // 2 + (tokens - ramp) * W
    p = matmul_params(shape)
    return (2 * tokens * (active_params(shape) - p["head"]) + 2 * p["head"]
            + n["full"] * _attn_flops(shape, causal)
            + n["window"] * _attn_flops(shape, banded))


def swa_decode_attention_cost(shape, contexts, block_tokens):
    """The decode step's attention calls over the live slots, in the
    bf16 the cell serves -> [(calls a step, flops, bytes of one call)]:
    one call a window layer over the last min(context, window)
    positions of its own pool, one a full layer over the whole
    context, both in whole blocks. A position is kv_heads x head_dim of
    K and as much of V; q is heads x head_dim in and as much out, a
    slot."""
    n, W = layer_counts(shape), shape["window"]
    row = 2 * shape["kv_heads"] * shape["head_dim"] * 2
    io = len(contexts) * 2 * shape["heads"] * shape["head_dim"] * 2

    def named(cs):
        return sum(-(-c // block_tokens) * block_tokens for c in cs)

    short = [min(c, W) for c in contexts]
    return [(n["window"], sum(_attn_flops(shape, c) for c in short),
             named(short) * row + io),
            (n["full"], sum(_attn_flops(shape, c) for c in contexts),
             named(contexts) * row + io)]


def moe_grouped_matmul_cost(shape, rows, experts_hit):
    """The two grouped products of the expert layers of ONE step ->
    (flops, bytes): `rows` (token, choice) pairs summed over the expert
    layers, `experts_hit` the experts they reached, summed likewise
    (the engine's counter: never all that are held — where routing
    concentrates, the weights of experts nobody reached are not read).
    An expert is three d x expert_width matrices in bf16; a row goes in
    as d bf16, leaves the gate-up product as 2 x expert_width bf16,
    comes back as expert_width bf16 and leaves as d float32."""
    d, m = shape["dim"], shape["expert_width"]
    flops = 2 * rows * 3 * d * m
    nbytes = experts_hit * 3 * d * m * 2 + rows * ((d + 3 * m) * 2 + d * 4)
    return flops, nbytes


def selfcheck():
    """Hand counts at one small shape (benchmarks/chip/tests runs this;
    `run.py --selfcheck` names its checks in a file this PR may not
    edit)."""
    s = {"vocab": 10, "dim": 16, "heads": 4, "kv_heads": 2, "head_dim": 8,
         "layer_types": ["sliding_attention", "full_attention",
                         "sliding_attention"],
         "num_dense_layers": 1, "dense_width": 48, "expert_width": 8,
         "n_experts": 6, "top_k": 2, "window": 4}
    assert layer_counts(s) == {"window": 2, "full": 1, "dense": 1,
                               "expert": 2}
    p = matmul_params(s)
    # q and gate 32 wide, k and v 16: 16 x 96 in, 32 x 16 out
    assert p["attention"] == 16 * 96 + 512 and p["dense"] == 3 * 16 * 48
    assert p["expert"] == 384 and p["router"] == 96 and p["head"] == 160
    # three attention layers, one dense FFN, two expert layers of
    # 2 routed + 1 shared experts and the router, the head
    act = 3 * 2048 + 2304 + 2 * (3 * 384 + 96) + 160
    assert active_params(s) == act
    # context 9: the full layer attends 9, each window layer 4
    assert decode_flops(s, 9) == 2 * act + 128 * 9 + 2 * 128 * 4
    # 6 rows: causal 21; banded 1 + 2 + 3 + 4 + 4 + 4 = 18
    assert prefill_flops(s, 6) == (6 * 2 * (act - 160) + 320 + 128 * 21
                                   + 2 * 128 * 18)
    # contexts 2 and 20, 4-token blocks, window 4: a position is
    # 2 x 2 x 8 x 2 B = 64 B; q and out 2 slots x 2 x 32 x 2 B = 256
    (wc, wf, wb), (fc, ff, fb) = swa_decode_attention_cost(s, [2, 20], 4)
    assert (wc, fc) == (2, 1)
    assert wf == 128 * (2 + 4) and wb == (4 + 4) * 64 + 256
    assert ff == 128 * 22 and fb == (4 + 20) * 64 + 256
    # 8 rows reached 5 experts: 2 x 8 x 384 multiply-adds; 5 experts of
    # 384 bf16 weights; a row 16 + 24 bf16 and 16 float32
    fl, by = moe_grouped_matmul_cost(s, 8, 5)
    assert fl == 2 * 8 * 384 and by == 5 * 768 + 8 * (80 + 64)
    return True
