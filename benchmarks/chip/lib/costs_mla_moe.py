"""Operations and bytes the latent-attention sparse-expert model needs,
from shapes alone, in the form each path computes.

Useful work only, as in `costs.py`: padded rows of a prefill bucket,
dead slots of a decode batch, a row tile's padding, a latent row's
padding lanes and recomputed operations do not count; a multiply-add
is two operations. `shape` is the configuration's "shape" group:
vocab, dim, heads, nope_dim, rope_dim, v_dim, kv_rank, layers,
num_dense_layers, dense_width, expert_width, n_shared_experts,
n_experts, top_k, experts_held.

A token passes through the shared expert and the routed experts of
THIS chip that it is routed to: top_k x held / n_experts of them on
average (the chip's share of an expert-parallel layer; the other
chips' experts are not this chip's work). The decode step attends in
the ABSORBED form: a position costs 2 x heads x (kv_rank + rope) for
the scores and 2 x heads x kv_rank for the fold into the latent, and
the absorption's two products (W_kvb's halves, before and after) are
matrix parameters like any other; a prefill chunk attends in the
EXPANDED form: W_kvb per chunk row, and 2 x heads x (nope + rope) +
2 x heads x v_dim an attended (query, key) pair.
"""

from __future__ import annotations


def layer_counts(shape):
    dense = int(shape["num_dense_layers"])
    return {"attention": int(shape["layers"]), "dense": dense,
            "expert": int(shape["layers"]) - dense}


def held_share(shape):
    lo, hi = shape.get("experts_held") or (0, shape["n_experts"])
    return (hi - lo) / shape["n_experts"]


def matmul_params(shape):
    """Matrix parameters one token passes through: an attention layer
    (W_q, W_kva, W_kvb, W_o), a dense FFN, ONE routed expert, the
    shared expert, the router, the head."""
    d, H = shape["dim"], shape["heads"]
    dn, dr, dv, r = (shape["nope_dim"], shape["rope_dim"], shape["v_dim"],
                     shape["kv_rank"])
    return {"attention": (d * H * (dn + dr) + d * (r + dr)
                          + r * H * (dn + dv) + H * dv * d),
            "dense": 3 * d * shape["dense_width"],
            "expert": 3 * d * shape["expert_width"],
            "shared": 3 * d * shape["n_shared_experts"]
            * shape["expert_width"],
            "router": d * shape["n_experts"],
            "head": shape["vocab"] * d}


def active_params(shape):
    """Matrix parameters ONE token is multiplied by on this chip,
    embedding row aside: in an expert layer the router, the shared
    expert and top_k x held / n_experts routed experts."""
    n, p = layer_counts(shape), matmul_params(shape)
    routed = shape["top_k"] * held_share(shape) * p["expert"]
    return (n["attention"] * p["attention"] + n["dense"] * p["dense"]
            + n["expert"] * (routed + p["shared"] + p["router"])
            + p["head"])


def _absorbed_flops(shape, attended):
    """Scores over kv_rank + rope and the fold into kv_rank, every
    query head, an attended position."""
    H, r = shape["heads"], shape["kv_rank"]
    return 2 * H * (r + shape["rope_dim"] + r) * attended


def _expanded_flops(shape, pairs):
    """QK^T over nope + rope and PV over v_dim, every head, a pair."""
    H = shape["heads"]
    return 2 * H * (shape["nope_dim"] + shape["rope_dim"]
                    + shape["v_dim"]) * pairs


def decode_flops(shape, context):
    """One generated token whose query attends over `context` cached
    positions (its own included) in every layer, absorbed."""
    n = layer_counts(shape)
    return (2 * active_params(shape)
            + n["attention"] * _absorbed_flops(shape, context))


def prefill_flops(shape, tokens):
    """`tokens` prompt rows from position 0, expanded, causal; the head
    for the last row only (the served path samples one token per
    prompt)."""
    n, p = layer_counts(shape), matmul_params(shape)
    causal = tokens * (tokens + 1) // 2
    return (2 * tokens * (active_params(shape) - p["head"]) + 2 * p["head"]
            + n["attention"] * _expanded_flops(shape, causal))


def mla_decode_attention_cost(shape, contexts, block_tokens):
    """The decode step's latent attention calls over the live slots, in
    the bf16 the cell serves -> [(calls a step, flops, bytes of one
    call)]: one call a layer over the whole context in whole blocks. A
    position is ONE latent row, kv_rank + rope bf16 (1,152 B at the
    published widths), read once for both products — a layout that
    pads the row stores more, and shows as a lost share, not as a
    higher count; q is heads x (kv_rank + rope) in and heads x kv_rank
    out, a slot."""
    H, r, dr = shape["heads"], shape["kv_rank"], shape["rope_dim"]
    named = sum(-(-c // block_tokens) * block_tokens for c in contexts)
    io = len(contexts) * H * (r + dr + r) * 2
    return [(layer_counts(shape)["attention"],
             sum(_absorbed_flops(shape, c) for c in contexts),
             named * (r + dr) * 2 + io)]


def selfcheck():
    """Hand counts at one small shape (benchmarks/chip/tests runs this;
    `run.py --selfcheck` names its checks in a file this PR may not
    edit)."""
    s = {"vocab": 10, "dim": 16, "heads": 2, "nope_dim": 4, "rope_dim": 2,
         "v_dim": 3, "kv_rank": 8, "layers": 3, "num_dense_layers": 1,
         "dense_width": 20, "expert_width": 5, "n_shared_experts": 2,
         "n_experts": 8, "top_k": 4, "experts_held": [0, 2]}
    assert layer_counts(s) == {"attention": 3, "dense": 1, "expert": 2}
    assert held_share(s) == 0.25
    p = matmul_params(s)
    # W_q 16 x 2 x 6, W_kva 16 x 10, W_kvb 8 x 2 x 7, W_o 6 x 16
    assert p["attention"] == 192 + 160 + 112 + 96
    assert p["dense"] == 960 and p["expert"] == 240 and p["shared"] == 480
    assert p["router"] == 128 and p["head"] == 160
    # three attention layers, one dense FFN, two expert layers of
    # 4 x 2/8 = 1 routed expert + the shared one + the router, the head
    act = 3 * 560 + 960 + 2 * (240 + 480 + 128) + 160
    assert active_params(s) == act
    # context 9: 2 heads x (8 + 2 + 8) x 2 = 72 a position, 3 layers
    assert decode_flops(s, 9) == 2 * act + 3 * 72 * 9
    # 6 rows: 21 pairs of 2 heads x (4 + 2 + 3) x 2 = 36
    assert prefill_flops(s, 6) == (6 * 2 * (act - 160) + 320
                                   + 3 * 36 * 21)
    # contexts 2 and 20 in 4-token blocks: 4 + 20 positions of
    # (8 + 2) x 2 B = 20 B; q in 2 x 10 and out 2 x 8 bf16 a slot
    ((calls, fl, by),) = mla_decode_attention_cost(s, [2, 20], 4)
    assert calls == 3 and fl == 72 * 22
    assert by == 24 * 20 + 2 * 2 * 18 * 2
    # the published widths: 576 bf16 = 1,152 B a position
    pub = dict(s, heads=32, nope_dim=128, rope_dim=64, v_dim=128,
               kv_rank=512)
    ((_, _, one),) = mla_decode_attention_cost(pub, [32], 32)
    assert one == 32 * 1152 + 32 * (576 + 512) * 2
    return True
