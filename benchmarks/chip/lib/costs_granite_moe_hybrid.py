"""Operations and bytes the Mamba-2 / grouped-query hybrid WITH routed
experts needs (granite-4.0-h-small's block), from shapes alone.

Useful work only, as in `costs.py`: padded rows of a prefill bucket,
dead slots of a decode batch, a row tile's padding and recomputed
operations do not count; a multiply-add is two operations. `shape` is
the configuration's "shape" group: vocab, dim, heads, kv_heads,
head_dim, layer_types, mlp_width, n_experts, top_k, expert_width,
experts_held, mamba_heads, mamba_head_dim, d_state, d_conv, chunk.

The mixers cost what `costs_granite_hybrid.py` counts for them (its
decode-call costs, `gqa_decode_attention_cost` and
`ssd_state_update_cost`, read this shape as they read h-micro's). The
FFN of EVERY layer is the router over all n_experts, the shared MLP
and the routed experts of THIS chip that a token is routed to: top_k x
held / n_experts of them on average (the chip's share of an
expert-parallel layer; the other chips' experts are not this chip's
work). The grouped products' cost is the sparse-expert family's
(`costs_afmoe.moe_grouped_matmul_cost`, which `moe_expert_roofline`
reads in this cell too): the weights of the experts a step REACHED and
the routed rows in and out.
"""

from __future__ import annotations

from lib import costs_granite_hybrid as mixers

_attn_flops = mixers._attn_flops


def layer_counts(shape):
    n = mixers.layer_counts(shape)
    return dict(n, expert=len(shape["layer_types"]))


def held_share(shape):
    lo, hi = shape.get("experts_held") or (0, shape["n_experts"])
    return (hi - lo) / shape["n_experts"]


def matmul_params(shape):
    """Matrix parameters one token passes through: a Mamba-2 layer's
    mixer, an attention layer's, the shared MLP, ONE routed expert,
    the router, the head."""
    p = mixers.matmul_params(dict(shape, mlp_mult=0))
    d = shape["dim"]
    return {"mamba": p["mamba"], "attention": p["attention"],
            "shared": 3 * d * shape["mlp_width"],
            "expert": 3 * d * shape["expert_width"],
            "router": d * shape["n_experts"], "head": p["head"]}


def active_params(shape):
    """Matrix parameters ONE token is multiplied by on this chip,
    embedding row aside."""
    n, p = layer_counts(shape), matmul_params(shape)
    routed = shape["top_k"] * held_share(shape) * p["expert"]
    return (n["mamba"] * p["mamba"] + n["attention"] * p["attention"]
            + n["expert"] * (routed + p["shared"] + p["router"])
            + p["head"])


def _token_flops(shape):
    """Everything of one token but the attention reads and the head:
    the matrices, the conv (2 d_conv a channel of xBC) and the
    recurrence (6 a state element)."""
    n = layer_counts(shape)
    di = shape["mamba_heads"] * shape["mamba_head_dim"]
    N = shape["d_state"]
    return (2 * (active_params(shape) - matmul_params(shape)["head"])
            + n["mamba"] * (2 * shape["d_conv"] * (di + 2 * N) + 6 * N * di))


def decode_flops(shape, context):
    """One generated token whose query attends over `context` cached
    positions (its own included) in every attention layer."""
    return (_token_flops(shape) + 2 * matmul_params(shape)["head"]
            + layer_counts(shape)["attention"]
            * _attn_flops(shape, context))


def prefill_flops(shape, tokens):
    """`tokens` prompt rows from position 0, the head for the last row
    only (the served path samples one token per prompt)."""
    causal = tokens * (tokens + 1) // 2
    return (tokens * _token_flops(shape) + 2 * matmul_params(shape)["head"]
            + layer_counts(shape)["attention"]
            * _attn_flops(shape, causal))


def selfcheck():
    """Hand counts at one small shape, and the published one
    (benchmarks/chip/tests runs this; `run.py --selfcheck` names its
    checks in a file this PR may not edit)."""
    s = {"vocab": 10, "dim": 16, "heads": 4, "kv_heads": 2, "head_dim": 8,
         "layer_types": ["mamba", "mamba", "attention"], "mlp_width": 12,
         "n_experts": 8, "top_k": 2, "expert_width": 4,
         "experts_held": [0, 4], "mamba_heads": 4, "mamba_head_dim": 8,
         "d_state": 16, "d_conv": 4, "chunk": 4}
    assert layer_counts(s) == {"mamba": 2, "attention": 1, "expert": 3}
    assert held_share(s) == 0.5
    p = matmul_params(s)
    # d 16, di 32, N 16, H 4: in_proj 16 x (64 + 32 + 4), out 32 x 16
    assert p["mamba"] == 16 * 100 + 512
    assert p["attention"] == 16 * (4 + 4) * 8 + 32 * 16
    assert p["shared"] == 3 * 16 * 12 and p["expert"] == 3 * 16 * 4
    assert p["router"] == 128 and p["head"] == 160
    # 2 x 1/2 = one routed expert a layer, the shared MLP, the router
    act = 2 * 2112 + 1536 + 3 * (192 + 576 + 128) + 160
    assert active_params(s) == act
    tok = 2 * (act - 160) + 2 * (2 * 4 * 64 + 6 * 16 * 32)
    # context 5: 4 heads x 8 wide, QK^T and PV, one attention layer
    assert decode_flops(s, 5) == tok + 320 + 4 * 32 * 5
    # 4 rows: causal 1 + 2 + 3 + 4
    assert prefill_flops(s, 4) == 4 * tok + 320 + 128 * 10
    # the published widths: an expert is 9,437,184 weights
    pub = dict(s, dim=4096, expert_width=768, mlp_width=1536, n_experts=72,
               heads=32, kv_heads=8, head_dim=128, mamba_heads=128,
               mamba_head_dim=64, d_state=128)
    pp = matmul_params(pub)
    assert pp["expert"] == 9_437_184 and pp["shared"] == 18_874_368
    assert pp["router"] == 294_912
    assert pp["mamba"] == 4096 * 16768 + 8192 * 4096
    assert pp["attention"] == 4096 * 6144 + 4096 * 4096
    return True
