"""Expert parallelism: Switch-style mixture-of-experts FFN with
all-to-all token dispatch over an 'expert' mesh axis.

A NEW capability beyond the 2018 reference (SURVEY.md §2.2 lists EP as
absent), first-class here because expert sharding shapes the collective
layout the same way data/tensor/sequence sharding do: experts live one
(or more) per device on the 'expert' axis, tokens are sharded over the
same axis, and two `lax.all_to_all` hops (dispatch + return) ride ICI.

Design (Switch Transformer routing, top-1):
  * gate: logits = x @ gate_w, expert = argmax, prob = softmax max —
    the token's output is scaled by its gate probability so the router
    receives gradient.
  * dispatch: each shard builds an [E, C, D] buffer (C = per-shard
    per-expert capacity); position-in-expert beyond C drops the token
    (standard capacity truncation — dropped tokens pass through with
    zero expert output).
  * all_to_all swaps the E axis for the shard axis: each device then
    holds every shard's buffer for ITS expert(s), runs the expert FFN
    on one dense [n*C, D] block (MXU-friendly), and the reverse
    all_to_all returns results to the token owners.

Everything runs inside `shard_map`; the routing one-hots are plain
matmuls/segment ops so the whole layer is differentiable (routing
indices are argmax — non-differentiable by construction, as in the
reference Switch formulation; the gate gets gradient through the
probability scaling).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


__all__ = ["expert_parallel_moe", "reference_moe", "moe_capacity"]


def moe_capacity(n_tokens_per_shard: int, n_experts: int,
                 capacity_factor: float = 1.25) -> int:
    """Per-shard per-expert slot count (Switch capacity rule)."""
    return max(1, int(math.ceil(
        n_tokens_per_shard / n_experts * capacity_factor)))


def _expert_ffn(x, w1, b1, w2, b2):
    return jnp.maximum(x @ w1 + b1, 0.0) @ w2 + b2


def reference_moe(x, gate_w, w1, b1, w2, b2):
    """Single-device oracle: every token goes to its argmax expert (no
    all-to-all, no capacity truncation), output scaled by the gate
    probability. With ample capacity the sharded path reproduces this
    exactly; under truncation only the sharded path drops tokens.

    x: [N, D]; gate_w: [D, E]; w1: [E, D, H]; b1: [E, H];
    w2: [E, H, D]; b2: [E, D].
    """
    logits = x @ gate_w  # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(logits, axis=-1)  # [N]
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
    outs = jax.vmap(_expert_ffn, in_axes=(None, 0, 0, 0, 0))(
        x, w1, b1, w2, b2
    )  # [E, N, D]
    picked = jnp.take_along_axis(
        outs, expert[None, :, None], axis=0
    )[0]  # [N, D]
    return picked * gate[:, None]


def _moe_shard(x, gate_w, w1, b1, w2, b2, axis_name: str, capacity: int):
    """Per-shard body under shard_map: x [n_local, D]; this device owns
    experts [e0, e0+e_local) where e_local = E // n_shards."""
    n_shards = lax.psum(1, axis_name)
    E = gate_w.shape[1]
    e_local = E // n_shards
    n_local, D = x.shape

    logits = x @ gate_w
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(logits, axis=-1)  # [n_local]
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]

    # position of each token within its expert's local queue
    onehot = jax.nn.one_hot(expert, E, dtype=x.dtype)  # [n_local, E]
    pos = (jnp.cumsum(onehot, axis=0) - 1.0)  # [n_local, E]
    pos_in_e = jnp.take_along_axis(pos, expert[:, None], axis=1)[:, 0]
    keep = pos_in_e < capacity
    slot = jnp.clip(pos_in_e.astype(jnp.int32), 0, capacity - 1)

    # dispatch buffer [E, C, D]: scatter kept tokens into their slot
    dispatch = jnp.zeros((E, capacity, D), x.dtype)
    dispatch = dispatch.at[expert, slot].add(
        jnp.where(keep[:, None], x, 0.0)
    )
    # group E as [n_shards, e_local, C, D] and swap shard <-> expert-group
    dispatch = dispatch.reshape(n_shards, e_local, capacity, D)
    recv = lax.all_to_all(
        dispatch, axis_name, split_axis=0, concat_axis=0, tiled=False
    )  # [n_shards, e_local, C, D]: peer s's tokens for my experts

    # expert params arrive SHARDED over the axis: [e_local, ...] locally
    my_w1, my_b1, my_w2, my_b2 = w1, b1, w2, b2

    def one_expert(tokens, w1e, b1e, w2e, b2e):
        # tokens [n_shards, C, D] -> one dense FFN block
        flat = tokens.reshape(-1, D)
        return _expert_ffn(flat, w1e, b1e, w2e, b2e).reshape(tokens.shape)

    recv_e = jnp.swapaxes(recv, 0, 1)  # [e_local, n_shards, C, D]
    out_e = jax.vmap(one_expert)(recv_e, my_w1, my_b1, my_w2, my_b2)
    out = jnp.swapaxes(out_e, 0, 1)  # [n_shards, e_local, C, D]

    back = lax.all_to_all(
        out, axis_name, split_axis=0, concat_axis=0, tiled=False
    ).reshape(E, capacity, D)
    # gather each token's result from its (expert, slot) cell
    y = back[expert, slot]  # [n_local, D]
    y = jnp.where(keep[:, None], y, 0.0)
    return y * gate[:, None]


def expert_parallel_moe(x, gate_w, w1, b1, w2, b2, mesh: Mesh,
                        axis: str = "expert",
                        capacity_factor: float = 1.25,
                        capacity: Optional[int] = None):
    """Top-1 MoE FFN with experts sharded over `axis`.

    x: [N, D] tokens, sharded over `axis` on dim 0 (N divisible by the
    axis size). Expert params are sharded over their leading E dim.
    Returns [N, D] with the same sharding as x.
    """
    n_shards = mesh.shape[axis]
    E = gate_w.shape[1]
    if E % n_shards:
        raise ValueError("n_experts %d must divide over %d shards"
                         % (E, n_shards))
    if x.shape[0] % n_shards:
        raise ValueError("token count %d must divide over %d shards"
                         % (x.shape[0], n_shards))
    if capacity is None:
        capacity = moe_capacity(x.shape[0] // n_shards, E, capacity_factor)

    fn = shard_map(
        lambda *a: _moe_shard(*a, axis_name=axis, capacity=capacity),
        mesh=mesh,
        in_specs=(P(axis, None), P(), P(axis, None, None), P(axis, None),
                  P(axis, None, None), P(axis, None)),
        out_specs=P(axis, None),
    )
    return fn(x, gate_w, w1, b1, w2, b2)
