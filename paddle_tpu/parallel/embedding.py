"""Sharded embedding tables: the TPU-native sparse/large-model path.

Replaces the reference's row-sharded sparse parameter-server design
(SURVEY.md §2.2 sparse row: SparseRemoteParameterUpdater, prefetch of
needed rows MultiGradientMachine.h:140-166, fluid SelectedRows +
split/sum ops, design doc large_model_dist_train.md): the table lives
row-sharded across a mesh axis; lookup is a local gather of in-range rows
plus one `psum` over the axis (each id's row lives on exactly one shard),
and the backward pass is the transpose — a local scatter-add of exactly
the rows each shard owns. No parameter server, no prefetch protocol; ICI
does the work.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


__all__ = ["sharded_lookup", "ShardedEmbedding"]


def _lookup_shard(table, ids, axis_name: str):
    """Inside shard_map: table [V/n, D] local shard, ids [N] replicated."""
    me = lax.axis_index(axis_name)
    v_loc = table.shape[0]
    local = ids - me * v_loc
    in_range = jnp.logical_and(local >= 0, local < v_loc)
    safe = jnp.clip(local, 0, v_loc - 1)
    rows = jnp.where(in_range[:, None], table[safe], 0)
    return lax.psum(rows, axis_name)


def sharded_lookup(table, ids, mesh: Optional[Mesh] = None, axis: str = "model"):
    """Global-view lookup: `table` is [V, D] sharded rows-first over
    `axis`; `ids` any int array; returns ids.shape + [D]. Differentiable —
    the vjp scatter-adds each shard's own rows (deterministic, no
    pserver round trip)."""
    if mesh is None:
        from .mesh import get_default_mesh

        mesh = get_default_mesh()
    flat = ids.reshape(-1)
    if mesh is None or axis not in mesh.axis_names or mesh.shape[axis] == 1:
        # out-of-range ids yield zero rows, matching the sharded path
        # (where no shard claims them) instead of jax's gather clamping
        valid = jnp.logical_and(flat >= 0, flat < table.shape[0])
        out = jnp.where(
            valid[:, None], table[jnp.clip(flat, 0, table.shape[0] - 1)], 0
        )
    else:
        if table.shape[0] % mesh.shape[axis] != 0:
            raise ValueError(
                "vocab %d not divisible by mesh axis %r size %d"
                % (table.shape[0], axis, mesh.shape[axis])
            )
        out = shard_map(
            functools.partial(_lookup_shard, axis_name=axis),
            mesh=mesh,
            in_specs=(P(axis, None), P()),
            out_specs=P(),
        )(table, flat)
    return out.reshape(tuple(ids.shape) + (table.shape[1],))


class ShardedEmbedding(object):
    """Convenience owner of a row-sharded table (init + lookup + where to
    place the array)."""

    def __init__(self, vocab: int, dim: int, mesh: Mesh, axis: str = "model",
                 dtype=jnp.float32, scale: float = 0.01, key=None):
        self.mesh = mesh
        self.axis = axis
        key = key if key is not None else jax.random.PRNGKey(0)
        table = scale * jax.random.normal(key, (vocab, dim), dtype)
        self.sharding = NamedSharding(mesh, P(axis, None))
        self.table = jax.device_put(table, self.sharding)

    def __call__(self, ids):
        return sharded_lookup(self.table, ids, self.mesh, self.axis)
