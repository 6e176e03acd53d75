"""Pipeline parallelism: GPipe-style microbatch pipeline over a 'pipe'
mesh axis.

A NEW capability beyond the 2018 reference (SURVEY.md §2.2 lists PP as
absent; the nearest reference machinery is ParallelNeuralNetwork's
per-layer device threads, ParallelNeuralNetwork.h:34). TPU-first
re-design: every device holds ONE pipeline stage's parameters (stage
dim sharded over the axis), and activations flow stage-to-stage with a
single `lax.ppermute` hop per tick inside a `lax.scan` — the classic
shard_map pipeline. With M microbatches and S stages the schedule runs
M + S - 1 ticks; per-device memory is one microbatch, and the bubble
fraction is the usual (S-1)/(M+S-1).

The stage body must be shape-preserving ([mb, D] -> [mb, D]) so one
rotating buffer serves every stage. Differentiable end-to-end (ppermute
and scan both have transpose rules), so the same schedule backpropagates
as the reverse pipeline.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["gpipe_pipeline", "reference_pipeline"]


def reference_pipeline(stage_fn: Callable, stage_params, x):
    """Sequential oracle: fold x through every stage on one device.
    stage_params: pytree whose leaves have a leading stage dim [S, ...]."""
    S = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
    out = x
    for s in range(S):
        p_s = jax.tree_util.tree_map(lambda a: a[s], stage_params)
        out = stage_fn(p_s, out)
    return out


def _pipe_shard(stage_fn, params, x, axis_name: str, n_micro: int):
    """Per-device body: params = THIS device's stage params (leading
    stage dim already sharded away to size 1); x = full input, used only
    by stage 0."""
    S = lax.psum(1, axis_name)
    stage = lax.axis_index(axis_name)
    params = jax.tree_util.tree_map(lambda a: a[0], params)
    B, D = x.shape
    mb = B // n_micro
    micro = x.reshape(n_micro, mb, D)

    n_ticks = n_micro + S - 1
    state = jnp.zeros((mb, D), x.dtype)
    outs = jnp.zeros((n_micro, mb, D), x.dtype)
    # the carry becomes device-varying after one tick; mark the zero
    # initials as varying so scan's carry types line up
    state = lax.pcast(state, (axis_name,), to="varying")
    outs = lax.pcast(outs, (axis_name,), to="varying")

    def tick(carry, t):
        state, outs = carry
        # stage 0 injects microbatch t (older ticks already flowed on)
        inject = micro[jnp.clip(t, 0, n_micro - 1)]
        state = jnp.where((stage == 0) & (t < n_micro), inject, state)
        state = stage_fn(params, state)
        # last stage banks microbatch t-(S-1) as it completes
        done_idx = t - (S - 1)
        outs = jnp.where(
            (stage == S - 1) & (done_idx >= 0),
            outs.at[jnp.clip(done_idx, 0, n_micro - 1)].set(state),
            outs,
        )
        # rotate: stage s -> s+1 (last stage's send is ignored by 0)
        state = lax.ppermute(
            state, axis_name,
            [(i, (i + 1) % S) for i in range(S)],
        )
        return (state, outs), None

    (_, outs), _ = lax.scan(tick, (state, outs), jnp.arange(n_ticks))
    # only the last stage holds real outputs; replicate via psum
    outs = jnp.where(stage == S - 1, outs, 0.0)
    outs = lax.psum(outs, axis_name)
    return outs.reshape(B, D)


def gpipe_pipeline(stage_fn: Callable, stage_params, x, mesh: Mesh,
                   axis: str = "pipe", n_microbatches: int = 4):
    """Run x through S pipeline stages sharded over `axis`.

    stage_fn(params_one_stage, x_mb) -> y_mb, shape-preserving.
    stage_params: pytree with leading stage dim S == mesh.shape[axis].
    x: [B, D] with B divisible by n_microbatches. Returns [B, D],
    replicated over the axis.
    """
    S = mesh.shape[axis]
    leaves = jax.tree_util.tree_leaves(stage_params)
    if not leaves or leaves[0].shape[0] != S:
        raise ValueError(
            "stage_params leading dim must equal the '%s' axis size %d"
            % (axis, S)
        )
    if x.shape[0] % n_microbatches:
        raise ValueError("batch %d must divide into %d microbatches"
                         % (x.shape[0], n_microbatches))

    param_specs = jax.tree_util.tree_map(
        lambda a: P(axis, *([None] * (a.ndim - 1))), stage_params
    )
    fn = shard_map(
        lambda p, xx: _pipe_shard(stage_fn, p, xx, axis_name=axis,
                                  n_micro=n_microbatches),
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
    )
    return fn(stage_params, x)
