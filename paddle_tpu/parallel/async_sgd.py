"""AsyncSGD -> local-SGD: the TPU-native redesign of asynchronous DP.

The reference's asynchronous data parallelism applies each trainer's
gradient to the shared parameters without waiting for the others (C++
pserver per-block async updates, ParameterServer2.h:127 + the AsyncSGD
algorithm setting in TrainerConfig.proto OptimizationConfig; the Go
pserver is async per gradient, go/pserver/service.go:285 SendGrad). The
statistical trade is staleness for communication: each replica trains on
parameters that miss the other replicas' in-flight updates.

A TPU SPMD step is globally synchronous by construction, so the redesign
expresses the same trade natively as **local SGD** (periodic model
averaging): every 'data'-axis replica keeps its OWN parameter + optimizer
state copy and runs `sync_every` optimizer steps purely locally — zero
inter-chip traffic — then the replicas average their models (one pmean
over ICI per round). `sync_every=1` with a gradient-linear update rule
(SGD, momentum) is *mathematically identical* to the synchronous
allreduce step, which is this module's exactness oracle
(tests/test_async_local.py); larger `sync_every` is the async regime:
between syncs each replica's updates are invisible to the others —
bounded staleness in place of the pserver's unbounded race.

Entry point: `Executor.run_async_local(...)` (fluid/executor.py), reached
from the user surface via `DistributeTranspiler.transpile(sync_mode=
False)` — see fluid/distribute_transpiler.py.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _revary(v, axis):
    return lax.pcast(v, axis, to="varying")


def build_local_sgd_fn(
    step,
    mesh: Mesh,
    feed_names: Sequence[str],
    steps: int,
    sync_every: int,
    axis: str = "data",
):
    """Wrap a single-step program fn into a jittable local-SGD runner.

    `step`: (persist: dict, feeds: dict, key) -> (fetches, new_persist)
    as built by core.lowering.build_step_fn, with persist_out ==
    persist_in. Feeds must each carry a leading [steps] dim, then the
    global batch dim (sharded over `axis`). Returns
      fn(persist, feeds, key) -> (fetches stacked [steps, ...] and
      replica-averaged, consensus new_persist)
    Parameters enter and leave UNstacked (ordinary replicated arrays):
    the per-replica copies exist only inside the computation, and every
    round ends on a pmean, so the result is the consensus model.
    """
    if steps % sync_every:
        raise ValueError(
            "steps (%d) must be a multiple of sync_every (%d)"
            % (steps, sync_every)
        )
    rounds = steps // sync_every
    nrep = mesh.shape[axis]
    feed_specs = {n: P(None, axis) for n in feed_names}

    def body(persist, feeds, key):
        # inside shard_map: persist values arrive stacked [1, ...] (this
        # replica's copy), feeds [steps, B/nrep, ...]
        persist = {n: v[0] for n, v in persist.items()}
        key = jax.random.fold_in(key, lax.axis_index(axis))
        # [steps, ...] -> [rounds, sync_every, ...]
        feeds = {
            n: v.reshape((rounds, sync_every) + v.shape[1:])
            for n, v in feeds.items()
        }

        def round_body(carry, xs):
            i, per_round = xs

            def local_body(c, xs_local):
                j, f = xs_local
                fetches, newp = step(
                    c, f, jax.random.fold_in(key, i * sync_every + j)
                )
                return newp, fetches

            newp, fetch_stack = lax.scan(
                local_body, carry,
                (jnp.arange(sync_every), per_round),
            )
            # sync point: replicas average their models (the only
            # collective; everything above ran replica-local). pcast
            # re-tags the now-identical copies as axis-varying so the
            # scan carry type stays stable (shard_map VMA tracking)
            newp = {
                n: _revary(lax.pmean(v, axis), axis)
                for n, v in newp.items()
            }
            return newp, fetch_stack

        new_persist, fetches = lax.scan(
            round_body, persist, (jnp.arange(rounds), feeds)
        )
        # report the replica-mean of each per-step fetch (pre-sync local
        # losses differ across replicas)
        fetches = jax.tree_util.tree_map(
            lambda a: lax.pmean(
                a.reshape((steps,) + a.shape[2:]), axis
            ),
            fetches,
        )
        return fetches, {n: v[None] for n, v in new_persist.items()}

    def fn(persist: Dict[str, Any], feeds: Dict[str, Any], key):
        stacked = {
            n: jnp.broadcast_to(v, (nrep,) + jnp.shape(v))
            for n, v in persist.items()
        }
        in_specs = ({n: P(axis) for n in stacked}, feed_specs, P())
        out_specs = (P(), {n: P(axis) for n in stacked})
        fetches, newp = shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs
        )(stacked, feeds, key)
        # every round ends on a pmean, so the replica copies are equal:
        # keep replica 0 as the consensus model
        return fetches, {n: v[0] for n, v in newp.items()}

    return fn
