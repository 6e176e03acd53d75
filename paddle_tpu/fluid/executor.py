"""Executor: run a Program with feed/fetch, compiling whole blocks to XLA.

API parity with reference python/paddle/v2/fluid/executor.py (Executor:166,
run:221, global_scope:27, scope_guard:39, fetch_var:137) — but the engine
is different by design: instead of injecting feed/fetch ops and interpreting
op-by-op in C++ (reference executor.cc:80), `run` compiles the block ONCE
per (program-version, feed-signature) into a single XLA computation via
jax.jit with donated parameter buffers, then replays it. See
core/lowering.py for the story.
"""

from __future__ import annotations

import collections
import contextlib
import os
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import core
from .core.kernels_control import LOD_SRC
from .core.kernels_sequence import LOD_SUFFIX, bucket_pow2, lod_key
from .core.lowering import build_step_fn
from .core.program import Program, Variable


class _TensorView(object):
    """Minimal stand-in for the reference's LoDTensor handle returned by
    scope.find_var(name).get_tensor()."""

    def __init__(self, scope, name):
        self._scope = scope
        self._name = name

    def get_tensor(self):
        return self

    def __array__(self, dtype=None):
        arr = np.asarray(self._scope.get(self._name))
        return arr.astype(dtype) if dtype else arr

    def set(self, value, place=None):
        self._scope.set(self._name, np.asarray(value))

    def shape(self):
        return list(np.asarray(self).shape)


class Scope(object):
    """name -> device array storage for persistables (params, optimizer
    state, BN running stats). Replaces the reference's C++ Scope tree
    (framework/scope.h); no hierarchy is needed because non-persistable
    intermediates live only inside the traced computation."""

    def __init__(self):
        self._vars: Dict[str, Any] = {}

    def get(self, name):
        return self._vars[name]

    def set(self, name, value):
        self._vars[name] = value

    def __contains__(self, name):
        return name in self._vars

    def keys(self):
        return self._vars.keys()

    def drop(self, name):
        self._vars.pop(name, None)

    # reference-compatible surface
    def find_var(self, name):
        return _TensorView(self, name) if name in self._vars else None

    def var(self, name):
        self._vars.setdefault(name, None)
        return _TensorView(self, name)


_global_scope = Scope()
_current_scope = _global_scope


def global_scope() -> Scope:
    return _current_scope


def switch_scope(scope: Scope) -> Scope:
    global _current_scope
    prev, _current_scope = _current_scope, scope
    return prev


@contextlib.contextmanager
def scope_guard(scope: Scope):
    prev = switch_scope(scope)
    try:
        yield
    finally:
        switch_scope(prev)


def as_numpy(tensor):
    if isinstance(tensor, (list, tuple)):
        return [as_numpy(t) for t in tensor]
    return np.asarray(tensor)


def fetch_var(name, scope: Optional[Scope] = None, return_numpy: bool = True):
    scope = scope or global_scope()
    val = scope.get(name if isinstance(name, str) else name.name)
    return np.asarray(val) if return_numpy else val


def _feed_name(f):
    return f.name if isinstance(f, Variable) else str(f)


class CompileCache(object):
    """Bounded LRU over compiled step entries, keyed by (program,
    feed-signature, ...) tuples. A long-lived serving or supervisor
    process walks many shape buckets over its lifetime; the old
    unbounded dict grew a compiled XLA executable per signature forever.
    Capacity counts ENTRIES (signatures), not bytes — each entry pins
    one compiled executable. Hit/miss/eviction counters are exposed via
    Executor.cache_stats() so occupancy is observable, not guessed.

    get() returns None on miss (the dict.get contract every call site
    already uses) and refreshes recency on hit; insertion evicts the
    least-recently-used entry past capacity. An evicted signature is
    not an error — the next run recompiles, exactly like first contact.
    """

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            capacity = int(
                os.environ.get("PADDLE_TPU_EXECUTOR_CACHE_CAP", "64")
            )
        if capacity < 1:
            raise ValueError("cache capacity must be >= 1")
        self.capacity = capacity
        self._od: "collections.OrderedDict[Any, Any]" = (
            collections.OrderedDict()
        )
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key):
        try:
            entry = self._od[key]
        except KeyError:
            self.misses += 1
            return None
        self._od.move_to_end(key)
        self.hits += 1
        return entry

    def __setitem__(self, key, entry):
        self._od[key] = entry
        self._od.move_to_end(key)
        while len(self._od) > self.capacity:
            self._od.popitem(last=False)
            self.evictions += 1

    def __contains__(self, key):
        return key in self._od

    def __len__(self):
        return len(self._od)

    def clear(self):
        self._od.clear()

    def stats(self) -> Dict[str, int]:
        return {
            "size": len(self._od),
            "capacity": self.capacity,
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
        }


class Executor(object):
    """Single-chip by default. Pass `mesh=jax.sharding.Mesh(...)` (or set a
    default via paddle_tpu.parallel.set_default_mesh) to run data/tensor-
    parallel: feeds shard on the mesh 'data' axis, params place per
    program.shardings (replicated unless annotated), and XLA SPMD inserts
    the gradient allreduce over ICI — replacing the reference's
    MultiGradientMachine / NCCL / pserver paths with identical global-batch
    semantics."""

    def __init__(self, places=None, mesh=None, cache_capacity=None):
        if isinstance(places, (list, tuple)):
            places = places[0] if places else None
        self.place = places
        self.mesh = mesh
        # bounded LRU (PADDLE_TPU_EXECUTOR_CACHE_CAP, default 64): a
        # long-lived serving/supervisor process must not grow a compiled
        # executable per shape bucket without limit
        self._cache = CompileCache(cache_capacity)
        self._run_counter = 0
        # (jitted entry, arg avals, host-arg snapshot) of last run
        self._last_exec = None
        self._capture_avals = False  # set by profiler.compiled_profile

    def _resolve_mesh(self):
        if self.mesh is not None:
            return self.mesh
        from ..parallel.mesh import get_default_mesh

        return get_default_mesh()

    def _maybe_preflight(self, program, feed, fetch_list, force=False):
        """Program-verifier pre-flight shared by EVERY run entry point
        (run / run_repeated / run_grad_accum / run_async_local), so
        PADDLE_TPU_VALIDATE=1 means what it says regardless of which
        loop drives the program."""
        if force or os.environ.get(
                "PADDLE_TPU_VALIDATE", "") not in ("", "0"):
            from ..analysis.program_lint import preflight

            preflight(
                program if program is not None
                else core.default_main_program(),
                feeds=list(feed or ()),
                fetches=fetch_list or (),
            )

    # ------------------------------------------------------------------
    def run(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[List[Any]] = None,
        feed_var_name: str = "feed",
        fetch_var_name: str = "fetch",
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
        validate: bool = False,
    ):
        """`validate=True` (or env PADDLE_TPU_VALIDATE=1) runs the
        paddle_tpu.analysis program verifier as a pre-flight: a
        malformed program (dangling input, dtype clash, duplicate
        parameter, unpaired grad var) raises ProgramVerifyError with
        P-coded findings BEFORE lowering, instead of surfacing as a
        cryptic tracer error inside the compiled step. Memoized per
        (program version, feed/fetch signature), so a cached training
        loop pays one dict lookup per run."""
        self._maybe_preflight(program, feed, fetch_list, force=validate)
        return self._execute(
            program, feed, fetch_list, scope, return_numpy,
            use_cache=use_program_cache, steps=None, scan_feeds=False,
        )

    def run_repeated(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[List[Any]] = None,
        steps: int = 1,
        scan_feeds: bool = False,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
    ):
        """Run `steps` training iterations in ONE compiled computation
        (lax.scan) — the host leaves the step loop entirely. With
        scan_feeds=True every feed must carry a leading [steps] dim holding
        per-step batches (LoD side-bands are always broadcast); otherwise
        the same feed is reused each step. Fetches return stacked
        [steps, ...]."""
        self._maybe_preflight(program, feed, fetch_list)
        return self._execute(
            program, feed, fetch_list, scope, return_numpy,
            use_cache=True, steps=int(steps), scan_feeds=scan_feeds,
        )

    def run_grad_accum(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[List[Any]] = None,
        micro_batches: int = 2,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
    ):
        """ONE optimizer step over `micro_batches` forward/backward
        passes (gradient accumulation): the feed batch splits into
        equal chunks, a lax.scan accumulates the mean of chunk
        gradients, and the update applies once — activations live one
        micro-batch at a time, so the effective batch is bounded by
        step count, not HBM (core/lowering.py build_accum_step_fn).

        Exactness caveat: chunk gradients are AVERAGED, which matches
        the full-batch step only for mean-reduced losses. A sum-reduced
        loss trains with gradients scaled by 1/micro_batches (a warning
        fires when the loss producer is a detectable sum reduction)."""
        self._maybe_preflight(program, feed, fetch_list)
        from .core.lowering import build_accum_step_fn

        if self._resolve_mesh() is not None:
            raise NotImplementedError(
                "run_grad_accum is single-chip; compose large batches "
                "on a mesh with the data axis instead"
            )
        if program is None:
            program = core.default_main_program()
        feed = dict(feed or {})
        scope = scope or global_scope()
        block = program.global_block()
        fetch_names = [_feed_name(f) for f in fetch_list or []]
        persist_names = sorted(
            v.name for v in program.list_vars() if v.persistable
        )
        feed_arrays = {}
        for name, value in feed.items():
            var = block.var(name) if block.has_var(name) else None
            data, lod = _split_lod_feed(value)
            if lod is not None:
                raise NotImplementedError(
                    "gradient accumulation with ragged (LoD) feeds is "
                    "not supported"
                )
            feed_arrays[name] = _to_device_dtype(data, var)
        persist_in = {n: scope.get(n) for n in persist_names if n in scope}
        feed_sig = tuple(
            (n, tuple(a.shape), str(a.dtype))
            for n, a in sorted(feed_arrays.items())
        )
        key = (
            "grad_accum", program.uid, program.version, program.amp,
            program.remat, feed_sig, tuple(fetch_names),
            tuple(sorted(persist_in)), int(micro_batches),
        )
        entry = self._cache.get(key)
        if entry is None:
            fn, _ = build_accum_step_fn(
                program,
                feed_names=list(feed_arrays),
                fetch_names=fetch_names,
                persist_names=persist_names,
                micro_batches=int(micro_batches),
                persist_in=list(persist_in),
            )
            entry = jax.jit(fn, donate_argnums=(0,))
            self._cache[key] = entry
        self._run_counter += 1
        rng = jax.random.fold_in(
            jax.random.PRNGKey(program.random_seed), self._run_counter
        )
        fetches, new_persist = entry(persist_in, feed_arrays, rng)
        _flush_print_effects(program)
        return _finish_run(
            scope, fetch_names, fetches, new_persist, return_numpy
        )

    def run_async_local(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[List[Any]] = None,
        steps: int = 1,
        sync_every: int = 1,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
    ):
        """AsyncSGD equivalent (reference ParameterServer2.h:127 /
        go/pserver SendGrad): local-SGD redesign — every 'data'-axis
        replica trains its OWN parameter + optimizer-state copy for
        `sync_every` steps with zero inter-chip traffic, then replicas
        average their models (one pmean per round). See
        parallel/async_sgd.py for the semantics argument. Feeds must be
        dense arrays with a leading [steps] dim then the global batch
        dim; fetches return stacked [steps, ...], replica-averaged.
        Parameters land back in the scope as ordinary consensus arrays
        (checkpoint/save need no special handling)."""
        self._maybe_preflight(program, feed, fetch_list)
        from ..parallel.async_sgd import build_local_sgd_fn

        if program is None:
            program = core.default_main_program()
        scope = scope or global_scope()
        mesh = self._resolve_mesh()
        if mesh is None or "data" not in mesh.axis_names:
            raise ValueError(
                "run_async_local needs a mesh with a 'data' axis "
                "(Executor(mesh=...) or parallel.set_default_mesh)"
            )
        from ..parallel.mesh import spans_processes

        if spans_processes(mesh):
            raise NotImplementedError(
                "run_async_local is single-controller for now: feeds "
                "enter as whole global arrays, not per-process shards "
                "(the _globalize_feeds assembly the sync path does is "
                "not wired here yet)"
            )
        if program.shardings:
            raise ValueError(
                "run_async_local composes with data parallelism only; "
                "drop the tensor-parallel shard_parameter annotations "
                "(replicas must own complete models): %r"
                % sorted(program.shardings)
            )
        block = program.global_block()
        fetch_names = [_feed_name(f) for f in fetch_list or []]
        persist_names = sorted(
            v.name for v in program.list_vars() if v.persistable
        )
        feed_arrays: Dict[str, Any] = {}
        for name, value in (feed or {}).items():
            data, lod = _split_lod_feed(value)
            if lod is not None:
                raise NotImplementedError(
                    "run_async_local supports dense feeds only (LoD "
                    "batches change shape per step)"
                )
            var = block.var(name) if block.has_var(name) else None
            feed_arrays[name] = _to_device_dtype(data, var)
        persist_in = {n: scope.get(n) for n in persist_names if n in scope}

        feed_sig = tuple(
            (n, tuple(a.shape), str(a.dtype))
            for n, a in sorted(feed_arrays.items())
        )
        key = (
            "async_local", program.uid, program.version, program.amp,
            program.remat,
            feed_sig, tuple(fetch_names),
            tuple(sorted(persist_in.keys())),
            int(steps), int(sync_every), mesh,
        )
        entry = self._cache.get(key)
        if entry is None:
            step, persist_out = build_step_fn(
                program,
                feed_names=list(feed_arrays.keys()),
                fetch_names=fetch_names,
                persist_names=persist_names,
                persist_in=list(persist_in.keys()),
            )
            if set(persist_out) != set(persist_in.keys()):
                raise ValueError(
                    "run_async_local requires the program to update (not "
                    "create) persistables; missing from scope: %r"
                    % sorted(set(persist_out) - set(persist_in))
                )
            fn = build_local_sgd_fn(
                step, mesh,
                feed_names=list(feed_arrays.keys()),
                steps=int(steps), sync_every=int(sync_every),
            )
            entry = jax.jit(fn, donate_argnums=(0,))
            self._cache[key] = entry

        self._run_counter += 1
        rng = jax.random.fold_in(
            jax.random.PRNGKey(program.random_seed), self._run_counter
        )
        fetches, new_persist = entry(persist_in, feed_arrays, rng)
        _flush_print_effects(program)
        return _finish_run(
            scope, fetch_names, fetches, new_persist, return_numpy
        )

    # ------------------------------------------------------------------
    def _execute(
        self,
        program,
        feed,
        fetch_list,
        scope,
        return_numpy,
        use_cache: bool,
        steps: Optional[int],
        scan_feeds: bool,
    ):
        from .core.lowering import build_multi_step_fn

        if program is None:
            program = core.default_main_program()
        feed = feed or {}
        fetch_list = fetch_list or []
        scope = scope or global_scope()

        block = program.global_block()
        fetch_names = [_feed_name(f) for f in fetch_list]
        persist_names = sorted(v.name for v in program.list_vars() if v.persistable)

        feed_arrays: Dict[str, Any] = {}
        for name, value in feed.items():
            var = block.var(name) if block.has_var(name) else None
            data, lod = _split_lod_feed(value)
            feed_arrays[name] = _to_device_dtype(data, var)
            if lod is not None:
                # rows are described by the FINEST level; a coarser outer
                # level (2-level beam-search feeds) rides a second side-band
                feed_arrays[lod_key(name)] = np.asarray(lod[-1], np.int32)
                if len(lod) > 1:
                    feed_arrays[name + LOD_SRC] = np.asarray(lod[0], np.int32)
        # LoD side-band offsets are never scanned: their leading dim is the
        # offset count, not steps
        scanned = (
            set(n for n in feed_arrays if "@" not in n)
            if scan_feeds
            else set()
        )

        mesh = self._resolve_mesh()
        if mesh is not None:
            from ..parallel.mesh import spans_processes

            if spans_processes(mesh):
                feed_arrays = _globalize_feeds(mesh, feed_arrays, scanned)

        feed_sig = tuple(
            (n, tuple(a.shape), str(a.dtype)) for n, a in sorted(feed_arrays.items())
        )
        # static time extent for RNN padding: bucket the batch's true max
        # sequence length to a power of two so recompiles happen per bucket,
        # not per batch composition (kernels_rnn.py docstring). Per-feed
        # buckets let ops with very different raggedness (CTC frames vs
        # labels) each pad tightly.
        seq_maxlen, seq_buckets = _lod_bucket(feed_arrays)
        persist_in = {n: scope.get(n) for n in persist_names if n in scope}

        # profiler block active: interpret-mode timed run (per-op cost
        # table, reference profiler.cc:198 ParseEvents) — single-step,
        # single-chip only
        from .profiler import active_op_collector

        collector = active_op_collector()
        if collector is not None and steps is None and mesh is None:
            from .core.lowering import profile_ops

            self._run_counter += 1
            rng = jax.random.fold_in(
                jax.random.PRNGKey(program.random_seed), self._run_counter
            )
            env: Dict[str, Any] = {}
            env.update(persist_in)
            env.update(feed_arrays)
            fetches, new_persist = profile_ops(
                program, env, fetch_names, persist_names, collector,
                base_key=rng, seq_maxlen=seq_maxlen,
                seq_buckets=seq_buckets,
            )
            _flush_print_effects(program)
            return _finish_run(
                scope, fetch_names, fetches, new_persist, return_numpy
            )
        if mesh is not None:
            # place persistables on their target shardings up-front (no-op
            # when already placed; once after startup for TP params created
            # replicated by a startup program that has no annotations)
            from jax.sharding import NamedSharding

            from ..parallel.mesh import replicated

            rep = replicated(mesh)
            for n in list(persist_in.keys()):
                spec = program.shardings.get(n)
                target = NamedSharding(mesh, spec) if spec is not None else rep
                arr = persist_in[n]
                if getattr(arr, "sharding", None) != target:
                    persist_in[n] = jax.device_put(arr, target)
        # sharding annotations are part of the compiled artifact: fingerprint
        # them so shard_parameter() after a run is not silently ignored
        shard_fp = tuple(sorted((k, str(v)) for k, v in program.shardings.items()))
        key = (
            program.uid,
            program.version,
            program.amp,
            program.remat,
            feed_sig,
            tuple(fetch_names),
            tuple(sorted(persist_in.keys())),
            steps,
            scan_feeds,
            shard_fp,
            seq_maxlen,
            tuple(sorted(seq_buckets.items())),
        ) + ((mesh,) if mesh is not None else ())  # Mesh hashes by devices+axes
        entry = self._cache.get(key) if use_cache else None
        if entry is None:
            if steps is None:
                fn, persist_out = build_step_fn(
                    program,
                    feed_names=list(feed_arrays.keys()),
                    fetch_names=fetch_names,
                    persist_names=persist_names,
                    persist_in=list(persist_in.keys()),
                    seq_maxlen=seq_maxlen,
                    seq_buckets=seq_buckets,
                )
            else:
                fn, persist_out = build_multi_step_fn(
                    program,
                    feed_names=list(feed_arrays.keys()),
                    fetch_names=fetch_names,
                    persist_names=persist_names,
                    steps=steps,
                    persist_in=list(persist_in.keys()),
                    scanned_feeds=scanned,
                    seq_maxlen=seq_maxlen,
                    seq_buckets=seq_buckets,
                )
            jit_kwargs = {}
            if mesh is not None:
                jit_kwargs = _mesh_jit_kwargs(
                    mesh,
                    program,
                    feed_arrays,
                    list(persist_in.keys()),
                    persist_out,
                    fetch_names,
                    scanned_feeds=scanned,
                )
            entry = jax.jit(fn, donate_argnums=(0,), **jit_kwargs)
            if use_cache:
                self._cache[key] = entry

        self._run_counter += 1
        rng = jax.random.fold_in(
            jax.random.PRNGKey(program.random_seed), self._run_counter
        )
        # aval snapshot BEFORE the call (args are donated): lets the
        # compiled-step profiler re-lower this exact signature to read
        # the scheduled HLO. Gated — the tree_map over every param is
        # wasted work on ordinary training steps.
        if self._capture_avals:
            # host snapshot BEFORE the call (args are donated): lets the
            # compiled-step profiler rebuild fresh device args per timed
            # run and measure pure device time (ADVICE r4: exe.run()
            # end-to-end folds host feed/fetch overhead into op rows)
            host_snap = jax.tree_util.tree_map(
                lambda a: np.asarray(a) if hasattr(a, "shape") else a,
                (persist_in, feed_arrays, rng),
            )
            self._last_exec = (
                entry,
                jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(
                        getattr(a, "shape", ()), getattr(a, "dtype", None)
                    ),
                    host_snap,
                ),
                host_snap,
            )
        fetches, new_persist = entry(persist_in, feed_arrays, rng)
        _flush_print_effects(program)
        return _finish_run(
            scope, fetch_names, fetches, new_persist, return_numpy
        )

    # convenience used by inference/serving paths ----------------------
    def close(self):
        self._cache.clear()
        # the profiler's aval/host-arg snapshot pins a compiled entry
        # plus a full host copy of the params — the LRU bound must not
        # be exceeded by a stale capture after close
        self._last_exec = None

    def cache_stats(self) -> Dict[str, int]:
        """Compilation-cache occupancy counters: size/capacity/hits/
        misses/evictions (observability for long-lived processes)."""
        return self._cache.stats()


def _flush_print_effects(program):
    """If the program contains a print op, block on pending jax.debug
    callbacks so debug output lands before run() returns (they would
    otherwise be dropped at interpreter teardown). The answer is
    memoized ON the program (version-keyed, dies with it) — no per-step
    op scan and no global cache to leak."""
    memo = getattr(program, "_print_flag", None)
    if memo is None or memo[0] != program.version:
        flag = any(
            op.type == "print" for blk in program.blocks for op in blk.ops
        )
        program._print_flag = memo = (program.version, flag)
    if memo[1]:
        jax.effects_barrier()


def _finish_run(scope, fetch_names, fetches, new_persist, return_numpy):
    """Shared run tail: persist write-back, NaN guard, numpy conversion."""
    for n, v in new_persist.items():
        scope.set(n, v)
    _maybe_check_nan_inf(fetch_names, fetches, new_persist)
    if return_numpy:
        return [np.asarray(f) for f in fetches]
    return fetches


def _maybe_check_nan_inf(fetch_names, fetches, new_persist):
    """Opt-in runtime numerics guard: set PADDLE_TPU_CHECK_NUMERICS=1
    (or the legacy FLAGS.check_nan_inf / PADDLE_FLAG_CHECK_NAN_INF)
    and every run scans the step's fetches and updated persistables for
    NaN/Inf, raising FloatingPointError that NAMES each offending var
    and whether it was a fetch or a persistable — the runtime
    counterpart of the static pre-flight (`validate=True`). Reference
    parity: executor.cc:30,132-140 scanned every op output per step;
    the fused XLA step has no per-op boundary, so the scan runs on the
    step's outputs after each run. Off by default: the scan forces a
    device->host copy of every fetched/updated array."""
    from ..utils import FLAGS

    if not (FLAGS.check_nan_inf or os.environ.get(
            "PADDLE_TPU_CHECK_NUMERICS", "") not in ("", "0")):
        return
    bad = []
    for kind, pairs in (("fetch", list(zip(fetch_names, fetches))),
                        ("persistable", list(new_persist.items()))):
        for name, v in pairs:
            arr = np.asarray(v)
            if (np.issubdtype(arr.dtype, np.floating)
                    and not np.isfinite(arr).all()):
                n_bad = int(arr.size - np.isfinite(arr).sum())
                bad.append("%s %r (%d/%d non-finite)"
                           % (kind, name, n_bad, arr.size))
    if bad:
        raise FloatingPointError(
            "check_numerics: non-finite values in %s" % "; ".join(bad)
        )


def _lod_bucket(feed_arrays):
    """Bucket each fed LoD's max sequence length up to the next power of
    two (min 8). Returns (global_max_bucket_or_None, {lod_name: bucket})."""
    bucket = bucket_pow2

    per_name = {}
    m = 0
    for n, a in feed_arrays.items():
        if n.endswith(LOD_SUFFIX):
            d = np.diff(np.asarray(a))
            if d.size and int(d.max()) > 0:
                per_name[n] = bucket(int(d.max()))
                m = max(m, int(d.max()))
    return (bucket(m) if m else None), per_name


def _split_lod_feed(value):
    """Accept numpy arrays, (data, lod) tuples, and objects exposing
    `.data/.lod` (our LoDTensor helper). Device-resident jax arrays
    pass through UNTOUCHED — np.asarray on them is a device->host copy
    that would defeat the device-resident fast path (_to_device_dtype)
    and pay a transfer each way per run call."""
    if isinstance(value, tuple) and len(value) == 2 and not np.isscalar(value[0]):
        data, lod = value
        if not isinstance(data, jax.Array):
            data = np.asarray(data)
        return data, _flatten_lod(lod)
    if hasattr(value, "lod") and hasattr(value, "data"):
        return np.asarray(value.data), _flatten_lod(value.lod())
    if isinstance(value, jax.Array):
        return value, None
    return np.asarray(value), None


def _flatten_lod(lod):
    """Normalise a fed LoD to a list of levels (each an int32 offsets
    vector). Reference feeds lod as [[..level0..], [..level1..]]."""
    if lod is None:
        return None
    if len(lod) and isinstance(lod[0], (list, tuple, np.ndarray)):
        return [np.asarray(lv, np.int32) for lv in lod]
    return [np.asarray(lod, np.int32)]


def _globalize_feeds(mesh, feed_arrays, scanned_feeds=()):
    """Multi-controller (DCN) path: each process feeds its process-LOCAL
    batch; assemble the global jax.Array per feed so the jitted SPMD step
    sees one logical batch spanning the pod (replaces the reference's
    per-trainer DataProvider split + pserver/NCCL aggregation —
    RemoteParameterUpdater.h:55, distribute_transpiler.py:132).

    Dense feeds shard their batch dim (axis 0, or axis 1 for scanned
    multi-step feeds whose leading dim is [steps]) over the 'data' axis.
    A non-divisible batch is an error, not a silent fallback — replicas
    built from divergent per-process data would desynchronise training
    undetectably. On a mesh with NO 'data' axis (pure TP/SP serving),
    feeds replicate; every process must then feed identical values.

    Ragged (LoD) feeds: every process contributes its local packed rows
    + offsets through a host allgather, and the exact global packed
    array + global offsets are rebuilt and fed REPLICATED (see
    _globalize_ragged — the offsets-vector LoD contract cannot express
    the inter-block gaps a sharded-padded layout would need). Every
    process must feed the same NUMBER of sequences (equal local batch,
    the SPMD contract); lengths may diverge freely (reference:
    variable-length Arguments per trainer, parameter/Argument.h:84)."""
    import jax as _jax
    from jax.sharding import NamedSharding, PartitionSpec

    from ..parallel.mesh import data_parallel_axes

    data_axes, n_data = data_parallel_axes(mesh)
    has_data = bool(data_axes)
    out = {}
    lod_bases = {
        n[: -len(LOD_SUFFIX)] for n in feed_arrays if n.endswith(LOD_SUFFIX)
    }
    for name, arr in feed_arrays.items():
        if isinstance(arr, _jax.Array) and not arr.is_fully_addressable:
            out[name] = arr  # caller already built a global array
            continue
        if name in lod_bases:
            _globalize_ragged(mesh, feed_arrays, name, out)
            continue
        if "@" in name:
            if name.split("@")[0] in lod_bases:
                continue  # handled together with its base feed
            raise NotImplementedError(
                "feed %r: only @LOD side-bands are supported on a "
                "multi-process mesh" % name
            )
        arr = np.asarray(arr)
        batch_axis = 1 if name in scanned_feeds else 0
        if has_data and arr.ndim > batch_axis and arr.shape[batch_axis] > 0:
            spec = [None] * arr.ndim
            spec[batch_axis] = data_axes
            sharding = NamedSharding(mesh, PartitionSpec(*spec))
        else:
            sharding = NamedSharding(mesh, PartitionSpec())
        try:
            out[name] = _jax.make_array_from_process_local_data(sharding, arr)
        except ValueError as e:
            # NO silent replicate fallback: replicas assembled from
            # divergent per-process batches would desynchronise training
            # undetectably
            raise ValueError(
                "feed %r local shape %s does not shard over the mesh's "
                "data-parallel tiers %s (%d-way total, %d processes); "
                "pad the batch or drop the remainder on the host: %s"
                % (name, arr.shape, list(data_axes), n_data,
                   _jax.process_count(), e)
            )
    return out


def _globalize_ragged(mesh, feed_arrays, name, out):
    """Assemble a global ragged feed: every process contributes its local
    packed rows + offsets via a host allgather (transport-padded to a
    power-of-two bucket so shapes agree), and the TRUE global packed
    array + exact global offsets are rebuilt host-side and fed
    replicated. Exact semantics — the global batch is byte-identical to
    a single process feeding all sequences, so losses match the
    single-process oracle.

    Perf note: the ragged payload replicates across processes (token ids
    and LoD side-bands are small next to activations; the reference's
    pserver path likewise shipped whole Arguments per trainer,
    Argument.h:84). Sharding the packed rows over 'data' instead would
    need per-sequence (start, len) gaps that the offsets-vector LoD
    contract cannot express."""
    import jax as _jax
    from jax.experimental import multihost_utils

    data = np.asarray(feed_arrays[name])
    offsets = np.asarray(feed_arrays[lod_key(name)], np.int32)
    nproc = _jax.process_count()
    total = data.shape[0]
    n_seqs = offsets.shape[0] - 1

    # agree on shapes: [total, n_seqs] from every process
    gathered = np.asarray(
        multihost_utils.process_allgather(
            np.asarray([total, n_seqs], np.int64)
        )
    ).reshape(nproc, 2)
    if not (gathered[:, 1] == n_seqs).all():
        raise ValueError(
            "ragged feed %r: every process must feed the SAME number of "
            "sequences (got %s); lengths may differ, counts may not"
            % (name, gathered[:, 1].tolist())
        )
    bucket = 8
    while bucket < int(gathered[:, 0].max()):
        bucket *= 2

    pad = bucket - total
    padded = np.concatenate(
        [data, np.zeros((pad,) + data.shape[1:], data.dtype)]
    ) if pad else data
    all_data = np.asarray(
        multihost_utils.process_allgather(padded)
    ).reshape((nproc, bucket) + data.shape[1:])
    all_offsets = np.asarray(
        multihost_utils.process_allgather(offsets.astype(np.int64))
    ).reshape(nproc, n_seqs + 1)

    # strip transport padding; rebuild the exact global packed array
    out[name] = np.concatenate(
        [all_data[p, : int(all_offsets[p, -1])] for p in range(nproc)]
    )
    parts = [np.zeros((1,), np.int64)]
    base = 0
    for p in range(nproc):
        parts.append(all_offsets[p, 1:] + base)
        base += int(all_offsets[p, -1])
    out[lod_key(name)] = np.concatenate(parts).astype(np.int32)

    src_key = name + LOD_SRC
    if src_key in feed_arrays:
        src = np.asarray(feed_arrays[src_key], np.int64)
        all_src = np.asarray(
            multihost_utils.process_allgather(src)
        ).reshape(nproc, -1)
        sparts = [np.zeros((1,), np.int64)]
        sbase = 0
        for p in range(nproc):
            sparts.append(all_src[p, 1:] + sbase)
            sbase += int(all_src[p, -1])
        out[src_key] = np.concatenate(sparts).astype(np.int32)


def _mesh_jit_kwargs(
    mesh, program, feed_arrays, persist_in_keys, persist_out, fetch_names,
    scanned_feeds=(),
):
    """Build in/out shardings for the step function under a mesh.

    Feeds: batch dim over 'data' (replicated if not divisible or 0-d).
    Persistables: program.shardings[name] if annotated (TP), else
    replicated. Fetches: replicated (they are scalars/metrics in practice).
    LoD offset side-bands are replicated.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    from ..parallel.mesh import data_parallel_axes, replicated

    rep = replicated(mesh)
    # batch dim shards over the mesh's data-parallel tiers (dcn* across
    # slices outermost, 'data' within — one definition shared with
    # _globalize_feeds). XLA's sharding propagation inserts the gradient
    # reduction over every tier, riding DCN only for the slice-crossing
    # part.
    data_axes, n_data = data_parallel_axes(mesh)

    def feed_shard(name, arr):
        if "@" in name:  # LoD / beam side-bands are replicated
            return rep
        # scanned feeds carry a leading [steps] dim; the batch is axis 1
        batch_axis = 1 if name in scanned_feeds else 0
        if (
            data_axes
            and arr.ndim > batch_axis
            and arr.shape[batch_axis] > 0
            and arr.shape[batch_axis] % n_data == 0
        ):
            spec = [None] * arr.ndim
            spec[batch_axis] = data_axes
            return NamedSharding(mesh, PartitionSpec(*spec))
        return rep

    def persist_shard(name):
        spec = program.shardings.get(name)
        if spec is None:
            return rep
        return NamedSharding(mesh, spec)

    in_shardings = (
        {n: persist_shard(n) for n in persist_in_keys},
        {n: feed_shard(n, a) for n, a in feed_arrays.items()},
        rep,
    )
    out_shardings = (
        [rep for _ in fetch_names],
        {n: persist_shard(n) for n in persist_out},
    )
    return {"in_shardings": in_shardings, "out_shardings": out_shardings}


_DTYPE_MAP = {"float64": "float32", "int64": "int32"}


def _to_device_dtype(arr, var: Optional[Variable]):
    """Feeds are normalised to TPU-friendly dtypes: f64->f32, i64->i32
    (the TPU has no 64-bit compute path worth using). Device-resident
    arrays of the right dtype pass through untouched — no host round-trip."""
    if isinstance(arr, jax.Array):
        want = None
        if var is not None and var.dtype is not None:
            want = _DTYPE_MAP.get(var.dtype, var.dtype)
        if want is None or str(arr.dtype) == want:
            return arr
        return arr.astype(want)
    arr = np.asarray(arr)
    if var is not None and var.dtype is not None:
        want = _DTYPE_MAP.get(var.dtype, var.dtype)
        if str(arr.dtype) != want:
            arr = arr.astype(want)
    else:
        want = _DTYPE_MAP.get(str(arr.dtype))
        if want:
            arr = arr.astype(want)
    return arr
