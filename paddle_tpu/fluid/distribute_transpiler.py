"""DistributeTranspiler: the reference's distributed-rewrite API, mapped
onto mesh data parallelism.

Reference (python/paddle/v2/fluid/distribute_transpiler.py:132): rewrites
the program into trainer programs (split+send grad blocks) and pserver
programs (listen_and_serv + optimize blocks) wired over gRPC. On TPU the
entire mechanism collapses: gradients are aggregated by one `psum` over
ICI that XLA inserts when the executor runs the UNMODIFIED program over a
mesh. The API is kept so reference scripts run:

  t = fluid.DistributeTranspiler()
  t.transpile(trainer_id, pservers=..., trainers=N)
  exe.run(t.get_trainer_program(), ...)   # data-parallel over the mesh

get_pserver_program returns an empty program — there is no pserver role
to play; running it is a no-op so pserver-branch scripts exit cleanly.

Multi-PROCESS (DCN) training: call
`paddle_tpu.parallel.DistributedContext.initialize(...)` in every process
(TPU pods autodetect; explicit coordinator/num_processes/process_id
elsewhere), build one global mesh over jax.devices(), and feed each
process its local batch shard — the executor assembles the global batch
(executor._globalize_feeds) and XLA SPMD runs one step across the pod.
tests/test_multihost.py proves train/checkpoint/kill/resume parity with
the reference multi-node axis (RemoteParameterUpdater.h:55,
go/pserver/service.go:120-226).

ASYNC SGD (reference ParameterServer2.h:127-139 AsyncSGD,
go/pserver/service.go:285 per-gradient async updates): redesigned as
**local SGD** — `Executor.run_async_local(steps, sync_every)` gives each
'data'-axis replica its own parameter/optimizer-state copy, runs
`sync_every` purely-local optimizer steps, then averages the models
(one pmean per round). That expresses async's actual trade — staleness
for communication — in a form a globally-synchronous SPMD step can
compile (parallel/async_sgd.py has the full argument; sync_every=1
with SGD/momentum is bit-equal to the sync allreduce step).
`transpile(sync_mode=False)` records the request and warns which call
to use; plain `exe.run` still executes synchronously because per-batch
async dispatch does not exist inside one compiled step.
"""

from __future__ import annotations

import warnings

from .core.program import Program, default_main_program

__all__ = ["DistributeTranspiler", "SimpleDistributeTranspiler",
           "memory_optimize"]


class DistributeTranspiler(object):
    def __init__(self):
        self._program = None
        self._trainers = 1

    def transpile(self, optimize_ops=None, params_grads=None, trainer_id=0,
                  program=None, pservers="127.0.0.1:6174", trainers=1,
                  split_method=None, sync_mode=True, **kwargs):
        """Accepts BOTH reference calling conventions: the v0.11 form
        `transpile(optimize_ops, params_grads, pservers=..., trainers=N)`
        (e.g. benchmark/cluster/vgg16/vgg16_fluid.py) and the later
        `transpile(trainer_id[, program], pservers=..., trainers=N)`."""
        if isinstance(optimize_ops, int):
            # later convention: first positional is trainer_id, second
            # (if any) is the program
            trainer_id = optimize_ops
            if isinstance(params_grads, Program):
                program = params_grads
            elif params_grads is not None:
                raise TypeError(
                    "transpile(trainer_id, program, ...): program must be "
                    "a Program, got %r" % type(params_grads)
                )
        # v0.11's (optimize_ops, params_grads) are accepted and unused:
        # SPMD needs no graph rewrite
        self._program = program or default_main_program()
        self._trainers = int(trainers)
        self._trainer_id = int(trainer_id)
        self._pservers = pservers.split(",") if isinstance(pservers, str) else list(pservers)
        self._sync_mode = bool(sync_mode)
        if not sync_mode:
            warnings.warn(
                "sync_mode=False (AsyncSGD) requested: use "
                "Executor.run_async_local(steps, sync_every) — the "
                "local-SGD redesign of async DP (parallel/async_sgd.py); "
                "plain exe.run executes synchronously"
            )

    def get_trainer_program(self) -> Program:
        """The original program, to be run by an Executor holding a mesh
        whose 'data' axis plays the role of `trainers`."""
        from ..parallel.mesh import (data_parallel_width, get_default_mesh,
                                     make_mesh, set_default_mesh)

        if not getattr(self, "_sync_mode", True):
            # fire at the point of use too — the transpile-time warning
            # may be long scrolled away
            warnings.warn(
                "AsyncSGD was requested (sync_mode=False): exe.run on "
                "this program is synchronous; drive it with "
                "Executor.run_async_local(steps, sync_every) for the "
                "local-SGD async semantics"
            )

        if get_default_mesh() is None:
            n = data_parallel_width(self._trainers)
            if n > 1:
                set_default_mesh(make_mesh({"data": n}))
            elif self._trainers > 1:
                warnings.warn(
                    "transpile(trainers=%d) on one CPU device: running "
                    "single-device with identical global-batch math"
                    % self._trainers
                )
        return self._program

    def get_pserver_program(self, endpoint, *args, **kwargs) -> Program:
        return Program()  # no pserver role on TPU; empty program = no-op

    def get_startup_program(self, endpoint=None, pserver_program=None):
        return Program()


class SimpleDistributeTranspiler(DistributeTranspiler):
    """reference distribute_transpiler_simple.py — same collapse."""


def memory_optimize(input_program, print_log=False, **kwargs):
    """reference memory_optimization_transpiler.py:270 rewrites var reuse
    via liveness analysis. Delegates to the real implementation: XLA's
    buffer assignment already does the reuse, and the remaining lever —
    rematerializing the forward region — is enabled here (see
    memory_optimization_transpiler.memory_optimize)."""
    from .memory_optimization_transpiler import memory_optimize as _mo

    return _mo(input_program, print_log=print_log, **kwargs)
