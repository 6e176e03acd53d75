"""Device mesh construction + sharding helpers.

Replaces the reference's device/topology plumbing (platform/device_context,
nccl_gpu_common.h Communicator, trainer_count flag) with jax.sharding.Mesh
over ICI. Axis conventions:

  'data'  — batch sharding (data parallelism; grads psum over this axis)
  'model' — tensor parallelism (weight sharding)
  'seq'   — sequence/context parallelism (ring attention milestone)
  'expert'— expert parallelism (MoE milestone)

Multi-host (DCN) note: jax.devices() already spans hosts under multi-host
runtime; the same mesh code covers pod slices — lay 'data' outermost so
its collectives ride DCN only when crossing slices.
"""

from __future__ import annotations

import zlib
from typing import Dict, Optional, Sequence, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

P = PartitionSpec

_default_mesh: Optional[Mesh] = None


def make_mesh(
    axes: Union[int, Dict[str, int], None] = None,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a Mesh. `axes` may be:
      - None: all local devices on one 'data' axis
      - int N: N devices on the 'data' axis
      - dict {'data': 4, 'model': 2}: multi-axis mesh (row-major)
    """
    devices = list(devices) if devices is not None else jax.devices()
    if axes is None:
        axes = {"data": len(devices)}
    if isinstance(axes, int):
        axes = {"data": axes}
    names = tuple(axes.keys())
    sizes = tuple(int(axes[n]) for n in names)
    n = int(np.prod(sizes))
    if n > len(devices):
        raise ValueError(
            "mesh needs %d devices but only %d available" % (n, len(devices))
        )
    arr = np.asarray(devices[:n]).reshape(sizes)
    return Mesh(arr, names)


def data_parallel_width(requested: int) -> int:
    """Devices a request for `requested` data-parallel trainers gets
    (the reference's `trainer_count` / `transpile(trainers=N)`). On an
    accelerator backend, asking for more chips than are visible is an
    error — quietly training on fewer would report one chip's speed as
    N's. On the CPU backend the reference's trainers were host threads,
    which XLA:CPU already spreads one device's work over, so the
    request clamps to the devices there are."""
    requested, have = int(requested), jax.device_count()
    if requested > have and jax.default_backend() != "cpu":
        raise RuntimeError(
            "%d data-parallel trainers requested but only %d %s "
            "device(s) are visible" % (requested, have,
                                       jax.default_backend()))
    return min(requested, have)


def make_hybrid_mesh(
    dcn_axes: Dict[str, int],
    ici_axes: Dict[str, int],
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Multi-slice mesh: `dcn_axes` partition across slices (collectives
    ride the data-center network), `ici_axes` partition within a slice
    (collectives ride the chip interconnect). DCN axes are laid
    outermost so only they cross slice boundaries — the layout the
    scaling playbook prescribes (dp over DCN x tp/sp over ICI), and the
    TPU-native form of the reference's two-tier topology (NCCL ring
    within a node, pserver/gRPC across nodes).

    Batch sharding convention: the executor data-shards over axes
    named 'dcn'/'dcn_*' and 'data' (data_parallel_axes); a DCN axis
    with any other name stays out of the batch partition (e.g. a
    cross-slice pipeline tier).

    Devices are grouped into slices by `slice_index` (TPU multi-slice)
    or `process_index` (multi-host CPU/GPU); a single-group platform —
    e.g. the one-process CPU test fixture — emulates the slice structure
    by splitting the device list into contiguous groups, so the mesh
    layout (and the collectives XLA inserts over it) compiles and
    validates without pod hardware.
    """
    devices = list(devices) if devices is not None else jax.devices()
    dcn_names = tuple(dcn_axes.keys())
    dcn_sizes = tuple(int(dcn_axes[n]) for n in dcn_names)
    ici_names = tuple(ici_axes.keys())
    ici_sizes = tuple(int(ici_axes[n]) for n in ici_names)
    n_slices = int(np.prod(dcn_sizes))
    per_slice = int(np.prod(ici_sizes))

    groups: Dict[int, list] = {}
    for d in devices:
        key = getattr(d, "slice_index", None)
        if key is None:
            key = getattr(d, "process_index", 0)
        groups.setdefault(int(key), []).append(d)
    ordered = [groups[k] for k in sorted(groups)]
    if len(ordered) == 1:
        # single-slice platform: emulate the slice split contiguously
        flat = ordered[0]
        if n_slices * per_slice > len(flat):
            raise ValueError(
                "hybrid mesh needs %d devices but only %d available"
                % (n_slices * per_slice, len(flat))
            )
        ordered = [
            flat[i * per_slice:(i + 1) * per_slice] for i in range(n_slices)
        ]
    if len(ordered) != n_slices:
        raise ValueError(
            "dcn axes %r want %d slices but the platform has %d device "
            "groups" % (dict(dcn_axes), n_slices, len(ordered))
        )
    for g in ordered:
        if len(g) < per_slice:
            raise ValueError(
                "ici axes %r want %d devices per slice, a slice has %d"
                % (dict(ici_axes), per_slice, len(g))
            )
        if len(g) > per_slice and len(groups) > 1:
            # a REAL multi-slice platform with surplus chips per slice:
            # silently dropping them would read as a working mesh while
            # under-utilizing the hardware. (The single-group emulation
            # path above keeps the silent split — its surplus is the
            # virtual-device fixture, not idle chips.)
            import warnings

            warnings.warn(
                "make_hybrid_mesh: slice has %d devices but ici axes %r "
                "use only %d — %d chips per slice will sit idle; size "
                "the ici axes to the slice"
                % (len(g), dict(ici_axes), per_slice, len(g) - per_slice),
                stacklevel=2,
            )
    arr = np.asarray(
        [g[:per_slice] for g in ordered], dtype=object
    ).reshape(dcn_sizes + ici_sizes)
    return Mesh(arr, dcn_names + ici_names)


def data_parallel_axes(mesh: Mesh):
    """(axes, total) of the mesh's data-parallel tiers: every axis named
    'dcn' or 'dcn_*' (slice-crossing, laid outermost by
    make_hybrid_mesh) plus 'data' (within a slice). The executor shards
    batch dims over exactly these axes — the single definition both the
    jit-sharding and multi-process feed paths use."""
    axes = tuple(
        a
        for a in mesh.axis_names
        if a == "data" or a == "dcn" or str(a).startswith("dcn_")
    )
    total = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    return axes, total


def set_default_mesh(mesh: Optional[Mesh]):
    global _default_mesh
    _default_mesh = mesh


def get_default_mesh() -> Optional[Mesh]:
    return _default_mesh


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_sharding(mesh: Mesh, ndim: int, axis: str = "data") -> NamedSharding:
    """Shard the leading (batch) dim over the data axis."""
    if axis not in mesh.axis_names:
        return replicated(mesh)
    return NamedSharding(mesh, P(axis, *([None] * (ndim - 1))))


def shard_parameter(var, spec: PartitionSpec):
    """Annotate a Parameter/Variable with a PartitionSpec (tensor
    parallelism). The executor places the scope array accordingly; XLA
    partitions every op touching it and inserts the collectives.

    Replaces the reference's per-layer `device` placement field
    (ModelConfig.proto:399 / ParallelNeuralNetwork.h) with per-tensor
    sharding — the TPU-idiomatic form of model parallelism.
    """
    program = var.block.program
    program.shardings[var.name] = spec
    return var


def shard_parameters_fsdp(program, mesh: Mesh, axis: str = "data",
                          min_size: int = 1024):
    """ZeRO-3/FSDP-style parameter sharding: every trainable parameter
    (and, through the optimizer-slot inheritance in
    fluid/optimizer.py _add_accumulator, all its optimizer state) is
    sharded over `axis` along its largest divisible dim. XLA SPMD then
    all-gathers weights where the forward needs them and
    reduce-scatters gradients — the memory-per-chip profile of FSDP
    without any new runtime machinery, since the program keeps
    global-batch semantics.

    Parameters smaller than `min_size` elements stay replicated (the
    gather latency would dominate), and parameters that already carry a
    sharding annotation (e.g. tensor-parallel specs) keep it. Call
    BEFORE optimizer.minimize() so the slots inherit the specs.
    Returns the sharded param names.
    """
    n = int(mesh.shape[axis])
    done = []
    for p in program.global_block().all_parameters():
        if not getattr(p, "trainable", True):
            continue
        if p.name in program.shardings:
            continue  # user-placed (TP) specs win
        shape = list(p.shape or [])
        if not shape or int(np.prod(shape)) < min_size:
            continue
        # largest dim divisible by the axis extent
        cand = sorted(
            (d for d in range(len(shape)) if shape[d] % n == 0),
            key=lambda d: -shape[d],
        )
        if not cand:
            continue
        spec = [None] * len(shape)
        spec[cand[0]] = axis
        shard_parameter(p, PartitionSpec(*spec))
        done.append(p.name)
    return done


class DistributedContext(object):
    """Process-level view of the distributed runtime (replaces the
    reference's trainer_id/num_gradient_servers flags, Flags.cpp:60-65,
    and the multi-node bootstrap the reference does via PSERVERS /
    TRAINING_ROLE env + etcd registration, notest_dist_fit_a_line.py:30-45
    and go/pserver/etcd_client.go:70)."""

    _initialized = False

    def __init__(self, mesh: Optional[Mesh] = None):
        self.mesh = mesh or get_default_mesh()

    @classmethod
    def initialize(
        cls,
        coordinator_address: Optional[str] = None,
        num_processes: Optional[int] = None,
        process_id: Optional[int] = None,
        local_device_ids: Optional[Sequence[int]] = None,
    ):
        """Join the multi-controller runtime (DCN): after this,
        jax.devices() spans every process and one global Mesh covers the
        pod — collectives ride ICI within a slice and DCN across.

        Arguments mirror jax.distributed.initialize and fall back to its
        env/cluster autodetection (TPU pods need no arguments at all; the
        CPU test fixture passes explicit localhost coordinates the way the
        reference's tests wired PSERVERS=127.0.0.1 endpoints).
        Idempotent per process.
        """
        if cls._initialized:
            return
        kwargs = {}
        if coordinator_address is not None:
            kwargs["coordinator_address"] = coordinator_address
        if num_processes is not None:
            kwargs["num_processes"] = int(num_processes)
        if process_id is not None:
            kwargs["process_id"] = int(process_id)
        if local_device_ids is not None:
            kwargs["local_device_ids"] = list(local_device_ids)
        jax.distributed.initialize(**kwargs)
        cls._initialized = True

    @classmethod
    def shutdown(cls):
        if cls._initialized:
            jax.distributed.shutdown()
            cls._initialized = False

    @property
    def world_size(self) -> int:
        return jax.device_count()

    @property
    def local_device_count(self) -> int:
        return jax.local_device_count()

    @property
    def process_index(self) -> int:
        return jax.process_index()

    @property
    def process_count(self) -> int:
        return jax.process_count()

    # --- per-process data sharding (replaces per-trainer file lists /
    # master task dispatch for the simple static case) ------------------
    def shard_reader(self, reader, verify_every: Optional[int] = None):
        """Wrap a v2-style reader so each process sees its 1/process_count
        slice of the stream (round-robin by instance). The global batch
        assembled by the executor is identical to single-process order-
        stability aside.

        Round-robin assignment REQUIRES every process to enumerate the
        identical stream (same shuffle seed); silent divergence would feed
        overlapping/duplicated data. `verify_every=K` guards this: after
        every K YIELDED items — the same consumer-visible ordinal on
        every process, so lockstep consumers (the executor's global-batch
        assembly pulls per-process equal counts) hit the collective at
        the same pull — processes all-gather (yield_count, crc-of-
        completed-rounds), and once more at stream end with the full
        (raw_count, crc). Any content or length divergence pairs
        mismatched payloads and raises on every process instead of
        hanging. (A consumer that abandons the generator mid-stream skips
        the end gather — the guard covers stream content/length, not
        consumer aborts.)
        """
        pidx, pcount = self.process_index, self.process_count

        def _check(count, crc):
            from jax.experimental import multihost_utils

            pairs = np.asarray(
                multihost_utils.process_allgather(
                    np.asarray([count, crc], np.uint32)
                )
            ).reshape(-1, 2)
            if len({(int(c), int(f)) for c, f in pairs}) != 1:
                raise RuntimeError(
                    "shard_reader stream divergence: per-process "
                    "(count, fingerprint) pairs %s differ — every "
                    "process must enumerate the identical reader order "
                    "(same shuffle seed, balanced length)" % pairs.tolist()
                )

        def _sharded():
            crc, i, yielded = 0, 0, 0
            # crc over all COMPLETE rounds of pcount raw items: identical
            # on every process at the same yield ordinal, even though
            # their raw positions within the current round differ
            round_crc = 0
            for i, item in enumerate(reader(), start=1):
                if verify_every and pcount > 1:
                    if (i - 1) % pcount == 0:
                        round_crc = crc  # round boundary: all complete
                    crc = _fingerprint(item, crc)
                if (i - 1) % pcount == pidx:
                    yielded += 1
                    yield item
                    if verify_every and pcount > 1 \
                            and yielded % verify_every == 0:
                        _check(yielded, round_crc)
            # end-of-stream gather: full stream totals; a diverging or
            # unbalanced stream pairs this with a peer's interval gather
            # (or an unequal payload) and raises on BOTH sides
            if verify_every and pcount > 1:
                _check(i, crc)

        return _sharded


def _fingerprint(item, crc: int) -> int:
    """Rolling CRC32 of a reader item (arrays / scalars / nested tuples),
    order-sensitive, for shard_reader's divergence guard."""
    if isinstance(item, (tuple, list)):
        for part in item:
            crc = _fingerprint(part, crc)
        return crc
    a = np.asarray(item)
    crc = zlib.crc32(str(a.dtype).encode() + str(a.shape).encode(), crc)
    return zlib.crc32(np.ascontiguousarray(a).tobytes(), crc)


def spans_processes(mesh: Optional[Mesh]) -> bool:
    """True when the mesh includes devices owned by other processes (the
    executor must then assemble global arrays from process-local feeds)."""
    if mesh is None:
        return False
    pidx = jax.process_index()
    return any(d.process_index != pidx for d in mesh.devices.flat)
