"""Pallas TPU one-token state update of a selective state-space layer
(Mamba-1), for the decode step of the hybrid family (ISSUE 27).

One decode step advances every live slot's state by one token:

    s <- exp(delta (x) A) * s + B (x) (delta u)        [N, di] a slot
    y  = sum_n s[n] C[n]                               [di]

All of it is elementwise over the state, so the call is bound by the
state's bytes in and out of HBM (N x di float32 a slot, twice). The
grid is one step a slot with the whole [N, di] tile in VMEM; the state
is updated in place (`input_output_aliases`). A parked slot (`live` 0)
copies its state through unchanged and bit-identical: the tile has to
be written back whatever the step did with it.

The state is kept [N, di], not the [di, N] the equations are written
in: di is the lane dimension, so a tile is dense, where N = 16 lanes
would pad every tile eightfold.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel_utils import resolve_interpret

__all__ = ["ssm_state_update", "ssm_state_update_reference",
           "ssm_chunk_scan", "ssm_chunk_scan_reference"]

KERNEL_NAME = "ssm_state_update"


def ssm_state_update_reference(state, delta, du, a_t, b, c, live):
    """The same update in plain jax.numpy (the CPU path and the
    kernel's oracle). state [S, N, di] f32; delta, du [S, di] f32
    (du = delta * u); a_t [N, di]; b, c [S, N]; live [S] bool
    -> (new state, y [S, di])."""
    new = (jnp.exp(delta[:, None, :] * a_t[None]) * state
           + b[:, :, None] * du[:, None, :])
    y = (new * c[:, :, None]).sum(axis=1)
    return jnp.where(live[:, None, None], new, state), y


def _kernel(live_ref, s_ref, d_ref, du_ref, a_ref, b_ref, c_ref,
            o_ref, y_ref):
    s = s_ref[0]  # [N, di]
    new = jnp.exp(d_ref[0] * a_ref[...]) * s + b_ref[0] * du_ref[0]
    o_ref[0] = jnp.where(live_ref[pl.program_id(0)] != 0, new, s)
    y_ref[0] = jnp.sum(new * c_ref[0], axis=0, keepdims=True)


def ssm_state_update(state, delta, du, a_t, b, c, live, interpret=None):
    """See `ssm_state_update_reference`; the state argument is donated
    to the result."""
    S, N, di = state.shape
    f32 = jnp.float32

    def slot(i, live):
        return (i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S,),
        in_specs=[pl.BlockSpec((1, N, di), slot),
                  pl.BlockSpec((1, 1, di), slot),
                  pl.BlockSpec((1, 1, di), slot),
                  pl.BlockSpec((N, di), lambda i, live: (0, 0)),
                  pl.BlockSpec((1, N, 1), slot),
                  pl.BlockSpec((1, N, 1), slot)],
        out_specs=[pl.BlockSpec((1, N, di), slot),
                   pl.BlockSpec((1, 1, di), slot)],
    )
    new, y = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, N, di), f32),
                   jax.ShapeDtypeStruct((S, 1, di), f32)],
        # operand 0 is the scalar-prefetch `live`; the state is operand 1
        input_output_aliases={1: 0},
        interpret=resolve_interpret(interpret),
        name=KERNEL_NAME,
        metadata={"kernel": KERNEL_NAME},
    )(live.astype(jnp.int32), state.astype(f32),
      delta.astype(f32)[:, None, :], du.astype(f32)[:, None, :],
      a_t.astype(f32), b.astype(f32)[:, :, None], c.astype(f32)[:, :, None])
    return new, y[:, 0]


def ssm_chunk_scan_reference(s0, delta, du, a_t, b, c):
    """The recurrence over the rows of a chunk in plain jax.numpy (the
    CPU path and the kernel's oracle): s0 [N, di]; delta, du [T, di]
    (du = delta * u); a_t [N, di]; b, c [T, N], all float32 -> (final
    state, y [T, di]). A row whose delta is 0 leaves the state as it
    was (exp(0) = 1, du = 0): how a padded row of a prefill bucket is
    kept from advancing it. Nothing [T, N, di] is ever materialised."""
    def step(s, xs):
        d_t, du_t, b_t, c_t = xs
        s = jnp.exp(d_t[None, :] * a_t) * s + b_t[:, None] * du_t[None, :]
        return s, (s * c_t[:, None]).sum(0)

    return jax.lax.scan(step, s0, (delta, du, b, c), unroll=4)


_LANES = 128


def _scan_kernel(s0_ref, d_ref, du_ref, a_ref, b_ref, c_ref, y_ref, sT_ref,
                 s_scr, *, rows: int, reps: int):
    tb = pl.program_id(1)

    @pl.when(tb == 0)
    def _init():
        s_scr[...] = s0_ref[...]

    a = a_ref[...]  # [N, tile]

    def row(t, s):
        d = d_ref[pl.ds(t, 1), :]  # [1, tile]
        du = du_ref[pl.ds(t, 1), :]
        # B and C come with their value repeated over 128 lanes
        b = jnp.tile(b_ref[t], (1, reps))  # [N, tile]
        c = jnp.tile(c_ref[t], (1, reps))
        s = jnp.exp(d * a) * s + b * du
        y_ref[pl.ds(t, 1), :] = jnp.sum(s * c, axis=0, keepdims=True)
        return s

    s = jax.lax.fori_loop(0, rows, row, s_scr[...])
    s_scr[...] = s

    @pl.when(tb == pl.num_programs(1) - 1)
    def _final():
        sT_ref[...] = s


def ssm_chunk_scan(s0, delta, du, a_t, b, c, interpret=None):
    """See `ssm_chunk_scan_reference`. Grid (channel tiles, blocks of
    rows), rows innermost: the state tile lives in a scratch from the
    first block of rows to the last."""
    N, di = s0.shape
    T = delta.shape[0]
    f32 = jnp.float32
    tile = min(512, di)
    rows = min(128, T)
    if di % tile or tile % _LANES or T % rows:
        raise ValueError(
            "ssm_chunk_scan tiles %d channels by %d and %d rows by %d"
            % (di, tile, T, rows))

    def lanes(x):  # [T, N] -> [T, N, 128], the value over every lane
        return jnp.broadcast_to(x.astype(f32)[:, :, None], (T, N, _LANES))

    chan = lambda j, t: (0, j)
    time = lambda j, t: (t, j)
    bc = lambda j, t: (t, 0, 0)
    y, sT = pl.pallas_call(
        functools.partial(_scan_kernel, rows=rows, reps=tile // _LANES),
        grid=(di // tile, T // rows),
        in_specs=[pl.BlockSpec((N, tile), chan),
                  pl.BlockSpec((rows, tile), time),
                  pl.BlockSpec((rows, tile), time),
                  pl.BlockSpec((N, tile), chan),
                  pl.BlockSpec((rows, N, _LANES), bc),
                  pl.BlockSpec((rows, N, _LANES), bc)],
        out_specs=[pl.BlockSpec((rows, tile), time),
                   pl.BlockSpec((N, tile), chan)],
        out_shape=[jax.ShapeDtypeStruct((T, di), f32),
                   jax.ShapeDtypeStruct((N, di), f32)],
        scratch_shapes=[pltpu.VMEM((N, tile), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="ssm_chunk_scan",
        metadata={"kernel": "ssm_chunk_scan"},
    )(s0.astype(f32), delta.astype(f32), du.astype(f32), a_t.astype(f32),
      lanes(b), lanes(c))
    return sT, y
