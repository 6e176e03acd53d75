"""The Mamba-2 (SSD) recurrence for the serving path (ISSUE 31): a
Pallas TPU one-token state update for the decode step, and the blocked
matrix form of the same recurrence for a prefill chunk.

A layer keeps, a slot, the state S [H, P, N] (H heads of P channels,
N state columns; B and C are shared by every head, one group):

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t
    y_t[h] = S_t[h] C_t

The decay is one scalar a HEAD (Mamba-1, `ssm_update.py`, has one a
channel and state column: an `exp` over the whole tile). The state is
held `[N, H * P]`, not the `[H, P, N]` the equations are written in:
the channels are the lane dimension and the sum over N runs down the
sublanes (vector adds), where `[.., P, N]` would sum ACROSS lanes 4,096
times a slot and layer. It is the Mamba-1 state's arrangement, for
the same reason, and one 128-lane tile holds two heads of 64.

`ssd_state_update`: every live slot's state by one token. Elementwise
over the state, so bound by its bytes in and out of HBM (N x H x P
float32 a slot, twice: 2 MB each way at 128 x 4,096). The grid is
(slots, channel tiles), the state is updated in place
(`input_output_aliases`), and a parked slot (`live` 0) copies its
state through bit-identical: the tile has to be written back whatever
the step did with it.

`ssd_chunk_scan`: the recurrence over the T rows of a chunk, `block`
rows at a time (the configuration's `mamba_chunk_size`, 256). With
a_t = dt_t A and cum the running sum of a inside a block:

    Y = (L o (C B^T)) (dt * X) + exp(cum) (C S_prev)
    L[i, j] = exp(cum_i - cum_j) for i >= j, else 0
    S_next = exp(cum_last) S_prev + B^T (exp(cum_last - cum) dt * X)

which is the recurrence's own numbers in four matrix products a block
(the first batched over the heads), plain XLA on the matrix unit; the
block's closing state feeds the next block (`lax.scan`). A row whose
dt is 0 changes nothing (decay 1, no input): how a padded row of a
prefill bucket is kept from advancing the state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel_utils import resolve_interpret

__all__ = ["ssd_state_update", "ssd_state_update_reference",
           "ssd_chunk_scan", "ssd_chunk_scan_reference"]

KERNEL_NAME = "ssd_state_update"
_HIGHEST = jax.lax.Precision.HIGHEST


def ssd_state_update_reference(state, da, dtx, b, c, live):
    """The same update in plain jax.numpy (the CPU path and the
    kernel's oracle). state [S, N, di] f32 (di = H * P, channel h * P +
    p); da [S, di] the step's log-decay dt[h] A[h] at every channel of
    head h; dtx [S, di] = dt[h] x[h, p]; b, c [S, N]; live [S] bool
    -> (new state, y [S, di])."""
    new = (jnp.exp(da)[:, None, :] * state
           + b[:, :, None] * dtx[:, None, :])
    y = (new * c[:, :, None]).sum(axis=1)
    return jnp.where(live[:, None, None], new, state), y


def _kernel(live_ref, s_ref, da_ref, dtx_ref, b_ref, c_ref, o_ref, y_ref):
    s = s_ref[0]  # [N, tile]
    new = jnp.exp(da_ref[0]) * s + b_ref[0] * dtx_ref[0]
    o_ref[0] = jnp.where(live_ref[pl.program_id(0)] != 0, new, s)
    y_ref[0] = jnp.sum(new * c_ref[0], axis=0, keepdims=True)


def ssd_state_update(state, da, dtx, b, c, live, interpret=None):
    """See `ssd_state_update_reference`; the state argument is donated
    to the result. A grid step takes a slot's whole rows up to 2,048
    channels, else 2,048 of them (1 MB of state each way)."""
    S, N, di = state.shape
    f32 = jnp.float32
    tile = min(di, 2048)
    if di % tile:
        raise ValueError("ssd_state_update tiles %d channels by %d"
                         % (di, tile))

    def chan(i, j, live):
        return (i, 0, j)

    def col(i, j, live):
        return (i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S, di // tile),
        in_specs=[pl.BlockSpec((1, N, tile), chan),
                  pl.BlockSpec((1, 1, tile), chan),
                  pl.BlockSpec((1, 1, tile), chan),
                  pl.BlockSpec((1, N, 1), col),
                  pl.BlockSpec((1, N, 1), col)],
        out_specs=[pl.BlockSpec((1, N, tile), chan),
                   pl.BlockSpec((1, 1, tile), chan)],
    )
    new, y = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, N, di), f32),
                   jax.ShapeDtypeStruct((S, 1, di), f32)],
        # operand 0 is the scalar-prefetch `live`; the state is operand 1
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=resolve_interpret(interpret),
        name=KERNEL_NAME,
        metadata={"kernel": KERNEL_NAME},
    )(live.astype(jnp.int32), state.astype(f32),
      da.astype(f32)[:, None, :], dtx.astype(f32)[:, None, :],
      b.astype(f32)[:, :, None], c.astype(f32)[:, :, None])
    return new, y[:, 0]


def ssd_chunk_scan_reference(s0, dt, x, a, b, c):
    """The recurrence row after row in plain jax.numpy (the oracle of
    the blocked form): s0 [N, di]; dt [T, H]; x [T, di] (di = H * P);
    a [H] (negative); b, c [T, N], all float32 -> (final state,
    y [T, di])."""
    P = x.shape[1] // dt.shape[1]

    def step(s, xs):
        dt_t, x_t, b_t, c_t = xs
        da = jnp.repeat(dt_t * a, P)
        s = jnp.exp(da)[None, :] * s + b_t[:, None] * (
            jnp.repeat(dt_t, P) * x_t)[None, :]
        return s, (s * c_t[:, None]).sum(0)

    return jax.lax.scan(step, s0, (dt, x, b, c))


def ssd_chunk_scan(s0, dt, x, a, b, c, block=256):
    """See `ssd_chunk_scan_reference` and the module's docstring: the
    same numbers, `block` rows at a time, on the matrix unit. T is a
    multiple of `block` or smaller than it (one block)."""
    f32 = jnp.float32
    N, di = s0.shape
    T, H = dt.shape
    P = di // H
    Q = min(int(block), T)
    if T % Q:
        raise ValueError("ssd_chunk_scan takes %d rows %d at a time"
                         % (T, Q))
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    ones = lower.astype(f32)
    a = a.astype(f32)

    def heads(v):  # [.., H] -> [.., di], a head's value at its channels
        return jnp.repeat(v, P, axis=-1)

    def step(s, blk):
        dt_q, x_q, b_q, c_q = blk  # [Q, H], [Q, di], [Q, N], [Q, N]
        cum = mm(ones, dt_q * a)  # [Q, H], running sum of a
        # rows i >= j only: above the diagonal the difference is
        # positive and its exp may overflow
        seg = cum.T[:, :, None] - cum.T[:, None, :]  # [H, i, j]
        m = (jnp.exp(jnp.where(lower[None], seg, -jnp.inf))
             * mm(c_q, b_q.T)[None] * dt_q.T[:, None, :])
        xh = x_q.reshape(Q, H, P).transpose(1, 0, 2)  # [H, Q, P]
        y = mm(m, xh).transpose(1, 0, 2).reshape(Q, di)
        y = y + heads(jnp.exp(cum)) * mm(c_q, s)
        last = cum[-1]
        w = heads(jnp.exp(last[None, :] - cum) * dt_q)  # [Q, di]
        s = heads(jnp.exp(last))[None, :] * s + mm(b_q.T, w * x_q)
        return s, y

    blocks = tuple(v.astype(f32).reshape((T // Q, Q) + v.shape[1:])
                   for v in (dt, x, b, c))
    s, y = jax.lax.scan(step, s0.astype(f32), blocks)
    return s, y.reshape(T, di)
