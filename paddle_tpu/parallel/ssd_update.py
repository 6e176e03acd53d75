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
float32 a slot, twice: 2 MB each way at 128 x 4,096). The state is
updated in place (`input_output_aliases`), and a parked slot (`live`
0) goes through bit-identical: its tile is written back whatever the
step did with it.

How the bytes move (ISSUE 34; the readings and the forms tried are in
PERF.md section 6, PR 34). This chip's HBM gives a kernel 755 GB/s
reading and 657 writing, and 657 for the two when a read and a write
are in flight together, which is what a grid over the state's blocks
with Pallas's own double-buffered copies does: 409 microseconds a call
at the cell's 64 slots of [128, 4096], at any grid step. Reads and
writes taken in TURNS cost their sum, 178 + 205 = 383. So the call is
ONE grid step that keeps the state in HBM and walks it itself: batches
of `_step_slots` whole slots, two buffers, and in every period batch
p - 1 is written out, THEN batch p + 1 read into the buffer it left,
while batch p is updated in place — half its slots under the write,
half under the read, a slot `_ROWS` rows at a time. `da` and `dtx`
travel as one `[S, 2, di]` operand, B and C as one `[S, 2, N]` (rows;
the kernel turns them down the sublanes), whole in VMEM beside y: so
the call's VMEM bounds slots x channels as well as a slot's state
(`_update` refuses with the numbers).

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


# State bytes a batch of slots weighs, at most (one slot where a slot
# already weighs more): a read phase or a write phase moves one batch,
# and every turn drains the copies' pipeline, ~0.35 microseconds. The
# call alone (my chip runs, PR 34): 416, 401, 395, 396 microseconds at
# 2, 4, 8, 16 MiB (1, 2, 4, 8 of the cell's slots a batch); in the
# cell's step `decode_step_ms` 28.19, 28.03, 27.43, 27.49.
_STEP_BYTES = 8 << 20
# A v5e core's VMEM. The call scopes TWICE what it holds (the two
# batches and the call's rows; beside them Mosaic's own scratch and
# what a row group spills), so it may hold half of this.
_VMEM_BYTES = 128 << 20
_ROWS = 8  # state rows walked at a time: one sublane tile of float32


def _step_slots(S: int, slot_bytes: int) -> int:
    """Whole slots a batch carries, by BYTES: the largest divisor of
    the S slots whose states weigh at most `_STEP_BYTES` together
    (`slot_bytes` a slot: N x di x 4), and one slot where a slot
    already weighs more. A divisor, so every batch is whole: 3 slots of
    16 KiB are one batch, 64 of 2 MiB go four a batch, 5 of 2 MiB one
    a batch."""
    return max(g for g in range(1, S + 1)
               if S % g == 0 and (g == 1 or g * slot_bytes <= _STEP_BYTES))


def _kernel(live_ref, dd_ref, bc_ref, s_hbm, o_hbm, y_ref, buf, col_ref,
            sem):
    """The whole call in one grid step: the state stays in HBM
    (`s_hbm`, and `o_hbm` the same bytes), `buf` [2, G, N, di] holds
    two batches of G slots, `col_ref` [N, 2] a slot's B and C as
    columns, `sem` one DMA semaphore a buffer (a buffer has one copy in
    flight at a time)."""
    G, N, di = buf.shape[1:]
    K = s_hbm.shape[0] // G
    rows = _ROWS if N % _ROWS == 0 else N
    half = (G + 1) // 2

    def read(p):  # batch p, HBM -> its buffer
        return pltpu.make_async_copy(s_hbm.at[pl.ds(p * G, G)],
                                     buf.at[p % 2], sem.at[p % 2])

    def write(p):
        return pltpu.make_async_copy(buf.at[p % 2],
                                     o_hbm.at[pl.ds(p * G, G)], sem.at[p % 2])

    def update(p, lo, hi):
        """Slots lo .. hi of batch p, in place in its buffer."""
        def slot(g, carry):
            i = p * G + g
            live = live_ref[i] != 0
            decay = jnp.exp(dd_ref[i, 0:1, :])  # [1, di]
            dtx = dd_ref[i, 1:2, :]
            col_ref[...] = bc_ref[i].T  # [N, 2]: B and C down the sublanes
            tile = buf.at[p % 2, g]

            # the slot's [N, di] walked `rows` rows at a time: what is
            # live between two row groups is `rows` rows, not the slot.
            # A loop, not 16 copies of its body: the decode program
            # holds 36 of these kernels and is lowered anew in every
            # process (`setup_s`)
            def group(r, y):
                at = pl.ds(pl.multiple_of(r * rows, rows), rows)
                s = tile[at, :]
                new = decay * s + col_ref[at, 0:1] * dtx
                tile[at, :] = jnp.where(live, new, s)
                return y + new * col_ref[at, 1:2]

            y = jax.lax.fori_loop(0, N // rows, group,
                                  jnp.zeros((rows, di), jnp.float32))
            y_ref[i] = jnp.sum(y, axis=0, keepdims=True)
            return carry

        jax.lax.fori_loop(lo, hi, slot, 0)

    read(0).start()
    read(0).wait()
    if K > 1:
        read(1).start()

    def period(p, carry):
        # batch p - 1 goes out, THEN batch p + 1 comes in (into the
        # buffer p - 1 left): never a read and a write in flight
        # together; batch p is updated under the two of them. Period
        # 0 has nothing to write: its read is under way already.
        @pl.when(p > 0)
        def _():
            write(p - 1).start()

        update(p, 0, half)

        @pl.when(p > 0)
        def _():
            write(p - 1).wait()

        @pl.when((p > 0) & (p + 1 < K))
        def _():
            read(p + 1).start()

        update(p, half, G)

        @pl.when(p + 1 < K)
        def _():
            read(p + 1).wait()

        return carry

    jax.lax.fori_loop(0, K, period, 0)
    write(K - 1).start()
    write(K - 1).wait()


def ssd_state_update(state, da, dtx, b, c, live, interpret=None):
    """See `ssd_state_update_reference`; the state argument is donated
    to the result. The state is walked in batches of whole slots
    (`_step_slots`), read and written back in turns (the module's
    docstring)."""
    S, N, di = state.shape
    return _update(state, da, dtx, b, c, live,
                   slots=_step_slots(S, N * di * 4),
                   interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("slots", "interpret"))
def _update(state, da, dtx, b, c, live, *, slots, interpret):
    """The call, `slots` slots a batch. Jitted, so that a program's 36
    layers trace and lower ONE kernel: every process lowers its decode
    program anew, and that is `setup_s` (0.3 s for the 36 so, 2.8 s
    one by one; 6.9 s with the row groups unrolled in Python)."""
    S, N, di = state.shape
    f32 = jnp.float32
    slot_bytes = N * di * 4
    G = slots
    # two batches; da, dtx, b, c and y whole, each double-buffered
    need = 2 * G * slot_bytes + 2 * S * (3 * di + 2 * max(N, 128)) * 4
    if 2 * need > _VMEM_BYTES:
        raise ValueError(
            "ssd_state_update holds two batches of whole slots and the "
            "call's rows in VMEM: %d slots of [%d, %d] float32 need %d "
            "MiB of the %d a call may hold"
            % (S, N, di, need >> 20, _VMEM_BYTES >> 21))

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i, live: (0,) * len(shape))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[whole(S, 2, di), whole(S, 2, N),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY), whole(S, 1, di)],
        scratch_shapes=[pltpu.VMEM((2, G, N, di), f32),
                        pltpu.VMEM((N, 2), f32),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    new, y = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, N, di), f32),
                   jax.ShapeDtypeStruct((S, 1, di), f32)],
        # operand 0 is the scalar-prefetch `live`; the state is operand 3
        input_output_aliases={3: 0},
        # what a call scopes, XLA cannot fill with the next layers'
        # weights while it runs: the step around the call moves with
        # this number, and not in one direction (PERF.md section 6,
        # PR 34): a rule of the operands, not a tuned value
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * need),
        interpret=interpret,
        name=KERNEL_NAME,
        metadata={"kernel": KERNEL_NAME},
    )(live.astype(jnp.int32), jnp.stack([da, dtx], axis=1).astype(f32),
      jnp.stack([b, c], axis=1).astype(f32), state.astype(f32))
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
