#!/usr/bin/env python3
"""Time the Mamba-2 one-token state update ALONE on the chip (ISSUE 34,
satellite 1; no benchmark cell runs this).

    chiprun -- python3 tools/time_ssd_update.py --streams
    chiprun -- python3 tools/time_ssd_update.py --forms phased --steps rule,1,2,4,8

One `ssd_state_update` call (`parallel/ssd_update.py`) at the geometry
of `granite4hmicro_reason_closed` — the slots from the configuration's
`engine` group, the state's `[d_state, mamba_heads x mamba_head_dim]`
from its `shape` group, float32 — with nothing beside it in the
program. For every form of the call and every step size it prints one
JSON line: device microseconds of the kernel a call (median over
--calls, read from a profiler trace by the kernel's name), its steps,
microseconds a step, and its share of the call's least time by the
cell's own cost function
(`benchmarks/chip/lib/costs_granite_hybrid.py:ssd_state_update_cost`,
the one `ssd_decode_roofline` divides by) over the published HBM
bandwidth; beside them the largest difference from
`ssd_state_update_reference` and whether a parked slot's state came
back bit for bit.

--forms: `apart` is the call as PR 31 wrote it (a grid over the state's
blocks, Pallas's own double-buffered copies in and out; `da`, `dtx`,
`b`, `c` four operands, `b` and `c` as `[S, N, 1]` columns; the tile's
arithmetic on whole arrays), kept here with its step made an argument;
`phased` is the program's kernel: one grid step, batches of slots read
and written back in turns, the side operands merged into `[S, 2, di]`
and `[S, 2, N]` rows. (That grid over whole slots WITH the merged
operands, ISSUE 34's first form, read what `apart` reads alone and was
retired in the cell: PERF.md section 6, PR 34.)
--steps: whole slots a grid step or batch, `half` for 2,048 channels of
one slot (1 MiB each way: `apart` at `half` is PR 31's call exactly,
and the FIRST line printed, next to the microseconds the same call
takes inside the cell's decode step, --cell-us), `rule` for what the
program itself picks (`_step_slots`). --streams times what the chip's
HBM gives a kernel at all: the state's bytes only read, only written,
and copied through VMEM with reads and writes in flight together.
--tiny is a rehearsal on the CPU (kernels interpreted, wall clock
only): its numbers are not device times and say so.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from lib import costs_granite_hybrid, peaks  # noqa: E402
from paddle_tpu.parallel import ssd_update as su  # noqa: E402
from time_decode_attention import _measure  # noqa: E402

CONFIG = "granite_4_0_h_micro"
TINY = {"mamba_heads": 4, "mamba_head_dim": 64, "d_state": 16}


def _kernel_apart(live_ref, s_ref, da_ref, dtx_ref, b_ref, c_ref, o_ref,
                  y_ref):
    """PR 31's body, a slot of the step after another."""
    G = s_ref.shape[0]
    for g in range(G):
        s = s_ref[g]  # [N, tile]
        new = jnp.exp(da_ref[g]) * s + b_ref[g] * dtx_ref[g]
        o_ref[g] = jnp.where(live_ref[pl.program_id(0) * G + g] != 0, new, s)
        y_ref[g] = jnp.sum(new * c_ref[g], axis=0, keepdims=True)


def _update_apart(state, da, dtx, b, c, live, G, tile, interpret):
    """PR 31's call: grid (slot groups, channel tiles), seven copies a
    step."""
    S, N, di = state.shape
    f32 = jnp.float32

    def chan(i, j, live):
        return (i, 0, j)

    def col(i, j, live):
        return (i, 0, 0)

    new, y = pl.pallas_call(
        _kernel_apart,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S // G, di // tile),
            in_specs=[pl.BlockSpec((G, N, tile), chan),
                      pl.BlockSpec((G, 1, tile), chan),
                      pl.BlockSpec((G, 1, tile), chan),
                      pl.BlockSpec((G, N, 1), col),
                      pl.BlockSpec((G, N, 1), col)],
            out_specs=[pl.BlockSpec((G, N, tile), chan),
                       pl.BlockSpec((G, 1, tile), chan)]),
        out_shape=[jax.ShapeDtypeStruct((S, N, di), f32),
                   jax.ShapeDtypeStruct((S, 1, di), f32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=100 << 20),
        interpret=interpret,
        name=su.KERNEL_NAME,
    )(live.astype(jnp.int32), state, da[:, None, :], dtx[:, None, :],
      b[:, :, None], c[:, :, None])
    return new, y[:, 0]


def _stream(kind, G, interpret):
    """The state's bytes and nothing else: `reads` HBM -> VMEM,
    `writes` VMEM -> HBM (two copies of G slots in flight), `copies`
    each block in and out again through Pallas's own pipeline, a read
    and a write in flight together."""
    def kernel(x_hbm, o_hbm, buf, sem):
        K = o_hbm.shape[0] // G

        def dma(k):
            at = pl.ds(k * G, G)
            if kind == "writes":
                return pltpu.make_async_copy(buf.at[k % 2], o_hbm.at[at],
                                             sem.at[k % 2])
            return pltpu.make_async_copy(x_hbm.at[at], buf.at[k % 2],
                                         sem.at[k % 2])

        dma(0).start()

        def body(k, carry):
            @pl.when(k + 1 < K)
            def _():
                dma(k + 1).start()

            dma(k).wait()
            return carry

        jax.lax.fori_loop(0, K, body, 0)

    def copy_kernel(s_ref, o_ref):
        o_ref[...] = s_ref[...]

    def fn(state):
        S, N, di = state.shape
        kw = dict(out_shape=jax.ShapeDtypeStruct(state.shape, state.dtype),
                  input_output_aliases={0: 0}, interpret=interpret,
                  compiler_params=pltpu.CompilerParams(
                      vmem_limit_bytes=100 << 20),
                  name=su.KERNEL_NAME)
        if kind == "copies":
            block = pl.BlockSpec((G, N, di), lambda i: (i, 0, 0))
            return pl.pallas_call(copy_kernel, grid=(S // G,),
                                  in_specs=[block], out_specs=block,
                                  **kw)(state)
        return pl.pallas_call(
            kernel, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((2, G, N, di), state.dtype),
                            pltpu.SemaphoreType.DMA((2,))], **kw)(state)
    return fn


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--forms", default="apart,phased")
    ap.add_argument("--streams", action="store_true")
    ap.add_argument("--steps", default="half,1,2,4",
                    help="comma list: slots a grid step, 'half', 'rule'")
    ap.add_argument("--slots", type=int)
    ap.add_argument("--cell-us", type=float, default=408.0,
                    help="the call inside the cell's decode step "
                         "(PERF.md section 5; ledger, PR 33)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "time_ssd_update.jsonl"))
    a = ap.parse_args(argv)

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not a.tiny:
        raise SystemExit("no TPU here: a time comes only from the chip "
                         "(--tiny rehearses on the CPU, wall clock only)")
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           CONFIG + ".json")) as f:
        conf = json.load(f)
    shape = dict(conf["shape"])
    if a.tiny:
        shape.update(TINY)
    S = a.slots or (4 if a.tiny else conf["engine"]["max_slots"])
    N = shape["d_state"]
    di = shape["mamba_heads"] * shape["mamba_head_dim"]
    rng = np.random.default_rng(a.seed)
    draw = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    state, dtx, b, c = draw(S, N, di), draw(S, di), draw(S, N), draw(S, N)
    da = -jnp.abs(draw(S, di))
    # timed with every slot live, as the cell's steps are; compared
    # with the reference with slot 0 parked
    args = (state, da, dtx, b, c, jnp.ones((S,), bool))
    check = args[:5] + (jnp.asarray(np.arange(S) > 0),)
    want_s, want_y = jax.jit(su.ssd_state_update_reference)(*check)

    # a live slot's bytes, whatever its context
    ((_, _, nbytes),) = costs_granite_hybrid.ssd_state_update_cost(
        shape, [1] * S, conf["engine"]["kv_block_tokens"])
    pk = peaks.device_peaks(dev.device_kind) if on_chip else None
    least = nbytes / pk["hbm_bytes_per_s"] * 1e6 if pk else None
    head = {"platform": dev.platform, "device_kind": dev.device_kind,
            "state": [S, N, di], "cost_bytes": nbytes,
            "least_us": least, "in_the_cell_us": a.cell_us,
            "in_the_cell_share_of_least": least and least / a.cell_us}
    print(json.dumps(head))
    out = [head]
    interpret = not on_chip
    kernel = "%" + su.KERNEL_NAME
    state_mb = S * N * di * 4 / 1e6
    for kind in ("reads", "writes", "copies") if a.streams else ():
        kern, whole = _measure(_stream(kind, 1, interpret), (state,),
                               a.calls, on_chip, kernel)
        moved = state_mb * (2 if kind == "copies" else 1)
        row = {"stream": kind, "bytes": moved * 1e6, "call_us": whole}
        if kern is not None:
            row.update(kernel_us=kern, gb_per_s=moved / kern * 1e3)
        print(json.dumps(row))
        out.append(row)
    for form, step in itertools.product(a.forms.split(","),
                                        a.steps.split(",")):
        if form != "apart" and step == "half":
            continue  # the program's kernel takes whole slots
        G = (1 if step == "half" else
             su._step_slots(S, N * di * 4) if step == "rule" else int(step))
        tile = di // 2 if step == "half" else di
        if form == "apart":
            fn = lambda *xs, G=G, tile=tile: _update_apart(  # noqa: E731
                *xs, G, tile, interpret)
        else:
            fn = lambda *xs, G=G: su._update(  # noqa: E731
                *xs, slots=G, interpret=interpret)
        row = {"form": form, "by": step, "slots_a_step": G,
               "channels_a_step": tile, "steps": S // G * (di // tile),
               "step_state_bytes": G * N * tile * 4}
        try:
            if S % G:
                raise ValueError("%d slots a step do not divide %d" % (G, S))
            kern, whole = _measure(fn, args, a.calls, on_chip, kernel)
            got_s, got_y = jax.jit(lambda *xs: fn(*xs))(*check)
        except Exception as e:  # refused by Mosaic is a reading
            row["refused"] = str(e).split("\n")[0][:200]
        else:
            row.update(
                state_max_err=float(jnp.abs(got_s - want_s).max()),
                y_max_err=float(jnp.abs(got_y - want_y)[1:].max()),
                parked_bit_identical=bool(jnp.array_equal(got_s[0],
                                                          state[0])),
                call_us=whole)
            if kern is not None:
                row.update(kernel_us=kern, us_per_step=kern / row["steps"],
                           kernel_share_of_least=least / kern)
            else:
                row["note"] = ("CPU wall clock, kernel interpreted: "
                               "no device time")
        print(json.dumps(row))
        out.append(row)
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "a") as f:
        for row in out:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
