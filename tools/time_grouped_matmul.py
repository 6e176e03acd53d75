#!/usr/bin/env python3
"""Time the routed experts' grouped product ALONE on the chip (ISSUE
33, tentpole 2: measure before writing a kernel; no benchmark cell
runs this).

    chiprun -- python3 tools/time_grouped_matmul.py
    chiprun -- python3 tools/time_grouped_matmul.py --rows 512 --tn 256,512,1024

At each of the cell's two geometries — a decode step's 64 slots x 8
choices = 512 rows and a 4,096-token chunk's 32,768 rows, over 128
experts of 2048 x 1024 (gate-up `[rows, 2048] x [128, 2048, 2048]`,
then down `[rows, 1024] x [128, 1024, 2048]`), bf16, every token's 8
experts drawn from the seed without favour — it prints one JSON line a
variant: device microseconds a call (median over --calls, from a
profiler trace: the whole program's, and for the Pallas kernel the
custom call's by its name), beside the least time of the two products
(the bytes of the experts REACHED plus the rows in and out over the
published HBM bandwidth, or the operations over the bf16 peak) and the
share of it the variant reaches.

Variants: `ragged_dot` is `jax.lax.ragged_dot` over the rows sorted by
expert (no padding); `pallas` is `parallel/routed_experts.py`'s
`moe_grouped_matmul` over the tile-aligned layout at --tm / --tn (`rule`
is what the program itself picks); `layer` is the whole `expert_ffn`:
the sorts, the gathers, both products, silu(g) * u and the combine.
--tiny is a rehearsal on the CPU (wall clock only): its numbers are not
device times and say so.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from lib import peaks  # noqa: E402
from paddle_tpu.parallel import routed_experts as re_  # noqa: E402

KERNEL = "%moe_grouped_matmul"


def _device_us(trace_dir):
    """Per execution of the traced program: (microseconds of the
    kernel's custom calls, or None where it has none; microseconds of
    the whole program), medians."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    ops, mods = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = [e.duration_ns * 1e-3 for e in line.events
                       if e.name.startswith(KERNEL)]
            elif line.name == "XLA Modules":
                mods = [e.duration_ns * 1e-3 for e in line.events
                        if e.name.startswith("jit_timed")]
    if not mods:
        raise RuntimeError("no jit_timed execution on the device in " + path)
    per_call = len(ops) // len(mods) if ops else 0
    kernel = (statistics.median(
        sum(ops[i:i + per_call]) for i in range(0, len(ops), per_call))
        if per_call else None)
    return kernel, statistics.median(mods)


def _measure(fn, args, calls, on_chip):
    @jax.jit
    def timed(*xs):  # a fresh program a measurement, named for the trace
        return fn(*xs)

    jax.block_until_ready(timed(*args))  # compiles
    jax.block_until_ready(timed(*args))
    if not on_chip:
        t0 = time.perf_counter()
        for _ in range(calls):
            jax.block_until_ready(timed(*args))
        return None, (time.perf_counter() - t0) / calls * 1e6
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(calls):
            jax.block_until_ready(timed(*args))
        jax.profiler.stop_trace()
        return _device_us(d)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", default="512,32768",
                    help="(token, choice) pairs a call")
    ap.add_argument("--experts", type=int, default=128)
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--dim", type=int, default=2048)
    ap.add_argument("--width", type=int, default=1024)
    ap.add_argument("--tm", default="rule")
    ap.add_argument("--tn", default="rule")
    ap.add_argument("--variants", default="ragged_dot,pallas,layer")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    if args.tiny:
        args.experts, args.top_k, args.dim, args.width = 16, 4, 128, 128
        args.rows, args.calls = "64,512", 2
    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.tiny:
        print("no TPU: --tiny is the only CPU mode", file=sys.stderr)
        return 1
    pk = peaks.device_peaks(dev.device_kind) if on_chip else None
    E, k, d, m = args.experts, args.top_k, args.dim, args.width
    dt = jnp.bfloat16
    kernel = "fused" if on_chip else "gather"
    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed)
    w_gu = jax.random.normal(key, (E, d, 2 * m), dt) * d ** -0.5
    w_down = jax.random.normal(jax.random.fold_in(key, 1), (E, m, d),
                               dt) * m ** -0.5
    p = {"w_gu": w_gu, "w_down": w_down}

    for A in (int(r) for r in args.rows.split(",")):
        N = A // k
        idx = jnp.asarray(np.argsort(rng.random((N, E)), axis=1)[:, :k],
                          jnp.int32)
        wts = jnp.asarray(rng.random((N, k)), jnp.float32)
        u = jax.random.normal(jax.random.fold_in(key, A), (N, d), dt)
        valid = jnp.ones(N, bool)
        counts = np.bincount(np.asarray(idx).ravel(), minlength=E)
        hit = int((counts > 0).sum())
        item = 2
        flops = 2 * A * (d * 2 * m + m * d)
        nbytes = (hit * (d * 2 * m + m * d) * item
                  + A * (d + 2 * m + m) * item + A * d * 4)
        least_us = None
        if pk:
            least_us = 1e6 * max(flops / pk["bf16_flops_per_s"],
                                 nbytes / pk["hbm_bytes_per_s"])
        base = {"rows": A, "experts": E, "experts_hit": hit,
                "rows_max": int(counts.max()), "flops": flops,
                "bytes": nbytes, "least_us": least_us,
                "platform": dev.platform}

        def report(variant, kernel_us, program_us, **more):
            rec = dict(base, variant=variant, kernel_us=kernel_us,
                       program_us=program_us, **more)
            if least_us:
                rec["share_of_least_pct"] = (
                    100.0 * least_us / (kernel_us or program_us))
            else:
                rec["note"] = "CPU wall clock: not a device time"
            print(json.dumps(rec), flush=True)

        for variant in args.variants.split(","):
            if variant == "ragged_dot":
                order = jnp.argsort(idx.reshape(-1), stable=True)
                xs = u[order // k]
                sizes = jnp.asarray(counts, jnp.int32)

                def both(xs, w_gu, w_down, sizes):
                    gu = jax.lax.ragged_dot(
                        xs, w_gu, sizes,
                        preferred_element_type=jnp.float32).astype(dt)
                    h = gu[:, :m]  # the products alone: no silu(g) * u
                    return jax.lax.ragged_dot(
                        h, w_down, sizes, preferred_element_type=jnp.float32)

                ku, pu = _measure(both, (xs, w_gu, w_down, sizes),
                                  args.calls, on_chip)
                report(variant, None, pu)
            elif variant == "pallas":
                tms = [re_.row_tile(A, E) if x == "rule" else int(x)
                       for x in args.tm.split(",")]
                tns = [None if x == "rule" else int(x)
                       for x in args.tn.split(",")]
                for tm in tms:
                    plan = re_.plan_rows(idx, valid, (0, E), tm)
                    xs = u[plan["src"]]
                    for tn in tns:
                        def both(xs, w_gu, w_down, plan, tm=tm, tn=tn):
                            gu = re_.grouped_matmul(xs, w_gu, plan, tm,
                                                    kernel=kernel, tn=tn)
                            return re_.grouped_matmul(
                                gu[:, :m], w_down, plan, tm,
                                out_dtype=jnp.float32, kernel=kernel, tn=tn)

                        ku, pu = _measure(both, (xs, w_gu, w_down, plan),
                                          args.calls, on_chip)
                        report(variant, ku, pu, tm=tm, tn=tn or "rule",
                               tiles=int(plan["n_tiles"]),
                               rows_padded=int(xs.shape[0]))
            elif variant == "layer":
                def layer(u, idx, wts, p, valid):
                    return re_.expert_ffn(u, idx, wts, p, valid,
                                          kernel=kernel)[0]

                ku, pu = _measure(layer, (u, idx, wts, p, valid), args.calls,
                                  on_chip)
                report(variant, ku, pu)
            else:
                raise SystemExit("unknown variant %r" % variant)
    return 0


if __name__ == "__main__":
    sys.exit(main())
