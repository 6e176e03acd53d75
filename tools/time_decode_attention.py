#!/usr/bin/env python3
"""Time the merged-pool decode attention call ALONE on the chip
(ISSUE 32, step 1; no benchmark cell runs this).

    chiprun -- python3 tools/time_decode_attention.py --cell granite \\
        --groups 4,8,16,32 --variants body,bare
    chiprun -- python3 tools/time_decode_attention.py --cell sambay --window 512

One `hybrid_decode_attention` call (`parallel/paged_attention.py`) at a
benchmark cell's geometry — the head shapes from the configuration's
`shape` group, slots, block size, pool blocks and positions from its
`engine` group, each an argument here — over contexts drawn uniformly
from --context-lo .. --context-hi through tables that name distinct
blocks, as an allocator would. For every group size G (blocks a grid
step; `rule` is what the program itself picks) and body variant it
prints one JSON line: device microseconds of the kernel a call (median
over --calls, read from a profiler trace by the kernel's name), its grid
steps, microseconds a step, the rest of the call (the work list's
programs) and the least time of the call from the cell's own cost
function (`benchmarks/chip/lib/costs_*.py`, the one its roofline metric
divides by) over the published HBM bandwidth.

Variants: `body` is the program's kernel. The others answer "what does
the work on the score tile cost?" and give WRONG ANSWERS, timing only:
`nomask` drops the head/position masks and the NEG_INF `where`s,
`nosplit` sends P as one 16-bit product instead of hi + lo halves,
`bare` drops both; `qkonly`, `pvonly` and `noproducts` keep `body`'s
tile work and drop one or both of the two matrix products.

--worklist also times `_decode_worklist` alone under both re-naming
rules. --tiny is a rehearsal on the CPU (kernels interpreted, wall
clock only): its numbers are not device times and say so.
"""

from __future__ import annotations

import argparse
import functools
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
from jax.experimental import pallas as pl  # noqa: E402

from lib import costs_granite_hybrid, costs_sambay, peaks  # noqa: E402
from paddle_tpu.parallel import paged_attention as pa  # noqa: E402
from paddle_tpu.parallel.kernel_utils import NEG_INF  # noqa: E402

CELLS = {
    # name: (configuration, cost function -> [(calls, flops, bytes)],
    #        (pair-rows a token, query rows a pair-row, row width))
    "granite": ("granite_4_0_h_micro",
                costs_granite_hybrid.gqa_decode_attention_cost,
                lambda sh: (sh["kv_heads"] // 2,
                            2 * sh["heads"] // sh["kv_heads"],
                            2 * sh["head_dim"])),
    "sambay": ("phi4_mini_flash",
               costs_sambay.hybrid_decode_attention_cost,
               lambda sh: (sh["kv_heads"] // 2,
                           2 * sh["heads"] // sh["kv_heads"],
                           2 * sh["dim"] // sh["heads"])),
}
TINY = {"granite": {"heads": 8, "kv_heads": 4, "head_dim": 8},
        "sambay": {"heads": 8, "kv_heads": 4, "dim": 64}}


def _timing_kernel(blk_ref, pos_ref, wslot_ref, wgrp_ref, *refs, Bt, G, span,
                   scale, rep=1, windowed=False, masks=True, split=True,
                   products="qk,pv"):
    """`_pa_decode_kernel` with the tile work cut out by parts."""
    if windowed:
        first_ref, q_ref, refs = refs[0], refs[1], refs[2:]
    else:
        first_ref, q_ref, refs = None, refs[0], refs[1:]
    k_refs, v_refs = refs[:G], refs[G:2 * G]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * G:]
    R, dh = q_ref.shape
    H = R // rep
    W = G * Bt
    i = pl.program_id(0)
    si, b = wslot_ref[i], wgrp_ref[i]
    pos = pos_ref[si]
    live = pos < span
    b_first = 0 if first_ref is None else jnp.where(
        live, first_ref[si] // W, 0)

    @pl.when(b == b_first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _accumulate_by_block():
        # the body's arithmetic with no [W*H, Dh] copy of K and V: a
        # product, a mask and an exp a block, the row state across them
        BH = Bt * H
        q = q_ref[...].astype(k_refs[0].dtype)
        col = jax.lax.broadcasted_iota(jnp.int32, (R, BH), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (R, BH), 0) // rep
        head = ((col & (H - 1)) if H & (H - 1) == 0
                else jax.lax.rem(col, H))
        other = head != row
        ss = []
        for g, r in enumerate(k_refs):
            sg = jax.lax.dot_general(
                q, r[...].reshape(BH, dh), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            at = (b * G + g) * Bt  # this block's first position
            masked = other | (col >= (pos - at + 1) * H)
            if first_ref is not None:
                masked = masked | (col < (first_ref[si] - at) * H)
            ss.append(jnp.where(masked, NEG_INF, sg))
        m_prev = m_ref[...]
        m_new = m_prev
        for sg in ss:
            m_new = jnp.maximum(m_new, jnp.max(sg, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        alpha = jnp.where(m_prev <= NEG_INF, 0.0, alpha)
        l_new = l_ref[...] * alpha
        acc = acc_ref[...] * alpha
        for sg, r in zip(ss, v_refs):
            pg = jnp.where(sg <= NEG_INF, 0.0, jnp.exp(sg - m_new))
            l_new = l_new + jnp.sum(pg, axis=1, keepdims=True)
            vg = r[...].reshape(BH, dh)
            hi = pg.astype(vg.dtype)
            lo = (pg - hi.astype(jnp.float32)).astype(vg.dtype)
            pv = jax.lax.dot_general(
                jnp.concatenate([hi, lo], axis=0), vg,
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            acc = acc + pv[:R] + pv[R:]
        l_ref[...] = l_new
        acc_ref[...] = acc
        m_ref[...] = m_new

    def _accumulate():
        k = jnp.concatenate([r[...].reshape(Bt * H, dh) for r in k_refs], 0)
        v = jnp.concatenate([r[...].reshape(Bt * H, dh) for r in v_refs], 0)
        if "qk" in products:
            s = jax.lax.dot_general(
                q_ref[...].astype(k.dtype), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
        else:  # nothing of the K tile is loaded; its copy is still waited for
            s = jnp.sum(q_ref[...].astype(jnp.float32), axis=1,
                        keepdims=True) + jnp.zeros((R, W * H), jnp.float32)
        if masks:
            col = jax.lax.broadcasted_iota(jnp.int32, (R, W * H), 1)
            row = jax.lax.broadcasted_iota(jnp.int32, (R, W * H), 0) // rep
            head = ((col & (H - 1)) if H & (H - 1) == 0
                    else jax.lax.rem(col, H))
            masked = (head != row) | (col >= (pos - b * W + 1) * H)
            if first_ref is not None:
                masked = masked | (col < (first_ref[si] - b * W) * H)
            s = jnp.where(masked, NEG_INF, s)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        if masks:
            p = jnp.where(s <= NEG_INF, 0.0, p)
            alpha = jnp.where(m_prev <= NEG_INF, 0.0, alpha)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if "pv" not in products:
            pv = p[:, :dh] * v[:1, :].astype(jnp.float32)
        elif split and v.dtype.itemsize == 2:
            hi = p.astype(v.dtype)
            lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
            pv = jax.lax.dot_general(
                jnp.concatenate([hi, lo], axis=0), v,
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            pv = pv[:R] + pv[R:]
        else:
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    pl.when(live)(_accumulate_by_block if products == "perblock"
                  else _accumulate)

    @pl.when(b == jnp.where(live, pos // W, 0))
    def _finalise():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = out[:, None, :].astype(o_ref.dtype)


VARIANTS = {
    "body": None,
    "nomask": functools.partial(_timing_kernel, masks=False),
    "nosplit": functools.partial(_timing_kernel, split=False),
    "bare": functools.partial(_timing_kernel, masks=False, split=False),
    # the two products by parts (the rest as `body`): what scoring a
    # row against its own head only could save is inside these
    "qkonly": functools.partial(_timing_kernel, products="qk"),
    "pvonly": functools.partial(_timing_kernel, products="pv"),
    "noproducts": functools.partial(_timing_kernel, products=""),
    # RIGHT answers: the body's arithmetic a block at a time, with no
    # concatenated copy of a group's K and V (PERF.md section 6)
    "perblock": functools.partial(_timing_kernel, products="perblock"),
}


def _device_us(trace_dir, op_prefix):
    """Per execution of the traced program: (microseconds of the ops
    named `op_prefix`, microseconds of the whole program), medians."""
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
                       if e.name.startswith(op_prefix)]
            elif line.name == "XLA Modules":
                mods = [e.duration_ns * 1e-3 for e in line.events
                        if e.name.startswith("jit_timed")]
    if not ops or not mods:
        raise RuntimeError("no %s event on the device in %s"
                           % (op_prefix, path))
    return statistics.median(ops), statistics.median(mods)


def _measure(fn, args, calls, on_chip, op_prefix):
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
        return _device_us(d, op_prefix)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", choices=sorted(CELLS), required=True)
    ap.add_argument("--window", type=int, default=0,
                    help="attend only the last N positions (a window "
                         "layer's call: `first` and `max_context`); 0 = all")
    ap.add_argument("--groups", default="rule",
                    help="comma list of blocks a grid step, or 'rule'")
    ap.add_argument("--variants", default="body")
    ap.add_argument("--slots", type=int)
    ap.add_argument("--block-tokens", type=int)
    ap.add_argument("--pool-blocks", type=int)
    ap.add_argument("--max-len", type=int)
    ap.add_argument("--context-lo", type=int, default=1536)
    ap.add_argument("--context-hi", type=int, default=4700)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--worklist", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "time_decode_attention.jsonl"))
    a = ap.parse_args(argv)

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not a.tiny:
        raise SystemExit("no TPU here: a time comes only from the chip "
                         "(--tiny rehearses on the CPU, wall clock only)")
    name, cost_fn, dims = CELLS[a.cell]
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           name + ".json")) as f:
        conf = json.load(f)
    shape, eng = dict(conf["shape"]), conf["engine"]
    if a.tiny:
        shape.update(TINY[a.cell])
    hk, rep, D = dims(shape)
    S = a.slots or (4 if a.tiny else eng["max_slots"])
    Bt = a.block_tokens or (8 if a.tiny else eng["kv_block_tokens"])
    L = a.max_len or (512 if a.tiny else conf["max_len"])
    lo, hi = ((40, 400) if a.tiny else (a.context_lo, a.context_hi))
    maxb = L // Bt
    rng = np.random.default_rng(a.seed)
    ctx = rng.integers(lo, hi + 1, S)  # attended positions: pos + 1
    pos = (ctx - 1).astype(np.int32)
    first = None
    if a.window:
        shape["window"] = a.window
        first = np.maximum(pos - a.window + 1, 0).astype(np.int32)
    named = [range((0 if first is None else first[s]) // Bt,
                   pos[s] // Bt + 1) for s in range(S)]
    need = sum(len(r) for r in named)
    NB = a.pool_blocks or (need + 8 if a.tiny or a.window
                           else eng["kv_pool_blocks"])
    NB += 1  # the pools keep one block beyond what an allocator hands out
    ids = rng.permutation(NB - 1)[:need]
    tables, at = np.full((S, maxb), -1, np.int32), 0
    for s, r in enumerate(named):
        tables[s, r.start:r.stop] = ids[at:at + len(r)]
        at += len(r)
    dt = jnp.dtype(a.dtype)
    kk, kv, kq = jax.random.split(jax.random.PRNGKey(a.seed), 3)
    k_pool = jax.random.normal(kk, (NB, Bt * hk, D), dt)
    v_pool = jax.random.normal(kv, (NB, Bt * hk, D), dt)
    q = jax.random.normal(kq, (S, hk, rep, D), dt)
    args = (q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(pos))
    if first is not None:
        args += (jnp.asarray(first),)

    rows = cost_fn(shape, [int(c) for c in ctx], Bt)
    _, flops, nbytes = rows[0] if (a.window or len(rows) == 1) else rows[-1]
    pk = peaks.device_peaks(dev.device_kind) if on_chip else None
    least = (max(nbytes / pk["hbm_bytes_per_s"],
                 flops / pk["bf16_flops_per_s"]) * 1e6 if pk else None)
    block_bytes = 2 * Bt * hk * D * dt.itemsize
    head = {"cell": a.cell, "platform": dev.platform,
            "device_kind": dev.device_kind, "q": list(q.shape),
            "pool": list(k_pool.shape), "dtype": str(dt), "window": a.window,
            "contexts": [int(ctx.min()), float(ctx.mean()), int(ctx.max())],
            "block_kv_bytes": block_bytes, "cost_bytes": nbytes,
            "least_us": least}
    print(json.dumps(head))
    out = [head]
    rule_group = pa._bytes_group

    def call(q, k, v, t, p, *f):
        return pa.paged_decode_attention(
            q, k, v, t, p, first=f[0] if f else None,
            max_context=a.window or None, scale=0.125)

    for g in a.groups.split(","):
        G = (rule_group(Bt, maxb, block_bytes) if g == "rule" else int(g))
        W = G * Bt
        g0 = 0 if first is None else first // W
        steps = int((pos // W - g0 + 1).sum())
        for variant in a.variants.split(","):
            pa._bytes_group = lambda *_, G=G: G
            body = pa._pa_decode_kernel
            if VARIANTS[variant] is not None:
                pa._pa_decode_kernel = VARIANTS[variant]
            try:
                kern, whole = _measure(call, args, a.calls, on_chip,
                                       "%hybrid_decode_attention")
            finally:
                pa._pa_decode_kernel, pa._bytes_group = body, rule_group
            row = {"G": G, "by": g, "variant": variant, "steps": steps,
                   "step_kv_bytes": G * block_bytes, "call_us": whole}
            if kern is not None:
                row.update(kernel_us=kern, us_per_step=kern / steps,
                           rest_us=whole - kern,
                           kernel_share_of_least=least / kern)
            else:
                row["note"] = "CPU wall clock, kernel interpreted: no device time"
            print(json.dumps(row))
            out.append(row)
        if a.worklist:
            span = maxb * Bt
            mg = (-(-a.window // W) + 1) if a.window else None
            t = jnp.asarray(np.pad(tables, ((0, 0), (0, -maxb % G)),
                                   constant_values=-1))
            for rule, switch in (("every entry", 1 << 30), ("look back", 0)):
                was, pa._LOOKBACK_FROM = pa._LOOKBACK_FROM, switch
                try:
                    fn = lambda t, p, *f: pa._decode_worklist(  # noqa: E731
                        t, p, Bt, G, span, first=f[0] if f else None,
                        max_groups=mg)
                    _, us = _measure(fn, (t,) + args[4:], a.calls, on_chip,
                                     "%")
                except Exception as e:  # too large to compile is a reading
                    us = "refused: %s" % str(e).split("\n")[0][:120]
                finally:
                    pa._LOOKBACK_FROM = was
                row = {"G": G, "worklist": rule,
                       "N": S * min(maxb // G + (1 if maxb % G else 0),
                                    mg or 1 << 30), "call_us": us}
                print(json.dumps(row))
                out.append(row)
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "a") as f:
        for row in out:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
