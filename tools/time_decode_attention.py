#!/usr/bin/env python3
"""Time the paged decode attention call ALONE on the chip
(no benchmark cell runs this). On one TPU chip:

    python3 tools/time_decode_attention.py --cell granite \\
        --groups 4,8,16,32 --variants body,noproducts
    python3 tools/time_decode_attention.py --cell trinity --window 2048
    python3 tools/time_decode_attention.py --cell sambay --window 512
    python3 tools/time_decode_attention.py --cell kanana \\
        --context-lo 1551 --context-hi 7168 --shorts rule,two,whole
    python3 tools/time_decode_attention.py --cell gpt \\
        --context-lo 300 --context-hi 1300 --forms call,merged \\
        --variants body,noproducts,copies

One `hybrid_decode_attention` call (`parallel/paged_attention.py`) at a
benchmark cell's geometry — or, `--cell kanana`, one
`mla_decode_attention` call, the same ring body over ONE latent pool
whose row is the key and whose first kv_rank lanes the value; or,
`--cell gpt`, the GPT block's `paged_decode_attention` call on its
4-D pool `[NB, Bt, H, Dh]` (`--forms call`, named
`paged_decode_attention`) and the same pools handed over as their
merged view with one query row a head (`--forms merged`, the
`hybrid_decode_attention` call) — the
head shapes from the configuration's `shape` group, slots, block size,
pool blocks and positions from its `engine` group, each an argument
here — over contexts drawn uniformly from --context-lo .. --context-hi
through tables that name distinct blocks, as an allocator would. For
every group size G (blocks a ring place; `rule` is what the program
itself picks), ladder of rungs a slot's last group folds (`--shorts`)
and body variant it prints one JSON line: device microseconds of the
kernel a call (median over --calls, read from a profiler trace by the
kernel's name), the groups it folds (`steps`) and how many of those
folds are cut in two overlapped halves (`overlapped`: the latent call's,
`_fold_halves`), the blocks it copies
(`blocks`, those the tables name) beside the blocks it folds
(`folded`: whole groups, and the first rung that covers a slot's last
one), microseconds a group, the rest of the program (`rest_us`: what
is left beside the kernel — there is no work list, the kernel
reads the tables) and the least time of the call from the cell's own
cost function (`benchmarks/chip/lib/costs_*.py`, the one its roofline
metric divides by) over the published HBM bandwidth.

Variants: `body` is the program's kernel; `serial` is the same kernel
with every fold one score tile whose chain runs serially (no
`_fold_halves`: the form before the halves, and what a call bound by its
copies still runs), checked against the reference like `body`. The
others keep its copies, ring and walk, put their own functions in the
place of its score tile and softmax fold, and give WRONG ANSWERS,
timing only: `nomask` drops the head/position masks and the NEG_INF
`where`s, `nosplit` sends P as one 16-bit product instead of hi + lo
halves, `bare` drops both; `qkonly`, `pvonly` and `noproducts` keep
`body`'s tile work and drop one or both of the two matrix products
(`noproducts`: what is left is the copies, the walk and the tile's
element-wise work); `copies` keeps the copies and the walk alone (no
score tile, no fold). `a+b` applies both: `serial+qkonly` is `qkonly`
with the serial fold; `halves` cuts every fold in two overlapped halves
(`_fold_halves`) on a call bound by its copies too, and answers like
`body`. A variant replaces functions of the ring body:
it does nothing to a call that another body serves, which is why each
row names the tree whose `paddle_tpu` it timed (`tree`) — run from a
checkout of an older tree, `--forms call` times that tree's GPT call.

--tiny is a rehearsal on the CPU (kernels interpreted, wall clock
only): its numbers are not device times and say so.
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

from lib import (costs, costs_afmoe, costs_granite_hybrid,  # noqa: E402
                 costs_mla_moe, costs_sambay, peaks)
from paddle_tpu.parallel import paged_attention as pa  # noqa: E402
from paddle_tpu.parallel.kernel_utils import NEG_INF  # noqa: E402

def _gpt_cost(shape, contexts, block_tokens):
    """`paged_decode_attention_cost` (-> flops, bytes) as the others'
    rows."""
    return [(1,) + costs.paged_decode_attention_cost(shape, contexts,
                                                     block_tokens)]


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
    # no pairing: a K/V head's rows as they are, its queries beside them
    "trinity": ("trinity_mini",
                costs_afmoe.swa_decode_attention_cost,
                lambda sh: (sh["kv_heads"], sh["heads"] // sh["kv_heads"],
                            sh["head_dim"])),
    # ONE latent row a token (c, the rotated k_r, zeros to whole 128-lane
    # tiles) that every head's absorbed query reads: no values pool
    "kanana": ("kanana_2_30b_a3b",
               costs_mla_moe.mla_decode_attention_cost,
               lambda sh: (1, sh["heads"],
                           -(-(sh["kv_rank"] + sh["rope_dim"]) // 128) * 128)),
    # the GPT block's 4-D pool: H heads of one query row each
    "gpt": ("cerebras_gpt_1p3b", _gpt_cost,
            lambda sh: (sh["heads"], 1, sh["dim"] // sh["heads"])),
}
TINY = {"gpt": {"heads": 4, "dim": 32},
        "granite": {"heads": 8, "kv_heads": 4, "head_dim": 8},
        "sambay": {"heads": 8, "kv_heads": 4, "dim": 64},
        "trinity": {"heads": 8, "kv_heads": 2, "head_dim": 16},
        "kanana": {"heads": 8, "kv_rank": 64, "rope_dim": 32}}


def _scores(q, k, scale, rep, at, pos, first, *, masks=True, product=True):
    """`pa._masked_scores` with parts cut out."""
    R, C = q.shape[0], k.shape[0]
    H = R // rep
    if product:
        s = jax.lax.dot_general(
            q.astype(k.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
    else:  # nothing of the K tile is loaded; its copy is still waited for
        s = jnp.sum(q.astype(jnp.float32), axis=1,
                    keepdims=True) + jnp.zeros((R, C), jnp.float32)
    if not masks:
        return s
    col = jax.lax.broadcasted_iota(jnp.int32, (R, C), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (R, C), 0) // rep
    head = (col & (H - 1)) if H & (H - 1) == 0 else jax.lax.rem(col, H)
    masked = (head != row) | (col >= (pos - at + 1) * H)
    if first is not None:
        masked = masked | (col < (first - at) * H)
    return jnp.where(masked, NEG_INF, s)


def _pv(p, v, l_cur, *, split=True, product=True):
    """`pa._pv` with parts cut out."""
    R = p.shape[0]
    if not product:  # one row of V read, whatever the tile's width
        return l_cur * v[:1, :].astype(jnp.float32)
    if split and v.dtype.itemsize == 2:
        hi = p.astype(v.dtype)
        lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
        pv = jax.lax.dot_general(
            jnp.concatenate([hi, lo], axis=0), v,
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return pv[:R] + pv[R:]
    return jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _fold(s, v, acc_ref, m_ref, l_ref, *, masks=True, **parts):
    """`pa._fold_tile` with parts cut out."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    if masks:
        p = jnp.where(s <= NEG_INF, 0.0, p)
        alpha = jnp.where(m_prev <= NEG_INF, 0.0, alpha)
    l_cur = jnp.sum(p, axis=1, keepdims=True)
    l_ref[...] = l_ref[...] * alpha + l_cur
    acc_ref[...] = acc_ref[...] * alpha + _pv(p, v, l_cur, **parts)
    m_ref[...] = m_new


def _halves(tiles, acc_ref, m_ref, l_ref, *, masks=True, **parts):
    """`pa._fold_halves` with parts cut out."""
    got = []
    for s, v in tiles:
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        if masks:
            p = jnp.where(s <= NEG_INF, 0.0, p)
        l_cur = jnp.sum(p, axis=1, keepdims=True)
        got.append((m, l_cur, _pv(p, v, l_cur, **parts)))
    m_new = m_ref[...]
    for m, _, _ in got:
        m_new = jnp.maximum(m_new, m)

    def weight(m):
        w = jnp.exp(m - m_new)
        return jnp.where(m <= NEG_INF, 0.0, w) if masks else w

    alpha = weight(m_ref[...])
    l, acc = l_ref[...] * alpha, acc_ref[...] * alpha
    for m, l_cur, pv in got:
        w = weight(m)
        l, acc = l + w * l_cur, acc + w * pv
    l_ref[...], acc_ref[...], m_ref[...] = l, acc, m_new


def _cut_parts(masks=True, split=True, qk=True, pv=True):
    """-> the replacements of `pa._masked_scores`, `pa._fold_tile` and
    `pa._fold_halves` that cut out the parts named False."""
    fold = dict(masks=masks, split=split, product=pv)
    return {"_masked_scores": functools.partial(_scores, masks=masks,
                                                product=qk),
            "_fold_tile": functools.partial(_fold, **fold),
            "_fold_halves": functools.partial(_halves, **fold)}


def _with_halves(ring_call):
    """`pa._ring_call` that cuts every fold of two 128-row score tiles
    or more in two overlapped halves (`overlap`), as the call bound by
    its fold does, whatever bounds this call."""
    def call(*args, **kw):
        return ring_call(*args, **dict(kw, overlap=True))

    call.clear_cache = ring_call.clear_cache
    return call


# name: what takes the place of functions of `pa` inside the program's
# own kernel, whose copies, ring and walk stay; "a+b" applies both
VARIANTS = {
    "body": {},
    # the fold before the halves: one score tile a fold, its
    # chain serial (the latent call's copy-bound siblings still fold so)
    "serial": {"_half_cut": lambda *_: None},
    "nomask": _cut_parts(masks=False),
    "nosplit": _cut_parts(split=False),
    "bare": _cut_parts(masks=False, split=False),
    # the two products by parts (the rest as `body`)
    "qkonly": _cut_parts(pv=False),
    "pvonly": _cut_parts(qk=False),
    "noproducts": _cut_parts(qk=False, pv=False),
    # the folds of a call bound by its fold (`_fold_halves`), on any call
    "halves": {"_ring_call": _with_halves(pa._ring_call)},
    # the copies and the walk: the score tile is zeros and no fold runs
    "copies": {"_masked_scores": lambda q, k, *_: jnp.zeros(
                   (q.shape[0], k.shape[0]), jnp.float32),
               "_fold_tile": lambda *_: None,
               "_fold_halves": lambda *_: None},
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


def _max_error(got, q, k_pool, v_pool, tables, pos, first, Bt, scale,
               v_lanes=0):
    """Largest |got - plain softmax attention through the tables|, the
    reference in float64 on the host, slot by slot. With `v_lanes` the
    one latent pool: q [S, H, W], the values a row's first lanes."""
    got, q = np.asarray(got, np.float64), np.asarray(q, np.float64)
    if v_lanes:
        q, got, v_pool = q[:, None], got[:, None], k_pool
    k_pool, v_pool = np.asarray(k_pool), np.asarray(v_pool)
    hk, D = q.shape[1], q.shape[3]
    worst = 0.0
    for s in range(len(pos)):
        ps = np.arange(0 if first is None else first[s], pos[s] + 1)
        rows = tables[s, ps // Bt].astype(np.int64) * Bt + ps % Bt
        k = k_pool.reshape(-1, hk, D)[rows].astype(np.float64)
        v = v_pool.reshape(-1, hk, D)[rows].astype(np.float64)
        if v_lanes:
            v = v[..., :v_lanes]
        sc = np.einsum("grd,ngd->grn", q[s], k) * scale
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        want = np.einsum("grn,ngd->grd", pr / pr.sum(-1, keepdims=True), v)
        worst = max(worst, float(np.abs(got[s] - want).max()))
    return worst


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
                         "layer's call: `first`); 0 = all")
    ap.add_argument("--groups", default="rule",
                    help="comma list of blocks a grid step, or 'rule'")
    ap.add_argument("--variants", default="body")
    ap.add_argument("--forms", default="call",
                    help="--cell gpt: comma list of 'call' (the 4-D pool, "
                         "as the GPT block calls it) and 'merged' (its "
                         "merged view, the grouped-query call)")
    ap.add_argument("--shorts", default="rule",
                    help="comma list of the ladders a slot's last group "
                         "folds: 'rule' (the program's `_rungs`), 'two' (a "
                         "quarter of a group or all of it: the older "
                         "rule), 'whole' (always all of it) or N "
                         "(rungs of N blocks)")
    ap.add_argument("--rings", default="rule",
                    help="comma list of groups the VMEM ring holds, or "
                         "'rule' (the program's `_RING`)")
    ap.add_argument("--slots", type=int)
    ap.add_argument("--block-tokens", type=int)
    ap.add_argument("--pool-blocks", type=int)
    ap.add_argument("--max-len", type=int)
    ap.add_argument("--context-lo", type=int, default=1536)
    ap.add_argument("--context-hi", type=int, default=4700)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=20)
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
    latent = a.cell == "kanana"
    v_lanes = shape["kv_rank"] if latent else 0
    S = a.slots or (4 if a.tiny else eng["max_slots"])
    # the GPT cell's engine keeps the engine's default 16-token blocks
    Bt = a.block_tokens or (8 if a.tiny else eng.get("kv_block_tokens", 16))
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
    if latent:  # the one pool: q [S, heads, row], no values pool
        v_pool, q = None, jax.random.normal(kq, (S, rep, D), dt)
        args = (q, k_pool, jnp.asarray(tables), jnp.asarray(pos))
    else:
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
    block_bytes = (1 if latent else 2) * Bt * hk * D * dt.itemsize
    head = {"cell": a.cell, "tree": os.path.dirname(os.path.dirname(
                os.path.abspath(pa.__file__))), "platform": dev.platform,
            "device_kind": dev.device_kind, "q": list(q.shape),
            "pool": list(k_pool.shape), "dtype": str(dt), "window": a.window,
            "contexts": [int(ctx.min()), float(ctx.mean()), int(ctx.max())],
            "block_kv_bytes": block_bytes, "cost_bytes": nbytes,
            "least_us": least, "v_lanes": v_lanes}
    print(json.dumps(head))
    out = [head]
    # the latent call's scale is the cell's, 1 / sqrt(nope + rope)
    # (the GPT block's 1 / sqrt(head width), the hybrids' 0.125)
    scale = ((shape["nope_dim"] + shape["rope_dim"]) ** -0.5 if latent
             else D ** -0.5 if a.cell == "gpt" else 0.125)

    def call(*xs, form="merged"):
        if latent:
            return pa.mla_decode_attention(*xs, v_lanes, scale)
        q, k, v, t, p, *f = xs
        if form == "call":  # the GPT block's: q [S, H, Dh], 4-D pools
            four = (NB, Bt, hk, D)
            return pa.paged_decode_attention(
                q.reshape(S, hk, D), k.reshape(four), v.reshape(four), t,
                p).reshape(q.shape)
        return pa.paged_decode_attention(
            q, k, v, t, p, first=f[0] if f else None, scale=scale)

    rule = {n: getattr(pa, n) for n in (
        "_bytes_group", "_RING", "_rungs", "_masked_scores", "_fold_tile",
        "_fold_halves", "_half_cut", "_ring_call")}

    def ladder(form, G):
        """The rungs a slot's last group folds under `form`."""
        if form == "rule":
            return rule["_rungs"](G, Bt * hk, latent)
        if form in ("two", "whole"):
            return ((max(1, G // 4),) if form == "two" else ()) + (G,)
        return tuple(range(int(form), G, int(form))) + (G,)

    forms = a.forms.split(",") if a.cell == "gpt" else ["merged"]
    sweep = [(g, r, sh, v, fm) for g in a.groups.split(",")
             for r in a.rings.split(",") for sh in a.shorts.split(",")
             for v in a.variants.split(",") for fm in forms]
    b0 = 0 if first is None else first // Bt
    blocks = pos // Bt + 1 - b0  # a slot's, from the block of `first`
    for g, r, sh, variant, form in sweep:
        G = (rule["_bytes_group"](Bt, maxb, block_bytes, latent)
             if g == "rule" else int(g))
        rungs = ladder(sh, G)
        last = (blocks - 1) % G + 1
        folds = [G] * int(((blocks - last) // G).sum()) + [
            next(b for b in rungs if b >= n) for n in last.tolist()]
        patch = {}
        for part in variant.split("+"):
            patch.update(VARIANTS[part])
        # the folds cut in two halves (`pa._fold_halves`): the latent
        # call's (the program overlaps where a call is bound by its
        # fold), or any call's under `halves`
        cut = patch.get("_half_cut", rule["_half_cut"])
        overlapped = sum(cut(b, Bt * hk) is not None for b in folds) \
            if latent or "_ring_call" in patch else 0
        pa._bytes_group = lambda *_, G=G: G
        pa._RING = rule["_RING"] if r == "rule" else int(r)
        pa._rungs = lambda *_, rungs=rungs: rungs
        for n, fn in patch.items():
            setattr(pa, n, fn)
        pa._ring_call.clear_cache()  # a body traced with THIS form
        row = {"G": G, "by": g, "ring": pa._RING, "shorts": sh,
               "rungs": list(rungs), "variant": variant, "form": form,
               "steps": len(folds), "overlapped": overlapped,
               "blocks": need, "folded": sum(folds),
               "folded_per_named": sum(folds) / need,
               "step_kv_bytes": G * block_bytes}
        try:
            timed = functools.partial(call, form=form)
            kern, row["call_us"] = _measure(
                timed, args, a.calls, on_chip,
                "%mla_decode_attention" if latent
                else "%paged_decode_attention" if form == "call"
                else "%hybrid_decode_attention")
            if set(variant.split("+")) <= {"body", "serial", "halves"}:
                # the others answer nothing
                # a function of its own: `jax.jit(call)` would reuse
                # the first row's trace, and check THAT row's form
                row["max_error"] = _max_error(
                    jax.jit(lambda *xs: timed(*xs))(*args), q, k_pool,
                    v_pool, tables, pos, first, Bt, scale, v_lanes)
                if not row["max_error"] < 0.02:  # bf16: an ulp at |out| < 4
                    raise SystemExit("the call is off its reference: %s"
                                     % json.dumps(row))
        finally:
            for n, fn in rule.items():
                setattr(pa, n, fn)
            pa._ring_call.clear_cache()
        if kern is not None:
            row.update(kernel_us=kern, us_per_step=kern / row["steps"],
                       rest_us=row["call_us"] - kern,
                       kernel_share_of_least=least / kern)
        else:
            row["note"] = "CPU wall clock, kernel interpreted: no device time"
        print(json.dumps(row))
        out.append(row)
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "a") as f:
        for row in out:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
