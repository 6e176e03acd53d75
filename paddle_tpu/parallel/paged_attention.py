"""Pallas TPU paged attention: attend THROUGH the block table, with the
gather happening inside the kernel (ISSUE 13).

The paged serving primitives (models/transformer.py: paged_decode_step,
paged_verify_step, paged_prefill_chunk) attend over a block-pool KV
cache [NB, Bt, H, Dh] indirected by per-slot block tables [S, MAXB]
(PagedAttention, Kwon et al., SOSP '23). Their XLA form materialises a
transient contiguous per-slot view [S, MAXB*Bt, H, Dh] PER LAYER
(`_paged_view` — PERF.md's "known trade until a fused paged kernel
lands"): HBM write + read of the whole gathered context every step,
which is exactly the traffic a decode step is bounded by. These kernels
delete that view: the grid walks each slot's block table with the
table and positions as SCALAR-PREFETCH operands
(PrefetchScalarGridSpec), so the pipeline DMAs each group's G K/V
blocks [Bt, H, Dh] straight from the pool buffer into VMEM (G blocks
per step so the score tile spans G*Bt >= 128 tokens — the reference
pages_per_compute_block idea; the decode call copies its blocks
itself, see "What the decode call's own copies are for" below) —
the "gather" is the index map, and no
HBM-resident contiguous view ever exists. Blockwise online softmax
(running (max, sum, acc), the flash_attention.py discipline) keeps
VMEM at one group of blocks plus the accumulators, regardless of
context length.

Two kernel bodies, chosen by the shape of the call and nothing else:

  * R >= 8 window rows (verify windows, prefill chunks) and every
    call on a quantized pool — `_pa_kernel`: a (slots, row-tiles,
    table-groups) grid, heads as a static loop, one `[R, W]` score
    tile a head. With R rows on the MXU's left the per-head product
    is the right shape.
  * R == 1, the decode step's one query a slot, on an unquantized
    pool — `_pa_ring_decode_kernel`: all of a slot's heads at once,
    ONE product `q [H * rep, Dh] . K^T` against a group's rows as they
    lie, (token, head), with the head match folded into the position
    mask. The call makes its own K/V copies. The pools stay in HBM; a
    grid step is a SLOT, reads the slot's block ids from the table
    itself and copies the blocks its context names, a group at a time,
    into a VMEM ring of three groups, two groups ahead of the one it
    folds. A 4-D pool `[NB, Bt, H, Dh]` (the GPT block's) goes in as
    the merged view `[NB, Bt * H, Dh]` it already is in memory, with
    `rep` 1; the hybrid families' pools are merged already.

What the decode call's own copies are for. In
the BlockSpec form a group of G blocks is 2G one-block operands of the
pipeline — an index map, a changed-index compare, a descriptor and a
wait for each, on the one instruction stream that also issues the
products — and a work list `blk [G, N]` gathered through the tables so
that the pipeline skips a block nobody names (~220 us of every step at
64 slots), and the pipeline drains at every slot.
Measured on the v5e at the cells' geometry, 64 slots (`tools/
time_decode_attention.py`; PERF.md section 6, PRs 32 and 36): a step
of that form fits 0.30 us + 0.108 us a block of 64 KiB (K + V) at
every G against 0.080 us of DMA, and the two products ADD to it
(Trinity's full call 1,191 us, 931 without them): the copies were
never what set the step. With the call's own copies a block costs its
DMA, 0.082 us, once two groups are in flight (with ONE ahead the queue
of copies runs empty at every group: 0.55 us a group, 1,084 us a
call), the products and the tile's work run under them, and a group's
fold is 0.5 us + 0.064 us a block, hidden from ~29 blocks up — so a
group is sized by BYTES (`_bytes_group`, `_STEP_BYTES`): 32 blocks of
granite's and Trinity's 64 KiB, 16 of the GPT pool's 128 KiB, 8 of
SambaY's 160 KiB; the latent
call, whose fold and not its copies binds it, 64 of its 40 KiB (1,427
-> 1,288 us with the rungs below, measured on one v5e chip), and it
cuts each fold in two halves whose chains overlap (`_fold_halves`:
1,288 -> 1,193 us).
Microseconds a call, the BlockSpec form -> this one (least, by the
cell's bytes):
Trinity's full call over contexts of 1.5-7.2 k 1,191 -> 791 (712), its
2,048-token window call 628 -> 414 (321), granite's call 874 -> 579
(507), SambaY's shared pool 1,425 -> 1,378 (1,267), its 512-token
window call 325 -> 255 (206), the GPT block's call (32 slots over
contexts of 300-1,300) 317 -> 295 (267, its copies alone 292); and no
work list. A window's walk starts
at the block of `first`, not at a group's edge, so its 65 blocks cost
65 blocks' copies, and a slot's last group folds the first rung of
whole score tiles that covers its blocks (`_rungs`: a quarter group or
the whole one where the copies bind, which took Trinity's window call
440 -> 414 us; a ladder of eighths where the fold does).

Masking mirrors the gather primitives exactly: row r of a window based
at `base` attends positions <= base + r, so unwritten depths — and the
garbage rows a `-1` (unallocated) table entry surfaces after its clamp
to block 0 — are excluded by position and contribute EXACTLY 0. A
fully-masked block is an exact no-op on the (m, l, acc) state (the
NEG_INF guards, kernel_utils.py), so a slot whose table tail is -1
produces bit-identical output to the same slot over a fully-allocated
table (the tier-1 garbage-row invariant, tests/test_paged_kernel.py).

Two numerics families, matching the callers they replace (the same
low-bit split models/transformer.py documents):

  * decode  — `_cached_attention`'s divide-after-matmul scaling
    (scores / sqrt(Dh)); softmax accumulation in f32.
  * chunk   — `reference_attention`'s scale-into-q (q * scale BEFORE
    the matmul), the verify/prefill family.

Online softmax reorders the reduction vs the one-shot softmax the XLA
path runs, so fused-vs-gather logits agree to float tolerance, not bit
— the tested bar (atol-pinned logits + greedy token identity through
the engine), the same class as the padded-prefill drift documented
since PR 2.

Quantized pools (ISSUE 14): with `k_scale`/`v_scale` [NB, H] the
pools hold int8/fp8 codes and the kernels dequantize IN VMEM — the
scales of the blocks each slot's table names ride as scalar-prefetch
operands (SMEM, like the tables; gathered through the table to a flat
[S, MAXB*H] row per slot, so their SMEM cost follows slots x table
width and never the pool size), the DMA stays in the storage dtype,
and each per-head f32 slice multiplies by its block's scalar scale
before the matmuls. The same no-HBM-view discipline, applied to the
dequantized values: they never exist outside VMEM. A quantized decode
call (R == 1) stays on `_pa_kernel`: its scales are scalars of the
head loop; no benchmark cell runs one yet (ROADMAP B.II.2).

`interpret=None` resolves via kernel_utils.resolve_interpret: CPU CI
runs the identical kernel interpreted; on TPU it compiles to Mosaic.

Alignment: the pool's block rows are the sublane dim — keep
`kv_block_tokens` a multiple of 8 (f32; 16 for bf16; 32 for int8/fp8
storage) — and Dh is the lane dim (128-aligned Dh runs the MXU
full-width; smaller Dh works, padded).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel_utils import NEG_INF, resolve_interpret

__all__ = ["paged_decode_attention", "paged_verify_attention",
           "paged_prefill_attention", "check_paged_smem", "paged_kv_write",
           "mla_decode_attention"]


def _pa_kernel(*args, Bt: int, R: int, G: int, scale: float,
               scale_in_q: bool, quant: bool):
    """One (slot, row-tile, table-GROUP) grid step: stream the G
    consecutive blocks the slot's table names at this depth range,
    fold them into the running online-softmax state for the R window
    rows of this row tile, every head.

    Grid (S, ceil(rows/R), ceil(MAXB/G)), groups innermost — the flash
    grid-reduction pattern: init at b == 0, accumulate per group,
    finalise at the last group. `tbl_ref` [S, MAXB] / `base_ref` [S]
    are scalar-prefetch refs; the g-th K/V BlockSpec index map already
    used tbl_ref to pick physical block tbl[s, b*G + g] (clamped to 0
    when unallocated — masked below, exact no-op), so the per-head
    score tile is [R, G*Bt]. Row tile t holds window rows
    [t*R, (t+1)*R): its first row sits at global position
    base + t*R, and nothing else distinguishes the tiles — each keeps
    its own (m, l, acc) state from init to finalise.

    Mosaic constraints shape the body, each probed by AOT-compiling
    for a virtual v5e when the body was written (PRs 13 and 21; NOT
    re-probed on this JAX, 0.9.0 — the body compiles as it stands and
    tests/test_tpu_aot_compile.py holds it to that): its dot takes 2D
    operands only (no batch dims), so heads run as a static in-kernel
    loop; 16-bit mid-dim VMEM extracts don't lower, so blocks upcast
    to f32 once and every head slices f32; per-head softmax state
    must be WHOLE refs, never slices of a shared scratch (see the
    comment below); and G groups blocks until G*Bt >= 128 so the
    score tile spans full 128-lane tiles (the reference
    pages_per_compute_block idea, jax paged_attention_kernel — also
    fewer, larger grid steps for the DMA pipeline to overlap). The
    per-head slices and the f32 products are NOT free: with ONE row
    (the decode shape) a live grid step measured 2.4-2.6 us against
    1.3 us of DMA, and a dead one 0.9 us (PERF.md section 5, PR 26)
    — why R == 1 on an unquantized pool has `_pa_ring_decode_kernel`;
    with R >= 8 rows the products amortise and this body stays.
    Re-probed for the decode body on JAX 0.9.0, compiling for a
    described v5e: a 16-bit `[H, Dh] -> [H, 1, Dh]` shape cast does
    NOT lower at Dh = 64 (the f32 one does, so the ring body casts
    after it).

    With `quant` (ISSUE 14) the pools hold int8/fp8 codes and two more
    SCALAR-PREFETCH operands carry the absmax scales of the blocks the
    tables name, flat [S, MAXB*H] f32 (entry (s, d*H + h) is table
    depth d, head h): after each group's blocks upcast to f32
    in VMEM (the same one-upcast-then-slice-f32 discipline the 16-bit
    path needs anyway), every per-head 2D slice multiplies by its
    block's scalar scale read from SMEM — dequantization happens
    entirely in VMEM/SMEM, the DMA stays in the storage dtype, and no
    HBM-materialised dequantized view ever exists (the discipline that
    killed the gather tax, applied to the quant read path)."""
    if quant:
        tbl_ref, base_ref, ksc_ref, vsc_ref, q_ref = args[:5]
        refs = args[5:]
    else:
        tbl_ref, base_ref, q_ref = args[:3]
        refs = args[3:]
    k_refs = refs[:G]
    v_refs = refs[G:2 * G]
    o_ref = refs[2 * G]
    H = o_ref.shape[1]  # the output block is HEAD-major (1, H, R, Dh)
    # per-head state lives in H SEPARATE whole refs, accessed full-ref
    # only: mid-dim slice reads/writes of a shared scratch poison
    # Mosaic's layout inference (the lane-1 m/l slices gave the score
    # tile a lane-replicated layout whose reduction does not lower,
    # and the sliced acc store needs the same unimplemented relayout);
    # whole-ref per-head state is the shipped paged_attention_kernel's
    # own shape discipline
    acc_refs = refs[2 * G + 1:2 * G + 1 + H]
    m_refs = refs[2 * G + 1 + H:2 * G + 1 + 2 * H]
    l_refs = refs[2 * G + 1 + 2 * H:]
    si = pl.program_id(0)
    b = pl.program_id(2)
    nb = pl.num_programs(2)
    W = G * Bt  # tokens per grid step

    @pl.when(b == 0)
    def _init():
        for ar, mr, lr in zip(acc_refs, m_refs, l_refs):
            ar[...] = jnp.zeros_like(ar)
            mr[...] = jnp.full_like(mr, NEG_INF)
            lr[...] = jnp.zeros_like(lr)

    base = base_ref[si] + pl.program_id(1) * R  # this tile's first row
    # whole-group skip: every row of this window sits at or below
    # base + R - 1, so a group starting past that depth is fully
    # masked — skip its matmuls entirely (masked groups are exact
    # no-ops on the state either way; this is pure speed)
    @pl.when(b * W <= base + R - 1)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)  # [R, H, Dh]
        ks = [r[0].astype(jnp.float32) for r in k_refs]  # G x [Bt, H, Dh]
        vs = [r[0].astype(jnp.float32) for r in v_refs]
        if scale_in_q:  # chunk family: scale folded into q pre-matmul
            q = q * scale
        # position mask: row r (global position base + r) attends
        # depths <= base + r; everything deeper — including the
        # garbage a clamped -1 (or tail-padded) entry streams —
        # contributes exactly 0
        depth = b * W + jax.lax.broadcasted_iota(jnp.int32, (R, W), 1)
        rowpos = base + jax.lax.broadcasted_iota(jnp.int32, (R, W), 0)
        masked = depth > rowpos  # [R, W]
        for hh in range(H):
            if quant:
                # dequant per (group entry, head): 2D f32 slice times
                # one scalar SMEM scale — layout-safe (no mid-dim
                # vector ops on the quantized block). An unallocated
                # (-1) entry carries block 0's scale, like its payload:
                # garbage-but-finite, position-masked below
                k = jnp.concatenate(
                    [ks[g][:, hh, :] * ksc_ref[si, (b * G + g) * H + hh]
                     for g in range(G)], axis=0)
                v = jnp.concatenate(
                    [vs[g][:, hh, :] * vsc_ref[si, (b * G + g) * H + hh]
                     for g in range(G)], axis=0)
            else:
                k = jnp.concatenate([kk[:, hh, :] for kk in ks], axis=0)
                v = jnp.concatenate([vv[:, hh, :] for vv in vs], axis=0)
            s = jax.lax.dot_general(
                q[:, hh, :], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [R, W]
            if not scale_in_q:  # decode family: scale after the matmul
                s = s * scale
            s = jnp.where(masked, NEG_INF, s)

            m_prev = m_refs[hh][...]  # [R, 1]
            l_prev = l_refs[hh][...]
            m_cur = jax.lax.broadcast_in_dim(
                jnp.max(s, axis=1), (R, 1), (0,))
            m_new = jnp.maximum(m_prev, m_cur)
            # fully-masked guards (kernel_utils.NEG_INF contract): a
            # group with no attended depth leaves (m, l, acc) exactly
            # unchanged
            p = jnp.exp(s - m_new)
            p = jnp.where(s <= NEG_INF, 0.0, p)
            alpha = jnp.exp(m_prev - m_new)
            alpha = jnp.where(m_prev <= NEG_INF, 0.0, alpha)

            l_refs[hh][...] = l_prev * alpha + jax.lax.broadcast_in_dim(
                jnp.sum(p, axis=1), (R, 1), (0,))
            pv = jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [R, Dh]
            acc_refs[hh][...] = acc_refs[hh][...] * alpha + pv
            m_refs[hh][...] = m_new

    @pl.when(b == nb - 1)
    def _finalise():
        # the output block is head-major so each head's write indexes
        # LEADING dims only (a mid-dim 16-bit store would not lower);
        # the builder transposes back outside the kernel
        for hh in range(H):
            denom = jnp.maximum(l_refs[hh][...], 1e-30)  # [R, 1]
            o_ref[0, hh] = (acc_refs[hh][...] / denom).astype(
                o_ref.dtype)


def _fold_tile(s, v, acc_ref, m_ref, l_ref):
    """Fold one masked score tile `s` [R, C] (NEG_INF where a column is
    not the row's) and its values `v` [C, Dh] into the rows' online
    softmax state. A masked column contributes EXACTLY 0 (the NEG_INF
    guards, kernel_utils.py). Against a 16-bit V, P goes as two 16-bit
    halves (hi + lo, stacked on the rows of ONE product, so V is loaded
    into the MXU once): P keeps ~16 bits of mantissa instead of 8, for
    1 % of the call."""
    m_prev = m_ref[...]  # [R, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(s <= NEG_INF, 0.0, p)
    alpha = jnp.exp(m_prev - m_new)
    alpha = jnp.where(m_prev <= NEG_INF, 0.0, alpha)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    pv = _pv(p, v)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_new


def _pv(p, v):
    """P [R, C] float32 . V [C, Dh] -> [R, Dh] float32; against a
    16-bit V, P as its hi + lo halves stacked on the rows of ONE
    product (`_fold_tile`)."""
    R = p.shape[0]
    if v.dtype.itemsize == 2:
        hi = p.astype(v.dtype)
        lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
        pv = jax.lax.dot_general(
            jnp.concatenate([hi, lo], axis=0), v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [2R, Dh]
        return pv[:R] + pv[R:]
    return jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # [R, Dh]


def _fold_halves(tiles, acc_ref, m_ref, l_ref):
    """`_fold_tile` over a tile cut in two, `tiles` = ((s, v), (s, v)):
    each half's row max, exp, sum and P . V depend on that half alone,
    and the two merge into the rows' state once. In one basic block the
    scheduler runs one half's q . K^T under the other's softmax and
    P . V, where one tile's chain is serial: product, row max, exp,
    product. The same online softmax — a masked column contributes
    EXACTLY 0, a half with no attended column weighs 0 — whose float32
    sums are taken in another order."""
    parts = []
    for s, v in tiles:
        m = jnp.max(s, axis=1, keepdims=True)  # [R, 1]
        p = jnp.where(s <= NEG_INF, 0.0, jnp.exp(s - m))
        parts.append((m, jnp.sum(p, axis=1, keepdims=True), _pv(p, v)))
    m_prev = m_ref[...]
    m_new = m_prev
    for m, _, _ in parts:
        m_new = jnp.maximum(m_new, m)

    def weight(m):
        return jnp.where(m <= NEG_INF, 0.0, jnp.exp(m - m_new))

    alpha = weight(m_prev)
    l, acc = l_ref[...] * alpha, acc_ref[...] * alpha
    for m, l_half, pv in parts:
        w = weight(m)
        l, acc = l + w * l_half, acc + w * pv
    l_ref[...] = l
    acc_ref[...] = acc
    m_ref[...] = m_new


def _masked_scores(q, k, scale, rep: int, at, pos, first):
    """The merged-pool call's score tile: q [R, Dh] (row r belongs to
    K/V head r // rep) against a group's K [C, Dh] as it lies in the
    ring, rows (token, head), the tile's first token at position `at`
    -> [R, C] float32, scaled after the product (the decode family),
    NEG_INF wherever column c = (token c // H, head c % H) is not row
    r's: another head's, or a position outside `first` <= . <= `pos`
    (unwritten depths, a short group's stale rows). Scoring every row
    against every head's rows is H times the model's FLOPs on an MXU
    the copies leave idle, in place of a head loop that slices each
    head's rows out of every block; the other heads' columns are exact
    zeros in P, so `P . V` sums, per row, its own head's rows only."""
    R, C = q.shape[0], k.shape[0]
    H = R // rep
    s = jax.lax.dot_general(
        q.astype(k.dtype), k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    col = jax.lax.broadcasted_iota(jnp.int32, (R, C), 1)
    if H > 1:
        # rep query rows share a K/V head
        row = jax.lax.broadcasted_iota(jnp.int32, (R, C), 0) // rep
        head = (col & (H - 1)) if H & (H - 1) == 0 else jax.lax.rem(col, H)
        masked = (head != row) | (col >= (pos - at + 1) * H)
    else:  # one latent row a token that every query reads
        masked = col >= pos - at + 1
    if first is not None:
        masked = masked | (col < (first - at) * H)
    return jnp.where(masked, NEG_INF, s)


def _zero_ring(*bufs):
    """A ring row that no copy has written may hold anything, and
    0 x NaN is NaN in P . V: the ring starts as zeros. What stays in a
    row after that is a pool block's, which a later short group masks
    to an exact 0 like any depth past `pos`."""
    for buf in bufs:
        buf[...] = jnp.zeros_like(buf)


# Groups of K/V the merged-pool decode call keeps in VMEM: the one
# being folded and the ones whose copies are under way
_RING = 3


def _rungs(G: int, rows: int, fold_bound: bool) -> tuple:
    """The blocks a slot's last group may fold, the last group folding
    the first that covers the blocks it names; blocks past them are
    masked to exact zeros. A rung is the fewest blocks, at least G // 8
    (G // 4 where the call is bound by its copies), whose rows (`rows`
    a block) fill whole 128-lane score tiles; with no such rung inside
    G, the group. A call bound by its fold (the latent call's 32-row
    block in groups of 64) climbs a ladder of rungs -> (r, 2r, ..., G):
    rungs of 8 (256 rows), so the padding it folds is under one rung.
    What a folded padding block costs grows with the group: that call
    alone on one v5e chip, folding a quarter
    group or all of it took 1,427 us at 32 blocks and 1,328 at 64, the
    ladder 1,413 and 1,288. A call whose copies hide its fold folds one
    rung or the whole group -> (r, G): granite's and Trinity's 128-row
    block 8 of 32, SambaY's 320-row block 2 of 8 — a rung is a fold
    body that every process start traces and lowers, and the ladder
    took Trinity's warm-up from 12.1 to 13.6-14.2 s and its calls
    nowhere (414.6 -> 412.6 us, 791.9 -> 794.7). One fold a group,
    whatever its rung — a loop of smaller folds would pay a group's
    fixed chain (~0.5 us) again each time; the fold-bound call cuts a
    fold in two overlapped halves instead (`_fold_halves`), which a
    rung of at least two 128-row tiles always allows."""
    r = max(1, G // (8 if fold_bound else 4))
    while r < G and r * rows % 128:
        r += 1
    if not fold_bound:
        return (r, G) if r < G else (G,)
    return tuple(range(r, G, r)) + (G,)


def _half_cut(blocks: int, rows: int):
    """Where a fold of `blocks` blocks of `rows` rows is cut in two for
    `_fold_halves`: the block nearest the middle before which the rows
    fill whole 128-row score tiles; None where no such block lies
    inside the fold (it is then one tile, `_fold_tile`)."""
    cuts = [b for b in range(1, blocks) if b * rows % 128 == 0]
    return min(cuts, key=lambda b: abs(2 * b - blocks)) if cuts else None


def _pa_ring_decode_kernel(tbl_ref, pos_ref, *refs, Bt: int, G: int,
                           rungs: tuple, span: int, scale: float, rep: int,
                           windowed: bool, v_lanes: int = 0,
                           overlap: bool = False):
    """One SLOT of the merged-pool decode call (ISSUE 36): the pools
    stay in HBM, the kernel reads the slot's block ids from its table
    row in scalar memory and copies the blocks its context names — and
    no other — into a VMEM ring of `_RING` groups of G blocks, K and
    V. A grid step folds its slot's groups one after another (ONE
    product `q [H * rep, Dh] . K^T` a group, row r of K/V head
    r // rep, `_masked_scores` keeping row r's own head's columns at
    its attended depths, and `_fold_tile`); around each
    fold it first STARTS the copies of the group `_RING` - 1 ahead and
    afterwards WAITS for the next one's, so a group's products run
    under the later groups' copies and the copies of one group under
    the next one's: the queue of copies never runs empty. What is
    ahead of a slot's last groups are the first groups of the slots
    after it, so the ring does not drain between slots.

    Scalar memory carries the walk across grid steps, `st`: the groups
    folded so far, the groups started so far (group c sits in ring
    place c mod `_RING`), and the cursor, the (slot, group) to start
    next, which skips parked slots; `cnt`: how many blocks were
    started into each ring place, which is how many to wait for.

    A slot's walk is over BLOCKS, from the block of `first[s]` (a
    window layer's first attended position; block 0 without `first`)
    to the block of `pos[s]`: ceil(blocks / G) groups, the last one
    short. A short group copies its own blocks only and folds the
    first of `rungs` (`_rungs`) that covers them, one static shape a
    rung behind one switch: the rows past its blocks hold an earlier
    group's blocks (or `_zero_ring`'s zeros), masked by position like
    any depth past `pos`. A parked slot (pos >= span) names no block:
    nothing is copied or folded for it and it writes zeros.

    All copies into one ring place signal that place's semaphore, each
    waited for with a descriptor of its own size.

    `v_lanes` > 0 (the latent pool, ISSUE 37): ONE pool, whose row is
    the key and whose first `v_lanes` lanes are the value — a block is
    copied once into one ring and read by both products.

    `overlap` (the call bound by its fold: the latent call): every
    fold of two 128-row score tiles or more — at the latent cell's
    geometry EVERY fold, a whole group of 64 blocks or a rung of the
    ladder, 8 blocks = 256 rows at the least — is cut in two halves
    (`_half_cut`) folded by `_fold_halves`: two
    chains in one basic block, so one half's q . K^T runs under the
    other's softmax and P . V. On one v5e chip, 128 slots over contexts
    of 1.5-7.2 k (least 809 us, copies alone 977): 1,288 us a call with
    one serial chain a fold, 1,193 with the halves; carrying the next
    GROUP's scores into this group's fold instead (a 256 KiB score tile
    in VMEM, a ring of 4) took 1,288-1,290. A call bound by its copies
    folds one tile a fold, as before: its fold is hidden already."""
    if windowed:
        first_ref, refs = refs[0], refs[1:]
    else:
        first_ref = None
    if v_lanes:
        (q_ref, k_hbm, o_ref,
         kbuf, sem, acc_ref, m_ref, l_ref, st_ref, cnt_ref) = refs
        rings = ((k_hbm, kbuf),)

        def values(place, rows):
            return kbuf[place, rows, pl.ds(0, v_lanes)]
    else:
        (q_ref, k_hbm, v_hbm, o_ref,
         kbuf, vbuf, sem, acc_ref, m_ref, l_ref, st_ref, cnt_ref) = refs
        rings = ((k_hbm, kbuf), (v_hbm, vbuf))

        def values(place, rows):
            return vbuf[place, rows]
    ring = kbuf.shape[0]
    BH = kbuf.shape[1] // G  # rows a block: (token, head)
    si = pl.program_id(0)
    nslots = pl.num_programs(0)
    FOLDED, STARTED, NEXT_SLOT, NEXT_GROUP = range(4)

    def named(s):
        """-> (the first block slot s attends, how many it attends)."""
        pos = pos_ref[s]
        b0 = 0 if first_ref is None else first_ref[s] // Bt
        return b0, jnp.where(pos < span, pos // Bt + 1 - b0, 0)

    def live_from(s):
        """-> the first slot >= s that names a block, or `nslots`."""
        return jax.lax.while_loop(
            lambda s: (s < nslots)
            & (pos_ref[jnp.minimum(s, nslots - 1)] >= span),
            lambda s: s + 1, s)

    def copies(n, place, wait, s=0, lo=0):
        """Start the copies of table entries lo .. lo + n of slot s
        into ring place `place`, or wait for n such copies there."""
        def one(i, carry):
            # a wait reads its descriptor's size and semaphore only
            blk = 0 if wait else jnp.maximum(tbl_ref[s, lo + i], 0)
            at = pl.ds(pl.multiple_of(i * BH, BH), BH)
            for pool, buf in rings:
                dma = pltpu.make_async_copy(pool.at[blk], buf.at[place, at],
                                            sem.at[place])
                dma.wait() if wait else dma.start()
            return carry

        jax.lax.fori_loop(0, n, one, 0)

    def start_next():
        """Start the group the cursor stands on and move the cursor."""
        s, j = st_ref[NEXT_SLOT], st_ref[NEXT_GROUP]

        @pl.when(s < nslots)
        def _():
            place = jax.lax.rem(st_ref[STARTED], ring)
            b0, n = named(s)
            n_here = jnp.minimum(n - j * G, G)
            copies(n_here, place, False, s, b0 + j * G)
            cnt_ref[place] = n_here
            st_ref[STARTED] = st_ref[STARTED] + 1
            more = (j + 1) * G < n
            st_ref[NEXT_SLOT] = jnp.where(more, s, live_from(s + 1))
            st_ref[NEXT_GROUP] = jnp.where(more, j + 1, 0)

    def wait_for(c):
        """Group c's copies, if it was started (it is the next to be
        folded: whatever is left to fold has been started by then)."""
        @pl.when(c < st_ref[STARTED])
        def _():
            place = jax.lax.rem(c, ring)
            copies(cnt_ref[place], place, True)

    @pl.when(si == 0)
    def _prime():
        _zero_ring(*(buf for _, buf in rings))
        st_ref[FOLDED] = 0
        st_ref[STARTED] = 0
        st_ref[NEXT_SLOT] = live_from(0)
        st_ref[NEXT_GROUP] = 0
        for _ in range(ring - 1):
            start_next()
        wait_for(0)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    pos = pos_ref[si]
    b0, n = named(si)
    groups = (n + G - 1) // G
    folded = st_ref[FOLDED]

    def group(j, carry):
        c = folded + j
        place = jax.lax.rem(c, ring)
        start_next()

        def tile(lo, hi):
            # blocks lo .. hi of the group; the group's first token
            # sits at position (b0 + j * G) * Bt (a tile from block 0
            # adds no 0: the serial fold's body stays as it was traced)
            rows = pl.ds(lo * BH, (hi - lo) * BH)
            s = _masked_scores(q_ref[...], kbuf[place, rows], scale, rep,
                               (b0 + j * G + lo) * Bt if lo
                               else (b0 + j * G) * Bt, pos,
                               None if first_ref is None else first_ref[si])
            return s, values(place, rows)

        def fold(blocks):
            # the group's first `blocks` blocks
            cut = _half_cut(blocks, BH) if overlap else None
            if cut is None:
                _fold_tile(*tile(0, blocks), acc_ref, m_ref, l_ref)
            else:
                _fold_halves((tile(0, cut), tile(cut, blocks)),
                             acc_ref, m_ref, l_ref)

        # the first rung that covers the blocks this group names (a
        # whole group names G or more), the whole group as branch 0:
        # Mosaic lowers a switch to a chain of ifs that tests 0 first,
        # and most groups are whole
        rung = sum((n - j * G > b).astype(jnp.int32) for b in rungs[:-1])
        jax.lax.switch((rung + 1) % len(rungs),
                       [functools.partial(fold, b) for b in rungs[-1:]
                        + rungs[:-1]])
        wait_for(c + 1)
        return carry

    jax.lax.fori_loop(0, groups, group, 0)
    st_ref[FOLDED] = folded + groups
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    o_ref[...] = out[:, None, :].astype(o_ref.dtype)


# what one TPU core gives one program: the scalar memory the v5e's
# compiler reports ("1.00M smem", less what a kernel's own scalars
# take beside the prefetch operands) and the vector memory a program
# may scope by default (16 MiB of the core's 128)
_SMEM_BYTES = 1 << 20
_SMEM_RESERVE = 16 << 10
_VMEM_BYTES = 16 << 20


def _group(Bt: int, maxb: int) -> int:
    """Table entries per grid step, by TOKENS: enough for the per-head
    score tile [R, G*Bt] to fill the 128-lane dim, capped at the whole
    table for tiny configs (the score tile then equals the array dim,
    which Mosaic also accepts). What `_pa_kernel` takes, and where
    `_bytes_group` starts doubling."""
    return max(1, min(-(-128 // Bt), maxb))


# K + V bytes a group of the merged-pool decode call carries at least.
# A group's fold has a part that does not shrink with the group (the
# chain product -> row max -> exp -> product, ~0.5 us on the v5e) beside
# 0.064 us a block of 64 KiB, whose copies take 0.082: past ~29 such
# blocks the copies hide the fold. Read on the v5e, the call alone, 64
# slots, microseconds a call (tools/time_decode_attention.py; PERF.md
# section 6, PR 36), by K + V bytes a group: Trinity's full call
# (contexts 1.5-7.2 k; least 712) 1,131 at 512 KiB, 910 at 1 MiB, 791
# at 2 MiB, 806 at 4 MiB; its 2,048-token window call (least 321) 578,
# 455, 414, 403; granite's (least 507) 665 at 1 MiB, 579 at 2 MiB;
# SambaY's shared pool (least 1,267) 1,445 at 640 KiB, 1,378 at
# 1.25 MiB, 1,379 at 2.5 MiB; its 512-token window call (least 206)
# 302, 255, 270: the copies bind it from 1.25 MiB on and a short
# window loses past that; the GPT pool's (32 slots, least 267) 294 at
# 1 MiB, 295 at 2 MiB, 297 at 4 MiB, its copies alone 292 at each.
# The latent call is bound by its fold and not
# by its copies (its one pool feeds both products): a group's fixed
# part (~0.47 us) is never hidden, and fewer, larger groups take it
# off — 128 slots over contexts of 1.5-7.2 k (least 809), groups of 8,
# 16, 32 and 64 blocks of 40 KiB with `_rungs`: 2,193, 1,684, 1,431
# and 1,296 (1,413 and 1,288 with the whole group as the switch's
# first branch), 1,427 at 32 with a quarter group or a whole one
# (one v5e chip). So it reaches twice the target (2.5 MiB:
# 64 of its blocks); 128 blocks pass the VMEM a program scopes. With
# each fold cut in two overlapped halves (`_fold_halves`) the call is
# 1,193 at 64 blocks: what a group adds over its copies (977 alone) is
# then mostly its q . K^T at 32 query rows (qkonly 1,079, pvonly 1,012)
_STEP_BYTES = 5 << 18


def _bytes_group(Bt: int, maxb: int, block_bytes: int,
                 fold_bound: bool = False) -> int:
    """Table entries per group of the merged-pool decode call, by
    BYTES: the fewest blocks, `_group`'s doubled, whose K + V
    (`block_bytes` a block: its rows x width x 2 x the dtype's size)
    reach `_STEP_BYTES` — twice that where the call is `fold_bound`
    (the latent call) — never more than the table holds: 32 blocks of
    granite's and Trinity's 64 KiB (2 MiB), 16 of the GPT pool's
    128 KiB (2 MiB), 8 of SambaY's 160 KiB (1.25 MiB), 64 of the latent
    pool's 40 KiB (2.5 MiB)."""
    G, target = _group(Bt, maxb), _STEP_BYTES * (2 if fold_bound else 1)
    while G * block_bytes < target and 2 * G <= maxb:
        G *= 2
    return G


def _row_tile(H: int, dh: int, W: int, q_itemsize: int,
              pool_itemsize: int) -> int:
    """Window rows per grid step: the largest power of two, at most
    256, whose VMEM fits beside one group's K/V. Counted per (head,
    lane) with Dh padded to whole 128-lane tiles: a group of W tokens
    holds K and V double-buffered in the pool dtype plus their f32
    upcasts; each window row holds the double-buffered q and out
    blocks, the f32 accumulator, and the (m, l) columns, which pad to
    a full lane tile each. At H=16, Dh=128, 16-bit: 4 MiB of K/V and
    40 KiB a row, so 256 rows — which the v5e's compiler accepts where
    it refuses 512. Never under one 8-row sublane tile; a geometry too
    wide even for that is the compiler's to refuse."""
    n = H * (-(-dh // 128) * 128)
    kv = W * n * (4 * pool_itemsize + 8)
    row = n * (4 * q_itemsize + 4) + 2 * H * 128 * 4
    rows = max(8, (_VMEM_BYTES - kv) // row)
    return min(256, 1 << (rows.bit_length() - 1))


def _smem_padded(rows: int, cols: int) -> int:
    # a 2-D 32-bit scalar-prefetch operand is laid out in (8, 128) tiles
    return (-(-rows // 8) * 8) * (-(-cols // 128) * 128) * 4


def check_paged_smem(slots: int, maxb: int, block_tokens: int,
                     heads: int, quant: bool, block_bytes=None):
    """Refuse a geometry whose prefetch operands cannot fit scalar
    memory — at construction, with the arithmetic, instead of at the
    first step's compile. One kernel call prefetches the block tables
    [S, MAXB], the row bases [S] and, on a quantized pool, the two
    flat scale rows [S, MAXB*H] — all padded to SMEM's (8, 128) word
    tiles (a 1-D operand counted as one such row of tiles, which is
    on the safe side), MAXB first padded to a whole number of
    `_pa_kernel`'s groups (the decode call on an unquantized pool reads
    the tables as they are, which is no more). `block_bytes` (K + V of
    one block) marks a merged 3-D pool, whose only kernel call is the
    decode call, which reads the tables beside `pos` and a window
    layer's `first`."""
    if block_bytes is not None:
        need = _smem_padded(slots, maxb) + 2 * _smem_padded(1, slots)
    else:
        G = _group(block_tokens, maxb)
        mb = -(-maxb // G) * G
        need = _smem_padded(slots, mb) + _smem_padded(1, slots)
        if quant:
            need += 2 * _smem_padded(slots, mb * heads)
    room = _SMEM_BYTES - _SMEM_RESERVE
    if need > room:
        raise ValueError(
            "fused paged attention keeps the block tables%s in scalar "
            "memory: %d slots x %d table entries%s need %d bytes, the "
            "core has %d (%d less %d for the kernel's own scalars) — "
            "lower max_slots, raise kv_block_tokens or lower max_len"
            % (" and the KV scales" if quant else "", slots, maxb,
               " x (1 + 2 x %d heads)" % heads if quant else "",
               need, room, _SMEM_BYTES, _SMEM_RESERVE))


def _merged_decode(q, k_pool, v_pool, tables, pos, first, *, scale,
                   interpret, name, v_lanes=0):
    """The R == 1 call on a merged 3-D pool `[NB, Bt * Hk, Dh]`:
    `_pa_ring_decode_kernel` over a grid of slots, its custom call
    named `name`. q [S, Hk, rep, Dh] -> out [S, Hk * rep, 1, Dh] (the
    shape the roofline metrics find the kernel by). The tables, `pos`
    and `first` go to scalar memory as they are; the pools are handed
    over in HBM, one operand each, and the kernel copies what the
    tables name, a group of `_bytes_group` blocks at a time. With
    `v_pool` None the values are the first `v_lanes` lanes of the one
    pool (the latent call): out [S, Hk * rep, 1, v_lanes]."""
    rows = k_pool.shape[1]  # of a block: (token, head)
    fold_bound = v_pool is None  # one pool feeds both products
    G = _bytes_group(rows // q.shape[1], tables.shape[1],
                     (1 if fold_bound else 2) * rows * k_pool.shape[2]
                     * k_pool.dtype.itemsize, fold_bound)
    return _ring_call(q, k_pool, v_pool, tables, pos, first, G=G,
                      rungs=_rungs(G, rows, fold_bound), ring=_RING,
                      scale=scale,
                      interpret=resolve_interpret(interpret), name=name,
                      v_lanes=v_lanes, overlap=fold_bound)


@functools.partial(jax.jit, static_argnames=(
    "G", "rungs", "ring", "scale", "interpret", "name", "v_lanes",
    "overlap"))
def _ring_call(q, k_pool, v_pool, tables, pos, first, *, G, rungs, ring,
               scale, interpret, name, v_lanes, overlap=False):
    """`_merged_decode`'s call, its group, rungs and ring given. Jitted,
    so that a program's layers of one geometry trace and lower ONE
    kernel, whose body holds a fold a rung: every process lowers its
    decode program anew, and that is `setup_s`."""
    S, Hk, rep, dh = q.shape
    R = Hk * rep
    rows = k_pool.shape[1]
    Bt, maxb = rows // Hk, tables.shape[1]
    pools = (k_pool,) if v_pool is None else (k_pool, v_pool)
    dv = v_lanes or dh
    prefetch = (jnp.asarray(tables, jnp.int32), jnp.asarray(pos, jnp.int32))
    if first is not None:
        prefetch += (jnp.asarray(first, jnp.int32),)

    def _slot_map(i, *prefetch):
        return (i, 0, 0, 0)

    kernel = functools.partial(
        _pa_ring_decode_kernel, Bt=Bt, G=G, rungs=rungs, span=maxb * Bt,
        scale=scale, rep=rep, windowed=first is not None, v_lanes=v_lanes,
        overlap=overlap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(S,),
        in_specs=[pl.BlockSpec((None, None, R, dh), _slot_map)]
        + [pl.BlockSpec(memory_space=pl.ANY) for _ in pools],
        out_specs=pl.BlockSpec((None, R, 1, dv), _slot_map),
        scratch_shapes=[pltpu.VMEM((ring, G * rows, dh), p.dtype)
                        for p in pools]
        + [pltpu.SemaphoreType.DMA((ring,)),
           pltpu.VMEM((R, dv), jnp.float32),
           pltpu.VMEM((R, 1), jnp.float32),
           pltpu.VMEM((R, 1), jnp.float32),
           pltpu.SMEM((4,), jnp.int32),
           pltpu.SMEM((ring,), jnp.int32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, R, 1, dv), q.dtype),
        # the ring and the walk's scalars carry over from a slot to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
        metadata={"kernel": name},
    )(*prefetch, q.reshape(S, 1, R, dh), *pools)


def _paged_attention(q, k_pool, v_pool, tables, base, *, name, scale,
                     scale_in_q, interpret, k_scale=None, v_scale=None):
    """Shared pallas_call builder: q [S, R, H, Dh] windows based at
    `base` [S] over per-slot tables [S, MAXB] into the pools
    [NB, Bt, H, Dh] -> out [S, R, H, Dh].

    `name` is the calling kernel's own (`paged_decode_attention`, ...):
    it names the custom call and rides in its `kernel_metadata`, so a
    device trace tells the three apart by name and not by the shape of
    what they return.

    `k_scale`/`v_scale` [NB, H] f32 (both or neither) mark a quantized
    pool (ISSUE 14). The kernel reads one scalar per (table entry,
    head), so what rides in SMEM beside the tables is the scales of
    the blocks the tables NAME — gathered here through the table into
    a flat [S, MAXB*H] row per slot (an activation-sized XLA gather,
    S x MAXB x H words, not a pool-sized view). Handing the kernel the
    pool's own [NB, H] array instead costs NB x 128 words once SMEM
    pads the minor dim, which a chip-sized pool (NB in the thousands)
    cannot fit; `check_paged_smem` is the bound that remains.

    The window-row dim R is the kernel's sublane dim: Mosaic wants it
    in whole 8-row tiles (the flash kernel refuses blocks under 8 for
    the same reason), so 1 < R < multiple-of-8 windows pad with zero
    rows up to the tile and slice the result. Windows taller than
    `_row_tile` rows (prefill chunks) run as several row tiles on a
    grid axis of their own, padded to a whole number of tiles — a
    whole 512+-row chunk in one block is more VMEM than a program may
    scope. Pad rows compute masked garbage nothing reads; every real
    row's online-softmax state is row-independent, so real rows are
    BIT-identical to the unpadded, untiled math. R == 1 (the decode
    shape on a quantized pool) stays unpadded."""
    S, R, H, dh = q.shape
    NB, Bt = k_pool.shape[0], k_pool.shape[1]
    maxb = tables.shape[1]
    tables = jnp.asarray(tables, jnp.int32)
    base = jnp.asarray(base, jnp.int32)
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    G = _group(Bt, maxb)
    Rt = _row_tile(H, dh, G * Bt, q.dtype.itemsize,
                   k_pool.dtype.itemsize)
    if R == 1:
        Rt = Rp = 1
    elif R <= Rt:
        Rt = Rp = -(-R // 8) * 8
    else:
        Rp = -(-R // Rt) * Rt
    if Rp != R:
        q = jnp.concatenate(
            [q, jnp.zeros((S, Rp - R, H, dh), q.dtype)], axis=1)
    # the table pads to a whole number of groups with -1 (unallocated)
    # entries — clamped and position-masked like any other -1, i.e.
    # exact no-ops
    pad = -maxb % G
    if pad:
        tables = jnp.concatenate(
            [tables, jnp.full((S, pad), -1, jnp.int32)], axis=1)

    # index maps take the scalar-prefetch refs after the grid indices:
    # (tbl, pos) unquantized, (tbl, pos, ksc, vsc) quantized — only
    # tbl is consulted, so the maps accept either arity
    def _q_map(si, t, b, tbl, *pref):
        return (si, t, 0, 0)

    def _o_map(si, t, b, tbl, *pref):
        return (si, 0, t, 0)

    def _kv_map(g):
        def _map(si, t, b, tbl, *pref):
            # THE gather: the pipeline DMAs pool block tbl[s, b*G+g]
            # for this grid step. -1 (unallocated or group padding)
            # clamps to block 0 — its rows are excluded by the
            # position mask, so they contribute exactly 0
            return (jnp.maximum(tbl[si, b * G + g], 0), 0, 0, 0)
        return _map

    kernel = functools.partial(
        _pa_kernel, Bt=Bt, R=Rt, G=G, scale=scale,
        scale_in_q=scale_in_q, quant=quant,
    )
    prefetch = (tables, base)
    if quant:
        named = jnp.clip(tables, 0, NB - 1)  # -1 reads block 0's scale
        prefetch = prefetch + tuple(
            jnp.asarray(sc, jnp.float32)[named].reshape(S, -1)
            for sc in (k_scale, v_scale))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(S, Rp // Rt, (maxb + pad) // G),
        in_specs=[pl.BlockSpec((1, Rt, H, dh), _q_map)]
        + [pl.BlockSpec((1, Bt, H, dh), _kv_map(g)) for g in range(G)]
        + [pl.BlockSpec((1, Bt, H, dh), _kv_map(g)) for g in range(G)],
        out_specs=pl.BlockSpec((1, H, Rt, dh), _o_map),
        scratch_shapes=[pltpu.VMEM((Rt, dh), jnp.float32)
                        for _ in range(H)]
        + [pltpu.VMEM((Rt, 1), jnp.float32) for _ in range(2 * H)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, Rp, dh), q.dtype),
        interpret=resolve_interpret(interpret),
        name=name,
        metadata={"kernel": name},
    )(*prefetch, q, *([k_pool] * G), *([v_pool] * G))
    # the kernel emits head-major [S, H, Rp, Dh] (leading-dim writes
    # only); this transpose is ordinary XLA on the activation-sized
    # output, not a pool-sized materialisation
    out = out.transpose(0, 2, 1, 3)
    return out[:, :R] if Rp != R else out


def paged_decode_attention(q, k_pool, v_pool, tables, pos,
                           interpret=None, k_scale=None, v_scale=None,
                           first=None, scale=None):
    """Batched single-token paged decode attention: one query per slot.

    q [S, H, Dh] at per-slot positions `pos` [S] over block tables
    [S, MAXB] into pools [NB, Bt, H, Dh] -> out [S, H, Dh]. Mirrors
    `_cached_attention` over `_paged_view` (divide-after-matmul
    scaling, depths > pos excluded) without ever materialising the
    view. A parked row (pos >= MAXB*Bt) returns zeros and costs no
    copy (on a quantized pool, `_pa_kernel`'s garbage, like the gather
    path's) — nothing reads it either way.
    `k_scale`/`v_scale` [NB, H] dequantize an int8/fp8 pool inside the
    kernel (ISSUE 14).

    Grouped queries over a merged pool (ISSUE 27), told by the shapes:
    q [S, Hk, rep, D] over pools [NB, Bt * Hk, D] (a block's rows are
    (token, head), the order a `[Bt, Hk, D]` block has in memory, kept
    3-D so that the device tiles Bt * Hk rows and not Hk) -> out
    [S, Hk, rep, D]: the `rep` queries of a K/V head attend its rows
    (the custom call is then named `hybrid_decode_attention`).
    The hybrid family's differential attention is this with D = two
    projected heads side by side: a query sits in the half of D that
    its key half occupies and is zero in the other, and the value read
    is the pair, D wide. `first` [S] is the first position a slot
    attends (a window layer's pos - window + 1; depths before it are
    masked and the blocks before its block neither copied nor walked);
    `scale` replaces 1 / sqrt(D) where D is not the head's width.
    An unquantized 4-D pool is that call with `rep` 1: the
    pools go in as their merged view `[NB, Bt * H, Dh]`, which is the
    memory they already are (a bitcast, no copy), q as
    `[S, H, 1, Dh]`, and the call keeps its name.
    This call copies its own K/V (`_pa_ring_decode_kernel`, ISSUE 36),
    a group of blocks at a time, the group sized by the bytes it moves
    and not by 128 tokens (`_bytes_group`, from the pool's block shape
    and dtype: 32 blocks for granite's and Trinity's pools, 16 for the
    GPT block's, 8 for SambaY's). The number of columns an
    online-softmax step folds is all that differs between two group
    sizes, so logits move within float tolerance."""
    dh = q.shape[-1]
    scale = 1.0 / math.sqrt(dh) if scale is None else scale
    if k_pool.ndim == 4 and k_scale is None and v_scale is None:
        NB, Bt, H = k_pool.shape[:3]
        out = _merged_decode(
            q.reshape(-1, H, 1, dh), k_pool.reshape(NB, Bt * H, dh),
            v_pool.reshape(NB, Bt * H, dh), tables, pos, first,
            scale=scale, interpret=interpret, name="paged_decode_attention")
        return out.reshape(q.shape)
    if k_pool.ndim == 3:
        out = _merged_decode(q, k_pool, v_pool, tables, pos, first,
                             scale=scale, interpret=interpret,
                             name="hybrid_decode_attention")
        return out.reshape(q.shape)
    out = _paged_attention(
        q[:, None], k_pool, v_pool, tables, pos,
        name="paged_decode_attention", scale=scale, scale_in_q=False,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale,
    )
    return out[:, 0]


def mla_decode_attention(q, pool, tables, pos, v_lanes, scale,
                         interpret=None):
    """The absorbed latent decode call (ISSUE 37): one query a slot,
    q [S, H, W] — every head's query folded into the latent's space —
    over a latent pool [NB, Bt, W] whose row is a token's key, c and
    the rotated k_r side by side, and whose first `v_lanes` lanes (c)
    are its value; tables [S, MAXB], positions `pos` [S] -> o_lat
    [S, H, v_lanes], float32 softmax, bf16 operands where the pool is.
    Scores are q . row * `scale` at depths <= pos; each live block is
    copied into VMEM ONCE and serves both products. The ring, the
    walk over the table in scalar memory and the fold are the
    merged-pool call's (`_pa_ring_decode_kernel` with `v_lanes`), with
    one K/V head that all H query rows share; its fold, not its copies,
    binds it, so its groups are twice the bytes (`_bytes_group`) and
    every fold is cut in two halves whose chains overlap
    (`_fold_halves`)."""
    S, H, W = q.shape
    out = _merged_decode(q.reshape(S, 1, H, W), pool, None, tables, pos,
                         None, scale=scale, interpret=interpret,
                         name="mla_decode_attention", v_lanes=v_lanes)
    return out.reshape(S, H, v_lanes)


def _kv_write_kernel(blk_ref, off_ref, *refs, Hk: int):
    """One slot's decode write: its token's Hk rows replace rows
    [off * Hk, (off + 1) * Hk) of the block the slot is filling, in
    every pool at once (K and V, or the one latent pool). The new rows
    come tiled over the whole block, so the write is a select on a row
    index, with no unaligned store."""
    n = len(refs) // 3
    lo = off_ref[pl.program_id(0)] * Hk
    row = jax.lax.broadcasted_iota(jnp.int32, refs[n].shape, 0)
    mine = (row >= lo) & (row < lo + Hk)
    for new, old, out in zip(refs[:n], refs[n:2 * n], refs[2 * n:]):
        out[...] = jnp.where(mine, new[...], old[...])


def paged_kv_write(k_pool, v_pool, k_new, v_new, tables, pos,
                   interpret=None):
    """The decode step's K/V write into merged 3-D pools (ISSUE 27):
    slot s's new rows `k_new[s]`, `v_new[s]` [Hk, D] land at position
    pos[s] through tables [S, MAXB] -> (k_pool, v_pool), updated in
    place; with `v_pool` and `v_new` None, the one pool -> (k_pool,).
    XLA lowers the same scatter to a loop of one small
    dynamic-update-slice a slot (3 us each, 18 scatters a step at 64
    slots: 3.5 ms of a 37 ms step on the v5e; PERF.md section 6, PR
    27); here a grid step copies the slot's block in, selects the new
    rows into it, and copies it out. A parked slot, or one whose entry
    is unallocated, writes the pool's LAST block, which the pools keep
    beyond what the allocator hands out so that nothing lives there."""
    NB, rows, D = k_pool.shape
    S, Hk = k_new.shape[0], k_new.shape[1]
    pools = [p for p in (k_pool, v_pool) if p is not None]
    news = [x for x in (k_new, v_new) if x is not None]
    Bt = rows // Hk
    maxb = tables.shape[1]
    bi = pos // Bt
    phys = jnp.take_along_axis(tables, jnp.clip(bi, 0, maxb - 1)[:, None],
                               axis=1)[:, 0]
    blk = jnp.where((bi < maxb) & (phys >= 0), phys, NB - 1).astype(jnp.int32)
    off = (pos % Bt).astype(jnp.int32)

    def tiled(x):  # [S, Hk, D] -> the rows repeated down a whole block
        return jnp.tile(x.astype(k_pool.dtype), (1, Bt, 1))

    def slot(i, blk, off):
        return (i, 0, 0)

    def block(i, blk, off):
        return (blk[i], 0, 0)

    n = len(pools)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[pl.BlockSpec((None, rows, D), slot)] * n
        + [pl.BlockSpec((None, rows, D), block)] * n,
        out_specs=[pl.BlockSpec((None, rows, D), block)] * n,
    )
    return pl.pallas_call(
        functools.partial(_kv_write_kernel, Hk=Hk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # operands 0 and 1 are the prefetched block ids and offsets
        input_output_aliases={2 + n + i: i for i in range(n)},
        interpret=resolve_interpret(interpret),
        name="paged_kv_write",
        metadata={"kernel": "paged_kv_write"},
    )(blk, off, *map(tiled, news), *pools)


def paged_verify_attention(q, k_pool, v_pool, tables, pos,
                           interpret=None, k_scale=None, v_scale=None):
    """K-row paged verify windows (the spec-decode path): q [S, K, H,
    Dh], row (s, i) at global position pos[s] + i, attending the slot's
    cache up to and including itself — the intra-window causal prefix
    falls out of the position mask, exactly like `paged_verify_step`'s
    gather form. Chunk-family numerics (scale-into-q); scales
    dequantize a quantized pool in-kernel (ISSUE 14)."""
    dh = q.shape[-1]
    return _paged_attention(
        q, k_pool, v_pool, tables, pos,
        name="paged_verify_attention",
        scale=1.0 / math.sqrt(dh), scale_in_q=True,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale,
    )


def paged_prefill_attention(q, k_pool, v_pool, table_row, start,
                            interpret=None, k_scale=None, v_scale=None):
    """Chunked paged prefill attention for ONE slot: a [C]-token chunk
    q [C, H, Dh] whose first row sits at global position `start`,
    attending cache[0:start] plus the intra-chunk causal prefix through
    `table_row` [MAXB]. Chunk-family numerics (scale-into-q), padded
    rows past true_len compute garbage nothing reads — identical
    semantics to `paged_prefill_chunk`'s gather form. A chunk taller
    than `_row_tile` rows runs as several row tiles of one call, so
    every bucket up to max_len fits VMEM. Scales dequantize a
    quantized pool in-kernel (ISSUE 14)."""
    C, H, dh = q.shape
    out = _paged_attention(
        q[None], k_pool, v_pool, jnp.asarray(table_row)[None],
        jnp.asarray(start, jnp.int32).reshape(1),
        name="paged_prefill_attention",
        scale=1.0 / math.sqrt(dh), scale_in_q=True,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale,
    )
    return out[0]
