"""Sequence/context-parallel attention: ring attention + Ulysses.

These are NEW capabilities beyond the 2018 reference (SURVEY.md §2.2: the
reference's long-sequence story was LoD ragged tensors + chunked RNNs;
attention-era sequence parallelism did not exist). They are first-class
here because they shape the core design for long-context models on TPU:

* ring_attention — blockwise-softmax attention where each 'seq' shard
  holds a [T/n] slice of Q locally and K/V blocks rotate around the mesh
  axis via `lax.ppermute` (one ICI hop per step, n steps). Memory per chip
  is O(T/n), compute overlaps the collective, and the online-softmax
  accumulation makes the result EXACTLY equal to full attention.
* ulysses_attention — all-to-all alternative: heads are exchanged for
  sequence (`lax.all_to_all`), each shard computes full-sequence attention
  for H/n heads, then the transpose all-to-all restores layout. Cheaper
  when H >= n and T is moderate; ring wins at very long T.

Both run inside `shard_map` over the mesh's 'seq' axis and are fully
differentiable (ppermute/all_to_all have transpose rules, the ring loop is
a lax.scan).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


__all__ = [
    "ring_attention",
    "ulysses_attention",
    "sequence_parallel_attention",
    "reference_attention",
]

_NEG_INF = -1e30


def reference_attention(q, k, v, causal: bool = False, scale=None):
    """Plain full attention [B, T, H, D] — the correctness oracle and the
    single-device fallback."""
    B, T, H, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    s = jnp.einsum("bthd,bshd->bhts", q * scale, k)
    if causal:
        mask = jnp.tril(jnp.ones((T, k.shape[1]), bool), k.shape[1] - T)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v)


def _varying(x, axis_name):
    """Mark a scan-carry constant as device-varying over the ring axis
    (shard_map's vma type system; constants start out unvarying)."""
    return lax.pcast(x, (axis_name,), to="varying")


def _online_softmax_update(o, l, m, s, vs):
    """One online-softmax accumulation over a pre-masked f32 score tile
    `s`: rescale the running (o, l) by the max shift and fold in this
    tile's contribution. The _NEG_INF guards keep fully-masked rows at
    exact zero (exp never sees inf - inf). Shared by both ring
    layouts so the numerics can never diverge."""
    m_new = jnp.maximum(m, s.max(axis=-1))
    safe = jnp.where(m_new <= _NEG_INF, 0.0, m_new)
    p = jnp.exp(s - safe[..., None])
    p = jnp.where(s <= _NEG_INF, 0.0, p)
    corr = jnp.where(m <= _NEG_INF, 0.0, jnp.exp(m - safe))
    l_new = l * corr + p.sum(axis=-1)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhts,bshd->bthd", p.astype(vs.dtype), vs,
        preferred_element_type=jnp.float32,
    )
    return o_new, l_new, m_new


def ring_attention(q, k, v, axis_name: str, causal: bool = False, scale=None):
    """Blockwise ring attention; call inside shard_map with q/k/v sharded
    [B, T/n, H, D] on the sequence axis."""
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    B, T, H, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    # keep the MXU matmuls in the input dtype (bf16 stays bf16) with f32
    # accumulation via preferred_element_type; softmax stats are f32 and
    # the scale multiplies the f32 scores post-matmul (folding it into
    # bf16 q would round it — same rule as the flash kernel)
    qf = q

    q_pos = me * T + jnp.arange(T)  # global row ids of the local queries
    perm = [(i, (i + 1) % n) for i in range(n)]

    o0 = _varying(jnp.zeros((B, T, H, D), jnp.float32), axis_name)
    l0 = _varying(jnp.zeros((B, H, T), jnp.float32), axis_name)
    m0 = _varying(jnp.full((B, H, T), _NEG_INF, jnp.float32), axis_name)

    def step(carry, i):
        o, l, m, kb, vb = carry
        src = (me - i) % n  # which shard's K/V block we hold this step

        def accumulate(o, l, m, kb, vb):
            k_pos = src * T + jnp.arange(T)
            s = jnp.einsum("bthd,bshd->bhts", qf, kb,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                mask = q_pos[:, None] >= k_pos[None, :]
                s = jnp.where(mask[None, None], s, _NEG_INF)
            return _online_softmax_update(o, l, m, s, vb)

        if causal:
            # a source chunk strictly to the right of this shard's rows
            # is fully masked: skip both matmuls. NOTE: the ring is
            # lock-step (every device reaches the ppermute each step),
            # so this frees compute/energy on the skipping devices but
            # does NOT shorten the critical path — the last shard
            # accumulates on every step. The latency fix is striped
            # (zigzag) row assignment so all shards do ~half a block
            # per step; future work.
            o, l, m = lax.cond(
                src > me,
                lambda o, l, m, kb, vb: (o, l, m),
                accumulate,
                o, l, m, kb, vb,
            )
        else:
            o, l, m = accumulate(o, l, m, kb, vb)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (o, l, m, kb, vb), None

    (o, l, _, _, _), _ = lax.scan(step, (o0, l0, m0, k, v), jnp.arange(n))
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def zigzag_ring_attention(q, k, v, axis_name: str, causal: bool = True,
                          scale=None):
    """Causal ring attention with ZIGZAG (striped) row assignment —
    the load-balance fix ring_attention's causal path documents as
    future work: with contiguous rows, the last shard's rows see every
    source block, so the lock-step ring's critical path never benefits
    from the causal skip. Striped, shard i holds stripe i (rows
    [iC, (i+1)C), C = T_local/2) and its mirror stripe 2n-1-i; each
    ring step then costs every shard ~2 stripe-matmuls instead of the
    tail shard's 4 (per-step work is the max over shards — lock-step).

    Because stripes are aligned, visibility per (q-stripe, k-stripe)
    pair is decided at stripe granularity: mirror-vs-front is always
    visible, front-vs-mirror never, equal indices are the tril
    diagonal — no global position arrays needed. Call inside shard_map
    with the STRIPED layout (sequence_parallel_attention permutes);
    causal only (the balance problem does not exist otherwise)."""
    if not causal:
        raise ValueError("zigzag ring attention is causal-only; use "
                         "ring_attention for the non-causal case")
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    B, T, H, D = q.shape
    C = T // 2
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    perm = [(i, (i + 1) % n) for i in range(n)]
    tril = jnp.tril(jnp.ones((C, C), bool))

    def accum(qs, ks, vs, masked):
        def f(o, l, m):
            s = jnp.einsum("bthd,bshd->bhts", qs, ks,
                           preferred_element_type=jnp.float32) * scale
            if masked:
                s = jnp.where(tril[None, None], s, _NEG_INF)
            return _online_softmax_update(o, l, m, s, vs)

        return f

    def attend(carry_half, qs, ks, vs, mode):
        """Online-softmax update of one q stripe against one k stripe.
        mode: 0 skip (fully masked), 1 diagonal (tril), 2 fully
        visible."""
        o, l, m = carry_half
        return lax.switch(
            mode,
            [lambda o, l, m: (o, l, m), accum(qs, ks, vs, True),
             accum(qs, ks, vs, False)],
            o, l, m,
        )

    def half_init():
        return (
            _varying(jnp.zeros((B, C, H, D), jnp.float32), axis_name),
            _varying(jnp.zeros((B, H, C), jnp.float32), axis_name),
            _varying(jnp.full((B, H, C), _NEG_INF, jnp.float32),
                     axis_name),
        )

    def step(carry, i):
        f_half, b_half, kb, vb = carry
        src = (me - i) % n
        kf, km = kb[:, :C], kb[:, C:]
        vf, vm = vb[:, :C], vb[:, C:]
        # front q stripe (index me) vs source front stripe (index src):
        # strictly later stripe sees all of an earlier one
        mode_ff = jnp.where(me > src, 2, jnp.where(me == src, 1, 0))
        f_half = attend(f_half, q[:, :C], kf, vf, mode_ff)
        # mirror q stripe (index 2n-1-me) vs source front: ALWAYS later
        # — unconditional accumulate, no branch to obscure the matmul
        b_half = accum(q[:, C:], kf, vf, False)(*b_half)
        # mirror q vs source mirror (index 2n-1-src): inverted order
        mode_bm = jnp.where(me < src, 2, jnp.where(me == src, 1, 0))
        b_half = attend(b_half, q[:, C:], km, vm, mode_bm)
        # front q vs source mirror: a front stripe never sees a mirror
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (f_half, b_half, kb, vb), None

    (f_half, b_half, _, _), _ = lax.scan(
        step, (half_init(), half_init(), k, v), jnp.arange(n)
    )

    def finish(half):
        o, l, _ = half
        return o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]

    return jnp.concatenate(
        [finish(f_half), finish(b_half)], axis=1
    ).astype(q.dtype)


def _zigzag_entry(q, k, v, mesh, axis, causal, scale):
    """Global-view zigzag dispatch: permute rows to the striped layout,
    run the balanced causal ring under shard_map, un-permute.

    Convenience form — it pays the stripe gather/scatter per call. A
    transformer stack should instead keep activations in the striped
    layout end-to-end (position-free layers are layout-oblivious):
    apply zigzag_permutation once at the embedding, call
    zigzag_ring_attention directly inside the model's shard_map region,
    and invert once at the head."""
    if not causal:
        raise ValueError("impl='zigzag' is causal-only")
    n = mesh.shape[axis]
    T = q.shape[1]
    if T % (2 * n) != 0:
        raise ValueError(
            "zigzag needs the sequence length (%d) divisible by 2*axis "
            "size (%d)" % (T, 2 * n)
        )
    perm, inv = zigzag_permutation(T, n)
    qz, kz, vz = (jnp.take(x, perm, axis=1) for x in (q, k, v))
    spec = P(None, axis, None, None)
    mapped = shard_map(
        functools.partial(zigzag_ring_attention, axis_name=axis,
                          causal=True, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    return jnp.take(mapped(qz, kz, vz), inv, axis=1)


def zigzag_permutation(T_global: int, n: int):
    """Row permutation taking the natural sequence order to the zigzag
    shard layout: shard i's contiguous slot holds stripe i then stripe
    2n-1-i. Returns (perm, inverse) index arrays of length T_global."""
    import numpy as _np

    C = T_global // (2 * n)
    order = []
    for i in range(n):
        order.append(_np.arange(i * C, (i + 1) * C))
        j = 2 * n - 1 - i
        order.append(_np.arange(j * C, (j + 1) * C))
    perm = _np.concatenate(order)
    inv = _np.argsort(perm)
    return perm, inv


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False,
                      scale=None, impl: str = "reference",
                      interpret: Optional[bool] = None):
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism; call
    inside shard_map with [B, T/n, H, D] shards. Requires H % n == 0.

    After the head<->sequence exchange each shard holds its heads' FULL
    sequence, so the local attention is exactly the single-chip problem
    — impl="flash" runs the pallas flash kernel per shard (O(T) memory,
    pallas backward; the enclosing shard_map needs check_vma=False for
    the interpret-mode CI path — sequence_parallel_attention arranges
    that); the default impl="reference" materialises the [T, T] scores
    (oracle path, and the pre-r5 behavior for direct callers).
    `interpret` follows flash_attention.resolve_interpret."""
    # exchange: split heads across the axis, gather the full sequence
    qg = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kg = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vg = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    if impl == "flash":
        from .flash_attention import flash_attention, resolve_interpret

        og = flash_attention(
            qg, kg, vg, causal=causal, scale=scale,
            interpret=resolve_interpret(interpret),
        )
    else:
        og = reference_attention(qg, kg, vg, causal=causal, scale=scale)
    return lax.all_to_all(og, axis_name, split_axis=1, concat_axis=2, tiled=True)


def sequence_parallel_attention(
    q, k, v,
    mesh: Optional[Mesh] = None,
    axis: str = "seq",
    impl: str = "ring",
    causal: bool = False,
    scale=None,
    interpret: Optional[bool] = None,
):
    """Global-view entry point: q/k/v are [B, T, H, D] global arrays; the
    sequence dim is sharded over `axis` of `mesh` and attention runs
    sequence-parallel. Without a mesh (or on a size-1 axis):
    impl="flash" runs the pallas flash kernel on the chip, anything else
    the plain full-matrix attention."""
    if mesh is None:
        from .mesh import get_default_mesh

        mesh = get_default_mesh()
    if impl == "zigzag" and not causal:
        # validate BEFORE the no-mesh fallback so a single-device dev
        # run fails the same way the multi-chip run will
        raise ValueError("impl='zigzag' is causal-only")
    if mesh is None or axis not in mesh.axis_names or mesh.shape[axis] == 1:
        if impl == "flash":
            from .flash_attention import flash_attention, resolve_interpret

            return flash_attention(
                q, k, v, causal=causal, scale=scale,
                interpret=resolve_interpret(interpret),
            )
        return reference_attention(q, k, v, causal=causal, scale=scale)
    if q.shape[1] % mesh.shape[axis] != 0:
        raise ValueError(
            "sequence length %d not divisible by mesh axis %r size %d"
            % (q.shape[1], axis, mesh.shape[axis])
        )
    flash_inner = False
    if impl == "flash":
        # multi-shard flash: ulysses' head<->seq all-to-all puts a full
        # sequence per shard, where the pallas kernel (fwd + backward)
        # applies unchanged; heads not divisible by the axis fall back
        # to ring (jnp online-softmax across ppermute steps)
        flash_inner = q.shape[2] % mesh.shape[axis] == 0
        impl = "ulysses" if flash_inner else "ring"
    if impl == "zigzag":
        return _zigzag_entry(q, k, v, mesh, axis, causal, scale)
    fn = {"ring": ring_attention, "ulysses": ulysses_attention}[impl]
    if impl == "ulysses" and q.shape[2] % mesh.shape[axis] != 0:
        raise ValueError("ulysses needs heads divisible by the seq axis size")
    spec = P(None, axis, None, None)
    body = functools.partial(fn, axis_name=axis, causal=causal,
                             scale=scale)
    if flash_inner:
        body = functools.partial(body, impl="flash", interpret=interpret)
    kwargs = dict(
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    if flash_inner:
        # interpret-mode pallas under the vma type system rejects the
        # kernel's internal dynamic_slice on mixed-vma operands (JAX's
        # own error text recommends check_vma=False as the workaround);
        # only the pallas-bearing path drops the check — ring and
        # ulysses-reference keep the replication typing
        mapped = shard_map(body, check_vma=False, **kwargs)
    else:
        mapped = shard_map(body, **kwargs)
    return mapped(q, k, v)
