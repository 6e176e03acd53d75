"""Plain reference of the sparse-expert model the cell
`trinitymini_reason_closed` serves (Trinity-Mini, `model_type` afmoe).

Straightforward jax.numpy in float32 with every matrix product at the
highest precision: full-matrix attention one query head at a time, the
routed experts one expert at a time over ALL rows with the rows that
did not choose it weighted 0 (no sorting, no grouping, no capacity),
routing by this file's own top-k over its own scores, no cache, no
kernels, no batching. It imports nothing of the program; the weights
are made here, from the seed, and upcast a layer — inside an expert
layer an expert — at a time, so that the 4.24 B parameters of the cut
never stand in float32 at once.

`shape` is the configuration's "shape" group: vocab, dim, heads,
kv_heads, head_dim, layers, layer_types, num_dense_layers,
dense_width, expert_width, n_experts, top_k, route_scale, route_norm,
window, rope_theta and, where a chip holds a share, experts_held
[lo, hi] and shared_expert_held (eps is 1e-5; no bias on any matrix).

  x0 = E[token] * sqrt(dim)
  every layer: a = Attn(RMS(x; g1)); x += RMS(a; g2)
               m = FFN(RMS(x; g3));  x += RMS(m; g4)
  logits = W_head RMS(x; g_f)          (its own matrix, not E)

  Attn(u)  [q | k | v | gate] = u W_qkvg (heads dh | kv dh | kv dh |
           heads dh); q, k <- RMS over dh with gains g_q, g_k, per
           head; in a sliding_attention layer q and k are rotated
           (RoPE, theta, all dh dims, the half-split rotate_half
           convention) and key j is visible to query i iff
           i - window < j <= i; in a full_attention layer NO position
           and j <= i; query head h reads K/V head h // (heads / kv);
           o = softmax(q k^T / sqrt(dh)) v * sigmoid(gate); W_o o
  FFN(u)   layer < num_dense_layers: W_down(silu(g) * up), [g | up] =
           u W_gu. Else s = sigmoid(u W_r); S = the top_k largest of
           s + b (b decides the choice only); w_e = route_scale * s_e /
           (sum_{e in S} s_e + 1e-20) (the division only with
           route_norm); out = Shared(u) + sum_{e in S, e held} w_e
           Expert_e(u), each a SwiGLU of width expert_width.

`quant="int8"` is the control: the same forward with both operands of
every matrix product — the router's among them — rounded to 8-bit
integers (absmax scale per row of the contraction), the nearest
precision below the bf16 that the configuration states.
`no_bias=True` and `no_scale=True` are planted faults for the tests: a
router that ignores its bias, or its scale.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-5


def dims(shape):
    lo, hi = shape.get("experts_held") or (0, shape["n_experts"])
    return {"d": shape["dim"], "hq": shape["heads"], "hkv": shape["kv_heads"],
            "dh": shape["head_dim"], "md": shape["dense_width"],
            "me": shape["expert_width"], "E": shape["n_experts"],
            "lo": int(lo), "hi": int(hi), "k": shape["top_k"],
            "W": shape["window"],
            "shared": bool(shape.get("shared_expert_held", True))}


def weight_shapes(shape, max_len=None):
    """The parameter tree the served entry takes, as shapes."""
    z = dims(shape)
    d, dh, me = z["d"], z["dh"], z["me"]
    nq, nk, Eh = z["hq"] * dh, z["hkv"] * dh, z["hi"] - z["lo"]
    attn = {"wqkvg": (d, 2 * nq + 2 * nk), "q_norm": (dh,), "k_norm": (dh,),
            "wo": (nq, d)}
    dense = {"w_gu": (d, 2 * z["md"]), "w_down": (z["md"], d)}
    moe = {"router": (d, z["E"]), "router_bias": (z["E"],),
           "experts": {"w_gu": (Eh, d, 2 * me), "w_down": (Eh, me, d)},
           "shared": {"w_gu": (d, 2 * me), "w_down": (me, d)}}
    return {"embed": (shape["vocab"], d), "norm_f": (d,),
            "head": (shape["vocab"], d),
            "blocks": [{"norm1": (d,), "norm2": (d,), "norm3": (d,),
                        "norm4": (d,), "attn": dict(attn),
                        "ffn": dict(dense if l < shape["num_dense_layers"]
                                    else moe)}
                       for l in range(len(shape["layer_types"]))]}


def param_count(shape):
    return sum(math.prod(s) for s in jax.tree_util.tree_leaves(
        weight_shapes(shape), is_leaf=lambda x: isinstance(x, tuple)))


def seed_key(seed):
    """A raw threefry key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                       jnp.uint32)


@functools.partial(jax.jit, static_argnames=("shp", "kind", "dtype"))
def _leaf(key, shp, kind, dtype):
    """One leaf in the type it is served in. Matrices N(0, 1 / the
    contraction's length): the embedding and the head [vocab, d] by d,
    so that the embedding leaves the sqrt(d) multiplier at unit scale
    (the size every layer's normed branch adds) and the head gives
    logits of unit spread; norm gains near 1 but not AT it, so that a
    dropped gain shows; the router's bias N(0, 0.05), small against the
    scores' spread and large enough that choice and weight differ."""
    if kind == "stacked":  # [E, rows, cols]: an expert at a time
        return jax.lax.map(lambda k: _leaf(k, shp[1:], "w", dtype),
                           jax.random.split(key, shp[0]))
    n = jax.random.normal(key, shp, jnp.float32)
    if kind == "norm":
        a = 1.0 + 0.1 * n
    elif kind == "router_bias":
        a = 0.05 * n
    else:
        a = n / math.sqrt(shp[-1] if kind == "vocab" else shp[-2])
    return a.astype(dtype)


def init_weights(shape, max_len, seed, dtype=jnp.bfloat16):
    """Random weights on the device, leaf by leaf (one small cached
    program per kind and shape), so that no float32 copy of more than
    one matrix (of a stacked leaf, one expert's) is live beside the
    8.5 GB they come to."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        weight_shapes(shape, max_len), is_leaf=lambda x: isinstance(x, tuple))
    key = seed_key(seed)
    dtype = jnp.dtype(dtype).name

    def kind(path, shp):
        name = str(getattr(path[-1], "key", "w"))
        if "norm" in name:
            return "norm"
        if name in ("embed", "head"):
            return "vocab"
        if name == "router_bias":
            return name
        return "stacked" if len(shp) == 3 else "w"

    out = [_leaf(jax.random.fold_in(key, i), shp, kind(path, shp), dtype)
           for i, (path, shp) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(treedef, out)


def _q8(x):
    """Round to 8-bit integers on an absmax scale per row of the last
    axis -> the dequantized float32 values."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s) * s


def _mm(a, b, quant):
    """a @ b at the highest float32 precision; under `quant` both
    operands are rounded along the contraction first."""
    if quant == "int8":
        a, b = _q8(a), jnp.swapaxes(_q8(jnp.swapaxes(b, -1, -2)), -1, -2)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, w):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + EPS) * w


def _rope(x, theta):
    """x [T, H, dh], row t at position t: x cos + rotate_half(x) sin,
    the angles' table repeated over the two halves."""
    T, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]  # [T, 1, dh]
    rot = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _attention(q, k, v, window, quant):
    """q [T, Hq, dh], k and v [T, Hkv, dh] -> [T, Hq * dh], one query
    head at a time so that one [T, T] score matrix is live; `window`
    0 is full causal."""
    T, hq, dh = q.shape
    rep = hq // k.shape[1]
    pos = jnp.arange(T)
    mask = pos[None, :] <= pos[:, None]
    if window:
        mask = mask & (pos[None, :] > pos[:, None] - window)

    def head(h):
        s = _mm(q[:, h] / math.sqrt(dh), k[:, h // rep].T, quant)
        prob = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return _mm(prob, v[:, h // rep], quant)  # [T, dh]

    o = jax.lax.map(head, jnp.arange(hq))  # [Hq, T, dh]
    return o.transpose(1, 0, 2).reshape(T, hq * dh)


def _swiglu(u, w_gu, w_down, quant):
    gu = _mm(u, w_gu.astype(jnp.float32), quant)
    m = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[:, :m]) * gu[:, m:],
               w_down.astype(jnp.float32), quant)


def _experts(u, p, lo, hi, top_k, route_scale, route_norm, shared, quant,
             no_bias, no_scale):
    """The expert layer over u [T, d] (float32), the leaves of `p` as
    they are stored: an expert's matrices are upcast when its turn
    comes."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(_mm(u, p["router"].astype(f32), quant))  # [T, E]
    b = 0.0 if no_bias else p["router_bias"].astype(f32)
    _, chosen = jax.lax.top_k(s + b, top_k)  # [T, k]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if route_norm:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    if not no_scale:
        w = w * route_scale

    def one(acc, xs):
        e, w_gu, w_down = xs
        mine = jnp.where(chosen == e, w, 0.0).sum(-1)  # [T]: 0 if not chosen
        return acc + mine[:, None] * _swiglu(u, w_gu, w_down, quant), None

    ex = p["experts"]
    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (jnp.arange(lo, hi), ex["w_gu"], ex["w_down"]))
    if shared:
        out = out + _swiglu(u, p["shared"]["w_gu"], p["shared"]["w_down"],
                            quant)
    return out


@functools.partial(jax.jit, static_argnames=(
    "hq", "hkv", "dh", "window", "dense", "lo", "hi", "top_k", "route_norm",
    "shared", "quant", "no_bias", "no_scale"))
def _layer(x, blk, theta, route_scale, hq, hkv, dh, window, dense, lo, hi,
           top_k, route_norm, shared, quant, no_bias=False, no_scale=False):
    """One layer -> x. `window` 0 is a full layer (no position).
    theta and route_scale are operands, so that the layers of one kind
    share one compiled program."""
    f32 = jnp.float32
    p = jax.tree_util.tree_map(lambda a: a.astype(f32), blk["attn"])
    T = x.shape[0]
    y = _mm(_rms(x, blk["norm1"].astype(f32)), p["wqkvg"], quant)
    nq, nk = hq * dh, hkv * dh
    q = _rms(y[:, :nq].reshape(T, hq, dh), p["q_norm"])
    k = _rms(y[:, nq:nq + nk].reshape(T, hkv, dh), p["k_norm"])
    v = y[:, nq + nk:nq + 2 * nk].reshape(T, hkv, dh)
    if window:
        q, k = _rope(q, theta), _rope(k, theta)
    o = _attention(q, k, v, window, quant) * jax.nn.sigmoid(
        y[:, nq + 2 * nk:])
    x = x + _rms(_mm(o, p["wo"], quant), blk["norm2"].astype(f32))
    u = _rms(x, blk["norm3"].astype(f32))
    ffn = blk["ffn"]
    if dense:
        m = _swiglu(u, ffn["w_gu"], ffn["w_down"], quant)
    else:
        m = _experts(u, ffn, lo, hi, top_k, route_scale, route_norm, shared,
                     quant, no_bias, no_scale)
    return x + _rms(m, blk["norm4"].astype(f32))


def hidden(params, tokens, shape, quant=None, **faults):
    """tokens [T] -> the final RMSNorm's float32 output [T, d], layer
    by layer so that only one layer's float32 copies are live."""
    tokens = jnp.asarray(tokens, jnp.int32)
    z = dims(shape)
    x = params["embed"][tokens].astype(jnp.float32) * math.sqrt(z["d"])
    for l, (blk, kind) in enumerate(zip(params["blocks"],
                                        shape["layer_types"])):
        x = _layer(x, blk, jnp.float32(shape["rope_theta"]),
                   jnp.float32(shape["route_scale"]), z["hq"], z["hkv"],
                   z["dh"], z["W"] if kind == "sliding_attention" else 0,
                   l < shape["num_dense_layers"], z["lo"], z["hi"], z["k"],
                   bool(shape["route_norm"]), z["shared"], quant, **faults)
    return _rms(x, params["norm_f"].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("quant",))
def _head(x, head, quant):
    return _mm(x, head.astype(jnp.float32).T, quant)


def logits(params, tokens, shape, quant=None, **faults):
    """tokens [T] -> float32 logits [T, vocab] (small shapes: the
    comparison below never holds all of it)."""
    return _head(hidden(params, tokens, shape, quant, **faults),
                 params["head"], quant)


@functools.partial(jax.jit, static_argnames=("quant",))
def _gap_rows(x, xq, head, picked, quant):
    """Rows of hidden states -> how far the logit of `picked` (or, with
    `xq`, of what the control's logits put first) lies below the
    reference's best."""
    ref = _mm(x, head.astype(jnp.float32).T, None)
    if xq is not None:
        picked = jnp.argmax(_mm(xq, head.astype(jnp.float32).T, quant), -1)
    got = jnp.take_along_axis(ref, picked[:, None], axis=-1)[:, 0]
    return ref.max(-1) - got


def served_gap(params, shape, prompt, served, pad_to, control=None,
               rows=512):
    """How far each served token's logit lies below the reference's
    best, over one request: the reference runs once over prompt +
    served tokens (padded on the right to `pad_to`, which a causal
    model ignores: a row's experts are its own); the head runs over the
    judged positions only, `rows` at a time (a whole [T, vocab] would
    not fit beside the weights). With `control`, the tokens judged are
    not the served ones but those the lower precision puts first at
    the same positions.
    -> {"max": widest gap, "sum": of all gaps, "n": positions compared,
        "flips": positions whose judged token is not the reference's first}"""
    n0, n1 = len(prompt), len(served)
    seq = np.zeros(pad_to, np.int32)
    seq[:n0] = prompt
    seq[n0:n0 + n1] = served
    x = hidden(params, seq, shape)
    xq = hidden(params, seq, shape, quant=control) if control else None
    # the token at position p + 1 was picked from the logits at p
    picked = np.append(seq[1:], 0).astype(np.int32)
    gaps = []
    for lo in range(n0 - 1, n0 + n1 - 1, rows):
        hi = min(lo + rows, n0 + n1 - 1)
        # every slice is `rows` long (one compiled shape): the last one
        # starts early and its head is dropped
        a = max(0, min(lo, pad_to - rows))
        sl = slice(a, a + rows)
        g = _gap_rows(x[sl], None if xq is None else xq[sl], params["head"],
                      jnp.asarray(picked[sl]), control)
        gaps.append(np.asarray(g, np.float64)[lo - a:hi - a])
    gaps = np.concatenate(gaps)
    return {"max": float(gaps.max()), "sum": float(gaps.sum()), "n": n1,
            "flips": int((gaps > 0).sum())}
