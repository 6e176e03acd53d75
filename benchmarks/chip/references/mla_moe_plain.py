"""Plain reference of the latent-attention sparse-expert model the cell
`kanana2_reason128_closed` serves (Kanana-2-30B-A3B, `model_type`
deepseek_v3, no query LoRA).

Straightforward jax.numpy in float32 with every matrix product at the
highest precision, in the PUBLISHED, expanded form: every head's keys
and values made from the latent by W_kvb, full-matrix attention one
query head at a time, the routed experts one expert at a time over ALL
rows with the rows that did not choose it weighted 0 (no sorting, no
grouping, no capacity), routing by this file's own top-k over its own
scores, no absorption, no cache, no kernels, no batching. It imports
nothing of the program; the weights are made here, from the seed, and
upcast a layer — inside an expert layer an expert — at a time.

`shape` is the configuration's "shape" group: vocab, dim, heads,
nope_dim, rope_dim, v_dim, kv_rank, layers, num_dense_layers,
dense_width, expert_width, n_shared_experts, n_experts, top_k,
route_scale, route_norm, rope_theta and, where a chip holds a share,
experts_held [lo, hi] and shared_expert_held (eps is 1e-6; no bias on
any matrix).

  x0 = E[token]
  every layer: x += Attn(RMS(x; g1)); x += FFN(RMS(x; g2))
  logits = W_head RMS(x; g_f)          (its own matrix, not E)

  Attn(u)  q = u W_q, viewed [T, heads, nope + rope] -> q_nope, q_pe;
           a = u W_kva [T, kv_rank + rope] -> c = RMS(a[:, :kv_rank];
           g_kv), k_pe = a[:, kv_rank:]; [k_nope | v] = c W_kvb viewed
           [T, heads, nope + v_dim]; q_pe and k_pe rotated as the
           published modeling code's `apply_rotary_pos_emb_interleave`
           does (rope_interleave true): view the rope dims as (pairs,
           2), transpose to (2, pairs), then x cos + rotate_half(x) sin
           with the angles' table repeated over the two halves (theta,
           no scaling); q = [q_nope | q_pe], k = [k_nope | k_pe] (k_pe
           the same for every head); o = softmax(q k^T / sqrt(nope +
           rope), causal) v; W_o o
  FFN(u)   layer < num_dense_layers: W_down(silu(g) * up), [g | up] =
           u W_gu. Else s = sigmoid(u W_r); S = the top_k largest of
           s + b (b decides the choice only); w_e = route_scale * s_e /
           (sum_{e in S} s_e + 1e-20) (the division only with
           route_norm); out = Shared(u) + sum_{e in S, e held} w_e
           Expert_e(u): experts of width expert_width, the shared one
           n_shared_experts x expert_width wide.

`quant="int8"` is the control: the same forward with both operands of
every matrix product — the router's among them — rounded to 8-bit
integers (absmax scale per row of the contraction), the nearest
precision below the bf16 that the configuration states.
`no_bias=True` and `no_rope=True` are planted faults for the tests: a
router that ignores its bias, or a rotary key left unrotated.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-6


def dims(shape):
    lo, hi = shape.get("experts_held") or (0, shape["n_experts"])
    return {"d": shape["dim"], "H": shape["heads"], "dn": shape["nope_dim"],
            "dr": shape["rope_dim"], "dv": shape["v_dim"],
            "r": shape["kv_rank"], "md": shape["dense_width"],
            "me": shape["expert_width"],
            "ms": shape["n_shared_experts"] * shape["expert_width"],
            "E": shape["n_experts"], "lo": int(lo), "hi": int(hi),
            "k": shape["top_k"],
            "shared": bool(shape.get("shared_expert_held", True))}


def weight_shapes(shape, max_len=None):
    """The parameter tree the served entry takes, as shapes."""
    z = dims(shape)
    d, H, r = z["d"], z["H"], z["r"]
    Eh = z["hi"] - z["lo"]
    attn = {"wq": (d, H * (z["dn"] + z["dr"])), "wkva": (d, r + z["dr"]),
            "kv_norm": (r,), "wkvb": (r, H * (z["dn"] + z["dv"])),
            "wo": (H * z["dv"], d)}
    dense = {"w_gu": (d, 2 * z["md"]), "w_down": (z["md"], d)}
    moe = {"router": (d, z["E"]), "router_bias": (z["E"],),
           "experts": {"w_gu": (Eh, d, 2 * z["me"]),
                       "w_down": (Eh, z["me"], d)},
           "shared": {"w_gu": (d, 2 * z["ms"]), "w_down": (z["ms"], d)}}
    return {"embed": (shape["vocab"], d), "norm_f": (d,),
            "head": (shape["vocab"], d),
            "blocks": [{"norm1": (d,), "norm2": (d,), "attn": dict(attn),
                        "ffn": dict(dense if l < shape["num_dense_layers"]
                                    else moe)}
                       for l in range(shape["layers"])]}


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
    """One leaf in the type it is served in. The embedding N(0, 1): no
    multiplier follows it, so its rows enter the residual at unit
    scale, the size every branch adds; every other matrix N(0, 1 / the
    contraction's length), the head [vocab, d] by d, so that it gives
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
    elif kind == "embed":
        a = n
    else:
        a = n / math.sqrt(shp[-1] if kind == "head" else shp[-2])
    return a.astype(dtype)


def init_weights(shape, max_len, seed, dtype=jnp.bfloat16):
    """Random weights on the device, leaf by leaf (one small cached
    program per kind and shape), so that no float32 copy of more than
    one matrix (of a stacked leaf, one expert's) is live beside the
    2.74 GB they come to."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        weight_shapes(shape, max_len), is_leaf=lambda x: isinstance(x, tuple))
    key = seed_key(seed)
    dtype = jnp.dtype(dtype).name

    def kind(path, shp):
        name = str(getattr(path[-1], "key", "w"))
        if "norm" in name:
            return "norm"
        if name in ("embed", "head", "router_bias"):
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


def _rope_interleave(x, theta):
    """x [T, H, dr], row t at position t, as the published code rotates
    it: (pairs, 2) -> (2, pairs), then x cos + rotate_half(x) sin."""
    T, H, dr = x.shape
    x = x.reshape(T, H, dr // 2, 2).transpose(0, 1, 3, 2).reshape(T, H, dr)
    inv = 1.0 / theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]  # [T, 1, dr]
    rot = jnp.concatenate([-x[..., dr // 2:], x[..., :dr // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _attention(q, k, v, quant):
    """q, k [T, H, dq], v [T, H, dv] -> [T, H * dv], one head at a time
    so that one [T, T] score matrix is live; causal."""
    T, H, dq = q.shape
    pos = jnp.arange(T)
    mask = pos[None, :] <= pos[:, None]

    def head(h):
        s = _mm(q[:, h] / math.sqrt(dq), k[:, h].T, quant)
        prob = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return _mm(prob, v[:, h], quant)  # [T, dv]

    o = jax.lax.map(head, jnp.arange(H))  # [H, T, dv]
    return o.transpose(1, 0, 2).reshape(T, -1)


def _swiglu(u, w_gu, w_down, quant):
    gu = _mm(u, w_gu.astype(jnp.float32), quant)
    m = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[:, :m]) * gu[:, m:],
               w_down.astype(jnp.float32), quant)


def _experts(u, p, lo, hi, top_k, route_scale, route_norm, shared, quant,
             no_bias):
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
    "H", "dn", "dr", "dv", "r", "dense", "lo", "hi", "top_k", "route_norm",
    "shared", "quant", "no_bias", "no_rope"))
def _layer(x, blk, theta, route_scale, H, dn, dr, dv, r, dense, lo, hi,
           top_k, route_norm, shared, quant, no_bias=False, no_rope=False):
    """One layer -> x. theta and route_scale are operands, so that the
    layers of one kind share one compiled program."""
    f32 = jnp.float32
    p = jax.tree_util.tree_map(lambda a: a.astype(f32), blk["attn"])
    T = x.shape[0]
    h = _rms(x, blk["norm1"].astype(f32))
    q = _mm(h, p["wq"], quant).reshape(T, H, dn + dr)
    a = _mm(h, p["wkva"], quant)
    c = _rms(a[:, :r], p["kv_norm"])
    k_pe = a[:, None, r:]  # [T, 1, dr]
    kv = _mm(c, p["wkvb"], quant).reshape(T, H, dn + dv)
    q_pe = q[..., dn:]
    if not no_rope:
        q_pe = _rope_interleave(q_pe, theta)
        k_pe = _rope_interleave(k_pe, theta)
    qq = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    kk = jnp.concatenate([kv[..., :dn],
                          jnp.broadcast_to(k_pe, (T, H, dr))], axis=-1)
    o = _attention(qq, kk, kv[..., dn:], quant)
    x = x + _mm(o, p["wo"], quant)
    u = _rms(x, blk["norm2"].astype(f32))
    ffn = blk["ffn"]
    if dense:
        m = _swiglu(u, ffn["w_gu"], ffn["w_down"], quant)
    else:
        m = _experts(u, ffn, lo, hi, top_k, route_scale, route_norm, shared,
                     quant, no_bias)
    return x + m


def hidden(params, tokens, shape, quant=None, **faults):
    """tokens [T] -> the final RMSNorm's float32 output [T, d], layer
    by layer so that only one layer's float32 copies are live."""
    tokens = jnp.asarray(tokens, jnp.int32)
    z = dims(shape)
    x = params["embed"][tokens].astype(jnp.float32)
    for l, blk in enumerate(params["blocks"]):
        x = _layer(x, blk, jnp.float32(shape["rope_theta"]),
                   jnp.float32(shape["route_scale"]), z["H"], z["dn"],
                   z["dr"], z["dv"], z["r"], l < shape["num_dense_layers"],
                   z["lo"], z["hi"], z["k"], bool(shape["route_norm"]),
                   z["shared"], quant, **faults)
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
