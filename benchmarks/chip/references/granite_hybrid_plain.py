"""Plain reference of the Mamba-2 / grouped-query hybrid the cell
`granite4hmicro_reason_closed` serves (granite-4.0-h-micro,
`model_type` granitemoehybrid, dense).

Straightforward jax.numpy in float32 with every matrix product at the
highest precision: a `lax.scan` over time for the Mamba-2 recurrence
(the SEQUENTIAL form: the program's prefill runs the blocked matrix
form, which is thereby checked against something independent),
full-matrix causal attention one query head at a time, no cache, no
kernels, no batching. It imports nothing of the program; the weights
are made here, from the seed.

`shape` is the configuration's "shape" group: vocab, dim, heads,
kv_heads, head_dim, layers, layer_types, mlp_mult, mamba_heads,
mamba_head_dim, d_state, d_conv, and the four published constants
embedding_multiplier, residual_multiplier, attention_multiplier,
logits_scaling (eps is 1e-5).

  x0 = embedding_multiplier * E[token]
  every layer: x += residual_multiplier * Mixer(RMSNorm(x));
               x += residual_multiplier * W_down(silu(g) * u),
               [g, u] = RMSNorm(x) W_gu
  logits = RMSNorm(x) E^T / logits_scaling   (tied embedding)
  RMSNorm with a learned scale; no bias on any matrix; no positional
  encoding.

  attention  `heads` query heads over `kv_heads` K/V heads of width
             head_dim: query head h reads K/V head h // (heads /
             kv_heads); softmax(q k^T * attention_multiplier, causal) v
  mamba      Mamba-2: [z | xBC | dt] = h W_in (di | di + 2N | H);
             xBC' = silu(causal depthwise conv over d_conv taps + bias);
             x [H, P], B [N], C [N] = split xBC'; dt = softplus(dt +
             dt_bias); A = -exp(A_log);
             S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t
             y_t[h] = S_t[h] C_t + D[h] x_t[h]
             out = RMSNorm(y * silu(z)) W_out

`quant="int8"` is the control: the same forward with both operands of
every matrix product rounded to 8-bit integers (absmax scale per row
of the contraction), the nearest precision below the bf16 that the
configuration states. The recurrence itself is elementwise and stays
float32 under the control.
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
    H, P, N = shape["mamba_heads"], shape["mamba_head_dim"], shape["d_state"]
    return {"d": shape["dim"], "m": shape["mlp_mult"] * shape["dim"],
            "di": H * P, "H": H, "P": P, "N": N, "K": shape["d_conv"],
            "hq": shape["heads"], "hkv": shape["kv_heads"],
            "dh": shape["head_dim"]}


def weight_shapes(shape, max_len=None):
    """The parameter tree the served entry takes, as shapes."""
    z = dims(shape)
    d, m, di, N, H = z["d"], z["m"], z["di"], z["N"], z["H"]
    mixers = {
        "mamba": {"in_proj": (d, 2 * di + 2 * N + H),
                  "conv_w": (di + 2 * N, z["K"]), "conv_b": (di + 2 * N,),
                  "dt_bias": (H,), "A_log": (H,), "D": (H,), "norm": (di,),
                  "out_proj": (di, d)},
        "attention": {"wqkv": (d, (z["hq"] + 2 * z["hkv"]) * z["dh"]),
                      "wo": (z["hq"] * z["dh"], d)},
    }
    return {"embed": (shape["vocab"], d), "norm_f": (d,), "blocks": [
        {"norm1": (d,), "mixer": mixers[kind], "norm2": (d,),
         "w_gu": (d, 2 * m), "w_down": (m, d)}
        for kind in shape["layer_types"]]}


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
    """One leaf in the type it is served in. Matrices N(0, 1/rows), the
    tied embedding with them, N(0, 1/vocab) (at N(0, 1/dim) the input
    token's own logit leads every row through the tie and greedy
    streams repeat one token); norm gains near 1 but not AT it, so
    that a dropped gain shows; the conv uniform +-d_conv^-1/2, its bias
    N(0, 0.1); the Mamba-2 leaves by the published initialisers (A
    uniform in [1, 16], dt bias the inverse softplus of a log-uniform
    step in [1e-3, 1e-1], D = 1)."""
    if kind == "A_log":
        a = jnp.log(jax.random.uniform(key, shp, jnp.float32, 1.0, 16.0))
    elif kind == "D":
        a = jnp.ones(shp, jnp.float32)
    elif kind == "dt_bias":
        u = jax.random.uniform(key, shp, jnp.float32)
        dt = jnp.maximum(jnp.exp(u * (math.log(0.1) - math.log(1e-3))
                                 + math.log(1e-3)), 1e-4)
        a = dt + jnp.log(-jnp.expm1(-dt))
    elif kind == "conv_w":
        bound = shp[1] ** -0.5
        a = jax.random.uniform(key, shp, jnp.float32, -bound, bound)
    else:
        n = jax.random.normal(key, shp, jnp.float32)
        if kind.startswith("norm"):
            a = 1.0 + 0.1 * n
        elif len(shp) == 1:
            a = 0.1 * n
        else:
            a = n / math.sqrt(shp[0])
    return a.astype(dtype)


def init_weights(shape, max_len, seed, dtype=jnp.bfloat16):
    """Random weights on the device, leaf by leaf (one small cached
    program per kind and shape), so that no float32 copy of more than
    one matrix is ever live beside the 6.4 GB they come to."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        weight_shapes(shape, max_len), is_leaf=lambda x: isinstance(x, tuple))
    key = seed_key(seed)
    dtype = jnp.dtype(dtype).name
    out = [_leaf(jax.random.fold_in(key, i), shp,
                 str(getattr(path[-1], "key", "w")), dtype)
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


def _mamba2(h, p, H, P, N, K, quant):
    T = h.shape[0]
    di = H * P
    zxd = _mm(h, p["in_proj"], quant)
    c = di + 2 * N
    z, xbc, dt = zxd[:, :di], zxd[:, di:di + c], zxd[:, di + c:]
    padded = jnp.concatenate([jnp.zeros((K - 1, c)), xbc], axis=0)
    conv = sum(padded[i:i + T] * p["conv_w"][:, i] for i in range(K))
    xbc = jax.nn.silu(conv + p["conv_b"])
    x = xbc[:, :di].reshape(T, H, P)
    B, C = xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])  # [T, H]
    A = -jnp.exp(p["A_log"])  # [H]

    def step(s, xs):  # s [H, P, N]
        dt_t, x_t, b_t, c_t = xs
        s = (jnp.exp(dt_t * A)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return s, (s * c_t[None, None, :]).sum(-1) + p["D"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (dt, x, B, C))
    y = y.reshape(T, di) * jax.nn.silu(z)
    return _mm(_rms(y, p["norm"]), p["out_proj"], quant)


def _attention(q, k, v, scale, quant):
    """q [T, Hq, dh], k and v [T, Hkv, dh] -> [T, Hq * dh], one query
    head at a time so that one [T, T] score matrix is live."""
    T, hq, dh = q.shape
    rep = hq // k.shape[1]
    pos = jnp.arange(T)
    mask = pos[None, :] <= pos[:, None]

    def head(h):
        s = _mm(q[:, h] * scale, k[:, h // rep].T, quant)
        prob = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return _mm(prob, v[:, h // rep], quant)  # [T, dh]

    o = jax.lax.map(head, jnp.arange(hq))  # [Hq, T, dh]
    return o.transpose(1, 0, 2).reshape(T, hq * dh)


@functools.partial(jax.jit, static_argnames=(
    "kind", "hq", "hkv", "dh", "H", "P", "N", "K", "quant"))
def _layer(x, blk, scale, res, kind, hq, hkv, dh, H, P, N, K, quant):
    """One layer -> x. The multipliers are operands, not constants, so
    that the layers of one kind share one compiled program."""
    blk = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), blk)
    p = blk["mixer"]
    T = x.shape[0]
    h = _rms(x, blk["norm1"])
    if kind == "mamba":
        o = _mamba2(h, p, H, P, N, K, quant)
    else:
        qkv = _mm(h, p["wqkv"], quant)
        q = qkv[:, :hq * dh].reshape(T, hq, dh)
        k = qkv[:, hq * dh:(hq + hkv) * dh].reshape(T, hkv, dh)
        v = qkv[:, (hq + hkv) * dh:].reshape(T, hkv, dh)
        o = _mm(_attention(q, k, v, scale, quant), p["wo"], quant)
    x = x + res * o
    gu = _mm(_rms(x, blk["norm2"]), blk["w_gu"], quant)
    m = gu.shape[-1] // 2
    return x + res * _mm(jax.nn.silu(gu[:, :m]) * gu[:, m:], blk["w_down"],
                         quant)


def hidden(params, tokens, shape, quant=None):
    """tokens [T] -> the final RMSNorm's float32 output [T, d], layer
    by layer so that only one layer's float32 weights are live."""
    tokens = jnp.asarray(tokens, jnp.int32)
    z = dims(shape)
    x = (params["embed"][tokens].astype(jnp.float32)
         * shape["embedding_multiplier"])
    for blk, kind in zip(params["blocks"], shape["layer_types"]):
        x = _layer(x, blk, jnp.float32(shape["attention_multiplier"]),
                   jnp.float32(shape["residual_multiplier"]), kind,
                   z["hq"], z["hkv"], z["dh"], z["H"], z["P"], z["N"],
                   z["K"], quant)
    return _rms(x, params["norm_f"].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("quant",))
def _head(x, embed, scaling, quant):
    return _mm(x, embed.astype(jnp.float32).T, quant) / scaling


def logits(params, tokens, shape, quant=None):
    """tokens [T] -> float32 logits [T, vocab] (small shapes: the
    comparison below never holds all of it)."""
    return _head(hidden(params, tokens, shape, quant), params["embed"],
                 jnp.float32(shape["logits_scaling"]), quant)


@functools.partial(jax.jit, static_argnames=("quant",))
def _gap_rows(x, xq, embed, scaling, picked, quant):
    """Rows of hidden states -> how far the logit of `picked` (or, with
    `xq`, of what the control's logits put first) lies below the
    reference's best."""
    ref = _mm(x, embed.astype(jnp.float32).T, None) / scaling
    if xq is not None:
        picked = jnp.argmax(_mm(xq, embed.astype(jnp.float32).T, quant), -1)
    got = jnp.take_along_axis(ref, picked[:, None], axis=-1)[:, 0]
    return ref.max(-1) - got


def served_gap(params, shape, prompt, served, pad_to, control=None,
               rows=512):
    """How far each served token's logit lies below the reference's
    best, over one request: the reference runs once over prompt +
    served tokens (padded on the right to `pad_to`, which a causal
    model ignores); the head runs over the judged positions only,
    `rows` at a time (a whole [T, vocab] would not fit beside the
    weights). With `control`, the tokens judged are not the served
    ones but those the lower precision puts first at the same
    positions.
    -> {"max": widest gap, "sum": of all gaps, "n": positions compared,
        "flips": positions whose judged token is not the reference's first}"""
    n0, n1 = len(prompt), len(served)
    seq = np.zeros(pad_to, np.int32)
    seq[:n0] = prompt
    seq[n0:n0 + n1] = served
    x = hidden(params, seq, shape)
    xq = hidden(params, seq, shape, quant=control) if control else None
    scaling = jnp.float32(shape["logits_scaling"])
    # the token at position p + 1 was picked from the logits at p
    picked = np.append(seq[1:], 0).astype(np.int32)
    gaps = []
    for lo in range(n0 - 1, n0 + n1 - 1, rows):
        hi = min(lo + rows, n0 + n1 - 1)
        # every slice is `rows` long (one compiled shape): the last one
        # starts early and its head is dropped
        a = max(0, min(lo, pad_to - rows))
        sl = slice(a, a + rows)
        g = _gap_rows(x[sl], None if xq is None else xq[sl], params["embed"],
                      scaling, jnp.asarray(picked[sl]), control)
        gaps.append(np.asarray(g, np.float64)[lo - a:hi - a])
    gaps = np.concatenate(gaps)
    return {"max": float(gaps.max()), "sum": float(gaps.sum()), "n": n1,
            "flips": int((gaps > 0).sum())}
