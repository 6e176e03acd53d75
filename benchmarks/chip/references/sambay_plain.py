"""Plain reference of the SambaY decoder-hybrid-decoder the hybrid cell
serves (Phi-4-mini-flash-reasoning; arXiv:2507.06607).

Straightforward jax.numpy in float32 with every matrix product at the
highest precision: a `lax.scan` over time for the Mamba recurrence,
full-matrix causal and banded attention, no cache, no kernels, no
batching. It imports nothing of the program; the weights are made
here, from the seed.

Residual form of every layer l: x += Mixer_l(LN(x)); x += W_down(
silu(W_gate h) * W_up h), h = LN(x); LayerNorm with scale and bias,
eps 1e-5; a final LayerNorm; logits x E^T with the tied embedding; no
positional encoding. The mixer by kind (`layer_kinds`):

  mamba   Mamba-1 (d_state 16, d_conv 4, dt_rank d/16, expand 2). The
          last Mamba layer of the first half also hands its scan
          output y_t (before the gate) up as the memory M_t.
  window  differential attention over the last `window` positions
  full    differential attention, full causal: its K/V are the ones
          every `cross` layer reads
  gmu     gated memory unit: (M_t * silu(h W_1)) W_2
  cross   a query only; differential attention onto `full`'s K/V

Differential attention: 2H query heads, H key and H value heads of
width dh. Pair p = 0..H-1 belongs to K/V group g = p // 2; with
j in {0, 1}: P_{p,j} = softmax(q_{2p+j} . k_{2g+j} / sqrt(dh)),
V_g = [v_{2g}; v_{2g+1}] (2 dh wide), a_p = P_{p,0} V_g - lambda
P_{p,1} V_g, lambda = exp(lq1.lk1) - exp(lq2.lk2) + lambda_init(l),
o_p = RMSNorm(a_p; scale, eps 1e-5) (1 - lambda_init(l)).

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
D_STATE, D_CONV = 16, 4


def layer_kinds(layers):
    """The mixer of each layer, from the depth alone: the first half
    alternates mamba / window, the second half opens with the memory's
    Mamba layer and the one full-attention layer and then alternates
    gmu / cross (32 layers: mamba at 0..16 even, window at 1..15 odd,
    full at 17, gmu at 18..30 even, cross at 19..31 odd)."""
    half = layers // 2
    kinds = []
    for l in range(layers):
        if l <= half:
            kinds.append("mamba" if l % 2 == 0 else "window")
        elif l == half + 1:
            kinds.append("full")
        else:
            kinds.append("gmu" if l % 2 == 0 else "cross")
    if layers % 2 or half % 2 or layers < 4:
        raise ValueError("layers must be a multiple of 4 (got %d)" % layers)
    return kinds


def lambda_init(l):
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def dims(shape):
    d = shape["dim"]
    dh = d // shape["heads"]
    return {"d": d, "m": shape["mlp_mult"] * d, "di": 2 * d,
            "rank": d // 16, "hq": shape["heads"],
            "hkv": shape["kv_heads"], "dh": dh}


def weight_shapes(shape, max_len=None):
    """The parameter tree the served entry takes, as shapes."""
    z = dims(shape)
    d, m, di, R, dh = z["d"], z["m"], z["di"], z["rank"], z["dh"]
    ln = {"g": (d,), "b": (d,)}
    lam = {"lam_q1": (dh,), "lam_k1": (dh,), "lam_q2": (dh,),
           "lam_k2": (dh,), "subln": (2 * dh,)}
    mixers = {
        "mamba": {"in_proj": (d, 2 * di), "conv_w": (di, D_CONV),
                  "conv_b": (di,), "x_proj": (di, R + 2 * D_STATE),
                  "dt_proj": (R, di), "dt_bias": (di,),
                  "A_log": (di, D_STATE), "D": (di,), "out_proj": (di, d)},
        "attn": dict(lam, wqkv=(d, (z["hq"] + 2 * z["hkv"]) * dh),
                     bqkv=((z["hq"] + 2 * z["hkv"]) * dh,),
                     wo=(d, d), bo=(d,)),
        "cross": dict(lam, wq=(d, d), bq=(d,), wo=(d, d), bo=(d,)),
        "gmu": {"w1": (d, di), "w2": (di, d)},
    }
    blocks = []
    for kind in layer_kinds(shape["layers"]):
        mix = mixers["attn" if kind in ("window", "full") else kind]
        blocks.append({"ln1": ln, "mixer": mix, "ln2": ln,
                       "w_gu": (d, 2 * m), "w_down": (m, d)})
    return {"embed": (shape["vocab"], d), "blocks": blocks, "ln_f": ln}


def seed_key(seed):
    """A raw threefry key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                       jnp.uint32)


@functools.partial(jax.jit, static_argnames=("shp", "kind", "dtype"))
def _leaf(key, shp, kind, dtype):
    """One leaf in the type it is served in. Matrices N(0, 1/fan_in);
    LayerNorm gains and the sub-norm's near 1 and biases near 0 but
    not AT them, so that a dropped gain or bias shows; the Mamba and
    lambda leaves by the family's published initialisers (A_log =
    log 1..16, dt bias the inverse softplus of a log-uniform step in
    [1e-3, 1e-1], D = 1, dt_proj uniform +-rank^-1/2, conv uniform
    +-d_conv^-1/2, lambda vectors N(0, 0.1))."""
    if kind == "A_log":
        a = jnp.broadcast_to(jnp.log(jnp.arange(1, shp[1] + 1,
                                                dtype=jnp.float32)), shp)
    elif kind == "D":
        a = jnp.ones(shp, jnp.float32)
    elif kind == "dt_bias":
        u = jax.random.uniform(key, shp, jnp.float32)
        dt = jnp.maximum(jnp.exp(u * (math.log(0.1) - math.log(1e-3))
                                 + math.log(1e-3)), 1e-4)
        a = dt + jnp.log(-jnp.expm1(-dt))
    elif kind in ("dt_proj", "conv_w"):
        bound = (shp[0] if kind == "dt_proj" else shp[1]) ** -0.5
        a = jax.random.uniform(key, shp, jnp.float32, -bound, bound)
    else:
        n = jax.random.normal(key, shp, jnp.float32)
        if kind in ("g", "subln"):
            a = 1.0 + 0.1 * n
        elif kind.startswith("lam_") or len(shp) == 1:
            a = 0.1 * n  # lambda vectors and every bias
        else:
            a = n / math.sqrt(shp[0])
    return a.astype(dtype)


def init_weights(shape, max_len, seed, dtype=jnp.bfloat16):
    """Random weights on the device, leaf by leaf (one small cached
    program per kind and shape), so that no float32 copy of more than
    one matrix is ever live beside the 7.7 GB they come to."""
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


def _ln(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * p["g"] + p["b"]


def _mamba(h, p, quant):
    """-> (the mixer's output [T, d], the scan output y [T, di])."""
    T = h.shape[0]
    di, R = p["conv_w"].shape[0], p["dt_proj"].shape[0]
    uz = _mm(h, p["in_proj"], quant)
    u, z = uz[:, :di], uz[:, di:]
    padded = jnp.concatenate([jnp.zeros((D_CONV - 1, di)), u], axis=0)
    conv = sum(padded[i:i + T] * p["conv_w"][:, i] for i in range(D_CONV))
    u = jax.nn.silu(conv + p["conv_b"])
    xp = _mm(u, p["x_proj"], quant)
    dr, B, C = xp[:, :R], xp[:, R:R + D_STATE], xp[:, R + D_STATE:]
    delta = jax.nn.softplus(_mm(dr, p["dt_proj"], quant) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])  # [di, N]

    def step(s, xs):
        d_t, u_t, b_t, c_t = xs
        s = jnp.exp(d_t[:, None] * A) * s + (d_t * u_t)[:, None] * b_t[None]
        return s, (s * c_t[None]).sum(-1) + p["D"] * u_t

    _, y = jax.lax.scan(step, jnp.zeros((di, D_STATE)), (delta, u, B, C))
    return _mm(y * jax.nn.silu(z), p["out_proj"], quant), y


def _diff_attention(q, k, v, p, lam0, window, quant):
    """q [T, 2H, dh], k and v [T, H, dh] -> [T, H * 2 dh], one query
    head at a time so that one [T, T] score matrix is live."""
    T, hq, dh = q.shape
    pos = jnp.arange(T)
    mask = pos[None, :] <= pos[:, None]
    if window:
        mask = mask & (pos[None, :] > pos[:, None] - window)
    vg = v.reshape(T, -1, 2 * dh)  # [T, H/2, 2 dh]

    def head(h):
        g, j = h // 4, h % 2
        s = _mm(q[:, h] / math.sqrt(dh), k[:, 2 * g + j].T, quant)
        prob = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return _mm(prob, vg[:, g], quant)  # [T, 2 dh]

    o = jax.lax.map(head, jnp.arange(hq))  # [2H, T, 2 dh]
    lam = (jnp.exp(jnp.dot(p["lam_q1"], p["lam_k1"]))
           - jnp.exp(jnp.dot(p["lam_q2"], p["lam_k2"])) + lam0)
    a = o[0::2] - lam * o[1::2]  # [H, T, 2 dh]
    a = a / jnp.sqrt((a * a).mean(-1, keepdims=True) + 1e-5) * p["subln"]
    a = a * (1.0 - lam0)
    return a.transpose(1, 0, 2).reshape(T, -1)


@functools.partial(jax.jit, static_argnames=("kind", "hq", "hkv", "window",
                                              "quant"))
def _layer(x, mem, kv, blk, lam0, kind, hq, hkv, window, quant):
    """One layer -> (x, the memory M, the full layer's (k, v)). `lam0`
    is the layer's lambda_init, an operand and not a constant, so that
    the layers of one kind share one compiled program."""
    blk = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), blk)
    p = blk["mixer"]
    T, d = x.shape
    dh = d // hq
    h = _ln(x, blk["ln1"])
    if kind == "mamba":
        o, y = _mamba(h, p, quant)
        mem = y  # the last Mamba layer's is the one the GMUs read
    elif kind == "gmu":
        o = _mm(mem * jax.nn.silu(_mm(h, p["w1"], quant)), p["w2"], quant)
    else:
        if kind == "cross":
            q = (_mm(h, p["wq"], quant) + p["bq"]).reshape(T, hq, dh)
            k, v = kv
        else:
            qkv = _mm(h, p["wqkv"], quant) + p["bqkv"]
            q = qkv[:, :hq * dh].reshape(T, hq, dh)
            k = qkv[:, hq * dh:(hq + hkv) * dh].reshape(T, hkv, dh)
            v = qkv[:, (hq + hkv) * dh:].reshape(T, hkv, dh)
            if kind == "full":
                kv = (k, v)
        a = _diff_attention(q, k, v, p, lam0,
                            window if kind == "window" else 0, quant)
        o = _mm(a, p["wo"], quant) + p["bo"]
    x = x + o
    h = _ln(x, blk["ln2"])
    gu = _mm(h, blk["w_gu"], quant)
    m = gu.shape[-1] // 2
    return (x + _mm(jax.nn.silu(gu[:, :m]) * gu[:, m:], blk["w_down"], quant),
            mem, kv)


def hidden(params, tokens, shape, quant=None):
    """tokens [T] -> the final LayerNorm's float32 output [T, d], layer
    by layer so that only one layer's float32 weights are live."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["embed"][tokens].astype(jnp.float32)
    z = dims(shape)
    mem = jnp.zeros((x.shape[0], z["di"]))
    kv = (jnp.zeros((x.shape[0], z["hkv"], z["dh"])),) * 2
    for l, (blk, kind) in enumerate(zip(params["blocks"],
                                        layer_kinds(shape["layers"]))):
        x, mem, kv = _layer(x, mem, kv, blk, jnp.float32(lambda_init(l)),
                            kind, z["hq"], z["hkv"], int(shape["window"]),
                            quant)
    ln_f = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                  params["ln_f"])
    return _ln(x, ln_f)


@functools.partial(jax.jit, static_argnames=("quant",))
def _head(x, embed, quant):
    return _mm(x, embed.astype(jnp.float32).T, quant)


def logits(params, tokens, shape, quant=None):
    """tokens [T] -> float32 logits [T, vocab] (small shapes: the
    comparison below never holds all of it)."""
    return _head(hidden(params, tokens, shape, quant), params["embed"], quant)


@functools.partial(jax.jit, static_argnames=("quant",))
def _gap_rows(x, xq, embed, picked, quant):
    """Rows of hidden states -> how far the logit of `picked` (or, with
    `xq`, of what the control's logits put first) lies below the
    reference's best."""
    ref = _mm(x, embed.astype(jnp.float32).T, None)
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
                      jnp.asarray(picked[sl]), control)
        gaps.append(np.asarray(g, np.float64)[lo - a:hi - a])
    gaps = np.concatenate(gaps)
    return {"max": float(gaps.max()), "sum": float(gaps.sum()), "n": n1,
            "flips": int((gaps > 0).sum())}
