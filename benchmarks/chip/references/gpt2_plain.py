"""Plain reference of the GPT-2 style decoder the LM cells serve.

Straightforward jax.numpy in float32 with every matrix product at the
highest precision: learned positions, pre-LayerNorm blocks (eps 1e-5),
full-matrix causal attention scaled by 1/sqrt(head), a 4x GELU MLP and
a weight-tied head. No kernels, no cache, no batching. It imports
nothing of the program; the weights are made here, from the seed.

Departures from Cerebras-GPT's published block, shared with the
program's block (models/transformer.py) so that the comparison is of
arithmetic and not of architecture: no biases on the linear layers,
and GELU in its tanh form (the published config says "gelu").

`quant="int8"` is the control: the same forward with both operands of
every matrix product rounded to 8-bit integers (absmax scale per row
of the contraction), the nearest precision below the bf16 that the
configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def weight_shapes(shape, max_len):
    """The parameter tree the served entry takes, as shapes."""
    d, m = shape["dim"], shape["mlp_mult"] * shape["dim"]
    ln = {"g": (d,), "b": (d,)}
    blk = {"ln1": ln, "wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
           "ln2": ln, "w1": (d, m), "w2": (m, d)}
    return {"embed": (shape["vocab"], d), "pos": (max_len, d),
            "blocks": [blk] * shape["layers"], "ln_f": ln}


def seed_key(seed):
    """A raw threefry key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                       jnp.uint32)


def init_weights(shape, max_len, seed, dtype=jnp.bfloat16):
    """Random weights on the device in one jitted call, in the type
    they are served in: matrices N(0, 1/dim); LayerNorm gains near 1
    and biases near 0 but not AT them, so that a dropped gain or bias
    shows. Leaves of one shape and kind are drawn as one stacked array
    and split, so the program stays a dozen operations long."""
    shapes = weight_shapes(shape, max_len)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    groups = {}
    for i, (path, shp) in enumerate(flat):
        kind = getattr(path[-1], "key", None)
        groups.setdefault((shp, kind if kind in ("g", "b") else "w"),
                          []).append(i)
    scale = 1.0 / math.sqrt(shape["dim"])

    def make(key):
        out = [None] * len(flat)
        for j, ((shp, kind), idx) in enumerate(sorted(groups.items())):
            a = jax.random.normal(jax.random.fold_in(key, j),
                                  (len(idx),) + shp, jnp.float32)
            a = {"g": 1.0 + 0.1 * a, "b": 0.1 * a, "w": scale * a}[kind]
            a = a.astype(dtype)
            for n, i in enumerate(idx):
                out[i] = a[n]
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(seed_key(seed))


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


@functools.partial(jax.jit, static_argnames=("heads", "quant"))
def _block(x, blk, heads, quant):
    blk = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), blk)
    T, d = x.shape
    dh = d // heads
    h = _ln(x, blk["ln1"])
    q = _mm(h, blk["wq"], quant).reshape(T, heads, dh).transpose(1, 0, 2)
    k = _mm(h, blk["wk"], quant).reshape(T, heads, dh).transpose(1, 0, 2)
    v = _mm(h, blk["wv"], quant).reshape(T, heads, dh).transpose(1, 0, 2)
    s = _mm(q / math.sqrt(dh), k.transpose(0, 2, 1), quant)
    mask = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    o = _mm(p, v, quant).transpose(1, 0, 2).reshape(T, d)
    x = x + _mm(o, blk["wo"], quant)
    h = _ln(x, blk["ln2"])
    return x + _mm(jax.nn.gelu(_mm(h, blk["w1"], quant), approximate=True),
                   blk["w2"], quant)


@jax.jit
def _embed(embed, pos, tokens):
    T = tokens.shape[0]
    return (embed[tokens].astype(jnp.float32)
            + pos[:T].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("quant",))
def _head(x, ln_f, embed, quant):
    ln_f = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), ln_f)
    return _mm(_ln(x, ln_f), embed.astype(jnp.float32).T, quant)


def logits(params, tokens, shape, quant=None):
    """tokens [T] -> float32 logits [T, vocab], layer by layer so that
    only one layer's float32 weights are live at a time."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = _embed(params["embed"], params["pos"], tokens)
    for blk in params["blocks"]:
        x = _block(x, blk, shape["heads"], quant)
    return _head(x, params["ln_f"], params["embed"], quant)


@jax.jit
def _gaps(ref, picked, lo, hi):
    """Per position p in [lo, hi): how far the logit of `picked[p]`
    lies below the reference's best at p; 0 outside the range."""
    best = ref.max(-1)
    got = jnp.take_along_axis(ref, picked[:, None], axis=-1)[:, 0]
    idx = jnp.arange(ref.shape[0])
    return jnp.where((idx >= lo) & (idx < hi), best - got, 0.0)


def served_gap(params, shape, prompt, served, pad_to, control=None):
    """How far each served token's logit lies below the reference's
    best, over one request: the reference runs once over prompt +
    served tokens (padded on the right to `pad_to`, which a causal
    model ignores). With `control`, the tokens judged are not the
    served ones but those the lower precision puts first at the same
    positions.
    -> {"max": widest gap, "sum": of all gaps, "n": positions compared,
        "flips": positions whose judged token is not the reference's first}"""
    n0, n1 = len(prompt), len(served)
    seq = np.zeros(pad_to, np.int32)
    seq[:n0] = prompt
    seq[n0:n0 + n1] = served
    ref = logits(params, seq, shape)
    if control is None:
        # the token at position p + 1 was picked from the logits at p
        picked = jnp.asarray(np.append(seq[1:], 0), jnp.int32)
    else:
        picked = jnp.argmax(logits(params, seq, shape, quant=control), -1)
    gaps = np.asarray(_gaps(ref, picked, n0 - 1, n0 + n1 - 1), np.float64)
    return {"max": float(gaps.max()), "sum": float(gaps.sum()), "n": n1,
            "flips": int((gaps > 0).sum())}
