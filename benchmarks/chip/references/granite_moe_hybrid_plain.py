"""Plain reference of the Mamba-2 / grouped-query hybrid with routed
experts the cell `granitehsmall_reason_closed` serves
(granite-4.0-h-small, `model_type` granitemoehybrid, 72 experts top-10
beside a shared MLP).

Straightforward jax.numpy in float32 with every matrix product at the
highest precision: the mixers are `granite_hybrid_plain.py`'s own (a
`lax.scan` over time for the Mamba-2 recurrence, full-matrix causal
attention one query head at a time), imported from that file; the
routed experts one expert at a time over ALL rows, the rows that did
not choose it weighted 0 (no sorting, no grouping, no capacity), by
this file's own top-k and softmax; no cache, no kernels, no batching.
It imports nothing of the program; the weights are made here, from the
seed, and upcast a layer — the routed experts an expert — at a time.

`shape` is the configuration's "shape" group: vocab, dim, heads,
kv_heads, head_dim, layers, layer_types, mlp_width, n_experts, top_k,
expert_width, experts_held [lo, hi], mamba_heads, mamba_head_dim,
d_state, d_conv, and the four published constants embedding_multiplier,
residual_multiplier, attention_multiplier, logits_scaling (eps 1e-5).

  x0 = embedding_multiplier * E[token]
  every layer: x += residual_multiplier * Mixer(RMSNorm(x))
               u = RMSNorm(x)
               x += residual_multiplier * (MoE(u) + Shared(u))
  logits = RMSNorm(x) E^T / logits_scaling   (tied embedding)

  MoE(u)     l = u W_r (W_r [dim, n_experts], no bias); S = the top_k
             largest l; w = softmax(l_S) over those k alone;
             sum_{e in S, e held} w_e Expert_e(u)
  Expert_e   W_down(silu(g) * up), [g | up] = u W_gu: `expert_width`
  Shared     the same SwiGLU, `mlp_width` wide

This chip's share: `experts_held` names the routed experts whose
weights are here; the router scores all `n_experts` and what the others
would add is left out, as in the program. The vocabulary is the slice
the configuration holds: `vocab` rows of the tied embedding.

Departures from the published modeling code
(`modeling_granitemoehybrid.py`, transformers 4.57):
  * the router's product is float32 from the float32 normed row; the
    published code rounds u W_r in the model's dtype (bf16) before
    `.float()`, so near-ties of the 10th and 11th logit may choose
    otherwise there; the top-k and the softmax are float32 in both;
  * the whole forward is float32 (the program's weights are bf16,
    upcast here); RMSNorm multiplies its gain in float32, where the
    published norm rounds to the input dtype first;
  * every matrix product at "highest" precision, the recurrence
    sequential: both are what a reference is for.

Controls (`reference_pass(control=...)`, `hidden(fault=...)`): "int8" is
the same forward with both operands of every matrix product — the
router's among them — rounded to 8-bit integers (absmax scale per row
of the contraction), the nearest precision below the bf16 that the
configuration states. The planted faults are what an implementation
could get wrong and still run: "bf16_state" rounds the recurrent state
to bfloat16 after every token, "sigmoid_router" weights the chosen
experts by their sigmoids normalised over the k (DeepSeek's scoring),
"softmax_all" by a softmax over all n_experts logits (not renormalised
over the k), "no_shared_expert" drops the shared MLP.
"""

from __future__ import annotations

import functools
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np


def _sibling(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location("chipref_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_dense = _sibling("granite_hybrid_plain")
HIGHEST, EPS, seed_key = _dense.HIGHEST, _dense.EPS, _dense.seed_key
_mm, _rms, _attention = _dense._mm, _dense._rms, _dense._attention
FAULTS = ("bf16_state", "sigmoid_router", "softmax_all", "no_shared_expert")


def dims(shape):
    lo, hi = shape.get("experts_held") or (0, shape["n_experts"])
    H, P = shape["mamba_heads"], shape["mamba_head_dim"]
    return {"d": shape["dim"], "m": shape["mlp_width"],
            "me": shape["expert_width"], "E": shape["n_experts"],
            "k": shape["top_k"], "lo": int(lo), "hi": int(hi),
            "di": H * P, "H": H, "P": P, "N": shape["d_state"],
            "K": shape["d_conv"], "hq": shape["heads"],
            "hkv": shape["kv_heads"], "dh": shape["head_dim"]}


def weight_shapes(shape, max_len=None):
    """The parameter tree the served entry takes, as shapes."""
    z = dims(shape)
    d, m, me, di, N, H = z["d"], z["m"], z["me"], z["di"], z["N"], z["H"]
    Eh = z["hi"] - z["lo"]
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
         "w_gu": (d, 2 * m), "w_down": (m, d), "router": (d, z["E"]),
         "experts": {"w_gu": (Eh, d, 2 * me), "w_down": (Eh, me, d)}}
        for kind in shape["layer_types"]]}


def param_count(shape):
    return sum(math.prod(s) for s in jax.tree_util.tree_leaves(
        weight_shapes(shape), is_leaf=lambda x: isinstance(x, tuple)))


@functools.partial(jax.jit, static_argnames=("shp", "dtype"))
def _stacked(key, shp, dtype):
    """[E, rows, cols], an expert at a time: N(0, 1/rows) each."""
    return jax.lax.map(lambda k: _dense._leaf(k, shp[1:], "w", dtype),
                       jax.random.split(key, shp[0]))


def init_weights(shape, max_len, seed, dtype=jnp.bfloat16):
    """Random weights on the device, leaf by leaf, by the dense
    reference's initialisers (the tied embedding N(0, 1/vocab), matrices
    N(0, 1/rows), the router's among them, the Mamba-2 leaves by the
    published initialisers); a stacked expert leaf an expert at a time,
    so that no float32 copy of more than one expert's matrix is live
    beside the 9.5 GB they come to."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        weight_shapes(shape, max_len), is_leaf=lambda x: isinstance(x, tuple))
    key = seed_key(seed)
    dtype = jnp.dtype(dtype).name
    out = []
    for i, (path, shp) in enumerate(flat):
        k = jax.random.fold_in(key, i)
        if len(shp) == 3:
            out.append(_stacked(k, shp, dtype))
        else:
            out.append(_dense._leaf(k, shp, str(getattr(path[-1], "key",
                                                        "w")), dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _mamba2(h, p, H, P, N, K, quant, bf16_state, upto):
    """`granite_hybrid_plain._mamba2`, with the planted fault of a state
    rounded to bfloat16 after every token -> (the mixer's output, the
    state after the first `upto` rows, [N, H P] as the program's cache
    lays a slot's state out)."""
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

    def step(carry, xs):  # s [H, P, N]
        s, kept = carry
        t, dt_t, x_t, b_t, c_t = xs
        s = (jnp.exp(dt_t * A)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        if bf16_state:
            # not astype(bfloat16).astype(float32): XLA may drop such a
            # pair of converts as excess precision (the TPU compiler
            # does), and the fault would not be planted at all
            s = jax.lax.reduce_precision(s, exponent_bits=8,
                                         mantissa_bits=7)
        kept = jnp.where(t == upto - 1, s, kept)
        return (s, kept), (s * c_t[None, None, :]).sum(-1) + p["D"][:, None] * x_t

    zero = jnp.zeros((H, P, N))
    (_, kept), y = jax.lax.scan(step, (zero, zero),
                                (jnp.arange(T), dt, x, B, C))
    y = y.reshape(T, di) * jax.nn.silu(z)
    return (_mm(_rms(y, p["norm"]), p["out_proj"], quant),
            kept.reshape(di, N).T)


def _swiglu(u, w_gu, w_down, quant):
    gu = _mm(u, w_gu.astype(jnp.float32), quant)
    m = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[:, :m]) * gu[:, m:],
               w_down.astype(jnp.float32), quant)


def _ffn(u, blk, lo, hi, top_k, quant, fault):
    """The routed experts held here and the shared MLP over u [T, d]
    (float32), the leaves as they are stored: an expert's matrices are
    upcast when its turn comes."""
    f32 = jnp.float32
    logits = _mm(u, blk["router"].astype(f32), quant)  # [T, E]
    if fault == "softmax_all":
        w, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    else:
        top, chosen = jax.lax.top_k(logits, top_k)  # [T, k]
        if fault == "sigmoid_router":
            s = jax.nn.sigmoid(top)
            w = s / s.sum(-1, keepdims=True)
        else:
            w = jax.nn.softmax(top, axis=-1)

    def one(acc, xs):
        e, w_gu, w_down = xs
        mine = jnp.where(chosen == e, w, 0.0).sum(-1)  # [T]: 0 if not chosen
        return acc + mine[:, None] * _swiglu(u, w_gu, w_down, quant), None

    ex = blk["experts"]
    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (jnp.arange(lo, hi), ex["w_gu"], ex["w_down"]))
    if fault != "no_shared_expert":
        out = out + _swiglu(u, blk["w_gu"], blk["w_down"], quant)
    return out


@functools.partial(jax.jit, static_argnames=(
    "kind", "hq", "hkv", "dh", "H", "P", "N", "K", "lo", "hi", "top_k",
    "quant", "fault"))
def _layer(x, blk, scale, res, upto, kind, hq, hkv, dh, H, P, N, K, lo, hi,
           top_k, quant, fault):
    """One layer -> (x, a Mamba-2 layer's state after `upto` rows, or
    None). The multipliers and `upto` are operands, not constants, so
    that the layers of one kind share one compiled program."""
    f32 = jnp.float32
    p = jax.tree_util.tree_map(lambda a: a.astype(f32), blk["mixer"])
    T = x.shape[0]
    h = _rms(x, blk["norm1"].astype(f32))
    state = None
    if kind == "mamba":
        o, state = _mamba2(h, p, H, P, N, K, quant, fault == "bf16_state",
                           upto)
    else:
        qkv = _mm(h, p["wqkv"], quant)
        q = qkv[:, :hq * dh].reshape(T, hq, dh)
        k = qkv[:, hq * dh:(hq + hkv) * dh].reshape(T, hkv, dh)
        v = qkv[:, (hq + hkv) * dh:].reshape(T, hkv, dh)
        o = _mm(_attention(q, k, v, scale, quant), p["wo"], quant)
    x = x + res * o
    u = _rms(x, blk["norm2"].astype(f32))
    return x + res * _ffn(u, blk, lo, hi, top_k, quant, fault), state


def hidden_and_states(params, tokens, shape, upto, quant=None, fault=None):
    """tokens [T] -> (the final RMSNorm's float32 output [T, d], and
    for every Mamba-2 layer in order its state after the first `upto`
    tokens, float32 [N, H P]), layer by layer so that only one layer's
    float32 copies are live."""
    if fault is not None and fault not in FAULTS:
        raise ValueError("no planted fault %r (%s)" % (fault, FAULTS))
    tokens = jnp.asarray(tokens, jnp.int32)
    z = dims(shape)
    x = (params["embed"][tokens].astype(jnp.float32)
         * shape["embedding_multiplier"])
    states = []
    for blk, kind in zip(params["blocks"], shape["layer_types"]):
        x, s = _layer(x, blk, jnp.float32(shape["attention_multiplier"]),
                      jnp.float32(shape["residual_multiplier"]),
                      jnp.int32(upto), kind, z["hq"], z["hkv"], z["dh"],
                      z["H"], z["P"], z["N"], z["K"], z["lo"], z["hi"],
                      z["k"], quant, fault)
        if s is not None:
            states.append(s)
    return _rms(x, params["norm_f"].astype(jnp.float32)), states


def hidden(params, tokens, shape, quant=None, fault=None):
    """tokens [T] -> the final RMSNorm's float32 output [T, d]."""
    return hidden_and_states(params, tokens, shape, len(tokens), quant,
                             fault)[0]


def logits(params, tokens, shape, quant=None, fault=None):
    """tokens [T] -> float32 logits [T, vocab] (small shapes: the
    comparison below never holds all of it)."""
    return _dense._head(hidden(params, tokens, shape, quant, fault),
                        params["embed"], jnp.float32(shape["logits_scaling"]),
                        quant)


def _control(control):
    """A control's name -> (quant, fault) of `hidden_and_states`."""
    quant = control if control == "int8" else None
    fault = control if control in FAULTS else None
    if control is not None and quant is None and fault is None:
        raise ValueError("no control %r" % (control,))
    return quant, fault


def reference_pass(params, shape, prompt, served, pad_to, control=None):
    """The reference once over prompt + served tokens, padded on the
    right to `pad_to` (which a causal model ignores: a row's experts
    are its own), in float32 or as the `control` ("int8" or one of
    FAULTS) computes it -> (the final norm's rows [pad_to, d], every
    Mamba-2 layer's state after the last token a judged position reads,
    prompt + served less one)."""
    n0, n1 = len(prompt), len(served)
    seq = np.zeros(pad_to, np.int32)
    seq[:n0] = prompt
    seq[n0:n0 + n1] = served
    return hidden_and_states(params, seq, shape, n0 + n1 - 1,
                             *_control(control))


@jax.jit
def logit_err(got, x, embed, scaling):
    """Logits `got` [R, vocab] against the reference's of its rows x
    [R, d] -> per row, the norm of the difference over the norm of the
    reference's logits about their mean (their spread: a shift of every
    logit alike changes no choice)."""
    want = _mm(x, embed.astype(jnp.float32).T, None) / scaling
    c = want - want.mean(-1, keepdims=True)
    return jnp.sqrt(((got - want) ** 2).sum(-1) / (c * c).sum(-1))


@jax.jit
def pick_rank(logits, picked):
    """Logits [R, vocab] and the token picked after each row -> how
    many tokens' logits lie above the picked one's: 0 where it is the
    first, 1 where it is the second."""
    got = jnp.take_along_axis(logits, picked[:, None], axis=-1)
    return (logits > got).sum(-1)


@functools.partial(jax.jit, static_argnames=("quant",))
def _control_err(xq, x, embed, scaling, picked, quant):
    got = _dense._head(xq, embed, scaling, quant)
    return logit_err(got, x, embed, scaling), pick_rank(got, picked)


def judge(params, shape, prompt, served, x, xq=None, control=None,
          rows=512):
    """The judged positions of one request, n1 = len(served) of them:
    position p's logits picked the token at p + 1, from the prompt's
    last row to the last served token but one. x: the float32
    reference's rows (`reference_pass`); xq: the control's, which then
    stands where the program's served tokens would. The head runs over
    the judged positions only, `rows` at a time.
    -> {"gaps": float64 [n1], how far the judged token's logit lies
        below the reference's best (the served token, or the one the
        control's logits put first); with xq also "logit_err": float64
        [n1], `logit_err` of the control's logits, and "pick_rank":
        [n1], `pick_rank` of the served tokens in them}"""
    quant = _control(control)[0]
    n0, n1 = len(prompt), len(served)
    pad_to = x.shape[0]
    scaling = jnp.float32(shape["logits_scaling"])
    picked = np.zeros(pad_to, np.int32)
    picked[n0 - 1:n0 + n1 - 1] = served
    out = {"gaps": []}
    if xq is not None:
        out["logit_err"], out["pick_rank"] = [], []
    for lo in range(n0 - 1, n0 + n1 - 1, rows):
        hi = min(lo + rows, n0 + n1 - 1)
        # every slice is `rows` long (one compiled shape): the last one
        # starts early and its head is dropped
        a = max(0, min(lo, pad_to - rows))
        sl = slice(a, a + rows)
        g = _dense._gap_rows(x[sl], None if xq is None else xq[sl],
                             params["embed"], scaling,
                             jnp.asarray(picked[sl]), quant)
        out["gaps"].append(np.asarray(g, np.float64)[lo - a:hi - a])
        if xq is not None:
            e, g = _control_err(xq[sl], x[sl], params["embed"], scaling,
                                jnp.asarray(picked[sl]), quant)
            out["logit_err"].append(np.asarray(e, np.float64)[lo - a:hi - a])
            out["pick_rank"].append(np.asarray(g)[lo - a:hi - a])
    return {k: np.concatenate(v) for k, v in out.items()}


def state_err(got, want, head_dim):
    """A Mamba-2 layer's state, [N, H P] as the program's cache holds a
    slot's, against the reference's -> per head, the norm of the
    difference over the norm of the reference's (float64 [H])."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    d = ((got - want) ** 2).reshape(got.shape[0], -1, head_dim).sum((0, 2))
    w = (want ** 2).reshape(want.shape[0], -1, head_dim).sum((0, 2))
    return np.sqrt(d / w)
