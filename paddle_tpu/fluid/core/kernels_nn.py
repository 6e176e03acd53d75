"""NN op kernels: conv/pool/norm, activations, losses, dropout, metrics.

Parity targets: reference operators/conv_op.*, pool_op.*, batch_norm_op.*,
layer_norm_op.*, softmax/cross_entropy family, dropout_op, accuracy/top_k,
lrn_op — all expressed on NCHW layouts like the reference API, lowered to
`lax.conv_general_dilated` / `lax.reduce_window` so XLA tiles them onto the
MXU directly (no im2col: that is a GPU-ism the TPU backend does not need).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .registry import register_op


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v), int(v))


def _triple(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v),) * 3


@register_op("conv2d")
def _conv2d(ctx, ins, attrs):
    x = ins["Input"][0]  # NCHW
    w = ins["Filter"][0]  # OIHW
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dil = _pair(attrs.get("dilations", [1, 1]))
    groups = int(attrs.get("groups", 1) or 1)
    out = lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dil,
        feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    return {"Output": out}


@register_op("depthwise_conv2d")
def _depthwise_conv2d(ctx, ins, attrs):
    attrs = dict(attrs)
    attrs["groups"] = ins["Input"][0].shape[1]
    return _conv2d(ctx, ins, attrs)


@register_op("conv2d_transpose")
def _conv2d_transpose(ctx, ins, attrs):
    x = ins["Input"][0]  # NCHW
    w = ins["Filter"][0]  # IOHW in reference conv2d_transpose
    if int(attrs.get("groups", 1) or 1) != 1:
        # reference conv_transpose_op.cc:101 enforces groups == 1
        raise NotImplementedError("conv2d_transpose requires groups == 1")
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dil = _pair(attrs.get("dilations", [1, 1]))
    # Paddle's conv2d_transpose == conv2d's input-gradient (IOHW filter):
    # dilate the input by `stride`, pad by d*(k-1)-p, run a stride-1 conv
    # with the spatially-flipped, channel-swapped kernel. Output size is
    # (i-1)*s - 2p + d*(k-1) + 1, matching conv2d_transpose_op.cc.
    w = jnp.swapaxes(w, 0, 1)[:, :, ::-1, ::-1]  # IOHW -> OIHW, flipped
    kh = dil[0] * (w.shape[2] - 1)
    kw = dil[1] * (w.shape[3] - 1)
    out = lax.conv_general_dilated(
        x,
        w,
        window_strides=(1, 1),
        padding=[(kh - pads[0], kh - pads[0]), (kw - pads[1], kw - pads[1])],
        lhs_dilation=strides,
        rhs_dilation=dil,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    return {"Output": out}


@register_op("conv3d")
def _conv3d(ctx, ins, attrs):
    x = ins["Input"][0]  # NCDHW
    w = ins["Filter"][0]  # OIDHW
    strides = _triple(attrs.get("strides", [1, 1, 1]))
    pads = _triple(attrs.get("paddings", [0, 0, 0]))
    dil = _triple(attrs.get("dilations", [1, 1, 1]))
    out = lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=[(p, p) for p in pads],
        rhs_dilation=dil,
        feature_group_count=int(attrs.get("groups", 1) or 1),
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
    )
    return {"Output": out}


def _pool(x, pooling_type, ksize, strides, pads, global_pooling, ceil_mode=False,
          exclusive=True, nd=2):
    if global_pooling:
        ksize = x.shape[-nd:]
        pads = (0,) * nd
    window = (1, 1) + tuple(ksize)
    stride = (1, 1) + tuple(strides)
    padding = ((0, 0), (0, 0)) + tuple((p, p) for p in pads)
    if ceil_mode:
        # extend the upper pad so the last partial window is kept
        padding = list(padding)
        for i in range(nd):
            size = x.shape[2 + i] + 2 * pads[i]
            rem = (size - ksize[i]) % strides[i]
            extra = (strides[i] - rem) % strides[i] if rem else 0
            padding[2 + i] = (pads[i], pads[i] + extra)
        padding = tuple(padding)
    if pooling_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, init, lax.max, window, stride, padding)
    # avg pooling: exclusive counts only un-padded elements per window
    summed = lax.reduce_window(x.astype(jnp.float32), 0.0, lax.add, window, stride, padding)
    if exclusive and any(p[0] or p[1] for p in padding):
        ones = jnp.ones(x.shape, jnp.float32)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, stride, padding)
        out = summed / counts
    else:
        out = summed / float(np.prod(ksize))
    return out.astype(x.dtype)


@register_op("pool2d")
def _pool2d(ctx, ins, attrs):
    x = ins["X"][0]
    out = _pool(
        x,
        attrs.get("pooling_type", "max"),
        _pair(attrs.get("ksize", [1, 1])),
        _pair(attrs.get("strides", [1, 1])),
        _pair(attrs.get("paddings", [0, 0])),
        attrs.get("global_pooling", False),
        attrs.get("ceil_mode", False),
        attrs.get("exclusive", True),
        nd=2,
    )
    return {"Out": out}


@register_op("pool3d")
def _pool3d(ctx, ins, attrs):
    x = ins["X"][0]
    out = _pool(
        x,
        attrs.get("pooling_type", "max"),
        _triple(attrs.get("ksize", [1, 1, 1])),
        _triple(attrs.get("strides", [1, 1, 1])),
        _triple(attrs.get("paddings", [0, 0, 0])),
        attrs.get("global_pooling", False),
        attrs.get("ceil_mode", False),
        attrs.get("exclusive", True),
        nd=3,
    )
    return {"Out": out}


@register_op("batch_norm")
def _batch_norm(ctx, ins, attrs):
    """Reference operators/batch_norm_op.cc: NCHW, per-channel affine,
    running stats updated in train mode with `momentum` EMA."""
    x = ins["X"][0]
    scale = ins["Scale"][0]
    bias = ins["Bias"][0]
    mean_in = ins["Mean"][0]
    var_in = ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False) or ctx.is_test
    layout = attrs.get("data_layout", "NCHW")
    c_axis = 1 if layout == "NCHW" else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != c_axis)
    bshape = [1] * x.ndim
    bshape[c_axis] = x.shape[c_axis]

    # statistics ALWAYS accumulate in f32 (a bf16 E[x^2]-E[x]^2 loses
    # mass catastrophically); the convert fuses into the reduce so the
    # HBM read stays bf16. Only the per-channel apply runs in x.dtype.
    f32 = jnp.float32
    if is_test:
        mean = mean_in.astype(f32)
        var = var_in.astype(f32)
        mean_out, var_out = mean_in, var_in
        saved_mean = mean
        saved_var = 1.0 / jnp.sqrt(var + eps)
    else:
        xs = x.astype(f32)
        mean = jnp.mean(xs, axis=axes)
        var = jnp.mean(jnp.square(xs), axis=axes) - jnp.square(mean)
        mean_out = mean_in.astype(f32) * momentum + mean * (1.0 - momentum)
        var_out = var_in.astype(f32) * momentum + var * (1.0 - momentum)
        saved_mean = mean
        saved_var = 1.0 / jnp.sqrt(var + eps)
    # running-stat EMA must not leak gradients into scale/bias updates
    mean = lax.stop_gradient(mean) if is_test else mean
    inv = 1.0 / jnp.sqrt(var + eps)
    # fold (mean, inv, scale, bias) into ONE per-channel multiply-add in
    # x's dtype — tiny vectors, so the f32->bf16 cast costs nothing and
    # the big activation tensor never leaves bf16
    eff_scale = (inv * scale.astype(f32)).astype(x.dtype)
    eff_bias = (
        bias.astype(f32) - mean * inv * scale.astype(f32)
    ).astype(x.dtype)
    y = x * eff_scale.reshape(bshape) + eff_bias.reshape(bshape)
    return {
        "Y": y,
        "MeanOut": lax.stop_gradient(mean_out),
        "VarianceOut": lax.stop_gradient(var_out),
        "SavedMean": saved_mean,
        "SavedVariance": saved_var,
    }


@register_op("layer_norm")
def _layer_norm(ctx, ins, attrs):
    x = ins["X"][0]
    eps = attrs.get("epsilon", 1e-5)
    begin = attrs.get("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    # statistics in f32 (see batch_norm); apply in x.dtype
    xs = x.astype(jnp.float32)
    mean = jnp.mean(xs, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xs - mean), axis=axes, keepdims=True)
    y = ((xs - mean) / jnp.sqrt(var + eps)).astype(x.dtype)
    if ins.get("Scale"):
        y = y * ins["Scale"][0].reshape((1,) * begin + x.shape[begin:])
    if ins.get("Bias"):
        y = y + ins["Bias"][0].reshape((1,) * begin + x.shape[begin:])
    return {"Y": y, "Mean": mean.reshape(x.shape[:begin]), "Variance": var.reshape(x.shape[:begin])}


@register_op("lrn")
def _lrn(ctx, ins, attrs):
    x = ins["X"][0]  # NCHW
    n = attrs.get("n", 5)
    k = attrs.get("k", 2.0)
    alpha = attrs.get("alpha", 1e-4)
    beta = attrs.get("beta", 0.75)
    # accumulate the cross-channel sum of squares in f32 (bf16-safe)
    sq = jnp.square(x.astype(jnp.float32))
    half = n // 2
    acc = lax.reduce_window(
        sq, 0.0, lax.add, (1, n, 1, 1), (1, 1, 1, 1), ((0, 0), (half, n - 1 - half), (0, 0), (0, 0))
    )
    mid = k + alpha * acc
    return {"Out": x * jnp.power(mid, -beta).astype(x.dtype), "MidOut": mid}


# --- activations --------------------------------------------------------

def _act(fn):
    def kern(ctx, ins, attrs):
        return {"Out": fn(ins["X"][0])}

    return kern


register_op("relu")(_act(jax.nn.relu))
register_op("sigmoid")(_act(jax.nn.sigmoid))
register_op("tanh")(_act(jnp.tanh))
register_op("softsign")(_act(jax.nn.soft_sign))
register_op("softplus")(_act(jax.nn.softplus))
register_op("relu6")(_act(lambda x: jnp.clip(x, 0.0, 6.0)))
register_op("gelu")(_act(jax.nn.gelu))
register_op("elu")(_act(jax.nn.elu))
register_op("silu")(_act(jax.nn.silu))
register_op("logsigmoid")(_act(jax.nn.log_sigmoid))
register_op("tanh_shrink")(_act(lambda x: x - jnp.tanh(x)))
register_op("softshrink")(
    lambda ctx, ins, attrs: {
        "Out": jnp.sign(ins["X"][0])
        * jnp.maximum(jnp.abs(ins["X"][0]) - attrs.get("lambda", 0.5), 0.0)
    }
)
register_op("hard_shrink")(
    lambda ctx, ins, attrs: {
        "Out": jnp.where(
            jnp.abs(ins["X"][0]) > attrs.get("threshold", 0.5), ins["X"][0], 0.0
        )
    }
)
register_op("thresholded_relu")(
    lambda ctx, ins, attrs: {
        "Out": jnp.where(ins["X"][0] > attrs.get("threshold", 1.0), ins["X"][0], 0.0)
    }
)
register_op("hard_sigmoid")(
    lambda ctx, ins, attrs: {
        "Out": jnp.clip(
            ins["X"][0] * attrs.get("slope", 0.2) + attrs.get("offset", 0.5), 0.0, 1.0
        )
    }
)
register_op("leaky_relu")(
    lambda ctx, ins, attrs: {
        "Out": jax.nn.leaky_relu(ins["X"][0], attrs.get("alpha", 0.02))
    }
)
register_op("brelu")(
    lambda ctx, ins, attrs: {
        "Out": jnp.clip(ins["X"][0], attrs.get("t_min", 0.0), attrs.get("t_max", 24.0))
    }
)
register_op("stanh")(
    lambda ctx, ins, attrs: {
        "Out": attrs.get("scale_b", 1.7159)
        * jnp.tanh(ins["X"][0] * attrs.get("scale_a", 2.0 / 3.0))
    }
)
register_op("swish")(
    lambda ctx, ins, attrs: {
        "Out": ins["X"][0] * jax.nn.sigmoid(attrs.get("beta", 1.0) * ins["X"][0])
    }
)


@register_op("prelu")
def _prelu(ctx, ins, attrs):
    x = ins["X"][0]
    alpha = ins["Alpha"][0]
    if alpha.size > 1 and x.ndim >= 2:
        if alpha.size == int(np.prod(x.shape[1:])):
            # element mode: one alpha per element of a sample
            alpha = alpha.reshape((1,) + tuple(x.shape[1:]))
        else:  # channel mode: one alpha per channel (axis 1)
            alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    return {"Out": jnp.where(x > 0, x, alpha * x)}


@register_op("softmax")
def _softmax(ctx, ins, attrs):
    return {"Out": jax.nn.softmax(ins["X"][0], axis=-1)}


@register_op("log_softmax")
def _log_softmax(ctx, ins, attrs):
    return {"Out": jax.nn.log_softmax(ins["X"][0], axis=-1)}


# --- losses -------------------------------------------------------------

@register_op("cross_entropy")
def _cross_entropy(ctx, ins, attrs):
    """Reference operators/cross_entropy_op.cc: hard labels are int64 [N,1],
    soft labels are a distribution with X's shape."""
    x = ins["X"][0]
    label = ins["Label"][0]
    eps = 1e-8
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        lbl = label.reshape(label.shape[0]).astype(jnp.int32)
        picked = jnp.take_along_axis(x, lbl[:, None], axis=-1)
        loss = -jnp.log(picked + eps)
    return {"Y": loss}


@register_op("softmax_with_cross_entropy")
def _softmax_with_cross_entropy(ctx, ins, attrs):
    logits = ins["Logits"][0]
    label = ins["Label"][0]
    logp = jax.nn.log_softmax(logits, axis=-1)
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
    else:
        lbl = label.reshape(label.shape[0]).astype(jnp.int32)
        loss = -jnp.take_along_axis(logp, lbl[:, None], axis=-1)
    return {"Softmax": jnp.exp(logp), "Loss": loss}


@register_op("sigmoid_cross_entropy_with_logits")
def _sigmoid_ce(ctx, ins, attrs):
    x = ins["X"][0]
    label = ins["Label"][0]
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    return {"Out": loss}


@register_op("hinge_loss")
def _hinge_loss(ctx, ins, attrs):
    logits = ins["Logits"][0]
    labels = ins["Labels"][0].astype(logits.dtype)
    return {"Loss": jnp.maximum(0.0, 1.0 - (2.0 * labels - 1.0) * logits)}


@register_op("huber_loss")
def _huber_loss(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    delta = attrs.get("delta", 1.0)
    r = y - x
    absr = jnp.abs(r)
    loss = jnp.where(absr <= delta, 0.5 * r * r, delta * (absr - 0.5 * delta))
    return {"Out": loss, "Residual": r}


@register_op("log_loss")
def _log_loss(ctx, ins, attrs):
    p = ins["Predicted"][0]
    l = ins["Labels"][0]
    eps = attrs.get("epsilon", 1e-4)
    return {"Loss": -l * jnp.log(p + eps) - (1 - l) * jnp.log(1 - p + eps)}


@register_op("smooth_l1_loss")
def _smooth_l1(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    diff = x - y
    if ins.get("InsideWeight"):
        diff = diff * ins["InsideWeight"][0]
    a = jnp.abs(diff)
    val = jnp.where(a < 1.0 / s2, 0.5 * s2 * diff * diff, a - 0.5 / s2)
    if ins.get("OutsideWeight"):
        val = val * ins["OutsideWeight"][0]
    out = jnp.sum(val.reshape(val.shape[0], -1), axis=1, keepdims=True)
    return {"Out": out, "Diff": diff}


@register_op("margin_rank_loss")
def _margin_rank_loss(ctx, ins, attrs):
    x1, x2 = ins["X1"][0], ins["X2"][0]
    label = ins["Label"][0]
    margin = attrs.get("margin", 0.0)
    act = jnp.maximum(0.0, -label * (x1 - x2) + margin)
    return {"Out": act, "Activated": (act > 0).astype(x1.dtype)}


@register_op("rank_loss")
def _rank_loss(ctx, ins, attrs):
    label = ins["Label"][0]
    left, right = ins["Left"][0], ins["Right"][0]
    d = left - right
    return {"Out": jnp.log1p(jnp.exp(d)) - label * d}


# --- dropout / noise ----------------------------------------------------

@register_op("dropout")
def _dropout(ctx, ins, attrs):
    x = ins["X"][0]
    p = attrs.get("dropout_prob", 0.5)
    if attrs.get("is_test", False) or ctx.is_test:
        # reference downscales at inference (dropout_op.cc upscale_in_train=False default)
        return {"Out": x * (1.0 - p), "Mask": jnp.ones_like(x)}
    key = ctx.next_key()
    mask = jax.random.bernoulli(key, 1.0 - p, x.shape).astype(x.dtype)
    return {"Out": x * mask, "Mask": mask}


@register_op("gaussian_random_noise")
def _gaussian_noise(ctx, ins, attrs):
    x = ins["X"][0]
    key = ctx.next_key()
    return {"Out": x + jax.random.normal(key, x.shape, x.dtype) * attrs.get("std", 1.0)}


# --- metrics ------------------------------------------------------------

@register_op("top_k")
def _top_k(ctx, ins, attrs):
    x = ins["X"][0]
    k = attrs.get("k", 1)
    vals, idx = lax.top_k(x, k)
    return {"Out": vals, "Indices": idx.astype(jnp.int32)}


@register_op("accuracy")
def _accuracy(ctx, ins, attrs):
    indices = ins["Indices"][0]
    label = ins["Label"][0]
    lbl = label.reshape(label.shape[0], 1).astype(indices.dtype)
    correct = jnp.any(indices == lbl, axis=1)
    num_correct = jnp.sum(correct.astype(jnp.int32))
    total = jnp.asarray(label.shape[0], jnp.int32)
    acc = num_correct.astype(jnp.float32) / total.astype(jnp.float32)
    return {
        "Accuracy": acc.reshape((1,)),
        "Correct": num_correct.reshape((1,)),
        "Total": total.reshape((1,)),
    }


@register_op("auc")
def _auc(ctx, ins, attrs):
    """Batch-local AUC by threshold bucketing (reference auc_op.cc uses the
    trapezoidal rule over score thresholds)."""
    pred = ins["Out"][0]
    label = ins["Label"][0].reshape(-1)
    score = pred[:, 1] if pred.ndim == 2 and pred.shape[1] == 2 else pred.reshape(-1)
    num_thresholds = attrs.get("num_thresholds", 200)
    thresholds = jnp.linspace(0.0, 1.0, num_thresholds)
    pos = (label > 0).astype(jnp.float32)
    neg = 1.0 - pos
    above = score[None, :] >= thresholds[:, None]
    tp = jnp.sum(above * pos[None, :], axis=1)
    fp = jnp.sum(above * neg[None, :], axis=1)
    tpr = tp / jnp.maximum(jnp.sum(pos), 1.0)
    fpr = fp / jnp.maximum(jnp.sum(neg), 1.0)
    auc = -jnp.trapezoid(tpr, fpr)
    return {"AUC": auc.reshape((1,))}


@register_op("flash_attention")
def _flash_attention(ctx, ins, attrs):
    """Fused blockwise attention on [B, T, H, D] (pallas kernel,
    parallel/flash_attention.py; interpret mode on CPU). The fluid
    surface's door to the hot kernel: the compute runs through the same
    custom-vjp flash path the transformer flagship uses."""
    from ...parallel.flash_attention import flash_attention as _flash
    from ...parallel.kernel_utils import resolve_interpret

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    out = _flash(
        q, k, v,
        causal=bool(attrs.get("causal", False)),
        scale=attrs.get("scale") or None,
        interpret=resolve_interpret(None),
    )
    return {"Out": out}


# --- r4 op-tail: pooling-with-index / unpool / spp / conv3d_transpose ---


def _pool_with_index(x, ksize, strides, pads, global_pooling, nd):
    """Max pooling that also returns the argmax's flat index within the
    UNPADDED input plane (reference math/pooling.cc
    MaxPool2dWithIndexFunctor: index = h * input_w + w; windows are
    clipped to the input, so a padding position can never win). Static
    shapes throughout: windows are materialised as a gather (XLA folds
    it), argmax ties break on the first element in window scan order —
    the same (h, w[, d]) order the reference loop visits."""
    spatial = x.shape[2:]
    if global_pooling:
        ksize = spatial
        pads = (0,) * nd
    neg = jnp.finfo(x.dtype).min if jnp.issubdtype(x.dtype, jnp.floating) \
        else jnp.iinfo(x.dtype).min
    pad_cfg = ((0, 0), (0, 0)) + tuple((p, p) for p in pads)
    xp = jnp.pad(x, pad_cfg, constant_values=neg)
    out_dims = [
        (spatial[i] + 2 * pads[i] - ksize[i]) // strides[i] + 1
        for i in range(nd)
    ]
    # per-axis window index grids: idx[i] has shape [out_i, k_i]
    grids = [
        np.arange(out_dims[i])[:, None] * strides[i] + np.arange(ksize[i])
        for i in range(nd)
    ]
    # broadcast to [N, C, out..., k...]: axis layout (o1..on, k1..kn)
    ix = []
    for i in range(nd):
        shape = [1] * (2 * nd)
        shape[i] = out_dims[i]
        shape[nd + i] = ksize[i]
        ix.append(grids[i].reshape(shape))
    windows = xp[(slice(None), slice(None)) + tuple(ix)]
    # -> [N, C, o..., kprod]
    kprod = int(np.prod(ksize))
    windows = windows.reshape(windows.shape[: 2 + nd] + (kprod,))
    arg = jnp.argmax(windows, axis=-1)
    out = jnp.take_along_axis(windows, arg[..., None], axis=-1)[..., 0]
    # flat index in the unpadded plane: per window element, its padded
    # coordinate minus pad, row-majored over the input spatial dims
    coord = np.zeros((int(np.prod(out_dims)), kprod), np.int32)
    flat_mult = np.cumprod((spatial[1:] + (1,))[::-1])[::-1]  # row-major
    o_grid = np.meshgrid(*[np.arange(o) for o in out_dims], indexing="ij")
    k_grid = np.meshgrid(*[np.arange(k) for k in ksize], indexing="ij")
    for i in range(nd):
        c = (
            o_grid[i].reshape(-1, 1) * strides[i]
            + k_grid[i].reshape(1, -1)
            - pads[i]
        )
        coord += c.astype(np.int32) * int(flat_mult[i])
    coord = jnp.asarray(coord.reshape(tuple(out_dims) + (kprod,)))
    mask = jnp.take_along_axis(
        jnp.broadcast_to(coord, arg.shape + (kprod,)), arg[..., None],
        axis=-1,
    )[..., 0]
    return out, mask


@register_op("max_pool2d_with_index")
def _max_pool2d_with_index(ctx, ins, attrs):
    """Reference operators/pool_with_index_op.cc (2-D)."""
    out, mask = _pool_with_index(
        ins["X"][0],
        _pair(attrs.get("ksize", [1, 1])),
        _pair(attrs.get("strides", [1, 1])),
        _pair(attrs.get("paddings", [0, 0])),
        attrs.get("global_pooling", False),
        nd=2,
    )
    return {"Out": out, "Mask": mask}


@register_op("max_pool3d_with_index")
def _max_pool3d_with_index(ctx, ins, attrs):
    """Reference operators/pool_with_index_op.cc (3-D, NCDHW)."""
    out, mask = _pool_with_index(
        ins["X"][0],
        _triple(attrs.get("ksize", [1, 1, 1])),
        _triple(attrs.get("strides", [1, 1, 1])),
        _triple(attrs.get("paddings", [0, 0, 0])),
        attrs.get("global_pooling", False),
        nd=3,
    )
    return {"Out": out, "Mask": mask}


@register_op("unpool")
def _unpool(ctx, ins, attrs):
    """Max unpooling (reference operators/unpool_op.cc +
    math/unpooling.cc): scatter each input element to the output-plane
    position its Indices entry names; everything else is zero. Output
    size = (in-1)*stride - 2*pad + ksize per spatial dim."""
    x = ins["X"][0]  # [N, C, H, W]
    idx = ins["Indices"][0].astype(jnp.int32)
    ksize = _pair(attrs.get("ksize", [1, 1]))
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    n, c, h, w = x.shape
    oh = (h - 1) * strides[0] - 2 * pads[0] + ksize[0]
    ow = (w - 1) * strides[1] - 2 * pads[1] + ksize[1]
    flat = jnp.zeros((n, c, oh * ow), x.dtype)
    bi = jnp.arange(n).reshape(n, 1, 1)
    ci = jnp.arange(c).reshape(1, c, 1)
    out = flat.at[bi, ci, idx.reshape(n, c, -1)].set(
        x.reshape(n, c, -1), mode="drop"
    )
    return {"Out": out.reshape(n, c, oh, ow)}


@register_op("spp")
def _spp(ctx, ins, attrs):
    """Spatial pyramid pooling (reference operators/spp_op.cc): levels
    p = 0..H-1 pool to 2^p x 2^p bins (ksize = ceil(in/bins), stride =
    ksize, pad centers the grid), flatten and concatenate along
    channels*bins^2."""
    x = ins["X"][0]
    height = int(attrs.get("pyramid_height", 1))
    ptype = attrs.get("pooling_type", "max")
    n, c, h, w = x.shape
    parts = []
    for p in range(height):
        bins = 2 ** p
        kh = -(-h // bins)
        kw = -(-w // bins)
        ph = (kh * bins - h + 1) // 2
        pw = (kw * bins - w + 1) // 2
        lvl = _pool(
            x, ptype, (kh, kw), (kh, kw), (ph, pw),
            global_pooling=False, exclusive=True,
        )
        parts.append(lvl.reshape(n, c * bins * bins))
    return {"Out": jnp.concatenate(parts, axis=1)}


@register_op("conv3d_transpose")
def _conv3d_transpose(ctx, ins, attrs):
    """Reference operators/conv_transpose_op.cc (3-D): conv3d's
    input-gradient with an IODHW filter — dilate the input by stride and
    run a stride-1 conv with the flipped, channel-swapped kernel. Output
    size = (i-1)*s - 2p + d*(k-1) + 1 per spatial dim."""
    x = ins["Input"][0]  # NCDHW
    w = ins["Filter"][0]  # IODHW
    if int(attrs.get("groups", 1) or 1) != 1:
        # reference conv_transpose_op.cc:101 enforces groups == 1
        raise NotImplementedError("conv3d_transpose requires groups == 1")
    strides = _triple(attrs.get("strides", [1, 1, 1]))
    pads = _triple(attrs.get("paddings", [0, 0, 0]))
    dil = _triple(attrs.get("dilations", [1, 1, 1]))
    w = jnp.swapaxes(w, 0, 1)[:, :, ::-1, ::-1, ::-1]
    ks = [dil[i] * (w.shape[2 + i] - 1) for i in range(3)]
    out = lax.conv_general_dilated(
        x,
        w,
        window_strides=(1, 1, 1),
        padding=[(ks[i] - pads[i], ks[i] - pads[i]) for i in range(3)],
        lhs_dilation=strides,
        rhs_dilation=dil,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
    )
    return {"Output": out}


@register_op("norm")
def _norm(ctx, ins, attrs):
    """SSD-style cross-channel L2 normalisation with learned per-channel
    scale (reference operators/norm_op.h): out[n,c,h,w] =
    x / sqrt(eps + sum_c x^2) * scale[c]."""
    x = ins["X"][0]
    scale = ins["Scale"][0].reshape(-1)
    eps = attrs.get("epsilon", 1e-10)
    denom = jnp.sqrt(eps + jnp.sum(
        jnp.square(x.astype(jnp.float32)), axis=1, keepdims=True
    ))
    out = (x / denom) * scale.reshape(1, -1, *([1] * (x.ndim - 2)))
    return {"Out": out.astype(x.dtype)}


@register_op("bilinear_tensor_product")
def _bilinear_tensor_product(ctx, ins, attrs):
    """out[b,k] = x[b,:] @ W[k] @ y[b,:] + bias[k] (reference
    operators/bilinear_tensor_product_op.h)."""
    x, y = ins["X"][0], ins["Y"][0]
    w = ins["Weight"][0]  # [size, M, N]
    out = jnp.einsum("bm,kmn,bn->bk", x, w, y)
    if ins.get("Bias") and ins["Bias"][0] is not None:
        out = out + ins["Bias"][0].reshape(1, -1)
    return {"Out": out}


@register_op("modified_huber_loss")
def _modified_huber_loss(ctx, ins, attrs):
    """Reference operators/modified_huber_loss_op.h: a = x * (2y - 1);
    loss = -4a for a < -1, (1-a)^2 for a < 1, else 0. Y in {0, 1}."""
    x = ins["X"][0]
    y = ins["Y"][0].astype(x.dtype)
    a = x * (2.0 * y - 1.0)
    loss = jnp.where(
        a < -1.0, -4.0 * a,
        jnp.where(a < 1.0, jnp.square(1.0 - a), jnp.zeros_like(a)),
    )
    return {"IntermediateVal": a, "Out": loss}


@register_op("soft_relu")
def _soft_relu(ctx, ins, attrs):
    """out = log(1 + exp(clip(x, -t, t))) (reference activation_op.h
    SoftReluFunctor). The clip is straight-through for the gradient:
    the reference backward is dx = dout * (1 - exp(-out)) = sigmoid of
    the CLIPPED input everywhere — a plain jnp.clip would instead kill
    the gradient outside [-t, t]."""
    x = ins["X"][0]
    t = attrs.get("threshold", 40.0)
    xc = x + lax.stop_gradient(jnp.clip(x, -t, t) - x)
    return {"Out": jnp.log1p(jnp.exp(xc))}
