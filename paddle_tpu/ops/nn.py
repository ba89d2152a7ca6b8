"""NN ops: conv, pool, norms, softmax, losses, embedding, dropout, top_k.

Parity targets: /root/reference/paddle/fluid/operators/conv_op.cc,
conv_transpose_op.cc, pool_op.cc, batch_norm_op.cc, layer_norm_op.cc,
group_norm_op.cc, softmax_op.cc, cross_entropy_op.cc,
softmax_with_cross_entropy_op.cc, sigmoid_cross_entropy_with_logits_op.cc,
lookup_table_op.cc, dropout_op.cc, top_k_op.cc, squared_l2_distance /
square_error_cost (layers), smooth_l1_loss_op.cc, huber_loss_op.cc,
log_loss_op.cc, lrn_op.cc.

Convs map straight onto lax.conv_general_dilated (the MXU path); XLA picks
TPU-friendly layouts internally so the public NCHW contract is free.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_grad_lowering, register_op
from .random_mask import keep_mask


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v), int(v))


# ------------------------------------------------------------------- conv
@register_op("conv2d", diff_inputs=["Input", "Filter"])
def _conv2d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dilations = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1)
    out = lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dilations,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups,
    )
    return {"Output": [out]}


@register_op("depthwise_conv2d", diff_inputs=["Input", "Filter"])
def _depthwise_conv2d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dilations = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", x.shape[1])
    out = lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dilations,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups,
    )
    return {"Output": [out]}


@register_op("conv2d_transpose", diff_inputs=["Input", "Filter"])
def _conv2d_transpose(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dilations = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1)
    # paddle stores the transpose-conv filter as (in, out/groups, kh, kw);
    # with transpose_kernel=True jax reads the declared-I slot as the
    # OUTPUT channels, so swap to (out/groups, in, kh, kw) first
    if groups != 1:
        raise NotImplementedError(
            "conv2d_transpose with groups > 1 is not supported yet")
    # jax only auto-transposes 'SAME'/'VALID' pads; explicit pairs apply
    # to the dilated conv directly, so the reference semantics
    # out = (in-1)*s + k_eff - 2p need pads of (k_eff - 1 - p)
    k_eff = [(w.shape[2 + i] - 1) * dilations[i] + 1 for i in range(2)]
    tp = [(k_eff[i] - 1 - pads[i], k_eff[i] - 1 - pads[i]) for i in range(2)]
    out = lax.conv_transpose(
        x,
        jnp.swapaxes(w, 0, 1),
        strides=strides,
        padding=tp,
        rhs_dilation=dilations,
        dimension_numbers=("NCHW", "IOHW", "NCHW"),
        transpose_kernel=True,
    )
    return {"Output": [out]}


@register_op("conv3d", diff_inputs=["Input", "Filter"])
def _conv3d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = tuple(attrs.get("strides", [1, 1, 1]))
    pads = tuple(attrs.get("paddings", [0, 0, 0]))
    dilations = tuple(attrs.get("dilations", [1, 1, 1]))
    out = lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=[(p, p) for p in pads],
        rhs_dilation=dilations,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        feature_group_count=attrs.get("groups", 1),
    )
    return {"Output": [out]}


# ------------------------------------------------------------------- pool
@register_op("pool2d", diff_inputs=["X"])
def _pool2d(ctx, ins, attrs):
    x = ins["X"][0]
    ptype = attrs.get("pooling_type", "max")
    ksize = _pair(attrs.get("ksize", [2, 2]))
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    if attrs.get("global_pooling", False):
        ksize = (x.shape[2], x.shape[3])
        strides = (1, 1)
        pads = (0, 0)
    window = (1, 1) + ksize
    strides4 = (1, 1) + strides
    padding = [(0, 0), (0, 0), (pads[0], pads[0]), (pads[1], pads[1])]
    if ptype == "max":
        if jnp.issubdtype(x.dtype, jnp.floating):
            init = -float("inf")  # scalar: keeps the differentiable max monoid
        else:
            # integer pools need a dtype-matched identity (weak int32 would
            # mismatch the operand dtype); 0-d concrete arrays still hit the
            # monoid special case
            init = jnp.array(jnp.iinfo(x.dtype).min, x.dtype)
        out = lax.reduce_window(x, init, lax.max, window, strides4, padding)
    else:
        s = lax.reduce_window(x, 0.0, lax.add, window, strides4, padding)
        if attrs.get("exclusive", True) and pads != (0, 0):
            ones = jnp.ones(x.shape, x.dtype)
            cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides4, padding)
            out = s / cnt
        else:
            out = s / (ksize[0] * ksize[1])
    return {"Out": [out]}


@register_op("pool2d_with_index", diff_inputs=["X"])
def _max_pool2d_with_index(ctx, ins, attrs):
    out = _pool2d(ctx, ins, {**attrs, "pooling_type": "max"})["Out"][0]
    return {"Out": [out], "Mask": [jnp.zeros(out.shape, jnp.int32)]}


# ------------------------------------------------------------------- norms
@register_op("batch_norm", diff_inputs=["X", "Scale", "Bias"])
def _batch_norm(ctx, ins, attrs):
    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean, var = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False)
    layout = attrs.get("data_layout", "NCHW")
    caxis = 1 if layout == "NCHW" else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != caxis)
    bshape = tuple(x.shape[caxis] if i == caxis else 1 for i in range(x.ndim))

    if is_test or attrs.get("use_global_stats", False):
        use_mean, use_var = mean, var
        mean_out, var_out = mean, var
        saved_mean = mean
        saved_var = var
    else:
        use_mean = jnp.mean(x, axis=axes)
        use_var = jnp.var(x, axis=axes)
        mean_out = mean * momentum + use_mean * (1 - momentum)
        var_out = var * momentum + use_var * (1 - momentum)
        saved_mean = use_mean
        saved_var = use_var
    inv = lax.rsqrt(use_var + eps)
    y = (x - use_mean.reshape(bshape)) * inv.reshape(bshape) * scale.reshape(
        bshape
    ) + bias.reshape(bshape)
    return {
        "Y": [y],
        "MeanOut": [mean_out],
        "VarianceOut": [var_out],
        "SavedMean": [saved_mean],
        "SavedVariance": [saved_var],
    }


@register_op("layer_norm", diff_inputs=["X", "Scale", "Bias"])
def _layer_norm(ctx, ins, attrs):
    x = ins["X"][0]
    scale = ins.get("Scale", [None])[0]
    bias = ins.get("Bias", [None])[0]
    eps = attrs.get("epsilon", 1e-5)
    begin = attrs.get("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps)
    bshape = (1,) * begin + x.shape[begin:]
    if scale is not None:
        y = y * scale.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    return {"Y": [y], "Mean": [mean.reshape(-1)], "Variance": [var.reshape(-1)]}


@register_op("rms_norm", diff_inputs=["X", "Scale"])
def _rms_norm(ctx, ins, attrs):
    """Root-mean-square norm (no mean centering, no shift) — the
    modern-decoder default (LLaMA-style). No reference counterpart
    (Fluid v1.3 predates RMSNorm); normalization in f32 regardless of
    the compute dtype so bf16 AMP keeps the rsqrt stable."""
    x = ins["X"][0]
    scale = ins.get("Scale", [None])[0]
    eps = attrs.get("epsilon", 1e-6)
    begin = attrs.get("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=axes, keepdims=True)
    y = (xf * lax.rsqrt(ms + eps)).astype(x.dtype)
    if scale is not None:
        bshape = (1,) * begin + x.shape[begin:]
        y = y * scale.reshape(bshape)
    return {"Y": [y]}


@register_op("group_norm", diff_inputs=["X", "Scale", "Bias"])
def _group_norm(ctx, ins, attrs):
    x = ins["X"][0]
    scale = ins.get("Scale", [None])[0]
    bias = ins.get("Bias", [None])[0]
    groups = attrs.get("groups", 1)
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape((n, groups, c // groups) + x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    y = ((xg - mean) * lax.rsqrt(var + eps)).reshape(x.shape)
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    return {"Y": [y], "Mean": [mean.reshape(n, groups)], "Variance": [var.reshape(n, groups)]}


@register_op("lrn", diff_inputs=["X"])
def _lrn(ctx, ins, attrs):
    x = ins["X"][0]
    n = attrs.get("n", 5)
    k = attrs.get("k", 2.0)
    alpha = attrs.get("alpha", 1e-4)
    beta = attrs.get("beta", 0.75)
    sq = x * x
    half = n // 2
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = sum(pad[:, i : i + x.shape[1]] for i in range(n))
    mid = k + alpha * acc
    return {"Out": [x / jnp.power(mid, beta)], "MidOut": [mid]}


# ------------------------------------------------------------------- softmax
@register_op("softmax", diff_inputs=["X"])
def _softmax(ctx, ins, attrs):
    axis = attrs.get("axis", -1)
    return {"Out": [jax.nn.softmax(ins["X"][0], axis=axis)]}


@register_op("log_softmax", diff_inputs=["X"])
def _log_softmax(ctx, ins, attrs):
    axis = attrs.get("axis", -1)
    return {"Out": [jax.nn.log_softmax(ins["X"][0], axis=axis)]}


@register_op("cross_entropy", diff_inputs=["X"])
def _cross_entropy(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    eps = 1e-8
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        lbl = label
        if lbl.ndim == x.ndim and lbl.shape[-1] == 1:
            lbl = jnp.squeeze(lbl, -1)
        picked = jnp.take_along_axis(x, lbl[..., None].astype(jnp.int32), axis=-1)
        loss = -jnp.log(picked + eps)
        ignore = attrs.get("ignore_index", -100)
        loss = jnp.where(lbl[..., None] == ignore, jnp.zeros_like(loss), loss)
    return {"Y": [loss]}


@register_op("softmax_with_cross_entropy", diff_inputs=["Logits"])
def _softmax_with_cross_entropy(ctx, ins, attrs):
    logits, label = ins["Logits"][0], ins["Label"][0]
    sm = jax.nn.softmax(logits, axis=-1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
    else:
        lbl = label
        if lbl.ndim == logits.ndim and lbl.shape[-1] == 1:
            lbl = jnp.squeeze(lbl, -1)
        loss = -jnp.take_along_axis(logp, lbl[..., None].astype(jnp.int32), axis=-1)
        ignore = attrs.get("ignore_index", -100)
        loss = jnp.where(lbl[..., None] == ignore, jnp.zeros_like(loss), loss)
    return {"Softmax": [sm], "Loss": [loss]}


@register_grad_lowering("softmax_with_cross_entropy")
def _softmax_with_cross_entropy_grad(ctx, ins, attrs):
    """Closed-form d_logits = dloss * (softmax - onehot(label)) — avoids
    re-tracing the forward (reference softmax_with_cross_entropy_op.cu)."""
    sm = ins["Softmax"][0]
    label = ins["Label"][0]
    dloss = ins["Loss@GRAD"][0]
    if attrs.get("soft_label", False):
        dlogits = (sm - label) * dloss
    else:
        lbl = label
        if lbl.ndim == sm.ndim and lbl.shape[-1] == 1:
            lbl = jnp.squeeze(lbl, -1)
        onehot = jax.nn.one_hot(lbl, sm.shape[-1], dtype=sm.dtype)
        dlogits = (sm - onehot) * dloss
    return {"Logits@GRAD": [dlogits]}


@register_op("sigmoid_cross_entropy_with_logits", diff_inputs=["X"])
def _sigmoid_xent(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore = attrs.get("ignore_index", -100)
    loss = jnp.where(label == ignore, jnp.zeros_like(loss), loss)
    if attrs.get("normalize", False):
        cnt = jnp.maximum(jnp.sum((label != ignore).astype(x.dtype)), 1.0)
        loss = loss / cnt
    return {"Out": [loss]}


@register_op("square_error_cost", diff_inputs=["X", "Y"])
def _square_error_cost(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    d = x - y
    return {"Out": [d * d]}


@register_op("smooth_l1_loss", diff_inputs=["X"])
def _smooth_l1(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    d = x - y
    ad = jnp.abs(d)
    diff = jnp.where(ad < 1.0 / s2, 0.5 * s2 * d * d, ad - 0.5 / s2)
    return {"Diff": [d], "Out": [jnp.sum(diff, axis=tuple(range(1, x.ndim)), keepdims=False).reshape(-1, 1)]}


@register_op("huber_loss", diff_inputs=["X", "Y"])
def _huber_loss(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    delta = attrs.get("delta", 1.0)
    d = y - x
    ad = jnp.abs(d)
    out = jnp.where(ad <= delta, 0.5 * d * d, delta * (ad - 0.5 * delta))
    return {"Out": [out], "Residual": [d]}


@register_op("log_loss", diff_inputs=["Predicted"])
def _log_loss(ctx, ins, attrs):
    p, label = ins["Predicted"][0], ins["Labels"][0]
    eps = attrs.get("epsilon", 1e-4)
    out = -label * jnp.log(p + eps) - (1 - label) * jnp.log(1 - p + eps)
    return {"Loss": [out]}


# ------------------------------------------------------------------- embedding
@register_op("lookup_table", diff_inputs=["W"])
def _lookup_table(ctx, ins, attrs):
    w, ids = ins["W"][0], ins["Ids"][0]
    ids = ids
    if ids.ndim >= 2 and ids.shape[-1] == 1:
        ids = jnp.squeeze(ids, -1)
    out = jnp.take(w, ids.astype(jnp.int32), axis=0)
    pad = attrs.get("padding_idx", -1)
    if pad is not None and pad != -1:
        mask = (ids != pad)[..., None]
        out = jnp.where(mask, out, jnp.zeros_like(out))
    return {"Out": [out]}


@register_op("lookup_table_v2", diff_inputs=["W"])
def _lookup_table_v2(ctx, ins, attrs):
    return _lookup_table(ctx, ins, attrs)


# ------------------------------------------------------------------- dropout
@register_op("dropout", diff_inputs=["X"], uses_rng=True)
def _dropout(ctx, ins, attrs):
    x = ins["X"][0]
    p = attrs.get("dropout_prob", 0.5)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if attrs.get("is_test", False) or ctx.is_test:
        if impl == "upscale_in_train":
            return {"Out": [x], "Mask": [jnp.ones_like(x)]}
        return {"Out": [x * (1.0 - p)], "Mask": [jnp.ones_like(x)]}
    seed = attrs.get("seed", 0)
    key = jax.random.PRNGKey(seed) if attrs.get("fix_seed", False) else ctx.next_rng()
    keep = keep_mask(ctx, key, 1.0 - p, x.shape, "dropout")
    if impl == "upscale_in_train":
        mask = keep.astype(x.dtype) / max(1.0 - p, 1e-8)
    else:
        mask = keep.astype(x.dtype)
    return {"Out": [x * mask], "Mask": [mask]}


@register_grad_lowering("dropout")
def _dropout_grad(ctx, ins, attrs):
    return {"X@GRAD": [ins["Out@GRAD"][0] * ins["Mask"][0]]}


# ------------------------------------------------------------------- top_k
@register_op("top_k", no_grad=True)
def _top_k(ctx, ins, attrs):
    x = ins["X"][0]
    k = attrs.get("k", 1)
    vals, idx = lax.top_k(x, k)
    return {"Out": [vals], "Indices": [idx.astype(jnp.int32)]}


@register_op("maxout", diff_inputs=["X"])
def _maxout(ctx, ins, attrs):
    x = ins["X"][0]
    groups = attrs["groups"]
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape((n, c // groups, groups) + x.shape[2:])
    return {"Out": [jnp.max(xg, axis=2)]}


@register_op("im2sequence", no_grad=True)
def _im2sequence(ctx, ins, attrs):  # rarely used; minimal static version
    raise NotImplementedError("im2sequence is not supported on the TPU build")


@register_op("label_smooth", diff_inputs=["X"])
def _label_smooth(ctx, ins, attrs):
    # reference operators/label_smooth_op.cc: (1-eps)*X + eps*prior (or 1/K)
    x = ins["X"][0]
    eps = attrs.get("epsilon", 0.0)
    dist = (ins.get("PriorDist") or [None])[0]
    if dist is not None:
        return {"Out": [(1.0 - eps) * x + eps * dist.reshape((1,) * (x.ndim - 1) + (-1,))]}
    return {"Out": [(1.0 - eps) * x + eps / x.shape[-1]]}
