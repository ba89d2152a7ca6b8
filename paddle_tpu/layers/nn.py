"""NN layer builders — the user-facing op-composition API.

Analog of /root/reference/python/paddle/fluid/layers/nn.py (157 defs listed
at nn.py:36). Each function appends ops to the default main program and
returns the output Variable(s); shapes are propagated eagerly (the
compile-time InferShape role, reference framework/shape_inference.h) so
later layers can size their parameters.
"""

from __future__ import annotations

from functools import reduce as _reduce
from operator import mul as _mul

from ..core.program import Variable, unique_name
from ..initializer import Constant, Normal, Xavier
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr
from .tensor import cast, concat, fill_constant  # re-exported via layers

__all__ = [
    "fc",
    "embedding",
    "label_smooth",
    "fused_attention",
    "dynamic_lstm",
    "dynamic_gru",
    "gru_unit",
    "similarity_focus",
    "tree_conv",
    "dynamic_lstmp",
    "lstm",
    "chunk_eval",
    "hash",
    "psroi_pool",
    "pool3d",
    "adaptive_pool3d",
    "conv3d_transpose",
    "ctc_greedy_decoder",
    "spectral_norm",
    "affine_grid",
    "grid_sampler",
    "sequence_scatter",
    "data_norm",
    "sampled_softmax_with_cross_entropy",
    "im2sequence",
    "selu",
    "multiplex",
    "space_to_depth",
    "shuffle_channel",
    "crop",
    "pad_constant_like",
    "dice_loss",
    "mean_iou",
    "add_position_encoding",
    "bilinear_tensor_product",
    "lstm_unit",
    "teacher_student_sigmoid_loss",
    "npair_loss",
    "gaussian_random_batch_size_like",
    "random_crop",
    "image_resize_short",
    "sequence_reshape",
    "lod_reset",
    "merge_selected_rows",
    "get_tensor_from_selected_rows",
    "autoincreased_step_counter",
    "sum",
    "conv2d",
    "conv2d_transpose",
    "conv3d",
    "pool2d",
    "adaptive_pool2d",
    "batch_norm",
    "layer_norm",
    "rms_norm",
    "group_norm",
    "dropout",
    "softmax",
    "log_softmax",
    "cross_entropy",
    "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits",
    "square_error_cost",
    "smooth_l1",
    "huber_loss",
    "log_loss",
    "matmul",
    "mul",
    "topk",
    "reshape",
    "squeeze",
    "unsqueeze",
    "transpose",
    "split",
    "stack",
    "unstack",
    "flatten",
    "expand",
    "gather",
    "gather_nd",
    "scatter",
    "pad",
    "pad2d",
    "slice",
    "strided_slice",
    "l2_normalize",
    "mean",
    "reduce_sum",
    "reduce_mean",
    "reduce_max",
    "reduce_min",
    "reduce_prod",
    "reduce_all",
    "reduce_any",
    "clip",
    "clip_by_norm",
    "scale",
    "one_hot",
    "prelu",
    "maxout",
    "lrn",
    "shape",
    "elementwise_add",
    "elementwise_sub",
    "elementwise_mul",
    "elementwise_div",
    "elementwise_max",
    "elementwise_min",
    "elementwise_pow",
    "elementwise_mod",
    "elementwise_floordiv",
    "equal",
    "not_equal",
    "less_than",
    "less_equal",
    "greater_than",
    "greater_equal",
    "logical_and",
    "logical_or",
    "logical_xor",
    "logical_not",
    "where",
    "cumsum",
    "sign",
    "cos_sim",
    "math_op",
    "uniform_random_batch_size_like",
    "gaussian_random",
    "sampling_id",
    "unbind",
]


def _prod(xs):
    return _reduce(_mul, xs, 1)


def _same_shape_out(helper, x, op_type, attrs=None, extra_inputs=None, dtype=None):
    out = helper.create_variable_for_type_inference(dtype or x.dtype)
    inputs = {"X": [x]}
    if extra_inputs:
        inputs.update(extra_inputs)
    helper.append_op(type=op_type, inputs=inputs, outputs={"Out": [out]}, attrs=attrs or {})
    out.shape = x.shape
    return out


# --------------------------------------------------------------------- fc
def fc(
    input,
    size,
    num_flatten_dims=1,
    param_attr=None,
    bias_attr=None,
    act=None,
    is_test=False,
    name=None,
):
    """Fully-connected (reference nn.py fc): mul + sum + bias + act."""
    helper = LayerHelper("fc", name=name, bias_attr=bias_attr, act=act)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    attrs = param_attr if isinstance(param_attr, (list, tuple)) else [param_attr] * len(inputs)
    mul_outs = []
    for x, pa in zip(inputs, attrs):
        in_dim = _prod(x.shape[num_flatten_dims:])
        w = helper.create_parameter(pa, [in_dim, size], x.dtype)
        out = helper.create_variable_for_type_inference(x.dtype)
        helper.append_op(
            type="mul",
            inputs={"X": [x], "Y": [w]},
            outputs={"Out": [out]},
            attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1},
        )
        out.shape = tuple(x.shape[:num_flatten_dims]) + (size,)
        mul_outs.append(out)
    if len(mul_outs) == 1:
        pre_bias = mul_outs[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(inputs[0].dtype)
        helper.append_op(type="sum", inputs={"X": mul_outs}, outputs={"Out": [pre_bias]})
        pre_bias.shape = mul_outs[0].shape
    pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims, size=size)
    return helper.append_activation(pre_act)


def embedding(
    input,
    size,
    is_sparse=False,
    is_distributed=False,
    padding_idx=None,
    param_attr=None,
    dtype="float32",
):
    """lookup_table (reference nn.py embedding / lookup_table_op.cc).
    is_sparse selects SelectedRows-style grads on the PS path; on the dense
    TPU path the scatter-add grad is already sparse-friendly under XLA."""
    helper = LayerHelper("embedding")
    w = helper.create_parameter(param_attr, size, dtype)
    # a row comes out in the dtype the table is STORED in (narrower than
    # asked under layer_helper.stored_dtype; the caller widens it)
    out = helper.create_variable_for_type_inference(
        w.dtype if w is not None else dtype)
    pad = -1 if padding_idx is None else (
        padding_idx if padding_idx >= 0 else size[0] + padding_idx
    )
    helper.append_op(
        type="lookup_table",
        inputs={"W": [w], "Ids": [input]},
        outputs={"Out": [out]},
        attrs={"is_sparse": is_sparse, "is_distributed": is_distributed,
               "padding_idx": pad},
    )
    ishape = input.shape or (-1,)
    if ishape and ishape[-1] == 1:
        ishape = ishape[:-1]
    out.shape = tuple(ishape) + (size[1],)
    return out


# --------------------------------------------------------------------- conv
def _pair(v):
    return tuple(v) if isinstance(v, (list, tuple)) else (v, v)


def _conv_dim(h, k, s, p, d=1):
    if h is None or h < 0:
        return -1
    return (h + 2 * p - (d * (k - 1) + 1)) // s + 1


def conv2d(
    input,
    num_filters,
    filter_size,
    stride=1,
    padding=0,
    dilation=1,
    groups=1,
    param_attr=None,
    bias_attr=None,
    use_cudnn=True,
    act=None,
    name=None,
):
    helper = LayerHelper("conv2d", name=name, bias_attr=bias_attr, act=act)
    k = _pair(filter_size)
    s = _pair(stride)
    p = _pair(padding)
    d = _pair(dilation)
    c = input.shape[1]
    filter_shape = [num_filters, c // groups, k[0], k[1]]
    std = (2.0 / (k[0] * k[1] * c)) ** 0.5
    w = helper.create_parameter(param_attr, filter_shape, input.dtype,
                                default_initializer=Normal(0.0, std))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="conv2d" if groups == 1 or groups != c else "depthwise_conv2d",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": list(s), "paddings": list(p), "dilations": list(d),
               "groups": groups},
    )
    n, _, h, wd = input.shape
    out.shape = (n, num_filters, _conv_dim(h, k[0], s[0], p[0], d[0]),
                 _conv_dim(wd, k[1], s[1], p[1], d[1]))
    pre_act = helper.append_bias_op(out, dim_start=1, size=num_filters)
    return helper.append_activation(pre_act)


def conv2d_transpose(
    input,
    num_filters,
    output_size=None,
    filter_size=None,
    padding=0,
    stride=1,
    dilation=1,
    groups=1,
    param_attr=None,
    bias_attr=None,
    use_cudnn=True,
    act=None,
    name=None,
):
    helper = LayerHelper("conv2d_transpose", name=name, bias_attr=bias_attr, act=act)
    k = _pair(filter_size)
    s = _pair(stride)
    p = _pair(padding)
    d = _pair(dilation)
    c = input.shape[1]
    w = helper.create_parameter(param_attr, [c, num_filters // groups, k[0], k[1]],
                                input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="conv2d_transpose",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": list(s), "paddings": list(p), "dilations": list(d),
               "groups": groups},
    )
    n, _, h, wd = input.shape

    def _tdim(x, kk, ss, pp, dd):
        if x is None or x < 0:
            return -1
        return (x - 1) * ss - 2 * pp + dd * (kk - 1) + 1

    out.shape = (n, num_filters, _tdim(h, k[0], s[0], p[0], d[0]),
                 _tdim(wd, k[1], s[1], p[1], d[1]))
    pre_act = helper.append_bias_op(out, dim_start=1, size=num_filters)
    return helper.append_activation(pre_act)


def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("conv3d", name=name, bias_attr=bias_attr, act=act)
    k = (filter_size,) * 3 if isinstance(filter_size, int) else tuple(filter_size)
    s = (stride,) * 3 if isinstance(stride, int) else tuple(stride)
    p = (padding,) * 3 if isinstance(padding, int) else tuple(padding)
    d = (dilation,) * 3 if isinstance(dilation, int) else tuple(dilation)
    c = input.shape[1]
    w = helper.create_parameter(param_attr, [num_filters, c // groups, *k], input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="conv3d", inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": list(s), "paddings": list(p), "dilations": list(d),
               "groups": groups},
    )
    n = input.shape[0]
    dims = [_conv_dim(x, kk, ss, pp, dd) for x, kk, ss, pp, dd in
            zip(input.shape[2:], k, s, p, d)]
    out.shape = (n, num_filters, *dims)
    pre_act = helper.append_bias_op(out, dim_start=1, size=num_filters)
    return helper.append_activation(pre_act)


def pool2d(
    input,
    pool_size=-1,
    pool_type="max",
    pool_stride=1,
    pool_padding=0,
    global_pooling=False,
    use_cudnn=True,
    ceil_mode=False,
    exclusive=True,
    name=None,
):
    helper = LayerHelper("pool2d", name=name)
    k = _pair(pool_size)
    s = _pair(pool_stride)
    p = _pair(pool_padding)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="pool2d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"pooling_type": pool_type, "ksize": list(k), "strides": list(s),
               "paddings": list(p), "global_pooling": global_pooling,
               "exclusive": exclusive, "ceil_mode": ceil_mode},
    )
    n, c, h, w = input.shape
    if global_pooling:
        out.shape = (n, c, 1, 1)
    else:
        out.shape = (n, c, _conv_dim(h, k[0], s[0], p[0]), _conv_dim(w, k[1], s[1], p[1]))
    return out


def adaptive_pool2d(input, pool_size, pool_type="max", name=None):
    n, c, h, w = input.shape
    oh, ow = _pair(pool_size)
    return pool2d(input, pool_size=(h // oh, w // ow), pool_type=pool_type,
                  pool_stride=(h // oh, w // ow), name=name)


# --------------------------------------------------------------------- norm
def batch_norm(
    input,
    act=None,
    is_test=False,
    momentum=0.9,
    epsilon=1e-5,
    param_attr=None,
    bias_attr=None,
    data_layout="NCHW",
    name=None,
    moving_mean_name=None,
    moving_variance_name=None,
    use_global_stats=False,
):
    helper = LayerHelper("batch_norm", name=name, act=act)
    c = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    scale = helper.create_parameter(param_attr, [c], input.dtype,
                                    default_initializer=Constant(1.0))
    bias = helper.create_parameter(bias_attr, [c], input.dtype, is_bias=True)
    mean = helper.create_global_variable(name=moving_mean_name, shape=[c],
                                         dtype=input.dtype, initializer=Constant(0.0))
    var = helper.create_global_variable(name=moving_variance_name, shape=[c],
                                        dtype=input.dtype, initializer=Constant(1.0))
    saved_mean = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    saved_var = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="batch_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                "Mean": [mean], "Variance": [var]},
        outputs={"Y": [out], "MeanOut": [mean], "VarianceOut": [var],
                 "SavedMean": [saved_mean], "SavedVariance": [saved_var]},
        attrs={"momentum": momentum, "epsilon": epsilon, "is_test": is_test,
               "data_layout": data_layout, "use_global_stats": use_global_stats},
    )
    out.shape = input.shape
    return helper.append_activation(out)


def layer_norm(
    input,
    scale=True,
    shift=True,
    begin_norm_axis=1,
    epsilon=1e-5,
    param_attr=None,
    bias_attr=None,
    act=None,
    name=None,
):
    helper = LayerHelper("layer_norm", name=name, act=act)
    norm_shape = [_prod(input.shape[begin_norm_axis:])]
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(param_attr, norm_shape, input.dtype,
                                    default_initializer=Constant(1.0))
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(bias_attr, norm_shape, input.dtype, is_bias=True)
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(input.dtype)
    m = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    v = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op(
        type="layer_norm", inputs=inputs,
        outputs={"Y": [out], "Mean": [m], "Variance": [v]},
        attrs={"epsilon": epsilon, "begin_norm_axis": begin_norm_axis},
    )
    out.shape = input.shape
    return helper.append_activation(out)


def rms_norm(input, begin_norm_axis=1, epsilon=1e-6, param_attr=None,
             name=None):
    """RMSNorm (scale only, f32 rsqrt): the modern-decoder norm; pair
    with rope/swiglu via models.gpt cfg norm='rms'."""
    helper = LayerHelper("rms_norm", name=name)
    norm_shape = [_prod(input.shape[begin_norm_axis:])]
    s = helper.create_parameter(param_attr, norm_shape, input.dtype,
                                default_initializer=Constant(1.0))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="rms_norm", inputs={"X": [input], "Scale": [s]},
        outputs={"Y": [out]},
        attrs={"epsilon": epsilon, "begin_norm_axis": begin_norm_axis},
    )
    out.shape = input.shape
    return out


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, name=None):
    helper = LayerHelper("group_norm", name=name, act=act)
    c = input.shape[1]
    inputs = {"X": [input]}
    if param_attr is not False:
        s = helper.create_parameter(param_attr, [c], input.dtype,
                                    default_initializer=Constant(1.0))
        inputs["Scale"] = [s]
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [c], input.dtype, is_bias=True)
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(input.dtype)
    m = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    v = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op(type="group_norm", inputs=inputs,
                     outputs={"Y": [out], "Mean": [m], "Variance": [v]},
                     attrs={"groups": groups, "epsilon": epsilon})
    out.shape = input.shape
    return helper.append_activation(out)


def l2_normalize(x, axis, epsilon=1e-10, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    norm = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op(type="norm", inputs={"X": [x]},
                     outputs={"Out": [out], "Norm": [norm]},
                     attrs={"axis": axis, "epsilon": epsilon})
    out.shape = x.shape
    return out


# --------------------------------------------------------------------- misc
def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    mask = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op(
        type="dropout", inputs={"X": [x]},
        outputs={"Out": [out], "Mask": [mask]},
        attrs={"dropout_prob": dropout_prob, "is_test": is_test,
               "fix_seed": seed is not None, "seed": seed or 0,
               "dropout_implementation": dropout_implementation},
    )
    out.shape = x.shape
    return out


def softmax(input, use_cudnn=False, name=None, axis=-1):
    helper = LayerHelper("softmax", name=name)
    return _same_shape_out(helper, input, "softmax", {"axis": axis})


def log_softmax(input, axis=-1, name=None):
    helper = LayerHelper("log_softmax", name=name)
    return _same_shape_out(helper, input, "log_softmax", {"axis": axis})


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="cross_entropy", inputs={"X": [input], "Label": [label]},
        outputs={"Y": [out]},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index},
    )
    if input.shape is not None:
        out.shape = tuple(input.shape[:-1]) + (1,)
    return out


def softmax_with_cross_entropy(
    logits, label, soft_label=False, ignore_index=-100,
    numeric_stable_mode=True, return_softmax=False,
):
    helper = LayerHelper("softmax_with_cross_entropy")
    sm = helper.create_variable_for_type_inference(logits.dtype)
    loss = helper.create_variable_for_type_inference(logits.dtype)
    helper.append_op(
        type="softmax_with_cross_entropy",
        inputs={"Logits": [logits], "Label": [label]},
        outputs={"Softmax": [sm], "Loss": [loss]},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index},
    )
    sm.shape = logits.shape
    loss.shape = tuple(logits.shape[:-1]) + (1,)
    if return_softmax:
        return loss, sm
    return loss


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100, name=None,
                                      normalize=False):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="sigmoid_cross_entropy_with_logits",
        inputs={"X": [x], "Label": [label]}, outputs={"Out": [out]},
        attrs={"ignore_index": ignore_index, "normalize": normalize},
    )
    out.shape = x.shape
    return out


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="square_error_cost",
                     inputs={"X": [input], "Y": [label]}, outputs={"Out": [out]})
    out.shape = input.shape
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1")
    diff = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="smooth_l1_loss", inputs={"X": [x], "Y": [y]},
                     outputs={"Diff": [diff], "Out": [out]},
                     attrs={"sigma": sigma or 1.0})
    out.shape = (x.shape[0], 1)
    return out


def huber_loss(input, label, delta):
    helper = LayerHelper("huber_loss")
    residual = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="huber_loss", inputs={"X": [input], "Y": [label]},
                     outputs={"Out": [out], "Residual": [residual]},
                     attrs={"delta": delta})
    out.shape = input.shape
    return out


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper("log_loss", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="log_loss",
                     inputs={"Predicted": [input], "Labels": [label]},
                     outputs={"Loss": [out]}, attrs={"epsilon": epsilon})
    out.shape = input.shape
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="matmul", inputs={"X": [x], "Y": [y]}, outputs={"Out": [out]},
        attrs={"transpose_X": transpose_x, "transpose_Y": transpose_y, "alpha": alpha},
    )
    if x.shape and y.shape:
        xs = list(x.shape)
        ys = list(y.shape)
        if transpose_x:
            xs[-1], xs[-2] = xs[-2], xs[-1]
        if transpose_y and len(ys) > 1:
            ys[-1], ys[-2] = ys[-2], ys[-1]
        batch = xs[:-2] if len(xs) >= len(ys) else ys[:-2]
        out.shape = tuple(batch + [xs[-2], ys[-1]]) if len(xs) > 1 else (ys[-1],)
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="mul", inputs={"X": [x], "Y": [y]}, outputs={"Out": [out]},
                     attrs={"x_num_col_dims": x_num_col_dims,
                            "y_num_col_dims": y_num_col_dims})
    out.shape = tuple(x.shape[:x_num_col_dims]) + tuple(y.shape[y_num_col_dims:])
    return out


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    vals = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    # the lowering emits int32 indices (ops/nn.py top_k; x64 is disabled
    # on device) — declaring int64 here was a latent annotation bug the
    # static verifier flags as dtype-annotation drift
    ids = helper.create_variable_for_type_inference("int32", stop_gradient=True)
    helper.append_op(type="top_k", inputs={"X": [input]},
                     outputs={"Out": [vals], "Indices": [ids]}, attrs={"k": k})
    if input.shape is not None:
        vals.shape = tuple(input.shape[:-1]) + (k,)
        ids.shape = vals.shape
    return vals, ids


# ----------------------------------------------------------------- reshape &c
def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape2", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op(type="reshape2", inputs={"X": [x]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"shape": list(shape)})
    if x.shape is not None:
        known = _prod([s for s in shape if s > 0])
        oshape = []
        for i, s in enumerate(shape):
            if s == 0:
                oshape.append(x.shape[i])
                known *= x.shape[i]
            else:
                oshape.append(s)
        if -1 in oshape and all(d >= 0 for d in x.shape):
            total = _prod(x.shape)
            oshape[oshape.index(-1)] = total // known
        out.shape = tuple(oshape)
    return helper.append_activation(out, act)


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze2", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    xshape = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op(type="squeeze2", inputs={"X": [input]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"axes": list(axes)})
    if input.shape is not None:
        ax = [a % len(input.shape) for a in axes]
        out.shape = tuple(s for i, s in enumerate(input.shape) if i not in ax or s != 1)
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze2", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    xshape = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op(type="unsqueeze2", inputs={"X": [input]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"axes": list(axes)})
    if input.shape is not None:
        s = list(input.shape)
        for a in sorted(axes):
            s.insert(a if a >= 0 else a + len(s) + 1, 1)
        out.shape = tuple(s)
    return out


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose2", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op(type="transpose2", inputs={"X": [x]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"axis": list(perm)})
    if x.shape is not None:
        out.shape = tuple(x.shape[p] for p in perm)
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    axis = dim % len(input.shape) if input.shape else dim
    if isinstance(num_or_sections, int):
        n = num_or_sections
        sections = []
        sizes = [input.shape[axis] // n] * n if input.shape else [None] * n
    else:
        sections = list(num_or_sections)
        n = len(sections)
        sizes = sections
    outs = [helper.create_variable_for_type_inference(input.dtype) for _ in range(n)]
    helper.append_op(
        type="split", inputs={"X": [input]}, outputs={"Out": outs},
        attrs={"axis": axis,
               "num": num_or_sections if isinstance(num_or_sections, int) else 0,
               "sections": sections},
    )
    for o, sz in zip(outs, sizes):
        if input.shape is not None:
            s = list(input.shape)
            s[axis] = sz
            o.shape = tuple(s)
    return outs


def stack(x, axis=0):
    helper = LayerHelper("stack")
    xs = x if isinstance(x, (list, tuple)) else [x]
    out = helper.create_variable_for_type_inference(xs[0].dtype)
    helper.append_op(type="stack", inputs={"X": xs}, outputs={"Y": [out]},
                     attrs={"axis": axis})
    if xs[0].shape is not None:
        s = list(xs[0].shape)
        s.insert(axis if axis >= 0 else axis + len(s) + 1, len(xs))
        out.shape = tuple(s)
    return out


def unstack(x, axis=0, num=None):
    helper = LayerHelper("unstack")
    n = num or x.shape[axis]
    outs = [helper.create_variable_for_type_inference(x.dtype) for _ in range(n)]
    helper.append_op(type="unstack", inputs={"X": [x]}, outputs={"Y": outs},
                     attrs={"axis": axis, "num": n})
    s = [d for i, d in enumerate(x.shape) if i != axis % len(x.shape)]
    for o in outs:
        o.shape = tuple(s)
    return outs


def unbind(input, axis=0):
    return unstack(input, axis)


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten2", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op(type="flatten2", inputs={"X": [x]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"axis": axis})
    if x.shape is not None:
        out.shape = (_prod(x.shape[:axis]) if axis else 1, _prod(x.shape[axis:]))
        if any(d < 0 for d in x.shape[:axis]):
            out.shape = (-1, _prod(x.shape[axis:]))
    return out


def expand(x, expand_times, name=None):
    helper = LayerHelper("expand", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="expand", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"expand_times": list(expand_times)})
    if x.shape is not None:
        out.shape = tuple(s * t if s >= 0 else -1 for s, t in zip(x.shape, expand_times))
    return out


def gather(input, index, overwrite=True):
    helper = LayerHelper("gather")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="gather", inputs={"X": [input], "Index": [index]},
                     outputs={"Out": [out]})
    if input.shape is not None and index.shape is not None:
        out.shape = (index.shape[0],) + tuple(input.shape[1:])
    return out


def gather_nd(input, index, name=None):
    helper = LayerHelper("gather_nd", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="gather_nd", inputs={"X": [input], "Index": [index]},
                     outputs={"Out": [out]})
    if input.shape is not None and index.shape is not None:
        out.shape = tuple(index.shape[:-1]) + tuple(input.shape[index.shape[-1]:])
    return out


def scatter(input, index, updates, overwrite=True, name=None):
    helper = LayerHelper("scatter", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="scatter",
        inputs={"X": [input], "Ids": [index], "Updates": [updates]},
        outputs={"Out": [out]}, attrs={"overwrite": overwrite},
    )
    out.shape = input.shape
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="pad", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"paddings": list(paddings), "pad_value": pad_value})
    if x.shape is not None:
        out.shape = tuple(
            (s + paddings[2 * i] + paddings[2 * i + 1]) if s >= 0 else -1
            for i, s in enumerate(x.shape)
        )
    return out


def pad2d(input, paddings=(0, 0, 0, 0), mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    helper = LayerHelper("pad2d", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="pad2d", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"paddings": list(paddings), "mode": mode,
                            "pad_value": pad_value})
    if input.shape is not None:
        n, c, h, w = input.shape
        out.shape = (n, c,
                     h + paddings[0] + paddings[1] if h >= 0 else -1,
                     w + paddings[2] + paddings[3] if w >= 0 else -1)
    return out


def slice(input, axes, starts, ends):
    helper = LayerHelper("slice")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="slice", inputs={"Input": [input]}, outputs={"Out": [out]},
                     attrs={"axes": list(axes), "starts": list(starts),
                            "ends": list(ends)})
    if input.shape is not None:
        s = list(input.shape)
        for a, st, e in zip(axes, starts, ends):
            dim = s[a]
            if dim < 0:
                continue
            st2 = max(st + dim, 0) if st < 0 else min(st, dim)
            e2 = max(e + dim, 0) if e < 0 else min(e, dim)
            s[a] = max(e2 - st2, 0)
        out.shape = tuple(s)
    return out


def strided_slice(input, axes, starts, ends, strides):
    helper = LayerHelper("strided_slice")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="strided_slice", inputs={"Input": [input]},
                     outputs={"Out": [out]},
                     attrs={"axes": list(axes), "starts": list(starts),
                            "ends": list(ends), "strides": list(strides)})
    return out


# --------------------------------------------------------------- reductions
def mean(x, name=None):
    helper = LayerHelper("mean", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="mean", inputs={"X": [x]}, outputs={"Out": [out]})
    out.shape = ()
    return out


def _reduce_layer(op_type, input, dim, keep_dim, name):
    helper = LayerHelper(op_type, name=name)
    dims = [dim] if isinstance(dim, int) else (list(dim) if dim is not None else None)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type=op_type, inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"dim": dims or [0], "keep_dim": keep_dim, "reduce_all": dims is None},
    )
    if input.shape is not None:
        if dims is None:
            out.shape = () if not keep_dim else (1,) * len(input.shape)
        else:
            nd = len(input.shape)
            ax = {d % nd for d in dims}
            out.shape = tuple(
                (1 if keep_dim else None) if i in ax else s
                for i, s in enumerate(input.shape)
            )
            out.shape = tuple(s for s in out.shape if s is not None)
    return out


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_sum", input, dim, keep_dim, name)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_mean", input, dim, keep_dim, name)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_max", input, dim, keep_dim, name)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_min", input, dim, keep_dim, name)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_prod", input, dim, keep_dim, name)


def reduce_all(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_all", input, dim, keep_dim, name)


def reduce_any(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_any", input, dim, keep_dim, name)


# ------------------------------------------------------------------- pointwise
def clip(x, min, max, name=None):
    helper = LayerHelper("clip", name=name)
    return _same_shape_out(helper, x, "clip", {"min": min, "max": max})


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm", name=name)
    return _same_shape_out(helper, x, "clip_by_norm", {"max_norm": max_norm})


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale", name=name, act=act)
    out = _same_shape_out(helper, x, "scale",
                          {"scale": scale, "bias": bias,
                           "bias_after_scale": bias_after_scale})
    return helper.append_activation(out)


def sign(x):
    helper = LayerHelper("sign")
    return _same_shape_out(helper, x, "sign")


def cumsum(x, axis=-1, exclusive=False, reverse=False):
    helper = LayerHelper("cumsum")
    return _same_shape_out(helper, x, "cumsum",
                           {"axis": axis, "exclusive": exclusive, "reverse": reverse})


def one_hot(input, depth):
    helper = LayerHelper("one_hot")
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op(type="one_hot", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"depth": depth})
    ishape = input.shape or (-1,)
    if ishape and ishape[-1] == 1:
        ishape = ishape[:-1]
    out.shape = tuple(ishape) + (depth,)
    return out


def prelu(x, mode, param_attr=None, name=None):
    helper = LayerHelper("prelu", name=name)
    if mode == "all":
        alpha_shape = [1]
    elif mode == "channel":
        alpha_shape = [x.shape[1]]
    else:
        alpha_shape = list(x.shape[1:])
    alpha = helper.create_parameter(param_attr, alpha_shape, x.dtype,
                                    default_initializer=Constant(0.25))
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="prelu", inputs={"X": [x], "Alpha": [alpha]},
                     outputs={"Out": [out]}, attrs={"mode": mode})
    out.shape = x.shape
    return out


def maxout(x, groups, name=None):
    helper = LayerHelper("maxout", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="maxout", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"groups": groups})
    if x.shape is not None:
        s = list(x.shape)
        s[1] //= groups
        out.shape = tuple(s)
    return out


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    mid = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op(type="lrn", inputs={"X": [input]},
                     outputs={"Out": [out], "MidOut": [mid]},
                     attrs={"n": n, "k": k, "alpha": alpha, "beta": beta})
    out.shape = input.shape
    return out


def shape(input):
    helper = LayerHelper("shape")
    out = helper.create_variable_for_type_inference("int32", stop_gradient=True)
    helper.append_op(type="shape", inputs={"Input": [input]}, outputs={"Out": [out]})
    out.shape = (len(input.shape),) if input.shape is not None else None
    return out


def cos_sim(X, Y):
    """cos_sim_op.cc analog (single lowering, not an l2_normalize
    composite, so the XNorm/YNorm byproducts match the reference op)."""
    helper = LayerHelper("cos_sim")
    out = helper.create_variable_for_type_inference(X.dtype)
    xn = helper.create_variable_for_type_inference(X.dtype)
    yn = helper.create_variable_for_type_inference(X.dtype)
    helper.append_op(type="cos_sim", inputs={"X": [X], "Y": [Y]},
                     outputs={"Out": [out], "XNorm": [xn], "YNorm": [yn]},
                     attrs={})
    return out


def where(condition, x, y, name=None):
    helper = LayerHelper("where", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="where_op",
                     inputs={"Condition": [condition], "X": [x], "Y": [y]},
                     outputs={"Out": [out]})
    out.shape = x.shape
    return out


# ------------------------------------------------------------- elementwise
def _elementwise(op_type, x, y, axis=-1, act=None, name=None):
    helper = LayerHelper(op_type, name=name, act=act)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type=op_type, inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]}, attrs={"axis": axis})
    out.shape = _broadcast_shape(x.shape, getattr(y, "shape", None))
    return helper.append_activation(out)


def _broadcast_shape(xs, ys):
    """numpy-style broadcast of two build-time shapes (-1 = unknown dim)."""
    if xs is None or ys is None:
        return xs if ys is None else (ys if xs is None else None)
    n = max(len(xs), len(ys))
    xs = (1,) * (n - len(xs)) + tuple(xs)
    ys = (1,) * (n - len(ys)) + tuple(ys)
    out = []
    for a, b in zip(xs, ys):
        if a == 1:
            out.append(b)
        elif b == 1 or a == b:
            out.append(a)
        elif a == -1 or b == -1:
            out.append(-1)
        else:
            out.append(max(a, b))
    return tuple(out)


def elementwise_add(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_add", x, y, axis, act, name)


def elementwise_sub(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_sub", x, y, axis, act, name)


def elementwise_mul(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_mul", x, y, axis, act, name)


def elementwise_div(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_div", x, y, axis, act, name)


def elementwise_max(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_max", x, y, axis, act, name)


def elementwise_min(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_min", x, y, axis, act, name)


def elementwise_pow(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_pow", x, y, axis, act, name)


def elementwise_mod(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_mod", x, y, axis, act, name)


def elementwise_floordiv(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_floordiv", x, y, axis, act, name)


def _compare(op_type, x, y, cond=None):
    helper = LayerHelper(op_type)
    if cond is None:
        cond = helper.create_variable_for_type_inference("bool", stop_gradient=True)
    helper.append_op(type=op_type, inputs={"X": [x], "Y": [y]}, outputs={"Out": [cond]})
    cond.shape = x.shape
    return cond


def less_than(x, y, cond=None):
    return _compare("less_than", x, y, cond)


def less_equal(x, y, cond=None):
    return _compare("less_equal", x, y, cond)


def greater_than(x, y, cond=None):
    return _compare("greater_than", x, y, cond)


def greater_equal(x, y, cond=None):
    return _compare("greater_equal", x, y, cond)


def equal(x, y, cond=None):
    return _compare("equal", x, y, cond)


def not_equal(x, y, cond=None):
    return _compare("not_equal", x, y, cond)


def _logical(op_type, x, y=None, out=None):
    helper = LayerHelper(op_type)
    if out is None:
        out = helper.create_variable_for_type_inference("bool", stop_gradient=True)
    inputs = {"X": [x]} if y is None else {"X": [x], "Y": [y]}
    helper.append_op(type=op_type, inputs=inputs, outputs={"Out": [out]})
    out.shape = x.shape
    return out


def logical_and(x, y, out=None, name=None):
    return _logical("logical_and", x, y, out)


def logical_or(x, y, out=None, name=None):
    return _logical("logical_or", x, y, out)


def logical_xor(x, y, out=None, name=None):
    return _logical("logical_xor", x, y, out)


def logical_not(x, out=None, name=None):
    return _logical("logical_not", x, None, out)


# ----------------------------------------------------------------- random
def uniform_random_batch_size_like(input, shape, dtype="float32", input_dim_idx=0,
                                   output_dim_idx=0, min=-1.0, max=1.0, seed=0):
    helper = LayerHelper("uniform_random_batch_size_like")
    out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op(
        type="uniform_random_batch_size_like", inputs={"Input": [input]},
        outputs={"Out": [out]},
        attrs={"shape": list(shape), "input_dim_idx": input_dim_idx,
               "output_dim_idx": output_dim_idx, "min": min, "max": max,
               "seed": seed, "dtype": dtype},
    )
    s = list(shape)
    s[output_dim_idx] = input.shape[input_dim_idx] if input.shape else -1
    out.shape = tuple(s)
    return out


def gaussian_random(shape, mean=0.0, std=1.0, seed=0, dtype="float32"):
    helper = LayerHelper("gaussian_random")
    out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op(type="gaussian_random", outputs={"Out": [out]},
                     attrs={"shape": list(shape), "mean": mean, "std": std,
                            "seed": seed, "dtype": dtype})
    out.shape = tuple(shape)
    return out


def sampling_id(x, min=0.0, max=1.0, seed=0, dtype="float32"):
    # sample an id from each row's categorical distribution
    helper = LayerHelper("sampling_id")
    out = argmax_of_gumbel = helper.create_variable_for_type_inference(
        "int64", stop_gradient=True
    )
    del argmax_of_gumbel
    helper.append_op(type="sampling_id", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"seed": seed})
    out.shape = (x.shape[0],)
    return out


# scalar/variable arithmetic used by Variable operator overloading
def math_op(x, other, op_type, reverse=False):
    if isinstance(other, Variable):
        a, b = (other, x) if reverse else (x, other)
        return _elementwise(op_type, a, b)
    val = float(other)
    if not reverse:
        if op_type == "elementwise_add":
            return scale(x, 1.0, val)
        if op_type == "elementwise_sub":
            return scale(x, 1.0, -val)
        if op_type == "elementwise_mul":
            return scale(x, val, 0.0)
        if op_type == "elementwise_div":
            return scale(x, 1.0 / val, 0.0)
    else:
        if op_type == "elementwise_add":
            return scale(x, 1.0, val)
        if op_type == "elementwise_sub":
            return scale(x, -1.0, val)
        if op_type == "elementwise_mul":
            return scale(x, val, 0.0)
    y = fill_constant([1], x.dtype, val)
    a, b = (y, x) if reverse else (x, y)
    if op_type in ("less_than", "less_equal", "greater_than", "greater_equal",
                   "equal", "not_equal"):
        return _compare(op_type, a, b)
    return _elementwise(op_type, a, b)


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32", name=None):
    """reference layers/nn.py label_smooth -> label_smooth_op.cc."""
    helper = LayerHelper("label_smooth", name=name)
    out = helper.create_variable_for_type_inference(dtype)
    inputs = {"X": [label]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist]
    helper.append_op(type="label_smooth", inputs=inputs, outputs={"Out": [out]},
                     attrs={"epsilon": float(epsilon)})
    out.shape = label.shape
    return out


def fused_attention(q, k, v, bias=None, scale=1.0, dropout=0.0,
                    causal=False, segment_ids=None, window=None, name=None,
                    mxu_dtype=None, flash_min_seq=None, n_head=None,
                    q_r=None, k_r=None):
    """Single-kernel scaled-dot-product attention over [B,H,S,D] tensors
    (Pallas flash kernel; see ops/attention.py). The reference composes
    this from matmul+softmax layer calls — SURVEY §5. ``causal=True``
    applies the lower-triangular mask in-kernel and SKIPS above-diagonal
    key blocks (~2x decoder-self-attention FLOPs at long S) — pass it
    instead of materializing a [S,S] causal bias.

    Rank-3 ``q``, ``k``, ``v`` are [B,S,H*D] with ``n_head`` heads, as
    three projections leave them, and ``Out`` comes back [B,S,H*D] for
    the output projection: the kernels then index the heads along the
    last axis themselves and no transpose stands on either side (the
    rank picks the layout; ``bias`` keeps [B|1,H|1,Sq|1,Sk]). A lowering
    that cannot take the kernel so (a short S's composed form, the ring)
    splits the heads inside and means the same. Of the forward-only
    arguments below rank 3 takes the shared key part alone (and with it
    ``mxu_dtype`` and ``flash_min_seq``).

    ``segment_ids`` ([B,S] int, 0 = padding — reader.pack_sequences
    layout) restricts attention to same-segment real keys for PACKED
    training WITHOUT materializing the [S,S] pack bias: single-device it
    folds to a mask once; under a sequence-parallel mesh the ids ride
    the ring and each pair builds its block mask from two [B,S/n] id
    vectors.

    ``window`` (an int, with ``causal``) keeps key j for query i iff
    ``0 <= i - j < window``: the kernel skips the key blocks wholly
    outside the band on both sides (device name ``flash_fwd_win``).
    ``k``/``v`` may hold fewer heads than ``q`` (grouped heads, no
    repeated copy) and ``v`` a last axis of its own, which the output
    takes. ``mxu_dtype`` (``"bfloat16"``) rounds float32 operands to it
    before the kernel, where XLA folds the convert into whatever wrote
    them: one MXU pass, the precision XLA's own float32 products have on
    the chip, on operands of half the bytes. ``flash_min_seq`` is this
    call's threshold for the kernel in place of the static default (the
    environment's ``PADDLE_TPU_FLASH_MIN_SEQ`` still wins).
    ``q_r`` [B,S,H*Dr] with ``k_r`` [B,Sk,Dr] (rank 3) is latent
    attention's expanded form read where its projections wrote it: a
    second part of every head's query and the ONE key part all heads
    share, a score ``q k^T + q_r k_r^T``; ``k`` and ``v`` are then one
    tensor [B,Sk,H*(D+Dv)] passed as both, head h's keys beside its
    values, and ``Out`` is [B,S,H*Dv]: no head's keys or values are
    built. All of these are forward-only (a serving prefill)."""
    if window is not None and (not causal or int(window) < 1):
        raise ValueError("fused_attention: window=%r needs causal=True and "
                         "window >= 1" % (window,))
    if len(q.shape) == 3 and (not n_head or q.shape[-1] % int(n_head)):
        raise ValueError("fused_attention: rank-3 q, k, v are [B,S,H*D] and "
                         "need n_head, a divisor of the last axis; got "
                         "n_head=%r for %r" % (n_head, tuple(q.shape)))
    if (q_r is None) != (k_r is None) or (k_r is not None and not (
            len(q.shape) == 3 and k is v)):
        raise ValueError("fused_attention: q_r and k_r come together, with "
                         "rank-3 q and ONE tensor as both k and v")
    helper = LayerHelper("fused_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    mask = helper.create_variable_for_type_inference(q.dtype)
    mask.stop_gradient = True
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if bias is not None:
        inputs["Bias"] = [bias]
    if segment_ids is not None:
        inputs["SegmentIds"] = [segment_ids]
    if k_r is not None:
        inputs["QR"], inputs["KR"] = [q_r], [k_r]
    helper.append_op(type="fused_attention", inputs=inputs,
                     outputs={"Out": [out], "Mask": [mask]},
                     # is_test: Program.clone(for_test=True) and the
                     # Predictor flip it, which turns the output dropout
                     # off at inference like the dropout op's
                     attrs=dict({"scale": float(scale),
                                 "dropout": float(dropout),
                                 "causal": bool(causal), "is_test": False},
                                **({"window": int(window)} if window
                                   else {}),
                                **({"mxu_dtype": str(mxu_dtype)}
                                   if mxu_dtype else {}),
                                **({"flash_min_seq": int(flash_min_seq)}
                                   if flash_min_seq else {}),
                                **({"n_head": int(n_head)}
                                   if len(q.shape) == 3 else {})))
    out.shape = tuple(q.shape[:-1]) + (
        (v.shape[-1] - q.shape[-1],) if k_r is not None
        else tuple(v.shape[-1:]))
    return out


# ----------------------------------------------------------------- recurrent
def dynamic_lstm(
    input,
    size,
    h_0=None,
    c_0=None,
    param_attr=None,
    bias_attr=None,
    use_peepholes=False,
    is_reverse=False,
    gate_activation="sigmoid",
    cell_activation="tanh",
    candidate_activation="tanh",
    dtype="float32",
    seq_len=None,
    name=None,
):
    """LSTM over a padded [B,S,4D] pre-projected batch (reference nn.py
    dynamic_lstm -> lstm_op.cc; input fc to 4*hidden done by the caller,
    same contract). LoD ragged input is replaced by the optional seq_len
    mask (SURVEY §5). use_peepholes is not supported on the TPU build."""
    if use_peepholes:
        raise NotImplementedError("peephole LSTM is not supported (TPU build)")
    helper = LayerHelper("lstm", name=name)
    hidden_size = size // 4
    w = helper.create_parameter(param_attr, [hidden_size, 4 * hidden_size], dtype)
    b = helper.create_parameter(bias_attr, [1, 4 * hidden_size], dtype, is_bias=True)
    hidden = helper.create_variable_for_type_inference(dtype)
    cell = helper.create_variable_for_type_inference(dtype)
    inputs = {"Input": [input], "Weight": [w], "Bias": [b]}
    if h_0 is not None:
        inputs["H0"] = [h_0]
    if c_0 is not None:
        inputs["C0"] = [c_0]
    if seq_len is not None:
        inputs["Length"] = [seq_len]
    helper.append_op(
        type="lstm", inputs=inputs,
        outputs={"Hidden": [hidden], "Cell": [cell]},
        attrs={"is_reverse": is_reverse, "gate_activation": gate_activation,
               "cell_activation": cell_activation,
               "candidate_activation": candidate_activation},
    )
    if input.shape is not None:
        out_shape = tuple(input.shape[:-1]) + (hidden_size,)
        hidden.shape = out_shape
        cell.shape = out_shape
    return hidden, cell


def gru_unit(input, hidden, size, param_attr=None, bias_attr=None,
             activation="tanh", gate_activation="sigmoid",
             origin_mode=False):
    """Single GRU step (reference nn.py:1042 / gru_unit_op.cc). `input`
    is the pre-projected [B, 3D] gates (size = 3*D), `hidden` [B, D].
    Returns (new_hidden, reset_hidden_prev, gate)."""
    helper = LayerHelper("gru_unit", bias_attr=bias_attr)
    D = size // 3
    w = helper.create_parameter(param_attr, [D, 3 * D], input.dtype)
    inputs = {"Input": [input], "HiddenPrev": [hidden], "Weight": [w]}
    b = helper.create_parameter(bias_attr, [1, 3 * D], input.dtype,
                                is_bias=True)
    if b is not None:
        inputs["Bias"] = [b]
    new_h = helper.create_variable_for_type_inference(input.dtype)
    reset_h = helper.create_variable_for_type_inference(input.dtype)
    gate = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="gru_unit", inputs=inputs,
        outputs={"Hidden": [new_h], "ResetHiddenPrev": [reset_h],
                 "Gate": [gate]},
        attrs={"activation": activation, "gate_activation": gate_activation,
               "origin_mode": origin_mode})
    new_h.shape = reset_h.shape = hidden.shape
    gate.shape = input.shape
    return new_h, reset_h, gate


def dynamic_gru(
    input,
    size,
    param_attr=None,
    bias_attr=None,
    is_reverse=False,
    gate_activation="sigmoid",
    candidate_activation="tanh",
    h_0=None,
    origin_mode=False,
    dtype="float32",
    seq_len=None,
    name=None,
):
    """GRU over a padded [B,S,3D] pre-projected batch (reference nn.py
    dynamic_gru -> gru_op.cc). size = hidden width D."""
    helper = LayerHelper("gru", name=name)
    w = helper.create_parameter(param_attr, [size, 3 * size], dtype)
    b = helper.create_parameter(bias_attr, [1, 3 * size], dtype, is_bias=True)
    hidden = helper.create_variable_for_type_inference(dtype)
    inputs = {"Input": [input], "Weight": [w], "Bias": [b]}
    if h_0 is not None:
        inputs["H0"] = [h_0]
    if seq_len is not None:
        inputs["Length"] = [seq_len]
    helper.append_op(
        type="gru", inputs=inputs, outputs={"Hidden": [hidden]},
        attrs={"is_reverse": is_reverse, "gate_activation": gate_activation,
               "activation": candidate_activation, "origin_mode": origin_mode},
    )
    if input.shape is not None:
        hidden.shape = tuple(input.shape[:-1]) + (size,)
    return hidden


# ------------------------------------------------------- misc tail (round 3)
def selu(x, scale=None, alpha=None, name=None):
    """reference nn.py selu."""
    helper = LayerHelper("selu", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    attrs = {}
    if scale is not None:
        attrs["scale"] = float(scale)
    if alpha is not None:
        attrs["alpha"] = float(alpha)
    helper.append_op(type="selu", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs=attrs)
    out.shape = x.shape
    return out


def multiplex(inputs, index):
    """reference nn.py multiplex: out[i] = inputs[index[i]][i]."""
    helper = LayerHelper("multiplex")
    out = helper.create_variable_for_type_inference(inputs[0].dtype)
    helper.append_op(type="multiplex",
                     inputs={"X": list(inputs), "Ids": [index]},
                     outputs={"Out": [out]})
    out.shape = inputs[0].shape
    return out


def space_to_depth(x, blocksize, name=None):
    helper = LayerHelper("space_to_depth", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="space_to_depth", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"blocksize": int(blocksize)})
    n, c, h, w = x.shape
    b = int(blocksize)
    out.shape = (n, c * b * b, h // b, w // b)
    return out


def shuffle_channel(x, group, name=None):
    helper = LayerHelper("shuffle_channel", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="shuffle_channel", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"group": int(group)})
    out.shape = x.shape
    return out


def crop(x, shape=None, offsets=None, name=None):
    """reference nn.py crop (static shape/offsets form)."""
    helper = LayerHelper("crop", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    shape = [int(s) for s in shape]
    offsets = [int(o) for o in (offsets or [0] * len(shape))]
    helper.append_op(type="crop", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"shape": shape, "offsets": offsets})
    out.shape = tuple(shape)
    return out


def pad_constant_like(x, y, pad_value=0.0, name=None):
    helper = LayerHelper("pad_constant_like", name=name)
    out = helper.create_variable_for_type_inference(y.dtype)
    helper.append_op(type="pad_constant_like",
                     inputs={"X": [x], "Y": [y]}, outputs={"Out": [out]},
                     attrs={"pad_value": float(pad_value)})
    out.shape = x.shape
    return out


def dice_loss(input, label, epsilon=1e-5):
    """reference nn.py dice_loss (input: probs [..., C], label ints)."""
    helper = LayerHelper("dice_loss")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="dice_loss_op",
                     inputs={"X": [input], "Label": [label]},
                     outputs={"Out": [out]},
                     attrs={"epsilon": float(epsilon)})
    out.shape = (1,)
    return out


def mean_iou(input, label, num_classes):
    """reference nn.py mean_iou -> (mean_iou, out_wrong, out_correct)."""
    helper = LayerHelper("mean_iou")
    miou = helper.create_variable_for_type_inference("float32",
                                                     stop_gradient=True)
    wrong = helper.create_variable_for_type_inference("int32",
                                                      stop_gradient=True)
    correct = helper.create_variable_for_type_inference("int32",
                                                        stop_gradient=True)
    helper.append_op(type="mean_iou",
                     inputs={"Predictions": [input], "Labels": [label]},
                     outputs={"OutMeanIou": [miou], "OutWrong": [wrong],
                              "OutCorrect": [correct]},
                     attrs={"num_classes": int(num_classes)})
    miou.shape = (1,)
    wrong.shape = correct.shape = (int(num_classes),)
    return miou, wrong, correct


def add_position_encoding(input, alpha=1.0, beta=1.0, name=None):
    helper = LayerHelper("add_position_encoding", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="add_position_encoding", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"alpha": float(alpha), "beta": float(beta)})
    out.shape = input.shape
    return out


def bilinear_tensor_product(x, y, size, act=None, name=None,
                            param_attr=None, bias_attr=None):
    """reference nn.py bilinear_tensor_product: out_k = x W_k y^T + b."""
    helper = LayerHelper("bilinear_tensor_product", name=name,
                         bias_attr=bias_attr, act=act)
    dx, dy = x.shape[-1], y.shape[-1]
    w = helper.create_parameter(param_attr, [int(size), dx, dy], x.dtype)
    inputs = {"X": [x], "Y": [y], "Weight": [w]}
    b = helper.create_parameter(bias_attr, [int(size)], x.dtype,
                                is_bias=True)
    if b is not None:
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="bilinear_tensor_product", inputs=inputs,
                     outputs={"Out": [out]})
    out.shape = (x.shape[0], int(size))
    return helper.append_activation(out, act)


def lstm_unit(x_t, hidden_t_prev, cell_t_prev, forget_bias=0.0,
              param_attr=None, bias_attr=None, name=None):
    """reference nn.py lstm_unit: fc([x, h_prev]) -> one LSTM cell step;
    returns (hidden, cell)."""
    helper = LayerHelper("lstm_unit", name=name)
    D = hidden_t_prev.shape[-1]
    gates = fc(input=[x_t, hidden_t_prev], size=4 * D,
               param_attr=param_attr, bias_attr=bias_attr)
    c = helper.create_variable_for_type_inference(x_t.dtype)
    h = helper.create_variable_for_type_inference(x_t.dtype)
    helper.append_op(type="lstm_unit",
                     inputs={"X": [gates], "C_prev": [cell_t_prev]},
                     outputs={"C": [c], "H": [h]},
                     attrs={"forget_bias": float(forget_bias)})
    c.shape = h.shape = cell_t_prev.shape
    return h, c


def teacher_student_sigmoid_loss(input, label, soft_max_up_bound=15.0,
                                 soft_max_lower_bound=-15.0):
    helper = LayerHelper("teacher_student_sigmoid_loss")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="teacher_student_sigmoid_loss",
                     inputs={"X": [input], "Label": [label]},
                     outputs={"Y": [out]})
    out.shape = (input.shape[0], 1) if input.shape else None
    return out


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    helper = LayerHelper("npair_loss")
    out = helper.create_variable_for_type_inference(anchor.dtype)
    helper.append_op(type="npair_loss_op",
                     inputs={"Anchor": [anchor], "Positive": [positive],
                             "Labels": [labels]},
                     outputs={"Out": [out]},
                     attrs={"l2_reg": float(l2_reg)})
    out.shape = (1,)
    return out


def gaussian_random_batch_size_like(input, shape, input_dim_idx=0,
                                    output_dim_idx=0, mean=0.0, std=1.0,
                                    seed=0, dtype="float32"):
    helper = LayerHelper("gaussian_random_batch_size_like")
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type="gaussian_random_batch_size_like",
                     inputs={"Input": [input]}, outputs={"Out": [out]},
                     attrs={"shape": [int(s) for s in shape],
                            "input_dim_idx": int(input_dim_idx),
                            "output_dim_idx": int(output_dim_idx),
                            "mean": float(mean), "std": float(std),
                            "dtype": dtype})
    s = list(int(v) for v in shape)
    if input.shape:
        s[output_dim_idx] = input.shape[input_dim_idx]
    out.shape = tuple(s)
    return out


def random_crop(x, shape, seed=None):
    """reference nn.py random_crop (trailing dims cropped to shape)."""
    helper = LayerHelper("random_crop")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="random_crop", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"shape": [int(s) for s in shape]})
    lead = tuple(x.shape[:len(x.shape) - len(shape)]) if x.shape else ()
    out.shape = lead + tuple(int(s) for s in shape)
    return out


def image_resize_short(input, out_short_len, resample="BILINEAR"):
    """reference nn.py image_resize_short: resize so the SHORT spatial
    side equals out_short_len (NCHW, static shapes)."""
    h, w = input.shape[2], input.shape[3]
    short = min(h, w)
    out_h = int(round(h * out_short_len / short))
    out_w = int(round(w * out_short_len / short))
    op_type = ("bilinear_interp" if resample.upper() == "BILINEAR"
               else "nearest_interp")
    helper = LayerHelper("image_resize_short")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type=op_type, inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"out_h": out_h, "out_w": out_w,
                            "align_corners": False})
    out.shape = (input.shape[0], input.shape[1], out_h, out_w)
    return out


def sequence_reshape(input, new_dim, length=None):
    """reference sequence_reshape_op.cc, masked-dense form: [B, T, D] ->
    [B, T*D//new_dim, new_dim]; lengths scale by D/new_dim."""
    helper = LayerHelper("sequence_reshape")
    B, T, D = input.shape
    out = reshape(input, shape=[B, T * D // int(new_dim), int(new_dim)])
    if length is None:
        return out
    from .tensor import cast as _cast

    scaled = scale(_cast(length, "float32"), scale=D / float(new_dim))
    return out, _cast(scaled, "int64")


def lod_reset(x, y=None, target_lod=None):
    """LoD travels as explicit length vars in this design
    (layers/sequence.py contract): the data is returned unchanged and
    the caller adopts `y`/target lengths where it passes lengths. Kept
    for reference API parity (lod_reset_op.cc)."""
    return x


def merge_selected_rows(x, name=None):
    """SelectedRows are dense here (sparse grads densify in the
    transpiler); identity for parity (merge_selected_rows_op.cc)."""
    return x


def get_tensor_from_selected_rows(x, name=None):
    """See merge_selected_rows: dense passthrough."""
    return x


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    """reference nn.py autoincreased_step_counter: a persistable int64
    counter bumped once per executed step."""
    helper = LayerHelper("step_counter")
    counter = helper.create_global_variable(
        name=counter_name or unique_name.generate("@STEP_COUNTER@"),
        shape=[1], dtype="int64",
        initializer=Constant(float(begin - step)))
    helper.append_op(type="increment_counter", inputs={"X": [counter]},
                     outputs={"Out": [counter]}, attrs={"step": int(step)})
    counter.stop_gradient = True
    return counter


def sum(x):
    """reference nn.py sum: elementwise sum of a list of tensors."""
    xs = x if isinstance(x, (list, tuple)) else [x]
    helper = LayerHelper("sum")
    out = helper.create_variable_for_type_inference(xs[0].dtype)
    helper.append_op(type="sum", inputs={"X": list(xs)},
                     outputs={"Out": [out]})
    out.shape = xs[0].shape
    return out


def pool3d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, name=None, exclusive=True):
    """reference nn.py pool3d (NCDHW)."""
    helper = LayerHelper("pool3d", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    trip = lambda v: [v] * 3 if isinstance(v, int) else list(v)
    helper.append_op(type="pool3d", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"pooling_type": pool_type,
                            "ksize": trip(pool_size),
                            "strides": trip(pool_stride),
                            "paddings": trip(pool_padding),
                            "global_pooling": global_pooling,
                            "exclusive": exclusive})
    n, c, d, h, w = input.shape
    if global_pooling:
        out.shape = (n, c, 1, 1, 1)
    else:
        k, s, p = trip(pool_size), trip(pool_stride), trip(pool_padding)
        dims = [(v + 2 * p[i] - k[i]) // s[i] + 1
                for i, v in enumerate((d, h, w))]
        out.shape = (n, c) + tuple(dims)
    return out


def adaptive_pool3d(input, pool_size, pool_type="max", require_index=False,
                    name=None):
    helper = LayerHelper("adaptive_pool3d", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    ps = [pool_size] * 3 if isinstance(pool_size, int) else list(pool_size)
    helper.append_op(type="adaptive_pool3d", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"ksize": ps, "pooling_type": pool_type})
    out.shape = tuple(input.shape[:2]) + tuple(ps)
    return out


def conv3d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=None,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None):
    """reference nn.py conv3d_transpose (NCDHW)."""
    helper = LayerHelper("conv3d_transpose", name=name,
                         bias_attr=bias_attr, act=act)
    c = input.shape[1]
    trip = lambda v: [v] * 3 if isinstance(v, int) else list(v)
    k = trip(filter_size)
    w = helper.create_parameter(param_attr,
                                [c, num_filters] + k, input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="conv3d_transpose",
                     inputs={"Input": [input], "Filter": [w]},
                     outputs={"Out": [out]},
                     attrs={"strides": trip(stride),
                            "paddings": trip(padding)})
    n, _, d, h, wd = input.shape
    s, p = trip(stride), trip(padding)
    dims = [s[i] * (v - 1) + k[i] - 2 * p[i]
            for i, v in enumerate((d, h, wd))]
    out.shape = (n, num_filters) + tuple(dims)
    out = helper.append_bias_op(out, dim_start=1, size=num_filters)
    return helper.append_activation(out, act)


def ctc_greedy_decoder(input, blank, length=None, name=None):
    """reference nn.py ctc_greedy_decoder, masked-dense: probs [B,T,C]
    (+ length [B]) -> (decoded ids [B,T] padded -1, lengths [B])."""
    helper = LayerHelper("ctc_greedy_decoder", name=name)
    out = helper.create_variable_for_type_inference("int32",
                                                    stop_gradient=True)
    olen = helper.create_variable_for_type_inference("int64",
                                                     stop_gradient=True)
    ins = {"Input": [input]}
    if length is not None:
        ins["Length"] = [length]
    helper.append_op(type="ctc_greedy_decoder", inputs=ins,
                     outputs={"Out": [out], "OutLength": [olen]},
                     attrs={"blank": int(blank)})
    out.shape = tuple(input.shape[:2]) if input.shape else None
    olen.shape = (input.shape[0],) if input.shape else None
    return out, olen


def spectral_norm(weight, dim=0, power_iters=1, eps=1e-12, name=None):
    helper = LayerHelper("spectral_norm", name=name)
    out = helper.create_variable_for_type_inference(weight.dtype)
    h = weight.shape[dim] if weight.shape else 1
    u = helper.create_global_variable(
        name=unique_name.generate("spectral_norm_u"), shape=[h],
        dtype="float32", initializer=Constant(1.0))
    helper.append_op(type="spectral_norm",
                     inputs={"Weight": [weight], "U": [u]},
                     outputs={"Out": [out], "UOut": [u]},
                     attrs={"dim": int(dim), "power_iters": int(power_iters),
                            "eps": float(eps)})
    out.shape = weight.shape
    return out


def affine_grid(theta, out_shape, name=None):
    """reference nn.py affine_grid: theta [N,2,3] -> grid [N,H,W,2]."""
    helper = LayerHelper("affine_grid", name=name)
    out = helper.create_variable_for_type_inference(theta.dtype)
    shape = [int(s) for s in (out_shape if isinstance(out_shape,
                                                      (list, tuple))
                              else list(out_shape))]
    helper.append_op(type="affine_grid", inputs={"Theta": [theta]},
                     outputs={"Output": [out]},
                     attrs={"output_shape": shape})
    out.shape = (theta.shape[0], shape[-2], shape[-1], 2)
    return out


def grid_sampler(x, grid, name=None):
    helper = LayerHelper("grid_sampler", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="grid_sampler",
                     inputs={"X": [x], "Grid": [grid]},
                     outputs={"Output": [out]})
    out.shape = tuple(x.shape[:2]) + tuple(grid.shape[1:3])
    return out


def sequence_scatter(input, index, updates, length=None, name=None):
    """reference sequence_scatter (masked-dense; length gates steps)."""
    helper = LayerHelper("sequence_scatter", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    ins = {"X": [input], "Ids": [index], "Updates": [updates]}
    if length is not None:
        ins["Length"] = [length]
    helper.append_op(type="sequence_scatter", inputs=ins,
                     outputs={"Out": [out]})
    out.shape = input.shape
    return out


def data_norm(input, act=None, epsilon=1e-4, param_attr=None,
              data_layout="NCHW", in_place=False, name=None,
              moving_mean_name=None, moving_variance_name=None,
              do_model_average_for_mean_and_var=False):
    """reference nn.py data_norm: normalization by running batch
    statistics (CTR models; no learned affine)."""
    helper = LayerHelper("data_norm", name=name)
    D = input.shape[-1]
    size_v = helper.create_global_variable(
        name=unique_name.generate("data_norm_size"), shape=[D],
        dtype="float32", initializer=Constant(1e-4))
    sum_v = helper.create_global_variable(
        name=unique_name.generate("data_norm_sum"), shape=[D],
        dtype="float32", initializer=Constant(0.0))
    sq_v = helper.create_global_variable(
        name=unique_name.generate("data_norm_sq"), shape=[D],
        dtype="float32", initializer=Constant(1e-4))
    out = helper.create_variable_for_type_inference(input.dtype)
    means = helper.create_variable_for_type_inference(input.dtype)
    scales = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="data_norm",
                     inputs={"X": [input], "BatchSize": [size_v],
                             "BatchSum": [sum_v],
                             "BatchSquareSum": [sq_v]},
                     outputs={"Y": [out], "BatchSizeOut": [size_v],
                              "BatchSumOut": [sum_v],
                              "BatchSquareSumOut": [sq_v],
                              "Means": [means], "Scales": [scales]},
                     attrs={"epsilon": float(epsilon)})
    out.shape = input.shape
    return helper.append_activation(out, act)


def sampled_softmax_with_cross_entropy(logits, label, num_samples,
                                       num_true=1, remove_accidental_hits=True,
                                       use_customized_samples=False,
                                       customized_samples=None,
                                       customized_probabilities=None,
                                       seed=0):
    """reference nn.py sampled_softmax_with_cross_entropy (uniform
    sampler)."""
    helper = LayerHelper("sampled_softmax")
    loss = helper.create_variable_for_type_inference(logits.dtype)
    helper.append_op(type="sampled_softmax_with_cross_entropy",
                     inputs={"Logits": [logits], "Label": [label]},
                     outputs={"Loss": [loss]},
                     attrs={"num_samples": int(num_samples)})
    loss.shape = (logits.shape[0], 1) if logits.shape else None
    return loss


def im2sequence(input, filter_size=1, stride=1, padding=0, input_image_size=None,
                out_stride=1, name=None):
    """reference nn.py im2sequence (op lowering pre-existing in ops/nn.py)."""
    helper = LayerHelper("im2sequence", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    pair = lambda v: [v] * 2 if isinstance(v, int) else list(v)
    helper.append_op(type="im2sequence", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"kernels": pair(filter_size),
                            "strides": pair(stride),
                            "paddings": pair(padding) * 2})
    return out


def dynamic_lstmp(input, size, proj_size, param_attr=None, bias_attr=None,
                  use_peepholes=False, is_reverse=False,
                  gate_activation="sigmoid", cell_activation="tanh",
                  candidate_activation="tanh", proj_activation="tanh",
                  dtype="float32", name=None, length=None):
    """reference nn.py dynamic_lstmp (projection LSTM; masked-dense:
    input [B, T, 4D] pre-projected, `length` [B] replaces LoD). Returns
    (projection [B, T, P], cell [B, T, D])."""
    helper = LayerHelper("dynamic_lstmp", name=name, bias_attr=bias_attr)
    D = size // 4
    w = helper.create_parameter(param_attr, [proj_size, 4 * D], dtype)
    wp = helper.create_parameter(param_attr, [D, proj_size], dtype)
    b = helper.create_parameter(bias_attr, [1, 4 * D], dtype, is_bias=True)
    proj = helper.create_variable_for_type_inference(dtype)
    cell = helper.create_variable_for_type_inference(dtype)
    ins = {"Input": [input], "Weight": [w], "ProjWeight": [wp]}
    if b is not None:
        ins["Bias"] = [b]
    if length is not None:
        ins["Length"] = [length]
    helper.append_op(type="lstmp", inputs=ins,
                     outputs={"Projection": [proj], "Cell": [cell]},
                     attrs={"gate_activation": gate_activation,
                            "cell_activation": cell_activation,
                            "candidate_activation": candidate_activation,
                            "proj_activation": proj_activation})
    if input.shape:
        proj.shape = tuple(input.shape[:2]) + (proj_size,)
        cell.shape = tuple(input.shape[:2]) + (D,)
    return proj, cell


def lstm(input, init_h, init_c, max_len, hidden_size, num_layers,
         dropout_prob=0.0, is_bidirec=False, is_test=False, name=None,
         default_initializer=None, seed=-1, length=None):
    """reference nn.py lstm (the cudnn-style stacked LSTM): composed
    from fc + the scan lstm op per layer/direction. input [B, T, D_in];
    init_h/init_c [num_layers*dirs, B, hidden]. Returns
    (rnn_out [B, T, hidden*dirs], last_h, last_c)."""
    from .tensor import concat

    dirs = 2 if is_bidirec else 1
    x = input
    last_hs, last_cs = [], []
    for layer in range(num_layers):
        outs = []
        for d in range(dirs):
            gates = fc(x, size=4 * hidden_size, num_flatten_dims=2)
            helper = LayerHelper("lstm_l%d_d%d" % (layer, d), name=name)
            w = helper.create_parameter(None, [hidden_size, 4 * hidden_size],
                                        "float32")
            hid = helper.create_variable_for_type_inference("float32")
            cell = helper.create_variable_for_type_inference("float32")
            ins = {"Input": [gates], "Weight": [w]}
            if length is not None:
                ins["Length"] = [length]
            helper.append_op(type="lstm", inputs=ins,
                             outputs={"Hidden": [hid], "Cell": [cell]},
                             attrs={"is_reverse": bool(d == 1)})
            if x.shape:
                hid.shape = tuple(x.shape[:2]) + (hidden_size,)
                cell.shape = hid.shape
            outs.append((hid, cell))
        x = (outs[0][0] if dirs == 1
             else concat([h for h, _ in outs], axis=2))
        if dropout_prob and not is_test:
            x = dropout(x, dropout_prob=dropout_prob)
    # last step states of the TOP layer per direction
    T = input.shape[1] if input.shape else max_len
    lh, lc = [], []
    for d, (h, c) in enumerate(outs):
        idx = 0 if d == 1 else T - 1
        lh.append(reshape(slice(h, axes=[1], starts=[idx], ends=[idx + 1]),
                          shape=[-1, hidden_size]))
        lc.append(reshape(slice(c, axes=[1], starts=[idx], ends=[idx + 1]),
                          shape=[-1, hidden_size]))
    last_h = concat(lh, axis=1) if dirs > 1 else lh[0]
    last_c = concat(lc, axis=1) if dirs > 1 else lc[0]
    return x, last_h, last_c


def chunk_eval(input, label, chunk_scheme, num_chunk_types,
               excluded_chunk_types=None, seq_length=None):
    """reference nn.py chunk_eval: chunking precision/recall/F1 over IOB
    -style tag sequences, via a numpy py_func (metric, no gradients).
    Dense contract: input/label [B, T] int64 + seq_length [B]."""
    import numpy as np

    from .decode import py_func

    excluded = set(excluded_chunk_types or [])
    scheme = chunk_scheme

    def _extract(tags, L):
        """(type, start, end) chunks from a tag row per scheme."""
        chunks = []
        start = None
        cur_type = None
        for t in range(int(L)):
            tag = int(tags[t])
            if scheme == "plain":
                ctype = tag
                begin = cur_type != ctype
                if begin and cur_type is not None:
                    chunks.append((cur_type, start, t - 1))
                if begin:
                    start, cur_type = t, ctype
                continue
            if scheme == "IOB":
                n = 2
                tag_kind, ctype = tag % n, tag // n
                is_begin = tag_kind == 0
                inside = tag_kind == 1
            elif scheme == "IOE":
                n = 2
                tag_kind, ctype = tag % n, tag // n
                is_begin = cur_type != ctype
                inside = True
            else:  # IOBES
                n = 4
                tag_kind, ctype = tag % n, tag // n
                is_begin = tag_kind in (0, 3)
                inside = tag_kind in (1, 2)
            is_o = tag >= num_chunk_types * (2 if scheme in ("IOB", "IOE")
                                             else 4)
            if cur_type is not None and (is_o or is_begin
                                         or ctype != cur_type):
                chunks.append((cur_type, start, t - 1))
                cur_type = None
            if not is_o and (is_begin or (inside and cur_type is None)):
                start, cur_type = t, ctype
        if cur_type is not None:
            chunks.append((cur_type, start, int(L) - 1))
        return {c for c in chunks if c[0] not in excluded}

    def _metric(inp, lab, lens=None):
        B, T = inp.shape
        n_inf = n_lab = n_cor = 0
        for b in range(B):
            L = T if lens is None else lens[b]
            infer = _extract(inp[b], L)
            gold = _extract(lab[b], L)
            n_inf += len(infer)
            n_lab += len(gold)
            n_cor += len(infer & gold)
        p = n_inf and n_cor / n_inf or 0.0
        r = n_lab and n_cor / n_lab or 0.0
        f1 = (p + r) and 2 * p * r / (p + r) or 0.0
        # int32: the embedded host callback cannot emit 64-bit results
        # while jax x64 is off
        return (np.float32(p), np.float32(r), np.float32(f1),
                np.int32(n_inf), np.int32(n_lab), np.int32(n_cor))

    helper = LayerHelper("chunk_eval")
    outs = [helper.create_variable_for_type_inference(dt,
                                                      stop_gradient=True)
            for dt in ("float32", "float32", "float32", "int32", "int32",
                       "int32")]
    for o in outs:
        o.shape = (1,)
    xs = [input, label] + ([seq_length] if seq_length is not None else [])
    py_func(_metric, xs, outs)
    return tuple(outs)


def hash(input, hash_size, num_hash=1, name=None):
    """reference nn.py hash (xxhash replaced by a multiplicative mixer —
    bucketing behavior, not hash-value parity; see ops/misc_ops.py)."""
    helper = LayerHelper("hash", name=name)
    out = helper.create_variable_for_type_inference("int64",
                                                    stop_gradient=True)
    helper.append_op(type="hash_op", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"num_hash": int(num_hash),
                            "mod_by": int(hash_size)})
    if input.shape:
        out.shape = tuple(input.shape) + (int(num_hash),)
    return out


def psroi_pool(input, rois, output_channels, spatial_scale, pooled_height,
               pooled_width, rois_batch=None, name=None):
    """reference nn.py psroi_pool (position-sensitive ROI average)."""
    helper = LayerHelper("psroi_pool", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    ins = {"X": [input], "ROIs": [rois]}
    if rois_batch is not None:
        ins["RoisBatch"] = [rois_batch]
    helper.append_op(type="psroi_pool", inputs=ins,
                     outputs={"Out": [out]},
                     attrs={"output_channels": int(output_channels),
                            "spatial_scale": float(spatial_scale),
                            "pooled_height": int(pooled_height),
                            "pooled_width": int(pooled_width)})
    if rois.shape:
        out.shape = (rois.shape[0], int(output_channels),
                     int(pooled_height), int(pooled_width))
    return out


def similarity_focus(input, axis, indexes, name=None):
    """reference nn.py similarity_focus (axis=1 channel focus)."""
    helper = LayerHelper("similarity_focus", name=name)
    out = helper.create_variable_for_type_inference(input.dtype,
                                                    stop_gradient=True)
    helper.append_op(type="similarity_focus", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"axis": int(axis),
                            "indexes": [int(i) for i in indexes]})
    out.shape = input.shape
    return out


def tree_conv(nodes_vector, edge_set, output_size, num_filters=1,
              max_depth=2, act="tanh", param_attr=None, bias_attr=None,
              name=None):
    """reference nn.py tree_conv (TBCNN; depth-2 windows — see the op)."""
    helper = LayerHelper("tree_conv", name=name, bias_attr=bias_attr,
                         act=act)
    F = nodes_vector.shape[-1]
    w = helper.create_parameter(param_attr,
                                [F, 3, int(output_size), int(num_filters)],
                                nodes_vector.dtype)
    out = helper.create_variable_for_type_inference(nodes_vector.dtype)
    helper.append_op(type="tree_conv",
                     inputs={"NodesVector": [nodes_vector],
                             "EdgeSet": [edge_set], "Filter": [w]},
                     outputs={"Out": [out]},
                     attrs={"max_depth": int(max_depth)})
    if nodes_vector.shape:
        out.shape = (nodes_vector.shape[0], nodes_vector.shape[1],
                     int(output_size), int(num_filters))
    return helper.append_activation(out, act)
