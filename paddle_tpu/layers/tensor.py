"""Tensor-creation / manipulation layer builders.

Analog of /root/reference/python/paddle/fluid/layers/tensor.py.
"""

from __future__ import annotations

import numpy as np

from ..core.program import Variable, default_main_program, default_startup_program, unique_name
from ..initializer import Constant
from ..layer_helper import LayerHelper

__all__ = [
    "create_tensor",
    "create_parameter",
    "create_global_var",
    "cast",
    "concat",
    "sums",
    "assign",
    "materialize",
    "fill_constant",
    "fill_constant_batch_size_like",
    "ones",
    "zeros",
    "ones_like",
    "zeros_like",
    "reverse",
    "argmax",
    "argmin",
    "argsort",
    "range",
    "linspace",
    "isfinite",
    "has_inf",
    "has_nan",
    "tensor_array_to_tensor",
]


def create_tensor(dtype="float32", name=None, persistable=False):
    helper = LayerHelper("create_tensor", name=name)
    return helper.block.create_var(
        name=name or unique_name.generate("create_tensor"),
        dtype=dtype,
        persistable=persistable,
    )


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    helper = LayerHelper("create_parameter", name=name)
    from ..param_attr import ParamAttr

    attr = ParamAttr._to_attr(attr)
    if name and not attr.name:
        attr.name = name
    return helper.create_parameter(attr, shape, dtype, is_bias, default_initializer)


def create_global_var(shape, value, dtype="float32", persistable=False,
                      force_cpu=False, name=None):
    helper = LayerHelper("global_var", name=name)
    return helper.create_global_variable(
        name=name, shape=shape, dtype=dtype, initializer=Constant(value)
    )


def cast(x, dtype):
    dtype = str(np.dtype(dtype)) if dtype != "bool" else "bool"
    helper = LayerHelper("cast")
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type="cast", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"in_dtype": x.dtype, "out_dtype": dtype})
    out.shape = x.shape
    out.stop_gradient = x.stop_gradient
    return out


def concat(input, axis=0, name=None):
    helper = LayerHelper("concat", name=name)
    out = helper.create_variable_for_type_inference(input[0].dtype)
    helper.append_op(type="concat", inputs={"X": input}, outputs={"Out": [out]},
                     attrs={"axis": axis})
    shapes = [v.shape for v in input]
    if all(s is not None for s in shapes):
        ref = list(shapes[0])
        try:
            ref[axis] = sum(s[axis] for s in shapes)
            if any(s[axis] < 0 for s in shapes):
                ref[axis] = -1
        except (IndexError, TypeError):
            ref = None
        out.shape = tuple(ref) if ref else None
    return out


def sums(input, out=None):
    helper = LayerHelper("sums")
    if out is None:
        out = helper.create_variable_for_type_inference(input[0].dtype)
    helper.append_op(type="sum", inputs={"X": input}, outputs={"Out": [out]})
    out.shape = input[0].shape
    return out


def materialize(x):
    """``x`` itself, as a value XLA has to write (op ``materialize``: an
    optimization barrier, no arithmetic and no copy). A residual stream
    is a chain of adds that XLA fuses into every reader, the last one
    included: the prefill's final norm then re-adds EVERY layer's output
    from the embedding up, and each of them — ``[P, d_model]`` float32 —
    stays alive to the end of the program. Behind this op a reader starts
    from the materialised sum (models/gpt.py ``build_prefill_step``)."""
    helper = LayerHelper("materialize")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="materialize", inputs={"X": [x]},
                     outputs={"Out": [out]})
    out.shape = x.shape
    return out


def assign(input, output=None):
    helper = LayerHelper("assign")
    if isinstance(input, np.ndarray):
        if output is None:
            output = helper.create_variable_for_type_inference(str(input.dtype))
        helper.append_op(
            type="assign_value",
            outputs={"Out": [output]},
            attrs={"shape": list(input.shape), "dtype": str(input.dtype),
                   "values": input.reshape(-1).tolist()},
        )
        output.shape = tuple(input.shape)
    else:
        if output is None:
            output = helper.create_variable_for_type_inference(input.dtype)
        helper.append_op(type="assign", inputs={"X": [input]}, outputs={"Out": [output]})
        output.shape = input.shape
    return output


def fill_constant(shape, dtype, value, force_cpu=False, out=None):
    helper = LayerHelper("fill_constant")
    if out is None:
        out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="fill_constant",
        outputs={"Out": [out]},
        attrs={"shape": [int(s) for s in shape], "dtype": dtype, "value": float(value)},
    )
    out.shape = tuple(int(s) for s in shape)
    out.stop_gradient = True
    return out


def fill_constant_batch_size_like(input, shape, dtype, value,
                                  input_dim_idx=0, output_dim_idx=0):
    helper = LayerHelper("fill_constant_batch_size_like")
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="fill_constant_batch_size_like",
        inputs={"Input": [input]},
        outputs={"Out": [out]},
        attrs={"shape": [int(s) for s in shape], "dtype": dtype, "value": float(value),
               "input_dim_idx": input_dim_idx, "output_dim_idx": output_dim_idx},
    )
    s = list(shape)
    s[output_dim_idx] = input.shape[input_dim_idx] if input.shape else -1
    out.shape = tuple(s)
    out.stop_gradient = True
    return out


def ones(shape, dtype="float32", force_cpu=False):
    return fill_constant(shape, dtype, 1.0)


def zeros(shape, dtype="float32", force_cpu=False):
    return fill_constant(shape, dtype, 0.0)


def ones_like(x, out=None):
    helper = LayerHelper("ones_like")
    if out is None:
        out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="fill_any_like", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"value": 1.0})
    out.shape = x.shape
    return out


def zeros_like(x, out=None):
    helper = LayerHelper("zeros_like")
    if out is None:
        out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="fill_any_like", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"value": 0.0})
    out.shape = x.shape
    return out


def reverse(x, axis):
    helper = LayerHelper("reverse")
    out = helper.create_variable_for_type_inference(x.dtype)
    axis = [axis] if isinstance(axis, int) else list(axis)
    helper.append_op(type="reverse", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"axis": axis})
    out.shape = x.shape
    return out


def argmax(x, axis=0):
    helper = LayerHelper("arg_max")
    out = helper.create_variable_for_type_inference("int64", stop_gradient=True)
    helper.append_op(type="arg_max", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"axis": axis})
    return out


def argmin(x, axis=0):
    helper = LayerHelper("arg_min")
    out = helper.create_variable_for_type_inference("int64", stop_gradient=True)
    helper.append_op(type="arg_min", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"axis": axis})
    return out


def argsort(x, axis=-1, name=None):
    helper = LayerHelper("argsort", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    ids = helper.create_variable_for_type_inference("int64", stop_gradient=True)
    helper.append_op(type="argsort", inputs={"X": [x]},
                     outputs={"Out": [out], "Indices": [ids]}, attrs={"axis": axis})
    out.shape = x.shape
    ids.shape = x.shape
    return out, ids


def range(start, end, step, dtype="float32"):
    helper = LayerHelper("range")
    s = fill_constant([1], dtype, start) if not isinstance(start, Variable) else start
    e = fill_constant([1], dtype, end) if not isinstance(end, Variable) else end
    st = fill_constant([1], dtype, step) if not isinstance(step, Variable) else step
    out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    attrs = {}
    # static bounds recorded for the lowering: XLA needs the output shape
    # at trace time (SURVEY §7 "static shapes")
    if not any(isinstance(v, Variable) for v in (start, end, step)):
        attrs = {"static_start": float(start), "static_end": float(end),
                 "static_step": float(step), "dtype": dtype}
        n = max(0, -(-int(float(end) - float(start)) // int(float(step))))
        out.shape = (n,)
    helper.append_op(type="range", inputs={"Start": [s], "End": [e], "Step": [st]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def linspace(start, stop, num, dtype="float32"):
    helper = LayerHelper("linspace")
    s = fill_constant([1], dtype, start) if not isinstance(start, Variable) else start
    e = fill_constant([1], dtype, stop) if not isinstance(stop, Variable) else stop
    n = fill_constant([1], "int32", num) if not isinstance(num, Variable) else num
    out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op(type="linspace", inputs={"Start": [s], "Stop": [e], "Num": [n]},
                     outputs={"Out": [out]})
    out.shape = (int(num),) if not isinstance(num, Variable) else None
    return out


def isfinite(x):
    helper = LayerHelper("isfinite")
    out = helper.create_variable_for_type_inference("bool", stop_gradient=True)
    helper.append_op(type="isfinite", inputs={"X": [x]}, outputs={"Out": [out]})
    out.shape = (1,)
    return out


def has_inf(x):
    return isfinite(x)  # coarse parity: finite check


def has_nan(x):
    return isfinite(x)


def tensor_array_to_tensor(input, axis=1, name=None, use_stack=False):
    """reference tensor.py tensor_array_to_tensor over the static
    TensorArray (layers/control_flow.py): stack or concat the items.
    Returns (tensor, sizes_var)."""
    items = list(getattr(input, "items", input))
    if any(i is None for i in items):
        raise ValueError("tensor array has unwritten slots")
    from .nn import stack as _stack

    if use_stack:
        out = _stack(items, axis=axis)
        sizes = [1] * len(items)
    else:
        out = concat(items, axis=axis)
        sizes = [i.shape[axis] if i.shape else -1 for i in items]
    sz = fill_constant([len(items)], "int32", 0.0)
    return out, sz
