"""LayerHelper: shared machinery for layer functions.

Analog of /root/reference/python/paddle/fluid/layer_helper.py — creates
parameters (in main + startup programs), temp output vars, bias/activation
epilogues.
"""

from __future__ import annotations

from typing import Optional

from .core.program import (
    Parameter,
    Variable,
    default_main_program,
    default_startup_program,
    unique_name,
)
from .initializer import Constant, Xavier
from .param_attr import ParamAttr

__all__ = ["LayerHelper", "stored_dtype"]

# Active parameter-stacking guards (innermost last): while a
# layers.scan_layers body builds, every create_parameter call is
# intercepted to create ONE stacked [n_layers, *shape] parameter and
# hand the body a per-iteration slice view — ordinary layer code
# (fc, layer_norm, fused_attention, ...) runs unchanged inside the
# scanned body. See layers/scan_ext.py.
_PARAM_STACKERS = []

# The dtype float32 MATRICES (rank >= 2) are stored in while a builder
# runs under ``stored_dtype`` (innermost last; None = as asked). Vectors
# — norm scales, biases — and every activation keep their own: an op that
# multiplies a float32 activation by such a matrix widens the matrix
# where it multiplies and accumulates in float32 (models/gpt.py
# cfg['weight_dtype']).
_STORED_DTYPE = []


def _stored(dtype, shape):
    """The dtype a parameter asked for as ``dtype`` is created in."""
    narrow = _STORED_DTYPE[-1] if _STORED_DTYPE else None
    return narrow if narrow and dtype == "float32" and len(shape) >= 2 \
        else dtype


class stored_dtype:
    """``with stored_dtype("bfloat16"):`` — parameters of rank >= 2 that
    a layer asks for in float32 are created, and initialised, in that
    dtype; ``None`` changes nothing."""

    def __init__(self, dtype):
        self.dtype = None if dtype in (None, "float32") else str(dtype)

    def __enter__(self):
        _STORED_DTYPE.append(self.dtype)
        return self

    def __exit__(self, *exc):
        _STORED_DTYPE.pop()
        return False


class _ParamStacker:
    """Collects stacked params + per-iteration slice vars for one
    scan_layers body (the StageBuilder pattern of layers/parallel_ext
    .py, applied transparently through LayerHelper)."""

    def __init__(self, n: int, sub_block):
        self.n = int(n)
        self.sub = sub_block
        self.stacked = []            # [n, *shape] Parameters
        self.slice_names = []        # body-visible per-iter views
        self._by_name = {}           # user name -> slice Variable (reuse)

    def create(self, helper: "LayerHelper", attr, shape, dtype, is_bias,
               default_initializer):
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        suffix = "b" if is_bias else "w"
        name = attr.name or unique_name.generate(
            "%s.%s" % (helper.name, suffix))
        if name in self._by_name:  # sharing-by-name inside the body
            return self._by_name[name]
        inner = _PARAM_STACKERS.pop()  # create the stacked param OUTSIDE
        try:
            stacked = helper.create_parameter(
                ParamAttr(name=name, initializer=attr.initializer,
                          trainable=attr.trainable,
                          regularizer=attr.regularizer,
                          gradient_clip=attr.gradient_clip,
                          learning_rate=attr.learning_rate),
                [self.n] + [int(s) for s in shape], dtype, is_bias=is_bias,
                default_initializer=default_initializer)
        finally:
            _PARAM_STACKERS.append(inner)
        slice_var = self.sub.create_var(
            name=unique_name.generate(name + ".layer"),
            shape=tuple(int(s) for s in shape), dtype=dtype)
        self.stacked.append(stacked)
        self.slice_names.append(slice_var.name)
        self._by_name[name] = slice_var
        return slice_var


class LayerHelper:
    def __init__(self, layer_type: str, **kwargs):
        self.layer_type = layer_type
        self.kwargs = kwargs
        self.name = kwargs.get("name") or unique_name.generate(layer_type)

    @property
    def main_program(self):
        return default_main_program()

    @property
    def startup_program(self):
        return default_startup_program()

    @property
    def block(self):
        return self.main_program.current_block()

    def create_parameter(
        self,
        attr,
        shape,
        dtype="float32",
        is_bias: bool = False,
        default_initializer=None,
    ) -> Optional[Parameter]:
        if _PARAM_STACKERS:
            # inside a scan_layers body: create the stacked parameter
            # and return the per-iteration slice view instead
            return _PARAM_STACKERS[-1].create(
                self, attr, shape, dtype, is_bias, default_initializer)
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        dtype = _stored(dtype, shape)
        suffix = "b" if is_bias else "w"
        name = attr.name or unique_name.generate("%s.%s" % (self.name, suffix))
        init = attr.initializer or default_initializer or (
            Constant(0.0) if is_bias else Xavier()
        )
        shape = [int(s) for s in shape]
        # sharing-by-name (reference ParamAttr semantics): a second layer
        # naming an existing parameter reuses it — same object, and no
        # duplicate initializer op in the startup program (a statically
        # unrolled decode loop re-creates its shared params every step)
        existing = self.main_program.global_block().vars.get(name)
        if isinstance(existing, Parameter):
            if list(existing.shape) != shape:
                raise ValueError(
                    "parameter %r reused with shape %s, created with %s"
                    % (name, shape, list(existing.shape)))
            return existing
        # parameters always live in the global block (reference
        # framework.py create_parameter does the same): a parameter
        # created inside an RNN/conditional sub-block must be visible to
        # append_backward and the executor's state analysis
        p = self.main_program.global_block().create_parameter(
            name=name,
            shape=shape,
            dtype=dtype,
            trainable=attr.trainable,
        )
        p.regularizer = attr.regularizer
        p.gradient_clip_attr = attr.gradient_clip
        p.optimize_attr = {"learning_rate": attr.learning_rate}
        sb = self.startup_program.global_block()
        sv = sb.create_var(
            name=name, shape=shape, dtype=dtype, persistable=True, stop_gradient=True
        )
        init(sv, sb)
        return p

    def create_variable_for_type_inference(self, dtype="float32", stop_gradient=False) -> Variable:
        return self.block.create_var(
            name=unique_name.generate(self.name + ".tmp"),
            dtype=dtype,
            stop_gradient=stop_gradient,
        )

    # persistable non-trainable state (bn running stats, auc buffers, lr...)
    def create_global_variable(self, name=None, shape=(1,), dtype="float32",
                               initializer=None, stop_gradient=True) -> Variable:
        name = name or unique_name.generate(self.name + ".global")
        main_block = self.main_program.global_block()
        v = main_block.create_var(
            name=name, shape=tuple(shape), dtype=dtype, persistable=True,
            stop_gradient=stop_gradient,
        )
        sb = self.startup_program.global_block()
        sv = sb.create_var(name=name, shape=tuple(shape), dtype=dtype,
                           persistable=True, stop_gradient=True)
        (initializer or Constant(0.0))(sv, sb)
        return v

    def append_op(self, **kwargs):
        return self.block.append_op(
            type=kwargs["type"],
            inputs=kwargs.get("inputs"),
            outputs=kwargs.get("outputs"),
            attrs=kwargs.get("attrs"),
        )

    def append_bias_op(self, input_var: Variable, dim_start=1, bias_attr=None,
                       size=None, dtype=None) -> Variable:
        attr = ParamAttr._to_attr(bias_attr if bias_attr is not None else self.kwargs.get("bias_attr"))
        if attr is False:
            return input_var
        if size is None:
            size = input_var.shape[-1] if input_var.shape else None
        b = self.create_parameter(attr, [size], dtype or input_var.dtype, is_bias=True)
        out = self.create_variable_for_type_inference(input_var.dtype)
        self.append_op(
            type="elementwise_add",
            inputs={"X": [input_var], "Y": [b]},
            outputs={"Out": [out]},
            attrs={"axis": dim_start},
        )
        out.shape = input_var.shape
        return out

    def append_activation(self, input_var: Variable, act=None) -> Variable:
        act = act if act is not None else self.kwargs.get("act")
        if act is None:
            return input_var
        out = self.create_variable_for_type_inference(input_var.dtype)
        self.append_op(type=act, inputs={"X": [input_var]}, outputs={"Out": [out]})
        out.shape = input_var.shape
        return out

    def input_dtype(self, var):
        return var.dtype
