"""Value-range transfer functions for the core op vocabulary.

The per-op half of the abstract interpreter (``ranges.py``), registered
with ``register_range_rule`` the way ``shape_rules.py`` registers shape
rules. Soundness contract: the output interval must contain EVERY value
the lowering can produce for inputs inside the input intervals —
over-approximate freely (⊤ is always sound), never under-approximate.
``finite=True`` claims every element is a finite float; set it only
when the math proves it.

Ops with no sensible static bound are declared in ``WIDEN_TO_TOP`` —
the explicit ⊤ list ``tools/repo_lint.py`` rule 7 holds against the
shape-rule vocabulary, so an op can never *silently* fall through the
analysis (an op in neither registry is counted as an ``unknown-op``
widening and trips repo lint once it grows a shape rule).
"""

from __future__ import annotations

import math
from typing import List

import numpy as np

from .ranges import (AbstractValue, F32_MAX, RangeContext, av_abs, av_add,
                     av_const, av_div, av_interval, av_join, av_max_const,
                     av_min_const, av_monotone, av_mul, av_sub, av_top,
                     register_range_rule)

__all__: List[str] = ["WIDEN_TO_TOP"]

_INF = math.inf


def _sym(a: AbstractValue) -> AbstractValue:
    """[-max|a|, max|a|] — the symmetric envelope (quantize/rotate)."""
    m = av_abs(a).hi
    return AbstractValue(-m, m, finite=a.finite and math.isfinite(m)
                         and m <= F32_MAX)


def _same(slot_in: str, slot_out: str = "Out"):
    def rule(ctx: RangeContext):
        ctx.set(slot_out, ctx.input_av(slot_in))

    return rule


def _const_out(lo: float, hi: float, integral: bool = False):
    def rule(ctx: RangeContext):
        ctx.set("Out", av_interval(lo, hi, integral=integral))

    return rule


# ------------------------------------------------- bounded activations
register_range_rule("sigmoid", "hard_sigmoid")(_const_out(0.0, 1.0))
register_range_rule("tanh")(_const_out(-1.0, 1.0))
register_range_rule("softsign")(_const_out(-1.0, 1.0))
register_range_rule("softmax")(_const_out(0.0, 1.0))
register_range_rule("one_hot")(_const_out(0.0, 1.0, integral=True))
register_range_rule("cos", "sin")(_const_out(-1.0, 1.0))


@register_range_rule("stanh")
def _rr_stanh(ctx):
    b = abs(float(ctx.attr("scale_b", 1.7159)))
    ctx.set("Out", av_interval(-b, b))


@register_range_rule("relu")
def _rr_relu(ctx):
    ctx.set("Out", av_max_const(ctx.input_av("X"), 0.0))


@register_range_rule("relu6")
def _rr_relu6(ctx):
    ctx.set("Out", av_min_const(
        av_max_const(ctx.input_av("X"), 0.0), 6.0))


@register_range_rule("brelu")
def _rr_brelu(ctx):
    lo = float(ctx.attr("t_min", 0.0))
    hi = float(ctx.attr("t_max", 24.0))
    ctx.set("Out", av_min_const(
        av_max_const(ctx.input_av("X"), lo), hi))


@register_range_rule("abs")
def _rr_abs(ctx):
    ctx.set("Out", av_abs(ctx.input_av("X")))


@register_range_rule("square")
def _rr_square(ctx):
    a = av_abs(ctx.input_av("X"))
    ctx.set("Out", av_mul(a, a))


@register_range_rule("exp")
def _rr_exp(ctx):
    ctx.set("Out", av_monotone(ctx.input_av("X"), math.exp, out_lo=0.0))


@register_range_rule("log")
def _rr_log(ctx):
    a = ctx.input_av("X")
    if a.lo <= 0:  # log of 0/negative: -inf or nan possible
        ctx.set("Out", av_top())
    else:
        ctx.set("Out", av_monotone(a, math.log))


@register_range_rule("sqrt")
def _rr_sqrt(ctx):
    a = ctx.input_av("X")
    if a.lo < 0:  # nan possible: no interval can contain it
        ctx.set("Out", av_top())
    else:
        ctx.set("Out", av_monotone(a, math.sqrt, out_lo=0.0))


@register_range_rule("rsqrt")
def _rr_rsqrt(ctx):
    a = ctx.input_av("X")
    if a.lo <= 0:
        ctx.set("Out", av_top())
    else:
        ctx.set("Out", av_interval(
            1.0 / math.sqrt(a.hi) if math.isfinite(a.hi) else 0.0,
            1.0 / math.sqrt(a.lo),
            finite=a.finite))


@register_range_rule("reciprocal")
def _rr_reciprocal(ctx):
    one = av_const(1.0).drop_const()
    ctx.set("Out", av_div(one, ctx.input_av("X")))


@register_range_rule("floor", "ceil", "round")
def _rr_rounding(ctx):
    a = ctx.input_av("X")
    lo = a.lo if not math.isfinite(a.lo) else math.floor(a.lo)
    hi = a.hi if not math.isfinite(a.hi) else math.ceil(a.hi)
    ctx.set("Out", AbstractValue(lo, hi, finite=a.finite, integral=True))


@register_range_rule("sign")
def _rr_sign(ctx):
    ctx.set("Out", av_interval(-1.0, 1.0, integral=True))


_LOG2 = math.log(2.0)


@register_range_rule("softplus")
def _rr_softplus(ctx):
    # max(0, x) <= softplus(x) <= max(0, x) + log(2), and the lowering
    # (jax.nn.softplus = logaddexp(x, 0)) is overflow-stable, so the
    # closed form is sound for ANY input — no exp() argument cap that
    # would under-approximate softplus(1000) = 1000
    a = ctx.input_av("X")
    lo = max(0.0, a.lo)
    hi = a.hi + _LOG2 if a.hi >= 0 else _LOG2
    ctx.set("Out", AbstractValue(
        lo, hi, finite=a.finite and math.isfinite(hi)
        and hi <= F32_MAX))


@register_range_rule("logsigmoid")
def _rr_logsigmoid(ctx):
    # logsigmoid(x) = -softplus(-x): negate the softplus envelope
    a = ctx.input_av("X")
    lo = min(0.0, a.lo) - _LOG2
    hi = min(0.0, a.hi)
    ctx.set("Out", AbstractValue(
        lo, hi, finite=a.finite and math.isfinite(lo)
        and abs(lo) <= F32_MAX))


@register_range_rule("log_softmax")
def _rr_log_softmax(ctx):
    ctx.set("Out", AbstractValue(-_INF, 0.0))


@register_range_rule("soft_relu")
def _rr_soft_relu(ctx):
    t = abs(float(ctx.attr("threshold", 40.0)))
    ctx.set("Out", av_interval(0.0, t + math.log(2.0)))


def _gated(min_val: float):
    """x·gate(x) activations (gelu/silu/mish...): bounded below by the
    function's global minimum, above by max(hi, 0)."""

    def rule(ctx: RangeContext):
        a = ctx.input_av("X")
        hi = max(a.hi, 0.0)
        ctx.set("Out", AbstractValue(
            min_val, hi,
            finite=a.finite and math.isfinite(hi) and hi <= F32_MAX))

    return rule


register_range_rule("gelu")(_gated(-0.171))
register_range_rule("silu", "swish")(_gated(-0.2785))
register_range_rule("mish")(_gated(-0.309))
register_range_rule("hard_swish")(_gated(-0.375))


@register_range_rule("leaky_relu")
def _rr_leaky_relu(ctx):
    alpha = float(ctx.attr("alpha", 0.02))
    a = ctx.input_av("X")
    if alpha < 0:
        ctx.set("Out", av_top())
        return
    ctx.set("Out", av_monotone(
        a, lambda x: x if x > 0 else alpha * x))


@register_range_rule("elu")
def _rr_elu(ctx):
    alpha = float(ctx.attr("alpha", 1.0))
    a = ctx.input_av("X")
    if alpha < 0:
        ctx.set("Out", av_top())
        return
    ctx.set("Out", av_monotone(
        a, lambda x: x if x > 0 else alpha * math.expm1(max(x, -700)),
        out_lo=-alpha))


@register_range_rule("tanh_shrink")
def _rr_tanh_shrink(ctx):
    ctx.set("Out", av_add(ctx.input_av("X"), av_interval(-1.0, 1.0)))


@register_range_rule("hard_shrink")
def _rr_hard_shrink(ctx):
    # out is x (past the threshold) or 0
    a = ctx.input_av("X")
    ctx.set("Out", AbstractValue(min(a.lo, 0.0), max(a.hi, 0.0),
                                 finite=a.finite, integral=a.integral))


@register_range_rule("thresholded_relu")
def _rr_thresholded_relu(ctx):
    a = ctx.input_av("X")
    t = float(ctx.attr("threshold", 1.0))
    kept = av_max_const(a, t)  # surviving x values are > t
    ctx.set("Out", kept.join(av_interval(0.0, 0.0)))


@register_range_rule("pow")
def _rr_pow(ctx):
    a = ctx.input_av("X")
    factor = ctx.attr("factor", 1.0)
    ctx.set("Out", _pow_av(a, factor))


def _pow_av(a: AbstractValue, factor) -> AbstractValue:
    try:
        f = float(factor)
    except (TypeError, ValueError):
        return av_top()
    if f == 1.0:
        return a
    if float(f).is_integer() and f >= 0:
        k = int(f)
        m = av_abs(a)
        try:
            hi = m.hi ** k if math.isfinite(m.hi) else _INF
        except OverflowError:
            hi = _INF
        if k % 2 == 0:
            lo = 0.0 if a.contains(0.0) else min(abs(a.lo),
                                                 abs(a.hi)) ** k
            return av_interval(lo, hi) if math.isfinite(hi) \
                else AbstractValue(lo, _INF)
        try:
            lo = a.lo ** k if math.isfinite(a.lo) else -_INF
            hi2 = a.hi ** k if math.isfinite(a.hi) else _INF
        except OverflowError:
            return AbstractValue(-_INF, _INF)
        return av_interval(lo, hi2) if (math.isfinite(lo)
                                        and math.isfinite(hi2)) \
            else AbstractValue(lo, hi2)
    if a.lo < 0:  # fractional power of a negative: nan possible
        return av_top()
    return av_monotone(a, lambda x: x ** f, out_lo=0.0)


@register_range_rule("prelu")
def _rr_prelu(ctx):
    x = ctx.input_av("X")
    alpha = ctx.input_av("Alpha")
    pos = av_max_const(x, 0.0)
    neg = av_mul(av_min_const(x, 0.0), alpha)
    ctx.set("Out", pos.join(neg))


# --------------------------------------------------- elementwise family
def _binary(fn):
    def rule(ctx: RangeContext):
        ctx.set("Out", fn(ctx.input_av("X"), ctx.input_av("Y")))

    return rule


register_range_rule("elementwise_add")(_binary(av_add))
register_range_rule("elementwise_sub")(_binary(av_sub))
register_range_rule("elementwise_mul")(_binary(av_mul))
register_range_rule("elementwise_div")(_binary(av_div))
register_range_rule("elementwise_max")(_binary(
    lambda a, b: AbstractValue(max(a.lo, b.lo), max(a.hi, b.hi),
                               finite=a.finite and b.finite,
                               integral=a.integral and b.integral)))
register_range_rule("elementwise_min")(_binary(
    lambda a, b: AbstractValue(min(a.lo, b.lo), min(a.hi, b.hi),
                               finite=a.finite and b.finite,
                               integral=a.integral and b.integral)))


@register_range_rule("elementwise_pow")
def _rr_elementwise_pow(ctx):
    a, b = ctx.input_av("X"), ctx.input_av("Y")
    if b.is_const and np.asarray(b.const).size == 1:
        ctx.set("Out", _pow_av(a, float(np.asarray(b.const).item())))
    elif a.lo >= 0 and b.bounded and a.bounded:
        cands = [a.lo ** b.lo, a.lo ** b.hi, a.hi ** b.lo, a.hi ** b.hi]
        try:
            ctx.set("Out", av_interval(min(cands), max(cands)))
        except OverflowError:
            ctx.set("Out", AbstractValue(0.0, _INF))
    else:
        ctx.set("Out", av_top())


@register_range_rule("elementwise_mod")
def _rr_elementwise_mod(ctx):
    a, b = ctx.input_av("X"), ctx.input_av("Y")
    if b.contains(0.0):
        ctx.set("Out", av_top())
        return
    m = min(av_abs(a).hi, av_abs(b).hi)
    ctx.set("Out", AbstractValue(-m, m, finite=a.finite and b.finite
                                 and math.isfinite(m),
                                 integral=a.integral and b.integral))


@register_range_rule("elementwise_floordiv")
def _rr_elementwise_floordiv(ctx):
    a, b = ctx.input_av("X"), ctx.input_av("Y")
    d = av_div(a, b)
    lo = d.lo if not math.isfinite(d.lo) else math.floor(d.lo)
    ctx.set("Out", AbstractValue(lo, d.hi, finite=d.finite,
                                 integral=True))


_BOOL01 = _const_out(0.0, 1.0, integral=True)
register_range_rule("less_than", "less_equal", "greater_than",
                    "greater_equal", "equal", "not_equal",
                    "logical_and", "logical_or", "logical_xor",
                    "logical_not", "isfinite", "reduce_all",
                    "reduce_any")(_BOOL01)


@register_range_rule("sum")
def _rr_sum(ctx):
    n = ctx.num_inputs("X")
    out = ctx.input_av("X", 0)
    for i in range(1, n):
        out = av_add(out, ctx.input_av("X", i))
    ctx.set("Out", out)


@register_range_rule("where_op")
def _rr_where(ctx):
    ctx.set("Out", ctx.input_av("X").join(ctx.input_av("Y")))


# --------------------------------------------- scaling / clipping / copy
@register_range_rule("scale")
def _rr_scale(ctx):
    a = ctx.input_av("X")
    s = float(ctx.attr("scale", 1.0))
    b = float(ctx.attr("bias", 0.0))
    sc = av_mul(a, av_const(s).drop_const())
    if ctx.attr("bias_after_scale", True):
        out = av_add(sc, av_const(b).drop_const())
    else:
        out = av_mul(av_add(a, av_const(b).drop_const()),
                     av_const(s).drop_const())
    if a.is_const:
        arr = np.asarray(a.const)
        out = av_const(arr * s + b if ctx.attr("bias_after_scale", True)
                       else (arr + b) * s)
    ctx.set("Out", out)


@register_range_rule("clip")
def _rr_clip(ctx):
    lo = float(ctx.attr("min", -_INF))
    hi = float(ctx.attr("max", _INF))
    ctx.set("Out", av_min_const(
        av_max_const(ctx.input_av("X"), lo), hi))


@register_range_rule("clip_by_norm")
def _rr_clip_by_norm(ctx):
    a = ctx.input_av("X")
    m = abs(float(ctx.attr("max_norm", _INF)))
    ctx.set("Out", av_min_const(av_max_const(a, -m), m))


@register_range_rule("increment")
def _rr_increment(ctx):
    step = float(ctx.attr("step", 1.0))
    ctx.set("Out", av_add(ctx.input_av("X"),
                          av_const(step).drop_const()))


register_range_rule("assign")(_same("X"))
register_range_rule("materialize")(_same("X"))
register_range_rule("share_data")(_same("X"))


@register_range_rule("cast")
def _rr_cast(ctx):
    from .ranges import INT_RANGES

    a = ctx.input_av("X")
    dt = str(ctx.attr("out_dtype", ""))
    lo, hi = a.lo, a.hi
    integral = a.integral or dt.startswith(("int", "uint"))
    finite = a.finite or dt.startswith(("int", "uint", "bool"))
    if dt == "bool":
        lo, hi = 0.0, 1.0
    elif dt.startswith(("int", "uint")) and not a.integral:
        # truncation toward zero: monotone, so the endpoint truncs
        # bound the image (a fractional interval like [0.5, 0.9] really
        # produces 0 — keeping the float bounds would claim otherwise)
        lo = lo if not math.isfinite(lo) else float(math.trunc(lo))
        hi = hi if not math.isfinite(hi) else float(math.trunc(hi))
    rng = INT_RANGES.get(dt)
    wrapped = rng is not None and (lo < rng[0] or hi > rng[1])
    if wrapped:
        # out-of-range int conversion wraps (implementation-defined):
        # the only sound claims are the target dtype's full range and
        # no exact constant
        lo, hi = rng
    const = None if wrapped else a.const
    if const is not None and dt:
        try:
            const = np.asarray(const).astype(
                dt if dt != "bool" else np.bool_)
        except (TypeError, ValueError):
            const = None
    ctx.set("Out", AbstractValue(lo, hi, finite=finite,
                                 integral=integral, const=const))


@register_range_rule("label_smooth")
def _rr_label_smooth(ctx):
    eps = float(ctx.attr("epsilon", 0.1))
    a = av_mul(ctx.input_av("X"), av_const(1.0 - eps).drop_const())
    ctx.set("Out", av_add(a, av_interval(0.0, max(eps, 0.0))))


@register_range_rule("sigmoid_cross_entropy_with_logits")
def _rr_sce(ctx):
    x = ctx.input_av("X")
    hi = x.magnitude + math.log(2.0) if x.bounded else _INF
    ctx.set("Out", AbstractValue(0.0, hi, finite=x.bounded
                                 and math.isfinite(hi)))


@register_range_rule("cumsum")
def _rr_cumsum(ctx):
    # prefix sums: k-element partial sums for k = 1..n
    a = ctx.input_av("X")
    n = ctx.input_numel("X")
    if n is None:
        lo = min(0.0, a.lo) if a.lo >= 0 else -_INF
        hi = max(0.0, a.hi) if a.hi <= 0 else _INF
        ctx.set("Out", AbstractValue(min(lo, a.lo), max(hi, a.hi)))
        return
    ctx.set("Out", AbstractValue(
        min(a.lo, n * a.lo), max(a.hi, n * a.hi),
        finite=_n_finite(a, n), integral=a.integral))


def _n_finite(a: AbstractValue, n: int) -> bool:
    return a.finite and a.bounded and n * max(abs(a.lo),
                                              abs(a.hi)) <= F32_MAX


register_range_rule("reverse")(_same("X"))
register_range_rule("roll")(_same("X"))


# ------------------------------------------------------------- literals
@register_range_rule("fill_constant", "fill_constant_batch_size_like")
def _rr_fill_constant(ctx):
    try:
        val = np.asarray(ctx.attr("value", 0.0),
                         dtype=str(ctx.attr("dtype", "float32")))
    except (TypeError, ValueError):
        ctx.set("Out", av_top())
        return
    ctx.set("Out", av_const(val))


@register_range_rule("fill_any_like")
def _rr_fill_any_like(ctx):
    try:
        ctx.set("Out", av_const(float(ctx.attr("value", 0.0))))
    except (TypeError, ValueError):
        ctx.set("Out", av_top())


@register_range_rule("assign_value")
def _rr_assign_value(ctx):
    vals = ctx.attr("values")
    if vals is None:
        ctx.set("Out", av_top())
        return
    try:
        arr = np.asarray(vals, dtype=str(ctx.attr("dtype", "float32")))
        shape = ctx.attr("shape")
        if shape:
            arr = arr.reshape([int(s) for s in shape])
    except (TypeError, ValueError):
        ctx.set("Out", av_top())
        return
    ctx.set("Out", av_const(arr))


@register_range_rule("gaussian_random")
def _rr_gaussian_random(ctx):
    # samples are finite floats with unbounded support
    ctx.set("Out", AbstractValue(finite=True))


@register_range_rule("uniform_random", "uniform_random_batch_size_like")
def _rr_uniform_random(ctx):
    lo = float(ctx.attr("min", -1.0))
    hi = float(ctx.attr("max", 1.0))
    ctx.set("Out", av_interval(min(lo, hi), max(lo, hi)))


@register_range_rule("truncated_gaussian_random")
def _rr_truncated_gaussian(ctx):
    mean = float(ctx.attr("mean", 0.0))
    std = abs(float(ctx.attr("std", 1.0)))
    ctx.set("Out", av_interval(mean - 2.0 * std, mean + 2.0 * std))


@register_range_rule("range")
def _rr_range(ctx):
    s, e = ctx.input_av("Start"), ctx.input_av("End")
    ctx.set("Out", AbstractValue(
        min(s.lo, e.lo), max(s.hi, e.hi),
        finite=s.finite and e.finite,
        integral=s.integral and e.integral))


@register_range_rule("shape")
def _rr_shape(ctx):
    ctx.set("Out", av_interval(-1.0, 2147483647.0, integral=True))


# ------------------------------------------------------ matmul-like ops
def _contraction(ctx, x, y, width):
    """K-wide sum of products: K * [min, max] of the endpoint products.
    Unknown K: only the all-zero and sign-definite cases keep bounds."""
    p = av_mul(x, y)
    if width is not None and width >= 0:
        lo, hi = width * p.lo, width * p.hi
        return AbstractValue(lo, hi,
                             finite=p.finite and math.isfinite(lo)
                             and math.isfinite(hi)
                             and max(abs(lo), abs(hi)) <= F32_MAX)
    lo = 0.0 if p.lo >= 0 else -_INF
    hi = 0.0 if p.hi <= 0 else _INF
    return AbstractValue(lo, hi)


@register_range_rule("mul")
def _rr_mul(ctx):
    ys = ctx.input_shape("Y")
    k = ys[0] if ys and ys[0] >= 0 else None
    ctx.set("Out", _contraction(ctx, ctx.input_av("X"),
                                ctx.input_av("Y"), k))


@register_range_rule("matmul", "matmul_v2")
def _rr_matmul(ctx):
    ys = ctx.input_shape("Y")
    k = None
    if ys and len(ys) >= 2:
        kd = ys[-1] if ctx.attr("transpose_Y", False) else ys[-2]
        k = kd if kd >= 0 else None
    elif ys and len(ys) == 1:
        k = ys[0] if ys[0] >= 0 else None
    ctx.set("Out", _contraction(ctx, ctx.input_av("X"),
                                ctx.input_av("Y"), k))


@register_range_rule("bmm")
def _rr_bmm(ctx):
    ys = ctx.input_shape("Y")
    k = ys[-2] if ys and len(ys) >= 2 and ys[-2] >= 0 else None
    ctx.set("Out", _contraction(ctx, ctx.input_av("X"),
                                ctx.input_av("Y"), k))


@register_range_rule("dot")
def _rr_dot(ctx):
    xs = ctx.input_shape("X")
    k = xs[-1] if xs and xs[-1] >= 0 else None
    ctx.set("Out", _contraction(ctx, ctx.input_av("X"),
                                ctx.input_av("Y"), k))


def _conv_rule(filter_slot="Filter", skip_first=True):
    def rule(ctx: RangeContext):
        fs = ctx.input_shape(filter_slot)
        k = None
        if fs is not None and len(fs) >= 3:
            dims = fs[1:] if skip_first else (fs[0],) + fs[2:]
            if all(d >= 0 for d in dims):
                k = 1
                for d in dims:
                    k *= d
        # conv ops write slot "Output" (the reference's naming), not
        # the elementwise family's "Out"
        ctx.set("Output", _contraction(ctx, ctx.input_av("Input"),
                                       ctx.input_av(filter_slot), k))

    return rule


register_range_rule("conv2d", "depthwise_conv2d", "conv3d")(_conv_rule())
register_range_rule("conv2d_transpose")(_conv_rule(skip_first=False))


@register_range_rule("pool2d", "pool2d_with_index")
def _rr_pool2d(ctx):
    # avg and max pooling both stay inside the input interval
    a = ctx.input_av("X")
    ctx.set("Out", a.drop_const())
    if ctx.op.outputs.get("Mask"):
        ctx.set("Mask", av_interval(0.0, 2147483647.0, integral=True))


@register_range_rule("maxout")
def _rr_maxout(ctx):
    ctx.set("Out", ctx.input_av("X").drop_const())


# ------------------------------------------------------------ reductions
def _reduced_count(ctx, slot="X"):
    shape = ctx.input_shape(slot)
    if shape is None:
        return None
    if ctx.attr("reduce_all", False) or ctx.attr("dim") is None:
        dims = range(len(shape))
    else:
        d = ctx.attr("dim")
        dims = [d] if isinstance(d, int) else list(d)
        dims = [i if i >= 0 else i + len(shape) for i in dims]
    n = 1
    for i in dims:
        if not 0 <= i < len(shape) or shape[i] < 0:
            return None
        n *= shape[i]
    return n


@register_range_rule("reduce_sum")
def _rr_reduce_sum(ctx):
    a = ctx.input_av("X")
    n = _reduced_count(ctx)
    if n is None:
        lo = 0.0 if a.lo >= 0 else -_INF
        hi = 0.0 if a.hi <= 0 else _INF
        ctx.set("Out", AbstractValue(min(lo, a.lo * 1.0),
                                     max(hi, a.hi * 1.0)))
        return
    lo, hi = min(a.lo, n * a.lo), max(a.hi, n * a.hi)
    ctx.set("Out", AbstractValue(lo, hi, finite=_n_finite(a, n),
                                 integral=a.integral))


@register_range_rule("reduce_mean", "mean")
def _rr_reduce_mean(ctx):
    ctx.set("Out", ctx.input_av("X").drop_const())


@register_range_rule("reduce_max", "reduce_min")
def _rr_reduce_minmax(ctx):
    ctx.set("Out", ctx.input_av("X").drop_const())


@register_range_rule("reduce_prod")
def _rr_reduce_prod(ctx):
    a = ctx.input_av("X")
    m = av_abs(a).hi
    if m <= 1.0:
        lo = 0.0 if a.lo >= 0 else -1.0
        ctx.set("Out", av_interval(lo, 1.0))
        return
    n = _reduced_count(ctx)
    if n is None or not math.isfinite(m):
        ctx.set("Out", av_top())
        return
    try:
        bound = m ** n
    except OverflowError:
        bound = _INF
    lo = 0.0 if a.lo >= 0 else -bound
    if math.isfinite(bound) and bound <= F32_MAX:
        ctx.set("Out", av_interval(lo, bound))
    else:
        ctx.set("Out", AbstractValue(lo if math.isfinite(lo) else -_INF,
                                     _INF))


@register_range_rule("squared_l2_norm")
def _rr_squared_l2_norm(ctx):
    a = av_abs(ctx.input_av("X"))
    n = ctx.input_numel("X")
    sq = av_mul(a, a)
    if n is None:
        ctx.set("Out", AbstractValue(0.0, _INF))
    else:
        hi = n * sq.hi
        ctx.set("Out", AbstractValue(
            0.0, hi, finite=sq.finite and math.isfinite(hi)
            and hi <= F32_MAX))


@register_range_rule("norm")
def _rr_norm(ctx):
    # l2-normalize along an axis: |out| <= 1 by construction
    ctx.set("Out", av_interval(-1.0, 1.0))
    if ctx.op.outputs.get("Norm"):
        ctx.set("Norm", AbstractValue(0.0, _INF,
                                      finite=ctx.input_av("X").bounded))


@register_range_rule("arg_max", "arg_min")
def _rr_arg_minmax(ctx):
    ctx.set("Out", av_interval(0.0, 2147483647.0, integral=True))


@register_range_rule("argsort")
def _rr_argsort(ctx):
    ctx.set("Out", ctx.input_av("X").drop_const())
    ctx.set("Indices", av_interval(0.0, 2147483647.0, integral=True))


@register_range_rule("top_k")
def _rr_top_k(ctx):
    ctx.set("Out", ctx.input_av("X").drop_const())
    ctx.set("Indices", av_interval(0.0, 2147483647.0, integral=True))


# --------------------------------------------------------- shape movers
_XSHAPE_AV = av_interval(-1.0, 2147483647.0, integral=True)


def _mover(ctx: RangeContext):
    ctx.set("Out", ctx.input_av("X").drop_const())
    if ctx.op.outputs.get("XShape"):
        ctx.set("XShape", _XSHAPE_AV)


register_range_rule("reshape", "reshape2", "transpose", "transpose2",
                    "squeeze", "squeeze2", "unsqueeze", "unsqueeze2",
                    "flatten", "flatten2", "slice", "gather", "expand",
                    "tile", "expand_as", "crop", "unstack")(_mover)


@register_range_rule("concat", "stack")
def _rr_concat(ctx):
    avs = [ctx.input_av("X", i) for i in range(ctx.num_inputs("X"))]
    ctx.set("Out", av_join(*avs).drop_const() if avs else av_top())


@register_range_rule("split")
def _rr_split(ctx):
    a = ctx.input_av("X").drop_const()
    for i, n in enumerate(ctx.op.outputs.get("Out", [])):
        if n:
            ctx.set("Out", a, idx=i)


@register_range_rule("pad", "pad2d")
def _rr_pad(ctx):
    v = float(ctx.attr("pad_value", 0.0))
    ctx.set("Out", ctx.input_av("X").join(av_const(v).drop_const()))


@register_range_rule("scatter")
def _rr_scatter(ctx):
    ctx.set("Out", ctx.input_av("X").join(ctx.input_av("Updates")))


@register_range_rule("kv_cache_write")
def _rr_kv_cache_write(ctx):
    ctx.set("Out", ctx.input_av("Cache").join(ctx.input_av("Value")))


@register_range_rule("mla_decode")
def _rr_mla_decode(ctx):
    """``o`` is a convex combination of latent rows (softmax weights),
    then one contraction over d_c with W's value half."""
    ws = ctx.input_shape("W")
    dc = ws[0] if ws and len(ws) == 2 and ws[0] >= 0 else None
    o = _sym(ctx.input_av("Cache")).drop_const()
    ctx.set("Out", _contraction(ctx, o, ctx.input_av("W"), dc))


@register_range_rule("mhc_pre")
def _rr_mhc_pre(ctx):
    """``H`` is a sum of n streams, each weighted by a sigmoid; every
    coefficient lies in [0, 2] (``H_pre`` and ``H_res`` in [0, 1],
    ``H_post`` in [0, 2]); the deviation is a distance from one."""
    n = int(ctx.attr("n", 1) or 1)
    x = _sym(ctx.input_av("X"))
    ctx.set("H", AbstractValue(n * x.lo, n * x.hi, finite=x.finite))
    ctx.set("Coef", av_interval(0.0, 2.0))
    ctx.set("DevOut", av_interval(0.0, float(n)))


@register_range_rule("mhc_post")
def _rr_mhc_post(ctx):
    """A row of ``H_res`` sums to one up to its rounds' error (no entry
    over one), and ``H_post`` is at most two: ``n |X| + 2 |Y|``."""
    n = int(ctx.attr("n", 1) or 1)
    x, y = _sym(ctx.input_av("X")), _sym(ctx.input_av("Y"))
    hi = n * x.hi + 2.0 * y.hi
    ctx.set("Out", AbstractValue(-hi, hi, finite=x.finite and y.finite
                                 and math.isfinite(hi) and hi <= F32_MAX))


@register_range_rule("ssm_scan", "ssm_update")
def _rr_ssm(ctx):
    """The state is a decaying sum (decay <= 1) of ``dt x (x) B``: finite
    where the operands are, with no bound that a sequence's length does
    not move; ``Y`` is its contraction with ``C`` plus ``D x``."""
    finite = all(ctx.input_av(s).finite
                 for s in ("X", "Dt", "Bm", "Cm", "ALog", "D", "DtBias"))
    if ctx.op.type == "ssm_update":
        finite = finite and ctx.input_av("State").finite
    top = AbstractValue(-F32_MAX, F32_MAX, finite=finite)
    ctx.set("Y", top)
    ctx.set("StateOut", top)


@register_range_rule("power_scan", "power_update")
def _rr_power(ctx):
    """The state is a decaying sum (gate <= 1) of ``phi(k) v^T``: finite
    where the operands are, with no bound that a sequence's length does
    not move. ``Y`` is a weighted mean of the values seen (weights >= 0,
    the normaliser at least their sum): inside ``V``'s range wherever
    that range holds zero."""
    finite = all(ctx.input_av(s).finite for s in ("Q", "K", "V", "Gate"))
    if ctx.op.type == "power_update":
        finite = finite and ctx.input_av("State").finite \
            and ctx.input_av("Norm").finite
    top = AbstractValue(-F32_MAX, F32_MAX, finite=finite)
    ctx.set("Y", top)
    ctx.set("StateOut", top)
    ctx.set("NormOut", top)


@register_range_rule("delta_scan", "delta_update")
def _rr_delta(ctx):
    """The state is a decaying sum (``exp(g) <= 1``) of corrections ``k
    u^T`` with unit keys and ``beta < 1``: finite where the operands are,
    with no bound that a sequence's length does not move."""
    slots = ["Q", "K", "V", "Beta", "A"]
    if ctx.op.type == "delta_update":
        slots.append("State")
    top = AbstractValue(-F32_MAX, F32_MAX,
                        finite=all(ctx.input_av(s).finite for s in slots))
    ctx.set("Y", top)
    ctx.set("StateOut", top)


@register_range_rule("mamba_scan", "mamba_update")
def _rr_mamba(ctx):
    """The state is a decaying sum (``exp(dt A) <= 1``) of ``dt B u``:
    finite where the operands are, with no bound that a sequence's length
    does not move."""
    slots = ["X", "Dt", "Bm", "Cm", "ALog", "D", "DtBias"]
    if ctx.op.type == "mamba_update":
        slots.append("State")
    top = AbstractValue(-F32_MAX, F32_MAX,
                        finite=all(ctx.input_av(s).finite for s in slots))
    ctx.set("Y", top)
    ctx.set("StateOut", top)


@register_range_rule("causal_conv", "causal_conv_step")
def _rr_causal_conv(ctx):
    """``silu`` of a K-term sum of products: at least silu's minimum
    (-0.2785), at most the sum's bound — the sum itself with attr
    ``act`` off; the carried rows are ``X``'s own values (beside what
    ``Rows`` held)."""
    ws = ctx.input_shape("W")
    K = ws[1] if ws and len(ws) == 2 and ws[1] >= 0 else None
    x = ctx.input_av("X")
    acc = _contraction(ctx, x, ctx.input_av("W"), K)
    if ctx.op.inputs.get("Bias"):
        acc = av_add(acc, ctx.input_av("Bias"))
    if ctx.attr("act", True):
        acc = AbstractValue(-0.2785, max(acc.hi, 0.0), finite=acc.finite)
    ctx.set("Out", acc)
    rows = x.join(av_const(0.0).drop_const())
    if ctx.op.inputs.get("Rows"):
        rows = rows.join(ctx.input_av("Rows"))
    ctx.set("RowsOut", rows)


@register_range_rule("moe_ffn")
def _rr_moe_ffn(ctx):
    """Each token's output is a sum of top_k expert outputs, each scaled
    by a gate in [0, 1]: top_k times the envelope of one expert — two
    chained contractions (D-wide, then F-wide; swiglu multiplies the two
    up-projections, |silu(g)| <= |g|) plus the biases where present."""
    w1s = ctx.input_shape("W1")
    D = w1s[1] if w1s and len(w1s) == 3 and w1s[1] >= 0 else None
    F = w1s[2] if w1s and len(w1s) == 3 and w1s[2] >= 0 else None
    x = ctx.input_av("XE" if ctx.num_inputs("XE") else "X")
    h = _contraction(ctx, x, ctx.input_av("W1"), D)
    if ctx.num_inputs("B1"):
        h = av_add(h, ctx.input_av("B1"))
    if ctx.num_inputs("W1V"):
        h = av_mul(_sym(h), _contraction(ctx, x, ctx.input_av("W1V"), D))
    else:
        h = av_max_const(h, 0.0)
        if ctx.attr("act") == "relu2":
            h = av_mul(h, h)
    y = _contraction(ctx, h, ctx.input_av("W2"), F)
    if ctx.num_inputs("B2"):
        y = av_add(y, ctx.input_av("B2"))
    # gates lie in [0, 1] (softmax or sigmoid scores, renormalised or
    # not) before ``route_scale`` multiplies them
    k = float(int(ctx.attr("top_k", 1) or 1)) \
        * abs(float(ctx.attr("route_scale", 1.0) or 1.0))
    out = av_mul(_sym(y), av_interval(0.0, k))
    if ctx.attr("n_zero"):
        # an identity expert returns the token: up to all k gates on it
        out = av_add(out, av_mul(_sym(x), av_interval(0.0, k)))
    ctx.set("Out", out)
    if ctx.op.outputs.get("AuxLoss"):
        ctx.set("AuxLoss", AbstractValue(0.0, _INF, finite=x.bounded))
    for slot in ("CountsOut", "TouchedOut", "CompactOut", "ZeroOut"):
        if ctx.op.outputs.get(slot):
            ctx.set(slot, AbstractValue(0.0, _INF))


@register_range_rule("fused_attention")
def _rr_fused_attention(ctx):
    """Each output row is a convex combination of V's rows (softmax
    weights), whatever the mask, the window or the grouping; the output
    dropout scales it by 0 or 1/keep."""
    v = _sym(ctx.input_av("V")).drop_const()
    drop = float(ctx.attr("dropout", 0.0) or 0.0)
    if drop and not ctx.attr("is_test", False):
        v = av_mul(v, av_interval(0.0, 1.0 / max(1e-6, 1.0 - drop)))
    ctx.set("Out", v)
    if ctx.op.outputs.get("Mask"):
        ctx.set("Mask", av_interval(0.0, 1.0 / max(1e-6, 1.0 - drop)))


@register_range_rule("rope")
def _rr_rope(ctx):
    # x*cos + rotate(x)*sin: magnitude at most sqrt(2) * max|x|
    a = _sym(ctx.input_av("X"))
    ctx.set("Out", av_mul(a, av_interval(-1.4143, 1.4143)))


@register_range_rule("dropout")
def _rr_dropout(ctx):
    a = ctx.input_av("X")
    p = float(ctx.attr("dropout_prob", 0.5))
    m = 1.0 / (1.0 - p) if p < 1.0 else 1.0
    scaled = av_mul(a, av_interval(0.0, m))
    ctx.set("Out", scaled.join(av_interval(0.0, 0.0)))
    if ctx.op.outputs.get("Mask"):
        ctx.set("Mask", av_interval(0.0, m))


# ----------------------------------------------------- lookups and norms
@register_range_rule("lookup_table", "lookup_table_v2")
def _rr_lookup_table(ctx):
    ctx.set("Out", ctx.input_av("W").drop_const())


@register_range_rule("batch_norm", "group_norm")
def _rr_batch_norm(ctx):
    # xhat = (x - mean)/sqrt(var + eps): the eps floor bounds the
    # denominator below by sqrt(eps), and the numerator's magnitude by
    # the span of (x - mean) — mean is the batch statistic (inside x's
    # interval) in train mode, the running Mean input in test mode, so
    # join the two. Loose (the true denominator is usually >> sqrt(eps))
    # but sound and FINITE — which is what the consumers of this
    # analysis need to know.
    x = ctx.input_av("X")
    eps = abs(float(ctx.attr("epsilon", 1e-5))) or 1e-5
    mean_src = x.join(ctx.input_av("Mean")) if ctx.num_inputs("Mean") \
        else x
    numer = av_sub(x, mean_src)
    if numer.bounded:
        r = numer.magnitude / math.sqrt(eps)
        xhat = av_interval(-r, r)
    else:
        xhat = AbstractValue(finite=False)
    scale = ctx.input_av("Scale") if ctx.num_inputs("Scale") \
        else av_const(1.0).drop_const()
    bias = ctx.input_av("Bias") if ctx.num_inputs("Bias") \
        else av_const(0.0).drop_const()
    ctx.set("Y", av_add(av_mul(xhat, scale), bias))
    var_hi = ((x.hi - x.lo) / 2.0) ** 2 if x.bounded else _INF
    batch_var = AbstractValue(0.0, var_hi,
                              finite=x.bounded and math.isfinite(var_hi)
                              and var_hi <= F32_MAX)
    for slot in ("MeanOut", "SavedMean"):
        if ctx.op.outputs.get(slot):
            ctx.set(slot, x.join(ctx.input_av("Mean"))
                    if ctx.num_inputs("Mean") else x.drop_const())
    for slot in ("VarianceOut", "SavedVariance"):
        if ctx.op.outputs.get(slot):
            ctx.set(slot, batch_var.join(ctx.input_av("Variance"))
                    if ctx.num_inputs("Variance") else batch_var)


@register_range_rule("layer_norm", "rms_norm")
def _rr_layer_norm(ctx):
    xs = ctx.input_shape("X")
    d = xs[-1] if xs and xs[-1] >= 0 else None
    if d is None:
        xhat = AbstractValue()
    else:
        r = math.sqrt(d)
        xhat = av_interval(-r, r)
    scale = ctx.input_av("Scale") if ctx.num_inputs("Scale") \
        else av_const(1.0).drop_const()
    bias = ctx.input_av("Bias") if ctx.num_inputs("Bias") \
        else av_const(0.0).drop_const()
    ctx.set("Y", av_add(av_mul(xhat, scale), bias))
    if ctx.op.outputs.get("Mean"):
        ctx.set("Mean", ctx.input_av("X").drop_const())
    if ctx.op.outputs.get("Variance"):
        ctx.set("Variance", AbstractValue(0.0, _INF,
                                          finite=ctx.input_av("X").bounded))


# ----------------------------------------------------------------- losses
@register_range_rule("cross_entropy")
def _rr_cross_entropy(ctx):
    ctx.set("Y", AbstractValue(0.0, _INF))


@register_range_rule("softmax_with_cross_entropy")
def _rr_softmax_xent(ctx):
    ctx.set("Loss", AbstractValue(0.0, _INF))
    ctx.set("Softmax", av_interval(0.0, 1.0))


@register_range_rule("square_error_cost")
def _rr_square_error(ctx):
    d = av_abs(av_sub(ctx.input_av("X"), ctx.input_av("Y")))
    ctx.set("Out", av_mul(d, d))


@register_range_rule("huber_loss")
def _rr_huber(ctx):
    ctx.set("Out", AbstractValue(0.0, _INF))
    if ctx.op.outputs.get("Residual"):
        ctx.set("Residual", av_sub(ctx.input_av("Y"),
                                   ctx.input_av("X")))


@register_range_rule("smooth_l1_loss")
def _rr_smooth_l1(ctx):
    ctx.set("Out", AbstractValue(0.0, _INF))
    if ctx.op.outputs.get("Diff"):
        ctx.set("Diff", av_sub(ctx.input_av("X"), ctx.input_av("Y")))


@register_range_rule("log_loss")
def _rr_log_loss(ctx):
    ctx.set("Loss", AbstractValue(0.0, _INF))


# ----------------------------------------------------- quantization ops
@register_range_rule("fake_quantize_abs_max",
                     "fake_quantize_range_abs_max",
                     "fake_quantize_moving_average_abs_max")
def _rr_fake_quantize(ctx):
    a = _sym(ctx.input_av("X"))  # quant-dequant stays inside +-max|x|
    ctx.set("Out", a)
    m = av_abs(ctx.input_av("X")).hi
    scale_av = AbstractValue(0.0, m, finite=math.isfinite(m)
                             and m <= F32_MAX)
    for slot in ("OutScale", "OutAccum", "OutState"):
        if ctx.op.outputs.get(slot):
            ctx.set(slot, scale_av if slot == "OutScale"
                    else AbstractValue(0.0, _INF))


@register_range_rule("fake_dequantize_max_abs")
def _rr_fake_dequantize(ctx):
    s = av_abs(ctx.input_av("Scale"))
    mr = abs(float(ctx.attr("max_range", 127.0))) or 1.0
    ctx.set("Out", av_mul(_sym(ctx.input_av("X")),
                          av_mul(s, av_const(1.0 / mr).drop_const())))


@register_range_rule("quantize_channel_abs_max")
def _rr_quantize_channel(ctx):
    q = float((1 << (int(ctx.attr("bit_length", 8)) - 1)) - 1)
    ctx.set("Out", av_interval(-q, q, integral=True))


@register_range_rule("dequantize_channel_abs_max")
def _rr_dequantize_channel(ctx):
    # |out| = |q| * scale / qmax <= scale
    s = av_abs(ctx.input_av("Scales"))
    ctx.set("Out", AbstractValue(-s.hi, s.hi,
                                 finite=math.isfinite(s.hi)
                                 and s.hi <= F32_MAX))


# --------------------------------------------------------- declared top
# Every op type that HAS a shape rule but no transfer function above
# widens to T by declaration: its value genuinely has no useful static
# bound (optimizer state updates, data-dependent ids, sequence/beam
# machinery). tools/repo_lint.py rule 7 pins this partition total —
# a shape-ruled op in neither place fails repo lint, so nothing can
# fall through the analysis silently. (Ops with no shape rule widen
# with reason="unknown-op"; gradients widen by the *_grad convention.)
WIDEN_TO_TOP = (
    # optimizer updates: post-update parameter magnitudes are a
    # training-dynamics question, not a static one
    "sgd", "momentum", "lars_momentum", "adam", "adamax", "adagrad",
    "decayed_adagrad", "adadelta", "rmsprop", "ftrl", "lamb",
    # stats-dependent local response normalization (batch/group norm
    # carry real eps-floored rules above)
    "lrn",
    # data-dependent id/sampling producers
    "sampling_id", "shard_index",
)
