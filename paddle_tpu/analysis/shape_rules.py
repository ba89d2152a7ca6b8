"""Shape/dtype inference rules for the core op vocabulary.

Importing this module attaches a rule to each op's OpDef ``infer_shape``
hook (core/registry.py:39) via ``register_shape_rule`` — the per-op
InferShape role of the reference (operators/*.cc InferShape methods),
recast as small pure functions over an ``InferContext``. Tensor
Processing Primitives (arXiv:2104.05755) argues a kernel vocabulary is
only checkable when each primitive declares its semantics; these rules
are those declarations for the compile-time checker.

Conventions:
* shapes are tuples with ``-1`` for symbolic dims (batch), ``None`` for
  unknown rank — rules must tolerate ``None`` inputs by leaving outputs
  unset (inference then falls back to the declared Variable shape);
* ``ctx.fail(msg)`` reports a HARD mismatch (error severity; validate()
  raises); use it only when every dim involved is known;
* rules set dtypes only where the op defines them (cast, comparisons,
  index producers) — elsewhere the declared var dtype stands.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from .. import ops as _ops  # noqa: F401  (lowerings must be registered first)
from ..core.registry import register_shape_rule
from .infer import (InferContext, dims_compatible, is_concrete, merge_dim,
                    merge_shapes, normalize_shape, numel, shapes_compatible)

__all__: List[str] = []  # rules register by side effect


# ------------------------------------------------------------------ helpers
def _bcast_dim(a: int, b: int, fail) -> int:
    if a == 1:
        return b
    if b == 1:
        return a
    if a == -1 or b == -1:
        return a if b == -1 else b if a == -1 else -1
    if a != b:
        fail("cannot broadcast dims %d and %d" % (a, b))
    return a


def _numpy_bcast(xs: Sequence[int], ys: Sequence[int], fail) -> tuple:
    """Trailing-aligned numpy broadcasting with -1 wildcards."""
    xs, ys = list(xs), list(ys)
    n = max(len(xs), len(ys))
    xs = [1] * (n - len(xs)) + xs
    ys = [1] * (n - len(ys)) + ys
    return tuple(_bcast_dim(a, b, fail) for a, b in zip(xs, ys))


def _paddle_bcast(ctx: InferContext, xs, ys, axis) -> Optional[tuple]:
    """Paddle elementwise broadcast: y's dims match a contiguous run of
    x's dims starting at ``axis`` (axis=-1 aligns trailing, == numpy)."""
    if xs is None or ys is None:
        return None
    xs, ys = list(xs), list(ys)
    if not ys:
        return tuple(xs)
    if axis is None or axis == -1 or len(xs) == len(ys):
        # default axis is exactly numpy trailing alignment (including a
        # lower-rank x against y — the lowering falls through to jnp
        # broadcasting there)
        return _numpy_bcast(xs, ys, ctx.fail)
    # strip trailing 1-dims paddle allows in y
    while ys and ys[-1] == 1 and len(ys) > len(xs) - axis:
        ys.pop()
    if axis < 0 or axis + len(ys) > len(xs):
        ctx.fail("broadcast axis %d places y (rank %d) outside x (rank %d)"
                 % (axis, len(ys), len(xs)))
    y_full = [1] * axis + ys + [1] * (len(xs) - axis - len(ys))
    return _numpy_bcast(xs, y_full, ctx.fail)


def _same_shape(in_slot: str, out_slot: str = "Out", dtype=None):
    def rule(ctx: InferContext):
        s = ctx.input_shape(in_slot)
        if s is not None or dtype is not None:
            ctx.set(out_slot, s, dtype=dtype)

    return rule


def _xshape(ctx: InferContext, xs) -> None:
    if xs is not None:
        ctx.set("XShape", (0,) + tuple(xs))


def _conv_dim(h: int, k: int, s: int, p: int, d: int = 1) -> int:
    if h < 0:
        return -1
    return (h + 2 * p - (d * (k - 1) + 1)) // s + 1


def _pair(v) -> Tuple[int, int]:
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)[:2]
    return (int(v), int(v))


def _is_int_dtype(dt: Optional[str]) -> bool:
    return dt is not None and (dt.startswith("int") or dt.startswith("uint"))


# --------------------------------------------------- same-shape vocabularies
_ACTIVATIONS = (
    "relu", "sigmoid", "tanh", "sqrt", "rsqrt", "abs", "exp", "log",
    "square", "reciprocal", "softplus", "softsign", "ceil", "floor",
    "round", "cos", "sin", "gelu", "relu6", "leaky_relu", "elu", "pow",
    "stanh", "hard_sigmoid", "hard_swish", "swish", "brelu", "soft_relu",
    "logsigmoid", "tanh_shrink", "thresholded_relu", "hard_shrink",
    "mish", "silu", "prelu", "softmax", "log_softmax",
)
register_shape_rule(*_ACTIVATIONS)(_same_shape("X"))

for _t in ("scale", "clip", "clip_by_norm", "sign", "increment",
           "assign", "materialize", "share_data", "cumsum", "reverse", "roll",
           "shard_index", "label_smooth",
           "sigmoid_cross_entropy_with_logits"):
    register_shape_rule(_t)(_same_shape("X"))

register_shape_rule("rope")(_same_shape("X"))


@register_shape_rule("fused_attention")
def _r_fused_attention(ctx):
    """Out (and the dropout mask) are Q's shape [B, H, Sq, D] at V's
    width; K is [B, Hkv, Sk, D] and V [B, Hkv, Sk, Dv] with Hkv dividing
    H (grouped heads), and a window is a causal call's. Rank-3 Q, K, V
    are [B, Sq, H*D], [B, Sk, H*D] and [B, Sk, H*D] with ``n_head`` heads
    and take no window. With a shared key part KR [B, Sk, Dr] (and QR
    [B, Sq, H*Dr]) K and V are one tensor [B, Sk, H*(D + Dv)] and Out is
    [B, Sq, H*Dv]."""
    qs, ks, vs = (ctx.input_shape(s) for s in ("Q", "K", "V"))
    shared = bool(ctx.op.inputs.get("KR"))
    if qs is not None:
        out = qs if vs is None or len(vs) != len(qs) \
            else tuple(qs[:-1]) + (vs[-1] - qs[-1] if shared else vs[-1],)
        ctx.set("Out", out)
        if "Mask" in ctx.op.outputs:
            ctx.set("Mask", out)
    if ctx.attr("window", 0) and not ctx.attr("causal", False):
        ctx.fail("a window needs causal=True")
    if qs is None or ks is None or not (is_concrete(qs[1:])
                                        and is_concrete(ks[1:])):
        return
    if len(qs) == 3 and shared:
        n_head = int(ctx.attr("n_head", 0) or 0)
        qr, kr = ctx.input_shape("QR"), ctx.input_shape("KR")
        if n_head <= 0 or qs[-1] % n_head or len(ks) != 3 \
                or ks[-1] % n_head or ks[-1] <= qs[-1] \
                or ctx.attr("window", 0) or ctx.op.inputs["V"] \
                != ctx.op.inputs["K"] or None in (qr, kr) \
                or tuple(kr[1:-1]) != tuple(ks[1:-1]) \
                or tuple(qr[1:]) != (qs[1], n_head * kr[-1]):
            ctx.fail("Q %s, QR %s, K = V %s and KR %s are not [B, Sq, H*D], "
                     "[B, Sq, H*Dr], ONE [B, Sk, H*(D + Dv)] and [B, Sk, Dr] "
                     "with n_head=%d heads and no window"
                     % (qs, qr, ks, kr, n_head))
        return
    if len(qs) == 3:
        n_head = int(ctx.attr("n_head", 0) or 0)
        if len(ks) != 3 or qs[-1] != ks[-1] or n_head <= 0 \
                or qs[-1] % n_head or ctx.attr("window", 0) \
                or (vs is not None and is_concrete(vs[1:])
                    and tuple(vs[1:]) != tuple(ks[1:])):
            ctx.fail("Q %s, K %s and V %s are not [B, Sq, H*D], [B, Sk, "
                     "H*D] and [B, Sk, H*D] with n_head=%d heads and no "
                     "window" % (qs, ks, vs, n_head))
        return
    if len(qs) != 4 or len(ks) != 4 or qs[-1] != ks[-1] \
            or ks[1] <= 0 or qs[1] % ks[1]:
        ctx.fail("Q %s and K %s are not [B, H, Sq, D] and [B, Hkv, Sk, D] "
                 "with Hkv dividing H" % (qs, ks))
    if vs is not None and is_concrete(vs[1:]) \
            and tuple(vs[1:-1]) != tuple(ks[1:-1]):
        ctx.fail("V %s is not K's shape %s up to its width" % (vs, ks))
register_shape_rule("kv_cache_write")(_same_shape("Cache"))


@register_shape_rule("mla_decode")
def _r_mla_decode(ctx):
    """Out is [B, 1, H * d_v]; QNope [B, 1, H, d_nope] and QRope
    [B, 1, H, d_rope] meet a cache [B, 1, S, d_c + d_rope] and the
    up-projection W [d_c, H * (d_nope + d_v)]."""
    qn, qr, cs, ws = (ctx.input_shape(s)
                      for s in ("QNope", "QRope", "Cache", "W"))
    dv = int(ctx.attr("d_v", 0) or 0)
    if qn is not None and len(qn) == 4:
        ctx.set("Out", (qn[0], 1, qn[2] * dv if qn[2] >= 0 else -1))
    if None in (qn, qr, cs, ws) or not all(
            is_concrete(t[1:]) for t in (qn, qr, cs)) \
            or not is_concrete(ws):
        return
    if len(qn) != 4 or len(qr) != 4 or len(cs) != 4 or len(ws) != 2 \
            or qn[1] != 1 or qr[1:3] != qn[1:3] or cs[1] != 1 \
            or cs[3] != ws[0] + qr[3] or ws[1] != qn[2] * (qn[3] + dv):
        ctx.fail("QNope %s, QRope %s, Cache %s and W %s are not "
                 "[B, 1, H, d_nope], [B, 1, H, d_rope], [B, 1, S, d_c + "
                 "d_rope] and [d_c, H (d_nope + d_v=%d)]"
                 % (qn, qr, cs, ws, dv))
register_shape_rule("scatter")(_same_shape("X"))

@register_shape_rule("mhc_pre")
def _r_mhc_pre(ctx):
    """H is X's shape at a stream's width, Coef at n (n + 2); X is
    [..., n C], Phi [n C, n (n + 2)], Alpha [3], B [n (n + 2)]."""
    xs, ps = ctx.input_shape("X"), ctx.input_shape("Phi")
    n = int(ctx.attr("n", 0) or 0)
    if n < 1:
        ctx.fail("mhc_pre needs n >= 1 streams")
        return
    k = n * (n + 2)
    if xs is not None:
        wide = xs[-1]
        ctx.set("H", tuple(xs[:-1]) + (wide // n if wide >= 0 else -1,))
        ctx.set("Coef", tuple(xs[:-1]) + (k,))
        if wide >= 0 and wide % n:
            ctx.fail("X %s is not [..., %d * C]" % (xs, n))
    if "DevOut" in ctx.op.outputs:
        ctx.set("DevOut", ctx.input_shape("Dev"))
    if xs is not None and ps is not None and is_concrete(ps) \
            and xs[-1] >= 0 and tuple(ps) != (xs[-1], k):
        ctx.fail("Phi %s is not [n C, n (n + 2)] = [%d, %d]"
                 % (ps, xs[-1], k))


@register_shape_rule("mhc_post")
def _r_mhc_post(ctx):
    """Out is X's shape [..., n C]; Y is [..., C] and Coef [..., n (n +
    2)] over the same rows."""
    xs, ys, cs = (ctx.input_shape(s) for s in ("X", "Y", "Coef"))
    n = int(ctx.attr("n", 0) or 0)
    if xs is not None:
        ctx.set("Out", xs)
    if None in (xs, ys, cs) or n < 1 or not all(
            is_concrete(t[1:]) for t in (xs, ys, cs)):
        return
    if tuple(xs[1:-1]) != tuple(ys[1:-1]) or xs[-1] != n * ys[-1] \
            or tuple(cs[1:]) != tuple(xs[1:-1]) + (n * (n + 2),):
        ctx.fail("X %s, Y %s and Coef %s are not [..., n C], [..., C] and "
                 "[..., n (n + 2)] at n = %d" % (xs, ys, cs, n))



def _ssm_out(ctx, state_slot):
    """``Y`` is ``X``'s shape; ``StateOut [B, G, N, (H / G) P]`` from the
    op's widths (``state_slot`` names an input that already has it)."""
    xs = ctx.input_shape("X")
    H, G = int(ctx.attr("heads", 0) or 0), int(ctx.attr("groups", 0) or 0)
    N = int(ctx.attr("state", 0) or 0)
    if H < 1 or G < 1 or N < 1 or H % G:
        ctx.fail("%s needs heads >= 1 in groups that divide them and "
                 "state >= 1" % ctx.op.type)
        return
    if xs is None:
        return
    ctx.set("Y", xs)
    wide = xs[-1]
    if wide >= 0 and wide % H:
        ctx.fail("X %s is not [..., %d heads * P]" % (xs, H))
        return
    st = ctx.input_shape(state_slot) if state_slot else None
    if st is None and wide >= 0:
        st = (xs[0], G, N, wide // G)
    if st is not None:
        ctx.set("StateOut", st)
    for slot, want in (("Dt", H), ("Bm", G * N), ("Cm", G * N)):
        got = ctx.input_shape(slot)
        if got is not None and got[-1] >= 0 and got[-1] != want:
            ctx.fail("%s %s is not [..., %d]" % (slot, got, want))


@register_shape_rule("ssm_scan")
def _r_ssm_scan(ctx):
    _ssm_out(ctx, None)


@register_shape_rule("ssm_update")
def _r_ssm_update(ctx):
    _ssm_out(ctx, "State")
    xs = ctx.input_shape("X")
    if xs is not None and len(xs) == 3 and xs[1] not in (1, -1):
        ctx.fail("ssm_update takes one position a row; X is %s" % (xs,))


def _power_out(ctx, kept):
    """``Y`` is ``Q``'s shape; ``StateOut [B, G, R, D]`` and ``NormOut
    [B, G, D, D]`` from the op's widths (``kernels.power.phi_plan``), or
    the shapes of the ``kept`` inputs where the op has them."""
    from ..kernels.power import phi_plan

    qs = ctx.input_shape("Q")
    H, G = int(ctx.attr("heads", 0) or 0), int(ctx.attr("groups", 0) or 0)
    if H < 1 or G < 1 or H % G:
        ctx.fail("%s needs heads >= 1 in groups that divide them"
                 % ctx.op.type)
        return
    if qs is None:
        return
    ctx.set("Y", qs)
    wide = qs[-1]
    if wide >= 0 and wide % H:
        ctx.fail("Q %s is not [..., %d heads * D]" % (qs, H))
        return
    D = wide // H if wide >= 0 else -1
    st = ctx.input_shape("State") if kept else None
    nz = ctx.input_shape("Norm") if kept else None
    if D >= 0:
        st = st or (qs[0], G, phi_plan(D)[2], D)
        nz = nz or (qs[0], G, D, D)
    if st is not None:
        ctx.set("StateOut", st)
    if nz is not None:
        ctx.set("NormOut", nz)
    for slot, want in (("K", G * D), ("V", G * D), ("Gate", G)):
        got = ctx.input_shape(slot)
        if D >= 0 and got is not None and got[-1] >= 0 and got[-1] != want:
            ctx.fail("%s %s is not [..., %d]" % (slot, got, want))


@register_shape_rule("power_scan")
def _r_power_scan(ctx):
    _power_out(ctx, False)


@register_shape_rule("power_update")
def _r_power_update(ctx):
    _power_out(ctx, True)
    qs = ctx.input_shape("Q")
    if qs is not None and len(qs) == 3 and qs[1] not in (1, -1):
        ctx.fail("power_update takes one position a row; Q is %s" % (qs,))


def _delta_out(ctx, kept):
    """``Y`` is ``V``'s shape; ``StateOut [B, Hv, Dk, Dv]`` from the op's
    widths, or the shape of the ``kept`` input where the op has it."""
    qs, vs = ctx.input_shape("Q"), ctx.input_shape("V")
    Hk = int(ctx.attr("k_heads", 0) or 0)
    Hv = int(ctx.attr("v_heads", 0) or 0)
    if Hk < 1 or Hv < 1 or Hv % Hk:
        ctx.fail("%s needs value heads >= 1 over key heads that divide "
                 "them" % ctx.op.type)
        return
    if vs is not None:
        ctx.set("Y", vs)
    st = ctx.input_shape("State") if kept else None
    if st is None and qs is not None and vs is not None \
            and qs[-1] >= 0 and vs[-1] >= 0:
        if qs[-1] % Hk or vs[-1] % Hv:
            ctx.fail("Q %s / V %s are not [..., %d / %d heads * D]"
                     % (qs, vs, Hk, Hv))
            return
        st = (vs[0], Hv, qs[-1] // Hk, vs[-1] // Hv)
    if st is not None:
        ctx.set("StateOut", st)
    for slot, want in (("K", qs[-1] if qs is not None else -1),
                       ("Beta", Hv), ("A", Hv)):
        got = ctx.input_shape(slot)
        if want >= 0 and got is not None and got[-1] >= 0 \
                and got[-1] != want:
            ctx.fail("%s %s is not [..., %d]" % (slot, got, want))


@register_shape_rule("delta_scan")
def _r_delta_scan(ctx):
    _delta_out(ctx, False)


@register_shape_rule("delta_update")
def _r_delta_update(ctx):
    _delta_out(ctx, True)
    qs = ctx.input_shape("Q")
    if qs is not None and len(qs) == 3 and qs[1] not in (1, -1):
        ctx.fail("delta_update takes one position a row; Q is %s" % (qs,))


def _mamba_out(ctx, kept):
    """``Y`` is ``X``'s shape; ``StateOut [B, 1, N, C]`` from ``ALog [C,
    N]``, or the shape of the ``State`` input where the op has it."""
    xs, al = ctx.input_shape("X"), ctx.input_shape("ALog")
    if xs is not None:
        ctx.set("Y", xs)
    st = ctx.input_shape("State") if kept else None
    if st is None and xs is not None and al is not None and len(al) == 2:
        st = (xs[0], 1, al[1], al[0])
    if st is not None:
        ctx.set("StateOut", st)
    C = xs[-1] if xs is not None else -1
    N = al[1] if al is not None and len(al) == 2 else -1
    for slot, want in (("Dt", C), ("Bm", N), ("Cm", N), ("D", C),
                       ("DtBias", C), ("ALog", C)):
        got = ctx.input_shape(slot)
        have = got[0 if slot == "ALog" else -1] if got else -1
        if want >= 0 and have >= 0 and have != want:
            ctx.fail("%s %s does not fit %d channels of %d states"
                     % (slot, got, C, N))


@register_shape_rule("mamba_scan")
def _r_mamba_scan(ctx):
    _mamba_out(ctx, False)


@register_shape_rule("mamba_update")
def _r_mamba_update(ctx):
    _mamba_out(ctx, True)
    xs = ctx.input_shape("X")
    if xs is not None and len(xs) == 3 and xs[1] not in (1, -1):
        ctx.fail("mamba_update takes one position a row; X is %s" % (xs,))


@register_shape_rule("causal_conv", "causal_conv_step")
def _r_causal_conv(ctx):
    """Out is X's shape [B, T, C] (of X's columns [lo, hi) with attr
    ``columns``); RowsOut [B, K - 1, C] under W [C, K]."""
    xs, ws = ctx.input_shape("X"), ctx.input_shape("W")
    columns = ctx.attr("columns", None)
    if xs is not None and columns and len(xs) == 3:
        if xs[2] >= 0 and not 0 <= columns[0] < columns[1] <= xs[2]:
            ctx.fail("columns %s lie outside X %s" % (list(columns), xs))
        xs = tuple(xs[:2]) + (int(columns[1]) - int(columns[0]),)
    if xs is not None:
        ctx.set("Out", xs)
    if xs is None or ws is None or len(xs) != 3 or len(ws) != 2:
        return
    ctx.set("RowsOut", (xs[0], ws[1] - 1, xs[2]))
    if xs[2] >= 0 and ws[0] >= 0 and xs[2] != ws[0]:
        ctx.fail("W %s is not [C, K] for X %s" % (ws, xs))
    rows = ctx.input_shape("Rows")
    if rows is not None and is_concrete(rows[1:]) and is_concrete(ws) \
            and tuple(rows[1:]) != (ws[1] - 1, ws[0]):
        ctx.fail("Rows %s is not [B, K - 1, C] = [B, %d, %d]"
                 % (rows, ws[1] - 1, ws[0]))


@register_shape_rule("cast")
def _r_cast(ctx):
    ctx.set("Out", ctx.input_shape("X"), dtype=str(ctx.attr("out_dtype")))


@register_shape_rule("fill_any_like")
def _r_fill_any_like(ctx):
    dt = ctx.attr("dtype")
    ctx.set("Out", ctx.input_shape("X"),
            dtype=str(dt) if dt else ctx.input_dtype("X"))


@register_shape_rule("dropout")
def _r_dropout(ctx):
    xs = ctx.input_shape("X")
    if xs is not None:
        ctx.set("Out", xs)
        ctx.set("Mask", xs)


# ------------------------------------------------------- elementwise family
def _r_elementwise(ctx: InferContext):
    out = _paddle_bcast(ctx, ctx.input_shape("X"), ctx.input_shape("Y"),
                        ctx.attr("axis", -1))
    if out is not None:
        ctx.set("Out", out)


register_shape_rule(
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_max", "elementwise_min",
    "elementwise_pow", "elementwise_mod",
    "elementwise_floordiv")(_r_elementwise)


def _r_compare(ctx: InferContext):
    out = _paddle_bcast(ctx, ctx.input_shape("X"), ctx.input_shape("Y"),
                        ctx.attr("axis", -1))
    ctx.set("Out", out, dtype="bool")


register_shape_rule("less_than", "less_equal", "greater_than",
                    "greater_equal", "equal", "not_equal")(_r_compare)


def _r_logical(ctx: InferContext):
    xs = ctx.input_shape("X")
    if ctx.input_name("Y") is None:
        ctx.set("Out", xs, dtype="bool")
        return
    out = _paddle_bcast(ctx, xs, ctx.input_shape("Y"), -1)
    ctx.set("Out", out, dtype="bool")


register_shape_rule("logical_and", "logical_or", "logical_xor",
                    "logical_not")(_r_logical)


@register_shape_rule("sum")
def _r_sum(ctx):
    out = None
    for i in range(ctx.num_inputs("X")):
        s = ctx.input_shape("X", i)
        if s is None:
            continue
        if out is not None and not shapes_compatible(out, s):
            ctx.fail("sum inputs disagree on shape: %s vs %s"
                     % (tuple(out), tuple(s)))
        out = merge_shapes(out, s)
    if out is not None:
        ctx.set("Out", out)


@register_shape_rule("where_op")
def _r_where(ctx):
    out = _paddle_bcast(ctx, ctx.input_shape("X"), ctx.input_shape("Y"), -1)
    if out is not None:
        ctx.set("Out", out)


# ---------------------------------------------------------- matmul family
@register_shape_rule("mul")
def _r_mul(ctx):
    xs, ys = ctx.input_shape("X"), ctx.input_shape("Y")
    if xs is None or ys is None:
        return
    xnc = int(ctx.attr("x_num_col_dims", 1))
    ync = int(ctx.attr("y_num_col_dims", 1))
    if not (0 < xnc <= len(xs) and 0 < ync <= len(ys)):
        ctx.fail("num_col_dims (%d, %d) out of range for ranks (%d, %d)"
                 % (xnc, ync, len(xs), len(ys)))
    k1, k2 = numel(xs[xnc:]), numel(ys[:ync])
    if k1 is not None and k2 is not None and k1 != k2:
        ctx.fail("contraction size mismatch: flatten(X%s)=%d vs "
                 "flatten(Y%s)=%d" % (tuple(xs[xnc:]), k1,
                                      tuple(ys[:ync]), k2))
    ctx.set("Out", tuple(xs[:xnc]) + tuple(ys[ync:]))


def _r_matmul(ctx: InferContext):
    xs, ys = ctx.input_shape("X"), ctx.input_shape("Y")
    if xs is None or ys is None:
        return
    tx = bool(ctx.attr("transpose_X", ctx.attr("trans_x", False)))
    ty = bool(ctx.attr("transpose_Y", ctx.attr("trans_y", False)))
    if len(xs) < 2 or len(ys) < 2:
        return  # 1-D edge cases: let the lowering's reshape semantics rule
    a = list(xs)
    b = list(ys)
    if tx:
        a[-1], a[-2] = a[-2], a[-1]
    if ty:
        b[-1], b[-2] = b[-2], b[-1]
    if not dims_compatible(a[-1], b[-2]):
        ctx.fail("contraction dim mismatch: X%s @ Y%s contracts %d "
                 "against %d" % (tuple(xs), tuple(ys), a[-1], b[-2]))
    batch = _numpy_bcast(a[:-2], b[:-2], ctx.fail)
    ctx.set("Out", batch + (a[-2], b[-1]))


register_shape_rule("matmul", "matmul_v2")(_r_matmul)


@register_shape_rule("bmm")
def _r_bmm(ctx):
    xs, ys = ctx.input_shape("X"), ctx.input_shape("Y")
    if xs is None or ys is None or len(xs) != 3 or len(ys) != 3:
        return
    if not dims_compatible(xs[0], ys[0]):
        ctx.fail("bmm batch dims differ: %s vs %s" % (xs, ys))
    if not dims_compatible(xs[2], ys[1]):
        ctx.fail("bmm contraction dim mismatch: X%s @ Y%s"
                 % (tuple(xs), tuple(ys)))
    ctx.set("Out", (merge_dim(xs[0], ys[0]), xs[1], ys[2]))


@register_shape_rule("dot")
def _r_dot(ctx):
    xs = ctx.input_shape("X")
    if xs is not None:
        ctx.set("Out", tuple(xs[:-1]) + (1,))


# ------------------------------------------------------------- reductions
@register_shape_rule("mean", "squared_l2_norm")
def _r_scalar_out(ctx):
    ctx.set("Out", ())


def _r_reduce(ctx: InferContext):
    xs = ctx.input_shape("X")
    if xs is None:
        return
    keep = bool(ctx.attr("keep_dim", False))
    if ctx.attr("reduce_all", False):
        ctx.set("Out", (1,) * len(xs) if keep else ())
        return
    rank = len(xs)
    dims = {d % rank for d in ctx.attr("dim", [0])}
    if keep:
        ctx.set("Out", tuple(1 if i in dims else s
                             for i, s in enumerate(xs)))
    else:
        ctx.set("Out", tuple(s for i, s in enumerate(xs)
                             if i not in dims))


register_shape_rule("reduce_sum", "reduce_mean", "reduce_max",
                    "reduce_min", "reduce_prod", "reduce_all",
                    "reduce_any")(_r_reduce)


def _r_arg_minmax(ctx: InferContext):
    xs = ctx.input_shape("X")
    if xs is None:
        ctx.set("Out", None, dtype="int32")
        return
    axis = int(ctx.attr("axis", -1)) % len(xs)
    ctx.set("Out", tuple(s for i, s in enumerate(xs) if i != axis),
            dtype="int32")


register_shape_rule("arg_max", "arg_min")(_r_arg_minmax)


@register_shape_rule("argsort")
def _r_argsort(ctx):
    xs = ctx.input_shape("X")
    if xs is not None:
        ctx.set("Out", xs)
        ctx.set("Indices", xs, dtype="int32")


@register_shape_rule("norm")
def _r_norm(ctx):
    xs = ctx.input_shape("X")
    if xs is None:
        return
    ctx.set("Out", xs)
    axis = int(ctx.attr("axis", -1)) % len(xs)
    ctx.set("Norm", tuple(1 if i == axis else s
                          for i, s in enumerate(xs)))


# --------------------------------------------------------- shape surgery
@register_shape_rule("reshape", "reshape2")
def _r_reshape(ctx):
    xs = ctx.input_shape("X")
    target = [int(s) for s in ctx.attr("shape", [])]
    _xshape(ctx, xs)
    if target.count(-1) > 1:
        ctx.fail("reshape target %s has more than one -1" % (target,))
    out: List[int] = []
    known = 1
    neg = -1
    for i, s in enumerate(target):
        if s == -1:
            neg = i
            out.append(-1)
        elif s == 0:
            if xs is None:
                out.append(-1)
            elif i >= len(xs):
                ctx.fail("reshape target dim %d copies input dim %d, but "
                         "input rank is %d" % (i, i, len(xs)))
            else:
                out.append(xs[i])
                known = known * xs[i] if known >= 0 and xs[i] >= 0 else -1
        else:
            out.append(s)
            known = known * s if known >= 0 else -1
    total = numel(xs) if xs is not None else None
    if total is not None and known > 0:
        if neg >= 0:
            if total % known:
                ctx.fail("cannot reshape %s (%d elements) to %s: %d not "
                         "divisible by %d"
                         % (tuple(xs), total, tuple(target), total, known))
            out[neg] = total // known
        elif total != known:
            ctx.fail("cannot reshape %s (%d elements) to %s (%d elements)"
                     % (tuple(xs), total, tuple(target), known))
    ctx.set("Out", tuple(out))


@register_shape_rule("transpose", "transpose2")
def _r_transpose(ctx):
    xs = ctx.input_shape("X")
    _xshape(ctx, xs)
    if xs is None:
        return
    axis = [int(a) for a in ctx.attr("axis", [])]
    if sorted(a % len(xs) for a in axis) != list(range(len(xs))):
        ctx.fail("transpose axis %s is not a permutation of rank %d"
                 % (axis, len(xs)))
    ctx.set("Out", tuple(xs[a % len(xs)] for a in axis))


@register_shape_rule("concat")
def _r_concat(ctx):
    shapes = [ctx.input_shape("X", i) for i in range(ctx.num_inputs("X"))]
    shapes = [s for s in shapes if s is not None]
    if not shapes:
        return
    rank = len(shapes[0])
    if any(len(s) != rank for s in shapes):
        ctx.fail("concat inputs have mixed ranks: %s"
                 % [tuple(s) for s in shapes])
    axis = int(ctx.attr("axis", 0)) % rank
    out = list(shapes[0])
    for s in shapes[1:]:
        for i in range(rank):
            if i == axis:
                continue
            if not dims_compatible(out[i], s[i]):
                ctx.fail("concat inputs disagree on non-axis dim %d: %s"
                         % (i, [tuple(x) for x in shapes]))
            out[i] = merge_dim(out[i], s[i])
    cat = 0
    for s in shapes:
        if s[axis] < 0:
            cat = -1
            break
        cat += s[axis]
    out[axis] = cat
    ctx.set("Out", tuple(out))


@register_shape_rule("split")
def _r_split(ctx):
    xs = ctx.input_shape("X")
    if xs is None:
        return
    axis = int(ctx.attr("axis", 0)) % len(xs)
    num = int(ctx.attr("num", 0) or 0)
    sections = list(ctx.attr("sections", []) or [])
    names = ctx.op.outputs.get("Out") or []
    dim = xs[axis]
    if num:
        if dim >= 0 and dim % num:
            ctx.fail("split axis dim %d not divisible into %d parts"
                     % (dim, num))
        part = dim // num if dim >= 0 else -1
        for i in range(len(names)):
            ctx.set("Out", tuple(part if j == axis else s
                                 for j, s in enumerate(xs)), idx=i)
    elif sections:
        if dim >= 0 and -1 not in sections and sum(sections) != dim:
            ctx.fail("split sections %s sum to %d, axis dim is %d"
                     % (sections, sum(sections), dim))
        for i in range(min(len(names), len(sections))):
            sec = sections[i]
            if sec == -1:
                rest = sum(s for s in sections if s != -1)
                sec = dim - rest if dim >= 0 else -1
            ctx.set("Out", tuple(sec if j == axis else s
                                 for j, s in enumerate(xs)), idx=i)


@register_shape_rule("squeeze", "squeeze2")
def _r_squeeze(ctx):
    xs = ctx.input_shape("X")
    _xshape(ctx, xs)
    if xs is None:
        return
    axes = [a % len(xs) for a in ctx.attr("axes", [])]
    if not axes:
        if not is_concrete(xs):
            return  # which dims are 1 is unknowable
        axes = [i for i, s in enumerate(xs) if s == 1]
    drop = {a for a in axes if xs[a] == 1}
    if any(xs[a] == -1 for a in axes):
        return  # might or might not squeeze at run time
    ctx.set("Out", tuple(s for i, s in enumerate(xs) if i not in drop))


@register_shape_rule("unsqueeze", "unsqueeze2")
def _r_unsqueeze(ctx):
    xs = ctx.input_shape("X")
    _xshape(ctx, xs)
    if xs is None:
        return
    out = list(xs)
    for a in sorted(int(a) for a in ctx.attr("axes", [])):
        out.insert(a if a >= 0 else a + len(out) + 1, 1)
    ctx.set("Out", tuple(out))


@register_shape_rule("flatten", "flatten2")
def _r_flatten(ctx):
    xs = ctx.input_shape("X")
    _xshape(ctx, xs)
    if xs is None:
        return
    axis = int(ctx.attr("axis", 1))
    lead, tail = numel(xs[:axis]), numel(xs[axis:])
    ctx.set("Out", (lead if lead is not None else -1,
                    tail if tail is not None else -1))


@register_shape_rule("stack")
def _r_stack(ctx):
    n = ctx.num_inputs("X")
    merged = None
    for i in range(n):
        s = ctx.input_shape("X", i)
        if s is None:
            return
        if merged is not None and not shapes_compatible(merged, s):
            ctx.fail("stack inputs disagree on shape: %s vs %s"
                     % (tuple(merged), tuple(s)))
        merged = merge_shapes(merged, s)
    if merged is None:
        return
    axis = int(ctx.attr("axis", 0))
    out = list(merged)
    out.insert(axis if axis >= 0 else axis + len(out) + 1, n)
    ctx.set("Y", tuple(out))


@register_shape_rule("unstack")
def _r_unstack(ctx):
    xs = ctx.input_shape("X")
    if xs is None:
        return
    axis = int(ctx.attr("axis", 0)) % len(xs)
    names = ctx.op.outputs.get("Y") or []
    if xs[axis] >= 0 and len(names) != xs[axis]:
        ctx.fail("unstack axis dim %d but %d outputs declared"
                 % (xs[axis], len(names)))
    part = tuple(s for i, s in enumerate(xs) if i != axis)
    for i in range(len(names)):
        ctx.set("Y", part, idx=i)


@register_shape_rule("slice")
def _r_slice(ctx):
    xs = ctx.input_shape("Input")
    if xs is None:
        return
    out = list(xs)
    for a, s, e in zip(ctx.attr("axes", []), ctx.attr("starts", []),
                       ctx.attr("ends", [])):
        a = int(a) % len(xs)
        dim = xs[a]
        if dim < 0:
            out[a] = -1
            continue
        s, e = int(s), int(e)
        s = max(s + dim, 0) if s < 0 else min(s, dim)
        e = max(e + dim, 0) if e < 0 else min(e, dim)
        out[a] = max(e - s, 0)
    ctx.set("Out", tuple(out))


@register_shape_rule("gather")
def _r_gather(ctx):
    xs, idx = ctx.input_shape("X"), ctx.input_shape("Index")
    if _is_int_dtype(ctx.input_dtype("Index")) is False \
            and ctx.input_dtype("Index") is not None:
        ctx.fail("gather Index dtype %s is not integral"
                 % ctx.input_dtype("Index"))
    if xs is None or idx is None:
        return
    if len(idx) == 2 and idx[1] == 1:
        idx = idx[:1]
    axis = int(ctx.attr("axis", 0)) % len(xs)
    ctx.set("Out", tuple(xs[:axis]) + tuple(idx) + tuple(xs[axis + 1:]))


@register_shape_rule("expand")
def _r_expand(ctx):
    xs = ctx.input_shape("X")
    times = [int(t) for t in ctx.attr("expand_times", [])]
    if xs is None or len(times) != len(xs):
        return
    ctx.set("Out", tuple(-1 if s < 0 else s * t
                         for s, t in zip(xs, times)))


@register_shape_rule("tile")
def _r_tile(ctx):
    xs = ctx.input_shape("X")
    reps = [int(t) for t in ctx.attr("repeat_times", [])]
    if xs is None or len(reps) != len(xs):
        return
    ctx.set("Out", tuple(-1 if s < 0 else s * t
                         for s, t in zip(xs, reps)))


@register_shape_rule("expand_as")
def _r_expand_as(ctx):
    ts = ctx.input_shape("target_tensor")
    if ts is not None:
        ctx.set("Out", ts)


@register_shape_rule("pad")
def _r_pad(ctx):
    xs = ctx.input_shape("X")
    p = list(ctx.attr("paddings", []))
    if xs is None or len(p) != 2 * len(xs):
        return
    ctx.set("Out", tuple(-1 if s < 0 else s + p[2 * i] + p[2 * i + 1]
                         for i, s in enumerate(xs)))


@register_shape_rule("pad2d")
def _r_pad2d(ctx):
    xs = ctx.input_shape("X")
    p = list(ctx.attr("paddings", []))
    if xs is None or len(xs) != 4 or len(p) != 4:
        return
    n, c, h, w = xs
    ctx.set("Out", (n, c, -1 if h < 0 else h + p[0] + p[1],
                    -1 if w < 0 else w + p[2] + p[3]))


@register_shape_rule("crop")
def _r_crop(ctx):
    shape = ctx.attr("shape")
    if shape:
        ctx.set("Out", tuple(int(s) for s in shape))


# ------------------------------------------------------------ constants/rng
def _r_attr_shape(ctx: InferContext):
    shape = ctx.attr("shape", [])
    dt = ctx.attr("dtype")
    ctx.set("Out", tuple(int(s) for s in shape),
            dtype=str(dt) if dt else "float32")


register_shape_rule("fill_constant", "gaussian_random",
                    "truncated_gaussian_random", "uniform_random",
                    "assign_value")(_r_attr_shape)


def _r_batch_size_like(ctx: InferContext):
    ref = ctx.input_shape("Input")
    shape = [int(s) for s in ctx.attr("shape", [])]
    in_idx = int(ctx.attr("input_dim_idx", 0))
    out_idx = int(ctx.attr("output_dim_idx", 0))
    if not shape:
        return
    if ref is not None and in_idx < len(ref) and out_idx < len(shape):
        shape[out_idx] = ref[in_idx]
    dt = ctx.attr("dtype")
    ctx.set("Out", tuple(shape), dtype=str(dt) if dt else "float32")


register_shape_rule("fill_constant_batch_size_like",
                    "uniform_random_batch_size_like")(_r_batch_size_like)


@register_shape_rule("shape")
def _r_shape_op(ctx):
    xs = ctx.input_shape("Input")
    ctx.set("Out", (len(xs),) if xs is not None else None, dtype="int32")


@register_shape_rule("isfinite")
def _r_isfinite(ctx):
    ctx.set("Out", (1,), dtype="bool")


@register_shape_rule("one_hot")
def _r_one_hot(ctx):
    xs = ctx.input_shape("X")
    depth = ctx.attr("depth")
    if xs is None or depth is None:
        ctx.set("Out", None, dtype="float32")
        return
    if len(xs) >= 2 and xs[-1] == 1:
        xs = xs[:-1]
    ctx.set("Out", tuple(xs) + (int(depth),), dtype="float32")


@register_shape_rule("range")
def _r_range(ctx):
    if "static_start" in ctx.op.attrs:
        import math

        start = ctx.attr("static_start")
        end = ctx.attr("static_end")
        step = ctx.attr("static_step")
        n = max(0, int(math.ceil((end - start) / step)))
        ctx.set("Out", (n,))


@register_shape_rule("sampling_id")
def _r_sampling_id(ctx):
    xs = ctx.input_shape("X")
    ctx.set("Out", tuple(xs[:-1]) if xs is not None else None,
            dtype="int32")


# ------------------------------------------------------------------- conv
def _r_conv2d(ctx: InferContext):
    xs, ws = ctx.input_shape("Input"), ctx.input_shape("Filter")
    if xs is None or ws is None or len(xs) != 4 or len(ws) != 4:
        return
    groups = int(ctx.attr("groups", 1) or 1)
    if xs[1] >= 0 and ws[1] >= 0 and xs[1] != ws[1] * groups:
        ctx.fail("input channels %d != filter in-channels %d x groups %d"
                 % (xs[1], ws[1], groups))
    s = _pair(ctx.attr("strides", [1, 1]))
    p = _pair(ctx.attr("paddings", [0, 0]))
    d = _pair(ctx.attr("dilations", [1, 1]))
    ctx.set("Output", (xs[0], ws[0],
                       _conv_dim(xs[2], ws[2], s[0], p[0], d[0]),
                       _conv_dim(xs[3], ws[3], s[1], p[1], d[1])))


register_shape_rule("conv2d", "depthwise_conv2d")(_r_conv2d)


@register_shape_rule("conv2d_transpose")
def _r_conv2d_transpose(ctx):
    xs, ws = ctx.input_shape("Input"), ctx.input_shape("Filter")
    if xs is None or ws is None or len(xs) != 4 or len(ws) != 4:
        return
    if xs[1] >= 0 and ws[0] >= 0 and xs[1] != ws[0]:
        ctx.fail("conv2d_transpose input channels %d != filter dim0 %d"
                 % (xs[1], ws[0]))
    s = _pair(ctx.attr("strides", [1, 1]))
    p = _pair(ctx.attr("paddings", [0, 0]))
    d = _pair(ctx.attr("dilations", [1, 1]))
    groups = int(ctx.attr("groups", 1) or 1)

    def tdim(x, k, ss, pp, dd):
        if x < 0:
            return -1
        return (x - 1) * ss - 2 * pp + dd * (k - 1) + 1

    ctx.set("Output", (xs[0], -1 if ws[1] < 0 else ws[1] * groups,
                       tdim(xs[2], ws[2], s[0], p[0], d[0]),
                       tdim(xs[3], ws[3], s[1], p[1], d[1])))


@register_shape_rule("conv3d")
def _r_conv3d(ctx):
    xs, ws = ctx.input_shape("Input"), ctx.input_shape("Filter")
    if xs is None or ws is None or len(xs) != 5 or len(ws) != 5:
        return
    groups = int(ctx.attr("groups", 1) or 1)
    if xs[1] >= 0 and ws[1] >= 0 and xs[1] != ws[1] * groups:
        ctx.fail("input channels %d != filter in-channels %d x groups %d"
                 % (xs[1], ws[1], groups))
    s = list(ctx.attr("strides", [1, 1, 1]))
    p = list(ctx.attr("paddings", [0, 0, 0]))
    d = list(ctx.attr("dilations", [1, 1, 1]))
    dims = [_conv_dim(xs[2 + i], ws[2 + i], s[i], p[i], d[i])
            for i in range(3)]
    ctx.set("Output", (xs[0], ws[0]) + tuple(dims))


def _r_pool2d(ctx: InferContext):
    xs = ctx.input_shape("X")
    if xs is None or len(xs) != 4:
        return
    if ctx.attr("global_pooling", False):
        out = (xs[0], xs[1], 1, 1)
    else:
        k = _pair(ctx.attr("ksize", [2, 2]))
        s = _pair(ctx.attr("strides", [1, 1]))
        p = _pair(ctx.attr("paddings", [0, 0]))
        out = (xs[0], xs[1], _conv_dim(xs[2], k[0], s[0], p[0]),
               _conv_dim(xs[3], k[1], s[1], p[1]))
    ctx.set("Out", out)
    if "Mask" in ctx.op.outputs:
        ctx.set("Mask", out, dtype="int32")


register_shape_rule("pool2d", "pool2d_with_index")(_r_pool2d)


# ------------------------------------------------------------------ norms
@register_shape_rule("batch_norm")
def _r_batch_norm(ctx):
    xs = ctx.input_shape("X")
    if xs is None:
        return
    ctx.set("Y", xs)
    caxis = 1 if ctx.attr("data_layout", "NCHW") == "NCHW" else len(xs) - 1
    c = (xs[caxis],)
    for slot in ("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"):
        if slot in ctx.op.outputs:
            ctx.set(slot, c)


@register_shape_rule("layer_norm")
def _r_layer_norm(ctx):
    xs = ctx.input_shape("X")
    if xs is None:
        return
    ctx.set("Y", xs)
    begin = int(ctx.attr("begin_norm_axis", 1))
    lead = numel(xs[:begin])
    for slot in ("Mean", "Variance"):
        if slot in ctx.op.outputs:
            ctx.set(slot, (lead if lead is not None else -1,))


@register_shape_rule("rms_norm")
def _r_rms_norm(ctx):
    xs = ctx.input_shape("X")
    if xs is not None:
        ctx.set("Y", xs)


@register_shape_rule("moe_ffn")
def _r_moe_ffn(ctx):
    """Out is X's shape (at XE's width where the experts read an input
    of their own), AuxLoss a float32 scalar, each tally its
    input's shape; the stacked parameters must agree with one another:
    W1 (and W1V) [E, D, F], W2 [E, F, D], Gate [X's width, E], biases
    [E, F] and [E, D]. The group sizes are data: nothing in a shape depends on
    them. A share (``n_local``) stacks ``n_local`` experts where the
    router and its selection bias keep all ``n_experts``, and ``n_zero``
    identity experts more where the layer has them."""
    xs, xe = ctx.input_shape("X"), ctx.input_shape("XE")
    if xs is not None:
        ctx.set("Out", xs if xe is None else tuple(xs[:-1]) + (xe[-1],))
    ctx.set("AuxLoss", (), dtype="float32")
    for slot in ("Counts", "Touched", "Compact", "Zero"):
        if slot + "Out" in ctx.op.outputs:
            ts = ctx.input_shape(slot)
            if ts is not None:
                ctx.set(slot + "Out", ts, dtype="int32")
    w1, w2 = ctx.input_shape("W1"), ctx.input_shape("W2")
    gate = ctx.input_shape("Gate")
    E = int(ctx.attr("n_experts", 0) or 0)
    held = int(ctx.attr("n_local", 0) or 0) or E
    wide = E + int(ctx.attr("n_zero", 0) or 0)     # the router's outputs
    if not all(is_concrete(s) for s in (w1, w2, gate) if s is not None):
        return
    if w1 is not None and w2 is not None and (
            len(w1) != 3 or len(w2) != 3
            or (w1[0], w1[2], w1[1]) != tuple(w2)):
        ctx.fail("expert weights W1 %s and W2 %s are not [E, D, F] and "
                 "[E, F, D]" % (w1, w2))
    for slot, want in (("W1V", w1),
                       ("B1", None if w1 is None else (w1[0], w1[2])),
                       ("B2", None if w2 is None else (w2[0], w2[2])),
                       ("Gate", None if w1 is None or xs is None
                        or xs[-1] < 0 else (xs[-1], wide or w1[0])),
                       ("RouterBias", (wide,) if E else None)):
        got = ctx.input_shape(slot)
        if got is not None and want is not None and is_concrete(got) \
                and tuple(got) != tuple(want):
            ctx.fail("%s is %s, the expert weights ask for %s"
                     % (slot, got, want))
    if w1 is not None and held and w1[0] != held:
        ctx.fail("n_experts=%d (held: %d) but W1 stacks %d experts"
                 % (E, held, w1[0]))
    if not 0 <= int(ctx.attr("expert_first", 0) or 0) <= E - held:
        ctx.fail("experts %d.. are not a share of n_experts=%d"
                 % (int(ctx.attr("expert_first", 0)), E))
    xin = xs if xe is None else xe
    if xin is not None and w1 is not None and xin[-1] >= 0 \
            and xin[-1] != w1[1]:
        ctx.fail("%s's width %d is not the experts' %d"
                 % ("X" if xe is None else "XE", xin[-1], w1[1]))
    k = int(ctx.attr("top_k", 1) or 1)
    if E and not 1 <= k <= wide:
        ctx.fail("top_k=%d outside [1, %d experts]" % (k, wide))


@register_shape_rule("group_norm")
def _r_group_norm(ctx):
    xs = ctx.input_shape("X")
    if xs is None or len(xs) < 2:
        return
    groups = int(ctx.attr("groups", 1) or 1)
    if xs[1] >= 0 and xs[1] % groups:
        ctx.fail("channels %d not divisible by groups %d" % (xs[1], groups))
    ctx.set("Y", xs)
    for slot in ("Mean", "Variance"):
        if slot in ctx.op.outputs:
            ctx.set(slot, (xs[0], groups))


@register_shape_rule("lrn")
def _r_lrn(ctx):
    xs = ctx.input_shape("X")
    if xs is not None:
        ctx.set("Out", xs)
        ctx.set("MidOut", xs)


@register_shape_rule("maxout")
def _r_maxout(ctx):
    xs = ctx.input_shape("X")
    groups = int(ctx.attr("groups", 1) or 1)
    if xs is None or len(xs) < 2:
        return
    if xs[1] >= 0 and xs[1] % groups:
        ctx.fail("maxout channels %d not divisible by groups %d"
                 % (xs[1], groups))
    ctx.set("Out", (xs[0], xs[1] // groups if xs[1] >= 0 else -1)
            + tuple(xs[2:]))


# ----------------------------------------------------------------- losses
@register_shape_rule("cross_entropy")
def _r_cross_entropy(ctx):
    xs = ctx.input_shape("X")
    if xs is not None:
        ctx.set("Y", tuple(xs[:-1]) + (1,))


@register_shape_rule("softmax_with_cross_entropy")
def _r_softmax_xent(ctx):
    ls = ctx.input_shape("Logits")
    lbl = ctx.input_shape("Label")
    if ls is None:
        return
    if not ctx.attr("soft_label", False) and lbl is not None:
        want = tuple(ls[:-1])
        got = tuple(lbl[:-1]) if len(lbl) == len(ls) and lbl[-1] == 1 \
            else tuple(lbl)
        if len(got) == len(want) and not shapes_compatible(got, want):
            ctx.fail("label shape %s does not align with logits %s"
                     % (tuple(lbl), tuple(ls)))
    ctx.set("Softmax", ls)
    ctx.set("Loss", tuple(ls[:-1]) + (1,))


@register_shape_rule("square_error_cost", "huber_loss")
def _r_pairwise_loss(ctx):
    xs, ys = ctx.input_shape("X"), ctx.input_shape("Y")
    if xs is not None and ys is not None \
            and not shapes_compatible(xs, ys):
        ctx.fail("inputs disagree on shape: %s vs %s"
                 % (tuple(xs), tuple(ys)))
    out = merge_shapes(xs, ys)
    if out is not None:
        ctx.set("Out", out)
        if "Residual" in ctx.op.outputs:
            ctx.set("Residual", out)


@register_shape_rule("smooth_l1_loss")
def _r_smooth_l1(ctx):
    xs = ctx.input_shape("X")
    if xs is not None:
        ctx.set("Diff", xs)
        ctx.set("Out", (xs[0], 1))


@register_shape_rule("log_loss")
def _r_log_loss(ctx):
    ps = ctx.input_shape("Predicted")
    if ps is not None:
        ctx.set("Loss", ps)


# -------------------------------------------------------------- embedding
def _r_lookup_table(ctx: InferContext):
    ws, ids = ctx.input_shape("W"), ctx.input_shape("Ids")
    idt = ctx.input_dtype("Ids")
    if idt is not None and not _is_int_dtype(idt):
        ctx.fail("lookup_table Ids dtype %s is not integral" % idt)
    if ws is not None and len(ws) != 2:
        ctx.fail("lookup_table W must be 2-D [vocab, dim], got %s"
                 % (tuple(ws),))
    if ids is None or ws is None:
        return
    if len(ids) >= 2 and ids[-1] == 1:
        ids = ids[:-1]
    ctx.set("Out", tuple(ids) + (ws[1],))


register_shape_rule("lookup_table", "lookup_table_v2")(_r_lookup_table)


@register_shape_rule("top_k")
def _r_top_k(ctx):
    xs = ctx.input_shape("X")
    k = int(ctx.attr("k", 1))
    if xs is None:
        ctx.set("Indices", None, dtype="int32")
        return
    if xs[-1] >= 0 and k > xs[-1]:
        ctx.fail("top_k k=%d exceeds last dim %d" % (k, xs[-1]))
    out = tuple(xs[:-1]) + (k,)
    ctx.set("Out", out)
    ctx.set("Indices", out, dtype="int32")


# -------------------------------------------------------------- optimizers
def _r_optimizer(ctx: InferContext):
    ps, gs = ctx.input_shape("Param"), ctx.input_shape("Grad")
    if ps is not None and gs is not None \
            and not shapes_compatible(ps, gs):
        ctx.fail("gradient shape %s does not match parameter shape %s"
                 % (tuple(gs), tuple(ps)))
    out = merge_shapes(ps, gs)
    if out is None:
        return
    for slot in ("ParamOut", "VelocityOut", "Moment1Out", "Moment2Out",
                 "MomentOut", "InfNormOut", "MeanSquareOut", "MeanGradOut",
                 "AvgSquaredGradOut", "AvgSquaredUpdateOut",
                 "SquaredAccumOut", "LinearAccumOut"):
        if slot in ctx.op.outputs:
            ctx.set(slot, out)
    for slot in ("Beta1PowOut", "Beta2PowOut"):
        if slot in ctx.op.outputs:
            ctx.set(slot, (1,))


register_shape_rule("sgd", "momentum", "lars_momentum", "adam", "adamax",
                    "adagrad", "decayed_adagrad", "adadelta", "rmsprop",
                    "ftrl", "lamb")(_r_optimizer)


# ------------------------------------------------------------ quantization
# (ops/quant_ops.py: the fake_quantize simulation family + the real
# int8 pair the quantize_pass inserts. The lowerings emit float scale
# statistics as shape-[1] f32 tensors and — for the real pair — int8
# payloads; declaring those here is what lets the dtype-annotation lint
# catch a var built with the wrong dtype, the topk-int32 class of bug.)
def _r_fake_quantize(ctx: InferContext):
    xs = ctx.input_shape("X")
    if xs is not None:
        ctx.set("Out", xs)
    for slot in ("OutScale", "OutAccum", "OutState"):
        if slot in ctx.op.outputs:
            ctx.set(slot, (1,), dtype="float32")


register_shape_rule("fake_quantize_abs_max",
                    "fake_quantize_range_abs_max",
                    "fake_quantize_moving_average_abs_max")(
                        _r_fake_quantize)


@register_shape_rule("fake_dequantize_max_abs")
def _r_fake_dequantize(ctx):
    xs = ctx.input_shape("X")
    if xs is not None:
        ctx.set("Out", xs)


def _quant_channel_count(ctx: InferContext) -> "Optional[int]":
    xs = ctx.input_shape("X")
    axis = int(ctx.attr("axis", 0))
    if xs is None or not -len(xs) <= axis < len(xs):
        return None
    c = xs[axis]
    return c if c >= 0 else None


@register_shape_rule("quantize_channel_abs_max")
def _r_quantize_channel(ctx):
    xs = ctx.input_shape("X")
    ss = ctx.input_shape("InScale")
    c = _quant_channel_count(ctx)
    if ss is not None and c is not None and is_concrete(ss) \
            and numel(ss) != c:
        ctx.fail("per-channel scale has %d entries but axis %d of X "
                 "has %d channels" % (numel(ss), ctx.attr("axis", 0), c))
    ctx.set("Out", xs, dtype="int8")


@register_shape_rule("dequantize_channel_abs_max")
def _r_dequantize_channel(ctx):
    xs = ctx.input_shape("X")
    ss = ctx.input_shape("Scales")
    c = _quant_channel_count(ctx)
    if ss is not None and c is not None and is_concrete(ss) \
            and numel(ss) != c:
        ctx.fail("per-channel scale has %d entries but axis %d of X "
                 "has %d channels" % (numel(ss), ctx.attr("axis", 0), c))
    ctx.set("Out", xs, dtype="float32")
