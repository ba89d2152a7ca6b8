"""Fused layernorm+residual: the transformer's per-layer hot path.

Every pre-norm block ends ``x = x + h`` and the NEXT block immediately
normalizes that sum — composed, that is two HBM round-trips over the
residual stream per layer. The fused kernel reads x and h once, emits
the new residual stream (``s = x + h``) AND its layer norm in the same
VMEM-resident sweep, plus the per-row mean/variance the program's
backward ops read.

Layout: 2-D ``[N, D]`` rows (the op lowering flattens ``[B, S, D]`` with
``begin_norm_axis`` to ``N = B*S``); ``scale``/``bias`` ride as ``[1, D]``
operands (block equal to the array dims — Mosaic-legal for any D, the
attention round-2 lesson applied). Rows block by the tuned ``bn``
(multiple of 8, or one block equal to N); N pads up with zero rows whose
outputs are sliced off (zero rows normalize to finite garbage and their
zero upstream grads kill every backward contribution).

Backward is its own Pallas kernel: per-row ``dx`` from the saved
mean/variance, with ``dscale``/``dbias`` accumulated across the row grid
into a revisited ``[1, D]`` output block. The residual stream's
cotangent (``gres``) adds straight into ``dx`` — x and h enter
symmetrically through the sum, so both get the same gradient.

Parity vs ``composed_layernorm_residual`` (the registered fallback, one
jnp expression mirroring ops/nn.py's ``layer_norm`` lowering after an
``elementwise_add``): forward atol 1e-5, backward atol 5e-5 at float32
(reduction order inside a row block differs from XLA's), pinned by
tests/test_kernels.py in interpret mode on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import assert_mosaic_ok, checked_pallas_call, pad_axis, \
    pad_len, use_interpret
from .registry import register_kernel

__all__ = ["composed_layernorm_residual", "layernorm_residual",
           "signature_for"]

_BN_CANDIDATES = (8, 16, 32, 64, 128, 256)


def signature_for(n: int, d: int, dtype) -> tuple:
    """Tuner signature: the flattened row count and the normalized width
    (batch/sequence factor into N — one tuned entry serves every
    leading-dim layout with the same totals)."""
    return (str(jnp.dtype(dtype)), int(n), int(d))


def composed_layernorm_residual(x, r, scale, bias, *, eps=1e-5):
    """The composed-XLA math (numerics reference + the tuner's
    'composed' candidate): elementwise add, then exactly the layer_norm
    lowering's expression (ops/nn.py) on 2-D rows."""
    s = x + r
    mean = jnp.mean(s, axis=-1, keepdims=True)
    var = jnp.var(s, axis=-1, keepdims=True)
    y = (s - mean) * lax.rsqrt(var + eps)
    y = y * scale.reshape(1, -1) + bias.reshape(1, -1)
    return y, s, mean.astype(jnp.float32), var.astype(jnp.float32)


def _candidates(sig):
    _dt, n, _d = sig
    out = []
    for bn in _BN_CANDIDATES:
        if bn <= pad_len(n, bn):
            out.append((bn,))
    if not any(c == (n,) for c in out) and n % 8 != 0:
        out.append((n,))  # single full block: legal for any N
    return out


def _check(cfg, sig):
    _dt, n, d = sig
    (bn,) = cfg
    np_ = pad_len(n, bn)
    bn_eff = min(bn, np_)
    assert_mosaic_ok((bn_eff, d), (np_, d), "layernorm_residual rows")
    assert_mosaic_ok((1, d), (1, d), "layernorm_residual scale/bias")


def _make_inputs(sig, rs):
    dt, n, d = sig
    mk = lambda *shape: jnp.asarray(rs.randn(*shape).astype("float32")) \
        .astype(dt)
    return (mk(n, d), mk(n, d), mk(d), mk(d))


# ---------------------------------------------------------------- forward
def _fwd_kernel(x_ref, r_ref, sc_ref, b_ref, y_ref, s_ref, m_ref, v_ref,
                *, eps):
    x = x_ref[...].astype(jnp.float32)
    r = r_ref[...].astype(jnp.float32)
    s = x + r                                       # [bn, D] f32
    mean = jnp.mean(s, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(s - mean), axis=-1, keepdims=True)
    y = (s - mean) * lax.rsqrt(var + eps)
    y = y * sc_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    s_ref[...] = s.astype(s_ref.dtype)
    y_ref[...] = y.astype(y_ref.dtype)
    m_ref[...] = mean
    v_ref[...] = var


def _forward_pallas(cfg, x, r, scale, bias, eps):
    n, d = x.shape
    (bn,) = cfg
    np_ = pad_len(n, bn)
    bn = min(bn, np_)
    nb = np_ // bn
    xp, rp = pad_axis(x, 0, np_), pad_axis(r, 0, np_)
    sc2, b2 = scale.reshape(1, d), bias.reshape(1, d)
    row = pl.BlockSpec((bn, d), lambda i: (i, 0))
    vec = pl.BlockSpec((1, d), lambda i: (0, 0))
    col = pl.BlockSpec((bn, 1), lambda i: (i, 0))
    y, s, mean, var = checked_pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        name="layernorm_residual_fwd",
        grid=(nb,),
        in_specs=[row, row, vec, vec],
        operands=[xp, rp, sc2, b2],
        out_specs=[row, row, col, col],
        out_shape=[
            jax.ShapeDtypeStruct((np_, d), x.dtype),
            jax.ShapeDtypeStruct((np_, d), x.dtype),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        ],
        scratch_shapes=[],
        interpret=use_interpret(),
    )
    return y[:n], s[:n], mean[:n], var[:n]


# --------------------------------------------------------------- backward
def _bwd_kernel(s_ref, m_ref, v_ref, sc_ref, gy_ref, gr_ref,
                dx_ref, dsc_ref, db_ref, *, eps):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dsc_ref[...] = jnp.zeros_like(dsc_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    s = s_ref[...].astype(jnp.float32)
    mean = m_ref[...]                               # [bn, 1]
    var = v_ref[...]
    rstd = lax.rsqrt(var + eps)
    xhat = (s - mean) * rstd                        # [bn, D]
    gy = gy_ref[...].astype(jnp.float32)
    gyh = gy * sc_ref[...].astype(jnp.float32)
    mg = jnp.mean(gyh, axis=-1, keepdims=True)
    mgx = jnp.mean(gyh * xhat, axis=-1, keepdims=True)
    ds = rstd * (gyh - mg - xhat * mgx)
    dx_ref[...] = (ds + gr_ref[...].astype(jnp.float32)) \
        .astype(dx_ref.dtype)
    # per-feature grads accumulate across the row grid into the one
    # revisited [1, D] output block (sequential TPU grid)
    dsc_ref[...] += jnp.sum(gy * xhat, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(gy, axis=0, keepdims=True)


def _backward_pallas(cfg, s, mean, var, scale, gy, gres, eps):
    n, d = s.shape
    (bn,) = cfg
    np_ = pad_len(n, bn)
    bn = min(bn, np_)
    nb = np_ // bn
    sp = pad_axis(s, 0, np_)
    mp, vp = pad_axis(mean, 0, np_), pad_axis(var, 0, np_)
    gyp, grp = pad_axis(gy, 0, np_), pad_axis(gres, 0, np_)
    sc2 = scale.reshape(1, d)
    row = pl.BlockSpec((bn, d), lambda i: (i, 0))
    vec = pl.BlockSpec((1, d), lambda i: (0, 0))
    col = pl.BlockSpec((bn, 1), lambda i: (i, 0))
    dx, dsc, db = checked_pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        name="layernorm_residual_bwd",
        grid=(nb,),
        in_specs=[row, col, col, vec, row, row],
        operands=[sp, mp, vp, sc2, gyp, grp],
        out_specs=[row, vec, vec],
        out_shape=[
            jax.ShapeDtypeStruct((np_, d), s.dtype),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        scratch_shapes=[],
        interpret=use_interpret(),
    )
    return dx[:n], dsc.reshape(d), db.reshape(d)


# ------------------------------------------------------------- custom vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 5))
def _ln_res(cfg, x, r, scale, bias, eps):
    return _forward_pallas(cfg, x, r, scale, bias, eps)


def _ln_res_fwd(cfg, x, r, scale, bias, eps):
    y, s, mean, var = _forward_pallas(cfg, x, r, scale, bias, eps)
    return (y, s, mean, var), (s, mean, var, scale)


def _ln_res_bwd(cfg, eps, res, gs):
    s, mean, var, scale = res
    gy, gres, gmean, gvar = gs
    dx, dsc, db = _backward_pallas(cfg, s, mean, var, scale,
                                   gy.astype(s.dtype),
                                   gres.astype(s.dtype), eps)
    # mean/variance cotangents (zero for program use — both outputs are
    # stop_gradient vars — but exact for direct callers): d mean/d s_j
    # = 1/D, d var/d s_j = 2 (s_j - mean)/D
    d = s.shape[-1]
    extra = gmean.astype(jnp.float32) / d \
        + gvar.astype(jnp.float32) * 2.0 \
        * (s.astype(jnp.float32) - mean) / d
    dx = (dx.astype(jnp.float32) + extra).astype(s.dtype)
    return dx, dx, dsc.astype(scale.dtype), db.astype(scale.dtype)


_ln_res.defvjp(_ln_res_fwd, _ln_res_bwd)


@register_kernel(
    "layernorm_residual",
    fallback=composed_layernorm_residual,
    signature=lambda args: signature_for(args[0].shape[0],
                                         args[0].shape[1], args[0].dtype),
    candidates=_candidates,
    check=_check,
    make_inputs=_make_inputs,
    tol="fwd atol 1e-5, bwd atol 5e-5 (float32, interpret mode)",
)
def layernorm_residual(cfg, x, r, scale, bias, *, eps=1e-5):
    """Fused residual-add + layer norm over 2-D rows ``[N, D]``:
    returns ``(y, s, mean, var)`` where ``s = x + r`` is the new
    residual stream, ``y = layer_norm(s) * scale + bias``, and
    ``mean``/``var`` are the per-row f32 statistics ``[N, 1]`` the
    backward ops re-derive from. ``cfg=(bn,)`` is the tuned row-block
    size (None picks 128); differentiable via a paired backward kernel
    (see module docstring for the parity tolerances)."""
    cfg = tuple(cfg) if cfg else (128,)
    return _ln_res(cfg, x, r, scale, bias, float(eps))
