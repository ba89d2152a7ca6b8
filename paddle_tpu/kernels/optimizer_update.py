"""Fused optimizer update: one flattened elementwise sweep per group.

The reference runs one update kernel per parameter; composed XLA traces
one jnp expression tree per ``adam``/``sgd`` op. The kernel tier's shape
(fed by PR 7's fusion machinery — ``fuse_kernel_tier_pass`` bundles a
consecutive run of same-hyperparameter optimizer ops into ONE
``fused_optimizer_update`` op): every param/grad/moment flattens into a
single 1-D stream, per-param scalars (bias-corrected learning rate,
decoupled weight decay) broadcast into per-element vectors, and the
whole update is one elementwise sweep. Adam has no cross-element
reduction, so the sweep computes the per-param math exactly — but the
LAYOUT change (one concat in, K splits out) is not free: XLA
materializes the concatenation, so ``sweep_group`` rides ONLY the tuned
pallas dispatch path where the tuner measured the kernel a win; the
fused op's composed default replays each constituent's own registered
lowering instead (bitwise, identical XLA graph —
ops/fused_ops.py::_fused_optimizer_update).

Kernel layout: the 1-D stream reshapes to ``[R, 128]`` (zero-padded; the
VPU's native lane width), rows block by the tuned ``br``. Every operand
is elementwise and same-shaped, so any (multiple-of-8 rows, 128) block
is Mosaic-legal — the candidate grid sweeps occupancy, not legality.

Parity vs the composed fallbacks (``composed_adam_update`` /
``composed_sgd_update`` — the exact expression trees of ops/
optimizer_ops.py with the scalars pre-broadcast): atol 2e-6 at float32
in interpret mode — the same elementwise expression on the same values,
but XLA's FMA contraction differs between the two compilations, so
individual elements can move 1-2 ULP; padding rows compute garbage that
is sliced off. Pinned by tests/test_kernels.py. (The fused op's
COMPOSED path, the default until a tuned entry exists, stays bitwise
with the unfused program — that pin lives in tests/test_optimizer.py.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import assert_mosaic_ok, checked_pallas_call, ceil_to, \
    pad_len, use_interpret
from .registry import register_kernel

__all__ = ["composed_adam_update", "composed_sgd_update", "adam_update",
           "sgd_update", "signature_for", "sweep_group",
           "composed_adam_group", "composed_sgd_group",
           "adam_group_pallas", "sgd_group_pallas",
           "OPT_IN_SLOTS", "OPT_OUT_SLOTS"]

_LANES = 128
_BR_CANDIDATES = (8, 16, 32, 64, 128, 256, 512)

# THE slot tables for fused_optimizer_update: the fusion pass
# (core/passes/kernel_fuse.py) assembles the fused op's ins/outs from
# these and the lowering (ops/fused_ops.py) consumes them — one shared
# definition, so a slot added for one side cannot silently miss the
# other (the core.program.op_effects lesson applied here)
OPT_IN_SLOTS = {
    "adam": ("Param", "Grad", "Moment1", "Moment2", "Beta1Pow",
             "Beta2Pow", "LearningRate"),
    "sgd": ("Param", "Grad", "LearningRate"),
}
OPT_OUT_SLOTS = {
    "adam": ("ParamOut", "Moment1Out", "Moment2Out", "Beta1PowOut",
             "Beta2PowOut"),
    "sgd": ("ParamOut",),
}


def signature_for(n: int, dtype, k: int = 1) -> tuple:
    """Tuner signature: total flattened element count, dtype, and the
    GROUP SIZE (constituent count). The sweep is shape-oblivious in n,
    but k shapes the concat/split wrapper cost the tuner must measure —
    a winner for a 2-param group says nothing about a 40-param one."""
    return (str(jnp.dtype(dtype)), int(n), int(k))


def composed_adam_update(p, g, m, v, lrt, lrwd, *, beta1=0.9, beta2=0.999,
                         epsilon=1e-8, weight_decay=0.0):
    """Adam on flat 1-D streams — the expression tree of ops/
    optimizer_ops.py's ``adam`` with ``lrt`` (bias-corrected lr) and
    ``lrwd`` (schedule lr x decoupled weight decay) pre-broadcast
    per element."""
    m_new = beta1 * m + (1 - beta1) * g
    v_new = beta2 * v + (1 - beta2) * g * g
    p_new = p - lrt * m_new / (jnp.sqrt(v_new) + epsilon)
    if weight_decay:
        p_new = p_new - lrwd * p
    return p_new, m_new, v_new


def composed_sgd_update(p, g, lrv):
    """SGD on flat 1-D streams: ``p - lrv * g`` with the learning rate
    pre-broadcast per element (ops/optimizer_ops.py's ``sgd``)."""
    return (p - lrv * g,)


def _candidates(sig):
    n = sig[1]
    rows = ceil_to(max(n, 1), _LANES) // _LANES
    out = []
    for br in _BR_CANDIDATES:
        if br <= pad_len(rows, br):
            out.append((br,))
    if not out:
        out.append((8,))
    return out


def _check(cfg, sig):
    n = sig[1]
    (br,) = cfg
    rows = ceil_to(max(n, 1), _LANES) // _LANES
    rp = pad_len(rows, br)
    assert_mosaic_ok((min(br, rp), _LANES), (rp, _LANES),
                     "optimizer_update rows")


def _to2d(a, n):
    rows = ceil_to(max(n, 1), _LANES) // _LANES
    flat = jnp.pad(a, (0, rows * _LANES - n))
    return flat.reshape(rows, _LANES)


def _sweep(kern, name, cfg, flats, n, dtype, n_out):
    (br,) = cfg
    rows = ceil_to(max(n, 1), _LANES) // _LANES
    rp = pad_len(rows, br)
    br = min(br, rp)
    ops2d = [jnp.pad(f2, ((0, rp - f2.shape[0]), (0, 0)))
             for f2 in (_to2d(f, n) for f in flats)]
    row = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    outs = checked_pallas_call(
        kern,
        name=name,
        grid=(rp // br,),
        in_specs=[row] * len(ops2d),
        operands=ops2d,
        out_specs=[row] * n_out,
        out_shape=[jax.ShapeDtypeStruct((rp, _LANES), dtype)] * n_out,
        scratch_shapes=[],
        interpret=use_interpret(),
    )
    return tuple(o.reshape(-1)[:n] for o in outs)


def _adam_kernel(p_ref, g_ref, m_ref, v_ref, lrt_ref, lrwd_ref,
                 po_ref, mo_ref, vo_ref, *, beta1, beta2, epsilon,
                 weight_decay):
    p, g = p_ref[...], g_ref[...]
    m_new = beta1 * m_ref[...] + (1 - beta1) * g
    v_new = beta2 * v_ref[...] + (1 - beta2) * g * g
    p_new = p - lrt_ref[...] * m_new / (jnp.sqrt(v_new) + epsilon)
    if weight_decay:
        p_new = p_new - lrwd_ref[...] * p
    po_ref[...] = p_new
    mo_ref[...] = m_new
    vo_ref[...] = v_new


def adam_update(cfg, p, g, m, v, lrt, lrwd, *, beta1=0.9, beta2=0.999,
                epsilon=1e-8, weight_decay=0.0):
    """Flattened Adam sweep: 1-D ``p/g/m/v`` plus per-element ``lrt``
    (bias-corrected lr) and ``lrwd`` (schedule lr x weight decay)
    streams, reshaped ``[R, 128]`` and row-blocked by the tuned
    ``cfg=(br,)`` (None picks 128). Returns ``(p_new, m_new, v_new)``;
    beta-pow rolls stay scalar ops outside the sweep. No grad path —
    optimizer ops are ``no_grad`` by contract."""
    cfg = tuple(cfg) if cfg else (128,)
    kern = functools.partial(
        _adam_kernel, beta1=beta1, beta2=beta2, epsilon=epsilon,
        weight_decay=weight_decay)
    return _sweep(kern, "adam_sweep", cfg, [p, g, m, v, lrt, lrwd],
                  p.size, p.dtype, 3)


def sweep_group(cfg, kind, ins, hyper):
    """One fused optimizer group through the flattened kernel sweep:
    concatenate every param/grad/moment stream, broadcast the per-param
    scalars (bias-corrected lr, schedule-lr x weight decay) per element,
    run ``adam_update``/``sgd_update`` once, split back. ONLY the tuned
    pallas dispatch path takes this — XLA materializes the
    concatenation, so the layout change must be a measured win
    (ops/fused_ops.py::_fused_optimizer_update has the replay-based
    composed default)."""
    ps, gs, lrs = ins["Param"], ins["Grad"], ins["LearningRate"]
    sizes = [p.size for p in ps]
    splits = []
    acc = 0
    for n in sizes[:-1]:
        acc += n
        splits.append(acc)
    cat = lambda xs: jnp.concatenate([a.reshape(-1) for a in xs])
    bcast = lambda scalars: jnp.concatenate(
        [jnp.broadcast_to(sc, (n,)) for sc, n in zip(scalars, sizes)])

    if kind == "sgd":
        lr_sc = [lr.reshape(()).astype(p.dtype)
                 for lr, p in zip(lrs, ps)]
        (p_new,) = sgd_update(cfg, cat(ps), cat(gs), bcast(lr_sc))
        return {"ParamOut": [o.reshape(p.shape) for o, p in
                             zip(jnp.split(p_new, splits), ps)]}

    b1 = hyper.get("beta1", 0.9)
    b2 = hyper.get("beta2", 0.999)
    eps = hyper.get("epsilon", 1e-8)
    wd = hyper.get("weight_decay", 0.0)
    m1s, m2s = ins["Moment1"], ins["Moment2"]
    b1ps, b2ps = ins["Beta1Pow"], ins["Beta2Pow"]
    lrt, lrwd = [], []
    for p, lr, b1p, b2p in zip(ps, lrs, b1ps, b2ps):
        lr_sc = lr.reshape(()).astype(p.dtype)
        b1p_ = b1p.reshape(()).astype(p.dtype)
        b2p_ = b2p.reshape(()).astype(p.dtype)
        lrt.append(lr_sc * jnp.sqrt(1 - b2p_ * b2) / (1 - b1p_ * b1))
        lrwd.append(lr_sc * wd)
    p_new, m_new, v_new = adam_update(
        cfg, cat(ps), cat(gs), cat(m1s), cat(m2s), bcast(lrt),
        bcast(lrwd), beta1=b1, beta2=b2, epsilon=eps, weight_decay=wd)
    return {
        "ParamOut": [o.reshape(p.shape) for o, p in
                     zip(jnp.split(p_new, splits), ps)],
        "Moment1Out": [o.reshape(m.shape) for o, m in
                       zip(jnp.split(m_new, splits), m1s)],
        "Moment2Out": [o.reshape(m.shape) for o, m in
                       zip(jnp.split(v_new, splits), m2s)],
        "Beta1PowOut": [b1p * b1 for b1p in b1ps],
        "Beta2PowOut": [b2p * b2 for b2p in b2ps],
    }


def _sgd_kernel(p_ref, g_ref, lrv_ref, po_ref):
    po_ref[...] = p_ref[...] - lrv_ref[...] * g_ref[...]


def sgd_update(cfg, p, g, lrv):
    """Flattened SGD sweep: ``p - lrv * g`` over the ``[R, 128]`` view,
    row-blocked by the tuned ``cfg=(br,)`` (None picks 128). Returns a
    1-tuple ``(p_new,)`` to mirror the fallback's pytree. No grad path —
    optimizer ops are ``no_grad`` by contract."""
    cfg = tuple(cfg) if cfg else (128,)
    return _sweep(_sgd_kernel, "sgd_sweep", cfg, [p, g, lrv], p.size,
                  p.dtype, 1)


# ---------------------------------------------------- registry entries
# The REGISTERED (tuner-measured) surface is the GROUP: pallas = the
# whole ``sweep_group`` wrapper (concat + per-param scalar broadcasts +
# kernel + K splits — the cost the layout change actually pays),
# composed = the per-param replay shape. Measuring the bare flat-stream
# kernel would let a few-percent kernel win persist a net
# steady-state LOSS once the concat overhead lands (review-confirmed);
# the group signature carries (n_total, K) for exactly this reason.
def _split_sizes(n, k):
    k = max(1, min(int(k), int(n))) if n else 1
    base, rem = divmod(int(n), k)
    return [base + (1 if i < rem else 0) for i in range(k)]


def _group_inputs(kind, sig, rs):
    dt, n, k = sig
    sizes = _split_sizes(n, k)
    mk = lambda s: jnp.asarray((rs.rand(s) + 0.1).astype("float32")) \
        .astype(dt)
    sc = lambda v: jnp.full((1,), v, jnp.float32).astype(dt)
    ins = {
        "Param": [mk(s) for s in sizes],
        "Grad": [mk(s) for s in sizes],
        "LearningRate": [sc(1e-3) for _ in sizes],
    }
    if kind == "adam":
        ins["Moment1"] = [mk(s) for s in sizes]
        ins["Moment2"] = [mk(s) for s in sizes]
        ins["Beta1Pow"] = [sc(0.9) for _ in sizes]
        ins["Beta2Pow"] = [sc(0.999) for _ in sizes]
    return (ins,)


def composed_adam_group(ins, *, beta1=0.9, beta2=0.999, epsilon=1e-8,
                        weight_decay=0.0):
    """Per-param Adam over a slot-dict group — the composed candidate
    mirroring the fused op's replay path (one expression tree per
    param, scalars applied by broadcast)."""
    outs = ([], [], [])
    for p, g, m, v, b1p, b2p, lr in zip(
            ins["Param"], ins["Grad"], ins["Moment1"], ins["Moment2"],
            ins["Beta1Pow"], ins["Beta2Pow"], ins["LearningRate"]):
        lr_sc = lr.reshape(()).astype(p.dtype)
        b1p_ = b1p.reshape(()).astype(p.dtype)
        b2p_ = b2p.reshape(()).astype(p.dtype)
        lrt = lr_sc * jnp.sqrt(1 - b2p_ * beta2) / (1 - b1p_ * beta1)
        pn, mn, vn = composed_adam_update(
            p, g, m, v, lrt, lr_sc * weight_decay, beta1=beta1,
            beta2=beta2, epsilon=epsilon, weight_decay=weight_decay)
        outs[0].append(pn)
        outs[1].append(mn)
        outs[2].append(vn)
    return outs


def composed_sgd_group(ins):
    """Per-param SGD over a slot-dict group (the replay-path shape)."""
    return ([p - lr.reshape(()).astype(p.dtype) * g
             for p, g, lr in zip(ins["Param"], ins["Grad"],
                                 ins["LearningRate"])],)


def _group_sig(args):
    ins = args[0]
    ps = ins["Param"]
    return signature_for(sum(int(p.size) for p in ps), ps[0].dtype,
                         len(ps))


@register_kernel(
    "adam_update",
    fallback=composed_adam_group,
    signature=_group_sig,
    candidates=_candidates,
    check=_check,
    make_inputs=lambda sig, rs: _group_inputs("adam", sig, rs),
    tol="atol 2e-6 at float32 (1-2 ULP FMA contraction), interpret mode",
)
def adam_group_pallas(cfg, ins, *, beta1=0.9, beta2=0.999, epsilon=1e-8,
                      weight_decay=0.0):
    """One fused Adam group through the FULL production wrapper
    (``sweep_group``: concat + per-param scalar broadcast + the
    ``[R, 128]`` kernel at ``cfg=(br,)`` + K splits) — what the tuner
    measures IS what a tuned dispatch runs. Returns per-param output
    lists matching ``composed_adam_group``."""
    hyper = {"beta1": beta1, "beta2": beta2, "epsilon": epsilon,
             "weight_decay": weight_decay}
    out = sweep_group(cfg, "adam", ins, hyper)
    return (out["ParamOut"], out["Moment1Out"], out["Moment2Out"])


@register_kernel(
    "sgd_update",
    fallback=composed_sgd_group,
    signature=_group_sig,
    candidates=_candidates,
    check=_check,
    make_inputs=lambda sig, rs: _group_inputs("sgd", sig, rs),
    tol="atol 2e-6 at float32 (1-2 ULP FMA contraction), interpret mode",
)
def sgd_group_pallas(cfg, ins):
    """One fused SGD group through the full production wrapper (see
    ``adam_group_pallas``). Returns ``([p_new, ...],)``."""
    return (sweep_group(cfg, "sgd", ins, {})["ParamOut"],)
