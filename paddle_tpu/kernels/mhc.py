"""The two halves of a manifold-constrained hyper-connection (mHC,
arXiv:2512.24880 on the hyper-connections of arXiv:2409.19606): the
residual path of a decoder whose token state is ``n`` streams of ``C``
values and not one vector (models/gpt.py ``cfg['residual'] = 'mhc'``).

The stream of a row is kept FLAT, ``X [R, n C]`` with stream ``i`` in
lanes ``i C .. (i + 1) C``: the same bytes as ``[R, n, C]``, and no axis
of ``n`` = 4 for the TPU to pad to a sublane tile of 8. Round a sub-block
``F`` of a layer:

* ``mhc_pre(X, phi, alpha, b)`` -> ``(h [R, C], coef [R, n (n + 2)],
  dev)``. With ``x~ = X / sqrt(mean(X^2) + eps)`` over all ``n C`` values
  of the row, ``H~ = alpha * (x~ phi) + b`` column group by column group
  (``phi = [phi_pre | phi_post | phi_res]``, ``[n C, n + n + n^2]``;
  ``alpha [3]`` one gate a group; ``b`` a bias a column), ``H_pre =
  sigmoid(H~_pre)``, ``H_post = 2 sigmoid(H~_post)``, ``H_res`` the
  Sinkhorn iterate of ``exp(clip(H~_res))``: ``iters`` rounds of columns
  then rows, each divided by its sum plus ``hc_eps`` — doubly stochastic
  up to the iteration's error. ``h = sum_i H_pre[i] X[i]`` is what the
  sub-block's norm reads; ``coef = [H_pre | H_post | H_res]`` row-major;
  ``dev`` the largest ``|row sum - 1|`` or ``|column sum - 1|`` of any
  ``H_res`` of the call (a health reading).
* ``mhc_post(X, y, coef)`` -> ``X' [R, n C]``: ``X'[i] = sum_j
  H_res[i, j] X[j] + H_post[i] y``.

Two forms of each, as ``gmm_composed`` stands beside ``gmm_pallas``:

* composed — ``jax.numpy``: what the CPU runs and what the tests compare
  the kernels with. On a TPU it reads ``X`` three times in ``mhc_pre``
  (statistics, the projection, the mix) and runs a 24-wide projection at
  24 of the MXU's 128 lanes.
* Pallas — ONE pass over ``X`` each, ``block_rows`` rows a grid step
  (a multiple of the 8-row sublane tile; the whole ``n C`` lanes of a
  row). ``mhc_pre``'s kernel takes the sum of squares, the projection
  (``phi`` padded to one 128-lane tile and held in VMEM; ``X`` split
  into a bfloat16 head and a bfloat16 remainder, two MXU passes that
  carry 16 mantissa bits against the bfloat16-stored ``phi``), ``H_pre``
  and the mix from the block while it is in VMEM, and writes ``h`` and
  the raw ``H~``; the mappings (sigmoids, the Sinkhorn rounds, ``dev``)
  are then float32 element-wise arithmetic on ``[n (n + 2), R]``
  coefficient-major vectors, 24 values a row where the stream has
  14,336. ``mhc_post``'s kernel reads ``X``, ``y`` and the 24
  coefficients and writes ``X'`` INTO ``X``'s buffer
  (``input_output_aliases``): the stream is never held twice.

``mhc_pre`` / ``mhc_post`` choose: the kernel where Pallas compiles
(``use_interpret()`` is false: a TPU), the composed form elsewhere;
``paddle_residual_plans_total`` counts which form each lowering took.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import ceil_to, checked_pallas_call, pad_axis, use_interpret

__all__ = ["mhc_pre", "mhc_post", "mhc_pre_composed", "mhc_post_composed",
           "mhc_pre_pallas", "mhc_post_pallas", "mappings", "block_rows",
           "KERNEL_PRE", "KERNEL_POST"]

# the names the device trace and the HLO show the calls under
KERNEL_PRE = "mhc_pre"
KERNEL_POST = "mhc_post"

_LANES = 128
# rows a grid step takes: a block is [rows, n C] float32, double-buffered
# in and (mhc_post) out — 64 rows of 14,336 lanes are 3.7 MB a buffer
_BLOCK_ROWS = 64
_VMEM_LIMIT_BYTES = 48 << 20


def block_rows(rows):
    """Rows one grid step takes of ``rows``: ``_BLOCK_ROWS``, or all of
    a shorter call rounded up to the 8-row sublane tile."""
    return min(_BLOCK_ROWS, ceil_to(int(rows), 8))


def _check(x, n):
    if x.ndim != 2 or x.shape[1] % n:
        raise ValueError("mhc: the stream %s is not [rows, %d * C]"
                         % (x.shape, n))
    return x.shape[1] // n


def mappings(pre, n, *, iters, hc_eps, clamp):
    """``(coef [R, n (n + 2)], dev)`` from the raw ``H~ [R, n (n + 2)]``:
    the two sigmoids and the Sinkhorn rounds, in float32 on
    coefficient-major vectors (one ``[R]`` vector a coefficient, so that
    a round is element-wise arithmetic whatever the backend)."""
    t = pre.astype(jnp.float32).T                          # [n(n+2), R]
    h_pre = jax.nn.sigmoid(t[:n])
    h_post = 2.0 * jax.nn.sigmoid(t[n:2 * n])
    m = jnp.exp(jnp.clip(t[2 * n:], clamp[0], clamp[1]))
    m = m.reshape(n, n, -1)                                # [i, j, R]
    for _ in range(int(iters)):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + hc_eps)   # columns
        m = m / (jnp.sum(m, axis=1, keepdims=True) + hc_eps)   # rows
    dev = jnp.maximum(jnp.max(jnp.abs(jnp.sum(m, axis=0) - 1.0)),
                      jnp.max(jnp.abs(jnp.sum(m, axis=1) - 1.0)))
    coef = jnp.concatenate([h_pre, h_post, m.reshape(n * n, -1)], axis=0)
    return coef.T, dev


def _affine(n, alpha, b):
    """``alpha`` spread over its column groups, and ``b``, as two
    ``[n (n + 2)]`` float32 vectors."""
    a = jnp.concatenate([jnp.broadcast_to(alpha[k].astype(jnp.float32), (w,))
                         for k, w in enumerate((n, n, n * n))])
    return a, b.astype(jnp.float32)


def mhc_pre_composed(x, phi, alpha, b, *, n, eps, iters, hc_eps, clamp):
    """The plain form (module docstring); the projection in float32 at
    the highest precision."""
    C = _check(x, n)
    x = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    proj = jnp.dot(x, phi.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST) * inv
    a, bb = _affine(n, alpha, b)
    coef, dev = mappings(proj * a + bb, n, iters=iters, hc_eps=hc_eps,
                         clamp=clamp)
    h = sum(coef[:, i:i + 1] * x[:, i * C:(i + 1) * C] for i in range(n))
    return h, coef, dev


def mhc_post_composed(x, y, coef, *, n):
    C = _check(x, n)
    x, y = x.astype(jnp.float32), y.astype(jnp.float32)
    return jnp.concatenate([
        sum(coef[:, 2 * n + n * i + j:2 * n + n * i + j + 1]
            * x[:, j * C:(j + 1) * C] for j in range(n))
        + coef[:, n + i:n + i + 1] * y for i in range(n)], axis=-1)


def _pre_kernel(x_ref, ab_ref, *refs, n, C, eps):
    *phi_refs, h_ref, t_ref = refs
    x = x_ref[...]                                         # [rows, n C]
    ss = jnp.sum(x * x, axis=-1, keepdims=True)
    # X as a bfloat16 head and a bfloat16 remainder (16 mantissa bits),
    # against phi's bfloat16 parts: the head of phi meets both, a
    # remainder of phi (a float32-stored phi has one) the head of X
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    proj = None
    for k, ref in enumerate(phi_refs):
        phi = ref[...]
        for part in (hi, lo)[:2 - k]:
            term = jnp.dot(part, phi, precision=jax.lax.Precision.DEFAULT,
                           preferred_element_type=jnp.float32)
            proj = term if proj is None else proj + term
    inv = jax.lax.rsqrt(ss * (1.0 / (n * C)) + eps)
    t = proj * inv * ab_ref[0:1, :] + ab_ref[1:2, :]       # [rows, 128]
    t_ref[...] = t
    g = jax.nn.sigmoid(t)
    h = g[:, 0:1] * x[:, :C]
    for i in range(1, n):
        h = h + g[:, i:i + 1] * x[:, i * C:(i + 1) * C]
    h_ref[...] = h


def mhc_pre_pallas(x, phi, alpha, b, *, n, eps, iters, hc_eps, clamp,
                   interpret=None):
    """The kernel and the mappings after it (module docstring)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C = _check(x, n)
    R, k = x.shape[0], n * (n + 2)
    if C % _LANES or k > _LANES:
        raise ValueError("mhc_pre: no block plan for a stream of %d x %d"
                         % (n, C))
    if interpret is None:
        interpret = use_interpret()
    tr = block_rows(R)
    xp = pad_axis(x.astype(jnp.float32), 0, ceil_to(R, tr))
    Rp = xp.shape[0]
    a, bb = _affine(n, alpha, b)
    ab = pad_axis(jnp.stack([a, bb]), 1, _LANES)           # [2, 128]
    phis = [pad_axis(phi.astype(jnp.bfloat16), 1, _LANES)]  # [n C, 128]
    if phi.dtype != jnp.bfloat16:
        phis.append(pad_axis(
            (phi.astype(jnp.float32) - phis[0][:, :k].astype(jnp.float32))
            .astype(jnp.bfloat16), 1, _LANES))
    h, t = checked_pallas_call(
        functools.partial(_pre_kernel, n=n, C=C, eps=float(eps)),
        name=KERNEL_PRE, grid=(Rp // tr,),
        in_specs=[pl.BlockSpec((tr, n * C), lambda r: (r, 0)),
                  pl.BlockSpec((2, _LANES), lambda r: (0, 0))]
        + [pl.BlockSpec((n * C, _LANES), lambda r: (0, 0))] * len(phis),
        operands=(xp, ab, *phis),
        out_specs=[pl.BlockSpec((tr, C), lambda r: (r, 0)),
                   pl.BlockSpec((tr, _LANES), lambda r: (r, 0))],
        out_shape=[jax.ShapeDtypeStruct((Rp, C), jnp.float32),
                   jax.ShapeDtypeStruct((Rp, _LANES), jnp.float32)],
        scratch_shapes=[], interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES))
    coef, dev = mappings(t[:R, :k], n, iters=iters, hc_eps=hc_eps,
                         clamp=clamp)
    return h[:R], coef, dev


def _post_kernel(x_ref, y_ref, c_ref, o_ref, *, n, C):
    y, c = y_ref[...], c_ref[...]
    xs = [x_ref[:, j * C:(j + 1) * C] for j in range(n)]
    for i in range(n):
        at = 2 * n + n * i
        acc = c[:, n + i:n + i + 1] * y
        for j in range(n):
            acc = acc + c[:, at + j:at + j + 1] * xs[j]
        o_ref[:, i * C:(i + 1) * C] = acc


def mhc_post_pallas(x, y, coef, *, n, interpret=None):
    """The kernel (module docstring): ``X'`` takes ``X``'s buffer."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C = _check(x, n)
    R, k = x.shape[0], n * (n + 2)
    if C % _LANES or coef.shape != (R, k) or y.shape != (R, C):
        raise ValueError("mhc_post: no block plan for a stream %s, y %s "
                         "and coefficients %s" % (x.shape, y.shape,
                                                  coef.shape))
    if interpret is None:
        interpret = use_interpret()
    tr = block_rows(R)
    Rp = ceil_to(R, tr)
    out = checked_pallas_call(
        functools.partial(_post_kernel, n=n, C=C),
        name=KERNEL_POST, grid=(Rp // tr,),
        in_specs=[pl.BlockSpec((tr, n * C), lambda r: (r, 0)),
                  pl.BlockSpec((tr, C), lambda r: (r, 0)),
                  pl.BlockSpec((tr, k), lambda r: (r, 0))],
        operands=(pad_axis(x.astype(jnp.float32), 0, Rp),
                  pad_axis(y.astype(jnp.float32), 0, Rp),
                  pad_axis(coef.astype(jnp.float32), 0, Rp)),
        out_specs=pl.BlockSpec((tr, n * C), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, n * C), jnp.float32),
        scratch_shapes=[], interpret=interpret,
        input_output_aliases={0: 0},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES))
    return out[:R]


def _note_plan(op, kernel, n):
    from ..observe.families import RESIDUAL_PLANS

    RESIDUAL_PLANS.labels(form="mhc", op=op, kernel=kernel,
                          streams=str(n)).inc()


def _takes_kernel(x, n):
    from . import kernels_enabled

    return kernels_enabled() and not use_interpret() \
        and x.shape[1] % (n * _LANES) == 0 and n * (n + 2) <= _LANES


def mhc_pre(x, phi, alpha, b, *, n, eps, iters, hc_eps, clamp):
    """``mhc_pre`` in whichever form this lowering can take (module
    docstring); decided from the operands alone."""
    kw = dict(n=n, eps=eps, iters=iters, hc_eps=hc_eps, clamp=clamp)
    if _takes_kernel(x, n):
        _note_plan("pre", "pallas", n)
        return mhc_pre_pallas(x, phi, alpha, b, interpret=False, **kw)
    _note_plan("pre", "composed", n)
    return mhc_pre_composed(x, phi, alpha, b, **kw)


def mhc_post(x, y, coef, *, n):
    if _takes_kernel(x, n):
        _note_plan("post", "pallas", n)
        return mhc_post_pallas(x, y, coef, n=n, interpret=False)
    _note_plan("post", "composed", n)
    return mhc_post_composed(x, y, coef, n=n)
