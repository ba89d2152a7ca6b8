"""The gated delta rule (arXiv:2412.06464) in the two forms a served
sequence needs (models/gpt.py ``layer_types`` entry ``"delta"``).

A layer has ``Hv`` value heads of ``Dv`` values over ``Hk`` key heads of
``Dk``; value head ``h`` reads the query and the key of key head ``h //
(Hv / Hk)``. With ``q`` and ``k`` l2-normalised (``normed``), ``g_t <= 0``
the log of the head's decay and ``0 < beta_t < 1`` its writing strength, a
head keeps one ``[Dk, Dv]`` matrix ``S`` and READS IT BEFORE IT WRITES
IT::

    S   <- exp(g_t) S
    u_t  = beta_t (v_t - S^T k_t)          (what the state got wrong of v_t)
    S   <- S + k_t u_t^T
    o_t  = S^T q_t

The state is ``[B, Hv, Dk, Dv]`` float32: a head's keys run down the
sublanes and its values along the lanes, so ``S^T k`` and ``S^T q`` are
sums of vector registers over the sublanes with the vector as one column,
``k u^T`` is that column times a row, and ``o`` comes out in ``v``'s own
``[.., Hv Dv]`` order.

* ``delta_update`` — ONE token a slot (the decode step). The Pallas
  kernel has a grid over the slots; a step reads ALL of a slot's heads
  once (2 MB at 32 heads of 128 x 128), corrects each from its own ``S^T
  k``, writes it INTO THE SAME BUFFER (``input_output_aliases``) and
  reads ``S^T q`` out of the new state in the same pass, on the VPU. The
  token's per-lane rows (decay, beta, v: ``[3 Hv, Dv]``) and its columns
  (head ``h``'s key in lane ``h``, its query in lane ``Hv + h`` of one
  ``[Dk, 128]`` tile) are built by XLA from the step's activations: one
  tile of columns a SLOT is why the grid is not over heads (a tile a head
  would be as many bytes as the head's state). Bound by bytes.
* ``delta_scan`` — a whole prompt (the prefill), CHUNKED. Inside a chunk
  of ``Q`` positions, with ``c_t`` the running sum of ``g`` from the
  chunk's start, ``Gamma_ts = exp(c_t - c_s)`` and ``S0`` the state
  before the chunk, the rows ``u_t`` solve a UNIT LOWER TRIANGULAR
  system::

      (I + L) U = diag(beta) (V - diag(exp c) K S0)
      L = strict_lower(diag(beta) (K K^T .* Gamma))
      O = diag(exp c) Q S0 + lower(Q K^T .* Gamma) U
      S = exp(c_Q) S0 + (K .* exp(c_Q - c))^T U

  The inverse of ``I + L`` is built BY HALVES: of a block ``[[A, 0], [C,
  B]]`` it is ``[[A', 0], [-B' C A', B']]``, so with ``T`` the inverse of
  the diagonal blocks of ``b`` rows and ``E`` the blocks between the
  halves of every ``2 b``, ``T <- T - T E T`` for ``b`` = 1, 2, 4, ...:
  two ``[Q, Q]`` products a doubling under masks, no slicing — forward
  substitution in blocks, and as stable. (The chip sweep that chose it,
  docs/KERNELS.md, also timed the nilpotent form ``(I - L)(I + L^2)(I +
  L^4) ...``: a third faster and the inverse only on paper — where keys
  repeat and ``beta`` nears 1 the powers of ``L`` grow like binomial
  coefficients and float32 loses every digit at a chunk of 64.) The grid
  is (slot x key head, chunk), chunks innermost and sequential, the ``Hv /
  Hk`` value heads of a key head one after another over the one ``K K^T``
  and ``Q K^T`` they share, their states carried in VMEM scratch. A
  prompt that is no multiple of ``Q`` is padded with positions of ``k =
  0``, ``beta = 0`` and ``g = 0``, which neither decay nor feed the
  state.

Each has a composed ``jax.numpy`` form with the same signature and the
same state layout: what the CPU runs, what ``PADDLE_TPU_KERNELS=0`` runs
on the chip, and what the tests compare the kernels with (the scan's
composed form is chunked too and solves with ``solve_triangular``; the
token-by-token form is the reference's, tests/references/).
``paddle_delta_plans_total`` counts which form and which chunk each
lowering took.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import ceil_to, checked_pallas_call, pad_axis, use_interpret

__all__ = ["delta_update", "delta_scan", "delta_update_composed",
           "delta_update_pallas", "delta_scan_composed",
           "delta_scan_pallas", "normed", "state_shape", "scan_chunk",
           "KERNEL_UPDATE", "KERNEL_SCAN", "CONV_TAPS", "CHUNK", "L2_EPS"]

# the names the device trace and the HLO show the calls under
KERNEL_UPDATE = "delta_update"
KERNEL_SCAN = "delta_scan"

# taps of the causal depth-wise convolution in front of q, k and v
CONV_TAPS = 4
# what the l2 norm of q and of k adds under its root
L2_EPS = 1e-6
# positions a chunk of the scan (the chip sweep's choice, docs/KERNELS.md)
CHUNK = 64

_LANES = 128
_VMEM_LIMIT_BYTES = 64 << 20
# the most bytes of state one step of the update kernel holds (in and out,
# double-buffered: four times this of VMEM)
_UPDATE_BLOCK_BYTES = 4 << 20
_HI = jax.lax.Precision.HIGHEST


def state_shape(batch, v_heads, k_dim, v_dim):
    """``[B, Hv, Dk, Dv]``: the layout a layer's state is kept in (module
    docstring)."""
    return (int(batch), int(v_heads), int(k_dim), int(v_dim))


def normed(q, k):
    """``q`` and ``k`` ``[..., Dk]`` as the recurrence takes them: each
    over the root of its squared sum plus ``L2_EPS``, ``q`` also over
    ``sqrt(Dk)``."""
    q, k = q.astype(jnp.float32), k.astype(jnp.float32)

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + L2_EPS)

    return unit(q) * (q.shape[-1] ** -0.5), unit(k)


def scan_chunk(T):
    """The chunk a prompt of ``T`` positions is scanned in: ``CHUNK``, or
    the prompt itself (to whole sublanes) where it is shorter."""
    return min(CHUNK, ceil_to(int(T), 8))


def _dims(q, v):
    """(Hk, Dk, Hv, Dv, J) of a call from its operands ``q [.., Hk, Dk]``
    and ``v [.., Hv, Dv]``."""
    (Hk, Dk), (Hv, Dv) = q.shape[-2:], v.shape[-2:]
    if Hv % Hk:
        raise ValueError("delta rule: %d value heads over %d key heads"
                         % (Hv, Hk))
    return Hk, Dk, Hv, Dv, Hv // Hk


# ------------------------------------------------------------ one token
def delta_update_composed(state, q, k, v, g, beta):
    """``(y [B, Hv, Dv], state')``: ``state [B, Hv, Dk, Dv]``, ``q`` /
    ``k`` ``[B, Hk, Dk]`` (``normed``), ``v [B, Hv, Dv]``, ``g`` / ``beta``
    ``[B, Hv]``."""
    _Hk, _Dk, _Hv, _Dv, J = _dims(q, v)
    q, k = jnp.repeat(q, J, axis=1), jnp.repeat(k, J, axis=1)
    s = state * jnp.exp(g)[:, :, None, None]
    u = beta[:, :, None] * (v - jnp.sum(s * k[..., None], axis=2))
    s = s + k[..., None] * u[:, :, None, :]
    return jnp.sum(s * q[..., None], axis=2), s


def _update_kernel(s_ref, r_ref, c_ref, o_ref, y_ref, *, Hv):
    rows, cols = r_ref[0], c_ref[0]           # [3 Hv.., Dv], [Dk, 128]
    for h in range(Hv):
        kc, qc = cols[:, h:h + 1], cols[:, Hv + h:Hv + h + 1]
        s = s_ref[0, h] * rows[h:h + 1]
        u = rows[Hv + h:Hv + h + 1] * (
            rows[2 * Hv + h:2 * Hv + h + 1]
            - jnp.sum(s * kc, axis=0, keepdims=True))
        s = s + kc * u
        o_ref[0, h] = s
        y_ref[0, h:h + 1] = jnp.sum(s * qc, axis=0, keepdims=True)


def _update_plan(state_shape_):
    """The state's block a step, or None where the kernel has none: all
    of a slot's heads, whose columns share one tile."""
    _B, Hv, Dk, Dv = (int(d) for d in state_shape_)
    if Dk % 8 or Dv % _LANES or 2 * Hv > _LANES \
            or Hv * Dk * Dv * 4 > _UPDATE_BLOCK_BYTES:
        return None
    return (1, Hv, Dk, Dv)


def delta_update_pallas(state, q, k, v, g, beta, *, interpret=None):
    """One token a slot into ``state [B, Hv, Dk, Dv]``, in place: a grid
    over the slots, each step one read and one write of the slot's heads
    (``input_output_aliases`` ties the state to the output) and the ``[Hv,
    Dv]`` of ``y``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block = _update_plan(state.shape)
    if block is None:
        raise ValueError("delta_update: no block plan for a state %s"
                         % (state.shape,))
    _Hk, Dk, Hv, Dv, J = _dims(q, v)
    B = v.shape[0]
    if interpret is None:
        interpret = use_interpret()
    f32 = jnp.float32
    rows = pad_axis(jnp.concatenate([
        jnp.broadcast_to(jnp.exp(g.astype(f32))[..., None], (B, Hv, Dv)),
        jnp.broadcast_to(beta.astype(f32)[..., None], (B, Hv, Dv)),
        v.astype(f32)], axis=1), 1, ceil_to(3 * Hv, 8))
    cols = pad_axis(jnp.swapaxes(jnp.concatenate(
        [jnp.repeat(k.astype(f32), J, axis=1),
         jnp.repeat(q.astype(f32), J, axis=1)], axis=1), 1, 2), 2, _LANES)
    new, y = checked_pallas_call(
        functools.partial(_update_kernel, Hv=Hv),
        name=KERNEL_UPDATE, grid=(B,),
        in_specs=[pl.BlockSpec(block, lambda b: (b, 0, 0, 0)),
                  pl.BlockSpec((1,) + rows.shape[1:], lambda b: (b, 0, 0)),
                  pl.BlockSpec((1, Dk, _LANES), lambda b: (b, 0, 0))],
        operands=(state, rows, cols),
        out_specs=[pl.BlockSpec(block, lambda b: (b, 0, 0, 0)),
                   pl.BlockSpec((1, Hv, Dv), lambda b: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, Hv, Dv), f32)],
        scratch_shapes=[], interpret=interpret,
        input_output_aliases={0: 0},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES))
    return y, new


# --------------------------------------------------------- whole prompt
def _chunked(q, k, v, g, beta, chunk):
    """The scan's operands padded to whole chunks (``k = 0``, ``beta = 0``,
    ``g = 0`` there) and the within-chunk inclusive sums of ``g``: ``(q,
    k, v, c, beta, T)`` with ``c [B, Tp, Hv]``."""
    T = q.shape[1]
    Tp = ceil_to(T, chunk)
    q, k, v, g, beta = (pad_axis(t.astype(jnp.float32), 1, Tp)
                        for t in (q, k, v, g, beta))
    c = jnp.cumsum(g.reshape(g.shape[0], Tp // chunk, chunk, -1),
                   axis=2).reshape(g.shape)
    return q, k, v, c, beta, T


def delta_scan_composed(q, k, v, g, beta, *, chunk=None):
    """``(y [B, T, Hv, Dv], state [B, Hv, Dk, Dv])`` from ``q`` / ``k``
    ``[B, T, Hk, Dk]`` (``normed``), ``v [B, T, Hv, Dv]``, ``g`` /
    ``beta`` ``[B, T, Hv]``, the state zero before the sequence. Chunked
    as the kernel is, a ``lax.scan`` over the chunks; the triangular
    system by ``solve_triangular``."""
    from jax.scipy.linalg import solve_triangular

    _Hk, Dk, Hv, Dv, J = _dims(q, v)
    B = q.shape[0]
    Q = scan_chunk(q.shape[1]) if chunk is None else int(chunk)
    q, k, v, c, beta, T = _chunked(q, k, v, g, beta, Q)
    nc = q.shape[1] // Q

    def per_chunk(t):             # [B, Tp, H, ...] -> [nc, B, H, Q, ...]
        t = t.reshape((B, nc, Q) + t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 1, 0), 2, 3)

    qs = per_chunk(jnp.repeat(q, J, axis=2))
    ks = per_chunk(jnp.repeat(k, J, axis=2))
    vs, cs, bs = per_chunk(v), per_chunk(c), per_chunk(beta)
    low = jnp.tril(jnp.ones((Q, Q), bool))
    strict = jnp.tril(jnp.ones((Q, Q), bool), -1)
    eye = jnp.eye(Q, dtype=jnp.float32)

    def step(s, xs):
        qc, kc, vc, cc, bc = xs   # [B, Hv, Q, D..], cc / bc [B, Hv, Q]
        gam = jnp.exp(jnp.where(low, cc[..., :, None] - cc[..., None, :],
                                -jnp.inf))
        kk = jnp.einsum("bhtd,bhsd->bhts", kc, kc, precision=_HI)
        qk = jnp.einsum("bhtd,bhsd->bhts", qc, kc, precision=_HI)
        lower = jnp.where(strict, bc[..., None] * gam * kk, 0.0)
        ec = jnp.exp(cc)[..., None]
        rhs = bc[..., None] * (vc - ec * jnp.einsum(
            "bhtd,bhdv->bhtv", kc, s, precision=_HI))
        u = solve_triangular(eye + lower, rhs, lower=True,
                             unit_diagonal=True)
        y = ec * jnp.einsum("bhtd,bhdv->bhtv", qc, s, precision=_HI) \
            + jnp.einsum("bhts,bhsv->bhtv", qk * gam, u, precision=_HI)
        tot = cc[..., -1]
        w = jnp.exp(tot[..., None] - cc)
        s = jnp.exp(tot)[..., None, None] * s + jnp.einsum(
            "bhsd,bhsv->bhdv", kc * w[..., None], u, precision=_HI)
        return s, y

    s0 = jnp.zeros((B, Hv, Dk, Dv), jnp.float32)
    s, ys = jax.lax.scan(step, s0, (qs, ks, vs, cs, bs))
    # [nc, B, Hv, Q, Dv] -> [B, Tp, Hv, Dv]
    y = jnp.moveaxis(jnp.moveaxis(ys, 0, 1), 2, 3).reshape(B, nc * Q, Hv, Dv)
    return y[:, :T], s


def _scan_kernel(q_ref, k_ref, kt_ref, v_ref, col_ref, row_ref, tot_ref,
                 y_ref, so_ref, s_ref, *, J, Q):
    from jax.experimental import pallas as pl

    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    def dot(a, b):
        return jnp.dot(a, b, precision=_HI,
                       preferred_element_type=jnp.float32)

    qc, kc, kt = q_ref[0], k_ref[0], kt_ref[0, 0]      # [Q, Dk] x2, [Dk, Q]
    kk, qk = dot(kc, kt), dot(qc, kt)                  # [t, s]
    t_i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    s_i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    apart = t_i ^ s_i          # < n: both in one aligned block of n
    col, row, tot = col_ref[0], row_ref[0, 0], tot_ref[0, 0]
    for j in range(J):
        ccol, bcol = col[:, j:j + 1], col[:, J + j:J + j + 1]   # [Q, 1]
        crow, wrow = row[j:j + 1], row[J + j:J + j + 1]         # [1, Q]
        gam = jnp.exp(jnp.where(t_i >= s_i, ccol - crow, -1e30))
        lower = jnp.where(t_i > s_i, bcol * gam * kk, 0.0)
        prev, ec = s_ref[j], jnp.exp(ccol)
        rhs = bcol * (v_ref[0, j] - ec * dot(kc, prev))
        # the inverse of I + L by halves: of a block [[A, 0], [C, B]] it
        # is [[A', 0], [-B' C A', B']], so with T the inverse of the
        # diagonal blocks of b rows and E the blocks C between the halves
        # of every 2 b, T <- T - T E T; b = 1, 2, 4, ...
        inv = jnp.where(t_i == s_i, 1.0,
                        jnp.where(apart < 2, -lower, 0.0))
        b = 2
        while b < Q:
            between = jnp.where((apart >= b) & (apart < 2 * b), lower, 0.0)
            inv = inv - dot(inv, dot(between, inv))
            b *= 2
        u = dot(inv, rhs)
        y_ref[0, j] = ec * dot(qc, prev) + dot(qk * gam, u)
        s_ref[j] = jnp.exp(tot[j:j + 1]) * prev + dot(
            kt * jnp.exp(wrow), u)

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        so_ref[0] = s_ref[...]


def _scan_plan(Hk, Dk, Hv, Dv, chunk):
    """Whether the kernel has a block plan for these widths at ``chunk``
    (a power of two: the inverse is built by halves)."""
    Q = int(chunk)
    return not (Hv % Hk or Dk % _LANES or Dv % _LANES or Q % 8
                or Q & (Q - 1) or 2 * (Hv // Hk) > 8)


def delta_scan_pallas(q, k, v, g, beta, *, chunk=None, interpret=None):
    """The chunked scan of a whole prompt (module docstring): a grid over
    (slot x key head, chunk), chunks innermost and sequential with the
    states of the key head's value heads in VMEM scratch."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Hk, Dk, Hv, Dv, J = _dims(q, v)
    Q = scan_chunk(q.shape[1]) if chunk is None else int(chunk)
    if not _scan_plan(Hk, Dk, Hv, Dv, Q):
        raise ValueError("delta_scan: no block plan for q %s, v %s at chunk "
                         "%d" % (q.shape, v.shape, Q))
    if interpret is None:
        interpret = use_interpret()
    B = q.shape[0]
    q, k, v, c, beta, T = _chunked(q, k, v, g, beta, Q)
    Tp = q.shape[1]
    nc, BH = Tp // Q, B * Hk

    def grouped(t):               # [B, Tp, Hk, ...] -> [B Hk, Tp, ...]
        return jnp.moveaxis(t, 2, 1).reshape((BH, Tp) + t.shape[3:])

    qg, kg = grouped(q), grouped(k)                    # [BH, Tp, Dk]
    ktg = jnp.swapaxes(kg.reshape(BH, nc, Q, Dk), 2, 3)  # [BH, nc, Dk, Q]
    vg = jnp.moveaxis(grouped(v.reshape(B, Tp, Hk, J, Dv)), 2, 1)
    cg, bg = (grouped(t.reshape(B, Tp, Hk, J)) for t in (c, beta))
    colg = jnp.concatenate([cg, bg], axis=-1)          # [BH, Tp, 2 J]
    cch = cg.reshape(BH, nc, Q, J)
    totc = cch[:, :, -1:]                              # [BH, nc, 1, J]
    rowg = pad_axis(jnp.swapaxes(jnp.concatenate(
        [cch, totc - cch], axis=-1), 2, 3), 2, 8)      # [BH, nc, 8, Q]
    totg = pad_axis(jnp.broadcast_to(
        jnp.swapaxes(totc, 2, 3), (BH, nc, J, Dv)), 2, 8)
    y, s = checked_pallas_call(
        functools.partial(_scan_kernel, J=J, Q=Q),
        name=KERNEL_SCAN, grid=(BH, nc),
        in_specs=[pl.BlockSpec((1, Q, Dk), lambda h, c: (h, c, 0)),
                  pl.BlockSpec((1, Q, Dk), lambda h, c: (h, c, 0)),
                  pl.BlockSpec((1, 1, Dk, Q), lambda h, c: (h, c, 0, 0)),
                  pl.BlockSpec((1, J, Q, Dv), lambda h, c: (h, 0, c, 0)),
                  pl.BlockSpec((1, Q, 2 * J), lambda h, c: (h, c, 0)),
                  pl.BlockSpec((1, 1, 8, Q), lambda h, c: (h, c, 0, 0)),
                  pl.BlockSpec((1, 1, 8, Dv), lambda h, c: (h, c, 0, 0))],
        operands=(qg, kg, ktg, vg, colg, rowg, totg),
        out_specs=[pl.BlockSpec((1, J, Q, Dv), lambda h, c: (h, 0, c, 0)),
                   pl.BlockSpec((1, J, Dk, Dv), lambda h, c: (h, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((BH, J, Tp, Dv), jnp.float32),
                   jax.ShapeDtypeStruct((BH, J, Dk, Dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((J, Dk, Dv), jnp.float32)],
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES))
    y = jnp.moveaxis(y.reshape(B, Hv, Tp, Dv), 1, 2)[:, :T]
    return y, s.reshape(B, Hv, Dk, Dv)


# ------------------------------------------------------------- dispatch
def _note_plan(kernel, form, chunk):
    from ..observe.families import DELTA_PLANS

    DELTA_PLANS.labels(kernel=kernel, form=form, chunk=str(chunk)).inc()


def _kernels_on():
    from . import kernels_enabled

    return kernels_enabled() and not use_interpret()


def delta_update(state, q, k, v, g, beta):
    """The one-token update in whichever form this lowering can take:
    the in-place kernel where Pallas compiles (a TPU) and the state has a
    block plan, the composed form elsewhere."""
    if _kernels_on() and _update_plan(state.shape) is not None:
        _note_plan(KERNEL_UPDATE, "pallas", 1)
        return delta_update_pallas(state, q, k, v, g, beta, interpret=False)
    _note_plan(KERNEL_UPDATE, "composed", 1)
    return delta_update_composed(state, q, k, v, g, beta)


def delta_scan(q, k, v, g, beta):
    """The scan of a whole prompt, in chunks of ``scan_chunk`` of its
    length, in whichever form this lowering can take (as
    ``delta_update``)."""
    chunk = scan_chunk(q.shape[1])
    if _kernels_on() and _scan_plan(*_dims(q, v)[:4], chunk):
        _note_plan(KERNEL_SCAN, "pallas", chunk)
        return delta_scan_pallas(q, k, v, g, beta, chunk=chunk,
                                 interpret=False)
    _note_plan(KERNEL_SCAN, "composed", chunk)
    return delta_scan_composed(q, k, v, g, beta, chunk=chunk)
