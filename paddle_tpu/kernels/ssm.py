"""The selective state-space recurrence of a Mamba-2 layer (arXiv:
2405.21060) in the two forms a served sequence needs, and the causal
depth-wise convolution in front of it (models/gpt.py ``cfg['mixers']``).

A layer has ``H`` heads of ``P`` values in ``G`` groups; head ``h`` reads
the ``B_t`` and ``C_t`` ``[N]`` of group ``h // (H / G)``. With ``dt_t,h``
already positive (softplus outside) and ``A_h < 0``::

    S_t,h = exp(dt_t,h A_h) S_t-1,h + dt_t,h  x_t,h (x) B_t,g
    y_t,h = S_t,h C_t,g

What a sequence keeps is ``S``: one ``[P, N]`` matrix a head, whatever
its length. It is stored GROUP-MAJOR AND TRANSPOSED, ``[B, G, N, L]``
with ``L = (H / G) P`` (head ``j`` of the group in lanes ``j P ..``): the
two short vectors every head of a group shares (``B_t``, ``C_t``) then
run down the sublanes and the per-head ones (decay, ``dt x``) along the
lanes, the sum over ``N`` that gives ``y`` is a sum of vector registers,
and ``y`` comes out in ``x``'s own ``[.., H P]`` order.

* ``ssm_update`` — ONE token a slot (the decode step). The Pallas kernel
  has a grid over (slot, group); a step reads the group's ``[N, L]``
  block of ``S`` once, writes it once INTO THE SAME BUFFER
  (``input_output_aliases``, as ``kv_cache_write``) and the ``[L]`` of
  ``y``. The per-lane rows (decay, ``dt x``) arrive as one ``[8, L]``
  tile and ``B_t``/``C_t`` as the columns of one ``[N, 8]`` tile, both
  built by XLA from the step's activations. Bound by bytes: the state is
  ``G N L`` floats a slot and the arithmetic five operations a float.
* ``ssm_scan`` — a whole prompt (the prefill), CHUNKED: inside a chunk of
  ``Q`` positions the recurrence is two matrix products (``(C B^T (.) decay
  (.) dt) x`` on the MXU), across chunks the state is carried in VMEM
  scratch. The grid is (group, chunk), chunks innermost and sequential;
  a step handles the ``H / G`` heads of its group one after another over
  the one ``C B^T`` they share. Returns ``y`` and the state after the
  last position. A prompt that is no multiple of ``Q`` is padded with
  ``dt = 0`` positions, which neither decay nor feed the state.

Each has a composed ``jax.numpy`` form with the same signature: what the
CPU runs, what ``PADDLE_TPU_KERNELS=0`` runs on the chip, and what the
tests compare the kernels with (the scan's composed form is chunked too,
a ``lax.scan`` over chunks: the token-by-token form is the reference's,
benchmarks/references/). ``paddle_ssm_plans_total`` counts which form
and which chunk each lowering took.

* ``conv_prefill`` — the convolution of a whole prompt, for every mixer
  that has one in front (``ssm``, ``mamba``, ``delta``, the gated
  convolution): ONE pass in a Pallas kernel, a grid over (batch, channel
  tile, block of positions) with the rows in front of a block carried in
  VMEM scratch, where the shape has a block plan; ``K`` shifted
  ``jax.numpy`` passes elsewhere. The same sums in the same order, which
  is ``conv_step``'s. ``paddle_conv_plans_total`` counts the form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import ceil_to, checked_pallas_call, pad_axis, use_interpret

__all__ = ["ssm_update", "ssm_scan", "ssm_update_composed",
           "ssm_update_pallas", "ssm_scan_composed", "ssm_scan_pallas",
           "conv_prefill", "conv_prefill_composed", "conv_prefill_pallas",
           "conv_step", "state_shape", "KERNEL_UPDATE", "KERNEL_SCAN",
           "KERNEL_CONV"]

# the names the device trace and the HLO show the calls under
KERNEL_UPDATE = "ssm_update"
KERNEL_SCAN = "ssm_scan"
KERNEL_CONV = "conv_prefill"

_LANES = 128
_VMEM_LIMIT_BYTES = 64 << 20
_HI = jax.lax.Precision.HIGHEST


def state_shape(batch, heads, head_dim, groups, state):
    """``[B, G, N, L]``: the layout a layer's state is kept in (module
    docstring)."""
    return (int(batch), int(groups), int(state),
            int(heads) // int(groups) * int(head_dim))


def _dims(x, dt, bm):
    """(H, P, G, N, J) of a call from its operands."""
    H = dt.shape[-1]
    P = x.shape[-1] // H
    G, N = bm.shape[-2:]
    if x.shape[-1] != H * P or H % G:
        raise ValueError("ssm: x %s is not [..., %d heads * P] in %d groups"
                         % (x.shape, H, G))
    return H, P, G, N, H // G


def _lane_rows(x, dt, a, G):
    """A token's per-lane operands ``[B, G, L]`` each: the decay ``exp(dt
    A)`` and ``dt x``, head ``j`` of a group in lanes ``j P ..``."""
    B, H = dt.shape
    P = x.shape[-1] // H
    decay = jnp.repeat(jnp.exp(dt * a[None, :]), P, axis=-1)
    xdt = x.astype(jnp.float32) * jnp.repeat(dt, P, axis=-1)
    return decay.reshape(B, G, -1), xdt.reshape(B, G, -1)


# ------------------------------------------------------------ one token
def ssm_update_composed(state, x, dt, a, bm, cm):
    """``(y [B, H P], state')``: ``state [B, G, N, L]``, ``x [B, H P]``,
    ``dt [B, H]`` (positive), ``a [H]`` (negative), ``bm``/``cm``
    ``[B, G, N]``."""
    H, P, G, _N, _J = _dims(x, dt, bm)
    decay, xdt = _lane_rows(x, dt, a, G)
    new = state * decay[:, :, None] + bm[:, :, :, None] * xdt[:, :, None]
    y = jnp.sum(new * cm[:, :, :, None], axis=2)               # [B, G, L]
    return y.reshape(-1, H * P), new


def _update_kernel(s_ref, r_ref, c_ref, o_ref, y_ref):
    rows, cols = r_ref[0, 0], c_ref[0, 0]         # [8, L], [N, 8]
    new = s_ref[0, 0] * rows[0:1] + cols[:, 0:1] * rows[1:2]
    o_ref[0, 0] = new
    y_ref[0, 0] = jnp.sum(new * cols[:, 1:2], axis=0, keepdims=True)


def _update_plan(state_shape_):
    B, G, N, L = (int(d) for d in state_shape_)
    if N % 8 or L % _LANES:
        return None
    return (1, 1, N, L)


def ssm_update_pallas(state, x, dt, a, bm, cm, *, interpret=None):
    """One token a slot into ``state [B, G, N, L]``, in place: a grid
    over (slot, group), each step one read and one write of the group's
    ``[N, L]`` block (``input_output_aliases`` ties the state to the
    output) and the ``[L]`` of ``y``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block = _update_plan(state.shape)
    if block is None:
        raise ValueError("ssm_update: no block plan for a state %s"
                         % (state.shape,))
    H, P, G, N, J = _dims(x, dt, bm)
    B, L = x.shape[0], J * P
    if interpret is None:
        interpret = use_interpret()
    rows = pad_axis(jnp.stack(_lane_rows(x, dt, a, G), axis=2), 2, 8)
    cols = pad_axis(jnp.stack([bm, cm], axis=-1).astype(jnp.float32), 3, 8)
    new, y = checked_pallas_call(
        _update_kernel, name=KERNEL_UPDATE, grid=(B, G),
        in_specs=[pl.BlockSpec(block, lambda b, g: (b, g, 0, 0)),
                  pl.BlockSpec((1, 1, 8, L), lambda b, g: (b, g, 0, 0)),
                  pl.BlockSpec((1, 1, N, 8), lambda b, g: (b, g, 0, 0))],
        operands=(state, rows, cols),
        out_specs=[pl.BlockSpec(block, lambda b, g: (b, g, 0, 0)),
                   pl.BlockSpec((1, 1, 1, L), lambda b, g: (b, g, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, G, 1, L), jnp.float32)],
        scratch_shapes=[], interpret=interpret,
        input_output_aliases={0: 0},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES))
    return y.reshape(B, H * P), new


# --------------------------------------------------------- whole prompt
def _chunked(x, dt, a, bm, cm, chunk):
    """The scan's operands padded to whole chunks (``dt = 0`` there) and
    the within-chunk inclusive sums of the log decay: ``(x, dt, lc, bm,
    cm, T)`` with ``lc [B, Tp, H]``."""
    T = x.shape[1]
    Tp = ceil_to(T, chunk)
    x, dt, bm, cm = (pad_axis(t.astype(jnp.float32), 1, Tp)
                     for t in (x, dt, bm, cm))
    la = dt * a[None, None, :]
    lc = jnp.cumsum(la.reshape(la.shape[0], Tp // chunk, chunk, -1),
                    axis=2).reshape(la.shape)
    return x, dt, lc, bm, cm, T


def ssm_scan_composed(x, dt, a, bm, cm, *, chunk=128):
    """``(y [B, T, H P], state [B, G, N, L])`` from ``x [B, T, H P]``,
    ``dt [B, T, H]`` (positive), ``a [H]``, ``bm``/``cm`` ``[B, T, G,
    N]``, the state zero before the sequence. Chunked as the kernel is,
    a ``lax.scan`` over the chunks."""
    H, P, G, N, J = _dims(x, dt, bm)
    B = x.shape[0]
    Q = min(int(chunk), ceil_to(x.shape[1], 8))
    x, dt, lc, bm, cm, T = _chunked(x, dt, a, bm, cm, Q)
    nc = x.shape[1] // Q

    def per_chunk(t, tail):       # [B, Tp, ...] -> [nc, B, Q, ...]
        return jnp.moveaxis(t.reshape((B, nc, Q) + tail), 1, 0)

    xs = per_chunk(x, (G, J, P))
    dts, lcs = per_chunk(dt, (G, J)), per_chunk(lc, (G, J))
    bs, cs = per_chunk(bm, (G, N)), per_chunk(cm, (G, N))
    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def step(s, c):
        xc, dtc, lcc, bc, cc = c
        cb = jnp.einsum("btgn,bsgn->bgts", cc, bc, precision=_HI)
        diff = lcc[:, :, None] - lcc[:, None]          # [B, t, s, G, J]
        diff = jnp.where(tri[None, :, :, None, None], diff, -jnp.inf)
        m = jnp.exp(diff) * dtc[:, None] \
            * jnp.moveaxis(cb, 1, -1)[..., None]       # [B, t, s, G, J]
        y = jnp.einsum("btsgj,bsgjp->btgjp", m, xc, precision=_HI)
        # what the state before the chunk still gives each position
        y = y + jnp.exp(lcc)[..., None] * jnp.einsum(
            "btgn,bgnjp->btgjp", cc, s, precision=_HI)
        tot = lcc[:, -1]                               # [B, G, J]
        w = jnp.exp(tot[:, None] - lcc) * dtc          # [B, s, G, J]
        s = jnp.exp(tot)[:, :, None, :, None] * s + jnp.einsum(
            "bsgn,bsgj,bsgjp->bgnjp", bc, w, xc, precision=_HI)
        return s, y

    s0 = jnp.zeros((B, G, N, J, P), jnp.float32)
    s, ys = jax.lax.scan(step, s0, (xs, dts, lcs, bs, cs))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, nc * Q, H * P)[:, :T]
    return y, s.reshape(B, G, N, J * P)


def _scan_kernel(x_ref, dr_ref, lr_ref, tr_ref, lcol_ref, bt_ref, c_ref,
                 y_ref, so_ref, s_ref, *, J, Q, P):
    from jax.experimental import pallas as pl

    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    cc, bt = c_ref[0], bt_ref[0]                       # [Q, N], [N, Q]
    cb = jnp.dot(cc, bt, precision=_HI,
                 preferred_element_type=jnp.float32)   # [t, s]
    t_i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    s_i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    seen = t_i >= s_i
    dr, lr, lcol = dr_ref[0], lr_ref[0], lcol_ref[0]   # [J,Q] [J,Q] [Q,J]
    tr = tr_ref[0]                 # [J, Q]: the chunk's total, repeated
    for j in range(J):
        lrow, drow = lr[j:j + 1, :], dr[j:j + 1, :]    # [1, Q]
        tot = tr[j:j + 1, :]                           # [1, Q]
        lc = lcol[:, j:j + 1]                          # [Q, 1]
        m = jnp.exp(jnp.where(seen, lc - lrow, -1e30)) * cb * drow
        xh, prev = x_ref[0, j], s_ref[j]               # [Q, P], [N, P]
        y = jnp.dot(m, xh, precision=_HI,
                    preferred_element_type=jnp.float32)
        y_ref[0, j] = y + jnp.exp(lc) * jnp.dot(
            cc, prev, precision=_HI, preferred_element_type=jnp.float32)
        w = jnp.exp(tot - lrow) * drow                 # [1, Q]
        # (a [1, 1] cannot be spread over sublanes and lanes at once:
        # the total comes as a row and its first P lanes scale the state)
        s_ref[j] = jnp.exp(tot[:, :P]) * prev + jnp.dot(
            bt * w, xh, precision=_HI, preferred_element_type=jnp.float32)

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        so_ref[0] = s_ref[...]


def _scan_plan(T, H, P, G, N, chunk):
    """The chunk a prompt of ``T`` positions is scanned in, or None
    where the kernel has no block plan."""
    if H % G or N % _LANES or P % 8 or int(chunk) % _LANES \
            or P > int(chunk):
        return None
    return int(chunk)


def ssm_scan_pallas(x, dt, a, bm, cm, *, chunk=128, interpret=None):
    """The chunked scan of a whole prompt (module docstring): a grid
    over (batch x group, chunk), chunks innermost and sequential with
    the group's state in VMEM scratch; inside a chunk ``C B^T`` once a
    group and, a head, the masked decay times it against ``x`` on the
    MXU."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, P, G, N, J = _dims(x, dt, bm)
    Q = _scan_plan(x.shape[1], H, P, G, N, chunk)
    if Q is None:
        raise ValueError("ssm_scan: no block plan for x %s in %d groups "
                         "of state %d" % (x.shape, G, N))
    if interpret is None:
        interpret = use_interpret()
    B = x.shape[0]
    x, dt, lc, bm, cm, T = _chunked(x, dt, a, bm, cm, Q)
    Tp = x.shape[1]
    nc, BG = Tp // Q, B * G

    def grouped(t, tail):          # [B, Tp, G, ...] -> [B G, Tp, ...]
        return jnp.moveaxis(t.reshape((B, Tp, G) + tail), 2, 1) \
            .reshape((BG, Tp) + tail)

    xg = jnp.moveaxis(grouped(x, (J, P)), 2, 1)        # [BG, J, Tp, P]
    dcol, lcol = grouped(dt, (J,)), grouped(lc, (J,))  # [BG, Tp, J]
    drow, lrow = jnp.swapaxes(dcol, 1, 2), jnp.swapaxes(lcol, 1, 2)
    trow = jnp.repeat(lrow.reshape(BG, J, nc, Q)[..., -1:], Q, axis=-1) \
        .reshape(BG, J, Tp)
    cg = grouped(cm, (N,))                             # [BG, Tp, N]
    btg = jnp.swapaxes(grouped(bm, (N,)), 1, 2)        # [BG, N, Tp]
    y, s = checked_pallas_call(
        functools.partial(_scan_kernel, J=J, Q=Q, P=P),
        name=KERNEL_SCAN, grid=(BG, nc),
        in_specs=[pl.BlockSpec((1, J, Q, P), lambda g, c: (g, 0, c, 0)),
                  pl.BlockSpec((1, J, Q), lambda g, c: (g, 0, c)),
                  pl.BlockSpec((1, J, Q), lambda g, c: (g, 0, c)),
                  pl.BlockSpec((1, J, Q), lambda g, c: (g, 0, c)),
                  pl.BlockSpec((1, Q, J), lambda g, c: (g, c, 0)),
                  pl.BlockSpec((1, N, Q), lambda g, c: (g, 0, c)),
                  pl.BlockSpec((1, Q, N), lambda g, c: (g, c, 0))],
        operands=(xg, drow, lrow, trow, lcol, btg, cg),
        out_specs=[pl.BlockSpec((1, J, Q, P), lambda g, c: (g, 0, c, 0)),
                   pl.BlockSpec((1, J, N, P), lambda g, c: (g, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((BG, J, Tp, P), jnp.float32),
                   jax.ShapeDtypeStruct((BG, J, N, P), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((J, N, P), jnp.float32)],
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES))
    y = jnp.moveaxis(y.reshape(B, G, J, Tp, P), 3, 1) \
        .reshape(B, Tp, H * P)[:, :T]
    s = jnp.moveaxis(s.reshape(B, G, J, N, P), 2, 3).reshape(B, G, N, J * P)
    return y, s


# ------------------------------------------------------------- dispatch
def _note_plan(op, kernel, chunk):
    from ..observe.families import SSM_PLANS

    SSM_PLANS.labels(op=op, kernel=kernel, chunk=str(chunk)).inc()


def _kernels_on():
    from . import kernels_enabled

    return kernels_enabled() and not use_interpret()


def ssm_update(state, x, dt, a, bm, cm):
    """The one-token update in whichever form this lowering can take:
    the in-place kernel where Pallas compiles (a TPU) and the state has a
    block plan, the composed form elsewhere."""
    if _kernels_on() and _update_plan(state.shape) is not None:
        _note_plan("update", "pallas", 1)
        return ssm_update_pallas(state, x, dt, a, bm, cm, interpret=False)
    _note_plan("update", "composed", 1)
    return ssm_update_composed(state, x, dt, a, bm, cm)


def ssm_scan(x, dt, a, bm, cm, *, chunk=128):
    """The scan of a whole prompt in whichever form this lowering can
    take (as ``ssm_update``)."""
    H, P, G, N, _J = _dims(x, dt, bm)
    Q = _scan_plan(x.shape[1], H, P, G, N, chunk) if _kernels_on() else None
    if Q is not None:
        _note_plan("scan", "pallas", Q)
        return ssm_scan_pallas(x, dt, a, bm, cm, chunk=Q, interpret=False)
    _note_plan("scan", "composed", chunk)
    return ssm_scan_composed(x, dt, a, bm, cm, chunk=chunk)


# ---------------------------------------------------------- convolution
def conv_prefill_composed(x, w, b, *, act=True, columns=None):
    """``conv_prefill`` in ``jax.numpy``: ``K`` slices of the padded
    prompt, each shifted by a row, tap by tap."""
    K = w.shape[1]
    if columns is not None:
        x = x[..., columns[0]:columns[1]]
    x = x.astype(jnp.float32)
    T = x.shape[1]
    wide = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(wide[:, j:j + T] * w[:, j].astype(jnp.float32)
              for j in range(K))
    if b is not None:
        out = out + b.astype(jnp.float32)
    return (jax.nn.silu(out) if act else out), wide[:, T:]


# the kernel's block of positions, its widest channel tile and the rows a
# piece of the block's walk holds (docs/KERNELS.md "Causal convolution of a
# prompt" has the sweep)
_CONV_BLOCK = 512
_CONV_TILE = 1024
_CONV_PIECE = 64


def _conv_kernel(x_ref, wb_ref, o_ref, seam_ref, *, K, Q, bias, act):
    """One ``[Q, tile]`` block of a prompt. ``seam_ref [16, tile]``: rows
    0-7 the eight rows in front of the block (zeros in front of the
    prompt), rows 8-15 the block's first eight, so that every row's
    ``K - 1`` predecessors lie at an offset of the scratch or of the
    block itself."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        seam_ref[0:8] = jnp.zeros((8,) + seam_ref.shape[1:], jnp.float32)

    seam_ref[8:16] = x_ref[0, 0:8]

    def taps(view, n):
        # ``conv_prefill_composed``'s sums, in its order
        out = view(0, n) * wb_ref[0:1]
        for j in range(1, K):
            out = out + view(j, n) * wb_ref[j:j + 1]
        if bias:
            out = out + wb_ref[K:K + 1]
        return jax.nn.silu(out) if act else out

    o_ref[0, 0:8] = taps(
        lambda j, n: seam_ref[pl.ds(8 - (K - 1) + j, n)], 8)
    for r in range(8, Q, _CONV_PIECE):
        n = min(_CONV_PIECE, Q - r)
        o_ref[0, r:r + n] = taps(
            lambda j, n, r=r: x_ref[0, pl.ds(r - (K - 1) + j, n)], n)
    seam_ref[0:8] = x_ref[0, Q - 8:Q]


def _conv_plan(T, C, K, lo=0, in_place=True):
    """``(block of positions, channel tile)`` of the Pallas form for a
    prompt of ``T`` positions over ``C`` channels that start at column
    ``lo`` of ``x``, or None where the composed form runs: a ``C`` or an
    ``lo`` that is no whole number of lane tiles, more taps than the eight
    rows of the seam hold, a prompt under one block — and an ``x`` that is
    not read ``in_place`` (as the columns of a wider tensor that exists
    anyway): in front of a kernel XLA writes out the slice or the product
    it fuses into the composed form, and the extra pass costs what the
    kernel wins."""
    T, C, K, lo = int(T), int(C), int(K), int(lo)
    if not in_place or C % _LANES or lo % _LANES or not 2 <= K <= 7 \
            or T < _CONV_BLOCK:
        return None
    tile = max(t for t in range(_LANES, _CONV_TILE + 1, _LANES)
               if C % t == 0 and lo % t == 0)
    return _CONV_BLOCK, tile


def conv_prefill_pallas(x, w, b, *, act=True, columns=None, plan=None,
                        interpret=None):
    """``conv_prefill`` in ONE pass: a grid over (batch, channel tile,
    block of positions), blocks innermost and sequential; a step reads
    its ``[Q, tile]`` of ``x`` once — where it lies in a wider ``x``
    (``columns``) —, takes the ``K`` shifted views in VMEM (the eight
    rows in front of the block carried in scratch: the pad is never
    built), and writes the block of ``out`` once, silu included. The taps
    and the bias arrive as one ``[8, tile]`` tile a channel tile. A ragged
    last block reads past the prompt into rows that only rows past the
    prompt depend on, which are not written."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T = x.shape[:2]
    lo, hi = (0, x.shape[2]) if columns is None else columns
    C, K = hi - lo, w.shape[1]
    Q, tile = plan or _conv_plan(T, C, K, lo) or (0, _LANES)
    if not 8 <= Q <= T or Q % 8 or lo % tile or C % tile \
            or not 2 <= K <= 7 or x.dtype != jnp.float32:
        raise ValueError("conv_prefill: no block plan for x %s %s, columns "
                         "%d-%d, %d taps" % (x.shape, x.dtype, lo, hi, K))
    if interpret is None:
        interpret = use_interpret()
    wb = [w.astype(jnp.float32).T]
    if b is not None:
        wb.append(b.astype(jnp.float32)[None])
    wb = pad_axis(jnp.concatenate(wb, axis=0), 0, 8)
    out = checked_pallas_call(
        functools.partial(_conv_kernel, K=K, Q=Q, bias=b is not None,
                          act=act),
        name=KERNEL_CONV, grid=(B, C // tile, -(-T // Q)),
        in_specs=[pl.BlockSpec((1, Q, tile),
                               lambda i, c, t: (i, t, lo // tile + c)),
                  pl.BlockSpec((8, tile), lambda i, c, t: (0, c))],
        operands=(x, wb),
        out_specs=pl.BlockSpec((1, Q, tile), lambda i, c, t: (i, t, c)),
        out_shape=jax.ShapeDtypeStruct((B, T, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((16, tile), jnp.float32)],
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES))
    return out, x[:, T - (K - 1):, lo:hi]


def conv_prefill(x, w, b, *, act=True, columns=None):
    """The causal depth-wise convolution of a whole prompt: ``x [B, T,
    C]`` (or the columns ``[lo, hi)`` of a wider one), ``w [C, K]`` (tap
    ``K - 1`` meets the position itself), ``b [C]`` or None, zeros before
    the sequence. Returns ``(out [B, T, C], rows [B, K - 1, C])``:
    ``rows`` are the last ``K - 1`` positions of ``x`` itself (zeros where
    the prompt is shorter), what the next token's convolution needs of
    the past. One pass in a Pallas kernel where Pallas compiles and the
    shape has a block plan (``_conv_plan``), ``K`` shifted passes in
    ``jax.numpy`` elsewhere: the same sums in the same order."""
    from ..observe.families import CONV_PLANS

    lo, hi = (0, x.shape[2]) if columns is None else columns
    plan = _conv_plan(x.shape[1], hi - lo, w.shape[1], lo,
                      in_place=columns is not None) \
        if _kernels_on() and x.dtype == jnp.float32 else None
    if plan is not None:
        CONV_PLANS.labels(kernel="pallas", chunk=str(plan[0])).inc()
        return conv_prefill_pallas(x, w, b, act=act, columns=columns,
                                   plan=plan, interpret=False)
    CONV_PLANS.labels(kernel="composed", chunk="0").inc()
    return conv_prefill_composed(x, w, b, act=act, columns=columns)


def conv_step(x, rows, w, b, *, act=True):
    """One token's convolution from the carried rows: ``x [B, 1, C]``,
    ``rows [B, K - 1, C]``. Returns ``(out [B, 1, C], rows')``, the rows
    shifted by the token."""
    window = jnp.concatenate([rows, x.astype(rows.dtype)], axis=1)
    # tap by tap in ``conv_prefill``'s order: the same sums, bit for bit
    out = sum(window[:, j:j + 1] * w[:, j].astype(jnp.float32)
              for j in range(w.shape[1]))
    if b is not None:
        out = out + b.astype(jnp.float32)
    return (jax.nn.silu(out) if act else out), window[:, 1:]
