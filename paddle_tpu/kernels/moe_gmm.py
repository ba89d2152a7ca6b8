"""Grouped matmul over ragged groups: the expert layer's two products.

``lhs [M, K]`` holds rows sorted by group (expert), ``group_sizes [E]``
says how many consecutive rows each group owns, ``rhs [E, K, N]`` is one
matrix a group; row ``r`` of group ``g`` becomes ``lhs[r] @ rhs[g]``.
Rows past ``sum(group_sizes)`` belong to no group and come out zero.

Two forms of the one function, as ``composed_attention`` stands beside
the flash kernels:

* ``gmm_composed`` — ``jax.lax.ragged_dot``. What the CPU runs, what the
  tests compare the kernel with, and what differentiates.
* ``gmm_pallas`` — the Pallas kernel. The grid is (column tiles, work
  tiles, reduction tiles); a WORK TILE is one (group, row tile) meeting,
  so a row tile that straddles a group boundary is visited once a group
  and stores under a row mask. Pallas issues no new DMA for a block
  whose index the next grid step keeps, and the reduction is the
  innermost axis: only a reduction held WHOLE (one reduction tile, which
  is what ``gmm_plan`` chooses wherever the block fits) leaves the
  ``[tk, tn]`` weight block's index unchanged across the consecutive
  work tiles of one group, and the row block's across those of one row
  tile, so that a group's weights are fetched once a column tile. A CUT
  reduction walks its tiles inside every work tile and fetches the
  group's ``[K, tn]`` weights, and the rows, again for each 128-row
  tile. Group ids, row-tile ids and group offsets are scalar-prefetched;
  the static grid holds the worst case ``tiles_m + E - 1`` work tiles and
  the ones past the real count repeat the last block indices — group,
  row tile AND reduction tile: an idle tile that still walked the
  reduction axis would fetch a weight block a step (a share of the
  experts leaves most work tiles idle: 31 of 32 rows belong to no held
  group) — and skip their body. With two ``rhs`` (gate and up) the
  kernel accumulates both
  products in one pass over ``lhs`` and stores ``silu(gate) * up``.

``gmm`` chooses: the kernel where Pallas compiles (``use_interpret()`` is
false, i.e. on a TPU) and the tile plan is legal, the composed form
elsewhere. Its gradient is the composed form's (``custom_vjp``), so a
training step differentiates whichever form its forward holds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import ceil_to, checked_pallas_call, mosaic_ok, use_interpret

__all__ = ["gmm", "gmm_composed", "gmm_pallas", "gmm_plan",
           "KERNEL_UP", "KERNEL_DOWN"]

# the names the device trace and the HLO show the two calls under
KERNEL_UP = "moe_gmm_up"
KERNEL_DOWN = "moe_gmm_down"

_TM = 128                      # row tile: one MXU pass of rows
_LANES = 128
# columns: an axis that one of these divides takes the widest that fits
_TN_POW2 = (512, 256)
# any other axis its widest divisor up to this (1024 columns under a whole
# reduction bought under 2% over 512 for twice the weight block)
_TN_MAX = 1024
# a [3584, 512] or [7680, 512] bf16 block and a [3072, 512] float32 one
# hold their reduction whole within it
_MAX_RHS_BLOCK_BYTES = 8 << 20
# of the v5e's 128 MiB; the widest plan's blocks (128x7680x512 bf16, two
# rhs) are ~39 MiB by _vmem_bytes' count
_VMEM_LIMIT_BYTES = 48 << 20


def _silu_mul(gate, up):
    return gate * jax.nn.sigmoid(gate) * up


def gmm_composed(lhs, rhs, group_sizes):
    """``jax.lax.ragged_dot`` per ``rhs``; two give ``silu(a) * b``."""
    rhs = rhs if isinstance(rhs, (list, tuple)) else (rhs,)
    outs = [jax.lax.ragged_dot(lhs, w.astype(lhs.dtype),
                               group_sizes.astype(jnp.int32))
            for w in rhs]
    return outs[0] if len(outs) == 1 else _silu_mul(*outs)


def _tiles(axis, most=None):
    """Tiles Mosaic takes of one axis, the largest first: its divisors
    that are multiples of 128, the whole axis included (none wider than
    ``most``); an axis 128 does not divide is taken whole."""
    if axis % _LANES:
        return [axis]
    return [t for t in range(axis, 0, -_LANES)
            if axis % t == 0 and (most is None or t <= most)]


def _vmem_bytes(tm, tk, tn, itemsize):
    """What a plan keeps in VMEM at the most: float32 rows, two ``rhs``
    (gate and up), every block double-buffered, a float32 accumulator a
    ``rhs``."""
    return (2 * tm * tk * 4 + 2 * 2 * tk * tn * itemsize
            + 2 * tm * tn * 4 + 2 * tm * tn * 4)


def gmm_plan(M, K, N, itemsize=4):
    """``(tm, tk, tn)`` or None where no legal plan exists (the caller
    then takes the composed form). ``tk``/``tn`` must divide ``K``/``N``
    — padding ``rhs`` would copy every expert's weights — or be the
    whole axis.

    The reduction is held WHOLE (``tk = K``) wherever the ``[K, tn]``
    weight block stays within ``_MAX_RHS_BLOCK_BYTES`` and the blocks fit
    VMEM, at 512 columns or else at 256: only then do consecutive work
    tiles of one group keep the weight block's index, and those of one
    row tile the row block's, so a group's weights are fetched once a
    column tile and not once a 128-row tile. A reduction that cannot be
    held whole is cut by the LARGEST of its divisors that are multiples
    of 128 that fits, the reduction chosen before the columns: a grid
    step costs about 0.4 us whatever it moves. The columns take 512 or
    256 where one divides them, else (2688 = 21 x 128) their largest
    divisor that is a multiple of 128 up to ``_TN_MAX``; an axis that 128
    does not divide is taken whole (docs/KERNELS.md "Tile plan of the
    grouped matmul" has the sweeps)."""
    tm = min(_TM, ceil_to(max(int(M), 1), 8))
    tns = [t for t in _TN_POW2 if N % t == 0] or _tiles(N, _TN_MAX)
    for tk in _tiles(K):
        for tn in tns:
            if (tk * tn * itemsize <= _MAX_RHS_BLOCK_BYTES
                    and _vmem_bytes(tm, tk, tn, itemsize)
                    <= _VMEM_LIMIT_BYTES
                    and mosaic_ok((1, tk, tn), (1, K, N))
                    and mosaic_ok((tm, tk), (ceil_to(M, tm), K))):
                return tm, tk, tn
    return None


def _work_tiles(group_sizes, M, tm, n_work):
    """Scalar-prefetch metadata: for each of the ``n_work`` static work
    tiles its group and row tile, the group offsets, and how many work
    tiles are real."""
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first_tile = starts // tm
    touched = jnp.where(sizes > 0, (ends - 1) // tm - first_tile + 1, 0)
    tile_ends = jnp.cumsum(touched)
    num = tile_ends[-1]
    w = jnp.arange(n_work, dtype=jnp.int32)
    # tiles past the real count repeat the last real one: same block
    # indices, so nothing new is fetched, and the body is skipped
    wc = jnp.minimum(w, jnp.maximum(num - 1, 0))
    gid = jnp.searchsorted(tile_ends, wc, side="right").astype(jnp.int32)
    gid = jnp.minimum(gid, sizes.shape[0] - 1)
    mid = first_tile[gid] + (wc - (tile_ends[gid] - touched[gid]))
    mid = jnp.clip(mid, 0, ceil_to(M, tm) // tm - 1).astype(jnp.int32)
    return gid, mid, offsets, num.reshape((1,)).astype(jnp.int32)


def _kernel(n_rhs, tm, nk, mxu_dtype, gid_ref, mid_ref, off_ref, num_ref,
            lhs_ref, *refs):
    from jax.experimental import pallas as pl

    rhs_refs, out_ref, acc_refs = (refs[:n_rhs], refs[n_rhs],
                                   refs[n_rhs + 1:])
    w, k = pl.program_id(1), pl.program_id(2)
    active = w < num_ref[0]

    @pl.when(jnp.logical_and(active, k == 0))
    def _():
        for acc in acc_refs:
            acc[...] = jnp.zeros_like(acc)

    @pl.when(active)
    def _():
        a = lhs_ref[...].astype(mxu_dtype)
        for rhs, acc in zip(rhs_refs, acc_refs):
            # bf16 operands are one MXU pass whatever the ambient
            # default_matmul_precision asks of float32 products
            acc[...] += jnp.dot(a, rhs[0].astype(mxu_dtype),
                                precision=jax.lax.Precision.DEFAULT,
                                preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(active, k == nk - 1))
    def _():
        g, m = gid_ref[w], mid_ref[w]
        rows = m * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = jnp.logical_and(rows >= off_ref[g], rows < off_ref[g + 1])
        # the first visit of a row tile clears what no group owns
        first = jnp.logical_or(w == 0, mid_ref[jnp.maximum(w - 1, 0)] != m)
        val = acc_refs[0][...] if n_rhs == 1 \
            else _silu_mul(acc_refs[0][...], acc_refs[1][...])
        keep = jnp.where(first, jnp.zeros_like(val),
                         out_ref[...].astype(val.dtype))
        out_ref[...] = jnp.where(mine, val, keep).astype(out_ref.dtype)


def gmm_pallas(lhs, rhs, group_sizes, *, name, plan=None, interpret=None,
               mxu_dtype=None):
    """The kernel. ``mxu_dtype`` is what the operands are cast to for
    the matmul (accumulation is float32 always): None keeps their own
    dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rhs = tuple(rhs) if isinstance(rhs, (list, tuple)) else (rhs,)
    M, K = lhs.shape
    E, _, N = rhs[0].shape
    plan = plan or gmm_plan(M, K, N, rhs[0].dtype.itemsize)
    if plan is None:
        raise ValueError("moe_gmm: no legal tile plan for [%d, %d] x "
                         "[%d, %d, %d]" % (M, K, E, K, N))
    tm, tk, tn = plan
    if interpret is None:
        interpret = use_interpret()
    Mp = ceil_to(M, tm)
    if Mp != M:
        lhs = jnp.pad(lhs, ((0, Mp - M), (0, 0)))
    tiles_m, nk, nn = Mp // tm, K // tk, N // tn
    n_work = tiles_m + E - 1
    gid, mid, offsets, num = _work_tiles(group_sizes, M, tm, n_work)

    _note_plan(name, plan, "pallas")

    def kt(w, k, num):
        # an idle work tile stays on the last reduction tile the last
        # real one ended on: its block indices do not move, nothing is
        # fetched for it
        return jnp.where(w < num[0], k, nk - 1)

    in_specs = [pl.BlockSpec((tm, tk),
                             lambda n, w, k, gid, mid, off, num:
                             (mid[w], kt(w, k, num)))]
    in_specs += [pl.BlockSpec((1, tk, tn),
                              lambda n, w, k, gid, mid, off, num:
                              (gid[w], kt(w, k, num), n))] * len(rhs)
    out = checked_pallas_call(
        functools.partial(_kernel, len(rhs), tm, nk,
                          mxu_dtype or lhs.dtype),
        name=name, grid=(nn, n_work, nk), in_specs=in_specs,
        operands=(lhs,) + rhs,
        out_specs=pl.BlockSpec((tm, tn),
                               lambda n, w, k, gid, mid, off, num:
                               (mid[w], n)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), lhs.dtype),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * len(rhs),
        interpret=interpret,
        scalar_prefetch=(gid, mid, offsets, num),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES))
    # row tiles that no work tile visited were never written
    owned = jnp.arange(Mp, dtype=jnp.int32)[:, None] < offsets[-1]
    return jnp.where(owned, out, jnp.zeros_like(out))[:M]


def _note_plan(name, plan, form):
    from ..observe.families import MOE_GMM_PLANS

    tile = "-" if plan is None else "%dx%dx%d" % plan
    MOE_GMM_PLANS.labels(kernel=name, tile=tile, form=form).inc()


def gmm(lhs, rhs, group_sizes, *, name):
    """``lhs @ rhs[group of each row]`` in whichever form this backend
    holds: the Pallas kernel where it compiles and a tile plan exists,
    the composed form otherwise (CPU: interpret mode is for tests of the
    kernel, not for running models). float32 operands meet the MXU as
    bfloat16, as every other float32 matmul of a compiled step does at
    the TPU's default precision. Differentiable through the composed
    form."""
    rhs = tuple(rhs) if isinstance(rhs, (list, tuple)) else (rhs,)
    M, K = lhs.shape
    N = rhs[0].shape[2]
    plan = None if use_interpret() else gmm_plan(
        M, K, N, rhs[0].dtype.itemsize)
    if plan is None:
        _note_plan(name, None, "composed")
        return gmm_composed(lhs, rhs, group_sizes)
    mxu = jnp.bfloat16 if lhs.dtype == jnp.float32 else None

    @jax.custom_vjp
    def run(lhs, rhs):
        return gmm_pallas(lhs, rhs, group_sizes, name=name, plan=plan,
                          interpret=False, mxu_dtype=mxu)

    def fwd(lhs, rhs):
        return run(lhs, rhs), (lhs, rhs)

    def bwd(res, g):
        _, vjp = jax.vjp(
            lambda a, b: gmm_composed(a, b, group_sizes), *res)
        return vjp(g)

    run.defvjp(fwd, bwd)
    return run(lhs, rhs)
