"""Absorbed latent attention of one decode step: every head of a slot
reads keys AND values out of the slot's one latent slab.

A latent-attention layer (MLA) keeps, per token, one row ``[c | k_r]``:
the normed key/value latent ``c`` (``d_c`` values) and the one rotated
key part ``k_r`` (``d_r`` values) all heads share. In the absorbed form
the up-projections are folded into the query and the output, so a head's
score against position ``s`` is ``q_abs[h] . row_s`` over the whole row
and its output ``sum_s p_s c_s`` over the first ``d_c`` values of it:
keys and values are the same bytes.

``q [B, H, d_c + d_r]`` (``q_lat | q_rope``, float32), ``cache
[B, 1, S, d_c + d_r]`` (the lane's latent cache: one "head", so that
``kv_cache_write`` and the splice treat it like every other cache
tensor), ``pos [B]``: slot ``b`` sees rows ``0 .. pos[b]``. Returns
``[B, H, d_c]`` float32.

Two forms of the one function, as ``gmm_composed`` stands beside
``gmm_pallas``:

* ``mla_decode_composed`` — ``jax.numpy``: scores over every row, a
  visibility mask, softmax, the weighted sum. It reads the slab twice
  and writes a ``[B, H, S]`` score tensor; what the CPU runs and what
  the tests compare the kernel with.
* ``mla_decode_pallas`` — one call a layer a step. The grid is ONE
  axis over the (slot, block) pairs that hold a visible row and nothing
  else (``work_list``): slot ``b`` has ``pos[b] // bs + 1`` of them,
  visited in ascending order, and the grid's bound is their traced
  count. Positions and the two tables are scalar-prefetched; every index
  map reads them. So consecutive steps always carry a new block, the
  pipeline's prefetch of a slot's first block runs under the last block
  of the slot before, and a step that would move and compute nothing
  (0.14-0.42 us each on a v5e, two thirds of a ``slots x max_len / bs``
  grid under the cells' traffic: docs/KERNELS.md has the timings) does
  not exist. Each block is read ONCE,
  rounded to bfloat16 for the MXU (as every float32 product of a
  compiled step is at the TPU's default precision), used as key over its
  whole width and as value over its first ``d_c`` lanes for all ``H``
  heads, under an online softmax (float32 running max, denominator and
  accumulator: reset at a slot's block 0, divided out at its last). The
  block follows the layout the TPU gives the slab
  (``kv_cache_write._s_minor``): a row of 576 values is 4.5 lane tiles,
  so the TPU stores the slab ``S``-minor, ``[W, S]`` in (8, 128) tiles
  with nothing padded, and the kernel works on the ``[B, W, S]`` view (a
  bitcast of that layout, not a copy: compiled for a described v5e the
  row-major view cost two relayouts of the whole slab a call), block
  ``(1, W, bs)``; a width of whole lane tiles keeps rows, block
  ``(1, bs, W)``.

``mla_decode`` chooses: the kernel where Pallas compiles
(``use_interpret()`` is false: a TPU) and a block plan exists, the
composed form elsewhere; ``paddle_mla_attention_plans_total`` counts
which form each lowering took (block ``"512 live"``: the rows of a step
and that only live pairs are walked), and the engine counts what the
walk saves a step in ``paddle_mla_decode_blocks_total``
(``blocks_of``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import checked_pallas_call, use_interpret
from .kv_cache_write import _s_minor

__all__ = ["mla_decode", "mla_decode_composed", "mla_decode_pallas",
           "decode_plan", "work_list", "blocks_of", "KERNEL"]

# the name the device trace and the HLO show the call under
KERNEL = "mla_decode"

_NEG = -1e30
_BLOCK_CHOICES = (512, 256, 128)
# a block is double-buffered; its float32 rows pad to whole lane tiles
_MAX_BLOCK_BYTES = 4 << 20


def mla_decode_composed(q, cache, pos, *, d_c, scale):
    """The plain form: ``softmax(q . rows * scale) @ rows[:, :d_c]`` over
    the rows ``<= pos[b]`` of each slot's slab."""
    rows = cache[:, 0]                                     # [B, S, W]
    s = jnp.einsum("bhw,bsw->bhs", q, rows,
                   preferred_element_type=jnp.float32) * scale
    seen = jnp.arange(rows.shape[1])[None, :] \
        <= pos.reshape((-1, 1)).astype(jnp.int32)          # [B, S]
    s = jnp.where(seen[:, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bsc->bhc", p, rows[:, :, :d_c],
                      preferred_element_type=jnp.float32)


def decode_plan(shape, dtype, H):
    """Rows a grid step takes of a ``[B, 1, S, W]`` latent cache, or None
    where the kernel does not apply (the caller then composes)."""
    B, one, S, W = (int(d) for d in shape)
    if one != 1 or jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                            jnp.dtype(jnp.bfloat16)):
        return None
    if H % 8:
        return None
    lanes = -(-W // 128) * 128
    for bs in _BLOCK_CHOICES:
        if S % bs == 0 and bs * lanes * jnp.dtype(dtype).itemsize \
                <= _MAX_BLOCK_BYTES:
            return bs
    return None


def work_list(pos, bs, nblk):
    """The (slot, block) pairs that hold a visible row, slot by slot and
    a slot's blocks ascending: ``slot_of [B * nblk]``, ``blk_of
    [B * nblk]`` and how many of them count, ``total [1]``. Slot ``b``
    has ``pos[b] // bs + 1`` pairs (a free slot stands at 0 and keeps
    its one block); entries past ``total`` repeat the last pair."""
    B = pos.shape[0]
    nb = pos // bs + 1
    ends = jnp.cumsum(nb)
    t = jnp.minimum(jnp.arange(B * nblk, dtype=jnp.int32), ends[-1] - 1)
    # the slots that end at or before t: B compares an entry, no loop
    slot_of = jnp.sum(t[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)
    blk_of = t - (ends - nb)[slot_of]
    return slot_of, blk_of, ends[-1:]


def blocks_of(pos, bs, max_len):
    """(live, grid) of one call over host positions ``pos`` (every slot
    of the lane, a free one at 0): the pairs ``work_list`` walks and the
    ``slots x max_len / bs`` steps of a grid over whole slabs."""
    return (int((pos // bs + 1).sum()), int(pos.size) * (max_len // bs))


def _kernel(pos_ref, slot_ref, blk_ref, q_ref, c_ref, o_ref, acc_ref, m_ref,
            l_ref, *, bs, d_c, scale, s_minor):
    from jax.experimental import pallas as pl

    t = pl.program_id(0)
    j = blk_ref[t]
    pos = pos_ref[slot_ref[t]]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # [W, bs] under the S-minor view, [bs, W] else: ``seq`` is the axis
    # of the block that counts positions
    rows = c_ref[0].astype(jnp.bfloat16)
    seq = 1 if s_minor else 0
    q = q_ref[0].astype(jnp.bfloat16)                      # [H, W]
    s = jax.lax.dot_general(
        q, rows, (((1,), (1 - seq,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32) * scale        # [H, bs]
    at = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(at <= pos, s, _NEG)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                 # [H, bs] f32
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(jnp.bfloat16),
        rows[:d_c] if s_minor else rows[:, :d_c],
        (((1,), (seq,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)                # [H, d_c]

    @pl.when(j == pos // bs)
    def _emit():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def mla_decode_pallas(q, cache, pos, *, d_c, scale, interpret=None):
    """The kernel (module docstring). Row 0 of a slot is always visible
    (``pos >= 0``), so the denominator is never zero."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, W = q.shape
    S = cache.shape[2]
    bs = decode_plan(cache.shape, cache.dtype, H)
    if bs is None or cache.shape[3] != W or not 0 < d_c <= W:
        raise ValueError("mla_decode: no block plan for q %s over cache %s "
                         "%s" % (q.shape, cache.shape, cache.dtype))
    if interpret is None:
        interpret = use_interpret()
    pos = jnp.clip(pos.reshape((-1,)).astype(jnp.int32), 0, S - 1)
    slot_of, blk_of, total = work_list(pos, bs, S // bs)

    s_minor = _s_minor(S, W)

    def of_slot(t, pos, slot_of, blk_of):
        return (slot_of[t], 0, 0)

    def rows_of(t, pos, slot_of, blk_of):
        return (slot_of[t], 0, blk_of[t]) if s_minor \
            else (slot_of[t], blk_of[t], 0)

    seen = jnp.swapaxes(cache, 2, 3) if s_minor else cache

    # the grid's one bound is traced: a step a pair of the work list
    return checked_pallas_call(
        functools.partial(_kernel, bs=bs, d_c=int(d_c), scale=float(scale),
                          s_minor=s_minor),
        name=KERNEL, grid=(total[0],),
        in_specs=[pl.BlockSpec((1, H, W), of_slot),
                  pl.BlockSpec((1, W, bs) if s_minor else (1, bs, W),
                               rows_of)],
        operands=(q, seen.reshape((B,) + seen.shape[2:])),
        out_specs=pl.BlockSpec((1, H, int(d_c)), of_slot),
        out_shape=jax.ShapeDtypeStruct((B, H, int(d_c)), jnp.float32),
        scratch_shapes=[pltpu.VMEM((H, int(d_c)), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32)],
        interpret=interpret, scalar_prefetch=(pos, slot_of, blk_of),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)))


def _note_plan(form, block, widths):
    from ..observe.families import MLA_ATTENTION_PLANS

    MLA_ATTENTION_PLANS.labels(form="absorbed", kernel=form,
                               block=str(block), widths=widths).inc()


def mla_decode(q, cache, pos, *, d_c, scale):
    """The step's latent attention in whichever form this lowering can
    take (module docstring); decided from the operands alone."""
    from . import kernels_enabled

    widths = "%dx%d" % (q.shape[-1], d_c)
    bs = None
    if kernels_enabled() and not use_interpret():
        bs = decode_plan(cache.shape, cache.dtype, q.shape[1])
    if bs is None:
        _note_plan("composed", "-", widths)
        return mla_decode_composed(q, cache, pos, d_c=d_c, scale=scale)
    _note_plan("pallas", "%d live" % bs, widths)
    return mla_decode_pallas(q, cache, pos, d_c=d_c, scale=scale,
                             interpret=False)
