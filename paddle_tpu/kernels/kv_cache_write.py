"""One new row a slot into a ``[B, n_kv, S, Dh]`` KV cache, in place.

The serving decode step writes each layer's new key and value at every
slot's OWN position (``pos [B]``). Composed, that is a ``vmap`` of
``lax.dynamic_update_slice`` over the batch axis, which the TPU compiler
unrolls into ``B`` dependent read / bounds-check / select / write rounds
a cache tensor: 48 x 32 of them a ``gpt2-medium`` step, 45% of its device
time, none of it bound by bytes.

``kv_cache_write_pallas`` is one call a cache tensor: a grid over the
slots whose block index map (positions are scalar-prefetched) picks the
one tile-aligned block of the slot's slab that holds its position; the
body replaces that position in the block and Pallas writes the block
back. ``input_output_aliases`` ties the cache to the output, so with the
executor's donation nothing but the ``B`` blocks moves. The block
follows the layout the TPU gives the slab (``_s_minor``):

* ``rows`` — ``Dh`` fills the lanes (a multiple of 128, or ``S`` is no
  better): the slab lies ``[S, Dh]`` and the block is the sublane tile
  of rows round the position, ``(1, n_kv, 8 | 16, Dh)``.
* ``cols`` — ``Dh`` is under a lane tile (64 at ``gpt2-medium``): the TPU
  stores such a slab ``S``-minor, ``[Dh, S]`` in (8, 128) tiles, so one
  position is a COLUMN through every tile of a 128-position strip. The
  kernel works on the ``[B, n_kv, Dh, S]`` view (a bitcast of that
  layout, not a copy), block ``(1, n_kv, Dh, 128)``; the update arrives
  slot-minor ``[n_kv, Dh, B]`` and a lane rotate carries slot ``b``'s
  lane to the position's lane.

A second form was tried and Mosaic refuses it where it matters: the
cache in ``memory_space=ANY`` and ``B`` asynchronous copies
``update[b] -> cache[b, :, pos[b], :]`` ("Slice shape along dimension 3
must be aligned to tiling (128), but is 64" at ``Dh`` 64 float32, "along
dimension 2 must be aligned to tiling (2), but is 1" for any bfloat16
cache); it compiles only for ``Dh`` 128 float32 (docs/KERNELS.md).

``kv_cache_write`` chooses: the kernel for per-slot positions writing
one row a slot where Pallas compiles and a block plan exists, the
composed form for everything else (a scalar position, several rows a
slot, a CPU backend, ``PADDLE_TPU_KERNELS=0``). Exact: every element of
the result is either the update cast to the cache's dtype or the input's
own bits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import ceil_to, checked_pallas_call, mosaic_ok, use_interpret

__all__ = ["kv_cache_write", "kv_cache_write_composed",
           "kv_cache_write_pallas", "write_plan", "KERNEL"]

# the name the device trace and the HLO show the call under
KERNEL = "kv_cache_write"

_LANES = 128
# one block is double-buffered on the way in and on the way out
_MAX_BLOCK_BYTES = 2 << 20


def _rows_written(pos, S):
    """The row each slot writes, as ``lax.dynamic_update_slice`` places
    it: a negative position counts from the end, then the start is
    clamped into the slab."""
    pos = pos.reshape((-1,)).astype(jnp.int32)
    return jnp.clip(jnp.where(pos < 0, pos + S, pos), 0, S - 1)


def kv_cache_write_composed(cache, upd, pos):
    """``lax.dynamic_update_slice`` on the sequence axis: one slice for
    a scalar position (every slot writes the same rows), a ``vmap`` of
    it over the batch axis for per-slot positions ``[B]`` / ``[B, 1]``."""
    upd = upd.astype(cache.dtype)
    zero = jnp.int32(0)
    if pos.size > 1:
        def write_slot(c, u, p):
            return jax.lax.dynamic_update_slice(c, u, (zero, p, zero))

        return jax.vmap(write_slot)(
            cache, upd, pos.reshape((-1,)).astype(jnp.int32))
    return jax.lax.dynamic_update_slice(
        cache, upd, (zero, zero, pos.reshape(()).astype(jnp.int32), zero))


def _s_minor(S, D):
    """Whether the TPU lays a ``[..., S, D]`` array out with ``S`` on the
    lanes: its default layout tiles the two minor axes (8 | 16, 128) and
    swaps them where that pads less (``D`` 64 would pad to 128). A wrong
    guess costs a relayout of the slab round the call, never a wrong
    answer; tests/test_chip_bringup.py compiles the benchmark's shapes
    and finds no such copy."""
    return (ceil_to(D, 8) * ceil_to(S, _LANES)
            < ceil_to(S, 8) * ceil_to(D, _LANES))


def write_plan(shape, dtype):
    """``(form, block)`` for a ``[B, n_kv, S, Dh]`` cache — ``block``
    indexes the array the kernel sees, ``[B, n_kv, Dh, S]`` under
    ``cols`` — or None where the kernel does not apply (the caller then
    takes the composed form)."""
    B, H, S, D = (int(d) for d in shape)
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return None
    if _s_minor(S, D):
        form, seen, block, step = "cols", (B, H, D, S), (1, H, D, _LANES), \
            _LANES
    else:
        step = 8 * (4 // dtype.itemsize)        # the dtype's sublane tile
        form, seen, block = "rows", (B, H, S, D), (1, H, step, D)
    if S % step or not mosaic_ok(block, seen):
        return None
    if H * D * step * dtype.itemsize > _MAX_BLOCK_BYTES:
        return None
    return form, block


def _rows_kernel(pos_ref, upd_ref, cache_ref, out_ref):
    from jax.experimental import pallas as pl

    row = pos_ref[pl.program_id(0)] % cache_ref.shape[2]
    rows = jax.lax.broadcasted_iota(jnp.int32, cache_ref.shape, 2)
    out_ref[...] = jnp.where(rows == row, upd_ref[...], cache_ref[...])


def _cols_kernel(pos_ref, upd_ref, cache_ref, out_ref):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    lane = pos_ref[b] % _LANES
    # slot b's values sit on lane b of the update; carry them to the
    # position's lane (the rotate is 32-bit: the update is cast after it)
    upd = pltpu.roll(upd_ref[...], (lane - b % _LANES) % _LANES, 2)
    lanes = jax.lax.broadcasted_iota(jnp.int32, upd.shape, 2)
    out_ref[0] = jnp.where(lanes == lane, upd.astype(out_ref.dtype),
                           cache_ref[0])


def kv_cache_write_pallas(cache, upd, pos, *, interpret=None):
    """Write ``upd [B, n_kv, 1, Dh]`` into ``cache [B, n_kv, S, Dh]`` at
    per-slot rows ``pos [B]`` (``[B, 1]``) with one in-place Pallas call:
    a grid over the slots, each step rewriting the one tile-aligned
    block that holds the slot's position (``write_plan``; module
    docstring for the two block orientations). Same result as the
    composed form bit for bit: the row is the one
    ``lax.dynamic_update_slice`` picks (negative counts from the end,
    then clamped), the update is cast to the cache's dtype, nothing else
    changes."""
    from jax.experimental import pallas as pl

    plan = write_plan(cache.shape, cache.dtype)
    if plan is None or upd.shape != cache.shape[:2] + (1, cache.shape[3]):
        raise ValueError("kv_cache_write: no block plan for cache %s %s, "
                         "update %s" % (cache.shape, cache.dtype, upd.shape))
    form, block = plan
    B, H, S, D = cache.shape
    if interpret is None:
        interpret = use_interpret()
    rows = _rows_written(pos, S)
    if form == "cols":
        kern, axis = _cols_kernel, 3
        seen = jnp.swapaxes(cache, 2, 3)            # the layout's own view
        # [n_kv, Dh, B] with the slots on the lanes, padded to whole tiles
        wide = jnp.float32 if cache.dtype.itemsize < 4 else cache.dtype
        upd = upd.astype(cache.dtype).astype(wide)[:, :, 0, :]
        upd = jnp.pad(jnp.transpose(upd, (1, 2, 0)),
                      ((0, 0), (0, 0), (0, ceil_to(B, _LANES) - B)))
        upd_spec = pl.BlockSpec((H, D, _LANES),
                                lambda b, rows: (0, 0, b // _LANES))
    else:
        kern, axis = _rows_kernel, 2
        seen = cache
        upd = upd.astype(cache.dtype)
        upd_spec = pl.BlockSpec((1, H, 1, D), lambda b, rows: (b, 0, 0, 0))

    def where(b, rows):
        """The slot's block: the tile of ``axis`` holding its row."""
        index = [b, 0, 0, 0]
        index[axis] = rows[b] // block[axis]
        return tuple(index)

    out = checked_pallas_call(
        kern, name=KERNEL, grid=(B,),
        in_specs=[upd_spec, pl.BlockSpec(block, where)],
        operands=(upd, seen),
        out_specs=pl.BlockSpec(block, where),
        out_shape=jax.ShapeDtypeStruct(seen.shape, seen.dtype),
        scratch_shapes=[], interpret=interpret,
        scalar_prefetch=(rows,),
        # operand 2 counting the prefetched rows: the cache IS the output
        input_output_aliases={2: 0})
    return jnp.swapaxes(out, 2, 3) if form == "cols" else out


def _note_plan(form, rows):
    from ..observe.families import KV_CACHE_WRITE_PLANS

    KV_CACHE_WRITE_PLANS.labels(form=form, rows=str(int(rows))).inc()


def kv_cache_write(cache, upd, pos):
    """The cache write in whichever form this lowering can take: the
    Pallas kernel for per-slot positions writing ONE row a slot, where
    Pallas compiles (``use_interpret()`` is false: a TPU) and
    ``write_plan`` has a block for the cache; the composed form for a
    scalar position (one ``dynamic_update_slice``, cheap as it is),
    several rows a slot (the multi-token step, the prefill's slab
    write), every CPU run and ``PADDLE_TPU_KERNELS=0``. Decided from the
    operands alone; ``paddle_kv_cache_write_plans_total`` counts which
    form each lowering took."""
    from . import kernels_enabled

    rows = upd.shape[2]
    if (pos.size > 1 and rows == 1 and kernels_enabled()
            and not use_interpret()
            and write_plan(cache.shape, cache.dtype) is not None):
        _note_plan("pallas", rows)
        return kv_cache_write_pallas(cache, upd, pos, interpret=False)
    _note_plan("composed", rows)
    return kv_cache_write_composed(cache, upd, pos)
