"""The keep mask of a dropout, drawn once from the chip's generator.

Both ops that drop elements (``ops/nn.py::_dropout`` and the output dropout
of ``ops/attention.py::_fused_attention``) draw here. The bits come from HLO
``RngBitGenerator`` (``jax.lax.rng_bit_generator``), which XLA neither fuses
into its consumers nor rematerialises: a threefry mask is cheap elementwise
code to XLA, and it cloned the twenty rounds into every fusion that read the
mask, six generations a mask in the BERT train step (PERF.md section 6,
PR 35). The 32-bit draws are compared as integers against
``round(keep * 2**32)``, so the rate is at least as fine as a float32
uniform's 2**-23 and no float path is built.

The stream is not threefry's: the 2-word subkey ``ctx.next_rng()`` hands out
seeds the generator's 4-word state through one constant-size threefry draw,
so the same program seed still gives the same masks, on every backend the
same lowering.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["keep_mask"]

_DRAW_BITS = 32


def _draw(key, threshold, shape):
    state = jax.random.bits(key, (4,), jnp.uint32)
    _, bits = lax.rng_bit_generator(state, shape, dtype=jnp.uint32)
    return bits < jnp.uint32(threshold)


def _data_shards(ctx, shape):
    """The mesh and its data axis when the lowering runs under a mesh whose
    data axis divides the operand's leading axis (the axis ParallelEngine
    shards a batch on), else None: the draw is then the whole operand."""
    from .attention import _in_manual_mesh

    mesh = getattr(ctx, "mesh", None)
    if mesh is None or mesh.size <= 1 or not shape or _in_manual_mesh():
        return None
    axis = getattr(ctx, "data_axis", "data")
    if axis not in mesh.axis_names or mesh.shape[axis] <= 1 \
            or shape[0] % mesh.shape[axis]:
        return None
    return mesh, axis


def keep_mask(ctx, key, keep, shape, site):
    """bool ``shape``: True with probability ``keep`` independently an
    element, from the 2-word threefry ``key``. ``site`` labels the plan
    counter (``dropout`` or ``fused_attention``).

    Under a mesh each chip draws its own rows from its own stream (the
    shard's index folded into the key): SPMD cannot partition
    ``RngBitGenerator``, so a draw of the global shape would come out whole
    on every chip and be sliced."""
    from ..observe.families import DROPOUT_MASK_PLANS

    DROPOUT_MASK_PLANS.labels(site=site, bits="rbg_u32").inc()
    shape = tuple(int(d) for d in shape)
    threshold = max(0, int(round(float(keep) * 2 ** _DRAW_BITS)))
    if threshold >= 2 ** _DRAW_BITS:   # keep == 1: nothing to draw
        return jnp.ones(shape, jnp.bool_)
    sharded = _data_shards(ctx, shape)
    if sharded is None:
        return _draw(key, threshold, shape)
    mesh, axis = sharded
    local = (shape[0] // mesh.shape[axis],) + shape[1:]

    def per_shard(k):
        return _draw(jax.random.fold_in(k, lax.axis_index(axis)),
                     threshold, local)

    # the draw depends on the data axis alone, so it is the same on every
    # chip along the other axes: check_vma cannot see that through the
    # generator
    return jax.shard_map(per_shard, mesh=mesh, in_specs=P(),
                         out_specs=P(axis), check_vma=False)(key)
