"""Basic tensor ops: constants, random init, cast/scale/assign, shape utils.

Parity targets: /root/reference/paddle/fluid/operators/fill_constant_op.cc,
gaussian_random_op.cc, uniform_random_op.cc, truncated_gaussian_random_op.cc,
assign_op.cc, cast_op.cc, scale_op.cc, shape_op.cc, increment_op.cc,
range_op.cc, clip_op.cc, clip_by_norm_op.cc, sign_op.cc, isfinite_op.cc,
one_hot_op.cc, fill_constant_batch_size_like_op.cc.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.lowering import as_jax_dtype
from ..core.registry import register_op


def _dt(attrs, default="float32"):
    return as_jax_dtype(attrs.get("dtype", default) or default)


@register_op("fill_constant", no_grad=True)
def _fill_constant(ctx, ins, attrs):
    shape = tuple(attrs.get("shape", []))
    val = attrs.get("value", 0.0)
    return {"Out": [jnp.full(shape, val, dtype=_dt(attrs))]}


@register_op("fill_constant_batch_size_like", no_grad=True)
def _fill_constant_bsl(ctx, ins, attrs):
    ref = ins["Input"][0]
    shape = list(attrs["shape"])
    in_idx = attrs.get("input_dim_idx", 0)
    out_idx = attrs.get("output_dim_idx", 0)
    shape[out_idx] = ref.shape[in_idx]
    return {"Out": [jnp.full(tuple(shape), attrs.get("value", 0.0), dtype=_dt(attrs))]}


@register_op("fill_any_like", no_grad=True)
def _fill_any_like(ctx, ins, attrs):
    x = ins["X"][0]
    dtype = attrs.get("dtype")
    dt = as_jax_dtype(dtype) if dtype else x.dtype
    return {"Out": [jnp.full(x.shape, attrs.get("value", 0.0), dtype=dt)]}


@register_op("gaussian_random", no_grad=True, uses_rng=True)
def _gaussian_random(ctx, ins, attrs):
    shape = tuple(attrs["shape"])
    seed = attrs.get("seed", 0)
    key = jax.random.PRNGKey(seed) if seed else ctx.next_rng()
    dt = _dt(attrs)
    out = attrs.get("mean", 0.0) + attrs.get("std", 1.0) * jax.random.normal(
        key, shape, dtype=dt
    )
    return {"Out": [out]}


@register_op("truncated_gaussian_random", no_grad=True, uses_rng=True)
def _trunc_gaussian(ctx, ins, attrs):
    shape = tuple(attrs["shape"])
    seed = attrs.get("seed", 0)
    key = jax.random.PRNGKey(seed) if seed else ctx.next_rng()
    dt = _dt(attrs)
    out = attrs.get("mean", 0.0) + attrs.get("std", 1.0) * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, dtype=dt
    )
    return {"Out": [out]}


@register_op("uniform_random", no_grad=True, uses_rng=True)
def _uniform_random(ctx, ins, attrs):
    shape = tuple(attrs["shape"])
    seed = attrs.get("seed", 0)
    key = jax.random.PRNGKey(seed) if seed else ctx.next_rng()
    dt = _dt(attrs)
    out = jax.random.uniform(
        key, shape, dtype=dt, minval=attrs.get("min", -1.0), maxval=attrs.get("max", 1.0)
    )
    return {"Out": [out]}


@register_op("uniform_random_batch_size_like", no_grad=True, uses_rng=True)
def _uniform_random_bsl(ctx, ins, attrs):
    ref = ins["Input"][0]
    shape = list(attrs["shape"])
    shape[attrs.get("output_dim_idx", 0)] = ref.shape[attrs.get("input_dim_idx", 0)]
    key = ctx.next_rng()
    out = jax.random.uniform(
        key, tuple(shape), dtype=_dt(attrs),
        minval=attrs.get("min", -1.0), maxval=attrs.get("max", 1.0),
    )
    return {"Out": [out]}


@register_op("assign")
def _assign(ctx, ins, attrs):
    return {"Out": [ins["X"][0]]}


@register_op("materialize", no_grad=True)
def _materialize(ctx, ins, attrs):
    """``X`` as it is, behind ``jax.lax.optimization_barrier``: XLA may
    neither fuse what made ``X`` into what reads ``Out`` nor recompute
    it there, so ``X`` is written once and its producers' operands die
    where it is made (``layers.materialize`` says who needs that)."""
    return {"Out": [jax.lax.optimization_barrier(ins["X"][0])]}


@register_op("assign_value", no_grad=True)
def _assign_value(ctx, ins, attrs):
    vals = attrs["values"]
    shape = tuple(attrs["shape"])
    return {"Out": [jnp.asarray(vals, dtype=_dt(attrs)).reshape(shape)]}


@register_op("share_data")
def _share_data(ctx, ins, attrs):
    return {"Out": [ins["X"][0]]}


@register_op("cast")
def _cast(ctx, ins, attrs):
    return {"Out": [ins["X"][0].astype(as_jax_dtype(attrs["out_dtype"]))]}


@register_op("scale")
def _scale(ctx, ins, attrs):
    x = ins["X"][0]
    s = attrs.get("scale", 1.0)
    b = attrs.get("bias", 0.0)
    if attrs.get("bias_after_scale", True):
        return {"Out": [x * s + b]}
    return {"Out": [(x + b) * s]}


@register_op("shape", no_grad=True)
def _shape(ctx, ins, attrs):
    x = ins["Input"][0]
    return {"Out": [jnp.asarray(x.shape, dtype=jnp.int32)]}


@register_op("increment", no_grad=True)
def _increment(ctx, ins, attrs):
    return {"Out": [ins["X"][0] + attrs.get("step", 1.0)]}


@register_op("range", no_grad=True)
def _range(ctx, ins, attrs):
    # static-shape contract: bounds must be trace-time constants on TPU;
    # the layer records them in attrs (tensor inputs only kept for
    # desc-level parity with range_op.cc)
    if "static_start" in attrs:
        return {"Out": [jnp.arange(attrs["static_start"], attrs["static_end"],
                                   attrs["static_step"]).astype(_dt(attrs))]}
    start, end, step = ins["Start"][0], ins["End"][0], ins["Step"][0]
    s, e, st = (float(jnp.asarray(v).reshape(())) for v in (start, end, step))
    dt = start.dtype if hasattr(start, "dtype") else _dt(attrs)
    return {"Out": [jnp.arange(s, e, st).astype(dt)]}


@register_op("clip")
def _clip(ctx, ins, attrs):
    return {"Out": [jnp.clip(ins["X"][0], attrs.get("min"), attrs.get("max"))]}


@register_op("clip_by_norm")
def _clip_by_norm(ctx, ins, attrs):
    x = ins["X"][0]
    max_norm = attrs["max_norm"]
    norm = jnp.sqrt(jnp.sum(x * x))
    scale = jnp.where(norm > max_norm, max_norm / jnp.maximum(norm, 1e-12), 1.0)
    return {"Out": [x * scale.astype(x.dtype)]}


@register_op("sign", no_grad=True)
def _sign(ctx, ins, attrs):
    return {"Out": [jnp.sign(ins["X"][0])]}


@register_op("isfinite", no_grad=True)
def _isfinite(ctx, ins, attrs):
    x = ins["X"][0]
    return {"Out": [jnp.all(jnp.isfinite(x)).reshape((1,))]}


@register_op("one_hot", no_grad=True)
def _one_hot(ctx, ins, attrs):
    x = ins["X"][0]
    depth = attrs["depth"]
    if x.ndim >= 2 and x.shape[-1] == 1:
        x = jnp.squeeze(x, -1)
    return {"Out": [jax.nn.one_hot(x, depth, dtype=jnp.float32)]}


@register_op("linspace", no_grad=True)
def _linspace(ctx, ins, attrs):
    start, stop, num = ins["Start"][0], ins["Stop"][0], ins["Num"][0]
    return {"Out": [jnp.linspace(float(start), float(stop), int(num))]}


@register_op("sampling_id", no_grad=True, uses_rng=True)
def _sampling_id(ctx, ins, attrs):
    x = ins["X"][0]
    seed = attrs.get("seed", 0)
    key = jax.random.PRNGKey(seed) if seed else ctx.next_rng()
    return {"Out": [jax.random.categorical(key, jnp.log(x + 1e-20), axis=-1)
                    .astype(jnp.int32)]}
