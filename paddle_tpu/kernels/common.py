"""Shared Pallas kernel infrastructure.

Hoisted out of ops/attention.py (where flash attention grew it first) so
every kernel gates its ``pallas_call``s through the
SAME Mosaic block-legality mirror and the same interpret-mode detection.
A kernel that validated its own specs with a private copy of the rule
would drift the moment Mosaic's constraint set moves.

The legality rule (the attention round-2 lesson, mirrored from
jax/_src/pallas/mosaic/lowering.py ``_check_block_mappings``): every
operand/output block's last two dims must be divisible by (8, 128)
respectively or equal to the corresponding array dims. ``assert_mosaic_ok``
runs on EVERY backend — including interpret mode — so the CPU test suite
rejects block specs real-TPU lowering would refuse.
"""

from __future__ import annotations

import os as _os

import jax

__all__ = ["assert_mosaic_ok", "mosaic_ok", "checked_pallas_call",
           "use_interpret", "ceil_to", "pad_len", "pad_axis"]


def use_interpret() -> bool:
    """Pallas interpret mode everywhere but on a TPU backend
    (``jax.devices()[0].platform == "tpu"``). A backend that cannot be
    asked raises — it never reads as "interpret".

    PADDLE_TPU_FLASH_INTERPRET overrides the detection for EVERY
    kernel (the knob keeps its historical name):
    "1" forces interpret mode (debugging numerics on any backend), "0"
    forces the compiled Mosaic path (ahead-of-time compiles for a
    described chip from a CPU host, tests/test_chip_bringup.py)."""
    env = _os.environ.get("PADDLE_TPU_FLASH_INTERPRET", "")
    if env != "":
        return env != "0"
    return jax.devices()[0].platform != "tpu"


def mosaic_ok(block_shape, array_shape) -> bool:
    """Non-raising form of ``assert_mosaic_ok`` — the plan functions
    filter with this; dispatch-time gates use the raising form so a bad
    spec carries its own diagnosis."""
    if len(block_shape) < 2 or len(array_shape) < 2:
        return True
    b2, b1 = block_shape[-2], block_shape[-1]
    a2, a1 = array_shape[-2], array_shape[-1]
    return bool((b2 > 0 and b1 > 0)
                and (b2 % 8 == 0 or b2 == a2)
                and (b1 % 128 == 0 or b1 == a1))


def assert_mosaic_ok(block_shape, array_shape, what) -> None:
    """Mirror of Mosaic's _check_block_mappings rule (jax/_src/pallas/
    mosaic/lowering.py): the last two block dims must be divisible by
    (8, 128) respectively or equal to the corresponding array dims.

    Runs on every backend — including interpret mode — so the CPU test
    suite rejects block specs that real-TPU lowering would refuse."""
    if not mosaic_ok(block_shape, array_shape):
        raise ValueError(
            f"Mosaic-illegal BlockSpec for {what}: block {tuple(block_shape)} "
            f"on array {tuple(array_shape)} — last two block dims must be "
            f"divisible by (8, 128) or equal to the array dims")


def checked_pallas_call(kern, *, name, grid, in_specs, operands, out_specs,
                        out_shape, scratch_shapes, interpret,
                        scalar_prefetch=(), compiler_params=None,
                        input_output_aliases=None):
    """``pl.pallas_call`` with the Mosaic legality mirror applied to every
    operand/output spec first, and shard_map vma propagation (outputs
    vary over every mesh axis an operand does — ring attention runs the
    flash kernels per shard).

    ``name`` is required: it is the string by which the kernel is found
    in a device profile and in the lowered HLO (Pallas enters a
    ``jax.named_scope(name)`` round the call and hands Mosaic the same
    ``kernel_name``), so it has to be one the call site chose and not
    whatever autodiff wrapper happens to surround it.

    ``scalar_prefetch`` arrays (int32, SMEM) come before the operands in
    the kernel's arguments and after the grid indices in every index
    map (``pltpu.PrefetchScalarGridSpec``); ``compiler_params`` goes to
    Mosaic as given. ``input_output_aliases`` maps an input's index —
    counted over ``scalar_prefetch`` then ``operands`` — to the output
    that reuses its buffer (an in-place update)."""
    from jax.experimental import pallas as pl

    single_out = not isinstance(out_specs, (list, tuple))
    specs = list(out_specs) if not single_out else [out_specs]
    shapes = list(out_shape) if not single_out else [out_shape]
    for i, (sp, op) in enumerate(zip(in_specs, operands)):
        assert_mosaic_ok(sp.block_shape, op.shape, f"inputs[{i}]")
    for i, (sp, sh) in enumerate(zip(specs, shapes)):
        assert_mosaic_ok(sp.block_shape, sh.shape, f"outputs[{i}]")
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    if vma:
        shapes = [jax.ShapeDtypeStruct(s.shape, s.dtype, vma=vma)
                  for s in shapes]
        out_shape = shapes if not single_out else shapes[0]
    extra = {} if compiler_params is None \
        else {"compiler_params": compiler_params}
    if input_output_aliases:
        extra["input_output_aliases"] = dict(input_output_aliases)
    if scalar_prefetch:
        from jax.experimental.pallas import tpu as pltpu

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalar_prefetch), grid=grid,
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes)
        return pl.pallas_call(
            kern, grid_spec=grid_spec, out_shape=out_shape,
            interpret=interpret, name=name, **extra)(
                *scalar_prefetch, *operands)
    return pl.pallas_call(
        kern, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch_shapes,
        interpret=interpret, name=name, **extra)(*operands)


def ceil_to(n: int, b: int) -> int:
    return -(-n // b) * b


def pad_len(S: int, blk: int) -> int:
    """Padded length: multiples of blk when blocked, else S (a single
    block equal to the array dims is Mosaic-legal for any S)."""
    return ceil_to(S, blk) if S > blk else S


def pad_axis(x, axis: int, to: int, value=0.0):
    import jax.numpy as jnp

    S = x.shape[axis]
    if S == to:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, to - S)
    return jnp.pad(x, cfg, constant_values=value)
