"""Whole-block lowering: Program ops -> one JAX computation.

This replaces the reference's per-op interpreter hot loop
(/root/reference/paddle/fluid/framework/executor.cc:452-458 and the kernel
dispatch in operator.cc:877-930). Instead of choosing a kernel per op at
runtime, each op's registered lowering emits JAX ops into a single trace;
XLA then fuses/schedules the whole step. Shape/dtype inference, data layout
transform and the garbage collector all disappear into the compiler.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from .program import Block
from .registry import get_op

__all__ = ["LowerContext", "lower_block", "op_scope"]


class LowerContext:
    """Carries trace-wide state across op lowerings: the PRNG key chain,
    the owning block (for sub-block control flow), and mode flags."""

    def __init__(self, block: Optional[Block] = None, rng: Optional[jax.Array] = None,
                 is_test: bool = False, amp: bool = False, mesh=None,
                 data_axis: str = "data", model_axis: str = "model",
                 seq_axis: str = "seq"):
        self.block = block
        self._rng = rng
        self.is_test = is_test
        self.amp = amp
        self.mesh = mesh  # jax Mesh when lowering under ParallelEngine:
        #                   ops with explicit-collective paths (pipeline,
        #                   moe) pick their shard_map axis from it
        self.data_axis = data_axis  # the engine's batch axis name
        self.model_axis = model_axis  # the engine's tensor-parallel axis
        self.seq_axis = seq_axis  # the engine's sequence-parallel axis
        self.rng_used = False

    def next_rng(self) -> jax.Array:
        if self._rng is None:
            # pure re-trace (vjp of a forward lowering) must not consume rng
            raise RuntimeError(
                "op requested RNG in a pure context; register a custom grad "
                "lowering that reuses saved randomness (e.g. dropout mask)"
            )
        self.rng_used = True
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def final_rng(self):
        return self._rng

    def sub(self, block: Block) -> "LowerContext":
        c = LowerContext(block, self._rng, self.is_test, self.amp, self.mesh,
                         self.data_axis, self.model_axis, self.seq_axis)
        return c

    def pure(self) -> "LowerContext":
        """Context for re-tracing a forward lowering inside a vjp: no RNG.
        Keeps the mesh: the re-trace must pick the same (shard_map vs
        sequential) path as the forward emission or XLA cannot CSE them."""
        return LowerContext(self.block, None, self.is_test, self.amp,
                            self.mesh, self.data_axis, self.model_axis,
                            self.seq_axis)


_NOT_IN_A_SCOPE = re.compile(r"[^\w./-]+")


def op_scope(op) -> str:
    """``<op.name_scope>/<op.type>``, just ``<op.type>`` for an op built
    under no ``name_scope``: the ``jax.named_scope`` its lowering runs
    under, so every instruction XLA makes of it says in its ``op_name``
    which Program op and which part of the model it came from
    (``observe/device_names.py`` reads it back). A pass-made op's
    ``fused:a,b`` (core/ir.py) stands under the first scope it replaced."""
    scope = getattr(op, "name_scope", "") or ""
    if scope.startswith("fused:"):
        scope = scope[len("fused:"):].split(",")[0]
    scope = _NOT_IN_A_SCOPE.sub("_", scope).strip("/")
    return "%s/%s" % (scope, op.type) if scope else op.type


def lower_op(ctx: LowerContext, op, env: Dict[str, Any]) -> None:
    with jax.named_scope(op_scope(op)):   # metadata only: no instruction
        _lower_op(ctx, op, env)


def _lower_op(ctx: LowerContext, op, env: Dict[str, Any]) -> None:
    opdef = get_op(op.type)
    ins: Dict[str, List[Any]] = {}
    for slot, names in op.inputs.items():
        ins[slot] = [env[n] if n else None for n in names]
    if ctx.amp:
        from .amp import amp_cast

        # the __amp__ attr stamped by core/passes/amp_pass.py (or set per
        # op by the user) overrides the table policy
        ins = amp_cast(op.type, op.attrs, ins)
    attrs = op.attrs
    if opdef.needs_env:
        attrs = dict(op.attrs)
        attrs["__env__"] = env
    outs = opdef.lowering(ctx, ins, attrs)
    upd = outs.pop("__env_update__", None) if isinstance(outs, dict) else None
    if upd:
        env.update(upd)
    for slot, names in op.outputs.items():
        vals = outs.get(slot)
        if vals is None:
            continue
        if not isinstance(vals, (list, tuple)):
            vals = [vals]
        for name, val in zip(names, vals):
            if name and val is not None:
                env[name] = val


def lower_ops(ctx: LowerContext, ops, env: Dict[str, Any]) -> None:
    """Lower a specific op sequence in order, mutating `env`."""
    for op in ops:
        try:
            lower_op(ctx, op, env)
        except Exception as e:
            raise RuntimeError(
                "while lowering op %r in name_scope %r (inputs=%s outputs=%s)"
                ": %s: %s"
                % (op.type, getattr(op, "name_scope", "") or "", op.inputs,
                   op.outputs, type(e).__name__, e)
            ) from e


def lower_block(ctx: LowerContext, block: Block, env: Dict[str, Any]) -> None:
    """Run every op's lowering in program order, mutating `env`
    (name -> traced value). This is the whole-program analog of
    Executor::RunPreparedContext's op loop."""
    lower_ops(ctx, block.ops, env)


def as_jax_dtype(dtype: str):
    """Program dtype -> on-device dtype.

    int64 is an API-boundary type: jax runs with x64 disabled (the TPU-native
    choice — 64-bit integer lanes waste VPU width), so id/index vars are
    int32 on device. The Executor range-checks int64 feeds at the boundary
    (executor._feed_to_device), replacing the reference's genuinely-64-bit
    lookup_table ids (/root/reference/paddle/fluid/operators/lookup_table_op.cc)
    with a checked narrowing."""
    if dtype == "bool":
        return jnp.bool_
    if dtype in ("int64", "uint64"):
        return jnp.dtype(dtype.replace("64", "32"))
    return jnp.dtype(dtype)
