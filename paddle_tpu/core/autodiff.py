"""Generic grad-op lowering via jax.vjp of the forward lowering.

The reference needs, per op: a GradOpDescMaker (framework/grad_op_desc_maker.h)
plus hand-written CPU+CUDA grad kernels. Here a grad op `<type>_grad` is
synthesized on first use: its lowering re-traces the *forward* lowering under
jax.vjp and applies the output cotangents. Correct by construction, and XLA
CSEs the re-trace against the forward pass, so no recompute cost.

Ops whose gradient must reuse saved forward state (dropout's mask) register a
custom grad_lowering instead (registry.register_grad_lowering).
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

ATTR_FWD_IN = "__fwd_in_slots__"
ATTR_FWD_OUT = "__fwd_out_slots__"
ATTR_DIFF = "__diff__"


def make_generic_grad(fwd_type: str):
    from .registry import OPS

    def _grad(ctx, ins: Dict[str, List[Any]], attrs: Dict[str, Any]):
        # one scope round every grad op's lowering, custom or generic:
        # whatever the grad op runs again of its forward (the jax.vjp
        # below re-traces it, and XLA does not merge two Pallas custom
        # calls) is then told apart, in the HLO and in a device profile,
        # from the forward op's own run by this string
        with jax.named_scope(fwd_type + "_grad"):
            return _lower_grad(ctx, ins, attrs)

    def _lower_grad(ctx, ins, attrs):
        fdef = OPS[fwd_type]
        if fdef.grad_lowering is not None:
            return fdef.grad_lowering(ctx, ins, attrs)

        fwd_in_slots: Dict[str, int] = attrs[ATTR_FWD_IN]
        fwd_out_slots: Dict[str, int] = attrs[ATTR_FWD_OUT]
        diff: List = [tuple(d) for d in attrs[ATTR_DIFF]]

        fwd_ins = {s: list(ins[s])[:n] for s, n in fwd_in_slots.items()}

        # probe trace to learn output dtypes (XLA dead-code-eliminates it)
        probe = fdef.lowering(ctx.pure(), fwd_ins, attrs)
        probe = {s: _as_list(probe.get(s)) for s in fwd_out_slots}
        float_outs = [
            (s, i)
            for s in fwd_out_slots
            for i, v in enumerate(probe[s])
            if v is not None and jnp.issubdtype(v.dtype, jnp.floating)
        ]

        def f(dvals):
            merged = {s: list(v) for s, v in fwd_ins.items()}
            for s, i in diff:
                merged[s][i] = dvals["%s:%d" % (s, i)]
            outs = fdef.lowering(ctx.pure(), merged, attrs)
            outs = {s: _as_list(outs.get(s)) for s in fwd_out_slots}
            return [outs[s][i] for s, i in float_outs]

        dvals0 = {"%s:%d" % (s, i): fwd_ins[s][i] for s, i in diff}
        primals, vjp = jax.vjp(f, dvals0)

        cots = []
        for (s, i), pv in zip(float_outs, primals):
            gslot = ins.get(s + "@GRAD")
            g = gslot[i] if gslot and i < len(gslot) else None
            if g is None:
                g = jnp.zeros_like(pv)
            elif g.dtype != pv.dtype or g.shape != pv.shape:
                g = jnp.broadcast_to(g.astype(pv.dtype), pv.shape)
            cots.append(g)
        (dins,) = vjp(cots)

        out: Dict[str, List[Any]] = {}
        for s, n in fwd_in_slots.items():
            out[s + "@GRAD"] = [None] * n
        for s, i in diff:
            out[s + "@GRAD"][i] = dins["%s:%d" % (s, i)]
        return out

    return _grad


def _as_list(v):
    if v is None:
        return []
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v]
