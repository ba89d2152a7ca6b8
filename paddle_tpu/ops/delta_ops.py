"""The ops of a gated delta-rule layer's core (models/gpt.py
``layer_types`` entry ``"delta"``; kernels/delta.py holds the arithmetic
and says how the state is laid out): the chunked scan over a whole prompt
and the one-token update of a slot's state in place. Inference-only:
neither has a backward.

Both take the layer's activations flat, as the convolution leaves them —
``Q`` / ``K`` ``[B, T, Hk Dk]``, ``V [B, T, Hv Dv]`` — and the two gates
raw, ``Beta`` / ``A`` ``[B, T, Hv]``, with the per-head parameters
``ALog`` and ``DtBias`` ``[Hv]``: the op takes the l2 norm of every
query and key head (``kernels.delta.normed``), ``beta = sigmoid(Beta)``
and the log decay ``g = -exp(ALog) softplus(A + DtBias)``. ``Y [B, T, Hv
Dv]`` is the readout of the new state.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from ..core.registry import register_op

__all__: List[str] = []


def _operands(ins, attrs):
    """``(q, k [B, T, Hk, Dk], v [B, T, Hv, Dv], g, beta [B, T, Hv])`` of
    a scan or an update."""
    from ..kernels.delta import normed

    Hk, Hv = int(attrs["k_heads"]), int(attrs["v_heads"])
    f32 = jnp.float32
    q, v = ins["Q"][0], ins["V"][0]
    lead = q.shape[:-1]
    q, k = normed(q.reshape(lead + (Hk, -1)),
                  ins["K"][0].reshape(lead + (Hk, -1)))
    g = -jnp.exp(ins["ALog"][0].astype(f32)) * jax.nn.softplus(
        ins["A"][0].astype(f32) + ins["DtBias"][0].astype(f32))
    return (q, k, v.astype(f32).reshape(lead + (Hv, -1)), g,
            jax.nn.sigmoid(ins["Beta"][0].astype(f32)))


@register_op("delta_scan", no_grad=True)
def _delta_scan(ctx, ins, attrs):
    """The chunked scan over a whole prompt from a zero state: ``Y`` and
    ``StateOut [B, Hv, Dk, Dv]``, the state after the last position,
    written whole (nothing of the variable's previous value survives), in
    chunks of ``kernels.delta.scan_chunk``. A Pallas kernel on the TPU,
    ``jax.numpy`` elsewhere; any ``T`` is right."""
    from ..kernels.delta import delta_scan

    y, state = delta_scan(*_operands(ins, attrs))
    return {"Y": [y.reshape(ins["V"][0].shape)], "StateOut": [state]}


@register_op("delta_update", no_grad=True)
def _delta_update(ctx, ins, attrs):
    """One token a row into ``State`` (persistable: the executor donates
    it and the kernel writes it in place): ``Y [B, 1, Hv Dv]`` read out of
    the new state, ``StateOut`` the same variable."""
    from ..kernels.delta import delta_update

    y, state = delta_update(
        ins["State"][0], *(t[:, 0] for t in _operands(ins, attrs)))
    return {"Y": [y.reshape(ins["V"][0].shape)], "StateOut": [state]}
