"""The ops of a power-retention layer's core (models/gpt.py
``layer_types`` entry ``"retention"``; kernels/power.py holds the
arithmetic and says how the state is laid out): the chunked scan over a
whole prompt and the one-token update of a slot's state in place.
Inference-only: neither has a backward.

Both take the layer's activations flat, as the projections, the head
norm and the rotation leave them — ``Q [B, T, H D]``, ``K`` / ``V`` ``[B,
T, G D]`` — and ``Gate [B, T, G]`` raw: the op takes the log of its
sigmoid, one gate a key-value head. ``Y [B, T, H D]`` is the normalised
readout.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from ..core.registry import register_op

__all__: List[str] = []


def _operands(ins, attrs):
    """``(q [B, T, H, D], k, v [B, T, G, D], lg [B, T, G])`` of a scan or
    an update, ``lg`` the log of the gate."""
    H, G = int(attrs["heads"]), int(attrs["groups"])
    q = ins["Q"][0].astype(jnp.float32)
    lead, D = q.shape[:-1], q.shape[-1] // H
    k = ins["K"][0].astype(jnp.float32).reshape(lead + (G, D))
    v = ins["V"][0].astype(jnp.float32).reshape(lead + (G, D))
    lg = jax.nn.log_sigmoid(ins["Gate"][0].astype(jnp.float32))
    return q.reshape(lead + (H, D)), k, v, lg


@register_op("power_scan", no_grad=True)
def _power_scan(ctx, ins, attrs):
    """The chunked scan over a whole prompt from a zero state: ``Y`` and
    ``StateOut [B, G, R, D]`` / ``NormOut [B, G, D, D]``, the state and
    the normaliser after the last position, written whole (nothing of the
    variables' previous values survives), in chunks that follow from
    ``T`` (``kernels.power.scan_chunk``). A Pallas kernel on the TPU,
    ``jax.numpy`` elsewhere; any ``T`` is right."""
    from ..kernels.power import power_scan

    q, k, v, lg = _operands(ins, attrs)
    y, state, norm = power_scan(q, k, v, lg)
    return {"Y": [y.reshape(ins["Q"][0].shape)], "StateOut": [state],
            "NormOut": [norm]}


@register_op("power_update", no_grad=True)
def _power_update(ctx, ins, attrs):
    """One token a row into ``State`` and ``Norm`` (persistable: the
    executor donates them and the kernel writes them in place): ``Y [B,
    1, H D]`` read out of the new state, ``StateOut`` and ``NormOut`` the
    same variables."""
    from ..kernels.power import power_update

    q, k, v, lg = _operands(ins, attrs)
    y, state, norm = power_update(
        ins["State"][0], ins["Norm"][0], q[:, 0], k[:, 0], v[:, 0],
        lg[:, 0])
    return {"Y": [y.reshape(ins["Q"][0].shape)], "StateOut": [state],
            "NormOut": [norm]}
