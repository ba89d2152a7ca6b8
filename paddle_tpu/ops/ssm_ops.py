"""The ops of a state-space (Mamba-2) mixer: the selective scan over a
whole prompt, its one-token update of a slot's state in place, and the
causal depth-wise convolution in front of both with its carried rows
(models/gpt.py ``cfg['mixers']``; kernels/ssm.py holds the arithmetic
and says how the state is laid out). Inference-only: none has a
backward.

All four take the layer's activations flat, as the projections leave
them — ``X [B, T, H P]``, ``Dt [B, T, H]`` (raw: the op adds ``DtBias``
and takes the softplus), ``Bm`` / ``Cm`` ``[B, T, G N]`` — and the
per-head parameters ``ALog``, ``D``, ``DtBias`` ``[H]``. ``Y`` is the
recurrence's output plus the skip ``D_h x``.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from ..core.registry import register_op

__all__: List[str] = []


def _operands(ins, attrs):
    """``(x, dt, a, bm, cm, skip)`` of a scan or an update: ``dt``
    positive, ``a`` negative, ``bm``/``cm`` split into groups, ``skip``
    = ``D_h x`` in ``x``'s shape."""
    x, dt = ins["X"][0].astype(jnp.float32), ins["Dt"][0]
    H, G = int(attrs["heads"]), int(attrs["groups"])
    N = int(attrs["state"])
    P = x.shape[-1] // H
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + ins["DtBias"][0].astype(jnp.float32))
    a = -jnp.exp(ins["ALog"][0].astype(jnp.float32))
    lead = x.shape[:-1]
    bm = ins["Bm"][0].astype(jnp.float32).reshape(lead + (G, N))
    cm = ins["Cm"][0].astype(jnp.float32).reshape(lead + (G, N))
    skip = x * jnp.repeat(ins["D"][0].astype(jnp.float32), P)
    return x, dt, a, bm, cm, skip


@register_op("ssm_scan", no_grad=True)
def _ssm_scan(ctx, ins, attrs):
    """The selective scan over a whole prompt from a zero state: ``Y
    [B, T, H P]`` and ``StateOut [B, G, N, L]``, the state after the
    last position, written whole (nothing of the variable's previous
    value survives). Chunked (attr ``chunk``): a Pallas kernel on the
    TPU, ``jax.numpy`` elsewhere; any ``T`` is right."""
    from ..kernels.ssm import ssm_scan

    x, dt, a, bm, cm, skip = _operands(ins, attrs)
    y, state = ssm_scan(x, dt, a, bm, cm, chunk=int(attrs["chunk"]))
    return {"Y": [y + skip], "StateOut": [state]}


@register_op("ssm_update", no_grad=True)
def _ssm_update(ctx, ins, attrs):
    """One token a row into ``State [B, G, N, L]`` (persistable: the
    executor donates it and the kernel writes it in place): ``Y [B, 1,
    H P]`` and ``StateOut``, the same variable."""
    from ..kernels.ssm import ssm_update

    x, dt, a, bm, cm, skip = _operands(ins, attrs)
    y, state = ssm_update(ins["State"][0], x[:, 0], dt[:, 0], a, bm[:, 0],
                          cm[:, 0])
    return {"Y": [y[:, None] + skip], "StateOut": [state]}


def _bias(ins):
    return ins["Bias"][0] if ins.get("Bias") else None


@register_op("causal_conv", no_grad=True)
def _causal_conv(ctx, ins, attrs):
    """Causal depth-wise convolution of a whole prompt ``X [B, T, C]``
    under ``W [C, K]`` and ``Bias [C]``, then silu (attr ``act``):
    ``Out`` and ``RowsOut [B, K - 1, C]``, the last ``K - 1`` positions
    of ``X`` itself for the next token, written whole. With attr
    ``columns`` ``[lo, hi]`` the prompt is those columns of a wider ``X``,
    read where they lie (no slice is made in front of the kernel)."""
    from ..kernels.ssm import conv_prefill

    columns = attrs.get("columns")
    out, rows = conv_prefill(ins["X"][0], ins["W"][0], _bias(ins),
                             act=bool(attrs.get("act", True)),
                             columns=columns and tuple(map(int, columns)))
    return {"Out": [out], "RowsOut": [rows]}


@register_op("causal_conv_step", no_grad=True)
def _causal_conv_step(ctx, ins, attrs):
    """One token's convolution from the carried ``Rows [B, K - 1, C]``
    (persistable, donated): ``Out [B, 1, C]`` and ``RowsOut``, the same
    variable shifted by the token."""
    from ..kernels.ssm import conv_step

    out, rows = conv_step(ins["X"][0], ins["Rows"][0], ins["W"][0],
                          _bias(ins), act=bool(attrs.get("act", True)))
    return {"Out": [out], "RowsOut": [rows]}
