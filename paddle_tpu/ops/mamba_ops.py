"""The ops of a Mamba-1 mixer's core (models/gpt.py ``layer_types`` entry
``"mamba"``; kernels/mamba.py holds the arithmetic and says how the state
is laid out): the selective scan over a whole prompt and the one-token
update of a slot's state in place. Inference-only: neither has a
backward. The causal convolution in front of both is ops/ssm_ops.py's.

Both take the layer's activations flat — ``X [B, T, C]`` (the convolved
channels), ``Dt [B, T, C]`` (raw: the op adds ``DtBias`` and takes the
softplus), ``Bm`` / ``Cm`` ``[B, T, N]`` — and the parameters ``ALog [C,
N]`` (``A = -exp(ALog)``: one decay rate a channel AND state), ``D`` and
``DtBias`` ``[C]``. ``Y`` is the recurrence's output plus the skip ``D_c
x``.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from ..core.registry import register_op

__all__: List[str] = []


def _operands(ins):
    """``(x, dt, a, bm, cm, skip)`` of a scan or an update: ``dt``
    positive, ``a`` negative, ``skip`` = ``D_c x`` in ``x``'s shape."""
    f32 = jnp.float32
    x = ins["X"][0].astype(f32)
    dt = jax.nn.softplus(ins["Dt"][0].astype(f32)
                         + ins["DtBias"][0].astype(f32))
    a = -jnp.exp(ins["ALog"][0].astype(f32))
    return (x, dt, a, ins["Bm"][0].astype(f32), ins["Cm"][0].astype(f32),
            x * ins["D"][0].astype(f32))


@register_op("mamba_scan", no_grad=True)
def _mamba_scan(ctx, ins, attrs):
    """The selective scan over a whole prompt from a zero state: ``Y [B,
    T, C]`` and ``StateOut [B, 1, N, C]``, the state after the last
    position, written whole (nothing of the variable's previous value
    survives), in blocks of ``kernels.mamba.scan_block``. A Pallas kernel
    that walks the positions on the TPU, ``jax.numpy`` elsewhere; any
    ``T`` is right."""
    from ..kernels.mamba import mamba_scan

    x, dt, a, bm, cm, skip = _operands(ins)
    y, state = mamba_scan(x, dt, a, bm, cm)
    return {"Y": [y + skip], "StateOut": [state]}


@register_op("mamba_update", no_grad=True)
def _mamba_update(ctx, ins, attrs):
    """One token a row into ``State [B, 1, N, C]`` (persistable: the
    executor donates it and the kernel writes it in place): ``Y [B, 1,
    C]`` and ``StateOut``, the same variable."""
    from ..kernels.mamba import mamba_update

    x, dt, a, bm, cm, skip = _operands(ins)
    y, state = mamba_update(ins["State"][0], x[:, 0], dt[:, 0], a,
                            bm[:, 0], cm[:, 0])
    return {"Y": [y[:, None] + skip], "StateOut": [state]}
