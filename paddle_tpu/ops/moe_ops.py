"""moe_ffn op: a mixture-of-experts FFN as one graph op — top-k routing
over stacked experts, Switch/GShard-style under a capacity or dropless.

The reference (Fluid v1.3) has no mixture-of-experts; this op promotes
`parallel/moe.py` into the Program/layers API (the 'ep' axis). Expert
weights arrive stacked [E, ...].

On one device (``_experts``): router in float32 -> top_k -> the (token,
expert) pairs sorted by expert -> a grouped matmul over the ragged
groups (``kernels/moe_gmm.py``: a Pallas kernel on the TPU, ragged_dot
elsewhere) -> activation -> a second grouped matmul -> the gate-weighted
sum back per token. ``act='relu'`` experts carry biases, ``'swiglu'``
experts (gate, up, down) none, ``'relu2'`` experts (``relu(x W1)^2 W2``)
neither a gate nor biases. With ``XE`` the experts read a tensor of
their own width (a latent of the tokens) while the router still scores
``X``: the output then has ``XE``'s width. ``dropless`` computes every pair; a
``capacity`` drops the overflow exactly as ``route_tokens`` says, by
giving the dropped pairs to no group before the sort — one path for both.

Under a ParallelEngine mesh with an 'expert' axis of size E each device
computes ITS expert on the tokens routed to it and the [capacity, D]
results all_gather back — with the engine's replicated activations every
device already holds the full token set, so this costs ONE collective and
capacity rows per expert (the general token-sharded case, where tokens
must first travel to their expert's device via all_to_all, lives in
`parallel/moe.py`'s ``moe_apply`` for shard_map users); ReLU experts
under a capacity only. All paths share ``route_tokens``, so single-device
and expert-parallel runs agree exactly (the parity contract the tests
pin): static capacity with choice-major priority, overflow tokens
contribute zero, aux load-balancing loss.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.registry import register_op

__all__: List[str] = []


def _experts(x, w1, w1v, b1, w2, b2, gate_w, E, top_k, capacity, act,
             norm_topk, z_loss, scoring=None, share=None, xe=None):
    """Single-device path, dropless, capacity-bound or a share: the
    (token, expert) pairs sorted by expert, two grouped matmuls over the
    ragged groups (kernels/moe_gmm.py), the gate-weighted sum back per
    token.

    ``capacity`` None is dropless: every pair computes. A number is the
    Switch/GShard discipline of ``route_tokens``: the pairs past an
    expert's capacity are given to no group before the sort, so they
    compute nothing and contribute zero — the same path, and the same
    answer as the expert-parallel branch below. ``share`` =
    ``(expert_first, n_local)`` says the stacked weights hold only the
    experts ``expert_first .. expert_first + n_local - 1`` of the ``E``
    the router scores: the pairs routed to an absent expert are given to
    no group in the same way, and this chip's output is its own experts'
    part of the layer (the parts of all shares add up to the whole).
    ``scoring`` is ``router``'s ``score``/``bias``/``route_scale``.
    ``xe [T, D']`` is what the experts read where that is not ``x`` (the
    router's input).

    Returns (out [T, D] — ``D'`` with ``xe`` —, aux, pairs given to each of the ``E`` experts
    the router scores [E] int32 — a share's own groups are its slice)."""
    from ..kernels.moe_gmm import KERNEL_DOWN, KERNEL_UP, gmm
    from ..parallel.moe import route_tokens, router

    T = x.shape[0]
    scoring = scoring or {}
    if capacity is None:
        expert_idx, gate, aux = router(x, gate_w, E, top_k, z_loss,
                                       norm_topk, **scoring)
        flat_e = expert_idx.reshape(-1)                  # [K*T]
    else:
        expert_idx, gate, _pos, keep, aux = route_tokens(
            x, gate_w, E, capacity, top_k, z_loss, norm_topk, **scoring)
        # a dropped pair belongs to no expert: it sorts behind them all
        flat_e = jnp.where(keep, expert_idx, E).reshape(-1)
        gate = jnp.where(keep, gate, 0)
    routed = sizes = jnp.sum(
        flat_e[:, None] == jnp.arange(E)[None, :], axis=0,
        dtype=jnp.int32)                                 # [E]
    if share is not None:
        first, E = share              # from here on E counts held groups
        local = flat_e - first
        held = jnp.logical_and(local >= 0, local < E)
        flat_e = jnp.where(held, local, E)
        gate = jnp.where(held.reshape(gate.shape), gate, 0)
        sizes = routed[first:first + E]
    order = jnp.argsort(flat_e, stable=True)             # pair -> sorted
    sorted_e = jnp.minimum(flat_e[order], E - 1)
    xs = (x if xe is None else xe)[order % T]            # [K*T, D]
    if act == "swiglu":
        h = gmm(xs, (w1, w1v), sizes, name=KERNEL_UP)
    elif act == "relu2":
        h = jnp.square(jax.nn.relu(gmm(xs, w1, sizes, name=KERNEL_UP)))
    else:
        h = jax.nn.relu(gmm(xs, w1, sizes, name=KERNEL_UP)
                        + b1[sorted_e])
    y = gmm(h, w2, sizes, name=KERNEL_DOWN)
    if b2 is not None:
        y = y + b2[sorted_e]
    # back to pair order (choice-major), then the k gated terms of a
    # token add in choice order: a gather and a fixed sum, no scatter
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    y = y[back].reshape(top_k, T, -1) * gate[:, :, None]
    return jnp.sum(y, axis=0), aux, routed


@register_op("moe_ffn",
             diff_inputs=["X", "W1", "W1V", "B1", "W2", "B2", "Gate"],
             needs_env=False)
def _moe_ffn(ctx, ins, attrs):
    from ..parallel.moe import route_tokens

    def opt(slot):
        return ins[slot][0] if ins.get(slot) else None

    x = ins["X"][0]
    w1, w2, gate_w = ins["W1"][0], ins["W2"][0], ins["Gate"][0]
    w1v, b1, b2, counts = opt("W1V"), opt("B1"), opt("B2"), opt("Counts")
    touched, xe = opt("Touched"), opt("XE")
    E = int(attrs["n_experts"])
    scoring = {"score": attrs.get("router_score", "softmax"),
               "bias": opt("RouterBias"),
               "route_scale": float(attrs.get("route_scale", 1.0))}
    if attrs.get("norm_topk_eps"):
        scoring["norm_eps"] = float(attrs["norm_topk_eps"])
    n_local = int(attrs.get("n_local") or E)
    share = None if n_local == E else \
        (int(attrs.get("expert_first", 0)), n_local)
    axis = attrs.get("axis", "expert")
    top_k = int(attrs.get("top_k", 1))
    z_loss = float(attrs.get("z_loss", 0.0))
    act = attrs.get("act", "relu")
    norm_topk = attrs.get("norm_topk")
    dropless = bool(attrs.get("dropless", False))

    D = x.shape[-1]
    xf = x.reshape(-1, D)
    T = xf.shape[0]
    capacity = None if dropless else \
        int(attrs.get("capacity") or -(-2 * T * top_k // E))

    mesh = ctx.mesh
    use_ep = mesh is not None and axis in mesh.axis_names \
        and mesh.shape[axis] > 1
    if use_ep and mesh.shape[axis] != E:
        raise ValueError(
            "moe_ffn with n_experts=%d under a mesh whose %r axis has %d "
            "devices — experts map one-per-device" % (E, axis,
                                                      mesh.shape[axis]))
    plain = share is None and scoring["score"] == "softmax" \
        and scoring["bias"] is None and scoring["route_scale"] == 1.0
    if use_ep and (dropless or act != "relu" or not plain):
        raise NotImplementedError(
            "moe_ffn: the expert-parallel branch runs ReLU experts under "
            "a capacity with the softmax router; dropless or swiglu "
            "experts, a share of the experts and the sigmoid router run "
            "on one device")

    if use_ep and xe is not None:
        raise NotImplementedError(
            "moe_ffn: experts with an input of their own (XE) run on one "
            "device")
    if not use_ep:
        out, aux, routed = _experts(
            xf, w1, w1v, b1, w2, b2, gate_w, E, top_k, capacity, act,
            norm_topk, z_loss, scoring, share,
            None if xe is None else xe.reshape(T, -1))
        outs = {"Out": out.reshape(x.shape[:-1] + out.shape[-1:]),
                "AuxLoss": aux}
        row = int(attrs.get("counts_row", 0))
        if counts is not None:
            # the device-side tally of routed pairs: this layer's row,
            # over all the experts the router scores
            outs["CountsOut"] = counts.at[row].add(
                routed.astype(counts.dtype))
        if touched is not None:
            # calls in which each HELD expert was given a pair: the
            # grouped matmul fetches no weights for an empty group
            first = share[0] if share is not None else 0
            outs["TouchedOut"] = touched.at[row].add(
                (routed[first:first + n_local] > 0).astype(touched.dtype))
        return outs

    def shard_body(xl, w1l, b1l, w2l, b2l, gl):
        # xl replicated on the axis -> routing is identical everywhere;
        # each device fills the send buffer, runs ITS expert on its
        # [capacity, D] slice, and one all_gather rebuilds [E, capacity,
        # D] results for the (replicated) token-side gather.
        expert_idx, gate, pos, keep, aux = route_tokens(
            xl, gl, E, capacity, top_k, z_loss, norm_topk)
        safe_e = jnp.where(keep, expert_idx, 0)       # [K, T]
        safe_p = jnp.where(keep, pos, 0)
        buf = jnp.zeros((E, capacity, D), xl.dtype)
        for kk in range(top_k):
            buf = buf.at[safe_e[kk], safe_p[kk]].add(
                jnp.where(keep[kk][:, None], xl, 0.0))

        d = lax.axis_index(axis)
        mine = lax.dynamic_index_in_dim(buf, d, axis=0, keepdims=False)
        h = jax.nn.relu(mine @ w1l[0] + b1l[0])
        y = h @ w2l[0] + b2l[0]                       # [capacity, D]
        ys = lax.all_gather(y, axis)                  # [E, capacity, D]

        out = jnp.zeros_like(xl)
        for kk in range(top_k):
            got = ys[safe_e[kk], safe_p[kk]]
            got = jnp.where(keep[kk][:, None], got, 0.0)
            out = out + got * gate[kk][:, None]
        return out, aux

    # check_vma off: ys is the same on every device after the
    # all_gather, but the varying-manner analysis cannot prove the
    # gathered values replicated (the parity tests pin it numerically)
    fn = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(),) + (P(axis),) * 4 + (P(),),
        out_specs=(P(), P()),
        check_vma=False,
    )
    out, aux = fn(xf, w1, b1, w2, b2, gate_w)
    return {"Out": out.reshape(x.shape), "AuxLoss": aux}
