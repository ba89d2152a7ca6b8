"""Expert parallelism: switch-style MoE over an 'expert' mesh axis
(top-1 Switch routing by default; top_k=2 for GShard-style).

The reference (Fluid v1.3) has no mixture-of-experts; this is the
TPU-first 'ep' extension completing the dp/tp/sp/pp/ep set: experts are
sharded one-per-device over a mesh axis, tokens route to their expert
with lax.all_to_all (the ICI shuffle), compute their expert FFN locally,
and shuffle back. Capacity is static (XLA needs static shapes): each
device sends up to `capacity` tokens per expert; overflow tokens drop to
zero contribution, exactly the Switch-Transformer discipline.

Differentiable end to end (all_to_all transposes to the reverse
shuffle); the router's load-balancing aux loss follows Switch (mean
fraction x mean probability per expert).

Use under shard_map with expert weights sharded on the axis:

    fn = shard_map(lambda w1, b1, w2, b2, x: moe_apply(...),
                   mesh, in_specs=(P("expert"), ..., P()), out_specs=P())
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["moe_apply", "route_tokens", "router"]


def router(x, gate_w, E, top_k=1, z_loss=0.0, norm_topk=None,
           score="softmax", bias=None, route_scale=1.0, norm_eps=1e-20):
    """Router scores in float32, the k best experts a token and their
    gates. ``score`` 'softmax' (over the experts) or 'sigmoid' (each
    expert's own). ``bias`` [E] is added to the scores for the SELECTION
    only: it changes who is chosen and never a gate, which is the chosen
    expert's raw score. ``norm_topk`` renormalises the k gates to sum to
    one: None is the Switch/GShard rule (raw for top_k=1, renormalised
    above), False keeps the raw scores (OLMoE), True always renormalises
    (sigmoid scores over ``sum + norm_eps``); ``route_scale`` multiplies
    the gates after that.

    Returns (expert_idx [K,T], gate [K,T], aux scalar)."""
    if score not in ("softmax", "sigmoid"):
        raise ValueError("router score must be 'softmax' or 'sigmoid'; "
                         "got %r" % (score,))
    logits = jnp.dot(x.astype(jnp.float32), gate_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)    # [T, E]
    sigmoid = score == "sigmoid"
    probs = jax.nn.sigmoid(logits) if sigmoid \
        else jax.nn.softmax(logits, axis=-1)             # [T, E]
    if bias is None:
        top_p, top_e = jax.lax.top_k(probs, top_k)       # [T, K] each
    else:
        _, top_e = jax.lax.top_k(
            probs + bias.astype(jnp.float32)[None, :], top_k)
        top_p = jnp.take_along_axis(probs, top_e, axis=-1)
    if norm_topk is None:
        # Switch: the output scales by the RAW router probability — that
        # product is how gradients reach the router at all; GShard:
        # gates renormalized over the chosen experts
        norm_topk = top_k > 1
    if norm_topk:
        denom = jnp.sum(top_p, axis=-1, keepdims=True)
        top_p = top_p / (denom + norm_eps if sigmoid else denom)
    if route_scale != 1.0:
        top_p = top_p * route_scale
    gate = top_p.T.astype(x.dtype)                       # [K, T]
    expert_idx = top_e.T                                 # [K, T]

    onehot1 = jax.nn.one_hot(expert_idx[0], E)
    if sigmoid:   # the balance term wants a distribution over the experts
        probs = probs / (jnp.sum(probs, axis=-1, keepdims=True) + 1e-20)
    aux = E * jnp.sum(jnp.mean(onehot1, axis=0) * jnp.mean(probs, axis=0))
    if z_loss:
        aux = aux + z_loss * jnp.mean(
            jax.nn.logsumexp(logits, axis=-1) ** 2)
    return expert_idx, gate, aux


def route_tokens(x, gate_w, E, capacity, top_k=1, z_loss=0.0,
                 norm_topk=None, **scoring):
    """Shared top-k routing/capacity math — the ONE derivation both the
    distributed paths and the single-device path (ops/moe_ops.py) use,
    so their exact-parity contract can't drift.

    top_k=1 is Switch routing; top_k>1 is GShard-style: each token goes
    to its k best experts with gates renormalized over the chosen
    probabilities (``norm_topk``, see ``router``), and capacity claims
    happen in CHOICE-MAJOR priority
    (every token's 1st choice before any 2nd choice — a token never
    loses its primary expert slot to another token's secondary).

    Returns (expert_idx [K,T], gate [K,T], pos [K,T], keep [K,T],
    aux scalar). ``scoring`` is ``router``'s ``score``/``bias``/
    ``route_scale``. The aux load-balancing loss follows Switch/GShard:
    first-choice dispatch fraction x mean router probability. With
    ``z_loss > 0`` the ST-MoE router z-loss —
    ``z_loss * mean(logsumexp(logits)^2)`` — folds into aux: it keeps
    router logits small (numerically stable under bf16) without
    changing which experts win.
    """
    T = x.shape[0]
    expert_idx, gate, aux = router(x, gate_w, E, top_k, z_loss, norm_topk,
                                   **scoring)

    # positions: flatten choice-major so cumsum gives 1st choices
    # priority over 2nd within each expert's capacity
    flat_e = expert_idx.reshape(-1)                      # [K*T]
    onehot = jax.nn.one_hot(flat_e, E)
    pos = (jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
           ).astype(jnp.int32).reshape(top_k, T)
    keep = pos < capacity
    return expert_idx, gate, pos, keep, aux


def moe_apply(expert_params, gate_w, x, axis_name, capacity=None,
              top_k=1, z_loss=0.0):
    """Route tokens to per-device experts and back.

    expert_params: pytree with leading expert dim sharded on `axis_name`
        (each device sees its slice of size 1); applied as
        h = relu(x @ w1 + b1); y = h @ w2 + b2 for (w1, b1, w2, b2).
    gate_w: [D, E] router weights (replicated).
    x: [T, D] local tokens (the data may also be sharded on another axis).
    capacity: max tokens each device routes to EACH expert (static);
        default ceil(2 * T * top_k / E). top_k: experts per token
        (1 = Switch, k>1 = GShard-style). z_loss: ST-MoE router z-loss
        weight folded into aux (see route_tokens).

    Returns ([T, D] outputs, aux_loss scalar).
    """
    from ..observe.families import ENGINE_COLLECTIVES

    ENGINE_COLLECTIVES.labels(kind="all_to_all").inc()  # per trace
    E = int(lax.psum(1, axis_name))
    T, D = x.shape
    capacity = int(capacity or -(-2 * T * top_k // E))

    expert_idx, gate, pos, keep, aux = route_tokens(x, gate_w, E,
                                                    capacity, top_k,
                                                    z_loss)

    # scatter tokens into the [E, capacity, D] send buffer (a top-2
    # token appears in both its experts' buffers)
    buf = jnp.zeros((E, capacity, D), x.dtype)
    safe_e = jnp.where(keep, expert_idx, 0)              # [K, T]
    safe_p = jnp.where(keep, pos, 0)
    for kk in range(safe_e.shape[0]):
        buf = buf.at[safe_e[kk], safe_p[kk]].add(
            jnp.where(keep[kk][:, None], x, 0.0))

    # all_to_all: dim 0 (expert) scatters, tokens from every device
    # gather on the expert's device -> [E, capacity, D] = per-source rows
    recv = lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)

    w1, b1, w2, b2 = jax.tree.map(lambda p: p[0], expert_params)
    h = jax.nn.relu(recv.reshape(-1, D) @ w1 + b1)
    y = (h @ w2 + b2).reshape(E, capacity, D)

    # shuffle results back to the token owners
    back = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)                    # [E, capacity, D]

    out = jnp.zeros((T, D), back.dtype)
    for kk in range(safe_e.shape[0]):
        got = back[safe_e[kk], safe_p[kk]]               # [T, D]
        got = jnp.where(keep[kk][:, None], got, 0.0)
        out = out + got * gate[kk][:, None]
    return out, aux
