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
``n_zero`` identity (zero-compute) experts stand behind the ``E`` with
weights in the router's outputs: a pair that chose one is given to no
group either, and its gate goes into ONE weight a token, ``out += w x``.

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

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.registry import register_op

__all__: List[str] = []


# A share's prefill cuts its sorted pair rows at ``_COMPACT_FACTOR`` times
# the share's even part of them (``compact_rows``): the rows past the
# held pairs belong to no group, and every stage after the sort would
# still run over them. Under ``_COMPACT_MIN_PAIRS`` pair rows the full
# length costs what the cut does (on the chip, an expert layer alone:
# 1.98 ms either way at Nemotron's decode step of 2,112 rows, 2.34
# against 2.20 at 5,632; docs/KERNELS.md) and a ``cond`` would only sit
# in the step, so such a program holds none.
_COMPACT_FACTOR = 2
_COMPACT_MIN_PAIRS = 4096


def compact_rows(M, E, n_local):
    """The static bound on a share's held pairs, a whole number of the
    grouped matmul's 128-row tiles, or None where the call keeps the
    full length: all experts held, fewer than ``_COMPACT_MIN_PAIRS``
    pair rows, or a bound that cuts nothing."""
    from ..kernels.common import ceil_to

    if n_local >= E or M < _COMPACT_MIN_PAIRS:
        return None
    cap = ceil_to(-(-_COMPACT_FACTOR * M * n_local // E), 128)
    return cap if cap < M else None


def _expert_rows(src, tok, sorted_e, sizes, w1, w1v, b1, w2, b2, act):
    """The experts over pair rows sorted by group: row ``r`` reads token
    ``tok[r]`` of ``src`` and belongs to group ``sorted_e[r]``; rows past
    ``sum(sizes)`` belong to none. Returns ``[rows, D]`` before the
    gates."""
    from ..kernels.moe_gmm import KERNEL_DOWN, KERNEL_UP, gmm

    xs = src[tok]                                        # [rows, D]
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
    return y


def _choices_back(y, order, gate, cut=None):
    """Sorted rows back to pair order (choice-major), then the k gated
    terms of a token add in choice order: a gather and a fixed sum, no
    scatter. ``cut``: ``y`` holds only the first ``cut`` sorted rows, and
    a pair past them (of no held group) reads a row of zeros."""
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    if cut is not None:
        back = jnp.minimum(back, cut)
        y = jnp.concatenate([y, jnp.zeros((1, y.shape[1]), y.dtype)])
    top_k, T = gate.shape
    y = y[back].reshape(top_k, T, -1) * gate[:, :, None]
    return jnp.sum(y, axis=0)


def _take(a, idx):
    """``a[idx]`` along the first axis for ``idx`` known to be in range:
    the one ``lax.gather``, without the wrapper's index arithmetic."""
    dnums = lax.GatherDimensionNumbers(
        offset_dims=tuple(range(1, a.ndim)), collapsed_slice_dims=(0,),
        start_index_map=(0,))
    return lax.gather(a, idx[:, None], dnums, (1,) + a.shape[1:],
                      mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _shifted(a, d, fill):
    """``a`` moved ``d`` rows down (up for a negative ``d``), ``fill``
    in the rows it left."""
    n = a.shape[0]
    cut = lax.slice_in_dim(a, 0, n - d) if d > 0 \
        else lax.slice_in_dim(a, -d, n)
    edge = (d, 0, 0) if d > 0 else (0, -d, 0)
    return lax.pad(cut, np.array(fill, a.dtype),
                   [edge] + [(0, 0, 0)] * (a.ndim - 1))


def _tokens_back(z, tok, T, most):
    """Gated rows ``z [rows, D]`` of tokens ``tok [rows]`` (``T`` for a
    row of no token) summed a token, ``[T, D]``, where a token owns at
    the most ``most`` of the rows and most tokens none: the rows sorted
    by token (stably), each token's run summed by a segmented scan of
    ``ceil(log2 most)`` doubling steps — a fixed tree over the run,
    whatever the device — and ONE gather of a token's last row (of a row
    of zeros where it owns none). No ``[K T, D]`` array exists and
    nothing scatters a row."""
    rows = z.shape[0]
    number = lax.iota(tok.dtype, rows)
    ts, perm = lax.sort((tok, number), num_keys=1, is_stable=True)
    z = _take(z, perm)
    d = 1
    while d < most:
        # sorted: the row d above is of this token only if all between are
        same = lax.eq(ts, _shifted(ts, d, -1))
        z = z + lax.select(lax.broadcast_in_dim(same, z.shape, (0,)),
                           _shifted(z, d, 0), lax.full_like(z, 0))
        d *= 2
    # where each token's run ends; every other row writes out of range,
    # each to a place of its own, and is dropped
    last = lax.ne(ts, _shifted(ts, -1, -1))
    at = lax.scatter(
        lax.full((T,), rows, ts.dtype),
        lax.select(last, ts, number + (T + 1))[:, None], number,
        lax.ScatterDimensionNumbers(
            update_window_dims=(), inserted_window_dims=(0,),
            scatter_dims_to_operand_dims=(0,)),
        unique_indices=True, mode=lax.GatherScatterMode.FILL_OR_DROP)
    return _take(lax.pad(z, np.array(0, z.dtype),
                         [(0, 1, 0), (0, 0, 0)]), at)


def _experts(x, w1, w1v, b1, w2, b2, gate_w, E, top_k, capacity, act,
             norm_topk, z_loss, scoring=None, share=None, xe=None,
             n_zero=0):
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
    router's input). ``n_zero`` identity experts are the router's outputs
    ``E .. E + n_zero - 1`` (dropless only): a pair that chose one belongs
    to no group, as a share's absent pair does — it is in no group's rows
    of either grouped matmul and past every cut — and the token's
    identity gates add up to one weight, ``out += w x``. A share's bound
    is reckoned over all ``E + n_zero`` outputs the pairs spread over.

    A share's held pairs are the FIRST ``sum(sizes)`` rows of the sorted
    order. Where ``compact_rows`` gives a bound ``cap``, the rows are
    cut at it before the gather whenever the held pairs fit (a
    ``lax.cond`` on the device): the gather, both grouped matmuls and
    the activation run over ``cap`` rows, and the way back sums the cut
    rows a token (``_tokens_back``) where that moves fewer rows than the
    choices' gather over the cut rows (``_choices_back``). An input
    whose router sends the share more than the bound takes the full
    length, as every call without a bound does: no pair is ever dropped.

    Returns (out [T, D] — ``D'`` with ``xe`` —, aux, pairs given to each
    of the ``E + n_zero`` outputs the router scores, int32 — a share's
    own groups are a slice of the first ``E`` —, the branch a bounded
    call took: int32 1 cut, 0 full; None for a call without a bound, and
    the most experts WITH weights one token chose, int32; None without
    identity experts)."""
    from ..parallel.moe import route_tokens, router

    T = x.shape[0]
    scoring = scoring or {}
    E_all = E + n_zero
    # the op stands under ``moe.experts`` (models/gpt.py); what scores and
    # picks says so in the device's operations' names, by a class of
    # core/program.py::SCOPE_CLASSES
    with jax.named_scope("moe.router"):
        if capacity is None:
            expert_idx, gate, aux = router(x, gate_w, E_all, top_k, z_loss,
                                           norm_topk, **scoring)
            flat_e = expert_idx.reshape(-1)              # [K*T]
        else:
            expert_idx, gate, _pos, keep, aux = route_tokens(
                x, gate_w, E, capacity, top_k, z_loss, norm_topk,
                **scoring)
            # a dropped pair belongs to no expert: sorts behind them all
            flat_e = jnp.where(keep, expert_idx, E).reshape(-1)
            gate = jnp.where(keep, gate, 0)
    routed = sizes = jnp.sum(
        flat_e[:, None] == jnp.arange(E_all)[None, :], axis=0,
        dtype=jnp.int32)                                 # [E + n_zero]
    identity = real_most = None
    if n_zero:
        ident = expert_idx >= E                          # [K, T]
        identity = jnp.sum(jnp.where(ident, gate, 0), axis=0)[:, None] * x
        real_most = jnp.max(jnp.sum(~ident, axis=0, dtype=jnp.int32))
        # an identity pair belongs to no group: it sorts behind them all
        flat_e = jnp.minimum(flat_e, E)
        gate = jnp.where(ident, 0, gate)
        sizes = routed[:E]
    cap = None
    if share is not None:
        cap = compact_rows(flat_e.shape[0], E_all, share[1])
        first, E = share              # from here on E counts held groups
        local = flat_e - first
        held = jnp.logical_and(local >= 0, local < E)
        flat_e = jnp.where(held, local, E)
        gate = jnp.where(held.reshape(gate.shape), gate, 0)
        sizes = routed[first:first + E]
    order = jnp.argsort(flat_e, stable=True)             # pair -> sorted
    sorted_e = jnp.minimum(flat_e[order], E - 1)
    src = x if xe is None else xe
    if cap is None:
        y = _expert_rows(src, order % T, sorted_e, sizes, w1, w1v, b1, w2,
                         b2, act)
        out, took = _choices_back(y, order, gate), None
    else:
        out, took = _bounded(src, order, sorted_e, sizes, gate, w1, w1v,
                             b1, w2, b2, cap=cap, act=act)
    if identity is not None:
        out = out + identity
    return out, aux, routed, took, real_most


@functools.partial(jax.jit, static_argnames=("cap", "act"))
def _bounded(src, order, sorted_e, sizes, gate, w1, w1v, b1, w2, b2, *,
             cap, act):
    """``_experts`` after the sort for a call with a bound ``cap`` on the
    held pairs: ``(out, 1)`` from the first ``cap`` sorted rows where the
    held pairs fit, ``(out, 0)`` from all of them where they do not.

    A jit of its own, because the expert layers of one program hand it
    the same shapes: the two bodies (four Pallas calls) are traced and
    lowered once a program, not once a layer — a layer's body twice over
    is what a prefill program's set-up would otherwise pay
    (``paddle_moe_gmm_plans_total`` then counts a program's plans once,
    not once a layer)."""
    T = src.shape[0]
    top_k, E = gate.shape[0], sizes.shape[0]
    M = order.shape[0]
    n_held = jnp.sum(sizes)

    def full():
        y = _expert_rows(src, order % T, sorted_e, sizes, w1, w1v, b1, w2,
                         b2, act)
        return _choices_back(y, order, gate)

    # a token owns at the most this many of the cut rows
    most = min(top_k, E)
    # rows a way back moves, in passes over them: a gather, the scan's
    # steps and a gather over cap rows against a gather and a sum over
    # all K T
    by_token = cap * (most - 1).bit_length() + cap < M

    def compact():
        # the held pairs are the first rows of the sorted order: every
        # row past them has a gate of zero
        rows = lax.slice_in_dim(order, 0, cap)
        tok = lax.rem(rows, lax.full_like(rows, T))
        y = _expert_rows(src, tok, lax.slice_in_dim(sorted_e, 0, cap),
                         sizes, w1, w1v, b1, w2, b2, act)
        if not by_token:
            return _choices_back(y, order, gate, cut=cap)
        g = _take(lax.reshape(gate, (M,)), rows)
        # the cut rows past the held pairs are pairs of no held group,
        # up to top_k a token: they go behind every token's run
        owned = lax.lt(lax.iota(n_held.dtype, cap),
                       lax.broadcast(n_held, (cap,)))
        return _tokens_back(y * g[:, None],
                            lax.select(owned, tok, lax.full_like(tok, T)),
                            T, most)

    fits = n_held <= cap
    return lax.cond(fits, compact, full), fits.astype(jnp.int32)


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
    touched, xe, compact = opt("Touched"), opt("XE"), opt("Compact")
    zero = opt("Zero")
    E = int(attrs["n_experts"])
    n_zero = int(attrs.get("n_zero") or 0)
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
        and scoring["bias"] is None and scoring["route_scale"] == 1.0 \
        and not n_zero
    if n_zero and (not dropless or xe is not None):
        raise NotImplementedError(
            "moe_ffn: identity experts (n_zero) are a dropless layer's, "
            "over the tokens themselves (no XE): a capacity has no rule "
            "for a pair that costs nothing")
    if use_ep and (dropless or act != "relu" or not plain):
        raise NotImplementedError(
            "moe_ffn: the expert-parallel branch runs ReLU experts under "
            "a capacity with the softmax router; dropless or swiglu "
            "experts, a share of the experts, identity experts and the "
            "sigmoid router run on one device")

    if use_ep and xe is not None:
        raise NotImplementedError(
            "moe_ffn: experts with an input of their own (XE) run on one "
            "device")
    if not use_ep:
        out, aux, routed, took, real_most = _experts(
            xf, w1, w1v, b1, w2, b2, gate_w, E, top_k, capacity, act,
            norm_topk, z_loss, scoring, share,
            None if xe is None else xe.reshape(T, -1), n_zero)
        outs = {"Out": out.reshape(x.shape[:-1] + out.shape[-1:]),
                "AuxLoss": aux}
        row = int(attrs.get("counts_row", 0))
        if counts is not None:
            # the device-side tally of routed pairs: this layer's row,
            # over all the experts WITH weights the router scores
            outs["CountsOut"] = counts.at[row].add(
                routed[:E].astype(counts.dtype))
        if touched is not None:
            # calls in which each HELD expert was given a pair: the
            # grouped matmul fetches no weights for an empty group
            first = share[0] if share is not None else 0
            outs["TouchedOut"] = touched.at[row].add(
                (routed[first:first + n_local] > 0).astype(touched.dtype))
        if zero is not None:
            # the pairs that chose an identity expert (column 0, summed)
            # and the most experts with weights one token chose (column
            # 1, a running maximum): the straggler's width
            outs["ZeroOut"] = zero.at[row, 0].add(
                jnp.sum(routed[E:]).astype(zero.dtype)
            ).at[row, 1].max(real_most.astype(zero.dtype))
        if compact is not None:
            # the bounded calls of this layer by the branch they took:
            # column 0 compact, column 1 full; a call without a bound
            # leaves the tally as it was
            outs["CompactOut"] = compact if took is None else \
                compact.at[row, 1 - took].add(1)
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
