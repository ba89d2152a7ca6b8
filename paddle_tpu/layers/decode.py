"""Decoding + host-callback layers.

Reference locations: layers/nn.py beam_search / beam_search_decode
(backed by operators/beam_search_op.cc, beam_search_decode_op.cc) and
layers/nn.py py_func (py_func_op.cc). Beams are a dense [B, beam] axis
here instead of a LoD level (see ops/beam_search_ops.py).
"""

from __future__ import annotations

from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr

__all__ = ["kv_cache_write", "mla_decode", "mhc_pre", "mhc_post",
           "ssm_mix", "mamba_mix", "power_retention", "delta_rule", "causal_conv", "rope", "beam_search", "beam_search_decode", "beam_gather", "py_func"]


def beam_search(pre_ids, pre_scores, scores, beam_size, end_id, name=None,
                ids=None, level=0):
    """One beam expansion step. pre_ids/pre_scores: [B, beam];
    scores: next-token log-probs [B, beam, V]. Returns
    (selected_ids, selected_scores, parent_idx), each [B, beam]."""
    helper = LayerHelper("beam_search", name=name)
    sel_ids = helper.create_variable_for_type_inference("int64",
                                                        stop_gradient=True)
    sel_scores = helper.create_variable_for_type_inference(
        pre_scores.dtype, stop_gradient=True)
    parent = helper.create_variable_for_type_inference("int64",
                                                       stop_gradient=True)
    helper.append_op(
        type="beam_search",
        inputs={"pre_ids": [pre_ids], "pre_scores": [pre_scores],
                "scores": [scores]},
        outputs={"selected_ids": [sel_ids], "selected_scores": [sel_scores],
                 "parent_idx": [parent]},
        attrs={"beam_size": beam_size, "end_id": end_id, "level": level})
    return sel_ids, sel_scores, parent


def beam_search_decode(ids, scores, parent_idx, beam_size=None, end_id=0,
                       name=None):
    """Backtrack stacked [T, B, beam] step outputs into sequences
    [B, beam, T] + final scores [B, beam]."""
    helper = LayerHelper("beam_search_decode", name=name)
    sent = helper.create_variable_for_type_inference("int64",
                                                     stop_gradient=True)
    sc = helper.create_variable_for_type_inference(scores.dtype,
                                                   stop_gradient=True)
    helper.append_op(
        type="beam_search_decode",
        inputs={"Ids": [ids], "ParentIdx": [parent_idx], "Scores": [scores]},
        outputs={"SentenceIds": [sent], "SentenceScores": [sc]},
        attrs={"beam_size": beam_size or 0, "end_id": end_id})
    return sent, sc


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None,
            name=None):
    """Run a Python callable inside the lowered step (py_func_op.cc).
    `out` declares result vars (shape/dtype must be set). backward_func is
    not differentiated through — py_func output gradients stop here, like
    registering the op no-grad; pass precomputed grads explicitly if
    needed (documented divergence: arbitrary Python backward in-graph
    would serialize the XLA step)."""
    helper = LayerHelper("py_func", name=name)
    xs = x if isinstance(x, (list, tuple)) else [x]
    outs = out if isinstance(out, (list, tuple)) else [out]
    for o in outs:
        assert o.shape is not None and all(
            s is not None and s >= 0 for s in o.shape), (
            "py_func out var %r needs a static shape" % o.name)
    helper.append_op(
        type="py_func",
        inputs={"X": list(xs)},
        outputs={"Out": list(outs)},
        attrs={"forward_func": func,
               "out_shapes": [list(o.shape) for o in outs],
               "out_dtypes": [o.dtype for o in outs]})
    return out


def beam_gather(x, parent_idx, name=None):
    """Reorder beam-grouped rows by parent index: x [B*beam, ...] with
    rows grouped per source, parent_idx [B, beam] -> x[b*beam + parent].
    The dense analog of the reference decoder's state reshuffle
    (contrib/decoder/beam_search_decoder.py sequence_expand/lod_reset)."""
    helper = LayerHelper("beam_gather", name=name)
    out = helper.create_variable_for_type_inference(x.dtype,
                                                    stop_gradient=True)
    out.shape = tuple(x.shape)
    helper.append_op(type="beam_gather",
                     inputs={"X": [x], "Index": [parent_idx]},
                     outputs={"Out": [out]})
    return out


def rope(x, pos, base=10000.0, name=None, yarn=None, heads_last=False,
         rotary_dim=None):
    """Rotary position embedding on a head tensor [..., S, D] (D even,
    rotate-half convention; ``heads_last``: on [B, S, H, D], the heads
    where a projection's reshape leaves them, so that no transpose
    stands round the rotation): position i rotates pair (x_j, x_{j+D/2})
    by angle pos_i * base^(-2j/D). `pos` is a [S] int var (or [1] for
    a decode step, or [B, S] for PACKED sequences whose positions
    reset at segment starts) — runtime positions, one executable for
    every step. Apply to q and k after head split, BEFORE attention
    (and before any GQA head repeat — the rotation is per head-dim,
    head-count blind). ``yarn`` — ``dict(factor=, low=, high=,
    mscale=1.0)`` — scales the frequencies per dimension (YaRN):
    dimension j keeps ``base^(-2j/D)`` below ``low``, has it divided by
    ``factor`` above ``high`` and a linear blend between, and cos and
    sin are multiplied by ``mscale``; factor 1 is the plain rotation bit
    for bit. ``rotary_dim`` (even, below D) rotates only the first that
    many values of a head — pairs ``(x_j, x_{j + rotary_dim / 2})`` at
    ``base^(-2j / rotary_dim)`` — and passes the others."""
    if x.shape is not None and x.shape[-1] is not None \
            and int(x.shape[-1]) % 2:
        raise ValueError(
            "rope needs an even head dim (rotate-half pairs); got %s"
            % (x.shape[-1],))
    if rotary_dim is not None and (
            int(rotary_dim) % 2 or not 0 < int(rotary_dim) <= x.shape[-1]):
        raise ValueError("rope rotary_dim must be even and in (0, %s]; got "
                         "%r" % (x.shape[-1], rotary_dim))
    helper = LayerHelper("rope", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    attrs = {"base": float(base)}
    if yarn:
        attrs.update(yarn_factor=float(yarn["factor"]),
                     yarn_low=float(yarn["low"]),
                     yarn_high=float(yarn["high"]),
                     yarn_mscale=float(yarn.get("mscale", 1.0)))
    if heads_last:
        attrs["heads_last"] = True
    if rotary_dim is not None and int(rotary_dim) < x.shape[-1]:
        attrs["rotary_dim"] = int(rotary_dim)
    helper.append_op(type="rope", inputs={"X": [x], "Pos": [pos]},
                     outputs={"Out": [out]}, attrs=attrs)
    out.shape = x.shape
    return out


def mhc_pre(x, n, epsilon, sinkhorn_iters, hc_eps, clamp, prefix,
            dev=None, name=None):
    """What a sub-block reads of ``n`` residual streams (op ``mhc_pre``,
    kernels/mhc.py): ``x [B, S, n C]`` holds stream ``i`` in lanes
    ``i C .. (i + 1) C``. Returns ``(h [B, S, C], coef [B, S, n (n +
    2)])``: the mixed vector ``sum_i H_pre[i] x[i]`` and the row's
    mappings ``[H_pre | H_post | H_res]`` for ``mhc_post``. Parameters
    ``<prefix>_phi.w_0 [n C, n (n + 2)]`` (``[phi_pre | phi_post |
    phi_res]``), ``<prefix>_alpha [3]`` (one gate a group) and
    ``<prefix>_b [n (n + 2)]``. ``dev`` is a persistable ``[1]`` float32
    var: the op keeps in it the largest ``|sum - 1|`` any row or column
    of an ``H_res`` has shown."""
    from ..initializer import Constant

    helper = LayerHelper("mhc_pre", name=name)
    n, wide = int(n), int(x.shape[-1])
    k = n * (n + 2)
    phi = helper.create_parameter(ParamAttr(name=prefix + "_phi.w_0"),
                                  [wide, k], dtype="float32")
    alpha = helper.create_parameter(
        ParamAttr(name=prefix + "_alpha", initializer=Constant(1.0)),
        [3], dtype="float32")
    b = helper.create_parameter(
        ParamAttr(name=prefix + "_b", initializer=Constant(0.0)),
        [k], dtype="float32", is_bias=True)
    h = helper.create_variable_for_type_inference(x.dtype)
    coef = helper.create_variable_for_type_inference("float32")
    inputs = {"X": [x], "Phi": [phi], "Alpha": [alpha], "B": [b]}
    outputs = {"H": [h], "Coef": [coef]}
    if dev is not None:
        inputs["Dev"] = [dev]
        outputs["DevOut"] = [dev]
    helper.append_op(
        type="mhc_pre", inputs=inputs, outputs=outputs,
        attrs={"n": n, "epsilon": float(epsilon),
               "sinkhorn_iters": int(sinkhorn_iters),
               "hc_eps": float(hc_eps), "clamp_min": float(clamp[0]),
               "clamp_max": float(clamp[1])})
    h.shape = tuple(x.shape[:-1]) + (wide // n,)
    coef.shape = tuple(x.shape[:-1]) + (k,)
    return h, coef


def mhc_post(x, y, coef, n, name=None):
    """What a sub-block writes back (op ``mhc_post``): ``out[i] = sum_j
    H_res[i, j] x[j] + H_post[i] y`` over the ``n`` streams of ``x
    [B, S, n C]``, with ``y [B, S, C]`` the sub-block's output and
    ``coef`` as ``mhc_pre`` returned it."""
    helper = LayerHelper("mhc_post", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="mhc_post",
                     inputs={"X": [x], "Y": [y], "Coef": [coef]},
                     outputs={"Out": [out]}, attrs={"n": int(n)})
    out.shape = x.shape
    return out


def mla_decode(q_nope, q_rope, cache, pos, w_shape, d_v, scale,
               param_attr=None, name=None):
    """Absorbed latent attention of one decode position (op
    ``mla_decode``, kernels/mla_decode.py): ``q_nope [B, 1, H, d_nope]``
    and the rotated ``q_rope [B, 1, H, d_rope]`` against the latent cache
    ``cache [B, 1, S, d_c + d_rope]`` at ``pos`` ([1] shared, or [B, 1]
    per slot: slot b sees rows ``<= pos[b]``), with the up-projection
    ``W_ukv [d_c, H (d_nope + d_v)]`` (``param_attr``; the prefill's
    expanded form multiplies by the same parameter) folded into the
    query (``q_lat = q_nope W_uk^T``) and the output (``ctx = o W_uv``).
    Returns ``[B, 1, H d_v]``."""
    helper = LayerHelper("mla_decode", name=name)
    w = helper.create_parameter(param_attr, list(w_shape), dtype=q_nope.dtype)
    out = helper.create_variable_for_type_inference(q_nope.dtype)
    helper.append_op(
        type="mla_decode",
        inputs={"QNope": [q_nope], "QRope": [q_rope], "Cache": [cache],
                "Pos": [pos], "W": [w]},
        outputs={"Out": [out]},
        attrs={"d_v": int(d_v), "scale": float(scale)})
    out.shape = (q_nope.shape[0], 1, int(q_nope.shape[2]) * int(d_v))
    return out


def kv_cache_write(cache, update, pos, name=None):
    """Write `update` [B, H, 1, D] into persistable `cache` [B, H, S, D]
    at sequence position `pos` — a [1] int var (all rows share one
    position: the lockstep decode step) or a [B]/[B, 1] int var
    (per-row positions: each cache slot advances independently, the
    continuous-batching serving step). Returns the cache var (the op
    writes the var in place graph-wise; the executor's donation makes
    it in-place on device). See models/gpt.py build_decode_step and
    build_serving_decode_step."""
    helper = LayerHelper("kv_cache_write", name=name)
    helper.append_op(
        type="kv_cache_write",
        inputs={"Cache": [cache], "Update": [update], "Pos": [pos]},
        outputs={"Out": [cache]},
        attrs={})
    return cache


def causal_conv(x, width, prefix, rows, step=False, act=True, bias=True,
                columns=None, name=None):
    """Causal depth-wise convolution over the sequence axis of ``x
    [B, T, C]`` (ops ``causal_conv`` / ``causal_conv_step``,
    kernels/ssm.py): ``out[t] = silu(sum_j w[:, j] x[t - width + 1 + j]
    + b)``, zeros before the sequence; ``act=False`` leaves the silu
    out and ``bias=False`` creates no ``b``. ``rows`` is
    a persistable ``[B, width - 1, C]`` var that keeps the last ``width -
    1`` positions of ``x`` itself: a whole prompt (``step=False``)
    overwrites it, one token (``step=True``, ``T`` = 1) reads the past
    out of it and shifts it. ``columns=(lo, hi)`` convolves those columns
    of a wider ``x``: a whole prompt reads them where they lie (the op's
    attr ``columns``: a slice in front of a kernel is a copy), one token
    is cut first. Parameters ``<prefix>.w_0 [C, width]`` and
    ``<prefix>.b_0 [C]``."""
    from ..initializer import Constant
    from .nn import slice as cut

    attrs = {"act": bool(act)}
    if columns is not None and step:
        x = cut(x, axes=[2], starts=[columns[0]], ends=[columns[1]])
    elif columns is not None:
        attrs["columns"] = [int(c) for c in columns]
    helper = LayerHelper("causal_conv", name=name)
    lo, hi = attrs.get("columns", (0, int(x.shape[-1])))
    C = hi - lo
    w = helper.create_parameter(ParamAttr(name=prefix + ".w_0"),
                                [C, int(width)], dtype="float32")
    inputs = {"X": [x], "W": [w]}
    if bias:
        inputs["Bias"] = [helper.create_parameter(
            ParamAttr(name=prefix + ".b_0", initializer=Constant(0.0)),
            [C], dtype="float32", is_bias=True)]
    out = helper.create_variable_for_type_inference("float32")
    if step:
        inputs["Rows"] = [rows]
    helper.append_op(type="causal_conv_step" if step else "causal_conv",
                     inputs=inputs,
                     outputs={"Out": [out], "RowsOut": [rows]},
                     attrs=attrs)
    out.shape = tuple(x.shape[:-1]) + (C,)
    return out


def ssm_mix(x, dt, bm, cm, state, heads, groups, n_state, prefix,
            chunk=128, step=False, name=None):
    """The selective state-space recurrence of a Mamba-2 layer (ops
    ``ssm_scan`` / ``ssm_update``, kernels/ssm.py). ``x [B, T, H P]``
    (the heads' inputs), ``dt [B, T, H]`` (raw), ``bm`` / ``cm`` ``[B,
    T, G N]``. ``state`` is a persistable ``[B, G, N, (H / G) P]`` var:
    a whole prompt (``step=False``) is scanned from a zero state in
    chunks of ``chunk`` and leaves its final state there; one token
    (``step=True``, ``T`` = 1) updates it in place. Returns ``y [B, T,
    H P]``, the skip ``D_h x`` included. Parameters ``<prefix>_a_log``,
    ``<prefix>_d`` and ``<prefix>_dt_b``, each ``[H]``: ``A_h = -exp(
    a_log_h)``, ``dt = softplus(dt + dt_b)``."""
    from ..initializer import Constant

    helper = LayerHelper("ssm_mix", name=name)
    H = int(heads)

    def vec(part, value):
        return helper.create_parameter(
            ParamAttr(name="%s_%s" % (prefix, part),
                      initializer=Constant(value)),
            [H], dtype="float32", is_bias=True)

    inputs = {"X": [x], "Dt": [dt], "Bm": [bm], "Cm": [cm],
              "ALog": [vec("a_log", 0.0)], "D": [vec("d", 1.0)],
              "DtBias": [vec("dt_b", 0.0)]}
    attrs = {"heads": H, "groups": int(groups), "state": int(n_state)}
    if step:
        inputs["State"] = [state]
    else:
        attrs["chunk"] = int(chunk)
    y = helper.create_variable_for_type_inference("float32")
    helper.append_op(type="ssm_update" if step else "ssm_scan",
                     inputs=inputs,
                     outputs={"Y": [y], "StateOut": [state]}, attrs=attrs)
    y.shape = x.shape
    return y


def mamba_mix(x, dt, bm, cm, state, n_state, prefix, step=False, name=None):
    """The selective state-space recurrence of a Mamba-1 layer (ops
    ``mamba_scan`` / ``mamba_update``, kernels/mamba.py): ``S[c, n] <-
    exp(dt[c] A[c, n]) S[c, n] + dt[c] B[n] x[c]``, ``y[c] = sum_n S[c, n]
    C[n] + D[c] x[c]`` — one decay a channel AND state, so no chunk of it
    is a matrix product. ``x [B, T, C]`` (the convolved channels), ``dt
    [B, T, C]`` (raw), ``bm`` / ``cm`` ``[B, T, N]``. ``state`` is a
    persistable ``[B, 1, N, C]`` var (``kernels.mamba.state_shape``): a
    whole prompt (``step=False``) is scanned from a zero state, position
    by position, and leaves its final state there; one token
    (``step=True``, ``T`` = 1) updates it in place. Returns ``y [B, T,
    C]``, the skip included. Parameters, float32 whatever the stored
    dtype: ``<prefix>_a_log [C, N]`` (``A = -exp(a_log)``), ``<prefix>_d``
    and ``<prefix>_dt_b`` ``[C]`` (``dt = softplus(dt + dt_b)``)."""
    from ..initializer import Constant
    from ..layer_helper import stored_dtype

    helper = LayerHelper("mamba_mix", name=name)
    C, N = int(x.shape[-1]), int(n_state)

    def vec(part, value, shape):
        return helper.create_parameter(
            ParamAttr(name="%s_%s" % (prefix, part),
                      initializer=Constant(value)),
            shape, dtype="float32", is_bias=True)

    with stored_dtype(None):      # A_log is [C, N] and stays float32
        a_log = vec("a_log", 0.0, [C, N])
    inputs = {"X": [x], "Dt": [dt], "Bm": [bm], "Cm": [cm],
              "ALog": [a_log], "D": [vec("d", 1.0, [C])],
              "DtBias": [vec("dt_b", 0.0, [C])]}
    if step:
        inputs["State"] = [state]
    y = helper.create_variable_for_type_inference("float32")
    helper.append_op(type="mamba_update" if step else "mamba_scan",
                     inputs=inputs,
                     outputs={"Y": [y], "StateOut": [state]})
    y.shape = x.shape
    return y


def power_retention(q, k, v, gate, state, norm, heads, groups, step=False,
                    name=None):
    """The core of a power-retention layer of degree 2 (ops
    ``power_scan`` / ``power_update``, kernels/power.py): attention whose
    weight is the squared scaled score under a decay, ``q [B, T, H D]``
    (normed and rotated as the model has them), ``k`` / ``v`` ``[B, T, G
    D]``, ``gate [B, T, G]`` raw (the op takes its sigmoid: one decay a
    key-value head). ``state`` and ``norm`` are persistable ``[B, G, R,
    D]`` and ``[B, G, D, D]`` vars (``kernels.power.state_shape`` /
    ``norm_shape``): a whole prompt (``step=False``) is scanned from
    zero, in chunks, and leaves both there; one token (``step=True``,
    ``T`` = 1) updates them in place and is read out of the new state.
    Returns ``y [B, T, H D]``, normalised. No parameters."""
    helper = LayerHelper("power_retention", name=name)
    inputs = {"Q": [q], "K": [k], "V": [v], "Gate": [gate]}
    attrs = {"heads": int(heads), "groups": int(groups)}
    if step:
        inputs["State"], inputs["Norm"] = [state], [norm]
    y = helper.create_variable_for_type_inference("float32")
    helper.append_op(type="power_update" if step else "power_scan",
                     inputs=inputs,
                     outputs={"Y": [y], "StateOut": [state],
                              "NormOut": [norm]}, attrs=attrs)
    y.shape = q.shape
    return y


def delta_rule(q, k, v, beta, a, state, k_heads, v_heads, prefix,
               step=False, name=None):
    """The core of a gated delta-rule layer (ops ``delta_scan`` /
    ``delta_update``, kernels/delta.py): a state ``S [Dk, Dv]`` a value
    head that is decayed, read at the key, corrected by what it got wrong
    of the value and read at the query, ``S <- exp(g) S; S <- S + k (beta
    (v - S^T k))^T; o = S^T q``. ``q`` / ``k`` ``[B, T, Hk Dk]`` and ``v
    [B, T, Hv Dv]`` as the convolution leaves them (the op takes the l2
    norm of every query and key head; value head ``h`` reads key head ``h
    // (Hv / Hk)``), ``beta`` / ``a`` ``[B, T, Hv]`` raw (the op takes
    ``sigmoid(beta)`` and ``g = -exp(a_log) softplus(a + dt_b)``).
    ``state`` is a persistable ``[B, Hv, Dk, Dv]`` var
    (``kernels.delta.state_shape``): a whole prompt (``step=False``) is
    scanned from zero, in chunks, and leaves its final state there; one
    token (``step=True``, ``T`` = 1) updates it in place and is read out
    of the new state. Returns ``y [B, T, Hv Dv]``. Parameters
    ``<prefix>_a_log`` and ``<prefix>_dt_b``, each ``[Hv]``."""
    from ..initializer import Constant

    helper = LayerHelper("delta_rule", name=name)
    Hv = int(v_heads)

    def vec(part):
        return helper.create_parameter(
            ParamAttr(name="%s_%s" % (prefix, part),
                      initializer=Constant(0.0)),
            [Hv], dtype="float32", is_bias=True)

    inputs = {"Q": [q], "K": [k], "V": [v], "Beta": [beta], "A": [a],
              "ALog": [vec("a_log")], "DtBias": [vec("dt_b")]}
    if step:
        inputs["State"] = [state]
    y = helper.create_variable_for_type_inference("float32")
    helper.append_op(type="delta_update" if step else "delta_scan",
                     inputs=inputs,
                     outputs={"Y": [y], "StateOut": [state]},
                     attrs={"k_heads": int(k_heads), "v_heads": Hv})
    y.shape = v.shape
    return y
