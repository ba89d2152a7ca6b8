"""Parallelism-extension layers: pipeline stage stacks (pp) and MoE (ep).

The reference (Fluid v1.3) has neither; these are the TPU-first
extensions that complete the dp/tp/sp/pp/ep set at the *framework* level
— Program-built models reach `parallel/pipeline.py` / `parallel/moe.py`
through ordinary layer calls, and ParallelEngine picks the collective
path when its mesh carries the matching axis (see
`ops/pipeline_ops.py`, `ops/moe_ops.py`).
"""

from __future__ import annotations

from typing import Callable, List, Optional

from ..core.program import Variable, name_scope, unique_name
from ..initializer import Constant, Xavier
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr

__all__ = ["pipeline", "moe_ffn"]


class StageBuilder:
    """Handed to the stage-body callback of ``pipeline``: creates
    per-stage parameters that are STORED stacked with a leading
    [n_stages] dim (one slice per pipeline device) and returns the
    current stage's slice as an ordinary variable the body's ops
    consume."""

    def __init__(self, helper: LayerHelper, sub_block, n_stages: int):
        self._helper = helper
        self._sub = sub_block
        self.n_stages = n_stages
        self.stacked: List[Variable] = []      # [n_stages, *shape] params
        self.slice_names: List[str] = []       # per-stage views in the body

    def param(self, shape, dtype: str = "float32", is_bias: bool = False,
              initializer=None) -> Variable:
        shape = [int(s) for s in shape]
        init = initializer or (Constant(0.0) if is_bias else Xavier())
        stacked = self._helper.create_parameter(
            ParamAttr(initializer=init), [self.n_stages] + shape, dtype,
            is_bias=is_bias)
        slice_var = self._sub.create_var(
            name=unique_name.generate(stacked.name + ".stage"),
            shape=tuple(shape), dtype=dtype)
        self.stacked.append(stacked)
        self.slice_names.append(slice_var.name)
        return slice_var


def pipeline(x: Variable, n_stages: int,
             stage_fn: Callable[[StageBuilder, Variable], Variable],
             n_microbatches: Optional[int] = None,
             name: Optional[str] = None) -> Variable:
    """GPipe-style stack of ``n_stages`` identical stages.

    ``stage_fn(pb, x) -> y`` builds ONE stage's computation (ordinary
    layer calls on ``x``); per-stage weights come from ``pb.param(...)``
    and are stored stacked. The classic GPipe contract applies: every
    stage maps activations of one shape to the same shape (y.shape ==
    x.shape). Stochastic bodies (dropout) are supported: one base PRNG
    key per pipeline op is folded per (stage, microbatch) and replayed
    in the backward (recompute's RngKey pattern), so the pipelined and
    sequential paths produce identical masks.

    Single device: the stages apply sequentially. Under ParallelEngine
    with a mesh 'pipe' axis of size n_stages: stages run one-per-device
    with ``lax.ppermute`` activation hops and microbatch overlap
    (parallel/pipeline.py); the engine shards the stacked params (and
    their optimizer slots) over the axis automatically — the layer
    records them on ``program._pipeline_params`` and
    ``ParallelEngine._with_ext_rules`` injects the 'pipe' rules; an
    explicit user rule for a stacked param overrides. Stages are
    per-sample maps, so both paths compute identical results.

    n_microbatches (default n_stages) splits the batch on the pipelined
    path; the batch size must be divisible by it.
    """
    helper = LayerHelper("pipeline", name=name)
    prog = helper.main_program
    parent = prog.current_block()
    sub = prog.create_block()
    pb = StageBuilder(helper, sub, n_stages)
    x_in = sub.create_var(
        name=unique_name.generate(helper.name + ".stage_in"),
        shape=x.shape, dtype=x.dtype)
    out_var = stage_fn(pb, x_in)
    prog.rollback()
    if tuple(out_var.shape or ()) != tuple(x.shape or ()):
        raise ValueError(
            "pipeline stage must preserve the activation shape (GPipe "
            "contract): body maps %s -> %s" % (x.shape, out_var.shape))
    # stochastic stage bodies (dropout) are supported via recompute's
    # RngKey pattern: one base key per pipeline op, folded per
    # (stage, microbatch) and replayed in the grad (ops/pipeline_ops.py)
    from ..core.recompute import segment_uses_rng

    uses_rng = segment_uses_rng(sub.ops, prog)

    out = parent.create_var(
        name=unique_name.generate(helper.name + ".out"),
        shape=x.shape, dtype=x.dtype)
    outputs = {"Out": [out]}
    if uses_rng:
        rng_var = parent.create_var(
            name=unique_name.generate(helper.name + ".rngkey"),
            shape=[], dtype="float32", persistable=False)
        outputs["RngKey"] = [rng_var]
    parent.append_op(
        type="pipeline",
        inputs={"X": [x], "StackedParams": [p.name for p in pb.stacked]},
        outputs=outputs,
        attrs={
            "sub_block": sub.idx,
            "n_stages": int(n_stages),
            "n_microbatches": int(n_microbatches or n_stages),
            "slice_names": list(pb.slice_names),
            "in_name": x_in.name,
            "out_name": out_var.name,
            "axis": "pipe",
            "uses_rng": uses_rng,
            "__sub_bound__": [x_in.name] + list(pb.slice_names),
        })
    # record for ParallelEngine's automatic 'pipe' sharding rules
    pp = getattr(prog, "_pipeline_params", None)
    if pp is None:
        pp = prog._pipeline_params = []
    pp.extend(p.name for p in pb.stacked)
    return out


def moe_ffn(x: Variable, n_experts: int, d_hidden: int,
            capacity: Optional[int] = None, top_k: int = 1,
            z_loss: float = 0.0, name: Optional[str] = None,
            act: str = "relu", dropless: bool = False,
            norm_topk: Optional[bool] = None, param_prefix=None,
            counts: Optional[Variable] = None, counts_row: int = 0,
            router_score: str = "softmax", router_bias: bool = False,
            route_scale: float = 1.0,
            n_expert_local: Optional[int] = None, expert_first: int = 0,
            n_shared_expert: int = 0,
            shared_expert_gate: bool = False,
            touched: Optional[Variable] = None,
            expert_input: Optional[Variable] = None,
            norm_topk_eps: Optional[float] = None,
            compact_calls: Optional[Variable] = None,
            n_zero_expert: int = 0,
            zero_pairs: Optional[Variable] = None):
    """Mixture-of-experts FFN (see ops/moe_ops.py).

    x: [B, D] (or [B, S, D], flattened internally). Returns (out, aux)
    where out has x's shape and aux is the Switch load-balancing loss
    (top_k=1 is Switch routing; top_k>=2 routes each token to its k
    best experts with renormalized gates, GShard-style) —
    add ``aux_weight * aux`` into the training objective or routing
    collapses. ``z_loss`` > 0 folds the ST-MoE router z-loss
    (``z_loss * mean(logsumexp(router logits)^2)``) into aux, keeping
    router logits small — the bf16-stability regularizer. Expert
    weights are stored stacked [n_experts, ...]; under a ParallelEngine
    mesh with an 'expert' axis of size n_experts the tokens shuffle to
    their expert's device with all_to_all, otherwise every expert
    computes locally (identical math).

    ``act``: 'relu' experts ``relu(x W1 + b1) W2 + b2`` (the default),
    'swiglu' experts ``(silu(x Wg) * (x Wu)) Wd`` without biases, or
    'relu2' experts ``relu(x W1)^2 W2`` with neither a gate nor biases
    (``<prefix>_{up,down}.w_0``). ``expert_input`` is a tensor of the
    same leading shape as ``x`` and a width of its own that the EXPERTS
    read (a latent of the tokens) while the router scores ``x``: the
    expert matrices and ``out`` then have its width.
    ``dropless=True`` computes every (token, expert) pair; otherwise
    ``capacity`` (default ``ceil(2 T top_k / E)``) bounds the pairs an
    expert takes and the overflow contributes zero. ``norm_topk``: None
    renormalises the k gates when top_k > 1 (GShard), False keeps the
    raw router probabilities, True always renormalises.
    ``param_prefix`` names the parameters ``<prefix>_{gate,up,down,
    router}.w_0`` (swiglu) so that several programs share them by name.
    ``counts`` is a persistable [rows, n_experts] int32 var: the op adds
    the pairs it routed to each expert to row ``counts_row``, in place
    on the device.

    ``router_score`` 'softmax' or 'sigmoid'; ``router_bias`` adds a
    parameter ``<prefix>_router_bias`` [n_experts] to the scores for the
    SELECTION only (the gates stay the raw scores); ``route_scale``
    multiplies the gates after ``norm_topk``; ``norm_topk_eps`` is what
    the sigmoid router adds to the sum it divides by (1e-20 where not
    given). ``n_expert_local`` <
    ``n_experts`` is a SHARE of an expert-parallel deployment: the
    router still scores all ``n_experts``, the stacked weights hold only
    experts ``expert_first .. expert_first + n_expert_local - 1`` and
    the output is their part of the layer (pairs routed elsewhere are
    given to no group before the sort, as a ``capacity`` run gives its
    overflow to none; the parts of all shares add up to the whole
    layer). ``n_shared_expert`` adds that many always-on swiglu experts
    of width ``d_hidden`` (one bias-free SwiGLU of their joint width,
    ``<prefix>_shared_{gate,up,down}.w_0``) to the output, whole on
    every share; ``shared_expert_gate`` multiplies that sum by the
    token's own scalar ``sigmoid(x w)`` (``<prefix>_shared_sgate.w_0 [D,
    1]``). ``touched`` is a persistable [rows, n_expert_local]
    int32 var: row ``counts_row`` counts the calls in which each held
    expert was given at least one pair. A share's call over enough
    tokens cuts its sorted pair rows at twice the share's even part of
    them wherever the held pairs fit, and takes the full length where
    they do not (``ops/moe_ops.py::compact_rows``); ``compact_calls`` is
    a persistable [rows, 2] int32 var whose row ``counts_row`` counts
    such calls by the branch they took: column 0 cut, column 1 full.

    ``n_zero_expert`` identity (zero-compute) experts stand BEHIND the
    ``n_experts`` with weights (``dropless`` only): the router, and its
    selection bias, are ``n_experts + n_zero_expert`` wide, ``top_k`` is
    taken over all of them, and a chosen identity expert returns the
    token itself, so a token's identity gates add up to ONE weight,
    ``out += w x``. Such a pair is given to no group before the sort, as
    a share's absent pair is: it is in no group's rows of either grouped
    matmul and past every cut of the sorted rows, and a share's bound is
    reckoned over all the router's outputs. A share (``n_expert_local``,
    of the ``n_experts`` with weights) returns its held experts' part
    plus the identity part, which every chip of a deployment computes
    alike for its own tokens: the shares' sum counts it once. ``counts``
    stays ``n_experts`` wide; ``zero_pairs`` is a persistable [rows, 2]
    int32 var whose row ``counts_row`` adds the pairs that chose an
    identity expert (column 0) and keeps the most experts with weights
    one token chose (column 1, a running maximum).
    """
    n_zero = int(n_zero_expert or 0)
    if not 1 <= int(top_k) <= int(n_experts) + n_zero:
        raise ValueError(
            "moe_ffn top_k must be in [1, n_experts]; got top_k=%s with "
            "n_experts=%s%s" % (top_k, n_experts,
                                " + %d identity" % n_zero if n_zero else ""))
    if act not in ("relu", "swiglu", "relu2"):
        raise ValueError("moe_ffn act must be 'relu', 'swiglu' or 'relu2'; "
                         "got %r" % (act,))
    if dropless and capacity:
        raise ValueError("moe_ffn: dropless=True takes no capacity")
    if router_score not in ("softmax", "sigmoid"):
        raise ValueError("moe_ffn router_score must be 'softmax' or "
                         "'sigmoid'; got %r" % (router_score,))
    n_local = int(n_expert_local or n_experts)
    if not (1 <= n_local <= int(n_experts)
            and 0 <= int(expert_first) <= int(n_experts) - n_local):
        raise ValueError(
            "moe_ffn: experts %d..%d are not a share of %d"
            % (expert_first, int(expert_first) + n_local - 1, n_experts))
    if n_shared_expert and act != "swiglu":
        raise ValueError("moe_ffn: shared experts are swiglu experts")
    if shared_expert_gate and not n_shared_expert:
        raise ValueError("moe_ffn: shared_expert_gate gates "
                         "n_shared_expert's sum")
    if n_zero and (not dropless or expert_input is not None):
        raise ValueError(
            "moe_ffn: n_zero_expert (identity experts) needs dropless=True "
            "and takes no expert_input: an identity expert returns the "
            "token the router scored")
    if zero_pairs is not None and not n_zero:
        raise ValueError("moe_ffn: zero_pairs tallies n_zero_expert's pairs")
    helper = LayerHelper("moe_ffn", name=name)
    D = int(x.shape[-1])
    mk = helper.create_parameter  # stacked expert weights + router

    def attr(part, **kw):
        return ParamAttr(name=None if param_prefix is None
                         else "%s_%s.w_0" % (param_prefix, part), **kw)

    inputs = {"X": [x]}
    D_router = D
    if expert_input is not None:
        if tuple(expert_input.shape[:-1]) != tuple(x.shape[:-1]):
            raise ValueError("moe_ffn: expert_input %s does not hold x %s's "
                             "tokens" % (expert_input.shape, x.shape))
        inputs["XE"] = [expert_input]
        D = int(expert_input.shape[-1])
    if act == "swiglu":
        inputs["W1"] = [mk(attr("gate"), [n_local, D, d_hidden],
                           "float32")]
        inputs["W1V"] = [mk(attr("up"), [n_local, D, d_hidden],
                            "float32")]
        inputs["W2"] = [mk(attr("down"), [n_local, d_hidden, D],
                           "float32")]
    elif act == "relu2":
        inputs["W1"] = [mk(attr("up"), [n_local, D, d_hidden], "float32")]
        inputs["W2"] = [mk(attr("down"), [n_local, d_hidden, D],
                           "float32")]
    else:
        inputs["W1"] = [mk(ParamAttr(), [n_local, D, d_hidden],
                           "float32")]
        inputs["B1"] = [mk(ParamAttr(initializer=Constant(0.0)),
                           [n_local, d_hidden], "float32",
                           is_bias=True)]
        inputs["W2"] = [mk(ParamAttr(), [n_local, d_hidden, D],
                           "float32")]
        inputs["B2"] = [mk(ParamAttr(initializer=Constant(0.0)),
                           [n_local, D], "float32", is_bias=True)]
    inputs["Gate"] = [mk(attr("router"), [D_router, n_experts + n_zero],
                         "float32")]
    if router_bias:
        inputs["RouterBias"] = [mk(
            ParamAttr(name=None if param_prefix is None
                      else param_prefix + "_router_bias",
                      initializer=Constant(0.0)),
            [n_experts + n_zero], "float32", is_bias=True)]
    out = helper.create_variable_for_type_inference(x.dtype)
    aux = helper.create_variable_for_type_inference("float32")
    outputs = {"Out": [out], "AuxLoss": [aux]}
    attrs = {"n_experts": int(n_experts),
             "capacity": int(capacity) if capacity else 0,
             "top_k": int(top_k),
             "z_loss": float(z_loss),
             "act": act,
             "dropless": bool(dropless),
             "axis": "expert"}
    if norm_topk is not None:
        attrs["norm_topk"] = bool(norm_topk)
    # the keys of the widened router and of a share are written only
    # where they are used: a program without them is the one it was
    if router_score != "softmax":
        attrs["router_score"] = router_score
    if float(route_scale) != 1.0:
        attrs["route_scale"] = float(route_scale)
    if norm_topk_eps:
        attrs["norm_topk_eps"] = float(norm_topk_eps)
    if n_zero:
        attrs["n_zero"] = n_zero
    if n_local != int(n_experts):
        attrs["n_local"] = n_local
        attrs["expert_first"] = int(expert_first)
    if counts is not None:
        inputs["Counts"] = [counts]
        outputs["CountsOut"] = [counts]
    if touched is not None:
        inputs["Touched"] = [touched]
        outputs["TouchedOut"] = [touched]
    if compact_calls is not None:
        inputs["Compact"] = [compact_calls]
        outputs["CompactOut"] = [compact_calls]
    if zero_pairs is not None:
        inputs["Zero"] = [zero_pairs]
        outputs["ZeroOut"] = [zero_pairs]
    if not (counts is None and touched is None and compact_calls is None
            and zero_pairs is None):
        attrs["counts_row"] = int(counts_row)
    helper.append_op(type="moe_ffn", inputs=inputs, outputs=outputs,
                     attrs=attrs)
    out.shape = tuple(x.shape[:-1]) + (D,)
    aux.shape = ()
    if n_shared_expert:
        from . import nn as _nn

        def fc(t, width, part, **kw):
            return _nn.fc(t, width, num_flatten_dims=len(x.shape) - 1,
                          bias_attr=False, param_attr=attr(part), **kw)

        wide = int(n_shared_expert) * int(d_hidden)
        # the always-on experts answer for their device time apart from
        # the routed ones (core/lowering.py::op_scope; the last class of
        # core/program.py::SCOPE_CLASSES in a scope path is its class)
        with name_scope("moe.shared"):
            hid = _nn.elementwise_mul(
                fc(x, wide, "shared_gate", act="swish"),
                fc(x, wide, "shared_up"))
            shared = fc(hid, D, "shared_down")
            if shared_expert_gate:
                shared = _nn.elementwise_mul(
                    shared, fc(x, 1, "shared_sgate", act="sigmoid"))
            out = _nn.elementwise_add(out, shared)
    prog = helper.main_program
    ep = getattr(prog, "_expert_params", None)
    if ep is None:
        ep = prog._expert_params = []
    ep.extend(v[0].name for slot, v in inputs.items()
              if slot in ("W1", "W1V", "B1", "W2", "B2"))
    return out, aux
