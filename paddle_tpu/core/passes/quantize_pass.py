"""Post-training int8 weight quantization as a verified IR pass.

The graduation of the ``ops/quant_ops.py`` fake-quantize family from
simulation to real rewrite (ROADMAP: the quantization half of the
deployable-inference tier): for each eligible matmul/conv/mul weight,
the pass

1. asks the **range engine** (``analysis/ranges.py``, scope values on)
   to prove the weight finite, and derives symmetric **per-channel
   scales** from its concrete scope value (abs-max per output channel);
2. bakes the scales as an ``assign_value`` literal — so the translation
   validator can machine-check the numbers, and the range engine flows
   exact bounds through the quantization artifacts themselves;
3. splices ``quantize_channel_abs_max`` (f32 -> int8 payload) and
   ``dequantize_channel_abs_max`` (int8 -> f32) — the ops' own
   registered lowerings, the single source of quantization semantics —
   and rewires the consumers' weight slot onto the dequantized value.

Eligibility is conservative: the weight must be a float32 persistable
with a concrete value in the run scope, never written by the program
(a training program's optimizer update disqualifies it), with no
gradient anywhere (backward through int8 storage is not this pass's
contract), rank 2 (matmul/mul) or 4 (conv2d), and at least
``PADDLE_TPU_OPTIMIZE_QUANT_MIN_ELEMS`` elements. Every refusal is
counted in ``paddle_quant_skipped_total{reason}``.

**Opt-in**: the pass is level 2 AND gated on
``PADDLE_TPU_OPTIMIZE_QUANT=1`` (default 0 — a default run provably
moves zero ``paddle_quant_*`` counters; the knob rides
``passes.config_key()`` into the executor plan-cache key).

**Contract change**: a quantized program is NOT bitwise the original —
that is the point. The pass's parity contract is the stated tolerance
(``QUANT_TOLERANCE``): fetches of the quantized program must match the
unquantized run within it (``tools/pass_fuzz.py`` holds a corpus entry
proving a wrong-scale rewrite trips BOTH the tolerance harness and the
TV ``quantize`` record check). Everything else in the pipeline keeps
the bitwise contract.
"""

from __future__ import annotations

import os
from typing import Dict

import numpy as np

from ..ir import Graph, Pass, register_pass

# the stated parity tolerance for quantized programs: fetches of a
# quantized program vs the unquantized run must satisfy
# np.allclose(..., **QUANT_TOLERANCE). Per-channel symmetric int8 puts
# per-weight error at <= scale/254 (~0.4% of the channel max); the
# allowance covers its accumulation through small-model matmul chains.
QUANT_TOLERANCE = {"rtol": 0.05, "atol": 0.05}

# (consumer op type -> weight slot). conv2d filters are [O, I, kh, kw]
# (channel axis 0); mul/matmul weights are [K, N] (channel axis 1,
# flipped by transpose_Y).
_WEIGHT_SLOTS = {
    "mul": "Y",
    "matmul": "Y",
    "matmul_v2": "Y",
    "conv2d": "Filter",
}


def quantize_enabled() -> bool:
    """``PADDLE_TPU_OPTIMIZE_QUANT=1`` opts the PTQ pass in (default
    0: the pass is a provable no-op and no paddle_quant_* family
    moves)."""
    return os.environ.get(
        "PADDLE_TPU_OPTIMIZE_QUANT", "0").lower() in ("1", "true", "on")


def quant_min_elems() -> int:
    """Size floor for weight quantization (tiny weights cost program
    churn and buy nothing). Malformed values fall back like
    fold_max_elems() — this rides the executor cache key via
    config_key()."""
    try:
        return int(os.environ.get(
            "PADDLE_TPU_OPTIMIZE_QUANT_MIN_ELEMS", "16"))
    except ValueError:
        return 16


@register_pass("post_training_quantize_pass")
class PostTrainingQuantizePass(Pass):
    """Rewrite eligible matmul/conv/mul weights to int8 storage with
    per-channel range-derived scales (see module docstring for the
    eligibility rules, the opt-in gate, and the tolerance contract)."""

    fetch_names = frozenset()
    scope = None
    bits = 8
    # knock-out seam for tools/pass_fuzz.py: False bakes deliberately
    # wrong (quartered) scales so the corpus can prove BOTH the
    # tolerance parity harness and the TV quantize-record check catch a
    # bad rewrite. NEVER ship False.
    scale_guard = True

    def apply(self, graph: Graph) -> Graph:
        from ...observe.families import (QUANT_OPS_INSERTED, QUANT_SKIPPED,
                                         QUANT_WEIGHTS)
        from .common import Dataflow

        self.rewrites = []
        self.stats = {"weights_quantized": 0, "ops_inserted": 0}
        self.changed = False
        if not quantize_enabled():
            return graph
        program = graph.program
        scope = self.scope
        df = Dataflow(program, fetch_names=self.fetch_names, scope=scope)
        floor = quant_min_elems()

        # group eligible consumers by weight name: one quantize/
        # dequantize pair per weight, every consumer rewired onto it
        candidates = {}  # wname -> [(op_node, slot, axis, ctype)]
        for node in graph.all_op_nodes():
            op = node.op
            slot = _WEIGHT_SLOTS.get(op.type)
            if slot is None:
                continue
            names = op.inputs.get(slot) or []
            if not names or not names[0]:
                continue
            wname = names[0]
            var = program.global_block()._find_var_recursive(wname)
            if var is None or not var.persistable:
                continue  # an activation operand (attention's Y, a
                #           computed filter), not a weight candidate
            axis = self._channel_axis(op)
            candidates.setdefault(wname, []).append(
                (node, slot, axis, op.type))

        ranges = None
        for wname in sorted(candidates):
            consumers = candidates[wname]
            var = program.global_block()._find_var_recursive(wname)
            reason = None
            if var.dtype != "float32":
                reason = "dtype"
            elif df.write_count(wname) > 0:
                reason = "written"
            elif self._has_grad(program, df, wname):
                reason = "grad"
            elif scope is None or not scope.has_var(wname):
                reason = "scope"
            if reason is None:
                axes = {a for _n, _s, a, _t in consumers}
                if len(axes) != 1:
                    reason = "shape"
            if reason is None:
                w = np.asarray(scope.find_var(wname))
                axis = consumers[0][2]
                if w.ndim not in (2, 4) or not -w.ndim <= axis < w.ndim:
                    reason = "shape"
                elif w.size < floor:
                    reason = "small"
            if reason is None:
                if ranges is None:
                    from ...analysis.ranges import RangeAnalysis

                    ranges = RangeAnalysis(
                        program, fetch_names=self.fetch_names,
                        scope=scope, use_scope_values=True)
                if not ranges.value_of(wname).finite:
                    reason = "unproven"
            if reason is not None:
                QUANT_SKIPPED.labels(reason=reason).inc()
                continue
            self._quantize_weight(graph, wname, var,
                                  w.astype(np.float32), consumers)
            QUANT_WEIGHTS.labels(op=consumers[0][3]).inc()
            QUANT_OPS_INSERTED.inc(3)
            self.stats["weights_quantized"] += 1
            self.stats["ops_inserted"] += 3
        self.changed = self.stats["weights_quantized"] > 0
        return graph

    @staticmethod
    def _channel_axis(op) -> int:
        if op.type == "conv2d":
            return 0  # Filter [O, I, kh, kw]: per output filter
        if op.type in ("matmul", "matmul_v2") \
                and op.attrs.get("transpose_Y", False):
            return 0  # Y [N, K]: output channels lead
        return 1      # Y [K, N]: output channels trail

    @staticmethod
    def _has_grad(program, df, wname: str) -> bool:
        from ..program import grad_var_name

        g = grad_var_name(wname)
        if df.write_positions(g) or df.read_positions(g):
            return True
        for block in program.blocks:
            if g in block.vars:
                return True
        return False

    def _quantize_weight(self, graph: Graph, wname: str, var, w,
                         consumers) -> None:
        axis = consumers[0][2]
        ax = axis if axis >= 0 else axis + w.ndim
        reduce_axes = tuple(i for i in range(w.ndim) if i != ax)
        scales = np.max(np.abs(w), axis=reduce_axes).astype(np.float32)
        if not self.scale_guard:
            scales = scales * 0.25  # knock-out seam (see class attr)
        sname = wname + ".quant_scale"
        qname = wname + ".quant"
        dqname = wname + ".dequant"
        shape = tuple(var.shape) if var.shape is not None else None
        graph.create_var_node(sname, shape=(int(scales.size),),
                              dtype="float32")
        graph.create_var_node(qname, shape=shape, dtype="int8")
        graph.create_var_node(dqname, shape=shape, dtype="float32")
        src_ops = [n.op for n, _s, _a, _t in consumers]
        # inserted in CONSUMER-FIRST order: Graph.materialize splices a
        # genuinely-new-name op before its first already-placed
        # consumer, processing new nodes in insertion order — dequant
        # anchors on the matmul, quantize then lands before dequant,
        # the scale literal before quantize
        dq_node = graph.insert_op_node(
            "dequantize_channel_abs_max",
            {"X": [qname], "Scales": [sname]}, {"Out": [dqname]},
            attrs={"axis": ax, "bit_length": self.bits},
            provenance_from=src_ops)
        q_node = graph.insert_op_node(
            "quantize_channel_abs_max",
            {"X": [wname], "InScale": [sname]}, {"Out": [qname]},
            attrs={"axis": ax, "bit_length": self.bits},
            provenance_from=src_ops)
        s_node = graph.insert_op_node(
            "assign_value", {}, {"Out": [sname]},
            attrs={"values": scales.ravel().tolist(),
                   "shape": [int(scales.size)], "dtype": "float32"},
            provenance_from=src_ops)
        for node, slot, _a, _t in consumers:
            graph.rewire_input(node, slot, wname, dqname)
        self.rewrites.append({
            "kind": "quantize", "weight": wname, "axis": ax,
            "bit_length": self.bits, "dequant": dqname,
            "quantized": qname, "scale_name": sname,
            "scale_op": s_node.op, "quant_op": q_node.op,
            "dequant_op": dq_node.op,
            "new_ops": [s_node.op, q_node.op, dq_node.op],
            "consumers": [(n.op, slot) for n, slot, _a, _t in consumers],
        })
