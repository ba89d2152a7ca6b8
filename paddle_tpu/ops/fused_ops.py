"""fused_elementwise: the one lowering body behind elementwise fusion.

The fusion pass (core/passes/fuse.py) collapses a single-consumer chain
of elementwise/activation ops into one op whose ``ops`` attr carries
the constituent descriptors:

    {"type": "relu", "attrs": {...}, "ins": {"X": [["x", 0]]},
     "out_slot": "Out"}

with input refs ``["x", i]`` (i-th external input, the op's ``X`` slot),
``["t", j]`` (j-th constituent's output), or ``["none", 0]``. The
lowering replays each constituent's OWN registered lowering in order,
applying the same per-op AMP cast ``lower_op`` would have applied — so
a fused chain is bitwise the unfused chain by construction, and every
future elementwise op fuses without touching this file.
"""

from __future__ import annotations

from ..core.registry import get_op, register_op


@register_op("fused_elementwise")
def _fused_elementwise(ctx, ins, attrs):
    ext = ins["X"]
    tmps = []
    amp = getattr(ctx, "amp", False)
    for spec in attrs["ops"]:
        sub_ins = {}
        for slot, refs in spec["ins"].items():
            vals = []
            for kind, i in refs:
                if kind == "none":
                    vals.append(None)
                elif kind == "x":
                    vals.append(ext[i])
                else:
                    vals.append(tmps[i])
            sub_ins[slot] = vals
        if amp:
            from ..core.amp import amp_cast

            sub_ins = amp_cast(spec["type"], spec["attrs"], sub_ins)
        outs = get_op(spec["type"]).lowering(ctx, sub_ins, spec["attrs"])
        val = outs[spec["out_slot"]]
        tmps.append(val[0] if isinstance(val, (list, tuple)) else val)
    return {"Out": [tmps[-1]]}

