"""Dump the compiled train step's performance artifacts for one
workload: optimized HLO, XLA cost analysis, donation aliasing, dominant
fusions — the inputs to the ResNet-50 MFU ladder (ROADMAP.md Queue 1
item 2; SURVEY §6 self-measurement contract).

Runs on CPU (structure analysis: aliasing, host-callback scan, op mix)
or on TPU (adds the real backend's compile). Usage:

    python tools/dump_step_hlo.py resnet50 --out /tmp/resnet50_hlo
    python tools/dump_step_hlo.py transformer --stage stablehlo

Writes <out>/step.<stage>.txt, <out>/cost.json, <out>/summary.json and
prints the summary line. Workload names match bench.py.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _alias_count(txt: str) -> int:
    start = txt.find("input_output_alias={")
    if start < 0:
        return 0
    i = txt.index("{", start)
    depth, j = 0, i
    while j < len(txt):
        if txt[j] == "{":
            depth += 1
        elif txt[j] == "}":
            depth -= 1
            if depth == 0:
                break
        j += 1
    return len(re.findall(r"\{[\d,\s]*\}:\s*\(\d+", txt[i:j + 1]))


def _op_histogram(txt: str, top: int = 15):
    """Crude op mix from HLO definition lines (dominant-op naming for
    the bottleneck analysis)."""
    counts = collections.Counter()
    for line in txt.splitlines():
        m = re.search(r"=\s+[^=]*?\s([a-z][a-z0-9-]*)\(",
                      line.split("metadata=")[0])
        if m:
            counts[m.group(1)] += 1
    return counts.most_common(top)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("workload", choices=["transformer", "transformer_long",
                                         "resnet50", "vgg16", "bert",
                                         "deepfm"])
    ap.add_argument("--out", default=None)
    ap.add_argument("--stage", choices=["optimized", "stablehlo"],
                    default="optimized")
    ap.add_argument("--quick", action="store_true", help="tiny batch")
    ap.add_argument("--fp32", action="store_true")
    args = ap.parse_args()

    # reuse bench.py's workload builders via a light shim: build the
    # program/feeds exactly as the bench does, then introspect instead
    # of timing
    import numpy as np

    import bench
    import paddle_tpu as fluid
    from paddle_tpu.core.scope import Scope, scope_guard

    captured = {}

    def capture_run_workload(name, unit, items_per_batch, build_fn,
                             feed_fn, amp, steps=10, warmup=3, quick=False,
                             recompute=False, uses_flash=False):
        main, startup = fluid.Program(), fluid.Program()
        scope = Scope()
        with scope_guard(scope):
            with fluid.program_guard(main, startup):
                loss = build_fn()
            if amp:
                main.set_amp(True)
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup, scope=scope)
            feed = feed_fn()
            txt = exe.lowered_hlo(main, feed=feed, fetch_list=[loss],
                                  scope=scope, stage=args.stage)
            cost = exe.cost_analysis(main, feed=feed, fetch_list=[loss],
                                     scope=scope)
        captured.update(name=name, txt=txt, cost=cost,
                        batch=items_per_batch)
        return {}

    bench._run_workload = capture_run_workload
    bench.WORKLOADS[args.workload](not args.fp32, args.quick)

    txt, cost = captured["txt"], captured["cost"]
    callbacks = [t for t in re.findall(r'custom_call_target="([^"]+)"', txt)
                 if "callback" in t or "python" in t]
    summary = {
        "workload": captured["name"],
        "stage": args.stage,
        "flops_per_step": cost.get("flops"),
        "bytes_accessed": cost.get("bytes accessed"),
        "alias_entries": _alias_count(txt),
        "host_callbacks": callbacks,
        "op_mix_top": _op_histogram(txt),
        "hlo_chars": len(txt),
    }
    out = args.out or ("/tmp/hlo_%s" % args.workload)
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "step.%s.txt" % args.stage), "w") as f:
        f.write(txt)
    with open(os.path.join(out, "cost.json"), "w") as f:
        json.dump(cost, f, indent=1, default=float)
    with open(os.path.join(out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1, default=float)
    print(json.dumps(summary, default=float))


if __name__ == "__main__":
    main()
