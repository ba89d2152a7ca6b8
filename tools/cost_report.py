#!/usr/bin/env python
"""Roofline cost report over example model programs.

The CLI face of ``paddle_tpu.analysis.cost`` (the per-op FLOPs /
bytes-moved / roofline engine), sharing the model-zoo builders with
tools/lint_program.py: build one or more example train programs, price
every op analytically, and report per-op and per-op-type FLOPs, bytes
moved, roofline seconds, the dominating bound (compute / memory /
overhead), the predicted step time, and the predicted MFU on the
resolved device model.

    python tools/cost_report.py                          # all examples
    python tools/cost_report.py --model gpt resnet       # a subset
    python tools/cost_report.py --batch-size 64          # evaluate B
    python tools/cost_report.py --steps-per-call 10      # window mode
    python tools/cost_report.py --top 20                 # more op rows
    python tools/cost_report.py --json                   # machine-readable

The prediction is the PRE-COMPILE analytic bracket (it cannot see XLA
fusion — docs/ANALYSIS.md "The cost engine" has the honesty note);
tests/test_cost.py holds it within a stated factor of the measured
step across the zoo. Device peaks come from ``DeviceModel.current()``
(env overrides > TPU table > persisted calibration > probe).

Exit code: 0 ok, 2 bad usage.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lint_program import EXAMPLE_BUILDERS, build_example  # noqa: E402


def analyze_example(name, batch_size=32, steps_per_call=1,
                    optimizer=True):
    """Build example ``name`` and price its train program. Returns
    (CostAnalysis, report dict)."""
    from paddle_tpu.analysis.cost import CostAnalysis

    main, _startup, loss = build_example(name, optimizer=optimizer)
    ca = CostAnalysis(main, fetch_names=[loss.name], site="cli")
    dev = ca.device
    report = {
        "batch_size": batch_size,
        "steps_per_call": steps_per_call,
        "flops": ca.flops(batch_size),
        "bytes_moved": ca.bytes_moved(batch_size),
        "flops_form": ca.flops_poly().describe(),
        "predicted_seconds": ca.predicted_seconds(
            batch_size, steps_per_call=steps_per_call),
        "predicted_mfu": ca.predicted_mfu(
            batch_size, steps_per_call=steps_per_call),
        "device": {"kind": dev.kind, "source": dev.source,
                   "peak_flops": dev.peak_flops,
                   "peak_bandwidth": dev.peak_bandwidth},
        "by_op_type": ca.by_op_type(batch_size),
        "unruled_ops": sorted(set(ca.unruled)),
    }
    return ca, report


def _fmt_eng(x, unit):
    for scale, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"),
                          (1e3, "K")):
        if x >= scale:
            return "%.2f %s%s" % (x / scale, suffix, unit)
    return "%.0f %s" % (x, unit)


def _print_report(name, report, top):
    print("== %s @ batch %d%s: predicted %.3f ms/step, MFU %.1f%% "
          "(device %s/%s)"
          % (name, report["batch_size"],
             " (K=%d window)" % report["steps_per_call"]
             if report["steps_per_call"] > 1 else "",
             report["predicted_seconds"] * 1e3,
             report["predicted_mfu"] * 100,
             report["device"]["kind"], report["device"]["source"]))
    print("   %s, %s moved | flops form: %s"
          % (_fmt_eng(report["flops"], "FLOP"),
             _fmt_eng(report["bytes_moved"], "B"),
             report["flops_form"]))
    for row in report["by_op_type"][:top]:
        print("   %-28s x%-3d %12s %12s %10.1f us"
              % (row["op_type"], row["count"],
                 _fmt_eng(row["flops"], "FLOP"),
                 _fmt_eng(row["bytes"], "B"),
                 row["seconds"] * 1e6))
    if report["unruled_ops"]:
        print("   (bytes-only ops without a FLOP rule: %s)"
              % ", ".join(report["unruled_ops"][:8]))


def main(argv=None):
    p = argparse.ArgumentParser(
        description="roofline cost report over example model programs")
    p.add_argument("--model", nargs="*", choices=sorted(EXAMPLE_BUILDERS),
                   help="examples to analyze (default: all)")
    p.add_argument("--batch-size", type=int, default=32,
                   help="batch size to evaluate the polynomials at")
    p.add_argument("--steps-per-call", type=int, default=1,
                   help="whole-loop-compilation window K (the per-call "
                        "host overhead amortizes by K)")
    p.add_argument("--top", type=int, default=10,
                   help="op-type rows to list, most expensive first")
    p.add_argument("--per-op", action="store_true",
                   help="include the full per-op table (JSON only)")
    p.add_argument("--json", action="store_true",
                   help="emit one JSON document instead of text")
    p.add_argument("--no-optimizer", action="store_true",
                   help="analyze the forward-only program (no Adam step)")
    args = p.parse_args(argv)
    if args.batch_size < 1:
        p.error("--batch-size must be >= 1")
    if args.steps_per_call < 1:
        p.error("--steps-per-call must be >= 1")

    names = args.model or sorted(EXAMPLE_BUILDERS)
    out = {}
    for name in names:
        ca, report = analyze_example(
            name, batch_size=args.batch_size,
            steps_per_call=args.steps_per_call,
            optimizer=not args.no_optimizer)
        if args.per_op:
            report["table"] = ca.table(args.batch_size)
        out[name] = report
        if not args.json:
            _print_report(name, report, args.top)
    if args.json:
        json.dump(out, sys.stdout, indent=2)
        sys.stdout.write("\n")
    return 0


if __name__ == "__main__":
    # standalone CLI runs force the cpu backend BEFORE paddle_tpu
    # imports jax (same contract as lint_program.py: NOT at module
    # import, which tests import in-process)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
