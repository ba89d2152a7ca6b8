#!/usr/bin/env python
"""Static peak-HBM report over example model programs.

The CLI face of ``paddle_tpu.analysis.memory`` (the liveness-based
peak-HBM engine), sharing the model-zoo builders with
tools/lint_program.py: build one or more example train programs, run
the memory analysis, and report the predicted peak, its op (with PR 5
provenance), the per-op live-byte timeline, the largest live tensors,
and — with a budget — the max safe batch size.

    python tools/memory_report.py                          # all examples
    python tools/memory_report.py --model gpt resnet       # a subset
    python tools/memory_report.py --batch-size 64          # evaluate B
    python tools/memory_report.py --steps-per-call 10      # window mode
    python tools/memory_report.py --device-budget 16G      # budget check
    python tools/memory_report.py --json                   # machine-readable
    python tools/memory_report.py --timeline               # per-op rows

The estimate is the PRE-COMPILE bracket (it cannot see XLA buffer
reuse/fusion — docs/ANALYSIS.md "The memory engine" has the honesty
note); the authoritative post-compile number is
``contrib.memory_usage_calc.compiled_memory_usage``, which
tests/test_memory.py holds this estimate within a stated factor of
across the zoo.

Exit code: 0 = every model fits (or no budget given), 1 = at least one
model's predicted peak exceeds --device-budget, 2 = bad usage.
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
    """Build example ``name`` and analyze its train program. Returns
    (MemoryAnalysis, report dict)."""
    from paddle_tpu.analysis.memory import MemoryAnalysis

    main, _startup, loss = build_example(name, optimizer=optimizer)
    ma = MemoryAnalysis(main, fetch_names=[loss.name],
                        steps_per_call=steps_per_call, site="cli")
    peak, pos = ma.peak(batch_size)
    op = None if pos < 0 else ma.df.ops[pos]
    report = {
        "batch_size": batch_size,
        "steps_per_call": steps_per_call,
        "peak_bytes": peak,
        "peak_op": None if op is None else {
            "pos": pos, "type": op.type,
            "name_scope": getattr(op, "name_scope", "") or "",
            "def_site": getattr(op, "def_site", None)},
        "peak_form": ma.peak_poly(batch_size).describe(),
        "breakdown": ma.breakdown(batch_size),
        "batch_dependent": ma.batch_dependent(),
        "unknown_tensors": list(ma.unknown),
    }
    return ma, report


def main(argv=None):
    p = argparse.ArgumentParser(
        description="static peak-HBM report over example model programs")
    p.add_argument("--model", nargs="*", choices=sorted(EXAMPLE_BUILDERS),
                   help="examples to analyze (default: all)")
    p.add_argument("--batch-size", type=int, default=32,
                   help="batch size to evaluate the byte polynomials at")
    p.add_argument("--steps-per-call", type=int, default=1,
                   help="whole-loop-compilation window K (stacked-feed "
                        "bytes multiply by K)")
    p.add_argument("--device-budget", default=None,
                   help="device HBM budget (bytes; K/M/G suffixes) — "
                        "exit 1 when any model's predicted peak "
                        "exceeds it, and report the max safe batch")
    p.add_argument("--top", type=int, default=5,
                   help="live tensors to list at the peak op")
    p.add_argument("--timeline", action="store_true",
                   help="print the full per-op live-byte timeline")
    p.add_argument("--json", action="store_true",
                   help="emit one JSON document instead of text")
    p.add_argument("--no-optimizer", action="store_true",
                   help="analyze the forward-only program (no Adam step)")
    args = p.parse_args(argv)
    if args.batch_size < 1:
        p.error("--batch-size must be >= 1")
    if args.steps_per_call < 1:
        p.error("--steps-per-call must be >= 1")

    from paddle_tpu.analysis.memory import parse_bytes

    budget = None
    if args.device_budget is not None:
        try:
            budget = parse_bytes(args.device_budget)
        except ValueError as e:
            p.error(str(e))

    names = args.model or sorted(EXAMPLE_BUILDERS)
    out = {}
    violations = 0
    for name in names:
        ma, report = analyze_example(
            name, batch_size=args.batch_size,
            steps_per_call=args.steps_per_call,
            optimizer=not args.no_optimizer)
        report["top_tensors"] = ma.top_tensors(args.batch_size, k=args.top)
        if args.timeline:
            report["timeline"] = ma.timeline(args.batch_size)
        if budget is not None:
            report["device_budget"] = budget
            report["fits"] = report["peak_bytes"] <= budget
            report["max_safe_batch"] = ma.max_safe_batch(budget)
            if not report["fits"]:
                violations += 1
        out[name] = report
        if not args.json:
            _print_report(name, report, budget)
    if args.json:
        json.dump(out, sys.stdout, indent=2)
        sys.stdout.write("\n")
    return 1 if violations else 0


def _print_report(name, report, budget):
    from paddle_tpu.analysis.memory import format_bytes

    bd = report["breakdown"]
    print("== %s @ batch %d%s: predicted peak %s"
          % (name, report["batch_size"],
             " (K=%d window)" % report["steps_per_call"]
             if report["steps_per_call"] > 1 else "",
             format_bytes(report["peak_bytes"])))
    op = report["peak_op"]
    if op is not None:
        where = op["name_scope"] or "-"
        site = " defined at %s" % op["def_site"] if op["def_site"] else ""
        print("   peak op: #%d %s (scope %s)%s"
              % (op["pos"], op["type"], where, site))
    print("   batch form at peak: %s bytes" % report["peak_form"])
    print("   persistable %s | feeds %s | activations %s | workspace %s"
          % tuple(format_bytes(bd[k]) for k in
                  ("persistable", "feed", "activation_peak",
                   "workspace_peak")))
    for t in report["top_tensors"]:
        site = " @ %s" % t["def_site"] if t["def_site"] else ""
        print("   %-44s %10s  %-11s%s"
              % (t["name"], format_bytes(t["bytes"]), t["kind"], site))
    if report.get("unknown_tensors"):
        print("   (unknown-shape tensors excluded: %s)"
              % ", ".join(report["unknown_tensors"][:5]))
    if budget is not None:
        safe = report["max_safe_batch"]
        print("   budget %s: %s%s"
              % (format_bytes(budget),
                 "FITS" if report["fits"] else "OVER BUDGET",
                 "" if safe is None else " (max safe batch %d)" % safe))
    if "timeline" in report:
        for row in report["timeline"]:
            print("   #%-4d %-28s %12s"
                  % (row["pos"], row["op_type"],
                     format_bytes(row["live_bytes"])))


if __name__ == "__main__":
    # standalone CLI runs force the cpu backend BEFORE paddle_tpu
    # imports jax (same contract as lint_program.py: NOT at module
    # import, which tests import in-process)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
