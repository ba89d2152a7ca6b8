#!/usr/bin/env python
"""Run the graph-optimizing pass pipeline over example model programs.

The CLI face of ``paddle_tpu.core.passes`` (docs/OPTIMIZER.md), sharing
the model-zoo builders with ``tools/lint_program.py``: builds one or
more example programs (train AND startup), runs the
``PADDLE_TPU_OPTIMIZE``-leveled pipeline on a clone, and reports what
each pass did.

    python tools/optimize_program.py                    # all examples
    python tools/optimize_program.py --model gpt mnist  # a subset
    python tools/optimize_program.py --level 1          # no fusion
    python tools/optimize_program.py --json             # machine-readable
    python tools/optimize_program.py --dot /tmp/dots    # pre/post graphs
    python tools/optimize_program.py --validate         # + rewrite logs

``--dot DIR`` writes ``<model>_<program>_{pre,post}.dot`` GraphViz files
(core/ir.py ``to_dot``) so a fusion or DCE decision can be eyeballed.
``--validate`` forces per-pass translation validation ON (even under
``PADDLE_TPU_OPTIMIZE_TV=0``) and prints each pass's declared rewrite
log — the removals/merges/forwards/fusions the validator held the pass
to (docs/OPTIMIZER.md "Translation validation contract").

Exit code: 0 = every program optimized, translation-validated and
re-verified clean, 1 = an optimizer pass broke invariants
(OptimizerPassError — TV violation or verify finding), 2 = bad usage.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lint_program import EXAMPLE_BUILDERS, build_example  # noqa: E402


def optimize_example(name, level=None, optimizer=True, tv=None):
    """Build example ``name`` and optimize train + startup programs.
    Returns {"main": {...}, "startup": {...}} with per-pass stats, each
    pass's declared rewrite log (human-readable lines), and the
    optimized programs under "_programs". ``tv=True`` forces per-pass
    translation validation on regardless of PADDLE_TPU_OPTIMIZE_TV."""
    from paddle_tpu.analysis.tv import describe_rewrites
    from paddle_tpu.core.passes import optimize_program

    main, startup, loss = build_example(name, optimizer=optimizer)
    report = {}
    programs = {}
    for tag, prog, fetch in (("main", main, [loss]),
                             ("startup", startup, [])):
        before = len(prog.global_block().ops)
        optimized, stats, mgr = optimize_program(
            prog, fetch_list=fetch, level=level, tv=tv,
            return_manager=True)
        programs[tag] = (prog, optimized)
        report[tag] = {
            "ops_before": before,
            "ops_after": len(optimized.global_block().ops),
            "passes": stats,
            "rewrite_log": [
                {"pass": entry["pass"],
                 "rewrites": describe_rewrites(entry["rewrites"])}
                for entry in mgr.rewrite_log],
        }
    report["_programs"] = programs
    return report


def _write_dots(name, programs, dot_dir):
    from paddle_tpu.core.ir import Graph

    os.makedirs(dot_dir, exist_ok=True)
    for tag, (pre, post) in programs.items():
        for stage, prog in (("pre", pre), ("post", post)):
            path = os.path.join(dot_dir, "%s_%s_%s.dot"
                                % (name, tag, stage))
            with open(path, "w") as f:
                f.write(Graph(prog).to_dot())
            print("wrote %s" % path)


def main(argv=None):
    p = argparse.ArgumentParser(
        description="graph-optimizing pass pipeline over example model "
                    "programs")
    p.add_argument("--model", nargs="*", choices=sorted(EXAMPLE_BUILDERS),
                   help="examples to optimize (default: all)")
    p.add_argument("--level", type=int, default=None,
                   help="pipeline level 0/1/2 (default: "
                        "PADDLE_TPU_OPTIMIZE, else 2)")
    p.add_argument("--json", action="store_true",
                   help="emit one JSON document instead of text")
    p.add_argument("--dot", metavar="DIR", default=None,
                   help="write pre/post GraphViz .dot files into DIR")
    p.add_argument("--no-optimizer", action="store_true",
                   help="optimize the forward-only program (no Adam "
                        "step; elementwise chains fuse more there)")
    p.add_argument("--validate", action="store_true",
                   help="force per-pass translation validation ON and "
                        "print each pass's declared rewrite log; exit "
                        "1 on any violation")
    args = p.parse_args(argv)

    from paddle_tpu.core.passes import OptimizerPassError

    names = args.model or sorted(EXAMPLE_BUILDERS)
    out = {}
    failed = 0
    for name in names:
        try:
            report = optimize_example(name, level=args.level,
                                      optimizer=not args.no_optimizer,
                                      tv=True if args.validate else None)
        except OptimizerPassError as e:
            failed += 1
            out[name] = {"error": str(e)}
            if not args.json:
                print("== %s: OPTIMIZER PASS FAILED\n%s" % (name, e))
            continue
        programs = report.pop("_programs")
        out[name] = report
        if args.dot:
            _write_dots(name, programs, args.dot)
        if not args.json:
            for tag in ("main", "startup"):
                r = report[tag]
                print("== %s %-8s %4d -> %4d ops"
                      % (name, tag, r["ops_before"], r["ops_after"]))
                for row in r["passes"]:
                    delta = row["ops_before"] - row["ops_after"]
                    extra = {k: v for k, v in row.items()
                             if k not in ("pass", "ops_before",
                                          "ops_after", "seconds") and v}
                    print("   %-38s %4d -> %4d (-%d)%s"
                          % (row["pass"], row["ops_before"],
                             row["ops_after"], delta,
                             "  %s" % extra if extra else ""))
                if args.validate:
                    for entry in r["rewrite_log"]:
                        print("   rewrite log [%s] (validated):"
                              % entry["pass"])
                        for line in entry["rewrites"]:
                            print("      " + line)
    if args.json:
        json.dump(out, sys.stdout, indent=2)
        sys.stdout.write("\n")
    return 1 if failed else 0


if __name__ == "__main__":
    # standalone CLI runs force the cpu backend BEFORE paddle_tpu imports
    # jax; deliberately only under __main__ (tests import this module and
    # call main() in-process — see tools/lint_program.py for the leak
    # this avoids)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
