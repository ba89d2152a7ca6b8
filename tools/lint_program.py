#!/usr/bin/env python
"""Static-verify example model programs from the command line.

The CLI face of ``paddle_tpu.analysis`` (Program.validate): builds one
or more example model programs (the model zoo's tiny configs — the same
ones tests/test_analysis.py pins as verify-clean), runs shape/dtype
inference + the IR lint suite over the train program AND its startup
program, and reports findings as text or JSON.

    python tools/lint_program.py                      # all examples
    python tools/lint_program.py --model gpt resnet   # a subset
    python tools/lint_program.py --json               # machine-readable
    python tools/lint_program.py --min-severity warning
    python tools/lint_program.py --validate           # + optimizer TV
    python tools/lint_program.py --ranges             # + value ranges

``--validate`` additionally runs the graph-optimizer pipeline over each
program with per-pass translation validation FORCED on
(``analysis/tv.py``) and prints the declared rewrite logs — the
standalone way to ask "does the optimizer provably preserve this
program?" without executing anything.

``--ranges`` additionally runs the value-range abstract interpreter
(``analysis/ranges.py``) over each train program and prints the per-var
interval table (text) or embeds it per model (JSON: each model maps to
``{"findings", "ranges", "range_stats"}`` instead of a bare findings
list). The numerics lint rules (bf16-overflow / domain-violation /
int-narrowing-loss) always ride the ordinary verify, so an
error-severity numerics finding exits 1 with or without the flag.

Exit code: 0 = no error findings (and, with --validate, every program
optimized TV-clean), 1 = at least one error or TV violation, 2 = bad
usage.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# tiny-config builders for every model-zoo program; each returns the loss
# Variable once called under a program_guard. Shared with
# tests/test_analysis.py (its "all example model programs verify clean"
# test parametrizes over this dict).
EXAMPLE_BUILDERS = {}


def _example(name):
    def deco(fn):
        EXAMPLE_BUILDERS[name] = fn
        return fn

    return deco


@_example("mnist")
def _build_mnist():
    from paddle_tpu.models import mnist

    return mnist.build("cnn")[0]


@_example("gpt")
def _build_gpt():
    from paddle_tpu.models import gpt

    cfg = dict(d_model=32, d_ff=64, n_head=2, n_layer=1, vocab=64,
               max_length=32, dropout=0.0)
    return gpt.build(cfg, seq_len=16)[0]


@_example("resnet")
def _build_resnet():
    from paddle_tpu.models import resnet

    return resnet.build(class_dim=10, image_shape=(3, 32, 32))[0]


@_example("transformer")
def _build_transformer():
    from paddle_tpu.models import transformer

    cfg = dict(d_model=32, d_ff=64, n_head=4, n_layer=2, src_vocab=100,
               trg_vocab=100, max_length=16, dropout=0.1)
    return transformer.build(cfg, seq_len=16)[0]


@_example("bert")
def _build_bert():
    from paddle_tpu.models import bert

    cfg = dict(d_model=32, d_ff=64, n_head=4, n_layer=2, vocab=100,
               type_vocab=2, max_length=64, dropout=0.1)
    return bert.build(cfg, seq_len=16, max_mask=4)[0]


@_example("ctr")
def _build_ctr():
    from paddle_tpu.models import ctr

    return ctr.build("deepfm", vocab=1000, emb_dim=8)[0]


@_example("vgg")
def _build_vgg():
    from paddle_tpu.models import vgg

    return vgg.build(class_dim=10, image_shape=(3, 32, 32))[0]


@_example("se_resnext")
def _build_se_resnext():
    from paddle_tpu.models import se_resnext

    return se_resnext.build(class_dim=10, image_shape=(3, 32, 32))[0]


@_example("vit")
def _build_vit():
    from paddle_tpu.models import vit

    cfg = dict(image_size=32, patch=8, d_model=32, d_ff=64, n_head=4,
               n_layer=2, n_class=10, dropout=0.0)
    return vit.build(cfg)[0]


@_example("stacked_lstm")
def _build_stacked_lstm():
    from paddle_tpu.models import stacked_lstm

    cfg = dict(vocab=60, emb_dim=16, hidden=16, num_layers=2,
               num_classes=2, seq_len=10)
    return stacked_lstm.build(cfg)[0]


@_example("machine_translation")
def _build_mt():
    from paddle_tpu.models import machine_translation

    cfg = dict(src_vocab=50, trg_vocab=50, emb_dim=16, hidden=16, seq_len=8)
    return machine_translation.build(cfg)[0]


def build_example(name, optimizer=True):
    """Build example ``name``'s (main, startup, loss) under fresh
    programs — shared by this CLI, tools/optimize_program.py, and the
    model-zoo gates in tests/test_analysis.py / tests/test_optimizer.py.
    ``optimizer=False`` skips the Adam step (forward-only program)."""
    import paddle_tpu as fluid

    builder = EXAMPLE_BUILDERS[name]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            loss = builder()
            if optimizer:
                fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main, startup, loss


def verify_example(name, optimize=True):
    """Build example ``name`` and verify train + startup programs.
    Returns (findings, programs) where findings is a flat Finding list."""
    from paddle_tpu.analysis import verify_program

    main, startup, loss = build_example(name, optimizer=optimize)
    findings = verify_program(main, fetch_list=[loss],
                              raise_on_error=False, site="cli")
    findings += verify_program(startup, raise_on_error=False, site="cli")
    return findings, (main, startup)


def main(argv=None):
    p = argparse.ArgumentParser(
        description="static program verifier over example model programs")
    p.add_argument("--model", nargs="*", choices=sorted(EXAMPLE_BUILDERS),
                   help="examples to verify (default: all)")
    p.add_argument("--json", action="store_true",
                   help="emit one JSON document instead of text")
    p.add_argument("--min-severity", choices=("info", "warning", "error"),
                   default="info", help="hide findings below this severity")
    p.add_argument("--no-optimizer", action="store_true",
                   help="verify the forward-only program (no Adam step)")
    p.add_argument("--validate", action="store_true",
                   help="also run the optimizer pipeline with per-pass "
                        "translation validation forced ON; print the "
                        "rewrite logs, exit 1 on any violation")
    p.add_argument("--ranges", action="store_true",
                   help="also run the value-range abstract interpreter "
                        "and print per-var intervals")
    args = p.parse_args(argv)

    order = {"info": 0, "warning": 1, "error": 2}
    names = args.model or sorted(EXAMPLE_BUILDERS)
    report = {}
    n_errors = 0
    for name in names:
        findings, (main, _startup) = verify_example(
            name, optimize=not args.no_optimizer)
        shown = [f for f in findings
                 if order[f.severity] >= order[args.min_severity]]
        n_errors += sum(1 for f in findings if f.severity == "error")
        report[name] = shown
        if not args.json:
            print("== %s: %d finding(s) at %s+ (%d error, %d warning, "
                  "%d info total)"
                  % (name, len(shown), args.min_severity,
                     sum(1 for f in findings if f.severity == "error"),
                     sum(1 for f in findings if f.severity == "warning"),
                     sum(1 for f in findings if f.severity == "info")))
            for f in shown:
                print("   " + f.format())
        if args.ranges:
            report[name] = _ranges_report(name, main, shown,
                                          quiet=args.json)
        if args.validate:
            n_errors += _validate_example(
                name, optimizer=not args.no_optimizer,
                quiet=args.json)
    if args.json:
        json.dump({name: (rep if isinstance(rep, dict)
                          else [f.to_dict() for f in rep])
                   for name, rep in report.items()},
                  sys.stdout, indent=2)
        sys.stdout.write("\n")
    return 1 if n_errors else 0


def _ranges_report(name, main, shown, quiet=False):
    """Run the range engine over one example's train program; print the
    interval table (text mode) and return the JSON-shaped report entry
    ``{"findings", "ranges", "range_stats"}``."""
    import math

    from paddle_tpu.analysis.ranges import RangeAnalysis

    ra = RangeAnalysis(main)
    stats = ra.stats()

    def _num(x):
        return None if not math.isfinite(x) else x

    ranges = {vname: {"lo": _num(av.lo), "hi": _num(av.hi),
                      "finite": av.finite, "integral": av.integral,
                      "const": av.is_const}
              for vname, av in ra.table()}
    if not quiet:
        print("   -- ranges: %(vars)d vars (%(const)d const, "
              "%(bounded)d bounded, %(finite)d finite, %(top)d top, "
              "%(declared_top)d declared-top)" % stats)
        for vname, av in ra.table():
            print("   %-48s %r" % (vname, av))
    return {"findings": [f.to_dict() for f in shown], "ranges": ranges,
            "range_stats": stats}


def _validate_example(name, optimizer=True, quiet=False) -> int:
    """Run the optimizer's translation validator over one example
    (level 2, TV forced on). Returns the number of failures (0/1) and
    prints the declared rewrite log unless ``quiet``."""
    from paddle_tpu.analysis.tv import describe_rewrites
    from paddle_tpu.core.passes import (OptimizerPassError,
                                        optimize_program)

    main, startup, loss = build_example(name, optimizer=optimizer)
    for tag, prog, fetch in (("main", main, [loss.name]),
                             ("startup", startup, [])):
        try:
            _, _, mgr = optimize_program(prog, fetch_list=fetch,
                                         level=2, tv=True,
                                         return_manager=True)
        except OptimizerPassError as e:
            # stderr under --json: stdout must stay one valid JSON
            # document (the exit code carries the verdict either way)
            print("== %s %s: TRANSLATION VALIDATION FAILED\n%s"
                  % (name, tag, e),
                  file=sys.stderr if quiet else sys.stdout)
            return 1
        if not quiet:
            for entry in mgr.rewrite_log:
                print("   %s rewrite log [%s] (validated):"
                      % (tag, entry["pass"]))
                for line in describe_rewrites(entry["rewrites"]):
                    print("      " + line)
    return 0


if __name__ == "__main__":
    # a static analysis needs no chip: standalone CLI runs default to the
    # cpu backend, set BEFORE paddle_tpu imports jax. Deliberately
    # NOT at module import or in main(): tests import this module and
    # call main() in-process, and an os.environ mutation there would
    # leak into every subprocess the rest of the test session spawns
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
