#!/usr/bin/env python
"""Capture example EAGER callables and report what the static toolchain
sees: the CLI face of ``paddle_tpu.imperative.jit``.

Each example builds eager layers under ``imperative.guard()``, captures
one call through ``imperative.jit``, and reports:

* lint/verify findings on the captured Program (def_site provenance
  points at the EAGER source lines — imperative/ is machinery);
* per-pass op counts from the level-2 TV-checked pipeline shakedown the
  capture already ran;
* the memory engine's predicted peak HBM bytes at the traced batch and
  any ``--batch`` sizes (priced from the capture's batch-size-free
  ``BytesPoly`` polynomials — no re-analysis).

    python tools/capture_program.py                  # all examples
    python tools/capture_program.py --model mlp      # a subset
    python tools/capture_program.py --batch 8 64     # price more batches
    python tools/capture_program.py --json           # machine-readable

Exit code: 0 = every captured program verify-clean (no error findings),
1 = at least one error finding or failed capture, 2 = bad usage.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# eager example builders: each returns (fn, args) where fn is the eager
# callable to capture and args are sample tensors for the first call.
# Built lazily INSIDE an imperative.guard (parameters draw numpy RNG).
EAGER_EXAMPLES = {}


def _example(name):
    def deco(fn):
        EAGER_EXAMPLES[name] = fn
        return fn

    return deco


@_example("mlp")
def _build_mlp():
    import numpy as np

    from paddle_tpu import imperative
    from paddle_tpu.imperative import nn, trace_op

    fc1, fc2 = nn.FC("fc1", 32, act="relu"), nn.FC("fc2", 10)

    def fwd(x):
        return fc2(fc1(x))

    x = imperative.to_variable(
        np.random.RandomState(0).rand(8, 64).astype("float32"))
    x.stop_gradient = True
    return fwd, (x,)


@_example("mlp_train")
def _build_mlp_train():
    import numpy as np

    from paddle_tpu import imperative
    from paddle_tpu.imperative import nn, optimizer, trace_op

    fc1, fc2 = nn.FC("fc1", 32, act="relu"), nn.FC("fc2", 1)
    adam = optimizer.Adam(learning_rate=1e-3)

    def step(x, y):
        h = trace_op("dropout", {"X": [fc1(x)]},
                     {"dropout_prob": 0.2, "is_test": False})["Out"][0]
        d = trace_op("elementwise_sub", {"X": [fc2(h)], "Y": [y]}, {})["Out"][0]
        sq = trace_op("square", {"X": [d]}, {})["Out"][0]
        loss = trace_op("reduce_mean", {"X": [sq]}, {})["Out"][0]
        loss.backward()
        adam.step(fc1.parameters() + fc2.parameters())
        return loss

    rs = np.random.RandomState(0)
    x = imperative.to_variable(rs.rand(8, 64).astype("float32"))
    y = imperative.to_variable(rs.rand(8, 1).astype("float32"))
    x.stop_gradient = True
    y.stop_gradient = True
    return step, (x, y)


@_example("conv")
def _build_conv():
    import numpy as np

    from paddle_tpu import imperative
    from paddle_tpu.imperative import nn

    conv = nn.Conv2D("conv", 3, 8, 3, act="relu")
    pool = nn.Pool2D("pool", pool_size=2, pool_type="max", pool_stride=2)
    fc = nn.FC("fc", 10)

    def fwd(x):
        return fc(pool(conv(x)))

    x = imperative.to_variable(
        np.random.RandomState(0).rand(4, 3, 16, 16).astype("float32"))
    x.stop_gradient = True
    return fwd, (x,)


def capture_example(name):
    """Capture one example under a fresh guard; returns the
    CapturedFunction (already traced once)."""
    import numpy as np

    from paddle_tpu import imperative

    np.random.seed(0)
    with imperative.guard(seed=0):
        fn, args = EAGER_EXAMPLES[name]()
        cap = imperative.jit(fn, name=name)
        cap(*args)
    return cap


def report_example(name, batches=()):
    """Capture ``name`` and build its report dict: findings, per-pass op
    counts, predicted peak bytes."""
    from paddle_tpu.analysis import verify_program

    cap = capture_example(name)
    entry = cap._last_entry
    program = entry.program
    findings = verify_program(program, fetch_list=entry.fetch_names,
                              raise_on_error=False, site="cli")
    peaks = {}
    if cap._ma is not None:
        for b in sorted({entry.lead or 1, *batches}):
            peaks[int(b)] = int(cap._ma.peak_bytes(b))
    return {
        "ops": len(program.global_block().ops),
        "feeds": list(entry.feed_order),
        "fetches": list(entry.fetch_names),
        "guards": len(entry.guards),
        "trainable": bool(entry.trainable),
        "findings": findings,
        "passes": [{"pass": r["pass"], "ops_before": r["ops_before"],
                    "ops_after": r["ops_after"]}
                   for r in entry.pass_stats],
        "peak_bytes": peaks,
    }


def main(argv=None):
    p = argparse.ArgumentParser(
        description="capture eager example callables into Programs and "
                    "report lint findings, per-pass op counts and "
                    "predicted peak HBM bytes")
    p.add_argument("--model", nargs="*", choices=sorted(EAGER_EXAMPLES),
                   help="examples to capture (default: all)")
    p.add_argument("--batch", nargs="*", type=int, default=[],
                   help="extra batch sizes to price against the memory "
                        "polynomials (the traced batch always prints)")
    p.add_argument("--json", action="store_true",
                   help="emit one JSON document instead of text")
    args = p.parse_args(argv)

    if any(b < 1 for b in args.batch):
        p.error("--batch sizes must be >= 1")

    names = args.model or sorted(EAGER_EXAMPLES)
    report = {}
    n_errors = 0
    for name in names:
        rep = report_example(name, batches=args.batch)
        n_errors += sum(1 for f in rep["findings"]
                        if f.severity == "error")
        report[name] = rep
        if args.json:
            continue
        print("== %s: %d op(s), %d feed(s), %d guard(s)%s"
              % (name, rep["ops"], len(rep["feeds"]), rep["guards"],
                 " [train step]" if rep["trainable"] else ""))
        print("   findings: %d error, %d warning, %d info"
              % tuple(sum(1 for f in rep["findings"] if f.severity == s)
                      for s in ("error", "warning", "info")))
        for f in rep["findings"]:
            print("      " + f.format())
        for row in rep["passes"]:
            print("   pass %-42s %3d -> %3d ops"
                  % (row["pass"], row["ops_before"], row["ops_after"]))
        for b, peak in sorted(rep["peak_bytes"].items()):
            print("   predicted peak @ batch %-5d %d bytes" % (b, peak))
    if args.json:
        json.dump({name: {**rep,
                          "findings": [f.to_dict()
                                       for f in rep["findings"]]}
                   for name, rep in report.items()},
                  sys.stdout, indent=2)
        sys.stdout.write("\n")
    return 1 if n_errors else 0


if __name__ == "__main__":
    # standalone CLI runs force the cpu backend BEFORE paddle_tpu imports
    # jax; NOT at module import — tests import this module in-process
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
