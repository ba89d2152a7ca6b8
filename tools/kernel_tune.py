#!/usr/bin/env python
"""Offline kernel-tier autotuning: predict, measure, print, persist.

ONE CLI for every kernel-tier tuning job (it absorbed the old
``tools/flash_tune.py`` — flash is just ``--op attention`` here now):

* **Microbenchmark mode** (default): tune the kernel registry's
  candidate grids for explicit shapes (or the built-in model-zoo
  signatures) and persist the winners to the shared JSON cache
  (``PADDLE_TPU_KERNEL_CACHE_DIR``; unset = measured and printed, not
  persisted) — the same entries lowering-time dispatch serves, so one
  offline run here means every later process given the same directory
  skips tuning entirely (docs/KERNELS.md).
* ``--auto``: route each grid through the unified autotuner
  (``kernels/autotune.py``): rank candidates by roofline-predicted
  cost, measure only the surviving top half, report what was pruned.

    python tools/kernel_tune.py --op layernorm_residual --shapes 4096x512
    python tools/kernel_tune.py --op adam_update --shapes 1000000 --json
    python tools/kernel_tune.py --op attention --shapes 1024:1024 --auto
    python tools/kernel_tune.py                    # every op, zoo shapes

Shape grammar (one comma-separated list): ``NxD`` rows for
``layernorm_residual``, ``N[:K]`` (total elements across a K-param
group, default K=8 — the concat/split wrapper the tuner measures
scales with K) for ``adam_update``/``sgd_update``, and ``SQ:SK`` (or a
bare ``S``) for ``attention``. ``--candidates`` overrides the registry
grid with the same per-op grammar (``64`` row-block / ``256x128``
BQxBK).

Prints one line per measured candidate plus the persisted winner; with
``--json`` emits a single JSON document instead. Exit codes: 0 ok,
2 when ANY candidate crashes the Mosaic block-legality checks (an
illegal grid entry is a bug, never a silent skip), 1 on other failures.
Honors ``PADDLE_TPU_KERNEL_TUNE_DETERMINISTIC`` (seeded fake timings —
CI exercises the full path without timing flakes).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# representative model-zoo signatures per op (transformer base S=128 and
# the S=1024 long-context variant; optimizer sweeps sized like the zoo's
# parameter groups)
ZOO_SHAPES = {
    "layernorm_residual": ["4096x512", "32768x512"],
    "adam_update": ["262144:16", "4194304:16"],
    "sgd_update": ["262144:16", "4194304:16"],
    "attention": ["128:128", "1024:1024"],
}

# optimizer sweeps tune per GROUP: N total elements across K params
# (the concat/split wrapper cost scales with K) — default K when the
# shape gives only N
_DEFAULT_GROUP = 8


def parse_sig(op: str, text: str, dtype: str):
    if op == "attention":
        parts = text.split(":")
        sq = int(parts[0])
        sk = int(parts[1]) if len(parts) > 1 else sq
        return (sq, sk)
    if op == "layernorm_residual":
        n, d = (int(v) for v in text.split("x"))
        return (dtype, n, d)
    parts = text.split(":")
    n = int(parts[0])
    k = int(parts[1]) if len(parts) > 1 else _DEFAULT_GROUP
    return (dtype, n, k)


def parse_candidates(op: str, text: str):
    out = []
    for tok in text.split(","):
        tok = tok.strip()
        if not tok:
            continue
        if "x" in tok:
            out.append(tuple(int(v) for v in tok.split("x")))
        else:
            out.append((int(tok),))
    return out


def main(argv=None) -> int:
    from paddle_tpu import kernels
    from paddle_tpu.kernels import tune

    ap = argparse.ArgumentParser(
        description="measure kernel-tier candidates and persist winners")
    ap.add_argument("--op", choices=kernels.all_kernels(), default=None,
                    help="one kernel (default: all registered)")
    ap.add_argument("--shapes", default=None,
                    help="comma-separated signatures (see module doc); "
                         "default: the model-zoo set for the op")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--candidates", default=None,
                    help="override the registry candidate grid")
    ap.add_argument("--json", action="store_true",
                    help="one JSON document instead of per-line output")
    ap.add_argument("--auto", action="store_true",
                    help="unified autotuner: roofline-prune each grid, "
                         "measure only the surviving top half")
    args = ap.parse_args(argv)
    if args.shapes and not args.op:
        # each op has its own shape grammar; a bare --shapes cannot
        # apply to all of them
        ap.error("--shapes requires --op (per-op shape grammar)")
    if args.candidates and not args.op:
        ap.error("--candidates requires --op (per-op candidate grammar)")

    from paddle_tpu.flags import enable_compile_cache

    enable_compile_cache()
    ops = [args.op] if args.op else kernels.all_kernels()
    report = {"cache": tune.cache_path(), "runs": []}
    legality_crash = False
    for op in ops:
        kdef = kernels.get_kernel(op)
        shapes = (args.shapes.split(",") if args.shapes
                  else ZOO_SHAPES.get(op, []))
        cands = parse_candidates(op, args.candidates) \
            if args.candidates else None
        for text in shapes:
            sig = parse_sig(op, text.strip(), args.dtype)
            grid = list(cands if cands is not None
                        else kdef.candidates(sig))
            run = {"op": op, "sig": list(sig), "candidates": []}
            # assert Mosaic legality for EVERY candidate up front: an
            # illegal entry is a grid bug and fails the whole tune
            for cfg in grid:
                try:
                    kdef.check(cfg, sig)
                except Exception as e:
                    legality_crash = True
                    run["candidates"].append(
                        {"cfg": list(cfg), "error": "%s: %s"
                         % (type(e).__name__, e)})
                    if not args.json:
                        print(json.dumps(
                            {"op": op, "sig": list(sig),
                             "cfg": list(cfg),
                             "error": str(e)}), flush=True)
            if any("error" in c for c in run["candidates"]):
                report["runs"].append(run)
                continue
            if args.auto:
                from paddle_tpu.kernels.autotune import autotune_kernel

                dec = autotune_kernel(op, sig, candidates=grid)
                for p in dec.get("pruned", []):
                    row = {"op": op, "sig": list(sig),
                           "label": p["label"], "pruned": True,
                           "predicted_seconds": p["predicted_seconds"]}
                    run["candidates"].append(row)
                    if not args.json:
                        print(json.dumps(row), flush=True)
            else:
                dec = tune.tune(op, sig, candidates=grid)
            for t in dec.get("timings", []):
                row = {"op": op, "sig": list(sig), "label": t["label"],
                       "seconds": t["seconds"]}
                run["candidates"].append(row)
                if not args.json:
                    print(json.dumps(row), flush=True)
            run["winner"] = {"choice": dec["choice"], "cfg": dec["cfg"],
                             "seconds": dec["seconds"]}
            if dec.get("errors"):
                run["measure_errors"] = dec["errors"]
            report["runs"].append(run)
            if not args.json:
                print(json.dumps({"op": op, "sig": list(sig),
                                  "winner": run["winner"],
                                  "persisted": tune.cache_path()}),
                      flush=True)
    if args.json:
        print(json.dumps(report, indent=1))
    if legality_crash:
        print("FAIL: Mosaic-illegal candidate(s) in the grid",
              file=sys.stderr)
        return 2
    if not report["runs"]:
        print("nothing tuned (no shapes for the selected op)",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
