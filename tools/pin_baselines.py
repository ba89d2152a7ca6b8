"""Pin measured bench rows into bench.py's BASELINES dict.

The contract: committed hardware numbers and the baseline pinning land
in the SAME commit, or regression tracking slips. This tool makes that a
one-liner:

    python bench.py | tee BENCH_rows.json
    python tools/pin_baselines.py BENCH_rows.json

Only rows with a real value pin; error rows are skipped. A row pins
when it beats (or first sets) the current baseline — regressions are
reported, not silently pinned over.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def load_rows(path):
    """Noise-tolerant bench JSON-lines parser: result rows only (error
    rows never pin)."""
    rows = []
    if not os.path.exists(path):
        return rows
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except ValueError:
                continue
            if not isinstance(row, dict):
                continue
            if not ("value" in row and "metric" in row):
                continue
            rows.append(row)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("bench_json", help="file of bench.py JSON lines")
    ap.add_argument("--force", action="store_true",
                    help="pin even when the new value is a regression")
    ap.add_argument("--bench", default=BENCH,
                    help="bench.py path to rewrite (tests use a copy)")
    args = ap.parse_args()

    rows = load_rows(args.bench_json)
    if not rows:
        print("no result rows in %s" % args.bench_json, file=sys.stderr)
        return 1

    src = open(args.bench).read()
    m = re.search(r"BASELINES = \{(.*?)\}", src, re.S)
    ms = re.search(r"BASELINE_SPC = \{(.*?)\}", src, re.S)
    if not m or not ms:
        print("BASELINES / BASELINE_SPC dict not found in bench.py",
              file=sys.stderr)
        return 1
    current = eval("{" + m.group(1) + "}")  # noqa: S307 - our own literal
    cur_spc = eval("{" + ms.group(1) + "}")  # noqa: S307
    # bench's default dispatch mode: baselines track the DEFAULT config
    # so every future plain `python bench.py` run regression-compares.
    # A/B rows measured at other steps_per_call values (sweeps like the
    # 2026-07-31 spc=50 probe) are informational — they must not
    # re-anchor the baseline away from the default mode (--force pins
    # them anyway).
    md = re.search(r"^DEFAULT_STEPS_PER_CALL\s*=\s*(\d+)", src, re.M)
    if not md:
        print("DEFAULT_STEPS_PER_CALL not found in bench.py — cannot "
              "tell sweep rows from default-mode rows", file=sys.stderr)
        return 1
    default_spc = int(md.group(1))

    changed = False
    for row in rows:
        name, value = row["metric"], float(row["value"])
        if row.get("recompute") or row.get("batch_scale", 1) != 1 \
                or "flash_min_seq" in row or row.get("pipelined") \
                or row.get("serving") or row.get("fleet") \
                or row.get("elastic") or row.get("quantized") \
                or row.get("dygraph") or row.get("artifact"):
            # fleet rows (prefix cache + speculative draft + router)
            # measure a DIFFERENT serving configuration again: they are
            # incomparable with non-fleet serving rows too, not just
            # with training baselines; elastic rows measure a chaos
            # RECOVERY path on CPU subprocesses, not a training config;
            # quantized rows compiled a DIFFERENT (int8-PTQ) program
            # with its own accuracy/latency trade; dygraph rows (eager
            # AND captured-replay) measure dispatch overhead on a toy
            # MLP, not any training baseline's workload; artifact rows
            # measure cold-start-to-first-token (a load path), not
            # steady-state training throughput
            print("SKIP %s: recompute/scaled-batch/dispatch-override/"
                  "pipelined/serving/fleet/elastic/quantized/dygraph/"
                  "artifact rows never pin over the plain-config "
                  "baseline" % name)
            continue
        if row.get("kernel_tuned") or row.get("kernels") == "off":
            # a tuned kernel-tier cache or the PADDLE_TPU_KERNELS=0
            # bypass compiled DIFFERENT kernels than the default config:
            # the numbers are incomparable with (and must never
            # re-anchor) the plain-config baseline
            print("SKIP %s: kernel-tier decisions differ from the "
                  "default config (tuned cache entries or "
                  "PADDLE_TPU_KERNELS=0) — incomparable with the "
                  "plain-config baseline" % name)
            continue
        if row.get("quick"):
            print("SKIP %s: --quick smoke row (tiny batch) never pins "
                  "as a baseline" % name)
            continue
        if row.get("platform") == "cpu" and not args.force:
            print("SKIP %s: measured on the CPU backend — baselines "
                  "hold HARDWARE numbers (--force to pin anyway)" % name)
            continue
        spc = int(row.get("steps_per_call", 1))
        old, old_spc = current.get(name), cur_spc.get(name, 1)
        if row.get("distributed"):
            # distributed rows (deepfm_dist) drive per-step RPC
            # callbacks — spc=1 IS their default mode, not a sweep
            pass
        elif spc != default_spc and not args.force:
            print("SKIP %s: steps_per_call=%d row is an A/B sweep, not "
                  "bench's default mode (%d) — baselines track the "
                  "default config (--force to pin anyway)"
                  % (name, spc, default_spc))
            continue
        if old is not None and spc != old_spc:
            # dispatch-mode change: value comparison vs the old mode is
            # meaningless — pin the new (value, mode) pair and say so
            print("MODE %s: baseline re-anchored at steps_per_call=%d "
                  "(was %d)" % (name, spc, old_spc))
        elif old is not None and value < old and not args.force:
            print("SKIP %s: %.1f is a regression vs baseline %.1f "
                  "(--force to pin anyway)" % (name, value, old))
            continue
        if old != value or old_spc != spc:
            current[name], cur_spc[name] = value, spc
            changed = True
            print("PIN  %s: %s -> %.1f (spc=%d)" % (name, old, value, spc))

    if not changed:
        print("nothing to pin")
        return 0

    body = "\n".join('    "%s": %.1f,' % (k, v)
                     for k, v in sorted(current.items()))
    spc_body = "\n".join('    "%s": %d,' % (k, cur_spc.get(k, 1))
                         for k in sorted(current))
    # replace BASELINE_SPC first: its span sits after BASELINES, so the
    # earlier slice indices stay valid
    src = (src[:ms.start()] + "BASELINE_SPC = {\n" + spc_body + "\n}"
           + src[ms.end():])
    src = src[:m.start()] + "BASELINES = {\n" + body + "\n}" + src[m.end():]
    with open(args.bench, "w") as f:
        f.write(src)
    print("bench.py BASELINES updated (%d entries)" % len(current))
    return 0


if __name__ == "__main__":
    sys.exit(main())
