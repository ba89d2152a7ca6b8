#!/usr/bin/env python3
"""Run one cell several times, one process a run, and report the spread.

    python3 benchmarks/tools/sets.py --workload <cell> --seeds 11,12,13 \
        [--seconds N] [--sets 2] [--trace-last] [--out chiprun_out/x.jsonl]

The builder's tool for setting bounds: each set runs the same seeds, the
spread of a metric is the distance between its quartiles as a share of
its median (``statistics.quantiles(n=4)``), and the bound is about five
times the wider set's. This parent never touches JAX: a chip belongs to
one process at a time."""

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib.stats import iqr_share, median  # noqa: E402


def run_once(command, workload, seed, seconds, trace):
    cmd = list(command) + ["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.time()
    proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    lines = proc.stdout.strip().splitlines()
    result = None
    if proc.returncode == 0 and lines:
        result = json.loads(lines[-1])
    return {"cmd": cmd[2:], "rc": proc.returncode, "wall_s": time.time() - t0,
            "result": result, "stderr_tail": proc.stderr[-1500:]}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1000000007,2000000011,3000000019")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace-last", action="store_true",
                    help="one more run with --trace 1 at the end")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    seconds = args.seconds or doc["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    out = args.out or os.path.join(ROOT, "chiprun_out",
                                   "sets_%s.jsonl" % args.workload)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    sets = []
    with open(out, "a") as log:
        for s in range(args.sets):
            runs = []
            for seed in seeds:
                r = run_once(doc["command"], args.workload, seed, seconds, 0)
                r["set"] = s
                log.write(json.dumps(r) + "\n")
                log.flush()
                print(json.dumps(r), flush=True)
                runs.append(r)
                if not (r["result"] and r["result"]["correct"]):
                    raise SystemExit("run failed or not correct: stopping")
            sets.append(runs)
        if args.trace_last:
            r = run_once(doc["command"], args.workload, seeds[0], seconds, 1)
            r["set"] = "trace"
            log.write(json.dumps(r) + "\n")
            print(json.dumps(r), flush=True)
    summary = {}
    for s, runs in enumerate(sets):
        good = [r["result"]["metrics"] for r in runs if r["result"]]
        if len(good) < 2:
            continue
        for name in good[0]:
            vals = [m[name]["value"] for m in good]
            # each side's first run compiles: its set-up is recorded apart
            if name == "setup_s" and s == 0:
                vals = vals[1:]
            if len(vals) >= 2:
                summary.setdefault(name, []).append(
                    {"set": s, "median": median(vals),
                     "spread": iqr_share(vals), "values": vals})
    print(json.dumps({"workload": args.workload, "summary": summary},
                     indent=1))
    with open(out, "a") as log:
        log.write(json.dumps({"workload": args.workload,
                              "summary": summary}) + "\n")


if __name__ == "__main__":
    main()
