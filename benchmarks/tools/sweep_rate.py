#!/usr/bin/env python3
"""Find the knee of an ``open_loop_blocks`` cell: one process, one set-up,
a few rates of ``--seconds`` each. Done ONCE, on the chip, when the cell
is defined; the traffic file's ``rate`` is 0.8 x the knee.

    python3 benchmarks/tools/sweep_rate.py --workload gpt2m_serve_chat \
        --rates 6,8,10,12 --seconds 45 [--seed N]

The knee is the highest rate at which the backlog does not grow (the
queue is as empty at the window's end as at its start and the completed
tokens per second keep up with the offered) and no request is refused.
Prints one JSON line per rate; the table goes into PERF.md."""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import open_loop  # noqa: E402
from benchmarks.lib.manifest import Manifest  # noqa: E402
from benchmarks.lib.stats import percentile  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--seed", type=int, default=1000000007)
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()

    import jax

    manifest = Manifest(args.manifest)
    cell = manifest.cell(args.workload)
    if not args.cpu_rehearsal and jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep_rate: no TPU; a knee is a device number")
    from benchmarks.run import _enable_cache

    _enable_cache()
    traffic = manifest.traffic(cell["traffic"])
    config = manifest.config(cell["config"])
    kind = manifest.load_module("kinds", traffic["kind"])
    cfg, serving = dict(config["model"]), config["serving"]

    from benchmarks.lib.jaxmon import JaxMonitor

    engine, _params = kind.build_engine(cfg, serving, traffic, args.seed,
                                        JaxMonitor())
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            tr = dict(traffic, rate=rate)
            horizon = tr["ramp_s"] + args.seconds
            requests = open_loop.schedule(tr, args.seed, horizon)
            prompts = open_loop.token_ids(requests, args.seed, cfg["vocab"])
            d = kind.drive(engine, tr, requests, prompts, args.seconds,
                           kind.Window())
            gen = d["gen"]
            window = d["t_close"] - d["t_open"]
            late = [gen.late[i] * 1e3 for i in d["in_window"]
                    if gen.late[i] is not None]
            print(json.dumps({
                "rate": rate,
                "offered_out_tok_s": sum(requests[i][2]
                                         for i in d["in_window"]) / window,
                "serve_tok_s": d["tokens_out"] / window,
                "req_tok_ms_p50": percentile(d["per_tok_ms"], 50),
                "req_tok_ms_p95": percentile(d["per_tok_ms"], 95),
                "completed_in_window": len(d["sample"]),
                "queue_at_close": d["queue_at_close"],
                "unfinished_at_close": d["unfinished_at_close"],
                "refused": gen.refused, "errors": d["errors"],
                "gen_late_ms_p95": percentile(late, 95),
            }), flush=True)
            time.sleep(1.0)
    finally:
        engine.stop()


if __name__ == "__main__":
    main()
