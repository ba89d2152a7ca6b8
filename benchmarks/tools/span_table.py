#!/usr/bin/env python3
"""Print where the host's time went, from a flight-recorder dump: for
every site of the program's spans the count, the median and the 95th
percentile in ms, and the self time of the spans that hold others.

    PADDLE_TPU_FLIGHT_RECORDER_PATH=/tmp/flight.json \
        python3 benchmarks/run.py --workload <cell> ... --trace 1
    python3 benchmarks/tools/span_table.py /tmp/flight.json \
        [--inside serving.engine.step] [--min-active 8]

The program writes the dump at exit (``paddle_tpu/observe/trace.py``).
It holds the whole ring: warm-up, ramp, window, drain and probes.
``--inside SITE`` keeps the spans below a span of that site;
``--min-active N`` keeps the decode steps with at least N riders (and
what is below them), which leaves out warm-up and probes, where a request
runs alone. For looking at a run by hand; the per-layer metrics read the
same spans through ``benchmarks/lib/program_spans.py``."""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.lib import program_spans  # noqa: E402
from benchmarks.lib.stats import median, percentile  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("dump")
    ap.add_argument("--inside", default=None)
    ap.add_argument("--min-active", type=int, default=None)
    args = ap.parse_args()
    with open(args.dump) as f:
        events = json.load(f)["events"]
    record = {"program_spans": events, "program_window": (None, None)}
    spans = program_spans.finished(record)
    if args.min_active is not None:
        steps = [ev for ev in spans if ev["site"] == "serving.engine.step"
                 and (ev["attrs"] or {}).get("active", 0)
                 >= args.min_active]
        keep = {ev["span"] for ev in steps}
        grew = True
        while grew:     # and everything below them
            below = {ev["span"] for ev in spans if ev["parent"] in keep}
            grew = not below <= keep
            keep |= below
        spans = [ev for ev in spans if ev["span"] in keep]
    if args.inside is not None:
        spans = [ev for ev in spans if ev["site"] == args.inside] \
            + program_spans.descendants(spans, args.inside)
    record["program_spans"] = spans
    by_site = {}
    for ev in spans:
        by_site.setdefault(ev["site"], []).append(ev)
    print("%-30s %7s %10s %10s %10s" % ("site", "n", "median_ms",
                                        "p95_ms", "self_ms"))
    for site in sorted(by_site):
        durs = [ev["dur"] * 1e3 for ev in by_site[site]]
        selfs = program_spans.self_ms(record, site)
        print("%-30s %7d %10.3f %10.3f %10.3f" % (
            site, len(durs), median(durs), percentile(durs, 95),
            median(selfs)))
    for site in ("executor.place", "executor.h2d"):
        attrs = [ev["attrs"] for ev in by_site.get(site, ())
                 if ev["attrs"]]
        if attrs:
            print("%s attrs, median: %s" % (site, {
                k: median([a[k] for a in attrs if k in a])
                for k in sorted(attrs[0])}))


if __name__ == "__main__":
    main()
