#!/usr/bin/env python
"""stats_dump: pretty-print a telemetry snapshot (live or saved sidecar).

Usage:
    python tools/stats_dump.py BENCH_resnet50.telemetry.json
    python tools/stats_dump.py BENCH_probe.telemetry.json --all
    python tools/stats_dump.py snapshot.json --prometheus
    python tools/stats_dump.py --live            # this process (near-empty;
                                                 # useful from a REPL/pdb)
    python tools/stats_dump.py --diff A.telemetry.json B.telemetry.json
                                                 # per-family deltas B vs A
    python tools/stats_dump.py BENCH_serving_decode.telemetry.json \
        --grep paddle_serving                    # just one family group
    python tools/stats_dump.py --watch 127.0.0.1:9464 --interval 2
                                                 # live: poll an exporter's
                                                 # /snapshot.json; first
                                                 # scrape renders the table,
                                                 # later ones the diff vs
                                                 # the previous scrape

Reads the JSON written by `paddle_tpu.observe.dump()` and renders
counters/gauges as a table and histograms with count/sum/mean and
estimated p50/p90/p99.
`--prometheus` re-renders the snapshot in text exposition format instead.

A serving process's snapshot carries the paddle_serving_* families —
queue depth/wait, batch rows, bucket hit/miss + padding waste, slot
occupancy, admission/retirement counters (docs/SERVING.md "Reading the
telemetry") — so `--grep paddle_serving` is the one-look serving health
view. Diagnosing a process that died early, from its dump: see
docs/OBSERVABILITY.md ("Reading a sidecar post-mortem").
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# runnable from any cwd: the repo root (parent of tools/) owns paddle_tpu
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def _percentile(buckets, count, q):
    """Estimate a quantile from cumulative {le: count} buckets (linear
    interpolation within the winning bucket, prometheus-style)."""
    if not count:
        return None
    target = q * count
    prev_le, prev_c = 0.0, 0
    items = sorted(((float("inf") if le == "+Inf" else float(le)), c)
                   for le, c in buckets.items())
    for le, c in items:
        if c >= target:
            if le == float("inf"):
                return prev_le  # open-ended bucket: report its lower edge
            span = c - prev_c
            frac = (target - prev_c) / span if span else 1.0
            return prev_le + (le - prev_le) * frac
        prev_le, prev_c = le, c
    return prev_le


def _fmt(v):
    if v is None:
        return "-"
    if isinstance(v, float):
        if v == int(v) and abs(v) < 1e12:
            return str(int(v))
        return "%.6g" % v
    return str(v)


def _label_str(labels):
    return ",".join("%s=%s" % kv for kv in sorted(labels.items()))


def _series_key(name, sample):
    """Canonical per-series key ('name{l=v,...}') — shared by the table
    and --diff renderers so their keys can never drift apart."""
    labels = sample["labels"]
    return name + ("{%s}" % _label_str(labels) if labels else "")


def render_table(snap, show_all=False, grep=None, out=sys.stdout):
    meta = "snapshot pid=%s unix_time=%s" % (snap.get("pid"),
                                             _fmt(snap.get("unix_time")))
    if grep:
        meta += "  (grep=%s)" % grep
    print(meta, file=out)
    print("-" * max(len(meta), 72), file=out)
    scalar_rows, hist_rows = [], []
    for name in sorted(snap["metrics"]):
        if grep and grep not in name:
            continue
        m = snap["metrics"][name]
        for s in m["samples"]:
            key = _series_key(name, s)
            if m["type"] == "histogram":
                if not show_all and not s["count"]:
                    continue
                cnt, tot = s["count"], s["sum"]
                hist_rows.append((
                    key, cnt, _fmt(tot), _fmt(tot / cnt if cnt else None),
                    _fmt(_percentile(s["buckets"], cnt, 0.5)),
                    _fmt(_percentile(s["buckets"], cnt, 0.9)),
                    _fmt(_percentile(s["buckets"], cnt, 0.99)),
                ))
            else:
                # gauges always render: a gauge at 0 is a signal
                # (paddle_resilience_watchdog_armed=0), only zero
                # counters are noise
                if not show_all and m["type"] == "counter" \
                        and not s["value"]:
                    continue
                scalar_rows.append((key, m["type"], _fmt(s["value"])))
    if scalar_rows:
        w = max(len(r[0]) for r in scalar_rows)
        print("%-*s %-8s %s" % (w, "metric", "type", "value"), file=out)
        for key, kind, val in scalar_rows:
            print("%-*s %-8s %s" % (w, key, kind, val), file=out)
    if hist_rows:
        print(file=out)
        w = max(len(r[0]) for r in hist_rows)
        print("%-*s %8s %10s %10s %10s %10s %10s"
              % (w, "histogram", "count", "sum", "mean", "p50", "p90",
                 "p99"), file=out)
        for key, cnt, tot, mean, p50, p90, p99 in hist_rows:
            print("%-*s %8d %10s %10s %10s %10s %10s"
                  % (w, key, cnt, tot, mean, p50, p90, p99), file=out)
    if not scalar_rows and not hist_rows:
        print("(all metrics zero — rerun with --all to list the schema)",
              file=out)


def render_diff(snap_a, snap_b, name_a="A", name_b="B", show_all=False,
                grep=None, out=sys.stdout):
    """Per-series comparison of two snapshots: counters/gauges print
    value A, value B and the delta; histograms print count/mean/p50/p99
    side by side. Built for comparing two ``observe.dump`` snapshots —
    e.g. a pipelined vs an unpipelined run — at a glance. Series present
    in only one snapshot render with '-' on the missing side."""
    print("diff: A=%s  B=%s" % (name_a, name_b), file=out)

    def _series(snap):
        table = {}
        for name, m in snap["metrics"].items():
            for s in m["samples"]:
                table[_series_key(name, s)] = (m["type"], s)
        return table

    sa, sb = _series(snap_a), _series(snap_b)
    scalar_rows, hist_rows = [], []
    for key in sorted(set(sa) | set(sb)):
        if grep and grep not in key:
            continue
        # a series present in only one sidecar is a schema change
        # (family added/removed between the two runs), not a value
        # delta — and a KIND change across versions must render, not
        # raise (treat it as removed-then-added, by each side's kind)
        in_a, in_b = key in sa, key in sb
        if in_a and in_b and sa[key][0] != sb[key][0]:
            scalar_rows.append((key, "%s->%s" % (sa[key][0], sb[key][0]),
                                "-", "-", "kind changed"))
            continue
        kind = (sa.get(key) or sb.get(key))[0]
        a = sa.get(key, (None, None))[1]
        b = sb.get(key, (None, None))[1]
        schema_note = None if (in_a and in_b) else (
            "removed" if in_a else "added")
        if kind == "histogram":
            def stats(s):
                if s is None or not s["count"]:
                    return (0, None, None, None)
                cnt = s["count"]
                return (cnt, s["sum"] / cnt,
                        _percentile(s["buckets"], cnt, 0.5),
                        _percentile(s["buckets"], cnt, 0.99))
            ca, ma, p50a, p99a = stats(a)
            cb, mb, p50b, p99b = stats(b)
            if not show_all and not ca and not cb and schema_note is None:
                continue
            key_note = key + (" [%s]" % schema_note if schema_note else "")
            hist_rows.append((key_note, ca, cb, _fmt(ma), _fmt(mb),
                              _fmt(p50a), _fmt(p50b), _fmt(p99a),
                              _fmt(p99b)))
        else:
            va = a["value"] if a is not None else None
            vb = b["value"] if b is not None else None
            # gauges always render, as in render_table: a gauge at 0 in
            # both snapshots is a signal too
            if not show_all and kind != "gauge" and not va and not vb \
                    and schema_note is None:
                continue
            delta = (vb or 0) - (va or 0)
            scalar_rows.append((key, kind, _fmt(va), _fmt(vb),
                                schema_note if schema_note
                                else ("%+g" % delta if delta else "0")))
    if scalar_rows:
        w = max(len(r[0]) for r in scalar_rows)
        print("%-*s %-8s %12s %12s %12s"
              % (w, "metric", "type", "A", "B", "delta"), file=out)
        for key, kind, va, vb, d in scalar_rows:
            print("%-*s %-8s %12s %12s %12s" % (w, key, kind, va, vb, d),
                  file=out)
    if hist_rows:
        print(file=out)
        w = max(len(r[0]) for r in hist_rows)
        print("%-*s %8s %8s %10s %10s %10s %10s %10s %10s"
              % (w, "histogram", "cnt A", "cnt B", "mean A", "mean B",
                 "p50 A", "p50 B", "p99 A", "p99 B"), file=out)
        for row in hist_rows:
            print("%-*s %8d %8d %10s %10s %10s %10s %10s %10s"
                  % ((w,) + row), file=out)
    if not scalar_rows and not hist_rows:
        print("(no non-zero series in either snapshot — --all lists "
              "the schema)", file=out)


def _fetch_snapshot(endpoint, timeout_s=5.0):
    """Pull /snapshot.json from a MetricsExporter (observe/export.py).
    stdlib-only on purpose: the watch loop must work from any shell
    without importing (or paying for) paddle_tpu."""
    from urllib.request import urlopen

    with urlopen("http://%s/snapshot.json" % endpoint,
                 timeout=timeout_s) as resp:
        snap = json.loads(resp.read().decode())
    if "metrics" not in snap:
        raise ValueError("%s/snapshot.json is not a telemetry snapshot"
                         % endpoint)
    return snap


def watch(endpoint, interval=2.0, count=None, grep=None,
          show_all=False, out=sys.stdout):
    """Live mode: poll an exporter endpoint. The first scrape renders
    the full table; every later one renders the per-series diff
    against the PREVIOUS scrape (the same renderers as the file
    modes, so --grep/--all compose unchanged)."""
    import time

    prev, n = None, 0
    try:
        while True:
            snap = _fetch_snapshot(endpoint)
            if prev is None:
                render_table(snap, show_all=show_all, grep=grep, out=out)
            else:
                render_diff(prev, snap,
                            name_a="scrape %d" % n,
                            name_b="scrape %d" % (n + 1),
                            show_all=show_all, grep=grep, out=out)
            print(file=out, flush=True)
            prev, n = snap, n + 1
            if count is not None and n >= count:
                return 0
            time.sleep(interval)
    except KeyboardInterrupt:
        return 0


def _load_snapshot(path, ap):
    with open(path) as f:
        snap = json.load(f)
    if "metrics" not in snap:
        ap.error("%s is not a telemetry snapshot (no 'metrics' key)" % path)
    return snap


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="pretty-print a paddle_tpu telemetry snapshot")
    ap.add_argument("snapshot", nargs="?", default=None,
                    help="path to a saved snapshot/sidecar JSON")
    ap.add_argument("--live", action="store_true",
                    help="snapshot THIS process's registry instead of a file")
    ap.add_argument("--prometheus", action="store_true",
                    help="render text exposition format instead of a table")
    ap.add_argument("--all", action="store_true",
                    help="include zero-valued series (show the full schema)")
    ap.add_argument("--diff", nargs=2, metavar=("A", "B"), default=None,
                    help="compare two snapshots: per-series value deltas "
                         "and histogram count/mean/p50/p99 side by side")
    ap.add_argument("--grep", default=None, metavar="SUBSTR",
                    help="only families whose name contains SUBSTR (e.g. "
                         "paddle_serving for the serving scheduler view)")
    ap.add_argument("--watch", default=None, metavar="HOST:PORT",
                    help="live mode: poll a MetricsExporter's "
                         "/snapshot.json; table first, then diffs vs "
                         "the previous scrape")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="--watch poll interval (seconds)")
    ap.add_argument("--count", type=int, default=None,
                    help="--watch: stop after N scrapes (default: "
                         "until Ctrl-C)")
    args = ap.parse_args(argv)

    if args.watch is not None:
        if args.live or args.snapshot is not None or args.prometheus \
                or args.diff is not None:
            ap.error("--watch composes only with --grep/--all/"
                     "--interval/--count")
        return watch(args.watch, interval=args.interval,
                     count=args.count, grep=args.grep,
                     show_all=args.all)

    if args.diff is not None:
        if args.live or args.snapshot is not None or args.prometheus:
            ap.error("--diff takes exactly two snapshot paths and "
                     "composes only with --all")
        render_diff(_load_snapshot(args.diff[0], ap),
                    _load_snapshot(args.diff[1], ap),
                    name_a=os.path.basename(args.diff[0]),
                    name_b=os.path.basename(args.diff[1]),
                    show_all=args.all, grep=args.grep)
        return 0

    if args.live == (args.snapshot is not None):
        ap.error("pass exactly one of: a snapshot path, or --live")

    if args.live:
        from paddle_tpu import observe

        snap = observe.snapshot()
    else:
        snap = _load_snapshot(args.snapshot, ap)

    if args.prometheus:
        if args.grep:
            ap.error("--grep composes with the table/--diff renderers, "
                     "not --prometheus (exposition format is all-series)")
        # Registry.render_prometheus renders from any saved snapshot dict
        from paddle_tpu.observe.metrics import Registry

        sys.stdout.write(Registry().render_prometheus(snap))
    else:
        render_table(snap, show_all=args.all, grep=args.grep)
    return 0


if __name__ == "__main__":
    sys.exit(main())
