#!/usr/bin/env python
"""fleet_top: live terminal dashboard over a fleet's metric exporters.

Scrapes one or more MetricsExporter endpoints (``/metrics``, parsed by
observe/promparse.py via ``FleetCollector.scrape``) on an interval and
renders one row per instance:

    instance        state  steps/s  tok/s  queue  slots  headroom

* steps/s  — windowed rate of ``paddle_executor_steps_total``
* tok/s    — windowed rate of ``paddle_serving_tokens_total``
* queue    — ``paddle_serving_queue_depth``
* slots    — ``paddle_serving_slots_active``
* headroom — ``paddle_serving_memory_headroom_bytes`` (the engine
  admission guard's budget-minus-predicted signal)
* state    — live/stale under the collector's lease, or unreachable

``--slo NAME=EXPR`` declares objectives (observe/slo.py grammar)
evaluated against the aggregated fleet snapshot each tick; breaches
print in the SLO footer. ``--once --json`` emits a single machine-
readable sample for CI (no loop, no screen control).

Usage::

    python tools/fleet_top.py 127.0.0.1:9464 127.0.0.1:9465
    python tools/fleet_top.py --port-file /tmp/t0.port --interval 2
    python tools/fleet_top.py 127.0.0.1:9464 --once --json \
        --slo 'p99_dispatch=p99(paddle_executor_run_seconds{site=run,phase=dispatch}) < 0.1'
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

# the metric names behind each dashboard column
STEPS = "paddle_executor_steps_total"
TOKENS = "paddle_serving_tokens_total"
QUEUE = "paddle_serving_queue_depth"
SLOTS = "paddle_serving_slots_active"
HEADROOM = "paddle_serving_memory_headroom_bytes"


def _value(snap, name):
    """Sum of a scalar family's samples in one instance snapshot
    (None when the family is absent)."""
    m = snap["metrics"].get(name)
    if m is None or not m["samples"]:
        return None
    return sum(s.get("value", s.get("count", 0.0)) for s in m["samples"])


def _fmt(v, nd=1):
    if v is None:
        return "-"
    if isinstance(v, float):
        if abs(v) >= 1 << 20:  # byte-sized values: render in MiB
            return "%.0fM" % (v / (1 << 20))
        return "%.*f" % (nd, v)
    return str(v)


class FleetTop:
    """One scrape-and-render engine; the CLI loops it."""

    def __init__(self, endpoints, lease_s=10.0, window_s=30.0,
                 slos=None):
        from paddle_tpu.observe import (FleetCollector, SloMonitor,
                                        TimeSeriesStore)

        self.endpoints = list(endpoints)
        self.fc = FleetCollector(lease_s=lease_s)
        # one ring store PER INSTANCE: series keys carry no instance
        # label, so a shared store would garble cross-instance rates
        self._mk_store = lambda: TimeSeriesStore(
            capacity=max(64, int(window_s * 4)))
        self.ts = {}
        self.window_s = float(window_s)
        self.unreachable = set()
        self.mon = SloMonitor(source=self.fc.fleet_snapshot)
        for name, expr in (slos or []):
            self.mon.objective(name, expr)
        self.last_breaches = []

    def _rate(self, store, snap, name):
        """Windowed per-second rate of a counter (None when absent)."""
        from paddle_tpu.observe.timeseries import series_key

        m = snap["metrics"].get(name)
        if not m:
            return None
        return store.rate(series_key(name, m["samples"][0]["labels"]),
                          window_s=self.window_s)

    def tick(self):
        """One scrape round; returns the row dicts."""
        for ep in self.endpoints:
            try:
                self.fc.scrape(ep)
                self.unreachable.discard(ep)
            except OSError:
                self.unreachable.add(ep)
        self.fc.sweep()
        rows = []
        for inst, meta in self.fc.instances().items():
            snap = self.fc.instance_snapshot(inst)
            store = self.ts.get(inst)
            if store is None:
                store = self.ts[inst] = self._mk_store()
            store.sample(snap=snap)
            rows.append({
                "instance": inst,
                "state": ("unreachable" if inst in self.unreachable
                          else "stale" if meta["stale"] else "live"),
                "steps_per_sec": self._rate(store, snap, STEPS),
                "tokens_per_sec": self._rate(store, snap, TOKENS),
                "queue_depth": _value(snap, QUEUE),
                "slots_active": _value(snap, SLOTS),
                "headroom_bytes": _value(snap, HEADROOM),
            })
        self.last_breaches = self.mon.evaluate()
        return rows

    def render(self, rows, out=sys.stdout):
        cols = ("instance", "state", "steps/s", "tok/s", "queue",
                "slots", "headroom")
        w = max([len("instance")] + [len(r["instance"]) for r in rows])
        print("%-*s %-11s %8s %8s %6s %6s %9s" % ((w,) + cols),
              file=out)
        for r in rows:
            print("%-*s %-11s %8s %8s %6s %6s %9s"
                  % (w, r["instance"], r["state"],
                     _fmt(r["steps_per_sec"], 2),
                     _fmt(r["tokens_per_sec"]),
                     _fmt(r["queue_depth"], 0),
                     _fmt(r["slots_active"], 0),
                     _fmt(r["headroom_bytes"])), file=out)
        if self.mon._objectives:
            if self.last_breaches:
                for b in self.last_breaches:
                    print("SLO BREACH %s: measured %.6g against %r"
                          % (b.objective, b.value, b.expr), file=out)
            else:
                print("SLO ok (%d objective(s))"
                      % len(self.mon._objectives), file=out)

    def close(self):
        self.fc.close()


def _parse_slo(text):
    name, eq, expr = text.partition("=")
    if not eq or not name.strip() or not expr.strip():
        raise argparse.ArgumentTypeError(
            "--slo takes NAME=EXPR (observe/slo.py grammar)")
    return name.strip(), expr.strip()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="live terminal dashboard over fleet exporters")
    ap.add_argument("endpoints", nargs="*",
                    help="exporter host:port targets")
    ap.add_argument("--port-file", action="append", default=[],
                    help="read an endpoint from an exporter port file "
                         "(PADDLE_TPU_METRICS_PORT_FILE rendezvous); "
                         "repeatable")
    ap.add_argument("--interval", type=float, default=2.0)
    ap.add_argument("--count", type=int, default=None,
                    help="stop after N ticks (default: forever)")
    ap.add_argument("--once", action="store_true",
                    help="one tick, then exit (CI mode)")
    ap.add_argument("--json", action="store_true",
                    help="emit rows as JSON instead of the table")
    ap.add_argument("--lease-s", type=float, default=10.0,
                    help="stale-instance lease (seconds)")
    ap.add_argument("--window-s", type=float, default=30.0,
                    help="rate window (seconds)")
    ap.add_argument("--slo", action="append", type=_parse_slo,
                    default=[], metavar="NAME=EXPR",
                    help="declare an objective, e.g. "
                         "'p99=p99(paddle_serving_request_seconds)"
                         " < 0.25'; repeatable")
    args = ap.parse_args(argv)

    endpoints = list(args.endpoints)
    for pf in args.port_file:
        with open(pf) as f:
            endpoints.append(f.read().strip())
    if not endpoints:
        ap.error("no endpoints (pass host:port or --port-file)")

    top = FleetTop(endpoints, lease_s=args.lease_s,
                   window_s=args.window_s, slos=args.slo)
    ticks = 1 if args.once else args.count
    n = 0
    try:
        while True:
            rows = top.tick()
            if args.json:
                print(json.dumps({
                    "unix_time": time.time(),
                    "rows": rows,
                    "breaches": [
                        {"objective": b.objective, "expr": b.expr,
                         "value": b.value, "threshold": b.threshold}
                        for b in top.last_breaches],
                }, default=float), flush=True)
            else:
                print("fleet_top  %s  (%d endpoint(s), %d unreachable)"
                      % (time.strftime("%H:%M:%S"), len(endpoints),
                         len(top.unreachable)))
                top.render(rows)
                print(flush=True)
            n += 1
            if ticks is not None and n >= ticks:
                return 0
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0
    finally:
        top.close()


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
