"""Thread-safe metrics registry: Counter / Gauge / Histogram families.

The runtime-telemetry substrate SURVEY §5 only partially covers: the
reference ships RecordEvent markers + aggregated event tables
(platform/profiler.cc) but no counters/gauges/histograms, so a wedged
run leaves no trail of *how far it got*. This registry is the missing
half: cheap process-wide metrics every hot subsystem (executor, RPC,
parallel engine, readers) writes unconditionally, exported as a JSON
snapshot (`snapshot()`) or Prometheus text exposition format
(`render_prometheus()`).

Design notes
* One process-wide `Registry` (module singleton in observe/__init__);
  families are idempotently declared — re-declaring with the same type
  returns the existing family, so module reloads and multiple import
  paths never double-register.
* Histograms use FIXED log-scale buckets (1-2-5 per decade, 1e-6..1e3)
  so two snapshots are always mergeable/diffable — no per-process
  adaptive boundaries.
* All mutation goes through one re-entrant lock. The hot-path cost is
  a dict lookup + float add under an uncontended lock — noise next to
  an XLA dispatch (µs vs ms), which is what lets the instrumentation
  stay ON even in benchmark runs.
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["Counter", "Gauge", "Histogram", "Family", "Registry",
           "DEFAULT_BUCKETS", "quantile_from_buckets"]

# 1-2-5 per decade, 1e-6 .. 1e3 (seconds-flavored but unit-agnostic:
# byte-sized values simply land in +Inf's lower neighbors)
DEFAULT_BUCKETS: Tuple[float, ...] = tuple(
    round(m * 10.0 ** e, 12)
    for e in range(-6, 4)
    for m in (1.0, 2.0, 5.0)
)

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")


def quantile_from_buckets(buckets, count, q):
    """Estimate quantile ``q`` from cumulative ``{le: count}`` buckets
    (prometheus-style linear interpolation within the winning bucket;
    the open-ended +Inf bucket reports its lower edge).

    THE shared percentile implementation: ``Histogram.quantile``,
    ``observe/slo.py`` and ``tools/stats_dump.py`` all route through
    this one function so a p99 means the same thing everywhere it is
    printed."""
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


def _escape_label_value(v: str) -> str:
    return v.replace("\\", r"\\").replace("\n", r"\n").replace('"', r"\"")


def _fmt(v: float) -> str:
    """Prometheus-friendly float: integers render without the .0 tail."""
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


class _Child:
    """One (family, label-values) time series."""

    def __init__(self, family: "Family", label_values: Tuple[str, ...]):
        self._family = family
        self._lock = family._registry._lock
        self.label_values = label_values


class Counter(_Child):
    def __init__(self, family, label_values):
        super().__init__(family, label_values)
        self._value = 0.0

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError("counters only go up; got %r" % (amount,))
        with self._lock:
            self._value += amount

    @property
    def value(self) -> float:
        with self._lock:
            return self._value

    def _reset(self):
        self._value = 0.0


class Gauge(_Child):
    def __init__(self, family, label_values):
        super().__init__(family, label_values)
        self._value = 0.0

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self._value += amount

    def dec(self, amount: float = 1.0) -> None:
        with self._lock:
            self._value -= amount

    @property
    def value(self) -> float:
        with self._lock:
            return self._value

    def _reset(self):
        self._value = 0.0


class Histogram(_Child):
    def __init__(self, family, label_values):
        super().__init__(family, label_values)
        self._bounds = family.buckets
        self._counts = [0] * (len(self._bounds) + 1)  # last = +Inf
        self._sum = 0.0
        self._count = 0

    def observe(self, value: float) -> None:
        value = float(value)
        # binary search is overkill for ~30 buckets; linear scan stays
        # cache-friendly and branch-predictable
        i = 0
        bounds = self._bounds
        n = len(bounds)
        while i < n and value > bounds[i]:
            i += 1
        with self._lock:
            self._counts[i] += 1
            self._sum += value
            self._count += 1

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    def cumulative_buckets(self) -> List[Tuple[str, int]]:
        """[(le_string, cumulative_count)] including the +Inf bucket."""
        with self._lock:
            counts = list(self._counts)
        out, acc = [], 0
        for bound, c in zip(self._bounds, counts):
            acc += c
            out.append((_fmt(bound), acc))
        out.append(("+Inf", acc + counts[-1]))
        return out

    def quantile(self, q: float) -> Optional[float]:
        """Estimated quantile from the fixed bucket boundaries (None
        while empty). Resolution is bucket-width-bounded: with the
        1-2-5/decade defaults the estimate lands within the true
        value's bucket."""
        if not 0.0 <= q <= 1.0:
            raise ValueError("quantile must be in [0, 1]; got %r" % (q,))
        return quantile_from_buckets(dict(self.cumulative_buckets()),
                                     self.count, q)

    def _reset(self):
        self._counts = [0] * (len(self._bounds) + 1)
        self._sum = 0.0
        self._count = 0


_KIND_OF = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}


class Family:
    """A named metric with a fixed label schema; children are the
    per-label-value time series (prometheus client_model analog)."""

    def __init__(self, registry: "Registry", name: str, kind: str,
                 help: str = "", labelnames: Sequence[str] = (),
                 buckets: Optional[Sequence[float]] = None):
        if not _NAME_RE.match(name):
            raise ValueError("invalid metric name %r" % name)
        for ln in labelnames:
            if not _LABEL_RE.match(ln):
                raise ValueError("invalid label name %r" % ln)
        self._registry = registry
        self.name = name
        self.kind = kind
        self.help = help
        self.labelnames = tuple(labelnames)
        self.buckets = tuple(sorted(buckets)) if buckets is not None \
            else DEFAULT_BUCKETS
        self._children: Dict[Tuple[str, ...], _Child] = {}
        if not self.labelnames:
            self.labels()  # materialize the single unlabeled series

    def labels(self, *values, **kv) -> _Child:
        if kv:
            if values:
                raise ValueError("pass label values positionally OR by "
                                 "keyword, not both")
            try:
                values = tuple(str(kv[ln]) for ln in self.labelnames)
            except KeyError as e:
                raise ValueError(
                    "missing label %s for metric %r (schema %s)"
                    % (e, self.name, self.labelnames)) from None
            extra = set(kv) - set(self.labelnames)
            if extra:
                raise ValueError("unknown labels %s for metric %r"
                                 % (sorted(extra), self.name))
        else:
            values = tuple(str(v) for v in values)
        if len(values) != len(self.labelnames):
            raise ValueError(
                "metric %r takes labels %s; got %d values"
                % (self.name, self.labelnames, len(values)))
        with self._registry._lock:
            child = self._children.get(values)
            if child is None:
                child = _KIND_OF[self.kind](self, values)
                self._children[values] = child
            return child

    # unlabeled-family convenience: family.inc()/set()/observe() hit the
    # default child, so call sites read like plain metrics
    def inc(self, amount: float = 1.0):
        self.labels().inc(amount)

    def set(self, value: float):
        self.labels().set(value)

    def dec(self, amount: float = 1.0):
        self.labels().dec(amount)

    def observe(self, value: float):
        self.labels().observe(value)

    def quantile(self, q: float):
        return self.labels().quantile(q)

    @property
    def value(self):
        return self.labels().value

    def _label_str(self, values: Tuple[str, ...]) -> str:
        return ",".join('%s="%s"' % (n, _escape_label_value(v))
                        for n, v in zip(self.labelnames, values))


class Registry:
    def __init__(self):
        self._lock = threading.RLock()
        self._families: Dict[str, Family] = {}

    # ------------------------------------------------------------ declare
    def _declare(self, name, kind, help, labels, buckets=None) -> Family:
        with self._lock:
            fam = self._families.get(name)
            if fam is not None:
                if fam.kind != kind or fam.labelnames != tuple(labels):
                    raise ValueError(
                        "metric %r already declared as %s%s" %
                        (name, fam.kind, fam.labelnames))
                if buckets is not None and \
                        tuple(sorted(buckets)) != fam.buckets:
                    # silently handing back the old bounds would bucket
                    # the new call site's observations wrong
                    raise ValueError(
                        "histogram %r already declared with buckets %s"
                        % (name, fam.buckets))
                return fam
            fam = Family(self, name, kind, help, labels, buckets)
            self._families[name] = fam
            return fam

    def counter(self, name: str, help: str = "",
                labels: Sequence[str] = ()) -> Family:
        return self._declare(name, "counter", help, labels)

    def gauge(self, name: str, help: str = "",
              labels: Sequence[str] = ()) -> Family:
        return self._declare(name, "gauge", help, labels)

    def histogram(self, name: str, help: str = "",
                  labels: Sequence[str] = (),
                  buckets: Optional[Sequence[float]] = None) -> Family:
        return self._declare(name, "histogram", help, labels, buckets)

    def get(self, name: str) -> Optional[Family]:
        with self._lock:
            return self._families.get(name)

    # ------------------------------------------------------------- export
    def snapshot(self) -> dict:
        """JSON-serializable dict of every family + child. Histograms
        export CUMULATIVE bucket counts (prometheus semantics), so a
        saved snapshot renders identically to a live one."""
        with self._lock:
            families = list(self._families.values())
        metrics = {}
        for fam in families:
            with self._lock:
                children = dict(fam._children)
            samples = []
            for values, child in sorted(children.items()):
                lbl = dict(zip(fam.labelnames, values))
                if fam.kind == "histogram":
                    samples.append({
                        "labels": lbl,
                        "sum": child.sum,
                        "count": child.count,
                        "buckets": dict(child.cumulative_buckets()),
                    })
                else:
                    samples.append({"labels": lbl, "value": child.value})
            metrics[fam.name] = {
                "type": fam.kind,
                "help": fam.help,
                "labelnames": list(fam.labelnames),
                "samples": samples,
            }
        return {
            "version": 1,
            "pid": os.getpid(),
            "unix_time": time.time(),
            "metrics": metrics,
        }

    def render_prometheus(self, snap: Optional[dict] = None) -> str:
        """Text exposition format (the /metrics wire format). Renders the
        live registry, or a previously saved `snapshot()` dict."""
        snap = snap if snap is not None else self.snapshot()
        lines: List[str] = []
        for name in sorted(snap["metrics"]):
            m = snap["metrics"][name]
            if m["help"]:
                lines.append("# HELP %s %s" % (
                    name, m["help"].replace("\\", r"\\").replace("\n", r"\n")))
            lines.append("# TYPE %s %s" % (name, m["type"]))
            # label order follows the declared schema, not the sample
            # dict: a JSON round-trip (dump writes sort_keys=True) must
            # render byte-identically to the live registry
            lnames = m.get("labelnames") or []
            for s in m["samples"]:
                order = [k for k in lnames if k in s["labels"]] + \
                    [k for k in s["labels"] if k not in lnames]
                lbl = ",".join('%s="%s"'
                               % (k, _escape_label_value(str(s["labels"][k])))
                               for k in order)
                if m["type"] == "histogram":
                    for le, c in _bucket_items(s["buckets"]):
                        blbl = (lbl + "," if lbl else "") + 'le="%s"' % le
                        lines.append("%s_bucket{%s} %s" % (name, blbl,
                                                           _fmt(c)))
                    suffix = "{%s}" % lbl if lbl else ""
                    lines.append("%s_sum%s %s" % (name, suffix,
                                                  _fmt(s["sum"])))
                    lines.append("%s_count%s %s" % (name, suffix,
                                                    _fmt(s["count"])))
                else:
                    suffix = "{%s}" % lbl if lbl else ""
                    lines.append("%s%s %s" % (name, suffix,
                                              _fmt(s["value"])))
        return "\n".join(lines) + "\n"

    def dump(self, path: str) -> dict:
        """Atomically write `snapshot()` as JSON to `path`; returns it."""
        snap = self.snapshot()
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        # pid+tid: concurrent dumps of the same path (e.g. a watchdog
        # thread racing the main thread's final dump) never share a tmp
        tmp = os.path.join(d, ".%s.tmp.%d.%d" % (
            os.path.basename(path), os.getpid(), threading.get_ident()))
        with open(tmp, "w") as f:
            json.dump(snap, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        return snap

    def reset(self) -> None:
        """Zero every child (families and label schemas survive) — test
        isolation, not a public runtime operation."""
        with self._lock:
            for fam in self._families.values():
                for child in fam._children.values():
                    child._reset()


def _bucket_items(buckets: dict) -> List[Tuple[str, float]]:
    """Sort bucket dict by numeric bound, +Inf last (JSON round-trips
    dicts in insertion order, but don't rely on it)."""
    items = [(k, v) for k, v in buckets.items() if k != "+Inf"]
    items.sort(key=lambda kv: float(kv[0]))
    if "+Inf" in buckets:
        items.append(("+Inf", buckets["+Inf"]))
    return items
