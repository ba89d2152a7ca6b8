"""Profiler: the Fluid session API over the program's one span type.

Analog of the reference profiling stack (SURVEY §5):
* `RecordEvent` RAII markers — platform/profiler.h:81. Here a
  `RecordEvent(name)` IS an `observe.trace` span named `name`: it lands
  in the flight recorder's ring beside the program's own spans
  (`executor.call` and its children, `serving.engine.step`, ...) and, as
  every span does, in a running `jax.profiler` trace.
* `EnableProfiler/DisableProfiler` + aggregated event tables —
  platform/profiler.cc (calls / total / min / max / avg per event key).
  A session keeps no event list of its own: `start_profiler` notes the
  time, `stop_profiler` aggregates the spans the ring recorded since.
* chrome://tracing JSON — `observe.trace.export_chrome_trace`.
* device side — DeviceTracer hooked CUPTI; the XLA/TPU analog is
  jax.profiler's trace (TensorBoard/Perfetto), started with the session
  when state includes the device.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional

from .observe import trace as _trace

__all__ = ["profiler", "start_profiler", "stop_profiler", "reset_profiler",
           "cuda_profiler", "RecordEvent", "is_profiler_enabled"]

_mark: Optional[float] = None  # perf_counter at session start; None = off
_xla_trace = False


def is_profiler_enabled() -> bool:
    return _mark is not None


def start_profiler(state: str = "All",
                   trace_dir: str = "/tmp/paddle_tpu_trace"):
    """EnableProfiler analog (profiler.h:166). state: CPU|GPU|All — GPU/All
    also starts the XLA device trace (DeviceTracer/CUPTI analog)."""
    global _mark, _xla_trace
    if _mark is not None:
        return
    _mark = time.perf_counter()
    if state in ("GPU", "All"):
        import jax

        try:
            jax.profiler.start_trace(trace_dir)
            _xla_trace = True
        except Exception:
            _xla_trace = False


def stop_profiler(sorted_key: Optional[str] = None,
                  profile_path: Optional[str] = None):
    """DisableProfiler analog: stop traces, print the aggregated table of
    the spans recorded since `start_profiler`, optionally dump the ring as
    chrome://tracing JSON to profile_path."""
    global _mark, _xla_trace
    if _mark is None:
        return
    mark, _mark = _mark, None
    if _xla_trace:
        import jax

        try:
            jax.profiler.stop_trace()
        finally:
            _xla_trace = False
    _print_table(mark, sorted_key)
    if profile_path:
        _trace.export_chrome_trace(profile_path)


def reset_profiler():
    """Drop what the running session has seen so far: its table starts
    again from now."""
    global _mark
    if _mark is not None:
        _mark = time.perf_counter()


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: Optional[str] = None,
             profile_path: Optional[str] = None,
             trace_dir: str = "/tmp/paddle_tpu_trace"):
    """Context manager (python/paddle/fluid/profiler.py:39 analog)."""
    start_profiler(state, trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(*a, **kw):  # name kept for porting ease; maps to XLA trace
    with profiler():
        yield


class RecordEvent:
    """RAII marker (platform/profiler.h:81): `observe.trace.trace_span`
    under its Fluid name."""

    def __init__(self, name: str):
        self.name = name
        self._span = None

    def __enter__(self):
        self._span = _trace.trace_span(self.name)
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        span, self._span = self._span, None
        return span.__exit__(*exc)


def record_event(name: str) -> RecordEvent:
    return RecordEvent(name)


# ---------------------------------------------------------------- reporting
_SORT_KEYS = {
    "calls": lambda r: -r[1],
    "total": lambda r: -r[2],
    "ave": lambda r: -r[3],
    "min": lambda r: r[4],
    "max": lambda r: -r[5],
}


def _print_table(mark: float, sorted_key=None):
    print("-------------------------  Profiling Report  "
          "-------------------------")
    if not _trace.trace_enabled():
        print("tracing is off (%s=0): no span was recorded"
              % _trace.ENV_TRACE)
        return
    events = _trace.RECORDER.events()
    if len(events) == _trace.RECORDER.capacity and events[0]["t"] > mark:
        print("the ring holds the last %d events (%s): the session's "
              "earlier spans are not counted"
              % (len(events), _trace.ENV_EVENTS))
    agg: Dict[str, List[float]] = {}
    for e in events:
        if e["ph"] == "E" and e["t"] - e["dur"] >= mark:
            agg.setdefault(e["site"], []).append(e["dur"] * 1e6)
    rows = [(name, len(ds), sum(ds), sum(ds) / len(ds), min(ds), max(ds))
            for name, ds in agg.items()]
    rows.sort(key=_SORT_KEYS.get(sorted_key, _SORT_KEYS["total"]))
    print("%-40s %8s %12s %12s %12s %12s" %
          ("Event", "Calls", "Total(us)", "Avg(us)", "Min(us)", "Max(us)"))
    for name, calls, total, avg, mn, mx in rows:
        print("%-40s %8d %12.1f %12.1f %12.1f %12.1f" %
              (name[:40], calls, total, avg, mn, mx))
