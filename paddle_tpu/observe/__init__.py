"""observe: process-wide runtime telemetry (metrics registry + spans).

The observability layer SURVEY §5's host-profiler only half covers:
``profiler.py`` answers "where did the time go" during an explicitly
started profiling session; this package answers "what has the process
done so far" at ANY moment — counters/gauges/histograms every hot
subsystem updates unconditionally, plus span tracing (``trace_span``:
the flight recorder's ring, and the host plane of any ``jax.profiler``
trace being taken).

    from paddle_tpu import observe

    observe.snapshot()            # JSON-able dict of every metric
    observe.render_prometheus()   # text exposition format
    observe.dump(path)            # atomic JSON snapshot to disk

    C = observe.counter("my_events_total", "what it counts")
    C.inc()
    with observe.trace_span("executor.call"):
        ...                       # a declared site (families.TRACE_SITES)

`tools/stats_dump.py` pretty-prints a live or saved snapshot. See
docs/OBSERVABILITY.md.
"""

from __future__ import annotations

from . import families  # noqa: F401  (declares the well-known families)
from . import trace  # noqa: F401  (trace contexts + flight recorder)
from .export import (MetricsExporter, active_exporter,  # noqa: F401
                     default_instance, start_from_env, stop_exporter)
from .families import REGISTRY
from .fleet import FleetCollector, TelemetryPusher  # noqa: F401
from .metrics import (Counter, DEFAULT_BUCKETS, Family, Gauge,  # noqa: F401
                      Histogram, Registry, quantile_from_buckets)
from .promparse import ParseError, parse_prometheus  # noqa: F401
from .shutdown import (install_shutdown_handlers,  # noqa: F401
                       uninstall_shutdown_handlers)
from .slo import Breach, Objective, SloMonitor  # noqa: F401
from .spans import mark_batch_produced, observe_feed_gap  # noqa: F401
from .timeseries import Ewma, TimeSeriesStore  # noqa: F401
from .trace import (FlightRecorder, TraceContext, attach,  # noqa: F401
                    current, dump_flight_recorder, export_chrome_trace,
                    new_trace, record_span, trace_enabled, trace_event,
                    trace_span)

__all__ = ["REGISTRY", "counter", "gauge", "histogram", "get_metric",
           "snapshot", "render_prometheus", "dump", "reset",
           "mark_batch_produced", "observe_feed_gap",
           "Counter", "Gauge", "Histogram", "Family", "Registry",
           "DEFAULT_BUCKETS", "quantile_from_buckets",
           "TraceContext", "FlightRecorder", "trace_enabled", "new_trace",
           "current", "attach", "trace_span", "trace_event", "record_span",
           "dump_flight_recorder", "export_chrome_trace",
           # the live telemetry plane (export/timeseries/fleet/slo/
           # promparse/shutdown — docs/OBSERVABILITY.md "Fleet
           # telemetry plane")
           "MetricsExporter", "active_exporter", "start_from_env",
           "stop_exporter", "default_instance",
           "Ewma", "TimeSeriesStore",
           "FleetCollector", "TelemetryPusher",
           "SloMonitor", "Objective", "Breach",
           "parse_prometheus", "ParseError",
           "install_shutdown_handlers", "uninstall_shutdown_handlers"]

# module-level facade over the process-wide registry
counter = REGISTRY.counter
gauge = REGISTRY.gauge
histogram = REGISTRY.histogram
get_metric = REGISTRY.get
snapshot = REGISTRY.snapshot
render_prometheus = REGISTRY.render_prometheus
dump = REGISTRY.dump


def reset():
    """Zero every metric AND the cross-subsystem span state (the pending
    feed-to-run stamp, the flight-recorder ring, this thread's trace
    context) — full test isolation, not a runtime operation."""
    from . import spans as _spans

    REGISTRY.reset()
    _spans._clear_batch_stamp()
    trace._reset()
