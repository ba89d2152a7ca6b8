#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json and print its metrics as the last line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell names a configuration (``benchmarks/configs/``), a traffic mix
(``benchmarks/traffic/``) whose ``kind`` names the runner
(``benchmarks/kinds/``), and the chips it needs. ``--trace 0`` prints the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics, each read
by its own file under ``benchmarks/layer_metrics/``.

This is a measurement: with no TPU, or another number of chips than the
cell asks for, it exits non-zero and prints no result. ``--cpu-rehearsal``
is the explicit other thing: tiny sizes on any backend to check the
control flow; it prints a result line without a single metric in it.
"""

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib.manifest import Manifest, ManifestError  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class Context:
    """What a runner kind gets: the cell's files, the clock marks of
    set-up and window, the compile monitor and the profiler."""

    def __init__(self, args, manifest, cell, devices, monitor):
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearsal = args.cpu_rehearsal
        self.manifest = manifest
        self.cell = cell
        self.chips = cell["chips"]
        self.config = manifest.config(cell["config"])
        self.traffic = manifest.traffic(cell["traffic"])
        self.devices = devices
        self.monitor = monitor
        self.t_start = T_START
        self.t_open = self.t_close = None
        self._window_snap = None
        self.setup_counts = self.window_counts = None
        self.trace_path = None
        self.trace_host_t0 = None
        self.keep_trace = args.keep_trace

    # -------------------------------------------------------------- window
    def open_window(self):
        """Set-up ends here: everything before counts as ``setup_s``."""
        self.setup_counts = self.monitor.snapshot()
        self._window_snap = self.monitor.snapshot()
        self.t_open = time.perf_counter()
        return self.t_open

    def close_window(self):
        self.t_close = time.perf_counter()
        self.window_counts = self.monitor.since(self._window_snap)
        return self.t_close

    # ------------------------------------------------------------ profiler
    def annotate(self, name, **kw):
        """A host span on the profiler's clock, by which an idle gap of
        the device is named; nothing when no trace is being taken."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench." + name, **kw)

    @contextlib.contextmanager
    def traced(self):
        """Profile the ``with`` body into a fresh directory and wrap it
        in the ``bench.window`` annotation the reduction looks for."""
        if not self.trace:
            yield
            return
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # no Python-level tracing: it slows the host it is measuring and
        # swells the file; TraceAnnotations are kept
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                self.trace_host_t0 = time.perf_counter()
                yield
        finally:
            jax.profiler.stop_trace()
        from benchmarks.lib import xplane

        self.trace_path = xplane.find_xplane(TRACE_DIR)

    def flight_spans(self, prefixes):
        """``(site, start, dur)`` of the program's flight-recorder spans
        that ended inside the window, on the host's ``perf_counter``."""
        from paddle_tpu.observe import trace as flight

        return [(ev["site"], ev["t"] - ev["dur"], ev["dur"])
                for ev in flight.recorder().events()
                if ev["ph"] == "E" and ev["site"].startswith(prefixes)
                and self.t_open <= ev["t"] <= self.t_close]

    def reduce_trace(self, host_spans=()):
        """The reduced trace, or None where none was taken. A rehearsal's
        trace has no TPU plane and reduces to None too. ``host_spans``
        (from ``flight_spans``) help to name the device's idle gaps."""
        if self.trace_path is None:
            return None
        from benchmarks.lib import xplane

        if self.keep_trace:
            os.makedirs(self.keep_trace, exist_ok=True)
            shutil.copy(self.trace_path, self.keep_trace)
        try:
            return xplane.reduce(self.trace_path, host_spans=host_spans,
                                 host_t0=self.trace_host_t0)
        except ValueError:
            if self.rehearsal:
                return None
            raise
        finally:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)


def _devices(chips, rehearsal):
    import jax

    devs = jax.devices()
    if rehearsal:
        if len(devs) < chips:
            raise SystemExit("rehearsal: the cell asks for %d devices, JAX "
                             "has %d" % (chips, len(devs)))
        return devs[:chips]
    if devs[0].platform != "tpu":
        raise SystemExit(
            "benchmarks/run.py: JAX found no TPU (platform %r). This is "
            "a measurement and has no CPU fallback; --cpu-rehearsal checks "
            "the control flow and reports no metric." % devs[0].platform)
    if len(devs) != chips:
        raise SystemExit("benchmarks/run.py: the cell asks for %d chip(s), "
                         "JAX reports %d" % (chips, len(devs)))
    return devs


def _enable_cache():
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` where set,
    else ``<checkout>/.jax_cache`` (the program's own entry-point helper
    decides), and every program in it, however quick its compile."""
    import jax

    from paddle_tpu.flags import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def _memory_peak(devices):
    """(peak bytes on the fullest chip, the counters it was made from).

    The runtime keeps two counters a chip: ``peak_bytes_in_use`` for the
    buffers (weights, optimizer state, caches, feeds) and
    ``peak_bytes_reserved`` for what running programs reserve for their
    temporaries. On the v5e the first leaves the temporaries out — BERT-
    base training reads 1.9 GB there, under its activations alone, and
    5.0 GB in the second — so the peak is their sum."""
    best, counters = None, None
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" not in stats:
            continue
        peak = stats["peak_bytes_in_use"] \
            + stats.get("peak_bytes_reserved", 0)
        if best is None or peak > best:
            best = peak
            counters = {k: stats.get(k) for k in (
                "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")}
    return best, counters


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on any backend: control flow only, "
                         "no metric is printed")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the .xplane.pb of a --trace 1 run to DIR")
    ap.add_argument("--manifest", default=None,
                    help="another BENCHMARK.json (the tests' tiny one)")
    args = ap.parse_args(argv)

    manifest = Manifest(args.manifest)
    cell = manifest.cell(args.workload)
    traffic_kind = manifest.traffic(cell["traffic"])["kind"]
    kind = manifest.load_module("kinds", traffic_kind)

    devices = _devices(cell["chips"], args.cpu_rehearsal)
    _enable_cache()
    from benchmarks.lib.jaxmon import JaxMonitor

    ctx = Context(args, manifest, cell, devices, JaxMonitor())
    record = kind.run(ctx)

    if ctx.t_open is None or ctx.t_close is None:
        raise SystemExit("kind %r never opened and closed its window"
                         % traffic_kind)
    record["setup_s"] = ctx.t_open - ctx.t_start
    record["setup_counts"] = ctx.setup_counts
    record["window_counts"] = ctx.window_counts
    record["memory_peak_bytes"], record["memory_sources"] = \
        _memory_peak(devices)
    record["device_kind"] = devices[0].device_kind
    record["chips"] = cell["chips"]
    if ctx.window_counts["backend_compiles"]:
        record["correct"] = False
        record.setdefault("why_not", []).append(
            "%d compilation(s) inside the measured window"
            % ctx.window_counts["backend_compiles"])

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    metrics = {}
    if args.trace:
        trace = record.get("trace")
        if trace is not None:
            device["busy_s"] = trace["busy_mean_s"]
            device["window_s"] = trace["window_s"]
        for m in manifest.metrics_for("per_layer", cell["name"]):
            reader = manifest.load_module("layer_metrics", m["name"])
            value = reader.read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(record["end_to_end"], setup_s=record["setup_s"])
        for m in manifest.metrics_for("end_to_end", cell["name"]):
            if m["name"] not in values:
                raise SystemExit("cell %s did not measure %s"
                                 % (cell["name"], m["name"]))
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    print("memory: %r" % (record["memory_sources"],), file=sys.stderr)
    print("facts: %s" % json.dumps(record.get("facts", {})), file=sys.stderr)
    if record.get("why_not"):
        print("NOT CORRECT: " + "; ".join(record["why_not"]),
              file=sys.stderr)
    last = {"correct": bool(record["correct"]),
            "attempted": record["attempted"], "failed": record["failed"],
            "metrics": metrics, "device": device}
    if args.cpu_rehearsal:
        # a CPU number is never written under a device metric's name:
        # the names it would have reported go on a line of their own
        print(json.dumps({"rehearsal": "passed",
                          "would_report": sorted(metrics),
                          "facts": record.get("facts", {})}))
        last["metrics"] = {}
    elif args.trace and record.get("trace") is not None:
        last["breakdown"] = record["trace"]["breakdown"]
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except ManifestError as e:
        sys.exit("benchmarks/run.py: %s" % e)
