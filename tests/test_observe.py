"""Telemetry-layer tests (observe/): registry semantics under threads,
label families, snapshot/prometheus round-trip, executor cache metrics,
RPC retry/deadline counters via the in-process RPC harness, span/profiler
composition, and the dumped snapshot + stats_dump CLI."""

import json
import os
import re
import subprocess
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observe

ROOT = os.path.join(os.path.dirname(__file__), os.pardir)
STATS_DUMP = os.path.join(ROOT, "tools", "stats_dump.py")


# --------------------------------------------------------------- registry
def test_counter_gauge_histogram_under_threads():
    reg = observe.Registry()
    c = reg.counter("t_c_total", "threaded counter")
    g = reg.gauge("t_g", "threaded gauge")
    h = reg.histogram("t_h_seconds", "threaded histogram")
    N, T = 1000, 8

    def work():
        for i in range(N):
            c.inc()
            g.inc()
            h.observe(i * 1e-3)

    ts = [threading.Thread(target=work) for _ in range(T)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # exact totals: increments are lock-protected, no lost updates
    assert c.value == N * T
    assert g.value == N * T
    assert h.labels().count == N * T
    assert abs(h.labels().sum - T * sum(i * 1e-3 for i in range(N))) < 1e-6

    with pytest.raises(ValueError):
        c.labels().inc(-1)  # counters only go up


def test_label_families():
    reg = observe.Registry()
    f = reg.counter("t_reqs_total", "labeled", labels=("method", "code"))
    f.labels(method="get", code="200").inc()
    f.labels("get", "500").inc(2)
    f.labels(method="get", code="200").inc()  # same child again
    with pytest.raises(ValueError):
        f.labels(method="get")  # missing label
    with pytest.raises(ValueError):
        f.labels(method="get", code="1", extra="x")  # unknown label
    with pytest.raises(ValueError):
        reg.counter("t_reqs_total", "", labels=("other",))  # schema clash
    with pytest.raises(ValueError):
        reg.gauge("t_reqs_total")  # kind clash
    # idempotent re-declaration returns the same family
    assert reg.counter("t_reqs_total", labels=("method", "code")) is f

    got = {tuple(sorted(s["labels"].items())): s["value"]
           for s in reg.snapshot()["metrics"]["t_reqs_total"]["samples"]}
    assert got == {
        (("code", "200"), ("method", "get")): 2.0,
        (("code", "500"), ("method", "get")): 2.0,
    }


def test_histogram_fixed_buckets_cumulative():
    reg = observe.Registry()
    h = reg.histogram("t_lat", "", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    b = dict(h.labels().cumulative_buckets())
    assert b == {"0.1": 1, "1": 2, "10": 3, "+Inf": 4}
    assert h.labels().count == 4
    # default buckets are the fixed 1-2-5 log-scale ladder
    assert observe.DEFAULT_BUCKETS[0] == 1e-6
    assert len(observe.DEFAULT_BUCKETS) == 30


def test_histogram_bucket_redeclare_mismatch_raises():
    reg = observe.Registry()
    reg.histogram("t_b", "", buckets=(0.1, 1.0))
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("t_b", "", buckets=(10.0, 100.0))
    # same (or unspecified) buckets re-declare fine
    reg.histogram("t_b", "", buckets=(1.0, 0.1))
    reg.histogram("t_b")


def test_registry_reset_zeroes_but_keeps_schema():
    reg = observe.Registry()
    f = reg.counter("t_r_total", labels=("k",))
    f.labels(k="a").inc(5)
    reg.reset()
    samples = reg.snapshot()["metrics"]["t_r_total"]["samples"]
    assert samples == [{"labels": {"k": "a"}, "value": 0.0}]


# ---------------------------------------------------- exposition format
_EXPO_LINE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'                    # metric name
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*"'    # first label
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*")*\})?'  # more labels
    r' (?P<value>\S+)$')


def _assert_valid_exposition(text):
    assert text.endswith("\n")
    for line in text.splitlines():
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            continue
        m = _EXPO_LINE.match(line)
        assert m, "invalid exposition line: %r" % line
        v = m.group("value")
        if v not in ("+Inf", "-Inf", "NaN"):
            float(v)  # raises on junk


def test_prometheus_exposition_parses_line_by_line():
    # exercise every metric kind, labels, and escaping in one registry
    reg = observe.Registry()
    reg.counter("t_e_total", "with \"quotes\" and \\slash",
                labels=("k",)).labels(k='va"l\\ue').inc()
    reg.gauge("t_e_g", "gauge").set(-2.5)
    reg.histogram("t_e_h", "hist").observe(0.5)
    _assert_valid_exposition(reg.render_prometheus())
    # the process-wide registry (executor/RPC instrumentation included)
    _assert_valid_exposition(observe.render_prometheus())


def test_snapshot_prometheus_round_trip(tmp_path):
    path = str(tmp_path / "snap.json")
    live = observe.dump(path)
    with open(path) as f:
        saved = json.load(f)
    # a saved snapshot renders exactly like the live registry it captured
    assert observe.render_prometheus(saved) == \
        observe.render_prometheus(live)
    _assert_valid_exposition(observe.render_prometheus(saved))
    # the well-known executor + RPC families are always present and
    # non-empty, even in a process that never ran a step (the sidecar-
    # on-probe-failure contract)
    for fam in ("paddle_executor_cache_misses_total",
                "paddle_executor_steps_total",
                "paddle_rpc_client_calls_total",
                "paddle_rpc_client_seconds"):
        assert saved["metrics"][fam]["samples"], fam


def test_help_and_type_lines_round_trip_declared_schema():
    """Every family declared in families.py renders exactly one # HELP
    and one # TYPE line whose kind matches the declaration — and a
    JSON-round-tripped snapshot preserves both (the exposition a scrape
    of a saved sidecar serves is byte-what a live scrape would have
    served)."""
    from paddle_tpu.observe.families import REGISTRY

    def parse_meta(text):
        helps, types = {}, {}
        for line in text.splitlines():
            if line.startswith("# HELP "):
                name, help_text = line[len("# HELP "):].split(" ", 1)
                assert name not in helps, "duplicate HELP for %s" % name
                helps[name] = help_text
            elif line.startswith("# TYPE "):
                name, kind = line[len("# TYPE "):].rsplit(" ", 1)
                assert name not in types, "duplicate TYPE for %s" % name
                types[name] = kind
        return helps, types

    live = REGISTRY.render_prometheus()
    helps, types = parse_meta(live)
    with REGISTRY._lock:
        declared = {name: fam for name, fam in REGISTRY._families.items()}
    assert len(declared) > 40
    for name, fam in declared.items():
        assert types.get(name) == fam.kind, name
        assert helps.get(name), "missing/empty HELP for %s" % name
        # HELP content is the declaration's help, newline-escaped
        assert helps[name] == fam.help.replace("\\", "\\\\") \
            .replace("\n", "\\n"), name
    # JSON round-trip preserves the metadata byte-for-byte
    rendered = REGISTRY.render_prometheus(
        json.loads(json.dumps(REGISTRY.snapshot())))
    assert parse_meta(rendered) == (helps, types)


def test_stats_dump_diff_marks_added_and_removed_families(tmp_path):
    """--diff on two sidecars with non-identical schemas (an old round
    vs a new one that gained/lost families) marks each one-sided series
    added/removed instead of rendering a bogus delta or raising on a
    kind change."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import stats_dump

    def snap(fams):
        return {"metrics": fams, "pid": 1, "unix_time": 0.0}

    gone = "paddle_gone" + "_total"        # concatenated: repo-lint-safe
    new_h = "paddle_new" + "_seconds"
    both = "paddle_both" + "_total"
    morph = "paddle_morph" + "_total"
    a = snap({
        gone: {"type": "counter", "help": "", "labelnames": [],
               "samples": [{"labels": {}, "value": 3}]},
        both: {"type": "counter", "help": "", "labelnames": [],
               "samples": [{"labels": {}, "value": 1}]},
        morph: {"type": "counter", "help": "", "labelnames": [],
                "samples": [{"labels": {}, "value": 2}]},
    })
    b = snap({
        new_h: {"type": "histogram", "help": "", "labelnames": [],
                "samples": [{"labels": {}, "sum": 1.0, "count": 2,
                             "buckets": {"1": 2, "+Inf": 2}}]},
        both: {"type": "counter", "help": "", "labelnames": [],
               "samples": [{"labels": {}, "value": 4}]},
        morph: {"type": "gauge", "help": "", "labelnames": [],
                "samples": [{"labels": {}, "value": 2}]},
    })
    import io

    out = io.StringIO()
    stats_dump.render_diff(a, b, out=out)   # must not raise
    text = out.getvalue()
    lines = {l.split()[0]: l for l in text.splitlines() if l.strip()}
    assert "removed" in lines[gone]
    assert "[added]" in lines[new_h]
    assert "kind changed" in lines[morph]
    assert "+3" in lines[both]
    # and through the CLI, file-to-file
    pa, pb = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    json.dump(a, open(pa, "w"))
    json.dump(b, open(pb, "w"))
    p = subprocess.run([sys.executable, STATS_DUMP, "--diff", pa, pb],
                       capture_output=True, text=True)
    assert p.returncode == 0, p.stderr
    assert "removed" in p.stdout and "[added]" in p.stdout


# ------------------------------------------------- executor integration
def _value(name, **labels):
    for s in observe.snapshot()["metrics"][name]["samples"]:
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s.get("value", s.get("count"))
    return 0.0


def test_executor_cache_and_step_metrics(fresh_programs):
    main, startup, scope = fresh_programs
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.fc(x, size=2)
    exe = fluid.Executor()
    exe.run(startup, scope=scope)

    h0 = _value("paddle_executor_cache_hits_total")
    m0 = _value("paddle_executor_cache_misses_total")
    s0 = _value("paddle_executor_steps_total")
    X = np.ones((3, 4), np.float32)
    for _ in range(3):
        exe.run(main, feed={"x": X}, fetch_list=[y.name], scope=scope)
    assert _value("paddle_executor_cache_misses_total") == m0 + 1
    assert _value("paddle_executor_cache_hits_total") == h0 + 2
    assert _value("paddle_executor_steps_total") == s0 + 3
    # first dispatch lands in the compile histogram; the steady steps
    # record BOTH phases: the async hand-off and the blocked completion
    assert _value("paddle_executor_run_seconds", site="run",
                  phase="dispatch") >= 2
    assert _value("paddle_executor_run_seconds", site="run",
                  phase="complete") >= 2


def test_run_repeated_counts_all_scanned_steps(fresh_programs):
    main, startup, scope = fresh_programs
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[2], dtype="float32")
        y = fluid.layers.fc(x, size=2)
    exe = fluid.Executor()
    exe.run(startup, scope=scope)
    s0 = _value("paddle_executor_steps_total")
    exe.run_repeated(main, feed={"x": np.ones((3, 2), np.float32)},
                     fetch_list=[y.name], scope=scope, steps=4)
    assert _value("paddle_executor_steps_total") == s0 + 4


# ------------------------------------------------------ RPC integration
def test_rpc_call_and_bytes_metrics():
    from paddle_tpu.distributed.rpc import RPCClient, RPCServer

    srv = RPCServer(port=0, num_trainers=1, sync=False)
    srv.start()
    c0 = _value("paddle_rpc_client_calls_total", method="send_var")
    b0 = _value("paddle_rpc_client_bytes_sent_total")
    r0 = _value("paddle_rpc_client_bytes_recv_total")
    cli = RPCClient("127.0.0.1:%d" % srv.port, trainer_id=0)
    cli.connect()
    payload = np.arange(12, dtype=np.float32).reshape(3, 4)
    cli.send_var("g", payload)
    srv.set_var("w", payload)
    got = cli.get_var("w")
    assert np.array_equal(got, payload)
    cli.close()
    srv.close()
    assert _value("paddle_rpc_client_calls_total",
                  method="send_var") == c0 + 1
    assert _value("paddle_rpc_client_bytes_sent_total") == \
        b0 + payload.nbytes
    assert _value("paddle_rpc_client_bytes_recv_total") == \
        r0 + payload.nbytes
    assert _value("paddle_rpc_client_seconds", method="get_var") >= 1
    assert _value("paddle_rpc_server_requests_total", method="set_var") >= 1


def test_rpc_retry_and_deadline_counters(monkeypatch):
    from paddle_tpu.distributed.rpc import RPCClient, RPCError, RPCServer

    # short deadline so the missing-var poll loop expires in ~0.4s
    monkeypatch.setenv("PADDLE_TPU_RPC_DEADLINE_MS", "400")
    srv = RPCServer(port=0, num_trainers=1, sync=False)
    srv.start()
    cli = RPCClient("127.0.0.1:%d" % srv.port, trainer_id=0)
    cli.connect()
    e0 = _value("paddle_rpc_client_errors_total", method="get_var")
    d0 = _value("paddle_rpc_client_deadline_expirations_total",
                method="get_var")
    r0 = _value("paddle_rpc_client_retries_total", method="get_var")
    with pytest.raises(RPCError):
        cli.get_var("never_pushed")
    cli.close()
    srv.close()
    assert _value("paddle_rpc_client_errors_total",
                  method="get_var") == e0 + 1
    assert _value("paddle_rpc_client_deadline_expirations_total",
                  method="get_var") == d0 + 1
    # the init-race poll loop retried at least twice before expiring
    assert _value("paddle_rpc_client_retries_total",
                  method="get_var") >= r0 + 2


def test_rpc_fast_failure_is_error_but_not_deadline_expiration():
    """get_var exhausting its retry COUNT against a live server (default
    60s deadline nowhere near burned) is an error, NOT a deadline
    expiration — the sidecar distinction between init-race and wedge."""
    from paddle_tpu.distributed.rpc import RPCClient, RPCError, RPCServer

    srv = RPCServer(port=0, num_trainers=1, sync=False)
    srv.start()
    cli = RPCClient("127.0.0.1:%d" % srv.port, trainer_id=0)
    cli.connect()
    e0 = _value("paddle_rpc_client_errors_total", method="get_var")
    d0 = _value("paddle_rpc_client_deadline_expirations_total",
                method="get_var")
    with pytest.raises(RPCError):
        cli.get_var("never_pushed", retries=2)  # fails in ~0.2s
    cli.close()
    srv.close()
    assert _value("paddle_rpc_client_errors_total",
                  method="get_var") == e0 + 1
    assert _value("paddle_rpc_client_deadline_expirations_total",
                  method="get_var") == d0


def test_reset_clears_pending_feed_gap_stamp(fresh_programs):
    main, startup, scope = fresh_programs
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[2], dtype="float32")
        y = fluid.layers.fc(x, size=2)
    exe = fluid.Executor()
    exe.run(startup, scope=scope)
    observe.mark_batch_produced()  # stale stamp from "another test"
    observe.reset()
    exe.run(main, feed={"x": np.ones((2, 2), np.float32)},
            fetch_list=[y.name], scope=scope)
    # the stale stamp must not leak a bogus gap into the zeroed histogram
    assert _value("paddle_feed_to_run_gap_seconds") == 0


def test_feed_to_run_gap(fresh_programs):
    main, startup, scope = fresh_programs
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[2], dtype="float32")
        y = fluid.layers.fc(x, size=2)
    exe = fluid.Executor()
    exe.run(startup, scope=scope)
    g0 = _value("paddle_feed_to_run_gap_seconds")
    observe.mark_batch_produced()
    exe.run(main, feed={"x": np.ones((2, 2), np.float32)},
            fetch_list=[y.name], scope=scope)
    assert _value("paddle_feed_to_run_gap_seconds") == g0 + 1
    # read-and-clear: a second run without a new batch records nothing
    exe.run(main, feed={"x": np.ones((2, 2), np.float32)},
            fetch_list=[y.name], scope=scope)
    assert _value("paddle_feed_to_run_gap_seconds") == g0 + 1


def test_reader_batch_counts():
    from paddle_tpu import reader

    b0 = _value("paddle_data_batches_total", source="reader.batch")
    r = reader.batch(lambda: iter(range(10)), batch_size=4)
    assert len(list(r())) == 3  # 4 + 4 + 2 (no drop_last)
    assert _value("paddle_data_batches_total",
                  source="reader.batch") == b0 + 3


# ------------------------------------------------- dump + stats_dump
def _stats_dump(*args):
    return subprocess.run([sys.executable, STATS_DUMP] + list(args),
                          timeout=120, stdout=subprocess.PIPE,
                          stderr=subprocess.PIPE, text=True)


def _fc_program(fresh_programs):
    main, startup, scope = fresh_programs
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[2], dtype="float32")
        y = fluid.layers.fc(x, size=2)
    exe = fluid.Executor()
    exe.run(startup, scope=scope)
    return lambda: exe.run(main, feed={"x": np.ones((2, 2), np.float32)},
                           fetch_list=[y.name], scope=scope)


def test_dump_renders_through_stats_dump(tmp_path, fresh_programs):
    step = _fc_program(fresh_programs)
    s0 = _value("paddle_executor_steps_total")
    for _ in range(3):
        step()
    path = tmp_path / "snap.json"
    observe.dump(str(path))
    snap = json.loads(path.read_text())
    assert snap["metrics"]["paddle_executor_steps_total"]["samples"][0][
        "value"] == s0 + 3
    # families of subsystems this process never entered are in the dump,
    # zeroed: absent and zero are different diagnoses
    assert snap["metrics"]["paddle_rpc_client_calls_total"]["samples"]

    out = _stats_dump(str(path))
    assert out.returncode == 0, out.stderr
    assert "paddle_executor_steps_total" in out.stdout
    assert "paddle_executor_run_seconds" in out.stdout   # a histogram row
    promo = _stats_dump(str(path), "--prometheus")
    assert promo.returncode == 0
    _assert_valid_exposition(promo.stdout)


def test_dump_after_a_failed_run_still_renders(tmp_path, fresh_programs):
    """A process whose step failed must still leave a diagnosable
    snapshot: every family present, the failure's own counter moved."""
    from paddle_tpu.resilience.faults import FaultPlan, InjectedFault

    step = _fc_program(fresh_programs)
    f0 = _value("paddle_resilience_faults_injected_total",
                site="executor.dispatch", mode="raise")
    with FaultPlan().arm("executor.dispatch", every=True):
        with pytest.raises(InjectedFault):
            step()
    path = tmp_path / "after_failure.json"
    observe.dump(str(path))
    snap = json.loads(path.read_text())
    assert set(snap["metrics"]) == set(observe.snapshot()["metrics"])
    out = _stats_dump(str(path), "--grep", "paddle_resilience")
    assert out.returncode == 0, out.stderr
    row = [l for l in out.stdout.splitlines()
           if l.startswith("paddle_resilience_faults_injected_total")
           and "executor.dispatch" in l and "raise" in l]
    assert row and float(row[0].split()[-1]) == f0 + 1, out.stdout
    # a gauge at 0 renders (zero-suppression only drops counters)
    assert "paddle_resilience_watchdog_armed" in out.stdout


def _mini_snap(steps, gap_bucket_counts):
    """Minimal valid telemetry snapshot for stats_dump --diff tests."""
    total = sum(gap_bucket_counts.values())
    acc, buckets = 0, {}
    for le in sorted(gap_bucket_counts, key=float):
        acc += gap_bucket_counts[le]
        buckets[le] = acc
    buckets["+Inf"] = total
    return {
        "version": 1, "pid": 1, "unix_time": 0.0,
        "metrics": {
            "paddle_executor_steps_total": {
                "type": "counter", "help": "", "labelnames": [],
                "samples": [{"labels": {}, "value": steps}]},
            "paddle_feed_to_run_gap_seconds": {
                "type": "histogram", "help": "", "labelnames": [],
                "samples": [{"labels": {}, "sum": 0.1 * total,
                             "count": total, "buckets": buckets}]},
            "paddle_resilience_watchdog_armed": {
                "type": "gauge", "help": "", "labelnames": [],
                "samples": [{"labels": {}, "value": 0}]},
        }}


def test_stats_dump_diff_prints_per_family_deltas(tmp_path):
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps(_mini_snap(10, {"0.01": 10})))
    b.write_text(json.dumps(_mini_snap(25, {"0.001": 15})))
    out = _stats_dump("--diff", str(a), str(b))
    assert out.returncode == 0, out.stderr
    # counter delta and side-by-side histogram stats both render
    assert "paddle_executor_steps_total" in out.stdout
    assert "+15" in out.stdout
    assert "paddle_feed_to_run_gap_seconds" in out.stdout
    line = [l for l in out.stdout.splitlines()
            if l.startswith("paddle_feed_to_run_gap_seconds")][0]
    cols = line.split()
    assert cols[1] == "10" and cols[2] == "15"  # cnt A, cnt B
    # a gauge at 0 in BOTH snapshots still renders (zero-suppression
    # only drops counters)
    assert "paddle_resilience_watchdog_armed" in out.stdout

    # a non-snapshot file is a usage error, not a traceback
    junk = tmp_path / "junk.json"
    junk.write_text("{}")
    bad = _stats_dump("--diff", str(a), str(junk))
    assert bad.returncode == 2
    assert "not a telemetry snapshot" in bad.stderr
