"""Every runner kind end to end through benchmarks/run.py's rehearsal
flag, on the CPU, with a tiny configuration, traffic, cell and per-layer
metric that live HERE: the harness finds them by name without a single
edit under benchmarks/."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = "tests/benchmarks/BENCHMARK.tiny.json"
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(tmp_path, *args, devices=1):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" % devices
    # the suite forces the flash kernel (interpreted) at every length;
    # the rehearsal takes the program's own dispatch
    env.pop("PADDLE_TPU_FLASH_MIN_SEQ", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    env["BENCH_RUN"] = "the driver sets this; the benchmark ignores it"
    # niced: the suite runs beside timing-sensitive tests of the program,
    # and a compile in here must not starve them
    return subprocess.run(
        ["nice", "-n", "19", sys.executable, "benchmarks/run.py",
         "--manifest", MANIFEST, *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def _lines(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = [json.loads(x) for x in proc.stdout.strip().splitlines()
           if x.startswith("{")]
    return out[-2], out[-1]


@pytest.mark.parametrize("cell,devices,trace,reports", [
    ("tiny_train", 1, 0, {"train_tok_s", "setup_s"}),
    ("tiny_train", 1, 1, {"compile_s", "tiny.windows_n"}),
    ("tiny_train_dp2", 2, 0, {"train_tok_s", "setup_s"}),
    ("tiny_serve", 1, 0, {"serve_tok_s", "req_tok_ms_p50",
                          "req_tok_ms_p95", "setup_s"}),
    ("tiny_serve", 1, 1, {"compile_s", "gen_late_ms_p95"}),
])
def test_rehearsal_runs_the_kind_and_names_no_device_metric(
        tmp_path, cell, devices, trace, reports):
    proc = _run(tmp_path, "--cpu-rehearsal", "--workload", cell,
                "--seed", str(2 ** 31 + 12345), "--seconds", "1",
                "--trace", str(trace), devices=devices)
    rehearsal, last = _lines(proc)
    assert set(last) == CONTRACT_KEYS
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    # a CPU number is never written under a device metric's name
    assert last["metrics"] == {}
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == devices
    assert "busy_s" not in last["device"]
    assert rehearsal["rehearsal"] == "passed"
    # trace-derived metrics have nothing to read on a CPU and are left out
    assert set(rehearsal["would_report"]) == reports


def test_the_measurement_path_fails_on_a_cpu(tmp_path):
    proc = _run(tmp_path, "--workload", "tiny_train", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not [x for x in proc.stdout.splitlines() if x.startswith("{")]


def test_an_unknown_cell_is_an_error_not_a_default(tmp_path):
    proc = _run(tmp_path, "--cpu-rehearsal", "--workload", "no_such_cell",
                "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert "no_such_cell" in proc.stderr
