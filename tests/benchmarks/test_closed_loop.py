"""The closed-loop cell: its sequence offers the same work whatever the
seed, the traffic file states what ISSUE 24 fixed, and the runner kind
and the readers of the program's spans run end to end through
``benchmarks/run.py --cpu-rehearsal`` with a tiny manifest that lives
here."""

import importlib.util
import json
import os
import subprocess
import sys
from collections import Counter

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import closed_loop  # noqa: E402
from benchmarks.lib.manifest import Manifest  # noqa: E402

MANIFEST = "tests/benchmarks/BENCHMARK.tiny_spans.json"
SEEDS = [0, 7, 2 ** 31 - 1, 2 ** 31 + 11, 4000000007]


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "batch_closed.json")) as f:
        return json.load(f)


def test_the_traffic_file_states_the_closed_loop(traffic):
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "chat_steady.json")) as f:
        chat = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "gpt2-medium.json")) as f:
        config = json.load(f)
    assert traffic["kind"] == "closed_loop"
    assert traffic["clients"] == config["serving"]["b_max"] == 32
    assert traffic["think_time_s"] == 0
    assert traffic["ramp_s"] == 8 and traffic["queue_capacity"] == 4096
    assert traffic["probes"] == 8
    assert traffic["reference_margin_tolerance"] == 0.02
    assert "who" in traffic
    # lengths as chat_steady: the cells differ in the arrivals alone
    for key in ("block", "prompt_lengths", "output_lengths"):
        assert traffic[key] == chat[key]


@pytest.mark.parametrize("seed", SEEDS)
def test_every_block_of_the_sequence_holds_the_stated_lengths(
        traffic, seed):
    block = traffic["block"]
    seq = closed_loop.sequence(traffic, seed, 10 * block - 3)
    assert len(seq) == 10 * block      # whole blocks
    want_p = Counter({int(k): v for k, v in
                      traffic["prompt_lengths"].items()})
    want_o = Counter({int(k): v for k, v in
                      traffic["output_lengths"].items()})
    for b in range(10):
        part = seq[b * block:(b + 1) * block]
        assert Counter(p for p, _o in part) == want_p
        assert Counter(o for _p, o in part) == want_o


def test_two_seeds_offer_the_same_multiset_in_another_order(traffic):
    a = closed_loop.sequence(traffic, SEEDS[1], 200)
    b = closed_loop.sequence(traffic, SEEDS[3], 200)
    assert a != b
    assert Counter(p for p, _ in a) == Counter(p for p, _ in b)
    assert Counter(o for _, o in a) == Counter(o for _, o in b)
    assert closed_loop.sequence(traffic, SEEDS[1], 200) == a
    # enough for max_req_s over ramp and window, and a block a client
    assert closed_loop.sequence_length(traffic, 45.0) == 53 * 20 + 32 * 20


def test_weights_that_do_not_fill_a_block_are_refused(traffic):
    broken = dict(traffic, prompt_lengths={"64": 3})
    with pytest.raises(ValueError, match="add up to block"):
        closed_loop.sequence(broken, 1, 20)


def test_the_tiny_manifest_keeps_to_the_contract_and_its_readers_agree():
    spec = importlib.util.spec_from_file_location(
        "bench_test_manifest",
        os.path.join(ROOT, "tests", "benchmarks", "test_manifest.py"))
    checks = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(checks)
    manifest = Manifest(os.path.join(ROOT, MANIFEST))
    checks.test_top_level_keys_and_limits(manifest)
    checks.test_names_units_and_entries(manifest)
    checks.test_every_cell_has_its_config_traffic_and_kind(manifest)
    checks.test_every_cell_reports_setup_one_more_end_to_end_and_a_layer(
        manifest)
    checks.test_every_per_layer_metric_has_a_reader_that_agrees(manifest)


def test_benchmark_json_only_grew(traffic):
    """What the benchmark had stays as it was: the new cell and metrics
    are appended, and the new cell's name is appended to lists."""
    doc = Manifest().doc
    assert [w["name"] for w in doc["workloads"]][:4] == [
        "bert_train_s512", "gpt2m_serve_chat", "bert_train_s128",
        "bert_train_s512_dp4"]
    cell = doc["workloads"][-1]
    assert cell == dict(cell, name="gpt2m_serve_batch", chips=1,
                        config="gpt2-medium", traffic="batch_closed")
    assert [m["name"] for m in doc["per_layer"]][:18][-1] \
        == "peak_hbm_gb.serve"
    assert doc["run_seconds"] == 45
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert e2e["serve_tok_s"]["workloads"] == ["gpt2m_serve_chat",
                                               "gpt2m_serve_batch"]
    assert e2e["req_tok_ms_p50"]["workloads"][-1] == "gpt2m_serve_batch"
    assert e2e["req_tok_ms_p95"]["workloads"] == ["gpt2m_serve_chat"]


def _run(tmp_path, *args, devices=1):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" % devices
    env.pop("PADDLE_TPU_FLASH_MIN_SEQ", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    return subprocess.run(
        ["nice", "-n", "19", sys.executable, "benchmarks/run.py",
         "--manifest", MANIFEST, "--cpu-rehearsal", *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


SERVE_SPANS = {"engine_step_ms", "engine_occ_pct", "step_sample_ms",
               "step_self_ms"}
TRAIN_SPANS = {"host_gather_ms.train", "host_dispatch_ms.train",
               "host_self_ms.train"}


@pytest.mark.parametrize("cell,devices,trace,reports", [
    ("tiny_serve_batch", 1, 0, {"serve_tok_s", "req_tok_ms_p50",
                                "setup_s"}),
    ("tiny_serve_batch", 1, 1, {"compile_s"} | SERVE_SPANS),
    ("tiny_serve", 1, 1, {"compile_s", "prefill_run_ms_p50",
                          "splice_ms_p50", "engine_ttft_ms_p95",
                          "engine_itl_ms_p95"} | SERVE_SPANS),
    ("tiny_train_dp2", 2, 1, {"compile_s", "host_place_ms.train"}
     | TRAIN_SPANS),
])
def test_rehearsal_runs_the_kind_and_the_span_readers(
        tmp_path, cell, devices, trace, reports):
    proc = _run(tmp_path, "--workload", cell, "--seed",
                str(2 ** 31 + 12345), "--seconds", "1", "--trace",
                str(trace), devices=devices)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = [json.loads(x) for x in proc.stdout.strip().splitlines()
           if x.startswith("{")]
    rehearsal, last = out[-2], out[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1 and last["metrics"] == {}
    assert rehearsal["rehearsal"] == "passed"
    # device-trace metrics (decode_dev_ms, the named kernels) have
    # nothing to read on a CPU and are left out
    assert set(rehearsal["would_report"]) == reports
    if cell == "tiny_serve_batch":
        facts = rehearsal["facts"]
        assert facts["clients"] == 4
        assert facts["completed_in_window"] >= 4
        assert facts["requests_submitted"] <= facts["requests_built"]
