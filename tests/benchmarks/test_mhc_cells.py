"""The cell PR 37 adds, on the CPU: its rehearsal through
benchmarks/run.py with a tiny manifest that lives HERE, the closed forms
of the residual streams against hand-counted numbers, the four new
readers on made-up records, the configuration against the catalog, and
the traffic's blocks. The tiny cell's reference is the benchmark's own
file, loaded by path (tests/benchmarks/references/tiny-mhc.py)."""

import json
import os
import subprocess
import sys
from collections import Counter

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import (closed_forms_mhc, closed_forms_mla,  # noqa: E402
                            closed_loop)
from benchmarks.lib.manifest import Manifest, load_path  # noqa: E402

MANIFEST = "tests/benchmarks/BENCHMARK.tiny_mhc.json"
CELL = "tiny_mhc_serve_docs"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("mhc_prefill_ms", "mhc_roofline", "mhc_decode_ms", "mhc_res_dev_max")


def _checkout(tmp_path):
    """A checkout of symlinks (``test_mla_cells._checkout`` says why)."""
    root = tmp_path / "checkout"
    root.mkdir()
    for name in ("benchmarks", "paddle_tpu", "tests", "BENCHMARK.json"):
        os.symlink(os.path.join(ROOT, name), root / name)
    return str(root)


def _rehearse(tmp_path, trace):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.pop("PADDLE_TPU_FLASH_MIN_SEQ", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    env["BENCH_RUN"] = "the driver sets this; the benchmark ignores it"
    proc = subprocess.run(
        ["nice", "-n", "19", sys.executable, "benchmarks/run.py",
         "--manifest", MANIFEST, "--cpu-rehearsal", "--workload", CELL,
         "--seed", str(2 ** 31 + 37037), "--seconds", "1",
         "--trace", str(trace)],
        cwd=_checkout(tmp_path), env=env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, "\n".join(
        x[:400] for x in proc.stderr.splitlines()
        if "cpu_aot_loader" not in x)[-3000:]
    out = [json.loads(x) for x in proc.stdout.strip().splitlines()
           if x.startswith("{")]
    return out[-2], out[-1]


@pytest.mark.parametrize("trace,reports", [
    (0, {"serve_tok_s", "req_tok_ms_p50", "setup_s"}),
    # program spans and counters are read on a CPU too (the mappings'
    # health reading among them); the device-trace readers have no TPU
    # plane there
    (1, {"cache_miss_n", "compile_s", "engine_occ_pct", "engine_step_ms",
         "step_sample_ms", "step_self_ms", "moe_touched_pct",
         "mhc_res_dev_max"}),
])
def test_rehearsal_of_the_new_cell(tmp_path, trace, reports):
    rehearsal, last = _rehearse(tmp_path, trace)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1 and last["metrics"] == {}
    assert rehearsal["rehearsal"] == "passed"
    assert set(rehearsal["would_report"]) == reports
    facts = rehearsal["facts"]
    assert facts["reference_tokens_compared"] > 0
    # a CPU computes float32 exactly, so the system IS the reference up
    # to the order of its sums; the reference with bfloat16 activations
    # AND bfloat16 mappings is not: both decide `correct`
    assert facts["reference_mean_margin"] <= 1e-5
    assert facts["control_bf16_mean_margin"] > 1e-5
    # answers after prompts past YaRN's original context are judged
    assert facts["reference_probes_long"] == 2
    assert facts["longest_prompt"] == 40
    assert facts["primers"] == facts["clients"] == 4
    assert 0 < facts["tokens_made"] <= facts["decode_steps"] \
        * facts["b_max"] + facts["requests_in_window"]
    # the cache is PR 32's: ONE latent tensor a layer, no stream in it
    assert facts["cache_bytes"] == {"latent": 3 * 4 * 64 * 40 * 4}
    cfg = Manifest(os.path.join(ROOT, MANIFEST)).config("tiny-mhc")["model"]
    assert facts["weight_bytes"] == {
        "bfloat16": 2 * closed_forms_mhc.matrix_params(cfg),
        "float32": 4 * closed_forms_mhc.vector_params(cfg)}
    assert facts["static_bytes"] == sum(facts["weight_bytes"].values()) \
        + facts["cache_bytes"]["latent"]
    # two ops a sub-block, two sub-blocks a layer, every lowering counted
    plans = facts["mhc_plans"]
    assert set(plans) == {"mhc pre composed n=4", "mhc post composed n=4"}
    assert plans["mhc pre composed n=4"] == plans["mhc post composed n=4"]
    assert plans["mhc pre composed n=4"] % (2 * 3) == 0
    assert facts["experts_held"] == 16
    assert 4 <= facts["experts_touched_mean"] <= 16
    assert 0 <= facts["mhc"]["res_dev"] < 0.5
    step = facts["decode_step_bytes"]
    assert step["streams"] == closed_forms_mhc.mhc_bytes(cfg, 4, 4, 2)
    assert step["total"] == pytest.approx(
        step["weights"] + step["experts"] + step["cache"]
        + step["streams"])


def test_the_real_manifest_finds_every_file_of_the_new_cell():
    m = Manifest()
    w = m.cell("xing_serve_docs")
    assert (w["config"], w["traffic"], w["chips"]) == (
        "xing4.0-29b-a4b", "batch_closed_long_prompts", 1)
    traffic = m.traffic(w["traffic"])
    assert traffic["kind"] == "closed_loop_mhc"
    assert os.path.isfile(m.find("kinds", traffic["kind"], (".py",)))
    assert os.path.isfile(m.find("references", w["config"], (".py",)))
    assert {e["name"] for e in m.metrics_for("end_to_end", w["name"])} \
        == {"serve_tok_s", "req_tok_ms_p50", "setup_s"}
    listed = {e["name"] for e in m.metrics_for("per_layer", w["name"])}
    for name in listed:
        assert os.path.isfile(m.find("layer_metrics", name, (".py",)))
    assert set(NEW) | {
        "mla_decode_ms", "mla_decode_roofline", "mla_flash_ms",
        "mla_flash_roofline", "engine_step_ms", "engine_occ_pct",
        "decode_dev_ms", "decode_bw_pct", "peak_hbm_gb.serve",
        "step_sample_ms", "step_self_ms", "setup_engine_s", "moe_gmm_ms",
        "moe_touched_pct"} <= listed
    # gmm_bytes counts every expert and ~87% are touched: it stays off
    assert not {"moe_gmm_roofline", "moe_load_max_pct", "flash_win_ms"} \
        & listed
    for name in NEW:
        (entry,) = [e for e in m.doc["per_layer"] if e["name"] == name]
        assert entry["workloads"] == ["xing_serve_docs"]
    # the limits of the contract: 24 cells, a quarter of them on 4 chips
    cells = m.doc["workloads"]
    assert len(cells) <= 24 and len(m.doc["configs"]) <= 24
    assert sum(1 for c in cells if c["chips"] == 4) \
        <= max(1, len(cells) // 4)
    assert cells[-1]["name"] == "xing_serve_docs"       # appended
    assert m.doc["configs"][-1]["name"] == "xing4.0-29b-a4b"
    assert [e["name"] for e in m.doc["per_layer"][-4:]] == list(NEW)


def test_the_configuration_holds_the_published_numbers():
    m = Manifest()
    cfg = m.config("xing4.0-29b-a4b")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(x) for x in f if x.strip()]
    (entry,) = [r for r in rows if r["name"] == "Xing4.0-29B-A4B"]
    assert cfg["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and key in cfg["reduced_why"], key
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers",
                              "num_nextn_predict_layers"]
    for key in ("deployment", "assumed", "departures", "guarantees"):
        assert cfg[key]
    model = cfg["model"]
    assert (model["d_model"], model["n_head"], model["q_lora_rank"],
            model["kv_lora_rank"], model["d_nope"], model["d_rope"],
            model["d_v"], model["d_ff"], model["d_expert"],
            model["n_expert"], model["n_expert_local"],
            model["expert_top_k"], model["route_scale"], model["vocab"],
            model["hc_mult"], model["hc_sinkhorn_iters"]) == (
        3584, 32, 768, 512, 128, 64, 128, 9216, 1024, 64, 64, 4, 2.0,
        131072, 4, 20)
    assert model["rope_scaling"] == entry["config"]["rope_scaling"]
    assert model["weight_dtype"] == "bfloat16"
    assert cfg["serving"] == {"b_max": 32, "max_len": 8448}
    from paddle_tpu.models import gpt

    gpt._check_cfg(model)


def test_closed_forms_against_hand_counted_numbers():
    model = Manifest().config("xing4.0-29b-a4b")["model"]
    c = closed_forms_mhc
    # ISSUE 37's reckoning: 28.41 M of attention a layer, 11.01 M an
    # expert, 0.69 M of mappings a layer, 4,047.6 M in all = 8.10 GB
    assert closed_forms_mla.attention_matrix_params(model) == 3584 * 768 \
        + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 + 4096 * 3584
    assert closed_forms_mla.attention_matrix_params(model) == 28_409_856
    assert closed_forms_mla.expert_params(model) == 11_010_048
    assert c.coefficients(model) == 24
    assert c.hc_matrix_params(model) == 14_336 * 24 == 344_064
    assert c.hc_vector_params(model) == 27
    assert c.matrix_params(model) - closed_forms_mla.matrix_params(model) \
        == 10 * 344_064
    assert c.vector_params(model) - closed_forms_mla.vector_params(model) \
        == 10 * 27 + 4 * 64
    assert round(c.param_count(model) / 1e6, 1) == 4047.7
    assert round(c.matrix_params(model) * 2 / 1e9, 2) == 8.10
    assert closed_forms_mla.cache_bytes(model, 32, 8448, 4) == 3_114_270_720
    assert round(c.static_bytes(model, 32, 8448, 4, 2) / 1e9, 2) == 11.21
    # a row and sub-block: (3 n + 2) C + 2 n (n + 2) float32 values
    assert c.stream_values_per_row(model) == 14 * 3584 + 48
    assert c.stream_values_per_row(model) * 4 == 200_896
    # an admission of 8,192: ten sub-blocks of rows and a phi each
    assert c.mhc_bytes(model, 8192, 4, 2) == 10 * (
        8192 * 200_896 + 344_064 * 2)
    roof = c.mhc_roofline(model, 8192, 4, 2, PEAKS)
    assert roof["bound"] == "memory"
    assert roof["seconds"] == pytest.approx(16_464_283_136 / 819e9,
                                            rel=1e-3)
    assert 0.019 < roof["seconds"] < 0.021
    # a decode step: the latent step's bytes and the streams of 32 rows
    base = closed_forms_mla.decode_step_bytes(model, 32, 8448, 4, 2, 56.0,
                                              32 * 3000)
    step = c.decode_step_bytes(model, 32, 8448, 4, 2, 56.0, 32 * 3000)
    assert step["streams"] == 10 * (32 * 200_896 + 688_128) == 71_168_000
    assert step["total"] == base["total"] + 71_168_000 + (270 + 256) * 4
    assert step["cache"] == base["cache"] and \
        step["experts"] == base["experts"]


def _reader(name):
    return load_path(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                  name + ".py"))


def _record(ops, steps=(), spans=()):
    return {
        "trace": {"ops": {0: ops}, "host_offset_s": 100.0, "t0": 100.0,
                  "t1": 110.0},
        "spans": {"serving.engine.step": list(steps)},
        "program_spans": [dict(ph="E", **s) for s in spans],
        "t_open": 0.0, "t_close": 10.0,
        "facts": {"longest_prompt": 8192, "window_s": 10.0,
                  "mhc": {"cfg": {"n_layer": 5, "d_model": 3584,
                                  "hc_mult": 4},
                          "itemsize": 4, "phi_itemsize": 2,
                          "res_dev": 3e-4}},
        "counters": {"mhc_res_dev": 3e-4},
        "peaks": PEAKS,
    }


def test_mhc_prefill_readers_on_a_made_up_record():
    ops = []
    for i in range(10):                  # an admission of 8,192
        ops.append(("mhc_pre.%d" % i, 102.0 + 0.02 * i, 0.0012))
        ops.append(("mhc_post.%d" % i, 102.01 + 0.02 * i, 0.0018))
    for i in range(10):                  # and one of 512
        ops.append(("mhc_pre.%d" % i, 104.0 + 0.001 * i, 0.0001))
    ops.append(("fusion.3", 102.005, 0.5))           # not these kernels
    spans = [dict(site="serving.engine.prefill", t=2.3, dur=0.35,
                  attrs={"prompt_len": 8192}),
             dict(site="serving.engine.prefill", t=4.1, dur=0.15,
                  attrs={"prompt_len": 512})]
    rec = _record(ops, spans=spans)
    assert _reader("mhc_prefill_ms").read(rec) == pytest.approx(30.0)
    least = closed_forms_mhc.mhc_roofline(
        rec["facts"]["mhc"]["cfg"], 8192, 4, 2, PEAKS)["seconds"]
    share = _reader("mhc_roofline").read(rec)
    assert share == pytest.approx(100.0 * least / 30e-3)
    assert 60 < share < 70
    # a composed plan has no operation under the kernels' names, and a
    # program from before this PR no facts.mhc: nothing read, none raised
    bare = _record([("fusion.1", 102.0, 0.1)], spans=spans)
    other = _record(ops, spans=spans)
    del other["facts"]["mhc"]
    for r in (bare, other, {"facts": {}}, {}):
        assert _reader("mhc_prefill_ms").read(r) is None
        assert _reader("mhc_roofline").read(r) is None
        assert _reader("mhc_decode_ms").read(r) is None


def test_mhc_decode_and_deviation_readers_on_a_made_up_record():
    ops = []
    for k in range(3):                   # three steps of twenty calls
        t = 101.0 + k
        for i in range(10):
            ops.append(("mhc_pre.%d" % i, t + 0.002 * i, 0.00001))
            ops.append(("mhc_post.%d" % i, t + 0.002 * i + 0.001, 0.00002))
        ops.append(("fusion.7", t + 0.03, 0.005))
    ops.append(("mhc_post.9", 108.5, 0.1))           # outside every step
    rec = _record(ops, steps=[(1.5 + k, 0.6) for k in range(3)])
    assert _reader("mhc_decode_ms").read(rec) == pytest.approx(0.3)
    assert _reader("mhc_res_dev_max").read(rec) == 3e-4
    assert _reader("mhc_res_dev_max").read({"counters": {}}) is None
    assert _reader("mhc_res_dev_max").read({}) is None


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
def test_every_block_of_the_traffic_holds_the_same_multiset(seed):
    m = Manifest()
    traffic = m.traffic("batch_closed_long_prompts")
    assert (traffic["clients"], traffic["ramp_s"], traffic["probes"],
            traffic["think_time_s"], traffic["trace_seconds"]) == (
        32, 10.0, 8, 0.0, 12.0)
    mixed = m.traffic("batch_closed_mixed_len")   # trinity_serve_mixed's
    assert traffic["prompt_lengths"] == mixed["prompt_lengths"]
    assert traffic["output_lengths"] == mixed["output_lengths"]
    seq = closed_loop.sequence(traffic, seed, 200)
    prompts = Counter({512: 8, 2048: 6, 6144: 4, 8192: 2})
    answers = Counter({32: 6, 64: 6, 128: 5, 256: 3})
    for lo in range(0, 200, 20):
        block = seq[lo:lo + 20]
        assert Counter(p for p, _ in block) == prompts
        assert Counter(n for _, n in block) == answers
    assert max(p + n for p, n in seq) <= 8448
    assert sum(p for p, _ in seq[:20]) / 20 == 2867.2
    assert sum(n for _, n in seq[:20]) / 20 == 99.2
    assert closed_loop.sequence(traffic, seed + 1, 200) != seq
    assert traffic["reference_probes"] == 64
    assert traffic["reference_probes_long"] == 16
    assert traffic["reference_long_over"] == 4096
    # every padded length the reference is compiled for
    pad = traffic["reference_pad_multiple"]
    assert {-(-(p + n) // pad) * pad for p, n in seq} \
        <= {768, 2304, 6400, 8448}
