"""The cell PR 40 adds, on the CPU: its rehearsal through
benchmarks/run.py with a tiny manifest that lives HERE, the closed forms
of the state-space layers and the latent experts against hand-counted
numbers, the five new readers on made-up records, the configuration
against the catalog key by key, and the traffic's blocks. The tiny cell's
reference is the benchmark's own file, loaded by path
(tests/benchmarks/references/tiny-ssm.py). The real manifest's entries
are looked up BY NAME: a later PR appends behind them."""

import json
import os
import subprocess
import sys
from collections import Counter

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import closed_forms_ssm, closed_loop  # noqa: E402
from benchmarks.lib.manifest import Manifest, load_path  # noqa: E402

MANIFEST = "tests/benchmarks/BENCHMARK.tiny_ssm.json"
CELL = "tiny_ssm_serve_many"
REAL, CONFIG = "nemotron_serve_many", "nemotron-3-super-120b-a12b"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("ssm_step_ms", "ssm_step_roofline", "ssm_scan_ms",
       "ssm_scan_roofline", "ssm_state_gb")


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
         "--seed", str(2 ** 31 + 40040), "--seconds", "1",
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
    # program spans and counters are read on a CPU too (the state
    # cache's bytes among them); the device-trace readers have no TPU
    # plane there
    (1, {"cache_miss_n", "compile_s", "engine_occ_pct", "engine_step_ms",
         "step_sample_ms", "step_self_ms", "moe_touched_pct",
         "ssm_state_gb"}),
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
    # to the order of its sums (the chunked scan against the token by
    # token one); the reference with bfloat16 activations, STATE and
    # cache is not: both decide `correct`
    assert facts["reference_mean_margin"] <= 1e-5
    assert facts["control_bf16_mean_margin"] > 1e-5
    assert facts["reference_probes_long"] == 2
    assert facts["longest_prompt"] == 40
    assert facts["primers"] == facts["clients"] == 4
    assert 0 < facts["tokens_made"] <= facts["decode_steps"] \
        * facts["b_max"] + facts["requests_in_window"]
    # two state-space layers' state and rows a slot, one attention slab
    cfg = Manifest(os.path.join(ROOT, MANIFEST)).config("tiny-ssm")["model"]
    assert facts["cache_bytes"] == {
        "state": closed_forms_ssm.state_bytes(cfg, 4),
        "full": closed_forms_ssm.slab_bytes(cfg, 4, 64)}
    assert facts["cache_bytes"]["state"] == 4 * 2 * (4 * 8 * 16 + 3 * 96) * 4
    assert facts["weight_bytes"] == {
        "bfloat16": 2 * closed_forms_ssm.matrix_params(cfg),
        "float32": 4 * closed_forms_ssm.vector_params(cfg)}
    assert facts["static_bytes"] == sum(facts["weight_bytes"].values()) \
        + sum(facts["cache_bytes"].values())
    assert facts["param_count"] == closed_forms_ssm.param_count(cfg)
    # a scan a state-space layer and prefill program, an update a layer
    # of the one decode program; every lowering counted with its chunk
    plans = facts["ssm_plans"]
    assert set(plans) == {"scan composed chunk=8", "update composed chunk=1"}
    assert plans["update composed chunk=1"] == 2
    assert plans["scan composed chunk=8"] == 2 * 3      # three lengths
    assert facts["experts_held"] == 8               # a share: 8 of 16
    assert 1 <= facts["experts_touched_mean"] <= 8
    step = facts["decode_step_bytes"]
    assert step["state"] == 2 * facts["cache_bytes"]["state"]
    assert step["total"] == pytest.approx(
        step["others"] + step["experts"] + step["state"] + step["cache"])


def test_the_real_manifest_finds_every_file_of_the_new_cell():
    m = Manifest()
    w = m.cell(REAL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "batch_closed_state_slots", 1)
    assert len(w["why"]) <= 200 and "4x" in w["why"]
    traffic = m.traffic(w["traffic"])
    assert traffic["kind"] == "closed_loop_ssm"
    assert os.path.isfile(m.find("kinds", traffic["kind"], (".py",)))
    assert os.path.isfile(m.find("references", w["config"], (".py",)))
    assert {e["name"] for e in m.metrics_for("end_to_end", w["name"])} \
        == {"serve_tok_s", "req_tok_ms_p50", "setup_s"}
    listed = {e["name"] for e in m.metrics_for("per_layer", w["name"])}
    for name in listed:
        assert os.path.isfile(m.find("layer_metrics", name, (".py",)))
    assert set(NEW) | {
        "engine_step_ms", "engine_occ_pct", "decode_dev_ms",
        "decode_bw_pct", "peak_hbm_gb.serve", "step_sample_ms",
        "step_self_ms", "setup_engine_s", "moe_gmm_ms",
        "moe_touched_pct"} <= listed
    # closed_forms_moe.gmm_bytes takes the expert's input as d_model
    # wide: the share stays off (PERF.md section 7)
    assert not {"moe_gmm_roofline", "moe_load_max_pct", "flash_win_ms",
                "mla_decode_ms", "mhc_decode_ms"} & listed
    for name in NEW:
        (entry,) = [e for e in m.doc["per_layer"] if e["name"] == name]
        assert entry["workloads"] == [REAL]
    (conf,) = [c for c in m.doc["configs"] if c["name"] == CONFIG]
    assert conf["file"] == "benchmarks/configs/%s.json" % CONFIG
    assert conf["reduced"] == m.config(CONFIG)["reduced"]
    # the limits of the contract: 24 cells, a quarter of them on 4 chips
    cells = m.doc["workloads"]
    assert 10 <= len(cells) <= 24 and 7 <= len(m.doc["configs"]) <= 24
    assert sum(1 for c in cells if c["chips"] == 4) \
        <= max(1, len(cells) // 4)
    assert len(m.doc["per_layer"]) <= 128
    # appended: behind everything PR 37 left
    names = [c["name"] for c in cells]
    assert names.index(REAL) > names.index("xing_serve_docs")
    metrics = [e["name"] for e in m.doc["per_layer"]]
    assert [n for n in metrics if n in NEW] == list(NEW)
    assert metrics.index(NEW[0]) > metrics.index("mhc_res_dev_max")


def test_the_configuration_holds_the_published_numbers():
    m = Manifest()
    cfg = m.config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(x) for x in f if x.strip()]
    (entry,) = [r for r in rows
                if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"]
    assert cfg["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and key in cfg["reduced_why"], key
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    # one whole period in its published order: layers 27-37
    pattern = entry["config"]["hybrid_override_pattern"]
    assert cfg["hybrid_override_pattern"] == pattern[27:38] == "MEMEMEMEM*E"
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["num_nextn_predict_layers"]) \
        == (11, 128, 32768, 0)
    assert cfg["published"]["chips_a_layer"] == 4
    for key in ("deployment", "assumed", "departures", "guarantees"):
        assert cfg[key]
    assert "previous tenant" in cfg["guarantees"] \
        and "company" in cfg["guarantees"]
    model = cfg["model"]
    kinds = {"M": "ssm", "*": "attention", "E": "experts"}
    assert model["mixers"] == [kinds[c] for c in "MEMEMEMEM*E"]
    c = entry["config"]
    assert (model["d_model"], model["n_head"], model["n_kv_head"],
            model["d_head"], model["ssm_heads"], model["ssm_head_dim"],
            model["ssm_groups"], model["ssm_state"], model["ssm_conv"],
            model["ssm_chunk"], model["d_expert"], model["d_expert_in"],
            model["d_shared_expert"], model["n_expert"],
            model["expert_top_k"], model["route_scale"],
            model["norm_eps"]) == (
        c["hidden_size"], c["num_attention_heads"],
        c["num_key_value_heads"], c["head_dim"], c["mamba_num_heads"],
        c["mamba_head_dim"], c["n_groups"], c["ssm_state_size"],
        c["conv_kernel"], c["chunk_size"], c["moe_intermediate_size"],
        c["moe_latent_size"], c["moe_shared_expert_intermediate_size"],
        c["n_routed_experts"], c["num_experts_per_tok"],
        c["routed_scaling_factor"], c["norm_eps"])
    assert model["ssm_heads"] * model["ssm_head_dim"] \
        == c["expand"] * c["hidden_size"]
    assert (model["n_expert_local"], model["vocab"], model["n_layer"],
            model["pos_emb"], model["ffn_act"], model["weight_dtype"]) == (
        128, 32768, 11, "none", "relu2", "bfloat16")
    assert cfg["serving"] == {"b_max": 96, "max_len": 2688}
    from paddle_tpu.models import gpt

    gpt._check_cfg(model)


def test_closed_forms_against_hand_counted_numbers():
    model = Manifest().config(CONFIG)["model"]
    c = closed_forms_ssm
    # ISSUE 40's reckoning: 109.64 M a state-space layer, 35.66 M of
    # attention, 54.53 M + 128 x 5.505 M an expert layer, 4,648 M in all
    assert c.ssm_matrix_params(model) == 4096 * 18_560 + 10_240 * 4 \
        + 8192 * 4096
    assert c.ssm_matrix_params(model) + c.ssm_vector_params(model) \
        == 109_617_152 + 18_816 == 109_635_968
    assert c.attention_params(model) == 35_651_584
    assert c.expert_params(model) == 2 * 1024 * 2688 == 5_505_024
    assert c.expert_layer_other_params(model) == 54_525_952
    assert round(c.param_count(model) / 1e6) == 4648
    assert round(c.matrix_params(model) * 2 / 1e9, 2) == 9.30
    # the uncut model: 120.67 B, 12.77 B of them active at top-22
    kinds = {"M": "ssm", "*": "attention", "E": "experts"}
    whole = dict(model, n_layer=88, vocab=131072, mixers=[
        kinds[x] for x in Manifest().config(CONFIG)["published"][
            "hybrid_override_pattern"]])
    assert round(c.param_count(whole, 512) / 1e9, 2) == 120.67
    assert round(c.param_count(whole, 22) / 1e9, 2) == 12.77
    # a slot: 5 x (128 x 64 x 128 + 3 x 10240) float32 = 21.6 MB
    assert c.state_values_per_slot(model) * 4 == 21_585_920
    assert c.state_bytes(model, 96) == 2_072_248_320
    assert c.slab_bytes(model, 96, 2688) == 96 * 2 * 2 * 2688 * 128 * 4
    assert round(c.static_bytes(model, 96, 2688, 4, 2) / 1e9, 1) == 11.9
    # the update of one layer over 96 slots: the state twice and the
    # token's operands
    assert c.update_bytes(model, 96) == 96 * 4 * (
        2 * 1_048_576 + 2 * 8192 + 2 * 1024 + 128)
    roof = c.update_roofline(model, 96, PEAKS)
    assert roof["bound"] == "memory"
    assert roof["bytes"] == 5 * c.update_bytes(model, 96)
    assert 0.0049 < roof["seconds"] < 0.0050
    # a scan of 2,048 positions: 16 chunks of 128
    per_pos = 128 * (2 * 128 * 64 + 4 * 128 * 64) + 8 * 2 * 128 * 128
    assert c.scan_flops(model, 2048) == 2048 * per_pos
    assert c.scan_flops(model, 2000) == c.scan_flops(model, 2048)
    assert c.scan_bytes(model, 2048) == 4 * (
        2048 * (2 * 8192 + 2048 + 128) + 1_048_576)
    roof = c.scan_roofline(model, 2048, PEAKS)
    assert roof["bound"] == "memory" and roof["flops"] == 5 * 2048 * per_pos
    # a decode step at 96 slots with 125 of 128 held experts touched:
    # ISSUE 40's 13.4 GB (7.0 experts, 4.1 state, 2.0 others, 0.5 slab)
    step = c.decode_step_bytes(model, 96, 2688, 4, 2, 125.0)
    assert step["experts"] == 5 * 125 * 5_505_024 * 2
    assert step["state"] == 2 * 2_072_248_320
    assert round(step["others"] / 1e9, 2) == 1.98
    assert round(step["total"] / 1e9, 1) == 13.5


def _reader(name):
    return load_path(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                  name + ".py"))


SSM_CFG = {"mixers": ["ssm", "experts"] * 2, "ssm_heads": 128,
           "ssm_head_dim": 64, "ssm_groups": 8, "ssm_state": 128,
           "ssm_conv": 4, "ssm_chunk": 128}


def _record(ops, steps=(), spans=()):
    return {
        "trace": {"ops": {0: ops}, "host_offset_s": 100.0, "t0": 100.0,
                  "t1": 110.0},
        "spans": {"serving.engine.step": list(steps)},
        "program_spans": [dict(ph="E", **s) for s in spans],
        "t_open": 0.0, "t_close": 10.0,
        "facts": {"longest_prompt": 2048, "window_s": 10.0, "b_max": 96,
                  "ssm": {"cfg": SSM_CFG, "itemsize": 4}},
        "counters": {"state_cache_bytes": 2_072_248_320},
        "peaks": PEAKS,
    }


def test_scan_readers_on_a_made_up_record():
    ops = []
    for i in range(2):                   # an admission of 2,048
        ops.append(("ssm_scan.%d" % i, 102.0 + 0.02 * i, 0.004))
    for i in range(2):                   # and one of 128
        ops.append(("ssm_scan.%d" % i, 104.0 + 0.001 * i, 0.0005))
    ops.append(("ssm_scan.9", 109.95, 0.01))   # its span leaves the stretch
    ops.append(("fusion.3", 102.005, 0.5))           # not this kernel
    ops.append(("ssm_update.1", 102.3, 0.2))         # nor this one
    spans = [dict(site="serving.engine.prefill", t=2.3, dur=0.35,
                  attrs={"prompt_len": 2048, "chunks": 16}),
             dict(site="serving.engine.prefill", t=4.1, dur=0.15,
                  attrs={"prompt_len": 128, "chunks": 1}),
             dict(site="serving.engine.prefill", t=10.2, dur=0.3,
                  attrs={"prompt_len": 512, "chunks": 4}),
             dict(site="serving.engine.splice", t=4.2, dur=0.01,
                  attrs={"slot": 3})]
    rec = _record(ops, spans=spans)
    # 9 ms of scans in a stretch of 10 s
    assert _reader("ssm_scan_ms").read(rec) == pytest.approx(0.9)
    least = sum(closed_forms_ssm.scan_roofline(SSM_CFG, T, PEAKS)["seconds"]
                for T in (2048, 128))
    share = _reader("ssm_scan_roofline").read(rec)
    assert share == pytest.approx(100.0 * least / 9e-3)
    assert 0 < share < 105
    # a composed plan has no operation under the kernel's name, and a
    # program from before this PR no facts.ssm: nothing read, none raised
    bare = _record([("fusion.1", 102.0, 0.1)], spans=spans)
    other = _record(ops, spans=spans)
    del other["facts"]["ssm"]
    for r in (bare, other, {"facts": {}}, {}):
        for name in ("ssm_scan_ms", "ssm_scan_roofline", "ssm_step_ms",
                     "ssm_step_roofline"):
            assert _reader(name).read(r) is None, name


def test_step_and_state_readers_on_a_made_up_record():
    ops = []
    for k in range(3):                   # three steps of two updates
        t = 101.0 + k
        for i in range(2):
            ops.append(("ssm_update.%d" % i, t + 0.002 * i, 0.0015))
        ops.append(("fusion.7", t + 0.03, 0.005))
    ops.append(("ssm_update.1", 108.5, 0.1))         # outside every step
    rec = _record(ops, steps=[(1.5 + k, 0.6) for k in range(3)])
    assert _reader("ssm_step_ms").read(rec) == pytest.approx(3.0)
    least = closed_forms_ssm.update_roofline(SSM_CFG, 96, PEAKS)["seconds"]
    share = _reader("ssm_step_roofline").read(rec)
    assert share == pytest.approx(100.0 * least / 3e-3)
    assert 60 < share < 70                # two layers: 1.98 ms at the peak
    assert _reader("ssm_state_gb").read(rec) == pytest.approx(2.07224832)
    assert _reader("ssm_state_gb").read({"counters": {}}) is None
    assert _reader("ssm_state_gb").read(
        {"counters": {"state_cache_bytes": 0}}) is None
    assert _reader("ssm_state_gb").read({}) is None


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
def test_every_block_of_the_traffic_holds_the_same_multiset(seed):
    m = Manifest()
    traffic = m.traffic("batch_closed_state_slots")
    assert (traffic["clients"], traffic["ramp_s"], traffic["probes"],
            traffic["think_time_s"], traffic["block"]) == (
        96, 20.0, 8, 0.0, 20)
    assert traffic["clients"] == m.config(CONFIG)["serving"]["b_max"]
    seq = closed_loop.sequence(traffic, seed, 200)
    prompts = Counter({128: 6, 512: 6, 1024: 5, 2048: 3})
    answers = Counter({128: 5, 256: 6, 384: 6, 512: 3})
    for lo in range(0, 200, 20):
        block = seq[lo:lo + 20]
        assert Counter(p for p, _ in block) == prompts
        assert Counter(n for _, n in block) == answers
    # every prompt a whole number of the scan's chunks, every answer a
    # multiple of 128 (what the primers are for)
    assert all(p % 128 == 0 and n % 128 == 0 for p, n in seq)
    assert max(p + n for p, n in seq) <= 2560 <= 2688
    assert sum(p for p, _ in seq[:20]) / 20 == 755.2
    assert sum(n for _, n in seq[:20]) / 20 == 300.8
    assert closed_loop.sequence(traffic, seed + 1, 200) != seq
    assert traffic["reference_probes_long"] <= traffic["reference_probes"]
    assert traffic["reference_long_over"] == 1024
    assert traffic["ssm_dt_range"] == [0.001, 0.1]   # time_step_min/max
    # every padded length the reference is compiled for
    pad = traffic["reference_pad_multiple"]
    assert {-(-(p + n) // pad) * pad for p, n in seq} \
        <= {512, 1024, 1536, 2048, 2560}
