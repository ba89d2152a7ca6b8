"""The cell PR 47 adds, on the CPU: its rehearsal through
benchmarks/run.py with a tiny manifest that lives HERE, the closed forms
of the shortcut layer against hand-counted numbers (and every roofline
share they feed against a hand count of what it may read), the two new
readers on made-up records, the configuration against the catalog, and
the traffic's blocks. The tiny cell's reference is the benchmark's own
file, loaded by path (tests/benchmarks/references/tiny-scmoe.py).

Written to stay green when later cells are appended: entries are found
by name, never by position or by a count."""

import json
import os
import subprocess
import sys
from collections import Counter

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import (closed_forms_mla, closed_forms_scmoe,  # noqa: E402
                            closed_loop)
from benchmarks.lib.manifest import Manifest, load_path  # noqa: E402

MANIFEST = "tests/benchmarks/BENCHMARK.tiny_scmoe.json"
TINY, CELL = "tiny_scmoe_serve_reason", "longcat_serve_reason"
CONFIG, TRAFFIC = "longcat-flash-omni", "batch_closed_shortcut_moe"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("moe_zero_pct", "moe_real_k_max")
LISTED = ("engine_step_ms", "engine_occ_pct", "decode_dev_ms",
          "decode_bw_pct", "peak_hbm_gb.serve", "step_sample_ms",
          "step_self_ms", "setup_engine_s", "moe_gmm_ms", "moe_touched_pct",
          "mla_decode_ms", "mla_decode_roofline", "mla_flash_ms",
          "mla_flash_roofline")


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
         "--manifest", MANIFEST, "--cpu-rehearsal", "--workload", TINY,
         "--seed", str(2 ** 31 + 47047), "--seconds", "1",
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
    # program spans and counters are read on a CPU too (the two new
    # tallies among them); the device-trace readers have no TPU plane
    (1, {"cache_miss_n", "compile_s", "engine_occ_pct", "engine_step_ms",
         "step_sample_ms", "step_self_ms", "moe_touched_pct",
         "moe_zero_pct", "moe_real_k_max"}),
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
    # is not: both decide `correct`
    assert facts["reference_mean_margin"] <= 1e-5
    assert facts["control_bf16_mean_margin"] > 1e-5
    assert facts["reference_probes_long"] == 2
    assert facts["longest_prompt"] == 40
    assert facts["primers"] == facts["clients"] == 4
    assert 0 < facts["tokens_made"] <= facts["decode_steps"] \
        * facts["b_max"] + facts["requests_in_window"]
    # TWO latent slabs a published layer: four of [4, 1, 64, 40] float32
    assert facts["cache_bytes"] == {"latent": 4 * 4 * 64 * 40 * 4}
    cfg = Manifest(os.path.join(ROOT, MANIFEST)).config("tiny-scmoe")["model"]
    assert facts["weight_bytes"] == {
        "bfloat16": 2 * closed_forms_scmoe.matrix_params(cfg),
        "float32": 4 * closed_forms_scmoe.vector_params(cfg)}
    assert facts["static_bytes"] == sum(facts["weight_bytes"].values()) \
        + facts["cache_bytes"]["latent"]
    # the attention calls of a step are the sub-layers', not the
    # published layers': what the two latent rooflines multiply by
    assert facts["mla"]["cfg"]["n_layer"] == 4
    assert facts["mla_plans"]["absorbed composed - 40x32"] == 4
    # a branch routes b_max x top_k pairs a step, identity pairs among
    # them: both tallies together count the steps
    pairs = facts["routed_pairs_total"] + facts["zero_pairs_total"]
    assert pairs == facts["steps_tallied"] * 2 * 4 * 6
    assert facts["zero_pairs_pct"] == pytest.approx(
        100.0 * facts["zero_pairs_total"] / pairs)
    assert 15 < facts["zero_pairs_pct"] < 60         # even share: 8 of 24
    assert 1 <= facts["real_experts_max"] <= 6
    assert facts["experts_held"] == 4
    assert 0 < facts["experts_touched_mean"] <= 4
    step = facts["decode_step_bytes"]
    assert step["total"] == pytest.approx(
        step["weights"] + step["experts"] + step["cache"])
    assert step["experts"] == pytest.approx(
        2 * facts["experts_touched_mean"] * 3 * 128 * 24 * 2)


def test_the_real_manifest_finds_every_file_of_the_new_cell():
    m = Manifest()
    w = m.cell(CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    traffic = m.traffic(w["traffic"])
    assert traffic["kind"] == "closed_loop_scmoe"
    assert os.path.isfile(m.find("kinds", traffic["kind"], (".py",)))
    assert os.path.isfile(m.find("references", w["config"], (".py",)))
    assert {e["name"] for e in m.metrics_for("end_to_end", w["name"])} \
        == {"serve_tok_s", "req_tok_ms_p50", "setup_s"}
    listed = {e["name"] for e in m.metrics_for("per_layer", w["name"])}
    for name in listed:
        assert os.path.isfile(m.find("layer_metrics", name, (".py",)))
    assert set(NEW) | set(LISTED) <= listed
    # moe_gmm_roofline's bytes count every held expert and 40% are
    # touched; moe_load_max_pct reads a whole layer's load: both stay off
    assert not {"moe_gmm_roofline", "moe_load_max_pct", "flash_win_ms",
                "mhc_roofline", "ssm_step_ms", "gqa_flash_ms"} & listed
    by_name = {e["name"]: e for e in m.doc["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] == "expert routing"
        assert by_name[name]["source"] == "program_counter"
    for name in LISTED:
        # appended behind the cells that were there, which keep their order
        cells = by_name[name]["workloads"]
        assert CELL in cells and cells.index(CELL) > cells.index(
            "pangu_serve_reason" if name.startswith("mla_")
            else "lfm2_serve_long_ctx")
    (entry,) = [c for c in m.doc["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["file"] == "benchmarks/configs/%s.json" % CONFIG
    # the limits of the contract: 24 cells, a quarter of them on 4 chips
    cells = m.doc["workloads"]
    assert len(cells) <= 24 and len(m.doc["configs"]) <= 24
    assert sum(1 for c in cells if c["chips"] == 4) \
        <= max(1, len(cells) // 4)
    names = [c["name"] for c in cells]
    assert names.index(CELL) > names.index("lfm2_serve_long_ctx")
    assert all(len(c["why"]) <= 200 for c in cells + m.doc["configs"])


def test_the_configuration_holds_the_published_numbers():
    m = Manifest()
    cfg = m.config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(x) for x in f if x.strip()]
    (entry,) = [r for r in rows if r["name"] == "LongCat-Flash-Omni"]
    assert cfg["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and key in cfg["reduced_why"], key
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert (cfg["num_layers"], cfg["n_routed_experts"],
            cfg["n_routed_experts_published"], cfg["vocab_size"]) == (
        4, 8, 512, 16384)
    for key in ("deployment", "assumed", "departures", "guarantees"):
        assert cfg[key]
    assert "64 chips" in cfg["deployment"] and "8 chips" in cfg["deployment"]
    model = cfg["model"]
    assert (model["d_model"], model["n_head"], model["q_lora_rank"],
            model["kv_lora_rank"], model["d_nope"], model["d_rope"],
            model["d_v"], model["d_ff"], model["d_expert"],
            model["n_expert"], model["n_zero_expert"],
            model["n_expert_local"], model["expert_top_k"],
            model["route_scale"], model["vocab"], model["n_layer"]) == (
        6144, 64, 1536, 512, 128, 64, 128, 12288, 2048, 512, 256, 8, 12,
        6.0, 16384, 2 * cfg["num_layers"])
    assert model["n_expert"] + model["n_zero_expert"] == 768
    assert model["mla_scale_q_lora"] is model["mla_scale_kv_lora"] is True
    assert model["shortcut_moe"] is True and model["norm_topk"] is False
    assert model["router_score"] == "softmax" and model["router_bias"]
    assert model["weight_dtype"] == "bfloat16"
    assert model["rope_theta"] == entry["config"]["rope_theta"]
    assert cfg["serving"] == {"b_max": 32, "max_len": 4096}
    from paddle_tpu.models import gpt

    gpt._check_cfg(model)
    assert gpt.expert_rows(model) == cfg["num_layers"]


def test_closed_forms_against_hand_counted_numbers():
    model = Manifest().config(CONFIG)["model"]
    c = closed_forms_scmoe
    # ISSUE 47's table: an attention, a dense SwiGLU, a router, a layer
    # outside its experts, an expert
    assert c.attention_matrix_params(model) == 6144 * 1536 \
        + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256 + 64 * 128 * 6144 \
        == 90_570_752
    assert c.dense_params(model) == 3 * 6144 * 12288 == 226_492_416
    assert c.router_width(model) == 768
    assert c.published_layer_params(model) == 638_844_928
    assert c.expert_params(model) == 3 * 6144 * 2048 == 37_748_736
    assert c.branches(model) == 4 and c.held_experts(model) == 8
    assert c.matrix_params(model) == 2 * 16384 * 6144 + 4 * (
        638_844_928 + 8 * 37_748_736) == 3_964_665_856
    # a sub-layer: two block norms and two latent norms; a branch: the
    # selection term; the final norm
    assert c.vector_params(model) == 6144 + 8 * (2 * 6144 + 1536 + 512) \
        + 4 * 768 == 123_904
    assert round(c.matrix_params(model) * 2 / 1e9, 2) == 7.93
    assert c.cache_bytes(model, 32, 4096, 4) == 32 * 4096 * 8 * 576 * 4 \
        == 2_415_919_104
    assert round(c.static_bytes(model, 32, 4096, 4, 2) / 1e9, 2) == 10.35
    # a decode step: everything but the table and the experts once, the
    # touched experts, the visible rows of all eight slabs
    step = c.decode_step_bytes(model, 32, 4096, 4, 2, 3.2, 32 * 1500)
    assert step["attention"] == 8 * 90_570_752 * 2
    assert step["others"] == (8 * 226_492_416 + 4 * 6144 * 768
                              + 16384 * 6144) * 2 + 123_904 * 4
    assert round(step["weights"] / 1e9, 2) == 5.31
    assert step["experts"] == pytest.approx(4 * 3.2 * 37_748_736 * 2)
    assert step["cache"] == 48_000 * 8 * 576 * 4
    assert step["total"] == pytest.approx(
        step["weights"] + step["experts"] + step["cache"])
    assert round(c.prefill_flops_per_token(model, 0.125) / 1e9, 2) == 5.15


def test_no_share_of_a_roofline_can_pass_its_hand_count():
    """What each share of the cell divides a measured time INTO, against
    a count by hand: the least seconds are what the shapes alone give,
    with the attention calls of a step counted once each (8, not 4 and
    not 16), so a kernel at its peak reads 100% and nothing reads more."""
    model = Manifest().config(CONFIG)["model"]
    mla_cfg = {k: model[k] for k in ("n_layer", "n_head", "kv_lora_rank",
                                     "d_nope", "d_rope", "d_v")}
    rows = 32 * 1500
    # the absorbed kernel: per visible row and head 2 x (576 + 512)
    # operations; the row's 576 float32 values read once; 8 calls a step
    dec = closed_forms_mla.mla_decode_roofline(mla_cfg, rows, 4, PEAKS)
    assert dec["flops"] == 8 * rows * 64 * 2 * (576 + 512)
    assert dec["bytes"] == 8 * rows * 576 * 4
    assert dec["bytes"] == closed_forms_scmoe.decode_step_bytes(
        model, 32, 4096, 4, 2, 3.2, rows)["cache"]
    assert dec["bound"] == "memory"
    assert dec["seconds"] == pytest.approx(884_736_000 / 819e9)
    # the kernel cannot run faster than its bytes at the HBM peak: a
    # measured time of exactly that reads 100%
    assert 100.0 * dec["seconds"] / (dec["bytes"] / 819e9) == 100.0
    # the expanded form: causal pairs x 2 x (192 + 128) x 64 heads, 8 calls
    fl = closed_forms_mla.mla_flash_roofline(mla_cfg, 3328, 4, PEAKS)
    assert fl["pairs"] == 3328 * 3329 // 2
    assert fl["flops"] == 8 * fl["pairs"] * 64 * 2 * 320
    assert fl["bound"] == "compute"
    assert fl["seconds"] == pytest.approx(fl["flops"] / 197e12)
    assert 0.0091 < fl["seconds"] < 0.0093
    # decode_bw_pct: the step's bytes over the HBM peak, 8.7 ms at 3.2
    # touched experts a branch and 1,500 visible rows a slot
    step = closed_forms_scmoe.decode_step_bytes(model, 32, 4096, 4, 2, 3.2,
                                                rows)
    assert 0.0080 < step["total"] / 819e9 < 0.0090
    # and no byte is counted twice: the parts are disjoint and add up
    assert step["total"] == step["attention"] + step["others"] \
        + step["experts"] + step["cache"]
    reader = _reader("decode_bw_pct")
    rec = {"facts": {"decode_step_bytes": step}, "peaks": PEAKS,
           "trace": None, "spans": {}}
    assert reader.read(rec) is None          # no device trace, no share


def _reader(name):
    return load_path(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                  name + ".py"))


def test_the_two_new_readers_on_made_up_records():
    rec = {"counters": {"zero_pairs_pct": 33.1, "real_experts_max": 12,
                        "zero_pairs": [10, 12, 9, 11]}}
    assert _reader("moe_zero_pct").read(rec) == 33.1
    assert _reader("moe_real_k_max").read(rec) == 12
    # a program from before this PR has no such tally: nothing is read
    # and nothing raised (the parent's line leaves the metrics out)
    for bare in ({"counters": {"routed_pairs": [[1, 2]]}},
                 {"counters": {}}, {"counters": None}, {}):
        for name in NEW:
            assert _reader(name).read(bare) is None
    for name in NEW:
        mod = _reader(name)
        assert (mod.LAYER, mod.SOURCE, mod.MOVES) == (
            "expert routing", "program_counter", "req_tok_ms_p50")


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
def test_every_block_of_the_traffic_holds_the_same_multiset(seed):
    m = Manifest()
    traffic = m.traffic(TRAFFIC)
    assert (traffic["clients"], traffic["ramp_s"], traffic["probes"],
            traffic["think_time_s"], traffic["trace_seconds"]) == (
        32, 20.0, 8, 0.0, 10.0)
    pangu = m.traffic("batch_closed_long_answers")  # pangu_serve_reason's
    assert traffic["prompt_lengths"] == pangu["prompt_lengths"]
    assert traffic["output_lengths"] == pangu["output_lengths"]
    assert traffic["block"] == pangu["block"] == 20
    seq = closed_loop.sequence(traffic, seed, 200)
    prompts = Counter({128: 6, 512: 6, 1024: 5, 3328: 3})
    answers = Counter({128: 5, 256: 6, 512: 6, 768: 3})
    for lo in range(0, 200, 20):
        block = seq[lo:lo + 20]
        assert Counter(p for p, _ in block) == prompts
        assert Counter(n for _, n in block) == answers
    assert max(p + n for p, n in seq) <= 4096
    assert closed_loop.sequence(traffic, seed + 1, 200) != seq
    assert traffic["reference_probes"] == 64
    assert traffic["reference_probes_long"] == 12
    assert 0 < traffic["router_bias_limit"] < 0.01
    assert 0 < traffic["reference_router_gap_floor"]
    assert len(traffic["reference_why"]) > 400
    # every padded length the reference is compiled for
    pad = traffic["reference_pad_multiple"]
    assert {-(-(p + n) // pad) * pad for p, n in seq} \
        <= {1024, 2048, 3072, 4096}
    # the primers: 128 + 4 i new tokens for slot i
    from benchmarks.kinds import closed_loop_scmoe

    class Engine:
        def submit(self, prompt, n_new):
            return (len(prompt), n_new)

    primers = closed_loop_scmoe.prime(Engine(), traffic, 16384, seed)
    assert primers == [(128, 128 + 4 * i) for i in range(32)]
