"""The cell PR 44 adds, on the CPU: its rehearsal through
benchmarks/run.py with a tiny manifest that lives HERE, the closed forms
of the gated convolution layers, the slab and the experts against
hand-counted numbers, the four new readers on made-up records, the
configuration against the catalog key by key, and the traffic's blocks.
The tiny cell's reference is the benchmark's own file, loaded by path
(tests/benchmarks/references/tiny-conv.py). The real manifest's entries
are looked up BY NAME and counts are pinned from below: a later PR
appends behind them."""

import json
import os
import subprocess
import sys
from collections import Counter

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import closed_forms_conv, closed_loop  # noqa: E402
from benchmarks.lib.manifest import Manifest, load_path  # noqa: E402

MANIFEST = "tests/benchmarks/BENCHMARK.tiny_conv.json"
CELL = "tiny_conv_serve_long_ctx"
REAL, CONFIG = "lfm2_serve_long_ctx", "lfm2-24b-a2b"
TRAFFIC = "batch_closed_long_ctx"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("gqa_flash_ms", "gqa_flash_roofline", "kv_live_pct",
       "conv_state_mb")


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
         "--seed", str(2 ** 31 + 44044), "--seconds", "1",
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
    # program spans and counters are read on a CPU too (the positions
    # counter and the carried rows' bytes among them); the device-trace
    # readers have no TPU plane there
    (1, {"cache_miss_n", "compile_s", "engine_occ_pct", "engine_step_ms",
         "step_sample_ms", "step_self_ms", "moe_touched_pct",
         "moe_load_max_pct", "kv_live_pct", "conv_state_mb"}),
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
    # to the order of its sums; the reference with bfloat16 activations,
    # carried rows and slab is not: both decide `correct`
    assert facts["reference_mean_margin"] <= 1e-5
    assert facts["control_bf16_mean_margin"] > 1e-5
    assert facts["reference_probes_long"] == 2
    assert facts["longest_prompt"] == 40
    assert facts["primers"] == facts["clients"] == 4
    assert 0 < facts["tokens_made"] <= facts["decode_steps"] \
        * facts["b_max"] + facts["requests_in_window"]
    # four convolution layers' two rows a slot, one attention slab
    cfg = Manifest(os.path.join(ROOT, MANIFEST)).config("tiny-conv")["model"]
    assert facts["cache_bytes"] == {
        "state": closed_forms_conv.rows_bytes(cfg, 4),
        "full": closed_forms_conv.slab_bytes(cfg, 4, 64)}
    assert facts["cache_bytes"]["state"] == 4 * 4 * 2 * 64 * 4
    assert facts["weight_bytes"] == {
        "bfloat16": 2 * closed_forms_conv.matrix_params(cfg),
        "float32": 4 * closed_forms_conv.vector_params(cfg)}
    assert facts["static_bytes"] == sum(facts["weight_bytes"].values()) \
        + sum(facts["cache_bytes"].values())
    assert facts["param_count"] == closed_forms_conv.param_count(cfg)
    # the window's decode steps each stood over b_max x max_len held
    # rows (the counter and the occupancy are read a step apart at most)
    seen = facts["positions"]
    assert abs(seen["held"] / (4 * 64) - facts["decode_steps"]) <= 2
    assert 0 < seen["live"] < seen["held"]
    assert facts["experts_held"] == 8
    assert 1 <= facts["experts_touched_mean"] <= 8
    step = facts["decode_step_bytes"]
    assert step["rows"] == 2 * facts["cache_bytes"]["state"]
    assert step["cache"] == facts["cache_bytes"]["full"]
    assert step["total"] == pytest.approx(
        step["others"] + step["experts"] + step["rows"] + step["cache"])


def test_the_real_manifest_finds_every_file_of_the_new_cell():
    m = Manifest()
    w = m.cell(REAL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(w["why"]) <= 200 and "64 clients" in w["why"]
    traffic = m.traffic(w["traffic"])
    assert traffic["kind"] == "closed_loop_conv"
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
        "moe_touched_pct", "moe_load_max_pct"} <= listed
    assert not {"flash_win_ms", "mla_decode_ms", "mla_flash_ms",
                "mhc_decode_ms", "ssm_step_ms", "ssm_state_gb"} & listed
    for name in NEW:
        (entry,) = [e for e in m.doc["per_layer"] if e["name"] == name]
        assert entry["workloads"][0] == REAL
    (conf,) = [c for c in m.doc["configs"] if c["name"] == CONFIG]
    assert conf["file"] == "benchmarks/configs/%s.json" % CONFIG
    assert conf["reduced"] == m.config(CONFIG)["reduced"]
    assert len(conf["why"]) <= 200
    # the limits of the contract: 24 cells, a quarter of them on 4 chips
    cells = m.doc["workloads"]
    assert 11 <= len(cells) <= 24 and 8 <= len(m.doc["configs"]) <= 24
    assert sum(1 for c in cells if c["chips"] == 4) \
        <= max(1, len(cells) // 4)
    assert len(m.doc["per_layer"]) <= 128
    # appended: behind everything PR 40 left
    names = [c["name"] for c in cells]
    assert names.index(REAL) > names.index("nemotron_serve_many")
    metrics = [e["name"] for e in m.doc["per_layer"]]
    assert [n for n in metrics if n in NEW] == list(NEW)
    assert metrics.index(NEW[0]) > metrics.index("ssm_state_gb")


def test_the_configuration_holds_the_published_numbers():
    m = Manifest()
    cfg = m.config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(x) for x in f if x.strip()]
    (entry,) = [r for r in rows if r["name"] == "LFM2-24B-A2B"]
    assert cfg["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and key in cfg["reduced_why"], key
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_dense_layers"]
    # published layers 1-5, in their published order
    pattern = entry["config"]["layer_types"]
    assert len(pattern) == 40 and pattern.count("conv") == 30
    assert cfg["layer_types"] == pattern[1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert pattern[2:] == ["full_attention", "conv", "conv", "conv"] * 9 \
        + ["full_attention", "conv"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["vocab_size"]) == (5, 1, 64, 65536)
    for key in ("deployment", "assumed", "departures", "guarantees"):
        assert cfg[key]
    assert "previous tenant" in cfg["guarantees"] \
        and "company" in cfg["guarantees"] \
        and "carried rows whole" in cfg["guarantees"]
    for key in ("head_dim", "tie_word_embeddings", "weights",
                "activations and caches", "serving.max_len"):
        assert cfg["assumed"][key], key
    model = cfg["model"]
    kinds = {"conv": "conv", "full_attention": "full"}
    assert model["layer_types"] == [kinds[k] for k in cfg["layer_types"]]
    c = entry["config"]
    assert (model["d_model"], model["n_head"], model["n_kv_head"],
            model["d_ff"], model["d_expert"], model["n_expert"],
            model["expert_top_k"], model["conv_taps"], model["norm_eps"],
            model["vocab"], model["max_length"], model["rope_theta"],
            model["norm_topk"], model["router_bias"]) == (
        c["hidden_size"], c["num_attention_heads"],
        c["num_key_value_heads"], c["intermediate_size"],
        c["moe_intermediate_size"], c["num_experts"],
        c["num_experts_per_tok"], c["conv_L_cache"], c["norm_eps"],
        c["vocab_size"], c["max_position_embeddings"],
        c["rope_parameters"]["rope_theta"], c["norm_topk_prob"],
        c["use_expert_bias"])
    assert c["conv_bias"] is False and c["routed_scaling_factor"] == 1 \
        and "route_scale" not in model
    assert model["d_head"] == c["hidden_size"] // c["num_attention_heads"]
    assert (model["n_layer"], model["n_dense_layer"], model["qk_norm"],
            model["tie_embeddings"], model["weight_dtype"],
            model["n_expert_local"], model["norm_topk_eps"]) == (
        5, 1, "head", True, "bfloat16", 64, 1e-6)
    assert cfg["serving"] == {"b_max": 64, "max_len": 16384 + 1024}
    from paddle_tpu.models import gpt

    gpt._check_cfg(model)


def test_closed_forms_against_hand_counted_numbers():
    conf = Manifest().config(CONFIG)
    model = conf["model"]
    c = closed_forms_conv
    # ISSUE 44's reckoning: 16.78 M a convolution operator, 10.49 M of
    # attention, 72.35 M of dense FFN, 9.437 M an expert, 134.22 M the
    # table; 2,700.6 M in all, 5.40 GB in bfloat16
    assert c.conv_matrix_params(model) == 4 * 2048 * 2048 == 16_777_216
    assert c.attention_params(model) == 2048 * 64 * (64 + 16) == 10_485_760
    assert c.dense_ffn_params(model) == 3 * 2048 * 11776 == 72_351_744
    assert c.expert_params(model) == 3 * 2048 * 1536 == 9_437_184
    assert c.matrix_params(model) == 65536 * 2048 + 4 * 16_777_216 \
        + 10_485_760 + 72_351_744 + 4 * (2048 * 64 + 64 * 9_437_184)
    assert c.vector_params(model) == 11 * 2048 + 2 * 64 + 4 * 2048 * 3 \
        + 4 * 64
    assert round(c.param_count(model) / 1e6, 1) == 2700.7
    assert c.param_count(model) - c.vector_params(model) == 2_700_607_488
    assert round(c.matrix_params(model) * 2 / 1e9, 2) == 5.40
    # the uncut model: 23.84 B, 2.33 B of them active at top-4
    kinds = {"conv": "conv", "full_attention": "full"}
    whole = dict(model, n_layer=40, n_dense_layer=2, layer_types=[
        kinds[k] for k in conf["published"]["layer_types"]])
    assert round(c.param_count(whole, 64) / 1e9, 2) == 23.84
    assert round(c.param_count(whole, 4) / 1e9, 2) == 2.33
    # a slot: 4 KB a position of the ONE attention layer, 16 KB a
    # convolution layer
    assert c.slab_bytes_per_position(model) == 2 * 8 * 64 * 4 == 4096
    assert c.rows_values_per_slot(model) * 4 == 4 * 16_384
    assert c.slab_bytes(model, 64, 17408) == 4_563_402_752
    assert c.rows_bytes(model, 64) == 4_194_304
    assert round(c.static_bytes(model, 64, 17408, 4, 2) / 1e9, 2) == 9.97
    # attention in all five layers would keep 20 KB a position: 22.8 GB
    every = dict(model, layer_types=["full"] * 5)
    assert round(c.slab_bytes(every, 64, 17408) / 1e9, 1) == 22.8
    # the flash forward of a 16,384-token prompt: 134 M causal pairs,
    # 1.1 TFLOP at 32 heads of 64, bound by compute
    roof = c.gqa_flash_roofline(model, 16384, 4, PEAKS)
    assert roof["layers"] == 1 and roof["pairs"] == 16384 * 16385 // 2
    assert roof["flops"] == roof["pairs"] * 4 * 64 * 32
    assert roof["bytes"] == 16384 * 64 * 4 * 2 * (32 + 8)
    assert roof["bound"] == "compute" and 0.0055 < roof["seconds"] < 0.0056
    # a decode step at 64 slots with 62.8 of 64 experts touched: ISSUE
    # 44's 9.9 GB (4.75 experts, 0.57 others, 4.56 slab, the rows twice)
    step = c.decode_step_bytes(model, 64, 17408, 4, 2, 62.8)
    assert step["experts"] == pytest.approx(4 * 62.8 * 9_437_184 * 2)
    assert step["rows"] == 2 * 4_194_304
    assert step["cache"] == 4_563_402_752
    assert round(step["others"] / 1e9, 2) == 0.57
    assert round(step["total"] / 1e9, 1) == 9.9


def _reader(name):
    return load_path(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                  name + ".py"))


GQA_CFG = {"n_layer": 5, "n_head": 32, "n_kv_head": 8, "d_head": 64,
           "layer_types": ["conv", "full", "conv", "conv", "conv"]}


def _record(ops, spans=()):
    return {
        "trace": {"ops": {0: ops}, "host_offset_s": 100.0, "t0": 100.0,
                  "t1": 110.0},
        "spans": {"serving.engine.step": []},
        "program_spans": [dict(ph="E", **s) for s in spans],
        "t_open": 0.0, "t_close": 10.0,
        "facts": {"longest_prompt": 16384, "window_s": 10.0, "b_max": 64,
                  "gqa_flash": {"cfg": GQA_CFG, "itemsize": 4}},
        "counters": {"state_cache_bytes": 4_194_304,
                     "positions": {"live": 300_000, "held": 1_114_112}},
        "peaks": PEAKS,
    }


def test_flash_readers_on_a_made_up_record():
    ops = [("flash_fwd.1", 102.1, 0.008),        # an admission of 16,384
           ("flash_fwd.1", 105.1, 0.010),        # another
           ("flash_fwd.1", 104.02, 0.001),       # one of 2,048
           ("flash_fwd_win.2", 102.15, 0.5),     # not this kernel
           ("fusion.3", 102.12, 0.5),            # nor this one
           ("flash_fwd.1", 109.95, 0.3)]         # its span leaves the stretch
    spans = [dict(site="serving.engine.prefill", t=2.3, dur=0.35,
                  attrs={"prompt_len": 16384, "state_layers": 4}),
             dict(site="serving.engine.prefill", t=5.3, dur=0.35,
                  attrs={"prompt_len": 16384, "state_layers": 4}),
             dict(site="serving.engine.prefill", t=4.1, dur=0.15,
                  attrs={"prompt_len": 2048, "state_layers": 4}),
             dict(site="serving.engine.prefill", t=10.2, dur=0.3,
                  attrs={"prompt_len": 16384, "state_layers": 4}),
             dict(site="serving.engine.splice", t=4.2, dur=0.01,
                  attrs={"slot": 3})]
    rec = _record(ops, spans=spans)
    assert _reader("gqa_flash_ms").read(rec) == pytest.approx(9.0)
    least = closed_forms_conv.gqa_flash_roofline(GQA_CFG, 16384, 4,
                                                 PEAKS)["seconds"]
    share = _reader("gqa_flash_roofline").read(rec)
    assert share == pytest.approx(100.0 * least / 9e-3)
    assert 60 < share < 65
    # a composed plan has no operation under the kernel's name, and a
    # program from before this PR no facts.gqa_flash: nothing read, none
    # raised
    bare = _record([("fusion.1", 102.0, 0.1)], spans=spans)
    other = _record(ops, spans=spans)
    del other["facts"]["gqa_flash"]
    for r in (bare, other, {"facts": {}}, {}):
        for name in ("gqa_flash_ms", "gqa_flash_roofline"):
            assert _reader(name).read(r) is None, name


def test_counter_readers_on_a_made_up_record():
    rec = _record([])
    assert _reader("kv_live_pct").read(rec) == pytest.approx(
        100.0 * 300_000 / 1_114_112)
    assert _reader("conv_state_mb").read(rec) == pytest.approx(4.194304)
    # the parent's program counts no positions and holds no such rows
    for r in ({"counters": {}}, {"counters": {"positions": None}},
              {"counters": {"positions": {}, "state_cache_bytes": 0}}, {}):
        assert _reader("kv_live_pct").read(r) is None
        assert _reader("conv_state_mb").read(r) is None


def test_the_window_tap_reads_the_counter_at_both_edges(monkeypatch):
    kind = load_path(os.path.join(ROOT, "benchmarks", "kinds",
                                  "closed_loop_conv.py"))

    class Ctx:
        trace = False

        def open_window(self):
            return 1.0

        def close_window(self):
            return 2.0

    readings = iter([{"live": 10, "held": 100}, {"live": 40, "held": 300}])
    monkeypatch.setattr(kind, "positions", lambda: next(readings))
    tap = kind.WindowTap(Ctx())
    assert tap.window_positions() is None
    assert (tap.open_window(), tap.close_window(), tap.trace) \
        == (1.0, 2.0, False)
    assert tap.window_positions() == {"live": 30, "held": 200}
    # a program without the counter
    monkeypatch.setattr(kind, "positions", dict)
    tap = kind.WindowTap(Ctx())
    tap.open_window(), tap.close_window()
    assert tap.window_positions() is None


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
def test_every_block_of_the_traffic_holds_the_same_multiset(seed):
    m = Manifest()
    traffic = m.traffic(TRAFFIC)
    assert (traffic["clients"], traffic["ramp_s"], traffic["think_time_s"],
            traffic["block"]) == (64, 30.0, 0.0, 20)
    assert traffic["clients"] == m.config(CONFIG)["serving"]["b_max"]
    seq = closed_loop.sequence(traffic, seed, 200)
    prompts = Counter({2048: 6, 4096: 6, 8192: 5, 16384: 3})
    answers = Counter({256: 5, 512: 6, 768: 6, 1024: 3})
    for lo in range(0, 200, 20):
        block = seq[lo:lo + 20]
        assert Counter(p for p, _ in block) == prompts
        assert Counter(n for _, n in block) == answers
    # every answer a multiple of 256 (what the primers are for)
    assert all(n % 256 == 0 for _, n in seq)
    assert max(p + n for p, n in seq) <= 17408 \
        == m.config(CONFIG)["serving"]["max_len"]
    assert sum(p for p, _ in seq[:20]) / 20 == 6348.8
    assert sum(n for _, n in seq[:20]) / 20 == 601.6
    assert closed_loop.sequence(traffic, seed + 1, 200) != seq
    assert traffic["reference_probes_long"] <= traffic["reference_probes"]
    assert traffic["reference_long_over"] == 8192
    assert traffic["conv_tap_limit"] == pytest.approx(3 ** -0.5)
    assert traffic["router_bias_limit"] == 0.01
    # every padded length the reference is compiled for
    pad = traffic["reference_pad_multiple"]
    assert {-(-(p + n) // pad) * pad for p, n in seq} \
        <= {3072, 5120, 9216, 17408}
    # the control has to land outside the limit it is held against
    assert 0 < traffic["reference_mean_margin_limit"] < 0.1
