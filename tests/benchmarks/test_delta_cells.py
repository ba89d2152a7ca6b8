"""The cell PR 53 adds, on the CPU: its rehearsal through
benchmarks/run.py with a tiny manifest that lives HERE, the closed forms
of a delta-rule layer beside gated attention and a share of the experts
against hand-counted numbers (and every roofline share they feed against
a hand count of what it may read), the five new readers on made-up
records, the configuration against the catalog, the traffic's blocks and
the chip sweep's rehearsal. The tiny cell's reference is the benchmark's
own file, loaded by path (tests/benchmarks/references/tiny-delta.py), and
that file is a bit-equal copy of tests/references/qwen3_next.py.

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

from benchmarks.lib import closed_forms_delta, closed_loop  # noqa: E402
from benchmarks.lib.manifest import Manifest, load_path  # noqa: E402

MANIFEST = "tests/benchmarks/BENCHMARK.tiny_delta.json"
TINY, CELL = "tiny_delta_serve_slots", "qwen3next_serve_slots"
CONFIG, TRAFFIC = "qwen3-next-80b-a3b", "batch_closed_delta_slots"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("delta_step_ms", "delta_step_roofline", "delta_scan_ms",
       "delta_scan_roofline", "delta_state_gb")
LISTED = ("engine_step_ms", "engine_occ_pct", "decode_dev_ms",
          "decode_bw_pct", "peak_hbm_gb.serve", "step_sample_ms",
          "step_self_ms", "setup_engine_s", "moe_gmm_ms",
          "moe_touched_pct", "kv_live_pct", "gqa_flash_ms",
          "gqa_flash_roofline", "decode_attn_ms", "decode_proj_ms",
          "decode_moe_ms", "decode_mixer_ms", "decode_norm_ms",
          "decode_head_ms", "decode_unscoped_ms",
          # the split of an admission: the prefill's join holds in this
          # cell (a traced run printed all seven, PERF.md section 5)
          "prefill_attn_ms", "prefill_proj_ms", "prefill_moe_ms",
          "prefill_mixer_ms", "prefill_norm_ms", "prefill_head_ms",
          "prefill_unscoped_ms")


def _checkout(tmp_path):
    """A checkout of symlinks (``test_mla_cells._checkout`` says why)."""
    root = tmp_path / "checkout"
    root.mkdir()
    for name in ("benchmarks", "paddle_tpu", "tests", "BENCHMARK.json"):
        os.symlink(os.path.join(ROOT, name), root / name)
    return str(root)


def _cpu_env(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.pop("PADDLE_TPU_FLASH_MIN_SEQ", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    return env


def test_rehearsal_of_the_new_cell(tmp_path):
    """One traced rehearsal: what a CPU can report (program spans and
    counters; the device-trace readers have no TPU plane), and the facts
    the readers and the judge go by."""
    env = _cpu_env(tmp_path)
    env["BENCH_RUN"] = "the driver sets this; the benchmark ignores it"
    proc = subprocess.run(
        ["nice", "-n", "19", sys.executable, "benchmarks/run.py",
         "--manifest", MANIFEST, "--cpu-rehearsal", "--workload", TINY,
         "--seed", str(2 ** 31 + 53053), "--seconds", "1", "--trace", "1"],
        cwd=_checkout(tmp_path), env=env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, "\n".join(
        x[:400] for x in proc.stderr.splitlines()
        if "cpu_aot_loader" not in x)[-3000:]
    out = [json.loads(x) for x in proc.stdout.strip().splitlines()
           if x.startswith("{")]
    rehearsal, last = out[-2], out[-1]
    assert last["correct"] is True and last["failed"] == 0, "\n".join(
        x[:600] for x in proc.stderr.splitlines() if "NOT CORRECT" in x)
    assert last["attempted"] >= 1 and last["metrics"] == {}
    assert rehearsal["rehearsal"] == "passed"
    assert set(rehearsal["would_report"]) == {
        "cache_miss_n", "compile_s", "engine_occ_pct", "engine_step_ms",
        "step_sample_ms", "step_self_ms", "delta_state_gb", "kv_live_pct",
        "moe_touched_pct"}
    facts = rehearsal["facts"]
    assert facts["reference_tokens_compared"] > 0
    # a CPU computes float32 exactly, so the system IS the reference up
    # to the order of its sums; the reference with bfloat16 activations
    # and a state rounded after every token is not: both decide `correct`
    assert facts["reference_mean_margin"] <= 1e-5
    assert facts["control_bf16_mean_margin"] > 1e-5
    assert facts["reference_probes_long"] == 1
    assert facts["longest_prompt"] == 40
    assert facts["primers"] == facts["clients"] == 4
    assert 0 < facts["tokens_made"] <= facts["decode_steps"] \
        * facts["b_max"] + facts["requests_in_window"]
    # three layers' state [4, 4, 16, 16] and rows [4, 3, 128], one
    # layer's slab pair [4, 2, 64, 32]: states and slabs in one lane
    held = 3 * 4 * (4 * 16 * 16 + 3 * 128) * 4
    assert facts["cache_bytes"] == {"state": held,
                                    "full": 4 * 2 * 2 * 64 * 32 * 4}
    cfg = Manifest(os.path.join(ROOT, MANIFEST)).config("tiny-delta")["model"]
    assert facts["weight_bytes"] == {
        "bfloat16": 2 * closed_forms_delta.matrix_params(cfg),
        "float32": 4 * closed_forms_delta.vector_params(cfg)}
    assert facts["static_bytes"] == sum(facts["weight_bytes"].values()) \
        + sum(facts["cache_bytes"].values())
    assert facts["delta_plans"]["delta_update composed chunk=1"] == 3
    assert facts["delta_plans"]["delta_scan composed chunk=40"] == 3
    assert facts["delta_chunks"]["chunks"] > 0
    assert facts["experts_held"] == 8
    assert 0 < facts["experts_touched_mean"] <= 8
    step = facts["decode_step_bytes"]
    assert step["state"] == 2 * held
    assert step["cache"] == facts["cache_bytes"]["full"]
    assert step["total"] == step["weights"] + step["state"] + step["cache"]


def test_the_real_manifest_finds_every_file_of_the_new_cell():
    m = Manifest()
    w = m.cell(CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    traffic = m.traffic(w["traffic"])
    assert traffic["kind"] == "closed_loop_delta"
    assert os.path.isfile(m.find("kinds", traffic["kind"], (".py",)))
    assert os.path.isfile(m.find("references", w["config"], (".py",)))
    assert {e["name"] for e in m.metrics_for("end_to_end", w["name"])} \
        == {"serve_tok_s", "req_tok_ms_p50", "setup_s"}
    listed = {e["name"] for e in m.metrics_for("per_layer", w["name"])}
    for name in listed:
        assert os.path.isfile(m.find("layer_metrics", name, (".py",)))
    assert set(NEW) | set(LISTED) <= listed
    # not the share whose bytes count every held expert where nine in ten
    # are touched (PERF.md section 7), nor another state-bearing kernel's
    assert not {"moe_gmm_roofline", "ssm_step_ms", "power_step_ms",
                "mla_decode_ms", "decode_ffn_ms"} & listed
    by_name = {e["name"]: e for e in m.doc["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] == (
            "decode engine" if name == "delta_state_gb"
            else "Pallas kernels")
    for name in LISTED:
        # appended behind the cells that were there, which keep their order
        cells = by_name[name]["workloads"]
        assert CELL in cells and all(
            cells.index(CELL) > cells.index(c) for c in cells
            if c in ("lfm2_serve_long_ctx", "brumby_serve_retention"))
    (entry,) = [c for c in m.doc["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["file"] == "benchmarks/configs/%s.json" % CONFIG
    # the limits of the contract: 24 cells, a quarter of them on 4 chips
    cells = m.doc["workloads"]
    assert len(cells) <= 24 and len(m.doc["configs"]) <= 24
    assert sum(1 for c in cells if c["chips"] == 4) \
        <= max(1, len(cells) // 4)
    names = [c["name"] for c in cells]
    assert names.index(CELL) > names.index("brumby_serve_retention")
    assert all(len(c["why"]) <= 200 for c in cells + m.doc["configs"])


def test_the_configuration_holds_the_published_numbers():
    m = Manifest()
    cfg = m.config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(x) for x in f if x.strip()]
    (entry,) = [r for r in rows
                if r["name"] == "Qwen3-Next-80B-A3B-Instruct"]
    assert cfg["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and key in cfg["reduced_why"], key
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (12, 64, 18992)
    assert 8 * 18992 == 151936 and 8 * 64 == 512
    for key in ("deployment", "assumed", "departures", "guarantees"):
        assert cfg[key]
    assert "eight chips share each layer" in cfg["deployment"]
    assert "32 chips" in cfg["deployment"]
    for line in ("linear layer", "full layer", "experts"):
        assert cfg["assumed"]["the layer"][line], line
    model = cfg["model"]
    pub = entry["config"]
    assert (model["d_model"], model["n_head"], model["n_kv_head"],
            model["d_head"], model["d_expert"], model["n_expert"],
            model["expert_top_k"], model["max_length"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"],
        pub["moe_intermediate_size"], pub["num_experts"],
        pub["num_experts_per_tok"], pub["max_position_embeddings"])
    assert (model["delta_k_heads"], model["delta_v_heads"],
            model["delta_k_dim"], model["delta_v_dim"]) == (
        pub["linear_num_key_heads"], pub["linear_num_value_heads"],
        pub["linear_key_head_dim"], pub["linear_value_head_dim"])
    assert model["rope_dim"] == pub["partial_rotary_factor"] \
        * pub["head_dim"] == 64
    assert model["layer_types"] == ["delta", "delta", "delta", "full"] * 3
    assert (model["n_layer"], model["vocab"], model["n_expert_local"]) \
        == (cfg["num_hidden_layers"], cfg["vocab_size"], cfg["num_experts"])
    assert model["n_shared_expert"] == 1 and model["shared_expert_gate"]
    assert model["attn_gate"] and model["qk_norm"] == "head"
    assert model["norm_topk"] is pub["norm_topk_prob"]
    assert model["tie_embeddings"] is pub["tie_word_embeddings"]
    assert model["weight_dtype"] == "bfloat16"
    assert model["rope_theta"] == pub["rope_theta"]
    assert model["norm_eps"] == pub["rms_norm_eps"]
    # taps, epsilon and chunk are the system's constants, not keys
    assert sorted(k for k in model if k.startswith("delta")) == [
        "delta_k_dim", "delta_k_heads", "delta_v_dim", "delta_v_heads"]
    assert cfg["serving"] == {"b_max": 128, "max_len": 2560}
    from paddle_tpu.kernels import delta
    from paddle_tpu.models import gpt

    gpt._check_cfg(model)
    assert gpt.state_layers(model) == [0, 1, 2, 4, 5, 6, 8, 9, 10]
    assert delta.CONV_TAPS == pub["linear_conv_kernel_dim"] \
        == closed_forms_delta.TAPS
    # every prompt of the mix is whole chunks
    traffic = m.traffic(TRAFFIC)
    assert all(int(p) % delta.scan_chunk(int(p)) == 0
               for p in traffic["prompt_lengths"])


def test_closed_forms_against_hand_counted_numbers():
    model = Manifest().config(CONFIG)["model"]
    c = closed_forms_delta
    # ISSUE 53's arithmetic: a linear layer, a full layer, what every
    # layer holds, an expert
    assert c.delta_matrix_params(model) == 2048 * 12288 + 2048 * 64 \
        + 4096 * 2048 == 33_685_504
    assert c.attention_params(model) == 2048 * 8192 + 2 * 2048 * 512 \
        + 4096 * 2048 == 27_262_976
    assert c.expert_params(model) == 3 * 2048 * 512 == 3_145_728
    assert c.moe_fixed_params(model) == 2048 * 512 + 3_145_728 + 2048
    assert c.matrix_params(model) == 2 * 18992 * 2048 + 9 * 33_685_504 \
        + 3 * 27_262_976 + 12 * (4_196_352 + 64 * 3_145_728) \
        == 2_929_025_024
    assert round(c.matrix_params(model) * 2 / 1e9, 2) == 5.86
    assert c.vector_params(model) == 25 * 2048 + 3 * 512 \
        + 9 * (8192 * 4 + 64 + 128)
    # the whole model: the published 80B
    whole = dict(model, n_layer=48, vocab=151936,
                 layer_types=["delta", "delta", "delta", "full"] * 12)
    del whole["n_expert_local"]
    assert round(c.param_count(whole) / 1e9, 1) == 79.7
    # a slot: 19.8 MB of state and rows whatever its length, 12,288 B of
    # slab a position
    assert c.state_values_per_slot(model) * 4 == 9 * 32 * 128 * 128 * 4 \
        == 9 * 2_097_152
    assert c.rows_values_per_slot(model) * 4 == 9 * 3 * 8192 * 4 \
        == 9 * 98_304
    assert c.state_bytes(model, 1) == 19_759_104
    assert c.slab_bytes_per_position(model) == 3 * 2 * 256 * 2 * 4 == 12_288
    assert c.slab_bytes(model, 1, 2560) == 31_457_280
    assert round(c.state_bytes(model, 128) / 1e9, 2) == 2.53
    assert round(c.slab_bytes(model, 128, 2560) / 1e9, 2) == 4.03
    assert round(c.static_bytes(model, 128, 2560, 4, 2) / 1e9, 1) == 12.4
    assert c.static_bytes(model, 128, 2560, 4, 2) / 16e9 > 0.75
    # 96 slots, ISSUE 53's fall-back: 10.8 GB
    assert round(c.static_bytes(model, 96, 2560, 4, 2) / 1e9, 1) == 10.8
    # a decode step: the touched experts, the states twice, the slabs
    step = c.decode_step_bytes(model, 128, 2560, 4, 2, 59.0)
    assert step["experts"] == 12 * 59 * 3_145_728 * 2
    assert step["state"] == 2 * 128 * 19_759_104
    assert step["cache"] == 128 * 31_457_280
    assert step["others"] == (c.matrix_params(model, 0)
                              - (18992 - 128) * 2048) * 2 \
        + c.vector_params(model) * 4
    assert step["total"] == step["others"] + step["experts"] \
        + step["state"] + step["cache"]
    assert 0.017 < step["total"] / 819e9 < 0.018
    # the chunked kernel's own products beside the count of the
    # mathematics (docs/KERNELS.md): ten [64, 64] products an inverse
    assert c.solve_products(64) == 10 and c.solve_products(128) == 12
    assert c.solve_products(2) == 0
    assert c.chunked_flops(model, 2048, 64) == 32 * (
        16 * 4 * 64 * 64 * 128
        + 32 * (4 * 64 * 128 * 128 + 10 * 2 * 64 ** 3
                + 4 * 64 * 64 * 128 + 2 * 64 * 128 * 128))
    assert c.chunked_flops(model, 2048, 64) > c.scan_flops(model, 2048)


def test_no_share_of_a_roofline_can_pass_its_hand_count():
    """What each share of the cell divides a measured time INTO, against
    a count by hand: the least seconds are what the shapes alone give, so
    a kernel at its peak reads 100% and nothing reads more — the padded
    tiles the update moves and the products the chunked scan adds to the
    recurrence are the kernels' own cost."""
    model = Manifest().config(CONFIG)["model"]
    c = closed_forms_delta
    up = c.update_roofline(model, 128, PEAKS)
    assert up["bytes"] == 9 * 128 * (2 * 32 * 128 * 128 + 2 * 16 * 128
                                     + 2 * 32 * 128 + 2 * 32) * 4
    assert up["flops"] == 9 * 128 * 32 * 7 * 128 * 128
    assert up["bound"] == "memory"
    assert up["seconds"] == pytest.approx(up["bytes"] / 819e9)
    assert 0.0059 < up["seconds"] < 0.0060
    # what the kernel really moves is more (rows [96, 128] and columns
    # [128, 128] a slot), so it cannot read over 100%
    moved = up["bytes"] + 9 * 128 * ((96 - 64 - 32) * 128 + 128 * 128) * 4
    assert moved > up["bytes"]
    # the scan: the token-by-token recurrence, 7 x 128 x 128 a token and
    # head, whatever chunk implements it
    for T in (256, 2048):
        sc = c.scan_roofline(model, T, PEAKS)
        assert sc["flops"] == 9 * T * 32 * 7 * 128 * 128
        assert sc["bytes"] == 9 * (T * (2 * 2048 + 2 * 4096 + 64)
                                   + 32 * 128 * 128) * 4
        assert sc["seconds"] == pytest.approx(max(sc["flops"] / 197e12,
                                                  sc["bytes"] / 819e9))
        # a chunked form does more than the count, never less
        for chunk in (32, 64, 128):
            assert 9 * c.chunked_flops(model, T, chunk) > sc["flops"]
    reader = _reader("decode_bw_pct")
    rec = {"facts": {"decode_step_bytes": c.decode_step_bytes(
        model, 128, 2560, 4, 2, 59.0)}, "peaks": PEAKS, "trace": None,
        "spans": {}}
    assert reader.read(rec) is None          # no device trace, no share


def _reader(name):
    return load_path(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                  name + ".py"))


def _record(model, ops, steps, prefills):
    """A made-up traced record: device operations ``(name, start, dur)``,
    step spans ``(end, dur)`` and finished prefill spans ``(end, dur,
    plen)``, all on one clock."""
    return {
        "facts": {"b_max": 128, "window_s": 10.0, "delta": {
            "cfg": {k: model[k] for k in (
                "n_layer", "layer_types", "delta_k_heads", "delta_k_dim",
                "delta_v_heads", "delta_v_dim")}, "itemsize": 4}},
        "peaks": PEAKS,
        "spans": {"serving.engine.step": steps},
        "trace": {"host_offset_s": 0.0, "t0": 0.0, "t1": 10.0,
                  "ops": {0: ops}},
        "program_spans": [
            {"ph": "E", "site": "serving.engine.prefill", "t": end,
             "dur": dur, "attrs": {"prompt_len": plen}}
            for end, dur, plen in prefills],
        "counters": {"delta_state_bytes": 2_529_165_312},
    }


def test_the_five_new_readers_on_made_up_records():
    model = Manifest().config(CONFIG)["model"]
    # two decode steps of nine updates of 1 ms; one admission of 2,048
    # with nine scans of 4 ms
    ops = []
    for s in (1.0, 2.0):
        ops += [("delta_update.%d" % i, s + 0.002 * i, 0.001)
                for i in range(9)]
    ops += [("delta_scan.%d" % i, 5.0 + 0.01 * i, 0.004) for i in range(9)]
    ops += [("fusion.1", 1.001, 0.001)]
    rec = _record(model, ops, [(1.03, 0.03), (2.03, 0.03)],
                  [(5.5, 0.5, 2048)])
    assert _reader("delta_state_gb").read(rec) == pytest.approx(2.5292, 1e-4)
    assert _reader("delta_step_ms").read(rec) == pytest.approx(9.0)
    share = _reader("delta_step_roofline").read(rec)
    assert share == pytest.approx(100 * closed_forms_delta.update_roofline(
        model, 128, PEAKS)["seconds"] / 0.009)
    assert 60 < share < 70
    # 36 ms of scans in a traced stretch of 10 s
    assert _reader("delta_scan_ms").read(rec) == pytest.approx(3.6)
    assert _reader("delta_scan_roofline").read(rec) == pytest.approx(
        100 * closed_forms_delta.scan_roofline(
            model, 2048, PEAKS)["seconds"] / 0.036)
    # a program from before this PR has no such kernel, span or gauge:
    # nothing is read and nothing raised (a parent's line leaves the
    # metrics out)
    for bare in ({"counters": {}}, {"counters": None}, {},
                 {"facts": {"power": {}}, "trace": None},
                 {"facts": {"delta": {}}, "trace": None, "spans": {}}):
        for name in NEW:
            assert _reader(name).read(bare) is None, name
    for name in NEW:
        mod = _reader(name)
        by_name = {e["name"]: e for e in Manifest().doc["per_layer"]}
        assert (mod.LAYER, mod.SOURCE, mod.MOVES, mod.UNIT) == tuple(
            by_name[name][k] for k in ("layer", "source", "moves", "unit"))


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5])
def test_every_block_of_the_traffic_holds_the_same_multiset(seed):
    m = Manifest()
    traffic = m.traffic(TRAFFIC)
    assert (traffic["clients"], traffic["ramp_s"], traffic["probes"],
            traffic["think_time_s"], traffic["block"]) == (
        128, 20.0, 8, 0.0, 20)
    seq = closed_loop.sequence(traffic, seed, 200)
    prompts = Counter({256: 5, 512: 6, 1024: 6, 2048: 3})
    answers = Counter({128: 5, 256: 6, 384: 6, 512: 3})
    for lo in range(0, 200, 20):
        block = seq[lo:lo + 20]
        assert Counter(p for p, _ in block) == prompts
        assert Counter(n for _, n in block) == answers
    assert sum(p * n for p, n in prompts.items()) / 20 == 832.0
    assert sum(a * n for a, n in answers.items()) / 20 == 300.8
    assert max(p + n for p, n in seq) <= 2560
    assert closed_loop.sequence(traffic, seed + 1, 200) != seq
    assert traffic["reference_probes"] == 64
    assert traffic["reference_probes_long"] == 16
    assert traffic["reference_long_over"] == 1024
    assert traffic["reference_router_gap_floor"] > 0
    assert traffic["delta_dt_range"] == [0.001, 0.1]
    assert traffic["delta_a_range"] == [1.0, 16.0]
    assert traffic["delta_tap_limit"] == 0.5 == 1 / 4 ** 0.5
    assert len(traffic["reference_why"]) > 400 and traffic["delta_why"]
    # every padded length the reference is compiled for
    pad = traffic["reference_pad_multiple"]
    assert {-(-(p + n) // pad) * pad for p, n in seq} \
        <= {512, 1024, 1536, 2048, 2560}
    # the primers: 128 + i new tokens for slot i, one step apart
    from benchmarks.kinds import closed_loop_delta

    class Engine:
        def submit(self, prompt, n_new):
            return (len(prompt), n_new)

    primers = closed_loop_delta.prime(Engine(), traffic, 18992, seed)
    assert primers == [(256, 128 + i) for i in range(128)]


def test_the_decays_parameters_are_drawn_where_the_model_puts_them():
    """``seeded_params`` on the tiny configuration: ``softplus(dt_b)`` in
    0.001-0.1, ``exp(a_log)`` in 1-16, the ``a`` columns of ``W_ba``
    within their limit and the ``b`` columns as drawn, the taps within
    0.5 and float32."""
    import numpy as np

    from benchmarks.kinds import closed_loop_delta, closed_loop_mla

    m = Manifest(os.path.join(ROOT, MANIFEST))
    conf = m.config("tiny-delta")
    traffic = m.traffic("tiny_batch_closed_delta_slots")
    cfg, serving = conf["model"], conf["serving"]
    got = closed_loop_delta.seeded_params(cfg, serving, traffic, 7)
    plain = closed_loop_mla.seeded_params(cfg, serving, 7)
    assert set(got) == set(plain)
    dt = np.log1p(np.exp(np.asarray(got["gpt_0_delta_dt_b"])))
    assert 0.001 <= dt.min() and dt.max() <= 0.1
    a = np.exp(np.asarray(got["gpt_1_delta_a_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    ba = np.asarray(got["gpt_2_delta_ba.w_0"].astype("float32"))
    assert np.abs(ba[:, 4:]).max() <= 0.005 < np.abs(ba[:, :4]).max()
    np.testing.assert_array_equal(
        ba[:, :4], np.asarray(plain["gpt_2_delta_ba.w_0"]
                              .astype("float32"))[:, :4])
    taps = got["gpt_0_delta_conv.w_0"]
    assert str(taps.dtype) == "float32" and taps.shape == (128, 4)
    assert 0.4 < float(abs(taps).max()) <= 0.5
    same = [n for n in got if "_delta_" not in n]
    assert all((np.asarray(got[n].astype("float32"))
                == np.asarray(plain[n].astype("float32"))).all()
               for n in same)


def test_the_benchmarks_reference_is_the_tests_reference_bit_for_bit():
    with open(os.path.join(ROOT, "tests", "references", "qwen3_next.py"),
              "rb") as f:
        mine = f.read()
    with open(os.path.join(ROOT, "benchmarks", "references",
                           CONFIG + ".py"), "rb") as f:
        assert f.read() == mine


def test_the_chip_sweep_rehearses(tmp_path):
    """tools/delta_sweep.py at a tiny shape in interpret mode: a scan row
    with the closed form's least time and its distance from the composed
    form on a mild and on a hard draw (repeating keys, ``beta`` near 1:
    the inverse by halves stays at rounding on both), the update's check
    row and its timed row."""
    out = tmp_path / "sweep.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "delta_sweep.py"),
         "--rehearse", "--reps", "1", "--out", str(out)],
        env=_cpu_env(tmp_path), capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = json.loads(out.read_text())["rows"]
    (scan,) = [r for r in rows if r.get("kernel") == "delta_scan"]
    assert scan["hard_y_max_abs"] < 1e-5 and scan["mild_y_max_abs"] < 1e-5
    assert scan["mild_state_max_abs"] < 1e-5
    assert all(r["least_ms"] > 0 and r["bound"] in ("compute", "memory")
               for r in rows if "kernel" in r)
    (check,) = [r for r in rows if "check" in r]
    assert check["update_state_max_abs"] < 1e-5
    assert rows[-1]["kernel"] == "delta_update"
    # and without a TPU it refuses to time anything
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "delta_sweep.py")],
        env=_cpu_env(tmp_path), capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and "times kernels on a TPU" in proc.stderr
