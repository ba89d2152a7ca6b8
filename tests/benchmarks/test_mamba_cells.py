"""The cell PR 58 adds, on the CPU: its rehearsal through
benchmarks/run.py with a tiny manifest that lives HERE, the closed forms
of a Mamba-1 layer beside multi-query attention and a dense FFN against
hand-counted numbers (and every roofline share they feed against a hand
count of what it may read), the six new readers on made-up records, the
configuration against the catalog, the traffic's blocks and the chip
sweep's rehearsal. The tiny cell's reference is the benchmark's own file,
loaded by path (tests/benchmarks/references/tiny-mamba.py), and that file
is a bit-equal copy of tests/references/jamba.py.

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

from benchmarks.lib import closed_forms_mamba, closed_loop  # noqa: E402
from benchmarks.lib.manifest import Manifest, load_path  # noqa: E402

MANIFEST = "tests/benchmarks/BENCHMARK.tiny_mamba.json"
TINY, CELL = "tiny_mamba_serve_docs", "jamba2_serve_docs"
CONFIG, TRAFFIC = "ai21-jamba2-3b", "batch_closed_long_docs"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("mamba_step_ms", "mamba_step_roofline", "mamba_scan_ms",
       "mamba_scan_roofline", "mamba_state_gb", "prefill_ffn_ms")
LISTED = ("engine_step_ms", "decode_dev_ms", "decode_bw_pct",
          "step_sample_ms", "step_self_ms", "setup_engine_s", "kv_live_pct",
          "decode_attn_ms", "decode_proj_ms", "decode_ffn_ms",
          "decode_mixer_ms", "decode_norm_ms", "decode_head_ms",
          "decode_unscoped_ms",
          # the accepted metrics that move ``serve_tok_s``: a traced run of
          # the cell prints every one (PERF.md section 5)
          "engine_occ_pct", "peak_hbm_gb.serve", "gqa_flash_ms",
          "gqa_flash_roofline", "prefill_attn_ms", "prefill_proj_ms",
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
         "--seed", str(2 ** 31 + 58058), "--seconds", "1", "--trace", "1"],
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
        "cache_miss_n", "compile_s", "engine_step_ms", "engine_occ_pct",
        "step_sample_ms", "step_self_ms", "mamba_state_gb", "kv_live_pct"}
    facts = rehearsal["facts"]
    # no router: every generated token is compared
    assert facts["reference_tokens_compared"] > 0
    assert facts["reference_tokens_near_tied"] == 0
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
    # five layers' state [4, 1, 8, 128] and rows [4, 3, 128], one layer's
    # slab pair [4, 1, 64, 16]: states and slabs in one lane
    held = 5 * 4 * (8 * 128 + 3 * 128) * 4
    assert facts["cache_bytes"] == {"state": held,
                                    "full": 4 * 2 * 1 * 64 * 16 * 4}
    cfg = Manifest(os.path.join(ROOT, MANIFEST)).config("tiny-mamba")["model"]
    assert facts["weight_bytes"] == {
        "bfloat16": 2 * closed_forms_mamba.matrix_params(cfg),
        "float32": 4 * closed_forms_mamba.vector_params(cfg)}
    assert facts["static_bytes"] == sum(facts["weight_bytes"].values()) \
        + sum(facts["cache_bytes"].values())
    assert facts["param_count"] == closed_forms_mamba.param_count(cfg)
    assert facts["mamba_plans"]["mamba_update composed block=1"] == 5
    assert facts["mamba_plans"]["mamba_scan composed block=40"] == 5
    assert facts["mamba_chunks"]["chunks"] > 0
    step = facts["decode_step_bytes"]
    assert step["state"] == 2 * held
    assert step["cache"] == facts["cache_bytes"]["full"]
    assert step["total"] == step["weights"] + step["state"] + step["cache"]


def test_the_real_manifest_finds_every_file_of_the_new_cell():
    m = Manifest()
    w = m.cell(CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    traffic = m.traffic(w["traffic"])
    assert traffic["kind"] == "closed_loop_mamba"
    assert os.path.isfile(m.find("kinds", traffic["kind"], (".py",)))
    assert os.path.isfile(m.find("references", w["config"], (".py",)))
    # tokens per chip-second at a per-token latency, as ISSUE 58 has it:
    # nine tenths of this cell's window is admissions, so its tokens a
    # second swing with how many long ones a 45 s window catches (a
    # standard deviation of 2.5% over my chip runs: PERF.md section 6);
    # that is the cell's own result and the check's to judge
    assert {e["name"] for e in m.metrics_for("end_to_end", w["name"])} \
        == {"serve_tok_s", "req_tok_ms_p50", "setup_s"}
    listed = {e["name"] for e in m.metrics_for("per_layer", w["name"])}
    for name in listed:
        assert os.path.isfile(m.find("layer_metrics", name, (".py",)))
    assert set(NEW) | set(LISTED) <= listed
    moved = {e["name"]: e["moves"] for e in m.doc["per_layer"]}
    assert {moved[n] for n in listed} == {"serve_tok_s", "req_tok_ms_p50",
                                          "setup_s"}
    assert {n: moved[n] for n in NEW} == {
        "mamba_step_ms": "req_tok_ms_p50",
        "mamba_step_roofline": "req_tok_ms_p50",
        "mamba_scan_ms": "serve_tok_s", "mamba_scan_roofline": "serve_tok_s",
        "mamba_state_gb": "serve_tok_s", "prefill_ffn_ms": "serve_tok_s"}
    # no experts, and no other state-bearing kernel's readers
    assert not {"moe_gmm_ms", "moe_touched_pct", "decode_moe_ms",
                "ssm_step_ms", "ssm_scan_ms", "delta_step_ms",
                "power_step_ms", "mla_decode_ms", "conv_state_mb"} & listed
    by_name = {e["name"]: e for e in m.doc["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
    assert {n: by_name[n]["layer"] for n in NEW} == {
        "mamba_step_ms": "Pallas kernels",
        "mamba_step_roofline": "Pallas kernels",
        "mamba_scan_ms": "Pallas kernels",
        "mamba_scan_roofline": "Pallas kernels",
        "mamba_state_gb": "decode engine",
        "prefill_ffn_ms": "model step on the device"}
    for name in LISTED:
        # appended behind the cells that were there, which keep their order
        cells = by_name[name]["workloads"]
        assert CELL in cells and all(
            cells.index(CELL) > cells.index(c) for c in cells
            if c in ("lfm2_serve_long_ctx", "brumby_serve_retention",
                     "qwen3next_serve_slots"))
    # nemotron's readers read what they read: its cell alone
    for name in ("ssm_step_ms", "ssm_scan_ms", "ssm_scan_roofline"):
        assert by_name[name]["workloads"] == ["nemotron_serve_many"]
    (entry,) = [c for c in m.doc["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == []
    assert entry["file"] == "benchmarks/configs/%s.json" % CONFIG
    # the limits of the contract: 24 cells, a quarter of them on 4 chips
    cells = m.doc["workloads"]
    assert len(cells) <= 24 and len(m.doc["configs"]) <= 24
    assert sum(1 for c in cells if c["chips"] == 4) \
        <= max(1, len(cells) // 4)
    names = [c["name"] for c in cells]
    assert names.index(CELL) > names.index("qwen3next_serve_slots")
    assert all(len(c["why"]) <= 200 for c in cells + m.doc["configs"])
    for word in ("closed loop", "32 clients", "2,048-16,384", "64-256",
                 "token by token", "26 Mamba-1", "no matrix form"):
        assert word in w["why"], word


def test_the_configuration_holds_the_published_numbers():
    m = Manifest()
    cfg = m.config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(x) for x in f if x.strip()]
    (entry,) = [r for r in rows if r["name"] == "AI21-Jamba2-3B"]
    assert cfg["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == [] and cfg["reduced_why"] == {}
    assert (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["intermediate_size"], cfg["mamba_d_state"],
            cfg["mamba_dt_rank"], cfg["mamba_d_conv"], cfg["vocab_size"],
            cfg["tie_word_embeddings"]) == (
        28, 2560, 20, 1, 8192, 16, 160, 4, 65536, True)
    for key in ("deployment", "assumed", "departures", "guarantees",
                "published"):
        assert cfg[key]
    assert "one chip holds the whole model: a replica" in cfg["deployment"]
    for line in ("layer order", "every layer", "mamba layer",
                 "attention layer", "weights", "serving.b_max",
                 "serving.max_len"):
        assert cfg["assumed"][line], line
    assert "i mod attn_layer_period (14) == attn_layer_offset (7)" \
        in cfg["assumed"]["layer order"]
    assert "[slots, 1, 16, 5120]" in " ".join(cfg["departures"])
    model = cfg["model"]
    pub = entry["config"]
    assert (model["d_model"], model["n_head"], model["n_kv_head"],
            model["d_head"], model["d_ff"], model["n_layer"],
            model["vocab"], model["max_length"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"],
        pub["hidden_size"] // pub["num_attention_heads"],
        pub["intermediate_size"], pub["num_hidden_layers"],
        pub["vocab_size"], pub["max_position_embeddings"])
    assert (model["mamba_inner"], model["mamba_state"],
            model["mamba_dt_rank"], model["ssm_conv"]) == (
        pub["mamba_expand"] * pub["hidden_size"], pub["mamba_d_state"],
        pub["mamba_dt_rank"], pub["mamba_d_conv"]) == (5120, 16, 160, 4)
    assert model["layer_types"] == [
        "full" if i % pub["attn_layer_period"] == pub["attn_layer_offset"]
        else "mamba" for i in range(28)]
    assert [i for i, t in enumerate(model["layer_types"]) if t == "full"] \
        == [7, 21]
    assert model["pos_emb"] == "none"
    assert model["tie_embeddings"] is pub["tie_word_embeddings"]
    assert model["weight_dtype"] == "bfloat16"
    assert model["norm_eps"] == pub["rms_norm_eps"]
    assert sorted(k for k in model if k.startswith("mamba")) == [
        "mamba_dt_rank", "mamba_inner", "mamba_state"]
    assert model["ssm_conv"] == 4         # the taps: Nemotron's key
    assert cfg["serving"] == {"b_max": 32, "max_len": 16640}
    from paddle_tpu.kernels import mamba
    from paddle_tpu.models import gpt

    gpt._check_cfg(model)
    assert gpt.state_layers(model) == [i for i in range(28)
                                       if i not in (7, 21)]
    # every prompt of the mix is whole blocks, and the longest request
    # fits the slab
    traffic = m.traffic(TRAFFIC)
    assert all(int(p) % mamba.scan_block(int(p)) == 0
               for p in traffic["prompt_lengths"])
    assert max(map(int, traffic["prompt_lengths"])) \
        + max(map(int, traffic["output_lengths"])) \
        <= cfg["serving"]["max_len"] == 130 * 128


def test_closed_forms_against_hand_counted_numbers():
    model = Manifest().config(CONFIG)["model"]
    c = closed_forms_mamba
    # ISSUE 58's arithmetic: a mamba mixer, the dense FFN, an attention
    # mixer, the table
    assert c.mamba_matrix_params(model) == 2560 * 10240 + 5120 * 192 \
        + 160 * 5120 + 5120 * 2560 == 41_123_840
    assert c.mamba_vector_params(model) == 5120 * 4 + 5120 + 5120 * 16 \
        + 2 * 5120 + 160 + 32 == 117_952
    assert c.ffn_params(model) == 3 * 2560 * 8192 == 62_914_560
    assert c.attention_params(model) == 2 * 2560 * 2560 + 2 * 2560 * 128 \
        == 13_762_560
    assert c.matrix_params(model) == 65536 * 2560 + 26 * 41_123_840 \
        + 2 * 13_762_560 + 28 * 62_914_560 == 3_026_124_800
    assert c.vector_params(model) == 57 * 2560 + 26 * 117_952
    # the whole model: the published 3B, 6.06 GB in bfloat16
    assert round(c.param_count(model) / 1e9, 2) == 3.03
    assert round(c.matrix_params(model) * 2 / 1e9, 2) == 6.05
    # a slot: 10.1 MB of state and rows whatever its length, 2,048 B of
    # slab a position
    assert c.state_values_per_slot(model) * 4 == 26 * 16 * 5120 * 4
    assert c.rows_values_per_slot(model) * 4 == 26 * 3 * 5120 * 4
    assert c.state_bytes(model, 1) == 10_117_120
    assert c.slab_bytes_per_position(model) == 2 * 1 * 128 * 2 * 4 == 2048
    assert c.slab_bytes(model, 1, 16640) == 34_078_720
    assert round(c.state_bytes(model, 32) / 1e9, 2) == 0.32
    assert round(c.slab_bytes(model, 32, 16640) / 1e9, 2) == 1.09
    # static bytes: 7.5 GB at 32 slots of 16,640, 47% of the chip; the
    # issue's fall-back of 16 slots is 6.8 GB
    assert round(c.static_bytes(model, 32, 16640, 4, 2) / 1e9, 1) == 7.5
    assert 0.46 < c.static_bytes(model, 32, 16640, 4, 2) / 16e9 < 0.48
    assert round(c.static_bytes(model, 16, 16640, 4, 2) / 1e9, 1) == 6.8
    # a decode step: the matrices with the WHOLE table (it is the head),
    # the states twice, the slabs whole: about 10 ms at the HBM peak
    step = c.decode_step_bytes(model, 32, 16640, 4, 2)
    assert step["weights"] == c.matrix_params(model) * 2 \
        + c.vector_params(model) * 4
    assert step["state"] == 2 * 32 * 10_117_120
    assert step["cache"] == 32 * 34_078_720
    assert step["total"] == step["weights"] + step["state"] + step["cache"]
    assert 0.0095 < step["total"] / 819e9 < 0.0096
    # what the kernel never writes: exp(dt A) at 16,384 positions
    assert round(c.discretised_bytes(model, 16384) / 1e9, 1) == 5.4


def test_no_share_of_a_roofline_can_pass_its_hand_count():
    """What each share of the cell divides a measured time INTO, against
    a count by hand: the least seconds are what the shapes alone give, so
    a kernel at its peak reads 100% and nothing reads more — the padded
    tiles the update moves and the relaid operands of the scan are the
    kernels' own cost."""
    model = Manifest().config(CONFIG)["model"]
    c = closed_forms_mamba
    up = c.update_roofline(model, 32, PEAKS)
    assert up["bytes"] == 26 * (32 * (2 * 5120 * 16 + 3 * 5120 + 2 * 16)
                                + 5120 * 16) * 4
    assert up["flops"] == 26 * 32 * 9 * 5120 * 16
    # BOTH kernels are held against the vector unit's peak (a
    # thirty-second of the bf16 peak: 4 x 8 x 128 ALUs against 4 x 128 x
    # 128 multiply-accumulators), on the count no implementation can do
    # less than: six operations a state (the exponential has a slot of its
    # own) and two a channel
    assert c.vector_ops_per_s(PEAKS) == 197e12 / 32
    assert c.recurrence_vector_ops(model, 1) == 6 * 5120 * 16 + 2 * 5120
    assert up["vector_ops"] == 26 * 32 * (6 * 5120 * 16 + 2 * 5120)
    assert up["bound"] == "vector"
    assert up["seconds"] == pytest.approx(up["vector_ops"] / (197e12 / 32))
    assert 0.000067 < up["seconds"] < 0.000068
    # not the bytes: XLA hands 22 of the 26 calls their state in VMEM, and
    # the first traced run read 213% of the byte term
    assert up["hbm_seconds"] == pytest.approx(up["bytes"] / 819e9)
    assert 0.00072 < up["hbm_seconds"] < 0.00074
    # the kernel's own loop does seven operations and an exponential a
    # state: even the kernels alone read under 100%
    assert 0.000346 > 3 * up["seconds"]       # my chip run, PR 58
    # the scan: the largest of ISSUE 58's two terms (9 x 5,120 x 16 a token
    # and layer over the bf16 peak; the fewest bytes over the HBM peak) and
    # of the vector unit's, which is the bound at every length
    for T in (2048, 16384):
        sc = c.scan_roofline(model, T, PEAKS)
        assert sc["flops"] == 26 * T * 9 * 5120 * 16
        assert sc["vector_ops"] == 26 * T * (6 * 5120 * 16 + 2 * 5120)
        assert sc["bytes"] == 26 * (T * (2 * 5120 + 2 * 16 + 160)
                                    + 5120 * 16) * 4
        assert sc["bound"] == "vector"
        assert sc["seconds"] == pytest.approx(
            sc["vector_ops"] / (197e12 / 32))
        assert sc["seconds"] > sc["bytes"] / 819e9 > sc["flops"] / 197e12
    # 0.0815 us a token and layer, where the kernel alone took 0.14 (my
    # chip run, PR 58)
    assert 0.0347 < c.scan_roofline(model, 16384, PEAKS)["seconds"] < 0.0348
    # a model of other widths, where the bytes bound the scan
    narrow = dict(model, mamba_state=1)
    assert c.scan_roofline(narrow, 2048, PEAKS)["bound"] == "memory"
    reader = _reader("decode_bw_pct")
    rec = {"facts": {"decode_step_bytes": c.decode_step_bytes(
        model, 32, 16640, 4, 2)}, "peaks": PEAKS, "trace": None,
        "spans": {}}
    assert reader.read(rec) is None          # no device trace, no share


def _reader(name):
    return load_path(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                  name + ".py"))


def _span(site, end, dur, ident, parent=None, **attrs):
    return {"ph": "E", "site": site, "t": end, "dur": dur, "span": ident,
            "parent": parent, "trace": "t", "tid": 1, "attrs": attrs or None}


def _record(model, ops, steps, prefills):
    """A made-up traced record: device operations ``(name, start, dur)``,
    step spans ``(end, dur)`` and finished prefill spans ``(end, dur,
    plen)``, all on one clock; every step dispatched plan ``step`` and
    every admission plan ``p<prompt_len>``."""
    spans = []
    for k, (end, dur) in enumerate(steps):
        spans += [_span("serving.engine.step", end, dur, 2 * k + 1),
                  _span("executor.dispatch", end - dur / 2, dur / 4,
                        2 * k + 2, 2 * k + 1, plan="step")]
    for k, (end, dur, plen) in enumerate(prefills, 500):
        spans += [_span("serving.engine.prefill", end, dur, 2 * k + 1,
                        prompt_len=plen),
                  _span("executor.dispatch", end - dur / 2, dur / 4,
                        2 * k + 2, 2 * k + 1, plan="p%d" % plen)]
    return {
        "facts": {"b_max": 32, "window_s": 10.0, "mamba": {
            "cfg": {k: model[k] for k in (
                "n_layer", "layer_types", "mamba_inner", "mamba_state",
                "mamba_dt_rank", "ssm_conv")}, "itemsize": 4}},
        "peaks": PEAKS,
        "spans": {"serving.engine.step": steps},
        "trace": {"host_offset_s": 0.0, "t0": 0.0, "t1": 10.0,
                  "ops": {0: [(n, s, d, "custom-call" if "mamba" in n
                               else "fusion") for n, s, d in ops]}},
        "program_spans": spans, "program_window": (0.0, 10.0),
        "counters": {"mamba_state_bytes": 323_747_840},
    }


def test_the_six_new_readers_on_made_up_records():
    model = Manifest().config(CONFIG)["model"]
    # two decode steps of 26 updates of 0.04 ms, each behind a fusion of
    # 0.01 ms that stacks its rows, and one fusion of another op; one
    # admission of 16,384 with 26 scans of 6 ms, each behind a relayout
    # of 2 ms, one of 2,048 with 26 of 0.8 ms
    ops, step_names, long_names = [], {"fusion.1": "L0/ffn/mul"}, {}
    for i in range(26):
        step_names["mamba_update.%d" % i] = "L%d/mixer/mamba_update" % i
        step_names["fusion.%d" % (10 + i)] = "L%d/mixer/mamba_update" % i
        long_names["mamba_scan.%d" % i] = "L%d/mixer/mamba_scan" % i
        long_names["copy.%d" % i] = "L%d/mixer/mamba_scan" % i
        long_names["fusion.%d" % (10 + i)] = "L%d/mixer/causal_conv" % i
    for s in (1.0, 2.0):
        ops += [("fusion.1", s + 0.001, 0.001)]
        for i in range(26):
            ops += [("fusion.%d" % (10 + i), s + 0.002 + 0.0002 * i, 0.00001),
                    ("mamba_update.%d" % i, s + 0.0021 + 0.0002 * i,
                     0.00004)]
    for i in range(26):
        ops += [("fusion.%d" % (10 + i), 5.0 + 0.01 * i, 0.001),
                ("copy.%d" % i, 5.001 + 0.01 * i, 0.002),
                ("mamba_scan.%d" % i, 5.003 + 0.01 * i, 0.006),
                ("mamba_scan.%d" % (26 + i), 7.0 + 0.002 * i, 0.0008)]
    tables = {"step": {"source": "ran", "fused": {}, "names": step_names},
              "p16384": {"source": "ran", "fused": {}, "names": long_names},
              "p2048": {"source": "ran", "fused": {}, "names": {}}}
    rec = _record(model, ops, [(1.03, 0.03), (2.03, 0.03)],
                  [(5.5, 0.5, 16384), (7.1, 0.1, 2048)])
    assert _reader("mamba_state_gb").read(rec) == pytest.approx(0.32375, 1e-4)
    # the update with what stands round it under the op, not the kernel's
    # name alone; the step's other fusion is not its
    step = _reader("mamba_step_ms")
    assert step.seconds_per_step(rec, tables) * 1e3 \
        == pytest.approx(26 * 0.05)
    assert step.read(rec) == pytest.approx(26 * 0.05)     # once a record
    share = _reader("mamba_step_roofline").read(rec)
    assert share == pytest.approx(100 * closed_forms_mamba.update_roofline(
        model, 32, PEAKS)["seconds"] / (26 * 0.00005))
    assert 5 < share < 6
    # the scans of the LONGEST admission with the relayout round them
    # (prefill_mixer_ms's rule), the convolution in front left out
    scan = _reader("mamba_scan_ms")
    got = scan.op_seconds(rec, "prefill", "mamba_scan", tables)
    assert got["prompt_len"] == 16384
    assert got["seconds"] == pytest.approx(26 * 0.008)
    assert got["kernel_seconds"] == pytest.approx(26 * 0.006)
    assert scan.read(rec) == pytest.approx(26 * 8.0)
    share = _reader("mamba_scan_roofline").read(rec)
    assert share == pytest.approx(100 * closed_forms_mamba.scan_roofline(
        model, 16384, PEAKS)["seconds"] / (26 * 0.008))
    assert 16 < share < 17
    # the prefill's dense FFN goes by the program's name table and the
    # dispatches under the span: decode_ffn_ms's reader at the prefill's
    # site (tests/benchmarks/test_device_scopes.py drives that join)
    ffn = _reader("prefill_ffn_ms")
    assert ffn.read(dict(rec, trace=None)) is None
    assert (ffn.SITE, ffn.CLASSES) == ("prefill", ("ffn",))
    assert _reader("decode_ffn_ms").CLASSES == ffn.CLASSES
    # a program from before this PR has no such kernel, span or gauge:
    # nothing is read and nothing raised (a parent's line leaves the
    # metrics out)
    for bare in ({"counters": {}}, {"counters": None}, {},
                 {"facts": {"delta": {}}, "trace": None},
                 {"facts": {"mamba": {}}, "trace": None, "spans": {}}):
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
            traffic["think_time_s"], traffic["block"],
            traffic["trace_seconds"]) == (32, 20.0, 4, 0.0, 20, 12.0)
    seq = closed_loop.sequence(traffic, seed, 200)
    prompts = Counter({2048: 6, 4096: 6, 8192: 5, 16384: 3})
    answers = Counter({64: 5, 128: 6, 192: 6, 256: 3})
    for lo in range(0, 200, 20):
        block = seq[lo:lo + 20]
        assert Counter(p for p, _ in block) == prompts
        assert Counter(n for _, n in block) == answers
    assert sum(p * n for p, n in prompts.items()) / 20 == 6348.8
    assert sum(a * n for a, n in answers.items()) / 20 == 150.4
    assert max(p + n for p, n in seq) <= 16640
    assert closed_loop.sequence(traffic, seed + 1, 200) != seq
    # batch_closed_long_ctx's prompts
    assert m.traffic("batch_closed_long_ctx")["prompt_lengths"] \
        == traffic["prompt_lengths"]
    assert traffic["reference_probes"] == 32
    assert traffic["reference_probes_long"] == 6
    assert traffic["reference_long_over"] == 8192
    assert traffic["reference_router_gap_floor"] == 0.0
    assert traffic["mamba_dt_range"] == [0.001, 0.1]
    assert traffic["mamba_a_range"] == [1.0, 16.0]
    assert traffic["mamba_d_range"] == [0.5, 1.5]
    assert traffic["mamba_conv_limit"] == 0.5 == 1 / 4 ** 0.5
    assert len(traffic["reference_why"]) > 400 and traffic["mamba_why"]
    # every padded length the reference is compiled for
    pad = traffic["reference_pad_multiple"]
    assert {-(-(p + n) // pad) * pad for p, n in seq} \
        <= {3072, 5120, 9216, 17408}
    # the primers: 64 + 2 i new tokens for slot i, two steps apart
    from benchmarks.kinds import closed_loop_mamba

    class Engine:
        def submit(self, prompt, n_new):
            return (len(prompt), n_new)

    primers = closed_loop_mamba.prime(Engine(), traffic, 65536, seed)
    assert primers == [(2048, 64 + 2 * i) for i in range(32)]


def test_the_recurrences_parameters_are_drawn_where_the_model_puts_them():
    """``seeded_params`` on the tiny configuration: ``softplus(dt_b)`` in
    0.001-0.1, ``exp(a_log)`` in 1-16 and float32 at rank 2, ``W_dt``
    within ``R^-1/2``, ``D`` in 0.5-1.5, the taps and their bias within
    0.5 and float32; everything else as ``closed_loop_mla`` draws it."""
    import numpy as np

    from benchmarks.kinds import closed_loop_mamba, closed_loop_mla

    m = Manifest(os.path.join(ROOT, MANIFEST))
    conf = m.config("tiny-mamba")
    traffic = m.traffic("tiny_batch_closed_long_docs")
    cfg, serving = conf["model"], conf["serving"]
    got = closed_loop_mamba.seeded_params(cfg, serving, traffic, 7)
    plain = closed_loop_mla.seeded_params(cfg, serving, 7)
    assert set(got) == set(plain)
    dt = np.log1p(np.exp(np.asarray(got["gpt_0_mamba_dt_b"])))
    assert 0.001 <= dt.min() and dt.max() <= 0.1001
    a_log = got["gpt_1_mamba_a_log"]
    assert str(a_log.dtype) == "float32" and a_log.shape == (128, 8)
    a = np.exp(np.asarray(a_log))
    assert 1.0 <= a.min() and a.max() <= 16.0
    w_dt = np.asarray(got["gpt_2_mamba_dt.w_0"].astype("float32"))
    assert str(got["gpt_2_mamba_dt.w_0"].dtype) == "bfloat16"
    assert 0.3 < np.abs(w_dt).max() <= 6 ** -0.5 + 1e-3
    d = np.asarray(got["gpt_4_mamba_d"])
    assert 0.5 <= d.min() and d.max() <= 1.5
    for part, shape in (("w_0", (128, 4)), ("b_0", (128,))):
        taps = got["gpt_0_mamba_conv." + part]
        assert str(taps.dtype) == "float32" and taps.shape == shape
        assert 0.4 < float(abs(taps).max()) <= 0.5
    assert "gpt_out_proj.w_0" not in got      # the table is the head
    same = [n for n in got if "_mamba_d" not in n and "_mamba_a" not in n
            and "_mamba_conv" not in n]
    assert len(same) > 30 and all(
        (np.asarray(got[n].astype("float32"))
         == np.asarray(plain[n].astype("float32"))).all() for n in same)


def test_the_benchmarks_reference_is_the_tests_reference_bit_for_bit():
    with open(os.path.join(ROOT, "tests", "references", "jamba.py"),
              "rb") as f:
        mine = f.read()
    with open(os.path.join(ROOT, "benchmarks", "references",
                           CONFIG + ".py"), "rb") as f:
        assert f.read() == mine


def test_the_chip_sweep_rehearses(tmp_path):
    """tools/mamba_sweep.py at a tiny shape in interpret mode: a scan row
    with the closed form's least time and its distance from the composed
    form, the update's check row and its timed row."""
    out = tmp_path / "sweep.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mamba_sweep.py"),
         "--rehearse", "--reps", "1", "--out", str(out)],
        env=_cpu_env(tmp_path), capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = json.loads(out.read_text())["rows"]
    (scan,) = [r for r in rows if r.get("kernel") == "mamba_scan"]
    assert scan["y_max_abs"] < 1e-5 and scan["state_max_abs"] < 1e-5
    assert scan["y_abs_max"] > 0.1
    assert all(r["least_ms"] > 0 and r["bound"] in ("memory", "vector")
               for r in rows if "kernel" in r)
    (check,) = [r for r in rows if "check" in r]
    assert check["update_state_max_abs"] < 1e-5
    assert rows[-1]["kernel"] == "mamba_update"
    # and without a TPU it refuses to time anything
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mamba_sweep.py")],
        env=_cpu_env(tmp_path), capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and "times kernels on a TPU" in proc.stderr
