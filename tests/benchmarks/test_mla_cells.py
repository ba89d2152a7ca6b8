"""The cell PR 32 adds, on the CPU: its rehearsal through
benchmarks/run.py with a tiny manifest that lives HERE, the closed forms
of the latent cache, the bf16 matrices and the two attention kernels
against hand-counted numbers, the four new readers on made-up records,
and the traffic's blocks."""

import json
import os
import subprocess
import sys
from collections import Counter

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import closed_forms_mla, closed_loop  # noqa: E402
from benchmarks.lib.manifest import Manifest, load_path  # noqa: E402

MANIFEST = "tests/benchmarks/BENCHMARK.tiny_mla.json"
CELL = "tiny_mla_serve_reason"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _checkout(tmp_path):
    """A checkout of symlinks: ``benchmarks/run.py`` keeps its profile
    under ``<checkout>/.bench_trace`` and empties it around every traced
    run, so two traced rehearsals from the one repository (another test
    file's, on another worker) delete each other's profile."""
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
         "--seed", str(2 ** 31 + 54321), "--seconds", "1",
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
    # program spans and counters are read on a CPU too; the device-trace
    # readers (the four new ones among them) have no TPU plane there
    (1, {"cache_miss_n", "compile_s", "engine_occ_pct", "engine_step_ms",
         "step_sample_ms", "step_self_ms", "moe_touched_pct"}),
])
def test_rehearsal_of_the_new_cell(tmp_path, trace, reports):
    rehearsal, last = _rehearse(tmp_path, trace)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1 and last["metrics"] == {}
    assert rehearsal["rehearsal"] == "passed"
    assert set(rehearsal["would_report"]) == reports
    facts = rehearsal["facts"]
    assert facts["reference_tokens_compared"] > 0
    # a CPU computes float32 exactly and the bf16-stored matrices are
    # widened where they multiply, so the system IS the reference; the
    # reference with bfloat16 activations is not: both decide `correct`
    assert facts["reference_mean_margin"] <= 1e-6
    assert facts["control_bf16_mean_margin"] > 1e-6
    # answers after the longest prompt are among the judged (two of a
    # window that a loaded machine may leave with few completions)
    assert facts["reference_probes_long"] == 2
    assert facts["longest_prompt"] == 20
    assert facts["primers"] == 4
    # serve_tok_s counts what the window's steps and admissions produced:
    # never more than every slot live at every step
    assert 0 < facts["tokens_made"] <= facts["decode_steps"] \
        * facts["b_max"] + facts["requests_in_window"]
    # ONE latent tensor a layer: 4 layers x 4 slots x 64 rows x 40 x 4 B
    assert facts["cache_bytes"] == {"latent": 4 * 4 * 64 * 40 * 4}
    # matrices in bfloat16, vectors in float32
    cfg = Manifest(os.path.join(ROOT, MANIFEST)).config("tiny-mla")["model"]
    assert facts["weight_bytes"] == {
        "bfloat16": 2 * closed_forms_mla.matrix_params(cfg),
        "float32": 4 * closed_forms_mla.vector_params(cfg)}
    assert facts["static_bytes"] == sum(facts["weight_bytes"].values()) \
        + facts["cache_bytes"]["latent"]
    assert set(facts["mla_plans"]) == {"absorbed composed - 40x32",
                                       "expanded fused_attention - 24x16"}
    assert facts["experts_held"] == 4
    assert 0 < facts["experts_touched_mean"] <= 4
    assert facts["routed_pairs_total"] \
        == facts["steps_tallied"] * 4 * 4 * 3
    step = facts["decode_step_bytes"]
    assert step["experts"] == pytest.approx(
        3 * facts["experts_touched_mean"] * 3 * 48 * 24 * 2)
    # the cache is counted by the rows the steps' slots had reached
    assert 4 < facts["mla"]["rows_visible_mean"] < 4 * 26
    assert step["cache"] == pytest.approx(
        facts["mla"]["rows_visible_mean"] * 4 * 40 * 4)
    assert step["total"] == pytest.approx(
        step["weights"] + step["experts"] + step["cache"])


def test_primers_end_a_few_steps_apart():
    """One primer a slot, the shortest prompt, ending g / clients steps
    apart (g = what every answer is a multiple of): the clients' first
    requests take the slots in that order and stay that far apart."""
    from benchmarks.kinds.closed_loop_mla import prime

    class Engine:
        def __init__(self):
            self.seen = []

        def submit(self, prompt, n_new):
            self.seen.append((len(prompt), int(prompt.max()), n_new))
            return len(self.seen)

    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "batch_closed_long_answers.json")) as f:
        traffic = json.load(f)
    eng = Engine()
    assert prime(eng, traffic, 19200, 2 ** 31 + 7) == list(range(1, 65))
    assert [n for _p, _m, n in eng.seen] == [128 + 2 * i for i in range(64)]
    assert {p for p, _m, _n in eng.seen} == {128}
    assert all(m < 19200 for _p, m, _n in eng.seen)
    with open(os.path.join(ROOT, "tests", "benchmarks", "traffic",
                           "tiny_batch_closed_long_answers.json")) as f:
        tiny = json.load(f)
    eng = Engine()
    prime(eng, tiny, 97, 5)
    assert [(p, n) for p, _m, n in eng.seen] == [(4, 3), (4, 3), (4, 4),
                                                 (4, 5)]


def test_tokens_made_is_the_live_slot_steps_and_the_admissions():
    from benchmarks.kinds.closed_loop_mla import tokens_made

    # 1,841 steps of 64 slots, 99.7% of them live, 302 requests submitted
    d = {"occupancy_mean": 0.997, "decode_steps": 1841,
         "in_window": list(range(302))}
    assert tokens_made(d, 64) == round(0.997 * 1841 * 64) + 302 == 117773
    assert tokens_made({"occupancy_mean": None, "decode_steps": 0,
                        "in_window": []}, 64) == 0


def test_the_real_manifest_finds_every_file_of_the_new_cell():
    m = Manifest()
    w = m.cell("pangu_serve_reason")
    assert (w["config"], w["traffic"], w["chips"]) == (
        "openpangu-ultra-moe-718b", "batch_closed_long_answers", 1)
    traffic = m.traffic(w["traffic"])
    assert traffic["kind"] == "closed_loop_mla"
    assert os.path.isfile(m.find("kinds", traffic["kind"], (".py",)))
    assert os.path.isfile(m.find("references", w["config"], (".py",)))
    assert {e["name"] for e in m.metrics_for("end_to_end", w["name"])} \
        == {"serve_tok_s", "req_tok_ms_p50", "setup_s"}
    listed = {e["name"] for e in m.metrics_for("per_layer", w["name"])}
    for name in listed:
        assert os.path.isfile(m.find("layer_metrics", name, (".py",)))
    assert {"mla_decode_ms", "mla_decode_roofline", "mla_flash_ms",
            "mla_flash_roofline", "engine_step_ms", "engine_occ_pct",
            "decode_dev_ms", "decode_bw_pct", "peak_hbm_gb.serve",
            "step_sample_ms", "step_self_ms", "moe_gmm_ms",
            "moe_touched_pct"} <= listed
    assert not {"moe_gmm_roofline", "moe_load_max_pct", "flash_win_ms"} \
        & listed
    assert len(m.doc["workloads"]) == 8
    assert sum(1 for c in m.doc["workloads"] if c["chips"] == 4) == 1


def test_the_configuration_holds_the_published_numbers():
    m = Manifest()
    cfg = m.config("openpangu-ultra-moe-718b")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(x) for x in f if x.strip()]
    (entry,) = [r for r in rows if r["name"] == "openPangu-Ultra-MoE-718B"]
    assert cfg["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and key in cfg["reduced_why"], key
        else:
            assert cfg[key] == value, key
    assert cfg["n_routed_experts_published"] == 256
    widths = {"hidden_size", "intermediate_size", "moe_intermediate_size",
              "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
              "num_attention_heads"}
    assert not widths & set(cfg["reduced"])
    for key in ("deployment", "assumed", "departures", "guarantees"):
        assert cfg[key]
    model = cfg["model"]
    assert (model["d_model"], model["n_head"], model["q_lora_rank"],
            model["kv_lora_rank"], model["d_nope"], model["d_rope"],
            model["d_v"], model["d_ff"], model["d_expert"],
            model["n_expert"], model["expert_top_k"],
            model["route_scale"]) == (7680, 128, 1536, 512, 128, 64, 128,
                                      18432, 2048, 256, 8, 2.5)
    assert model["weight_dtype"] == "bfloat16"
    assert cfg["serving"] == {"b_max": 64, "max_len": 4096}
    from paddle_tpu.models import gpt

    gpt._check_cfg(model)


def test_closed_forms_against_hand_counted_numbers():
    model = Manifest().config("openpangu-ultra-moe-718b")["model"]
    c = closed_forms_mla
    # ISSUE 32's reckoning: 196.58 M of attention a layer, 47.19 M an
    # expert, 3.409 B parameters in all
    assert c.attention_matrix_params(model) == 7680 * 1536 \
        + 1536 * 128 * 192 + 7680 * 576 + 512 * 128 * 256 + 16384 * 7680
    assert c.attention_matrix_params(model) == 196_575_232
    assert c.expert_params(model) == 47_185_920
    assert round(c.matrix_params(model) / 1e6) == 3409
    # the latent row: 576 values a layer, 11,520 B a token, 3.02 GB
    assert c.latent_width(model) == 576
    assert c.cache_bytes_per_token(model, 4) == 11_520
    assert c.cache_bytes(model, 64, 4096, 4) == 3_019_898_880
    # the same five layers as 128 expanded heads of 192 + 128
    assert 5 * 128 * (192 + 128) * 4 == 819_200
    assert round(c.static_bytes(model, 64, 4096, 4, 2) / 1e9, 2) == 9.84
    # a step over every row at 6.9 touched experts: 9.1 GB
    step = c.decode_step_bytes(model, 64, 4096, 4, 2, 6.9)
    assert round(step["attention"] / 1e9, 2) == 1.97
    assert round(step["others"] / 1e9, 2) == 1.54
    assert round(step["experts"] / 1e9, 2) == 2.60
    assert step["cache"] == 3_019_898_880
    assert round(step["total"] / 1e9, 1) == 9.1
    # ... and over the rows its slots have reached
    half = c.decode_step_bytes(model, 64, 4096, 4, 2, 6.9, 64 * 2048)
    assert half["cache"] == step["cache"] // 2
    assert half["total"] == pytest.approx(step["total"] - step["cache"] / 2)
    # mla_decode: 2 x (576 + 512) a row and head, the row read once
    roof = c.mla_decode_roofline(model, 64 * 4096, 4, PEAKS)
    assert roof["flops"] == 5 * 64 * 4096 * 128 * 2 * 1088
    assert round(roof["flops"] / 1e9) == 365
    assert roof["bytes"] == 3_019_898_880 and roof["bound"] == "memory"
    assert roof["seconds"] == pytest.approx(3_019_898_880 / 819e9)
    assert roof["flops"] / roof["bytes"] == pytest.approx(120.9, abs=0.1)
    # the prefill's flash call at 3,328: the true widths 192 and 128
    flash = c.mla_flash_roofline(model, 3328, 4, PEAKS)
    assert flash["pairs"] == 3328 * 3329 // 2
    assert flash["flops"] == 5 * flash["pairs"] * 128 * 2 * 320
    assert flash["bound"] == "compute"
    assert flash["seconds"] == pytest.approx(flash["flops"] / 197e12)


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
        "facts": {"longest_prompt": 3328, "window_s": 10.0,
                  "mla": {"cfg": {"n_layer": 5, "n_head": 128,
                                  "kv_lora_rank": 512, "d_nope": 128,
                                  "d_rope": 64, "d_v": 128},
                          "cache_itemsize": 4, "flash_itemsize": 4,
                          "rows_visible_mean": 100_000.0}},
        "peaks": PEAKS,
    }


def test_mla_decode_readers_on_a_made_up_record():
    ops = []
    for k in range(3):               # three steps of five kernel calls
        t = 101.0 + k
        ops += [("mla_decode.%d" % i, t + 0.001 * i, 0.0004)
                for i in range(5)]
        ops.append(("fusion.7", t + 0.01, 0.005))
    ops.append(("mla_decode.9", 108.5, 0.1))     # outside every step
    rec = _record(ops, steps=[(1.5 + k, 0.6) for k in range(3)])
    ms = _reader("mla_decode_ms").read(rec)
    assert ms == pytest.approx(5 * 0.4)
    least = closed_forms_mla.mla_decode_roofline(
        rec["facts"]["mla"]["cfg"], 100_000.0, 4, PEAKS)["seconds"]
    assert _reader("mla_decode_roofline").read(rec) == pytest.approx(
        100.0 * least / 2e-3)
    # a program without the kernel: nothing to read, nothing raised
    bare = _record([("fusion.1", 101.0, 0.1)], steps=[(1.5, 0.6)])
    assert _reader("mla_decode_ms").read(bare) is None
    assert _reader("mla_decode_roofline").read(bare) is None
    assert _reader("mla_decode_ms").read({"facts": {}}) is None


def test_mla_flash_readers_on_a_made_up_record():
    ops = [("flash_fwd.%d" % i, 102.0 + 0.01 * i, 0.004) for i in range(5)]
    ops += [("flash_fwd.%d" % i, 104.0 + 0.001 * i, 0.0002)
            for i in range(5)]                     # a 512-token admission
    ops.append(("flash_fwd_win.1", 102.001, 0.5))  # not this kernel
    spans = [dict(site="serving.engine.prefill", t=2.2, dur=0.25,
                  attrs={"prompt_len": 3328}),
             dict(site="serving.engine.prefill", t=4.1, dur=0.15,
                  attrs={"prompt_len": 512})]
    rec = _record(ops, spans=spans)
    ms = _reader("mla_flash_ms").read(rec)
    assert ms == pytest.approx(5 * 4.0)
    least = closed_forms_mla.mla_flash_roofline(
        rec["facts"]["mla"]["cfg"], 3328, 4, PEAKS)["seconds"]
    assert _reader("mla_flash_roofline").read(rec) == pytest.approx(
        100.0 * least / 20e-3)
    # another configuration's record (no facts.mla) reads nothing
    other = _record(ops, spans=spans)
    del other["facts"]["mla"]
    assert _reader("mla_flash_ms").read(other) is None
    assert _reader("mla_flash_roofline").read(other) is None


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
def test_every_block_of_the_traffic_holds_the_same_multiset(seed):
    traffic = Manifest().traffic("batch_closed_long_answers")
    assert (traffic["clients"], traffic["ramp_s"], traffic["probes"],
            traffic["think_time_s"]) == (64, 20.0, 8, 0.0)
    seq = closed_loop.sequence(traffic, seed, 200)
    assert len(seq) == 200
    prompts = Counter({128: 6, 512: 6, 1024: 5, 3328: 3})
    answers = Counter({128: 5, 256: 6, 512: 6, 768: 3})
    for lo in range(0, 200, 20):
        block = seq[lo:lo + 20]
        assert Counter(p for p, _ in block) == prompts
        assert Counter(n for _, n in block) == answers
    assert max(p + n for p, n in seq) <= 4096
    # the seed permutes inside a block, and only that
    other = closed_loop.sequence(traffic, seed + 1, 200)
    assert other != seq
    assert sorted(other[:20]) != sorted(seq[:20]) or other[:20] != seq[:20]
    assert traffic["reference_probes"] == 64
    assert traffic["reference_probes_long"] >= 12
