"""The cells PR 30 adds, on the CPU: their rehearsal through
benchmarks/run.py with a tiny manifest that lives HERE, the closed forms
of the two-shape cache, the banded kernel and the touched experts against
hand-counted numbers, the three new readers on made-up records, and the
prefix traffic's schedule against ``chat_steady``'s."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import closed_forms_afmoe, open_loop  # noqa: E402
from benchmarks.lib.manifest import Manifest  # noqa: E402

MANIFEST = "tests/benchmarks/BENCHMARK.tiny_afmoe.json"


def _rehearse(tmp_path, cell, trace):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.pop("PADDLE_TPU_FLASH_MIN_SEQ", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    env["BENCH_RUN"] = "the driver sets this; the benchmark ignores it"
    proc = subprocess.run(
        ["nice", "-n", "19", sys.executable, "benchmarks/run.py",
         "--manifest", MANIFEST, "--cpu-rehearsal", "--workload", cell,
         "--seed", str(2 ** 31 + 12345), "--seconds", "1",
         "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = [json.loads(x) for x in proc.stdout.strip().splitlines()
           if x.startswith("{")]
    return out[-2], out[-1]


SERVE_SPANS = {"cache_miss_n", "compile_s", "engine_occ_pct",
               "engine_step_ms", "step_sample_ms", "step_self_ms"}
CHAT_SPANS = {"gen_late_ms_p95", "queue_wait_ms", "splice_ms_p50",
              "engine_ttft_ms_p95", "engine_itl_ms_p95"}


@pytest.mark.parametrize("cell,trace,reports", [
    ("tiny_afmoe_serve_mixed", 0,
     {"serve_tok_s", "req_tok_ms_p50", "setup_s"}),
    # the touched tally is a program counter: read on a CPU too; the
    # device-trace readers have no TPU plane to read there
    ("tiny_afmoe_serve_mixed", 1, SERVE_SPANS | {"moe_touched_pct"}),
    ("tiny_serve_prefix", 0,
     {"serve_tok_s", "req_tok_ms_p50", "req_tok_ms_p95", "setup_s"}),
    ("tiny_serve_prefix", 1, SERVE_SPANS | CHAT_SPANS),
])
def test_rehearsal_of_the_new_cells(tmp_path, cell, trace, reports):
    rehearsal, last = _rehearse(tmp_path, cell, trace)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1 and last["metrics"] == {}
    assert rehearsal["rehearsal"] == "passed"
    assert set(rehearsal["would_report"]) == reports
    facts = rehearsal["facts"]
    assert facts["reference_tokens_compared"] > 0
    if cell == "tiny_serve_prefix":
        # every admission of the run found the system prompt in the store
        assert facts["prefix_misses"] == 0 and facts["prefix_hits"] > 0
        return
    # a CPU computes float32 exactly, so the system IS the reference, and
    # the reference in bfloat16 is not: both are conditions of `correct`
    assert facts["reference_mean_margin"] <= 1e-6
    assert facts["control_bf16_mean_margin"] > 1e-6
    # answers after prompts longer than the window are among the judged
    assert facts["reference_probes_long"] == 8
    # two cache shapes: four rings of 8 rows, one slab of 64
    per_slot = 2 * 2 * 16 * 4
    assert facts["cache_bytes"] == {"ring": 4 * 4 * 8 * per_slot,
                                    "full": 4 * 64 * per_slot}
    assert facts["experts_held"] == 4
    assert 0 < facts["experts_touched_mean"] <= 4
    assert facts["steps_tallied"] >= facts["decode_steps"]
    # 4 rows x 4 experts a step on each of the four expert layers
    assert facts["routed_pairs_total"] \
        == facts["steps_tallied"] * 4 * 4 * 4
    step = facts["decode_step_bytes"]
    assert step["experts"] == pytest.approx(
        4 * facts["experts_touched_mean"] * 3 * 48 * 24 * 4)
    assert step["total"] == pytest.approx(
        step["weights"] + step["experts"] + step["cache"])


def test_the_real_manifest_finds_every_file_of_the_new_cells():
    m = Manifest()
    names = [w["name"] for w in m.doc["workloads"]]
    assert "trinity_serve_mixed" in names
    for cell in (c for c in ("trinity_serve_mixed", "gpt2m_serve_prefix")
                 if c in names):
        w = m.cell(cell)
        kind = m.traffic(w["traffic"])["kind"]
        assert os.path.isfile(m.find("kinds", kind, (".py",)))
        assert {e["name"] for e in m.metrics_for("end_to_end", cell)} \
            >= {"serve_tok_s", "req_tok_ms_p50", "setup_s"}
        for metric in m.metrics_for("per_layer", cell):
            assert os.path.isfile(m.find("layer_metrics", metric["name"],
                                         (".py",)))
    listed = {e["name"] for e in m.metrics_for("per_layer",
                                               "trinity_serve_mixed")}
    assert {"flash_win_ms", "flash_win_roofline", "moe_touched_pct",
            "moe_gmm_ms", "decode_bw_pct", "peak_hbm_gb.serve"} <= listed
    # an every-expert byte count would read over the chip's peak here
    assert not {"moe_gmm_roofline", "moe_load_max_pct"} & listed


def test_the_configuration_holds_the_published_numbers():
    m = Manifest()
    cfg = m.config("trinity-large-preview")
    assert cfg["reference"] == os.path.relpath(
        m.find("references", "trinity-large-preview", (".py",)), ROOT)
    entry = [c for c in m.doc["configs"]
             if c["name"] == "trinity-large-preview"][0]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == cfg["source"]
    model = cfg["model"]
    for top, ours in (("hidden_size", "d_model"),
                      ("intermediate_size", "d_ff"),
                      ("moe_intermediate_size", "d_expert"),
                      ("head_dim", "d_head"),
                      ("num_attention_heads", "n_head"),
                      ("num_key_value_heads", "n_kv_head"),
                      ("num_experts_per_tok", "expert_top_k"),
                      ("num_shared_experts", "n_shared_expert"),
                      ("num_hidden_layers", "n_layer"),
                      ("num_experts", "n_expert_local"),
                      ("vocab_size", "vocab"),
                      ("sliding_window", "window"),
                      ("rms_norm_eps", "norm_eps"),
                      ("rope_theta", "rope_theta"),
                      ("route_scale", "route_scale"),
                      ("route_norm", "norm_topk"),
                      ("score_func", "router_score"),
                      ("max_position_embeddings", "max_length")):
        assert cfg[top] == model[ours], top
    # the cuts, and what stays published beside them
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 8, 200192 // 8)
    assert model["n_expert"] == 256 and cfg["num_dense_layers"] == 6
    assert len(cfg["layer_types"]) == 60       # the group copied whole
    assert [t.split("_")[0] for t in cfg["layer_types"][:1]
            + cfg["layer_types"][8:12]] == model["layer_types"]
    assert model["emb_scale"] == pytest.approx(3072 ** 0.5)
    from paddle_tpu.models import gpt
    gpt._check_cfg(model)


TINY = json.load(open(os.path.join(
    ROOT, "tests", "benchmarks", "configs", "tiny-afmoe.json")))["model"]


def test_closed_forms_afmoe_against_hand_counts():
    cf = closed_forms_afmoe
    # attention: q, gate, o 48*96 each, k, v 48*32 each, four norms of 48
    # and two per-head scales of 16
    att = 3 * 48 * 96 + 2 * 48 * 32 + 4 * 48 + 2 * 16
    assert cf.attention_params(TINY) == att == 17120
    assert cf.expert_params(TINY) == 3 * 48 * 24
    assert cf.dense_layer_params(TINY) == att + 3 * 48 * 96
    # router 48*16 + its bias 16, one shared expert, 4 held experts
    layer = att + 48 * 16 + 16 + 3456 + 4 * 3456
    assert cf.expert_layer_params(TINY, 4) == layer
    assert cf.param_count(TINY) \
        == 2 * 97 * 48 + 48 + (att + 13824) + 4 * layer
    # the caches: four rings of 8 rows, one slab of 64, 2 x 2 heads x 16
    assert [cf.cache_rows(TINY, i, 64) for i in range(5)] == [8] * 4 + [64]
    assert cf.cache_rows(TINY, 0, 6) == 6      # never more than max_len
    assert cf.cache_elements_per_slot(TINY, 64) == {
        "ring": 4 * 8 * 64, "full": 64 * 64}
    step = cf.decode_step_bytes(TINY, 4, 64, 4, 4, 1.5)
    assert step["weights"] == (cf.param_count(TINY, 0) - 97 * 48) * 4
    assert step["experts"] == 4 * 1.5 * 3456 * 4
    assert (step["cache_ring"], step["cache_full"]) \
        == (4 * 2048 * 4, 4 * 4096 * 4)
    assert step["total"] == step["weights"] + step["experts"] \
        + step["cache_ring"] + step["cache_full"]
    # a band of 8 over 20 positions: 1 + 2 + .. + 8, then 12 rows of 8
    assert cf.banded_pairs(20, 8) == 36 + 12 * 8
    assert cf.banded_pairs(5, 8) == 15
    brute = sum(1 for i in range(20) for j in range(20) if 0 <= i - j < 8)
    assert cf.banded_pairs(20, 8) == brute
    peaks = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e6}
    roof = cf.flash_win_roofline(TINY, 20, 4, peaks)
    assert roof["layers"] == 4
    assert roof["flops"] == 4 * 132 * 4 * 16 * 6
    assert roof["bytes"] == 4 * 20 * 16 * 4 * 2 * (6 + 2)
    assert roof["bound"] == "compute"
    assert cf.flash_win_roofline(TINY, 8, 4, peaks)["layers"] == 0


def test_closed_forms_afmoe_at_the_published_widths():
    """ISSUE 30's arithmetic: 62.9 M of attention a layer, a dense layer
    of 176.2 M and an expert layer of 318.5 M with 8 held experts, 6.42
    GB of weights, a slot of 268.4 MB, a step of about 7.6 GB."""
    cf = closed_forms_afmoe
    cfg = Manifest().config("trinity-large-preview")["model"]
    assert cf.attention_params(cfg) == pytest.approx(62.9e6, rel=1e-3)
    assert cf.dense_layer_params(cfg) == pytest.approx(176.2e6, rel=1e-3)
    assert cf.expert_layer_params(cfg, 8) == pytest.approx(318.5e6,
                                                           rel=1e-3)
    assert cf.param_count(cfg) * 4 == pytest.approx(6.42e9, rel=1e-3)
    slot = cf.cache_elements_per_slot(cfg, 16384)
    assert slot == {"ring": 4 * 2 * 8 * 4096 * 128,
                    "full": 2 * 8 * 16384 * 128}
    assert sum(slot.values()) * 4 == pytest.approx(268.4e6, rel=1e-3)
    step = cf.decode_step_bytes(cfg, 16, 16384, 4, 4, 1.77)
    assert step["cache"] == pytest.approx(4.295e9, rel=1e-3)
    assert step["weights"] == pytest.approx(2.48e9, rel=3e-3)
    assert step["experts"] == pytest.approx(0.80e9, rel=1e-2)
    assert step["total"] == pytest.approx(7.58e9, rel=3e-3)
    # all eight experts a layer would be 3.6 GB: over what 13 ms can read
    assert cf.decode_step_bytes(cfg, 16, 16384, 4, 4, 8)["experts"] \
        == pytest.approx(3.62e9, rel=1e-2)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    roof = cf.flash_win_roofline(cfg, 8192, 4, peaks)
    assert roof["pairs"] == 4096 * 4097 // 2 + 4096 * 4096
    assert roof["bound"] == "compute"
    assert roof["seconds"] == pytest.approx(12.56e-3, rel=1e-3)


def _reader(name):
    return Manifest().load_module("layer_metrics", name)


def test_the_new_readers_on_a_made_up_record():
    ops = [("fusion.1", 10.0000, 0.0010, "fusion"),
           # an admission of the longest prompt: four banded calls
           ("flash_fwd_win.3", 10.0010, 0.0030, "custom-call"),
           ("flash_fwd_win.4", 10.0050, 0.0030, "custom-call"),
           ("flash_fwd.5", 10.0090, 0.0020, "custom-call"),
           # a shorter windowed prompt: not the one the metric is of
           ("flash_fwd_win.7", 10.0300, 0.0020, "custom-call"),
           # the longest again
           ("flash_fwd_win.3", 10.0500, 0.0032, "custom-call"),
           ("flash_fwd_win.4", 10.0540, 0.0032, "custom-call")]

    def span(end, dur, plen):
        return {"ph": "E", "site": "serving.engine.prefill", "t": end,
                "dur": dur, "attrs": {"prompt_len": plen}}

    record = {
        "trace": {"ops": {0: ops}, "host_offset_s": 5.0, "t0": 9.9,
                  "t1": 10.1, "window_s": 0.2},
        "program_spans": [span(5.0120, 0.0120, 20), span(5.0340, 0.0050, 12),
                          span(5.0600, 0.0110, 20),
                          {"ph": "E", "site": "serving.engine.step",
                           "t": 5.07, "dur": 0.001, "attrs": {}}],
        "program_window": (4.9, 5.1),
        "facts": {"longest_prompt": 20,
                  "flash_win": {"cfg": {k: TINY[k] for k in (
                      "n_layer", "n_head", "n_kv_head", "d_head", "window",
                      "layer_types")}, "itemsize": 4}},
        "peaks": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e12},
        "counters": {"experts_touched_mean": 1.5, "experts_held": 4},
    }
    assert _reader("flash_win_ms").read(record) == pytest.approx(6.2)
    least_s = 4 * 132 * 4 * 16 * 6 / 1e9
    assert _reader("flash_win_roofline").read(record) \
        == pytest.approx(100 * least_s / 6.2e-3)
    assert _reader("moe_touched_pct").read(record) == pytest.approx(37.5)


@pytest.mark.parametrize("name", ["flash_win_ms", "flash_win_roofline",
                                  "moe_touched_pct"])
def test_the_new_readers_return_nothing_where_there_is_nothing(name):
    """A program without the banded kernel or the tally (the parent
    commit under this PR's benchmark files), a run without a trace, a
    traced stretch without an admission of the longest prompt: no metric,
    and no exception."""
    read = _reader(name).read
    assert read({}) is None
    assert read({"trace": None, "spans": {}, "facts": {},
                 "counters": {"occupancy_mean": 0.9}}) is None
    no_kernel = {"trace": {"ops": {0: [("fusion.1", 1.0, 0.1, "fusion")]},
                           "host_offset_s": 0.0, "t0": 0.0, "t1": 9.0,
                           "window_s": 9.0},
                 "program_spans": [], "facts": {"longest_prompt": 20},
                 "counters": {"routed_pairs": None},
                 "peaks": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}}
    assert read(no_kernel) is None
    no_admission = dict(no_kernel, trace=dict(
        no_kernel["trace"],
        ops={0: [("flash_fwd_win.1", 1.0, 0.1, "custom-call")]}))
    assert read(no_admission) is None


def test_chat_prefix_has_chat_steadys_due_times_and_one_system_prompt():
    m = Manifest()
    steady, prefix = m.traffic("chat_steady"), m.traffic("chat_prefix")
    for key in ("block", "rate", "ramp_s", "prompt_lengths",
                "output_lengths", "queue_capacity", "probes"):
        assert prefix[key] == steady[key], key
    kind = m.load_module("kinds", prefix["kind"])
    L = prefix["prefix_len"]
    longest = L + max(map(int, prefix["prompt_lengths"])) \
        + max(map(int, prefix["output_lengths"]))
    assert longest == 1024 == m.config("gpt2-medium")["serving"]["max_len"]
    for seed in (7, 2 ** 31 + 5):
        a = open_loop.schedule(steady, seed, 53.0)
        b = open_loop.schedule(prefix, seed, 53.0)
        assert a == b                     # due times, lengths, answers
        system = kind.system_prompt(seed, 50257, L)
        prompts = kind.prompts_of(b, seed, 50257, system)
        tails = open_loop.token_ids(a, seed, 50257)
        assert len(prompts) == len(b) > 300
        for p, t, (_due, tail_len, _n) in zip(prompts, tails, b):
            assert len(p) == L + tail_len
            assert np.array_equal(p[:L], system)
            assert np.array_equal(p[L:], t)   # chat_steady's own prompt
        assert not np.array_equal(system,
                                  kind.system_prompt(seed + 1, 50257, L))


# what BENCHMARK.json held before PR 30, by name
CELLS_BEFORE = ["bert_train_s512", "gpt2m_serve_chat", "bert_train_s128",
                "bert_train_s512_dp4", "gpt2m_serve_batch",
                "olmoe_serve_batch"]
CONFIGS_BEFORE = ["bert-base", "gpt2-medium", "olmoe-1b-7b"]
LAST_METRIC_BEFORE = "moe_load_max_pct"


def test_benchmark_json_only_grew_by_prefix():
    """Cells, configurations and metrics are appended; an old metric's
    ``workloads`` only gains names at its end, and no old cell drops out
    of one."""
    doc = Manifest().doc
    assert [w["name"] for w in doc["workloads"]][:6] == CELLS_BEFORE
    assert [c["name"] for c in doc["configs"]][:3] == CONFIGS_BEFORE
    names = [m["name"] for m in doc["per_layer"]]
    cut = names.index(LAST_METRIC_BEFORE) + 1
    assert cut == 34
    new_cells = {w["name"] for w in doc["workloads"][6:]}
    for metric in doc["end_to_end"] + doc["per_layer"][:cut]:
        cells = metric.get("workloads")
        if cells is None:
            continue
        old = [c for c in cells if c not in new_cells]
        assert cells[:len(old)] == old, metric["name"]
        assert set(old) <= set(CELLS_BEFORE)
    assert {m["name"] for m in doc["per_layer"][cut:]} == {
        "flash_win_ms", "flash_win_roofline", "moe_touched_pct"}
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    assert doc["run_seconds"] == 45
