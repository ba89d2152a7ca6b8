"""The cell PR 26 adds, on the CPU: its rehearsal through
benchmarks/run.py with a tiny manifest that lives HERE, the closed forms
of the expert layer against hand-counted numbers, and the three new
readers on made-up records."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import closed_forms_moe  # noqa: E402
from benchmarks.lib.manifest import Manifest  # noqa: E402

MANIFEST = "tests/benchmarks/BENCHMARK.tiny_moe.json"


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
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = [json.loads(x) for x in proc.stdout.strip().splitlines()
           if x.startswith("{")]
    return out[-2], out[-1]


SERVE_SPANS = {"cache_miss_n", "compile_s", "engine_occ_pct",
               "engine_step_ms", "step_sample_ms", "step_self_ms"}


@pytest.mark.parametrize("cell,trace,reports", [
    ("tiny_moe_serve_batch", 0,
     {"serve_tok_s", "req_tok_ms_p50", "setup_s"}),
    # the device-side routing tally is a program counter: read on a CPU too
    ("tiny_moe_serve_batch", 1, SERVE_SPANS | {"moe_load_max_pct"}),
])
def test_rehearsal_of_the_new_cell(tmp_path, cell, trace, reports):
    rehearsal, last = _rehearse(tmp_path, cell, trace)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1 and last["metrics"] == {}
    assert rehearsal["rehearsal"] == "passed"
    assert set(rehearsal["would_report"]) == reports
    facts = rehearsal["facts"]
    assert facts["reference_tokens_compared"] > 0
    # a CPU computes float32 exactly, so the system IS the reference ...
    assert facts["reference_worst_margin"] <= 1e-3
    assert facts["reference_mean_margin"] <= 1e-6
    # ... and the reference in bfloat16 (weights, activations, cache) is
    # not: that it lands outside the limit is a condition of `correct`
    assert facts["control_bf16_mean_margin"] > 1e-6
    assert facts["control_bf16_not_argmax_pct"] > 0
    # every decode step routes b_max rows x top_k pairs a layer
    assert facts["routed_pairs_total"] > 0
    assert facts["routed_pairs_total"] % (4 * 2 * 2) == 0
    assert set(facts["moe_gmm_plans"]) == {"moe_gmm_up - composed",
                                           "moe_gmm_down - composed"}


def test_the_real_manifest_finds_every_file_of_the_new_cell():
    m = Manifest()
    for cell in ("olmoe_serve_batch",):
        w = m.cell(cell)
        kind = m.traffic(w["traffic"])["kind"]
        assert os.path.isfile(m.find("kinds", kind, (".py",)))
        assert m.metrics_for("end_to_end", cell)
        for metric in m.metrics_for("per_layer", cell):
            assert os.path.isfile(m.find("layer_metrics", metric["name"],
                                         (".py",)))
    cfg = m.config("olmoe-1b-7b")
    assert cfg["reference"] == os.path.relpath(
        m.find("references", "olmoe-1b-7b", (".py",)), ROOT)
    # the catalog's keys at the top level, the depth alone reduced
    assert cfg["num_hidden_layers"] == 4 == cfg["model"]["n_layer"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    for top, ours in (("hidden_size", "d_model"),
                      ("intermediate_size", "d_expert"),
                      ("num_experts", "n_expert"),
                      ("num_experts_per_tok", "expert_top_k"),
                      ("num_attention_heads", "n_head"),
                      ("vocab_size", "vocab"),
                      ("rms_norm_eps", "norm_eps"),
                      ("rope_theta", "rope_theta"),
                      ("norm_topk_prob", "norm_topk"),
                      ("max_position_embeddings", "max_length")):
        assert cfg[top] == cfg["model"][ours], top
    from paddle_tpu.models import gpt
    gpt._check_cfg(cfg["model"])


TINY = dict(d_model=64, n_head=4, n_layer=2, vocab=97, n_expert=8,
            expert_top_k=2, d_expert=32)


def test_closed_forms_moe_against_hand_counts():
    # one layer: q k v o 4*64*64 = 16384; four norm scales 256; router
    # 64*8 = 512; experts 3*8*64*32 = 49152  -> 66304
    assert closed_forms_moe.expert_params_per_layer(TINY) == 49152
    assert closed_forms_moe.param_count(TINY) \
        == 97 * 64 * 2 + 2 * 66304 + 64
    assert closed_forms_moe.cache_elements_per_slot(TINY, 64) \
        == 2 * 2 * 4 * 64 * 16
    got = closed_forms_moe.decode_step_bytes(TINY, 4, 64, 4, 4)
    assert got["weights"] == (97 * 64 + 2 * 66304 + 64) * 4
    assert got["experts"] == 2 * 49152 * 4
    assert got["cache"] == 4 * 16384 * 4
    assert got["total"] == got["weights"] + got["cache"]
    # 4 rows x 2 experts = 8 pairs, 6*64*32 a pair
    assert closed_forms_moe.gmm_flops(8, TINY) == 8 * 6 * 64 * 32
    assert closed_forms_moe.gmm_bytes(TINY, 4) == 49152 * 4
    peaks = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e6}
    roof = closed_forms_moe.gmm_step_roofline(TINY, 4, 4, peaks)
    assert roof["flops"] == 2 * 8 * 6 * 64 * 32
    assert roof["bytes"] == 2 * 49152 * 4
    assert roof["bound"] == "memory"
    assert roof["seconds"] == pytest.approx(roof["bytes"] / 1e6)


def test_closed_forms_moe_at_the_published_widths():
    """ISSUE 26's arithmetic: 419.6 M parameters a layer, 7.54 GB of
    weights and 2.15 GB of cache, a step of 9.27 GB, 7.87 ms of expert
    bytes."""
    cfg = Manifest().config("olmoe-1b-7b")["model"]
    assert closed_forms_moe.expert_params_per_layer(cfg) == 402653184
    assert closed_forms_moe.param_count(cfg) == pytest.approx(1.884e9,
                                                              rel=1e-3)
    step = closed_forms_moe.decode_step_bytes(cfg, 32, 1024, 4, 4)
    assert step["weights"] == pytest.approx(7.13e9, rel=2e-3)
    assert step["experts"] == pytest.approx(6.44e9, rel=1e-3)
    assert step["cache"] == pytest.approx(2.15e9, rel=2e-3)
    assert step["total"] == pytest.approx(9.27e9, rel=2e-3)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    roof = closed_forms_moe.gmm_step_roofline(cfg, 32, 4, peaks)
    assert roof["bound"] == "memory"
    assert roof["seconds"] == pytest.approx(7.87e-3, rel=1e-3)
    prefill = closed_forms_moe.gmm_step_roofline(cfg, 512, 4, peaks)
    assert prefill["flops"] == 4 * 4096 * 6 * 2048 * 1024
    assert prefill["bound"] == "memory"     # float32 weights: 7.87 ms
    assert prefill["flops"] / 197e12 == pytest.approx(1.05e-3, rel=0.01)


def _reader(name):
    return Manifest().load_module("layer_metrics", name)


def test_moe_readers_on_a_made_up_record():
    ops = [("fusion.1", 10.0000, 0.0010, "fusion"),
           ("moe_gmm_up.3", 10.0010, 0.0030, "custom-call"),
           ("moe_gmm_down.4", 10.0040, 0.0010, "custom-call"),
           # a prefill's kernels, outside any step span
           ("moe_gmm_up.9", 10.0200, 0.0090, "custom-call"),
           ("moe_gmm_up.3", 10.0300, 0.0032, "custom-call"),
           ("moe_gmm_down.4", 10.0332, 0.0012, "custom-call")]
    record = {
        "trace": {"ops": {0: ops}, "host_offset_s": 5.0, "t0": 9.9,
                  "t1": 10.1},
        # (end on the host's clock, duration)
        "spans": {"serving.engine.step": [(5.0060, 0.0060),
                                          (5.0360, 0.0060)]},
        "facts": {"moe": {"cfg": dict(TINY), "rows": 4,
                          "weight_itemsize": 4}},
        "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        "counters": {"routed_pairs": [[10, 30, 20, 20], [5, 5, 5, 5]]},
    }
    assert _reader("moe_gmm_ms").read(record) == pytest.approx(4.2)
    least_s = 2 * 49152 * 4 / 1e9
    assert _reader("moe_gmm_roofline").read(record) \
        == pytest.approx(100 * least_s / 4.2e-3)
    assert _reader("moe_load_max_pct").read(record) \
        == pytest.approx(150.0)


@pytest.mark.parametrize("name", ["moe_gmm_ms", "moe_gmm_roofline",
                                  "moe_load_max_pct"])
def test_moe_readers_return_nothing_where_there_is_nothing_to_read(name):
    """A program without the kernel or the tally (the parent commit), a
    run without a trace: no metric, and no exception."""
    read = _reader(name).read
    assert read({}) is None
    assert read({"trace": None, "spans": {}, "facts": {},
                 "counters": {"occupancy_mean": 0.9}}) is None
    no_kernel = {"trace": {"ops": {0: [("fusion.1", 1.0, 0.1, "fusion")]},
                           "host_offset_s": 0.0, "t0": 0.0, "t1": 9.0},
                 "spans": {"serving.engine.step": [(1.2, 0.3)]},
                 "facts": {}, "counters": {"routed_pairs": None},
                 "peaks": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}}
    assert read(no_kernel) is None


# what BENCHMARK.json held before PR 26, by name: cells as (name, config,
# traffic, chips); metrics as (name, bound or the metric it moves, cells)
CELLS_BEFORE = [
    ("bert_train_s512", "bert-base", "pretrain_s512_b32", 1),
    ("gpt2m_serve_chat", "gpt2-medium", "chat_steady", 1),
    ("bert_train_s128", "bert-base", "pretrain_s128_b128", 1),
    ("bert_train_s512_dp4", "bert-base", "pretrain_s512_b32_dp4", 4),
    ("gpt2m_serve_batch", "gpt2-medium", "batch_closed", 1)]
TRAIN = ["bert_train_s512", "bert_train_s128", "bert_train_s512_dp4"]
FLASH = ["bert_train_s512", "bert_train_s512_dp4"]
SERVE = ["gpt2m_serve_chat", "gpt2m_serve_batch"]
CHAT, DP4 = SERVE[:1], TRAIN[2:]
END_TO_END_BEFORE = [
    ("train_tok_s", 0.01, TRAIN), ("serve_tok_s", 0.06, SERVE),
    ("req_tok_ms_p50", 0.07, SERVE), ("req_tok_ms_p95", 0.07, CHAT),
    ("setup_s", 0.1, None)]
PER_LAYER_BEFORE = [
    ("compile_s", "setup_s", None), ("cache_miss_n", "setup_s", None),
    ("host_gap_ms.train", "train_tok_s", TRAIN),
    ("step_dev_ms.train", "train_tok_s", TRAIN),
    ("mfu_pct.train", "train_tok_s", TRAIN),
    ("flash_ms.train", "train_tok_s", TRAIN),
    ("flash_roofline", "train_tok_s", FLASH),
    ("coll_ms.dp4", "train_tok_s", DP4),
    ("coll_exposed_pct.dp4", "train_tok_s", DP4),
    ("gen_late_ms_p95", "req_tok_ms_p95", CHAT),
    ("queue_wait_ms", "req_tok_ms_p95", CHAT),
    ("prefill_ms_p50", "req_tok_ms_p95", CHAT),
    ("engine_step_ms", "req_tok_ms_p50", SERVE),
    ("engine_occ_pct", "serve_tok_s", SERVE),
    ("decode_dev_ms", "req_tok_ms_p50", SERVE),
    ("decode_bw_pct", "req_tok_ms_p50", SERVE),
    ("peak_hbm_gb.train", "train_tok_s", TRAIN),
    ("peak_hbm_gb.serve", "serve_tok_s", SERVE),
    ("host_gather_ms.train", "train_tok_s", TRAIN),
    ("host_place_ms.train", "train_tok_s", DP4),
    ("host_dispatch_ms.train", "train_tok_s", TRAIN),
    ("host_self_ms.train", "train_tok_s", TRAIN),
    ("flash_fwd_ms.train", "train_tok_s", FLASH),
    ("flash_bwd_ms.train", "train_tok_s", FLASH),
    ("flash_refwd_ms.train", "train_tok_s", FLASH),
    ("step_sample_ms", "req_tok_ms_p50", SERVE),
    ("step_self_ms", "req_tok_ms_p50", SERVE),
    ("prefill_run_ms_p50", "req_tok_ms_p95", CHAT),
    ("splice_ms_p50", "req_tok_ms_p95", CHAT),
    ("engine_ttft_ms_p95", "req_tok_ms_p95", CHAT),
    ("engine_itl_ms_p95", "req_tok_ms_p95", CHAT)]


def test_benchmark_json_only_grew_by_prefix():
    """What the benchmark had before this PR stays where it was, as a
    PREFIX of every list: cells, configurations and metrics are appended,
    and a metric's ``workloads`` only gains names at its end. (The pinned
    ``test_closed_loop.py::test_benchmark_json_only_grew`` asserts the
    last cell by position and so fails on any appended cell; this one
    survives the next.)"""
    doc = Manifest().doc
    assert doc["run_seconds"] == 45
    assert [c["name"] for c in doc["configs"]][:2] == ["bert-base",
                                                       "gpt2-medium"]
    assert [(w["name"], w["config"], w["traffic"], w["chips"])
            for w in doc["workloads"]][:len(CELLS_BEFORE)] == CELLS_BEFORE
    for section, key, before in (
            ("end_to_end", "bound", END_TO_END_BEFORE),
            ("per_layer", "moves", PER_LAYER_BEFORE)):
        now = doc[section][:len(before)]
        assert [(m["name"], m[key]) for m in now] \
            == [(name, value) for name, value, _ in before]
        for m, (_, _, cells) in zip(now, before):
            if cells is None:
                assert "workloads" not in m, m["name"]
            else:
                assert m["workloads"][:len(cells)] == cells, m["name"]
    assert len(doc["end_to_end"]) == len(END_TO_END_BEFORE)


class _FakeReference:
    """A reference whose verdicts are written down: per answer the
    system's margins, the control's margins and the router gaps."""

    def __init__(self, verdicts):
        self.verdicts = verdicts

    def greedy_margin_fn(self, params, cfg, pad_multiple, controls):
        assert controls == ((7, 7),) and pad_multiple == 256
        return lambda tokens, prompt_len: self.verdicts[prompt_len]


LIMITS = {"reference_pad_multiple": 256, "reference_router_gap_floor": 0.01,
          "reference_mean_margin_limit": 2e-4,
          "reference_not_argmax_limit_pct": 4.0,
          "reference_margin_tolerance": 0.15}
Z = np.zeros(100)
WIDE = np.full(100, 0.5)  # router gaps far from a tie


def _with(base, **at):
    out = base.copy()
    for i, v in at.items():
        out[int(i[1:])] = v
    return out


@pytest.mark.parametrize("system,control,gaps,why", [
    # the system within every limit, the control outside the mean's
    (_with(Z, _3=0.01), _with(Z, _3=0.01, _7=0.02), WIDE, []),
    # a control that reads like the system: the limit tells nothing
    (_with(Z, _3=0.01), _with(Z, _3=0.01), WIDE, ["no longer bites"]),
    (_with(Z, _3=0.03), _with(Z, _7=0.05), WIDE, ["on average"]),
    (_with(Z, _1=1e-4, _2=1e-4, _3=1e-4, _4=1e-4, _5=1e-4),
     _with(Z, _7=0.05), WIDE, ["not the float32 reference's argmax"]),
    (_with(Z, _3=0.2), _with(Z, _7=0.3), WIDE,
     ["on average", "tolerance 0.1500"]),
    # the system's one flip sits on a near-tie of the router: left out
    (_with(Z, _3=0.03), _with(Z, _7=0.05), _with(WIDE, _3=0.001), []),
])
def test_judge_holds_the_system_inside_and_the_control_outside(
        system, control, gaps, why):
    kind = Manifest().load_module("kinds", "closed_loop_moe")
    ref = _FakeReference({5: ([system, control], gaps)})
    got, facts = kind.judge(ref, None, None, LIMITS, [(None, 5)])
    assert len(got) == len(why), got
    for line, part in zip(got, why):
        assert part in line
    assert facts["reference_tokens_compared"] \
        + facts["reference_tokens_near_tied"] == 100


def test_judge_without_a_token_to_compare_is_not_correct():
    kind = Manifest().load_module("kinds", "closed_loop_moe")
    got, facts = kind.judge(_FakeReference({}), None, None, LIMITS, [])
    assert got == ["no token was compared with the reference"]
    assert facts["reference_tokens_compared"] == 0


def test_the_cells_limits_are_the_ones_perf_md_gives():
    tr = Manifest().traffic("batch_closed_olmoe")
    assert {k: tr[k] for k in LIMITS} == dict(
        LIMITS, reference_not_argmax_limit_pct=4.1)
    assert tr["reference_probes"] == 160 and tr["probes"] == 8
    for text in ("2.0e-4", "4.1%", "0.15"):
        assert text in tr["reference_why"]
