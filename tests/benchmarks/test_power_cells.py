"""The cell PR 51 adds, on the CPU: its rehearsal through
benchmarks/run.py with a tiny manifest that lives HERE, the closed forms
of a power-retention layer against hand-counted numbers (and every
roofline share they feed against a hand count of what it may read), the
five new readers on made-up records, the configuration against the
catalog, the traffic's blocks and the chip sweep's rehearsal. The tiny
cell's reference is the benchmark's own file, loaded by path
(tests/benchmarks/references/tiny-power.py), and that file is a bit-equal
copy of tests/references/brumby.py.

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

from benchmarks.lib import closed_forms_power, closed_loop  # noqa: E402
from benchmarks.lib.manifest import Manifest, load_path  # noqa: E402

MANIFEST = "tests/benchmarks/BENCHMARK.tiny_power.json"
TINY, CELL = "tiny_power_serve_retention", "brumby_serve_retention"
CONFIG, TRAFFIC = "brumby-14b-base", "batch_closed_retention_slots"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("power_step_ms", "power_step_roofline", "power_scan_ms",
       "power_scan_roofline", "power_state_gb")
LISTED = ("engine_step_ms", "engine_occ_pct", "decode_dev_ms",
          "decode_bw_pct", "peak_hbm_gb.serve", "step_sample_ms",
          "step_self_ms", "setup_engine_s", "decode_proj_ms",
          "decode_ffn_ms", "decode_mixer_ms", "decode_norm_ms",
          "decode_head_ms", "decode_unscoped_ms")


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
         "--seed", str(2 ** 31 + 51051), "--seconds", "1", "--trace", "1"],
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
        "step_sample_ms", "step_self_ms", "power_state_gb"}
    facts = rehearsal["facts"]
    assert facts["reference_tokens_compared"] > 0
    assert facts["reference_tokens_near_tied"] == 0     # there is no router
    # a CPU computes float32 exactly, so the system IS the reference up
    # to the order of its sums; the reference with bfloat16 activations
    # and a state rounded after every token is not: both decide `correct`
    assert facts["reference_mean_margin"] <= 1e-5
    assert facts["control_bf16_mean_margin"] > 1e-5
    # (one answer after the longest prompt is all the tiny cell asks:
    # a loaded machine completes few requests in its one-second window)
    assert facts["reference_probes_long"] == 1
    assert facts["longest_prompt"] == 40
    assert facts["primers"] == facts["clients"] == 4
    assert 0 < facts["tokens_made"] <= facts["decode_steps"] \
        * facts["b_max"] + facts["requests_in_window"]
    # two layers' state [4, 2, 256, 16] and normaliser [4, 2, 16, 16]:
    # the lane holds nothing else
    held = 2 * 4 * 2 * (256 * 16 + 16 * 16) * 4
    assert facts["cache_bytes"] == {"state": held}
    cfg = Manifest(os.path.join(ROOT, MANIFEST)).config("tiny-power")["model"]
    assert facts["weight_bytes"] == {
        "bfloat16": 2 * closed_forms_power.matrix_params(cfg),
        "float32": 4 * closed_forms_power.vector_params(cfg)}
    assert facts["static_bytes"] == sum(facts["weight_bytes"].values()) \
        + held
    assert facts["power"]["pairs"] == 136 and facts["power"]["kept_rows"] \
        == 256
    assert facts["power_plans"]["power_update composed chunk=1"] == 2
    # (prompts of up to 40 positions: one chunk of a lane's width)
    assert facts["power_plans"]["power_scan composed chunk=128"] % 2 == 0
    assert facts["power_chunks"]["chunks"] > 0
    assert facts["kv_cache_write_plans"] == {} and facts["flash_plans"] == {}
    step = facts["decode_step_bytes"]
    assert step["cache"] == 0
    assert step["state"] == 2 * 4 * 2 * 2 * 136 * 17 * 4
    assert step["total"] == step["weights"] + step["state"]


def test_the_real_manifest_finds_every_file_of_the_new_cell():
    m = Manifest()
    w = m.cell(CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    traffic = m.traffic(w["traffic"])
    assert traffic["kind"] == "closed_loop_power"
    assert os.path.isfile(m.find("kinds", traffic["kind"], (".py",)))
    assert os.path.isfile(m.find("references", w["config"], (".py",)))
    assert {e["name"] for e in m.metrics_for("end_to_end", w["name"])} \
        == {"serve_tok_s", "req_tok_ms_p50", "setup_s"}
    listed = {e["name"] for e in m.metrics_for("per_layer", w["name"])}
    for name in listed:
        assert os.path.isfile(m.find("layer_metrics", name, (".py",)))
    assert set(NEW) | set(LISTED) <= listed
    # no key-value rows, no experts, no other state-bearing kernel
    # (nor the prefill's split: the join of an admission's operations
    # takes the splice of ten state tensors for the prefill in every
    # traced run of the cell, PERF.md section 7, so nothing is printed)
    assert not {n for n in listed if n.startswith("prefill_")}
    assert not {"decode_attn_ms", "prefill_attn_ms", "decode_moe_ms",
                "moe_gmm_ms", "ssm_step_ms", "gqa_flash_ms",
                "kv_live_pct", "mla_decode_ms"} & listed
    by_name = {e["name"]: e for e in m.doc["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] == (
            "decode engine" if name == "power_state_gb"
            else "Pallas kernels")
    for name in LISTED:
        # appended behind the cells that were there, which keep their order
        cells = by_name[name]["workloads"]
        assert CELL in cells and cells.index(CELL) > cells.index(
            "lfm2_serve_long_ctx")
    (entry,) = [c for c in m.doc["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == "benchmarks/configs/%s.json" % CONFIG
    # the limits of the contract: 24 cells, a quarter of them on 4 chips
    cells = m.doc["workloads"]
    assert len(cells) <= 24 and len(m.doc["configs"]) <= 24
    assert sum(1 for c in cells if c["chips"] == 4) \
        <= max(1, len(cells) // 4)
    names = [c["name"] for c in cells]
    assert names.index(CELL) > names.index("longcat_serve_reason")
    assert all(len(c["why"]) <= 200 for c in cells + m.doc["configs"])


def test_the_configuration_holds_the_published_numbers():
    m = Manifest()
    cfg = m.config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(x) for x in f if x.strip()]
    (entry,) = [r for r in rows if r["name"] == "Brumby-14B-Base"]
    assert cfg["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and key in cfg["reduced_why"], key
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert (cfg["num_hidden_layers"],
            cfg["published"]["num_hidden_layers"]) == (5, 40)
    for key in ("deployment", "assumed", "departures", "guarantees"):
        assert cfg[key]
    assert "eight stages" in cfg["deployment"]
    for line in ("degree", "the layer", "state_rows_kept",
                 "state dtype", "chunk", "max_len", "b_max"):
        assert cfg["assumed"][line], line
    model = cfg["model"]
    assert (model["d_model"], model["n_head"], model["n_kv_head"],
            model["d_head"], model["d_ff"], model["vocab"],
            model["n_layer"], model["max_length"]) == (
        5120, 40, 8, 128, 17408, 151936, cfg["num_hidden_layers"], 32768)
    assert model["layer_types"] == ["retention"] * 5
    assert model["qk_norm"] == "head"
    # degree, epsilon and chunk are the system's constants, not keys
    assert not [k for k in model if k.startswith("retention")]
    assert model["tie_embeddings"] is False
    assert model["weight_dtype"] == "bfloat16"
    assert model["rope_theta"] == entry["config"]["rope_theta"]
    assert model["norm_eps"] == entry["config"]["rms_norm_eps"]
    assert cfg["serving"] == {"b_max": 32, "max_len": 9216}
    from paddle_tpu.kernels import power
    from paddle_tpu.models import gpt

    gpt._check_cfg(model)
    assert gpt.state_layers(model) == list(range(5))
    # the benchmark's own count of the rows kept is the kernel's
    assert closed_forms_power.kept_rows(model) == power.phi_plan(128)[2]
    # every prompt of the mix is whole chunks, and the benchmark's rule
    # for the chunk is the kernel's
    traffic = m.traffic(TRAFFIC)
    assert all(int(p) % power.scan_chunk(int(p)) == 0
               for p in traffic["prompt_lengths"])
    assert all(closed_forms_power.scan_chunk(T) == power.scan_chunk(T)
               for T in (1, 127, 128, 129, 1000, 1024, 1025, 8192, 9216))


def test_closed_forms_against_hand_counted_numbers():
    model = Manifest().config(CONFIG)["model"]
    c = closed_forms_power
    # ISSUE 51's arithmetic: a layer's matrices, the vocabulary, the
    # state of a layer and slot
    assert c.layer_matrix_params(model) == 2 * 5120 * 5120 \
        + 2 * 5120 * 1024 + 5120 * 8 + 3 * 5120 * 17408 == 330_342_400
    assert c.layer_vector_params(model) == 2 * 5120 + 2 * 128 + 8 == 10_504
    assert c.matrix_params(model) == 2 * 151936 * 5120 + 5 * 330_342_400 \
        == 3_207_536_640
    assert c.vector_params(model) == 5 * 10_504 + 5120 == 57_640
    assert round(c.matrix_params(model) * 2 / 1e9, 2) == 6.42
    assert c.pairs(model) == 128 * 129 // 2 == 8256
    assert c.kept_rows(model) == 36 * 256 == 9216
    # exact: 8 x 8,256 x 129 float32; kept: 8 x (9,216 x 128 + 128 x 128)
    assert c.state_values_per_slot(model, exact=True) * 4 \
        == 5 * 8 * 8256 * 129 * 4 == 5 * 34_080_768
    assert c.state_values_per_slot(model) * 4 \
        == 5 * 8 * (9216 * 128 + 128 * 128) * 4 == 5 * 38_273_024
    assert c.state_bytes(model, 32) == 6_123_683_840
    assert round(c.state_bytes(model, 32, exact=True) / 1e9, 2) == 5.45
    assert round(c.static_bytes(model, 32, 9216, 4, 2) / 1e9, 2) == 12.54
    # keys and values for the same slots would not fit: 8,192 B a
    # position and layer
    assert 2 * 8 * 128 * 4 == 8192
    assert 32 * 5 * 9216 * 8192 == 12_079_595_520
    assert c.state_values_per_slot(model, exact=True) * 4 // 5 \
        // 8192 == 4160        # positions one layer's state stands for
    # a decode step: everything but the table once, the state twice
    step = c.decode_step_bytes(model, 32, 9216, 4, 2)
    assert step["weights"] == (5 * 330_342_400 + 151936 * 5120) * 2 \
        + 57_640 * 4 == 4_859_479_200
    assert step["state"] == 2 * 32 * 5 * 34_080_768 == 10_905_845_760
    assert step["cache"] == 0
    assert step["total"] == step["weights"] + step["state"]
    assert c.decode_step_bytes(model, 32, 123456, 4, 2) == step


def test_no_share_of_a_roofline_can_pass_its_hand_count():
    """What each share of the cell divides a measured time INTO, against
    a count by hand: the least seconds are what the shapes alone give at
    the exact 8,256 pairs, so a kernel at its peak reads 100% and nothing
    reads more — the 9,216 rows the kernel moves are its own cost."""
    model = Manifest().config(CONFIG)["model"]
    c = closed_forms_power
    up = c.update_roofline(model, 32, PEAKS)
    assert up["bytes"] == 5 * 32 * (2 * 8 * 8256 * 129 + 2 * 40 * 128
                                    + 2 * 8 * 128 + 8) * 4
    assert up["flops"] == 5 * 32 * 8256 * 128 * (3 * 8 + 2 * 40)
    assert up["bound"] == "memory"
    assert up["seconds"] == pytest.approx(up["bytes"] / 819e9)
    assert 0.0133 < up["seconds"] < 0.0134
    # what the kernel really moves is more, so it cannot read over 100%
    kept = 5 * 32 * 2 * 8 * (9216 * 128 + 128 * 128) * 4
    assert kept > up["bytes"]
    assert 100.0 * up["seconds"] / (kept / 819e9) < 90.0
    # the scan at the chunk the prompt's length gives: compute-bound,
    # the first chunk reads no state
    Q = 1024
    assert c.scan_chunk(200) == 256
    for T in (1024, 8192):
        sc = c.scan_roofline(model, T, PEAKS)
        assert sc["flops"] == 5 * (
            40 * (T * 4 * Q * 128 + (T - Q) * 2 * 8256 * 128)
            + 8 * T * 2 * 8256 * 128)
        assert sc["bytes"] == 5 * (T * (2 * 40 * 128 + 2 * 8 * 128 + 8)
                                   + 8 * 8256 * 129) * 4
        assert sc["bound"] == "compute"
        assert sc["seconds"] == pytest.approx(sc["flops"] / 197e12)
    # the kernel's own products are of the 9,216 rows in blocks of 128
    # at six passes: more than the count, so its share stays under 100
    assert 2 * 8256 * 128 < 128 * 2 * 128 * 128
    # a ragged last chunk is computed whole
    assert c.scan_flops(model, 1000) == c.scan_flops(model, 1024)
    assert c.scan_flops(model, 1100) == c.scan_flops(model, 2048)
    # (shorter chunks: fewer scores, more positions that read the state)
    assert c.scan_flops(model, 2048, chunk=128) \
        - c.scan_flops(model, 2048) == 40 * (
            2048 * 4 * (128 - 1024) * 128 + (1024 - 128) * 2 * 8256 * 128)
    # decode_bw_pct: the step's bytes over the HBM peak, 19.2 ms
    step = c.decode_step_bytes(model, 32, 9216, 4, 2)
    assert 0.0192 < step["total"] / 819e9 < 0.0193
    reader = _reader("decode_bw_pct")
    rec = {"facts": {"decode_step_bytes": step}, "peaks": PEAKS,
           "trace": None, "spans": {}}
    assert reader.read(rec) is None          # no device trace, no share


def _reader(name):
    return load_path(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                  name + ".py"))


def _record(model, ops, steps, prefills):
    """A made-up traced record: device operations ``(name, start, dur)``,
    step spans ``(end, dur)`` and finished prefill spans ``(end, dur,
    plen)``, all on one clock."""
    return {
        "facts": {"b_max": 32, "window_s": 10.0, "power": {
            "cfg": {k: model[k] for k in (
                "d_model", "n_head", "n_kv_head", "d_head", "n_layer",
                "layer_types")}, "itemsize": 4}},
        "peaks": PEAKS,
        "spans": {"serving.engine.step": steps},
        "trace": {"host_offset_s": 0.0, "t0": 0.0, "t1": 10.0,
                  "ops": {0: ops}},
        "program_spans": [
            {"ph": "E", "site": "serving.engine.prefill", "t": end,
             "dur": dur, "attrs": {"prompt_len": plen}}
            for end, dur, plen in prefills],
        "counters": {"power_state_bytes": 6_123_683_840},
    }


def test_the_five_new_readers_on_made_up_records():
    model = Manifest().config(CONFIG)["model"]
    # two decode steps of five updates of 4 ms; one admission of 8,192
    # with five scans of 60 ms
    ops = []
    for s in (1.0, 2.0):
        ops += [("power_update.%d" % i, s + 0.005 * i, 0.004)
                for i in range(5)]
    ops += [("power_scan.%d" % i, 5.0 + 0.07 * i, 0.06) for i in range(5)]
    ops += [("fusion.1", 1.001, 0.001)]
    rec = _record(model, ops, [(1.03, 0.03), (2.03, 0.03)],
                  [(5.5, 0.5, 8192)])
    assert _reader("power_state_gb").read(rec) == pytest.approx(6.1237, 1e-4)
    assert _reader("power_step_ms").read(rec) == pytest.approx(20.0)
    share = _reader("power_step_roofline").read(rec)
    assert share == pytest.approx(100 * closed_forms_power.update_roofline(
        model, 32, PEAKS)["seconds"] / 0.020)
    assert 60 < share < 70
    # 0.3 s of scans in a traced stretch of 10 s
    assert _reader("power_scan_ms").read(rec) == pytest.approx(30.0)
    assert _reader("power_scan_roofline").read(rec) == pytest.approx(
        100 * closed_forms_power.scan_roofline(
            model, 8192, PEAKS)["seconds"] / 0.3)
    # a program from before this PR has no such kernel, span or gauge:
    # nothing is read and nothing raised (a parent's line leaves the
    # metrics out)
    for bare in ({"counters": {}}, {"counters": None}, {},
                 {"facts": {"ssm": {}}, "trace": None},
                 {"facts": {"power": {}}, "trace": None, "spans": {}}):
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
            traffic["think_time_s"], traffic["trace_seconds"]) == (
        32, 30.0, 4, 0.0, 10.0)
    long_ctx = m.traffic("batch_closed_long_ctx")   # lfm2_serve_long_ctx's
    assert traffic["output_lengths"] == long_ctx["output_lengths"]
    assert traffic["block"] == long_ctx["block"] == 20
    seq = closed_loop.sequence(traffic, seed, 200)
    prompts = Counter({1024: 6, 2048: 6, 4096: 5, 8192: 3})
    answers = Counter({256: 5, 512: 6, 768: 6, 1024: 3})
    for lo in range(0, 200, 20):
        block = seq[lo:lo + 20]
        assert Counter(p for p, _ in block) == prompts
        assert Counter(n for _, n in block) == answers
    assert sum(p * n for p, n in prompts.items()) / 20 == 3174.4
    assert sum(a * n for a, n in answers.items()) / 20 == 601.6
    # 8 requests in 20 END past the 4,128 positions one layer's state
    # costs as keys and values (the shortest answer is 256), 3 at twice
    assert sum(n for p, n in prompts.items() if p + 256 > 4128) == 8
    assert sum(n for p, n in prompts.items() if p > 2 * 4128) == 0 \
        and prompts[8192] == 3
    assert max(p + n for p, n in seq) <= 9216
    assert closed_loop.sequence(traffic, seed + 1, 200) != seq
    assert traffic["reference_probes"] == 32
    assert traffic["reference_probes_long"] == 8
    assert traffic["reference_long_over"] == 4096
    assert traffic["reference_router_gap_floor"] == 0.0
    assert 0.99 <= traffic["gate_range"][0] < traffic["gate_range"][1] < 1
    assert len(traffic["reference_why"]) > 400
    # every padded length the reference is compiled for
    pad = traffic["reference_pad_multiple"]
    assert {-(-(p + n) // pad) * pad for p, n in seq} \
        <= {2048, 3072, 5120, 9216}
    # the primers: 256 + 4 i new tokens for slot i, as ISSUE 51 gives them
    from benchmarks.kinds import closed_loop_power

    class Engine:
        def submit(self, prompt, n_new):
            return (len(prompt), n_new)

    primers = closed_loop_power.prime(Engine(), traffic, 151936, seed)
    assert primers == [(1024, 256 + 4 * i) for i in range(32)]


def test_the_benchmarks_reference_is_the_tests_reference_bit_for_bit():
    with open(os.path.join(ROOT, "tests", "references", "brumby.py"),
              "rb") as f:
        mine = f.read()
    with open(os.path.join(ROOT, "benchmarks", "references",
                           CONFIG + ".py"), "rb") as f:
        assert f.read() == mine


def test_the_chip_sweep_rehearses(tmp_path):
    """tools/power_sweep.py at a tiny shape in interpret mode: the check
    row (kernel against composed form), a scan row and the update row,
    each with the closed form's least time beside it."""
    out = tmp_path / "sweep.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "power_sweep.py"),
         "--rehearse", "--out", str(out)], env=_cpu_env(tmp_path),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = json.loads(out.read_text())["rows"]
    check = rows[0]
    assert check["scan_y_max_abs"] < 1e-4
    assert check["scan_state_max_rel"] < 1e-5
    assert check["update_state_max_rel"] < 1e-5
    assert [r.get("kernel") for r in rows[1:]] == ["power_scan",
                                                   "power_update"]
    assert all(r["least_ms"] > 0 and r["bound"] in ("compute", "memory")
               for r in rows[1:])
    # and without a TPU it refuses to time anything
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "power_sweep.py")],
        env=_cpu_env(tmp_path), capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and "times come from a TPU" in proc.stderr
