"""The join of PR 49 (``benchmarks/lib/device_scopes.py``) against
hand-made records: classing by the last declared class of a scope path,
the ambiguity rule (a name two plans of one span place differently is
unscoped), the root-of-fusion rule and ``mixed_pct``, the per-step
division of a train stretch, and ``None`` (never a raise) wherever there
is nothing to read. Then the real manifest's new entries against their
readers, and a rehearsal of a tiny manifest that lives HERE
(``BENCHMARK.tiny_scopes.json``): on the CPU every new reader is loaded
and called, raises nothing and reports nothing (no TPU plane)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import device_scopes  # noqa: E402
from benchmarks.lib.manifest import Manifest  # noqa: E402

MANIFEST = Manifest()
TINY = "tests/benchmarks/BENCHMARK.tiny_scopes.json"
CLASSES = ("embed", "attn.qkv", "attn.core", "attn.out", "ffn",
           "moe.router", "moe.experts", "moe.shared", "mixer", "conv",
           "mhc", "norm", "head", "loss", "opt")
NEW = {
    "decode_attn_ms": ("decode", ("attn.core",)),
    "decode_proj_ms": ("decode", ("attn.qkv", "attn.out")),
    "decode_ffn_ms": ("decode", ("ffn",)),
    "decode_moe_ms": ("decode", ("moe.router", "moe.experts",
                                 "moe.shared")),
    "decode_mixer_ms": ("decode", ("mixer", "conv")),
    "decode_mhc_ms": ("decode", ("mhc",)),
    "decode_norm_ms": ("decode", ("norm",)),
    "decode_head_ms": ("decode", ("head", "embed")),
    "decode_unscoped_ms": ("decode", (None,)),
    "prefill_attn_ms": ("prefill", ("attn.core",)),
    "prefill_proj_ms": ("prefill", ("attn.qkv", "attn.out", "ffn")),
    "prefill_moe_ms": ("prefill", ("moe.router", "moe.experts",
                                   "moe.shared")),
    "prefill_mixer_ms": ("prefill", ("mixer", "conv")),
    "prefill_mhc_ms": ("prefill", ("mhc",)),
    "prefill_norm_ms": ("prefill", ("norm",)),
    "prefill_head_ms": ("prefill", ("head", "embed")),
    "prefill_unscoped_ms": ("prefill", (None,)),
    "train_attn_ms": ("train", ("attn.qkv", "attn.core", "attn.out")),
    "train_ffn_ms": ("train", ("ffn",)),
    "train_norm_ms": ("train", ("norm",)),
    "train_head_ms": ("train", ("embed", "head", "loss")),
    "train_opt_ms": ("train", ("opt",)),
    "train_unscoped_ms": ("train", (None,)),
}
_ids = iter(range(1, 10 ** 6))


def classify(path):
    for comp in reversed((path or "").split("/")):
        if comp in CLASSES:
            return comp
    return None


def span(name, start, dur, parent=None, **attrs):
    return {"t": start + dur, "ph": "E", "site": name, "trace": "t",
            "span": next(_ids), "parent": parent and parent["span"],
            "tid": 1, "dur": dur, "attrs": attrs or None}


def op(name, start_ms, dur_ms, opcode="fusion"):
    return (name, 100.0 + start_ms * 1e-3, dur_ms * 1e-3, opcode)


TABLES = {
    "aaaa": {"source": "compiled",
             "names": {"fusion.1": "L0/attn.core/softmax",
                       "fusion.2": "L0/attn.qkv/mul",
                       "fusion.3": "L0/ffn/mul",
                       "fusion.4": "L0/moe.experts/moe_ffn/moe.router",
                       "moe_gmm_up.1": "L0/moe.experts/moe_ffn",
                       "fusion.5": "head/arg_max",
                       "fusion.6": "tower/mul",       # no declared class
                       "copy.1": None,
                       "slice-done.1": "L0/ffn/mul",  # its user's
                       "fusion.9": "L1/attn.out/mul"},
             "inherited": ["slice-done.1"],
             # fusion.2's root is a projection, its body holds a norm too
             "fused": {"fusion.1": ["L0/attn.core/softmax"],
                       "fusion.2": ["L0/attn.qkv/mul",
                                    "L0/norm/layer_norm"],
                       "fusion.3": ["L0/ffn/mul", "L0/ffn/relu"]}},
    # the logits plan of the same program: fusion.9 is something else
    "bbbb": {"source": "cache",
             "names": {"fusion.9": "head/mul", "fusion.1":
                       "L0/attn.core/softmax"},
             "fused": {}},
}


def serving_record(step_plans=(("aaaa",), ("aaaa",), ("aaaa", "bbbb"))):
    """Three decode steps of 10 ms from host second 10.0, a step's
    operations 1 ms after its start; the device clock is the host's plus
    90 s. The third step dispatched two plans."""
    spans, ops = [], []
    for i, plans in enumerate(step_plans):
        t = 10.0 + 0.010 * i
        step = span("serving.engine.step", t, 0.010, active=2)
        call = span("executor.call", t, 0.002, step, site="run")
        spans += [step, call]
        spans += [span("executor.dispatch", t + 0.0005, 0.001, call,
                       plan=p) for p in plans]
        at = (t + 90.0 - 100.0) * 1e3 + 1.0
        k = i + 1
        ops += [op("fusion.1", at, 1.0 * k), op("fusion.2", at + 1, 2.0),
                op("fusion.3", at + 3, 0.375),
                op("slice-done.1", at + 3.5, 0.125, "async-done"),
                op("fusion.4", at + 4, 0.25),
                op("moe_gmm_up.1", at + 5, 0.75, "custom-call"),
                op("fusion.5", at + 6, 0.125), op("fusion.6", at + 7, 0.5),
                op("copy.1", at + 7.5, 0.25, "copy"),
                op("fusion.9", at + 8, 0.125),
                op("while.1", at, 9.0, "while")]      # a container
    # an admission of 64 tokens and one of 128, each its own plan
    for t, plen, plan in ((10.031, 64, "cccc"), (10.036, 128, "dddd")):
        pre = span("serving.engine.prefill", t, 0.004, prompt_len=plen)
        spans += [pre, span("executor.dispatch", t + 0.0001, 0.001, pre,
                            plan=plan)]
        at = (t + 90.0 - 100.0) * 1e3
        ops += [op("fusion.7", at + 1, 1.5), op("fusion.8", at + 2, 0.5)]
    return {"program_spans": spans, "program_window": (9.0, 20.0),
            "facts": {}, "spans": {},
            "trace": {"t0": 99.9, "t1": 100.2, "host_offset_s": 90.0,
                      "window_s": 0.3, "ops": {0: sorted(
                          ops, key=lambda e: e[1])}}}


PREFILL_TABLES = {
    "cccc": {"source": "compiled", "fused": {},
             "names": {"fusion.7": "L0/attn.core/fused_attention",
                       "fusion.8": "L0/ffn/mul"}},
    "dddd": {"source": "compiled", "fused": {},
             "names": {"fusion.7": "L0/attn.core/fused_attention",
                       "fusion.8": "L0/moe.experts/moe_ffn"}},
}


def test_a_decode_step_splits_by_the_last_class_of_the_scope():
    got = device_scopes.split(serving_record(), "decode", TABLES, classify)
    ms = {k: v * 1e3 for k, v in got["by_class"].items()}
    assert got["spans"] == 3 and got["plans"] == ["aaaa", "bbbb"]
    assert got["sources"] == ["cache", "compiled"]
    assert ms["attn.core"] == pytest.approx(2.0)      # median of 1, 2, 3
    assert ms["attn.qkv"] == pytest.approx(2.0)       # the ROOT's class
    assert ms["ffn"] == pytest.approx(0.5)
    # the lowering's own class refines its op's
    assert ms["moe.router"] == pytest.approx(0.25)
    assert ms["moe.experts"] == pytest.approx(0.75)
    assert ms["head"] == pytest.approx(0.125)
    # the container is no operation; a scope without a class, a name the
    # table maps to nothing: unscoped. fusion.9 is ``attn.out`` in two
    # steps and AMBIGUOUS in the third (two plans place it differently):
    # its median over the steps is still the projection's
    assert ms["attn.out"] == pytest.approx(0.125)
    assert ms[None] == pytest.approx(0.75)
    assert "norm" not in ms
    # fusion.2 holds two classes: 3 x 2.0 of the classed 3 x 3.875 + 6
    # (fusion.9 is classed in two steps only: 2 x 0.125)
    classed = 6.0 + 3 * (2.0 + 0.5 + 0.25 + 0.75 + 0.125) + 2 * 0.125
    assert got["mixed_pct"] == pytest.approx(100 * 6.0 / classed)
    # a weight's sliced prefetch answers to the matvec that uses it
    assert got["inherited_pct"] == pytest.approx(100 * 3 * 0.125 / classed)
    assert got["foreign_s"] is None and got["run_ops"] == []
    assert set(got["classes"]) == {"attn.core", "attn.qkv", "attn.out",
                                   "ffn", "moe.router", "moe.experts",
                                   "norm", "head"}


def test_a_name_two_plans_place_differently_is_unscoped():
    record = serving_record(step_plans=(("aaaa", "bbbb"),) * 3)
    got = device_scopes.split(record, "decode", TABLES, classify)
    ms = {k: v * 1e3 for k, v in got["by_class"].items()}
    assert "attn.out" not in ms
    assert ms[None] == pytest.approx(0.875)
    assert ms["attn.core"] == pytest.approx(2.0)   # placed alike by both


def test_the_prefill_takes_the_longest_prompt_of_the_stretch():
    got = device_scopes.split(serving_record(), "prefill", PREFILL_TABLES,
                              classify)
    assert got["prompt_len"] == 128 and got["spans"] == 1
    assert got["plans"] == ["cccc", "dddd"]
    ms = {k: v * 1e3 for k, v in got["by_class"].items()}
    assert ms == {"attn.core": pytest.approx(1.5),
                  "moe.experts": pytest.approx(0.5)}


def admission_record(ops_ms, plan="eeee"):
    """One admission of 4 ms at host second 10.0 (device 100.0) that
    dispatched ``plan``; ``ops_ms`` are ``(name, start, dur[, opcode])``
    in ms from its start."""
    pre = span("serving.engine.prefill", 10.0, 0.004, prompt_len=128)
    spans = [pre, span("executor.dispatch", 10.0001, 0.001, pre, plan=plan)]
    return {"program_spans": spans, "program_window": (9.0, 20.0),
            "facts": {}, "spans": {},
            "trace": {"t0": 99.9, "t1": 100.2, "host_offset_s": 90.0,
                      "window_s": 0.3, "ops": {0: [op(*o) for o in ops_ms]}}}


OWN = {"eeee": {"source": "ran", "fused": {},
                "names": {"fusion.7": "L0/attn.core/fused_attention",
                          "fusion.30": "L0/ffn/mul",      # a loop body's
                          "fusion.8": "L0/moe.experts/moe_ffn",
                          "copy.3": "head/arg_max"},
                "entry": [["tokens.1", "fusion.7", "while.2", "fusion.8",
                           "copy.3", "tuple.4"]]}}


def test_an_admission_counts_only_its_own_programs_run():
    # the decode step in flight ends inside the span: its fusion.8 is not
    # the prefill's (two programs number their fusions alike), fusion.99
    # the prefill's table does not know, and its last operation, copy.3,
    # stands LATER in the prefill's ENTRY than the operation after it
    tail = [("fusion.8", 0.1, 0.3), ("fusion.99", 0.4, 0.2),
            ("copy.3", 0.6, 0.1, "copy")]
    run = [("fusion.7", 1.0, 1.5), ("while.2", 2.5, 0.9, "while"),
           ("fusion.30", 2.55, 0.25), ("fusion.30", 2.85, 0.25),
           ("fusion.8", 3.4, 0.5), ("copy.3", 3.9, 0.0625, "copy")]
    got = device_scopes.split(admission_record(tail + run), "prefill", OWN,
                              classify)
    ms = {k: v * 1e3 for k, v in got["by_class"].items()}
    assert ms == {"attn.core": pytest.approx(1.5),
                  "ffn": pytest.approx(0.5),
                  "moe.experts": pytest.approx(0.5),
                  "head": pytest.approx(0.0625)}
    assert got["foreign_s"] * 1e3 == pytest.approx(0.6)
    assert got["run_ops"] == [4, 4] and got["spans"] == 1
    assert got["run_s"] == [pytest.approx(2.5625e-3)] * 2
    # an admission with nothing in flight before it leaves nothing out
    got = device_scopes.split(admission_record(run), "prefill", OWN,
                              classify)
    assert got["foreign_s"] == 0.0 and got["run_ops"] == [4, 4]
    assert got["by_class"]["attn.core"] * 1e3 == pytest.approx(1.5)
    # a span whose last operation is not its program's reads nothing
    got = device_scopes.split(admission_record(run + [("fusion.99", 3.97,
                                                       0.01)]),
                              "prefill", OWN, classify)
    assert got is None


def test_the_order_that_explains_the_most_is_the_program_that_ran():
    events = [(0.0, 1.0, "fusion.2", "fusion"), (1.0, 1.0, "fusion.1",
                                                 "fusion"),
              (2.0, 1.0, "fusion.2", "fusion"), (3.0, 1.0, "fusion.3",
                                                 "fusion")]
    # the K-step scan's order knows only the last one; the step's three
    assert device_scopes.own_run(events, [["fusion.3"],
                                          ["fusion.1", "fusion.2",
                                           "fusion.3"]]) == (1.0, 3)
    assert device_scopes.own_run(events, [["fusion.9"]]) == (None, 0)
    assert device_scopes.own_run([], [["fusion.9"]]) == (None, 0)


def test_operations_of_one_tick_run_in_the_entrys_order():
    order = ["copy-start.1", "fusion.1", "copy-done.1", "cond.2",
             "fusion.3"]
    # the profiler's clock ticks coarser than a copy-done lasts: three
    # ENTRY operations share a timestamp and come in any order; a branch's
    # first operation shares its conditional's, and is no ENTRY operation
    events = [(5.0, 0.5, "fusion.9", "fusion"),        # another program's
              (6.0, 0.0, "copy-done.1", "copy-done"),
              (6.0, 0.0, "copy-start.1", "copy-start"),
              (6.0, 1.0, "fusion.1", "fusion"),
              (7.0, 0.0, "custom-call.7", "custom-call"),
              (7.0, 2.0, "cond.2", "conditional"),
              (7.5, 1.0, "fusion.1", "fusion"),         # the branch's own
              (9.0, 1.0, "fusion.3", "fusion")]
    assert device_scopes.own_run(events, [order]) == (6.0, 5)
    # ... but one of them twice is two runs
    again = events[:4] + [(6.0, 0.0, "copy-done.1", "copy-done")] \
        + events[4:]
    assert device_scopes.own_run(again, [order]) == (7.0, 2)


def test_a_train_stretch_divides_by_its_steps():
    record = serving_record()
    record["facts"] = {"windows_traced": 2, "steps_per_window": 4}
    got = device_scopes.split(record, "train", TABLES, classify)
    # every operation of the stretch, every plan of the window, 8 steps
    assert got["spans"] == 1
    assert got["by_class"]["attn.core"] * 1e3 == pytest.approx(6.0 / 8)
    record = serving_record()
    record["facts"] = {"windows_traced": 0, "steps_per_window": 4}
    assert device_scopes.split(record, "train", TABLES, classify) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_every_new_reader_is_its_constants_and_one_call(name, monkeypatch):
    reader = MANIFEST.load_module("layer_metrics", name)
    site, classes = NEW[name]
    assert (reader.SITE, reader.CLASSES) == (site, classes)
    entry = next(m for m in MANIFEST.doc["per_layer"] if m["name"] == name)
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        entry["layer"], entry["unit"], entry["moves"], entry["source"])
    assert entry["layer"] == "model step on the device"
    cells = {w["name"] for w in MANIFEST.doc["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= cells
    tables = dict(TABLES, **PREFILL_TABLES)
    record = serving_record()
    record["facts"] = {"windows_traced": 1, "steps_per_window": 2}
    real = device_scopes._split
    monkeypatch.setattr(
        device_scopes, "_split",
        lambda rec, which, _t, _c: real(rec, which, tables, classify))
    value = reader.read(record)
    split = device_scopes.split(record, site)
    held = set(split["classes"])
    if None in classes:
        assert value == pytest.approx(
            split["by_class"].get(None, 0.0) * 1e3)
    elif not held & set(classes):
        assert value is None        # a cell without that part
    else:
        assert value == pytest.approx(sum(
            split["by_class"].get(c, 0.0) for c in classes) * 1e3)


@pytest.mark.parametrize("damage", ["no trace", "no offset", "no span",
                                    "no plan", "no table", "no program"])
def test_nothing_to_read_is_none_and_no_raise(damage, monkeypatch):
    record = serving_record()
    tables = TABLES
    if damage == "no trace":
        record["trace"] = None
    elif damage == "no offset":
        record["trace"]["host_offset_s"] = None
    elif damage == "no span":
        record["program_spans"] = [
            ev for ev in record["program_spans"]
            if ev["site"] != "serving.engine.step"]
    elif damage == "no plan":
        record["program_spans"] = [
            ev for ev in record["program_spans"]
            if ev["site"] != "executor.dispatch"]
    elif damage == "no table":
        tables = {"aaaa": None, "bbbb": None}
    elif damage == "no program":
        # the parent of the PR that added the tables
        monkeypatch.setattr(device_scopes, "_device_names", lambda: None)
        tables = None
    assert device_scopes.split(record, "decode", tables,
                               classify if tables is not None
                               else None) is None
    if damage == "no program":
        reader = MANIFEST.load_module("layer_metrics", "decode_attn_ms")
        assert reader.read(serving_record()) is None


def test_a_table_the_program_cannot_make_fails_no_run(monkeypatch, capsys):
    class Broken:
        scope_class = staticmethod(classify)

        @staticmethod
        def table(plan):
            raise RuntimeError("the compiler refused plan %s" % plan)

    monkeypatch.setattr(device_scopes, "_device_names", lambda: Broken)
    reader = MANIFEST.load_module("layer_metrics", "decode_attn_ms")
    assert reader.read(serving_record()) is None
    assert "no table for plan aaaa: RuntimeError" in capsys.readouterr().err


def test_the_real_manifest_only_grew_by_these_entries():
    doc = MANIFEST.doc
    names = [m["name"] for m in doc["per_layer"]]
    assert set(NEW) <= set(names)
    first = min(names.index(n) for n in NEW)
    assert set(names[first:first + len(NEW)]) == set(NEW)   # appended
    serving = {w["name"] for w in doc["workloads"]
               if "serve" in w["name"]}
    by_name = {m["name"]: m for m in doc["per_layer"]}
    assert set(by_name["decode_unscoped_ms"]["workloads"]) >= {
        "gpt2m_serve_chat", "gpt2m_serve_batch", "trinity_serve_mixed",
        "lfm2_serve_long_ctx", "pangu_serve_reason"}
    assert set(by_name["decode_unscoped_ms"]["workloads"]) <= serving
    assert set(by_name["train_opt_ms"]["workloads"]) >= {
        "bert_train_s512", "bert_train_s128"}
    assert by_name["decode_mhc_ms"]["workloads"] == ["xing_serve_docs"]


def _rehearse(tmp_path, cell):
    root = tmp_path / "checkout"
    root.mkdir()
    for name in ("benchmarks", "paddle_tpu", "tests", "BENCHMARK.json"):
        os.symlink(os.path.join(ROOT, name), root / name)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    env["BENCH_RUN"] = "the driver sets this; the benchmark ignores it"
    env["DEVICE_SCOPES_CALLS"] = str(tmp_path / "calls.jsonl")
    proc = subprocess.run(
        ["nice", "-n", "19", sys.executable, "-c",
         # count the readers' calls from inside the run
         "import json, os, runpy, sys\n"
         "from benchmarks.lib import device_scopes as d\n"
         "real = d.read_ms\n"
         "def read_ms(record, site, classes):\n"
         "    got = real(record, site, classes)\n"
         "    with open(os.environ['DEVICE_SCOPES_CALLS'], 'a') as f:\n"
         "        f.write(json.dumps([site, list(classes), got]) + '\\n')\n"
         "    return got\n"
         "d.read_ms = read_ms\n"
         "sys.argv = ['benchmarks/run.py'] + sys.argv[1:]\n"
         "runpy.run_path('benchmarks/run.py', run_name='__main__')\n",
         "--manifest", TINY, "--cpu-rehearsal", "--workload", cell,
         "--seed", str(2 ** 31 + 49049), "--seconds", "1", "--trace", "1"],
        cwd=str(root), env=env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, "\n".join(
        x[:400] for x in proc.stderr.splitlines()
        if "cpu_aot_loader" not in x)[-3000:]
    out = [json.loads(x) for x in proc.stdout.strip().splitlines()
           if x.startswith("{")]
    with open(tmp_path / "calls.jsonl") as f:
        calls = [json.loads(x) for x in f]
    return out[-2], out[-1], calls


@pytest.mark.parametrize("trace", [True])
@pytest.mark.parametrize("cell,sites", [
    ("tiny_serve_batch", {"decode": 9, "prefill": 8}),
    ("tiny_train", {"train": 6}),
])
def test_rehearsal_calls_every_new_reader_and_reports_none(
        tmp_path, cell, sites, trace):
    rehearsal, last, calls = _rehearse(tmp_path, cell)
    assert last["correct"] is True and last["metrics"] == {}
    assert rehearsal["rehearsal"] == "passed"
    # a CPU trace has no TPU plane: nothing of the new names is reported
    assert not set(rehearsal["would_report"]) & set(NEW)
    assert "compile_s" in rehearsal["would_report"]
    counted = {}
    for site, _classes, got in calls:
        counted[site] = counted.get(site, 0) + 1
        assert got is None
    assert counted == sites
