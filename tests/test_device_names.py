"""Every device operation answers to the Program op and model sub-block
that made it (PR 49).

* ``core/lowering.py::lower_op`` enters ``jax.named_scope("<op.name_scope>/
  <op.type>")``: metadata only — the StableHLO without locations is byte
  for byte what it is with ``jax.named_scope`` patched away;
* grad ops stand where their forward op was built, the optimizer's under
  ``opt``, a pass-made op under the first scope it replaced;
* every op of the serving and training programs sits under a declared
  class (a builder that forgets a scope fails here);
* ``observe/device_names.py``: the table of a compiled plan places the
  instructions of the entry, the fused and the loop computations; with
  tracing off nothing is registered; a table asked for against a cache
  directory an UNSCOPED lowering populated still comes back scoped, and
  leaves the directory as it found it.
"""

import contextlib
import gc
import os
import re
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import lowering
from paddle_tpu.core.scope import Scope, scope_guard
from paddle_tpu.models import bert, gpt
from paddle_tpu.observe import device_names, trace

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

GPT = dict(d_model=32, d_ff=64, n_head=2, n_layer=2, vocab=64,
           max_length=16, dropout=0.0)
BERT = dict(d_model=32, d_ff=64, n_head=2, n_layer=2, vocab=64,
            type_vocab=2, max_length=16, dropout=0.1)
SCOPE = re.compile(r"^(L\d+(\.\d+)?/)?(%s)(/(%s))*$" % (
    "|".join(re.escape(c) for c in device_names.CLASSES),
    "|".join(re.escape(c) for c in device_names.CLASSES)))


def _tiny(name):
    """The tiny cfg of one architecture, from the test file that owns it."""
    if name == "gpt2":
        return dict(GPT)
    module = {"afmoe": "test_afmoe", "mla": "test_mla", "mhc": "test_mhc",
              "ssm": "test_ssm", "conv": "test_gated_conv",
              "scmoe": "test_scmoe", "olmoe": "test_olmoe"}[name]
    return __import__(module).tiny_cfg()


def _bert_train(seq=8):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss, _feeds = bert.build(BERT, seq_len=seq, max_mask=2)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    feed = {"src_ids": np.ones((2, seq), "int64"),
            "sent_ids": np.zeros((2, seq), "int64"),
            "input_mask": np.ones((2, seq), "float32"),
            "mask_pos": np.zeros((2, 2), "int64"),
            "mask_label": np.ones((2, 2), "int64"),
            "mask_weight": np.ones((2, 2), "float32")}
    return main, startup, loss, feed


def _decode(cfg=None, batch=2, max_len=12):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.build_serving_decode_step(cfg or GPT, batch=batch,
                                      max_len=max_len)
    feed = {"token": np.ones((batch, 1), "int64"),
            "pos": np.zeros((batch, 1), "int64")}
    return main, startup, feed


def _ran(main, startup, feed, fetch, runs=2, repeated=0):
    """(executor, scope, the plan tag of the last dispatch) after
    ``runs`` dispatches of ``main``."""
    trace._reset()
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        for _ in range(runs):
            if repeated:
                exe.run_repeated(main, feed=feed, fetch_list=fetch,
                                 scope=scope, steps=repeated)
            else:
                exe.run(main, feed=feed, fetch_list=fetch, scope=scope)
    tags = [e["attrs"]["plan"] for e in trace.recorder().events()
            if e["site"] == "executor.dispatch" and e["ph"] == "E"]
    return exe, scope, tags[-1]


# --------------------------------------------------------------- lowering
def _stablehlo(debug_info):
    main, startup, feed = _decode()
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        plan, feeds, const, mut, rng = exe._gather(
            main, feed, [gpt.NEXT_TOKEN_VAR], scope)
        return plan.fn.lower(feeds, const, mut, rng).as_text(
            debug_info=debug_info)


def test_a_scope_is_metadata_only(monkeypatch):
    with_scopes = _stablehlo(False)
    assert "L1/attn.core/softmax" in _stablehlo(True)
    assert "attn.core" not in with_scopes
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert "L1/attn.core" not in _stablehlo(True)
    assert _stablehlo(False) == with_scopes      # byte for byte


def test_a_grad_op_and_an_optimizer_op_carry_their_scopes():
    main, _startup, _loss, _feed = _bert_train()
    ops = main.global_block().ops
    grads = [op for op in ops if op.type.endswith("_grad")]
    assert grads and all(SCOPE.match(op.name_scope) for op in grads)
    assert any(op.name_scope == "L1/attn.qkv" and op.type == "mul_grad"
               for op in grads)
    assert any(op.name_scope == "L0/norm" and op.type == "layer_norm_grad"
               for op in grads)
    adam = [op for op in ops if op.type == "adam"]
    assert adam and {op.name_scope for op in adam} == {"opt"}
    # the sums and assigns of backward stand with the op they follow
    assert all(SCOPE.match(op.name_scope) for op in ops
               if op.type in ("sum", "assign", "fill_constant"))
    assert lowering.op_scope(adam[0]) == "opt/adam"


def test_a_pass_made_op_keeps_a_usable_scope():
    from paddle_tpu.core.ir import Graph

    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = fluid.layers.data("x", [4], dtype="float32")
        with fluid.name_scope("L3/ffn"):
            y = fluid.layers.relu(x)
        with fluid.name_scope("L3/norm"):
            z = fluid.layers.relu(y)
    relus = main.global_block().ops[-2:]
    graph = Graph(main)
    node = graph.insert_op_node("relu", {"X": [x.name]}, {"Out": [z.name]},
                                provenance_from=relus)
    assert node.op.name_scope == "fused:L3/ffn,L3/norm"
    assert lowering.op_scope(node.op) == "L3/ffn/relu"
    assert device_names.scope_class(
        device_names.scope_path("jit(step)/L3/ffn/relu/max")) == "ffn"
    relus[0].name_scope = relus[1].name_scope = ""
    bare = graph.insert_op_node("relu", {"X": [x.name]}, {"Out": [z.name]},
                                provenance_from=relus)
    assert lowering.op_scope(bare.op) == "relu/relu"


def test_a_lowering_failure_names_the_layer():
    main, startup, feed = _decode()
    victim = next(op for op in main.global_block().ops
                  if op.name_scope == "L1/attn.core" and op.type == "softmax")
    victim.attrs["axis"] = 17
    main._bump()
    with pytest.raises(Exception, match=r"'softmax' in name_scope "
                                        r"'L1/attn\.core'"):
        with scope_guard(Scope()) as _s:
            scope = Scope()
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup, scope=scope)
            exe.run(main, feed=feed, fetch_list=[gpt.NEXT_TOKEN_VAR],
                    scope=scope)


# ------------------------------------------------------------ the models
def _unscoped(program):
    return sorted({(op.type, op.name_scope)
                   for op in program.global_block().ops
                   if not SCOPE.match(op.name_scope or "")})


@pytest.mark.parametrize("arch", ["gpt2", "olmoe", "afmoe", "mla", "mhc",
                                  "ssm", "conv", "scmoe"])
def test_every_op_of_the_serving_programs_sits_under_a_class(arch):
    cfg = _tiny(arch)
    for build, kw in ((gpt.build_serving_decode_step, {"batch": 2}),
                      (gpt.build_prefill_step, {"batch": 1,
                                                "prompt_len": 8})):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            build(cfg, max_len=16, **kw)
        assert _unscoped(main) == []
        layers = {device_names.layer_of(op.name_scope)
                  for op in main.global_block().ops}
        want = {"L%d.%d" % (i // 2, i % 2) if gpt.has_shortcut(cfg)
                else "L%d" % i for i in range(cfg["n_layer"])}
        assert layers - {None} == want
    if not (gpt.has_state(cfg) or gpt.has_latent(cfg) or cfg.get("mixers")
            or gpt.has_rings(cfg, 16)):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            gpt.build_multi_token_decode_step(cfg, batch=2, steps=2,
                                              max_len=16)
        assert _unscoped(main) == []


@pytest.mark.parametrize("which", ["bert", "gpt", "gpt_new_style"])
def test_every_op_of_the_train_programs_sits_under_a_class(which):
    if which == "bert":
        main = _bert_train()[0]
    else:
        cfg = dict(GPT) if which == "gpt" else dict(
            _tiny("afmoe"), dropout=0.1)
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            loss, _ = gpt.build(cfg, seq_len=8, use_fused_attention=False)
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    assert _unscoped(main) == []
    classes = {device_names.scope_class(op.name_scope)
               for op in main.global_block().ops}
    assert {"embed", "attn.qkv", "attn.core", "attn.out", "ffn", "norm",
            "head", "loss", "opt"} <= classes


# -------------------------------------------------------------- the table
def test_scope_paths_drop_what_jax_adds():
    path = device_names.scope_path
    assert path("jit(step)/jit(main)/L3/attn.core/softmax/reduce_max") \
        == "L3/attn.core/softmax"
    assert path("jit(multi)/while/body/L0/norm/layer_norm_grad/"
                "layer_norm_grad/transpose(jvp(jit(_var)))/mul") \
        == "L0/norm/layer_norm_grad"
    assert path("jit(step)/jvp(L2)/ffn/mul/dot_general;jit(step)/x/y") \
        == "L2/ffn/mul"
    assert path("jit(step)/L1.0/moe.experts/moe_ffn/moe.router/jit(_take)"
                "/gather") == "L1.0/moe.experts/moe_ffn/moe.router"
    assert device_names.scope_class(path(
        "jit(step)/L1.0/moe.experts/moe_ffn/moe.router/dot_general")) \
        == "moe.router"
    assert device_names.layer_of("L1.0/moe.experts/moe_ffn") == "L1.0"
    assert path("feeds[0]") is None and path("reduce.4") is None
    # an instruction XLA made that happens to be called as an op type is
    assert device_names.scope_class(path("reduce_sum")) is None
    assert device_names.scope_class("tower/mul") is None


HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0.1: f32[8,16], param_1.2: f32[16,4]) -> f32[8,4] {
  %param_0.1 = f32[8,16]{1,0} parameter(0)
  %param_1.2 = f32[16,4]{1,0} parameter(1)
  %rsqrt.3 = f32[8,16]{1,0} rsqrt(%param_0.1), metadata={op_name="jit(step)/L0/norm/layer_norm/rsqrt"}
  ROOT %convolution.4 = f32[8,4]{1,0} convolution(%rsqrt.3, %param_1.2), dim_labels=bf_io->bf, metadata={op_name="jit(step)/L0/ffn/mul/dot_general"}
}

%body.5 (arg.6: (f32[8,4])) -> (f32[8,4]) {
  %arg.6 = (f32[8,4]{1,0}) parameter(0)
  %get-tuple-element.7 = f32[8,4]{1,0} get-tuple-element(%arg.6), index=0
  %add.8 = f32[8,4]{1,0} add(%get-tuple-element.7, %get-tuple-element.7), metadata={op_name="jit(multi)/while/body/opt/adam/add"}
  ROOT %tuple.9 = (f32[8,4]{1,0}) tuple(%add.8)
}

ENTRY %main.20 (const_vals_0_.1: f32[32,4], feeds_0_.2: f32[8,16]) -> f32[8,4] {
  %const_vals_0_.1 = f32[32,4]{1,0} parameter(0), metadata={op_name="const_vals[0]"}
  %feeds_0_.2 = f32[8,16]{1,0} parameter(1), metadata={op_name="feeds[0]"}
  %slice-start.1 = ((f32[32,4]{1,0}), f32[16,4]{1,0:S(1)}, s32[]) slice-start(%const_vals_0_.1), slice={[16:32], [0:4]}
  %slice-done.1 = f32[16,4]{1,0:S(1)} slice-done(%slice-start.1)
  %copy.11 = f32[8,16]{0,1} copy(%feeds_0_.2), metadata={op_name="feeds[0]"}
  %convolution_rsqrt_fusion = f32[8,4]{1,0} fusion(%copy.11, %slice-done.1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/L0/ffn/mul/dot_general"}
  %tuple.12 = (f32[8,4]{1,0}) tuple(%convolution_rsqrt_fusion)
  %while.13 = (f32[8,4]{1,0}) while(%tuple.12), condition=%cond.99, body=%body.5
  %custom-call.14 = f32[8,4]{1,0} custom-call(%convolution_rsqrt_fusion), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/L0/attn.core/kv_cache_write/pallas_call"}
  %partition-id.15 = u32[] partition-id()
  ROOT %get-tuple-element.16 = f32[8,4]{1,0} get-tuple-element(%while.13), index=0
}
"""


def test_parse_hlo_places_roots_loop_bodies_and_what_xla_made():
    names, fused, inherited, entry = device_names.parse_hlo(HLO)
    # the ENTRY's instructions in the order a scheduled module runs them
    assert entry[:3] == ["const_vals_0_.1", "feeds_0_.2", "slice-start.1"]
    assert entry[-1] == "get-tuple-element.16" and "add.8" not in entry
    # a fusion stands under its ROOT's path and lists what it holds
    assert names["convolution_rsqrt_fusion"] == "L0/ffn/mul"
    assert fused == {"convolution_rsqrt_fusion": ["L0/ffn/mul",
                                                  "L0/norm/layer_norm"]}
    assert names["rsqrt.3"] == "L0/norm/layer_norm"
    # a scan's body, JAX's ``while/body`` taken off
    assert names["add.8"] == "opt/adam"
    assert names["custom-call.14"] == "L0/attn.core/kv_cache_write"
    # what XLA made for nobody answers to the instruction that uses it:
    # the weight's sliced prefetch and the feed's layout copy to the
    # matvec, through the start/done pair; parameters likewise
    for name in ("slice-start.1", "slice-done.1", "copy.11",
                 "const_vals_0_.1", "param_1.2"):
        assert names[name] == "L0/ffn/mul" and name in inherited
    assert "convolution_rsqrt_fusion" not in inherited
    # and an instruction nobody scoped uses stays unplaced
    assert names["partition-id.15"] is None


def _computations(text):
    """{computation: [instruction names]} and the ENTRY's name."""
    comps, entry, body = {}, None, None
    for line in text.splitlines():
        m = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$", line)
        if m:
            body = comps.setdefault(m.group(2), [])
            entry = m.group(2) if m.group(1) else entry
            continue
        m = re.match(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s", line)
        if m and body is not None:
            op = re.search(r"\s([a-z][a-z0-9\-]*)\(", line[m.end() - 1:])
            body.append((m.group(1), op.group(1) if op else "?"))
    return comps, entry


DATA_OPS = ("parameter", "constant", "tuple", "get-tuple-element",
            "bitcast", "copy", "iota", "broadcast", "while", "call",
            "conditional", "copy-start", "copy-done", "partition-id")


def test_the_table_of_a_decode_plan_places_its_instructions():
    main, startup, feed = _decode()
    exe, scope, tag = _ran(main, startup, feed, [gpt.NEXT_TOKEN_VAR])
    assert device_names.tables()[tag] is None       # nothing compiled yet

    def loads():
        return sum(e["site"] in ("executor.load.lower",
                                 "executor.load.backend")
                   for e in trace.recorder().events())

    before = loads()
    table = device_names.table(tag)
    # read off the executable that ran (JAX's memo of the lowering):
    # nothing was lowered or compiled for it
    assert table["source"] == "ran" and table["same_names"]
    assert before and loads() == before
    assert "slice-done" not in "".join(table["inherited"])   # CPU: none
    assert device_names.tables()[tag] is table
    text = exe.lowered_hlo(main, feed=feed, fetch_list=[gpt.NEXT_TOKEN_VAR],
                           scope=scope)
    comps, entry = _computations(text)
    assert table["entry"] == [[n for n, _op in comps[entry]]]
    # every operation the device would run as one of the ENTRY's is placed
    missing = [(n, op) for n, op in comps[entry]
               if op not in DATA_OPS and table["names"].get(n) is None]
    assert missing == []
    classes = {device_names.scope_class(table["names"][n])
               for n, _op in comps[entry]}
    assert {"embed", "attn.qkv", "attn.core", "attn.out", "ffn",
            "head"} <= classes
    # a fusion stands under a path found inside it, and lists them all
    fusions = [n for n, op in comps[entry] if op == "fusion"]
    assert fusions
    for name in fusions:
        assert table["fused"][name]
        assert table["names"][name] in table["fused"][name]
    # the fused computations' own instructions are in the table too
    inner = [n for c, body in comps.items() if c.startswith("fused_")
             for n, op in body if op not in DATA_OPS]
    assert inner and all(n in table["names"] for n in inner)
    assert sum(table["names"][n] is not None for n in inner) \
        > 0.8 * len(inner)


def test_a_program_built_under_no_class_compiles_nothing_either():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4], dtype="float32")
        out = fluid.layers.fc(x, 3)
    exe, _scope, tag = _ran(main, startup, {"x": np.ones((2, 4), "float32")},
                            [out])
    table = device_names.table(tag)
    # no class in the executable's text, none in the lowering's: not the
    # cache's trap, so no compile of the table's own
    assert table["source"] == "ran" and table["names_differ"] == []
    assert {device_names.scope_class(p)
            for p in table["names"].values()} == {None}
    assert any(p == "mul" for p in table["names"].values())
    plan = next(p for p in exe._cache.values() if p.sig == tag)
    assert list(plan.hlo_text) == ["optimized"]


def test_the_table_places_a_loop_bodys_instructions():
    main, startup, _loss, feed = _bert_train()
    exe, scope, tag = _ran(main, startup, feed, [_loss], repeated=3)
    table = device_names.table(tag)
    plan = next(p for p in exe._cache.values() if p.sig == tag)
    key = next(k for k in plan.hlo_text if k != "optimized")
    comps, entry = _computations(plan.hlo_text[key])
    assert any(op == "while" for _n, op in comps[entry])
    body = max((b for c, b in comps.items() if c != entry
                and not c.startswith("fused_")), key=len)
    placed = [table["names"].get(n) for n, op in body
              if op not in DATA_OPS]
    assert len(placed) > 50 and sum(p is not None for p in placed) \
        > 0.9 * len(placed)
    classes = {device_names.scope_class(p) for p in placed}
    assert {"attn.core", "ffn", "norm", "head", "loss", "opt"} <= classes
    assert any(p and p.endswith("_grad") for p in placed)


def test_two_signatures_of_one_plan_disagree_into_none():
    main, startup, _loss, feed = _bert_train()
    trace._reset()
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        exe.run(main, feed=feed, fetch_list=[_loss], scope=scope)
        exe.run_repeated(main, feed=feed, fetch_list=[_loss], scope=scope,
                         steps=2)
    plan = next(p for p in exe._cache.values() if p.loads.get("run"))
    table = device_names.table(plan.sig)
    one = device_names.parse_hlo(plan.hlo_text["optimized"])[0]
    differ = [n for n, p in one.items()
              if n in table["names"] and table["names"][n] != p]
    assert all(table["names"][n] is None for n in differ)


def test_the_mesh_engines_scan_has_a_table_too():
    from paddle_tpu.parallel.engine import ParallelEngine, make_mesh

    device_names.reset()
    trace._reset()
    main, startup, loss, feed = _bert_train()
    window = {k: np.stack([np.concatenate([v, v])] * 2)
              for k, v in feed.items()}                 # K 2, batch 4
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        engine = ParallelEngine(main, loss_name=loss.name,
                                mesh=make_mesh(jax.devices()[:2]))
        for _ in range(2):
            engine.run_repeated(window, [loss], scope, steps=2,
                                feed_stacked=True)
    tags = [e["attrs"]["plan"] for e in trace.recorder().events()
            if e["site"] == "executor.dispatch" and e["ph"] == "E"]
    del engine, exe          # the reader asks after the run
    gc.collect()
    table = device_names.table(tags[-1])
    assert table["same_names"] and table["source"] == "ran"
    classes = {device_names.scope_class(p) for p in table["names"].values()}
    assert {"attn.core", "ffn", "norm", "head", "loss", "opt"} <= classes
    # the data-parallel gradient all-reduces answer to a grad op's scope
    reduces = [p for n, p in table["names"].items()
               if n.startswith("all-reduce") and p]
    assert reduces


def test_tracing_off_registers_nothing():
    device_names.reset()
    trace.set_trace_enabled(False)
    try:
        main, startup, feed = _decode()
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup, scope=scope)
            exe.run(main, feed=feed, fetch_list=[gpt.NEXT_TOKEN_VAR],
                    scope=scope)
        assert device_names.tables() == {}
        assert device_names.table("00000000") is None
    finally:
        trace.set_trace_enabled(True)


def test_a_steady_dispatch_and_an_unasked_table_cost_nothing(monkeypatch):
    calls = []
    real = device_names.optimized_text
    monkeypatch.setattr(device_names, "optimized_text",
                        lambda *a: calls.append(a) or real(*a))
    main, startup, feed = _decode()
    registered = []
    real_register = device_names.register
    monkeypatch.setattr(device_names, "register",
                        lambda *a, **k: registered.append(a[1])
                        or real_register(*a, **k))
    _exe, _scope, tag = _ran(main, startup, feed, [gpt.NEXT_TOKEN_VAR],
                             runs=4)
    # the startup's dispatch and the step's FIRST one: never a steady one
    assert registered == ["run", "run"]
    assert calls == [] and tag in device_names.tables()


def test_a_table_outlives_its_executor_and_the_registry_is_bounded(
        monkeypatch):
    device_names.reset()
    main, startup, feed = _decode()
    exe, _scope, tag = _ran(main, startup, feed, [gpt.NEXT_TOKEN_VAR])
    exe._cache.clear()
    del exe
    gc.collect()
    # whoever asks does so after the run: the engine is gone by then
    assert device_names.table(tag)["names"]
    monkeypatch.setattr(device_names, "KEEP", 2)
    main2, startup2, feed2 = _decode(batch=3)
    _exe, _scope, tag2 = _ran(main2, startup2, feed2, [gpt.NEXT_TOKEN_VAR])
    # two more signatures (a startup's, the step's) pushed the oldest out
    assert tag not in device_names.tables() and tag2 in device_names.tables()
    assert device_names.table(tag) is None


# ------------------------------------------------------ the cache's trap
def _instruction_names(text):
    return sorted(n for body in _computations(text)[0].values()
                  for n, _op in body)


def test_a_cache_an_unscoped_lowering_filled_still_gives_a_scoped_table(
        tmp_path, monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc

    main, startup, feed = _decode()
    fetch = [gpt.NEXT_TOKEN_VAR]
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()
    try:
        # a checkout from before the scopes populates the cache ...
        with monkeypatch.context() as m:
            m.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
            exe, scope, tag = _ran(main, startup, feed, fetch)
            plan = next(p for p in exe._cache.values() if p.sig == tag)
            args = exe._gather(main, feed, fetch, scope)[1:]
            old = plan.fn.lower(*args).compile().as_text()
        assert os.listdir(tmp_path) and "attn.core" not in old
        # ... this tree LOADS that entry to run (its key holds no
        # metadata), and what it loaded names nothing; yet the table is
        # this tree's, instruction for instruction — and its own compile
        # leaves nothing in the cache for the next process to be pushed
        # out by
        device_names.reset()
        jax.clear_caches()
        exe, scope, tag = _ran(main, startup, feed, fetch)
        held = sorted(os.listdir(tmp_path))
        loaded = [e["attrs"]["cache"] for e in trace.recorder().events()
                  if e["site"] == "executor.load.backend"
                  and e["ph"] == "E" and e["attrs"].get("plan") == tag]
        assert loaded == ["hit"]
        plan = next(p for p in exe._cache.values() if p.sig == tag)
        args = exe._gather(main, feed, fetch, scope)[1:]
        ran = plan.fn.lower(*args).compile().as_text()
        assert "attn.core" not in ran
        table = device_names.table(tag)
        assert table["source"] == "compiled" and table["same_names"]
        assert "attn.core" in plan.hlo_text["optimized"]
        assert _instruction_names(plan.hlo_text["optimized"]) \
            == _instruction_names(ran)
        assert sum(v is not None for v in table["names"].values()) > 100
        assert sorted(os.listdir(tmp_path)) == held
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_enable_compilation_cache", False)
        jax.config.update("jax_compilation_cache_dir", None)
        cc.reset_cache()
