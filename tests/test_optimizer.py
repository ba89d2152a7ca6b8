"""Graph-optimizing pass pipeline (core/passes/) tests.

Covers, per docs/OPTIMIZER.md:

* each pass in isolation (fold / copy-prop / CSE / DCE / fusion / AMP
  tagging) on hand-built programs;
* the safety invariants: RNG consumers survive every pass, in-place
  rewrites never CSE, verify-after-every-pass fails loudly with the
  pass name;
* executor integration: optimization happens on a clone at prepare
  time, the level keys the plan cache, PADDLE_TPU_OPTIMIZE=0 provably
  bypasses (zero paddle_optimizer_* movement), and optimized runs are
  BITWISE identical to unoptimized ones — through dropout (RNG chain)
  and under bf16 AMP;
* the model-zoo gate: every example train+startup program optimizes
  clean at level 2 with a measurable op-count reduction on >= 3 models;
* (slow) the cold steps/sec pin: an elementwise-chain-heavy workload
  runs >= 1.1x faster at level 2 than at level 0, calibrated-ratio
  pattern, no absolute-ms asserts.
"""

import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.ir import Graph
from paddle_tpu.core.passes import (OptimizerPassError, PIPELINE,
                                    PassManager, optimize_level,
                                    optimize_program)
from paddle_tpu.core.scope import Scope, scope_guard
from paddle_tpu.observe.families import REGISTRY

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))


def _ops(prog):
    return [op.type for op in prog.global_block().ops]


def _optimizer_counters():
    """name -> total over samples, for every paddle_optimizer_* family
    (histogram samples contribute their observation count)."""
    snap = REGISTRY.snapshot()["metrics"]
    out = {}
    for name, fam in snap.items():
        if name.startswith("paddle_optimizer_"):
            out[name] = sum(s.get("value", s.get("count", 0))
                            for s in fam["samples"])
    return out


# --------------------------------------------------------------- passes
def test_constant_folding_evaluates_const_subgraph(fresh_programs):
    main, startup, scope = fresh_programs
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        c = fluid.layers.fill_constant([4], "float32", 3.0)
        c = fluid.layers.scale(c, scale=2.0)
        c = fluid.layers.exp(c)
        out = fluid.layers.elementwise_add(x, c)
        loss = fluid.layers.reduce_mean(out)
    n0 = len(main.global_block().ops)
    opt, stats = optimize_program(main, fetch_list=[loss], level=1)
    fold = [r for r in stats if r["pass"] == "constant_folding_pass"][0]
    assert fold["folded"] == 3 and fold["materialized"] == 1
    assert len(opt.global_block().ops) == n0 - 2
    av = [op for op in opt.global_block().ops if op.type == "assign_value"]
    assert len(av) == 1
    np.testing.assert_allclose(av[0].attrs["values"],
                               [float(np.exp(6.0))] * 4, rtol=1e-6)
    # user program untouched
    assert len(main.global_block().ops) == n0
    # the folded program computes the same value
    with scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        X = np.ones((2, 4), np.float32)
        a, = exe.run(main, feed={"x": X}, fetch_list=[loss.name],
                     scope=scope)
        b, = exe.run(opt, feed={"x": X}, fetch_list=[loss.name],
                     scope=scope)
    assert np.array_equal(a, b)


def test_fold_skips_when_materialization_is_churn(fresh_programs):
    # ONE fill_constant consumed by a survivor: replacing it with one
    # assign_value removes nothing — the pass must leave it alone
    main, startup, scope = fresh_programs
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        c = fluid.layers.fill_constant([4], "float32", 1.5)
        loss = fluid.layers.reduce_mean(fluid.layers.elementwise_add(x, c))
    opt, stats = optimize_program(main, fetch_list=[loss], level=1)
    fold = [r for r in stats if r["pass"] == "constant_folding_pass"][0]
    assert fold["folded"] == 0
    assert "fill_constant" in _ops(opt)


def test_copy_propagation_drops_pure_copies(fresh_programs):
    main, startup, scope = fresh_programs
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        h = fluid.layers.relu(x)
        c = fluid.layers.assign(h)          # pure copy -> dropped
        loss = fluid.layers.reduce_mean(c)
        # a copy into a PERSISTABLE target is state, not litter
        snap = fluid.layers.create_tensor("float32", name="snap",
                                          persistable=True) \
            if hasattr(fluid.layers, "create_tensor") else None
        if snap is not None:
            fluid.layers.assign(h, output=snap)
    n_assign = _ops(main).count("assign")
    opt, stats = optimize_program(main, fetch_list=[loss], level=1)
    cp = [r for r in stats if r["pass"] == "copy_propagation_pass"][0]
    assert cp["copies_removed"] == 1
    assert _ops(opt).count("assign") == n_assign - 1
    # the consumer reads the source directly now
    mean = [op for op in opt.global_block().ops
            if op.type == "reduce_mean"][0]
    relu = [op for op in opt.global_block().ops if op.type == "relu"][0]
    assert mean.input("X") == relu.output("Out")
    # copy-prop also normalizes names so CSE sees through copies:
    # exp(assign(h)) and exp(h) merge once the copy is gone
    main2, startup2 = fluid.Program(), fluid.Program()
    with fluid.program_guard(main2, startup2):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        h = fluid.layers.relu(x)
        a = fluid.layers.exp(fluid.layers.assign(h))
        b = fluid.layers.exp(h)
        loss2 = fluid.layers.reduce_mean(fluid.layers.elementwise_add(
            a, b))
    opt2, stats2 = optimize_program(main2, fetch_list=[loss2], level=1)
    assert _ops(opt2).count("exp") == 1
    assert _ops(opt2).count("assign") == 0


def test_cse_merges_duplicates_not_versioned_rewrites(fresh_programs):
    main, startup, scope = fresh_programs
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        a = fluid.layers.exp(x)
        b = fluid.layers.exp(x)      # duplicate of a
        loss = fluid.layers.reduce_mean(fluid.layers.elementwise_add(a, b))
    opt, stats = optimize_program(main, fetch_list=[loss], level=1)
    cse = [r for r in stats
           if r["pass"] == "common_subexpression_elimination_pass"][0]
    assert cse["cse_removed"] == 1
    assert _ops(opt).count("exp") == 1
    # the surviving add reads the SAME var twice now
    add = [op for op in opt.global_block().ops
           if op.type == "elementwise_add"][0]
    assert add.input("X") == add.input("Y")

    # versioned rewrite: identical reads AROUND an in-place write to the
    # source must NOT merge
    main2 = fluid.Program()
    blk = main2.global_block()
    blk.create_var(name="s", shape=(4,), dtype="float32",
                   persistable=True)
    blk.create_var(name="r1", shape=(4,), dtype="float32")
    blk.create_var(name="r2", shape=(4,), dtype="float32")
    blk.append_op("exp", {"X": ["s"]}, {"Out": ["r1"]})
    blk.append_op("scale", {"X": ["s"]}, {"Out": ["s"]}, {"scale": 2.0})
    blk.append_op("exp", {"X": ["s"]}, {"Out": ["r2"]})
    blk.append_op("elementwise_add", {"X": ["r1"], "Y": ["r2"]},
                  {"Out": ["out"]})
    opt2, _ = optimize_program(main2, fetch_list=["out"], level=1,
                               verify=False)
    assert _ops(opt2).count("exp") == 2


def test_cse_never_merges_onto_an_overwritten_target():
    """Review regression: a first occurrence whose OUTPUT name is later
    rewritten is not a stable merge target — rewired consumers would
    read the overwritten value. [a=scale(x,2); a=tanh(x); b=scale(x,2)]
    must keep b."""
    main = fluid.Program()
    blk = main.global_block()
    blk.create_var(name="x", shape=(4,), dtype="float32", is_data=True)
    for n in ("a", "b", "outv"):
        blk.create_var(name=n, shape=(4,), dtype="float32")
    blk.append_op("scale", {"X": ["x"]}, {"Out": ["a"]}, {"scale": 2.0})
    blk.append_op("tanh", {"X": ["x"]}, {"Out": ["a"]})
    blk.append_op("scale", {"X": ["x"]}, {"Out": ["b"]}, {"scale": 2.0})
    blk.append_op("scale", {"X": ["b"]}, {"Out": ["outv"]},
                  {"scale": 1.0})
    opt, _ = optimize_program(main, fetch_list=["outv"], level=1,
                              verify=False)
    consumer = [op for op in opt.global_block().ops
                if op.output("Out") == ["outv"]][0]
    assert consumer.input("X") == ["b"]  # NOT rewired onto stale 'a'
    # b's producer survives as scale(x, 2.0); the dead 'a' writers are
    # legitimately DCE'd afterwards
    b_prod = [op for op in opt.global_block().ops
              if op.output("Out") == ["b"]][0]
    assert b_prod.type == "scale" and b_prod.attrs["scale"] == 2.0


def test_copy_propagation_keeps_snapshot_copies():
    """Review regression: assign(w)->snap where w is updated in place
    AFTER the copy is a SNAPSHOT — dropping it would hand consumers the
    updated value."""
    main = fluid.Program()
    blk = main.global_block()
    blk.create_var(name="w", shape=(4,), dtype="float32",
                   persistable=True)
    for n in ("snap", "outv"):
        blk.create_var(name=n, shape=(4,), dtype="float32")
    blk.append_op("assign", {"X": ["w"]}, {"Out": ["snap"]})
    blk.append_op("scale", {"X": ["w"]}, {"Out": ["w"]}, {"scale": 0.5})
    blk.append_op("scale", {"X": ["snap"]}, {"Out": ["outv"]},
                  {"scale": 1.0})
    opt, stats = optimize_program(main, fetch_list=["outv"], level=1,
                                  verify=False)
    assert "assign" in _ops(opt)
    cp = [r for r in stats if r["pass"] == "copy_propagation_pass"][0]
    assert cp["copies_removed"] == 0


def test_dce_is_fetch_relative_and_keeps_rng_ops(fresh_programs):
    main, startup, scope = fresh_programs
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        live = fluid.layers.reduce_mean(fluid.layers.relu(x))
        # dead-but-RNG: dropout must survive (removing it would shift
        # the key chain of every later RNG consumer)
        dead_rng = fluid.layers.dropout(x, dropout_prob=0.5)
        fluid.layers.tanh(dead_rng)  # dead, pure -> removed
        fluid.layers.sigmoid(x)      # dead, pure -> removed
    opt, stats = optimize_program(main, fetch_list=[live], level=1)
    types = _ops(opt)
    assert "dropout" in types
    assert "tanh" not in types and "sigmoid" not in types
    dce = [r for r in stats if r["pass"] == "dead_op_elimination_pass"][0]
    assert dce["dce_removed"] == 2


def test_fusion_collapses_chain_and_matches_bitwise(fresh_programs):
    main, startup, scope = fresh_programs
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.relu(x)
        h = fluid.layers.scale(h, scale=1.7, bias=0.3)
        h = fluid.layers.tanh(h)
        h = fluid.layers.sigmoid(h)
        out = fluid.layers.reduce_mean(h)
    opt, stats = optimize_program(main, fetch_list=[out], level=2)
    fu = [r for r in stats if r["pass"] == "fuse_elementwise_pass"][0]
    assert fu["chains_fused"] == 1 and fu["ops_fused_away"] == 3
    types = _ops(opt)
    assert types.count("fused_elementwise") == 1
    for t in ("relu", "scale", "tanh", "sigmoid"):
        assert t not in types
    fused = [op for op in opt.global_block().ops
             if op.type == "fused_elementwise"][0]
    assert fused.attrs["fused_types"] == "relu+scale+tanh+sigmoid"
    # pass-created op carries synthesized provenance (def site = the
    # first constituent's build site, in THIS file)
    assert fused.def_site and "test_optimizer" in fused.def_site
    with scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        X = np.random.RandomState(3).randn(4, 8).astype(np.float32)
        a, = exe.run(main, feed={"x": X}, fetch_list=[out.name],
                     scope=scope)
        b, = exe.run(opt, feed={"x": X}, fetch_list=[out.name],
                     scope=scope)
    assert np.array_equal(a, b)


def test_fusion_respects_multi_consumer_and_fetch_boundaries(
        fresh_programs):
    main, startup, scope = fresh_programs
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h1 = fluid.layers.relu(x)
        h2 = fluid.layers.tanh(h1)      # h1 fetched -> link not fusable
        out = fluid.layers.reduce_mean(h2)
    opt, _ = optimize_program(main, fetch_list=[out, h1], level=2)
    assert "fused_elementwise" not in _ops(opt)
    assert "relu" in _ops(opt) and "tanh" in _ops(opt)


def test_two_interdependent_fused_chains_order_correctly(
        fresh_programs):
    """Review regression: one pass creating two new ops where chain B
    consumes chain A's output, with A's surviving consumer placed AFTER
    B's — materialize must anchor each replacement op at its removed
    original producer's slot, not at min(consumer)."""
    main, startup, scope = fresh_programs
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        out_a = fluid.layers.tanh(fluid.layers.relu(x))     # chain A
        out_b = fluid.layers.exp(fluid.layers.sigmoid(out_a))  # chain B
        s_b = fluid.layers.reduce_sum(out_b)   # B's consumer FIRST
        s_a = fluid.layers.reduce_sum(out_a)   # A's consumer after
    opt, stats = optimize_program(main, fetch_list=[s_b, s_a], level=2)
    fu = [r for r in stats if r["pass"] == "fuse_elementwise_pass"][0]
    assert fu["chains_fused"] == 2
    types = _ops(opt)
    assert types.count("fused_elementwise") == 2
    # producer chain A precedes consumer chain B in the optimized order
    fused = [op for op in opt.global_block().ops
             if op.type == "fused_elementwise"]
    assert fused[0].output("Out") == [out_a.name]
    assert fused[1].output("Out") == [out_b.name]
    with scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        X = np.random.RandomState(1).randn(3, 4).astype(np.float32)
        a = exe.run(main, feed={"x": X}, fetch_list=[s_b, s_a],
                    scope=scope)
        b = exe.run(opt, feed={"x": X}, fetch_list=[s_b, s_a],
                    scope=scope)
    for va, vb in zip(a, b):
        assert np.array_equal(va, vb)


def test_malformed_fold_cap_env_falls_back(fresh_programs, monkeypatch):
    """Review regression: a typo'd PADDLE_TPU_OPTIMIZE_FOLD_MAX_ELEMS
    must not crash the executor (config_key runs in _cache_key on every
    run) — it falls back to the default like optimize_level does."""
    from paddle_tpu.core.passes import config_key
    from paddle_tpu.core.passes.fold import fold_max_elems

    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE_FOLD_MAX_ELEMS", "16k")
    assert fold_max_elems() == 16384
    assert config_key()[1] == 16384
    main, startup, scope = fresh_programs
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        loss = fluid.layers.reduce_mean(fluid.layers.relu(x))
    with scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        lv, = exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                      fetch_list=[loss.name], scope=scope)
    assert np.isfinite(float(lv))


def test_fusion_never_moves_a_read_past_an_inplace_write(monkeypatch):
    """Review regression: the fused op runs at the chain TAIL's slot, so
    a chain whose external input is re-written in place between head and
    tail must not fuse — the head's read would move past the write."""
    main = fluid.Program()
    blk = main.global_block()
    blk.create_var(name="x", shape=(4,), dtype="float32", is_data=True)
    for n in ("w", "t1", "t2", "outv"):
        blk.create_var(name=n, shape=(4,), dtype="float32")
    blk.append_op("scale", {"X": ["x"]}, {"Out": ["w"]}, {"scale": 1.0})
    blk.append_op("relu", {"X": ["w"]}, {"Out": ["t1"]})
    blk.append_op("scale", {"X": ["w"]}, {"Out": ["w"]}, {"scale": 3.0})
    blk.append_op("tanh", {"X": ["t1"]}, {"Out": ["t2"]})
    blk.append_op("elementwise_add", {"X": ["t2"], "Y": ["w"]},
                  {"Out": ["outv"]})
    opt, _ = optimize_program(main, fetch_list=["outv"], level=2,
                              verify=False)
    # the relu->tanh chain would swallow relu's read of pre-update w;
    # it must stay unfused (a tail segment whose reads all sit at/after
    # the final write of w may still fuse)
    for op in opt.global_block().ops:
        if op.type == "fused_elementwise":
            assert "relu" not in op.attrs["fused_types"]

    def run(level):
        monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", str(level))
        sc = Scope()
        X = np.array([[-1.0, 0.5, 2.0, -0.25]], np.float32)
        with scope_guard(sc):
            return fluid.Executor().run(main, feed={"x": X},
                                        fetch_list=["outv"],
                                        scope=sc)[0]

    assert np.array_equal(run(0), run(2))


def test_passes_keep_scope_backed_undeclared_state(fresh_programs):
    """Review regression: an UNDECLARED name living in the run scope is
    persistable state per analyze_block — no pass may drop its write.
    Here copy-prop would have deleted assign(t)->snap."""
    main, startup, scope = fresh_programs
    blk = main.global_block()
    blk.create_var(name="x", shape=(4,), dtype="float32", is_data=True)
    blk.create_var(name="t", shape=(4,), dtype="float32")
    blk.append_op("scale", {"X": ["x"]}, {"Out": ["t"]}, {"scale": 2.0})
    blk.append_op("assign", {"X": ["t"]}, {"Out": ["snap"]})  # undeclared
    import jax.numpy as jnp

    with scope_guard(scope):
        scope.set_var("snap", jnp.zeros((1, 4), jnp.float32))
        opt, stats = optimize_program(main, fetch_list=["t"],
                                      scope=scope, level=1, verify=False)
        assert "assign" in _ops(opt)  # the write-back survives
        exe = fluid.Executor()
        X = np.arange(4, dtype=np.float32).reshape(1, 4)
        exe.run(main, feed={"x": X}, fetch_list=["t"], scope=scope)
        np.testing.assert_array_equal(np.asarray(scope.find_var("snap")),
                                      2.0 * X)


def test_amp_pass_stamps_policy_tags(fresh_programs):
    main, startup, scope = fresh_programs
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        h = fluid.layers.fc(x, size=4)
        loss = fluid.layers.reduce_mean(fluid.layers.softmax(h))
    main.set_amp(True)
    opt, stats = optimize_program(main, fetch_list=[loss], level=1)
    tags = {op.type: op.attrs.get("__amp__")
            for op in opt.global_block().ops}
    assert tags["mul"] == "bf16"
    assert tags["softmax"] == "f32"
    assert tags["reduce_mean"] == "f32"
    amp = [r for r in stats if r["pass"] == "amp_bf16_pass"][0]
    assert amp["amp_tagged"] == len(opt.global_block().ops)
    # without program.amp the pass is a no-op
    opt2, stats2 = optimize_program(main.clone().set_amp(False),
                                    fetch_list=[loss], level=1)
    assert all("__amp__" not in op.attrs
               for op in opt2.global_block().ops)


def test_broken_pass_fails_loudly_with_pass_name(fresh_programs,
                                                 monkeypatch):
    import paddle_tpu.core.passes as passes_mod
    from paddle_tpu.core.ir import Pass, register_pass

    @register_pass("test_breaking_pass")
    class _Breaker(Pass):
        """Test-only pass that breaks def-before-use on purpose."""

        fetch_names = frozenset()
        scope = None

        def apply(self, graph):
            # make the FIRST op read the LAST op's output: a
            # def-before-use ERROR no pass is allowed to introduce
            out = graph.op_nodes[-1].op.output_names()[0]
            graph.op_nodes[0].op.inputs.setdefault("X", []).insert(0, out)
            return graph

    main, startup, scope = fresh_programs
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        loss = fluid.layers.reduce_mean(fluid.layers.relu(x))
    monkeypatch.setattr(passes_mod, "PIPELINE",
                        (("test_breaking_pass", 1),))
    with pytest.raises(OptimizerPassError) as ei:
        optimize_program(main, fetch_list=[loss], level=1)
    assert "test_breaking_pass" in str(ei.value)


# --------------------------------------------------- executor integration
def _tiny_train(seed=11, dropout=0.3):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h = fluid.layers.fc(x, size=16, act="relu")
            if dropout:
                h = fluid.layers.dropout(h, dropout_prob=dropout)
            pred = fluid.layers.fc(h, size=1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            dead = fluid.layers.fc(x, size=4, act="tanh")
            fluid.layers.reduce_mean(dead)  # dead branch for DCE
            fluid.optimizer.Adam(1e-3).minimize(loss)
    return main, startup, loss


def _train_steps(level, monkeypatch, steps=3, amp=False):
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", str(level))
    main, startup, loss = _tiny_train()
    if amp:
        main.set_amp(True)
    scope = Scope()
    rng = np.random.RandomState(0)
    X = rng.randn(16, 8).astype(np.float32)
    Y = X.sum(1, keepdims=True).astype(np.float32)
    with scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        losses = [exe.run(main, feed={"x": X, "y": Y},
                          fetch_list=[loss.name], scope=scope)[0]
                  for _ in range(steps)]
        params = {n: np.asarray(scope.find_var(n))
                  for n in ("fc_0.w_0", "fc_1.w_0")}
    return losses, params


def test_optimized_training_is_bitwise_identical(monkeypatch):
    """Level 2 vs level 0, three steps THROUGH dropout (the RNG chain)
    and the Adam update: losses and parameters bitwise equal."""
    l0, p0 = _train_steps(0, monkeypatch)
    l2, p2 = _train_steps(2, monkeypatch)
    for a, b in zip(l0, l2):
        assert np.array_equal(a, b)
    for n in p0:
        assert np.array_equal(p0[n], p2[n]), n


def test_optimized_amp_training_is_bitwise_identical(monkeypatch):
    """The stamped (__amp__ attr) and table AMP paths cast at the same
    points: bf16 training at level 2 == level 0 bitwise."""
    l0, p0 = _train_steps(0, monkeypatch, amp=True)
    l2, p2 = _train_steps(2, monkeypatch, amp=True)
    for a, b in zip(l0, l2):
        assert np.array_equal(a, b)
    for n in p0:
        assert np.array_equal(p0[n], p2[n]), n


# tiny presets of the two model families the benchmark's cells train and
# serve: residual add -> layer_norm pairs a layer, and an ``adam`` op a
# parameter (what the deleted kernel-tier fusion pass used to rewrite)
_TINY = dict(d_model=32, d_ff=64, n_head=2, n_layer=2, vocab=64,
             max_length=16, dropout=0.1)


def _tiny_model(family, optimizer="adam", amp=False, seed=5):
    from paddle_tpu.models import bert, gpt

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    rs = np.random.RandomState(seed)
    B, S, M = 2, 8, 3
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            if family == "bert":
                loss, _ = bert.build(dict(_TINY, type_vocab=2), seq_len=S,
                                     max_mask=M)
                feed = {
                    "src_ids": rs.randint(1, 64, (B, S)).astype("int64"),
                    "sent_ids": rs.randint(0, 2, (B, S)).astype("int64"),
                    "input_mask": np.ones((B, S), "float32"),
                    "mask_pos": rs.randint(0, B * S, (B, M)).astype("int64"),
                    "mask_label": rs.randint(0, 64, (B, M)).astype("int64"),
                    "mask_weight": np.ones((B, M), "float32"),
                }
            else:
                loss, _ = gpt.build(dict(_TINY), seq_len=S)
                feed = {"ids": rs.randint(1, 64, (B, S)).astype("int64")}
            opt = (fluid.optimizer.Adam(1e-3) if optimizer == "adam"
                   else fluid.optimizer.SGD(1e-2))
            opt.minimize(loss)
    main.set_amp(amp)
    return main, startup, loss, feed


@pytest.mark.parametrize("family", ["bert", "gpt"])
def test_level2_leaves_add_layernorm_and_adam_runs_as_written(family):
    """The pipeline that is left rewrites neither a residual add +
    ``layer_norm`` pair nor a run of ``adam`` ops: every ``layer_norm``
    and every ``adam`` of the program is still there at level 2, one op
    each, and the only fused op types are the elementwise chain and the
    attention op the builder wrote."""
    main, _startup, loss, _feed = _tiny_model(family)
    before = _ops(main)
    after = _ops(optimize_program(main, fetch_list=[loss], level=2)[0])
    assert before.count("layer_norm") >= 2 * _TINY["n_layer"]
    for kind in ("layer_norm", "layer_norm_grad", "adam"):
        assert after.count(kind) == before.count(kind) > 0, kind
    assert {t for t in after if t.startswith("fused_")} <= {
        "fused_elementwise", "fused_attention", "fused_attention_grad"}


@pytest.mark.parametrize("case", ["sgd", "adam", "adam+amp"])
def test_optimized_step_is_bitwise_the_unoptimized_one(case, monkeypatch):
    """Three train steps of the tiny BERT (dropout on, so the RNG chain
    is real) at level 2 and at level 0: losses and every parameter
    bitwise equal, with and without bf16 AMP."""
    optimizer, _, amp = case.partition("+")

    def run(level):
        monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", str(level))
        main, startup, loss, feed = _tiny_model("bert", optimizer,
                                                amp=bool(amp))
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup, scope=scope)
            losses = [np.asarray(exe.run(main, feed=feed, fetch_list=[loss],
                                         scope=scope)[0])
                      for _ in range(3)]
            params = {p.name: np.asarray(scope.find_var(p.name))
                      for p in main.global_block().all_parameters()}
        return losses, params

    l0, p0 = run(0)
    l2, p2 = run(2)
    assert len(p0) > 10 and np.isfinite(l0[-1]).all()
    for a, b in zip(l0, l2):
        assert np.array_equal(a, b)
    for n in p0:
        assert np.array_equal(p0[n], p2[n]), n


def test_level0_provably_bypasses_pipeline(monkeypatch):
    """PADDLE_TPU_OPTIMIZE=0: zero movement across EVERY
    paddle_optimizer_* family while the program still runs."""
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "0")
    assert optimize_level() == 0
    before = _optimizer_counters()
    main, startup, loss = _tiny_train()
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        X = np.zeros((4, 8), np.float32)
        exe.run(main, feed={"x": X, "y": np.zeros((4, 1), np.float32)},
                fetch_list=[loss.name], scope=scope)
    assert _optimizer_counters() == before
    # and the bypass is honest at the API level too
    same, stats = optimize_program(main, fetch_list=[loss], level=0)
    assert same is main and stats == []


def test_level_keys_plan_cache_and_program_untouched(monkeypatch):
    """Changing the level re-prepares (the optimized plan never serves a
    level-0 run), and prepare-time optimization runs on a clone."""
    from paddle_tpu.observe.families import EXECUTOR_CACHE_MISSES

    main, startup, loss = _tiny_train(dropout=0.0)
    n_ops = len(main.global_block().ops)
    version = main.version
    scope = Scope()
    X = np.zeros((4, 8), np.float32)
    feed = {"x": X, "y": np.zeros((4, 1), np.float32)}
    with scope_guard(scope):
        exe = fluid.Executor()
        monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "2")
        exe.run(startup, scope=scope)
        exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
        m0 = EXECUTOR_CACHE_MISSES.value
        exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
        assert EXECUTOR_CACHE_MISSES.value == m0  # cache hit
        monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "0")
        exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
        assert EXECUTOR_CACHE_MISSES.value == m0 + 1  # re-prepared
        # every output-changing optimizer knob keys the cache, not just
        # the level: a different fold cap must also re-prepare
        monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "2")
        monkeypatch.setenv("PADDLE_TPU_OPTIMIZE_FOLD_MAX_ELEMS", "0")
        exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
        assert EXECUTOR_CACHE_MISSES.value == m0 + 2
    assert len(main.global_block().ops) == n_ops
    assert main.version == version


def test_optimizer_stats_reach_telemetry_snapshot(monkeypatch):
    """The paddle_optimizer_* families move under a level-2 run — the
    registry snapshot ``observe.dump`` writes (stats_dump --grep
    paddle_optimizer reads it)."""
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "2")
    before = _optimizer_counters()
    main, startup, loss = _tiny_train()
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        X = np.zeros((4, 8), np.float32)
        exe.run(main, feed={"x": X, "y": np.zeros((4, 1), np.float32)},
                fetch_list=[loss.name], scope=scope)
    after = _optimizer_counters()
    assert after["paddle_optimizer_programs_optimized_total"] \
        > before["paddle_optimizer_programs_optimized_total"]
    d_in = after["paddle_optimizer_ops_in_total"] \
        - before["paddle_optimizer_ops_in_total"]
    d_out = after["paddle_optimizer_ops_out_total"] \
        - before["paddle_optimizer_ops_out_total"]
    assert d_in > d_out > 0  # this program measurably shrank
    assert after["paddle_optimizer_ops_removed_total"] \
        > before["paddle_optimizer_ops_removed_total"]
    assert after["paddle_optimizer_pass_seconds"] \
        > before["paddle_optimizer_pass_seconds"]


# ------------------------------------------------------- model-zoo gate
_REDUCTIONS = {}


def _zoo_models():
    from lint_program import EXAMPLE_BUILDERS

    return sorted(EXAMPLE_BUILDERS)


@pytest.mark.parametrize("model", _zoo_models())
def test_model_zoo_optimizes_clean_at_level2(model):
    """ALL example-zoo train + startup programs optimize at level 2
    with verify-after-every-pass clean (no OptimizerPassError)."""
    from optimize_program import optimize_example

    report = optimize_example(model, level=2)
    _REDUCTIONS[model] = (report["main"]["ops_before"]
                          - report["main"]["ops_after"])
    assert report["main"]["ops_after"] <= report["main"]["ops_before"]
    assert report["startup"]["ops_after"] \
        <= report["startup"]["ops_before"]


def test_model_zoo_op_count_reduction_on_three_models():
    """Acceptance: a measurable op-count reduction on >= 3 model-zoo
    train programs (runs after the parametrized gate above)."""
    assert len(_REDUCTIONS) >= 3
    reduced = [m for m, d in _REDUCTIONS.items() if d > 0]
    assert len(reduced) >= 3, _REDUCTIONS


def test_model_zoo_mnist_training_bitwise_identical(monkeypatch):
    """A real model-zoo program (mnist cnn, conv/pool/softmax/xent +
    Adam): two training steps at level 2 == level 0 bitwise."""
    from paddle_tpu.models import mnist

    def steps(level):
        monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", str(level))
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 3
        startup.random_seed = 3
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                loss, acc, _feeds = mnist.build("cnn")
                fluid.optimizer.Adam(1e-3).minimize(loss)
        scope = Scope()
        rng = np.random.RandomState(0)
        img = rng.rand(8, 784).astype(np.float32)
        label = rng.randint(0, 10, (8, 1)).astype(np.int64)
        with scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup, scope=scope)
            return [exe.run(main, feed={"img": img, "label": label},
                            fetch_list=[loss.name, acc.name],
                            scope=scope)
                    for _ in range(2)]

    for s0, s2 in zip(steps(0), steps(2)):
        for a, b in zip(s0, s2):
            assert np.array_equal(a, b)


# ------------------------------------------------------------ slow perf
def _chain_heavy(n_links=30, n_dup=10, dup_len=12, n_dead=12,
                 dead_len=10):
    """An elementwise-chain-heavy program (~700 ops): one long
    activation chain to the loss, weight-SHARED duplicate fc towers
    (structurally identical, param names included — CSE merges all but
    one), a const subgraph (fold), and dead sigmoid chains (DCE)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 5
    startup.random_seed = 5
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[64], dtype="float32")
            h = fluid.layers.fc(x, size=64)
            for _ in range(n_links):
                h = fluid.layers.tanh(fluid.layers.scale(
                    h, scale=1.01, bias=0.01))
            for _ in range(n_dup):  # identical shared-weight towers
                d = x
                for j in range(dup_len):
                    d = fluid.layers.fc(
                        d, size=64, act="relu",
                        param_attr=fluid.ParamAttr(name="sw_%d" % j),
                        bias_attr=fluid.ParamAttr(name="sb_%d" % j))
                h = fluid.layers.elementwise_add(h, d)
            c = fluid.layers.fill_constant([64], "float32", 2.0)
            for _ in range(10):  # const subgraph -> fold
                c = fluid.layers.scale(c, scale=1.1, bias=0.1)
            h = fluid.layers.elementwise_add(h, c)
            for _ in range(n_dead):  # dead branches -> DCE
                d = x
                for _ in range(dead_len):
                    d = fluid.layers.sigmoid(fluid.layers.scale(
                        d, scale=3.0))
                fluid.layers.reduce_mean(d)
            loss = fluid.layers.reduce_mean(h)
    return main, startup, loss


@pytest.mark.slow
def test_chain_heavy_workload_speedup_at_level2(monkeypatch):
    """>= 1.1x cold steps/sec at PADDLE_TPU_OPTIMIZE=2 vs =0 on an
    elementwise-chain-heavy workload.

    "Cold steps/sec" = N steps INCLUDING prepare + first-dispatch
    trace/compile from a fresh executor — the cost graph-level
    optimization actually owns: XLA re-fuses the steady-state HLO either
    way (and this suite pins steady-state BITWISE parity instead), but
    every op the pipeline removes is an op jax never traces and XLA
    never re-optimizes, and that cost is paid again on EVERY new feed
    signature, model revision, and serving bucket. Calibrated-ratio
    pattern: up to 5 attempts, best ratio wins, no absolute-ms asserts
    (measured 1.26-1.47x on the 2-core CI box; the pin is 1.1x)."""
    # the workload's premise must hold before timing anything: the
    # pipeline collapses it by an order of magnitude
    m, _s, l = _chain_heavy()
    opt, _ = optimize_program(m, fetch_list=[l], level=2)
    assert len(opt.global_block().ops) * 5 <= len(m.global_block().ops)

    steps = 4
    X = np.random.RandomState(0).randn(8, 64).astype(np.float32)

    def cold_steps_per_sec(level):
        monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", str(level))
        main, startup, loss = _chain_heavy()
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup, scope=scope)
            t0 = time.perf_counter()
            for _ in range(steps):
                vals = exe.run(main, feed={"x": X},
                               fetch_list=[loss.name], scope=scope)
            dt = time.perf_counter() - t0
        assert np.isfinite(float(vals[0]))
        return steps / dt

    best = 0.0
    for _attempt in range(5):
        sps0 = cold_steps_per_sec(0)
        sps2 = cold_steps_per_sec(2)
        best = max(best, sps2 / sps0)
        if best >= 1.1:
            break
    assert best >= 1.1, "level2/level0 cold steps/sec ratio %.3f" % best
