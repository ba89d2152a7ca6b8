"""Value-range abstract interpretation (analysis/ranges.py): interval
algebra, the whole-program engine (versions, sub-blocks, widening,
calibration, scope values), the range-powered numerics lint rules, the
model-zoo gates, and the --ranges CLI."""

import json
import math
import os
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers as L
from paddle_tpu.analysis import lint_program
from paddle_tpu.analysis.ranges import (Calibration, RangeAnalysis,
                                        av_abs, av_add, av_const,
                                        av_div, av_interval, av_mul,
                                        av_top)
from paddle_tpu.core.scope import Scope, scope_guard

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "tools"))

import lint_program as lint_cli  # noqa: E402

INF = math.inf


@pytest.fixture
def fresh_programs():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        yield main, startup


# ----------------------------------------------------------- the algebra
def test_interval_arithmetic_soundness():
    a = av_interval(-2.0, 3.0)
    b = av_interval(1.0, 4.0)
    s = av_add(a, b)
    assert (s.lo, s.hi) == (-1.0, 7.0) and s.finite
    m = av_mul(a, b)
    assert (m.lo, m.hi) == (-8.0, 12.0)
    d = av_div(a, b)  # divisor positive: bounds from endpoint quotients
    assert d.lo == -2.0 and d.hi == 3.0
    # divisor interval containing zero: no sound bounds exist
    assert av_div(a, av_interval(-1.0, 1.0)).is_top
    ab = av_abs(av_interval(-5.0, 2.0))
    assert (ab.lo, ab.hi) == (0.0, 5.0)
    j = a.join(av_interval(10.0, 11.0))
    assert (j.lo, j.hi) == (-2.0, 11.0)


def test_const_and_refine():
    c = av_const(np.array([1.0, -3.0, 2.0], dtype=np.float32))
    assert c.is_const and (c.lo, c.hi) == (-3.0, 2.0) and c.finite
    ci = av_const(np.array([2, 5]))
    assert ci.integral
    r = av_top().refine(-1.0, 1.0)
    assert r.bounded and (r.lo, r.hi) == (-1.0, 1.0)
    # refinement intersects with existing knowledge
    r2 = av_interval(0.0, 10.0).refine(-5.0, 4.0)
    assert (r2.lo, r2.hi) == (0.0, 4.0)


def test_finiteness_requires_f32_bounds():
    huge = av_interval(0.0, 3.0e38)
    doubled = av_mul(huge, av_const(2.0).drop_const())
    # 6e38 exceeds the f32 range: two finite f32s can still overflow
    assert doubled.hi == 6.0e38 and not doubled.finite


# ------------------------------------------------------------- the engine
def test_engine_const_propagation_and_bounds(fresh_programs):
    main, _ = fresh_programs
    x = L.data(name="x", shape=[8], dtype="float32")
    c = L.fill_constant([8], "float32", 2.0)
    s = L.scale(c, scale=3.0, bias=1.0)
    t = L.tanh(x)
    r = L.relu(t)
    m = L.elementwise_mul(r, s)
    ra = RangeAnalysis(main)
    assert ra.value_of(c.name).is_const
    sv = ra.value_of(s.name)
    assert sv.is_const and float(np.asarray(sv.const).ravel()[0]) == 7.0
    assert (ra.value_of(t.name).lo, ra.value_of(t.name).hi) == (-1.0, 1.0)
    assert ra.value_of(r.name).lo == 0.0
    mv = ra.value_of(m.name)
    assert (mv.lo, mv.hi) == (0.0, 7.0) and mv.finite


def test_engine_matmul_contraction_width(fresh_programs):
    main, _ = fresh_programs
    x = L.data(name="x", shape=[8], dtype="float32")
    s = L.sigmoid(x)                      # [0, 1]
    w = L.fill_constant([8, 4], "float32", 0.5)
    out = L.mul(s, w)                     # K=8, products in [0, 0.5]
    ra = RangeAnalysis(main)
    av = ra.value_of(out.name)
    assert av.bounded and av.lo == 0.0 and av.hi == 4.0


def test_engine_rides_dataflow_write_versions(fresh_programs):
    main, _ = fresh_programs
    w = L.create_parameter([4], "float32", name="rv_w")
    pre = L.scale(w, scale=1.0)
    lr = L.fill_constant([1], "float32", 0.1)
    w.block.append_op("sgd",
                      {"Param": [w.name], "Grad": [pre.name],
                       "LearningRate": [lr.name]},
                      {"ParamOut": [w.name]},
                      {"__op_role__": "optimize"})
    post = L.scale(w, scale=1.0)
    scope = Scope()
    scope.set_var(w.name, np.full(4, 0.25, dtype=np.float32))
    ra = RangeAnalysis(main, scope=scope, use_scope_values=True)
    # version 0 = the external scope value; version 1 = post-sgd (T:
    # sgd widens by declaration)
    v0 = ra.at_version(w.name, 0)
    assert v0.bounded and v0.lo == 0.25 and v0.hi == 0.25
    assert ra.at_version(w.name, 1).is_top
    assert ra.declared_top(w.name)
    # the pre-update read was judged by the bounded external value
    assert ra.value_of(pre.name).bounded
    # the post-update read sees the widened version
    assert not ra.value_of(post.name).bounded


def test_unknown_op_widens_with_counter(fresh_programs):
    from paddle_tpu import observe

    def widened_count(reason):
        fam = observe.snapshot()["metrics"][
            "paddle_analysis_ranges_widened_total"]
        return {tuple(s["labels"].items()): s["value"]
                for s in fam["samples"]}.get((("reason", reason),), 0)

    main, _ = fresh_programs
    x = L.data(name="x", shape=[4], dtype="float32")
    lbl = L.data(name="lbl", shape=[1], dtype="int64")
    acc = L.accuracy(L.softmax(x), lbl)  # accuracy has no range rule
    before = widened_count("unknown-op")
    ra = RangeAnalysis(main)
    assert ra.widened.get("accuracy") == "unknown-op"
    assert not ra.declared_top(acc.name)  # a gap, not a declaration
    assert widened_count("unknown-op") > before


def test_conditional_sub_block_joins_fallthrough(fresh_programs):
    main, _ = fresh_programs
    x = L.data(name="x", shape=[4], dtype="float32")
    z = L.fill_constant([4], "float32", 0.0)
    pred = L.less_than(L.reduce_mean(x),
                       L.fill_constant([1], "float32", 0.5))

    def then():
        L.assign(L.fill_constant([4], "float32", 3.0), output=z)

    L.cond(pred, then)
    out = L.elementwise_add(x, z)  # noqa: F841  (keeps z live)
    ra = RangeAnalysis(main)
    zv = ra.value_of(z.name)
    # branch taken -> 3.0, not taken -> 0.0: the join
    assert zv.bounded and zv.lo == 0.0 and zv.hi == 3.0


def test_loop_sub_block_widens_unstable_writes(fresh_programs):
    main, _ = fresh_programs
    x = L.fill_constant([4], "float32", 1.0)
    sub = main.create_block()
    sub.append_op("scale", {"X": [x.name]}, {"Out": [x.name]},
                  {"scale": 1.1})
    main.rollback()
    # loop-shaped: sub_block attr, no condition -> bounded fixpoint
    main.global_block().append_op(
        "while_stub", {}, {}, {"sub_block": sub.idx})
    ra = RangeAnalysis(main)
    assert ra.value_of(x.name).is_top  # 1.1*x does not stabilize
    assert "while_stub" in ra.widened \
        and ra.widened["while_stub"] == "loop"


def test_loop_sub_block_keeps_stable_writes(fresh_programs):
    main, _ = fresh_programs
    x = L.fill_constant([4], "float32", 5.0)
    sub = main.create_block()
    sub.append_op("tanh", {"X": [x.name]}, {"Out": [x.name]}, {})
    main.rollback()
    main.global_block().append_op(
        "while_stub", {}, {}, {"sub_block": sub.idx})
    ra = RangeAnalysis(main)
    xv = ra.value_of(x.name)
    # tanh's image is [-1, 1] on every iteration: stable — joined with
    # the pre-state 5.0 because a loop may run ZERO times
    assert xv.bounded and xv.lo == -1.0 and xv.hi == 5.0


def test_real_while_op_takes_the_loop_path(fresh_programs):
    """Review regression: a real `while` op ALSO carries a `condition`
    attr, so attr presence must not classify it as a conditional — an
    increment body must widen, not get the single-pass join."""
    main, _ = fresh_programs
    x = L.fill_constant([1], "float32", 0.0)
    cond = L.fill_constant([1], "bool", True)
    sub = main.create_block()
    sub.append_op("increment", {"X": [x.name]}, {"Out": [x.name]},
                  {"step": 1.0})
    main.rollback()
    main.global_block().append_op(
        "while", {"Condition": [cond.name]}, {},
        {"sub_block": sub.idx, "condition": cond.name})
    ra = RangeAnalysis(main)
    assert ra.value_of(x.name).is_top  # x grows without bound
    assert ra.widened.get("while") == "loop"


# ----------------------------------------------------------- calibration
def test_calibration_refines_feeds_and_counts(fresh_programs):
    from paddle_tpu import observe

    def batches():
        fam = observe.snapshot()["metrics"][
            "paddle_analysis_ranges_calibration_batches_total"]
        return fam["samples"][0]["value"] if fam["samples"] else 0

    main, startup = fresh_programs
    x = L.data(name="x", shape=[4], dtype="float32")
    out = L.scale(x, scale=2.0)
    scope = Scope()
    exe = fluid.Executor()
    cal = Calibration()
    before = batches()
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        with cal.attach():
            for lo in (0.0, -0.5):
                exe.run(main,
                        feed={"x": np.linspace(lo, 1.0, 8).reshape(
                            2, 4).astype(np.float32)},
                        fetch_list=[out], scope=scope)
    assert cal.batches == 2
    assert batches() == before + 2
    assert cal.observed["x"] == (-0.5, 1.0)
    ra = RangeAnalysis(main, calibration=cal)
    xv = ra.value_of(x.name)
    assert (xv.lo, xv.hi) == (-0.5, 1.0)
    ov = ra.value_of(out.name)
    assert (ov.lo, ov.hi) == (-1.0, 2.0)
    # detached: further runs are not observed
    with scope_guard(scope):
        exe.run(main, feed={"x": np.full((2, 4), 9.0, np.float32)},
                fetch_list=[out], scope=scope)
    assert cal.observed["x"] == (-0.5, 1.0)


def test_scope_values_give_exact_weight_intervals(fresh_programs):
    main, _ = fresh_programs
    x = L.data(name="x", shape=[4], dtype="float32")
    w = L.create_parameter([4], "float32", name="sv_w")
    out = L.elementwise_mul(L.sigmoid(x), w)
    scope = Scope()
    scope.set_var(w.name, np.array([-2.0, 0.5, 1.0, 3.0], np.float32))
    ra = RangeAnalysis(main, scope=scope, use_scope_values=True)
    wv = ra.value_of(w.name)
    assert (wv.lo, wv.hi) == (-2.0, 3.0)
    ov = ra.value_of(out.name)
    assert (ov.lo, ov.hi) == (-2.0, 3.0)
    # default: scope values are NOT read (lint stays cheap)
    ra2 = RangeAnalysis(main, scope=scope)
    assert not ra2.value_of(w.name).bounded


# -------------------------------------------------- numerics lint rules
def _findings(main, rule, **kw):
    return [f for f in lint_program(main, **kw) if f.rule == rule]


def test_domain_violation_log_of_nonpositive(fresh_programs):
    main, _ = fresh_programs
    L.log(L.fill_constant([4], "float32", -1.0))
    fs = _findings(main, "domain-violation")
    assert len(fs) == 1 and fs[0].severity == "error"
    assert "log" in fs[0].message


def test_domain_violation_exp_overflow(fresh_programs):
    main, _ = fresh_programs
    L.exp(L.fill_constant([4], "float32", 100.0))
    fs = _findings(main, "domain-violation")
    assert len(fs) == 1 and fs[0].severity == "error"
    # possible-but-not-certain overflow is a warning
    main2 = fluid.Program()
    with fluid.program_guard(main2, fluid.Program()):
        x = L.data(name="x", shape=[4], dtype="float32")
        L.exp(L.clip(x, min=-1.0, max=95.0))
    fs2 = _findings(main2, "domain-violation")
    assert len(fs2) == 1 and fs2[0].severity == "warning"


def test_domain_violation_division_by_const_zero(fresh_programs):
    main, _ = fresh_programs
    x = L.data(name="x", shape=[4], dtype="float32")
    L.elementwise_div(x, L.fill_constant([4], "float32", 0.0))
    fs = _findings(main, "domain-violation")
    assert len(fs) == 1 and fs[0].severity == "error"


def test_domain_rules_silent_on_top_inputs(fresh_programs):
    main, _ = fresh_programs
    x = L.data(name="x", shape=[4], dtype="float32")
    L.log(x)          # T input: no proof, no finding
    L.exp(x)
    L.elementwise_div(x, x)
    assert _findings(main, "domain-violation") == []


def test_bf16_overflow_rule(fresh_programs):
    main, _ = fresh_programs
    main.set_amp(True)
    x = L.data(name="x", shape=[4], dtype="float32")
    big = L.fill_constant([4], "float32", 3.395e38)
    L.elementwise_mul(L.sigmoid(x), big)
    fs = _findings(main, "bf16-overflow")
    assert len(fs) == 1 and fs[0].severity == "warning"
    # without amp the rule never runs
    main.amp = False
    assert _findings(main, "bf16-overflow") == []


def test_int_narrowing_loss_at_feed_boundary(fresh_programs):
    main, _ = fresh_programs
    ids = L.data(name="ids", shape=[1], dtype="int64")
    L.cast(ids, "float32")
    cal = Calibration()
    cal.observe("ids", np.array([[0], [3_000_000_000]], dtype=np.int64))
    fs = _findings(main, "int-narrowing-loss", calibration=cal)
    assert len(fs) == 1 and fs[0].severity == "error"
    assert "int32" in fs[0].message
    # without calibration evidence: silent (the int64-feed info advisory
    # still covers the no-evidence case)
    assert _findings(main, "int-narrowing-loss") == []


def test_int_narrowing_loss_at_cast(fresh_programs):
    main, _ = fresh_programs
    L.cast(L.fill_constant([2], "float32", 300.0), "int8")
    fs = _findings(main, "int-narrowing-loss")
    assert len(fs) == 1 and fs[0].severity == "error"
    # partially-outside finite bound: info
    main2 = fluid.Program()
    with fluid.program_guard(main2, fluid.Program()):
        x = L.data(name="x", shape=[4], dtype="float32")
        L.cast(L.clip(x, min=0.0, max=300.0), "int8")
    fs2 = _findings(main2, "int-narrowing-loss")
    assert len(fs2) == 1 and fs2[0].severity == "info"


def test_int_narrowing_models_truncation(fresh_programs):
    """Review regression: 127.5 cast to int8 truncates to 127 — no
    value is lost, so the rule must stay silent (pre-truncation float
    bounds would false-positive an error on a correct program)."""
    main, _ = fresh_programs
    L.cast(L.fill_constant([2], "float32", 127.5), "int8")
    x = L.data(name="x", shape=[2], dtype="float32")
    L.cast(L.clip(x, min=127.2, max=127.9), "int8")
    assert _findings(main, "int-narrowing-loss") == []


def test_cast_rule_truncates_fractional_intervals(fresh_programs):
    """Review regression: casting a fractional interval to an int dtype
    truncates toward zero — [0.5, 0.9] really produces 0, and the old
    pass-through bounds (lo=0.5>0) silenced the downstream
    division-by-zero proof."""
    main, _ = fresh_programs
    u = main.global_block().create_var(name="u", shape=[4],
                                       dtype="float32")
    main.global_block().append_op(
        "uniform_random", {}, {"Out": [u.name]},
        {"shape": [4], "min": 0.5, "max": 0.9, "dtype": "float32"})
    c = L.cast(u, "int32")
    back = L.cast(c, "float32")
    x = L.data(name="x", shape=[4], dtype="float32")
    L.elementwise_div(x, back)
    ra = RangeAnalysis(main)
    cv = ra.value_of(c.name)
    assert (cv.lo, cv.hi) == (0.0, 0.0) and cv.integral
    fs = _findings(main, "domain-violation")
    assert len(fs) == 1 and fs[0].severity == "error"


# ------------------------------------------------------- model-zoo gates
@pytest.mark.parametrize("model", sorted(lint_cli.EXAMPLE_BUILDERS))
def test_model_zoo_range_analyzes_clean(model):
    """Every model-zoo train AND startup program runs through the range
    engine without a crash, with zero unknown-op widenings among
    shape-ruled types (repo-lint rule 7's runtime shadow) and the
    declared-T accounting consistent."""
    from paddle_tpu.analysis.range_rules import WIDEN_TO_TOP
    from paddle_tpu.core.registry import OPS

    main, startup, loss = lint_cli.build_example(model)
    for prog, fetch in ((main, [loss.name]), (startup, [])):
        ra = RangeAnalysis(prog, fetch_names=fetch)
        st = ra.stats()
        assert st["vars"] > 0
        assert st["declared_top"] <= st["top"]
        for op_type, reason in ra.widened.items():
            if reason != "unknown-op":
                continue
            opdef = OPS.get(op_type)
            assert opdef is None or opdef.infer_shape is None, \
                ("shape-ruled op %r widened as unknown-op: add a range "
                 "rule or a WIDEN_TO_TOP entry" % op_type)
            assert op_type not in WIDEN_TO_TOP


def test_model_zoo_finite_fraction_pinned():
    """With startup-initialized scope weights and one calibrated
    synthetic feed batch, a pinned model subset proves finite intervals
    on >= 60% of non-T-declared vars (the acceptance floor), and the
    train+startup aggregate across the subset holds >= 60% too."""
    models = ("mnist", "gpt", "ctr", "transformer", "vit")
    rng = np.random.RandomState(0)
    agg_n = agg_d = 0
    for model in models:
        main, startup, loss = lint_cli.build_example(model)
        scope = Scope()
        exe = fluid.Executor()
        with scope_guard(scope):
            exe.run(startup, scope=scope)
        cal = Calibration()
        for var in main.global_block().vars.values():
            if not var.is_data:
                continue
            shape = [2 if (s is None or s < 0) else int(s)
                     for s in (var.shape or [2])]
            if var.dtype.startswith(("int", "uint")):
                cal.observe(var.name, np.ones(shape, dtype="int64"))
            else:
                cal.observe(var.name,
                            rng.uniform(-1, 1, shape).astype("float32"))
        ra = RangeAnalysis(main, fetch_names=[loss.name], scope=scope,
                           calibration=cal, use_scope_values=True)
        rs = RangeAnalysis(startup)
        for st in (ra.stats(), rs.stats()):
            agg_n += st["const"] + st["bounded"]
            agg_d += st["vars"] - st["declared_top"]
        st = ra.stats()
        frac = (st["const"] + st["bounded"]) / max(
            st["vars"] - st["declared_top"], 1)
        assert frac >= 0.60, (model, st)
    assert agg_n / agg_d >= 0.60, (agg_n, agg_d)


def test_range_rule_partition_covers_model_zoo_ops():
    """Schema pin (repo-lint rule 7's runtime half): every op type with
    a shape rule that appears in a model-zoo program is range-ruled or
    declared WIDEN_TO_TOP."""
    from paddle_tpu.analysis.range_rules import WIDEN_TO_TOP
    from paddle_tpu.analysis.ranges import RANGE_RULES
    from paddle_tpu.core.registry import OPS

    seen = set()
    for model in sorted(lint_cli.EXAMPLE_BUILDERS):
        main, startup, _loss = lint_cli.build_example(model)
        for prog in (main, startup):
            for block in prog.blocks:
                seen.update(op.type for op in block.ops)
    shaped = {t for t in seen
              if t in OPS and OPS[t].infer_shape is not None}
    uncovered = shaped - set(RANGE_RULES) - set(WIDEN_TO_TOP)
    assert uncovered == set(), sorted(uncovered)


# ------------------------------------------------------------------- CLI
def test_lint_program_cli_ranges_json(capsys):
    rc = lint_cli.main(["--model", "mnist", "--ranges", "--json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    entry = out["mnist"]
    assert set(entry) == {"findings", "ranges", "range_stats"}
    assert entry["range_stats"]["vars"] > 0
    some = next(iter(entry["ranges"].values()))
    assert set(some) == {"lo", "hi", "finite", "integral", "const"}


def test_lint_program_cli_ranges_text(capsys):
    rc = lint_cli.main(["--model", "mnist", "--ranges",
                        "--min-severity", "error"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "-- ranges:" in out


def test_softplus_bounds_contain_large_inputs(fresh_programs):
    """Review regression: softplus(x) ~ x for large x (the lowering is
    the overflow-stable logaddexp) — the transfer function must not cap
    the bound below reachable values."""
    main, _ = fresh_programs
    x = L.data(name="x", shape=[4], dtype="float32")
    sp = L.softplus(L.clip(x, min=0.0, max=1000.0))
    ls = L.logsigmoid(L.clip(x, min=-1000.0, max=0.0))
    ra = RangeAnalysis(main)
    spv = ra.value_of(sp.name)
    assert spv.lo == 0.0 and spv.hi >= 1000.0, spv  # contains sp(1000)
    lsv = ra.value_of(ls.name)
    assert lsv.lo <= -1000.0 and lsv.hi == 0.0, lsv
