"""analysis/memory.py: the liveness-based peak-HBM engine (ISSUE 15).

* BytesPoly algebra: shapes -> batch polynomials, evaluation, parsing;
* liveness: temps that die early leave the live set, the peak op and
  its top tensors carry PR 5 provenance, breakdown splits persistable/
  feed/activation/workspace;
* the linear batch form is EXACT: the symbolic (-1 batch) analysis
  evaluated at B matches an independently built concrete-batch program,
  for two batch sizes;
* window mode: ``steps_per_call=K`` multiplies stacked-feed bytes by
  exactly K;
* the model-zoo ground-truth gate: static peak within the stated
  factor (``ZOO_GATE_FACTOR``) of XLA's own ``memory_analysis()`` on
  >= 9/11 train programs (CPU backend);
* memory lint rules: OOM-before-compile fires with provenance on a
  synthetic over-budget program, stays silent without a budget /
  on the zoo; max-safe-batch solves the closed form; dead-persistable
  flags untouched resident state;
* serving: the predicted-bytes admission guard (engine + router) and
  ``decode_cache_bytes``;
* tools/memory_report.py CLI: text + JSON + exit 1 on budget violation.
"""

import json
import os
import re
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observe
from paddle_tpu.analysis import ProgramVerifyError, verify_program
from paddle_tpu.analysis.memory import (BytesPoly, MemoryAnalysis,
                                        ZOO_GATE_FACTOR,
                                        decode_cache_bytes, dtype_bytes,
                                        format_bytes, parse_bytes)
from paddle_tpu.core.scope import Scope, scope_guard

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "tools"))


def _value(name, **labels):
    for s in observe.snapshot()["metrics"][name]["samples"]:
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s.get("value", s.get("count"))
    return 0.0


def _fc_train(hidden=8, optimizer=True, data_shape=(4,)):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", list(data_shape), dtype="float32")
        h = layers.fc(x, hidden, act="relu")
        h2 = layers.fc(h, hidden * 2, act="relu")
        loss = layers.mean(h2)
        if optimizer:
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main, startup, loss


def _synth_feed(main, batch):
    """Zero feeds for every data var (-1 dims -> batch); id-valued
    feeds stay at 0, which every vocab accepts."""
    feed = {}
    for v in main.global_block().vars.values():
        if not v.is_data:
            continue
        shape = [batch if (d is None or d < 0) else int(d)
                 for d in (v.shape or [])]
        dt = str(v.dtype or "float32")
        feed[v.name] = np.zeros(
            shape, dtype="int64" if "int" in dt else "float32")
    return feed


# ------------------------------------------------------------ BytesPoly
def test_bytes_poly_algebra():
    p = BytesPoly.from_dims((-1, 784), 4)          # 3136*B
    assert p.terms == {1: 3136.0}
    assert p.at(1) == 3136 and p.at(32) == 3136 * 32
    assert p.degree == 1 and not p.is_const
    q = BytesPoly.from_dims((10, 10), 8)           # const 800
    assert q.is_const and q.at(999) == 800
    s = p + q + 200
    assert s.at(2) == 3136 * 2 + 1000
    assert (p.scaled(3)).at(2) == 3 * 3136 * 2
    assert (s - q).at(2) == 3136 * 2 + 200
    # two symbolic dims -> degree 2
    d2 = BytesPoly.from_dims((-1, -1, 4), 4)
    assert d2.degree == 2 and d2.at(3) == 9 * 16
    assert "3136*B" in p.describe()
    assert BytesPoly.from_shape(None, "float32") is None


def test_parse_and_format_bytes():
    assert parse_bytes("4096") == 4096
    assert parse_bytes("16G") == 16 << 30
    assert parse_bytes("512MB") == 512 << 20
    assert parse_bytes("1.5K") == 1536
    assert parse_bytes(123) == 123
    with pytest.raises(ValueError, match="unparseable"):
        parse_bytes("lots")
    assert format_bytes(16 << 30) == "16.00 GB"
    assert format_bytes(100) == "100 B"


def test_unknown_dtype_warns_and_defaults():
    with pytest.warns(UserWarning, match="unknown dtype"):
        assert dtype_bytes("complex128") == 4
    assert dtype_bytes("bfloat16") == 2


# ------------------------------------------------------------ liveness
def test_liveness_timeline_and_provenance():
    main, _, loss = _fc_train(optimizer=False)
    ma = MemoryAnalysis(main, fetch_names=[loss.name])
    tl = ma.timeline(32)
    assert len(tl) == len(main.global_block().ops)
    peak, pos = ma.peak(32)
    assert peak == max(r["live_bytes"] for r in tl)
    assert tl[pos]["live_bytes"] == peak
    # the first fc's temps are dead by the mean op at the end: the
    # last op's live bytes sit strictly below the peak
    assert tl[-1]["live_bytes"] < peak
    top = ma.top_tensors(32, k=3)
    assert top and top[0]["bytes"] >= top[-1]["bytes"]
    # PR 5 provenance rides every tensor (layers build from this file)
    assert any(t["def_site"] for t in top)
    bd = ma.breakdown(32)
    assert bd["peak"] == peak
    assert bd["persistable"] > 0 and bd["feed"] == 4 * 4 * 32


def test_linear_batch_form_exact_for_two_batch_sizes():
    """The symbolic (-1 batch) analysis evaluated at B matches an
    INDEPENDENTLY built concrete-batch program's analysis — for two
    batch sizes, pinning the polynomial against ground truth instead
    of against itself."""
    main, _, loss = _fc_train(optimizer=False)
    ma = MemoryAnalysis(main, fetch_names=[loss.name])
    assert ma.batch_dependent()
    poly = ma.peak_poly(4)
    assert poly.degree == 1
    for batch in (4, 16):
        cmain, cstartup = fluid.Program(), fluid.Program()
        with fluid.program_guard(cmain, cstartup):
            x = layers.data("x", [batch, 4], dtype="float32",
                            append_batch_size=False)
            h = layers.fc(x, 8, act="relu")
            h2 = layers.fc(h, 16, act="relu")
            closs = layers.mean(h2)
        cma = MemoryAnalysis(cmain, fetch_names=[closs.name])
        assert not cma.batch_dependent()
        assert cma.peak_bytes(1) == ma.peak_bytes(batch)
        assert poly.at(batch) == ma.peak_bytes(batch)


def test_window_mode_k_scaling_pinned():
    main, _, loss = _fc_train()
    ma = MemoryAnalysis(main, fetch_names=[loss.name])
    feed_bytes = ma.feed_poly.at(32)
    assert feed_bytes == 4 * 4 * 32
    for k in (4, 10):
        assert (ma.peak_bytes(32, steps_per_call=k)
                - ma.peak_bytes(32, steps_per_call=1)
                == (k - 1) * feed_bytes)
    # the constructor default is the query default
    ma_k = MemoryAnalysis(main, fetch_names=[loss.name], steps_per_call=4)
    assert ma_k.peak_bytes(32) == ma.peak_bytes(32, steps_per_call=4)


def test_workspace_rules_conv_and_softmax():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = layers.data("img", [3, 16, 16], dtype="float32")
        c = layers.conv2d(img, num_filters=8, filter_size=3, padding=1)
        flat = layers.reshape(c, [-1, 8 * 16 * 16])
        sm = layers.softmax(layers.fc(flat, 10))
        loss = layers.mean(sm)
    ma = MemoryAnalysis(main, fetch_names=[loss.name])
    by_type = {}
    for i, op in enumerate(ma.df.ops):
        by_type.setdefault(op.type, i)
    assert "conv2d" in by_type and "softmax" in by_type
    # conv im2col workspace: out_spatial x (k*k*Cin) elements
    conv_ws = ma.workspace_polys[by_type["conv2d"]]
    assert conv_ws.at(2) == 2 * 16 * 16 * 9 * 3 * 4
    # softmax budgets one input-sized temp
    sm_ws = ma.workspace_polys[by_type["softmax"]]
    assert sm_ws.at(2) == 2 * 10 * 4


def test_observe_families_count_sites():
    main, _, loss = _fc_train(optimizer=False)
    before = _value("paddle_analysis_memory_programs_total", site="api")
    MemoryAnalysis(main, fetch_names=[loss.name], site="api")
    assert _value("paddle_analysis_memory_programs_total",
                  site="api") == before + 1


# --------------------------------------------------------- contrib API
def test_contrib_memory_usage_delegates_and_naive_compares():
    from paddle_tpu.contrib.memory_usage_calc import memory_usage

    main, _, _ = _fc_train(optimizer=False)
    as_bytes = {"B": 1, "KB": 1 << 10, "MB": 1 << 20, "GB": 1 << 30}

    def b(pair):
        return pair[0] * as_bytes[pair[1]]

    engine = b(memory_usage(main, batch_size=32))
    naive = b(memory_usage(main, batch_size=32, naive=True))
    # liveness can only tighten the whole-block sum
    assert 0 < engine <= naive
    # both scale with batch
    assert b(memory_usage(main, batch_size=64)) > engine
    with pytest.raises(ValueError):
        memory_usage(main, batch_size=0)


def test_contrib_naive_warns_on_unknown_dtype():
    from paddle_tpu.contrib.memory_usage_calc import memory_usage

    main, _, _ = _fc_train(optimizer=False)
    var = main.global_block().create_var(name="weird", shape=[4])
    var.dtype = "complex64"
    with pytest.warns(UserWarning, match="unknown dtype"):
        memory_usage(main, batch_size=2, naive=True)


# ------------------------------------------------------- model-zoo gate
# the two models whose XLA AOT compile dominates the gate's wall time
# (~35s/~28s cold vs seconds for the rest); the acceptance floor is
# >= 9/11 within the factor, so the gate pays ground-truth compiles for
# the other nine and still ANALYZES all eleven. (Both were measured
# in-factor when the gate was established: 1.25x / 1.16x.)
_ZOO_XLA_SKIP = ("se_resnext", "resnet")


def test_zoo_static_within_stated_factor_of_xla():
    """Ground truth, not vibes: across the model-zoo train programs
    (forward + backward + Adam, CPU backend), the static estimate sits
    within ZOO_GATE_FACTOR of XLA's own memory_analysis() on >= 9/11 —
    and every one of the 11 programs analyzes without error."""
    from lint_program import EXAMPLE_BUILDERS, build_example
    from paddle_tpu.contrib.memory_usage_calc import compiled_memory_usage

    batch = 8
    ratios, ok = {}, 0
    for name in sorted(EXAMPLE_BUILDERS):
        main, startup, loss = build_example(name)
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup, scope=scope)
            static = MemoryAnalysis(
                main, fetch_names=[loss.name],
                scope=scope).peak_bytes(batch)
            assert static > 0
            if name in _ZOO_XLA_SKIP:
                continue
            feed = _synth_feed(main, batch)
            xla = compiled_memory_usage(exe, main, feed,
                                        fetch_list=[loss], scope=scope)
        if not xla:
            continue  # backend reported nothing: no ground truth
        ratios[name] = static / xla
        if 1.0 / ZOO_GATE_FACTOR <= ratios[name] <= ZOO_GATE_FACTOR:
            ok += 1
    assert len(ratios) >= 9, "XLA memory_analysis unavailable: %r" % ratios
    assert ok >= 9, "only %d/11 within %gx: %r" % (ok, ZOO_GATE_FACTOR,
                                                   ratios)


# ----------------------------------------------------------- lint rules
def test_oom_lint_fires_with_provenance(monkeypatch):
    main, _, loss = _fc_train(hidden=64)
    # peak at B=1 is a few hundred KB; a 10 KB budget provably cannot
    # hold it at ANY batch size -> error naming the peak op
    monkeypatch.setenv("PADDLE_TPU_DEVICE_HBM_BYTES", "10K")
    with pytest.raises(ProgramVerifyError) as ei:
        verify_program(main, fetch_list=[loss])
    msg = str(ei.value)
    assert "memory-over-budget" in msg
    assert "defined at" in msg  # top live tensors carry provenance
    findings = ei.value.findings
    f = next(f for f in findings if f.rule == "memory-over-budget")
    assert f.op_type is not None  # anchored to the peak op


def test_oom_lint_silent_without_budget_and_under_generous_budget(
        monkeypatch):
    main, _, loss = _fc_train()
    monkeypatch.delenv("PADDLE_TPU_DEVICE_HBM_BYTES", raising=False)
    rules = [f.rule for f in verify_program(main, fetch_list=[loss],
                                            raise_on_error=False)]
    assert "memory-over-budget" not in rules
    assert "max-safe-batch" not in rules
    monkeypatch.setenv("PADDLE_TPU_DEVICE_HBM_BYTES", "1T")
    rules = [f.rule for f in verify_program(main, fetch_list=[loss],
                                            raise_on_error=False)]
    assert "memory-over-budget" not in rules


def test_memory_rules_honor_the_rules_filter(monkeypatch):
    """The two budget rule names share one run — selecting only one of
    them must emit only that kind (the rules= subset contract)."""
    from paddle_tpu.analysis import lint_program

    main, _, loss = _fc_train(hidden=64)
    monkeypatch.setenv("PADDLE_TPU_DEVICE_HBM_BYTES", "10K")
    only_safe = lint_program(main, fetch_names=[loss.name],
                             rules=["max-safe-batch"])
    assert not any(f.rule == "memory-over-budget" for f in only_safe)
    only_over = lint_program(main, fetch_names=[loss.name],
                             rules=["memory-over-budget"])
    assert [f.rule for f in only_over] == ["memory-over-budget"]


def test_max_safe_batch_info_solves_the_closed_form(monkeypatch):
    main, _, loss = _fc_train()
    ma = MemoryAnalysis(main, fetch_names=[loss.name])
    budget = ma.peak_bytes(100)  # fits B=100, not (say) B=100000
    monkeypatch.setenv("PADDLE_TPU_DEVICE_HBM_BYTES", str(budget))
    findings = verify_program(main, fetch_list=[loss],
                              raise_on_error=False)
    infos = [f for f in findings if f.rule == "max-safe-batch"]
    assert len(infos) == 1
    m = re.search(r"batch size fitting .* is (\d+)", infos[0].message)
    assert m, infos[0].message
    safe = int(m.group(1))
    assert safe >= 100
    assert ma.peak_bytes(safe) <= budget < ma.peak_bytes(safe + 1)


def test_dead_persistable_flagged_and_absent_when_used():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        loss = layers.mean(layers.fc(x, 4))
        # declared resident, touched by NOTHING in main (startup
        # initializes it, but main just pays HBM for it)
        main.global_block().create_var(
            name="orphan_table", shape=[128, 64], dtype="float32",
            persistable=True)
    findings = verify_program(main, fetch_list=[loss],
                              raise_on_error=False)
    dead = [f for f in findings if f.rule == "dead-persistable"]
    assert len(dead) == 1 and dead[0].var == "orphan_table"
    assert "resident" in dead[0].message
    # every USED persistable (the fc weights) stays unflagged
    assert not any(f.var != "orphan_table" for f in dead)


def test_zoo_stays_clean_under_memory_rules():
    """The new rules add zero errors/warnings to a representative zoo
    program without a budget configured (the full-zoo gate lives in
    test_analysis.py and now covers them too)."""
    from lint_program import verify_example

    findings, _ = verify_example("mnist")
    noisy = [f.format() for f in findings
             if f.severity in ("error", "warning")]
    assert not noisy, noisy


# ------------------------------------------------------ serving guard
TINY_CFG = dict(d_model=32, d_ff=64, n_head=2, n_layer=1, vocab=64,
                max_length=32, dropout=0.0)


def test_decode_cache_bytes_closed_form():
    # 2 slabs x n_layer x [batch, n_kv, max_len, head_dim] x 4B
    assert decode_cache_bytes(TINY_CFG, batch=2, max_len=24) \
        == 2 * 1 * 2 * 2 * 24 * 16 * 4
    gqa = dict(TINY_CFG, n_head=4, n_kv_head=2)
    assert decode_cache_bytes(gqa, batch=2, max_len=24) \
        == 2 * 1 * 2 * 2 * 24 * 8 * 4


def test_engine_admission_guard_and_router_memory_rejection():
    from paddle_tpu.serving import (DecodeEngine, MemoryBudgetExceeded,
                                    ReplicaRouter)

    eng = DecodeEngine(TINY_CFG, b_max=2, max_len=24)
    resident = eng.predicted_resident_bytes()
    assert resident and resident > decode_cache_bytes(
        TINY_CFG, batch=2, max_len=24)
    # the per-P chord is monotone and above resident
    assert eng.predicted_bytes(4) > resident
    assert eng.predicted_bytes(20) >= eng.predicted_bytes(4)
    eng.start()
    try:
        prompt = np.arange(1, 5).astype("int64")
        # no budget: the guard is inert
        assert len(eng.submit(prompt, 3).result(timeout=300)) == 7
        denied0 = _value("paddle_serving_memory_admissions_denied_total")
        eng.device_budget = resident  # prefill extra can never fit
        with pytest.raises(MemoryBudgetExceeded, match="predicted"):
            eng.submit(prompt, 3)
        assert _value("paddle_serving_memory_admissions_denied_total") \
            == denied0 + 1
        # a generous budget admits again
        eng.device_budget = eng.predicted_bytes(4) + (1 << 20)
        assert len(eng.submit(prompt, 3).result(timeout=300)) == 7
    finally:
        eng.stop()

    # router: when EVERY replica's guard refuses, the rejection is
    # counted under reason="memory" and surfaces to the caller
    router = ReplicaRouter(
        lambda i: DecodeEngine(TINY_CFG, b_max=1, max_len=24),
        n_replicas=1)
    try:
        prompt = np.arange(1, 5).astype("int64")
        router.replicas[0].engine.device_budget = 10
        mem0 = _value("paddle_serving_router_rejected_total",
                      reason="memory")
        with pytest.raises(MemoryBudgetExceeded):
            router.submit(prompt, 3)
        assert _value("paddle_serving_router_rejected_total",
                      reason="memory") == mem0 + 1
        router.replicas[0].engine.device_budget = None
        assert len(router.submit(prompt, 3).result(timeout=300)) == 7
    finally:
        router.close()


# ------------------------------------------------------------- CLI
def test_memory_report_cli_text_json_and_budget_exit(capsys):
    import memory_report

    rc = memory_report.main(["--model", "mnist", "--batch-size", "16"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "predicted peak" in out and "peak op" in out
    assert "batch form at peak" in out

    rc = memory_report.main(["--model", "mnist", "--json",
                             "--batch-size", "16", "--timeline",
                             "--device-budget", "1T"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 0
    rep = data["mnist"]
    assert rep["fits"] is True
    assert rep["peak_bytes"] > 0
    assert rep["peak_op"]["type"]
    assert rep["timeline"] and all("live_bytes" in r
                                   for r in rep["timeline"])
    assert rep["top_tensors"][0]["bytes"] >= rep["top_tensors"][-1]["bytes"]

    # a violated budget exits 1 and says so
    rc = memory_report.main(["--model", "mnist", "--batch-size", "16",
                             "--device-budget", "64K"])
    out = capsys.readouterr().out
    assert rc == 1 and "OVER BUDGET" in out


def test_memory_report_cli_window_mode(capsys):
    import memory_report

    rc = memory_report.main(["--model", "mnist", "--json",
                             "--batch-size", "8"])
    base = json.loads(capsys.readouterr().out)["mnist"]["peak_bytes"]
    assert rc == 0
    rc = memory_report.main(["--model", "mnist", "--json",
                             "--batch-size", "8",
                             "--steps-per-call", "10"])
    windowed = json.loads(capsys.readouterr().out)["mnist"]["peak_bytes"]
    assert rc == 0 and windowed > base
