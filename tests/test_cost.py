"""analysis/cost.py + analysis/cost_rules.py: the roofline cost engine
(ISSUE 17).

* rule-table hygiene: COST_RULES and ZERO_COST are disjoint, zero-cost
  ops price to exactly nothing;
* FLOPs rules are EXACT batch polynomials: the fc matmul prices
  2*B*M*N, grad ops ride their base rule scaled by GRAD_FLOPS_FACTOR,
  unruled ops contribute bytes only and are counted;
* DeviceModel resolution: all-four env pin (source 'env', never
  probes), partial env layering over the TPU table, table lookup by
  device-kind substring, malformed env raises;
* roofline queries: window K amortizes exactly the call overhead,
  bound() classifies compute/memory/overhead, predicted MFU is
  analytic-flops over predicted-time-at-peak;
* the model-zoo ground-truth gate: predicted step seconds within
  ``ZOO_COST_GATE_FACTOR`` (4x) of the measured CPU-backend step on
  >= 9/11 train programs — the same anchored-to-reality contract as
  the memory engine's 2x gate.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observe
from paddle_tpu.analysis.cost import (CostAnalysis, DeviceModel,
                                      ZOO_COST_GATE_FACTOR,
                                      predict_step_seconds)
from paddle_tpu.analysis.cost_rules import (COST_RULES,
                                            GRAD_FLOPS_FACTOR, ZERO_COST)
from paddle_tpu.core.scope import Scope, scope_guard

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "tools"))

# the four-field env pin: deterministic device, no probe, no disk
_PIN = {"PADDLE_TPU_PEAK_TFLOPS": "100",      # 1e14 FLOP/s
        "PADDLE_TPU_PEAK_GBPS": "1000",       # 1e12 B/s
        "PADDLE_TPU_OP_OVERHEAD_US": "1",     # 1e-6 s
        "PADDLE_TPU_CALL_OVERHEAD_US": "100"}  # 1e-4 s


@pytest.fixture
def pinned_device(monkeypatch):
    for k, v in _PIN.items():
        monkeypatch.setenv(k, v)
    return DeviceModel.current()


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
        h2 = layers.fc(h, 1)
        loss = layers.mean(h2)
        if optimizer:
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main, startup, loss


# ------------------------------------------------------------ rule table
def test_rule_tables_are_disjoint_and_nonempty():
    assert set(COST_RULES) and set(ZERO_COST)
    assert not set(COST_RULES) & set(ZERO_COST)


def test_zero_cost_ops_price_to_nothing():
    """A program made of shape-plumbing ops contributes zero FLOPs and
    zero bytes for those ops (they move no payload at runtime)."""
    main, _, loss = _fc_train(optimizer=False)
    ca = CostAnalysis(main, fetch_names=[loss.name])
    for c in ca.op_costs:
        if c.op_type in ZERO_COST:
            assert c.flops.at(32) == 0 and c.bytes.at(32) == 0
            assert c.ruled


def test_matmul_flops_are_exact_batch_polynomial(pinned_device):
    """fc's mul op prices exactly 2*B*M*N FLOPs — a polynomial of the
    batch dim, evaluated anywhere."""
    main, _, loss = _fc_train(hidden=16, optimizer=False,
                              data_shape=(784,))
    ca = CostAnalysis(main, fetch_names=[loss.name])
    muls = [c for c in ca.op_costs if c.op_type == "mul"]
    assert muls
    first = muls[0]  # x [B,784] @ W [784,16]
    for b in (1, 8, 64):
        assert first.flops.at(b) == 2 * b * 784 * 16
    assert not first.flops.is_const


def test_grad_ops_scale_base_rule_by_factor():
    main, _, loss = _fc_train(hidden=16, optimizer=True,
                              data_shape=(784,))
    ca = CostAnalysis(main, fetch_names=[loss.name])
    by_type = {}
    for c in ca.op_costs:
        by_type.setdefault(c.op_type, []).append(c)
    fwd = by_type["mul"][0]
    bwd = next(c for c in by_type["mul_grad"]
               if c.flops.at(8) == GRAD_FLOPS_FACTOR * fwd.flops.at(8))
    assert bwd.ruled


def test_unruled_op_contributes_bytes_only_and_is_counted():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
    gb = main.global_block()
    out = gb.create_var(name="myst_out", shape=[-1, 4], dtype="float32")
    gb.append_op(type="mystery_op", inputs={"X": [x]},
                 outputs={"Out": [out]})
    u0 = _value("paddle_cost_unruled_ops_total")
    ca = CostAnalysis(main, infer=False)
    assert "mystery_op" in ca.unruled
    assert _value("paddle_cost_unruled_ops_total") == u0 + 1
    c = next(c for c in ca.op_costs if c.op_type == "mystery_op")
    assert not c.ruled and c.flops.at(8) == 0
    assert c.bytes.at(8) == 2 * 8 * 4 * 4  # in + out, f32


# ----------------------------------------------------------- DeviceModel
def test_device_model_env_pin_all_four(pinned_device):
    dev = pinned_device
    assert dev.source == "env"
    assert dev.peak_flops == 100e12
    assert dev.peak_bandwidth == 1000e9
    assert dev.op_overhead == pytest.approx(1e-6)
    assert dev.call_overhead == pytest.approx(1e-4)
    # env FLOP peak pins the conv-class ceiling too
    assert dev.conv_peak_flops == dev.peak_flops


def test_device_model_table_and_partial_env_layering(monkeypatch):
    monkeypatch.setattr(DeviceModel, "_device_kind",
                        staticmethod(lambda: "tpu:TPU v4"))
    dev = DeviceModel.current()
    assert dev.source == "table"
    assert dev.peak_flops == 275e12 and dev.peak_bandwidth == 1228e9
    assert dev.conv_peak_flops == dev.peak_flops  # MXU: classes alike
    # one env field layers over the table base, the rest stay put
    monkeypatch.setenv("PADDLE_TPU_PEAK_GBPS", "500")
    dev2 = DeviceModel.current()
    assert dev2.source == "env"
    assert dev2.peak_bandwidth == 500e9
    assert dev2.peak_flops == 275e12
    assert dev2.conv_peak_flops == 275e12  # preserved: flops not pinned


@pytest.mark.parametrize("field,attr", [
    ("bf16_flops_per_s", "peak_flops"),
    ("hbm_bytes_per_s", "peak_bandwidth")])
def test_program_peaks_agree_with_the_benchmarks(monkeypatch, field, attr):
    """Two tables of chip peaks stay, on purpose: the yardstick's
    (benchmarks/lib/peaks.py, keyed by the exact ``device_kind``) and
    the cost model's (``cost._TPU_PEAK_*``, by substring). On the chip
    the ledger is measured on they must give the same numbers."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_bench_peaks", os.path.join(ROOT, "benchmarks", "lib", "peaks.py"))
    peaks = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(peaks)
    for k in ("PADDLE_TPU_PEAK_TFLOPS", "PADDLE_TPU_PEAK_GBPS"):
        monkeypatch.delenv(k, raising=False)
    for kind, row in peaks.PEAKS.items():
        monkeypatch.setattr(DeviceModel, "_device_kind",
                            staticmethod(lambda kind=kind: "tpu:" + kind))
        dev = DeviceModel.current()
        assert dev.source == "table", (kind, dev)
        assert getattr(dev, attr) == row[field], (kind, field)


def test_device_model_malformed_env_raises(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PEAK_TFLOPS", "fast")
    with pytest.raises(ValueError, match="PADDLE_TPU_PEAK_TFLOPS"):
        DeviceModel.current()
    monkeypatch.setenv("PADDLE_TPU_PEAK_TFLOPS", "-3")
    with pytest.raises(ValueError, match="positive"):
        DeviceModel.current()


# ------------------------------------------------------ roofline queries
def test_window_k_amortizes_exactly_the_call_overhead(pinned_device):
    main, _, loss = _fc_train()
    ca = CostAnalysis(main, fetch_names=[loss.name],
                      device=pinned_device)
    p1 = ca.predicted_seconds(8, steps_per_call=1)
    p10 = ca.predicted_seconds(8, steps_per_call=10)
    call = pinned_device.call_overhead
    assert p1 - p10 == pytest.approx(call * (1 - 1 / 10))
    assert 0 < ca.predicted_mfu(8, steps_per_call=10) <= 1.0


def test_bound_classifies_all_three_regimes(monkeypatch):
    # a peak so low the matmul is compute-bound, bandwidth so high
    # nothing is memory-bound; tiny ops fall under the op overhead
    monkeypatch.setenv("PADDLE_TPU_PEAK_TFLOPS", "1e-6")   # 1e6 FLOP/s
    monkeypatch.setenv("PADDLE_TPU_PEAK_GBPS", "1e9")
    monkeypatch.setenv("PADDLE_TPU_OP_OVERHEAD_US", "1")
    monkeypatch.setenv("PADDLE_TPU_CALL_OVERHEAD_US", "1")
    main, _, loss = _fc_train(hidden=64, optimizer=False,
                              data_shape=(784,))
    ca = CostAnalysis(main, fetch_names=[loss.name])
    mul = next(r for r in ca.table(64) if r["op_type"] == "mul")
    assert mul["bound"] == "compute"
    # flip the regime: absurd compute peak, starved bandwidth
    monkeypatch.setenv("PADDLE_TPU_PEAK_TFLOPS", "1e6")
    monkeypatch.setenv("PADDLE_TPU_PEAK_GBPS", "1e-3")     # 1e6 B/s
    ca2 = CostAnalysis(main, fetch_names=[loss.name])
    mul2 = next(r for r in ca2.table(64) if r["op_type"] == "mul")
    assert mul2["bound"] == "memory"
    # both peaks absurd, one full second of per-op overhead: every op
    # (the matmul included) disappears under scheduling cost
    monkeypatch.setenv("PADDLE_TPU_PEAK_GBPS", "1e9")
    monkeypatch.setenv("PADDLE_TPU_OP_OVERHEAD_US", "1e6")
    ca3 = CostAnalysis(main, fetch_names=[loss.name])
    assert {r["bound"] for r in ca3.table(64)} == {"overhead"}


def test_predict_step_seconds_convenience_and_site_counter(
        pinned_device):
    main, _, loss = _fc_train()
    c0 = _value("paddle_cost_programs_total", site="api")
    secs = predict_step_seconds(main, batch_size=8,
                                fetch_names=[loss.name])
    assert secs > 0
    assert _value("paddle_cost_programs_total", site="api") == c0 + 1


# ------------------------------------------------------- model-zoo gate
# XLA AOT compile time dominates for these two (the memory gate's
# skip list); the floor is >= 9/11 so the other nine carry the gate
_ZOO_MEASURE_SKIP = ("se_resnext", "resnet")


def _synth_feed(main, batch):
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


@pytest.mark.slow
def test_zoo_predicted_within_stated_factor_of_measured():
    """Ground truth, not vibes: across the model-zoo train programs
    (forward + backward + Adam, CPU backend, live-calibrated device
    model), the roofline's predicted step seconds sit within
    ZOO_COST_GATE_FACTOR of the measured warm step on >= 9/11 — and
    every one of the 11 programs prices without error."""
    from lint_program import EXAMPLE_BUILDERS, build_example

    assert ZOO_COST_GATE_FACTOR == 4.0
    batch = 8
    ratios, ok = {}, 0
    for name in sorted(EXAMPLE_BUILDERS):
        main, startup, loss = build_example(name)
        scope = Scope()
        with scope_guard(scope):
            ca = CostAnalysis(main, fetch_names=[loss.name], scope=scope)
            pred = ca.predicted_seconds(batch)
            assert pred > 0
            if name in _ZOO_MEASURE_SKIP:
                continue
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup, scope=scope)
            feed = _synth_feed(main, batch)
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
                best = min(best, time.perf_counter() - t0)
        ratios[name] = pred / best
        if 1.0 / ZOO_COST_GATE_FACTOR <= ratios[name] \
                <= ZOO_COST_GATE_FACTOR:
            ok += 1
    assert len(ratios) >= 9
    assert ok >= 9, "only %d/%d within %gx: %r" % (
        ok, len(ratios), ZOO_COST_GATE_FACTOR, ratios)
