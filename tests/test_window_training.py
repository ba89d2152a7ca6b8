"""Whole-loop compilation: windowed scanned training through the
pipeline (ISSUE 13 tentpole).

* windowed ``train_loop`` (steps_per_call=K) is BITWISE the per-step
  loop — params, optimizer slots and the RNG chain advance exactly as
  unrolled, through dropout (the clause that makes RNG real) and Adam;
* a ragged final window (reader dry / shape change) falls back to the
  per-step path instead of compiling a second scan length, counted in
  ``paddle_pipeline_window_ragged_steps_total``;
* ``steps_per_call`` resolves as argument > env > 1, and validates;
* crash-mid-window resume parity: ``resilient_train_loop`` with K>1
  checkpoints only at window boundaries, records ``steps_per_call`` in
  the manifest, and a crashed-and-recovered run ends bitwise identical
  to an uninterrupted one;
* (slow) the acceptance pin: windowed ``train_loop`` at K>=10 sustains
  >= 1.5x steps/sec over the per-step loop on a dispatch-bound
  workload — calibrated best-of-5 ratio, no absolute-ms asserts —
  with bitwise parameter/RNG parity asserted alongside.
"""

import os
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observe
from paddle_tpu.core.executor import RNG_VAR, _resolve_steps_per_call
from paddle_tpu.core.scope import Scope, scope_guard


def _value(name, **labels):
    for s in observe.snapshot()["metrics"][name]["samples"]:
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s.get("value", s.get("count"))
    return 0.0


def _build(seed=7, dropout=True, hidden=16):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        h = layers.fc(x, hidden, act="relu")
        if dropout:
            h = layers.dropout(h, dropout_prob=0.3)
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square(pred - y))
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(loss)
    return main, startup, loss


def _batches(n, seed=0, batch=16):
    rs = np.random.RandomState(seed)
    return [{"x": rs.randn(batch, 8).astype("float32"),
             "y": rs.randn(batch, 1).astype("float32")} for _ in range(n)]


def _state(scope):
    """Every scope array incl. optimizer slots AND the RNG chain, in a
    name-order comparable across two independently built copies of the
    model ((len, name) = numeric layer order)."""
    names = sorted(scope.local_var_names(), key=lambda n: (len(n), n))
    return [(n, np.asarray(scope.find_var(n))) for n in names]


def _assert_bitwise(state_a, state_b):
    assert len(state_a) == len(state_b) and state_a
    for (na, a), (nb, b) in zip(state_a, state_b):
        assert a.tobytes() == b.tobytes(), (na, nb)


def _run_loop(batches, steps_per_call, seed=7, on_step=None, **kw):
    main, startup, loss = _build(seed=seed)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        n, last = exe.train_loop(
            main, iter(batches), fetch_list=[loss], scope=scope,
            steps_per_call=steps_per_call, on_step=on_step, **kw)
        return n, last, _state(scope)


# ------------------------------------------------------------ parity
def test_windowed_train_loop_bitwise_parity_k4_vs_k1():
    """THE semantics contract: K=4 windows vs the per-step loop, same
    batches — params, Adam slots and the RNG chain byte-equal (dropout
    in the model makes the RNG clause real), window fetch values equal
    to the per-step values at the window-end steps."""
    batches = _batches(8)
    seen1, seen4 = [], []
    n1, last1, s1 = _run_loop(batches, 1,
                              on_step=lambda i, v: seen1.append(
                                  (i, v[0].tobytes())))
    n4, last4, s4 = _run_loop(batches, 4,
                              on_step=lambda i, v: seen4.append(
                                  (i, v[0].tobytes())))
    assert n1 == n4 == 8  # step counts, not dispatch counts
    _assert_bitwise(s1, s4)
    # on_step fires per WINDOW at its last step's index, with the
    # window's last-step fetch values — byte-equal to the per-step run
    assert [i for i, _ in seen4] == [3, 7]
    per_step = dict(seen1)
    for i, v in seen4:
        assert v == per_step[i]
    assert np.array_equal(last1[0], last4[0])


def test_windowed_ragged_final_window_falls_back():
    """7 batches at K=4: one full window + 3 per-step fallback
    dispatches — no second scan length is ever compiled, the ragged
    steps are counted, and parity still holds."""
    r0 = _value("paddle_pipeline_window_ragged_steps_total")
    w0 = observe.snapshot()["metrics"][
        "paddle_pipeline_window_steps_per_dispatch"]["samples"][0]["count"]
    batches = _batches(7)
    n1, _, s1 = _run_loop(batches, 1)
    n4, _, s4 = _run_loop(batches, 4)
    assert n1 == n4 == 7
    _assert_bitwise(s1, s4)
    assert _value("paddle_pipeline_window_ragged_steps_total") == r0 + 3
    w1 = observe.snapshot()["metrics"][
        "paddle_pipeline_window_steps_per_dispatch"]["samples"][0]["count"]
    assert w1 == w0 + 1  # exactly one full-window scan dispatch
    assert _value("paddle_pipeline_window_size") == 4


def test_windowed_shape_change_flushes_window_per_step():
    """A batch whose shapes differ from the open window flushes the
    buffered feeds through the per-step path (stacking never mixes
    shapes) — and the loop still resolves every step."""
    batches = _batches(3, batch=16) + _batches(3, batch=8, seed=1)
    r0 = _value("paddle_pipeline_window_ragged_steps_total")
    n, _, _ = _run_loop(batches, 4)
    assert n == 6
    # 3 flushed (shape change) + 3 ragged tail = all 6 per-step
    assert _value("paddle_pipeline_window_ragged_steps_total") == r0 + 6


def test_windowed_reduce_fetches_mean():
    batches = _batches(4)
    seen1, seen4 = [], []
    _run_loop(batches, 1, on_step=lambda i, v: seen1.append(
        float(np.asarray(v[0]).reshape(-1)[0])))
    _, last4, _ = _run_loop(batches, 4, reduce_fetches="mean",
                            on_step=lambda i, v: seen4.append(
                                float(np.asarray(v[0]).reshape(-1)[0])))
    assert len(seen4) == 1
    np.testing.assert_allclose(seen4[0], np.mean(seen1), rtol=1e-5)


def test_run_pipelined_validates_window_args():
    main, startup, loss = _build()
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        with pytest.raises(ValueError, match="steps_per_call"):
            exe.run_pipelined(main, iter(_batches(2)), [loss], scope,
                              steps_per_call=0)
        with pytest.raises(ValueError, match="last|mean|sum"):
            exe.run_pipelined(main, iter(_batches(2)), [loss], scope,
                              reduce_fetches="avg")


def test_windowed_prefetcher_stacks_one_h2d_per_window():
    """THE H2D half of the amortization: a windowed loop's prefetch
    thread stacks K host batches host-side and hands off ONE WindowFeed
    per window — one device_put (one h2d histogram observation) per K
    steps, same total bytes as the per-step loop."""
    batches = _batches(8)

    def h2d():
        s = observe.snapshot()["metrics"]["paddle_pipeline_h2d_seconds"][
            "samples"][0]
        return s["count"], _value("paddle_pipeline_h2d_bytes_total")

    c0, b0 = h2d()
    n1, _, s1 = _run_loop(batches, 1)
    c1, b1 = h2d()
    assert c1 - c0 == 8  # classic loop: one hand-off per batch
    n4, _, s4 = _run_loop(batches, 4)
    c2, b2 = h2d()
    assert c2 - c1 == 2  # windowed: one hand-off per K-batch window
    assert b2 - b1 == b1 - b0  # same payload bytes, 4x fewer calls
    _assert_bitwise(s1, s4)


def test_caller_supplied_prefetcher_windows_loop_side():
    """A caller-constructed DevicePrefetcher hands over per-step
    device-resident feeds (no window resolver): the loop windows them
    via jnp.stack — dispatch still amortizes (one scan per K steps,
    window telemetry moves) and parity holds."""
    batches = _batches(8)
    n1, _, s1 = _run_loop(batches, 1)
    w0 = observe.snapshot()["metrics"][
        "paddle_pipeline_window_steps_per_dispatch"]["samples"][0]["count"]
    main, startup, loss = _build()
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        pre = fluid.DevicePrefetcher(iter(batches), place=exe.place,
                                     program=main)
        assert pre.resolved_window is None  # no resolver installed
        n4, _ = exe.train_loop(main, pre, fetch_list=[loss], scope=scope,
                               steps_per_call=4)[:2]
        s4 = _state(scope)
    assert n1 == n4 == 8
    _assert_bitwise(s1, s4)
    w1 = observe.snapshot()["metrics"][
        "paddle_pipeline_window_steps_per_dispatch"]["samples"][0]["count"]
    assert w1 == w0 + 2  # two K=4 scan dispatches, windowed loop-side


def test_windowed_const_feed_ragged_tail_stays_bitwise():
    """Review regression: the windowed loop's by-name const tier holds
    the K-STACKED device copy — a ragged per-step fallback dispatch
    must NOT be served that [K, ...] array (broadcasting would train on
    silently wrong math). 6 batches at K=4 = one full window + 2 ragged
    steps with the const feed in play; bitwise parity vs the per-step
    loop proves the shape-guarded lookup re-transferred."""
    batches = _batches(6)
    const_y = batches[0]["y"]
    for b in batches:
        b["y"] = const_y

    def run(spc):
        main, startup, loss = _build()
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup, scope=scope)
            n, _ = exe.train_loop(main, iter(batches), fetch_list=[loss],
                                  scope=scope, steps_per_call=spc,
                                  const_feed_names=("y",))[:2]
            return n, _state(scope)

    n1, s1 = run(1)
    n4, s4 = run(4)
    assert n1 == n4 == 6
    _assert_bitwise(s1, s4)


def test_windowed_const_feed_transfers_once():
    """const_feed_names in window mode: the stacked window caches by
    NAME — the first window transfers it, every later window reuses the
    device copy (bytes_saved moves), and values still reach the scan
    stacked like any feed."""
    batches = _batches(8)
    const_y = batches[0]["y"]
    for b in batches:
        b["y"] = const_y
    h0 = _value("paddle_pipeline_const_feed_hits_total")
    main, startup, loss = _build()
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        n, _ = exe.train_loop(main, iter(batches), fetch_list=[loss],
                              scope=scope, steps_per_call=4,
                              const_feed_names=("y",))[:2]
    assert n == 8
    # window 2 hits the by-name tier (window 1 stored the stacked copy)
    assert _value("paddle_pipeline_const_feed_hits_total") == h0 + 1


# -------------------------------------------------------- resolution
def test_resolve_steps_per_call_precedence(monkeypatch):
    """The argument, else PADDLE_TPU_STEPS_PER_CALL, else 1; either
    source raises on a value that is no integer >= 1, never a silent
    clamp to the per-step loop."""
    monkeypatch.delenv("PADDLE_TPU_STEPS_PER_CALL", raising=False)
    assert _resolve_steps_per_call() == 1
    monkeypatch.setenv("PADDLE_TPU_STEPS_PER_CALL", "25")
    assert _resolve_steps_per_call(4) == 4
    assert _resolve_steps_per_call() == 25
    monkeypatch.setenv("PADDLE_TPU_STEPS_PER_CALL", "bogus")
    with pytest.raises(ValueError, match="STEPS_PER_CALL"):
        _resolve_steps_per_call()
    monkeypatch.setenv("PADDLE_TPU_STEPS_PER_CALL", "0")
    with pytest.raises(ValueError, match="STEPS_PER_CALL.*>= 1"):
        _resolve_steps_per_call()
    monkeypatch.delenv("PADDLE_TPU_STEPS_PER_CALL")
    with pytest.raises(ValueError, match="steps_per_call"):
        _resolve_steps_per_call(0)


# -------------------------------------------------- supervisor windows
def test_supervisor_windowed_checkpoints_at_window_boundaries(tmp_path):
    """K=2, checkpoint_every=3: checkpoints land at the FIRST window
    boundary at-or-after each multiple (steps 4, 6, 8 for 8 steps) and
    the manifest records steps_per_call."""
    from paddle_tpu.resilience import resilient_train_loop
    from paddle_tpu.resilience.supervisor import read_manifest

    main, startup, loss = _build()
    scope = Scope()
    d = str(tmp_path / "ck")
    seen = []
    with scope_guard(scope):
        r = resilient_train_loop(
            main, lambda: iter(_batches(8)), [loss], scope=scope,
            checkpoint_dir=d, startup_program=startup,
            checkpoint_every=3, keep_last=8, max_restarts=0,
            steps_per_call=2, on_step=lambda s, v: seen.append(s))
    assert r.steps == 8
    # on_step fires per WINDOW at its last global step
    assert seen == [2, 4, 6, 8]
    man = read_manifest(d)
    assert man["steps_per_call"] == 2 and man["completed"]
    dirs = sorted(p for p in os.listdir(d) if p.startswith("step_"))
    # boundary checkpoints at 4 (first window edge past 3), 6, 8 + the
    # completed-run final checkpoint (also step 8)
    assert dirs == ["step_00000004", "step_00000006", "step_00000008"]


def test_supervisor_manifest_records_resolved_k_on_all_ragged_run(
        tmp_path):
    """Review regression: the manifest's steps_per_call is the loop's
    RESOLVED K (handle-reported), not max(h.steps) seen — a K=4 run
    whose reader dries up after 3 batches dispatches only ragged
    per-step fallbacks (every h.steps == 1), but the manifest must
    still say 4: that is the dispatch shape a resumed run re-resolves
    and re-aligns to."""
    from paddle_tpu.resilience import resilient_train_loop
    from paddle_tpu.resilience.supervisor import read_manifest

    main, startup, loss = _build()
    scope = Scope()
    d = str(tmp_path / "ck")
    with scope_guard(scope):
        r = resilient_train_loop(
            main, lambda: iter(_batches(3)), [loss], scope=scope,
            checkpoint_dir=d, startup_program=startup,
            checkpoint_every=2, keep_last=8, max_restarts=0,
            steps_per_call=4)
    assert r.steps == 3
    assert read_manifest(d)["steps_per_call"] == 4


def test_malformed_env_steps_per_call_raises_at_call_time(monkeypatch):
    """Review regression: a malformed PADDLE_TPU_STEPS_PER_CALL must
    raise AT run_pipelined call time with the rest of the argument
    validation — not from the prefetch fill thread (surfacing
    mid-iteration as a reader failure) at the first batch."""
    main, startup, loss = _build()
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        monkeypatch.setenv("PADDLE_TPU_STEPS_PER_CALL", "bogus")
        with pytest.raises(ValueError, match="STEPS_PER_CALL"):
            exe.run_pipelined(main, iter(_batches(2)), [loss],
                              scope=scope)
        monkeypatch.setenv("PADDLE_TPU_STEPS_PER_CALL", "0")
        with pytest.raises(ValueError, match="STEPS_PER_CALL.*>= 1"):
            exe.run_pipelined(main, iter(_batches(2)), [loss],
                              scope=scope)


def test_crash_mid_window_resume_parity(tmp_path):
    """A FaultPlan raise mid-run (between windows; a window is one
    indivisible dispatch) recovers from the last window-boundary
    checkpoint, replays, and ends BITWISE identical to an
    uninterrupted windowed run AND to an uninterrupted per-step run."""
    from paddle_tpu.resilience import resilient_train_loop
    from paddle_tpu.resilience.faults import FaultPlan
    from paddle_tpu.resilience.supervisor import read_manifest

    batches = _batches(8)

    def run(steps_per_call, fault, ckdir):
        main, startup, loss = _build()
        scope = Scope()
        with scope_guard(scope):
            if fault:
                # startup dispatch = occurrence 1; occurrence 4 lands
                # after the checkpoint at step 4 finalized
                with FaultPlan().arm("executor.dispatch", steps=(4,)):
                    r = resilient_train_loop(
                        main, lambda: iter(batches), [loss], scope=scope,
                        checkpoint_dir=ckdir, startup_program=startup,
                        checkpoint_every=2, max_restarts=2,
                        backoff_base_s=0.001, backoff_cap_s=0.01,
                        steps_per_call=steps_per_call)
            else:
                r = resilient_train_loop(
                    main, lambda: iter(batches), [loss], scope=scope,
                    checkpoint_dir=ckdir, startup_program=startup,
                    checkpoint_every=2, max_restarts=0,
                    steps_per_call=steps_per_call)
            return r, _state(scope)

    r_clean, s_clean = run(2, False, str(tmp_path / "clean"))
    r_crash, s_crash = run(2, True, str(tmp_path / "crash"))
    r_step, s_step = run(1, False, str(tmp_path / "step"))
    assert r_clean.steps == r_crash.steps == r_step.steps == 8
    assert r_crash.restarts >= 1
    _assert_bitwise(s_clean, s_crash)
    _assert_bitwise(s_clean, s_step)
    # the crashed run resumed from a WINDOW-BOUNDARY checkpoint
    man = read_manifest(str(tmp_path / "crash"))
    assert man["steps_per_call"] == 2


# ------------------------------------------------------ the speedup pin
@pytest.mark.slow
def test_windowed_train_loop_beats_per_step_on_dispatch_bound_workload():
    """Acceptance: windowed train_loop (K=25 >= the required 10)
    sustains >= 1.5x steps/sec over the per-step loop on a
    dispatch-bound workload (tiny step: per-step host dispatch
    dominates; one scan dispatch per K steps amortizes it) — with
    BITWISE parameter/RNG parity between the two segments asserted
    alongside. Calibrated best-of-5 ratio, no absolute-ms asserts:
    the failure mode on this throttled box is noise-induced
    under-measurement, and a genuine regression fails all 5."""
    steps, k = 100, 25
    batches = _batches(steps)

    def segment(spc):
        main, startup, loss = _build(hidden=8)
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup, scope=scope)
            # pay every compile OUTSIDE the timed loop, against a
            # STARTUP-FRESH scratch scope driven through the exact loop
            # shape being timed: jit caches key on argument layouts,
            # and a fresh scope's first step consumes startup-layout
            # state while steady state consumes post-step layouts — two
            # executable variants, both of which a 2-window warm loop
            # compiles (a run()/run_repeated warmup compiles NEITHER of
            # the pipelined loop's variants)
            warm_scope = Scope()
            with scope_guard(warm_scope):
                exe.run(startup, scope=warm_scope)
                exe.train_loop(main, iter(batches[:2 * spc + 2]),
                               fetch_list=[loss], scope=warm_scope,
                               steps_per_call=spc)
            t0 = time.perf_counter()
            n, last = exe.train_loop(main, iter(batches),
                                     fetch_list=[loss], scope=scope,
                                     steps_per_call=spc)
            dt = time.perf_counter() - t0
            assert n == steps
            return dt, _state(scope)

    speedup = 0.0
    for attempt in range(5):
        if attempt:
            time.sleep(1.0)  # let a transient load spike decorrelate
        dt1, s1 = segment(1)
        dtk, sk = segment(k)
        _assert_bitwise(s1, sk)  # parity holds on EVERY attempt
        speedup = dt1 / dtk
        print("per-step %.3fs windowed(K=%d) %.3fs speedup %.2fx"
              % (dt1, k, dtk, speedup))
        if speedup >= 1.5:
            break
    assert speedup >= 1.5, (dt1, dtk)
