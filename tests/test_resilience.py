"""Resilience runtime: fault injection plane, wedge watchdog,
checkpoint-resume supervisor (docs/RESILIENCE.md).

Chaos-test discipline (ISSUE 4): calibrated RATIOS between injected
durations and detection deadlines plus event/counter assertions — no
absolute-millisecond timing (this box throttles to ~2 cpu shares with
20-60ms scheduler noise)."""

import os
import random
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import io, layers, observe
from paddle_tpu.core.executor import RNG_VAR
from paddle_tpu.core.scope import Scope, scope_guard
from paddle_tpu.resilience import (FaultPlan, Heartbeat, InjectedFault,
                                   Watchdog, backoff_delay, fault_point,
                                   millis_env, read_manifest,
                                   resilient_train_loop, run_with_deadline,
                                   write_manifest)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _value(name, **labels):
    fam = observe.get_metric(name)
    return fam.labels(**labels).value if labels else fam.value


# ------------------------------------------------------------ fault plan
def test_fault_plan_parse_grammar():
    p = FaultPlan.parse(
        "executor.dispatch@3:wedge=0.5;rpc.send@1,4:raise;"
        "device_put@p=0.25:raise;reader.next@*:delay=0.01;"
        "checkpoint.write@2+:crash;seed=7")
    assert len(p.specs) == 5 and p.seed == 7
    r = repr(p)
    for frag in ("executor.dispatch@3:wedge=0.5", "rpc.send@1,4:raise",
                 "device_put@p=0.25:raise", "reader.next@*:delay=0.01",
                 "checkpoint.write@2+:crash"):
        assert frag in r, r


def test_fault_plan_parse_rejects_junk():
    with pytest.raises(ValueError, match="site@trigger:action"):
        FaultPlan.parse("executor.dispatch-raise")
    with pytest.raises(ValueError, match="mode must be one of"):
        FaultPlan.parse("executor.dispatch@1:explode")
    with pytest.raises(ValueError, match="exactly ONE trigger"):
        FaultPlan().arm("rpc.send", steps=(1,), every=True)
    with pytest.raises(ValueError, match="probability"):
        FaultPlan().arm("rpc.send", p=1.5)


def test_fault_point_fires_on_chosen_occurrence_with_telemetry():
    site, mode = "executor.dispatch", "raise"
    i0 = _value("paddle_resilience_faults_injected_total",
                site=site, mode=mode)
    plan = FaultPlan().arm(site, steps=(2,))
    with plan:
        assert _value("paddle_resilience_fault_sites_armed") == 1
        fault_point(site)  # occurrence 1: passes
        with pytest.raises(InjectedFault) as e:
            fault_point(site)
        assert e.value.occurrence == 2 and e.value.site == site
        fault_point(site)  # occurrence 3: passes again
    assert _value("paddle_resilience_fault_sites_armed") == 0
    assert _value("paddle_resilience_faults_injected_total",
                  site=site, mode=mode) == i0 + 1
    assert plan.occurrences(site) == 3 and plan.injected == 1
    fault_point(site)  # uninstalled: noop


def test_fault_plan_occurrences_count_across_installs():
    """The chaos schedule stays deterministic across supervisor
    recoveries because counters are per-plan-lifetime, not per-install."""
    plan = FaultPlan().arm("rpc.send", steps=(3,))
    with plan:
        fault_point("rpc.send")
        fault_point("rpc.send")
    with plan:  # re-install: counter continues at 3
        with pytest.raises(InjectedFault):
            fault_point("rpc.send")


def test_probabilistic_trigger_is_seed_deterministic():
    def fire_pattern(seed):
        plan = FaultPlan(seed=seed).arm("device_put", p=0.5)
        out = []
        with plan:
            for _ in range(32):
                try:
                    fault_point("device_put")
                    out.append(0)
                except InjectedFault:
                    out.append(1)
        return out

    a, b, c = fire_pattern(7), fire_pattern(7), fire_pattern(8)
    assert a == b
    assert a != c  # overwhelmingly likely for 32 fair draws
    assert 0 < sum(a) < 32


def test_env_plan_requires_exclusive_install():
    plan = FaultPlan().arm("reader.next", every=True)
    with plan:
        with pytest.raises(RuntimeError, match="already installed"):
            FaultPlan().install()


# --------------------------------------------------------------- backoff
def test_backoff_full_jitter_envelope_and_determinism():
    rng = random.Random(3)
    delays = [backoff_delay(k, 0.05, 1.0, rng) for k in range(12)]
    for k, d in enumerate(delays):
        assert 0.0 <= d <= min(1.0, 0.05 * 2 ** k)
    rng2 = random.Random(3)
    assert delays == [backoff_delay(k, 0.05, 1.0, rng2)
                      for k in range(12)]
    # the envelope saturates at the cap
    assert all(backoff_delay(30, 0.05, 1.0, rng) <= 1.0 for _ in range(8))
    with pytest.raises(ValueError):
        backoff_delay(-1, 0.05, 1.0)


def test_millis_env_junk_falls_back(monkeypatch):
    monkeypatch.setenv("PT_TEST_KNOB", "junk")
    assert millis_env("PT_TEST_KNOB", 250) == 0.25
    monkeypatch.setenv("PT_TEST_KNOB", "-5")
    assert millis_env("PT_TEST_KNOB", 250) == 0.25
    monkeypatch.setenv("PT_TEST_KNOB", "100")
    assert millis_env("PT_TEST_KNOB", 250) == 0.1
    monkeypatch.delenv("PT_TEST_KNOB")
    assert millis_env("PT_TEST_KNOB", 250) == 0.25


# -------------------------------------------------------------- watchdog
def _wait_for(pred, timeout=10.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return True
        time.sleep(0.01)
    return pred()


def test_watchdog_wedge_vs_compile_grace():
    hb = Heartbeat()
    fired = []
    w0 = _value("paddle_resilience_wedges_detected_total",
                site="executor.dispatch")
    wd = Watchdog(deadline_s=0.1, poll_s=0.02, compile_grace_s=30.0,
                  on_wedge=fired.append, heartbeat=hb)
    with wd.watching():
        # a first-signature compile may legally outlive the steady-state
        # deadline many times over (ratio 0.4s busy vs 0.1s deadline)
        hb.begin("executor.dispatch", compiling=True)
        time.sleep(0.4)
        assert not fired, "compile-grace stamp misjudged as a wedge"
        hb.end("executor.dispatch")

        # a steady-state dispatch stalling past the deadline IS a wedge
        hb.begin("executor.dispatch", step=5)
        assert _wait_for(lambda: fired)
        assert fired[0].site == "executor.dispatch"
        assert fired[0].step == 5
        # one detection per stalled op, not one per poll
        time.sleep(0.3)
        assert len(fired) == 1
        hb.end("executor.dispatch")

        # a NEW stall re-arms the detector
        hb.begin("executor.dispatch", step=6)
        assert _wait_for(lambda: len(fired) >= 2)
    assert wd.wedges == fired
    assert _value("paddle_resilience_wedges_detected_total",
                  site="executor.dispatch") == w0 + len(fired)
    assert _value("paddle_resilience_watchdog_armed") == 0


def test_watchdog_sees_oldest_open_op_through_concurrent_stamps():
    """A healthy thread stamping begin/end (a serving batcher) must not
    mask a wedged dispatch: the heartbeat tracks OPEN operations, and
    the wedged one stays oldest."""
    hb = Heartbeat()
    fired = []
    stop = threading.Event()

    def churn():
        while not stop.is_set():
            t = hb.begin("executor.wait")
            hb.end("executor.wait", t)
            time.sleep(0.005)

    t = threading.Thread(target=churn, daemon=True)
    with Watchdog(deadline_s=0.1, poll_s=0.02, on_wedge=fired.append,
                  heartbeat=hb).watching():
        tok = hb.begin("executor.dispatch", step=3)  # wedges, never ends
        t.start()
        try:
            assert _wait_for(lambda: fired), \
                "concurrent healthy stamps masked the wedged dispatch"
        finally:
            stop.set()
            t.join()
        hb.end("executor.dispatch", tok)
    assert fired[0].site == "executor.dispatch" and fired[0].step == 3


def test_watchdog_idle_heartbeat_never_fires_and_zeroes_age():
    hb = Heartbeat()
    fired = []
    t = hb.begin("executor.dispatch")
    hb.end("executor.dispatch", t)
    with Watchdog(deadline_s=0.05, poll_s=0.01, on_wedge=fired.append,
                  heartbeat=hb).watching():
        time.sleep(0.25)
        # idle polls write 0, not the last busy age — a gauge frozen at
        # a long compile's age would trip age alerts on a healthy
        # process forever
        assert _value("paddle_resilience_heartbeat_age_seconds") == 0
    assert not fired


def test_watchdog_policy_exception_does_not_kill_detector():
    hb = Heartbeat()
    seen = []

    def bad_policy(event):
        seen.append(event)
        raise RuntimeError("broken policy")

    wd = Watchdog(deadline_s=0.05, poll_s=0.01, on_wedge=bad_policy,
                  heartbeat=hb)
    with wd.watching():
        hb.begin("executor.dispatch")
        assert _wait_for(lambda: seen)
        hb.end("executor.dispatch")
        hb.begin("executor.dispatch")
        assert _wait_for(lambda: len(seen) >= 2), \
            "detector thread died in the policy callback"
        hb.end("executor.dispatch")


def test_run_with_deadline_outcomes():
    ok, val, dt = run_with_deadline(lambda: 42, 30.0)
    assert ok and val == 42
    ok, val, dt = run_with_deadline(
        lambda: (_ for _ in ()).throw(ValueError("boom")), 30.0)
    assert not ok and isinstance(val, ValueError)
    # wedged call: sleep 30s vs deadline 0.3s (100x ratio)
    ok, val, dt = run_with_deadline(lambda: time.sleep(30), 0.3,
                                    poll_s=0.05)
    assert not ok and isinstance(val, TimeoutError)
    assert dt < 30


# ------------------------------------------------- fault-site integration
def _build(seed=42, dropout=False):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        h = layers.fc(x, 16, act="relu")
        if dropout:
            h = layers.dropout(h, dropout_prob=0.3)
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square(pred - y))
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(loss)
    main.random_seed = seed
    startup.random_seed = seed
    return main, startup, loss


def _batches(n, seed=0):
    rs = np.random.RandomState(seed)
    return [{"x": rs.randn(16, 8).astype("float32"),
             "y": rs.randn(16, 1).astype("float32")} for _ in range(n)]


def _params(scope, main):
    """Persistable values sorted by (len, name) — numeric layer order,
    comparable across two independently built copies of the model."""
    d = {n: np.asarray(scope.find_var(n)) for n in scope.local_var_names()
         if main.global_block().vars.get(n) is not None
         and main.global_block().vars[n].persistable}
    return [d[k] for k in sorted(d, key=lambda n: (len(n), n))]


def test_executor_dispatch_fault_site_fires_and_state_survives():
    main, startup, loss = _build()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    batches = _batches(3)
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        exe.run(main, feed=batches[0], fetch_list=[loss], scope=scope)
        snap = _params(scope, main)
        # occurrence counting is PER PLAN: dispatches before install
        # don't count, so the next dispatch is occurrence 1
        with FaultPlan().arm("executor.dispatch", steps=(1,)):
            with pytest.raises(InjectedFault):
                exe.run(main, feed=batches[1], fetch_list=[loss],
                        scope=scope)
        # the fault fired BEFORE dispatch: scope state is untouched, so
        # the step is cleanly retryable
        for a, b in zip(snap, _params(scope, main)):
            assert np.array_equal(a, b)
        out = exe.run(main, feed=batches[2], fetch_list=[loss],
                      scope=scope)
        assert np.isfinite(out[0]).all()


def test_reader_and_device_put_fault_sites_surface_in_train_loop():
    main, startup, loss = _build()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        with FaultPlan().arm("reader.next", steps=(2,)):
            with pytest.raises(InjectedFault):
                exe.train_loop(main, lambda: iter(_batches(4)),
                               fetch_list=[loss], scope=scope)
        with FaultPlan().arm("device_put", steps=(2,)):
            with pytest.raises(InjectedFault):
                exe.train_loop(main, lambda: iter(_batches(4)),
                               fetch_list=[loss], scope=scope)


def test_executor_heartbeat_stamps_dispatch_and_fetch_wait():
    from paddle_tpu.resilience import heartbeat

    main, startup, loss = _build()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        seq0 = heartbeat().snapshot()["seq"]
        exe.run(main, feed=_batches(1)[0], fetch_list=[loss], scope=scope)
        snap = heartbeat().snapshot()
    # begin/end around the dispatch AND around the blocking numpy fetch
    # conversion — the host block where a wedged device would hang, so
    # the watchdog must see it as busy, not idle
    assert snap["seq"] >= seq0 + 4
    assert snap["phase"] == Heartbeat.IDLE
    assert snap["site"] == "executor.wait"


def test_uninstall_restores_env_plan_armed_gauge(monkeypatch):
    """Telemetry must not report the injection plane inactive while an
    env-armed plan keeps routing faults after an explicit plan exits."""
    from paddle_tpu.resilience import faults

    monkeypatch.setenv(faults.ENV_VAR, "rpc.send@999:raise")
    monkeypatch.setattr(faults, "_ENV_CHECKED", False)
    monkeypatch.setattr(faults, "_ENV_PLAN", None)
    try:
        fault_point("rpc.send")  # parses the env plan (occurrence 1)
        assert _value("paddle_resilience_fault_sites_armed") == 1
        with FaultPlan().arm("device_put", steps=(99,), every=False):
            assert _value("paddle_resilience_fault_sites_armed") == 1
        # explicit plan gone, env plan still live -> still armed
        assert _value("paddle_resilience_fault_sites_armed") == 1
    finally:
        # drop the env plan again so later tests see an inactive plane
        monkeypatch.delenv(faults.ENV_VAR)
        faults._ENV_CHECKED = False
        faults._ENV_PLAN = None
        observe.get_metric("paddle_resilience_fault_sites_armed").set(0)


# ------------------------------------------------------------ rpc backoff
def test_rpc_get_var_jitter_clamps_to_remaining_deadline(monkeypatch):
    """Base backoff FAR above the deadline: the sleep must clamp to the
    remaining deadline (checked BEFORE sleeping), so the call returns in
    deadline-scale time, never base-backoff-scale (30s vs 0.4s budget —
    the generous-ratio assertion bounds it at 15s)."""
    from paddle_tpu.distributed.rpc import RPCClient, RPCError, RPCServer

    monkeypatch.setenv("PADDLE_TPU_RPC_DEADLINE_MS", "400")
    monkeypatch.setenv("PADDLE_TPU_RPC_RETRY_BASE_MS", "30000")
    monkeypatch.setenv("PADDLE_TPU_RPC_RETRY_CAP_MS", "60000")
    srv = RPCServer(port=0, num_trainers=1, sync=False)
    srv.start()
    cli = RPCClient("127.0.0.1:%d" % srv.port, trainer_id=0)
    cli.connect()
    t0 = time.monotonic()
    with pytest.raises(RPCError):
        cli.get_var("never_pushed")
    elapsed = time.monotonic() - t0
    cli.close()
    srv.close()
    assert elapsed < 15.0, (
        "get_var slept a full unclamped backoff instead of the "
        "remaining deadline: %.1fs" % elapsed)


def test_rpc_get_var_never_sleeps_after_final_attempt(monkeypatch):
    """retries=1 exhausts the count on the first miss: no retry can
    follow, so no backoff sleep may precede the raise (base 30s vs the
    sub-second native call — a generous-ratio bound of 10s)."""
    from paddle_tpu.distributed.rpc import RPCClient, RPCError, RPCServer

    monkeypatch.setenv("PADDLE_TPU_RPC_RETRY_BASE_MS", "30000")
    monkeypatch.setenv("PADDLE_TPU_RPC_RETRY_CAP_MS", "60000")
    srv = RPCServer(port=0, num_trainers=1, sync=False)
    srv.start()
    cli = RPCClient("127.0.0.1:%d" % srv.port, trainer_id=0)
    cli.connect()
    r0 = _value("paddle_rpc_client_retries_total", method="get_var")
    t0 = time.monotonic()
    with pytest.raises(RPCError):
        cli.get_var("never_pushed", retries=1)
    elapsed = time.monotonic() - t0
    cli.close()
    srv.close()
    assert elapsed < 10.0, "slept after the final (only) attempt"
    assert _value("paddle_rpc_client_retries_total",
                  method="get_var") == r0  # zero retries happened


# --------------------------------------------------------------- manifest
def test_manifest_write_read_atomic(tmp_path):
    d = str(tmp_path / "ck")
    assert read_manifest(d) is None
    man = {"version": 1, "latest": "step_00000002", "step": 2, "epoch": 0,
           "batch_in_epoch": 2, "completed": False, "var_names": ["w"],
           "retained": ["step_00000002"]}
    write_manifest(d, man)
    assert read_manifest(d) == man
    # no staging litter
    assert [p for p in os.listdir(d) if ".tmp" in p] == []


# ------------------------------------------------------------- supervisor
def test_supervisor_trains_checkpoints_and_prunes(tmp_path):
    main, startup, loss = _build()
    scope = Scope()
    d = str(tmp_path / "ck")
    seen = []
    with scope_guard(scope):
        r = resilient_train_loop(
            main, lambda: iter(_batches(6)), [loss], scope=scope,
            checkpoint_dir=d, startup_program=startup, checkpoint_every=2,
            keep_last=2, max_restarts=0,
            on_step=lambda s, v: seen.append(s))
    assert r.steps == 6 and r.restarts == 0
    assert seen == [1, 2, 3, 4, 5, 6]
    assert np.isfinite(r.last[0]).all()
    man = read_manifest(d)
    assert man["completed"] and man["step"] == 6 and man["epoch"] == 1
    # retain-last-K pruned everything older
    dirs = sorted(p for p in os.listdir(d) if p.startswith("step_"))
    assert dirs == sorted(man["retained"]) and len(dirs) <= 2
    assert man["latest"] == "step_00000006"


def test_supervisor_resumes_completed_run_without_training(tmp_path):
    main, startup, loss = _build()
    scope = Scope()
    d = str(tmp_path / "ck")
    with scope_guard(scope):
        r1 = resilient_train_loop(
            main, lambda: iter(_batches(4)), [loss], scope=scope,
            checkpoint_dir=d, startup_program=startup, checkpoint_every=2,
            max_restarts=0)
        p_done = _params(scope, main)
        # second call (fresh scope, as a restarted process would have)
        scope2 = Scope()
        with scope_guard(scope2):
            steps = []
            r2 = resilient_train_loop(
                main, lambda: iter(_batches(4)), [loss], scope=scope2,
                checkpoint_dir=d, startup_program=startup,
                checkpoint_every=2, max_restarts=0,
                on_step=lambda s, v: steps.append(s))
            assert r2.resumed_from == r1.steps == 4
            assert steps == []  # completed run: nothing replays
            for a, b in zip(p_done, _params(scope2, main)):
                assert np.array_equal(a, b)


def test_supervisor_recovers_via_restart_before_first_checkpoint(tmp_path):
    rec0 = _value("paddle_resilience_recoveries_total", kind="restart")
    main, startup, loss = _build()
    scope = Scope()
    with scope_guard(scope):
        # startup is occurrence 1; fault the FIRST train step — no
        # checkpoint exists yet, so recovery re-runs startup
        with FaultPlan().arm("executor.dispatch", steps=(2,)):
            r = resilient_train_loop(
                main, lambda: iter(_batches(4)), [loss], scope=scope,
                checkpoint_dir=str(tmp_path / "ck"),
                startup_program=startup, checkpoint_every=2,
                max_restarts=1, backoff_base_s=0.001, backoff_cap_s=0.01)
    assert r.steps == 4 and r.restarts == 1
    assert _value("paddle_resilience_recoveries_total",
                  kind="restart") == rec0 + 1


def test_resume_false_recovery_restarts_instead_of_stale_resume(tmp_path):
    """resume=False must hold through RECOVERY: a fault before this
    run's first own checkpoint restarts from startup, never resuming a
    PREVIOUS run's manifest left in the same directory."""
    d = str(tmp_path / "ck")
    main, startup, loss = _build()
    s1 = Scope()
    with scope_guard(s1):
        resilient_train_loop(main, lambda: iter(_batches(4)), [loss],
                             scope=s1, checkpoint_dir=d,
                             startup_program=startup, checkpoint_every=2,
                             max_restarts=0)
    stale_step = read_manifest(d)["step"]
    assert stale_step == 4

    rr0 = _value("paddle_resilience_recoveries_total", kind="restart")
    rs0 = _value("paddle_resilience_recoveries_total", kind="resume")
    main2, startup2, loss2 = _build()
    s2 = Scope()
    with scope_guard(s2):
        # fault the FIRST step (occurrence 2 after startup) — before any
        # checkpoint of THIS run exists
        with FaultPlan().arm("executor.dispatch", steps=(2,)):
            r = resilient_train_loop(
                main2, lambda: iter(_batches(6, seed=5)), [loss2],
                scope=s2, checkpoint_dir=d, startup_program=startup2,
                checkpoint_every=3, max_restarts=1, resume=False,
                backoff_base_s=0.001, backoff_cap_s=0.01)
    assert r.resumed_from is None and r.steps == 6
    assert _value("paddle_resilience_recoveries_total",
                  kind="restart") == rr0 + 1
    assert _value("paddle_resilience_recoveries_total",
                  kind="resume") == rs0
    # the directory now belongs to the new run
    assert read_manifest(d)["step"] == 6


def test_on_step_at_least_once_across_recovery(tmp_path):
    """Every step must reach on_step at least once even when a fault
    drops in-flight handles: handles pending at a checkpoint boundary
    are drained BEFORE the manifest finalizes, so recovery never
    resumes past an un-notified step."""
    main, startup, loss = _build()
    scope = Scope()
    seen = []
    with scope_guard(scope):
        # fault the dispatch right after the step-4 checkpoint
        # (occurrences: 1=startup, 2..=steps; 6 = step 5)
        with FaultPlan().arm("executor.dispatch", steps=(6,)):
            r = resilient_train_loop(
                main, lambda: iter(_batches(8)), [loss], scope=scope,
                checkpoint_dir=str(tmp_path / "ck"),
                startup_program=startup, checkpoint_every=4,
                max_in_flight=2, max_restarts=1,
                backoff_base_s=0.001, backoff_cap_s=0.01,
                on_step=lambda s, v: seen.append(s))
    assert r.steps == 8 and r.restarts == 1
    # at-least-once: every step notified; replays allowed, gaps not
    assert sorted(set(seen)) == list(range(1, 9)), seen


def test_supervisor_exhausted_restarts_reraises(tmp_path):
    main, startup, loss = _build()
    scope = Scope()
    with scope_guard(scope):
        with FaultPlan().arm("executor.dispatch", every=True):
            with pytest.raises(InjectedFault):
                resilient_train_loop(
                    main, lambda: iter(_batches(4)), [loss], scope=scope,
                    checkpoint_dir=str(tmp_path / "ck"),
                    startup_program=startup, max_restarts=2,
                    backoff_base_s=0.001, backoff_cap_s=0.01)


def test_fault_during_recovery_consumes_restart_budget(tmp_path):
    """A retryable fault raised DURING recovery (here: the startup
    re-dispatch) must consume the restart budget like any other, not
    escape after one restart with budget unused."""
    i0 = _value("paddle_resilience_faults_injected_total",
                site="executor.dispatch", mode="raise")
    main, startup, loss = _build()
    scope = Scope()
    with scope_guard(scope):
        # occurrence 1 = entry startup (passes); 2+ = every later
        # dispatch, INCLUDING the recovery startup re-runs
        with FaultPlan().arm("executor.dispatch", from_step=2):
            with pytest.raises(InjectedFault):
                resilient_train_loop(
                    main, lambda: iter(_batches(4)), [loss], scope=scope,
                    checkpoint_dir=str(tmp_path / "ck"),
                    startup_program=startup, max_restarts=2,
                    backoff_base_s=0.001, backoff_cap_s=0.01)
    # first train step + one faulting recovery per budgeted restart:
    # 1 + max_restarts injections, proof each recovery failure was
    # caught and counted rather than escaping on the first
    assert _value("paddle_resilience_faults_injected_total",
                  site="executor.dispatch", mode="raise") == i0 + 3


def test_write_manifest_cleans_dead_pid_staging(tmp_path):
    d = str(tmp_path / "ck")
    os.makedirs(d)
    path = os.path.join(d, "manifest.json")
    # a dead writer's staging file (real, reaped pid)
    proc = subprocess.Popen([sys.executable, "-c", "pass"])
    proc.wait()
    orphan = "%s.tmp.%d" % (path, proc.pid)
    open(orphan, "w").write("{}")
    # a live writer's staging file must survive (pid 1 is always alive;
    # our own pid can't stand in for it — that IS write_manifest's own
    # staging name, consumed by its rename)
    live = "%s.tmp.1" % path
    open(live, "w").write("{}")
    write_manifest(d, {"version": 1, "latest": "step_00000001",
                       "step": 1, "epoch": 0, "batch_in_epoch": 1,
                       "completed": False, "var_names": [],
                       "retained": ["step_00000001"]})
    left = sorted(p for p in os.listdir(d) if ".tmp." in p)
    assert left == [os.path.basename(live)], left
    assert read_manifest(d)["step"] == 1


def test_supervisor_rejects_non_callable_reader(tmp_path):
    main, startup, loss = _build()
    with pytest.raises(TypeError, match="zero-arg callable"):
        resilient_train_loop(main, iter(_batches(2)), [loss],
                             checkpoint_dir=str(tmp_path / "ck"))


def test_save_persistables_async_extra_vars_roundtrip(tmp_path):
    """The RNG chain rides the checkpoint via extra_vars; names absent
    from the scope are skipped, not errors."""
    main, startup, loss = _build(dropout=True)
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        exe.run(main, feed=_batches(1)[0], fetch_list=[loss], scope=scope)
        assert scope.find_var(RNG_VAR) is not None
        io.save_persistables_async(
            exe, str(tmp_path / "ck"), main, scope=scope,
            extra_vars=(RNG_VAR, "no_such_var")).wait()
    from paddle_tpu.native.tensor_store import load_tensors

    data = load_tensors(str(tmp_path / "ck" / "__model_combined__"))
    assert RNG_VAR in data
    assert np.array_equal(data[RNG_VAR],
                          np.asarray(scope.find_var(RNG_VAR)))
    assert "no_such_var" not in data


# --------------------------------------------- crash mid-checkpoint write
def test_crash_between_tmp_write_and_rename_keeps_previous(tmp_path):
    """ISSUE 4 satellite: SIGKILL the writer in the exact window between
    the staged tmp write and the atomic rename. The previous checkpoint
    must stay loadable, and the orphaned tmp must be cleaned by the NEXT
    save_persistables_async to that path."""
    target = str(tmp_path / "ck")
    code = (
        "import os, numpy as np\n"
        "import paddle_tpu  # noqa: F401 — arms the env fault plan\n"
        "from paddle_tpu.native import tensor_store as ts\n"
        "ts.save_tensors(%r, {'w': np.arange(4, dtype='float32')})\n"
        "ts.save_tensors(%r, {'w': np.zeros(4, dtype='float32')})\n"
        "raise SystemExit('crash fault did not fire')\n" % (target, target))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_FAULT_PLAN="checkpoint.write@2:crash")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=180)
    assert proc.returncode == -9, (proc.returncode, proc.stderr[-500:])

    from paddle_tpu.native.tensor_store import load_tensors

    # previous checkpoint survived the crash intact
    assert np.array_equal(load_tensors(target)["w"],
                          np.arange(4, dtype="float32"))
    litter = [p for p in os.listdir(tmp_path) if ".tmp." in p]
    assert len(litter) == 1, litter

    # the next save to the same path cleans the dead writer's litter
    o0 = _value("paddle_resilience_checkpoint_orphans_cleaned_total")
    main, startup, loss = _build()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        io.save_persistables_async(exe, str(tmp_path), main, scope=scope,
                                   filename="ck").wait()
    assert [p for p in os.listdir(tmp_path) if ".tmp." in p] == []
    assert _value(
        "paddle_resilience_checkpoint_orphans_cleaned_total") == o0 + 1
    # and the new checkpoint is the live writer's, fully loadable
    data = load_tensors(target)
    assert "w" not in data and len(data) > 0


def test_orphan_cleanup_spares_live_writers(tmp_path):
    """A tmp staged by a LIVE pid (concurrent writer in another process)
    must never be collected."""
    from paddle_tpu.native.tensor_store import save_tensors

    target = str(tmp_path / "ck")
    live = "%s.tmp.%d.999" % (target, os.getpid())
    open(live, "w").write("staged-by-a-live-writer")
    save_tensors(target, {"w": np.ones(2, dtype="float32")})
    assert os.path.exists(live)


# --------------------------------------------------- the slow chaos proof
@pytest.mark.slow
def test_chaos_wedge_and_crash_resume_bitwise_identical(tmp_path):
    """ISSUE 4 acceptance: a seeded FaultPlan injects a WEDGE (caught by
    the watchdog within its deadline — 0.8s stall vs 0.2s deadline, a 4x
    calibrated ratio, asserted via the recorded event and counters, no
    ms timing) and a mid-run CRASH into resilient_train_loop; the
    supervisor resumes from the manifest both times and the final params
    are BITWISE identical to the fault-free run, with injected/recovered
    counts visible in paddle_resilience_* telemetry. Dropout in the
    model makes the equality cover the checkpointed RNG chain, not just
    params."""
    steps, every = 12, 4
    batches = _batches(steps, seed=1)
    reader = lambda: iter(batches)  # noqa: E731

    # ---- fault-free baseline
    main, startup, loss = _build(dropout=True)
    s1 = Scope()
    with scope_guard(s1):
        r1 = resilient_train_loop(
            main, reader, [loss], scope=s1,
            checkpoint_dir=str(tmp_path / "a"), startup_program=startup,
            checkpoint_every=every, max_restarts=0)
        p0 = _params(s1, main)
    assert r1.steps == steps and r1.restarts == 0

    # ---- chaos run: same model built fresh, same seeds
    main2, startup2, loss2 = _build(dropout=True)
    s2 = Scope()
    d = str(tmp_path / "b")
    i0 = _value("paddle_resilience_faults_injected_total",
                site="executor.dispatch", mode="wedge")
    r0 = _value("paddle_resilience_recoveries_total", kind="resume")
    wedges = []
    # occurrence map: startup=1, train step k = k+1. Occurrence 7 (step
    # 6, past the step-4 checkpoint) wedges 0.8s then raises; after the
    # resume replays steps 5+, occurrence 11 raises again mid-run.
    plan = FaultPlan.parse(
        "executor.dispatch@7:wedge=0.8;executor.dispatch@11:raise")
    with scope_guard(s2), plan:
        r2 = resilient_train_loop(
            main2, reader, [loss2], scope=s2, checkpoint_dir=d,
            startup_program=startup2, checkpoint_every=every,
            max_restarts=3, watchdog_deadline_s=0.2,
            on_wedge=wedges.append, backoff_base_s=0.01,
            backoff_cap_s=0.05, backoff_seed=0)
        p1 = _params(s2, main2)

    # the wedge was caught by the watchdog while the dispatch stalled
    assert wedges and wedges[0].site == "executor.dispatch"
    assert r2.wedges == len(wedges)
    # both injected faults recovered via manifest resume
    assert r2.steps == steps and r2.restarts == 2
    assert _value("paddle_resilience_faults_injected_total",
                  site="executor.dispatch", mode="wedge") == i0 + 1
    assert _value("paddle_resilience_recoveries_total",
                  kind="resume") == r0 + 2
    man = read_manifest(d)
    assert man["completed"] and man["step"] == steps
    assert RNG_VAR in man["var_names"]

    # the headline: bitwise identity with the uninterrupted run
    assert len(p0) == len(p1)
    for a, b in zip(p0, p1):
        assert a.dtype == b.dtype and np.array_equal(a, b)
