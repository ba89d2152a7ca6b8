"""Set-up named from inside the program (ISSUE 34).

* ONE ``jax.monitoring`` listener (``observe/trace.py`` "Program loads")
  records a retroactive span for every stage JAX runs to make a program
  executable — ``executor.load.trace`` / ``.lower`` / ``.backend`` — as
  children of the ``executor.dispatch`` that caused it, tagged with the
  plan; a steady dispatch has none;
* a plan that loads its program AGAIN reads ``nth`` 2, the dispatch span
  says what differed (``uncommitted``, ``resharded``), and the dispatch is
  a compile-time sample, not a run-time one (since ISSUE 46 a fresh
  process's second step is no such load — ``tests/test_load_once.py`` —
  so the tests here change the state under a live plan to see one);
* ``executor.prepare`` spans the plan-cache miss path of ``Executor``,
  ``seed_plan`` and ``ParallelEngine``, which counts its miss;
* the serving engine's own set-up is ``serving.engine.build`` and
  ``serving.engine.load_params``;
* the two cumulative counters say what the spans say;
* ``PADDLE_TPU_TRACE=0`` records nothing and registers nothing.
"""

import io
import os
import sys

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import observe
from paddle_tpu.core.scope import Scope, scope_guard
from paddle_tpu.observe import families, trace
from paddle_tpu.serving import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CFG = dict(d_model=32, d_ff=64, n_head=2, n_layer=2, vocab=64,
           max_length=32, dropout=0.0)
STAGES = ("executor.load.trace", "executor.load.lower",
          "executor.load.backend")
FEED = {"x": np.ones((8, 4), "float32")}


@pytest.fixture(autouse=True)
def _fresh_ring(monkeypatch):
    observe.reset()
    # a two-layer MLP traces in about a millisecond: keep every trace
    monkeypatch.setattr(trace, "_TRACE_FLOOR_S", 0.0)
    yield
    observe.reset()


def _ended(site=None):
    return [e for e in trace.recorder().events() if e["ph"] == "E"
            and (site is None or e["site"] == site)]


def _loads():
    return [e for e in _ended() if e["site"] in STAGES]


def _mlp(commit=False):
    """A fresh two-layer train program, its startup run; with ``commit``
    the startup's (uncommitted) arrays are committed to their device, as
    a step's outputs are."""
    main, startup = fluid.Program(), fluid.Program()
    scope = Scope()
    with scope_guard(scope):
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", [4], dtype="float32")
            loss = fluid.layers.mean(fluid.layers.fc(x, 2))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
    if commit:
        dev = jax.devices()[0]
        for name in scope.local_var_names():
            v = scope.find_var(name)
            if isinstance(v, jax.Array):
                scope.set_var(name, jax.device_put(v, dev))
    observe.reset()   # the startup run's spans are not the test's
    return exe, main, scope, loss


def _loosen(main, scope):
    """Swap the program's parameters in the scope for loose copies, as a
    caller who restores them from its own arrays between two steps does:
    the next dispatch is another argument signature."""
    for p in main.global_block().all_parameters():
        scope.set_var(p.name, jax.device_put(
            np.asarray(scope.find_var(p.name))))


def _run_thrice(exe, main, scope, loss):
    """Three steps, the parameters swapped for loose ones after the
    first: the second loads the program again, the third is steady."""
    _run(exe, main, scope, loss)
    _loosen(main, scope)
    _run(exe, main, scope, loss)
    _run(exe, main, scope, loss)


def _run(exe, main, scope, loss, **kw):
    with scope_guard(scope):
        if kw:
            return exe.run_repeated(main, feed=FEED, fetch_list=[loss],
                                    scope=scope, **kw)
        return exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)


# ------------------------------------------------------ the stage spans
@pytest.mark.parametrize("kw", [{}, {"steps": 3}], ids=["run", "repeated"])
def test_first_dispatch_holds_trace_lower_backend(kw):
    exe, main, scope, loss = _mlp(commit=True)
    _run(exe, main, scope, loss, **kw)
    (dispatch,) = _ended("executor.dispatch")
    plan = dispatch["attrs"]["plan"]
    mine = [e for e in _loads() if e["parent"] == dispatch["span"]]
    # in the order JAX ran them, each inside the dispatch span
    assert [e["site"] for e in mine if e["dur"] > 0][-2:] == \
        list(STAGES[1:])
    assert STAGES[0] in [e["site"] for e in mine]
    for e in mine:
        assert e["attrs"]["plan"] == plan and e["attrs"]["fun"]
        assert dispatch["t"] - dispatch["dur"] - 1e-3 \
            <= e["t"] - e["dur"] <= e["t"] <= dispatch["t"] + 1e-3
    (backend,) = [e for e in mine if e["site"] == STAGES[2]]
    assert backend["attrs"]["nth"] == 1
    assert backend["attrs"]["cache"] == "off"   # tests enable no cache
    assert "nth" not in dispatch["attrs"]


@pytest.mark.parametrize("kw", [{}, {"steps": 3}], ids=["run", "repeated"])
def test_steady_dispatch_records_no_load(kw):
    exe, main, scope, loss = _mlp(commit=True)
    _run(exe, main, scope, loss, **kw)
    before = len(_loads())
    assert before >= 3
    prepared = len(_ended("executor.prepare"))
    for _ in range(3):
        _run(exe, main, scope, loss, **kw)
    assert len(_loads()) == before
    assert len(_ended("executor.prepare")) == prepared == 1
    assert len(_ended("executor.dispatch")) == 4


def test_second_load_says_nth_2_and_why():
    """The first step hands back committed parameters, the caller puts
    loose ones in their place: the same plan, the same signature, and
    JAX loads the program again (PERF.md, set-up)."""
    exe, main, scope, loss = _mlp()
    compile_h = families.EXECUTOR_COMPILE_SECONDS.labels()
    run_h = families.EXECUTOR_RUN_SECONDS.labels(site="run",
                                                phase="dispatch")
    _run_thrice(exe, main, scope, loss)
    first, second, third = _ended("executor.dispatch")
    assert first["attrs"]["plan"] == second["attrs"]["plan"]
    nth = {e["parent"]: e["attrs"]["nth"] for e in _ended(STAGES[2])}
    assert nth == {first["span"]: 1, second["span"]: 2}
    assert second["attrs"]["nth"] == 2
    # the two parameters the step wrote had come back committed
    assert second["attrs"]["uncommitted"] == 2
    assert second["attrs"]["resharded"] == 0
    assert "uncommitted" not in first["attrs"]
    assert "nth" not in third["attrs"]
    # both loading dispatches are compile-time samples, whichever time
    # round; only the third is a steady one
    assert compile_h.count == 2 and run_h.count == 1
    plan = list(exe._cache.values())[-1]   # the startup's plan is first
    assert plan.loads == {"run": 2} and plan.compiled_sigs == {"run"}


def test_a_load_outside_any_dispatch_has_no_plan():
    jax.jit(lambda a: a * 3 + 1)(np.arange(7.0))
    (backend,) = _ended(STAGES[2])
    assert "plan" not in backend["attrs"] and "nth" not in backend["attrs"]
    assert backend["parent"] is None
    assert families.PROGRAM_LOADS.labels(cache="off", again="0").value == 1


# ------------------------------------------------------------- prepare
def test_executor_prepare_spans_the_miss_path():
    exe, main, scope, loss = _mlp(commit=True)
    _run(exe, main, scope, loss)
    (prepare,) = _ended("executor.prepare")
    (gather,) = _ended("executor.gather")
    (dispatch,) = _ended("executor.dispatch")
    assert prepare["parent"] == gather["span"]
    assert prepare["attrs"]["plan"] == dispatch["attrs"]["plan"]
    assert prepare["attrs"]["ops_in"] == len(main.global_block().ops)
    assert 0 < prepare["attrs"]["ops_out"] <= prepare["attrs"]["ops_in"]
    # the pass pipeline runs inside it
    (pipeline,) = _ended("optimizer.pipeline")
    assert pipeline["parent"] == prepare["span"]
    assert families.EXECUTOR_PREPARE_SECONDS.labels().count == 1


def test_seed_plan_is_a_prepare_and_no_miss():
    exe, main, scope, loss = _mlp(commit=True)
    with scope_guard(scope):
        assert exe.seed_plan(main, FEED, [loss], scope=scope)
    (prepare,) = _ended("executor.prepare")
    assert prepare["attrs"]["ops_in"] == len(main.global_block().ops)
    assert families.EXECUTOR_CACHE_MISSES.labels().value == 0
    _run(exe, main, scope, loss)
    assert len(_ended("executor.prepare")) == 1
    assert families.EXECUTOR_CACHE_MISSES.labels().value == 0


@pytest.mark.parametrize("kw", [{}, {"steps": 2}], ids=["run", "repeated"])
def test_parallel_engine_counts_its_miss_and_prepares(kw):
    from paddle_tpu.parallel import ParallelEngine
    from paddle_tpu.parallel.engine import make_mesh

    exe, main, scope, loss = _mlp()
    engine = ParallelEngine(main, loss_name=loss.name,
                            mesh=make_mesh(jax.devices()[:4]))
    call = engine.run_repeated if kw else engine.run
    with scope_guard(scope):
        for _ in range(3):
            call(FEED, [loss], scope, **kw)
    assert families.EXECUTOR_CACHE_MISSES.labels().value == 1
    assert families.EXECUTOR_PREPARE_SECONDS.labels().count == 1
    (prepare,) = _ended("executor.prepare")
    dispatches = _ended("executor.dispatch")
    assert prepare["attrs"]["plan"] == dispatches[0]["attrs"]["plan"]
    assert prepare["attrs"]["ops_in"] == prepare["attrs"]["ops_out"] \
        == len(main.global_block().ops)
    # the first mesh dispatch loads, a compile-time sample; placed
    # arguments sit where the plan wants them from the first call on, so
    # nothing loads again
    loaded = {e["parent"] for e in _ended(STAGES[2])}
    assert loaded == {dispatches[0]["span"]}
    assert families.EXECUTOR_COMPILE_SECONDS.labels().count == 1
    assert len(_loads()) == len([e for e in _loads() if e["parent"]
                                 == dispatches[0]["span"]])


# ------------------------------------------------------------ counters
def test_counters_say_what_the_spans_say():
    exe, main, scope, loss = _mlp()
    _run_thrice(exe, main, scope, loss)
    _run(exe, main, scope, loss, steps=2)
    seconds = families.PROGRAM_LOAD_SECONDS
    for site, stage in zip(STAGES[1:], ("lower", "backend")):
        assert seconds.labels(stage=stage).value == pytest.approx(
            sum(e["dur"] for e in _ended(site)))
    # a trace inside another's trace is a span of its own and counted
    # once: the counter is the union of the trace spans
    from tools.trace_view import _covered

    assert seconds.labels(stage="trace").value == pytest.approx(
        _covered(_ended(STAGES[0])), abs=2e-3)
    assert seconds.labels(stage="trace").value <= sum(
        e["dur"] for e in _ended(STAGES[0])) + 1e-9
    backends = _ended(STAGES[2])
    loads = families.PROGRAM_LOADS
    assert loads.labels(cache="off", again="1").value == \
        sum(1 for e in backends if e["attrs"].get("nth", 1) >= 2) >= 1
    assert sum(loads.labels(cache=c, again=a).value
               for c in ("hit", "miss", "off") for a in "01") \
        == len(backends)


def test_nested_traces_are_spans_inside_the_outer_span():
    inner = jax.jit(lambda a: a * 2.0)

    def outer(a):
        return inner(a) + inner(a + 1)

    jax.jit(outer)(np.arange(5.0))
    traces = _ended(STAGES[0])
    assert len(traces) >= 2
    whole = max(traces, key=lambda e: e["dur"])
    for e in traces:
        assert whole["t"] - whole["dur"] - 1e-4 <= e["t"] - e["dur"]
        assert e["t"] <= whole["t"] + 1e-4
    assert families.PROGRAM_LOAD_SECONDS.labels(stage="trace").value \
        == pytest.approx(whole["dur"], abs=1e-3)


def test_short_traces_are_left_out(monkeypatch):
    monkeypatch.setattr(trace, "_TRACE_FLOOR_S", 3600.0)
    jax.jit(lambda a: a - 5)(np.arange(3.0))
    assert not _ended(STAGES[0])
    assert len(_ended(STAGES[1])) == len(_ended(STAGES[2])) == 1
    assert families.PROGRAM_LOAD_SECONDS.labels(stage="trace").value == 0


# ------------------------------------------------------------- tracing off
def test_trace_off_records_nothing_and_files_by_the_first_dispatch():
    exe, main, scope, loss = _mlp()
    prior = trace.set_trace_enabled(False)
    try:
        for _ in range(3):
            _run(exe, main, scope, loss)
        assert len(trace.recorder()) == 0
        assert trace.recorder().recorded == 0
        for stage in ("trace", "lower", "backend"):
            assert families.PROGRAM_LOAD_SECONDS.labels(
                stage=stage).value == 0
        # nothing listened: the first dispatch of the signature is taken
        # for the loading one, the second load is not seen
        assert families.EXECUTOR_COMPILE_SECONDS.labels().count == 1
        assert families.EXECUTOR_RUN_SECONDS.labels(
            site="run", phase="dispatch").count == 2
    finally:
        trace.set_trace_enabled(prior)


def test_trace_off_registers_no_listener(monkeypatch):
    from jax._src import monitoring

    monkeypatch.setattr(trace, "_WATCHING", False)
    before = (len(monitoring.get_event_listeners()),
              len(monitoring.get_event_duration_listeners()))
    prior = trace.set_trace_enabled(False)
    try:
        assert trace.watch_program_loads() is False
        fluid.Executor(fluid.TPUPlace())
        assert (len(monitoring.get_event_listeners()),
                len(monitoring.get_event_duration_listeners())) == before
        assert trace.open_loads("abc") is None
    finally:
        trace.set_trace_enabled(prior)
    # on again, the next Executor registers it, once
    try:
        fluid.Executor(fluid.TPUPlace())
        fluid.Executor(fluid.TPUPlace())
        assert trace.watch_program_loads() is True
        assert (len(monitoring.get_event_listeners()),
                len(monitoring.get_event_duration_listeners())) \
            == (before[0] + 1, before[1] + 1)
    finally:
        monitoring.unregister_event_listener(trace._on_jax_event)
        monitoring.unregister_event_duration_listener(
            trace._on_jax_duration)


def test_an_entry_point_is_watched_before_its_first_executor(monkeypatch):
    """``flags.enable_compile_cache`` (every entry point's first call)
    registers the listener: a benchmark's own jitted programs compile
    before any Executor exists."""
    from jax._src import monitoring

    from paddle_tpu import flags

    monkeypatch.setattr(trace, "_WATCHING", False)
    monkeypatch.setattr(jax.config, "update", lambda *a, **k: None)
    before = len(monitoring.get_event_duration_listeners())
    try:
        flags.enable_compile_cache()
        assert trace._WATCHING
        assert len(monitoring.get_event_duration_listeners()) == before + 1
    finally:
        monitoring.unregister_event_listener(trace._on_jax_event)
        monitoring.unregister_event_duration_listener(
            trace._on_jax_duration)


# ------------------------------------------------------ serving set-up
def test_serving_engine_names_its_own_set_up():
    from paddle_tpu.models import gpt

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.build_serving_decode_step(CFG, batch=1, max_len=32)
    shapes = {p.name: tuple(p.shape)
              for p in main.global_block().all_parameters()
              if len(p.shape) == 2 and p.name.startswith("gpt_")}
    rs = np.random.RandomState(0)
    params = {n: rs.uniform(-0.1, 0.1, s).astype("float32")
              for n, s in shapes.items()}
    eng = DecodeEngine(CFG, params=params, b_max=2, max_len=32,
                       queue_capacity=8)
    builds = _ended("serving.engine.build")
    assert [e["attrs"]["program"] for e in builds] == \
        ["decode", "footprint", "footprint"]
    assert all(e["attrs"]["ops"] > 0 for e in builds)
    (placed,) = _ended("serving.engine.load_params")
    assert placed["attrs"]["arrays"] == len(params)
    assert placed["attrs"]["bytes"] == sum(v.nbytes
                                           for v in params.values())
    assert placed["attrs"]["dtype"] == "float32"
    with eng:
        eng.submit(np.arange(1, 6, dtype="int64"), 3).result(timeout=300)
        eng.submit(np.arange(1, 6, dtype="int64"), 3).result(timeout=300)
    prefill = [e for e in _ended("serving.engine.build")
               if e["attrs"]["program"] == "prefill"]
    assert [e["attrs"]["P"] for e in prefill] == [5]   # built once
    # every program the engine dispatched was loaded by a plan
    assert all("plan" in e["attrs"] for e in _ended(STAGES[2])
               if e["attrs"]["fun"] == "jit(step)")


def test_draft_lane_builds_are_named_draft():
    eng = DecodeEngine(CFG, b_max=2, max_len=32, queue_capacity=8,
                       draft_cfg=dict(CFG, n_layer=1), spec_k=2)
    programs = [e["attrs"]["program"]
                for e in _ended("serving.engine.build")]
    assert "decode" in programs and "draft_decode" in programs
    assert len(_ended("serving.engine.load_params")) == 2
    del eng


# ------------------------------------------------------- the dump's table
def test_trace_view_prints_the_program_loads(tmp_path):
    from tools import trace_view

    exe, main, scope, loss = _mlp()
    _run_thrice(exe, main, scope, loss)
    path = trace.dump_flight_recorder(str(tmp_path / "flight.json"))
    dump = trace_view.load_dump(path)
    assert trace_view.validate(dump) == []
    rows = trace_view.program_loads(dump)
    mine = [r for r in rows if r["plan"] != "-"]
    assert [r["nth"] for r in mine] == [1, 2]
    assert mine[0]["plan"] == mine[1]["plan"]
    assert mine[1]["why"] == "uncommitted=2 resharded=0"
    assert mine[0]["why"] == "" and mine[0]["cache"] == "off"
    # the first load committed what the startup program left loose: the
    # two parameters and the learning rate
    assert [r["committed"] for r in mine] == [3, 0]
    assert all(r["backend_s"] > 0 and r["lower_s"] > 0 for r in mine)
    out = io.StringIO()
    trace_view.summarize(dump, out=out)
    assert "program loads" in out.getvalue()
    assert "uncommitted=2" in out.getvalue()
    assert "committed" in out.getvalue().split("why again")[0]


def test_new_sites_are_declared():
    for site in STAGES + ("executor.prepare", "serving.engine.build",
                          "serving.engine.load_params"):
        assert site in families.TRACE_SITES
