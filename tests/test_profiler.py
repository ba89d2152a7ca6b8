"""Profiler tests: the Fluid session API (reference test_profiler.py
analog) over the program's one span type — a session's table and its
chrome trace are read from the flight recorder's ring."""

import json
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observe, profiler
from paddle_tpu.observe import trace


@pytest.fixture(autouse=True)
def _fresh_ring():
    observe.reset()
    yield
    observe.reset()


def _table(out):
    """{event: calls} of a printed report."""
    rows = {}
    for line in out.splitlines():
        parts = line.split()
        if len(parts) == 6 and parts[1].isdigit():
            rows[parts[0]] = int(parts[1])
    return rows


def test_record_event_table_and_chrome_trace(tmp_path, capsys):
    path = str(tmp_path / "trace.json")
    with profiler.RecordEvent("before_the_session"):
        pass
    profiler.start_profiler(state="CPU")
    for _ in range(3):
        with profiler.RecordEvent("my_block"):
            np.dot(np.ones((64, 64)), np.ones((64, 64)))
    profiler.stop_profiler(sorted_key="total", profile_path=path)

    out = capsys.readouterr().out
    assert "Profiling Report" in out
    # the table counts what the session saw, the export writes the ring
    assert _table(out) == {"my_block": 3}

    evs = json.load(open(path))["traceEvents"]
    mine = [e for e in evs if e["name"] == "my_block"]
    assert len(mine) == 3
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in mine)
    assert any(e["name"] == "before_the_session" for e in evs)


@pytest.mark.parametrize("steps", [1, 3], ids=["run", "run_repeated"])
def test_session_table_holds_the_executors_spans(steps, capsys,
                                                 fresh_programs):
    main, startup, scope = fresh_programs
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.fc(x, size=2)
    exe = fluid.Executor()
    exe.run(startup, scope=scope)
    X = np.ones((3, 4), np.float32)
    with profiler.profiler(state="CPU", sorted_key="calls"):
        for _ in range(4):
            exe.run_repeated(main, feed={"x": X}, fetch_list=[y.name],
                             scope=scope, steps=steps)
        with profiler.RecordEvent("user_block"):
            pass
    rows = _table(capsys.readouterr().out)
    # the startup run came before the session and is not counted
    for site in ("executor.call", "executor.gather", "executor.dispatch",
                 "executor.complete", "executor.write_back"):
        assert rows[site] == 4, (site, rows)
    assert rows["user_block"] == 1
    assert not any(name.startswith("executor_run") for name in rows)


def test_session_with_ring_off_says_so(capsys):
    prior = trace.set_trace_enabled(False)
    try:
        with profiler.profiler(state="CPU"):
            with profiler.RecordEvent("unseen"):
                pass
    finally:
        trace.set_trace_enabled(prior)
    out = capsys.readouterr().out
    assert "Profiling Report" in out
    assert trace.ENV_TRACE + "=0" in out
    assert "unseen" not in out and "Calls" not in out


def test_session_state_and_reset(capsys):
    assert not profiler.is_profiler_enabled()
    profiler.stop_profiler()              # no session: nothing printed
    assert capsys.readouterr().out == ""
    profiler.start_profiler(state="CPU")
    assert profiler.is_profiler_enabled()
    with profiler.RecordEvent("dropped_by_reset"):
        pass
    profiler.reset_profiler()
    with profiler.record_event("kept"):
        pass
    profiler.stop_profiler()
    assert not profiler.is_profiler_enabled()
    assert _table(capsys.readouterr().out) == {"kept": 1}


@pytest.mark.parametrize("key,first", [
    ("calls", "often"), ("total", "long"), ("ave", "long"),
    ("min", "often"), ("max", "long")])
def test_report_is_sorted_by_the_fluid_key(key, first, capsys):
    """``sorted_key`` as in Fluid's ``stop_profiler``: most calls, largest
    total / average / maximum, smallest minimum first. The spans are
    retroactive, so their durations are exact."""
    profiler.start_profiler(state="CPU")
    t0 = time.perf_counter()
    # user-chosen names, as RecordEvent's are (not declared span sites)
    for name, start, dur in [("often", t0 + i * 1e-3, 1e-4)
                             for i in range(3)] + [("long", t0, 5e-2)]:
        trace.record_span(name, start, dur)
    profiler.stop_profiler(sorted_key=key)
    rows = list(_table(capsys.readouterr().out))
    assert rows == [first, "long" if first == "often" else "often"]
