"""The ten readers of set-up (ISSUE 34), each against a hand-made record:
a function traced inside another's trace is counted once, a span that
ended after the window opened is not set-up, a ring that dropped events
closes no account (``None``), and a program from before the sites
existed has nothing to read (``None``, never a raise)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import setup_spans  # noqa: E402
from benchmarks.lib.manifest import Manifest  # noqa: E402

MANIFEST = Manifest()
_ids = iter(range(1, 10 ** 6))
OPENED = 160.0      # the window opens here; set-up began 60 s before
STAGE_METRICS = ("setup_trace_s", "setup_lower_s", "setup_cache_load_s",
                 "setup_xla_compile_s", "program_loads_n",
                 "program_reloads_n", "setup_reload_s")
ALL = STAGE_METRICS + ("setup_prepare_s", "setup_engine_s",
                       "setup_unnamed_s")


def span(name, start, end, parent=None, **attrs):
    """One finished span as the flight recorder's ``E`` event."""
    return {"t": end, "ph": "E", "site": name, "trace": "t",
            "span": next(_ids), "parent": parent and parent["span"],
            "tid": 1, "dur": end - start, "attrs": attrs or None}


def reader(name):
    return MANIFEST.load_module("layer_metrics", name)


def setup_record():
    """A serving process: the engine builds and places (101-104), a call
    (104.5-147) prepares a plan and dispatches it twice — the second
    dispatch loads the program AGAIN from the cache — and another plan
    compiles. After the window opened (160) more of everything, which is
    not set-up."""
    build = span("serving.engine.build", 101.0, 103.0, program="decode")
    placed = span("serving.engine.load_params", 103.0, 104.0, arrays=9)
    call = span("executor.call", 104.5, 147.0, site="run", steps=1)
    prepare = span("executor.prepare", 105.0, 108.0, call, plan="aa")
    first = span("executor.dispatch", 110.0, 130.0, call, plan="aa")
    again = span("executor.dispatch", 131.0, 140.0, call, plan="aa",
                 nth=2, uncommitted=7, resharded=0)
    other = span("executor.dispatch", 141.0, 146.0, call, plan="bb")
    late = span("executor.dispatch", 158.0, 162.0, plan="cc")
    return {
        "program_window": (OPENED, OPENED + 45.0),
        "program_spans_dropped": False,
        "setup_s": 60.0,
        "program_spans": [
            build, placed, call, prepare, first, again, other, late,
            # the step traced inside the K-step scan's trace: 8 s, not 11
            span("executor.load.trace", 110.0, 118.0, first, fun="multi",
                 plan="aa"),
            span("executor.load.trace", 111.0, 114.0, first, fun="step",
                 plan="aa"),
            span("executor.load.lower", 118.0, 120.0, first, plan="aa"),
            span("executor.load.backend", 120.0, 130.0, first, plan="aa",
                 cache="hit", nth=1),
            span("executor.load.lower", 131.0, 132.0, again, plan="aa"),
            span("executor.load.backend", 132.0, 140.0, again, plan="aa",
                 cache="hit", nth=2),
            span("executor.load.trace", 141.0, 142.0, other, plan="bb"),
            span("executor.load.backend", 142.0, 146.0, other, plan="bb",
                 cache="miss", nth=1),
            # begun in set-up, ended in the window: the window's
            span("executor.load.backend", 159.0, 161.0, late, plan="cc",
                 cache="off", nth=1),
            span("executor.load.trace", 170.0, 175.0, fun="f"),
            span("executor.prepare", 170.0, 171.0, plan="dd"),
            span("serving.engine.build", 171.0, 172.0, program="prefill"),
        ],
    }


WANT = {
    "setup_trace_s": 9.0,          # 8 (nested once) + 1
    "setup_lower_s": 3.0,
    "setup_cache_load_s": 18.0,    # the two hits
    "setup_xla_compile_s": 4.0,    # the miss
    "program_loads_n": 3,
    "program_reloads_n": 1,
    "setup_reload_s": 9.0,         # lower + backend of the second load
    "setup_prepare_s": 3.0,
    "setup_engine_s": 3.0,
    # 60 less [101, 104] and [104.5, 147]
    "setup_unnamed_s": 60.0 - 3.0 - 42.5,
}


@pytest.mark.parametrize("name", ALL)
def test_reader_on_a_hand_made_set_up(name):
    assert reader(name).read(setup_record()) == pytest.approx(WANT[name])


def test_the_account_closes():
    record = setup_record()
    spans = setup_spans.ended(record)
    assert all(ev["t"] <= OPENED for ev in spans)
    named = setup_spans.union_s(spans, OPENED - record["setup_s"])
    assert named + reader("setup_unnamed_s").read(record) \
        == pytest.approx(record["setup_s"])
    stages = sum(reader(n).read(record) for n in STAGE_METRICS[:4])
    # what jax.monitoring would sum from outside counts the nested trace
    # twice: the four stage metrics lie below it
    assert stages == pytest.approx(34.0) and stages < 34.0 + 3.0


@pytest.mark.parametrize("name", ALL)
def test_a_ring_that_dropped_events_reads_none(name):
    record = dict(setup_record(), program_spans_dropped=True)
    assert reader(name).read(record) is None


@pytest.mark.parametrize("name", ALL)
def test_no_window_reads_none(name):
    record = setup_record()
    del record["program_window"]     # a rehearsal: no reduced trace
    assert reader(name).read(record) is None
    record["trace"] = None
    assert reader(name).read(record) is None


@pytest.mark.parametrize("name", STAGE_METRICS + ("setup_prepare_s",
                                                  "setup_engine_s"))
def test_a_program_without_the_sites_reads_none(name):
    """The parent commit's program: calls and dispatches, none of the
    five sites this PR adds."""
    record = setup_record()
    record["program_spans"] = [
        ev for ev in record["program_spans"]
        if ev["site"] in ("executor.call", "executor.dispatch")]
    assert reader(name).read(record) is None
    # what no span covers can still be told, and is larger there
    assert reader("setup_unnamed_s").read(record) == pytest.approx(17.5)


def test_a_warm_run_compiles_nothing():
    record = setup_record()
    for ev in record["program_spans"]:
        if (ev["attrs"] or {}).get("cache") == "miss":
            ev["attrs"]["cache"] = "hit"
    assert reader("setup_xla_compile_s").read(record) == 0.0
    assert reader("setup_cache_load_s").read(record) == pytest.approx(22.0)
    for ev in record["program_spans"]:
        if ev["site"] == "executor.load.backend":
            ev["attrs"]["nth"] = 1
    assert reader("program_reloads_n").read(record) == 0
    assert reader("setup_reload_s").read(record) == 0.0


def test_set_up_is_cut_at_its_own_beginning():
    """A span from before the process's ``setup_s`` began (another run's,
    in a ring that outlives it) takes nothing off the unnamed seconds."""
    record = setup_record()
    record["program_spans"].append(span("executor.call", 50.0, 99.0))
    assert reader("setup_unnamed_s").read(record) == pytest.approx(14.5)


def test_readers_take_the_ring_itself():
    """No ``program_spans`` in the record: the readers snapshot the
    program's ring, once, and say ``None`` when it has turned over."""
    import time

    import jax
    import numpy as np

    from paddle_tpu import observe
    from paddle_tpu.observe import trace

    observe.reset()
    trace.watch_program_loads()
    try:
        jax.jit(lambda a: a * 7 - 2)(np.arange(11.0))
        opened = time.perf_counter()
        jax.jit(lambda a: a * 5 - 1)(np.arange(13.0))   # the window's
        record = {"program_window": (opened, opened + 1.0), "setup_s": 5.0}
        assert reader("program_loads_n").read(record) == 1
        assert "program_spans" in record
        assert reader("setup_xla_compile_s").read(record) > 0
        assert reader("setup_cache_load_s").read(record) == 0.0
        assert reader("program_reloads_n").read(record) == 0
        assert 0 < reader("setup_unnamed_s").read(record) < 5.0
        ring = trace.recorder()
        capacity = ring.capacity
        ring.resize(16)
        for _ in range(20):
            trace.trace_event("executor.call")
        fresh = {"program_window": (opened, opened + 1.0), "setup_s": 5.0}
        assert reader("program_loads_n").read(fresh) is None
        assert reader("setup_unnamed_s").read(fresh) is None
        ring.resize(capacity)
    finally:
        observe.reset()
