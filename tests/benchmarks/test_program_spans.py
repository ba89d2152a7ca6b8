"""The readers of the program's own spans, each against a hand-made
record: phase medians, self time with overlapping children, per-request
token gaps rebuilt from the step spans' riders, the named kernels, and
``None`` (never a raise) where the program records none of it."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import program_spans  # noqa: E402
from benchmarks.lib.manifest import Manifest  # noqa: E402

MANIFEST = Manifest()
_ids = iter(range(1, 10 ** 6))


def span(name, start, dur, parent=None, trace="t", **attrs):
    """One finished span as the flight recorder's ``E`` event."""
    return {"t": start + dur, "ph": "E", "site": name, "trace": trace,
            "span": next(_ids), "parent": parent and parent["span"],
            "tid": 1, "dur": dur, "attrs": attrs or None}


def reader(name):
    return MANIFEST.load_module("layer_metrics", name)


def train_record():
    """Three mesh calls of 1.0 s; the phases of call i last (i + 1) times
    the base. A fourth call lies outside the window."""
    spans = []
    for i, t in enumerate((10.0, 12.0, 14.0, 99.0)):
        k = i + 1
        call = span("executor.call", t, 1.0, site="run_repeated", steps=4)
        gather = span("executor.gather", t, 0.004 * k, call)
        spans += [call, gather,
                  span("executor.h2d", t + 0.001, 0.002 * k, gather),
                  span("executor.place", t + 0.1, 0.030 * k, call,
                       arrays=6, bytes=1 << 20),
                  span("executor.dispatch", t + 0.2, 0.005 * k, call),
                  span("executor.write_back", t + 0.3, 0.001 * k, call),
                  span("executor.complete", t + 0.4, 0.5, call)]
    # a dispatch outside any call (the pipelined loop's) is not a window's
    spans.append(span("executor.dispatch", 11.5, 0.2))
    return {"program_spans": spans, "program_window": (9.0, 20.0),
            "facts": {}}


@pytest.mark.parametrize("name,want_ms", [
    ("host_gather_ms.train", 8.0),
    ("host_place_ms.train", 60.0),
    ("host_dispatch_ms.train", 10.0),
    # 1000 - (gather 8 + place 60 + dispatch 10 + write_back 2 + 500)
    ("host_self_ms.train", 420.0),
])
def test_train_phase_readers_take_the_median_call_inside_the_window(
        name, want_ms):
    assert reader(name).read(train_record()) == pytest.approx(want_ms)


def test_self_time_takes_overlapping_children_off_once():
    step = span("serving.engine.step", 0.0, 0.030)
    record = {"program_spans": [
        step,
        span("serving.engine.feeds", 0.000, 0.001, step),
        span("executor.call", 0.001, 0.020, step),
        # a retroactive child that overlaps the call and runs past the
        # step's end: only its part inside the step and outside the call
        span("serving.queue.wait", 0.015, 0.100, step),
        span("serving.engine.sample", 0.028, 0.001, step),
    ], "program_window": (None, None)}
    # covered: [0, 0.021] + [0.015, 0.030] = the whole step
    assert program_spans.self_ms(record, "serving.engine.step") == [0.0]
    record["program_spans"].pop(3)
    assert program_spans.self_median_ms(
        record, "serving.engine.step") == pytest.approx(8.0)
    # a grandchild is its parent's, not the step's
    call = record["program_spans"][2]
    record["program_spans"].append(
        span("executor.dispatch", 0.002, 0.001, call))
    assert program_spans.self_median_ms(
        record, "serving.engine.step") == pytest.approx(8.0)


def serve_record():
    """Two requests on one engine. ``a`` is admitted at 1.00 and rides
    steps ending 1.03, 1.06, 1.12; ``b``'s admission (1.06 to 1.09) holds
    the batch up between two of ``a``'s tokens; ``b`` rides the last."""
    loop = "loop"
    spans = []
    admit_a = span("serving.engine.admit", 0.98, 0.02, trace="a")
    admit_b = span("serving.engine.admit", 1.06, 0.03, trace="b")
    spans += [
        span("serving.queue.wait", 0.97, 0.01, trace="a"),
        span("serving.request.first_token", 0.97, 0.03, trace="a",
             prompt_len=8, queued_s=0.01),
        admit_a,
        span("serving.engine.prefill", 0.98, 0.012, admit_a, trace="a"),
        span("serving.engine.splice", 0.992, 0.004, admit_a, trace="a"),
        span("serving.engine.sample", 0.997, 0.002, admit_a, trace="a",
             active=1),
        span("serving.request.first_token", 1.00, 0.09, trace="b",
             prompt_len=8, queued_s=0.06),
        admit_b,
        span("serving.engine.prefill", 1.06, 0.020, admit_b, trace="b"),
        span("serving.engine.splice", 1.08, 0.006, admit_b, trace="b"),
    ]
    for start, dur, riders, sample in ((1.00, 0.03, ["a"], 0.002),
                                       (1.03, 0.03, ["a"], 0.004),
                                       (1.09, 0.03, ["a", "b"], 0.006)):
        step = span("serving.engine.step", start, dur, trace=loop,
                    active=len(riders), traces=riders)
        spans += [step,
                  span("serving.engine.feeds", start, 0.001, step,
                       trace=loop),
                  span("executor.call", start + 0.001, 0.020, step,
                       trace=loop),
                  span("serving.engine.sample", start + dur - sample,
                       sample, step, trace=loop, active=len(riders))]
    return {"program_spans": spans, "program_window": (0.9, 2.0)}


def test_token_times_are_rebuilt_from_admit_ends_and_step_riders():
    times = program_spans.token_times(serve_record())
    assert times["a"] == pytest.approx([1.00, 1.03, 1.06, 1.12])
    assert times["b"] == pytest.approx([1.09, 1.12])
    gaps = sorted(program_spans.token_gaps_ms(serve_record()))
    assert gaps == pytest.approx([30.0, 30.0, 30.0, 60.0])
    # every request's gaps add up to last token minus first
    for stamps in times.values():
        assert sum(b - a for a, b in zip(stamps, stamps[1:])) \
            == pytest.approx(stamps[-1] - stamps[0])


@pytest.mark.parametrize("name,want_ms", [
    # the admission's own sample span is not a step's
    ("step_sample_ms", 4.0),
    # 30 - (feeds 1 + call 20 + sample 2, 4, 6) = 7, 5, 3
    ("step_self_ms", 5.0),
    ("prefill_run_ms_p50", 16.0),
    ("splice_ms_p50", 5.0),
    # 30 and 90 ms: the 95th percentile by linear interpolation
    ("engine_ttft_ms_p95", 87.0),
    # gaps 30, 30, 30, 60
    ("engine_itl_ms_p95", 55.5),
])
def test_serving_readers(name, want_ms):
    assert reader(name).read(serve_record()) == pytest.approx(want_ms)


def kernel_record(names):
    ops = [(n, float(i), 0.002 * (i + 1), "custom-call")
           for i, n in enumerate(names)]
    ops.append(("fusion.7", 50.0, 1.0, "fusion"))
    return {"trace": {"ops": {0: ops, 1: []}},
            "facts": {"windows_traced": 1, "steps_per_window": 2}}


ONE_CHIP = ["flash_fwd.3", "jvp_flash_refwd_.3", "jvp_flash_bwd_dkv_.3",
            "jvp_flash_bwd_dq_.3"]


@pytest.mark.parametrize("name,want_ms", [
    ("flash_fwd_ms.train", 1.0), ("flash_refwd_ms.train", 2.0),
    ("flash_bwd_ms.train", 7.0)])
def test_kernel_readers_find_each_run_by_the_name_the_program_chose(
        name, want_ms):
    assert reader(name).read(kernel_record(ONE_CHIP)) \
        == pytest.approx(want_ms)


def test_a_recomputation_that_went_away_reads_zero_not_none():
    record = kernel_record(["flash_fwd.3", "jvp_flash_bwd_dkv_.3",
                            "jvp_flash_bwd_dq_.3"])
    assert reader("flash_refwd_ms.train").read(record) == 0.0
    # kernels that nobody named yet (the parent's): nothing to say
    unnamed = kernel_record(["closed_call.3", "jvp__.3",
                             "transpose_jvp___.3"])
    for name in ("flash_fwd_ms.train", "flash_refwd_ms.train",
                 "flash_bwd_ms.train"):
        assert reader(name).read(unnamed) is None


NEW_READERS = [
    "host_gather_ms.train", "host_place_ms.train",
    "host_dispatch_ms.train", "host_self_ms.train", "flash_fwd_ms.train",
    "flash_bwd_ms.train", "flash_refwd_ms.train", "step_sample_ms",
    "step_self_ms", "prefill_run_ms_p50", "splice_ms_p50",
    "engine_ttft_ms_p95", "engine_itl_ms_p95"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_program_that_records_none_of_it_reads_none(name):
    """The driver runs these readers against the parent's program too."""
    old_program = {
        "program_spans": [span("executor.dispatch", 1.0, 0.01),
                          span("executor.complete", 1.1, 0.5),
                          span("serving.engine.step", 2.0, 0.03,
                               active=1, traces=["a"]),
                          span("serving.engine.admit", 1.9, 0.02,
                               trace="a")],
        "program_window": (0.0, 9.0), "trace": None,
        "facts": {"windows_traced": 0}}
    got = reader(name).read(old_program)
    if name == "step_self_ms":
        # the step span is the parent's too: its self time is the span
        assert got == pytest.approx(30.0)
    else:
        assert got is None
    assert reader(name).read({"program_spans": [], "facts": {}}) is None


def test_the_window_is_the_measured_one_on_the_hosts_clock():
    record = {"trace": {"t0": 500.0, "host_offset_s": 400.0,
                        "window_s": 3.0},
              "facts": {"window_s": 45.0}}
    assert program_spans.window(record) == (100.0, 145.0)
    record["facts"] = {"elapsed_s": 44.0}
    assert program_spans.window(record) == (100.0, 144.0)
    # a rehearsal has no reduced trace: nothing is cut
    assert program_spans.window({"trace": None}) == (None, None)
    inside = span("executor.call", 101.0, 1.0)
    outside = span("executor.call", 10.0, 1.0)
    record["program_spans"] = [outside, inside]
    assert program_spans.finished(record) == [inside]


def test_the_readers_read_the_live_ring_where_the_record_has_no_spans():
    from paddle_tpu.observe import trace

    trace._reset()
    with trace.trace_span("executor.call", site="run", steps=1):
        with trace.trace_span("executor.gather"):
            pass
    try:
        assert reader("host_gather_ms.train").read({"facts": {}}) >= 0.0
        assert reader("host_place_ms.train").read({"facts": {}}) is None
    finally:
        trace._reset()
