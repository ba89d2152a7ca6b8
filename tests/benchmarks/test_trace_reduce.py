"""The yardstick's arithmetic: the .xplane.pb reduction, the percentile
and the closed-form FLOPs and bytes."""

import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import closed_forms, stats, xplane  # noqa: E402
from benchmarks.lib.peaks import peaks_for  # noqa: E402

SMALL = os.path.join(ROOT, "tests", "benchmarks", "data",
                     "small.xplane.pb")
BERT = dict(d_model=768, d_ff=3072, n_head=12, n_layer=12, vocab=30522)
GPT2M = dict(d_model=1024, d_ff=4096, n_head=16, n_layer=24, vocab=50257,
             max_length=1024, tie_embeddings=True)


# ------------------------------------------------------- interval algebra
def _ev(*triples):
    return [(n, s, d, xplane.op_group(n)) for n, s, d in triples]


def test_event_names_are_parsed_from_the_hlo_line():
    raw = ("%transpose_jvp___.228 = (bf16[384,512,64]{2,1,0:T(8,128)(2,1)},"
           " bf16[384,512,64]{2,1,0:T(8,128)(2,1)S(1)}) custom-call("
           "bf16[384,512,64]{2,1,0:T(8,128)(2,1)} %bitcast.5452)")
    assert xplane.parse_event_name(raw) == ("transpose_jvp___.228",
                                            "custom-call")
    assert xplane.parse_event_name(
        "%while.5 = (s32[]{:T(128)}, f32[768]{0:T(1024)}) while(%tuple.1)"
    ) == ("while.5", "while")
    assert xplane.parse_event_name(
        "%all-reduce-start.3 = f32[768]{0} all-reduce-start(f32[768] %p)"
    ) == ("all-reduce-start.3", "all-reduce-start")
    assert xplane.parse_event_name("dot_general.1") == ("dot_general.1",
                                                        "dot_general")


def test_union_and_busy_seconds_merge_overlaps():
    events = _ev(("a", 0.0, 1.0), ("b", 0.5, 1.0), ("c", 3.0, 0.5),
                 ("d", 3.5, 0.25))
    assert xplane.union(events) == [(0.0, 1.5), (3.0, 3.75)]
    assert xplane.busy_seconds(events) == pytest.approx(2.25)
    assert xplane.busy_seconds([]) == 0.0


def test_clip_cuts_events_to_the_window():
    events = _ev(("a", 0.0, 1.0), ("b", 2.0, 2.0), ("c", 9.0, 1.0))
    assert xplane.clip(events, 0.5, 3.0) == _ev(("a", 0.5, 0.5),
                                                ("b", 2.0, 1.0))


def test_per_op_seconds_groups_by_name_without_the_numeric_suffix():
    events = _ev(("fusion.12", 0, 1.0), ("fusion.7", 1, 2.0),
                 ("all-reduce-start.3", 3, 0.5), ("copy", 4, 0.25),
                 ("while.5", 0, 5.0))
    got = xplane.per_op_seconds(xplane.leaves(events))
    assert "while" not in got
    assert got["fusion"] == (3.0, 2)
    assert got["all-reduce-start"] == (0.5, 1)
    assert got["copy"] == (0.25, 1)


def test_a_collective_is_exposed_only_where_nothing_else_runs():
    events = _ev(("fusion.1", 0.0, 1.0), ("all-reduce.1", 0.5, 1.5),
                 ("fusion.2", 1.5, 0.25), ("all-gather.9", 3.0, 1.0))
    coll = xplane.collectives(events)
    assert [e[0] for e in coll] == ["all-reduce.1", "all-gather.9"]
    others = [e for e in events if e not in coll]
    # all-reduce 0.5..2.0 is covered 0.5..1.0 and 1.5..1.75: 0.75 exposed
    assert xplane.exposed_seconds(coll, others) == pytest.approx(1.75)


def test_idle_gaps_are_named_by_the_annotation_that_covers_them():
    events = _ev(("fusion.1", 0.0, 1.0), ("fusion.2", 1.5, 0.5),
                 ("fusion.3", 4.0, 1.0))
    spans = [("bench.fetch", 0.9, 0.7), ("bench.sleep", 2.1, 1.8)]
    gaps = xplane.idle_gaps(events, spans, 0.0, 6.0)
    assert gaps[0] == ("bench.sleep", pytest.approx(2.0))
    assert ("bench.fetch", pytest.approx(0.5)) in gaps
    assert ("between_annotations", pytest.approx(1.0)) in gaps


# -------------------------------------------------- the recorded trace
@pytest.fixture(scope="module")
def small():
    return xplane.reduce(SMALL)


def test_recorded_trace_has_every_chip_and_a_window(small):
    assert sorted(small["ops"]) == [0, 1, 2, 3]
    assert 0.05 < small["window_s"] < 5.0
    for dev, events in small["ops"].items():
        assert events, dev
        assert all(small["t0"] <= e[1] and e[1] + e[2] <= small["t1"] + 1e-9
                   for e in events)


def test_recorded_trace_busy_union_is_below_the_window_and_the_op_sum(small):
    for dev, events in small["ops"].items():
        busy = small["busy_s"][dev]
        assert 0 < busy < small["window_s"]
        assert busy <= sum(e[2] for e in events) + 1e-12
    assert small["busy_mean_s"] == pytest.approx(
        sum(small["busy_s"].values()) / 4)


def test_recorded_trace_holds_a_collective_per_step(small):
    coll = xplane.collectives(small["ops"][0]) \
        + xplane.collectives(small["async_ops"].get(0, []))
    # three steps, one psum each; in this trace the chip's clock runs
    # 0.7-1.0 ms behind the host's, so the first step's operations fall
    # just before the window annotation opens and are cut with it
    assert len(coll) >= 2
    assert xplane.busy_seconds(coll) > 0
    assert any(e[3].startswith("all-reduce") for e in coll)


def test_recorded_trace_names_its_idle_gaps_by_the_sleep_annotation(small):
    steps = [s for s in small["spans"] if s[0] == "bench.step"]
    assert len(steps) == 3
    gaps = small["breakdown"]["idle_gaps"]
    assert gaps and len(gaps) <= 10
    # the host slept 20 ms after each step: the longest gaps are its
    assert gaps[0][0] == "bench.sleep" and gaps[0][1] > 0.015
    top = small["breakdown"]["device_ops"]
    assert 1 <= len(top) <= 10 and all(s > 0 for _n, s in top)


# ------------------------------------------------------------ statistics
def test_percentile_interpolates_between_order_statistics():
    xs = [4.0, 1.0, 3.0, 2.0]
    assert stats.percentile(xs, 50) == pytest.approx(2.5)
    assert stats.percentile(xs, 0) == 1.0
    assert stats.percentile(xs, 100) == 4.0
    assert stats.percentile(list(range(101)), 95) == pytest.approx(95.0)
    assert stats.percentile([], 50) is None
    assert stats.median([7.0]) == 7.0


def test_spread_is_the_quartile_distance_over_the_median():
    assert stats.iqr_share([10.0, 10.0, 10.0, 10.0, 10.0, 10.0]) == 0.0
    # statistics.quantiles (exclusive): q1 = 1.75, q3 = 5.25 of 1..6
    assert stats.iqr_share([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)


# ----------------------------------------------------------- closed forms
def test_bert_flops_per_token_are_the_issue_s_numbers():
    assert closed_forms.bert_matmul_params(BERT) == 84934656    # 84.9 M
    s512 = closed_forms.bert_train_flops_per_token(BERT, 512, 80)
    s128 = closed_forms.bert_train_flops_per_token(BERT, 128, 20)
    assert s512["dense"] == 6 * 84934656                        # 509.6 M
    assert s512["attention"] == 12 * 3 * 4 * 512 * 768          # 56.6 M
    assert s128["attention"] == 12 * 3 * 4 * 128 * 768          # 14.2 M
    assert s512["head"] == pytest.approx(22.5e6, rel=2e-3)
    assert s512["total"] == pytest.approx(589e6, rel=2e-3)
    assert s128["total"] == pytest.approx(546e6, rel=2e-3)


def test_gpt2_medium_decode_step_bytes():
    assert closed_forms.gpt_cache_elements_per_slot(GPT2M, 1024) \
        == 24 * 2 * 16 * 1024 * 64                               # 50.3 M
    assert closed_forms.gpt_param_count(GPT2M) == pytest.approx(355e6,
                                                                rel=2e-3)
    b = closed_forms.gpt_decode_step_bytes(GPT2M, 32, 1024, 4, 4)
    assert b["cache"] == 32 * 50331648 * 4                       # 6.44 GB
    assert b["total"] == pytest.approx(7.86e9, rel=2e-3)
    half = closed_forms.gpt_decode_step_bytes(GPT2M, 64, 1024, 2, 4)
    assert half["cache"] == b["cache"]      # 64 bf16 slots hold the same


def test_flash_roofline_is_compute_bound_at_s512_and_names_its_bound():
    peaks = peaks_for("TPU v5 lite")
    r = closed_forms.flash_train_roofline(32, 12, 512, 64, 12, 2, peaks)
    assert r["flops"] == 12 * 6 * 2 * 32 * 12 * 512 * 512 * 64
    assert r["bound"] == "compute"
    assert r["seconds"] == pytest.approx(r["flops"] / 197e12)
    short = closed_forms.flash_train_roofline(32, 12, 128, 64, 12, 2, peaks)
    assert short["bound"] == "memory"
    assert math.isclose(short["seconds"], short["bytes"] / 819e9)


def test_an_unknown_device_is_an_error_not_a_default():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")


def test_the_innermost_span_names_a_gap_two_spans_cover_alike():
    events = _ev(("fusion.1", 0.0, 1.0), ("fusion.2", 2.0, 1.0))
    spans = [("serving.engine.admit", 0.5, 2.0),
             ("serving.engine.prefill", 0.9, 1.2)]
    assert xplane.idle_gaps(events, spans, 0.0, 3.0) == [
        ("serving.engine.prefill", pytest.approx(1.0))]
