"""``tools/trace_view.py --xplane`` (PR 49): a kept device profile joined
with the name tables a flight-recorder dump carries — device time by
scope class and by layer for each program the chip ran, and the longest
idle gaps by the innermost program span over each. Against the repo's
recorded TPU trace (``tests/benchmarks/data/small.xplane.pb``) and a
hand-made host plane."""

import gzip
import io
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "tools")):
    if p not in sys.path:
        sys.path.insert(0, p)

import trace_view  # noqa: E402

from benchmarks.lib import xplane  # noqa: E402

SMALL = os.path.join(ROOT, "tests", "benchmarks", "data", "small.xplane.pb")
TABLE = {"source": "compiled", "fused": {},
         "names": {"convert_reduce_fusion": "L3/norm/layer_norm",
                   "copy-done": None, "copy-start": None,
                   "psum_invariant.7": None}}


def _dump(tables):
    return {"events": [], "extra": {"device_names": tables}}


def test_a_module_is_split_by_the_table_that_holds_its_instructions():
    planes = xplane.load(SMALL)
    other = {"source": "cache", "fused": {}, "names": {"fusion.7": "x/mul"}}
    (row,) = trace_view.device_by_scope(
        _dump({"other000": other, "abcd0123": TABLE}), planes)
    assert row["plan"] == "abcd0123" and row["runs"] == 3
    assert row["module"].startswith("jit_body(")
    assert set(row["by_class"]) == {"norm", "unscoped"}
    assert set(row["by_layer"]) == {"L3", "-"}
    assert row["by_class"]["norm"] == pytest.approx(row["by_layer"]["L3"])
    assert sum(row["by_class"].values()) == pytest.approx(row["seconds"])
    assert 0 < row["placed_pct"] < 100
    # no table at all: everything the chip ran is unscoped, nothing raises
    (bare,) = trace_view.device_by_scope({"events": []}, planes)
    assert bare["plan"] is None and set(bare["by_class"]) == {"unscoped"}


def test_a_gap_is_named_by_the_innermost_program_span_over_it():
    ops = [("fusion.1", 10.0, 1.0, "fusion"),
           ("fusion.2", 11.5, 0.5, "fusion"),      # a gap of 0.5 before it
           ("fusion.3", 12.1, 0.9, "fusion")]      # and one of 0.1
    host = [("bench.window", 10.0, 3.0, "bench"),
            ("serving.engine.step", 10.9, 1.0, "x"),
            ("executor.call", 11.0, 0.6, "x"),
            ("executor.complete", 11.05, 0.4, "x"),
            ("tpu::System::Execute", 11.1, 0.2, "x"),   # not a program span
            ("serving.engine.admit", 12.0, 0.5, "x")]
    planes = {"/device:TPU:0": {"XLA Ops": ops},
              "/host:CPU": {"main": host}}
    gaps = trace_view.idle_gaps(planes)
    assert [round(g[0], 6) for g in gaps] == [0.5, 0.1]
    assert gaps[0][1] == "executor.complete"
    assert gaps[0][2] == ["serving.engine.step", "executor.call",
                          "executor.complete"]
    assert gaps[1][1] == "serving.engine.admit"


def test_the_view_prints_from_a_gzipped_dump(tmp_path):
    path = tmp_path / "ring.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump(dict(_dump({"abcd0123": TABLE}), reason="atexit"), f)
    assert trace_view.main([str(path), "--xplane", SMALL]) == 0
    out = io.StringIO()
    trace_view.print_device_view(trace_view.load_dump(str(path)), SMALL, out)
    text = out.getvalue()
    assert "plan=abcd0123" in text and "norm" in text
    assert "bench.sleep" in text                 # the recorded gaps' span
    # the summary names the tables and does not print them whole
    out = io.StringIO()
    trace_view.summarize(trace_view.load_dump(str(path)), out)
    assert "4 instructions (compiled)" in out.getvalue()
