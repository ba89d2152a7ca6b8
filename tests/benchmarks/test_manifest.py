"""BENCHMARK.json keeps to the contract's limits and every name in it
has its file."""

import importlib.util
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib.manifest import Manifest, ManifestError  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
MANIFESTS = ["BENCHMARK.json", "tests/benchmarks/BENCHMARK.tiny.json"]


@pytest.fixture(scope="module", params=MANIFESTS)
def manifest(request):
    return Manifest(os.path.join(ROOT, request.param))


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits(manifest):
    doc = manifest.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(manifest.path) <= 64 * 1024
    assert 1 <= len(doc["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in doc["paths"])
    assert len(doc["command"]) <= 32 and all(_line(w)
                                             for w in doc["command"])
    assert isinstance(doc["run_seconds"], int)
    assert 1 <= doc["run_seconds"] <= 51
    assert 1 <= len(doc["configs"]) <= 24
    assert 1 <= len(doc["workloads"]) <= 24
    assert 1 <= len(doc["end_to_end"]) <= 16
    assert 1 <= len(doc["per_layer"]) <= 128


def test_names_units_and_entries(manifest):
    doc = manifest.doc
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"])
        assert _line(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in doc["paths"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 2, 4) and _line(w["why"])
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for section in ("configs", "workloads"):
        names = [e["name"] for e in doc[section]]
        assert len(names) == len(set(names))
    metric_names = [m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(pairs) == len(set(pairs))
    files = [c["file"] for c in doc["configs"]]
    assert len(files) == len(set(files))
    assert "setup_s" in [m["name"] for m in doc["end_to_end"]]
    four = sum(1 for w in doc["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(doc["workloads"]) // 4)


def test_every_cell_has_its_config_traffic_and_kind(manifest):
    used = set()
    for w in manifest.doc["workloads"]:
        config = manifest.config(w["config"])
        used.add(w["config"])
        assert "model" in config and "source" in config
        traffic = manifest.traffic(w["traffic"])
        kind = manifest.load_module("kinds", traffic["kind"])
        assert callable(kind.run)
    assert used == {c["name"] for c in manifest.doc["configs"]}


def test_every_cell_reports_setup_one_more_end_to_end_and_a_layer(manifest):
    for w in manifest.doc["workloads"]:
        e2e = [m["name"] for m in
               manifest.metrics_for("end_to_end", w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert manifest.metrics_for("per_layer", w["name"])


def test_every_per_layer_metric_has_a_reader_that_agrees(manifest):
    doc = manifest.doc
    cells = {w["name"] for w in doc["workloads"]}
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    layers = {}
    for m in doc["per_layer"]:
        reader = manifest.load_module("layer_metrics", m["name"])
        assert callable(reader.read)
        assert reader.LAYER == m["layer"]
        assert reader.UNIT == m["unit"]
        assert reader.MOVES == m["moves"]
        assert reader.SOURCE == m["source"]
        assert m["moves"] in e2e
        # listed only in cells that report the metric it moves
        moved = e2e[m["moves"]]
        moved_cells = set(moved.get("workloads", cells))
        assert set(m.get("workloads", cells)) <= moved_cells
        assert set(m.get("workloads", cells)) <= cells
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    # one spelling per layer
    assert all(len(v) == 1 for v in layers.values())


def test_a_name_without_a_file_is_an_error(manifest):
    with pytest.raises(ManifestError):
        manifest.load_module("layer_metrics", "no_such_metric")
    with pytest.raises(ManifestError):
        manifest.cell("no_such_cell")


def test_kernel_shares_are_named_as_the_contract_asks():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    for m in doc["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"
    assert importlib.util.find_spec("benchmarks.run") is not None
