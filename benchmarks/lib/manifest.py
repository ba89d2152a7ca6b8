"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix, one runner
kind or one per-layer metric is a file of its own, found by name in the
directories ``paths`` lists — so a later PR adds files and entries and
edits nothing that is here."""

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


class ManifestError(ValueError):
    pass


def _read_json(path):
    with open(path) as f:
        return json.load(f)


def load_path(path):
    """A Python file as a module, whatever its name (names hold dots)."""
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in path)
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Manifest:
    def __init__(self, path=None, root=ROOT):
        self.root = root
        self.path = path or os.path.join(root, "BENCHMARK.json")
        self.doc = _read_json(self.path)
        self.paths = list(self.doc["paths"])

    # ------------------------------------------------------------ finding
    def find(self, subdir, name, suffixes):
        """The one file ``<path>/<subdir>/<name><suffix>`` under the
        benchmark's directories."""
        tried = []
        for base in self.paths:
            for suffix in suffixes:
                cand = os.path.join(self.root, base, subdir, name + suffix)
                tried.append(cand)
                if os.path.isfile(cand):
                    return cand
        raise ManifestError("no %s file for %r (looked for %s)"
                            % (subdir, name, ", ".join(tried)))

    def load_module(self, subdir, name):
        """A kind or a per-layer reader, loaded by path: names hold dots
        and dashes, so they are not importable module names."""
        return load_path(self.find(subdir, name, (".py",)))

    # ------------------------------------------------------------- entries
    def cell(self, name):
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError("no workload %r in %s (has: %s)" % (
            name, self.path,
            ", ".join(w["name"] for w in self.doc["workloads"])))

    def config(self, name):
        for c in self.doc["configs"]:
            if c["name"] == name:
                return _read_json(os.path.join(self.root, c["file"]))
        raise ManifestError("no config %r in %s" % (name, self.path))

    def traffic(self, name):
        path = self.find("traffic", name, DATA_SUFFIXES)
        if not path.endswith(".json"):
            raise ManifestError("traffic %s: only .json is read today"
                                % path)
        return _read_json(path)

    def metrics_for(self, section, cell_name):
        """The metrics of ``end_to_end`` or ``per_layer`` that the cell
        reports: those without a ``workloads`` key, or listing it."""
        return [m for m in self.doc[section]
                if "workloads" not in m or cell_name in m["workloads"]]
