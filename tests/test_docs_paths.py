"""The documents describe the tree as it is: every repo path a document
names in backticks exists.

Checked: ``tools/…``, ``paddle_tpu/…``, ``tests/…``, ``benchmarks/…``,
``examples/…`` and ``docs/…`` paths, root-level ``UPPER_CASE.md/.json``
files, and bare ``name.py`` files (which must exist somewhere in the
tree: documents name modules by their basename). ``PERF.md``,
``ROADMAP.md`` and ``CHANGES.md`` are not checked: they tell history.
"""

import glob
import os
import re

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))

_TREE = re.compile(
    r"^(?:tools|paddle_tpu|tests|benchmarks|examples|docs)/[\w./-]+")
_ROOT_FILE = re.compile(r"^[A-Z][A-Z_]*\.(?:md|json|jsonl)$")
_BARE_PY = re.compile(r"^\w+\.py$")
_SKIP_DIRS = {".git", "__pycache__", "chiprun_out", ".jax_cache"}


def _py_basenames():
    names = set()
    for _dir, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in _SKIP_DIRS]
        names.update(f for f in files if f.endswith(".py"))
    return names


def named_paths(text):
    """(path, kind) for every checked path inside a backtick span."""
    for span in re.findall(r"`([^`\n]+)`", text):
        for token in span.split():
            token = token.strip("()[],;\"'")
            m = _TREE.match(token)
            if m and not re.search(r"[*<{]", token):
                # `a/b.py::test`, `a/b.py:12` name the file
                yield m.group(0).split("::")[0].rstrip(".:/"), "tree"
            elif _ROOT_FILE.match(token):
                yield token, "root"
            elif _BARE_PY.match(token):
                yield token, "py"


def test_the_rule_sees_what_it_should():
    text = ("run `python tools/gone.py --flag`, read `GONE.md` and "
            "`gone.py:70`; `paddle_tpu/core/executor.py:141-165`, "
            "`tests/test_docs_paths.py::test_x`, `docs/*.md` and "
            "`benchmarks/layer_metrics/<name>.py` are fine")
    assert list(named_paths(text)) == [
        ("tools/gone.py", "tree"), ("GONE.md", "root"),
        ("paddle_tpu/core/executor.py", "tree"),
        ("tests/test_docs_paths.py", "tree")]
    assert list(named_paths("`gone.py`")) == [("gone.py", "py")]


@pytest.mark.parametrize("doc", DOCS)
def test_every_repo_path_a_document_names_exists(doc):
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        text = f.read()
    basenames = _py_basenames()
    missing = sorted({
        path for path, kind in named_paths(text)
        if not (path in basenames if kind == "py"
                else os.path.exists(os.path.join(ROOT, path)))})
    assert not missing, "%s names paths that do not exist: %s" % (
        doc, missing)
