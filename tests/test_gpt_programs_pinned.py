"""Every serving program of every serving configuration, pinned op for op.

``references/gpt_programs_pr55.json`` was taken from the parent of PR 56
(commit 5c039df, ``models/gpt.py`` as PR 55 left it) by ``digest`` below,
BEFORE that PR folded the prefill's and the decode step's layer walkers
into one: the refactor is checked against it and the file is not taken
again. For each ``benchmarks/configs/*.json`` that serves (all but
``bert-base``), at the configuration's own widths, layers and
``serving['max_len']`` (no case takes 2 s to build, so no ``n_layer`` was
cut): the serving decode step at batch 4, the lockstep decode step at
batch 2, and the prefill at 128 tokens and at ``_LONG`` — above the
window where the configuration has one, else the longest prompt its
cell's traffic offers.

A case holds ``n_ops`` and a sha256 over, per op, its type, every slot
WITH its variables' names (so the order the layers were called in, which
numbers the temporaries), its attributes (as ``test_afmoe.py``'s digest
takes them) and its ``name_scope``; the sorted ``(name, shape, dtype)`` of
the parameters (as a sha256) and those of ``cache_names`` in the order the
builder returned them (the engine splices by it); and the start-up program's ops as
a SORTED list (what each initialiser writes and with what, not the order
they stand in: a cache's zero fill draws nothing, so where it stands
among the parameters' draws changes no value). Programs only: nothing is
compiled or run.

Since PR 58 every prefill writes its residual stream after every layer
(one ``materialize`` op a layer: an optimization barrier, no arithmetic).
The file is still the parent's: a prefill is digested WITH THOSE OPS
TAKEN OUT and their readers handed the value they passed on, after the
case has counted them (``n_layer`` in a prefill, none in a decode step),
so everything else of the program is still held to what PR 55 built.

``python tests/test_gpt_programs_pinned.py <commit>`` prints the digests
of the tree it runs on under that commit's name (it writes no file).
"""
import hashlib
import json
import os
import sys

import pytest

import paddle_tpu as fluid
from paddle_tpu.core.program import unique_name
from paddle_tpu.models import gpt

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REFERENCE = os.path.join(HERE, "references", "gpt_programs_pr55.json")

# the second prompt length of each configuration's prefill
_LONG = {
    "brumby-14b-base": 8192,
    "gpt2-medium": 512,
    "lfm2-24b-a2b": 16384,
    "longcat-flash-omni": 3328,
    "nemotron-3-super-120b-a12b": 2048,
    "olmoe-1b-7b": 512,
    "openpangu-ultra-moe-718b": 3328,
    "qwen3-next-80b-a3b": 2048,
    "trinity-large-preview": 6144,      # above its window of 4,096
    "xing4.0-29b-a4b": 8192,
}
_BUILDS = {
    "serving_decode": lambda c, n, P: gpt.build_serving_decode_step(
        c, batch=4, max_len=n),
    "decode": lambda c, n, P: gpt.build_decode_step(c, batch=2, max_len=n),
    "prefill": lambda c, n, P: gpt.build_prefill_step(
        c, batch=1, prompt_len=P, max_len=n),
}
CASES = [(name, build, P) for name in sorted(_LONG)
         for build, P in (("serving_decode", None), ("decode", None),
                          ("prefill", 128), ("prefill", _LONG[name]))]


def _case_id(case):
    name, build, P = case
    return "%s-%s" % (name, build if P is None else "%s_%d" % (build, P))


def _ops(program, scoped):
    """The block's ops without the residual pins (module docstring)."""
    passed = {op.outputs["Out"][0]: op.inputs["X"][0]
              for op in program.global_block().ops
              if op.type == "materialize"}

    def slots(named):
        return sorted((slot, [passed.get(n, n) for n in names])
                      for slot, names in named.items())

    return [[op.type, slots(op.inputs), slots(op.outputs),
             sorted((k, repr(v)) for k, v in op.attrs.items()
                    if not k.startswith("_") and k != "op_callstack")]
            + ([op.name_scope] if scoped else [])
            for op in program.global_block().ops if op.type != "materialize"]


def _n_layer(name):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        return json.load(f)["model"]["n_layer"]


def _sha(what):
    return hashlib.sha256(json.dumps(what, sort_keys=True).encode()) \
        .hexdigest()


def digest(case):
    """What ``REFERENCE`` holds of one case."""
    name, build, P = case
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        conf = json.load(f)
    prog, start = fluid.Program(), fluid.Program()
    with unique_name.guard(), fluid.program_guard(prog, start):
        _logits, cache_names = _BUILDS[build](
            conf["model"], conf["serving"]["max_len"], P)
    block = prog.global_block()
    ops = _ops(prog, scoped=True)

    def described(variables):
        return sorted([v.name, list(v.shape), str(v.dtype)]
                      for v in variables)

    return {"n_ops": len(ops), "sha256": _sha(ops),
            "pins": len(block.ops) - len(ops),
            "params": _sha(described(block.all_parameters())),
            "n_params": len(block.all_parameters()),
            "caches": [[n, list(block.var(n).shape), str(block.var(n).dtype)]
                       for n in cache_names],
            "startup": _sha(sorted(_ops(start, scoped=False)))}


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_the_serving_program_is_the_parents(case):
    with open(REFERENCE) as f:
        want = json.load(f)["cases"][_case_id(case)]
    got = digest(case)
    layers = got.pop("pins")
    assert layers == (0 if case[2] is None else _n_layer(case[0]))
    for key in ("caches", "n_params", "params", "startup",
                "n_ops", "sha256"):
        assert got[key] == want[key], key


if __name__ == "__main__":
    print('{"taken_from": %s,\n "cases": {\n%s\n}}' % (
        json.dumps(sys.argv[1]), ",\n".join(
            "  %s: %s" % (json.dumps(_case_id(c)),
                          json.dumps(digest(c), sort_keys=True))
            for c in CASES)))
