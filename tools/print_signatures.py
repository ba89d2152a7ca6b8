"""Print the public API surface as stable one-line signatures.

Analog of /root/reference/tools/print_signatures.py, which feeds the
API-stability gate tools/diff_api.py against the committed
paddle/fluid/API.spec (527 symbols). Usage:

    python tools/print_signatures.py > API.spec

tests/test_api_spec.py regenerates the list and diffs it against the
committed API.spec, so accidental API breaks fail CI the same way the
reference's gate does.
"""

from __future__ import annotations

import inspect
import sys


MODULES = [
    "paddle_tpu",
    "paddle_tpu.analysis",
    "paddle_tpu.layers",
    "paddle_tpu.layers.sequence",
    "paddle_tpu.layers.detection",
    "paddle_tpu.layers.loss",
    "paddle_tpu.layers.decode",
    "paddle_tpu.layers.control_flow",
    "paddle_tpu.layers.io",
    "paddle_tpu.layers.tensor",
    "paddle_tpu.layers.metric_op",
    "paddle_tpu.layers.learning_rate_scheduler",
    "paddle_tpu.optimizer",
    "paddle_tpu.initializer",
    "paddle_tpu.regularizer",
    "paddle_tpu.clip",
    "paddle_tpu.io",
    "paddle_tpu.metrics",
    "paddle_tpu.nets",
    "paddle_tpu.profiler",
    "paddle_tpu.imperative",
    "paddle_tpu.imperative.nn",
    "paddle_tpu.imperative.optimizer",
    "paddle_tpu.imperative.jit",
    "paddle_tpu.inference",
    "paddle_tpu.export",
    "paddle_tpu.kernels",
    "paddle_tpu.serving",
    "paddle_tpu.resilience",
    "paddle_tpu.observe",
    "paddle_tpu.distributed",
    "paddle_tpu.distributed.transpiler",
    "paddle_tpu.transpiler",
    "paddle_tpu.contrib.quantize",
    "paddle_tpu.contrib.decoder",
    "paddle_tpu.contrib.utils",
    "paddle_tpu.contrib.reader.ctr_reader",
    "paddle_tpu.contrib.int8_inference",
    "paddle_tpu.contrib.memory_usage_calc",
    "paddle_tpu.contrib.op_frequence",
    "paddle_tpu.average",
    "paddle_tpu.compat",
    "paddle_tpu.data_feed_desc",
    "paddle_tpu.debugger",
    "paddle_tpu.distribute_lookup_table",
    "paddle_tpu.evaluator",
    "paddle_tpu.utils",
    "paddle_tpu.utils.plot",
    "paddle_tpu.graphviz",
    "paddle_tpu.net_drawer",
    "paddle_tpu.async_executor",
    "paddle_tpu.parallel",
    "paddle_tpu.core.passes",
]


def _sig(obj) -> str:
    try:
        return str(inspect.signature(obj))
    except (TypeError, ValueError):
        return "(...)"


def collect():
    import importlib

    lines = []
    for modname in MODULES:
        mod = importlib.import_module(modname)
        names = getattr(mod, "__all__", None)
        if names is None:
            names = [n for n in dir(mod) if not n.startswith("_")]
        for name in sorted(set(names)):
            obj = getattr(mod, name, None)
            if obj is None or inspect.ismodule(obj):
                continue
            if inspect.isclass(obj):
                lines.append("%s.%s.__init__ %s"
                             % (modname, name, _sig(obj.__init__)))
                for mname, meth in sorted(vars(obj).items()):
                    if mname.startswith("_") or not callable(meth):
                        continue
                    lines.append("%s.%s.%s %s"
                                 % (modname, name, mname, _sig(meth)))
            elif callable(obj):
                lines.append("%s.%s %s" % (modname, name, _sig(obj)))
    return sorted(set(lines))


if __name__ == "__main__":
    sys.stdout.write("\n".join(collect()) + "\n")
