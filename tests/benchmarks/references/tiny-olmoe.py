"""The plain reference of the tests' tiny OLMoE-shaped configuration: the
repo's own copy (``tests/references/olmoe.py``), found here by the
configuration's name as the benchmark's kinds look it up."""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "references", "olmoe.py")
_spec = importlib.util.spec_from_file_location("tests_reference_olmoe",
                                               _PATH)
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)

forward = _mod.forward
greedy_margin_fn = _mod.greedy_margin_fn
