#!/usr/bin/env python
"""AST-based repo lint: cheap structural invariants CI can hold.

Rule families (all wired into the fast tier via
tests/test_repo_lint.py):

1. **bare-except** — ``except:`` swallows KeyboardInterrupt/SystemExit;
   in the resilience and serving paths that turns an operator Ctrl-C or
   a supervisor kill into a silently-absorbed fault, so those trees must
   always name what they catch (``except Exception:`` at minimum).
2. **undeclared-family** — every observe metric family name referenced
   anywhere in code must be declared in ``paddle_tpu/observe/families.py``
   (the schema-is-the-signal contract: a telemetry sidecar carries every
   family's zeroed schema only when declaration is centralized). A
   string literal that LOOKS like a family name (``paddle_*_total`` ...)
   but is not declared is either a typo'd reference — which would
   silently create an empty series — or a decentralized declaration.
3. **undeclared-trace-site** — the same contract for span/trace-event
   SITE names: every literal first argument of a
   ``trace_span``/``trace_event``/``record_span`` call must appear in
   ``families.py``'s ``TRACE_SITES`` tuple. A typo'd site would
   fragment a trace across names ``tools/trace_view.py`` can't group —
   and would silently drop out of the dump validator's vocabulary.
4. **undocumented-pass** — every class registered with
   ``@register_pass(...)`` must carry a docstring: the pass registry IS
   the optimizer's catalog (docs/OPTIMIZER.md points at it), and an
   ``OptimizerPassError`` names the failing pass — a nameable pass with
   no stated contract is undiagnosable. (The ``paddle_optimizer_*``
   families a pass records are covered by rule 2 like every other
   family reference.)
6. **undeclared-fault-site** — the trace-site contract (rule 3) for the
   fault-injection plane: every literal site passed to ``fault_point``
   (the compiled-in hot-path stamps) or armed via ``FaultPlan.arm``
   must be declared in ``families.py``'s ``FAULT_SITES`` tuple. A
   typo'd site would arm a spec nothing ever fires (a chaos test that
   silently tests nothing) — or stamp a site whose injections land in
   an undeclared ``paddle_resilience_faults_injected_total`` series
   outside the pre-materialized schema. Dynamic sites (variables,
   concatenation, the env-plan parser) are skipped like rule 3's.

8. **undocumented-env-knob** — every ``PADDLE_TPU_*`` environment knob
   READ in ``paddle_tpu/`` or ``tools/`` (AST scan of literal
   ``os.environ[...]`` / ``os.environ.get/setdefault/pop`` /
   ``os.getenv`` arguments) must appear in a docs/*.md knob table —
   the knob inventory has grown past grep-ability, and an undocumented
   knob is a behavior switch nobody can discover. Dynamic names
   (prefix concatenation, helper wrappers) are skipped like rule 3's
   dynamic sites; the documented set is every ``PADDLE_TPU_*`` token
   mentioned in ``docs/*.md`` (tables are prose — the mention IS the
   documentation contract).

7. **range-rule-coverage** — the value-range abstract interpreter
   (``analysis/ranges.py``) must never widen a *shape-ruled* op
   silently: every op type registered with ``register_shape_rule`` in
   ``analysis/shape_rules.py`` must either carry a
   ``register_range_rule`` transfer function in
   ``analysis/range_rules.py`` or be listed in that module's explicit
   ``WIDEN_TO_TOP`` declaration — and the two sets must be disjoint
   (a declared-⊤ op with a rule is a stale declaration). This keeps
   the partition TOTAL over the checkable op vocabulary (a superset of
   what appears in model-zoo programs — the runtime schema-pin test in
   tests/test_ranges.py holds the model-zoo subset against reality),
   so growing an op a shape rule without deciding its range story
   fails CI. Registrations are resolved through the three idioms the
   rule files use: literal decorator/call args, ``*NAME`` star-args
   against module-level tuple assignments, and ``for V in (...)``
   loops over literal tuples.

9. **dead-family** — the reverse of rule 2: every family declared in
   ``families.py`` must be REFERENCED somewhere in ``paddle_tpu/`` or
   ``tools/`` (by the module-level variable it is assigned to, or by
   its name in a string literal). A declared-but-never-written family is schema noise: it renders as a forever-zero
   series that reads like "this subsystem did nothing" when the truth
   is "nothing ever reports here". Tests/examples do not count as
   references — a family only a test touches measures nothing.

10. **cost-rule-coverage** — rule 7's mirror for the roofline cost
    engine (``analysis/cost.py``): every op type registered with
    ``register_shape_rule`` must either carry a ``register_cost_rule``
    transfer function in ``analysis/cost_rules.py`` or be listed in
    that module's explicit ``ZERO_COST`` declaration (pure
    metadata/layout ops that move no payload bytes and execute no
    FLOPs) — and the two sets must be disjoint. Without this, growing
    an op a shape rule silently prices it bytes-only: its FLOPs vanish
    from predicted MFU, exactly the silent
    widening rule 7 exists to prevent in the range engine. Same
    registration-idiom resolution as rule 7.

11. **undeclared-artifact-section** — the trace-site contract (rule 3)
    for the deployable-artifact container (``paddle_tpu/export/``):
    every literal section name passed to ``write_section`` /
    ``read_section`` / ``section_path`` must be declared in
    ``export/format.py``'s ``SECTIONS`` schema tuple. The manifest's
    section list IS the format — a section written outside the schema
    would round-trip unchecked (no recorded version, outside the
    ordered manifest contract docs/DEPLOYMENT.md documents), and a
    typo'd read would silently degrade every artifact. The runtime
    mirror (declared tuple == ``paddle_tpu.export.format.SECTIONS``)
    is pinned in tests/test_repo_lint.py.

12. **dist-verifier-vocabulary** — the distributed verifier
    (``analysis/distributed.py``) matches trainer-side ops against its
    ``WIRE_OPS``/``BARRIER_OPS`` tuples: every op type named there must
    exist in the op registry (AST scan of ``register_op(...)`` literal
    first args across ``paddle_tpu/``) — a typo'd entry silently
    exempts that op from wire typing and the deadlock graph. And every
    ``paddle_analysis_dist_*`` observe family the verifier references
    (by imported variable or string literal) must be declared in
    ``families.py`` — the rule-2/9 contract pinned specifically for
    this engine, because its families are the only launch-abort signal
    a fleet dashboard sees. (``listen_and_serv`` is deliberately in
    NEITHER set: the Executor special-cases it as the PS-loop entry,
    it never lowers through the registry.)

Usage: ``python tools/repo_lint.py [--root DIR]``; exit 1 on violations.
"""

from __future__ import annotations

import argparse
import ast
import os
import re
import sys
from typing import Dict, List, Set

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# directories whose bare excepts are load-bearing bugs (the fault/serving
# planes must never absorb KeyboardInterrupt/SystemExit). Every serving/
# module — including the fleet tier's prefix store and router — rides the
# directory entry. distributed/ joined with the elastic
# tier: rpc.py/ps.py/membership.py sit under the same supervisor-kill
# discipline as resilience/ (an absorbed SIGTERM would wedge a whole
# generation teardown).
BARE_EXCEPT_PATHS = (
    os.path.join("paddle_tpu", "resilience"),
    os.path.join("paddle_tpu", "serving"),
    os.path.join("paddle_tpu", "distributed"),
    os.path.join("tools", "elastic_demo.py"),
)

FAMILIES_FILE = os.path.join("paddle_tpu", "observe", "families.py")

# a family-name-shaped string literal: paddle_<words>; the paddle_tpu
# prefix is the package itself (env vars, module ids), never a family
_FAMILY_RE = re.compile(r"paddle_(?!tpu(?:_|$))[a-z0-9]+(?:_[a-z0-9]+)+")
# prometheus render suffixes a reference may legitimately carry
_RENDER_SUFFIXES = ("_bucket", "_sum", "_count")


def iter_py_files(root: str) -> List[str]:
    out = []
    for sub in ("paddle_tpu", "tools", "tests", "examples"):
        top = os.path.join(root, sub)
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            out.extend(os.path.join(dirpath, f) for f in filenames
                       if f.endswith(".py"))
    return sorted(out)


def _parse(path: str):
    with open(path, "r", encoding="utf-8") as f:
        src = f.read()
    return ast.parse(src, filename=path)


def declared_families(root: str) -> Set[str]:
    """Family names declared via REGISTRY.counter/gauge/histogram(...) in
    observe/families.py (first positional string argument)."""
    tree = _parse(os.path.join(root, FAMILIES_FILE))
    names: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if not (isinstance(fn, ast.Attribute)
                and fn.attr in ("counter", "gauge", "histogram")):
            continue
        if node.args and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            names.add(node.args[0].value)
    return names


def declared_family_vars(root: str) -> Dict[str, str]:
    """{module-level variable: family name} for every
    ``VAR = REGISTRY.counter/gauge/histogram("name", ...)`` assignment
    in observe/families.py — the identifiers call sites import, which
    is how rule 9 resolves a code reference back to its family."""
    tree = _parse(os.path.join(root, FAMILIES_FILE))
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        call = node.value
        if not (isinstance(call, ast.Call)
                and isinstance(call.func, ast.Attribute)
                and call.func.attr in ("counter", "gauge", "histogram")):
            continue
        if not (call.args and isinstance(call.args[0], ast.Constant)
                and isinstance(call.args[0].value, str)):
            continue
        for t in node.targets:
            if isinstance(t, ast.Name):
                out[t.id] = call.args[0].value
    return out


def dead_family_violations(root: str, files=None) -> List[str]:
    """Rule 9: declared ⊆ referenced. A reference is the family's
    assignment variable used (or imported) in ``paddle_tpu/`` or
    ``tools/``, or the family name appearing inside a
    string literal there (the ``REGISTRY.get("...")``/snapshot-reader
    idiom). families.py itself and the tests/examples trees never
    count."""
    var_to_name = declared_family_vars(root)
    declared = declared_families(root)
    referenced: Set[str] = set()
    fam_rel = FAMILIES_FILE.replace("/", os.sep)
    for path in (files or iter_py_files(root)):
        rel = os.path.relpath(path, root)
        if rel == fam_rel or rel.split(os.sep)[0] in ("tests", "examples"):
            continue
        for node in ast.walk(_parse(path)):
            if isinstance(node, ast.Name) and node.id in var_to_name:
                referenced.add(var_to_name[node.id])
            elif isinstance(node, ast.ImportFrom):
                for alias in node.names:
                    if alias.name in var_to_name:
                        referenced.add(var_to_name[alias.name])
            elif isinstance(node, ast.Constant) \
                    and isinstance(node.value, str):
                for m in _FAMILY_RE.finditer(node.value):
                    name = m.group(0)
                    for suf in ("",) + _RENDER_SUFFIXES:
                        base = name[: -len(suf)] if suf else name
                        if base in declared:
                            referenced.add(base)
                            break
    violations = []
    for name in sorted(declared - referenced):
        violations.append(
            "%s: family %r is declared but never referenced in "
            "paddle_tpu/ or tools/ (a forever-zero series is "
            "schema noise — wire it up or remove the declaration)"
            % (FAMILIES_FILE, name))
    return violations


def bare_except_violations(root: str, paths=None) -> List[str]:
    violations = []
    targets = [p for p in iter_py_files(root)
               if any(os.sep + bp + os.sep in p or p.endswith(bp)
                      for bp in (paths or BARE_EXCEPT_PATHS))]
    for path in targets:
        for node in ast.walk(_parse(path)):
            if isinstance(node, ast.ExceptHandler) and node.type is None:
                violations.append(
                    "%s:%d: bare `except:` in a resilience/serving path "
                    "(name the exception type; bare except absorbs "
                    "KeyboardInterrupt/SystemExit)"
                    % (os.path.relpath(path, root), node.lineno))
    return violations


def family_ref_violations(root: str, files=None) -> List[str]:
    declared = declared_families(root)
    # a candidate must END like a real family does (the last token of
    # some declared name, or a prometheus render suffix) — this keeps
    # prose like "paddle_analysis_config" (an API-name transliteration)
    # out while still catching mid-name typos of real references
    suffixes = {n.rsplit("_", 1)[-1] for n in declared}
    suffixes.update(s.lstrip("_") for s in _RENDER_SUFFIXES)
    violations = []
    fam_rel = FAMILIES_FILE.replace("/", os.sep)
    for path in (files or iter_py_files(root)):
        rel = os.path.relpath(path, root)
        if rel == fam_rel:
            continue  # the declaration site itself
        refs: Dict[str, int] = {}
        for node in ast.walk(_parse(path)):
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                for m in _FAMILY_RE.finditer(node.value):
                    # only whole-literal or clearly-delimited mentions:
                    # prose can legally mention a family mid-sentence, and
                    # the regex already guarantees word-ish boundaries
                    refs.setdefault(m.group(0), node.lineno)
        for name, lineno in sorted(refs.items()):
            if name.rsplit("_", 1)[-1] not in suffixes:
                continue
            base = name
            for suf in _RENDER_SUFFIXES:
                if base.endswith(suf) and base[: -len(suf)] in declared:
                    base = base[: -len(suf)]
                    break
            if base not in declared:
                violations.append(
                    "%s:%d: observe family %r is referenced but not "
                    "declared in %s" % (rel, lineno, name, FAMILIES_FILE))
    return violations


# calls whose literal first argument is a trace SITE name (observe/trace.py
# API); new_trace() takes no site, so it is not in the set
_TRACE_CALL_FNS = ("trace_span", "trace_event", "record_span")


def declared_trace_sites(root: str) -> Set[str]:
    """Site names in families.py's ``TRACE_SITES = (...)`` tuple."""
    return _declared_tuple(root, "TRACE_SITES")


def trace_site_violations(root: str, files=None) -> List[str]:
    declared = declared_trace_sites(root)
    violations = []
    fam_rel = FAMILIES_FILE.replace("/", os.sep)
    for path in (files or iter_py_files(root)):
        rel = os.path.relpath(path, root)
        if rel == fam_rel:
            continue
        for node in ast.walk(_parse(path)):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            fn_name = fn.id if isinstance(fn, ast.Name) else (
                fn.attr if isinstance(fn, ast.Attribute) else None)
            if fn_name not in _TRACE_CALL_FNS:
                continue
            if not node.args or not isinstance(node.args[0], ast.Constant) \
                    or not isinstance(node.args[0].value, str):
                continue  # dynamic sites are a deliberate escape hatch
            site = node.args[0].value
            if site not in declared:
                violations.append(
                    "%s:%d: trace site %r is used by %s() but not "
                    "declared in %s TRACE_SITES"
                    % (rel, node.lineno, site, fn_name, FAMILIES_FILE))
    return violations


def _declared_tuple(root: str, var_name: str) -> Set[str]:
    """String elements of a top-level ``VAR = (...)`` tuple/list in
    observe/families.py (TRACE_SITES, FAULT_SITES)."""
    return _module_tuple(os.path.join(root, FAMILIES_FILE), var_name)


def _module_tuple(path: str, var_name: str) -> Set[str]:
    """String elements of a top-level ``VAR = (...)`` tuple/list in an
    arbitrary module (rule 12 reads WIRE_OPS/BARRIER_OPS this way)."""
    tree = _parse(path)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id == var_name
                   for t in node.targets):
            continue
        if isinstance(node.value, (ast.Tuple, ast.List)):
            return {el.value for el in node.value.elts
                    if isinstance(el, ast.Constant)
                    and isinstance(el.value, str)}
    return set()


def declared_fault_sites(root: str) -> Set[str]:
    """Site names in families.py's ``FAULT_SITES = (...)`` tuple."""
    return _declared_tuple(root, "FAULT_SITES")


def _receiver_name(node) -> str:
    """Terminal name of an attribute-call receiver: ``plan.arm`` ->
    ``plan``, ``FaultPlan(seed=s).arm`` -> ``FaultPlan``."""
    if isinstance(node, ast.Call):
        node = node.func
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def fault_site_violations(root: str, files=None) -> List[str]:
    """Rule 6: literal first args of ``fault_point(...)`` and
    ``<plan>.arm(...)`` must be declared in FAULT_SITES."""
    declared = declared_fault_sites(root)
    violations = []
    fam_rel = FAMILIES_FILE.replace("/", os.sep)
    for path in (files or iter_py_files(root)):
        rel = os.path.relpath(path, root)
        if rel == fam_rel:
            continue
        for node in ast.walk(_parse(path)):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            fn_name = fn.id if isinstance(fn, ast.Name) else (
                fn.attr if isinstance(fn, ast.Attribute) else None)
            # `arm` only as an attribute call on a FaultPlan-shaped
            # receiver (FaultPlan().arm / plan.arm) — an unrelated
            # API's `.arm(...)` is not a fault site; `fault_point` in
            # either form
            if fn_name == "arm":
                if not isinstance(fn, ast.Attribute) or \
                        "plan" not in _receiver_name(fn.value).lower():
                    continue
            if fn_name not in ("fault_point", "arm"):
                continue
            if not node.args or not isinstance(node.args[0], ast.Constant) \
                    or not isinstance(node.args[0].value, str):
                continue  # dynamic sites are a deliberate escape hatch
            site = node.args[0].value
            if site not in declared:
                violations.append(
                    "%s:%d: fault site %r is used by %s() but not "
                    "declared in %s FAULT_SITES"
                    % (rel, node.lineno, site, fn_name, FAMILIES_FILE))
    return violations


def pass_docstring_violations(root: str, files=None) -> List[str]:
    """Every ``@register_pass("...")``-decorated class needs a
    docstring (rule 4 above)."""
    violations = []
    for path in (files or iter_py_files(root)):
        rel = os.path.relpath(path, root)
        for node in ast.walk(_parse(path)):
            if not isinstance(node, ast.ClassDef):
                continue
            for deco in node.decorator_list:
                fn = deco.func if isinstance(deco, ast.Call) else deco
                fn_name = fn.id if isinstance(fn, ast.Name) else (
                    fn.attr if isinstance(fn, ast.Attribute) else None)
                if fn_name != "register_pass":
                    continue
                if not ast.get_docstring(node):
                    violations.append(
                        "%s:%d: pass class %r is registered via "
                        "register_pass but has no docstring (the pass "
                        "registry is the optimizer's catalog)"
                        % (rel, node.lineno, node.name))
    return violations


SHAPE_RULES_FILE = os.path.join("paddle_tpu", "analysis",
                                "shape_rules.py")
RANGE_RULES_FILE = os.path.join("paddle_tpu", "analysis",
                                "range_rules.py")


def _rule_registrations(path: str, fn_name: str) -> Set[str]:
    """Op types registered via ``fn_name(...)`` in one rule file,
    resolving the three registration idioms: literal string args,
    ``*NAME`` star-args against module-level tuple/list assignments,
    and ``for V in (...):`` loops over literal tuples."""
    tree = _parse(path)
    tuples: Dict[str, Set[str]] = {}
    loop_vars: Dict[str, Set[str]] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and isinstance(
                node.value, (ast.Tuple, ast.List)):
            elts = {e.value for e in node.value.elts
                    if isinstance(e, ast.Constant)
                    and isinstance(e.value, str)}
            for t in node.targets:
                if isinstance(t, ast.Name):
                    tuples[t.id] = elts
        elif isinstance(node, ast.For) and isinstance(
                node.target, ast.Name) and isinstance(
                node.iter, (ast.Tuple, ast.List)):
            loop_vars[node.target.id] = {
                e.value for e in node.iter.elts
                if isinstance(e, ast.Constant)
                and isinstance(e.value, str)}
    out: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = fn.id if isinstance(fn, ast.Name) else (
            fn.attr if isinstance(fn, ast.Attribute) else None)
        if name != fn_name:
            continue
        for arg in node.args:
            if isinstance(arg, ast.Constant) and isinstance(
                    arg.value, str):
                out.add(arg.value)
            elif isinstance(arg, ast.Starred) and isinstance(
                    arg.value, ast.Name):
                out.update(tuples.get(arg.value.id, ()))
            elif isinstance(arg, ast.Name):
                out.update(loop_vars.get(arg.id, ()))
                out.update(tuples.get(arg.id, ()))
    return out


def declared_widen_to_top(root: str) -> Set[str]:
    """String elements of range_rules.py's ``WIDEN_TO_TOP`` tuple."""
    tree = _parse(os.path.join(root, RANGE_RULES_FILE))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id == "WIDEN_TO_TOP"
                   for t in node.targets):
            continue
        if isinstance(node.value, (ast.Tuple, ast.List)):
            return {e.value for e in node.value.elts
                    if isinstance(e, ast.Constant)
                    and isinstance(e.value, str)}
    return set()


def range_rule_coverage_violations(root: str) -> List[str]:
    """Rule 7: shape-ruled op types must be range-ruled or declared in
    WIDEN_TO_TOP, and those two sets must be disjoint."""
    shape_path = os.path.join(root, SHAPE_RULES_FILE)
    range_path = os.path.join(root, RANGE_RULES_FILE)
    if not os.path.exists(shape_path) or not os.path.exists(range_path):
        return []  # synthetic trees without the analysis package
    shaped = _rule_registrations(shape_path, "register_shape_rule")
    ranged = _rule_registrations(range_path, "register_range_rule")
    widen = declared_widen_to_top(root)
    violations = []
    for t in sorted(shaped - ranged - widen):
        violations.append(
            "%s: op type %r has a shape rule but neither a range "
            "transfer rule in %s nor a WIDEN_TO_TOP declaration (the "
            "range engine would widen it SILENTLY — decide its range "
            "story)" % (SHAPE_RULES_FILE, t, RANGE_RULES_FILE))
    for t in sorted(ranged & widen):
        violations.append(
            "%s: op type %r is declared WIDEN_TO_TOP but also has a "
            "range transfer rule (stale declaration — remove one)"
            % (RANGE_RULES_FILE, t))
    return violations


COST_RULES_FILE = os.path.join("paddle_tpu", "analysis",
                               "cost_rules.py")


def declared_zero_cost(root: str) -> Set[str]:
    """String elements of cost_rules.py's ``ZERO_COST`` tuple."""
    tree = _parse(os.path.join(root, COST_RULES_FILE))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id == "ZERO_COST"
                   for t in node.targets):
            continue
        if isinstance(node.value, (ast.Tuple, ast.List)):
            return {e.value for e in node.value.elts
                    if isinstance(e, ast.Constant)
                    and isinstance(e.value, str)}
    return set()


def cost_rule_coverage_violations(root: str) -> List[str]:
    """Rule 10 (the rule-7 mirror for the cost engine): shape-ruled op
    types must carry a cost transfer rule or an explicit ``ZERO_COST``
    declaration, and those two sets must be disjoint."""
    shape_path = os.path.join(root, SHAPE_RULES_FILE)
    cost_path = os.path.join(root, COST_RULES_FILE)
    if not os.path.exists(shape_path) or not os.path.exists(cost_path):
        return []  # synthetic trees without the analysis package
    shaped = _rule_registrations(shape_path, "register_shape_rule")
    costed = _rule_registrations(cost_path, "register_cost_rule")
    zero = declared_zero_cost(root)
    violations = []
    for t in sorted(shaped - costed - zero):
        violations.append(
            "%s: op type %r has a shape rule but neither a cost "
            "transfer rule in %s nor a ZERO_COST declaration (the cost "
            "engine would price it bytes-only SILENTLY — decide its "
            "FLOP story)" % (SHAPE_RULES_FILE, t, COST_RULES_FILE))
    for t in sorted(costed & zero):
        violations.append(
            "%s: op type %r is declared ZERO_COST but also has a cost "
            "transfer rule (stale declaration — remove one)"
            % (COST_RULES_FILE, t))
    return violations


# ------------------------------------------------- rule 8: env knobs
# the trees whose env reads are user-facing knobs (tests drive
# internals and document their knobs next to the cases they shape)
ENV_KNOB_ROOTS = ("paddle_tpu", "tools")
_ENV_KNOB_PREFIX = "PADDLE_TPU_"
_ENV_GET_FNS = ("get", "getenv", "setdefault", "pop")
_ENV_KNOB_RE = re.compile(r"PADDLE_TPU_[A-Z0-9_]+")


def _env_receiver_ok(fn) -> bool:
    """Only ``os.environ.<get/...>`` / ``environ.<get/...>`` /
    ``os.getenv`` receivers count — an unrelated object's
    ``.get("PADDLE_TPU_X")`` or ``.getenv(...)`` (a test's override
    map, a config helper) is not an environment read."""
    if isinstance(fn, ast.Name):  # bare getenv (from os import getenv)
        return fn.id == "getenv"
    if isinstance(fn, ast.Attribute):
        recv = fn.value
        if fn.attr == "getenv":
            return isinstance(recv, ast.Name) and recv.id == "os"
        return (isinstance(recv, ast.Attribute) and recv.attr == "environ") \
            or (isinstance(recv, ast.Name) and recv.id == "environ")
    return False


def env_knob_reads(root: str, files=None) -> Dict[str, List[str]]:
    """{knob name: ["rel/path:line", ...]} for every literal
    ``PADDLE_TPU_*`` env access in ENV_KNOB_ROOTS. Dynamic names
    (concatenation, f-strings, helper indirection) are skipped — the
    deliberate escape hatch every literal-contract rule here shares."""
    targets = []
    for path in (files or iter_py_files(root)):
        rel = os.path.relpath(path, root)
        if rel.split(os.sep)[0] in ENV_KNOB_ROOTS:
            targets.append(path)
    out: Dict[str, List[str]] = {}

    def note(name, rel, lineno):
        if name.startswith(_ENV_KNOB_PREFIX):
            out.setdefault(name, []).append("%s:%d" % (rel, lineno))

    for path in targets:
        rel = os.path.relpath(path, root)
        for node in ast.walk(_parse(path)):
            if isinstance(node, ast.Call):
                fn = node.func
                fn_name = fn.id if isinstance(fn, ast.Name) else (
                    fn.attr if isinstance(fn, ast.Attribute) else None)
                if fn_name in _ENV_GET_FNS and _env_receiver_ok(fn) \
                        and node.args \
                        and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str):
                    note(node.args[0].value, rel, node.lineno)
            elif isinstance(node, ast.Subscript):
                recv = node.value
                is_env = (isinstance(recv, ast.Attribute)
                          and recv.attr == "environ") or (
                    isinstance(recv, ast.Name) and recv.id == "environ")
                if is_env and isinstance(node.slice, ast.Constant) \
                        and isinstance(node.slice.value, str):
                    note(node.slice.value, rel, node.lineno)
    return out


def documented_knobs(root: str) -> Set[str]:
    """Every PADDLE_TPU_* token mentioned anywhere in docs/*.md."""
    out: Set[str] = set()
    docs = os.path.join(root, "docs")
    if not os.path.isdir(docs):
        return out
    for fname in os.listdir(docs):
        if not fname.endswith(".md"):
            continue
        with open(os.path.join(docs, fname), "r", encoding="utf-8") as f:
            out.update(_ENV_KNOB_RE.findall(f.read()))
    return out


def env_knob_violations(root: str, files=None) -> List[str]:
    """Rule 8: scanned knob set ⊆ documented knob set."""
    documented = documented_knobs(root)
    violations = []
    for name, sites in sorted(env_knob_reads(root, files=files).items()):
        if name not in documented:
            violations.append(
                "%s: env knob %r is read in code but appears in no "
                "docs/*.md knob table (document it where its subsystem's "
                "knobs live)" % (sites[0], name))
    return violations


# --------------------------------------- rule 11: artifact sections
EXPORT_FORMAT_FILE = os.path.join("paddle_tpu", "export", "format.py")
# calls whose literal section-name argument (by position) must be
# declared in format.py's SECTIONS tuple — the container schema
_SECTION_CALL_ARG = {"write_section": 2, "read_section": 2,
                     "section_path": 0}


def declared_artifact_sections(root: str) -> Set[str]:
    """Section names in export/format.py's ``SECTIONS = (...)`` tuple."""
    path = os.path.join(root, EXPORT_FORMAT_FILE)
    if not os.path.exists(path):
        return set()
    for node in ast.walk(_parse(path)):
        if not isinstance(node, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id == "SECTIONS"
                   for t in node.targets):
            continue
        if isinstance(node.value, (ast.Tuple, ast.List)):
            return {el.value for el in node.value.elts
                    if isinstance(el, ast.Constant)
                    and isinstance(el.value, str)}
    return set()


def artifact_section_violations(root: str, files=None) -> List[str]:
    """Rule 11: every literal section name handed to
    ``write_section``/``read_section``/``section_path`` must be
    declared in export/format.py's SECTIONS schema tuple. Dynamic
    names (variables, loops over the tuple itself) are skipped like
    rule 3's dynamic sites."""
    if not os.path.exists(os.path.join(root, EXPORT_FORMAT_FILE)):
        return []  # synthetic trees without the export package
    declared = declared_artifact_sections(root)
    fmt_rel = EXPORT_FORMAT_FILE.replace("/", os.sep)
    violations = []
    for path in (files or iter_py_files(root)):
        rel = os.path.relpath(path, root)
        if rel == fmt_rel:
            continue  # the schema file's own helpers/doc examples
        for node in ast.walk(_parse(path)):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            fn_name = fn.id if isinstance(fn, ast.Name) else (
                fn.attr if isinstance(fn, ast.Attribute) else None)
            argpos = _SECTION_CALL_ARG.get(fn_name)
            if argpos is None or len(node.args) <= argpos:
                continue
            arg = node.args[argpos]
            if not isinstance(arg, ast.Constant) \
                    or not isinstance(arg.value, str):
                continue  # dynamic names are the escape hatch
            if arg.value not in declared:
                violations.append(
                    "%s:%d: artifact section %r is passed to %s() but "
                    "not declared in %s SECTIONS (the manifest schema "
                    "tuple is the container format — declare it there)"
                    % (rel, node.lineno, arg.value, fn_name,
                       EXPORT_FORMAT_FILE))
    return violations


ANALYSIS_DIST_FILE = os.path.join("paddle_tpu", "analysis",
                                  "distributed.py")
_DIST_FAMILY_PREFIX = "paddle_analysis_dist"


def registered_op_types(root: str) -> Set[str]:
    """Op types registered via ``register_op(...)`` anywhere under
    ``paddle_tpu/`` (literal first args, the decorator idiom), resolved
    through the same three idioms as rules 7/10."""
    out: Set[str] = set()
    for path in iter_py_files(root):
        rel = os.path.relpath(path, root)
        if rel.split(os.sep)[0] != "paddle_tpu":
            continue
        out |= _rule_registrations(path, "register_op")
    return out


def dist_verifier_violations(root: str, files=None) -> List[str]:
    """Rule 12: the distributed verifier's op vocabulary must exist in
    the op registry, and every ``paddle_analysis_dist_*`` family it
    references must be declared in families.py."""
    dist_path = os.path.join(root, ANALYSIS_DIST_FILE)
    if not os.path.exists(dist_path):
        return []  # synthetic trees without the analysis package
    rel = ANALYSIS_DIST_FILE.replace("/", os.sep)
    violations = []

    registered = registered_op_types(root)
    for var in ("WIRE_OPS", "BARRIER_OPS"):
        names = _module_tuple(dist_path, var)
        if not names:
            violations.append(
                "%s: %s tuple is missing or empty — the verifier's op "
                "vocabulary must be declared as a module-level literal "
                "tuple (rule 12 and the deadlock graph both read it)"
                % (rel, var))
            continue
        for op_type in sorted(names - registered):
            violations.append(
                "%s: %s names op type %r which no register_op(...) "
                "call under paddle_tpu/ registers — a typo here "
                "silently exempts the op from wire typing and the "
                "deadlock graph" % (rel, var, op_type))

    declared = declared_families(root)
    var_to_name = declared_family_vars(root)
    for node in ast.walk(_parse(dist_path)):
        if isinstance(node, ast.ImportFrom) and node.module \
                and node.module.rsplit(".", 1)[-1] == "families":
            for alias in node.names:
                fam = var_to_name.get(alias.name)
                if fam is None:
                    violations.append(
                        "%s:%d: imports %r from observe/families.py "
                        "but no REGISTRY.counter/gauge/histogram "
                        "assignment declares it" % (rel, node.lineno,
                                                    alias.name))
        elif isinstance(node, ast.Constant) \
                and isinstance(node.value, str):
            for m in _FAMILY_RE.finditer(node.value):
                name = m.group(0)
                if not name.startswith(_DIST_FAMILY_PREFIX) \
                        or name == _DIST_FAMILY_PREFIX:
                    continue  # the bare prefix is prose (globs in docs)
                if not any((name[: -len(s)] if s else name) in declared
                           for s in ("",) + _RENDER_SUFFIXES):
                    violations.append(
                        "%s:%d: references family %r which is not "
                        "declared in %s" % (rel, node.lineno, name,
                                            FAMILIES_FILE))
    return violations


def run(root: str = REPO_ROOT) -> List[str]:
    """All violations (empty list = clean). tests/test_repo_lint.py
    asserts on this."""
    return (bare_except_violations(root) + family_ref_violations(root)
            + trace_site_violations(root)
            + pass_docstring_violations(root)
            + fault_site_violations(root)
            + range_rule_coverage_violations(root)
            + env_knob_violations(root)
            + dead_family_violations(root)
            + cost_rule_coverage_violations(root)
            + artifact_section_violations(root)
            + dist_verifier_violations(root))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="AST-based repo lint")
    p.add_argument("--root", default=REPO_ROOT)
    args = p.parse_args(argv)
    violations = run(args.root)
    for v in violations:
        print(v)
    print("%d violation(s)" % len(violations))
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
