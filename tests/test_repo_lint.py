"""tools/repo_lint.py: AST repo lint, wired into the fast tier.

The repo itself must be clean (that IS the CI gate), and the two rule
families are unit-tested against a synthetic repo root so a regression
in the detector itself cannot silently pass the gate.
"""

import os
import sys
import textwrap

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "tools"))

import repo_lint  # noqa: E402


def test_repo_is_clean():
    violations = repo_lint.run(ROOT)
    assert violations == [], "\n".join(violations)


def test_declared_families_parse():
    declared = repo_lint.declared_families(ROOT)
    assert "paddle_executor_steps_total" in declared
    assert "paddle_analysis_findings_total" in declared
    assert "paddle_serving_ttft_seconds" in declared
    assert len(declared) > 40


def test_declared_trace_sites_parse():
    sites = repo_lint.declared_trace_sites(ROOT)
    # the real TRACE_SITES tuple: executor + serving + rpc + resilience
    assert "executor." + "dispatch" in sites
    assert "serving.request." + "done" in sites
    assert "rpc." + "client" in sites
    assert "resilience." + "wedge" in sites
    assert len(sites) >= 15
    # declarations and the runtime tuple agree (the lint parses the AST,
    # the runtime imports the module — they must be the same set)
    from paddle_tpu.observe.families import TRACE_SITES

    assert sites == set(TRACE_SITES)


def _fake_repo(tmp_path, resilience_src, other_src):
    (tmp_path / "paddle_tpu" / "resilience").mkdir(parents=True)
    (tmp_path / "paddle_tpu" / "observe").mkdir(parents=True)
    (tmp_path / "tools").mkdir()
    (tmp_path / "tests").mkdir()
    (tmp_path / "examples").mkdir()
    # family names are assembled by concatenation so the literals in THIS
    # test file never trip the real repo's lint run
    good_counter = "paddle_good" + "_things_total"
    good_hist = "paddle_good" + "_seconds"
    (tmp_path / "paddle_tpu" / "observe" / "families.py").write_text(
        textwrap.dedent("""
        REGISTRY = None
        A = REGISTRY.counter(%r, "help")
        B = REGISTRY.histogram(%r, "help")
        """ % (good_counter, good_hist)))
    (tmp_path / "paddle_tpu" / "resilience" / "mod.py").write_text(
        resilience_src)
    (tmp_path / "paddle_tpu" / "other.py").write_text(other_src)
    # every declared family is referenced from tools/ so the dead-family
    # rule (9) stays quiet in these synthetic repos unless a test
    # removes this file to exercise it deliberately
    (tmp_path / "tools" / "use_families.py").write_text(
        'USED = (%r, %r)\n' % (good_counter, good_hist))
    return str(tmp_path)


def test_bare_except_detected(tmp_path):
    root = _fake_repo(
        tmp_path,
        "def f():\n    try:\n        pass\n    except:\n        pass\n",
        "x = 1\n")
    out = repo_lint.run(root)
    assert len(out) == 1 and "bare `except:`" in out[0]
    # named excepts (and bare excepts OUTSIDE resilience/serving) pass
    root2 = _fake_repo(
        tmp_path / "second",
        "def f():\n    try:\n        pass\n"
        "    except Exception:\n        pass\n",
        "def g():\n    try:\n        pass\n    except:\n        pass\n")
    assert repo_lint.run(root2) == []


def test_undeclared_family_reference_detected(tmp_path):
    # build the names by concatenation so THIS file never trips the lint
    good = "paddle_good" + "_things_total"
    bad = "paddle_typo" + "_things_total"
    root = _fake_repo(
        tmp_path, "x = 1\n",
        'A = "%s"\nB = "%s"\n' % (good, bad))
    out = repo_lint.run(root)
    assert len(out) == 1 and bad in out[0]


def test_render_suffixes_resolve_to_base_family(tmp_path):
    ref = "paddle_good" + "_seconds_bucket"
    root = _fake_repo(tmp_path, "x = 1\n", 'A = "%s"\n' % ref)
    assert repo_lint.run(root) == []


def _fake_repo_with_sites(tmp_path, other_src):
    root = _fake_repo(tmp_path, "x = 1\n", other_src)
    # append a TRACE_SITES declaration to the synthetic families.py
    fam = os.path.join(root, "paddle_tpu", "observe", "families.py")
    with open(fam, "a") as f:
        f.write('TRACE_SITES = ("good.site", "other.site")\n')
    return root


def test_undeclared_trace_site_detected(tmp_path):
    # names assembled by concatenation so THIS file never trips the lint
    src = (
        "def trace_span(s):\n    return s\n"
        'a = trace_span("good" + chr(46) + "site")\n'   # dynamic: skipped
        'b = trace_span("good.site")\n'                  # declared: ok
        'c = trace_span("ty" + "po.site")\n'             # dynamic: skipped
    )
    root = _fake_repo_with_sites(tmp_path, src)
    assert repo_lint.run(root) == []
    bad = (
        "class T:\n"
        "    def trace_event(self, s):\n        return s\n"
        "t = T()\n"
        't.trace_event("typo.site")\n'
    )
    root2 = _fake_repo_with_sites(tmp_path / "second", bad)
    out = repo_lint.run(root2)
    assert len(out) == 1 and "typo.site" in out[0] \
        and "TRACE_SITES" in out[0]


def test_undocumented_pass_detected(tmp_path):
    # a register_pass class without a docstring is a violation; with one
    # (and for non-pass classes) the rule stays silent
    bad = (
        "def register_pass(name):\n"
        "    def deco(cls):\n        return cls\n    return deco\n"
        '@register_pass("p1")\n'
        "class NoDoc:\n    pass\n"
    )
    root = _fake_repo(tmp_path, "x = 1\n", bad)
    out = repo_lint.pass_docstring_violations(root)
    assert len(out) == 1 and "NoDoc" in out[0] and "docstring" in out[0]
    good = (
        "def register_pass(name):\n"
        "    def deco(cls):\n        return cls\n    return deco\n"
        '@register_pass("p1")\n'
        'class WithDoc:\n    """Documented."""\n'
        "class Plain:\n    pass\n"
    )
    root2 = _fake_repo(tmp_path / "second", "x = 1\n", good)
    assert repo_lint.pass_docstring_violations(root2) == []


def test_repo_pass_classes_are_documented():
    # subset of test_repo_is_clean, kept separate so a regression names
    # the rule (same pattern as the trace-site rule below)
    assert repo_lint.pass_docstring_violations(ROOT) == []


def test_optimizer_family_refs_in_passes_are_declared():
    # the paddle_optimizer_* families the pass pipeline records are
    # covered by the undeclared-family rule like everything else — pin
    # it explicitly on the pass package's files
    passes_dir = os.path.join(ROOT, "paddle_tpu", "core", "passes")
    files = [os.path.join(passes_dir, f) for f in os.listdir(passes_dir)
             if f.endswith(".py")]
    assert files, "pass package moved?"
    assert repo_lint.family_ref_violations(ROOT, files=files) == []


def test_optimizer_pass_schema_matches_pipeline():
    # families.py pre-materializes the per-pass series from a plain
    # tuple (imports would cycle); it must track the runtime pipeline
    from paddle_tpu.core.passes import PIPELINE
    from paddle_tpu.observe.families import _OPTIMIZER_PASSES

    assert tuple(name for name, _lvl in PIPELINE) == _OPTIMIZER_PASSES


def test_repo_uses_only_declared_trace_sites():
    # the real tree is clean under the new rule (subset of
    # test_repo_is_clean, kept separate so a trace-site regression
    # names the rule in the failure)
    assert repo_lint.trace_site_violations(ROOT) == []


def _fake_repo_with_fault_sites(tmp_path, other_src):
    root = _fake_repo(tmp_path, "x = 1\n", other_src)
    fam = os.path.join(root, "paddle_tpu", "observe", "families.py")
    with open(fam, "a") as f:
        f.write('FAULT_SITES = ("good.fault", "other.fault")\n')
    return root


def test_undeclared_fault_site_detected(tmp_path):
    # rule 6: literal fault_point()/FaultPlan.arm() sites must be in
    # FAULT_SITES; dynamic sites and declared ones stay silent (names
    # assembled by concatenation so THIS file never trips the lint)
    src = (
        "def fault_point(s):\n    return s\n"
        "class Plan:\n"
        "    def arm(self, s, **kw):\n        return self\n"
        "class Servo:\n"
        "    def arm(self, s):\n        return self\n"
        'a = fault_point("good.fault")\n'            # declared: ok
        'b = fault_point("ty" + "po.fault")\n'       # dynamic: skipped
        'c = Plan().arm("other.fault", steps=(1,))\n'  # declared: ok
        'd = Servo().arm("left")\n'  # non-FaultPlan receiver: not a site
    )
    root = _fake_repo_with_fault_sites(tmp_path, src)
    assert repo_lint.run(root) == []
    bad = (
        "def fault_point(s):\n    return s\n"
        "class Plan:\n"
        "    def arm(self, s, **kw):\n        return self\n"
        'a = fault_point("typo.fault")\n'
        'b = Plan().arm("typo.armed", every=True)\n'
    )
    root2 = _fake_repo_with_fault_sites(tmp_path / "second", bad)
    out = repo_lint.run(root2)
    assert len(out) == 2
    assert any("typo.fault" in v and "fault_point" in v for v in out)
    assert any("typo.armed" in v and "FAULT_SITES" in v for v in out)


def test_repo_uses_only_declared_fault_sites():
    # subset of test_repo_is_clean, kept separate so a fault-site
    # regression names the rule (same pattern as the trace-site rule)
    assert repo_lint.fault_site_violations(ROOT) == []


def test_declared_fault_sites_parse():
    sites = repo_lint.declared_fault_sites(ROOT)
    assert "executor." + "dispatch" in sites
    assert "checkpoint." + "write" in sites
    assert "membership." + "join" in sites
    # declarations and the runtime tuple agree (the lint parses the
    # AST, the runtime imports the module — same contract as
    # TRACE_SITES)
    from paddle_tpu.observe.families import FAULT_SITES

    assert sites == set(FAULT_SITES)


# ------------------------------------------------ rule 7: range coverage
def _range_rule_tree(tmp_path, shape_src, range_src):
    root = tmp_path / "rr"
    (root / "paddle_tpu" / "analysis").mkdir(parents=True)
    (root / "paddle_tpu" / "observe").mkdir(parents=True)
    for d in ("tools", "tests", "examples"):
        (root / d).mkdir()
    (root / "paddle_tpu" / "observe" / "families.py").write_text(
        "REGISTRY = None\n")
    (root / "paddle_tpu" / "analysis" / "shape_rules.py").write_text(
        shape_src)
    (root / "paddle_tpu" / "analysis" / "range_rules.py").write_text(
        range_src)
    return str(root)


def test_range_rule_coverage_detected(tmp_path):
    # an op with a shape rule but no range story trips rule 7; the
    # three registration idioms (literal, *star, for-loop) all resolve
    shape_src = (
        "_ACTS = (\"actA\", \"actB\")\n"
        "register_shape_rule(*_ACTS)(None)\n"
        "for _t in (\"loopC\",):\n"
        "    register_shape_rule(_t)(None)\n"
        "@register_shape_rule(\"litD\", \"uncovE\")\n"
        "def _r(ctx):\n    pass\n")
    range_src = (
        "@register_range_rule(\"actA\", \"litD\")\n"
        "def _rr(ctx):\n    pass\n"
        "WIDEN_TO_TOP = (\"actB\", \"loopC\")\n")
    out = repo_lint.range_rule_coverage_violations(
        _range_rule_tree(tmp_path, shape_src, range_src))
    assert len(out) == 1 and "uncovE" in out[0] \
        and "WIDEN_TO_TOP" in out[0]
    # covered partition: clean
    range_src2 = range_src.replace("(\"actB\", \"loopC\")",
                                   "(\"actB\", \"loopC\", \"uncovE\")")
    assert repo_lint.range_rule_coverage_violations(
        _range_rule_tree(tmp_path / "b", shape_src, range_src2)) == []
    # overlap (declared T with a rule) is a stale declaration
    range_src3 = range_src2.replace("\"actA\", \"litD\"",
                                    "\"actA\", \"litD\", \"actB\"")
    out3 = repo_lint.range_rule_coverage_violations(
        _range_rule_tree(tmp_path / "c", shape_src, range_src3))
    assert len(out3) == 1 and "actB" in out3[0] and "stale" in out3[0]


def test_range_rule_registrations_match_runtime():
    """Schema pin: the AST resolver sees exactly what the runtime
    registries hold — for shape rules AND range rules — so rule 7 can
    never silently diverge from reality."""
    import paddle_tpu  # noqa: F401  (fills the registries)
    from paddle_tpu.analysis.range_rules import WIDEN_TO_TOP
    from paddle_tpu.analysis.ranges import RANGE_RULES
    from paddle_tpu.core.registry import OPS

    ast_shaped = repo_lint._rule_registrations(
        os.path.join(ROOT, repo_lint.SHAPE_RULES_FILE),
        "register_shape_rule")
    ast_ranged = repo_lint._rule_registrations(
        os.path.join(ROOT, repo_lint.RANGE_RULES_FILE),
        "register_range_rule")
    assert ast_shaped == {t for t, d in OPS.items()
                          if d.infer_shape is not None}
    assert ast_ranged == set(RANGE_RULES)
    assert repo_lint.declared_widen_to_top(ROOT) == set(WIDEN_TO_TOP)
    # the partition is total AND disjoint on the real tree
    assert repo_lint.range_rule_coverage_violations(ROOT) == []


# ------------------------------------------------- rule 8: env knobs
def _env_knob_tree(tmp_path, code_src, doc_src=None, tools_src=None):
    root = tmp_path / "ek"
    (root / "paddle_tpu" / "observe").mkdir(parents=True)
    for d in ("tools", "tests", "examples"):
        (root / d).mkdir()
    (root / "paddle_tpu" / "observe" / "families.py").write_text(
        "REGISTRY = None\n")
    (root / "paddle_tpu" / "mod.py").write_text(code_src)
    if tools_src is not None:
        (root / "tools" / "t.py").write_text(tools_src)
    if doc_src is not None:
        (root / "docs").mkdir()
        (root / "docs" / "KNOBS.md").write_text(doc_src)
    return str(root)


def test_undocumented_env_knob_detected(tmp_path):
    # knob names assembled by concatenation so THIS file never trips
    # the real repo's rule-8 scan
    doc = "PADDLE_TPU_" + "DOCD"
    undoc = "PADDLE_TPU_" + "MYSTERY"
    src = (
        "import os\n"
        'a = os.environ.get("%s", "0")\n'
        'b = os.environ["%s"]\n'
        # dynamic names are the deliberate escape hatch
        'c = os.environ.get("PADDLE_TPU_" + "DYN", "")\n'
        # an unrelated dict's .get is NOT an env read
        'd = {}.get("PADDLE_TPU_" "NOTENV", "")\n' % (doc, undoc))
    root = _env_knob_tree(tmp_path, src,
                          doc_src="| `%s` | a knob |\n" % doc)
    out = repo_lint.env_knob_violations(root)
    assert len(out) == 1 and undoc in out[0] and "docs/*.md" in out[0]
    # documenting it cleans the tree
    root2 = _env_knob_tree(
        tmp_path / "b", src,
        doc_src="| `%s` | a | \n| `%s` | b |\n" % (doc, undoc))
    assert repo_lint.env_knob_violations(root2) == []


def test_env_knob_scan_covers_tools_and_getenv(tmp_path):
    knob = "PADDLE_TPU_" + "TOOLKNOB"
    root = _env_knob_tree(
        tmp_path, "x = 1\n",
        tools_src="import os\nv = os.getenv(%r)\n" % knob)
    out = repo_lint.env_knob_violations(root)
    assert len(out) == 1 and knob in out[0]
    # tests/examples are out of scope: the same read there is silent
    root2 = _env_knob_tree(tmp_path / "b", "x = 1\n")
    with open(os.path.join(root2, "tests", "t.py"), "w") as f:
        f.write("import os\nv = os.getenv(%r)\n" % knob)
    assert repo_lint.env_knob_violations(root2) == []


def test_env_knob_scan_matches_real_tree():
    """Schema pin on the real tree: the scanner finds the well-known
    knobs, every scanned knob is documented (the tree is clean under
    rule 8 — subset of test_repo_is_clean, kept separate so a
    regression names the rule), and docs mention at least every
    scanned knob."""
    reads = repo_lint.env_knob_reads(ROOT)
    validate = "PADDLE_TPU_" + "VALIDATE"
    budget = "PADDLE_TPU_" + "DEVICE_HBM_BYTES"
    assert validate in reads and budget in reads
    assert len(reads) >= 20
    documented = repo_lint.documented_knobs(ROOT)
    assert set(reads) <= documented
    assert repo_lint.env_knob_violations(ROOT) == []


# -------------------------------------------------- rule 9: dead families
def test_dead_family_detected(tmp_path):
    """A family declared in families.py but never referenced anywhere
    in paddle_tpu/ or tools/ is a forever-zero series —
    rule 9 names it."""
    root = _fake_repo(tmp_path, "x = 1\n", "x = 1\n")
    os.remove(os.path.join(root, "tools", "use_families.py"))
    out = repo_lint.dead_family_violations(root)
    assert len(out) == 2
    assert any("paddle_good" + "_things_total" in v for v in out)
    assert any("paddle_good" + "_seconds" in v for v in out)
    # and run() carries them too (the rule is wired into the gate)
    assert any("never referenced" in v for v in repo_lint.run(root))


def test_dead_family_reference_forms(tmp_path):
    """All three reference forms keep a family alive: the declaration
    VAR imported by name, the VAR used as a bare name, and the family
    name as a string literal (render-suffix variants included).
    tests/ and examples/ do NOT count as references."""
    counter = "paddle_good" + "_things_total"
    hist_ref = "paddle_good" + "_seconds_bucket"  # suffix resolves to base
    # import of the declaration var A keeps the counter alive; a string
    # literal (with render suffix) keeps the histogram alive
    root = _fake_repo(
        tmp_path, "from ..observe.families import A\n",
        'S = "%s"\n' % hist_ref)
    os.remove(os.path.join(root, "tools", "use_families.py"))
    assert repo_lint.dead_family_violations(root) == []
    # a reference that only lives in tests/ does not count
    root2 = _fake_repo(tmp_path / "b", "x = 1\n", "x = 1\n")
    os.remove(os.path.join(root2, "tools", "use_families.py"))
    with open(os.path.join(root2, "tests", "t.py"), "w") as f:
        f.write('S = "%s"\nfrom x import A, B\n' % counter)
    assert len(repo_lint.dead_family_violations(root2)) == 2


def test_declared_family_vars_parse_real_tree():
    """The VAR-name map over the real families.py resolves the
    telemetry-plane declarations this PR added."""
    fams = repo_lint.declared_family_vars(ROOT)
    assert fams.get("SLO_BREACHES") == "paddle_slo" + "_breaches_total"
    assert fams.get("FLEET_INSTANCES") == "paddle_fleet" + "_instances"
    assert repo_lint.dead_family_violations(ROOT) == []


# ------------------------------------------- rule 10: cost coverage
def _cost_rule_tree(tmp_path, shape_src, cost_src):
    root = tmp_path / "cr"
    (root / "paddle_tpu" / "analysis").mkdir(parents=True)
    (root / "paddle_tpu" / "observe").mkdir(parents=True)
    for d in ("tools", "tests", "examples"):
        (root / d).mkdir()
    (root / "paddle_tpu" / "observe" / "families.py").write_text(
        "REGISTRY = None\n")
    (root / "paddle_tpu" / "analysis" / "shape_rules.py").write_text(
        shape_src)
    (root / "paddle_tpu" / "analysis" / "cost_rules.py").write_text(
        cost_src)
    return str(root)


def test_cost_rule_coverage_detected(tmp_path):
    # an op with a shape rule but no FLOP story trips rule 10; the
    # registration idioms resolve like rule 7's
    shape_src = (
        "_ACTS = (\"actA\", \"actB\")\n"
        "register_shape_rule(*_ACTS)(None)\n"
        "@register_shape_rule(\"litC\", \"uncovD\")\n"
        "def _r(ctx):\n    pass\n")
    cost_src = (
        "@register_cost_rule(\"actA\", \"litC\")\n"
        "def _cr(ctx):\n    pass\n"
        "ZERO_COST = (\"actB\",)\n")
    out = repo_lint.cost_rule_coverage_violations(
        _cost_rule_tree(tmp_path, shape_src, cost_src))
    assert len(out) == 1 and "uncovD" in out[0] and "ZERO_COST" in out[0]
    # covered partition: clean
    cost_src2 = cost_src.replace("(\"actB\",)", "(\"actB\", \"uncovD\")")
    assert repo_lint.cost_rule_coverage_violations(
        _cost_rule_tree(tmp_path / "b", shape_src, cost_src2)) == []
    # overlap (declared zero-cost with a rule) is a stale declaration
    cost_src3 = cost_src2.replace("\"actA\", \"litC\"",
                                  "\"actA\", \"litC\", \"actB\"")
    out3 = repo_lint.cost_rule_coverage_violations(
        _cost_rule_tree(tmp_path / "c", shape_src, cost_src3))
    assert len(out3) == 1 and "actB" in out3[0] and "stale" in out3[0]
    # a tree without the cost engine is out of rule 10's scope
    assert repo_lint.cost_rule_coverage_violations(str(tmp_path)) == []


def test_cost_rule_registrations_match_runtime():
    """Schema pin (rule 7's mirror): the AST resolver sees exactly what
    the runtime COST_RULES registry and ZERO_COST declaration hold, so
    rule 10 can never silently diverge from reality."""
    import paddle_tpu  # noqa: F401  (fills the registries)
    from paddle_tpu.analysis.cost_rules import COST_RULES, ZERO_COST

    ast_costed = repo_lint._rule_registrations(
        os.path.join(ROOT, repo_lint.COST_RULES_FILE),
        "register_cost_rule")
    assert ast_costed == set(COST_RULES)
    assert repo_lint.declared_zero_cost(ROOT) == set(ZERO_COST)
    # the partition is total AND disjoint on the real tree
    assert repo_lint.cost_rule_coverage_violations(ROOT) == []


def _artifact_tree(tmp_path, caller_src,
                   sections=("program", "params")):
    """Synthetic tree with an export package: a SECTIONS schema tuple
    plus one caller module for rule 11 to scan."""
    root = _fake_repo(tmp_path, "x = 1\n", "y = 1\n")
    exp = os.path.join(root, "paddle_tpu", "export")
    os.makedirs(exp)
    with open(os.path.join(exp, "format.py"), "w") as f:
        f.write("SECTIONS = (%s)\n"
                % "".join("%r, " % s for s in sections))
    with open(os.path.join(exp, "artifact.py"), "w") as f:
        f.write(caller_src)
    return root


def test_undeclared_artifact_section_detected(tmp_path):
    src = textwrap.dedent("""
        def save(blobs, manifest, zf):
            write_section(blobs, manifest, "program", b"x")
            write_section(blobs, manifest, "tuned_kernelz", b"x")
            fmt.read_section(manifest, zf, "params")
    """)
    out = repo_lint.artifact_section_violations(
        _artifact_tree(tmp_path, src))
    assert len(out) == 1 and "tuned_kernelz" in out[0]
    assert "SECTIONS" in out[0]


def test_declared_and_dynamic_artifact_sections_pass(tmp_path):
    src = textwrap.dedent("""
        def load(manifest, zf, name):
            read_section(manifest, zf, "program")
            read_section(manifest, zf, name)        # dynamic: skipped
            for s in ("params",):
                section_path(s)                     # dynamic: skipped
            section_path("params")
    """)
    assert repo_lint.artifact_section_violations(
        _artifact_tree(tmp_path, src)) == []


def test_artifact_rule_out_of_scope_without_export_package(tmp_path):
    # a tree with no export/format.py is out of rule 11's scope even
    # if something in it happens to call a write_section-shaped name
    root = _fake_repo(tmp_path, "x = 1\n",
                      'def f(a, b):\n'
                      '    write_section(a, b, "whatever", b"")\n')
    assert repo_lint.artifact_section_violations(root) == []


def test_artifact_sections_match_runtime():
    """Schema pin: the AST-parsed SECTIONS tuple is exactly what the
    runtime container format exposes, and the real tree only passes
    declared names."""
    from paddle_tpu.export.format import SECTIONS

    assert repo_lint.declared_artifact_sections(ROOT) == set(SECTIONS)
    assert repo_lint.artifact_section_violations(ROOT) == []


# ------------------------------------------------- rule 12: dist verifier
def _dist_tree(tmp_path, wire_ops='("send", "recv")',
               barrier_ops='("send_barrier",)', extra_src=""):
    """Synthetic tree with an analysis/distributed.py, a register_op'd
    vocabulary, and a declared paddle_analysis_dist family."""
    root = _fake_repo(tmp_path, "x = 1\n", "y = 1\n")
    fam_name = "paddle_analysis_dist" + "_jobs_total"
    fam = os.path.join(root, "paddle_tpu", "observe", "families.py")
    with open(fam, "a") as f:
        f.write('C = REGISTRY.counter(%r, "help")\n' % fam_name)
    with open(os.path.join(root, "tools", "use_families.py"), "a") as f:
        f.write('USED += (%r,)\n' % fam_name)
    ops_dir = os.path.join(root, "paddle_tpu", "ops")
    os.makedirs(ops_dir)
    with open(os.path.join(ops_dir, "wire_ops.py"), "w") as f:
        f.write(textwrap.dedent("""
            def register_op(name, **kw):
                def deco(fn):
                    return fn
                return deco

            @register_op("send", no_grad=True)
            def _send(): pass

            @register_op("recv", no_grad=True)
            def _recv(): pass

            @register_op("send_barrier", no_grad=True)
            def _sb(): pass
        """))
    adir = os.path.join(root, "paddle_tpu", "analysis")
    os.makedirs(adir)
    with open(os.path.join(adir, "distributed.py"), "w") as f:
        f.write("WIRE_OPS = %s\nBARRIER_OPS = %s\n%s"
                % (wire_ops, barrier_ops, extra_src))
    return root


def test_dist_vocabulary_clean_tree_passes(tmp_path):
    assert repo_lint.dist_verifier_violations(_dist_tree(tmp_path)) == []


def test_dist_vocabulary_unregistered_op_detected(tmp_path):
    root = _dist_tree(tmp_path, wire_ops='("send", "send_varz")')
    out = repo_lint.dist_verifier_violations(root)
    assert len(out) == 1 and "send_varz" in out[0]
    assert "register_op" in out[0]


def test_dist_vocabulary_missing_tuple_detected(tmp_path):
    root = _dist_tree(tmp_path, barrier_ops="()")
    out = repo_lint.dist_verifier_violations(root)
    assert len(out) == 1 and "BARRIER_OPS" in out[0]


def test_dist_family_reference_checked(tmp_path):
    # an import of an undeclared family var and a typo'd literal both trip
    bad_literal = "paddle_analysis_dist" + "_typo_total"
    root = _dist_tree(
        tmp_path,
        extra_src=("from ..observe.families import C, D\n"
                   'NAME = "%s"\n' % bad_literal))
    out = repo_lint.dist_verifier_violations(root)
    assert len(out) == 2
    assert any("'D'" in v for v in out)
    assert any(bad_literal in v for v in out)


def test_dist_rule_out_of_scope_without_verifier(tmp_path):
    root = _fake_repo(tmp_path, "x = 1\n", "y = 1\n")
    assert repo_lint.dist_verifier_violations(root) == []


def test_dist_vocabulary_matches_runtime():
    """Schema pin: the AST-parsed WIRE_OPS/BARRIER_OPS tuples are
    exactly the runtime verifier's, every entry is a registered op, and
    the real tree is rule-12 clean."""
    from paddle_tpu.analysis.distributed import BARRIER_OPS, WIRE_OPS
    from paddle_tpu.core.registry import OPS

    dist_path = os.path.join(ROOT, repo_lint.ANALYSIS_DIST_FILE)
    assert repo_lint._module_tuple(dist_path, "WIRE_OPS") == set(WIRE_OPS)
    assert repo_lint._module_tuple(
        dist_path, "BARRIER_OPS") == set(BARRIER_OPS)
    registered = repo_lint.registered_op_types(ROOT)
    assert set(WIRE_OPS) | set(BARRIER_OPS) <= registered
    assert registered <= set(OPS)
    assert repo_lint.dist_verifier_violations(ROOT) == []
