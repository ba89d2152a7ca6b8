"""tools/pass_fuzz.py: the differential pass fuzzer, wired into CI.

* fast tier: a fixed-seed ~25-program smoke (level 2 vs level 0 bitwise
  + TV-clean) and the five-miscompile knock-out corpus — each corpus
  entry must be (a) differentially clean with its guard in place,
  (b) caught BY THE TRANSLATION VALIDATOR (a ``tv-*`` violation, not
  just a wrong number) with the guard knocked out, and (c) a REAL
  miscompile with the guard out and validation off;
* property tests reusing the fuzzer's program generator for the two
  seams PR 7 round 3 patched by hand: PatternMatcher overlapping-match
  enumeration and Graph.materialize splice anchoring;
* slow tier: the full >=200-seed sweep (the seed is in the test output
  on failure — replay with ``python tools/pass_fuzz.py --start SEED
  --seeds 1``).
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "tools"))

import pass_fuzz  # noqa: E402

SMOKE_SEEDS = 25


def test_pass_fuzz_fixed_seed_smoke():
    """~25 seeded programs, bitwise level 2 vs 0 + TV-clean (the fast-
    tier differential gate; the full sweep rides the slow marker)."""
    failures = {}
    for seed in range(SMOKE_SEEDS):
        problems = pass_fuzz.fuzz_one(seed)
        if problems:
            failures[seed] = problems
    assert not failures, (
        "pass fuzzer found differential failures (replay with "
        "`python tools/pass_fuzz.py --start <seed> --seeds 1`): %r"
        % failures)


@pytest.mark.parametrize("name", sorted(pass_fuzz.CORPUS))
def test_miscompile_corpus_guarded_clean_and_tv_catches(name):
    """The five historical miscompiles: guarded pipeline is clean; with
    the guard knocked out the translation validator trips (tv-* rule);
    with the guard out AND validation off the miscompile is real."""
    r = pass_fuzz.corpus_check(name)
    assert r["clean"] == [], "guarded pipeline not clean: %r" % r
    assert r["tv_trips"], \
        "validator did NOT catch the knocked-out guard: %r" % r
    assert all(rule.startswith("tv-") for rule in r["tv_rules"]), r
    assert r["miscompiles"], (
        "knocked-out guard did not reproduce the miscompile "
        "(guard may be dead code): %r" % r)


# ------------------------------------------------- generator property
def _graph_and_program(seed):
    from paddle_tpu.core.ir import Graph

    main, _startup, _feed, fetch = pass_fuzz.gen_program(seed)
    return Graph(main), main, fetch


@pytest.mark.parametrize("seed", range(6))
def test_patternmatcher_enumerates_every_producer_link_consumer(seed):
    """PR 7 round 3 seam #1: overlapping/adjacent matches. On a random
    program, the generic (op)->(var)->(op) pattern must enumerate
    EXACTLY the set of producer/var/consumer triples the graph edges
    define — overlaps included, nothing double-counted."""
    from paddle_tpu.core.ir import PatternMatcher

    graph, _main, _fetch = _graph_and_program(seed)
    pm = PatternMatcher()
    a = pm.new_op("a")
    v = pm.new_var("v")
    b = pm.new_op("b")
    pm.feeds(a, v)
    pm.feeds(v, b)
    got = {(id(m["a"]), id(m["v"]), id(m["b"])) for m in pm.match(graph)}
    want = set()
    for vn in graph.all_var_nodes():
        for prod in vn.inputs:
            for cons in vn.outputs:
                if cons is not prod:  # an op never binds two roles
                    want.add((id(prod), id(vn), id(cons)))
    assert got == want
    # structural soundness of every binding
    for m in pm.match(graph):
        assert m["v"] in m["a"].outputs
        assert m["b"] in m["v"].outputs


@pytest.mark.parametrize("seed", range(6))
def test_materialize_splice_keeps_def_chains_on_random_programs(seed):
    """PR 7 round 3 seam #2: splice anchoring. After the full level-2
    pipeline (fusion inserts replacement ops, folding inserts
    assign_values), every op's read must still be defined before it —
    no def-before-use, on ANY generated program."""
    from paddle_tpu.analysis import lint_program
    from paddle_tpu.core.passes import optimize_program

    main, _startup, _feed, fetch = pass_fuzz.gen_program(seed)
    opt, _stats = optimize_program(main, fetch_list=list(fetch), level=2)
    findings = lint_program(opt, fetch_names=list(fetch),
                            rules=("def-before-use",))
    assert [f for f in findings if f.severity == "error"] == []


def test_materialize_anchors_replacement_between_producer_and_consumer():
    """Direct splice-anchoring property on a generated graph: replace a
    mid-chain pure op with a hand-built equivalent; materialize must
    place the replacement after its input's producer and before its
    output's first consumer."""
    from paddle_tpu.analysis.dataflow import Dataflow

    graph, main, fetch = _graph_and_program(3)
    df = Dataflow(main, fetch_names=fetch)
    victim = None
    for node in graph.op_nodes:
        op = node.op
        if op.type in ("relu", "tanh", "sigmoid") and df.can_remove(op):
            victim = node
            break
    assert victim is not None, "generator produced no pure unary op?"
    ins = {s: list(ns) for s, ns in victim.op.inputs.items()}
    outs = {s: list(ns) for s, ns in victim.op.outputs.items()}
    graph.remove_op_node(victim)
    graph.insert_op_node(victim.op.type, ins, outs,
                         provenance_from=[victim.op])
    out = graph.materialize()
    df2 = Dataflow(out, fetch_names=fetch)
    new_op = [op for op in out.global_block().ops
              if op is not victim.op and op.type == victim.op.type
              and op.outputs == outs]
    pos = df2.pos_of(new_op[0])
    for n in new_op[0].input_names():
        w = df2.last_write_before(n, pos)
        assert w is not None or df2.write_positions(n) == (), \
            "replacement op spliced before its producer"
    for n in new_op[0].output_names():
        assert all(r >= pos for r in df2.read_positions(n)), \
            "replacement op spliced after a consumer"


# ---------------------------------------------------------- slow sweep
@pytest.mark.slow
def test_pass_fuzz_full_sweep_200_seeds():
    """Acceptance: >=200 seeded programs, bitwise level 2 vs level 0 and
    TV-clean. Failures print the seed for deterministic replay."""
    failures = {}
    for seed in range(200):
        problems = pass_fuzz.fuzz_one(seed)
        if problems:
            failures[seed] = problems
    assert not failures, (
        "pass fuzzer sweep failed (replay each with `python "
        "tools/pass_fuzz.py --start <seed> --seeds 1`): %r" % failures)


def test_generator_emits_quant_clip_and_activation_patterns():
    """The generator's vocabulary covers the quantization-adjacent
    shapes: clip, fake_quantize (simulation ops entering via
    transpilers), and the widened activation set — so the differential
    sweep exercises them against fold/CSE/fusion."""
    seen = set()
    for seed in range(60):
        main, _startup, _feed, _fetch = pass_fuzz.gen_program(seed)
        seen.update(op.type for op in main.global_block().ops)
        if {"clip", "fake_quantize_abs_max", "gelu"} <= seen:
            break
    assert "clip" in seen
    assert "fake_quantize_abs_max" in seen
    assert "gelu" in seen


def test_quantize_corpus_entry_uses_tolerance_harness():
    """The quantize entry's parity leg is the STATED tolerance, not
    bitwise (quantized programs only): the guarded pipeline really
    quantizes (outputs differ bitwise from level 0) yet reports clean."""
    import numpy as np

    cfg = pass_fuzz._corpus_cfg("quantize_wrong_scale")
    assert cfg["tolerance"] and cfg["env"] == {
        "PADDLE_TPU_OPTIMIZE_QUANT": "1"}
    main, startup, feed, fetch = pass_fuzz.build_corpus_program(
        "quantize_wrong_scale")
    base, _ = pass_fuzz.run_program(main, startup, feed, fetch, level=0,
                                    env=cfg["env"])
    opt, _ = pass_fuzz.run_program(main, startup, feed, fetch, level=2,
                                   env=cfg["env"])
    diffs = [not np.array_equal(a, b)
             for a, b in zip(base[0], opt[0])]
    assert any(diffs), "guarded quantize produced bitwise-equal output"
    assert pass_fuzz.diff_run(main, startup, feed, fetch,
                              tolerance=cfg["tolerance"],
                              env=cfg["env"]) == []


def test_peak_invariant_holds_on_fixed_seeds():
    """The post-pipeline memory invariant in isolation: the default
    level-2 pipeline never increases the statically predicted peak on
    seeded programs (fuzz_one also runs it per seed; this pins the
    helper's contract directly, incl. that it runs the optimizer on a
    CLONE — the input program's op count must not change)."""
    for seed in (0, 3, 11):
        main, _startup, _feed, fetch = pass_fuzz.gen_program(seed)
        n_ops = len(main.global_block().ops)
        assert pass_fuzz.peak_invariant(main, fetch) == []
        assert len(main.global_block().ops) == n_ops
