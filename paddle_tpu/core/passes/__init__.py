"""Graph-optimizing pass pipeline over verified Programs.

The optimizer the PR 5 verifier infrastructure exists to serve (the
reference's ``framework/ir/`` pass registry, rebuilt on core/ir.py's
Graph/Pass/PatternMatcher substrate): an ordered,
``PADDLE_TPU_OPTIMIZE``-leveled (0/1/2, default 2) pipeline the
Executor runs automatically at prepare time — on a CLONE, so the user's
program is untouched and the optimized plan is what the plan cache
holds (the level is part of the cache key; level 0 provably bypasses
everything).

Pipeline (docs/OPTIMIZER.md has the catalog):

====================================== ===== ==============================
pass                                   level what it does
====================================== ===== ==============================
constant_folding_pass                    1   evaluate const-only subgraphs
copy_propagation_pass                    1   drop assign/share_data copies
common_subexpression_elimination_pass    1   merge value-identical ops
dead_op_elimination_pass                 1   fetch-relative backward slice
post_training_quantize_pass              2   int8 PTQ weights (opt-in:
                                             PADDLE_TPU_OPTIMIZE_QUANT)
amp_bf16_pass                            1   stamp bf16 policy onto the IR
                                             (range-aware f32 keep)
fuse_elementwise_pass                    2   chain -> one fused op
====================================== ===== ==============================

Safety: every pass preserves BITWISE semantics (RNG consumers are never
removed, merged, or reordered), and the manager holds two independent
gates after every structural pass — a pass that breaks the program
fails loudly with the pass name (``OptimizerPassError``) instead of
miscompiling:

* **translation validation** (``analysis/tv.py``, on by default,
  ``PADDLE_TPU_OPTIMIZE_TV=0`` opts out): the pass's declared rewrite
  log is machine-checked against before/after reaching-definition
  facts — undeclared removals/creations/reorderings, reads that moved
  past a write, merges of non-equivalent values and dropped root defs
  all fail here, *including rewrites that produce a different but
  still-valid program* (the shape of every historical miscompile);
* **re-verify** (``PADDLE_TPU_OPTIMIZE_VERIFY=0`` opts out): shape
  inference + the error-capable lint rules, catching structurally
  invalid output.

``paddle_optimizer_*`` observe families count programs, removed/folded/
fused ops, per-pass seconds and TV checks/violations;
``optimizer.pipeline`` / ``optimizer.pass`` / ``optimizer.tv`` trace
spans put optimization in the flight recorder.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional, Sequence

from ..ir import Graph, get_pass
from ..program import Program
from . import amp_pass, cse, fold, fuse  # noqa: F401
from . import quantize_pass as _quantize_pass  # noqa: F401

__all__ = [
    "PIPELINE",
    "OptimizerPassError",
    "PassManager",
    "config_key",
    "optimize_level",
    "optimize_program",
    "optimize_for_execution",
    "tv_each_pass",
    "verify_each_pass",
]

# (pass name, minimum PADDLE_TPU_OPTIMIZE level). Order is load-bearing:
# folding creates copies for CSE to see through, copy-prop normalizes
# names so CSE keys match, DCE sweeps what the first three strand, and
# fusion runs on the final surviving op set. families.py mirrors these
# names for the paddle_optimizer_* per-pass schema (pinned by a test).
PIPELINE = (
    ("constant_folding_pass", 1),
    ("copy_propagation_pass", 1),
    ("common_subexpression_elimination_pass", 1),
    ("dead_op_elimination_pass", 1),
    # int8 PTQ AFTER the cleanup passes (quantizing a weight DCE would
    # remove is waste) and BEFORE the fusion passes (the inserted
    # dequantize must not sit inside a fused chain's slot window);
    # PADDLE_TPU_OPTIMIZE_QUANT=0 (default) makes it a provable no-op
    ("post_training_quantize_pass", 2),
    # AMP stamping BEFORE the fusion passes: the stamps ride into the
    # fused descriptors (the replay honors each constituent's __amp__,
    # so stamped == table stays bitwise), and the range-aware f32 keep
    # can see ops a fused chain would otherwise swallow
    ("amp_bf16_pass", 1),
    ("fuse_elementwise_pass", 2),
)


def optimize_level() -> int:
    """Effective ``PADDLE_TPU_OPTIMIZE`` level (0 = bypass, 1 = fold/
    copy-prop/CSE/DCE, 2 = + elementwise fusion; default 2)."""
    try:
        return max(0, min(2, int(os.environ.get(
            "PADDLE_TPU_OPTIMIZE", "2"))))
    except ValueError:
        return 2


def config_key() -> tuple:
    """Every knob that changes WHAT the pipeline produces, for the
    executor's plan-cache key: a run under one optimizer config must
    never be served a plan compiled under another. The quantize opt-in
    and the range-aware amp guard both change output — a quantized plan
    must never serve an unquantized run and vice versa."""
    from .amp_pass import amp_range_guard
    from .fold import fold_max_elems
    from .quantize_pass import quant_min_elems, quantize_enabled

    level = optimize_level()
    if level <= 0:
        return (0,)
    return (level, fold_max_elems(), quantize_enabled(),
            quant_min_elems(), amp_range_guard())


def verify_each_pass() -> bool:
    """``PADDLE_TPU_OPTIMIZE_VERIFY=0`` disables the per-pass re-verify
    (on by default: a broken pass must fail loudly, not miscompile)."""
    return os.environ.get(
        "PADDLE_TPU_OPTIMIZE_VERIFY", "1").lower() not in (
            "0", "false", "off")


def tv_each_pass() -> bool:
    """``PADDLE_TPU_OPTIMIZE_TV=0`` disables per-pass translation
    validation (on by default; like VERIFY it changes checking, never
    output, so it is deliberately not part of ``config_key()``)."""
    from ...analysis.tv import tv_enabled

    return tv_enabled()


class OptimizerPassError(RuntimeError):
    """An optimizing pass broke program invariants: the post-pass verify
    found error findings that were NOT present before the pipeline ran.
    Carries the offending pass name and the new findings."""

    def __init__(self, pass_name: str, findings):
        self.pass_name = pass_name
        self.findings = list(findings)
        lines = ["optimizer pass %r broke program invariants "
                 "(%d new error finding(s)):" % (pass_name,
                                                 len(self.findings))]
        lines += ["  " + f.format() for f in self.findings]
        lines.append("  (set PADDLE_TPU_OPTIMIZE=0 to bypass the "
                     "optimizer; please report this as a pass bug)")
        super().__init__("\n".join(lines))


class PassManager:
    """Run the leveled pipeline over ONE program in place.

    The caller hands in the program to mutate (the Executor clones
    first); ``run`` returns per-pass stats
    ``[{"pass", "ops_before", "ops_after", "seconds", ...}, ...]``.
    ``fetch_names`` anchor the fetch-relative passes (DCE, and the
    "don't rewire a fetched name" guard everywhere); ``scope`` lets
    persistable-by-scope state resolve the way the executor's block
    analysis resolves it.
    """

    def __init__(self, level: Optional[int] = None,
                 fetch_names: Sequence[str] = (), scope=None,
                 verify: Optional[bool] = None,
                 tv: Optional[bool] = None):
        self.level = optimize_level() if level is None else int(level)
        self.fetch_names = tuple(fetch_names or ())
        self.scope = scope
        self.verify = verify_each_pass() if verify is None else bool(verify)
        self.tv = tv_each_pass() if tv is None else bool(tv)
        self.rewrite_log: List[Dict] = []  # per-pass, for --validate

    def run(self, program: Program) -> List[Dict]:
        if self.level <= 0:
            return []
        from ...analysis.tv import ProgramSnapshot
        from ...observe import trace as _tr
        from ...observe.families import (OPTIMIZER_OPS_IN,
                                         OPTIMIZER_OPS_OUT,
                                         OPTIMIZER_OPS_REMOVED,
                                         OPTIMIZER_PASS_SECONDS,
                                         OPTIMIZER_PROGRAMS,
                                         OPTIMIZER_SECONDS)

        t_pipeline = time.perf_counter()
        baseline = self._error_sigs(program) if self.verify else None
        stats: List[Dict] = []
        self.rewrite_log = []
        # trace_span returns a shared NOOP while tracing is off; this
        # runs once per plan-cache miss, so no hot-path guard needed
        with _tr.trace_span("optimizer.pipeline", level=self.level):
            ops_in = len(program.global_block().ops)
            for name, min_level in PIPELINE:
                if self.level < min_level:
                    continue
                p = get_pass(name)
                p.fetch_names = frozenset(self.fetch_names)
                p.scope = self.scope
                before = len(program.global_block().ops)
                # snapshot BEFORE the pass mutates the program in place
                # (O(ops) — the translation validator checks the after-
                # state against this, modulo the pass's rewrite log);
                # tv_exempt passes (attr-only, never a log) skip the cost
                snap = (ProgramSnapshot(program)
                        if self.tv and not getattr(p, "tv_exempt", False)
                        else None)
                t0 = time.perf_counter()
                with _tr.trace_span("optimizer.pass", **{"pass": name}):
                    graph = p.apply(Graph(program))
                    graph.materialize()
                dt = time.perf_counter() - t0
                after = len(program.global_block().ops)
                OPTIMIZER_PASS_SECONDS.labels(**{"pass": name}).observe(dt)
                if after < before:
                    OPTIMIZER_OPS_REMOVED.labels(
                        **{"pass": name}).inc(before - after)
                row = {"pass": name, "ops_before": before,
                       "ops_after": after, "seconds": dt}
                row.update(getattr(p, "stats", None) or {})
                stats.append(row)
                rewrites = getattr(p, "rewrites", None)
                if rewrites:
                    self.rewrite_log.append({"pass": name,
                                             "rewrites": rewrites})
                # translation validation: check the pass's declared
                # rewrite log against before/after dataflow facts.
                # Gated on the pass DECLARING a log (self.rewrites is
                # not None) — a third-party pass with no declaration
                # support still rides the shape re-verify below
                if self.tv and rewrites is not None \
                        and getattr(p, "changed", True):
                    if snap is None:
                        from ...analysis.tv import RewriteViolation
                        raise OptimizerPassError(name, [RewriteViolation(
                            "bad-log", "tv_exempt pass emitted a rewrite "
                            "log (no pre-pass snapshot to check against)")])
                    self._tv_check(name, snap, program, rewrites)
                # re-verify only when the pass changed program structure
                # (a no-op application cannot have broken anything, and
                # the attr-only amp pass never alters the graph) — the
                # per-pass check costs one shape-inference walk, so
                # skipping provably-clean ones keeps the pipeline well
                # under the trace time it saves. A pass that does not
                # declare `self.changed` is ALWAYS verified: op count
                # alone cannot prove an application was a no-op
                # (rewires preserve it)
                if self.verify and getattr(p, "changed", True):
                    self._check(name, program, baseline)
            ops_out = len(program.global_block().ops)
            OPTIMIZER_OPS_IN.inc(ops_in)
            OPTIMIZER_OPS_OUT.inc(ops_out)
            OPTIMIZER_PROGRAMS.labels(level=str(self.level)).inc()
            OPTIMIZER_SECONDS.observe(time.perf_counter() - t_pipeline)
            self._count_rewrites(stats)
        return stats

    # ------------------------------------------ translation validation
    def _tv_check(self, pass_name, snap, program, rewrites):
        from ...analysis.tv import validate_rewrite
        from ...observe import trace as _tr
        from ...observe.families import (OPTIMIZER_TV_CHECKS,
                                         OPTIMIZER_TV_SECONDS,
                                         OPTIMIZER_TV_VIOLATIONS)

        t0 = time.perf_counter()
        with _tr.trace_span("optimizer.tv", **{"pass": pass_name}):
            violations = validate_rewrite(
                snap, program, rewrites,
                fetch_names=self.fetch_names, scope=self.scope)
        OPTIMIZER_TV_CHECKS.labels(**{"pass": pass_name}).inc()
        OPTIMIZER_TV_SECONDS.observe(time.perf_counter() - t0)
        if violations:
            OPTIMIZER_TV_VIOLATIONS.labels(
                **{"pass": pass_name}).inc(len(violations))
            raise OptimizerPassError(pass_name, violations)

    # ------------------------------------------------------ verification
    def _error_sigs(self, program):
        """Multiset of error-finding signatures — the per-pass verify
        only fails on NEW errors, so a program that already carried a
        (tolerated) lint error does not misattribute it to a pass."""
        from collections import Counter

        return Counter((f.rule, f.op_type, f.var)
                       for f in self._findings(program)
                       if f.severity == "error")

    # the lint rules that can produce ERROR findings — the per-pass
    # check only fails on new errors, so warning/info-only rules
    # (dead-var, double-write, int64 boundaries...) are skipped for
    # speed; shape/dtype invariants ride infer_program_shapes
    _ERROR_RULES = ("unregistered-op", "def-before-use",
                    "fetch-undefined", "sub-block")

    def _findings(self, program):
        # deliberately NOT analysis.verify_program: the per-pass check
        # is optimizer-internal and must not inflate the
        # paddle_analysis_* counters once per pass
        from ...analysis import infer_program_shapes, lint_program

        findings = []
        infer_program_shapes(program, findings, fill=True)
        lint_program(program, fetch_names=list(self.fetch_names),
                     scope=self.scope, findings=findings,
                     rules=self._ERROR_RULES)
        return findings

    def _check(self, pass_name, program, baseline):
        findings = [f for f in self._findings(program)
                    if f.severity == "error"]
        from collections import Counter

        now = Counter((f.rule, f.op_type, f.var) for f in findings)
        new = now - baseline
        if new:
            fresh = [f for f in findings
                     if new.get((f.rule, f.op_type, f.var))]
            raise OptimizerPassError(pass_name, fresh)

    @staticmethod
    def _count_rewrites(stats):
        from ...observe.families import (OPTIMIZER_OPS_FOLDED,
                                         OPTIMIZER_OPS_FUSED)

        for row in stats:
            if row.get("folded"):
                OPTIMIZER_OPS_FOLDED.inc(row["folded"])
            if row.get("ops_fused_away"):
                OPTIMIZER_OPS_FUSED.inc(row["ops_fused_away"] +
                                        row.get("chains_fused", 0))


def optimize_program(program: Program, fetch_list=None, scope=None,
                     level: Optional[int] = None,
                     verify: Optional[bool] = None,
                     tv: Optional[bool] = None,
                     return_manager: bool = False):
    """Clone ``program``, run the leveled pipeline on the clone, and
    return ``(optimized_clone, per_pass_stats)``. The input program is
    never mutated; at level 0 the INPUT program itself is returned with
    empty stats (no clone — the bypass really is a bypass), so only
    treat the result as a scratch copy when the level is > 0.
    ``fetch_list`` takes names or Variables; ``tv`` overrides the
    ``PADDLE_TPU_OPTIMIZE_TV`` default. ``return_manager=True`` appends
    the ``PassManager`` to the tuple so callers can read its
    ``rewrite_log`` without re-implementing the clone/bypass contract
    (the ``--validate`` CLIs)."""
    names = [v if isinstance(v, str) else v.name
             for v in (fetch_list or [])]
    mgr = PassManager(level=level, fetch_names=names, scope=scope,
                      verify=verify, tv=tv)
    if mgr.level <= 0:
        return (program, [], mgr) if return_manager else (program, [])
    clone = program.clone()
    stats = mgr.run(clone)
    return (clone, stats, mgr) if return_manager else (clone, stats)


def optimize_for_execution(program: Program, fetch_names: Sequence[str],
                           scope=None,
                           level: Optional[int] = None) -> Program:
    """Executor prepare-time entry: returns the program to lower (the
    optimized clone, or the original untouched at level 0)."""
    lvl = optimize_level() if level is None else level
    if lvl <= 0:
        return program
    optimized, _ = optimize_program(program, fetch_list=list(fetch_names),
                                    scope=scope, level=lvl)
    return optimized
