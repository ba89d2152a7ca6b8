#!/usr/bin/env python
"""Differential pass fuzzer: seeded random programs, level 2 vs level 0.

The optimizer's correctness story has three legs — the dataflow engine
every pass queries (``analysis/dataflow.py``), the per-pass translation
validator (``analysis/tv.py``), and THIS harness, which closes the loop
empirically: generate a seeded random program exercising every hazard
the historical miscompiles involved (elementwise chains, in-place
optimizer updates, assign copies, shared subexpressions, dead branches,
RNG consumers, conditional sub-blocks), run it at ``PADDLE_TPU_OPTIMIZE``
level 2 and level 0 on CPU, and require BITWISE-identical fetches and
persistable state plus a TV-clean pipeline. One seed = one program =
one fully deterministic replay (the seed is printed on every failure).

    python tools/pass_fuzz.py --seeds 200            # sweep
    python tools/pass_fuzz.py --seeds 1 --start 1234 # replay one seed
    python tools/pass_fuzz.py --corpus               # the five miscompiles
    python tools/pass_fuzz.py --json                 # machine-readable

The **corpus** re-expresses the five confirmed historical miscompiles
(CSE write-versioning, copy-prop aliasing, materialize ordering, fusion
read-after-write, a wrong quantization scale) as tiny
programs, each paired with a **knock-out** that disables exactly the
guard whose absence caused the original bug (the passes expose the
guards as documented class-attr seams; the materialize knock-out
reinstates the pre-review min-consumer splice). ``--corpus`` proves,
per entry: (a) the guarded pipeline is differentially clean, (b) with
the guard knocked out the translation validator trips
(``OptimizerPassError`` carrying a ``tv-*`` violation — NOT just a
wrong number), and (c) with the guard out AND validation off the
miscompile is real (bitwise diff or broken program). A future pass
regression therefore cannot land silently: either TV names it, or this
harness bisects it to a seed.

Every fuzzed seed additionally holds a **post-pipeline memory
invariant**: the default level-2 pipeline must never INCREASE the
statically predicted peak (``analysis/memory.py`` — fold/copy-prop/
CSE/DCE/fusion only remove or merge tensors); violations print the
seed like every other mismatch.

Exit code: 0 = all clean, 1 = any failure, 2 = bad usage.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import random  # noqa: E402

import numpy as np  # noqa: E402

D = 8  # feature width of every generated tensor
B = 4  # feed batch rows

_UNARY = ("relu", "tanh", "sigmoid", "gelu", "softplus", "square")
_BINARY = ("elementwise_add", "elementwise_sub", "elementwise_mul",
           "elementwise_max", "elementwise_min")


# ------------------------------------------------------------ generator
def gen_program(seed):
    """Build one seeded random (main, startup, feed, fetch_names)
    program. Pure function of the seed: layer choices, constants and
    wiring all come from ``random.Random(seed)``; the feed comes from
    ``np.random.RandomState(seed)``."""
    import paddle_tpu as fluid

    rng = random.Random(seed)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 7  # dropout RNG chain: fixed, level-independent
    startup.random_seed = 7
    fetch = []
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            L = fluid.layers
            x = L.data(name="x", shape=[D], dtype="float32")
            vals = [x]
            recipes = []  # (kind, payload) replayable for shared subexprs
            n_params = 0

            def emit(kind, payload):
                recipes.append((kind, payload))
                return _apply(L, vals, kind, payload)

            for _step in range(rng.randint(10, 22)):
                roll = rng.random()
                if roll < 0.30:
                    emit("unary", (rng.choice(_UNARY),
                                   rng.randrange(len(vals))))
                elif roll < 0.45:
                    emit("binary", (rng.choice(_BINARY),
                                    rng.randrange(len(vals)),
                                    rng.randrange(len(vals))))
                elif roll < 0.55:
                    emit("scale", (round(rng.uniform(-1.2, 1.2), 3),
                                   round(rng.uniform(-0.5, 0.5), 3),
                                   rng.randrange(len(vals))))
                elif roll < 0.65 and recipes:
                    # shared subexpression: REPLAY an earlier recipe
                    # verbatim — structurally identical ops, CSE fodder
                    emit(*recipes[rng.randrange(len(recipes))])
                elif roll < 0.70:
                    emit("copy", (rng.randrange(len(vals)),))
                elif roll < 0.76:
                    emit("const_chain", (round(rng.uniform(0.5, 2.0), 3),
                                         rng.randint(1, 4),
                                         rng.randrange(len(vals))))
                elif roll < 0.80:
                    emit("clip", (round(rng.uniform(-1.0, -0.1), 3),
                                  round(rng.uniform(0.1, 1.0), 3),
                                  rng.randrange(len(vals))))
                elif roll < 0.84:
                    # fake-quantize simulation: pure, deterministic,
                    # CSE/fold-adjacent (quant-dequant of a live value)
                    emit("fake_quantize", (len(recipes),
                                           rng.randrange(len(vals))))
                elif roll < 0.88:
                    emit("dropout", (rng.choice((0.2, 0.5)),
                                     rng.randrange(len(vals))))
                elif roll < 0.92:
                    # dead branch: never fetched, reduced to a scalar
                    d = L.tanh(vals[rng.randrange(len(vals))])
                    L.reduce_mean(L.sigmoid(d))
                elif roll < 0.97:
                    n_params += 1
                    _param_update_block(fluid, L, rng, vals, n_params,
                                        seed)
                else:
                    _cond_block(fluid, L, rng, vals)
            loss = L.reduce_mean(vals[-1])
            fetch.append(loss.name)
            if len(vals) > 2 and rng.random() < 0.5:
                fetch.append(L.reduce_mean(
                    vals[rng.randrange(1, len(vals))]).name)
    feed = {"x": np.random.RandomState(seed).uniform(
        -1.0, 1.0, size=(B, D)).astype(np.float32)}
    return main, startup, feed, fetch


def _apply(L, vals, kind, payload):
    if kind == "unary":
        op, i = payload
        vals.append(getattr(L, op)(vals[i % len(vals)]))
    elif kind == "binary":
        op, i, j = payload
        fn = {"elementwise_add": L.elementwise_add,
              "elementwise_sub": L.elementwise_sub,
              "elementwise_mul": L.elementwise_mul,
              "elementwise_max": L.elementwise_max,
              "elementwise_min": L.elementwise_min}[op]
        vals.append(fn(vals[i % len(vals)], vals[j % len(vals)]))
    elif kind == "scale":
        s, b, i = payload
        vals.append(L.scale(vals[i % len(vals)], scale=s, bias=b))
    elif kind == "copy":
        (i,) = payload
        vals.append(L.assign(vals[i % len(vals)]))
    elif kind == "const_chain":
        v0, n, i = payload
        c = L.fill_constant([D], "float32", v0)
        for _ in range(n):
            c = L.scale(c, scale=1.1, bias=0.1)
        vals.append(L.elementwise_add(vals[i % len(vals)], c))
    elif kind == "dropout":
        p, i = payload
        vals.append(L.dropout(vals[i % len(vals)], dropout_prob=p))
    elif kind == "clip":
        lo, hi, i = payload
        vals.append(L.clip(vals[i % len(vals)], min=lo, max=hi))
    elif kind == "fake_quantize":
        tag, i = payload
        vals.append(_fake_quantize(vals[i % len(vals)], tag))
    else:  # pragma: no cover - recipe vocabulary is closed
        raise ValueError(kind)


def _fake_quantize(x, tag):
    """Append a fake_quantize_abs_max op by hand (no layers wrapper —
    the quant family enters programs through transpilers). A REPLAYED
    recipe (shared-subexpression fodder) re-emits the same op over the
    same input but needs fresh output names, so the name carries both
    the recipe tag and the input it quantizes."""
    block = x.block
    base = "fz_fq_%s_%s" % (tag, x.name.replace("@", "_"))
    n = 0
    while block.has_var("%s_%d.out" % (base, n)):
        n += 1
    out = block.create_var(name="%s_%d.out" % (base, n), dtype="float32")
    sc = block.create_var(name="%s_%d.scale" % (base, n), dtype="float32")
    block.append_op("fake_quantize_abs_max", {"X": [x.name]},
                    {"Out": [out.name], "OutScale": [sc.name]},
                    {"bit_length": 8})
    return out


def _sgd(block, param, grad, lr):
    block.append_op("sgd",
                    {"Param": [param.name], "Grad": [grad.name],
                     "LearningRate": [lr.name]},
                    {"ParamOut": [param.name]},
                    {"__op_role__": "optimize"})


def _param_update_block(fluid, L, rng, vals, idx, seed):
    """In-place optimizer update + optional pre-update snapshot: the
    copy-prop/CSE hazard shapes, wired into the live value stream."""
    w = L.create_parameter([D], "float32", name="fz_w_%d_%d"
                           % (seed % 1000, idx))
    lr = L.fill_constant([1], "float32", 0.05)
    snap = L.assign(w) if rng.random() < 0.6 else None
    pre = L.tanh(w) if rng.random() < 0.5 else None
    grad = L.scale(w, scale=0.3)  # reads w: RAW fodder around the sgd
    block = w.block
    _sgd(block, w, grad, lr)
    if rng.random() < 0.5:  # a second, ADJACENT update
        w2 = L.create_parameter([D], "float32", name="fz_v_%d_%d"
                                % (seed % 1000, idx))
        _sgd(block, w2, grad, lr)
        vals.append(L.elementwise_add(vals[-1], w2))
    post = L.tanh(w)  # reads the UPDATED w: versioned-CSE fodder vs pre
    vals.append(L.elementwise_add(vals[-1], post))
    if pre is not None:
        vals.append(L.elementwise_add(vals[-1], pre))
    if snap is not None:
        vals.append(L.elementwise_add(vals[-1], snap))


def _cond_block(fluid, L, rng, vals):
    """Conditional sub-block writing a pre-created var (layers.cond):
    pins its names, exercises sub-block parent-chain resolution."""
    z = L.fill_constant([D], "float32", 0.0)
    pred = L.less_than(L.reduce_mean(vals[-1]),
                       L.fill_constant([1], "float32", 0.25))

    def then():
        L.assign(L.fill_constant([D], "float32", 1.0), output=z)

    L.cond(pred, then)
    vals.append(L.elementwise_add(vals[-1], z))


# ----------------------------------------------------------- harness
@contextlib.contextmanager
def _env_overrides(env):
    old = {}
    for k, v in (env or {}).items():
        old[k] = os.environ.get(k)
        os.environ[k] = str(v)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def run_program(main, startup, feed, fetch, level, steps=2, env=None):
    """Run ``steps`` executor steps at the given optimize level in a
    fresh scope; returns (per-step fetch arrays, persistable arrays).
    ``env`` holds extra environment overrides for the run (the quantize
    corpus entry opts the PTQ pass in with it)."""
    import paddle_tpu as fluid
    from paddle_tpu.core.scope import Scope, scope_guard

    overrides = dict(env or {})
    overrides["PADDLE_TPU_OPTIMIZE"] = str(level)
    with _env_overrides(overrides):
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup, scope=scope)
            outs = []
            for _ in range(steps):
                vals = exe.run(main, feed=dict(feed) if feed else None,
                               fetch_list=list(fetch), scope=scope)
                outs.append([np.asarray(v) for v in vals])
            persist = {}
            for var in main.global_block().vars.values():
                if var.persistable and scope.has_var(var.name):
                    persist[var.name] = np.asarray(
                        scope.find_var(var.name))
        return outs, persist


def _arrays_match(a, b, tolerance):
    if tolerance is None:
        return a.tobytes() == b.tobytes()
    return a.shape == b.shape and bool(np.allclose(a, b, **tolerance))


def diff_run(main, startup, feed, fetch, steps=2, tolerance=None,
             env=None):
    """Differential check: level 2 vs level 0. BITWISE by default;
    ``tolerance`` (an ``np.allclose`` kwargs dict) switches to the
    stated-tolerance parity harness — the contract for QUANTIZED
    programs only, where bitwise is impossible by design. Returns a
    list of mismatch descriptions (empty = clean). An
    OptimizerPassError or execution failure at level 2 is reported as a
    failure, never swallowed."""
    base, base_p = run_program(main, startup, feed, fetch, level=0,
                               steps=steps, env=env)
    try:
        opt, opt_p = run_program(main, startup, feed, fetch, level=2,
                                 steps=steps, env=env)
    except Exception as e:  # OptimizerPassError, lowering KeyError, ...
        return ["level-2 run failed: %s: %s" % (type(e).__name__, e)]
    word = "bitwise" if tolerance is None else (
        "beyond tolerance %r" % (tolerance,))
    problems = []
    for s, (a, b) in enumerate(zip(base, opt)):
        for i, (va, vb) in enumerate(zip(a, b)):
            if not _arrays_match(va, vb, tolerance):
                problems.append("step %d fetch %r differs %s"
                                % (s, fetch[i], word))
    for name in sorted(set(base_p) | set(opt_p)):
        pa, pb = base_p.get(name), opt_p.get(name)
        if pa is None or pb is None or not _arrays_match(pa, pb,
                                                         tolerance):
            problems.append("persistable %r differs %s" % (name, word))
    return problems


def peak_invariant(main, fetch, batch_size=B):
    """Post-pipeline memory invariant: the default level-2 pipeline
    (fold/copy-prop/CSE/DCE/fusion — quantize is opt-in and NOT part
    of this check) must never INCREASE the statically predicted peak
    (analysis/memory.py): every default pass removes or merges
    tensors, so a higher optimized peak means either a pass
    materialized something it should not have, or the byte model
    mis-attributes a lifetime. Returns a problem list (empty = holds);
    failures print alongside the seed like every fuzz mismatch."""
    from paddle_tpu.analysis.memory import MemoryAnalysis
    from paddle_tpu.core.passes import optimize_program

    base = MemoryAnalysis(main,
                          fetch_names=fetch).peak_bytes(batch_size)
    opt_prog = optimize_program(main, fetch_list=list(fetch), level=2)[0]
    opt = MemoryAnalysis(opt_prog,
                         fetch_names=fetch).peak_bytes(batch_size)
    if opt > base:
        return ["level-2 pipeline INCREASED the predicted peak: "
                "%d -> %d bytes at batch %d" % (base, opt, batch_size)]
    return []


def fuzz_one(seed, steps=2):
    """Generate + differentially check ONE seed (bitwise level 2 vs 0
    plus the predicted-peak invariant). Returns problem list."""
    main, startup, feed, fetch = gen_program(seed)
    problems = diff_run(main, startup, feed, fetch, steps=steps)
    main2, _, _, fetch2 = gen_program(seed)  # diff_run's runs filled
    problems += peak_invariant(main2, fetch2)  # shapes; check pristine
    return problems


# ------------------------------------------------------------- corpus
# The five confirmed historical miscompiles, as programs + knock-outs.
def _corpus_cse_write_versioning(fluid, L):
    """PR 7: CSE merged identical reads AROUND an in-place write."""
    s = L.create_parameter([D], "float32", name="cwv_s")
    r1 = L.tanh(s)
    lr = L.fill_constant([1], "float32", 0.5)
    _sgd(s.block, s, L.scale(s, scale=1.0), lr)  # in-place update of s
    r2 = L.tanh(s)  # same op+input NAME, different write version
    out = L.reduce_mean(L.elementwise_add(r1, r2))
    return [out.name]


def _corpus_copy_prop_aliasing(fluid, L):
    """PR 7: a pre-update snapshot copy dropped as if it were an alias."""
    w = L.create_parameter([D], "float32", name="cpa_w")
    snap = L.assign(w)  # SNAPSHOT of w before the update
    lr = L.fill_constant([1], "float32", 0.5)
    _sgd(w.block, w, L.scale(w, scale=1.0), lr)
    out = L.reduce_mean(L.elementwise_add(snap, L.scale(w, scale=0.0)))
    return [out.name]


def _corpus_materialize_ordering(fluid, L):
    """PR 7 round 3: min-consumer splicing put fused chain B before the
    fused chain A it consumes."""
    x = L.data(name="x", shape=[D], dtype="float32")
    out_a = L.tanh(L.relu(x))          # chain A
    out_b = L.sigmoid(L.tanh(out_a))   # chain B consumes A
    s_b = L.reduce_mean(out_b)         # B's consumer FIRST
    s_a = L.reduce_mean(out_a)         # A's consumer after
    return [s_b.name, s_a.name]


def _corpus_fusion_read_after_write(fluid, L):
    """PR 7 round 4: a chain's external read moved past an in-place
    write when the fused body ran at the chain tail's slot."""
    w = L.create_parameter([D], "float32", name="raw_w")
    t1 = L.relu(w)  # reads PRE-update w
    lr = L.fill_constant([1], "float32", 0.5)
    _sgd(w.block, w, L.scale(w, scale=1.0), lr)  # in-place update
    t2 = L.tanh(t1)  # relu->tanh chain would fuse at THIS slot
    out = L.reduce_mean(L.elementwise_add(t2, w))
    return [out.name]


def _corpus_quantize_wrong_scale(fluid, L):
    """PR 14: the int8 PTQ pass with deliberately wrong (quartered)
    per-channel scales — values past 25% of the channel max clip, so
    the dequantized weight is badly wrong. The guarded pipeline must
    stay within the stated QUANT_TOLERANCE; the knocked-out one must
    trip the TV quantize-record scale check, and with validation off
    the parity harness must catch the real accuracy hole."""
    x = L.data(name="x", shape=[D], dtype="float32")
    w = L.create_parameter([D, D], "float32", name="qws_w")
    h = L.mul(x, w)
    out = L.reduce_mean(L.tanh(h))
    return [out.name, h.name]


@contextlib.contextmanager
def _patch_attr(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def _knockout_cse():
    from paddle_tpu.core.passes.cse import \
        CommonSubexpressionEliminationPass as P

    with _patch_attr(P, "versioned", False):
        yield


@contextlib.contextmanager
def _knockout_copy_prop():
    from paddle_tpu.core.passes.cse import CopyPropagationPass as P

    with _patch_attr(P, "snapshot_guard", False):
        yield


@contextlib.contextmanager
def _knockout_fusion_raw():
    from paddle_tpu.core.passes.fuse import FuseElementwisePass as P

    with _patch_attr(P, "move_guard", False):
        yield


def _buggy_materialize(self):
    """The pre-PR 7-round-3 Graph.materialize: EVERY new op splices at
    min(consumer position) — no replacement anchoring. Resurrected only
    as the materialize-ordering knock-out."""
    block = self.program.global_block()
    old_pos = {id(op): i for i, op in enumerate(block.ops)}
    alive = {id(n.op) for n in self.op_nodes}
    keyed = sorted((old_pos[id(op)], k, op)
                   for k, op in enumerate(block.ops) if id(op) in alive)
    order = [op for _i, _k, op in keyed]
    for node in (n for n in self.op_nodes if id(n.op) not in old_pos):
        pos = {id(op): i for i, op in enumerate(order)}
        consumers = [pos[id(c.op)] for vn in node.outputs
                     for c in vn.outputs
                     if c is not node and id(c.op) in pos]
        if consumers:
            at = min(consumers)
        else:
            producers = [pos[id(p.op)] for vn in node.inputs
                         for p in vn.inputs
                         if p is not node and id(p.op) in pos]
            at = max(producers) + 1 if producers else len(order)
        order.insert(at, node.op)
    block.ops = order
    self.program._bump()
    return self.program


@contextlib.contextmanager
def _knockout_materialize():
    from paddle_tpu.core.ir import Graph

    with _patch_attr(Graph, "materialize", _buggy_materialize):
        yield


@contextlib.contextmanager
def _knockout_quant_scale():
    from paddle_tpu.core.passes.quantize_pass import \
        PostTrainingQuantizePass as P

    with _patch_attr(P, "scale_guard", False):
        yield


CORPUS = {
    "cse_write_versioning": (_corpus_cse_write_versioning, _knockout_cse),
    "copy_prop_aliasing": (_corpus_copy_prop_aliasing,
                           _knockout_copy_prop),
    "materialize_ordering": (_corpus_materialize_ordering,
                             _knockout_materialize),
    "fusion_read_after_write": (_corpus_fusion_read_after_write,
                                _knockout_fusion_raw),
    "quantize_wrong_scale": (_corpus_quantize_wrong_scale,
                             _knockout_quant_scale),
}

# per-entry deviations from the bitwise default: the quantize entry
# opts the PTQ pass in, compares under the pass's STATED tolerance (the
# quantized-programs-only parity contract), and needs the run scope
# (the pass derives scales from concrete scope weights, and the TV
# check re-derives them from the same scope).
CORPUS_CFG = {
    "quantize_wrong_scale": {
        "env": {"PADDLE_TPU_OPTIMIZE_QUANT": "1"},
        "tolerance": "QUANT_TOLERANCE",  # resolved from quantize_pass
        "needs_scope": True,
    },
}


def _corpus_cfg(name):
    cfg = dict(CORPUS_CFG.get(name, ()))
    if cfg.get("tolerance") == "QUANT_TOLERANCE":
        from paddle_tpu.core.passes.quantize_pass import QUANT_TOLERANCE

        cfg["tolerance"] = dict(QUANT_TOLERANCE)
    return cfg


def build_corpus_program(name):
    """(main, startup, feed, fetch) for one corpus entry."""
    import paddle_tpu as fluid

    builder, _ko = CORPUS[name]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 7
    startup.random_seed = 7
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            fetch = builder(fluid, fluid.layers)
    feed = {}
    if "x" in main.global_block().vars:
        feed = {"x": np.random.RandomState(0).uniform(
            -1.0, 1.0, size=(B, D)).astype(np.float32)}
    return main, startup, feed, fetch


def _corpus_scope(main, startup, env):
    """Fresh scope with the startup program run (the quantize entry's
    pass + TV check both need concrete weights)."""
    import paddle_tpu as fluid
    from paddle_tpu.core.scope import Scope, scope_guard

    scope = Scope()
    with _env_overrides(env), scope_guard(scope):
        fluid.Executor().run(startup, scope=scope)
    return scope


def corpus_check(name):
    """Three-way proof for one corpus entry (see module docstring):
    returns {"clean": [...], "tv_trips": bool, "tv_rules": [...],
    "miscompiles": bool, "knocked_out_problems": [...]}. Entries with a
    CORPUS_CFG row run under its env/tolerance/scope config (the
    quantize entry's parity leg is the stated-tolerance harness, not
    bitwise)."""
    from paddle_tpu.core.passes import OptimizerPassError, optimize_program

    _builder, knockout = CORPUS[name]
    cfg = _corpus_cfg(name)
    env = cfg.get("env")
    tolerance = cfg.get("tolerance")
    result = {}
    # (a) guarded pipeline: differentially clean
    main, startup, feed, fetch = build_corpus_program(name)
    result["clean"] = diff_run(main, startup, feed, fetch,
                               tolerance=tolerance, env=env)
    # (b) guard knocked out: the translation validator trips
    with knockout(), _env_overrides(env):
        main, startup, feed, fetch = build_corpus_program(name)
        scope = _corpus_scope(main, startup, env) \
            if cfg.get("needs_scope") else None
        try:
            optimize_program(main, fetch_list=list(fetch), level=2,
                             scope=scope, verify=False, tv=True)
            result["tv_trips"] = False
            result["tv_rules"] = []
        except OptimizerPassError as e:
            result["tv_trips"] = True
            result["tv_rules"] = sorted(
                {getattr(f, "rule", "?") for f in e.findings})
        # (c) guard out AND validation off: the miscompile is REAL
        main, startup, feed, fetch = build_corpus_program(name)
        problems = diff_run(
            main, startup, feed, fetch, tolerance=tolerance,
            env=dict(env or {}, PADDLE_TPU_OPTIMIZE_TV="0",
                     PADDLE_TPU_OPTIMIZE_VERIFY="0"))
        result["miscompiles"] = bool(problems)
        result["knocked_out_problems"] = problems
    return result


# ---------------------------------------------------------------- CLI
def main(argv=None):
    p = argparse.ArgumentParser(
        description="differential pass fuzzer (level 2 vs level 0, "
                    "bitwise + TV-clean)")
    p.add_argument("--seeds", type=int, default=25,
                   help="number of seeds to sweep (default 25)")
    p.add_argument("--start", type=int, default=0,
                   help="first seed (replay a failure with "
                        "--start SEED --seeds 1)")
    p.add_argument("--steps", type=int, default=2,
                   help="executor steps per program (default 2)")
    p.add_argument("--corpus", action="store_true",
                   help="run the five-miscompile knock-out corpus "
                        "instead of the random sweep")
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)

    failures = 0
    report = {}
    if args.corpus:
        for name in sorted(CORPUS):
            r = corpus_check(name)
            ok = (not r["clean"]) and r["tv_trips"] and r["miscompiles"]
            failures += 0 if ok else 1
            report[name] = r
            if not args.json:
                print("== corpus %-26s %s" % (name, "ok" if ok else
                                              "FAIL %r" % (r,)))
    else:
        for seed in range(args.start, args.start + args.seeds):
            problems = fuzz_one(seed, steps=args.steps)
            report[str(seed)] = problems
            if problems:
                failures += 1
                print("== seed %d FAILED (replay: python "
                      "tools/pass_fuzz.py --start %d --seeds 1)"
                      % (seed, seed))
                for pr in problems:
                    print("   " + pr)
            elif not args.json:
                print("== seed %d ok" % seed)
    if args.json:
        json.dump(report, sys.stdout, indent=2)
        sys.stdout.write("\n")
    return 1 if failures else 0


if __name__ == "__main__":
    # standalone CLI runs force the cpu backend BEFORE paddle_tpu
    # imports jax; only under __main__ (tests import this module — see
    # tools/lint_program.py for the env-leak this avoids)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
