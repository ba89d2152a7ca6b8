#!/usr/bin/env python
"""Time the grouped matmul ALONE under explicit tile plans, device time
off a profiler trace (docs/KERNELS.md "Tile plan of the grouped matmul").

    python tools/gmm_sweep.py [--reps 10] [--out chiprun_out/gmm_sweep.json]
    python tools/gmm_sweep.py --layer     # a share's whole expert layer
    JAX_PLATFORMS=cpu python tools/gmm_sweep.py --rehearse   # tiny, no times

A CASE is one product of one configuration's expert layer at one row
count: ``lhs [M, K]`` float32 rows sorted by expert, ``rhs [E, K, N]`` in
the configuration's weight dtype, ``n_rhs`` of them (2 for gated
experts), the group sizes drawn as a router would leave them — ``tokens x top_k`` pairs over ``routed``
experts of unequal popularity, of which this chip holds the first ``E``,
so most rows belong to no group where it holds a share. Every candidate
plan of a case runs ``--reps`` times inside one ``jax.profiler`` trace
under a kernel name of its own; the table gives the median device time
of a call, the grid steps, the weight bytes the touched groups own and
what share of the HBM peak those bytes alone are in that time. A host
clock round one call would carry ~0.4 ms of dispatch (PERF.md, PR 25).

``--layer`` (PR 45) times the WHOLE expert layer of the three
configurations that hold a share of their experts
(``ops/moe_ops.py::_experts``: router, sort, gather, both products, the
way back) at a decode step's tokens and at prompt lengths of the cell's
traffic, as the program holds it — the sorted pair rows cut at
``compact_rows`` wherever the call is long enough — against the full
length (the threshold lifted out of reach: what every call ran before),
the device's busy time of a call off a trace of its own.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

TRACE_DIR = os.path.join(REPO, ".bench_trace", "gmm_sweep")

# name: (tokens a call, top_k, routed, held E, K, N, n_rhs, weight dtype,
#        [(tk, tn)...]); the first plan of a case is the one gmm_plan gave
# before the PR that added the case (PR 41: Nemotron's; PR 42: the rest)
NEMOTRON_UP = [(1024, 128), (1024, 384), (1024, 896), (1024, 2688)]
NEMOTRON_DOWN = [(128, 512), (384, 512), (896, 512), (2688, 512),
                 (896, 1024)]
# PR 42: the reduction held whole against its cuts, at 512 and 256 columns
# and under a weight-block cap of 4 or 8 MiB
XING_UP = [(512, 512), (1792, 512), (3584, 256), (3584, 512), (3584, 1024)]
PANGU_UP = [(512, 512), (1536, 512), (3840, 512), (7680, 256), (7680, 512)]
PANGU_DOWN = [(1024, 512), (2048, 256), (2048, 512)]
TRINITY = [(1024, 512), (1536, 512), (3072, 256), (3072, 512)]
OLMOE_UP = [(1024, 512), (2048, 256), (2048, 512)]
XING_DOWN = [(1024, 512)]          # whole before PR 42: the reference


def cases():
    out = {}
    for tokens in (96, 128, 512, 2048):
        what = "decode" if tokens == 96 else "prefill%d" % tokens
        out["nemotron_up_" + what] = (tokens, 22, 512, 128, 1024, 2688, 1,
                                      "bfloat16", NEMOTRON_UP)
        out["nemotron_down_" + what] = (tokens, 22, 512, 128, 2688, 1024,
                                        1, "bfloat16", NEMOTRON_DOWN)

    def add(stem, decode, prefills, *shape):
        for tokens in (decode,) + prefills:
            what = "decode" if tokens == decode else "prefill%d" % tokens
            out["%s_%s" % (stem, what)] = (tokens,) + shape

    # the decode call is one token a slot: b_max tokens
    add("xing_up", 32, (512, 2048, 6144, 8192),
        4, 64, 64, 3584, 1024, 2, "bfloat16", XING_UP)
    add("xing_down", 32, (512, 2048, 6144, 8192),
        4, 64, 64, 1024, 3584, 1, "bfloat16", XING_DOWN)
    add("pangu_up", 64, (1024, 3328),
        8, 256, 8, 7680, 2048, 2, "bfloat16", PANGU_UP)
    add("pangu_down", 64, (1024, 3328),
        8, 256, 8, 2048, 7680, 1, "bfloat16", PANGU_DOWN)
    add("trinity_up", 16, (8192,),
        4, 256, 8, 3072, 3072, 2, "float32", TRINITY)
    add("trinity_down", 16, (8192,),
        4, 256, 8, 3072, 3072, 1, "float32", TRINITY)
    add("olmoe_up", 32, (512,),
        8, 64, 64, 2048, 1024, 2, "float32", OLMOE_UP)
    return out


def group_sizes(rng, tokens, top_k, routed, held, sigma=1.0):
    """Pairs each held expert is given when ``tokens`` tokens choose
    ``top_k`` distinct experts of ``routed`` whose popularity is
    log-normal (a seeded router does not spread its pairs evenly:
    PERF.md section 5 reads 81% of the held experts touched where even
    routing would touch 98%)."""
    logits = rng.normal(0.0, sigma, routed)
    noise = rng.gumbel(size=(tokens, routed))
    chosen = np.argsort(-(logits + noise), axis=1)[:, :top_k]
    return np.bincount(chosen.reshape(-1), minlength=routed)[:held]


# name: (router's width, experts' width or None for the same, F, routed,
#        held, top_k, act, weight dtype, tokens a call: the decode step's
#        b_max first)
LAYERS = {
    "pangu": (7680, None, 2048, 256, 8, 8, "swiglu", "bfloat16",
              (64, 256, 512, 1024, 3328)),
    "trinity": (3072, None, 3072, 256, 8, 4, "swiglu", "float32",
                (16, 512, 1024, 2048, 8192)),
    "nemotron": (4096, 1024, 2688, 512, 128, 22, "relu2", "bfloat16",
                 (96, 256, 512, 2048)),
}


def layer_rows(args):
    """One row a (configuration, tokens, form): the layer's device time
    a call with the rows cut and at full length, the branch the cut form
    took, and how far the two outputs lie apart."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import xplane
    from paddle_tpu.ops import moe_ops

    threshold = moe_ops._COMPACT_MIN_PAIRS
    out = []
    for name, (D, De, F, E, held, k, act, dtype, tokens) in LAYERS.items():
        if args.only not in name:
            continue
        if args.rehearse:
            D, De, F, E, held, tokens = 64, De and 32, 128, 32, 4, (16, 1280)
        Dx = De or D
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 6)

        def draw(key, shape, fan, dt=jnp.float32):
            return (jax.random.normal(key, shape) / fan ** 0.5).astype(dt)

        w1 = draw(keys[0], (held, Dx, F), Dx, dtype)
        w1v = draw(keys[1], (held, Dx, F), Dx, dtype) \
            if act == "swiglu" else None
        w2 = draw(keys[2], (held, F, Dx), F, dtype)
        router = draw(keys[3], (D, E), D)

        def layer(x, xe, w1, w1v, w2, router):
            got, _aux, routed, took, _most = moe_ops._experts(
                x, w1, w1v, None, w2, None, router, E, k, None, act, True,
                0.0, {"score": "sigmoid"}, (0, held), xe)
            return got, routed, jnp.int32(-1) if took is None else took

        for T in tokens:
            x = draw(keys[4], (T, D), 1.0)
            xe = draw(keys[5], (T, De), 1.0) if De else None
            operands = (x, xe, w1, w1v, w2, router)
            want = None
            for form in ("full", "cut"):
                moe_ops._COMPACT_MIN_PAIRS = \
                    1 << 62 if form == "full" else threshold
                cap = moe_ops.compact_rows(k * T, E, held)
                if form == "cut" and cap is None:
                    continue
                # a function of its own a form: jit's cache goes by it,
                # and the threshold is read while tracing
                fn = jax.jit(lambda *a: layer(*a))
                got, routed, took = jax.block_until_ready(fn(*operands))
                rows = cap or k * T
                row = {"layer": name, "tokens": T, "pair_rows": k * T,
                       "form": form, "rows": rows,
                       "work_tiles": -(-rows // 128) + held - 1,
                       "held_pairs": int(routed[:held].sum()),
                       "took": int(took)}
                if want is None:
                    want = got
                else:
                    row["max_abs_diff_vs_full"] = float(
                        jnp.max(jnp.abs(got - want)))
                if not args.rehearse:
                    shutil.rmtree(TRACE_DIR, ignore_errors=True)
                    with jax.profiler.trace(TRACE_DIR):
                        for _ in range(args.reps):
                            last = fn(*operands)
                        jax.block_until_ready(last)
                    ops = xplane.device_ops(
                        xplane.load(xplane.find_xplane(TRACE_DIR)))
                    row["layer_ms"] = 1e3 * xplane.busy_seconds(
                        xplane.leaves(ops[min(ops)])) / args.reps
                out.append(row)
    moe_ops._COMPACT_MIN_PAIRS = threshold
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--only", default="", help="substring of case names")
    ap.add_argument("--seed", type=int, default=41,
                    help="of the routers' draws (the tables: 41)")
    ap.add_argument("--sigma", type=float, default=1.0,
                    help="spread of the experts' log-popularity (the "
                         "tables: 1; 0 is an even router)")
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out",
                                                  "gmm_sweep.json"))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes in interpret mode, no trace")
    ap.add_argument("--layer", action="store_true",
                    help="a share's whole expert layer, rows cut against "
                         "full length, in place of the kernels alone")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks.lib.peaks import peaks_for
    from paddle_tpu.kernels import moe_gmm
    from paddle_tpu.kernels.common import ceil_to

    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit("gmm_sweep: times come from a TPU; this is %s"
                         % dev.platform)
    rng = np.random.default_rng(args.seed)
    rows = layer_rows(args) if args.layer else []
    todo = {} if args.layer else cases()
    for cname, (tokens, top_k, routed, E, K, N, n_rhs, dtype, plans) in \
            todo.items():
        if args.only not in cname:
            continue
        if args.rehearse:
            tokens, routed, E, plans = 16, 4 * min(E, 8), min(E, 8), plans[:2]
        sizes = group_sizes(rng, tokens, top_k, routed, E, args.sigma)
        M = tokens * top_k
        lhs = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
        rhs = tuple(
            (jax.random.normal(jax.random.PRNGKey(i), (E, K, N),
                               jnp.float32) / K ** 0.5
             ).astype(dtype) for i in range(n_rhs))
        gs = jnp.asarray(sizes, jnp.int32)
        tm = min(128, ceil_to(M, 8))
        runs, want = [], None
        for tk, tn in plans:
            key = "gmmsweep_%03d_end" % len(rows)
            row = {"case": cname, "M": M, "K": K, "N": N, "n_rhs": n_rhs,
                   "weights": dtype,
                   "groups": E, "touched": int((sizes > 0).sum()),
                   "rows_owned": int(sizes.sum()),
                   "plan": "%dx%dx%d" % (tm, tk, tn),
                   "grid_steps": (N // tn) * (ceil_to(M, tm) // tm + E - 1)
                   * (K // tk),
                   "weight_bytes": int((sizes > 0).sum()) * K * N
                   * jnp.dtype(dtype).itemsize * n_rhs}
            rows.append(row)
            try:
                fn = jax.jit(lambda a, b, g, _p=(tm, tk, tn), _k=key:
                             moe_gmm.gmm_pallas(
                                 a, b, g, name=_k, plan=_p,
                                 interpret=args.rehearse,
                                 mxu_dtype=jnp.bfloat16))
                got = jax.block_until_ready(fn(lhs, rhs, gs))
            except Exception as exc:  # noqa: BLE001 — a refused plan is a row
                row["refused"] = "%s: %s" % (type(exc).__name__,
                                             str(exc)[:300])
                continue
            if want is None:
                want = got   # the first plan is the one that ran before
            row["max_abs_diff_vs_first"] = float(
                jnp.max(jnp.abs(got - want)))
            runs.append((key, fn, (lhs, rhs, gs), row))
        if not args.rehearse:
            _trace(runs, args.reps)
    for row in rows:
        if "device_ms" in row:
            row["weight_hbm_pct"] = 100.0 * row["weight_bytes"] / (
                row["device_ms"] * 1e-3) / peaks_for(
                    dev.device_kind)["hbm_bytes_per_s"]
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": dev.device_kind, "reps": args.reps,
                   "rows": rows}, f, indent=1)
    return 0


def _trace(runs, reps):
    """Run every (key, fn, operands, row) ``reps`` times under one trace
    and write the median device time of a call into its row."""
    import jax

    from benchmarks.lib import xplane
    from benchmarks.lib.stats import median

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    with jax.profiler.trace(TRACE_DIR):
        for _key, fn, operands, _row in runs:
            for _ in range(reps):
                out = fn(*operands)
            jax.block_until_ready(out)
    ops = xplane.device_ops(xplane.load(xplane.find_xplane(TRACE_DIR)))
    events = ops[min(ops)]
    for key, _fn, _operands, row in runs:
        durs = [e[2] for e in events if key in e[0]]
        if durs:
            row["device_ms"] = 1e3 * median(durs)
            row["calls_traced"] = len(durs)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
