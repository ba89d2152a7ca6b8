#!/usr/bin/env python
"""Time the grouped matmul ALONE under explicit tile plans, device time
off a profiler trace (docs/KERNELS.md "Tile plan of the grouped matmul").

    python tools/gmm_sweep.py [--reps 10] [--out chiprun_out/gmm_sweep.json]
    JAX_PLATFORMS=cpu python tools/gmm_sweep.py --rehearse   # tiny, no times

A CASE is one product of one configuration's expert layer at one row
count: ``lhs [M, K]`` float32 rows sorted by expert, ``rhs [E, K, N]``
bfloat16, ``n_rhs`` of them (2 for gated experts), the group sizes drawn
as a router would leave them — ``tokens x top_k`` pairs over ``routed``
experts of unequal popularity, of which this chip holds the first ``E``,
so most rows belong to no group where it holds a share. Every candidate
plan of a case runs ``--reps`` times inside one ``jax.profiler`` trace
under a kernel name of its own; the table gives the median device time
of a call, the grid steps, the weight bytes the touched groups own and
what share of the HBM peak those bytes alone are in that time. A host
clock round one call would carry ~0.4 ms of dispatch (PERF.md, PR 25).
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

# name: (tokens a call, top_k, routed, held E, K, N, n_rhs, [(tk, tn)...])
# the first plan of a case is the one gmm_plan gave before PR 41
NEMOTRON_UP = [(1024, 128), (1024, 384), (1024, 896)]
NEMOTRON_DOWN = [(128, 512), (384, 512), (896, 512), (2688, 512),
                 (896, 1024)]


def cases():
    out = {}
    for tokens in (96, 128, 512, 2048):
        what = "decode" if tokens == 96 else "prefill%d" % tokens
        out["nemotron_up_" + what] = (tokens, 22, 512, 128, 1024, 2688, 1,
                                      NEMOTRON_UP)
        out["nemotron_down_" + what] = (tokens, 22, 512, 128, 2688, 1024,
                                        1, NEMOTRON_DOWN)
    # not adopted: the reductions of the two older bf16 configurations
    # that a wider multiple of 128 also divides
    out["pangu_up_decode"] = (64, 8, 256, 8, 7680, 2048, 2,
                              [(512, 512), (768, 512), (1536, 512)])
    out["pangu_up_prefill1024"] = (1024, 8, 256, 8, 7680, 2048, 2,
                                   [(512, 512), (768, 512), (1536, 512)])
    out["xing_up_decode"] = (32, 4, 64, 64, 3584, 1024, 2,
                             [(512, 512), (896, 512), (1792, 512)])
    out["xing_up_prefill2048"] = (2048, 4, 64, 64, 3584, 1024, 2,
                                  [(512, 512), (896, 512), (1792, 512)])
    return out


def group_sizes(rng, tokens, top_k, routed, held):
    """Pairs each held expert is given when ``tokens`` tokens choose
    ``top_k`` distinct experts of ``routed`` whose popularity is
    log-normal (a seeded router does not spread its pairs evenly:
    PERF.md section 5 reads 81% of the held experts touched where even
    routing would touch 98%)."""
    logits = rng.normal(0.0, 1.0, routed)
    noise = rng.gumbel(size=(tokens, routed))
    chosen = np.argsort(-(logits + noise), axis=1)[:, :top_k]
    return np.bincount(chosen.reshape(-1), minlength=routed)[:held]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--only", default="", help="substring of case names")
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out",
                                                  "gmm_sweep.json"))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes in interpret mode, no trace")
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
    rng = np.random.default_rng(41)
    rows = []
    for cname, (tokens, top_k, routed, E, K, N, n_rhs, plans) in \
            cases().items():
        if args.only not in cname:
            continue
        if args.rehearse:
            tokens, routed, E, plans = 16, 4 * min(E, 8), min(E, 8), plans[:2]
        sizes = group_sizes(rng, tokens, top_k, routed, E)
        M = tokens * top_k
        lhs = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
        rhs = tuple(
            (jax.random.normal(jax.random.PRNGKey(i), (E, K, N),
                               jnp.float32) / K ** 0.5
             ).astype(jnp.bfloat16) for i in range(n_rhs))
        gs = jnp.asarray(sizes, jnp.int32)
        tm = min(128, ceil_to(M, 8))
        runs, want = [], None
        for tk, tn in plans:
            key = "gmmsweep_%03d_end" % len(rows)
            row = {"case": cname, "M": M, "K": K, "N": N, "n_rhs": n_rhs,
                   "groups": E, "touched": int((sizes > 0).sum()),
                   "rows_owned": int(sizes.sum()),
                   "plan": "%dx%dx%d" % (tm, tk, tn),
                   "grid_steps": (N // tn) * (ceil_to(M, tm) // tm + E - 1)
                   * (K // tk),
                   "weight_bytes": int((sizes > 0).sum()) * K * N * 2
                   * n_rhs}
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
