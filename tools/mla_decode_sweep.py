#!/usr/bin/env python
"""Time the absorbed latent-attention kernel ALONE at the three latent
cells' shapes, device time off a profiler trace (docs/KERNELS.md
"Absorbed latent attention").

    python tools/mla_decode_sweep.py [--reps 20] [--bs 256]
    JAX_PLATFORMS=cpu python tools/mla_decode_sweep.py --rehearse

A CASE is one cell's decode call (``b_max`` slots, heads, ``max_len``)
under one draw of positions: ``traffic`` as the cell's closed loop
leaves them (a slot holds a request with probability proportional to
its answer's length, somewhere inside that answer: prompt + u), and the
two extremes, every slot at 0 and every slot at ``max_len - 1``. A row a
case: the median device time of a call, microseconds a slot, the
(slot, block) pairs that hold a visible row (``live``) of the
``b_max x max_len / bs`` a static grid has, and what share of the HBM
peak the visible rows' bytes alone are in that time — the number
``mla_decode_roofline`` reads inside a step
(``benchmarks/lib/closed_forms_mla.py``). ``--bs`` holds the block
against ``decode_plan``'s own choice.
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

TRACE_DIR = os.path.join(REPO, ".bench_trace", "mla_decode_sweep")

LONG_ANSWERS = ({128: 6, 512: 6, 1024: 5, 3328: 3},
                {128: 5, 256: 6, 512: 6, 768: 3})
LONG_PROMPTS = ({512: 8, 2048: 6, 6144: 4, 8192: 2},
                {32: 6, 64: 6, 128: 5, 256: 3})
# cell: (b_max, heads, max_len, prompt and answer multisets of its traffic)
CELLS = {
    "pangu_serve_reason": (64, 128, 4096, LONG_ANSWERS),
    "xing_serve_docs": (32, 32, 8448, LONG_PROMPTS),
    "longcat_serve_reason": (32, 64, 4096, LONG_ANSWERS),
}
D_C, D_R = 512, 64


def traffic_positions(rng, B, lengths):
    """Where ``B`` slots of a full closed loop stand: a slot spends
    ``n`` steps on an answer of ``n`` tokens, so the request it holds is
    drawn by answer length, and its position is uniform inside it."""
    prompts, answers = (np.repeat(list(d), list(d.values()))
                        for d in lengths)
    n = rng.choice(answers, size=B, p=answers / answers.sum())
    return rng.choice(prompts, size=B) + (rng.random(B) * n).astype(int)


def case_rows(args, K, dev):
    """The rows of the table (module docstring), timed unless
    ``args.rehearse``."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import xplane
    from benchmarks.lib.peaks import peaks_for
    from benchmarks.lib.stats import median

    rng = np.random.default_rng(args.seed)
    rows = []
    for cell, (B, H, S, lengths) in CELLS.items():
        if args.only not in cell:
            continue
        d_c, d_r, shrink = D_C, D_R, 1
        if args.rehearse:
            # 4 blocks of 128 rows, and 3 where the cell's count is odd
            shrink = S // (384 if S % 512 else 512)
            B, H, S, d_c, d_r = 4, 8, S // shrink, 128, 64
        W = d_c + d_r
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 2)
        q = jax.random.normal(keys[0], (B, H, W), jnp.float32)
        cache = jax.random.normal(keys[1], (B, 1, S, W), jnp.float32)
        bs = K.decode_plan(cache.shape, cache.dtype, H)
        if bs is None:
            rows.append({"cell": cell, "S": S, "bs": args.bs,
                         "refused": "no block plan"})
            continue
        fn = jax.jit(lambda q, c, p: K.mla_decode_pallas(
            q, c, p, d_c=d_c, scale=W ** -0.5, interpret=args.rehearse))
        draws = [("traffic", np.minimum(
            traffic_positions(rng, B, lengths) // shrink, S - 1))
                 for _ in range(args.draws)]
        for what, at in draws + [("all_0", np.zeros(B, int)),
                                 ("all_full", np.full(B, S - 1))]:
            pos = jnp.asarray(at, jnp.int32)
            got = jax.block_until_ready(fn(q, cache, pos))
            want = K.mla_decode_composed(q, cache, pos, d_c=d_c,
                                         scale=W ** -0.5)
            live, grid = K.blocks_of(at, bs, S)
            row = {"cell": cell, "B": B, "H": H, "S": S, "bs": bs,
                   "positions": what, "rows_visible": int(at.sum()) + B,
                   "blocks_live": live, "blocks_grid": grid,
                   "live_of_grid_pct": 100.0 * live / grid,
                   "max_abs_diff_vs_composed": float(
                       jnp.max(jnp.abs(got - want)))}
            rows.append(row)
            if args.rehearse:
                continue
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            with jax.profiler.trace(TRACE_DIR):
                for _ in range(args.reps):
                    got = fn(q, cache, pos)
                jax.block_until_ready(got)
            ops = xplane.device_ops(
                xplane.load(xplane.find_xplane(TRACE_DIR)))
            call_s = median([e[2] for e in ops[min(ops)]
                             if K.KERNEL in e[0]])
            row["device_ms"] = 1e3 * call_s
            row["us_a_slot"] = 1e6 * call_s / B
            row["visible_hbm_pct"] = 100.0 * row["rows_visible"] * W * 4 / (
                call_s * peaks_for(dev.device_kind)["hbm_bytes_per_s"])
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--only", default="", help="substring of cell names")
    ap.add_argument("--seed", type=int, default=48)
    ap.add_argument("--draws", type=int, default=3,
                    help="draws of the traffic's positions a cell")
    ap.add_argument("--bs", type=int, default=0,
                    help="hold the block at this many rows")
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "mla_decode_sweep.json"))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes in interpret mode, no trace")
    args = ap.parse_args(argv)

    import jax

    from paddle_tpu.kernels import mla_decode as K

    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit("mla_decode_sweep: times come from a TPU; this is "
                         "%s" % dev.platform)
    choices = K._BLOCK_CHOICES
    if args.bs or args.rehearse:
        K._BLOCK_CHOICES = (args.bs or 128,)
    try:
        rows = case_rows(args, K, dev)
    finally:
        K._BLOCK_CHOICES = choices
    for row in rows:
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": dev.device_kind, "reps": args.reps,
                   "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
