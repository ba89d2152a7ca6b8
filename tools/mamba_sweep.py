#!/usr/bin/env python
"""Time the two Mamba-1 kernels ALONE at Jamba2-3B's widths (5,120
channels of 16 states; docs/KERNELS.md "Mamba-1 selective scan"): the
time-walking scan of one layer over a prompt, a row a (prompt length,
lanes of a channel tile, block of positions, positions the loop's body
holds), and the in-place update of one layer over the decode step's
slots.

    python tools/mamba_sweep.py [--reps 3] [--slots 32]
    JAX_PLATFORMS=cpu python tools/mamba_sweep.py --rehearse

Times are the host's clock round ``block_until_ready`` of ``reps``
calls chained inside ONE jitted loop after a warm run (a scan of 2,048
positions and an update take a millisecond and less: a dispatch a call
would be a third of the reading). Each row carries the closed form's least time
(benchmarks/lib/closed_forms_mamba.py: the token-by-token recurrence
against the fewest bytes) and the share of it the kernel reached, and how
far the kernel's output and state stand from the composed form's.
``--rehearse`` runs tiny shapes in interpret mode and times nothing worth
reading."""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

C, N, R = 5120, 16, 160
PROMPTS = (2048, 16384)
BLOCKS_LONG = (256, 512)
LANES = (128, 640)
BLOCKS = (128, 256, 512)
UNROLLS = (1, 2, 4, 8)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _cfg(c=C):
    return {"n_layer": 1, "layer_types": ["mamba"], "mamba_inner": c,
            "mamba_state": N, "mamba_dt_rank": R, "ssm_conv": 4}


def _operands(jax, jnp, seed, B, T, c):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    u = jax.random.normal(ks[0], (B, T, c), jnp.float32)
    dt = jnp.exp(jax.random.uniform(ks[1], (B, T, c), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    a = -jax.random.uniform(ks[2], (c, N), jnp.float32, 1.0, 16.0)
    bm = jax.random.normal(ks[3], (B, T, N), jnp.float32)
    cm = jax.random.normal(ks[4], (B, T, N), jnp.float32)
    return u, dt, a, bm, cm


def _timed(fn, args, reps):
    """Seconds a call of ``fn(*args)``, ``reps`` calls chained INSIDE one
    jitted loop (each call's first operand waits on the last call's first
    output), so that the host's dispatch — a tenth of a millisecond and
    more, beside kernels of that order — is paid once."""
    import jax

    def chained(*a):
        def body(_i, carry):
            out = fn(a[0] + 0.0 * carry, *a[1:])[0]
            return out.reshape(carry.shape)
        first = fn(*a)[0]
        return jax.lax.fori_loop(0, reps, body, first)

    run = jax.jit(chained)
    jax.block_until_ready(run(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(run(*args))
    return (time.perf_counter() - t0) / (reps + 1)


def rows_of(args):
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import closed_forms_mamba as forms
    from paddle_tpu.kernels import mamba

    interpret = bool(args.rehearse)
    c = 1024 if interpret else C
    prompts = (300,) if interpret else PROMPTS
    plans = [(128, 128, 2)] if interpret else [
        p for p in itertools.product(LANES, BLOCKS, UNROLLS)
        if args.full or p[1] != 128]
    rows = []
    for T in prompts:
        ops = _operands(jax, jnp, T, 1, T, c)
        want = jax.jit(lambda *a: mamba.mamba_scan_composed(
            *a, block=16))(*ops) if T <= 2048 else None
        for lanes, block, unroll in plans:
            if T > 2048 and not args.full and (
                    block not in BLOCKS_LONG or unroll not in (2, 8)):
                continue
            fn = jax.jit(lambda *a, p=(lanes, block, unroll):
                         mamba.mamba_scan_pallas(
                             *a, lanes=p[0], block=p[1], unroll=p[2],
                             interpret=interpret))
            try:
                secs = _timed(fn, ops, args.reps)
            except Exception as exc:  # noqa: BLE001 — a plan Mosaic refuses
                rows.append({"kernel": mamba.KERNEL_SCAN, "prompt": T,
                             "lanes": lanes, "block": block,
                             "unroll": unroll,
                             "refused": str(exc).splitlines()[0][:200]})
                continue
            least = forms.scan_roofline(_cfg(c), T, PEAKS)
            row = {"kernel": mamba.KERNEL_SCAN, "prompt": T, "lanes": lanes,
                   "block": block, "unroll": unroll, "ms": secs * 1e3,
                   "us_a_token": secs * 1e6 / T,
                   "least_ms": least["seconds"] * 1e3,
                   "bound": least["bound"],
                   "roofline_pct": 100.0 * least["seconds"] / secs}
            if want is not None:
                y, s = fn(*ops)
                row["y_max_abs"] = float(jnp.abs(y - want[0]).max())
                row["y_abs_max"] = float(jnp.abs(want[0]).max())
                row["state_max_abs"] = float(jnp.abs(s - want[1]).max())
            rows.append(row)
    B = 2 if interpret else args.slots
    state = jnp.zeros(mamba.state_shape(B, c, N), jnp.float32) + 0.5
    u1, dt1, a, b1, c1 = _operands(jax, jnp, 3, B, 1, c)
    u1, dt1, b1, c1 = (t[:, 0] for t in (u1, dt1, b1, c1))
    want_y, want_s = mamba.mamba_update_composed(state, u1, dt1, a, b1, c1)

    def step(state):
        y, state = mamba.mamba_update_pallas(state, u1, dt1, a, b1, c1,
                                             interpret=interpret)
        return state, y

    state, y = jax.jit(step, donate_argnums=(0,))(state)
    check = {"check": "update: pallas against composed",
             "update_y_max_abs": float(jnp.abs(y - want_y).max()),
             "update_state_max_abs": float(jnp.abs(state - want_s).max()),
             "y_abs_max": float(jnp.abs(want_y).max())}
    secs = _timed(step, (state,), args.reps * 16)
    least = forms.update_roofline(_cfg(c), B, PEAKS)
    rows += [check, {"kernel": mamba.KERNEL_UPDATE, "slots": B,
                     "tile": mamba._update_plan(state.shape),
                     "ms": secs * 1e3, "least_ms": least["seconds"] * 1e3,
                     "bound": least["bound"],
                     "roofline_pct": 100.0 * least["seconds"] / secs}]
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--full", action="store_true",
                    help="every (lanes, block, unroll), at both prompts")
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "mamba_sweep.json"))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes in interpret mode: the control flow "
                    "only")
    args = ap.parse_args(argv)
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        sys.exit("tools/mamba_sweep.py times kernels on a TPU; this is %r "
                 "(--rehearse for the CPU)" % jax.devices()[0].platform)
    rows = rows_of(args)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "rows": rows}, f,
                  indent=1)
    for row in rows:
        print(json.dumps(row))


if __name__ == "__main__":
    main()
