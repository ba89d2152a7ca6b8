#!/usr/bin/env python
"""Time the two delta-rule kernels ALONE at Qwen3-Next's widths (32 value
heads over 16 key heads of 128; docs/KERNELS.md "Gated delta rule"): the
chunked scan of one layer over a prompt, a row a (prompt length, chunk),
and the in-place update of one layer over the decode step's slots.

    python tools/delta_sweep.py [--reps 5] [--slots 128]
    JAX_PLATFORMS=cpu python tools/delta_sweep.py --rehearse

Times are the host's clock round ``block_until_ready`` over ``reps``
calls after a warm one (the kernels take milliseconds: a dispatch is
noise beside them). Each row carries the closed form's least time
(benchmarks/lib/closed_forms_delta.py: the token-by-token recurrence) and
the share of it the kernel reached, and — what chose the form of the
chunk's triangular solve, docs/KERNELS.md — how far the kernel's output
and state stand from the composed form's
(``solve_triangular``) under two draws of the operands: ``mild`` (keys of
random direction, ``beta`` in 0.3-0.7, decays of 0.9-0.999) and ``hard``
(every key within a hundredth of one direction, ``beta`` 0.999, no decay:
a sequence that repeats itself). ``--rehearse`` runs tiny shapes in
interpret mode and times nothing worth reading."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

HK, HV, D = 16, 32, 128
PROMPTS = (256, 2048)
CHUNKS = (32, 64, 128)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _cfg(hk=HK, hv=HV):
    return {"n_layer": 1, "layer_types": ["delta"], "delta_k_heads": hk,
            "delta_k_dim": D, "delta_v_heads": hv, "delta_v_dim": D}


def _operands(jax, jnp, delta, seed, B, T, hk, hv, hard=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, T, hk, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, hk, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, hv, D), jnp.float32)
    if hard:
        k = k[:, :1] + 0.01 * k
        g = jnp.zeros((B, T, hv), jnp.float32)
        beta = jnp.full((B, T, hv), 0.999, jnp.float32)
    else:
        g = jnp.log(jax.random.uniform(ks[3], (B, T, hv), jnp.float32,
                                       0.9, 0.999))
        beta = jax.random.uniform(ks[4], (B, T, hv), jnp.float32, 0.3, 0.7)
    q, k = delta.normed(q, k)
    return q, k, v, g, beta


def _timed(fn, args, reps):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def rows_of(args):
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import closed_forms_delta as forms
    from paddle_tpu.kernels import delta

    interpret = bool(args.rehearse)
    hk, hv = (1, 2) if interpret else (HK, HV)
    prompts = (96,) if interpret else PROMPTS
    chunks = (32,) if interpret else CHUNKS
    rows = []
    for T in prompts:
        draws = {name: _operands(jax, jnp, delta, T, 1, T, hk, hv, hard)
                 for name, hard in (("mild", False), ("hard", True))}
        want = {name: delta.delta_scan_composed(*ops, chunk=64)
                for name, ops in draws.items()}
        for Q in chunks:
            fn = jax.jit(lambda *a, Q=Q: delta.delta_scan_pallas(
                *a, chunk=Q, interpret=interpret))
            secs = _timed(fn, draws["mild"], args.reps)
            least = forms.scan_roofline(_cfg(hk, hv), T, PEAKS)
            row = {"kernel": delta.KERNEL_SCAN, "prompt": T, "chunk": Q,
                   "ms": secs * 1e3, "least_ms": least["seconds"] * 1e3,
                   "bound": least["bound"],
                   "roofline_pct": 100.0 * least["seconds"] / secs,
                   "chunked_gflop": forms.chunked_flops(
                       _cfg(hk, hv), T, Q) / 1e9}
            for name, ops in draws.items():
                y, s = fn(*ops)
                row[name + "_y_max_abs"] = float(
                    jnp.abs(y - want[name][0]).max())
                row[name + "_state_max_abs"] = float(
                    jnp.abs(s - want[name][1]).max())
            rows.append(row)
    B = 2 if interpret else args.slots
    state = jnp.zeros(delta.state_shape(B, hv, D, D), jnp.float32) + 0.5
    q1, k1, v1, g1, b1 = (t[:, 0] for t in _operands(
        jax, jnp, delta, 3, B, 1, hk, hv))
    want_y, want_s = delta.delta_update_composed(state, q1, k1, v1, g1, b1)

    def step(state):
        y, state = delta.delta_update_pallas(state, q1, k1, v1, g1, b1,
                                             interpret=interpret)
        return state, y

    fn = jax.jit(step, donate_argnums=(0,))
    state, y = fn(state)
    check = {"check": "update: pallas against composed",
             "update_y_max_abs": float(jnp.abs(y - want_y).max()),
             "update_state_max_abs": float(jnp.abs(state - want_s).max())}
    t0 = time.perf_counter()
    for _ in range(args.reps * 4):
        state, _y = fn(state)
    jax.block_until_ready(state)
    secs = (time.perf_counter() - t0) / (args.reps * 4)
    least = forms.update_roofline(_cfg(hk, hv), B, PEAKS)
    rows += [check, {"kernel": delta.KERNEL_UPDATE, "slots": B,
                     "ms": secs * 1e3, "least_ms": least["seconds"] * 1e3,
                     "bound": least["bound"],
                     "roofline_pct": 100.0 * least["seconds"] / secs}]
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--slots", type=int, default=128)
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "delta_sweep.json"))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes in interpret mode: the control flow "
                    "only")
    args = ap.parse_args(argv)
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        sys.exit("tools/delta_sweep.py times kernels on a TPU; this is %r "
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
