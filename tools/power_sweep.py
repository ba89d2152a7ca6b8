#!/usr/bin/env python
"""Time the two power-retention kernels ALONE at Brumby's widths (40
query heads over 8 key-value heads of 128; docs/KERNELS.md "Power
retention"): the chunked scan of one layer over a prompt, a row a (prompt
length, chunk), and the in-place update of one layer over the decode
step's slots.

    python tools/power_sweep.py [--reps 5] [--slots 32] [--sub]
    JAX_PLATFORMS=cpu python tools/power_sweep.py --rehearse

Times are the host's clock round ``block_until_ready`` over ``reps``
calls after a warm one (the kernels take milliseconds: a dispatch is
noise beside them). Each row carries the closed form's least time
(benchmarks/lib/closed_forms_power.py, the exact 8,256 pairs) and the
share of it the kernel reached. ``check`` rows compare the kernel with
its composed form on the device at a short prompt. ``--sub`` adds, at
the configuration's chunk, a row a (prompt length, sub-block inside a
chunk, rows a product of the state's read and feed): the two constants
of ``kernels/power.py`` (``_SUB``, ``_RUN``) set for one compile each,
which is how they were chosen. ``--rehearse`` runs tiny shapes in
interpret mode and times nothing worth reading."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

HEADS, GROUPS, D = 40, 8, 128
PROMPTS = (1024, 2048, 4096, 8192)
CHUNKS = (128, 256, 512, 1024)
# (sub-block inside a chunk, rows a product of the read and the feed):
# the sub-block at the kernel's run, then the run at the kernel's
# sub-block; a sub-block of the whole chunk is "none"
SUBS = (128, 256, 512, 1024)
RUNS = (128, 256)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _cfg(heads=HEADS, groups=GROUPS):
    return {"d_model": heads * D, "n_head": heads, "n_kv_head": groups,
            "d_head": D, "n_layer": 1, "layer_types": ["retention"]}


def _operands(jax, jnp, seed, B, T, heads, groups):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, T, heads, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, groups, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, groups, D), jnp.float32)
    lg = jnp.log(jax.random.uniform(ks[3], (B, T, groups), jnp.float32,
                                    0.99, 0.9995))
    return q, k, v, lg


def _timed(fn, args, reps):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _scan_check(power, ops, chunk, interpret):
    """The scan's kernel against its composed form over ``ops``: the
    check row, and both results."""
    import jax.numpy as jnp

    want = power.power_scan_composed(*ops, chunk=chunk)
    got = power.power_scan_pallas(*ops, chunk=chunk, interpret=interpret)
    scale = float(jnp.abs(want[1]).max())
    return {"check": "pallas against composed",
            "scan_y_max_abs": float(jnp.abs(got[0] - want[0]).max()),
            "scan_state_max_rel": float(
                jnp.abs(got[1] - want[1]).max()) / scale}, want, got


def _sub_rows(args, prompts, heads, groups):
    """The scan at the chunk ``scan_chunk`` gives each prompt, under
    every (``_SUB``, ``_RUN``) of the sweep: the constants are read when
    the kernel is traced, so each pair is a compile of its own."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import power

    interpret = bool(args.rehearse)
    kept = power._SUB, power._RUN
    pairs = [(sub, kept[1]) for sub in SUBS] \
        + [(kept[0], run) for run in RUNS if run != kept[1]]
    # the kernel against its composed form at the chunk a long prompt
    # takes: three chunks, the last ragged, so the read and the feed cross
    # every tile column and the scores are cut in sub-blocks
    T = 300 if interpret else 2500
    Q = 128 if interpret else power.scan_chunk(T)
    check, _want, _got = _scan_check(
        power, _operands(jax, jnp, 4, 1, T, heads, groups), Q, interpret)
    rows = [dict(check, prompt=T, chunk=Q)]
    try:
        for T in prompts:
            ops = _operands(jax, jnp, T, 1, T, heads, groups)
            Q = power.scan_chunk(T)
            for sub, run in pairs:
                power._SUB, power._RUN = sub, run
                fn = jax.jit(lambda q, k, v, lg: power.power_scan_pallas(
                    q, k, v, lg, chunk=Q, interpret=interpret))
                rows.append({"kernel": power.KERNEL_SCAN, "prompt": T,
                             "chunk": Q, "sub": sub, "run": run,
                             "ms": _timed(fn, ops, args.reps) * 1e3})
    finally:
        power._SUB, power._RUN = kept
    return rows


def rows_of(args):
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import closed_forms_power as forms
    from paddle_tpu.kernels import power

    interpret = bool(args.rehearse)
    heads, groups = (5, 1) if interpret else (HEADS, GROUPS)
    prompts = (256,) if interpret else PROMPTS
    chunks = (128,) if interpret else CHUNKS
    rows = []
    # the kernels against their composed forms, on this device
    check, want, got = _scan_check(
        power, _operands(jax, jnp, 1, 1, 200 if interpret else 600, heads,
                         groups), 128, interpret)
    scale = float(jnp.abs(want[1]).max())
    q1, k1, v1, l1 = (t[:, 0] for t in _operands(jax, jnp, 2, 1, 1, heads,
                                                 groups))
    u_want = power.power_update_composed(want[1], want[2], q1, k1, v1, l1)
    u_got = power.power_update_pallas(got[1], got[2], q1, k1, v1, l1,
                                      interpret=interpret)
    rows.append(dict(check,
                     update_y_max_abs=float(
                         jnp.abs(u_got[0] - u_want[0]).max()),
                     update_state_max_rel=float(
                         jnp.abs(u_got[1] - u_want[1]).max()) / scale))
    for T in prompts:
        ops = _operands(jax, jnp, T, 1, T, heads, groups)
        for Q in chunks:
            fn = jax.jit(lambda q, k, v, lg, Q=Q: power.power_scan_pallas(
                q, k, v, lg, chunk=Q, interpret=interpret))
            secs = _timed(fn, ops, args.reps)
            least = forms.scan_roofline(_cfg(heads, groups), T, PEAKS,
                                        chunk=Q)
            rows.append({"kernel": power.KERNEL_SCAN, "prompt": T,
                         "chunk": Q, "ms": secs * 1e3,
                         "least_ms": least["seconds"] * 1e3,
                         "bound": least["bound"],
                         "roofline_pct": 100.0 * least["seconds"] / secs})
    if args.sub:
        rows.extend(_sub_rows(args, prompts, heads, groups))
    B = 2 if interpret else args.slots
    state = jnp.zeros(power.state_shape(B, groups, D), jnp.float32) + 0.5
    norm = jnp.zeros(power.norm_shape(B, groups, D), jnp.float32) + 0.5
    q1, k1, v1, l1 = (t[:, 0] for t in _operands(jax, jnp, 3, B, 1, heads,
                                                 groups))

    def step(state, norm):
        y, state, norm = power.power_update_pallas(
            state, norm, q1, k1, v1, l1, interpret=interpret)
        return state, norm, y

    fn = jax.jit(step, donate_argnums=(0, 1))
    state, norm, _y = fn(state, norm)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(args.reps * 4):
        state, norm, _y = fn(state, norm)
    jax.block_until_ready(state)
    secs = (time.perf_counter() - t0) / (args.reps * 4)
    least = forms.update_roofline(_cfg(heads, groups), B, PEAKS)
    rows.append({"kernel": power.KERNEL_UPDATE, "slots": B,
                 "ms": secs * 1e3, "least_ms": least["seconds"] * 1e3,
                 "bound": least["bound"],
                 "roofline_pct": 100.0 * least["seconds"] / secs})
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "power_sweep.json"))
    ap.add_argument("--sub", action="store_true",
                    help="sweep the sub-block inside a chunk and the rows "
                    "a product of the state's read and feed")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes in interpret mode")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit("power_sweep: times come from a TPU; this is %s"
                         % dev.platform)
    if args.rehearse:
        args.reps = 1
    rows = rows_of(args)
    for row in rows:
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": dev.device_kind, "reps": args.reps,
                   "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
