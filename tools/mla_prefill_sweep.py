#!/usr/bin/env python
"""Time the latent prefill's attention sub-block ALONE (projection
outputs -> context, one layer) at the three latent cells' longest
prompts, and the flash forward alone, device time off a profiler trace
(docs/KERNELS.md "Operand layouts of the flash kernels").

    python tools/mla_prefill_sweep.py [--reps 5] [--only pangu]
    JAX_PLATFORMS=cpu python tools/mla_prefill_sweep.py --rehearse

A row a (cell, case). The sub-block's two forms start from what the
projections write — ``qb``'s output [1, P, H*(d_nope + d_rope)], ``kvb``'s
[1, P, H*(d_nope + d_v)] and the rotated ``k_r`` [1, P, d_rope], float32 —
and end at the context [1, P, H*d_v] float32 the output projection reads:

  block_heads  every head's q, k and v built [1, H, P, D] (transpose,
               expand, concat), the rank-4 call, the context transposed
               back: the form the prefill had before PR 50
  block_lanes  the rank-3 call with the shared key part: q rotated where
               it lies, ``kvb``'s output as it stands

and the kernel alone, on operands that exist before the timed call:

  heads_f32    rank 4, float32 operands, ``mxu_dtype="bfloat16"``
  heads_bf16   rank 4, bfloat16 operands
  lanes_bf16   rank 3 with the shared key part, bfloat16 operands

``device_ms`` is every device operation of a call, ``kernel_ms`` those
named ``flash_fwd`` alone: their difference is the data movement round
the kernel. Run from a checkout without the shared key part (copy this
file into it) the ``lanes`` cases are left out and ``heads_f32`` is the
kernel that rounds in its body. ``--check`` lengths compare the two forms
at ragged lengths where they run.
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

TRACE_DIR = os.path.join(REPO, ".bench_trace", "mla_prefill_sweep")

# cell: (heads, longest prompt); the widths are the three configurations'
CELLS = {
    "pangu_serve_reason": (128, 3328),
    "xing_serve_docs": (32, 8192),
    "longcat_serve_reason": (64, 3328),
}
D_NOPE, D_ROPE, D_V = 128, 64, 128


def _rotate(x, pos, heads_last):
    """Rotate-half rotation of ``x [..., S, D]`` (``heads_last``:
    [B, S, H, D]) at ``pos [S]``, as ops/tensor_ops.py's ``rope``."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = 10000.0 ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    if heads_last:
        sin, cos = sin[:, None, :], cos[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def forms(A, H, P, dn=D_NOPE, dr=D_ROPE, dv=D_V):
    """``{case: (fn, make operands)}`` for ``H`` heads and a prompt of
    ``P`` tokens; ``fn`` is what a row times."""
    import jax
    import jax.numpy as jnp

    scale = float(dn + dr) ** -0.5
    pos = jnp.arange(P, dtype=jnp.int32)
    lanes = "shared" in inspect.signature(A.flash_attention).parameters
    call = dict(scale=scale, causal=True, min_seq=128)

    def proj(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return (jax.random.normal(k1, (1, P, H * (dn + dr)), jnp.float32),
                jax.random.normal(k2, (1, P, H * (dn + dv)), jnp.float32),
                jax.random.normal(k3, (1, P, dr), jnp.float32))

    def heads_operands(qb, kvb, k_r):
        q = qb.reshape(1, P, H, dn + dr)
        q_rope = _rotate(q[..., dn:].transpose(0, 2, 1, 3), pos, False)
        q = jnp.concatenate([q[..., :dn].transpose(0, 2, 1, 3), q_rope], -1)
        kv = kvb.reshape(1, P, H, dn + dv).transpose(0, 2, 1, 3)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            k_r[:, None], (1, H, P, dr))], -1)
        return q, k, kv[..., dn:]

    def lanes_operands(qb, kvb, k_r):
        q = qb.reshape(1, P, H, dn + dr)
        q_r = _rotate(q[..., dn:], pos, True).reshape(1, P, H * dr)
        return q[..., :dn].reshape(1, P, H * dn), q_r, kvb, k_r

    def block_heads(qb, kvb, k_r):
        out = A.flash_attention(*heads_operands(qb, kvb, k_r),
                                mxu_dtype="bfloat16", **call)
        return out.transpose(0, 2, 1, 3).reshape(1, P, H * dv)

    def kernel_heads(q, k, v):
        mxu = "bfloat16" if q.dtype == jnp.float32 else None
        return A.flash_attention(q, k, v, mxu_dtype=mxu, **call)

    def kernel_lanes(q, q_r, kv, k_r):
        mxu = "bfloat16" if q.dtype == jnp.float32 else None
        return A.flash_attention(q, kv, kv, mxu_dtype=mxu, n_head=H,
                                 shared=(q_r, k_r), **call)

    def block_lanes(qb, kvb, k_r):
        return kernel_lanes(*lanes_operands(qb, kvb, k_r))

    def narrow(make):
        return lambda key: tuple(t.astype(jnp.bfloat16) for t in make(key))

    heads = lambda key: jax.jit(heads_operands)(*proj(key))  # noqa: E731
    out = {"block_heads": (block_heads, proj),
           "heads_f32": (kernel_heads, heads),
           "heads_bf16": (kernel_heads, narrow(heads))}
    if lanes:
        packed = lambda key: jax.jit(lanes_operands)(*proj(key))  # noqa: E731
        out.update({"block_lanes": (block_lanes, proj),
                    "lanes_bf16": (kernel_lanes, narrow(packed))})
    return out


def timed(fn, operands, reps):
    """``(device_ms, kernel_ms)`` a call of the jitted ``fn``: the leaf
    device operations of ``reps`` traced calls, all of them and those of
    the flash forward."""
    import jax

    from benchmarks.lib import xplane
    from paddle_tpu.ops.attention import KERNEL_FWD

    jax.block_until_ready(fn(*operands))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    with jax.profiler.trace(TRACE_DIR):
        for _ in range(reps):
            got = fn(*operands)
        jax.block_until_ready(got)
    ops = xplane.device_ops(xplane.load(xplane.find_xplane(TRACE_DIR)))
    leaves = xplane.leaves(ops[min(ops)])
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return (1e3 * sum(e[2] for e in leaves) / reps,
            1e3 * sum(e[2] for e in leaves if KERNEL_FWD in e[0]) / reps)


def case_rows(args, A):
    import jax
    import jax.numpy as jnp

    rows = []
    key = jax.random.PRNGKey(args.seed)
    cells = [(c, H, P) for c, (H, P) in CELLS.items() if args.only in c]
    if args.rehearse:
        # three lane tiles and a sublane tile: a ragged single block
        cells = [(c, 2, 392) for c, _h, _p in cells]
    for cell, H, P in cells:
        want = None
        for case, (fn, make) in forms(A, H, P).items():
            operands = make(key)
            fn = jax.jit(fn)
            got = jax.block_until_ready(fn(*operands))
            row = {"cell": cell, "H": H, "P": P, "case": case}
            if case.startswith("block_"):
                # the two forms over the same projection outputs
                got = got.astype(jnp.float32)
                want = got if want is None else want
                row["max_abs_diff_vs_block_heads"] = float(
                    jnp.max(jnp.abs(got - want)))
            if not args.rehearse:
                row["device_ms"], row["kernel_ms"] = timed(
                    fn, operands, args.reps)
            rows.append(row)
            del operands, got
    for P in args.check:
        # a ragged length: the two forms over one draw
        got = {case: jax.jit(fn)(*make(key))
               for case, (fn, make) in forms(A, 4, P).items()
               if case.startswith("block_")}
        if len(got) == 2:
            rows.append({"check": P, "finite": bool(jnp.isfinite(
                got["block_lanes"]).all()), "max_abs_diff": float(jnp.max(
                    jnp.abs(got["block_lanes"] - got["block_heads"])))})
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--only", default="", help="substring of cell names")
    ap.add_argument("--seed", type=int, default=50)
    ap.add_argument("--check", type=int, nargs="*",
                    default=[136, 200, 392, 1100, 1408],
                    help="ragged lengths at which the two forms are compared")
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "mla_prefill_sweep.json"))
    ap.add_argument("--rehearse", action="store_true",
                    help="a tiny shape in interpret mode, no trace")
    args = ap.parse_args(argv)

    import jax

    from paddle_tpu.ops import attention as A

    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit("mla_prefill_sweep: times come from a TPU; this is "
                         "%s" % dev.platform)
    if args.rehearse:
        args.check = args.check[:2]
    rows = case_rows(args, A)
    for row in rows:
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": dev.device_kind, "reps": args.reps,
                   "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
