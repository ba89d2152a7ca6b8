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
the kernel. ``--heads 1 2 4`` (the default) times ``lanes_bf16`` again at
each count of heads a multi-pass grid step, by putting a function of the
sweep's own in the place of ``ops/attention.py::_forward_heads`` (the
library has no flag for it): column ``heads``, with ``us_a_block`` =
``kernel_ms`` over the visited 512 x 512 blocks of all heads; a row
without the column ran at the rule's own count (``rule_heads``).
``--grouped`` adds the rank-4 grouped calls of ``trinity_serve_mixed``
(banded and full) and ``lfm2_serve_long_ctx`` at their longest prompts,
float32 operands, at every divisor of the group. Run from a checkout without the shared key part (copy this
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
# the grouped rank-4 calls: (query heads, key/value heads, head width,
# longest prompt, window)
GROUPED = {
    "trinity_serve_mixed.win": (48, 8, 128, 8192, 4096),
    "trinity_serve_mixed.full": (48, 8, 128, 8192, None),
    "lfm2_serve_long_ctx": (32, 8, 64, 16384, None),
}


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


class step_heads:
    """``with step_heads(A, n):`` every multi-pass forward lowered inside
    takes ``n`` heads a grid step (``None``: the rule's own); ``.seen`` is
    the count the last lowering took."""

    def __init__(self, A, n):
        self.A, self.n, self.seen = A, n, None

    def __enter__(self):
        self.rule = rule = self.A._forward_heads

        def forced(H, group, bq, bk, single_pass, *a, **kw):
            got = rule(H, group, bq, bk, single_pass, *a, **kw)
            self.seen = got if single_pass or self.n is None else self.n
            return self.seen

        self.A._forward_heads = forced
        return self

    def __exit__(self, *exc):
        self.A._forward_heads = self.rule


def visited_blocks(A, H, P, window=None):
    """(bq, bk)-blocks the causal forward computes at ``P`` keys, all
    ``H`` heads."""
    Sp, Skp, bq, bk = A._forward_plan(P, P, 128, None, True, window)
    nq, nk = Sp // bq, Skp // bk
    if window is None or window >= P:
        return H * sum(1 for iq in range(nq) for ik in range(nk)
                       if ik * bk <= iq * bq + bq - 1)
    return H * A._band_blocks(nq, nk, bq, bk, window)


def stepped_rows(A, args, base, fn, operands, counts, kernel, blocks):
    """One row for the rule's own count of heads a multi-pass step and one
    for each of ``counts``: ``fn(*operands)`` lowered afresh under
    ``step_heads``, compared with the first and timed. A count the chip's
    compiler refuses (over the VMEM) is a row that says so."""
    import jax

    rows, want = [], None
    for n in [None] + list(counts):
        with step_heads(A, n) as rule:
            jitted = jax.jit(lambda *a: fn(*a))
            try:
                got = jax.block_until_ready(jitted(*operands))
            except Exception as exc:  # noqa: BLE001 — what Mosaic said
                rows.append(dict(base, heads=n, refused=str(
                    exc).splitlines()[0][:200]))
                continue
            row = dict(base, **{"heads" if n else "rule_heads": rule.seen})
            want = got if want is None else want
            row["equal_to_the_rule"] = bool((got == want).all())
            if not args.rehearse:
                row["device_ms"], row["kernel_ms"] = timed(
                    jitted, operands, args.reps, kernel)
                row["us_a_block"] = 1e3 * row["kernel_ms"] / blocks
        rows.append(row)
    return rows


def timed(fn, operands, reps, kernel=None):
    """``(device_ms, kernel_ms)`` a call of the jitted ``fn``: the leaf
    device operations of ``reps`` traced calls, all of them and those of
    the flash forward."""
    import jax

    from benchmarks.lib import xplane
    from paddle_tpu.ops.attention import KERNEL_FWD

    kernel = kernel or KERNEL_FWD
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
            1e3 * sum(e[2] for e in leaves if kernel in e[0]) / reps)


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
    stepped = hasattr(A, "_forward_heads")
    for cell, H, P in cells if stepped else []:
        # the kernel alone at each count of heads a multi-pass step
        if args.rehearse:
            H, P = 4, 1300          # three key blocks, the last ragged
        fn, make = forms(A, H, P)["lanes_bf16"]
        rows += stepped_rows(
            A, args, {"cell": cell, "H": H, "P": P, "case": "lanes_bf16"},
            fn, make(key), args.heads, A.KERNEL_FWD, visited_blocks(A, H, P))
    for cell, (H, Hkv, D, P, window) in GROUPED.items() \
            if stepped and args.grouped else []:
        if args.only not in cell:
            continue
        if args.rehearse:
            H, Hkv, P, window = H // Hkv * 2, 2, 1200, window and 300
        operands = tuple(jax.random.normal(k, (1, h, P, D), jnp.float32)
                         for k, h in zip(jax.random.split(key, 3),
                                         (H, Hkv, Hkv)))
        fn = lambda q, k, v, D=D, window=window: A.flash_attention(  # noqa: E731
            q, k, v, scale=D ** -0.5, causal=True, window=window)
        group = H // Hkv
        rows += stepped_rows(
            A, args, {"cell": cell, "H": H, "Hkv": Hkv, "P": P,
                      "case": "heads_f32"}, fn, operands,
            [g for g in range(1, group + 1) if group % g == 0],
            A.KERNEL_FWD_WIN if window else A.KERNEL_FWD,
            visited_blocks(A, H, P, window))
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
    ap.add_argument("--heads", type=int, nargs="*", default=[1, 2, 4],
                    help="heads a multi-pass grid step the kernel alone "
                    "is timed at")
    ap.add_argument("--grouped", action="store_true",
                    help="also the grouped rank-4 calls of Trinity and LFM2")
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
