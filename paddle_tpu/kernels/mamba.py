"""The selective state-space recurrence of a Mamba-1 layer (arXiv:
2312.00752) in the two forms a served sequence needs (models/gpt.py
``cfg['layer_types']`` entry ``"mamba"``; the causal convolution in front
of it is kernels/ssm.py's ``conv_prefill`` / ``conv_step`` as they are).

A layer has ``C`` channels of ``N`` states each. With ``dt_t,c`` already
positive (softplus outside) and ``A_c,n < 0``::

    S_t[c, n] = exp(dt_t[c] A[c, n]) S_t-1[c, n] + dt_t[c] B_t[n] u_t[c]
    y_t[c]    = sum_n S_t[c, n] C_t[n]

The decay is one number a CHANNEL AND STATE and step. Mamba-2's
(kernels/ssm.py) is one scalar a head, which is what lets a chunk of
positions be written as ``(C B^T (.) decay (.) dt) x``, two matrix
products: the decay between two positions factors out of the sum over
``n``. Here it stands inside that sum, so a chunk's map from ``u`` to
``y`` is a different ``[Q, Q]`` matrix for every channel — ``C`` of them
— and no product of shared matrices gives it: the recurrence is ``C N``
independent scalar recurrences, sequential in time, on the vector unit.

What a sequence keeps is ``S``, stored TRANSPOSED as ``[B, 1, N, C]``
(kernels/ssm.py's ``[B, G, N, L]`` at one group): states down the
sublanes, channels along the lanes.

* ``mamba_update`` — ONE token a slot (the decode step). The Pallas
  kernel has a grid over (channel tile, slot), slots innermost; a step
  reads its ``[N, tile]`` of ``S`` and of ``A^T`` (the same block for
  every slot of a tile: fetched once a tile), forms ``exp(dt (.) A)`` in
  registers and writes ``S`` once INTO THE SAME BUFFER
  (``input_output_aliases``) and the ``[tile]`` of ``y``. It differs
  from ``ssm_update`` in the rank of the decay: there a row ``[L]`` that
  XLA builds, here an ``[N, tile]`` block the kernel builds.
* ``mamba_scan`` — a whole prompt (the prefill). The kernel WALKS TIME:
  the grid is (batch, channel tile, block of positions), blocks innermost
  and sequential, the tile's state carried in VMEM scratch across them;
  inside a block a ``lax.fori_loop`` over the positions does the
  recurrence in registers. A tile is ``8 x lanes`` channels and its state
  ``N`` vector tiles ``[8, lanes]`` (channel ``8 lanes k + lanes s + l``
  at sublane ``s``, lane ``l`` of tile ``k``): a position's ``dt`` and
  ``u`` are then one full tile each, ``B_t[n]`` and ``C_t[n]`` are
  SCALARS (read from SMEM and splat), every operation is element-wise on
  whole registers and the sum over ``n`` is a sum of registers.
  ``exp(dt (.) A)`` is formed in the loop and never written to HBM (at
  16,384 positions ``[T, 5120, 16]`` would be 5.4 GB a layer). Returns
  ``y`` and the state after the last position. A prompt that is no whole
  number of blocks is padded with ``dt = 0`` positions, which neither
  decay nor feed the state.

Each has a composed ``jax.numpy`` form with the same signature: what the
CPU runs, what ``PADDLE_TPU_KERNELS=0`` runs on the chip, and what the
tests compare the kernels with. The scan's composed form is blocked too:
a ``lax.scan`` over blocks, inside a block an associative scan over the
pairs (decay, feed) — ``[block, C, N]`` at a time; the token-by-token
form is the reference's (benchmarks/references/).
``paddle_mamba_plans_total`` counts which form and which block each
lowering took.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import ceil_to, checked_pallas_call, pad_axis, use_interpret

__all__ = ["mamba_update", "mamba_scan", "mamba_update_composed",
           "mamba_update_pallas", "mamba_scan_composed",
           "mamba_scan_pallas", "state_shape", "scan_block",
           "KERNEL_UPDATE", "KERNEL_SCAN"]

# the names the device trace and the HLO show the calls under
KERNEL_UPDATE = "mamba_update"
KERNEL_SCAN = "mamba_scan"

_LANES = 128
_VMEM_LIMIT_BYTES = 64 << 20
# the scan's plan (docs/KERNELS.md "Mamba-1 selective scan" has the
# sweep): positions a grid step; positions the loop's body holds; and the
# most vector registers a channel tile's state may take (a tile is 8 x
# lanes channels, its state N x lanes / 128 registers: at 80 of the 64
# there are it spills, and is still a seventh faster than 16, which wait
# on their own chain of decay, product and sum)
BLOCK = 256
UNROLL = 8
_STATE_VREGS = 80
# the update's channel tile: the largest whole number of lane tiles that
# divides C and keeps a step's two [N, tile] blocks at or under this
_UPDATE_BLOCK_BYTES = 1 << 20


def state_shape(batch, channels, state):
    """``[B, 1, N, C]``: the layout a layer's state is kept in (module
    docstring)."""
    return (int(batch), 1, int(state), int(channels))


def scan_block(T):
    """The block of positions a prompt of ``T`` is scanned in: ``BLOCK``,
    or the prompt rounded up to 8 where it is shorter."""
    return min(BLOCK, ceil_to(int(T), 8))


def _dims(u, a, bm):
    C, N = a.shape
    if u.shape[-1] != C or bm.shape[-1] != N:
        raise ValueError("mamba: u %s / B %s do not fit A %s ([channels, "
                         "states])" % (u.shape, bm.shape, a.shape))
    return C, N


# ------------------------------------------------------------ one token
def mamba_update_composed(state, u, dt, a, bm, cm):
    """``(y [B, C], state')``: ``state [B, 1, N, C]``, ``u`` / ``dt``
    ``[B, C]`` (``dt`` positive), ``a [C, N]`` (negative), ``bm`` /
    ``cm`` ``[B, N]``."""
    _dims(u, a, bm)
    u, dt = u.astype(jnp.float32), dt.astype(jnp.float32)
    decay = jnp.exp(dt[:, None, :] * a.T[None])              # [B, N, C]
    new = state[:, 0] * decay + bm[:, :, None] * (dt * u)[:, None, :]
    y = jnp.sum(new * cm[:, :, None], axis=1)
    return y, new[:, None]


def _update_kernel(s_ref, a_ref, r_ref, c_ref, o_ref, y_ref):
    rows, cols = r_ref[0], c_ref[0]               # [8, tile], [N, 8]
    new = s_ref[0, 0] * jnp.exp(rows[0:1] * a_ref[...]) \
        + cols[:, 0:1] * rows[1:2]
    o_ref[0, 0] = new
    y_ref[0] = jnp.sum(new * cols[:, 1:2], axis=0, keepdims=True)


def _update_plan(state_shape_):
    """The channel tile of a state ``[B, 1, N, C]``, or None where the
    kernel has no block plan."""
    _B, G, N, C = (int(d) for d in state_shape_)
    if G != 1 or N % 8 or C % _LANES:
        return None
    n = C // _LANES
    fits = [d for d in range(1, n + 1)
            if n % d == 0 and 4 * N * d * _LANES <= _UPDATE_BLOCK_BYTES]
    return max(fits) * _LANES if fits else None


def mamba_update_pallas(state, u, dt, a, bm, cm, *, interpret=None):
    """One token a slot into ``state [B, 1, N, C]``, in place: a grid
    over (channel tile, slot), each step one read and one write of the
    slot's ``[N, tile]`` block (``input_output_aliases`` ties the state to
    the output), one read of ``A^T``'s — whose block index only moves
    with the tile, so it is fetched once a tile — and the ``[tile]`` of
    ``y``. The per-lane rows (``dt``, ``dt u``) arrive as one ``[8, C]``
    tile a slot and ``B_t`` / ``C_t`` as the columns of one ``[N, 8]``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile = _update_plan(state.shape)
    if tile is None:
        raise ValueError("mamba_update: no block plan for a state %s"
                         % (state.shape,))
    C, N = _dims(u, a, bm)
    B = u.shape[0]
    if interpret is None:
        interpret = use_interpret()
    u, dt = u.astype(jnp.float32), dt.astype(jnp.float32)
    rows = pad_axis(jnp.stack([dt, dt * u], axis=1), 1, 8)   # [B, 8, C]
    cols = pad_axis(jnp.stack([bm, cm], axis=-1).astype(jnp.float32), 2, 8)
    new, y = checked_pallas_call(
        _update_kernel, name=KERNEL_UPDATE, grid=(C // tile, B),
        in_specs=[pl.BlockSpec((1, 1, N, tile), lambda k, b: (b, 0, 0, k)),
                  pl.BlockSpec((N, tile), lambda k, b: (0, k)),
                  pl.BlockSpec((1, 8, tile), lambda k, b: (b, 0, k)),
                  pl.BlockSpec((1, N, 8), lambda k, b: (b, 0, 0))],
        operands=(state, a.T.astype(jnp.float32), rows, cols),
        out_specs=[pl.BlockSpec((1, 1, N, tile), lambda k, b: (b, 0, 0, k)),
                   pl.BlockSpec((1, 1, tile), lambda k, b: (b, 0, k))],
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, 1, C), jnp.float32)],
        scratch_shapes=[], interpret=interpret,
        input_output_aliases={0: 0},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES))
    return y[:, 0], new


# --------------------------------------------------------- whole prompt
def mamba_scan_composed(u, dt, a, bm, cm, *, block=BLOCK):
    """``(y [B, T, C], state [B, 1, N, C])`` from ``u`` / ``dt`` ``[B, T,
    C]`` (``dt`` positive), ``a [C, N]``, ``bm`` / ``cm`` ``[B, T, N]``,
    the state zero before the sequence. Blocked as the kernel is: a
    ``lax.scan`` over blocks of ``block`` positions, inside a block an
    associative scan over (decay, feed) pairs ``[block, C, N]``."""
    C, N = _dims(u, a, bm)
    B, T = u.shape[:2]
    Q = min(int(block), ceil_to(T, 8))
    Tp = ceil_to(T, Q)
    u, dt, bm, cm = (pad_axis(t.astype(jnp.float32), 1, Tp)
                     for t in (u, dt, bm, cm))
    nb = Tp // Q

    def per_block(t):             # [B, Tp, W] -> [nb, B, Q, W]
        return jnp.moveaxis(t.reshape(B, nb, Q, t.shape[-1]), 1, 0)

    def combine(left, right):     # first `left`, then `right`
        return right[0] * left[0], right[0] * left[1] + right[1]

    def step(s, blk):
        ub, dtb, bb, cb = blk
        decay = jnp.exp(dtb[..., None] * a[None, None])       # [B,Q,C,N]
        feed = (dtb * ub)[..., None] * bb[:, :, None, :]
        run, acc = jax.lax.associative_scan(combine, (decay, feed), axis=1)
        states = run * s[:, None] + acc
        return states[:, -1], jnp.sum(states * cb[:, :, None, :], axis=-1)

    s0 = jnp.zeros((B, C, N), jnp.float32)
    s, ys = jax.lax.scan(step, s0, tuple(map(per_block, (u, dt, bm, cm))))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, Tp, C)[:, :T]
    return y, jnp.swapaxes(s, 1, 2)[:, None]


def _scan_kernel(u_ref, dt_ref, a_ref, bc_ref, y_ref, so_ref, s_ref, *,
                 N, Q, unroll):
    from jax.experimental import pallas as pl

    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    def token(t, states):
        dt = dt_ref[0, t, 0]                          # [8, lanes]
        du = dt * u_ref[0, t, 0]
        y = jnp.zeros_like(du)
        new = []
        for n in range(N):
            s = jnp.exp(dt * a_ref[0, n]) * states[n] + bc_ref[0, n, t] * du
            y = y + bc_ref[0, N + n, t] * s
            new.append(s)
        y_ref[0, t, 0] = y
        return tuple(new)

    def tokens(i, states):
        # (Mosaic's loop takes no partial unroll: the body holds
        # ``unroll`` positions itself)
        for j in range(unroll):
            states = token(i * unroll + j, states)
        return states

    states = jax.lax.fori_loop(0, Q // unroll, tokens,
                               tuple(s_ref[n] for n in range(N)))
    for n in range(N):
        s_ref[n] = states[n]

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        so_ref[0, 0] = s_ref[...]


def _tile_lanes(C, N):
    """Lanes of the scan's channel tile: the most whole lane tiles that
    divide ``C / 8`` and keep the tile's state within ``_STATE_VREGS``;
    None where ``C`` is no whole number of ``8 x 128`` channels."""
    if C % (8 * _LANES):
        return None
    n = C // (8 * _LANES)
    fits = [d for d in range(1, n + 1)
            if n % d == 0 and N * d <= max(_STATE_VREGS, N)]
    return max(fits) * _LANES


def _scan_plan(T, C, N, block=None, lanes=None, unroll=None):
    """``(block of positions, lanes of a channel tile, positions a loop
    body)`` of a prompt of ``T`` over ``C`` channels, or None where the
    kernel has no plan."""
    lanes = int(lanes or _tile_lanes(C, N) or 0)
    Q, unroll = int(block or scan_block(T)), int(unroll or UNROLL)
    if not lanes or lanes % _LANES or C % (8 * lanes) or Q % _LANES \
            or Q % unroll or N > 64:
        return None
    return Q, lanes, unroll


def mamba_scan_pallas(u, dt, a, bm, cm, *, block=None, lanes=None,
                      unroll=None, interpret=None):
    """The time-walking scan of a whole prompt (module docstring): a grid
    over (batch, channel tile, block of positions), blocks innermost and
    sequential with the tile's state in VMEM scratch."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, N = _dims(u, a, bm)
    B, T = u.shape[:2]
    plan = _scan_plan(T, C, N, block, lanes, unroll)
    if plan is None:
        raise ValueError("mamba_scan: no block plan for u %s at state %d"
                         % (u.shape, N))
    Q, L, unroll = plan
    if interpret is None:
        interpret = use_interpret()
    Tp, nt = ceil_to(T, Q), C // (8 * L)
    u, dt, bm, cm = (pad_axis(t.astype(jnp.float32), 1, Tp)
                     for t in (u, dt, bm, cm))
    # a position's channels as tiles of [8, lanes]
    u5, dt5 = (t.reshape(B, Tp, nt, 8, L) for t in (u, dt))
    a4 = jnp.moveaxis(a.astype(jnp.float32).reshape(nt, 8, L, N), -1, 1)
    # B_t and C_t as scalars: [B, 2 N, Tp], positions along the last axis
    bc = jnp.swapaxes(jnp.concatenate([bm, cm], axis=-1), 1, 2)
    y, s = checked_pallas_call(
        functools.partial(_scan_kernel, N=N, Q=Q, unroll=unroll),
        name=KERNEL_SCAN, grid=(B, nt, Tp // Q),
        in_specs=[pl.BlockSpec((1, Q, 1, 8, L),
                               lambda b, k, c: (b, c, k, 0, 0)),
                  pl.BlockSpec((1, Q, 1, 8, L),
                               lambda b, k, c: (b, c, k, 0, 0)),
                  pl.BlockSpec((1, N, 8, L), lambda b, k, c: (k, 0, 0, 0)),
                  pl.BlockSpec((1, 2 * N, Q), lambda b, k, c: (b, 0, c),
                               memory_space=pltpu.SMEM)],
        operands=(u5, dt5, a4, bc),
        out_specs=[pl.BlockSpec((1, Q, 1, 8, L),
                                lambda b, k, c: (b, c, k, 0, 0)),
                   pl.BlockSpec((1, 1, N, 8, L),
                                lambda b, k, c: (b, k, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, nt, 8, L), jnp.float32),
                   jax.ShapeDtypeStruct((B, nt, N, 8, L), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, 8, L), jnp.float32)],
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES))
    y = y.reshape(B, Tp, C)[:, :T]
    return y, jnp.moveaxis(s, 1, 2).reshape(B, 1, N, C)


# ------------------------------------------------------------- dispatch
def _note_plan(kernel, form, block):
    from ..observe.families import MAMBA_PLANS

    MAMBA_PLANS.labels(kernel=kernel, form=form, block=str(block)).inc()


def _kernels_on():
    from . import kernels_enabled

    return kernels_enabled() and not use_interpret()


def mamba_update(state, u, dt, a, bm, cm):
    """The one-token update in whichever form this lowering can take:
    the in-place kernel where Pallas compiles (a TPU) and the state has a
    block plan, the composed form elsewhere."""
    if _kernels_on() and _update_plan(state.shape) is not None:
        _note_plan(KERNEL_UPDATE, "pallas", 1)
        return mamba_update_pallas(state, u, dt, a, bm, cm, interpret=False)
    _note_plan(KERNEL_UPDATE, "composed", 1)
    return mamba_update_composed(state, u, dt, a, bm, cm)


def mamba_scan(u, dt, a, bm, cm):
    """The scan of a whole prompt in whichever form this lowering can
    take (as ``mamba_update``), in blocks of ``scan_block``."""
    C, N = _dims(u, a, bm)
    Q = scan_block(u.shape[1])
    if _kernels_on() and _scan_plan(u.shape[1], C, N) is not None:
        _note_plan(KERNEL_SCAN, "pallas", Q)
        return mamba_scan_pallas(u, dt, a, bm, cm, interpret=False)
    _note_plan(KERNEL_SCAN, "composed", Q)
    return mamba_scan_composed(u, dt, a, bm, cm, block=Q)
