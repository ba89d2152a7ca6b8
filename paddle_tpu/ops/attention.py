"""Flash attention: blocked Pallas kernels + custom VJP.

The reference has NO fused attention op — attention is composed from
matmul/softmax/elementwise layer calls (SURVEY §5, e.g.
/root/reference/python/paddle/fluid/tests/unittests/dist_transformer.py).
This op is the TPU-first upgrade slot, implementing the FlashAttention-2
scheme end to end:

  forward:  grid (B*H/heads, Sq/bq, Sk/bk) with the K axis innermost;
            the [Sq,Sk] score matrix never exists in HBM. Saves the
            logsumexp rows.
  backward: two Pallas kernels re-deriving the probabilities from the
            saved logsumexp — dK/dV sweeps query blocks per key block,
            dQ sweeps key blocks per query block, with
            delta = rowsum(dO*O) precomputed outside.

How much of a head's score matrix one grid step computes is planned per
kernel from the call's static shapes (``_block_plan``), because a grid
step costs about as much as the MXU work of a 128x128 block of scores at
D 64 (0.5 us on a v5e: pipeline bookkeeping, five to seven small DMAs,
MXU fill and drain that nothing overlaps). The plan takes the axis a
kernel reduces over in ONE block up to 1024 and gives the other axis
what is left of a 512x512 float32 score tile (S <= 512: one pass over a
whole head), and such a kernel is a different, shorter program, chosen
at trace time:

  * no online-softmax carry: one max, one exp, one sum, one write; no
    m/l/acc scratch, no alpha rescale, no init/emit phases (likewise no
    dK/dV or dQ accumulator);
  * up to four heads a grid step where no [bq, bk] operand has to move
    (no bias, or a key mask), so the per-step cost is paid once for them
    and one head's matmuls overlap the next head's softmax.

Heads a grid step, in both plans, follow the call's static shapes and
nothing else. A single-pass kernel takes the largest count up to
``_HEADS_PER_STEP`` that divides H, fewer by the lane tiles its widest
operand takes (``_heads_per_step``). The MULTI-PASS forward (keys past
1,024, or a window narrower than the keys: the long serving prefills)
takes the largest count up to ``_HEADS_PER_STEP`` that divides H — the
group, for grouped heads, whose query heads then share ONE K/V block,
fetched once for them — and whose VMEM account (``_forward_vmem``: the
blocks twice, two heads' score and probability tiles, the carry) fits
the 16 MiB a kernel here is compiled under (``_forward_heads``, the one
rule ``_forward_pallas`` and tests/test_kernel_plans.py read). The heads
are unrolled in the step with a carry each: a block's three MXU passes
take 1.0 us at the v5e's peak and its float32 softmax 1.4 us on the
vector unit, and one head alone runs them in turn (2.46 us a block at
Xing's 8,192 keys, 1.78 at four heads: docs/KERNELS.md). A full
[Sq, Sk] bias and the multi-pass backward kernels keep one head.

The dK/dV kernel computes the scores TRANSPOSED ([bk, bq] = k q^T)
whenever the bias is absent or a key mask: dV = p^T g and dK = ds^T q
then contract over the minor axis of p^T/ds^T like any matmul, where the
[bq, bk] form first turns two whole score tiles round on the XLU; the
row statistics reach it as lane-dense [1, bq] rows. Longer sequences
keep the blocked scheme with running max/denominator/accumulator in VMEM
scratch (S > 1024: 512x512 blocks). Causal calls skip whole blocks
above the diagonal there, and mask in-kernel only the blocks the
diagonal crosses.

VMEM account of one grid step (v5e: 128 MiB, 16 MiB of it scoped to a
kernel by default), at the largest plan, bq = bk = 512, D = 64, bf16:
q/k/v/g/o blocks 5 x heads x 512 x 128 lanes x 2 B x 2 buffers = 5 MiB at
four heads; float32 [512, 512] temporaries of 1 MiB each (scores,
probabilities, dp, ds and their bf16 copies: five to six alive in the
dK/dV kernel); the statistics are [1, bq] rows, 2 KiB apiece. A full
[Sq, Sk] bias block adds 2 x 1 MiB and a trainable bias' ds output
2 x 1 MiB more, at one head a step. tests/test_chip_bringup.py compiles
every such case against the described chip's limit.

Mosaic layout notes (the round-2 lesson): every operand/output block's
last two dims must be (8,128)-divisible or equal to the array dims. The
per-row logsumexp/delta vectors therefore travel as rank-3 arrays,
never as rank-2 [B*H, S] with (1, bq) blocks (1 is neither 8-divisible
nor equal to B*H): lane-dense [B*H, 1, S] with (heads, 1, bq) blocks
where bq is whole lane tiles (see "row statistics" below: the kernels
turn a row into the [bq, 1] column they need in VMEM), else [B*H, S, 1]
with (1, bq, 1) blocks — a minor dim equal to the array's minor dim of 1
is Mosaic-legal and verified on TPU v5e, but every element of such a
block travels as a 128-lane row.
``_assert_mosaic_ok`` re-implements that rule and gates every
pallas_call here, including in interpret mode, so the CPU test suite
fails on any spec real TPU lowering would reject. Beyond the mirror,
the REAL Mosaic lowering path runs in CI via TPU-target jax.export
(tests/test_tpu_lowering.py): forward + both backward kernels lower to
``tpu_custom_call`` on a CPU-only machine, and tests/test_chip_bringup.py
compiles them for a described v5e (VMEM limits included) — only
execution needs the chip (chip_smoke.py).

Ragged sequence lengths are padded to a whole number of lane tiles (128)
with key-side additive masking (-1e9), never to a multiple of the block:
every planned block divides the padded length, so VMEM stays bounded for
any S and S 640 is not computed as 1024. The one call that pads nothing
is the causal forward with a shared key part (the latent prefill): its
last blocks hang over the operands' end, the causal mask is the padded
keys' mask, and the kernel zeroes the values' rows past the last key.

Layout: q,k,v [B, H, S, D]; bias broadcastable [B|1, H|1, Sq|1, Sk],
additive (-1e9 at masked positions). By default the bias is a constant
mask (stop_gradient applied, so its cotangent is semantically zero);
pass ``bias_grad=True`` for a trainable bias (e.g. relative position) —
the dK/dV kernel then also emits the per-block score gradients, reduced
to the bias' broadcast shape. On non-TPU backends the kernels run in
interpret mode (tests) so numerics match the TPU path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.registry import register_grad_lowering, register_op
from ..kernels.common import (assert_mosaic_ok, ceil_to, checked_pallas_call,
                              pad_axis, pad_len, use_interpret)
from .random_mask import keep_mask

__all__ = ["flash_attention", "flash_attention_with_lse", "pallas_mode",
           "fused_attention_enabled", "flash_min_seq", "flash_effective",
           "composed_attention"]

# Block plan. Each of the three kernels gets (bq, bk) from the call's own
# static shapes (``_block_plan``) and from nothing else. Constraints
# (Mosaic tiling + the validator below): BQ % 8 == 0, BK % 128 == 0.
import os as _os

_LANE = 128        # sequences pad to whole lane tiles, blocks are made of them
_MAX_BLOCK = 512   # longest block edge: a 512x512 float32 score tile is 1 MiB
                   # of VMEM, and each kernel holds a handful of them
_HEADS_PER_STEP = 4  # most heads one grid step takes
# scoped VMEM a kernel here is compiled under (no call asks Mosaic for more)
_VMEM_LIMIT_BYTES = 16 * 1024 * 1024


_MASK = -1e9  # additive mask for padded key columns


def causal_bias_block(s, dtype=None):
    """[1, 1, s, s] additive causal bias: ``_MASK`` strictly above the
    diagonal, 0 elsewhere — the ONE construction shared by the
    trainable-bias causal fold (flash_attention), the ring schedules
    (parallel/ring_attention.py), and tests, so the mask constant and
    dtype can never diverge across paths."""
    r = jnp.arange(s)
    return jnp.where(r[None, :] > r[:, None], jnp.asarray(_MASK),
                     jnp.asarray(0.0)).astype(
        dtype or jnp.float32)[None, None]


# interpret-mode autodetect: hoisted to kernels/common.py (the whole
# kernel tier shares the PADDLE_TPU_FLASH_INTERPRET knob); kept under
# the historical private name for this module's many call sites
_use_interpret = use_interpret


def fused_attention_enabled() -> bool:
    """Single source of truth for the PADDLE_TPU_FUSED_ATTENTION knob
    (default on): models and whoever labels a run must agree on which
    path it exercises."""
    return _os.environ.get("PADDLE_TPU_FUSED_ATTENTION", "1") != "0"


def flash_min_seq() -> int:
    """The sequence length from which the fused-attention op runs the
    Pallas kernel: ``PADDLE_TPU_FLASH_MIN_SEQ`` where it is set, else
    256 (``flash_effective`` has the whole rule).

    Below it ``flash_attention`` lowers to the COMPOSED XLA math
    (materialized [Sq,Sk] scores — fully fused by XLA, no kernel-launch
    or blocked-softmax overhead): at short S the score matrix is tiny
    and the blocked online-softmax scheme costs more than it saves. The
    benchmark has a cell on each side (``bert_train_s128`` composed,
    ``bert_train_s512`` the kernel); the 256 itself is not measured on
    the current code (ROADMAP.md Queue 1).

    The environment's value is a user-set selection between two paths
    (0 forces the kernel always — the hardware A/B lever and what the
    kernel's tests at small S set; a huge value forces composed always):
    a named debt, ROADMAP.md Queue 3. Parsed at call time, not import."""
    raw = _os.environ.get("PADDLE_TPU_FLASH_MIN_SEQ", "256")
    try:
        return int(raw)
    except ValueError:
        raise ValueError(
            "PADDLE_TPU_FLASH_MIN_SEQ must be a decimal integer "
            "(sequence length); got %r" % (raw,)) from None


def flash_effective(seq_len: int, kv_len: int = None) -> bool:
    """Whether the fused-attention op would actually run the Pallas
    kernel at these sequence lengths (chip_smoke.py and the benchmark
    label flash vs composed from this, so a short-S run never claims a
    kernel measurement): ``max(Sq, Sk)`` at or over
    ``PADDLE_TPU_FLASH_MIN_SEQ`` where it is set, else 256
    (tests/test_flash_dispatch.py). An op built with its own
    ``flash_min_seq=`` attribute (latent attention's 128) puts that in
    256's place; the environment still wins."""
    return _flash_decision(seq_len, kv_len)


def _flash_decision(seq_len: int, kv_len: int = None, min_seq: int = None):
    """``flash_effective`` with a caller's own threshold ``min_seq`` in
    place of the static 256 (``fused_attention(flash_min_seq=)``)."""
    sq = int(seq_len)
    s = max(sq, int(kv_len) if kv_len is not None else sq)
    if min_seq is None or _os.environ.get("PADDLE_TPU_FLASH_MIN_SEQ") \
            is not None:
        return s >= flash_min_seq()
    return s >= int(min_seq)


def composed_attention(q, k, v, bias=None, scale=1.0, causal=False,
                       window=None):
    """The unfused attention math the reference composes from layer
    calls (matmul/softmax — SURVEY §5, dist_transformer.py), as one jnp
    expression XLA fuses end to end: scores and softmax in f32 (matching
    the kernel's in-VMEM accumulation dtype), output cast back to the
    input dtype. Used by ``flash_attention`` below ``flash_min_seq()``
    and as the numerics reference everywhere (chip_smoke.py, parity
    tests). ``window`` (with ``causal``) keeps key j for query i iff
    ``0 <= i - j < window``; k and v of fewer heads than q are grouped
    (query head h reads key/value head ``h // (H / Hkv)``)."""
    g = q.shape[1] // k.shape[1]
    if g > 1:
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        keep = jnp.tril(jnp.ones((sq, sk), bool))
        if window is not None:
            keep = jnp.logical_and(
                keep, jnp.triu(jnp.ones((sq, sk), bool), 1 - int(window)))
        s = jnp.where(keep, s, _MASK)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def pallas_mode() -> str:
    """'compiled' (real Mosaic lowering) or 'interpret' — what the flash
    kernels would run as right now. Bench rows record this so an
    accidental interpret fallback on hardware can never masquerade as a
    fused-kernel measurement."""
    return "interpret" if _use_interpret() else "compiled"


_NEG = -1e30


# Mosaic legality mirror + checked pallas_call + padding helpers were
# born here and are now SHARED kernel-tier infrastructure
# (kernels/common.py) — the attention kernels keep their historical
# private names so the blocked-kernel code below reads unchanged.
_assert_mosaic_ok = assert_mosaic_ok
_checked_pallas_call = checked_pallas_call
_ceil_to = ceil_to
_pad_len = pad_len
_pad_axis = pad_axis


def _pad_bias(bias, Sq, Sqp, Sk, Skp):
    """Pad/construct the additive bias so padded key columns are masked.

    Padded *query* rows need no masking (their outputs/grads are sliced
    off, and zero padding in g kills their dK/dV contributions)."""
    if Skp != Sk:
        if bias is None:
            col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, Skp), 3)
            bias = jnp.where(col < Sk, 0.0, _MASK).astype(jnp.float32)
        else:
            if bias.shape[3] == 1:  # key-broadcast bias: materialize to mask
                bias = jnp.broadcast_to(
                    bias, bias.shape[:3] + (Sk,))
            pad = [(0, 0)] * 4
            pad[3] = (0, Skp - bias.shape[3])
            bias = jnp.pad(bias, pad, constant_values=_MASK)
    if bias is not None and bias.shape[2] > 1 and bias.shape[2] != Sqp:
        # mask padded *query* rows too: keeps exp(s - lse) at exactly 0
        # for them in the backward kernels (their grads are sliced off,
        # but a large positive trainable bias could otherwise overflow)
        bias = _pad_axis(bias, 2, Sqp, _MASK)
    return bias


def _bias_spec_and_operand(bias, H, heads, bq, bk, iq_pos, ik_pos,
                           column=False):
    """BlockSpec + operand for a broadcastable bias.

    iq_pos/ik_pos say which grid axes carry the q/k block indices (the
    forward and the two backward kernels order their grids differently);
    grid axis 0 counts groups of ``heads`` consecutive rows of the
    flattened B*H axis (``heads`` divides H, so a group never straddles
    two batch entries). ``column`` hands a key-mask bias [B|1,H|1,1,Sk]
    over as [.., Sk, 1] blocks, for the kernel that computes the scores
    transposed."""
    if column:
        bias = bias.reshape(bias.shape[:2] + (bias.shape[3], 1))
        iq_pos, ik_pos = ik_pos, iq_pos  # keys ride the row axis now
        bq, bk = bk, 1
    Bb, Hb, Sqb, Skb = bias.shape
    blk_h = heads if Hb > 1 else 1
    blk_q = bq if Sqb > 1 else 1
    blk_k = bk if Skb > 1 else 1

    def bias_map(*idx, Bb=Bb, Hb=Hb, Sqb=Sqb, Skb=Skb, H=H):
        bh = idx[0] * heads
        b = (bh // H) if Bb > 1 else 0
        h = ((bh % H) // heads) if Hb > 1 else 0
        return (b, h,
                idx[iq_pos] if Sqb > 1 else 0,
                idx[ik_pos] if Skb > 1 else 0)

    return pl.BlockSpec((1, blk_h, blk_q, blk_k), bias_map), bias


def _bias_block(b_ref, h):
    """Head ``h``'s float32 bias block out of a (1, heads|1, ., .) ref."""
    return b_ref[0, h if b_ref.shape[1] > 1 else 0].astype(jnp.float32)


# ------------------------------------------------------- operand layout
# The kernels take their operands in one of two layouts, picked by the
# operands' rank (docs/KERNELS.md "Operand layouts of the flash kernels"):
#
#   heads  q, k, v [B, H, S, D]: a block is [heads, rows, D] of the
#          flattened [B*H, S, D] array, and a kernel reads head h as
#          ``ref[h]``.
#   lanes  q, k, v [B, S, H*D], as the projections leave them: a block
#          is [1, rows, heads*D] with heads*D whole lane tiles, the head
#          group a block index along the LAST axis, so nothing is
#          transposed round the call. A head narrower than a lane tile
#          (D 64: two a tile) is read as the whole tile that holds it:
#          where a product CONTRACTS over the lanes (q k^T, g v^T) one
#          side has the other heads' lanes zeroed by a lane select (a
#          v5e MXU pass is 128 deep, so the 64-deep product already paid
#          for them); where the lanes are the product's columns (p v,
#          p^T g, ds^T q, ds k) it runs at the tile's full width and the
#          head keeps its own lanes of the result when the tile is
#          written. No lane moves.
#          The forward-only call takes one more form of it, picked by the
#          presence of a key part all heads share (``shared``, the latent
#          prefill): q [B, S, H*D] beside a second part q_r [B, S, H*Dr],
#          ONE tensor [B, Sk, H*(D + Dv)] with a head's keys beside its
#          values (one block, two static lane slices: D and Dv are whole
#          lane tiles) and k_r [B, Sk, Dr], one block a key step for every
#          head; a score is q k^T + q_r k_r^T, two MXU passes as a
#          (D + Dr)-deep contraction costs.
class _Lanes:
    """Where head h lies in a packed [1, rows, heads*D] block. ``pitch``
    and ``first`` place a head's ``D`` lanes inside a wider record a head
    (``first`` lanes into each ``pitch``: a head's keys and its values
    side by side in one tensor, whole lane tiles each)."""

    def __init__(self, D, pitch=None, first=0):
        self.D = D
        self.W = D if D % _LANE == 0 else _LANE   # lanes a head is read in
        self.per = self.W // D                    # heads a tile
        self.pitch, self.first = pitch or self.W, first

    def tile(self, h):
        t = h // self.per
        return slice(t * self.pitch + self.first,
                     t * self.pitch + self.first + self.W)

    def own(self, shape, h):
        """bool ``shape``: the lanes of head ``h`` inside its tile. (This
        and the selects below are ``jax.lax`` primitives, not their
        ``jax.numpy`` wrappers: a step's kernels hold some two thousand of
        them, and each wrapper is a jit to trace — 8 s of a 50 s set-up.)"""
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
        lo = (h % self.per) * self.D
        return jax.lax.bitwise_and(jax.lax.ge(lane, np.int32(lo)),
                                   jax.lax.lt(lane, np.int32(lo + self.D)))


def _lanes_ok(H, D):
    """Whether H heads of width D can be blocked along the lanes: whole
    lane tiles a head, or a whole number of heads a tile and of tiles a
    batch entry."""
    return D % _LANE == 0 or (_LANE % D == 0 and H % (_LANE // D) == 0)


def _head(ref, h, lanes, own=False):
    """Head ``h`` of an operand block. ``own`` (lanes layout, several
    heads a tile) zeroes the other heads' lanes: the side of a
    contraction over the lanes that picks the head."""
    if lanes is None:
        return ref[h]
    x = ref[0, :, lanes.tile(h)]
    if own and lanes.per > 1:
        x = jax.lax.select(lanes.own(x.shape, h), x,
                           jax.lax.full_like(x, 0))
    return x


class _HeadOut:
    """Writes head results into an output block. In the lanes layout a
    result comes a tile wide and holds its head in the head's own lanes;
    the tile is written once, when all its heads are in."""

    def __init__(self, ref, lanes):
        self.ref, self.lanes, self.parts = ref, lanes, []

    def put(self, h, val):
        lanes = self.lanes
        if lanes is None:
            self.ref[h] = val.astype(self.ref.dtype)
            return
        self.parts.append(val)
        if len(self.parts) < lanes.per:
            return
        out = self.parts[-1]
        for i in range(lanes.per - 2, -1, -1):
            out = jax.lax.select(lanes.own(out.shape, i), self.parts[i],
                                 out)
        self.ref[0, :, lanes.tile(h)] = out.astype(self.ref.dtype)
        self.parts = []


def _carry(ref, h):
    """Head ``h``'s running state: a multi-pass plan keeps one a head of
    the step, in both layouts."""
    return ref.at[h]


def _block_spec(lanes, H, heads, rows, width, seq_of):
    """BlockSpec of a q/k/v/g/o block of ``rows`` sequence positions;
    ``seq_of(*grid indices)`` is its block index along the sequence.
    Grid axis 0 counts groups of ``heads`` rows of the flattened B*H
    axis in both layouts."""
    if lanes is None:
        return pl.BlockSpec((heads, rows, width),
                            lambda *idx: (idx[0], seq_of(*idx), 0))
    per_b = np.int32(H // heads)     # (lax: grid indices are not negative)
    return pl.BlockSpec(
        (1, rows, heads * width),
        lambda *idx: (jax.lax.div(idx[0], per_b), seq_of(*idx),
                      jax.lax.rem(idx[0], per_b)))


# --------------------------------------------------------- kernel names
# The names under which the four kernel runs of a layer are found in a
# device profile and in the HLO: XLA names a Pallas custom call after the
# innermost name scope (``flash_fwd.3``; under autodiff
# ``jvp_flash_refwd_.3``, ``jvp_flash_bwd_dkv_.3``), and only that reaches
# the profiler's event. The forward kernel runs twice a layer in a train
# step: once in the forward op, and again when the grad op differentiates
# the forward lowering (``jax.vjp`` runs the ``custom_vjp`` forward rule
# for its residuals; XLA does not merge two custom calls) — the rule's run
# carries its own name so a trace can say whether it went away.
KERNEL_FWD = "flash_fwd"
KERNEL_FWD_WIN = "flash_fwd_win"   # the forward under a causal window
KERNEL_REFWD = "flash_refwd"
KERNEL_BWD_DKV = "flash_bwd_dkv"
KERNEL_BWD_DQ = "flash_bwd_dq"


# ----------------------------------------------------------- block plan
def _largest_block(Sp, cap):
    """The longest block of whole lane tiles that divides the padded
    length ``Sp`` and is at most ``cap`` (never under one tile); a length
    of at most one lane tile is its own block."""
    if Sp <= _LANE:
        return Sp
    n = Sp // _LANE
    return _LANE * max(d for d in range(1, n + 1)
                       if n % d == 0 and (d == 1 or d * _LANE <= cap))


def _block_plan(kernel, Sq, Sk, D, dtype, causal=False, want_db=False,
                window=None):
    """``(bq, bk)`` of one grid step of ``kernel`` (one of the KERNEL_*
    names; the forward's rerun plans like the forward), from what is
    static at trace time.

    A sequence pads to whole lane tiles (128) and never to a multiple of
    the block: each block divides the padded length, so S 500 is one 512
    block and S 640 stays 640. The axis a kernel REDUCES over (keys for
    the forward and dQ, queries for dK/dV) is planned first and taken
    whole up to 1024, because a kernel whose single block covers its
    reduction drops the carry (see the kernels); past that it is cut in
    ``_MAX_BLOCK`` pieces. The other axis takes what is left of a
    ``_MAX_BLOCK``² score tile: 512x512 at S 512, 256x1024 at S 1024,
    128x640 at S 640 (five lane tiles divide by nothing else).

    Measured on a v5e (PERF.md §6, PR 25), which is why these inputs do
    not move the plan: ``causal`` — the larger block won at every length
    tried although it skips fewer blocks above the diagonal (S 1024
    forward: 2.85 ms at 128, 1.45 at 256, 0.87 at 512, 0.65 with the key
    axis whole and nothing skipped); ``want_db``, ``D``, ``dtype`` — the
    score tiles are float32 [bq, bk] whatever the operands, and a full
    [bq, bk] bias or ds block fits beside them at one head a step
    (``_heads_per_step``).

    ``window`` (the forward only) does move it: a band narrower than the
    keys is worth cutting the key axis for, because the blocks wholly
    outside the band are skipped on both sides of it — so the key block
    is at most the window rounded up to lane tiles (and ``_MAX_BLOCK``),
    even where the whole key axis would fit in one block."""
    del D, dtype, causal, want_db
    sq, sk = _pad_len(Sq, _LANE), _pad_len(Sk, _LANE)
    dkv = kernel == KERNEL_BWD_DKV
    red, par = (sq, sk) if dkv else (sk, sq)
    b_red = red if red <= 2 * _MAX_BLOCK else _largest_block(red, _MAX_BLOCK)
    if window is not None and not dkv and window < red:
        b_red = _largest_block(
            red, min(_MAX_BLOCK, _pad_len(int(window), _LANE)))
    b_par = _largest_block(par, _MAX_BLOCK * _MAX_BLOCK // b_red)
    return (b_red, b_par) if dkv else (b_par, b_red)


def _padded_plan(kernel, Sq, Sk, D, dtype, causal, want_db, window=None):
    """``(Sqp, Skp, bq, bk)``: the lengths padded to whole lane tiles
    and the plan's blocks, which divide them."""
    bq, bk = _block_plan(kernel, Sq, Sk, D, dtype, causal, want_db, window)
    return _pad_len(Sq, _LANE), _pad_len(Sk, _LANE), bq, bk


def _forward_plan(S, Sk, D, dtype, causal, window=None):
    """``_padded_plan`` of the forward kernel, whose causal calls may pad
    further than a lane tile."""
    Sp, Skp, bq, bk = _padded_plan(KERNEL_FWD, S, Sk, D, dtype, causal,
                                   False, window)
    if causal and window is None and Skp > bk \
            and min(bq, bk) <= _MAX_BLOCK // 2:
        # a causal length past one key block whose lane tiles divide by
        # no block over 256 (3,328 = 26 tiles: 256x256 blocks, 91 of them
        # a head): pad to whole ``_MAX_BLOCK`` blocks instead. The causal
        # mask already keeps every padded key from every real query, and
        # 28 blocks of 512x512 took half the time of the 91 (97 -> 49 ms
        # for five calls of 128 heads on the chip, PR 32; PERF.md
        # section 6 has the same ratio at S 1024, PR 25)
        Sp, Skp, bq, bk = _padded_plan(
            KERNEL_FWD, _pad_len(S, _MAX_BLOCK), _pad_len(Sk, _MAX_BLOCK),
            D, dtype, causal, False, window)
    return Sp, Skp, bq, bk


def _heads_per_step(H, single_pass, bias, want_db=False, width=_LANE,
                    lanes=None):
    """How many (batch, head) rows one grid step takes. A single-pass
    kernel with no [bq, bk] tile to move (no bias or a key mask, no
    score-gradient output) takes up to ``_HEADS_PER_STEP`` heads a step,
    the largest count that divides H, so that a group stays inside one
    batch entry and one [B,1,1,S] bias block serves it: the per-step
    overhead is paid once, and one head's matmuls overlap the next
    head's softmax. Every other kernel this rule serves (a forward with
    a full bias, the multi-pass backward kernels; the multi-pass forward
    has ``_forward_heads``) keeps one head a step (in the
    lanes layout, the heads of one lane tile, ``lanes.per``: two at D 64,
    each with a carry of its own; every count is a multiple of it): a
    [heads, bq, bk] float32 bias or ds block would not fit VMEM.
    ``width`` is the widest operand's last axis: ``_HEADS_PER_STEP`` is
    what fits at one lane tile of width, and blocks wider than that take
    as many fewer heads as they take more lanes (four heads of a
    256x1024 plan at q/k 192 wide, 256 lanes in VMEM, want 47.6 MB of
    the 46 MB scoped limit: found on the chip, PR 32)."""
    slim = not want_db and (bias is None or bias.shape[2] == 1)
    least = 1 if lanes is None else lanes.per
    if not (single_pass and slim):
        return least
    most = max(least, _HEADS_PER_STEP // -(-int(width) // _LANE))
    return max(g for g in range(least, most + 1, least) if H % g == 0)


def _forward_vmem(heads, bq, bk, single_pass, D, Dv, itemsize, out_itemsize,
                  lanes=None, Dr=0, one_kv=False):
    """Bytes of VMEM one grid step of the forward holds at ``heads`` heads,
    by this module's account: every operand and output block twice (the
    pipeline's two buffers) at its dtype's bytes and its width padded to
    whole lane tiles; the float32 score and probability tiles [bq, bk] of
    the TWO heads the unrolled step has in flight (one head's matmuls
    beside another's softmax); a head's rescaled accumulator and its
    ``p v`` product, float32 [bq, Dv] each; the carry of a multi-pass plan
    (accumulator, row maximum and denominator a head, the two columns a
    lane tile wide each). No bias block: a key mask is a row, and a full
    [Sq, Sk] bias keeps the least count whatever fits. ``Dr`` is the width of a shared key part (its blocks a lane tile
    wide), ``one_kv`` a grouped call whose heads read ONE K/V block. An
    upper bound on what Mosaic allotted at every shape and count compiled
    for a described v5e (docs/KERNELS.md has the table), which is what
    the count rests on; tests/test_chip_bringup*.py compile the cells'."""
    tile = lambda w: _ceil_to(int(w), _LANE)  # noqa: E731
    if lanes is None:
        kv_heads = 1 if one_kv else heads
        q_w, k_w, v_w, o_w = (heads * tile(D), kv_heads * tile(D),
                              kv_heads * tile(Dv), heads * tile(Dv))
    else:       # [1, rows, heads * D]: a block's lanes are packed
        q_w, k_w, v_w, o_w = (tile(heads * D), tile(heads * D),
                              tile(heads * Dv), tile(heads * Dv))
    r_w = tile(Dr) if Dr else 0
    blocks = itemsize * (bq * (q_w + heads * r_w) + bk * (k_w + v_w + r_w)) \
        + out_itemsize * bq * o_w + 4 * heads * 8 * bq
    tiles = 2 * 4 * bq * bk * min(heads, 2) + 2 * 4 * heads * bq * tile(Dv)
    carry = 0 if single_pass else 4 * heads * bq * (tile(Dv) + 2 * _LANE)
    return 2 * blocks + tiles + carry


def _forward_heads(H, group, bq, bk, single_pass, bias, D, Dv, itemsize,
                   out_itemsize, lanes=None, Dr=0):
    """How many query heads one grid step of the FORWARD takes: the one
    rule, read by ``_forward_pallas`` and by tests/test_kernel_plans.py.

    A single-pass plan keeps ``_heads_per_step``'s count (one head for a
    grouped call). A multi-pass plan (keys past 1,024, or a window
    narrower than the keys) takes the largest count, up to
    ``_HEADS_PER_STEP``, that divides ``H`` — ``group`` for a grouped
    call, whose heads then share ONE K/V block fetched once for them — is
    a multiple of the heads a lane tile holds in the lanes layout, and
    whose ``_forward_vmem`` account fits ``_VMEM_LIMIT_BYTES``, the scoped
    VMEM the call is compiled under: the heads are unrolled in the step,
    so one head's MXU passes are issued beside another's softmax. A full
    [Sq, Sk] bias keeps the least count, as everywhere. From the call's
    static shapes alone."""
    least = 1 if lanes is None else lanes.per
    if single_pass:
        return 1 if group > 1 else _heads_per_step(
            H, True, bias, width=D + Dv if Dr else max(D, Dv), lanes=lanes)
    if bias is not None and bias.shape[2] > 1:
        return least
    fits = [g for g in range(least, _HEADS_PER_STEP + 1, least)
            if (group if group > 1 else H) % g == 0
            and _forward_vmem(g, bq, bk, False, D, Dv, itemsize, out_itemsize,
                              lanes=lanes, Dr=Dr, one_kv=group > 1)
            <= _VMEM_LIMIT_BYTES]
    return max(fits, default=least)


def _note_plan(kernel, bq, bk, single_pass, heads, visited=None, lanes=None):
    """``visited`` = (blocks a windowed forward computes, blocks in the
    square): it rides the block label, ``"512x512 76of256"``. ``layout``
    says how the operands came: ``heads`` [B,H,S,D] or ``lanes``
    [B,S,H*D]."""
    from ..observe.families import FLASH_BLOCK_PLANS, FLASH_STEP_HEADS

    block = "%dx%d" % (bq, bk)
    if visited is not None:
        block += " %dof%d" % visited
    FLASH_BLOCK_PLANS.labels(kernel=kernel, block=block,
                             single_pass="1" if single_pass else "0",
                             layout="heads" if lanes is None
                             else "lanes").inc()
    FLASH_STEP_HEADS.labels(kernel=kernel,
                            single_pass="1" if single_pass else "0",
                            heads=str(heads)).inc()


def _band_blocks(nq, nk, bq, bk, window):
    """How many (iq, ik) blocks hold a visible (query, key) pair under a
    causal window: the blocks ``_for_block`` runs."""
    return sum(1 for iq in range(nq) for ik in range(nk)
               if ik * bk <= iq * bq + bq - 1
               and ik * bk + bk - 1 >= iq * bq - (window - 1))


# --------------------------------------------------------------- causal
def _causal_keep(shape, iq, ik, bq, bk, transposed=False, window=None):
    """bool ``shape``: which scores of the (iq, ik) block survive the
    lower-triangular mask — global query position iq*bq+r >= key position
    ik*bk+c — and, under a ``window``, whose key is also among the
    query's last ``window`` positions (qpos - kpos < window). Queries run
    along the rows, or along the columns when the kernel computes the
    scores transposed. ``jax.lax`` primitives, as ``_Lanes.own``: a
    multi-pass step applies one mask to every head it holds."""
    lax = jax.lax
    qpos = lax.add(lax.broadcasted_iota(jnp.int32, shape,
                                        1 if transposed else 0), iq * bq)
    kpos = lax.add(lax.broadcasted_iota(jnp.int32, shape,
                                        0 if transposed else 1), ik * bk)
    keep = lax.ge(qpos, kpos)
    if window is not None:
        keep = lax.bitwise_and(
            keep, lax.lt(lax.sub(qpos, kpos), np.int32(window)))
    return keep


def _masked(s, keep):
    """``s`` with ``_MASK`` where ``keep`` is false."""
    return jax.lax.select(keep, s, jax.lax.full_like(s, _MASK))


def _causal_mask(s, iq, ik, bq, bk, transposed=False, window=None):
    """``s`` of the (iq, ik) block under ``_causal_keep``'s mask."""
    return _masked(s, _causal_keep(s.shape, iq, ik, bq, bk, transposed,
                                   window))


def _for_block(body, causal, iq, ik, bq, bk, window=None):
    """Run ``body(masked)`` for the (iq, ik) block. A causal call skips it
    when it lies entirely above the diagonal (every key position > every
    query position: Mosaic then skips the block's MXU work, ~2x step
    FLOPs saved at long causal S), runs it without the in-kernel mask
    when it lies entirely on or below, and masks only where the diagonal
    crosses it. A ``window`` adds the band's lower edge: a block whose
    every key is older than every query's window is skipped too, and one
    the edge crosses is masked."""
    if not causal:
        body(False)
        return
    below = ik * bk + bk - 1 <= iq * bq
    visible = ik * bk <= iq * bq + bq - 1
    if window is not None:
        # the oldest key the block's LAST query sees is at or before the
        # block's first key: the whole block is inside every query's band
        below = jnp.logical_and(
            below, ik * bk >= iq * bq + bq - 1 - (window - 1))
        visible = jnp.logical_and(
            visible, ik * bk + bk - 1 >= iq * bq - (window - 1))
    pl.when(below)(lambda: body(False))
    pl.when(jnp.logical_and(visible, jnp.logical_not(below)))(
        lambda: body(True))


# ------------------------------------------------------- row statistics
# The per-row logsumexp/delta vectors live in HBM as lane-dense
# [B*H, 1, S] rows wherever a block of them is whole lane tiles: a
# [bq, 1] block of a [B*H, S, 1] array pads every element to a 128-lane
# row (256 KiB of DMA for 2 KiB of numbers at bq 512; measured on a v5e:
# the S 512 forward 0.94 -> 0.74 ms and dQ 1.50 -> 0.96 ms a call).
# The kernels that want them down the rows of a [bq, bk] tile turn
# them round in VMEM (four 128x128 XLU tiles at bq 512). A block that
# is not whole lane tiles (S < 128, an odd forced BQ) keeps the
# [B*H, S, 1] layout: a (1, bq) block would not be Mosaic-legal.
def _stat_rows(bq):
    return bq % _LANE == 0


def _stat_spec(heads, bq, rows, iq_pos):
    """BlockSpec of a statistics block, [heads, 1, bq] of a [B*H, 1, S]
    array or [heads, bq, 1] of a [B*H, S, 1] one; ``iq_pos`` is the grid
    axis that counts query blocks."""
    if rows:
        return pl.BlockSpec((heads, 1, bq),
                            lambda *idx: (idx[0], 0, idx[iq_pos]))
    return pl.BlockSpec((heads, bq, 1),
                        lambda *idx: (idx[0], idx[iq_pos], 0))


def _stat_operand(x, rows):
    """[B*H, S] statistics in the layout ``_stat_spec`` blocks."""
    return x[:, None, :] if rows else x[:, :, None]


def _to_row(col):
    """[r, 1] column -> lane-dense [1, r] row (r in whole lane tiles)."""
    return jnp.broadcast_to(col, (col.shape[0], _LANE)).T[:1]


def _to_col(row):
    """Lane-dense [1, r] row -> [r, 1] column."""
    return jnp.broadcast_to(row, (_LANE, row.shape[1])).T[:, :1]


# --------------------------------------------------------------- forward
def _dot_f32(a, b, ca, cb):
    """``a`` contracted with ``b`` over axes ``ca``/``cb`` at the operands'
    dtype (bf16 hits the MXU at full rate) with f32 accumulation. bf16
    operands name DEFAULT precision outright: under a process-wide
    ``jax_default_matmul_precision=highest`` (the CPU test suite sets it)
    they would otherwise ask Mosaic for an fp32 contraction of bf16
    vectors, which the TPU compiler refuses ("Bad lhs type")."""
    precision = None if a.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _fwd_kernel(*refs, scale, nk, causal, bq, bk, heads, has_bias, rows,
                window=None, lanes=None, shared=None, sk=None, one_kv=False):
    # ``one_kv``: the step's heads are query heads of ONE group and the
    # K/V blocks hold the one head they all read (grouped heads)
    kv = (lambda h: 0) if one_kv else (lambda h: h)
    if shared is None:
        q_ref, k_ref, v_ref = refs[:3]
        n_in, lk, lv, lo = 3, lanes, lanes, lanes
    else:
        # (q, q_r, kv, k_r): a head's keys and values lie side by side in
        # ONE block, and the key part ``k_r`` all heads share is one more
        q_ref, qr_ref, k_ref, kr_ref = refs[:4]
        v_ref = k_ref
        n_in, (lr, lk, lv, lo) = 4, shared
    b_ref = refs[n_in] if has_bias else None
    o_ref, lse_ref = refs[n_in + has_bias:n_in + 2 + has_bias]
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    def scores(h, keep):
        # dots run at the INPUT dtype (bf16 hits the MXU at full rate)
        # with f32 accumulation; only the softmax state is explicitly f32.
        # ``keep`` is the causal mask of a block the diagonal (or the
        # band's edge) crosses, None elsewhere. From here down to the
        # carry the per-head code is ``jax.lax`` primitives, as
        # ``_Lanes.own`` says why: a multi-pass step traces it once a head
        # and branch, in every layer of every prefill program.
        lax = jax.lax
        s = _dot_f32(_head(q_ref, h, lanes, own=True),
                     _head(k_ref, kv(h), lk), 1, 1)       # [bq, bk]
        if shared is not None:
            s = lax.add(s, _dot_f32(_head(qr_ref, h, lr), kr_ref[0], 1, 1))
        s = lax.mul(s, np.float32(scale))
        if b_ref is not None:
            s = lax.add(s, _bias_block(b_ref, h))
        return s if keep is None else _masked(s, keep)

    def mask(masked):
        return _causal_keep((bq, bk), iq, ik, bq, bk, window=window) \
            if masked else None

    def values(h, masked):
        v = _head(v_ref, kv(h), lv)                       # [bk, D]
        if sk is not None and masked:
            # a ragged last key block: the rows past the last key hold
            # whatever the buffer held, and 0 x that is not 0
            row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jax.lax.select(jax.lax.lt(row, sk - ik * bk), v,
                               jax.lax.full_like(v, 0))
        return v

    if nk == 1:
        # one block holds every key of the row: one softmax and one
        # write, no running max/denominator/accumulator to carry
        out = _HeadOut(o_ref, lo)
        keep = mask(causal)
        for h in range(heads):
            v = values(h, causal)
            s = scores(h, keep)
            m = jnp.max(s, axis=-1, keepdims=True)        # [bq, 1]
            p = jnp.exp(s - m)                            # [bq, bk] f32
            l = jnp.sum(p, axis=-1, keepdims=True)
            out.put(h, _dot_f32(p.astype(v.dtype), v, 1, 0) / l)
            lse = m + jnp.log(l)
            lse_ref[h] = _to_row(lse) if rows else lse
        return

    acc_ref, m_ref, l_ref = refs[n_in + 2 + has_bias:]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute(masked):
        # the step's heads unrolled: nothing of one head waits for
        # another, so the scheduler issues one head's MXU passes beside
        # another head's softmax on the vector unit
        lax = jax.lax
        keep = mask(masked)
        for h in range(heads):
            m_h, l_h, acc_h = (_carry(r, h)
                               for r in (m_ref, l_ref, acc_ref))
            v = values(h, masked)
            s = scores(h, keep)
            m_prev = m_h[...]                         # [bq, 1]
            l_prev = l_h[...]
            m_new = lax.max(m_prev, _row_stat(lax.reduce_max, s))
            alpha = lax.exp(lax.sub(m_prev, m_new))
            p = lax.exp(lax.sub(s, m_new))            # [bq, bk] f32
            l_h[...] = lax.add(lax.mul(alpha, l_prev),
                               _row_stat(lax.reduce_sum, p))
            m_h[...] = m_new
            acc_h[...] = lax.add(
                lax.mul(acc_h[...], alpha),
                _dot_f32(lax.convert_element_type(p, v.dtype), v, 1, 0))

    _for_block(_compute, causal, iq, ik, bq, bk, window)

    @pl.when(ik == nk - 1)
    def _emit():
        lax = jax.lax
        out = _HeadOut(o_ref, lo)
        for h in range(heads):
            l = _carry(l_ref, h)[...]
            out.put(h, lax.div(_carry(acc_ref, h)[...], l))
            lse = lax.add(_carry(m_ref, h)[...], lax.log(l))  # [bq, 1]
            lse_ref[h] = _to_row(lse) if rows else lse


def _row_stat(reduce, x):
    """``reduce`` (``lax.reduce_max`` / ``lax.reduce_sum``) of [r, c] ``x``
    along its rows, kept as a [r, 1] column."""
    return jax.lax.expand_dims(reduce(x, (1,)), (1,))


def _packed_dims(q, k, n_head):
    """``(B, H, S, D, Sk)`` of a call in the lanes layout, [B, S, H*D]
    operands with ``n_head`` heads."""
    B, S, HD = q.shape
    return B, int(n_head), S, HD // int(n_head), k.shape[1]


def _forward_pallas(q, k, v, bias, scale, causal=False, name=KERNEL_FWD,
                    window=None, mxu_dtype=None, n_head=None, shared=None):
    """The forward kernel's call. ``window`` (an int, with ``causal``)
    bands the mask: blocks wholly outside the band are skipped on both
    sides and never fetched (the key block index is held inside the band,
    and a block index that does not move is not copied again). ``k`` and
    ``v`` may hold fewer heads than ``q`` (grouped heads): query head
    ``h`` reads key/value head ``h // (H / Hkv)`` through the block
    index, so no repeated copy of K and V exists. ``v`` may have a
    width of its own (latent attention's expanded form: q and k 192 wide,
    v 128): its blocks, the accumulator and the output take ``v``'s, the
    score tile is [bq, bk] whatever the widths. ``mxu_dtype`` rounds the
    operands to it HERE, before the call (XLA folds the convert into
    whatever wrote them, so they are written, and fetched, at half the
    bytes); the output keeps the operands' own dtype. Interpret mode
    multiplies float32 exactly, as the composed form does, and rounds
    nothing.
    Rank-3 operands are the lanes layout ([B, S, H*D] with ``n_head``
    heads, ``flash_attention`` has refused what it does not take): the
    output comes back [B, S, H*D] too, the statistics [B*H, S] always.
    ``shared = (q_r, k_r)`` (rank 3) is latent attention's expanded form
    as its projections write it: ``k`` IS ``v``, one [B, Sk, H*(D + Dv)]
    tensor with head ``h``'s keys beside its values, ``q_r`` [B, S, H*Dr]
    a second part of every head's query and ``k_r`` [B, Sk, Dr] the ONE
    key part all heads share; a score is ``q k^T + q_r k_r^T``. A causal
    call of this form pads nothing: the last blocks are ragged, the mask
    keeps every key past the last from every real query and the kernel
    zeroes the values' rows there."""
    lanes, out_dtype = None, q.dtype
    # what an operand block weighs on the chip (interpret mode rounds
    # nothing, and plans as the chip does)
    itemsize = jnp.dtype(mxu_dtype or q.dtype).itemsize
    if mxu_dtype is not None and not _use_interpret():
        q, k, v = (t.astype(mxu_dtype) for t in (q, k, v))
        if shared is not None:
            shared = tuple(t.astype(mxu_dtype) for t in shared)
    if shared is not None:
        B, S, H, Sk = q.shape[0], q.shape[1], int(n_head), k.shape[1]
        D = q.shape[2] // H
        Hkv, Dv = H, k.shape[2] // H - D
        lanes = _Lanes(D)
    elif q.ndim == 3:
        B, H, S, D, Sk = _packed_dims(q, k, n_head)
        Hkv, Dv, lanes = H, D, _Lanes(D)
    else:
        B, H, S, D = q.shape
        Sk, Hkv, Dv = k.shape[2], k.shape[1], v.shape[3]
    seq = 2 if lanes is None else 1      # the operands' sequence axis
    if causal and S != Sk:
        raise ValueError(
            "causal flash attention requires Sq == Sk (self-attention); "
            "got %d/%d" % (S, Sk))
    if H % Hkv:
        raise ValueError("query heads %d must divide by key/value heads %d"
                         % (H, Hkv))
    group = H // Hkv
    if window is not None:
        window = int(window)
        if not causal or window < 1:
            raise ValueError("a window needs causal=True and window >= 1; "
                             "got causal=%r window=%r" % (causal, window))
        if window >= S:
            window = None      # the band is the whole triangle
    Sp, Skp, bq, bk = _forward_plan(S, Sk, D, q.dtype, causal, window)
    nq, nk = Sp // bq, Skp // bk
    # the causal mask is the padded keys' mask where nothing is padded
    ragged = shared is not None and causal
    if bias is not None or not ragged:
        bias = _pad_bias(bias, S, Sp, Sk, Skp)
    heads = _forward_heads(
        H, group, bq, bk, nk == 1, bias, D, Dv, itemsize,
        jnp.dtype(out_dtype).itemsize, lanes=lanes,
        Dr=shared[1].shape[2] if shared is not None else 0)
    rows = _stat_rows(bq)
    _note_plan(name, bq, bk, nk == 1, heads,
               None if window is None
               else (_band_blocks(nq, nk, bq, bk, window), nq * nk), lanes)
    if not ragged:
        q = _pad_axis(q, seq, Sp)
        k = _pad_axis(k, seq, Skp)
        v = k if shared is not None else _pad_axis(v, seq, Skp)
    if lanes is None:
        q = q.reshape(B * H, Sp, D)
        k, v = k.reshape(B * Hkv, Skp, D), v.reshape(B * Hkv, Skp, Dv)

    def kv_seq(bh, iq, ik):
        if causal and window is None:
            # hold the index at the diagonal: a block above it is skipped
            # and, repeating its neighbour's index, moves no bytes
            ik = jnp.minimum(ik, (iq * bq + bq - 1) // bk)
        if window is not None:
            # hold the index inside the band: the skipped blocks on
            # either side repeat a neighbour's index and move no bytes
            ik = jnp.clip(ik, jnp.maximum(iq * bq - (window - 1), 0) // bk,
                          (iq * bq + bq - 1) // bk)
        return ik

    def kv_map(bh, iq, ik):
        # a grouped call's step holds ``heads`` query heads of one group
        # and the ONE K/V head they read; else a K/V head a query head
        return (bh * heads // group if group > 1 else bh,
                kv_seq(bh, iq, ik), 0)

    q_spec = _block_spec(lanes, H, heads, bq, D, lambda bh, iq, ik: iq)
    if shared is not None:
        # q_r and k_r in whole lane tiles (a head of q_r a tile, zeros
        # behind its Dr lanes: no lane moves in the kernel, and the MXU
        # pass is a tile deep whatever Dr)
        q_r, k_r = shared
        Dr, Wr = k_r.shape[2], _ceil_to(k_r.shape[2], _LANE)
        q_r = _pad_axis(q_r.reshape(B, S, H, Dr), 3, Wr).reshape(B, S, H * Wr)
        k_r = _pad_axis(k_r, 2, Wr)
        if not ragged:
            q_r, k_r = _pad_axis(q_r, 1, Sp), _pad_axis(k_r, 1, Skp)
        in_specs = [
            q_spec,
            _block_spec(lanes, H, heads, bq, Wr, lambda bh, iq, ik: iq),
            _block_spec(lanes, H, heads, bk, D + Dv, kv_seq),
            pl.BlockSpec((1, bk, Wr), lambda bh, iq, ik: (
                jax.lax.div(bh, np.int32(H // heads)), kv_seq(bh, iq, ik),
                0))]
        operands = [q, q_r, k, k_r]
        shared = (_Lanes(Wr), _Lanes(D, D + Dv), _Lanes(Dv, D + Dv, D),
                  _Lanes(Dv))
    else:
        if lanes is None:
            kv_heads = 1 if group > 1 else heads
            kv_specs = [pl.BlockSpec((kv_heads, bk, D), kv_map),
                        pl.BlockSpec((kv_heads, bk, Dv), kv_map)]
        else:
            kv_specs = [_block_spec(lanes, H, heads, bk, D, kv_seq)] * 2
        in_specs = [q_spec] + kv_specs
        operands = [q, k, v]
    if bias is not None:
        spec, opnd = _bias_spec_and_operand(bias, H, heads, bq, bk, 1, 2)
        in_specs.append(spec)
        operands.append(opnd)

    kern = functools.partial(_fwd_kernel, scale=scale, nk=nk, causal=causal,
                             bq=bq, bk=bk, heads=heads,
                             has_bias=bias is not None, rows=rows,
                             window=window, lanes=lanes, shared=shared,
                             sk=Sk if ragged and Sk % bk else None,
                             one_kv=group > 1)
    # a multi-pass plan carries the output, the row maximum and the
    # denominator in VMEM: one of each a head of the step
    carry = lambda *shape: pltpu.VMEM(  # noqa: E731
        (heads,) + shape, jnp.float32)
    out, lse = _checked_pallas_call(
        kern,
        name=name,
        grid=(B * H // heads, nq, nk),
        in_specs=in_specs,
        operands=operands,
        out_specs=[
            _block_spec(lanes, H, heads, bq, Dv, lambda bh, iq, ik: iq),
            _stat_spec(heads, bq, rows, 1),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sp, Dv) if lanes is None
                                 else (B, S if ragged else Sp, H * Dv),
                                 out_dtype),
            jax.ShapeDtypeStruct((B * H, 1, Sp) if rows else (B * H, Sp, 1),
                                 jnp.float32),
        ],
        scratch_shapes=[] if nk == 1 else [
            carry(bq, Dv if lanes is None else _Lanes(Dv).W),
            carry(bq, 1),
            carry(bq, 1),
        ],
        interpret=_use_interpret(),
    )
    lse = lse[:, 0, :S] if rows else lse[:, :S, 0]
    out = out[:, :S]                 # (nothing to cut off a ragged call's)
    return (out.reshape(B, H, S, Dv) if lanes is None else out), lse


# -------------------------------------------------------------- backward
def _dkv_kernel(*refs, scale, nq, causal, bq, bk, heads, has_bias, want_db,
                transposed, rows, lanes=None):
    q_ref, k_ref, v_ref = refs[:3]
    b_ref = refs[3] if has_bias else None
    i = 3 + has_bias
    g_ref, lse_ref, d_ref, dk_ref, dv_ref = refs[i:i + 5]
    ds_ref = refs[i + 5] if want_db else None
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    # the scores as [bq, bk], or transposed as [bk, bq]: then p^T g and
    # ds^T q contract over the minor axis of p/ds like any matmul, where
    # the [bq, bk] form has to turn both tiles round first; the row
    # statistics arrive as [1, bq] rows and a key mask as a [bk, 1] column
    c = 1 if transposed else 0

    def grads(h, masked):
        # lanes layout: the key side picks the head where the lanes
        # contract (k q^T, v g^T); p^T g and ds^T q run a tile wide
        q = _head(q_ref, h, lanes)                # [bq, D]
        k = _head(k_ref, h, lanes, own=True)      # [bk, D]
        v = _head(v_ref, h, lanes, own=True)      # [bk, D]
        g = _head(g_ref, h, lanes)                # [bq, D]
        lse, delta = lse_ref[h], d_ref[h]         # [1, bq] rows, or [bq, 1]
        if rows and not transposed:
            lse, delta = _to_col(lse), _to_col(delta)
        s = (_dot_f32(k, q, 1, 1) if transposed
             else _dot_f32(q, k, 1, 1)) * scale
        if b_ref is not None:
            s = s + _bias_block(b_ref, h)
        if masked:
            s = _causal_mask(s, iq, ik, bq, bk, transposed)
        p = jnp.exp(s - lse)                      # [bq, bk] f32
        # dv = p^T g ; dp = g v^T ; ds = p*(dp-delta)*scale ; dk = ds^T q
        dv = _dot_f32(p.astype(g.dtype), g, c, 0)
        dp = _dot_f32(v, g, 1, 1) if transposed else _dot_f32(g, v, 1, 1)
        ds = p * (dp - delta) * scale
        dk = _dot_f32(ds.astype(q.dtype), q, c, 0)
        if ds_ref is not None:
            # raw score gradient (pre-scale is ds/scale; bias adds after
            # the scale, so its cotangent drops the trailing *scale)
            ds_ref[h] = p * (dp - delta)
        return dk, dv

    if nq == 1:
        # one block holds every query: nothing to accumulate over
        dk_out, dv_out = _HeadOut(dk_ref, lanes), _HeadOut(dv_ref, lanes)
        for h in range(heads):
            dk, dv = grads(h, causal)
            dk_out.put(h, dk)
            dv_out.put(h, dv)
        return

    dk_acc, dv_acc = refs[i + 5 + want_db:]

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute(masked):
        for h in range(heads):
            dk, dv = grads(h, masked)
            _carry(dk_acc, h)[...] += dk
            _carry(dv_acc, h)[...] += dv

    _for_block(_compute, causal, iq, ik, bq, bk)

    @pl.when(iq == nq - 1)
    def _emit():
        dk_out, dv_out = _HeadOut(dk_ref, lanes), _HeadOut(dv_ref, lanes)
        for h in range(heads):
            dk_out.put(h, _carry(dk_acc, h)[...])
            dv_out.put(h, _carry(dv_acc, h)[...])


def _dq_kernel(*refs, scale, nk, causal, bq, bk, heads, has_bias, rows,
               lanes=None):
    q_ref, k_ref, v_ref = refs[:3]
    b_ref = refs[3] if has_bias else None
    i = 3 + has_bias
    g_ref, lse_ref, d_ref, dq_ref = refs[i:i + 4]
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    def grad(h, masked):
        # lanes layout: the query side picks the head where the lanes
        # contract (q k^T, g v^T); ds k runs a tile wide
        k = _head(k_ref, h, lanes)
        s = _dot_f32(_head(q_ref, h, lanes, own=True), k, 1, 1) * scale
        if b_ref is not None:
            s = s + _bias_block(b_ref, h)
        if masked:
            s = _causal_mask(s, iq, ik, bq, bk)
        lse, delta = lse_ref[h], d_ref[h]         # [bq, 1], or [1, bq] rows
        if rows:
            lse, delta = _to_col(lse), _to_col(delta)
        p = jnp.exp(s - lse)
        dp = _dot_f32(_head(g_ref, h, lanes, own=True),
                      _head(v_ref, h, lanes), 1, 1)
        ds = p * (dp - delta) * scale             # [bq, bk] f32
        return _dot_f32(ds.astype(k.dtype), k, 1, 0)

    if nk == 1:
        out = _HeadOut(dq_ref, lanes)
        for h in range(heads):
            out.put(h, grad(h, causal))
        return

    dq_acc, = refs[i + 4:]

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _compute(masked):
        for h in range(heads):
            _carry(dq_acc, h)[...] += grad(h, masked)

    _for_block(_compute, causal, iq, ik, bq, bk)

    @pl.when(ik == nk - 1)
    def _emit():
        out = _HeadOut(dq_ref, lanes)
        for h in range(heads):
            out.put(h, _carry(dq_acc, h)[...])


def _backward_pallas(q, k, v, bias, o, lse, g, scale, want_db=False,
                     g_lse=None, causal=False, n_head=None):
    """dQ, dK, dV (and the score gradient a trainable bias wants) in the
    operands' own layout; rank-3 operands are the lanes layout, as in
    ``_forward_pallas``."""
    lanes = None
    if q.ndim == 3:
        B, H, S, D, Sk = _packed_dims(q, k, n_head)
        lanes = _Lanes(D)
    else:
        B, H, S, D = q.shape
        Sk = k.shape[2]
    BH = B * H
    seq = 2 if lanes is None else 1      # the operands' sequence axis
    plan = lambda kernel: _padded_plan(  # noqa: E731
        kernel, S, Sk, D, q.dtype, causal, want_db)
    Sp, Skp, bq, bk = plan(KERNEL_BWD_DKV)
    bias = _pad_bias(bias, S, Sp, Sk, Skp)
    q, gf, of = (_pad_axis(t, seq, Sp) for t in (q, g, o))
    kf, vf = _pad_axis(k, seq, Skp), _pad_axis(v, seq, Skp)
    if lanes is None:
        qf, kf, vf, gf, of = (t.reshape(BH, t.shape[2], D)
                              for t in (q, kf, vf, gf, of))
        delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32),
                        axis=-1)                   # [BH, Sp]
    else:
        qf = q
        delta = jnp.sum((gf.astype(jnp.float32) * of.astype(jnp.float32))
                        .reshape(B, Sp, H, D), axis=-1)
        delta = delta.transpose(0, 2, 1).reshape(BH, Sp)
    if g_lse is not None:
        # lse cotangent: dlse_i/ds_ij = p_ij, so ds gains +p*g_lse_i —
        # algebraically a -g_lse shift of delta (ds = p*(dp - delta))
        delta = delta - _pad_axis(
            g_lse.reshape(BH, S).astype(jnp.float32), 1, Sp)
    # padded lse rows pair with zero g rows, so their p values are
    # harmless (ds and p^T g both vanish); zero-fill keeps exp() finite
    lse = _pad_axis(lse, 1, Sp)                # [BH, Sp]
    interp = _use_interpret()
    has_bias = bias is not None

    # dK/dV: one key block per (bh, ik), sweep query blocks innermost
    nq, nk = Sp // bq, Skp // bk
    heads = _heads_per_step(H, nq == 1, bias, want_db, lanes=lanes)
    _note_plan(KERNEL_BWD_DKV, bq, bk, nq == 1, heads, lanes=lanes)
    # transposed scores take the statistics as [1, bq] rows (one block
    # over all of a short S is legal too) and a bias that is a key mask;
    # a [Sq, Sk] bias, and the ds tile a trainable one wants back, keep
    # the [bq, bk] orientation
    transposed = (not want_db and (_stat_rows(bq) or bq == Sp)
                  and (not has_bias or bias.shape[2] == 1))
    rows = transposed or _stat_rows(bq)
    q_spec = _block_spec(lanes, H, heads, bq, D, lambda bh, ik, iq: iq)
    k_spec = _block_spec(lanes, H, heads, bk, D, lambda bh, ik, iq: ik)
    in_specs = [q_spec, k_spec, k_spec]
    operands = [qf, kf, vf]
    if has_bias:
        spec, opnd = _bias_spec_and_operand(bias, H, heads, bq, bk, 2, 1,
                                            column=transposed)
        in_specs.append(spec)
        operands.append(opnd)
    stat = _stat_spec(heads, bq, rows, 2)
    in_specs += [q_spec, stat, stat]
    operands += [gf, _stat_operand(lse, rows), _stat_operand(delta, rows)]
    out_specs = [k_spec, k_spec]
    out_shape = [jax.ShapeDtypeStruct(kf.shape, k.dtype),
                 jax.ShapeDtypeStruct(vf.shape, v.dtype)]
    # a multi-pass plan accumulates in VMEM: one accumulator a head
    carry = lambda rows_: pltpu.VMEM(  # noqa: E731
        (heads, rows_, D if lanes is None else lanes.W), jnp.float32)
    if want_db:
        # per-block score grads, written once per grid cell (O(S^2) HBM —
        # only materialized when a trainable bias asks for it)
        out_specs.append(
            pl.BlockSpec((heads, bq, bk), lambda bh, ik, iq: (bh, iq, ik)))
        out_shape.append(
            jax.ShapeDtypeStruct((BH, Sp, Skp), jnp.float32))
    kern = functools.partial(_dkv_kernel, scale=scale, nq=nq, causal=causal,
                             bq=bq, bk=bk, heads=heads, has_bias=has_bias,
                             want_db=want_db, transposed=transposed,
                             rows=rows, lanes=lanes)
    res = _checked_pallas_call(
        kern,
        name=KERNEL_BWD_DKV,
        grid=(BH // heads, nk, nq),
        in_specs=in_specs,
        operands=operands,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[] if nq == 1 else [carry(bk), carry(bk)],
        interpret=interp,
    )
    if want_db:
        dk, dv, ds_full = res
    else:
        dk, dv = res
        ds_full = None

    # dQ: one query block per (bh, iq), sweep key blocks innermost. Its
    # plan may differ from dK/dV's in the blocks, never in the padding.
    _, _, bq, bk = plan(KERNEL_BWD_DQ)
    nq, nk = Sp // bq, Skp // bk
    heads = _heads_per_step(H, nk == 1, bias, lanes=lanes)
    _note_plan(KERNEL_BWD_DQ, bq, bk, nk == 1, heads, lanes=lanes)
    q_spec = _block_spec(lanes, H, heads, bq, D, lambda bh, iq, ik: iq)
    k_spec = _block_spec(lanes, H, heads, bk, D, lambda bh, iq, ik: ik)
    in_specs = [q_spec, k_spec, k_spec]
    operands = [qf, kf, vf]
    if has_bias:
        spec, opnd = _bias_spec_and_operand(bias, H, heads, bq, bk, 1, 2)
        in_specs.append(spec)
        operands.append(opnd)
    rows = _stat_rows(bq)
    stat = _stat_spec(heads, bq, rows, 1)
    in_specs += [q_spec, stat, stat]
    operands += [gf, _stat_operand(lse, rows), _stat_operand(delta, rows)]
    kern = functools.partial(_dq_kernel, scale=scale, nk=nk, causal=causal,
                             bq=bq, bk=bk, heads=heads, has_bias=has_bias,
                             rows=rows, lanes=lanes)
    dq = _checked_pallas_call(
        kern,
        name=KERNEL_BWD_DQ,
        grid=(BH // heads, nq, nk),
        in_specs=in_specs,
        operands=operands,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        scratch_shapes=[] if nk == 1 else [carry(bq)],
        interpret=interp,
    )

    dq, dk, dv = dq[:, :S], dk[:, :Sk], dv[:, :Sk]
    if lanes is None:
        dq = dq.reshape(B, H, S, D)
        dk = dk.reshape(B, H, Sk, D)
        dv = dv.reshape(B, H, Sk, D)
    db = None
    if want_db:
        ds_full = ds_full[:, :S, :Sk].reshape(B, H, S, Sk)
        db = ds_full
    return dq, dk, dv, db


def _reduce_to_bias_shape(ds, bias_shape):
    """Sum the full [B,H,Sq,Sk] score grad down to a broadcastable bias."""
    axes = tuple(i for i, (d, b) in enumerate(zip(ds.shape, bias_shape))
                 if b == 1 and d != 1)
    if axes:
        ds = jnp.sum(ds, axis=axes, keepdims=True)
    return ds


def _attention_reference(q, k, v, bias, scale):
    """Plain-XLA attention: the numeric contract for the kernels.
    One implementation — the short-S production dispatch IS the
    reference (composed_attention above)."""
    bias = None if bias is None else bias.astype(jnp.float32)
    return composed_attention(q, k, v, bias, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fa_maskbias(q, k, v, bias, scale, causal=False, n_head=None):
    out, _ = _forward_pallas(q, k, v, bias, scale, causal=causal,
                             n_head=n_head)
    return out


def _fa_maskbias_fwd(q, k, v, bias, scale, causal=False, n_head=None):
    out, lse = _forward_pallas(q, k, v, bias, scale, causal=causal,
                               name=KERNEL_REFWD, n_head=n_head)
    return out, (q, k, v, bias, out, lse)


def _fa_maskbias_bwd(scale, causal, n_head, res, g):
    q, k, v, bias, o, lse = res
    dq, dk, dv, _ = _backward_pallas(q, k, v, bias, o, lse, g, scale,
                                     causal=causal, n_head=n_head)
    # bias enters through stop_gradient (see flash_attention), so this
    # zero cotangent is discarded upstream — it is structural, not a
    # silently-wrong trainable-bias gradient.
    db = None if bias is None else jnp.zeros_like(bias)
    return dq, dk, dv, db


_fa_maskbias.defvjp(_fa_maskbias_fwd, _fa_maskbias_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fa_trainbias(q, k, v, bias, scale):
    out, _ = _forward_pallas(q, k, v, bias, scale)
    return out


def _fa_trainbias_fwd(q, k, v, bias, scale):
    out, lse = _forward_pallas(q, k, v, bias, scale, name=KERNEL_REFWD)
    return out, (q, k, v, bias, out, lse)


def _fa_trainbias_bwd(scale, res, g):
    q, k, v, bias, o, lse = res
    dq, dk, dv, ds = _backward_pallas(q, k, v, bias, o, lse, g, scale,
                                      want_db=True)
    db = _reduce_to_bias_shape(ds, bias.shape).astype(bias.dtype)
    return dq, dk, dv, db


_fa_trainbias.defvjp(_fa_trainbias_fwd, _fa_trainbias_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fa_with_lse(q, k, v, bias, scale, causal=False):
    return _forward_pallas(q, k, v, bias, scale, causal=causal)


def _fa_with_lse_fwd(q, k, v, bias, scale, causal=False):
    out, lse = _forward_pallas(q, k, v, bias, scale, causal=causal,
                               name=KERNEL_REFWD)
    return (out, lse), (q, k, v, bias, out, lse)


def _fa_with_lse_bwd(scale, causal, res, gs):
    q, k, v, bias, o, lse = res
    g_out, g_lse = gs
    dq, dk, dv, _ = _backward_pallas(q, k, v, bias, o, lse,
                                     g_out.astype(q.dtype), scale,
                                     g_lse=g_lse, causal=causal)
    db = None if bias is None else jnp.zeros_like(bias)
    return dq, dk, dv, db


_fa_with_lse.defvjp(_fa_with_lse_fwd, _fa_with_lse_bwd)


def flash_attention_with_lse(q, k, v, bias=None, scale=1.0, causal=False):
    """Fused attention returning (out [B,H,S,D], lse [B,H,S] row
    log-sum-exps). The lse output is differentiable (its cotangent folds
    into the backward's delta shift), which lets callers merge partial
    attentions over key shards with logaddexp weights —
    parallel/ring_attention.py's flash path builds on this. bias is a
    constant mask here (stop_gradient); causal=True applies the
    triangular mask in-kernel with above-diagonal block skipping (the
    ring path's diagonal step)."""
    bias = None if bias is None else jax.lax.stop_gradient(bias)
    out, lse = _fa_with_lse(q, k, v, bias, scale, causal)
    B, H, S, _ = q.shape
    return out, lse.reshape(B, H, S)


_FORWARD_ONLY = ("fused_attention with a window, grouped key/value heads, a "
                 "value width of its own, a shared key part, mxu_dtype or "
                 "flash_min_seq is forward-only; a training build composes "
                 "its band bias (models/gpt.py build)")


def _forward_only(q, k, v, window=None, mxu_dtype=None, min_seq=None,
                  shared=None):
    """Whether a call asks for what only the serving prefill's forward
    has: a window, fewer key/value heads, a value width of its own, a
    shared key part, the MXU's dtype or a threshold of its own. None of
    it has a backward rule. The lanes layout takes the shared key part
    (with it a value width, ``mxu_dtype`` and a threshold: the latent
    prefill) and refuses the rest."""
    if q.ndim == 3:    # [B, S, H*D]: grouped heads or a value width show
        shaped = (k.shape[-1] != q.shape[-1]    # in the packed width
                  or v.shape[-1] != q.shape[-1])
    else:
        shaped = k.shape[1] != q.shape[1] or v.shape[-1] != q.shape[-1]
    return bool(window) or shaped or bool(mxu_dtype) or bool(min_seq) \
        or shared is not None


def _shared_widths(q, k, v, shared, n_head):
    """``(D, Dv)`` of a call with a shared key part, checked: q
    [B, S, H*D], k and v ONE [B, Sk, H*(D + Dv)] tensor, ``shared`` =
    (q_r [B, S, H*Dr], k_r [B, Sk, Dr])."""
    q_r, k_r = shared
    H, D, Dr = n_head, q.shape[-1] // n_head, k_r.shape[-1]
    Dv = k.shape[-1] // H - D
    if not (q.ndim == k.ndim == q_r.ndim == k_r.ndim == 3
            and k.shape == v.shape and k.shape[-1] % H == 0 and Dv > 0
            and q_r.shape == q.shape[:2] + (H * Dr,)
            and k_r.shape[:2] == k.shape[:2]):
        raise ValueError(
            "a shared key part takes q [B, S, H*D], q_r [B, S, H*Dr], "
            "k_r [B, Sk, Dr] and ONE tensor [B, Sk, H*(D + Dv)] as both k "
            "and v; got q %r q_r %r k %r v %r k_r %r with n_head=%d"
            % (q.shape, q_r.shape, k.shape, v.shape, k_r.shape, H))
    return D, Dv


def _expanded(q, kv, shared, n_head):
    """The rank-4 operands of a call with a shared key part: q and k
    [B, H, S, D + Dr], v [B, H, Sk, Dv], every head's keys rebuilt with
    ``k_r`` behind them — what the kernels' other layout, the composed
    form and the tests' reference take."""
    q_r, k_r = shared
    B, S, H = q.shape[0], q.shape[1], n_head
    Sk, D = kv.shape[1], q.shape[2] // n_head
    qh = jnp.concatenate([q.reshape(B, S, H, D), q_r.reshape(B, S, H, -1)],
                         axis=3).transpose(0, 2, 1, 3)
    kvh = kv.reshape(B, Sk, H, -1).transpose(0, 2, 1, 3)
    kh = jnp.concatenate([kvh[..., :D], jnp.broadcast_to(
        k_r[:, None], (B, H) + k_r.shape[1:])], axis=3)
    return qh, kh, kvh[..., D:]


def _packed_heads(q, n_head):
    """The head count of [B, S, H*D] operands, checked."""
    if not n_head or q.shape[-1] % int(n_head):
        raise ValueError(
            "rank-3 q, k, v are [B, S, H*D] and need n_head, a divisor of "
            "the last axis; got n_head=%r for %r" % (n_head, q.shape))
    return int(n_head)


def _split_heads(x, n_head):
    """[B, S, H*D] -> [B, H, S, D]."""
    B, S, HD = x.shape
    return x.reshape(B, S, n_head, HD // n_head).transpose(0, 2, 1, 3)


def _merge_heads(x):
    """[B, H, S, D] -> [B, S, H*D]."""
    B, H, S, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, H * D)


def _unpacked(fn, q, k, v, n_head):
    """``fn`` over [B, H, S, D] operands for a caller that holds
    [B, S, H*D]: what a lowering runs where the kernels cannot take the
    lanes layout, so a Program means the same everywhere."""
    return _merge_heads(fn(*(_split_heads(t, n_head) for t in (q, k, v))))


def flash_attention(q, k, v, bias=None, scale=1.0, bias_grad=False,
                    causal=False, window=None, mxu_dtype=None,
                    min_seq=None, n_head=None, shared=None):
    """Fused attention. ``bias`` is a constant additive mask by default
    (non-differentiable: stop_gradient is applied); pass
    ``bias_grad=True`` to get the true bias cotangent, at the cost of an
    O(Sq*Sk) score-gradient buffer in the backward pass.

    ``causal=True`` applies the lower-triangular mask IN-KERNEL and
    skips key blocks entirely above the diagonal via pl.when — ~2x the
    step FLOPs of a dense mask at long S (decoder self-attention should
    pass this instead of a materialized causal bias; a padding bias may
    still be passed alongside). Requires Sq == Sk. Composes with
    ``bias_grad=True`` by materializing the triangular mask into the
    bias term (the trainable-bias kernels keep dense blocks anyway, so
    no block-skip is lost relative to that path).

    ``window`` (an int, with ``causal``) keeps key j for query i iff
    ``0 <= i - j < window``; ``k``/``v`` with fewer heads than ``q`` are
    grouped heads; ``v`` narrower or wider than ``q``/``k`` gives an
    output of ``v``'s width; ``mxu_dtype`` rounds float32 operands to it
    before the kernel (one bf16 MXU pass, as XLA's own float32 products
    at the TPU's default precision, on operands written and fetched at
    half the bytes; the composed form is left to XLA's). Each is the
    serving prefill's FORWARD-ONLY call: the
    kernel runs under the name ``flash_fwd_win`` (a window) or
    ``flash_fwd`` and no backward rule exists for it (the training build
    of a windowed layer composes its band bias instead). ``min_seq`` is
    the caller's threshold for the kernel in place of the static 256;
    the environment's still wins.

    Rank-3 ``q``, ``k``, ``v`` are [B, S, H*D] with ``n_head`` heads, as
    the projections leave them, and the output comes back so: where the
    kernel runs it blocks the heads along the lanes and nothing is
    transposed round it; below the kernel's threshold, or at a head width
    that does not tile the lanes, the call unpacks to [B, H, S, D] and
    means the same. ``bias`` keeps its [B|1, H|1, Sq|1, Sk] form.

    ``shared = (q_r, k_r)`` (rank 3, forward-only) is latent attention's
    expanded form read where its projections wrote it: ``k`` and ``v``
    are ONE tensor [B, Sk, H*(D + Dv)], head ``h``'s keys beside its
    values, ``q_r`` [B, S, H*Dr] is a second part of every head's query
    and ``k_r`` [B, Sk, Dr] the one key part all heads share: a score is
    ``q k^T + q_r k_r^T``, and no head's keys or values are built. It
    takes ``mxu_dtype`` and ``min_seq``; where ``D`` or ``Dv`` is not
    whole lane tiles, or under the threshold, it builds the rank-4
    operands inside and means the same. The other forward-only features
    and a trainable bias are rank-4 only."""
    if q.ndim == 3:
        n_head = _packed_heads(q, n_head)
        if shared is not None:
            D, Dv = _shared_widths(q, k, v, shared, n_head)
            if window is not None or bias_grad:
                raise NotImplementedError(
                    _FORWARD_ONLY + "; a shared key part takes no window "
                    "and no trainable bias")
            if not (_flash_decision(q.shape[1], k.shape[1], min_seq)
                    and D % _LANE == 0 and Dv % _LANE == 0):
                return _merge_heads(flash_attention(
                    *_expanded(q, k, shared, n_head), bias, scale,
                    causal=causal, mxu_dtype=mxu_dtype, min_seq=min_seq))
        elif bias_grad or _forward_only(q, k, v, window, mxu_dtype):
            raise NotImplementedError(
                _FORWARD_ONLY + "; [B, S, H*D] operands take none of them, "
                "nor a trainable bias")
        elif not (_flash_decision(q.shape[1], k.shape[1], min_seq)
                  and _lanes_ok(n_head, q.shape[-1] // n_head)):
            return _unpacked(
                lambda a, b, c: flash_attention(
                    a, b, c, bias, scale, causal=causal, min_seq=min_seq),
                q, k, v, n_head)
    elif shared is not None:
        raise ValueError("a shared key part takes [B, S, H*D] operands")
    grouped = shared is not None or (
        q.ndim == 4 and (k.shape[1] != q.shape[1]
                         or v.shape[-1] != q.shape[-1]
                         or mxu_dtype is not None))
    if window is not None or grouped:
        if bias_grad:
            raise ValueError("a window, grouped key/value heads, a value "
                             "width of its own or mxu_dtype take no "
                             "trainable bias: the call is forward-only")
        if window is not None and not causal:
            raise ValueError("window=%r needs causal=True" % (window,))
    if causal and bias_grad:
        # trainable bias + causal (e.g. a learned relative-position
        # bias on a decoder): materialize the triangular mask INTO the
        # bias term. Nothing is lost vs an in-kernel mask — the
        # trainable-bias kernels keep dense blocks anyway (the O(Sq*Sk)
        # score-grad buffer forbids block skipping) — and the bias
        # cotangent stays exact: masked positions carry zero
        # probability, hence zero ds. The mask rides outside the
        # custom_vjp, so autodiff routes the ds cotangent through the
        # add to the caller's bias only.
        if bias is None:
            bias_grad = False  # nothing trainable: plain causal path
        else:
            S, Sk = q.shape[2], k.shape[2]
            if S != Sk:
                raise ValueError(
                    "causal flash attention requires Sq == Sk "
                    "(self-attention); got Sq=%d Sk=%d" % (S, Sk))
            bias = bias + jax.lax.stop_gradient(
                causal_bias_block(S, bias.dtype))
            causal = False
    from .. import kernels

    use_flash = _flash_decision(q.shape[-2], k.shape[-2], min_seq)
    kernels.note_decision("attention", "flash" if use_flash else "composed")
    if kernels.kernels_enabled():
        from ..observe.families import KERNEL_DISPATCHES

        # counted a compile, like the kernels' plan families (and
        # PADDLE_TPU_KERNELS=0 moves nothing)
        KERNEL_DISPATCHES.labels(
            op="attention",
            impl="pallas" if use_flash else "composed").inc()
    if not use_flash:
        # short-S dispatch: the composed XLA path wins below the
        # threshold (flash_effective). Same numerics, same bias semantics
        # (constant mask unless bias_grad — autodiff then yields the
        # true bias cotangent, like the trainable-bias kernel)
        cbias = bias if (bias is None or bias_grad) \
            else jax.lax.stop_gradient(bias)
        return composed_attention(q, k, v, cbias, scale, causal, window)
    if window is not None or grouped:
        banded = window is not None and int(window) < q.shape[2]
        return _forward_pallas(
            q, k, v, bias, scale, causal=causal, window=window,
            name=KERNEL_FWD_WIN if banded else KERNEL_FWD,
            mxu_dtype=mxu_dtype, n_head=n_head, shared=shared)[0]
    if bias is None:
        return _fa_maskbias(q, k, v, None, scale, causal, n_head)
    if bias_grad:
        return _fa_trainbias(q, k, v, bias, scale)
    return _fa_maskbias(q, k, v, jax.lax.stop_gradient(bias), scale,
                        causal, n_head)


def _seg_mask_full(seg):
    """[B,S] packed segment ids -> [B,1,S,S] additive block-diagonal
    mask (same-segment AND key-is-real; 0 = padding). The single-device
    fallback for SegmentIds — the sp ring path never materializes it
    (it applies the same rule per ring pair)."""
    from ..parallel.ring_attention import _seg_mask

    return _seg_mask(seg, seg)


def _maybe_shard_mapped_flash(ctx, q, k, v, bias, scale, causal=False,
                              seg=None, window=None, mxu_dtype=None,
                              min_seq=None, n_head=None, shared=None):
    """Mosaic kernels cannot be auto-partitioned by the SPMD partitioner
    (jax raises at multi-device lowering), so under a ParallelEngine mesh
    the op-level flash call wraps itself in shard_map: batch shards over
    the engine's data axis, heads over the 'model' axis (when they
    divide), everything else replicated inside. When the mesh carries a
    sequence axis ('seq') that divides S — and the bias is in key-mask
    form [B|1,1,1,S] — self-attention rides RING ATTENTION instead: the
    sequence stays sharded, K/V blocks hop the ring via lax.ppermute,
    and per-shard partials merge by logsumexp (parallel/ring_attention
    .py) — the sp-native long-context path, never an S-gather. The ring
    branch engages on every backend (its composed per-step path is plain
    jnp on CPU; the flash per-step kernels on TPU); the plain wrap only
    engages on the compiled path — CPU interpret mode lowers to
    partitionable jax ops. Pinned by tests/test_tpu_lowering.py::
    test_dp_tp_train_step_lowers_for_tpu (NotImplementedError without
    the wrap) and the sp ring tests.

    Rank-3 operands ([B, S, H*D], ``n_head`` heads) keep their layout
    through the plain wrap (batch over the data axis, the packed axis
    over 'model' where the heads divide); the ring, and a shard whose
    heads do not tile the lanes, unpack to [B, H, S, D] here and run what
    rank 4 runs."""
    mesh = getattr(ctx, "mesh", None)
    packed = q.ndim == 3
    if _forward_only(q, k, v, window, mxu_dtype, min_seq, shared):
        if packed and shared is None:
            raise NotImplementedError(
                _FORWARD_ONLY + "; [B, S, H*D] operands take none of them "
                "without a shared key part")
        # the serving prefill's forward-only call: one device, no ids
        if seg is not None or (mesh is not None and mesh.size > 1
                               and not _in_manual_mesh()):
            raise NotImplementedError(
                "fused_attention with a window, grouped key/value heads, "
                "a value width of its own, a shared key part, mxu_dtype or "
                "flash_min_seq runs on one device and takes no segment ids")
        return flash_attention(q, k, v, bias, scale, causal=causal,
                               window=window, mxu_dtype=mxu_dtype,
                               min_seq=min_seq, n_head=n_head, shared=shared)

    def local(a, b, c, d=None, heads=n_head):
        return flash_attention(a, b, c, d, scale, causal=causal,
                               n_head=heads)

    if mesh is None or mesh.size <= 1 or _in_manual_mesh():
        # _in_manual_mesh: already inside a shard_map region (pipeline
        # stage bodies, ring steps) — Mosaic-in-manual-mesh is the
        # supported pattern; nesting shard_map is a trace error
        if seg is not None:
            sm = _seg_mask_full(seg)
            bias = sm if bias is None else bias + sm
        return local(q, k, v, bias)

    from jax.sharding import PartitionSpec as P

    B, S = q.shape[0], q.shape[-2]
    H = _packed_heads(q, n_head) if packed else q.shape[1]
    d_ax = getattr(ctx, "data_axis", "data")
    m_ax = getattr(ctx, "model_axis", "model")
    s_ax = getattr(ctx, "seq_axis", "seq")
    b_ax = d_ax if (d_ax in mesh.axis_names and mesh.shape[d_ax] > 1
                    and B % mesh.shape[d_ax] == 0) else None
    h_ax = m_ax if (m_ax in mesh.axis_names
                    and mesh.shape[m_ax] > 1
                    and H % mesh.shape[m_ax] == 0) else None
    seq_live = s_ax in mesh.axis_names and mesh.shape[s_ax] > 1
    if packed:
        if h_ax is not None:
            n_head = H // mesh.shape[h_ax]      # a shard's heads
        if seq_live or not _lanes_ok(n_head, q.shape[-1] // H):
            # the ring shards [B, H, S, D] along S; a shard's heads that
            # do not tile the lanes: run what rank 4 runs here
            return _unpacked(
                lambda a, b, c: _maybe_shard_mapped_flash(
                    ctx, a, b, c, bias, scale, causal, seg=seg), q, k, v, H)

    ring_ok = (seq_live
               and q.shape == k.shape and S % mesh.shape[s_ax] == 0
               and (bias is None or (bias.shape[1] == 1
                                     and bias.shape[2] == 1
                                     and bias.shape[3] == S)))
    if ring_ok:
        from ..parallel.ring_attention import ring_attention

        use_flash = not _use_interpret()
        qs = P(b_ax, h_ax, s_ax, None)
        bspec = None if bias is None else P(
            b_ax if bias.shape[0] != 1 else None, None, None, s_ax)
        # packed segment ids shard exactly like the sequence: the local
        # shard is the query side, a travelling copy is the key side
        sspec = None if seg is None else P(b_ax, s_ax)

        def ring(a, b, c, d=None, s=None):
            return ring_attention(a, b, c, scale, s_ax, causal=causal,
                                  kv_bias=d, use_flash=use_flash, seg=s)

        in_specs, args = (qs,) * 3, (q, k, v)
        ring_fn = ring
        if bias is not None and seg is not None:
            in_specs, args = in_specs + (bspec, sspec), args + (bias, seg)
        elif bias is not None:
            in_specs, args = in_specs + (bspec,), args + (bias,)
        elif seg is not None:
            in_specs, args = in_specs + (sspec,), args + (seg,)
            ring_fn = lambda a, b, c, s: ring(a, b, c, None, s)  # noqa: E731
        fn = jax.shard_map(ring_fn, mesh=mesh, in_specs=in_specs,
                           out_specs=qs, check_vma=False)
        return fn(*args)

    if seg is not None:
        # sharded but no seq axis (dp/tp only): fold the pack mask into
        # the bias and take the plain sharded-batch path below
        sm = _seg_mask_full(seg)
        bias = sm if bias is None else bias + sm
    if _use_interpret():
        return local(q, k, v, bias)

    # the heads: axis 1 of [B, H, S, D], the last of [B, S, H*D]
    qs = P(b_ax, None, h_ax) if packed else P(b_ax, h_ax)
    args, specs = (q, k, v), (qs, qs, qs)
    if bias is not None:
        args += (bias,)
        specs += (P(b_ax if bias.shape[0] != 1 else None,
                    h_ax if bias.shape[1] != 1 else None),)
    return jax.shard_map(functools.partial(local, heads=n_head), mesh=mesh,
                         in_specs=specs, out_specs=qs)(*args)


def _in_manual_mesh() -> bool:
    """True when tracing inside a shard_map region (some mesh axis is
    already Manual) — nesting another shard_map there is a trace error."""
    try:
        cur = jax.sharding.get_abstract_mesh()
    except Exception:
        return False
    return cur is not None and any(
        "Manual" in str(t) for t in getattr(cur, "axis_types", ()))


def _shared_of(ins):
    """``(q_r, k_r)`` of an op built with a shared key part, else None."""
    if not ins.get("KR"):
        return None
    return ins["QR"][0], ins["KR"][0]


@register_op("fused_attention", diff_inputs=["Q", "K", "V"], uses_rng=True)
def _fused_attention(ctx, ins, attrs):
    q = ins["Q"][0]
    k = ins["K"][0]
    v = ins["V"][0]
    bias = (ins.get("Bias") or [None])[0]
    seg = (ins.get("SegmentIds") or [None])[0]
    scale = attrs.get("scale", 1.0)
    dropout = attrs.get("dropout", 0.0)
    causal = bool(attrs.get("causal", False))
    window = int(attrs.get("window", 0) or 0) or None
    if bias is not None:
        bias = bias.astype(jnp.float32)  # mask bias adds in f32 in-kernel
    out = _maybe_shard_mapped_flash(
        ctx, q, k, v, bias, scale, causal, seg=seg, window=window,
        mxu_dtype=attrs.get("mxu_dtype") or None,
        min_seq=attrs.get("flash_min_seq") or None,
        n_head=attrs.get("n_head"), shared=_shared_of(ins))
    if dropout and not (attrs.get("is_test", False) or ctx.is_test):
        # dropout on the *output* (weights-dropout does not commute with the
        # fused kernel; divergence from the layer-composed path documented).
        # The mask is a saved output so the grad op can replay it without
        # RNG (same pattern as the dropout op, ops/nn.py).
        keep = 1.0 - dropout
        mask = keep_mask(ctx, ctx.next_rng(), keep, out.shape,
                         "fused_attention").astype(out.dtype) / keep
    else:
        mask = jnp.ones_like(out)
    return {"Out": [out * mask], "Mask": [mask]}


@register_grad_lowering("fused_attention")
def _fused_attention_grad(ctx, ins, attrs):
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    bias = (ins.get("Bias") or [None])[0]
    seg = (ins.get("SegmentIds") or [None])[0]
    mask = (ins.get("Mask") or [None])[0]
    g = ins["Out@GRAD"][0]
    if _forward_only(q, k, v, attrs.get("window"), attrs.get("mxu_dtype"),
                     attrs.get("flash_min_seq"), _shared_of(ins)):
        raise NotImplementedError(_FORWARD_ONLY)
    if mask is not None:
        g = (g * mask).astype(q.dtype)
    if bias is not None:
        bias = bias.astype(jnp.float32)
    scale = attrs.get("scale", 1.0)
    causal = bool(attrs.get("causal", False))
    _, vjp = jax.vjp(
        lambda a, b, c: _maybe_shard_mapped_flash(
            ctx, a, b, c, bias, scale, causal, seg=seg,
            n_head=attrs.get("n_head")), q, k, v)
    dq, dk, dv = vjp(g.astype(q.dtype))
    return {"Q@GRAD": [dq], "K@GRAD": [dk], "V@GRAD": [dv]}
