"""Ring attention: sequence/context parallelism over the mesh.

The reference has NO sequence parallelism (SURVEY §5 "Long-context …
Absent") — this is the TPU-first extension slot called out there. Design
follows blockwise/ring attention: the sequence axis is sharded over a mesh
axis; each step every device computes flash-style partial attention
(running max / numerator / denominator) against its current K/V block,
then rotates K/V one hop around the ring with lax.ppermute so compute
overlaps the ICI transfer. After n_shards steps every query block has seen
every key block without any device ever holding the full sequence.

Use under shard_map with q,k,v sharded on the sequence dim:

    mesh = Mesh(devices, ("sp",))
    f = shard_map(lambda q,k,v: ring_attention(q,k,v,scale=s,axis_name="sp",
                                               causal=True),
                  mesh=mesh, in_specs=P(None,None,"sp",None),
                  out_specs=P(None,None,"sp",None), check_vma=False)

check_vma=False is part of the contract for the flash paths: pallas
interpret mode (the CPU test backend) evaluates kernels through jax's
hlo_interpreter, whose internal index bookkeeping is not varying-manner
consistent — strict vma rejects it inside jax itself. The engine's
op-level wrap (ops/attention.py) already passes it.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ring_attention"]


def _seg_mask(q_seg, k_seg):
    """Additive block-diagonal mask from packed segment ids.
    q_seg:[B,Sq] k_seg:[B,Sk] -> [B,1,Sq,Sk]; a key is visible iff it
    shares the query's segment id AND is a real token (seg id > 0 —
    pack_sequences reserves 0 for padding). Computed per ring pair from
    two [B,Sl] id vectors, so the full [S,S] pack bias is NEVER
    materialized anywhere on the sp path."""
    keep = ((q_seg[:, :, None] == k_seg[:, None, :])
            & (k_seg[:, None, :] > 0))
    return jnp.where(keep, 0.0, -1e9)[:, None].astype(jnp.float32)


def _block_partials(q, k, v, scale, mask):
    """Unnormalised flash partials for one K/V block.
    q:[B,H,Sq,D] k,v:[B,H,Sk,D] mask:[...,Sq,Sk] additive or None.
    Returns o_hat (= sum_j exp(s - m) v_j), m (rowmax), l (rowsum)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        s = s + mask
    m = jnp.max(s, axis=-1)                        # [B,H,Sq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                        # [B,H,Sq]
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o, m, l


def ring_attention(q, k, v, scale: float, axis_name: str,
                   causal: bool = False,
                   kv_bias: Optional[jax.Array] = None,
                   use_flash: bool = False,
                   schedule: str = "auto",
                   seg: Optional[jax.Array] = None):
    """Attention over a sequence sharded on `axis_name`.

    q,k,v: [B,H,Sl,D] local shards. kv_bias: [B,1,1,Sl] additive bias that
    travels with the K/V blocks (e.g. padding mask). causal=True applies
    the global lower-triangular mask using ring positions.

    (Telemetry: counts one `ring_ppermute` collective per trace.)

    seg: [B,Sl] packed segment ids sharded like the sequence (local
    shard; 0 = padding) — enables PACKED training (multiple documents
    per row, reader.pack_sequences layout) under sp: the local ids are
    the query side, a travelling copy rides the ring as the key side,
    and each pair applies the block-diagonal same-segment mask from the
    two id vectors (see _seg_mask). O(Sl^2) per pair instead of an
    [S,S] pack bias.

    use_flash=True runs each ring step through the Pallas flash kernel
    (ops/attention.py flash_attention_with_lse) instead of a
    materialized [Sl, Sl] score block: per-step VMEM stays O(block)
    regardless of the local shard length, and the normalized partials
    merge with logaddexp weights — the fully-fused long-context path.
    Differentiable end to end (the per-step custom VJPs compose with the
    plain-jnp merge).

    schedule: "auto" (default) runs the zigzag/striped chunk assignment
    for causal rings — flash AND plain per-pair kernels (requires >1
    ring devices and an even local shard length; falls back to
    contiguous otherwise) — balanced causal work, ~2x the contiguous
    schedule's wall-clock at long S. "contiguous" forces the plain
    assignment; "zigzag" demands the striped one and raises when its
    requirements don't hold.
    """
    if schedule not in ("auto", "contiguous", "zigzag"):
        raise ValueError("schedule must be auto|contiguous|zigzag")
    from ..observe.families import ENGINE_COLLECTIVES

    ENGINE_COLLECTIVES.labels(kind="ring_ppermute").inc()  # per trace
    n_static = int(lax.psum(1, axis_name))
    want_zigzag = (schedule == "zigzag"
                   or (schedule == "auto" and causal))
    if want_zigzag and causal and n_static > 1 and q.shape[2] % 2 == 0:
        return _ring_attention_zigzag(q, k, v, scale, axis_name,
                                      kv_bias, use_flash, seg=seg)
    if schedule == "zigzag":
        raise ValueError(
            "zigzag schedule requires causal=True, >1 ring devices "
            "and an even local shard length")
    if use_flash:
        return _ring_attention_flash(q, k, v, scale, axis_name, causal,
                                     kv_bias, seg=seg)
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, H, Sl, D = q.shape
    perm = [(j, (j + 1) % n) for j in range(n)]

    q32 = q.astype(jnp.float32)
    neg = jnp.float32(-1e9)

    def step(i, carry):
        o_acc, m_acc, l_acc, k_cur, v_cur, b_cur, s_cur = carry
        src = (idx - i) % n                        # origin block of k_cur
        mask = None
        if causal:
            q_pos = idx * Sl + jnp.arange(Sl)      # global query positions
            k_pos = src * Sl + jnp.arange(Sl)
            mask = jnp.where(k_pos[None, :] > q_pos[:, None], neg, 0.0)
            mask = mask[None, None]
        if s_cur is not None:
            sm = _seg_mask(seg, s_cur)
            mask = sm if mask is None else mask + sm
        if b_cur is not None:
            bm = b_cur.astype(jnp.float32)
            mask = bm if mask is None else mask + bm
        o, m, l = _block_partials(q32, k_cur, v_cur, scale, mask)
        new_m = jnp.maximum(m_acc, m)
        a = jnp.exp(m_acc - new_m)
        b = jnp.exp(m - new_m)
        o_acc = o_acc * a[..., None] + o * b[..., None]
        l_acc = l_acc * a + l * b
        k_cur, v_cur, b_cur, s_cur = _rotate(axis_name, perm,
                                             k_cur, v_cur, b_cur, s_cur)
        return o_acc, new_m, l_acc, k_cur, v_cur, b_cur, s_cur

    o0 = jnp.zeros((B, H, Sl, D), jnp.float32)
    m0 = jnp.full((B, H, Sl), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sl), jnp.float32)
    carry = (o0, m0, l0, k, v, kv_bias, seg)
    # the ring length is static (mesh-axis size), so the loop unrolls and
    # XLA pipelines each ppermute against the next block's matmuls
    for i in range(int(n)):
        carry = step(i, carry)
    o_acc, _, l_acc = carry[0], carry[1], carry[2]
    return (o_acc / l_acc[..., None]).astype(q.dtype)


def _rotate(axis_name, perm, *vals):
    """One ring hop for every (possibly None) travelling value."""
    return [v if v is None else lax.ppermute(v, axis_name, perm)
            for v in vals]


def _ring_attention_flash(q, k, v, scale, axis_name, causal, kv_bias,
                          seg=None):
    """Flash-kernel ring: each step yields a NORMALIZED partial (out, lse)
    from the Pallas kernel; partials over key shards merge with
    logaddexp weights (out = sum_i out_i * softmax_i(lse_i)).

    Causality needs no per-step [Sl, Sl] position mask: with equal
    shards, only the diagonal block (ring step 0, a STATIC index) is
    partially masked; every other block is fully visible (source shard
    strictly earlier) or fully hidden (strictly later), so its merge is
    gated by one per-device boolean instead of a materialized mask. The
    kv padding bias stays in its broadcastable [B, 1, 1, Sl] form the
    kernel streams natively."""
    from ..ops.attention import flash_attention_with_lse

    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, H, Sl, D = q.shape
    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(i, carry):
        o_acc, lse_acc, k_cur, v_cur, b_cur, s_cur = carry
        bias = None if b_cur is None else b_cur.astype(jnp.float32)
        if s_cur is not None:
            # packed rows: per-pair [B,1,Sl,Sl] same-segment mask from
            # the two id vectors (O(Sl^2) per step, never [S,S])
            sm = _seg_mask(seg, s_cur)
            bias = sm if bias is None else bias + sm
        # diagonal block (ring step 0, src == idx): the kernel's causal
        # path masks in-VMEM and skips above-diagonal key blocks — no
        # materialized [Sl, Sl] diagonal bias
        o_i, lse_i = flash_attention_with_lse(
            q, k_cur, v_cur, bias, scale, causal=causal and i == 0)
        new_lse = jnp.logaddexp(lse_acc, lse_i)
        w_acc = jnp.exp(lse_acc - new_lse)[..., None]
        w_i = jnp.exp(lse_i - new_lse)[..., None]
        o_new = o_acc * w_acc + o_i.astype(jnp.float32) * w_i
        if causal and i > 0:
            # src = (idx - i) % n is an earlier shard iff idx >= i;
            # otherwise the block is entirely in the future: keep acc
            visible = idx >= i
            o_new = jnp.where(visible, o_new, o_acc)
            new_lse = jnp.where(visible, new_lse, lse_acc)
        k_cur, v_cur, b_cur, s_cur = _rotate(axis_name, perm,
                                             k_cur, v_cur, b_cur, s_cur)
        return o_new, new_lse, k_cur, v_cur, b_cur, s_cur

    o0 = jnp.zeros((B, H, Sl, D), jnp.float32)
    lse0 = jnp.full((B, H, Sl), -jnp.inf, jnp.float32)
    carry = (o0, lse0, k, v, kv_bias, seg)
    for i in range(int(n)):
        carry = step(i, carry)
    return carry[0].astype(q.dtype)


# ------------------------------------------------------- zigzag schedule
def _zigzag_permutes(n):
    """Chunk-routing permutations between the contiguous layout (device
    i holds global chunks {2i, 2i+1}) and the zigzag layout (device d
    holds {d, 2n-1-d}). Even-gid chunks and odd-gid chunks each move as
    a unit, so two ppermutes realize the re-shard."""
    def z(g):
        return g if g < n else 2 * n - 1 - g

    fwd_even = [(i, z(2 * i)) for i in range(n)]
    fwd_odd = [(i, z(2 * i + 1)) for i in range(n)]
    inv_even = [(d, s) for s, d in fwd_even]
    inv_odd = [(d, s) for s, d in fwd_odd]
    return fwd_even, fwd_odd, inv_even, inv_odd


def _ring_attention_zigzag(q, k, v, scale, axis_name, kv_bias,
                           use_flash, seg=None):
    """Causal ring on the ZIGZAG (striped) chunk assignment:
    device d owns global chunks {d, 2n-1-d} (each Sl/2 rows), so the
    causal visible-work per (device, step) is a CONSTANT two of the four
    chunk pairs (three on the self step) — the naive contiguous causal
    ring leaves late devices computing every step while early devices
    discard theirs, capping wall-clock at the dense cost; zigzag halves
    it. Invisible pairs skip entirely through lax.cond; the two diagonal
    pairs (self step only — a statically known step) apply the causal
    mask (in-VMEM on the flash path, a materialized triangular block on
    the plain path — which materializes score blocks anyway). Partials
    merge by logsumexp per q chunk, and two ppermute pairs re-shard
    contiguous->zigzag->contiguous at the boundaries (no device ever
    holds the full sequence). The schedule is shared by the flash and
    plain per-pair kernels: both yield normalized (out, lse) partials.
    """
    from ..ops.attention import flash_attention_with_lse

    n = int(lax.psum(1, axis_name))
    idx = lax.axis_index(axis_name)
    B, H, Sl, D = q.shape
    fwd_even, fwd_odd, inv_even, inv_odd = _zigzag_permutes(n)
    d_even = (idx % 2) == 0

    def to_zigzag(x, chunk_axis):
        """[.., Sl, ..] contiguous -> (c0 [gid=idx], c1 [gid=2n-1-idx])."""
        lo, hi = jnp.split(x, 2, axis=chunk_axis)
        recv_e = lax.ppermute(lo, axis_name, fwd_even)
        recv_o = lax.ppermute(hi, axis_name, fwd_odd)
        c0 = jnp.where(d_even, recv_e, recv_o)
        c1 = jnp.where(d_even, recv_o, recv_e)
        return c0, c1

    def from_zigzag(c0, c1, chunk_axis):
        send_e = jnp.where(d_even, c0, c1)
        send_o = jnp.where(d_even, c1, c0)
        lo = lax.ppermute(send_e, axis_name, inv_even)
        hi = lax.ppermute(send_o, axis_name, inv_odd)
        return jnp.concatenate([lo, hi], axis=chunk_axis)

    q0, q1 = to_zigzag(q, 2)
    k0, k1 = to_zigzag(k, 2)
    v0, v1 = to_zigzag(v, 2)
    b0 = b1 = None
    if kv_bias is not None:
        b0, b1 = to_zigzag(kv_bias.astype(jnp.float32), 3)
    qs0 = qs1 = s0 = s1 = None
    if seg is not None:
        # segment ids chunk-split exactly like the sequence: one static
        # copy per q chunk, one travelling copy per kv chunk
        qs0, qs1 = to_zigzag(seg, 1)
        s0, s1 = qs0, qs1

    perm = [(j, (j + 1) % n) for j in range(n)]
    qg0, qg1 = idx, 2 * n - 1 - idx

    def pair(qc, kc, vc, bc, causal_pair, qsc=None, ksc=None):
        if ksc is not None:
            sm = _seg_mask(qsc, ksc)
            bc = sm if bc is None else bc + sm
        if use_flash:
            o, lse = flash_attention_with_lse(qc, kc, vc, bc, scale,
                                              causal=causal_pair)
            return o.astype(jnp.float32), lse
        # plain pair: materialized score block -> normalized partial.
        # lse = m + log(l) merges identically to the kernel's.
        from ..ops.attention import causal_bias_block

        mask = None
        if causal_pair:
            mask = causal_bias_block(qc.shape[2])
        if bc is not None:
            bm = bc.astype(jnp.float32)
            mask = bm if mask is None else mask + bm
        o_hat, m, l = _block_partials(qc.astype(jnp.float32), kc, vc,
                                      scale, mask)
        return o_hat / l[..., None], m + jnp.log(l)

    def neutral(qc):
        # mark the constants sp-varying so lax.cond branch types match
        # the kernel outputs under strict varying-manner checking
        o = jnp.zeros(qc.shape, jnp.float32)
        l = jnp.full(qc.shape[:3], -jnp.inf, jnp.float32)
        return (lax.pcast(o, axis_name, to="varying"),
                lax.pcast(l, axis_name, to="varying"))

    def merge(acc, part):
        o_a, l_a = acc
        o_i, l_i = part
        new = jnp.logaddexp(l_a, l_i)
        w_a = jnp.where(jnp.isneginf(new), 0.0, jnp.exp(l_a - new))
        w_i = jnp.where(jnp.isneginf(new), 0.0, jnp.exp(l_i - new))
        return o_a * w_a[..., None] + o_i * w_i[..., None], new

    def visible_pair(acc, pred, qc, kc, vc, bc, qsc=None, ksc=None):
        # bc closes over the branches — lax.cond supports captured
        # tracers including ones that carry cotangents (the flash
        # kernel stop_gradients its bias; the plain pair's bias grad
        # DOES flow through this capture, pinned by
        # test_zigzag_plain_causal_with_bias_and_grads)
        part = lax.cond(
            pred,
            lambda qq, kk, vv: pair(qq, kk, vv, bc, False, qsc, ksc),
            lambda qq, kk, vv: neutral(qq),
            qc, kc, vc)
        return merge(acc, part)

    acc0 = neutral(q0)
    acc1 = neutral(q1)
    kc0, kc1, vc0, vc1, bc0, bc1 = k0, k1, v0, v1, b0, b1
    sc0, sc1 = s0, s1
    for j in range(n):
        if j == 0:
            # self step (static): both diagonals causal; (q1, k0) is the
            # always-visible full pair; (q0, k1) is never visible
            acc0 = merge(acc0, pair(q0, kc0, vc0, bc0, True, qs0, sc0))
            acc1 = merge(acc1, pair(q1, kc1, vc1, bc1, True, qs1, sc1))
            acc1 = merge(acc1, pair(q1, kc0, vc0, bc0, False, qs1, sc0))
        else:
            p = (idx - j) % n
            kg0, kg1 = p, 2 * n - 1 - p
            acc0 = visible_pair(acc0, qg0 > kg0, q0, kc0, vc0, bc0, qs0, sc0)
            acc0 = visible_pair(acc0, qg0 > kg1, q0, kc1, vc1, bc1, qs0, sc1)
            acc1 = visible_pair(acc1, qg1 > kg0, q1, kc0, vc0, bc0, qs1, sc0)
            acc1 = visible_pair(acc1, qg1 > kg1, q1, kc1, vc1, bc1, qs1, sc1)
        kc0, vc0, bc0, sc0 = _rotate(axis_name, perm, kc0, vc0, bc0, sc0)
        kc1, vc1, bc1, sc1 = _rotate(axis_name, perm, kc1, vc1, bc1, sc1)

    out = from_zigzag(acc0[0], acc1[0], 2)
    return out.astype(q.dtype)
