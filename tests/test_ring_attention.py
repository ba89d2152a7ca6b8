"""Ring attention (sequence parallelism) vs single-device attention.

The sequence axis is sharded over all 8 virtual devices; the ring result
must match the unsharded flash/composed attention exactly (same f32
accumulation), including causal masking and a travelling padding bias.
"""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
import pytest

from jax import shard_map

from paddle_tpu.ops.attention import _attention_reference
from paddle_tpu.parallel.ring_attention import ring_attention


def _run_ring(q, k, v, scale, causal=False, kv_bias=None):
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    in_specs = [P(None, None, "sp", None)] * 3
    if kv_bias is not None:
        in_specs.append(P(None, None, None, "sp"))

        def f(q, k, v, b):
            return ring_attention(q, k, v, scale, "sp", causal=causal,
                                  kv_bias=b)
    else:

        def f(q, k, v):
            return ring_attention(q, k, v, scale, "sp", causal=causal)

    fn = shard_map(f, mesh=mesh, in_specs=tuple(in_specs),
                   out_specs=P(None, None, "sp", None))
    args = (q, k, v) if kv_bias is None else (q, k, v, kv_bias)
    return jax.jit(fn)(*args)


def test_ring_matches_full_attention():
    rs = np.random.RandomState(0)
    B, H, S, D = 2, 2, 32, 8
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
               for _ in range(3))
    scale = D ** -0.5
    out = _run_ring(q, k, v, scale)
    ref = _attention_reference(q, k, v, None, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_ring_causal():
    rs = np.random.RandomState(1)
    B, H, S, D = 1, 2, 16, 8
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
               for _ in range(3))
    scale = D ** -0.5
    from paddle_tpu.ops.attention import causal_bias_block
    causal_bias = causal_bias_block(S)
    out = _run_ring(q, k, v, scale, causal=True)
    ref = _attention_reference(q, k, v, causal_bias, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_ring_with_padding_bias():
    rs = np.random.RandomState(2)
    B, H, S, D = 2, 2, 32, 8
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
               for _ in range(3))
    scale = D ** -0.5
    bias = jnp.asarray(
        np.where(rs.rand(B, 1, 1, S) > 0.25, 0, -1e9).astype("float32"))
    out = _run_ring(q, k, v, scale, kv_bias=bias)
    ref = _attention_reference(q, k, v, bias, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def _run_ring_flash(q, k, v, scale, causal=False):
    mesh = Mesh(np.array(jax.devices()), ("sp",))

    def f(q, k, v):
        return ring_attention(q, k, v, scale, "sp", causal=causal,
                              use_flash=True)

    # check_vma=False: the pallas interpreter can't yet thread varying
    # manual axes through its internal dynamic_slices (jax suggests this
    # workaround in its own error message)
    fn = shard_map(f, mesh=mesh, in_specs=(P(None, None, "sp", None),) * 3,
                   out_specs=P(None, None, "sp", None), check_vma=False)
    return jax.jit(fn)(q, k, v)


def test_ring_flash_matches_full_attention():
    """use_flash=True: per-step Pallas kernel + logaddexp merge."""
    rs = np.random.RandomState(3)
    B, H, S, D = 2, 2, 64, 8
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
               for _ in range(3))
    scale = D ** -0.5
    out = _run_ring_flash(q, k, v, scale)
    ref = _attention_reference(q, k, v, None, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_ring_flash_causal_grads_match_dense():
    """Gradients compose through the per-step custom VJPs + merge."""
    rs = np.random.RandomState(4)
    B, H, S, D = 1, 2, 32, 8
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
               for _ in range(3))
    scale = D ** -0.5
    from paddle_tpu.ops.attention import causal_bias_block
    causal_bias = causal_bias_block(S)
    mesh = Mesh(np.array(jax.devices()), ("sp",))

    fn = shard_map(
        lambda a, b, c: ring_attention(a, b, c, scale, "sp", causal=True,
                                       use_flash=True),
        mesh=mesh, in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None), check_vma=False)
    ga = jax.jit(jax.grad(lambda a, b, c: jnp.sum(fn(a, b, c) ** 2),
                          (0, 1, 2)))(q, k, v)
    gr = jax.grad(lambda a, b, c: jnp.sum(
        _attention_reference(a, b, c, causal_bias, scale) ** 2),
        (0, 1, 2))(q, k, v)
    for x, r in zip(ga, gr):
        np.testing.assert_allclose(np.asarray(x), np.asarray(r),
                                   atol=2e-4, rtol=2e-4)


def test_ring_flash_with_padding_bias():
    rs = np.random.RandomState(5)
    B, H, S, D = 2, 2, 64, 8
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
               for _ in range(3))
    scale = D ** -0.5
    # mask out the last quarter of keys per batch row
    keep = np.zeros((B, 1, 1, S), "float32")
    keep[:, :, :, 3 * S // 4:] = -1e9
    kv_bias = jnp.asarray(keep)
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    fn = shard_map(
        lambda a, b, c, bb: ring_attention(a, b, c, scale, "sp",
                                           kv_bias=bb, use_flash=True),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3 + (P(None, None, None, "sp"),),
        out_specs=P(None, None, "sp", None), check_vma=False)
    out = jax.jit(fn)(q, k, v, kv_bias)
    ref = _attention_reference(q, k, v, kv_bias, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_zigzag_causal_matches_dense_with_padding_bias():
    """The zigzag (striped) causal schedule — balanced visible work per
    (device, step) — must match the dense causal reference with a pad
    bias riding the re-shard + ring, forward and gradients."""
    rs = np.random.RandomState(7)
    B, H, S, D = 2, 2, 64, 8
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
               for _ in range(3))
    scale = D ** -0.5
    keep = np.zeros((B, 1, 1, S), "float32")
    keep[:, :, :, 7 * S // 8:] = -1e9
    kv_bias = jnp.asarray(keep)
    from paddle_tpu.ops.attention import causal_bias_block
    causal_bias = causal_bias_block(S)
    mesh = Mesh(np.array(jax.devices()), ("sp",))

    fn = shard_map(
        lambda a, b, c, bb: ring_attention(a, b, c, scale, "sp",
                                           causal=True, kv_bias=bb,
                                           use_flash=True,
                                           schedule="zigzag"),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3
        + (P(None, None, None, "sp"),),
        out_specs=P(None, None, "sp", None), check_vma=False)
    out = jax.jit(fn)(q, k, v, kv_bias)
    ref = _attention_reference(q, k, v, causal_bias + kv_bias, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)

    ga = jax.jit(jax.grad(lambda a, b, c: jnp.sum(fn(a, b, c, kv_bias) ** 2),
                          (0, 1, 2)))(q, k, v)
    gr = jax.grad(lambda a, b, c: jnp.sum(
        _attention_reference(a, b, c, causal_bias + kv_bias, scale) ** 2),
        (0, 1, 2))(q, k, v)
    for x, r in zip(ga, gr):
        np.testing.assert_allclose(np.asarray(x), np.asarray(r),
                                   atol=3e-4, rtol=3e-4)


def test_zigzag_rejected_without_causal():
    import pytest

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 1, 16, 8).astype("float32"))
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    fn = shard_map(
        lambda a, b, c: ring_attention(a, b, c, 1.0, "sp", causal=False,
                                       use_flash=True, schedule="zigzag"),
        mesh=mesh, in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None), check_vma=False)
    with pytest.raises(Exception, match="zigzag"):
        jax.jit(fn)(q, q, q)


def test_contiguous_causal_schedule_still_covered():
    """The contiguous causal gating (idx >= i visibility) remains the
    production fallback for odd shard lengths / explicit requests — pin
    it explicitly now that "auto" reroutes causal rings to zigzag."""
    rs = np.random.RandomState(4)
    B, H, S, D = 1, 2, 32, 8
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
               for _ in range(3))
    scale = D ** -0.5
    from paddle_tpu.ops.attention import causal_bias_block
    causal_bias = causal_bias_block(S)
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    fn = shard_map(
        lambda a, b, c: ring_attention(a, b, c, scale, "sp", causal=True,
                                       use_flash=True,
                                       schedule="contiguous"),
        mesh=mesh, in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None), check_vma=False)
    out = jax.jit(fn)(q, k, v)
    ref = _attention_reference(q, k, v, causal_bias, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_zigzag_plain_causal_with_bias_and_grads():
    """The zigzag schedule on the PLAIN (non-flash) path: materialized
    per-pair score blocks, same balanced causal schedule — forward and
    gradients must match the dense reference (pad bias riding along)."""
    rs = np.random.RandomState(11)
    B, H, S, D = 2, 2, 64, 8
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
               for _ in range(3))
    scale = D ** -0.5
    keep = np.zeros((B, 1, 1, S), "float32")
    keep[:, :, :, 7 * S // 8:] = -1e9
    kv_bias = jnp.asarray(keep)
    from paddle_tpu.ops.attention import causal_bias_block
    causal_bias = causal_bias_block(S)
    mesh = Mesh(np.array(jax.devices()), ("sp",))

    fn = shard_map(
        lambda a, b, c, bb: ring_attention(a, b, c, scale, "sp",
                                           causal=True, kv_bias=bb,
                                           schedule="zigzag"),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3
        + (P(None, None, None, "sp"),),
        out_specs=P(None, None, "sp", None), check_vma=False)
    out = jax.jit(fn)(q, k, v, kv_bias)
    ref = _attention_reference(q, k, v, causal_bias + kv_bias, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)

    # grads including the BIAS cotangent: on the plain path the bias is
    # not stop_gradient'd, and its cotangent flows through the lax.cond
    # captures (see visible_pair) — trainable-bias sp training
    ga = jax.jit(jax.grad(
        lambda a, b, c, bb: jnp.sum(fn(a, b, c, bb) ** 2),
        (0, 1, 2, 3)))(q, k, v, kv_bias)
    gr = jax.grad(lambda a, b, c, bb: jnp.sum(
        _attention_reference(a, b, c, causal_bias + bb, scale) ** 2),
        (0, 1, 2, 3))(q, k, v, kv_bias)
    for x, r in zip(ga, gr):
        np.testing.assert_allclose(np.asarray(x), np.asarray(r),
                                   atol=3e-4, rtol=3e-4)


def test_plain_auto_causal_routes_zigzag_and_odd_shard_falls_back():
    """auto + causal on the plain path takes the zigzag schedule when
    the local shard is even (parity pinned above); an ODD local shard
    must quietly fall back to the contiguous schedule and stay exact."""
    rs = np.random.RandomState(12)
    B, H, D = 1, 2, 8
    S = 8 * 3  # Sl = 3: odd -> contiguous fallback
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
               for _ in range(3))
    scale = D ** -0.5
    from paddle_tpu.ops.attention import causal_bias_block
    causal_bias = causal_bias_block(S)
    out = _run_ring(q, k, v, scale, causal=True)
    ref = _attention_reference(q, k, v, causal_bias, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_sp_path_emits_no_paddle_deprecation_warnings():
    """Jax API drift guard (round-4 finding: lax.pvary deprecated in
    jax 0.8+). The zigzag causal path must not trip ANY
    DeprecationWarning attributed to paddle_tpu code — the next jax
    bump turns those warnings into hard removals."""
    import warnings

    rs = np.random.RandomState(21)
    B, H, S, D = 1, 2, 16, 8
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
               for _ in range(3))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        _run_ring(q, k, v, D ** -0.5, causal=True)
    ours = [w for w in caught
            if issubclass(w.category, DeprecationWarning)
            and "paddle_tpu" in str(w.filename)]
    assert not ours, ["%s:%d %s" % (w.filename, w.lineno, w.message)
                      for w in ours]


def _seg_feed(seed=5):
    rs = np.random.RandomState(seed)
    B, H, S, D = 2, 2, 32, 8
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
               for _ in range(3))
    seg_np = np.zeros((B, S), dtype="int64")
    seg_np[0, :10] = 1
    seg_np[0, 10:25] = 2
    seg_np[1, :16] = 1
    seg_np[1, 16:30] = 2
    keep = ((seg_np[:, :, None] == seg_np[:, None, :])
            & (seg_np[:, None, :] > 0))
    seg_bias = jnp.asarray(
        np.where(keep, 0.0, -1e9).astype("float32"))[:, None]
    return q, k, v, jnp.asarray(seg_np), seg_np, seg_bias


def _run_ring_seg(q, k, v, seg, scale, causal, use_flash,
                  schedule="auto"):
    mesh = Mesh(np.array(jax.devices()), ("sp",))

    def f(qq, kk, vv, ss):
        return ring_attention(qq, kk, vv, scale, "sp", causal=causal,
                              seg=ss, use_flash=use_flash,
                              schedule=schedule)

    fn = shard_map(
        f, mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3 + (P(None, "sp"),),
        out_specs=P(None, None, "sp", None), check_vma=False)
    return jax.jit(fn)(q, k, v, seg)


def test_ring_segment_ids_match_dense_pack_bias():
    """Packed rows over the ring: travelling segment-id vectors must
    reproduce the dense materialized pack-bias attention exactly (real
    tokens compared; padding rows are loss-masked garbage both ways),
    on the plain AND flash per-pair kernels, causal (zigzag) and not."""
    q, k, v, seg, seg_np, seg_bias = _seg_feed()
    D = q.shape[-1]
    scale = D ** -0.5
    from paddle_tpu.ops.attention import causal_bias_block

    real = (seg_np > 0)[:, None, :, None]
    for causal in (False, True):
        bias = seg_bias if not causal else seg_bias + causal_bias_block(
            q.shape[2])
        ref = np.asarray(_attention_reference(q, k, v, bias, scale))
        for use_flash in (False, True):
            out = np.asarray(_run_ring_seg(q, k, v, seg, scale, causal,
                                           use_flash))
            err = np.abs((out - ref) * real).max()
            assert err < 3e-5, (causal, use_flash, err)


def test_ring_segment_ids_grads_match_dense():
    """q/k/v cotangents through the seg-masked ring (zigzag causal,
    plain pair kernel) == dense autodiff over the materialized mask."""
    q, k, v, seg, seg_np, seg_bias = _seg_feed(seed=6)
    D = q.shape[-1]
    scale = D ** -0.5
    from paddle_tpu.ops.attention import causal_bias_block

    bias = seg_bias + causal_bias_block(q.shape[2])
    real = jnp.asarray((seg_np > 0)[:, None, :, None].astype("float32"))

    def ring_loss(a, b, c):
        o = _run_ring_seg(a, b, c, seg, scale, True, False)
        return jnp.sum((o * real) ** 2)

    def dense_loss(a, b, c):
        o = _attention_reference(a, b, c, bias, scale)
        return jnp.sum((o * real) ** 2)

    ga = jax.grad(ring_loss, (0, 1, 2))(q, k, v)
    gr = jax.grad(dense_loss, (0, 1, 2))(q, k, v)
    for x, r in zip(ga, gr):
        np.testing.assert_allclose(np.asarray(x), np.asarray(r),
                                   atol=3e-4, rtol=3e-4)
