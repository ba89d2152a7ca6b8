"""Fused (Pallas) attention vs the layer-composed path.

The reference has no fused attention op (SURVEY §5); the numeric contract
here is: fused_attention == matmul/softmax/matmul composition, forward and
backward, and the transformer model trains identically either way (modulo
dropout placement, which the fused path applies to the output).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import transformer
from paddle_tpu.ops.attention import _attention_reference, flash_attention


def test_flash_attention_matches_reference():
    rs = np.random.RandomState(0)
    B, H, S, D = 2, 4, 32, 16
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
               for _ in range(3))
    bias = jnp.asarray(
        np.where(rs.rand(B, 1, 1, S) > 0.2, 0, -1e9).astype("float32"))
    for b in (None, bias):
        out = flash_attention(q, k, v, b, D ** -0.5)
        ref = _attention_reference(q, k, v, b, D ** -0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


def test_flash_attention_grads():
    rs = np.random.RandomState(1)
    B, H, S, D = 1, 2, 16, 8
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
               for _ in range(3))

    def f(q, k, v):
        return flash_attention(q, k, v, None, D ** -0.5).sum()

    def g(q, k, v):
        return _attention_reference(q, k, v, None, D ** -0.5).sum()

    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


def test_flash_attention_long_context_blocked():
    # S=2048 >> the 128-row block: exercises the online-softmax accumulation
    # across 16 KV blocks (VMEM-bounded; the [S,S] scores never materialize)
    rs = np.random.RandomState(2)
    B, H, S, D = 1, 1, 2048, 32
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
               for _ in range(3))
    causal = jnp.asarray(
        np.triu(np.full((S, S), -1e9, dtype="float32"), 1)[None, None])
    out = flash_attention(q, k, v, causal, D ** -0.5)
    ref = _attention_reference(q, k, v, causal, D ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_flash_attention_bf16():
    rs = np.random.RandomState(3)
    B, H, S, D = 2, 2, 256, 32
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D)).astype(jnp.bfloat16)
               for _ in range(3))
    out = flash_attention(q, k, v, None, D ** -0.5)
    ref = _attention_reference(q, k, v, None, D ** -0.5)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref, dtype=np.float32),
        atol=2e-2, rtol=2e-2)


def test_flash_attention_grads_blocked_with_bias():
    # multi-block backward: the two Pallas grad kernels vs the XLA vjp
    rs = np.random.RandomState(4)
    B, H, S, D = 1, 2, 256, 16
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
               for _ in range(3))
    bias = jnp.asarray(
        np.where(rs.rand(B, 1, 1, S) > 0.2, 0, -1e9).astype("float32"))

    def f(q, k, v):
        return (flash_attention(q, k, v, bias, D ** -0.5) ** 2).sum()

    def g(q, k, v):
        return (_attention_reference(q, k, v, bias, D ** -0.5) ** 2).sum()

    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_transformer_with_fused_attention_trains():
    cfg = dict(d_model=32, d_ff=64, n_head=4, n_layer=2, src_vocab=100,
               trg_vocab=100, max_length=16, dropout=0.0)
    rs = np.random.RandomState(0)
    batch = {"src_ids": rs.randint(1, 100, (4, 16)).astype("int64"),
             "trg_ids": rs.randint(1, 100, (4, 16)).astype("int64"),
             "lbl_ids": rs.randint(1, 100, (4, 16)).astype("int64")}

    def run(fused):
        main, startup = fluid.Program(), fluid.Program()
        scope = fluid.core.scope.Scope()
        with fluid.core.scope.scope_guard(scope):
            with fluid.program_guard(main, startup):
                loss, _ = transformer.build(cfg, seq_len=16,
                                            use_fused_attention=fused)
                fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup, scope=scope)
            ls = []
            for _ in range(4):
                (l,) = exe.run(main, feed=batch, fetch_list=[loss], scope=scope)
                ls.append(float(l))
        return ls

    fused, composed = run(True), run(False)
    # dropout=0 => identical programs up to the attention implementation
    np.testing.assert_allclose(fused, composed, rtol=1e-4, atol=1e-5)
    assert fused[-1] < fused[0]


def test_flash_attention_trainable_bias_cotangent():
    """bias_grad=True (VERDICT r2 weak #5): a trainable bias (relative
    position) must receive its true cotangent, matching the composed
    reference — including broadcast reduction over the batch axis."""
    rs = np.random.RandomState(7)
    B, H, S, D = 2, 2, 128, 16
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
               for _ in range(3))
    bias = jnp.asarray(rs.randn(1, H, S, S).astype("float32") * 0.1)

    def f(bias):
        return (flash_attention(q, k, v, bias, D ** -0.5,
                                bias_grad=True) ** 2).sum()

    def g(bias):
        return (_attention_reference(q, k, v, bias, D ** -0.5) ** 2).sum()

    got = jax.grad(f)(bias)
    want = jax.grad(g)(bias)
    assert got.shape == bias.shape
    assert float(jnp.abs(got).max()) > 0  # not the zero-cotangent bug
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_pallas_mode_env_override(monkeypatch):
    from paddle_tpu.ops.attention import pallas_mode

    monkeypatch.delenv("PADDLE_TPU_FLASH_INTERPRET", raising=False)
    assert pallas_mode() == "interpret"  # CPU backend autodetect
    monkeypatch.setenv("PADDLE_TPU_FLASH_INTERPRET", "0")
    assert pallas_mode() == "compiled"
    monkeypatch.setenv("PADDLE_TPU_FLASH_INTERPRET", "1")
    assert pallas_mode() == "interpret"


def test_causal_flash_matches_dense_causal_reference():
    """In-kernel causal (block skip + intra-block triangle) must equal
    the composed path with a materialized causal bias — forward AND all
    three gradients, including ragged S (block padding) and a pad-mask
    bias riding alongside."""
    rs = np.random.RandomState(0)
    for S, with_pad_bias in ((64, False), (200, True)):
        B, H, D = 2, 3, 16
        q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
                   for _ in range(3))
        tri = np.triu(np.full((S, S), -1e9, "float32"), k=1)[None, None]
        dense_bias = jnp.asarray(tri)
        pad_bias = None
        if with_pad_bias:
            pad = np.where(rs.rand(B, 1, 1, S) > 0.1, 0, -1e9)
            pad_bias = jnp.asarray(pad.astype("float32"))
            dense_bias = dense_bias + pad_bias

        def loss_causal(q, k, v):
            out = flash_attention(q, k, v, pad_bias, D ** -0.5,
                                  causal=True)
            return jnp.sum(out ** 2), out

        def loss_dense(q, k, v):
            out = _attention_reference(q, k, v, dense_bias, D ** -0.5)
            return jnp.sum(out ** 2), out

        (lc, oc), gc = jax.value_and_grad(loss_causal, argnums=(0, 1, 2),
                                          has_aux=True)(q, k, v)
        (ld, od), gd = jax.value_and_grad(loss_dense, argnums=(0, 1, 2),
                                          has_aux=True)(q, k, v)
        np.testing.assert_allclose(np.asarray(oc), np.asarray(od),
                                   atol=2e-5, rtol=2e-5)
        for a, b in zip(gc, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-4, rtol=3e-4)


def test_causal_flash_bf16():
    rs = np.random.RandomState(1)
    B, H, S, D = 2, 2, 128, 32
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D)).astype(jnp.bfloat16)
               for _ in range(3))
    out = flash_attention(q, k, v, None, D ** -0.5, causal=True)
    tri = jnp.asarray(np.triu(np.full((S, S), -1e9, "float32"), k=1)
                      [None, None])
    ref = _attention_reference(q, k, v, tri, D ** -0.5)
    np.testing.assert_allclose(np.asarray(out).astype("float32"),
                               np.asarray(ref).astype("float32"),
                               atol=3e-2, rtol=3e-2)


def test_causal_flash_error_paths():
    import pytest

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 1, 32, 8).astype("float32"))
    kv = jnp.asarray(rs.randn(1, 1, 64, 8).astype("float32"))
    with pytest.raises(ValueError, match="Sq == Sk"):
        flash_attention(q, kv, kv, None, 1.0, causal=True)
    # causal+bias_grad IS supported (mask materialized into the bias) —
    # but still self-attention only
    bias = jnp.zeros((1, 1, 32, 64), jnp.float32)
    with pytest.raises(ValueError, match="Sq == Sk"):
        flash_attention(q, kv, kv, bias, 1.0, bias_grad=True, causal=True)


def test_flash_causal_with_trainable_bias():
    """causal=True composes with bias_grad=True: the triangular mask is
    materialized into the bias OUTSIDE the custom_vjp, so the caller's
    bias cotangent is exact (zero in masked positions) and matches the
    dense composed reference."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.attention import (_attention_reference,
                                          flash_attention)

    rs = np.random.RandomState(21)
    B, H, S, D = 1, 2, 32, 8
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
               for _ in range(3))
    bias = jnp.asarray(rs.randn(1, H, S, S).astype("float32") * 0.3)
    scale = D ** -0.5
    from paddle_tpu.ops.attention import causal_bias_block
    causal_bias = causal_bias_block(S)

    def f(a, b, c, bb):
        return jnp.sum(flash_attention(a, b, c, bb, scale, bias_grad=True,
                                       causal=True) ** 2)

    def ref(a, b, c, bb):
        return jnp.sum(_attention_reference(a, b, c, bb + causal_bias,
                                            scale) ** 2)

    out = flash_attention(q, k, v, bias, scale, bias_grad=True,
                          causal=True)
    expect = _attention_reference(q, k, v, bias + causal_bias, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-4, rtol=1e-4)

    g = jax.grad(f, (0, 1, 2, 3))(q, k, v, bias)
    gr = jax.grad(ref, (0, 1, 2, 3))(q, k, v, bias)
    for x, r in zip(g, gr):
        np.testing.assert_allclose(np.asarray(x), np.asarray(r),
                                   atol=3e-4, rtol=3e-4)
    # masked (strictly-upper) positions carry zero bias cotangent
    db = np.asarray(g[3])
    iu = np.triu_indices(S, 1)
    assert np.abs(db[:, :, iu[0], iu[1]]).max() < 1e-6


def test_flash_causal_bias_grad_none_bias_is_plain_causal():
    """bias_grad=True with bias=None degrades to the plain causal path
    (nothing trainable) instead of erroring or wasting a ds buffer."""
    import jax.numpy as jnp

    from paddle_tpu.ops.attention import (_attention_reference,
                                          flash_attention)

    rs = np.random.RandomState(22)
    B, H, S, D = 1, 1, 32, 8
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
               for _ in range(3))
    scale = D ** -0.5
    from paddle_tpu.ops.attention import causal_bias_block
    causal_bias = causal_bias_block(S)
    out = flash_attention(q, k, v, None, scale, bias_grad=True,
                          causal=True)
    expect = _attention_reference(q, k, v, causal_bias, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------- block plan (ISSUE 25)
def _reference_with_lse(q, k, v, bias, scale, causal):
    """composed_attention's scores again, for the row log-sum-exps the
    kernels save (the reference returns only the output)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    if causal:
        n = s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -1e9)
    from paddle_tpu.ops.attention import composed_attention

    return (composed_attention(q, k, v, bias, scale, causal),
            jax.nn.logsumexp(s, axis=-1))


PLAN_PARITY_CASES = {
    # name: (B, H, S, D), dtype, bias kind, causal, atol forward, atol grads
    # (the tolerances the tests above hold the same paths to)
    "s512_maskbias_bf16": ((1, 2, 512, 32), "bfloat16", "mask", False,
                           2e-2, 3e-2),
    "s512_maskbias_f32": ((1, 2, 512, 32), "float32", "mask", False,
                          1e-4, 1e-4),
    "ragged_s500": ((1, 2, 500, 16), "float32", "mask", False, 1e-4, 1e-4),
    "s640": ((1, 2, 640, 16), "float32", "mask", False, 1e-4, 1e-4),
    "causal_s1024": ((1, 1, 1024, 16), "float32", None, True, 2e-5, 3e-4),
    "trainable_bias": ((2, 2, 256, 16), "float32", "trainable", False,
                       1e-4, 2e-4),
    "full_bias": ((2, 2, 256, 16), "float32", "full", False, 1e-4, 1e-4),
}


def _plan_parity_inputs(case):
    shape, dtype, bias_kind, causal, atol_f, atol_g = PLAN_PARITY_CASES[case]
    B, H, S, D = shape
    rs = np.random.RandomState(sum(map(ord, case)))
    q, k, v = (jnp.asarray(rs.randn(*shape).astype("float32")).astype(dtype)
               for _ in range(3))
    bias = None
    if bias_kind == "mask":
        bias = jnp.asarray(
            np.where(rs.rand(B, 1, 1, S) > 0.2, 0, -1e9).astype("float32"))
    elif bias_kind == "trainable":
        bias = jnp.asarray(rs.randn(1, H, S, S).astype("float32") * 0.1)
    elif bias_kind == "full":
        bias = jnp.asarray(rs.randn(B, H, S, S).astype("float32") * 0.1)
    w_lse = jnp.asarray(rs.randn(B, H, S).astype("float32") * 0.1)
    return q, k, v, bias, w_lse


@pytest.mark.parametrize("blocks", ["planned", "forced_128x128"])
@pytest.mark.parametrize("case", sorted(PLAN_PARITY_CASES))
def test_flash_block_plan_parity(case, blocks, monkeypatch):
    """Forward, dq/dk/dv, the bias cotangent where the bias is trainable
    and the lse cotangent where the path has an lse output, against
    ``composed_attention``: under the plan the shapes give, and with a
    plan of 128x128 in its place, so that the multi-block carry stays
    covered where the plan takes one block."""
    from paddle_tpu.observe.families import FLASH_BLOCK_PLANS
    from paddle_tpu.ops import attention

    monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_SEQ", "0")
    planned = attention._block_plan
    if blocks == "forced_128x128":
        monkeypatch.setattr(attention, "_block_plan",
                            lambda *a, **k: (128, 128))
    shape, dtype, bias_kind, causal, atol_f, atol_g = PLAN_PARITY_CASES[case]
    D = shape[-1]
    scale = D ** -0.5
    q, k, v, bias, w_lse = _plan_parity_inputs(case)
    trainable = bias_kind == "trainable"
    f32 = jnp.float32

    def flash_loss(q, k, v, bias):
        if trainable:  # no lse output on the trainable-bias path
            out = attention.flash_attention(q, k, v, bias, scale,
                                            bias_grad=True)
            return jnp.sum(out.astype(f32) ** 2), out
        out, lse = attention.flash_attention_with_lse(q, k, v, bias, scale,
                                                      causal)
        return jnp.sum(out.astype(f32) ** 2) + jnp.sum(lse * w_lse), \
            (out, lse)

    def ref_loss(q, k, v, bias):
        out, lse = _reference_with_lse(q, k, v, bias, scale, causal)
        if trainable:
            return jnp.sum(out.astype(f32) ** 2), out
        return jnp.sum(out.astype(f32) ** 2) + jnp.sum(lse * w_lse), \
            (out, lse)

    argnums = (0, 1, 2, 3) if trainable else (0, 1, 2)
    # (under autodiff the custom_vjp's forward rule runs: the rerun's name)
    single = FLASH_BLOCK_PLANS.labels(
        kernel=attention.KERNEL_REFWD,
        block="%dx%d" % planned(
            attention.KERNEL_FWD, shape[2], shape[2], D, q.dtype, causal),
        single_pass="1", layout="heads")
    before = single.value
    (_, got), g_got = jax.value_and_grad(flash_loss, argnums,
                                         has_aux=True)(q, k, v, bias)
    (_, want), g_want = jax.value_and_grad(ref_loss, argnums,
                                           has_aux=True)(q, k, v, bias)
    as_np = lambda t: np.asarray(t, dtype=np.float32)  # noqa: E731
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(as_np(a), as_np(b), atol=atol_f,
                                   rtol=atol_f)
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(as_np(a), as_np(b), atol=atol_g,
                                   rtol=atol_g)
    # the counter says which plan was lowered: the whole-axis single pass
    # under the plan, never under the forced 128x128 blocks at these
    # lengths
    assert (single.value > before) == (blocks == "planned")


_PLAN_LENGTHS = [32, 100, 128, 200, 256, 384, 500, 512, 640, 768, 896, 1000,
                 1024, 1152, 2048, 4096]


@pytest.mark.parametrize("S", _PLAN_LENGTHS)
def test_flash_block_plan_is_legal_and_pads_under_a_lane_tile(S):
    """The plan function alone: every block divides the padded length,
    the padding stays under one lane tile (S 640 never becomes 1024),
    Mosaic's (8, 128) rule holds for every block, and the causal and
    want_db inputs change the plan only as its docstring says."""
    from paddle_tpu.kernels.common import mosaic_ok, pad_len
    from paddle_tpu.ops import attention as A

    kernels = (A.KERNEL_FWD, A.KERNEL_REFWD, A.KERNEL_BWD_DKV,
               A.KERNEL_BWD_DQ)
    Sp = pad_len(S, 128)
    assert 0 <= Sp - S < 128
    for kernel in kernels:
        for causal in (False, True):
            for Sk in ((S,) if causal else (S, 384)):
                Skp = pad_len(Sk, 128)
                bq, bk = A._block_plan(kernel, S, Sk, 64, jnp.bfloat16,
                                       causal)
                assert Sp % bq == 0 and Skp % bk == 0, (kernel, bq, bk)
                assert bq * bk <= A._MAX_BLOCK ** 2
                # q/k/v blocks, the [bq, 1] statistics, a [bq, bk] bias
                assert mosaic_ok((1, bq, 64), (4, Sp, 64))
                assert mosaic_ok((1, bk, 64), (4, Skp, 64))
                assert mosaic_ok((1, bq, 1), (4, Sp, 1))
                assert mosaic_ok((1, 1, bq, bk), (1, 1, Sp, Skp))
                # the axis the kernel reduces over is whole up to 1024
                red, b_red = (Sp, bq) if kernel == A.KERNEL_BWD_DKV \
                    else (Skp, bk)
                assert b_red == red if red <= 1024 else b_red <= 512
                # causal and a trainable bias (want_db) plan like a plain
                # call (measured: the docstring says why)
                assert (bq, bk) == A._block_plan(
                    kernel, S, Sk, 64, jnp.bfloat16, False, want_db=True)
                assert (bq, bk) == A._block_plan(
                    kernel, S, Sk, 64, jnp.bfloat16, not causal)
                # pure in D and dtype: the score tile is float32 [bq, bk]
                assert (bq, bk) == A._block_plan(kernel, S, Sk, 128,
                                                 jnp.float32, causal)
    # the forward's rerun plans like the forward
    assert A._block_plan(A.KERNEL_REFWD, S, S, 64, jnp.bfloat16) \
        == A._block_plan(A.KERNEL_FWD, S, S, 64, jnp.bfloat16)
    if S <= 512:  # one block over the whole (padded) sequence
        assert A._block_plan(A.KERNEL_FWD, S, S, 64, jnp.bfloat16) \
            == (Sp, Sp)
    want = {640: (128, 640), 1024: (256, 1024), 2048: (512, 512)}.get(S)
    if want:
        assert A._block_plan(A.KERNEL_FWD, S, S, 64, jnp.bfloat16) == want
        assert A._block_plan(A.KERNEL_BWD_DQ, S, S, 64, jnp.bfloat16) == want
        assert A._block_plan(A.KERNEL_BWD_DKV, S, S, 64, jnp.bfloat16) \
            == want[::-1]


# ------------------------------------------ operand layouts (ISSUE 38)
def _split(x, H):
    B, S, HD = x.shape
    return x.reshape(B, S, H, HD // H).transpose(0, 2, 1, 3)


def _merge(x):
    B, H, S, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, H * D)


def _lane_plans():
    """{(kernel, layout): count} of the flash plans lowered so far."""
    from paddle_tpu.observe import REGISTRY

    seen = {}
    for s in REGISTRY.snapshot()["metrics"][
            "paddle_flash_block_plans_total"]["samples"]:
        key = (s["labels"]["kernel"], s["labels"]["layout"])
        seen[key] = seen.get(key, 0) + s["value"]
    return seen


LAYOUT_CASES = [
    # (H, D, S, causal, key-mask bias, dropout): twelve and sixteen heads
    # of 64 (two a lane tile, four a grid step), eight of 128 (one a
    # tile); S 384 is three lane tiles, 200 pads to 256
    (12, 64, 256, False, True, 0.1),
    (12, 64, 384, True, False, 0.0),
    (12, 64, 512, False, True, 0.0),
    (12, 64, 512, True, True, 0.1),
    (16, 64, 256, True, True, 0.0),
    (16, 64, 384, False, True, 0.1),
    (16, 64, 512, False, False, 0.1),
    (8, 128, 256, False, False, 0.1),
    (8, 128, 384, True, True, 0.1),
    (8, 128, 512, False, True, 0.0),
    (6, 64, 200, False, True, 0.1),    # two heads a step: 4 does not divide 6
    (8, 32, 256, True, True, 0.0),     # four heads a lane tile
]


@pytest.mark.parametrize("H,D,S,causal,masked,dropout", LAYOUT_CASES)
def test_rank3_operands_equal_rank4_on_transposed_operands(
        H, D, S, causal, masked, dropout, monkeypatch):
    """The op over [B, S, H*D] operands (the kernels index the heads along
    the lanes) against the op over the same values split to [B, H, S, D]:
    ``Out`` is the plain result under the saved ``Mask``, and the grad op
    replays that mask into the same three gradients."""
    from paddle_tpu.core.lowering import LowerContext
    from paddle_tpu.core.registry import get_op

    monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_SEQ", "0")
    op = get_op("fused_attention")
    rs = np.random.RandomState(H * 1000 + S + D)
    B = 2 if S <= 256 else 1
    q, k, v, g = (jnp.asarray(rs.randn(B, S, H * D).astype("float32"))
                  for _ in range(4))
    ins = {}
    if masked:
        ins["Bias"] = [jnp.asarray(np.where(rs.rand(B, 1, 1, S) > 0.2, 0,
                                            -1e9).astype("float32"))]
    attrs = {"scale": D ** -0.5, "causal": causal}
    before = _lane_plans()
    got = op.lowering(LowerContext(rng=jax.random.PRNGKey(S + H)),
                      dict(ins, Q=[q], K=[k], V=[v]),
                      dict(attrs, dropout=dropout, n_head=H))
    out3, mask3 = got["Out"][0], got["Mask"][0]
    lowered = {key: n - before.get(key, 0)
               for key, n in _lane_plans().items() if n > before.get(key, 0)}
    assert lowered == {("flash_fwd", "lanes"): 1}
    assert out3.shape == mask3.shape == (B, S, H * D)
    if dropout:
        np.testing.assert_allclose(np.unique(np.asarray(mask3)),
                                   [0.0, 1.0 / (1.0 - dropout)], rtol=1e-6)
    else:
        np.testing.assert_array_equal(np.asarray(mask3), 1.0)
    plain = op.lowering(LowerContext(rng=jax.random.PRNGKey(0)),
                        dict(ins, Q=[_split(q, H)], K=[_split(k, H)],
                             V=[_split(v, H)]),
                        dict(attrs, dropout=0.0))["Out"][0]
    np.testing.assert_allclose(np.asarray(out3),
                               np.asarray(_merge(plain) * mask3),
                               atol=1e-4, rtol=1e-4)
    before = _lane_plans()
    g3 = op.grad_lowering(
        LowerContext(), dict(ins, Q=[q], K=[k], V=[v], Mask=[mask3],
                             **{"Out@GRAD": [g]}),
        dict(attrs, dropout=dropout, n_head=H))
    lowered = {key for key, n in _lane_plans().items()
               if n > before.get(key, 0)}
    assert lowered == {("flash_refwd", "lanes"), ("flash_bwd_dkv", "lanes"),
                       ("flash_bwd_dq", "lanes")}
    g4 = op.grad_lowering(
        LowerContext(), dict(ins, Q=[_split(q, H)], K=[_split(k, H)],
                             V=[_split(v, H)], Mask=[_split(mask3, H)],
                             **{"Out@GRAD": [_split(g, H)]}),
        dict(attrs, dropout=dropout))
    for slot in ("Q@GRAD", "K@GRAD", "V@GRAD"):
        assert g3[slot][0].shape == (B, S, H * D)
        np.testing.assert_allclose(np.asarray(g3[slot][0]),
                                   np.asarray(_merge(g4[slot][0])),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("blocks", [(128, 128), (128, 256)])
def test_rank3_multi_pass_carry_matches_rank4(blocks, monkeypatch):
    """Blocks forced under the sequence: the lanes layout carries a
    maximum, a denominator and an accumulator a head of the step (two at
    D 64), and a causal call still skips above the diagonal."""
    monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_SEQ", "0")
    from paddle_tpu.ops import attention

    monkeypatch.setattr(attention, "_block_plan", lambda *a, **k: blocks)
    H, D, S = 4, 64, 512
    rs = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rs.randn(1, S, H * D).astype("float32"))
               for _ in range(3))
    bias = jnp.asarray(
        np.where(rs.rand(1, 1, 1, S) > 0.2, 0, -1e9).astype("float32"))

    def packed(q, k, v):
        return jnp.sum(flash_attention(q, k, v, bias, D ** -0.5, causal=True,
                                       n_head=H) ** 2)

    def split(q, k, v):
        return jnp.sum(flash_attention(_split(q, H), _split(k, H),
                                       _split(v, H), bias, D ** -0.5,
                                       causal=True) ** 2)

    before = _lane_plans()
    got = jax.value_and_grad(packed, (0, 1, 2))(q, k, v)
    assert {key for key, n in _lane_plans().items()
            if n > before.get(key, 0)} == {
        ("flash_refwd", "lanes"), ("flash_bwd_dkv", "lanes"),
        ("flash_bwd_dq", "lanes")}
    want = jax.value_and_grad(split, (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4)


@pytest.mark.parametrize("what", ["window", "grouped_heads", "value_width",
                                  "mxu_dtype", "flash_min_seq",
                                  "trainable_bias", "no_n_head"])
def test_rank3_operands_refuse_the_forward_only_features(what):
    """[B, S, H*D] operands take what has a backward rule and nothing
    else, under the message the grad op uses; and they need ``n_head``."""
    from paddle_tpu.core.lowering import LowerContext
    from paddle_tpu.core.registry import get_op

    H, D, S = 4, 64, 256
    q = k = v = jnp.zeros((1, S, H * D), jnp.float32)
    attrs = {"scale": 0.125, "causal": True, "n_head": H}
    if what == "window":
        attrs["window"] = 64
    elif what == "grouped_heads":
        k = v = jnp.zeros((1, S, 2 * D), jnp.float32)
    elif what == "value_width":
        v = jnp.zeros((1, S, H * 32), jnp.float32)
    elif what == "mxu_dtype":
        attrs["mxu_dtype"] = "bfloat16"
    elif what == "flash_min_seq":
        attrs["flash_min_seq"] = 128
    elif what == "trainable_bias":
        with pytest.raises(NotImplementedError, match="forward-only"):
            flash_attention(q, k, v, jnp.zeros((1, H, S, S)), 0.125,
                            bias_grad=True, n_head=H)
        return
    elif what == "no_n_head":
        with pytest.raises(ValueError, match="n_head"):
            flash_attention(q, k, v, None, 0.125)
        with pytest.raises(ValueError, match="n_head"):
            flash_attention(q, k, v, None, 0.125, n_head=7)
        return
    ins = {"Q": [q], "K": [k], "V": [v]}
    op = get_op("fused_attention")
    with pytest.raises(NotImplementedError, match="forward-only"):
        op.lowering(LowerContext(is_test=True), ins, attrs)
    with pytest.raises(NotImplementedError, match="forward-only"):
        op.grad_lowering(LowerContext(), dict(ins, **{"Out@GRAD": [q]}),
                         attrs)


@pytest.mark.parametrize("why", ["under_min_seq", "head_width_off_the_tile",
                                 "odd_heads_a_tile"])
def test_rank3_operands_unpack_where_the_kernel_cannot_take_them(
        why, monkeypatch):
    """Under ``flash_min_seq()`` the call runs the composed form, and a
    head width (or count) that does not fill lane tiles runs the kernel
    over [B, H, S, D]: either way inside the lowering, with the result of
    the split call."""
    from paddle_tpu.observe.families import KERNEL_DISPATCHES
    from paddle_tpu.ops.attention import composed_attention

    H, D, S = {"under_min_seq": (4, 64, 128),
               "head_width_off_the_tile": (4, 48, 256),
               "odd_heads_a_tile": (3, 64, 256)}[why]
    monkeypatch.delenv("PADDLE_TPU_FLASH_MIN_SEQ", raising=False)
    rs = np.random.RandomState(9)
    q, k, v = (jnp.asarray(rs.randn(2, S, H * D).astype("float32"))
               for _ in range(3))
    composed = KERNEL_DISPATCHES.labels(op="attention", impl="composed")
    before, plans = composed.value, _lane_plans()

    def packed(q, k, v):
        return jnp.sum(flash_attention(q, k, v, None, D ** -0.5, causal=True,
                                       n_head=H) ** 2)

    def reference(q, k, v):
        return jnp.sum(_merge(composed_attention(
            _split(q, H), _split(k, H), _split(v, H), None, D ** -0.5,
            True)) ** 2)

    got = jax.value_and_grad(packed, (0, 1, 2))(q, k, v)
    lowered = {key for key, n in _lane_plans().items()
               if n > plans.get(key, 0)}
    if why == "under_min_seq":
        assert composed.value > before and not lowered
    else:
        assert lowered == {("flash_refwd", "heads"),
                           ("flash_bwd_dkv", "heads"),
                           ("flash_bwd_dq", "heads")}
    want = jax.value_and_grad(reference, (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4)


def _attention_ops(**kw):
    """Op types of one ``multi_head_attention`` layer, in order."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [256, 128], dtype="float32")
        rope = kw.pop("rope", False)
        if rope:
            kw["rope_pos"] = fluid.layers.data(
                "pos", [256], dtype="int64", append_batch_size=False)
        transformer.multi_head_attention(
            x, x, None, 128, 2, 0.1, False, "att", **kw)
    return [op.type for op in main.global_block().ops]


def test_fused_bert_layer_holds_no_transpose():
    """The fused layer without rotation or grouped heads hands its three
    projections to ``fused_attention`` as they are, [B, S, H*D] with
    ``n_head``, and the result to the output projection."""
    types = _attention_ops(use_fused_attention=True)
    assert "transpose2" not in types and "reshape2" not in types
    assert types.count("fused_attention") == 1
    at = types.index("fused_attention")
    assert types[:at].count("mul") == 3 and types[at + 1:].count("mul") == 1


@pytest.mark.parametrize("kind", ["rope", "grouped_heads", "composed"])
def test_rotated_grouped_and_composed_layers_keep_their_four_transposes(
        kind):
    kw = {"rope": dict(use_fused_attention=True, rope=True),
          "grouped_heads": dict(use_fused_attention=True, n_kv_head=1),
          "composed": dict(use_fused_attention=False)}[kind]
    types = _attention_ops(**kw)
    assert types.count("transpose2") == 4
    assert types.count("fused_attention") == (kind != "composed")


# ------------------------------------------- the shared key part (PR 50)
def _latent_operands(S, H, dn, dr, dv, seed, B=1):
    """(q [B,S,H*dn], q_r [B,S,H*dr], kv [B,S,H*(dn+dv)], k_r [B,S,dr]):
    latent attention's expanded form where its projections write it."""
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(B, S, w).astype("float32"))
                 for w in (H * dn, H * dr, H * (dn + dv), dr))


def _shared_call(q, q_r, kv, k_r, H, scale, **kw):
    return flash_attention(q, kv, kv, None, scale, causal=True,
                           mxu_dtype="bfloat16", min_seq=128, n_head=H,
                           shared=(q_r, k_r), **kw)


def _expanded_reference(q, q_r, kv, k_r, H, scale):
    """The composed form over every head's q, k [B,H,S,dn+dr] and v
    [B,H,S,dv], built as the prefill built them before PR 50."""
    from paddle_tpu.ops.attention import composed_attention

    B, S, dn, dr = q.shape[0], q.shape[1], q.shape[2] // H, k_r.shape[2]
    qh = jnp.concatenate([_split(q, H), _split(q_r, H)], axis=3)
    kvh = _split(kv, H)
    kh = jnp.concatenate(
        [kvh[..., :dn], jnp.broadcast_to(k_r[:, None], (B, H, S, dr))],
        axis=3)
    return _merge(composed_attention(qh, kh, kvh[..., dn:], None, scale,
                                     True))


SHARED_CASES = [
    # S, H, d_nope, d_rope, d_v: one lane tile, three, a length that is no
    # whole block (200; three lane tiles + 8), two / four / six heads (one
    # and two a grid step), a rotated part a whole lane tile wide, a value
    # width of two tiles
    (128, 2, 128, 64, 128), (384, 4, 128, 64, 128), (200, 6, 128, 64, 128),
    (392, 2, 128, 64, 128), (384, 6, 128, 64, 128), (128, 4, 128, 128, 128),
    (200, 2, 128, 64, 256),
]


@pytest.mark.parametrize("S,H,dn,dr,dv", SHARED_CASES)
def test_shared_key_part_equals_composed_on_the_expanded_operands(
        S, H, dn, dr, dv, monkeypatch):
    """The rank-3 call with the one key part all heads share (``kvb``'s
    output as both k and v, q in two parts) against the composed form
    over every head's rebuilt keys and values; it runs the forward kernel
    in the lanes layout and pads nothing."""
    monkeypatch.delenv("PADDLE_TPU_FLASH_MIN_SEQ", raising=False)
    q, q_r, kv, k_r = _latent_operands(S, H, dn, dr, dv, S + H)
    scale = (dn + dr) ** -0.5
    before = _lane_plans()
    got = _shared_call(q, q_r, kv, k_r, H, scale)
    assert got.shape == (1, S, H * dv) and got.dtype == jnp.float32
    assert {key for key, n in _lane_plans().items()
            if n > before.get(key, 0)} == {("flash_fwd", "lanes")}
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(
        got, _expanded_reference(q, q_r, kv, k_r, H, scale), atol=2e-5,
        rtol=0)


@pytest.mark.parametrize("S,blocks", [(200, None), (1100, None),
                                      (392, (128, 128)), (392, (256, 128))])
def test_a_ragged_last_block_reads_nothing_past_the_last_key(
        S, blocks, monkeypatch):
    """A causal call with the shared key part pads nothing: its last
    query and key blocks hang over the operands' end, where the
    interpreter plants NaN (the chip leaves whatever the buffer held).
    One block (200), the planned 512 x 512 over 1,100 (the last key block
    holds 76 keys) and forced blocks with a carry: finite and equal."""
    from jax.experimental import pallas as pl

    from paddle_tpu.ops import attention

    def probe(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    past = pl.pallas_call(
        probe, grid=(2,), in_specs=[pl.BlockSpec((128, 128),
                                                 lambda i: (i, 0))],
        out_specs=pl.BlockSpec((128, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((256, 128), jnp.float32),
        interpret=True)(jnp.ones((200, 128), jnp.float32))
    assert bool(jnp.isnan(past[200:]).all())    # what this test rests on
    monkeypatch.delenv("PADDLE_TPU_FLASH_MIN_SEQ", raising=False)
    if blocks:
        monkeypatch.setattr(
            attention, "_forward_plan",
            lambda S, Sk, *a, **k: (-(-S // blocks[0]) * blocks[0],
                                    -(-Sk // blocks[1]) * blocks[1]) + blocks)
    H, dn, dr, dv = 2, 128, 64, 128
    q, q_r, kv, k_r = _latent_operands(S, H, dn, dr, dv, S, B=2)
    pads = []
    monkeypatch.setattr(attention, "_pad_axis", lambda x, axis, to, *a: (
        pads.append((x.shape, axis, to)), attention.pad_axis(x, axis, to,
                                                              *a))[1])
    got = _shared_call(q, q_r, kv, k_r, H, 0.07)
    # the one pad left is q_r's and k_r's last axis, to a lane tile
    assert {axis for _shape, axis, _to in pads} <= {2, 3}
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(
        got, _expanded_reference(q, q_r, kv, k_r, H, 0.07), atol=2e-5,
        rtol=0)


@pytest.mark.parametrize("why", ["under_min_seq", "width_off_the_tile",
                                 "not_causal"])
def test_shared_key_part_means_the_same_where_the_kernel_cannot_take_it(
        why, monkeypatch):
    """Under the call's threshold the composed form, at a head width that
    is no whole lane tile the kernel's other layout, both over operands
    the lowering builds inside; a call that is not causal keeps the lanes
    layout and pads (its padded keys need a mask of their own)."""
    from paddle_tpu.observe.families import KERNEL_DISPATCHES
    from paddle_tpu.ops.attention import _expanded, composed_attention

    monkeypatch.delenv("PADDLE_TPU_FLASH_MIN_SEQ", raising=False)
    S, dn, dr, dv = {"under_min_seq": (96, 128, 64, 128),
                     "width_off_the_tile": (256, 16, 8, 16),
                     "not_causal": (200, 128, 64, 128)}[why]
    H = 4
    q, q_r, kv, k_r = _latent_operands(S, H, dn, dr, dv, 11)
    composed = KERNEL_DISPATCHES.labels(op="attention", impl="composed")
    before, plans = composed.value, _lane_plans()
    causal = why != "not_causal"
    got = flash_attention(q, kv, kv, None, 0.2, causal=causal, min_seq=128,
                          n_head=H, shared=(q_r, k_r))
    lowered = {key for key, n in _lane_plans().items()
               if n > plans.get(key, 0)}
    if why == "under_min_seq":
        assert composed.value > before and not lowered
    else:
        assert lowered == {("flash_fwd", "heads" if dn == 16 else "lanes")}
    want = _merge(composed_attention(*_expanded(q, kv, (q_r, k_r), H), None,
                                     0.2, causal))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("what", ["window", "two_tensors", "rank_4",
                                  "trainable_bias", "grad"])
def test_shared_key_part_refuses_what_it_does_not_take(what):
    from paddle_tpu.core.lowering import LowerContext
    from paddle_tpu.core.registry import get_op

    H, S = 2, 128
    q, q_r, kv, k_r = _latent_operands(S, H, 128, 64, 128, 3)
    if what == "window":
        with pytest.raises(NotImplementedError, match="forward-only"):
            flash_attention(q, kv, kv, None, 0.1, causal=True, window=64,
                            n_head=H, shared=(q_r, k_r))
    elif what == "two_tensors":
        with pytest.raises(ValueError, match="ONE tensor"):
            flash_attention(q, kv, kv[..., :H * 128], None, 0.1,
                            causal=True, n_head=H, shared=(q_r, k_r))
    elif what == "rank_4":
        with pytest.raises(ValueError, match=r"\[B, S, H\*D\]"):
            flash_attention(_split(q, H), _split(q, H), _split(q, H), None,
                            0.1, causal=True, shared=(q_r, k_r))
    elif what == "trainable_bias":
        with pytest.raises(NotImplementedError, match="forward-only"):
            flash_attention(q, kv, kv, jnp.zeros((1, H, S, S)), 0.1,
                            bias_grad=True, n_head=H, shared=(q_r, k_r))
    else:
        ins = {"Q": [q], "K": [kv], "V": [kv], "QR": [q_r], "KR": [k_r],
               "Out@GRAD": [q]}
        with pytest.raises(NotImplementedError, match="forward-only"):
            get_op("fused_attention").grad_lowering(
                LowerContext(), ins, {"scale": 0.1, "causal": True,
                                      "n_head": H})


# --------------------- several heads a multi-pass grid step (PR 57)
def _step_heads(kernel):
    """{heads label: multi-pass lowerings of ``kernel`` counted so far}."""
    from paddle_tpu.observe import REGISTRY

    return {s["labels"]["heads"]: s["value"] for s in REGISTRY.snapshot()[
        "metrics"]["paddle_flash_step_heads_total"]["samples"]
        if s["labels"]["kernel"] == kernel
        and s["labels"]["single_pass"] == "0" and s["value"]}


def _multi_pass_call(form):
    """``(call, kernel name, heads a step the rule gives)`` of one
    multi-pass forward a form; ``call()`` returns (out, lse)."""
    from paddle_tpu.ops import attention as A

    rs = np.random.RandomState(57)
    draw = lambda *shape: jnp.asarray(  # noqa: E731
        rs.randn(*shape).astype("float32"))
    if form == "latent_ragged_causal":
        # 1,300 keys: three 512-blocks, the last 276 keys long
        H = 4
        q, q_r, kv, k_r = _latent_operands(1300, H, 128, 64, 128, 57)
        return (lambda: A._forward_pallas(
            q, kv, kv, None, 0.07, causal=True, mxu_dtype="bfloat16",
            n_head=H, shared=(q_r, k_r))), A.KERNEL_FWD, 4
    if form == "grouped_heads_under_a_window":
        # twelve query heads over two key/value heads (group 6: three a
        # step, two steps a K/V head), a window that is no whole block
        q, k, v = draw(1, 12, 1200, 64), draw(1, 2, 1200, 64), \
            draw(1, 2, 1200, 64)
        return (lambda: A._forward_pallas(
            q, k, v, None, 0.125, causal=True, window=300,
            name=A.KERNEL_FWD_WIN)), A.KERNEL_FWD_WIN, 3
    q, k, v = (draw(2, 4, 1100, 64) for _ in range(3))
    return (lambda: A._forward_pallas(q, k, v, None, 0.125, causal=True)), \
        A.KERNEL_FWD, 4


@pytest.mark.parametrize("form", ["latent_ragged_causal",
                                  "grouped_heads_under_a_window",
                                  "ungrouped_causal"])
def test_multi_pass_heads_a_step_equal_one_head_a_step(form, monkeypatch):
    """The multi-pass forward at the count ``_forward_heads`` gives
    against the same call held to one head a step (the test puts its own
    function in the rule's place): a head's arithmetic and its order of
    key blocks do not depend on what else the step holds, so output and
    logsumexp are equal to the last bit."""
    from paddle_tpu.ops import attention as A

    call, kernel, want = _multi_pass_call(form)
    before = _step_heads(kernel)
    out, lse = call()
    counted = {h for h, n in _step_heads(kernel).items()
               if n > before.get(h, 0)}
    assert counted == {str(want)}
    rule = A._forward_heads
    monkeypatch.setattr(
        A, "_forward_heads", lambda H, group, bq, bk, single_pass, *a, **kw:
        rule(H, group, bq, bk, single_pass, *a, **kw) if single_pass else 1)
    before = _step_heads(kernel)
    out_1, lse_1 = call()
    assert {h for h, n in _step_heads(kernel).items()
            if n > before.get(h, 0)} == {"1"}
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(lse).all())
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_1))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(lse_1))
