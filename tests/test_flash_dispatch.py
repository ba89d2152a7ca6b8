"""Short-S dispatch policy: below PADDLE_TPU_FLASH_MIN_SEQ the
fused-attention entry points run the composed XLA math instead of the
Pallas kernel (the 2026-07-31 v5e window measured the S=128 transformer
slower on the kernel than the r1 composed baseline — flash pays off at
long S). The policy must be numerics-neutral and honestly labeled.

Note: tests/conftest.py pins PADDLE_TPU_FLASH_MIN_SEQ=0 suite-wide so
kernel tests keep kernel coverage; these tests set the env themselves.
"""

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _clean_decisions():
    """The decision ledger must never leak into later tests."""
    yield
    from paddle_tpu import kernels

    kernels.reset_decisions()


def _qkv(B=2, H=2, S=64, D=32, seed=0):
    import jax.numpy as jnp

    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
    return mk(), mk(), mk()


def test_flash_effective_threshold(monkeypatch):
    from paddle_tpu.ops import attention as A

    monkeypatch.delenv("PADDLE_TPU_FLASH_MIN_SEQ", raising=False)
    assert A.flash_min_seq() == 256
    assert not A.flash_effective(128)
    assert A.flash_effective(256)
    assert A.flash_effective(1024)
    # cross-attention: the longer side decides
    assert A.flash_effective(64, 512)

    monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_SEQ", "0")
    assert A.flash_effective(1)
    monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_SEQ", "100000")
    assert not A.flash_effective(4096)

    monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_SEQ", "128k")
    with pytest.raises(ValueError, match="PADDLE_TPU_FLASH_MIN_SEQ"):
        A.flash_min_seq()


def test_short_seq_dispatches_composed_same_numerics(monkeypatch):
    """flash_attention at S<threshold returns the composed result, and it
    matches the kernel (forced) within interpret-mode tolerance — fwd
    and all three input grads."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import attention as A

    q, k, v = _qkv()
    scale = q.shape[-1] ** -0.5

    def loss(fn):
        return lambda a, b, c: (fn(a, b, c, None, scale) ** 2).sum()

    monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_SEQ", "256")
    out_short = A.flash_attention(q, k, v, scale=scale)
    g_short = jax.grad(loss(lambda a, b, c, bias, s: A.flash_attention(
        a, b, c, bias, s)), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out_short),
        np.asarray(A.composed_attention(q, k, v, scale=scale)),
        rtol=0, atol=0)  # identical: it IS the composed path

    monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_SEQ", "0")
    out_kernel = A.flash_attention(q, k, v, scale=scale)
    g_kernel = jax.grad(loss(lambda a, b, c, bias, s: A.flash_attention(
        a, b, c, bias, s)), argnums=(0, 1, 2))(q, k, v)

    np.testing.assert_allclose(np.asarray(out_short),
                               np.asarray(out_kernel), atol=2e-5)
    for gs, gk in zip(g_short, g_kernel):
        np.testing.assert_allclose(np.asarray(gs), np.asarray(gk),
                                   atol=5e-5)
    del jnp


def test_short_seq_causal_and_bias_parity(monkeypatch):
    """Causal masking and additive key bias agree between the dispatch
    target and the kernel at short S."""
    import jax.numpy as jnp

    from paddle_tpu.ops import attention as A

    q, k, v = _qkv(S=64)
    scale = q.shape[-1] ** -0.5
    # pad-style key bias: mask out the last 7 keys
    bias = jnp.zeros((2, 1, 1, 64), jnp.float32).at[:, :, :, 57:].set(-1e9)

    monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_SEQ", "256")
    out_c = A.flash_attention(q, k, v, bias, scale=scale, causal=True)
    monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_SEQ", "0")
    out_k = A.flash_attention(q, k, v, bias, scale=scale, causal=True)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_k),
                               atol=2e-5)


SEQUENCE_CASES = {
    # name: (PADDLE_TPU_FLASH_MIN_SEQ or None, the op's own flash_min_seq
    #        or None, Sq, Sk, the kernel runs)
    "bert_train_s128_cell": (None, None, 128, 128, False),
    "bert_train_s512_cell": (None, None, 512, 512, True),
    "one_under_256": (None, None, 255, 255, False),
    "at_256": (None, None, 256, 256, True),
    "longer_side_decides": (None, None, 64, 512, True),
    "both_sides_short": (None, None, 64, 128, False),
    "ops_own_128_in_place_of_256": (None, 128, 200, 200, True),
    "under_the_ops_own_128": (None, 128, 127, 127, False),
    "env_wins_over_the_ops_own": ("1024", 128, 512, 512, False),
    "env_zero_forces_the_kernel": ("0", None, 8, 8, True),
}


@pytest.mark.parametrize("case", sorted(SEQUENCE_CASES))
def test_the_sequence_decides(case, monkeypatch):
    """Flash against composed is a threshold on ``max(Sq, Sk)``:
    ``PADDLE_TPU_FLASH_MIN_SEQ`` where it is set, else the op's own
    ``flash_min_seq=``, else 256 — and nothing else is asked.
    ``decisions_seen()["attention"]`` (what benchmarks/lib/train_loop.py
    reports) says which form the call lowered to, and below the
    threshold the result IS the composed one."""
    import jax.numpy as jnp

    from paddle_tpu import kernels
    from paddle_tpu.ops import attention as A

    env, own, sq, sk, want = SEQUENCE_CASES[case]
    if env is None:
        monkeypatch.delenv("PADDLE_TPU_FLASH_MIN_SEQ", raising=False)
    else:
        monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_SEQ", env)
    assert A._flash_decision(sq, sk, own) is want
    if own is None:
        assert A.flash_effective(sq, sk) is want
    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randn(1, 1, sq, 32).astype("float32"))
    k, v = (jnp.asarray(rs.randn(1, 1, sk, 32).astype("float32"))
            for _ in range(2))
    kernels.reset_decisions()
    out = A.flash_attention(q, k, v, None, 32 ** -0.5, min_seq=own)
    assert kernels.decisions_seen()["attention"] == {
        "choice": "flash" if want else "composed"}
    ref = A.composed_attention(q, k, v, scale=32 ** -0.5)
    if want:
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
    else:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_flash_env_keys_the_plan_cache(monkeypatch):
    """Changing PADDLE_TPU_FLASH_MIN_SEQ mid-process re-prepares: a plan
    cached under one env value must never be served under another (the
    value rides kernels.config_key() into the executor's plan-cache
    key)."""
    import paddle_tpu as fluid
    from paddle_tpu.core.scope import Scope, scope_guard
    from paddle_tpu.observe.families import EXECUTOR_CACHE_MISSES

    monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_SEQ", "100000")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data("x", [2, 8, 32], dtype="float32")
            out = fluid.layers.fused_attention(x, x, x, scale=0.2)
            loss = fluid.layers.mean(out)
    scope = Scope()
    X = np.random.RandomState(0).randn(2, 2, 8, 32).astype(np.float32)
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        exe.run(main, feed={"x": X}, fetch_list=[loss], scope=scope)
        m0 = EXECUTOR_CACHE_MISSES.value
        monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_SEQ", "0")
        exe.run(main, feed={"x": X}, fetch_list=[loss], scope=scope)
        assert EXECUTOR_CACHE_MISSES.value == m0 + 1  # re-prepared
        exe.run(main, feed={"x": X}, fetch_list=[loss], scope=scope)
        assert EXECUTOR_CACHE_MISSES.value == m0 + 1  # then cache-hits


def test_fused_attention_op_short_seq_trains(monkeypatch):
    """The fused_attention op in a Program at S<threshold lowers through
    the composed dispatch and trains (grad path included)."""
    monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_SEQ", "256")
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.core.scope import Scope, scope_guard

    main, startup = fluid.Program(), fluid.Program()
    with scope_guard(Scope()):
        with fluid.program_guard(main, startup):
            x = layers.data("x", [2, 8, 32], dtype="float32")  # [H,S,D]
            q = layers.fc(x, 32, num_flatten_dims=3)
            out = layers.fused_attention(q, q, q, scale=32 ** -0.5)
            loss = layers.mean(out)
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        val, = exe.run(
            main,
            feed={"x": np.random.RandomState(0)
                  .randn(4, 2, 8, 32).astype("float32")},
            fetch_list=[loss])
    assert np.isfinite(np.asarray(val)).all()


@pytest.mark.parametrize("layout", ["lanes", "heads"])
def test_block_plan_counter_says_which_layout_a_call_took(layout,
                                                          monkeypatch):
    """``paddle_flash_block_plans_total{layout}``: a [B, S, H*D] call
    counts its four kernel plans under ``lanes``, a [B, H, S, D] call
    under ``heads``, and neither touches the other's series."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.observe.families import FLASH_BLOCK_PLANS
    from paddle_tpu.ops import attention as A

    monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_SEQ", "0")
    assert FLASH_BLOCK_PLANS.labelnames == ("kernel", "block", "single_pass",
                                            "layout")
    q, k, v = _qkv(B=1, H=2, S=256, D=64)
    if layout == "lanes":
        q, k, v = (A._merge_heads(t) for t in (q, k, v))
    n_head = 2 if layout == "lanes" else None
    kernels = (A.KERNEL_FWD, A.KERNEL_REFWD, A.KERNEL_BWD_DKV,
               A.KERNEL_BWD_DQ)
    series = {(n, lay): FLASH_BLOCK_PLANS.labels(
        kernel=n, block="256x256", single_pass="1", layout=lay)
        for n in kernels for lay in ("lanes", "heads")}
    before = {key: c.value for key, c in series.items()}

    def loss(q, k, v):
        return jnp.sum(A.flash_attention(q, k, v, None, 0.125,
                                         n_head=n_head))

    loss(q, k, v)                           # the forward's own name
    jax.grad(loss, (0, 1, 2))(q, k, v)      # the rerun and both backwards
    grew = {key for key, c in series.items() if c.value > before[key]}
    assert grew == {(n, layout) for n in kernels}
