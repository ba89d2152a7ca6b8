"""layers.rope: rotary position embeddings (rotate-half convention) —
numerics vs a hand-rolled reference, the relative-position property,
gradients, and the GPT integration (training parity + KV-cache decode
with rotated cached keys, composed with GQA).
"""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.scope import Scope, scope_guard


def _ref_rope(x, pos, base=10000.0):
    d = x.shape[-1]
    half = d // 2
    inv = base ** (-np.arange(half, dtype="float64") / half)
    ang = pos.astype("float64")[:, None] * inv[None, :]
    sin, cos = np.sin(ang), np.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return np.concatenate([x1 * cos - x2 * sin,
                           x1 * sin + x2 * cos], -1).astype(x.dtype)


def _run_rope(x, pos):
    main, startup = fluid.Program(), fluid.Program()
    scope = Scope()
    with scope_guard(scope):
        with fluid.program_guard(main, startup):
            xv = layers.data("x", list(x.shape), dtype="float32",
                             append_batch_size=False)
            pv = layers.data("p", [len(pos)], dtype="int64",
                             append_batch_size=False)
            out = layers.rope(xv, pv)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        (o,) = exe.run(main, feed={"x": x, "p": pos}, fetch_list=[out],
                       scope=scope)
    return np.asarray(o)


def test_rope_matches_reference():
    rs = np.random.RandomState(0)
    x = rs.randn(2, 3, 8, 16).astype("float32")
    pos = np.arange(8).astype("int64")
    got = _run_rope(x, pos)
    np.testing.assert_allclose(got, _ref_rope(x, pos), atol=1e-5,
                               rtol=1e-5)


def test_rope_relative_position_property():
    """q_i . k_j after rotation depends only on (i - j): shifting BOTH
    positions by a constant leaves every dot product unchanged."""
    rs = np.random.RandomState(1)
    q = rs.randn(1, 1, 6, 32).astype("float32")
    k = rs.randn(1, 1, 6, 32).astype("float32")

    def scores(shift):
        pos = (np.arange(6) + shift).astype("int64")
        qr, kr = _run_rope(q, pos), _run_rope(k, pos)
        return np.einsum("bhqd,bhkd->bhqk", qr, kr)

    np.testing.assert_allclose(scores(0), scores(37), atol=1e-3,
                               rtol=1e-3)


def test_rope_norm_preserved_and_zero_pos_identity():
    rs = np.random.RandomState(2)
    x = rs.randn(1, 2, 4, 16).astype("float32")
    pos = np.arange(4).astype("int64")
    out = _run_rope(x, pos)
    np.testing.assert_allclose(np.linalg.norm(out, axis=-1),
                               np.linalg.norm(x, axis=-1), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(out[:, :, 0], x[:, :, 0], atol=1e-6)


GQA_ROPE_CFG = dict(d_model=32, d_ff=64, n_head=4, n_kv_head=2,
                    n_layer=2, vocab=64, max_length=16, dropout=0.0,
                    pos_emb="rope")


def test_gpt_rope_trains_and_paths_match():
    from paddle_tpu.models import gpt

    rs = np.random.RandomState(3)
    feed = {"ids": rs.randint(1, 64, (2, 8)).astype("int64")}

    def run(fused):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 11
        startup.random_seed = 11
        scope = Scope()
        with scope_guard(scope):
            with fluid.program_guard(main, startup):
                loss, _ = gpt.build(GQA_ROPE_CFG, seq_len=8,
                                    use_fused_attention=fused)
                fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup, scope=scope)
            # no learned position table under rope
            assert scope.find_var("gpt_pos_emb") is None
            ls = []
            for _ in range(3):
                (l,) = exe.run(main, feed=feed, fetch_list=[loss],
                               scope=scope)
                ls.append(float(np.asarray(l).reshape(-1)[0]))
        return ls

    composed = run(False)
    fused = run(True)
    np.testing.assert_allclose(composed, fused, rtol=1e-4, atol=1e-5)
    assert composed[-1] < composed[0]


def test_gpt_rope_decode_matches_full_forward():
    """RoPE + GQA through the KV cache: rotated keys live in the
    n_kv-head cache and greedy decode equals the full forward."""
    import test_gpt_decode as tgd

    tgd._assert_decode_matches_full(GQA_ROPE_CFG)


def test_rope_per_row_positions():
    """[B, S] positions (packed rows): each row rotates by ITS
    positions — row b equals a separate call with pos[b]."""
    rs = np.random.RandomState(4)
    x = rs.randn(2, 2, 6, 16).astype("float32")
    pos = np.stack([np.arange(6), np.array([0, 1, 2, 0, 1, 2])]
                   ).astype("int64")

    main, startup = fluid.Program(), fluid.Program()
    scope = Scope()
    with scope_guard(scope):
        with fluid.program_guard(main, startup):
            xv = layers.data("x", list(x.shape), dtype="float32",
                             append_batch_size=False)
            pv = layers.data("p", list(pos.shape), dtype="int64",
                             append_batch_size=False)
            out = layers.rope(xv, pv)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        (o,) = exe.run(main, feed={"x": x, "p": pos}, fetch_list=[out],
                       scope=scope)
    o = np.asarray(o)
    for b in range(2):
        np.testing.assert_allclose(
            o[b], _ref_rope(x[b], pos[b]), atol=1e-5, rtol=1e-5,
            err_msg="row %d" % b)


@pytest.mark.parametrize("rows", ["one", "per_row"])
def test_rope_heads_last_is_the_rotation_of_the_transposed_tensor(rows):
    """``heads_last`` rotates [B, S, H, D] where a projection's reshape
    leaves it: the same numbers, bit for bit, as transposing to
    [B, H, S, D], rotating and transposing back ([S] and [B, S]
    positions alike)."""
    rs = np.random.RandomState(6)
    x = rs.randn(2, 6, 3, 16).astype("float32")
    pos = np.arange(6).astype("int64") if rows == "one" else np.stack(
        [np.arange(6), np.array([0, 1, 2, 0, 1, 2])]).astype("int64")
    yarn = dict(factor=4.0, low=1, high=5, mscale=1.2)

    main, startup = fluid.Program(), fluid.Program()
    scope = Scope()
    with scope_guard(scope):
        with fluid.program_guard(main, startup):
            xv = layers.data("x", list(x.shape), dtype="float32",
                             append_batch_size=False)
            pv = layers.data("p", list(pos.shape), dtype="int64",
                             append_batch_size=False)
            here = layers.rope(xv, pv, yarn=yarn, heads_last=True)
            there = layers.transpose(
                layers.rope(layers.transpose(xv, perm=[0, 2, 1, 3]), pv,
                            yarn=yarn), perm=[0, 2, 1, 3])
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        a, b = exe.run(main, feed={"x": x, "p": pos},
                       fetch_list=[here, there], scope=scope)
    assert tuple(here.shape) == x.shape
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
