"""The keep mask of a dropout (ops/random_mask.py, ISSUE 35): drawn once an
op from ``RngBitGenerator``, compared as integers, saved as ``Mask`` and read
by the grad op. Properties of the draw, not its bits: the stream is not
threefry's and is free to differ from one backend to the next.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.backward import append_backward
from paddle_tpu.core.lowering import LowerContext
from paddle_tpu.core.registry import get_op
from paddle_tpu.core.scope import Scope, scope_guard

SITES = ["dropout", "fused_attention"]
# >= 10**6 elements at either site: [2048, 512] and [B, H, S, D]
DROPOUT_SHAPE = (2048, 512)
ATTN_SHAPE = (64, 4, 64, 64)


def _lower_site(site, p, key, is_test=False, impl="upscale_in_train"):
    """One op lowered straight from the registry over ones (so ``Out`` is
    the mask the forward applied). Returns (ctx, Out, Mask, keep scale)."""
    ctx = LowerContext(rng=key, is_test=is_test)
    if site == "dropout":
        outs = get_op("dropout").lowering(
            ctx, {"X": [jnp.ones(DROPOUT_SHAPE, jnp.float32)]},
            {"dropout_prob": p, "dropout_implementation": impl})
        scale = 1.0 / (1.0 - p) if impl == "upscale_in_train" else 1.0
    else:
        # uniform scores over ones: the attention output is ones
        ones = jnp.ones(ATTN_SHAPE, jnp.float32)
        outs = get_op("fused_attention").lowering(
            ctx, {"Q": [ones], "K": [ones], "V": [ones]},
            {"scale": 1.0, "dropout": p, "flash_min_seq": 1 << 20})
        scale = 1.0 / (1.0 - p)
    return ctx, np.asarray(outs["Out"][0]), np.asarray(outs["Mask"][0]), scale


@pytest.mark.parametrize("p", [0.1, 0.5])
@pytest.mark.parametrize("site", SITES)
def test_keep_rate_is_within_four_sigma(site, p):
    _ctx, out, mask, scale = _lower_site(site, p, jax.random.PRNGKey(11))
    n = out.size
    assert n >= 10 ** 6
    kept = np.count_nonzero(out)
    sigma = math.sqrt(n * p * (1.0 - p))
    assert abs(kept - n * (1.0 - p)) < 4.0 * sigma, (kept, n, p)
    # two values only, and Out is the saved Mask applied
    np.testing.assert_allclose(np.unique(mask), [0.0, scale], rtol=1e-6)
    np.testing.assert_array_equal(out, mask)


@pytest.mark.parametrize("p", [0.1, 0.5])
@pytest.mark.parametrize("site", SITES)
def test_upscale_in_train_preserves_the_mean(site, p):
    _ctx, out, _mask, _scale = _lower_site(site, p, jax.random.PRNGKey(5))
    # mean of n scaled Bernoulli draws: sigma = sqrt(p / ((1 - p) n))
    sigma = math.sqrt(p / ((1.0 - p) * out.size))
    assert abs(float(out.mean(dtype=np.float64)) - 1.0) < 4.0 * sigma


def test_downgrade_in_infer_keeps_kept_elements_unscaled():
    _ctx, out, mask, _ = _lower_site("dropout", 0.5, jax.random.PRNGKey(5),
                                     impl="downgrade_in_infer")
    np.testing.assert_array_equal(np.unique(mask), [0.0, 1.0])
    np.testing.assert_array_equal(out, mask)


@pytest.mark.parametrize("site", SITES)
def test_same_key_same_mask_and_next_key_another(site):
    key = jax.random.PRNGKey(3)
    ctx, a, _, _ = _lower_site(site, 0.1, key)
    _, b, _, _ = _lower_site(site, 0.1, key)
    np.testing.assert_array_equal(a, b)
    # the chain moved on: the next op of the same trace draws another mask
    _, c, _, _ = _lower_site(site, 0.1, ctx.final_rng())
    assert ctx.rng_used
    differ = np.mean((a == 0) != (c == 0))
    assert 0.1 < differ < 0.26, differ          # 2 p (1 - p) = 0.18


@pytest.mark.parametrize("site", SITES)
def test_is_test_draws_nothing(site):
    ctx, out, mask, _ = _lower_site(site, 0.1, jax.random.PRNGKey(3),
                                    is_test=True)
    assert ctx.rng_used is False
    np.testing.assert_array_equal(mask, np.ones_like(mask))
    want = 0.9 if site == "dropout" else 1.0    # downgrade_in_infer default
    _, out_d, _, _ = _lower_site(site, 0.1, jax.random.PRNGKey(3),
                                 is_test=True, impl="downgrade_in_infer")
    np.testing.assert_allclose(out, np.ones_like(out))
    np.testing.assert_allclose(out_d, want * np.ones_like(out_d), rtol=1e-6)


def test_keep_of_one_keeps_everything_and_zero_nothing():
    _, out, _, _ = _lower_site("dropout", 0.0, jax.random.PRNGKey(1))
    assert np.count_nonzero(out) == out.size
    _, out, _, _ = _lower_site("dropout", 1.0, jax.random.PRNGKey(1),
                               impl="downgrade_in_infer")
    assert np.count_nonzero(out) == 0


def test_mask_plans_are_counted_at_lowering():
    from paddle_tpu.observe.families import DROPOUT_MASK_PLANS

    plans = {s: DROPOUT_MASK_PLANS.labels(site=s, bits="rbg_u32")
             for s in SITES}
    before = {s: c.value for s, c in plans.items()}
    for site in SITES:
        _lower_site(site, 0.1, jax.random.PRNGKey(0))
        _lower_site(site, 0.1, jax.random.PRNGKey(0), is_test=True)
    assert {s: c.value - before[s] for s, c in plans.items()} == {
        "dropout": 1, "fused_attention": 1}


# ------------------------------------------------- through the Executor
def _dropout_program(p=0.1, seed=None, program_seed=7):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = program_seed
    with fluid.program_guard(main, startup):
        x = layers.data("x", [64], dtype="float32")
        x.stop_gradient = False
        y = layers.dropout(x, p, seed=seed,
                           dropout_implementation="upscale_in_train")
        loss = layers.reduce_sum(y)
        append_backward(loss)
    return main, startup, [y.name, "x@GRAD"]


def _run_steps(build, mode, steps=3, feed=None):
    """The fetches of every step (sequential) or of the last step of one
    K-step window (repeated)."""
    main, startup, fetch = build()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    feed = feed or {"x": np.ones((256, 64), "float32")}
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        if mode == "repeated":
            return [exe.run_repeated(main, feed=feed, fetch_list=fetch,
                                     scope=scope, steps=steps)]
        return [exe.run(main, feed=feed, fetch_list=fetch, scope=scope)
                for _ in range(steps)]


@pytest.mark.parametrize("mode", ["sequential", "repeated"])
def test_dropout_grad_reads_the_mask_the_forward_applied(mode):
    for out, grad in _run_steps(_dropout_program, mode):
        out, grad = np.asarray(out), np.asarray(grad)
        dropped = out == 0
        assert 0.05 < dropped.mean() < 0.15
        assert np.all(grad[dropped] == 0)
        np.testing.assert_allclose(grad[~dropped], 1.0 / 0.9, rtol=1e-6)


def test_consecutive_steps_draw_different_masks_and_a_seed_reproduces():
    first = _run_steps(_dropout_program, "sequential")
    again = _run_steps(_dropout_program, "sequential")
    for (a, _), (b, _) in zip(first, again):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    zeros = [np.asarray(o) == 0 for o, _ in first]
    assert (zeros[0] != zeros[1]).any() and (zeros[1] != zeros[2]).any()
    other = _run_steps(lambda: _dropout_program(program_seed=8),
                       "sequential")
    assert (zeros[0] != (np.asarray(other[0][0]) == 0)).any()
    # the K-step window walks the same chain as K single steps
    window = _run_steps(_dropout_program, "repeated")
    np.testing.assert_array_equal(np.asarray(window[0][0]),
                                  np.asarray(first[-1][0]))


def test_fix_seed_reproduces_whatever_the_program_seed():
    runs = [_run_steps(lambda s=s: _dropout_program(seed=123,
                                                    program_seed=s),
                       "sequential", steps=2) for s in (7, 8)]
    masks = [np.asarray(out) for run in runs for out, _ in run]
    for m in masks[1:]:                 # every step, either program seed
        np.testing.assert_array_equal(masks[0], m)
    unseeded = np.asarray(_run_steps(_dropout_program, "sequential",
                                     steps=1)[0][0])
    assert (masks[0] != unseeded).any()


def _attention_program(p=0.5):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        q, k, v = (layers.data(n, [2, 16, 8], dtype="float32")
                   for n in "qkv")
        v.stop_gradient = False
        out = layers.fused_attention(q, k, v, scale=0.25, dropout=p)
        loss = layers.reduce_sum(out)
        append_backward(loss)
    mask = main.global_block().ops[0].outputs["Mask"][0]
    assert main.global_block().ops[0].type == "fused_attention"
    return main, startup, [out.name, mask, "v@GRAD"]


@pytest.mark.parametrize("mode", ["sequential", "repeated"])
def test_fused_attention_grad_reads_the_mask_the_forward_applied(mode):
    rs = np.random.RandomState(0)
    feed = {n: rs.randn(3, 2, 16, 8).astype("float32") for n in "qkv"}
    scores = np.einsum("bhqd,bhkd->bhqk", feed["q"], feed["k"]) * 0.25
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    plain = np.einsum("bhqk,bhkd->bhqd", probs, feed["v"])
    seen = []
    for out, mask, dv in _run_steps(_attention_program, mode, feed=feed):
        out, mask, dv = (np.asarray(a) for a in (out, mask, dv))
        np.testing.assert_allclose(np.unique(mask), [0.0, 2.0])
        np.testing.assert_allclose(out, plain * mask, atol=1e-5)
        # d sum(Out) / dV = probs^T (1 * Mask): the SAME mask
        np.testing.assert_allclose(
            dv, np.einsum("bhqk,bhqd->bhkd", probs, mask), atol=1e-5)
        seen.append(mask)
    if mode == "sequential":
        assert (seen[0] != seen[1]).any()


# ------------------------------------------------------ under a data mesh
def test_sharded_draw_is_per_shard_and_matches_the_rate():
    """Four virtual devices on the data axis: the lowering enters a
    ``shard_map`` and every shard draws its own rows from its own stream
    (no two shards hold the same mask)."""
    import re

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.ops.random_mask import keep_mask

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4, 1),
                ("data", "model"))
    shape = (64, 4096)
    ctx = LowerContext(rng=jax.random.PRNGKey(0), mesh=mesh)

    def draw(key):
        return keep_mask(ctx, key, 0.9, shape, "dropout")

    with mesh:
        fn = jax.jit(draw, in_shardings=NamedSharding(mesh, P()),
                     out_shardings=NamedSharding(mesh, P("data")))
        hlo = fn.lower(jax.random.PRNGKey(2)).as_text()
        keep = np.asarray(fn(jax.random.PRNGKey(2)))
    # the generator is called inside the manual region on one shard's rows,
    # never on the global shape (the CPU backend expands the op when it
    # compiles, so this reads the module as lowered)
    drawn = set(re.findall(
        r"rng_bit_generator.*->.*tensor<([\dx]+)xui32>\)", hlo))
    assert drawn == {"16x4096"}, drawn
    n = keep.size
    assert abs(keep.sum() - 0.9 * n) < 4 * math.sqrt(n * 0.09)
    shards = keep.reshape(4, 16, 4096)
    for i in range(4):
        for j in range(i):
            assert (shards[i] != shards[j]).any()
    np.testing.assert_array_equal(keep,
                                  np.asarray(fn(jax.random.PRNGKey(2))))
    # leading axis the data axis does not divide: one draw of the whole
    whole = keep_mask(ctx, jax.random.PRNGKey(2), 0.9, (6, 128), "dropout")
    assert whole.shape == (6, 128)
