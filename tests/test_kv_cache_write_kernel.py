"""The in-place KV-cache write kernel (kernels/kv_cache_write.py) against
its registered fallback, bit for bit, in interpret mode — and the
dispatch rule: which lowering takes the kernel and which the composed
form, as ``paddle_kv_cache_write_plans_total`` counts them."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import kv_cache_write as kvw
from paddle_tpu.observe.families import KV_CACHE_WRITE_PLANS

F32, BF16 = jnp.float32, jnp.bfloat16

# [B, n_kv, S, Dh]: Dh 64 lies S-minor on the TPU at these S (the cols
# form), Dh 128 row-major (the rows form); n_kv 2 under 8 query heads is
# the GQA cache (the kernel never sees the query heads)
SHAPES = {
    "dh64_cols": ((4, 2, 256, 64), "cols"),
    "dh128_rows": ((4, 2, 64, 128), "rows"),
    "dh64_square_rows": ((4, 2, 64, 64), "rows"),
    "dh96_cols": ((4, 3, 128, 96), "cols"),
}


def _positions(case, S):
    return {
        "first_last": [0, S - 1, 0, S - 1],
        "past_the_end": [S, S + 40, 10 * S, S - 1],
        "negative": [-1, -3, -S, -S - 7],
        "same_in_all": [5, 5, 5, 5],
        "all_different": [1, S // 2 + 2, 7, S - 2],
    }[case]


@pytest.mark.parametrize("positions", ["first_last", "past_the_end",
                                       "negative", "same_in_all",
                                       "all_different"])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_kernel_equals_fallback_bit_for_bit(shape, dtype, positions):
    (B, H, S, D), form = SHAPES[shape]
    assert kvw.write_plan((B, H, S, D), dtype)[0] == form
    rs = np.random.RandomState(sum(map(ord, shape + positions)))
    cache = jnp.asarray(rs.randn(B, H, S, D), dtype)
    # a float32 update into either cache: the cast is the kernel's too
    upd = jnp.asarray(rs.randn(B, H, 1, D), F32)
    pos = jnp.asarray(_positions(positions, S), jnp.int32).reshape(B, 1)
    got = kvw.kv_cache_write_pallas(cache, upd, pos, interpret=True)
    want = kvw.kv_cache_write_composed(cache, upd, pos)
    assert got.dtype == want.dtype == jnp.dtype(dtype)
    bits = np.uint32 if dtype == F32 else np.uint16
    got, want = (np.asarray(a).view(bits) for a in (got, want))
    np.testing.assert_array_equal(got, want)
    # exactly one row a slot differs from the input, every other row is
    # the input's own bits
    changed = (got != np.asarray(cache).view(bits)).any(axis=(1, 3))
    assert changed.sum(axis=1).tolist() == [1] * B


def test_no_plan_where_the_block_would_not_tile():
    assert kvw.write_plan((3, 2, 1000, 64), F32) is None      # S % 128
    assert kvw.write_plan((3, 2, 60, 128), F32) is None       # S % 8
    assert kvw.write_plan((3, 2, 64, 128), jnp.int8) is None
    assert kvw.write_plan((2, 64, 1024, 128), BF16) == (
        "rows", (1, 64, 16, 128))
    # a strip of 4 MiB a slot is past what the kernel double-buffers
    assert kvw.write_plan((2, 128, 1024, 64), F32) is None
    with pytest.raises(ValueError, match="no block plan"):
        kvw.kv_cache_write_pallas(
            jnp.zeros((3, 2, 1000, 64)), jnp.zeros((3, 2, 1, 64)),
            jnp.zeros((3,), jnp.int32), interpret=True)


def _count(form, rows):
    return KV_CACHE_WRITE_PLANS.labels(form=form, rows=str(rows)).value


def _dispatch_args(case):
    cache = jnp.zeros((4, 2, 256, 64), F32)
    one = jnp.ones((4, 2, 1, 64), F32)
    per_slot = jnp.asarray([[3], [9], [0], [255]], jnp.int32)
    return {
        # the lockstep generate loop: one position for every slot
        "scalar_pos": (cache, one, jnp.asarray([7], jnp.int32), 1),
        # the multi-token / prefill write: several rows a slot
        "several_rows": (cache, jnp.ones((4, 2, 5, 64), F32),
                         jnp.asarray([0], jnp.int32), 5),
        "several_rows_per_slot": (cache, jnp.ones((4, 2, 5, 64), F32),
                                  per_slot, 5),
        # per-slot, one row, but on a CPU backend
        "cpu_backend": (cache, one, per_slot, 1),
        # per-slot, one row, no block plan for the slab
        "no_plan": (jnp.zeros((4, 2, 100, 64), F32), one, per_slot, 1),
    }[case]


@pytest.mark.parametrize("case", ["scalar_pos", "several_rows",
                                  "several_rows_per_slot", "cpu_backend",
                                  "no_plan"])
def test_dispatch_takes_the_composed_form(case, monkeypatch):
    cache, upd, pos, rows = _dispatch_args(case)
    if case != "cpu_backend":
        # even where Pallas would compile: the operands decide
        monkeypatch.setenv("PADDLE_TPU_FLASH_INTERPRET", "0")
    else:
        monkeypatch.delenv("PADDLE_TPU_FLASH_INTERPRET", raising=False)
    before = (_count("composed", rows), _count("pallas", rows))
    got = kvw.kv_cache_write(cache, upd, pos)
    assert (_count("composed", rows), _count("pallas", rows)) == (
        before[0] + 1, before[1])
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(kvw.kv_cache_write_composed(cache, upd, pos)))


def test_dispatch_takes_the_kernel_where_pallas_compiles(monkeypatch):
    """Per-slot positions, one row a slot, a block plan, and the compiled
    path (forced, as tests/test_chip_bringup.py does): the lowering holds
    the Pallas call and counts ``form="pallas"``. Traced only — nothing
    here can run a Mosaic kernel. The tier's bypass switch turns it
    off."""
    cache, upd, pos, rows = _dispatch_args("cpu_backend")
    monkeypatch.setenv("PADDLE_TPU_FLASH_INTERPRET", "0")
    before = _count("pallas", rows)
    # a new function object a trace: jax caches a trace by the function
    jaxpr = jax.make_jaxpr(lambda *a: kvw.kv_cache_write(*a))(cache, upd, pos)
    assert _count("pallas", rows) == before + 1
    assert "pallas_call" in str(jaxpr) and "scatter" not in str(jaxpr)
    monkeypatch.setenv("PADDLE_TPU_KERNELS", "0")
    jaxpr = jax.make_jaxpr(lambda *a: kvw.kv_cache_write(*a))(cache, upd, pos)
    assert _count("pallas", rows) == before + 1
    assert "pallas_call" not in str(jaxpr)


def test_the_op_lowering_goes_through_the_dispatch(fresh_programs):
    """``layers.kv_cache_write`` on a CPU run: the composed form, counted,
    for the serving step's per-slot write."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.layer_helper import LayerHelper

    main, startup, scope = fresh_programs
    with fluid.program_guard(main, startup):
        helper = LayerHelper("t")
        cache = helper.create_global_variable(name="t_cache",
                                              shape=(2, 2, 128, 64))
        upd = layers.data("upd", [2, 2, 1, 64], dtype="float32",
                          append_batch_size=False)
        pos = layers.data("pos", [2, 1], dtype="int64",
                          append_batch_size=False)
        out = layers.kv_cache_write(cache, upd, pos)
    exe = fluid.Executor(fluid.TPUPlace())
    scope.set_var("t_cache", np.zeros((2, 2, 128, 64), "float32"))
    before = _count("composed", 1)
    got, = exe.run(main, feed={"upd": np.ones((2, 2, 1, 64), "float32"),
                               "pos": np.array([[3], [100]], "int64")},
                   fetch_list=[out], scope=scope)
    assert _count("composed", 1) == before + 1
    want = np.zeros((2, 2, 128, 64), "float32")
    want[0, :, 3], want[1, :, 100] = 1.0, 1.0
    np.testing.assert_array_equal(got, want)
