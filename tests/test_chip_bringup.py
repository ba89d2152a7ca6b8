"""Chip bring-up guards that need no chip (ISSUE 21).

(a) The main path's kernels compile for a DESCRIBED TPU v5e at BERT-base
    S512 shapes (and, PR 27, the serving cells' cache write with the whole
    ``gpt2-medium`` decode step round it): the TPU compiler is installed here and compiles for a chip
    that is not attached (on-chip-measurement guide, section 2.3), so a
    fast-memory overrun or a slice off the tiling fails here, not on the
    chip. Nothing runs: a compile that passes is not a chip run.
(b) ``use_interpret`` decides from ``platform == "tpu"`` alone and raises
    when the backend cannot be asked.
(c) ``flags.enable_compile_cache`` is placed from outside.
(d) ``chip_smoke.py`` fails on the CPU and never claims a TPU it did not see.
(e) ``TPUPlace`` refuses a CPU nobody asked for.
"""

import json
import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


# ------------------------------------------------ (a) compiles for the v5e
@pytest.fixture(scope="module")
def v5e_topology():
    """A described v5e 2x2; the persistent compile cache is off around
    these compiles (an entry written for a described chip cannot be read
    back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # another process describing the chip must not lock this one out
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no TPU compiler here: skip
        pytest.skip("get_topology_desc cannot describe a v5e here: %s" % exc)
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def v5e(v5e_topology):
    """Sharding on one chip of the described v5e 2x2."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_topology.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FLASH_INTERPRET", "0")


def _compile(fn, dev, *args):
    """Compile ``fn`` for the described chip; ``args`` is a pytree of
    (shape, dtype) leaves. Returns the count of Mosaic kernels in it."""
    is_leaf = lambda x: isinstance(x, tuple) and len(x) == 2 \
        and isinstance(x[0], tuple)  # noqa: E731
    sds = jax.tree_util.tree_map(
        lambda sd: jax.ShapeDtypeStruct(sd[0], sd[1], sharding=dev),
        args, is_leaf=is_leaf)
    text = jax.jit(fn).lower(*sds).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


BF16, F32 = jnp.bfloat16, jnp.float32

FLASH_CASES = {
    # name: (B, H, S, D), causal, bias shape or None, bias_grad, dtype
    "bert_s512_maskbias": ((16, 12, 512, 64), False, (16, 1, 1, 512), False,
                           BF16),
    "causal_s1024": ((8, 12, 1024, 64), True, None, False, BF16),
    "ragged_s500_maskbias": ((2, 12, 500, 64), False, (2, 1, 1, 500), False,
                             BF16),
    "causal_d128": ((2, 8, 512, 128), True, None, False, BF16),
    "trainable_bias": ((2, 12, 512, 64), False, (1, 12, 512, 512), True,
                       BF16),
    "full_bias": ((2, 12, 512, 64), False, (2, 12, 512, 512), False, BF16),
    # chip_smoke.py's kernels phase also runs float32 inputs
    "f32_s256": ((2, 4, 256, 64), False, None, False, F32),
    "f32_causal_s256": ((2, 4, 256, 64), True, None, False, F32),
    # the S512 benchmark cells' exact call, and the longest causal call:
    # the block plan's caps against the described chip's VMEM
    "bert_cell_b32_s512": ((32, 12, 512, 64), False, (32, 1, 1, 512), False,
                           BF16),
    "causal_s2048": ((2, 12, 2048, 64), True, None, False, BF16),
}


@pytest.mark.parametrize("case", sorted(FLASH_CASES))
def test_flash_attention_compiles_for_v5e(case, v5e, compiled_kernels):
    from paddle_tpu.ops.attention import flash_attention

    shape, causal, bias_shape, bias_grad, dtype = FLASH_CASES[case]
    scale = shape[-1] ** -0.5

    def loss(q, k, v, bias=None):
        out = flash_attention(q, k, v, bias, scale, bias_grad=bias_grad,
                              causal=causal)
        return jnp.sum(out.astype(F32) ** 2)

    args = [(shape, dtype)] * 3
    argnums = (0, 1, 2)
    if bias_shape is not None:
        args.append((bias_shape, F32))
        if bias_grad:
            argnums += (3,)
    n = _compile(jax.value_and_grad(loss, argnums=argnums), v5e, *args)
    # forward, dK/dV and dQ (a layer of the step program holds four: its
    # grad op runs the forward again)
    assert n == 3, "%s: %d Mosaic kernels" % (case, n)


LANES_CASES = {
    # name: (B, H, S, D), causal, bias shape or None, dtype — operands
    # [B, S, H*D], the head a block index along the lanes (PR 38)
    "bert_cell_b32_s512": ((32, 12, 512, 64), False, (32, 1, 1, 512), BF16),
    "sixteen_heads": ((4, 16, 512, 64), False, (4, 1, 1, 512), BF16),
    "causal_d128": ((2, 8, 512, 128), True, None, BF16),
    "ragged_s384_maskbias": ((2, 12, 384, 64), False, (2, 1, 1, 384), BF16),
    # multi-pass: two heads a step, each with a carry of its own
    "causal_s2048": ((2, 12, 2048, 64), True, None, BF16),
    # a full [Sq, Sk] bias: one lane tile of heads a step
    "full_bias": ((2, 12, 512, 64), False, (2, 12, 512, 512), BF16),
    "f32_s256": ((2, 4, 256, 64), False, None, F32),
}


@pytest.mark.parametrize("case", sorted(LANES_CASES))
def test_flash_attention_over_lanes_compiles_for_v5e(case, v5e,
                                                     compiled_kernels):
    """The three kernels over [B, S, H*D] operands: Mosaic takes the
    lane-tile blocks, the in-kernel lane selects and the per-head carry
    at real sizes, and no transpose stands round the calls."""
    from paddle_tpu.ops.attention import flash_attention

    shape, causal, bias_shape, dtype = LANES_CASES[case]
    B, H, S, D = shape

    def loss(q, k, v, bias=None):
        out = flash_attention(q, k, v, bias, D ** -0.5, causal=causal,
                              n_head=H)
        return jnp.sum(out.astype(F32) ** 2)

    args = [((B, S, H * D), dtype)] * 3
    if bias_shape is not None:
        args.append((bias_shape, F32))
    sds = [jax.ShapeDtypeStruct(sd[0], sd[1], sharding=v5e) for sd in args]
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        *sds).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert " transpose(" not in text


@pytest.mark.parametrize("tokens", [32, 64, 512])
def test_expert_layer_compiles_for_v5e(tokens, v5e, compiled_kernels):
    """OLMoE's expert layer at published widths — the decode step's 32
    rows, the shortest and the longest prefill of the benchmark's mix —
    holds both grouped-matmul kernels, under the names the device trace
    shows them by."""
    from paddle_tpu.kernels import moe_gmm
    from paddle_tpu.ops.moe_ops import _experts

    D, F, E, k = 2048, 1024, 64, 8

    def layer(x, router, gate, up, down):
        out, _aux, sizes = _experts(x, gate, up, None, down, None, router,
                                    E, k, None, "swiglu", False, 0.0)
        return out, sizes

    sds = [jax.ShapeDtypeStruct(shape, F32, sharding=v5e) for shape in (
        (tokens, D), (D, E), (E, D, F), (E, D, F), (E, F, D))]
    text = jax.jit(layer).lower(*sds).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert moe_gmm.KERNEL_UP in text and moe_gmm.KERNEL_DOWN in text


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("block", [8, 64, 256])
def test_layernorm_residual_compiles_for_v5e(dtype, block, v5e,
                                             compiled_kernels):
    from paddle_tpu.kernels import layernorm

    n, d = 8192, 768  # B16 x S512 rows at BERT-base width
    assert (block,) in layernorm._candidates(
        layernorm.signature_for(n, d, dtype))

    def loss(x, r, scale, bias):
        y, s, _mean, _var = layernorm.layernorm_residual(
            (block,), x, r, scale, bias)
        return jnp.sum(y.astype(F32)) + jnp.sum(s.astype(F32))

    count = _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2, 3)), v5e,
        ((n, d), dtype), ((n, d), dtype), ((d,), dtype), ((d,), dtype))
    assert count == 2  # forward and backward


@pytest.mark.parametrize("kind", ["adam", "sgd"])
def test_optimizer_sweep_compiles_for_v5e(kind, v5e, compiled_kernels):
    """One candidate on a small group: the full-size sweep (13.75M
    elements) takes 8-12 s per candidate to compile, and the kernel is
    oblivious to the total length."""
    from paddle_tpu.kernels import optimizer_update as ou

    sizes = [768 * 768, 768 * 3072, 3072, 768]  # one encoder layer's kinds
    sig = ou.signature_for(sum(sizes), "float32", len(sizes))
    cfg = ou._candidates(sig)[-1]
    vec = [((s,), F32) for s in sizes]
    one = [((1,), F32) for _ in sizes]
    ins = {"Param": vec, "Grad": vec, "LearningRate": one}
    if kind == "adam":
        ins.update(Moment1=vec, Moment2=vec, Beta1Pow=one, Beta2Pow=one)
        fn = lambda ins: ou.adam_group_pallas(cfg, ins)  # noqa: E731
    else:
        fn = lambda ins: ou.sgd_group_pallas(cfg, ins)  # noqa: E731
    assert _compile(fn, v5e, ins) == 1


def _cache_sized(text, shape):
    """HLO lines that produce a cache-sized array by a copy, a transpose
    or a fusion (either orientation of the two minor axes)."""
    import re

    b, h, s, d = shape
    made = re.compile(r"= \S+\[%d,%d,(%d,%d|%d,%d)\]\S* "
                      r"(copy|transpose|fusion)\(" % (b, h, s, d, d, s))
    return [line.strip()[:160] for line in text.splitlines()
            if made.search(line)]


@pytest.mark.parametrize("d_head", [64, 128])
def test_kv_cache_write_compiles_in_place_for_v5e(d_head, v5e,
                                                  compiled_kernels):
    """The serving cells' cache tensors (``gpt2-medium``: 16 heads of 64,
    stored S-minor by the TPU; OLMoE: 16 of 128, row-major), float32, 32
    slots of 1,024 positions: one Mosaic kernel, the donated cache
    aliased to the output, and no relayout of the slab round the call —
    the kernel's block orientation matches the layout the TPU chose."""
    from paddle_tpu.kernels import kv_cache_write as kvw

    shape = (32, 16, 1024, d_head)
    assert kvw.write_plan(shape, F32)[0] == {64: "cols", 128: "rows"}[d_head]
    sds = [jax.ShapeDtypeStruct(sh, dt, sharding=v5e) for sh, dt in (
        (shape, F32), ((32, 16, 1, d_head), F32), ((32, 1), jnp.int32))]
    text = jax.jit(
        lambda c, u, p: kvw.kv_cache_write_pallas(None, c, u, p,
                                                  interpret=False),
        donate_argnums=0).lower(*sds).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert kvw.KERNEL in text
    assert "input_output_alias={ {}: (0, {}, may-alias) }" in text
    assert _cache_sized(text, shape) == []


def _lower_step(main, feeds, fetch, dev, rng=False):
    """Lower one program's step for the described chip from shapes alone
    (nothing runs). ``feeds`` maps a name to its shape (int32) or to
    (shape, dtype); ``rng`` hands the step a key, which a training program
    that draws needs. Returns (lowered, names of the donated state)."""
    from paddle_tpu.core.executor import analyze_block

    class _Initialised:                 # nothing is run: shapes only
        def has_var(self, name):
            return True

    (feed_names, _fetch, const_state, mut_state, _written, _rng,
     step) = analyze_block(main, sorted(feeds), [fetch], _Initialised())
    block = main.global_block()

    def sds(name):
        var = block.vars[name]
        return jax.ShapeDtypeStruct(tuple(var.shape), jnp.dtype(var.dtype),
                                    sharding=dev)

    def feed_sds(name):
        shape, dtype = feeds[name] if isinstance(feeds[name][0], tuple) \
            else (feeds[name], jnp.int32)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    def fn(feed_vals, const_vals, mut_vals, key=None):
        fetches, new_mut, _, new_key = step(feed_vals, const_vals, mut_vals,
                                            key)
        return fetches, new_mut, new_key

    key = (jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=dev),) \
        if rng else ()
    lowered = jax.jit(fn, donate_argnums=(2,)).lower(
        [feed_sds(n) for n in feed_names],
        [sds(n) for n in const_state], [sds(n) for n in mut_state], *key)
    return lowered, mut_state


def test_gpt2_medium_serving_decode_step_writes_its_cache_in_place(
        v5e, compiled_kernels):
    """The whole ``gpt2-medium`` serving decode step (32 slots, 1,024
    positions, float32) compiled for the described chip: 48 Pallas calls
    (K and V of 24 layers), no ``scatter`` left of the vmapped update,
    every cache donated into its output, none copied or relaid — and the
    program's counter says 48 ``pallas``, 0 ``composed``."""
    import re

    import paddle_tpu as fluid
    from paddle_tpu.kernels import kv_cache_write as kvw
    from paddle_tpu.models import gpt
    from paddle_tpu.observe.families import KV_CACHE_WRITE_PLANS

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "gpt2-medium.json")) as f:
        conf = json.load(f)
    cfg = dict(gpt.base_config(), **conf["model"])
    batch, max_len = conf["serving"]["b_max"], conf["serving"]["max_len"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        logits, caches = gpt.build_serving_decode_step(cfg, batch=batch,
                                                       max_len=max_len)
    plans = {form: KV_CACHE_WRITE_PLANS.labels(form=form, rows="1")
             for form in ("pallas", "composed")}
    before = {form: c.value for form, c in plans.items()}
    lowered, mut_state = _lower_step(
        main, {"token": (batch, 1), "pos": (batch, 1)}, logits.name, v5e)
    assert sorted(mut_state) == sorted(caches)
    assert "scatter" not in lowered.as_text()
    text = lowered.compile().as_text()
    n_cache = 2 * cfg["n_layer"]
    assert {f: c.value - before[f] for f, c in plans.items()} == {
        "pallas": n_cache, "composed": 0}
    assert text.count('custom_call_target="tpu_custom_call"') == n_cache
    assert len(re.findall(r"%s[.\d]* = " % kvw.KERNEL, text)) == n_cache
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    assert len(re.findall(r"may-alias|must-alias",
                          aliases.group(1))) == n_cache
    assert _cache_sized(text, (batch, cfg["n_head"], max_len,
                               cfg["d_model"] // cfg["n_head"])) == []


TRINITY_PROMPTS = [512, 2048, 6144, 8192]


@pytest.mark.parametrize("P", TRINITY_PROMPTS)
def test_windowed_flash_forward_compiles_for_v5e(P, v5e, compiled_kernels):
    """The serving prefill's attention calls of ``trinity_serve_mixed`` at
    published widths — 48 query heads over 8 key/value heads of 128,
    float32, every prompt length of the mix: the band of 4,096 (one
    kernel under the name ``flash_fwd_win`` where the prompt is longer
    than the window) and the full causal call, grouped heads in both."""
    from paddle_tpu.ops import attention as A

    q = ((1, 48, P, 128), F32)
    kv = ((1, 8, P, 128), F32)
    for window in (4096, None):
        fn = lambda q, k, v, w=window: A.flash_attention(  # noqa: E731
            q, k, v, None, 128 ** -0.5, causal=True, window=w)
        sds = [jax.ShapeDtypeStruct(sh, dt, sharding=v5e)
               for sh, dt in (q, kv, kv)]
        text = jax.jit(fn).lower(*sds).compile().as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        banded = window is not None and window < P
        assert (A.KERNEL_FWD_WIN in text) == banded
        # grouped heads ride the block index: K and V are never repeated
        assert "broadcast" not in text and "concatenate" not in text


def _trinity():
    from paddle_tpu.models import gpt

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "trinity-large-preview.json")) as f:
        conf = json.load(f)
    return gpt, conf["model"], conf["serving"]


def test_trinity_serving_decode_step_compiles_for_v5e(v5e, compiled_kernels):
    """The whole ``trinity-large-preview`` serving decode step (16 slots,
    rings of 4,096 beside slabs of 16,384, float32, 8 of 256 experts)
    for the described chip: ten in-place Pallas cache writes (K and V of
    four rings and one slab, the ring rows at ``pos mod 4096``), both
    grouped matmuls of the four expert layers, the two tallies donated
    beside the caches, and 10.7 GB of arguments."""
    import paddle_tpu as fluid
    from paddle_tpu.kernels import kv_cache_write as kvw
    from paddle_tpu.kernels import moe_gmm
    from paddle_tpu.observe.families import KV_CACHE_WRITE_PLANS

    gpt, cfg, serving = _trinity()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        _logits, caches = gpt.build_serving_decode_step(
            cfg, batch=serving["b_max"], max_len=serving["max_len"])
    shapes = {n: tuple(main.global_block().vars[n].shape) for n in caches}
    assert [shapes[n][2] for n in caches] == [4096] * 8 + [16384] * 2
    plans = {form: KV_CACHE_WRITE_PLANS.labels(form=form, rows="1")
             for form in ("pallas", "composed")}
    before = {form: c.value for form, c in plans.items()}
    lowered, mut = _lower_step(
        main, {"token": (16, 1), "pos": (16, 1)}, gpt.NEXT_TOKEN_VAR, v5e)
    assert sorted(mut) == sorted(caches + [gpt.ROUTED_PAIRS_VAR,
                                           gpt.EXPERTS_TOUCHED_VAR])
    compiled = lowered.compile()
    text = compiled.as_text()
    assert {f: c.value - before[f] for f, c in plans.items()} == {
        "pallas": 10, "composed": 0}
    assert text.count('custom_call_target="tpu_custom_call"') == 10 + 8
    assert text.count(kvw.KERNEL) >= 10
    assert moe_gmm.KERNEL_UP in text and moe_gmm.KERNEL_DOWN in text
    mem = compiled.memory_analysis()
    # 6.42 GB of weights and 4.29 GB of caches: 10.7 GB in all
    assert 10.6e9 < mem.argument_size_in_bytes < 10.8e9
    assert mem.temp_size_in_bytes < 1.0e9
    print("trinity decode step:", mem)


@pytest.mark.parametrize("P", [512, 8192])
def test_trinity_prefill_compiles_for_v5e(P, v5e, compiled_kernels):
    """The batch=1 prefill of the shortest and the longest prompt of the
    mix for the described chip: five flash forwards (four banded at
    8,192, none at 512), no [P, P] score tensor, and temporaries that fit
    beside the 10.7 GB the engine holds."""
    import paddle_tpu as fluid
    from paddle_tpu.ops import attention as A

    gpt, cfg, serving = _trinity()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.build_prefill_step(cfg, batch=1, prompt_len=P,
                               max_len=serving["max_len"])
    lowered, _ = _lower_step(main, {"tokens": (1, P)}, gpt.NEXT_TOKEN_VAR,
                             v5e)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 5
    assert (A.KERNEL_FWD_WIN in text) == (P > 4096)
    assert "f32[1,48,%d,%d]" % (P, P) not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 3.0e9, mem
    print("trinity prefill P=%d:" % P, mem)


def _pangu():
    from paddle_tpu.models import gpt

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "openpangu-ultra-moe-718b.json")) as f:
        conf = json.load(f)
    return gpt, conf["model"], conf["serving"]


def test_pangu_serving_decode_step_compiles_for_v5e(v5e, compiled_kernels):
    """The whole ``openpangu-ultra-moe-718b`` serving decode step (64
    slots of 4,096 latent rows, bf16 matrices, 8 of 256 experts) for the
    described chip: five in-place Pallas writes of one 576-value row a
    slot, five ``mla_decode`` calls over the S-minor view of the slab (no
    copy or relayout of a cache), both grouped matmuls of the four expert
    layers on bf16 right-hand sides, no float32 copy of a matrix, and
    9.84 GB of arguments."""
    import re

    import paddle_tpu as fluid
    from paddle_tpu.kernels import kv_cache_write as kvw
    from paddle_tpu.kernels import mla_decode, moe_gmm
    from paddle_tpu.observe.families import (KV_CACHE_WRITE_PLANS,
                                             MLA_ATTENTION_PLANS)

    gpt, cfg, serving = _pangu()
    B, S = serving["b_max"], serving["max_len"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        _logits, caches = gpt.build_serving_decode_step(cfg, batch=B,
                                                        max_len=S)
    assert caches == ["gpt_%d_cache_c" % i for i in range(5)]
    assert all(tuple(main.global_block().vars[n].shape) == (B, 1, S, 576)
               for n in caches)
    write = KV_CACHE_WRITE_PLANS.labels(form="pallas", rows="1")
    absorbed = MLA_ATTENTION_PLANS.labels(form="absorbed", kernel="pallas",
                                          block="512", widths="576x512")
    before = write.value, absorbed.value
    lowered, mut = _lower_step(
        main, {"token": (B, 1), "pos": (B, 1)}, gpt.NEXT_TOKEN_VAR, v5e)
    assert sorted(mut) == sorted(caches + [gpt.ROUTED_PAIRS_VAR,
                                           gpt.EXPERTS_TOUCHED_VAR])
    compiled = lowered.compile()
    text = compiled.as_text()
    assert (write.value - before[0], absorbed.value - before[1]) == (5, 5)
    assert text.count('custom_call_target="tpu_custom_call"') == 5 + 5 + 8
    assert len(re.findall(r"%s[.\d]* = " % mla_decode.KERNEL, text)) == 5
    assert len(re.findall(r"%s[.\d]* = " % kvw.KERNEL, text)) == 5
    assert moe_gmm.KERNEL_UP in text and moe_gmm.KERNEL_DOWN in text
    # neither the slab nor its S-minor view is copied or relaid
    assert _cache_sized(text, (B, 1, S, 576)) == []
    assert _cache_sized(text, (B, 1, 576, S)) == []
    relaid = re.compile(r"= \S+\[%d,(576,%d|%d,576)\]\S* "
                        r"(copy|transpose|fusion)\(" % (B, S, S))
    assert not relaid.search(text)
    # no float32 copy of a stored matrix (the largest: an expert stack)
    assert not re.search(r"f32\[8,7680,2048\][^ ]* (copy|convert)\(", text)
    assert not re.search(r"f32\[7680,18432\][^ ]* (copy|convert)\(", text)
    mem = compiled.memory_analysis()
    # 6.82 GB of bf16 matrices and 3.02 GB of latent cache
    assert 9.8e9 < mem.argument_size_in_bytes < 9.9e9, mem
    assert mem.temp_size_in_bytes < 1.0e9, mem
    print("pangu decode step:", mem)


@pytest.mark.parametrize("P", [128, 512, 1024, 3328])
def test_pangu_prefill_compiles_for_v5e(P, v5e, compiled_kernels):
    """The batch=1 prefill of every prompt length of the mix: five flash
    forwards at q/k 192 and v 128 wide (the kernel at 128 too; single
    pass up to 1,024, where four heads a step overran the scoped VMEM on
    the chip: two a step at 256 lanes of width), no [P, P] score tensor,
    and temporaries that fit beside the 9.84 GB the engine holds."""
    import paddle_tpu as fluid
    from paddle_tpu.observe.families import (FLASH_BLOCK_PLANS,
                                             MLA_ATTENTION_PLANS)

    gpt, cfg, serving = _pangu()
    form = MLA_ATTENTION_PLANS.labels(form="expanded",
                                      kernel="fused_attention", block="-",
                                      widths="192x128")
    built = form.value
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.build_prefill_step(cfg, batch=1, prompt_len=P,
                               max_len=serving["max_len"])
    assert form.value == built + 5
    block = {128: "128x128", 512: "512x512", 1024: "256x1024",
             3328: "512x512"}[P]       # 3,328 pads to 7 blocks of 512
    plan = FLASH_BLOCK_PLANS.labels(kernel="flash_fwd", block=block,
                                    single_pass="0" if P == 3328 else "1",
                                    layout="heads")
    before = plan.value
    lowered, _ = _lower_step(main, {"tokens": (1, P)}, gpt.NEXT_TOKEN_VAR,
                             v5e)
    assert plan.value == before + 5
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 5
    assert text.count("flash_fwd") >= 5
    if P > 128:       # [1, 128, 128, 128] is also a head tensor's shape
        assert "f32[1,128,%d,%d]" % (P, P) not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4.5e9, mem
    print("pangu prefill P=%d:" % P, mem)


# ------------------------------------------------------ (b) use_interpret
class _Dev:
    def __init__(self, platform, device_kind="fake"):
        self.platform = platform
        self.device_kind = device_kind


@pytest.mark.parametrize("platform,kind,want", [
    ("tpu", "TPU v5 lite", False),
    ("cpu", "cpu", True),
    # the platform decides, never a device_kind that merely says "TPU"
    ("other", "TPU v5 lite", True),
])
def test_use_interpret_decides_from_platform(monkeypatch, platform, kind,
                                             want):
    from paddle_tpu.kernels.common import use_interpret

    monkeypatch.delenv("PADDLE_TPU_FLASH_INTERPRET", raising=False)
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev(platform, kind)])
    assert use_interpret() is want


def _no_backend(*_a):
    raise RuntimeError("backend init failed")


def test_use_interpret_raises_when_backend_cannot_be_asked(monkeypatch):
    from paddle_tpu.kernels.common import use_interpret

    monkeypatch.delenv("PADDLE_TPU_FLASH_INTERPRET", raising=False)
    monkeypatch.setattr(jax, "devices", _no_backend)
    with pytest.raises(RuntimeError, match="backend init failed"):
        use_interpret()


@pytest.mark.parametrize("knob,want", [("1", True), ("0", False)])
def test_use_interpret_debug_knob_needs_no_backend(monkeypatch, knob, want):
    from paddle_tpu.kernels.common import use_interpret

    monkeypatch.setenv("PADDLE_TPU_FLASH_INTERPRET", knob)
    monkeypatch.setattr(jax, "devices", _no_backend)
    assert use_interpret() is want


# ------------------------------------------------------ (c) compile cache
# flags.py is loaded by path: importing the whole package would cost each
# probe process several seconds and change nothing about the answer
_CACHE_PROBE = textwrap.dedent("""
    import importlib.util, json
    import jax
    set_in_code = []
    real_update = jax.config.update
    def spy(name, value):
        set_in_code.append(name)
        return real_update(name, value)
    jax.config.update = spy
    spec = importlib.util.spec_from_file_location("flags", %r)
    flags = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(flags)
    returned = flags.enable_compile_cache()
    print(json.dumps({"returned": returned, "set_in_code": set_in_code,
                      "dir": jax.config.jax_compilation_cache_dir}))
""") % os.path.join(ROOT, "paddle_tpu", "flags.py")


def _probe_cache(cwd, cache_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("PYTHONPATH", None)
    if cache_env:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], cwd=cwd,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_dir_from_environment_is_not_set_in_code(tmp_path):
    got = _probe_cache(str(tmp_path), str(tmp_path / "outside"))
    assert got["dir"] == got["returned"] == str(tmp_path / "outside")
    assert "jax_compilation_cache_dir" not in got["set_in_code"]
    assert "jax_persistent_cache_min_compile_time_secs" in got["set_in_code"]


def test_compile_cache_default_is_the_checkout_from_any_cwd(tmp_path):
    (tmp_path / "elsewhere").mkdir()
    a = _probe_cache(str(tmp_path), None)
    b = _probe_cache(str(tmp_path / "elsewhere"), None)
    assert a["dir"] == b["dir"] == os.path.join(ROOT, ".jax_cache")


# ------------------------------------------------------- (d) chip_smoke.py
def _chip_smoke(*args, cwd=ROOT, script=None, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, script or os.path.join(ROOT, "chip_smoke.py"),
         *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("args", [(), ("--chips", "4")])
def test_chip_smoke_fails_on_the_cpu_without_a_result(args):
    out = _chip_smoke(*args)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_chip_smoke_alone_in_a_directory_fails_without_a_result(tmp_path):
    import shutil

    script = shutil.copy(os.path.join(ROOT, "chip_smoke.py"), str(tmp_path))
    out = _chip_smoke(cwd=str(tmp_path), script=script, PYTHONPATH="")
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_chip_smoke_rehearsal_passes_and_never_says_ok(tmp_path):
    """The tiny CPU rehearsal drives every default phase (kernels, train,
    serve) and still never prints the contract's result line."""
    out = _chip_smoke("--cpu-rehearsal",
                      JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.strip()]
    assert [l.get("phase") for l in lines[:-1]] == [
        "device", "kernels", "train", "serve", "cache"]
    assert all(l.get("ok", True) for l in lines)
    assert '"ok": true' not in out.stdout.splitlines()[-1]
    assert lines[-1] == {"rehearsal": "passed",
                         "device": {"platform": "cpu", "kind": "cpu",
                                    "count": 1}}
    assert lines[0]["compile_cache_dir"] == str(tmp_path / "cache")
    train = lines[2]
    assert np.all(np.isfinite(train["losses"]))
    assert train["kernel_tier"]["attention"]["choice"] == "flash"


def test_chip_smoke_forced_failure_exits_nonzero(tmp_path):
    """A phase that fails (here: the flash kernel cannot be built) ends
    the run non-zero, with no result line."""
    out = _chip_smoke("--cpu-rehearsal", PADDLE_TPU_FLASH_BQ="7")
    assert out.returncode != 0
    assert '"rehearsal": "passed"' not in out.stdout
    assert '"ok": true' not in out.stdout
    assert "chip_smoke: FAILED" in out.stderr


# ------------------------------------------------------------ (e) TPUPlace
def test_tpuplace_refuses_a_cpu_nobody_asked_for(monkeypatch):
    import paddle_tpu as fluid

    place = fluid.TPUPlace()
    assert place.jax_device().platform == "cpu"  # the tests ask for it
    monkeypatch.setattr("paddle_tpu.core.place._cpu_requested",
                        lambda: False)
    with pytest.raises(RuntimeError, match="no accelerator"):
        place.jax_device()
    with pytest.raises(RuntimeError, match="no accelerator"):
        fluid.Executor(place).run(fluid.Program())
    # CPUPlace stays what it says
    assert fluid.CPUPlace().jax_device().platform == "cpu"


@pytest.mark.parametrize("platforms,want", [
    ("cpu", True), ("cpu,tpu", True), ("tpu", False), ("tpu,cpu", False),
    ("", False), (None, False)])
def test_cpu_requested_reads_jax_platforms(monkeypatch, platforms, want):
    from paddle_tpu.core import place

    class _Cfg:
        jax_platforms = platforms

    monkeypatch.setattr(jax, "config", _Cfg)
    assert place._cpu_requested() is want


# ------------------------------- (a) the train step's dropout masks, PR 35
BERT_CELLS = {
    # cell: (seq, batch, masks, Pallas calls the traffic file expects)
    "bert_train_s512": (512, 32, 80, 48),
    "bert_train_s128": (128, 128, 20, 0),
}


def _mask_sized(text, op, least=1 << 20):
    """Instructions ``op`` of the module whose result is a u32 array of at
    least ``least`` elements (a dropout mask's bits; nothing else in the
    step is u32 and that large)."""
    import re

    found = []
    for m in re.finditer(r"= \(?u32\[([\d,]+)\]\S*(?:, [^)]*\))? %s\(" % op,
                         text):
        dims = [int(d) for d in m.group(1).split(",")]
        if int(np.prod(dims)) >= least:
            found.append(tuple(dims))
    return found


def _flash_plans_by_layout():
    """{layout: flash kernel plans lowered so far}."""
    from paddle_tpu.observe import REGISTRY

    seen = {}
    for s in REGISTRY.snapshot()["metrics"][
            "paddle_flash_block_plans_total"]["samples"]:
        lay = s["labels"]["layout"]
        seen[lay] = seen.get(lay, 0) + s["value"]
    return seen


@pytest.mark.parametrize("cell", sorted(BERT_CELLS))
def test_bert_train_step_draws_each_mask_once_for_v5e(cell, v5e,
                                                      compiled_kernels,
                                                      monkeypatch):
    """The ``bert-base`` train step as ``benchmarks/lib/train_loop.py``
    builds it (``bert.build`` + ``Adam.minimize`` + bf16 AMP), compiled for
    the described chip: one ``rng-bit-generator`` a dropout (37), no
    threefry over a mask (its rounds are ``shift-right-logical`` on the
    mask's u32 bits, which XLA cloned into every consumer: PERF.md
    section 6, PR 35), the cell's count of Pallas calls, and the plan
    counter reads 37."""
    import paddle_tpu as fluid
    from paddle_tpu.models import bert
    from paddle_tpu.observe.families import DROPOUT_MASK_PLANS

    seq, batch, masks, n_calls = BERT_CELLS[cell]
    # the cells run under the static threshold (composed attention below
    # S 256), not under the suite's "always the kernel"
    monkeypatch.delenv("PADDLE_TPU_FLASH_MIN_SEQ", raising=False)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "bert-base.json")) as f:
        conf = json.load(f)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss, _feeds = bert.build(dict(conf["model"]), seq_len=seq,
                                  max_mask=masks)
        fluid.optimizer.Adam(
            learning_rate=conf["train"]["learning_rate"]).minimize(loss)
    main.set_amp(conf["train"]["amp"] == "bf16")
    plans = {site: DROPOUT_MASK_PLANS.labels(site=site, bits="rbg_u32")
             for site in ("dropout", "fused_attention")}
    before = {site: c.value for site, c in plans.items()}
    flash_before = _flash_plans_by_layout()
    feeds = {"src_ids": (batch, seq), "sent_ids": (batch, seq),
             "input_mask": ((batch, seq), jnp.float32),
             "mask_pos": (batch, masks), "mask_label": (batch, masks),
             "mask_weight": ((batch, masks), jnp.float32)}
    lowered, _ = _lower_step(main, feeds, loss.name, v5e, rng=True)
    n_layer = conf["model"]["n_layer"]
    assert {s: c.value - before[s] for s, c in plans.items()} == {
        "dropout": 2 * n_layer + 1, "fused_attention": n_layer}
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == n_calls
    # PR 38: every kernel of the step takes the projections as they are
    flash_now = _flash_plans_by_layout()
    assert {lay: flash_now.get(lay, 0) - flash_before.get(lay, 0)
            for lay in ("lanes", "heads")} == {"lanes": n_calls, "heads": 0}
    drawn = _mask_sized(text, "rng-bit-generator")
    assert len(drawn) == 3 * n_layer + 1 == 37
    assert {int(np.prod(d)) for d in drawn} == {batch * seq * 768}
    assert text.count(" rng-bit-generator(") == len(drawn)
    assert _mask_sized(text, "shift-right-logical") == []


def test_dropout_mask_is_drawn_per_shard_on_the_v5e_mesh(v5e_topology):
    """The dropout op over the four chips of the described 2x2, operand
    ``[128 * 512, 768]`` bf16 sharded on the data axis as ParallelEngine
    jits a step: SPMD cannot partition ``rng-bit-generator`` (a draw of
    the global shape comes out whole on every chip, then sliced), so the
    lowering draws inside a ``shard_map``. Every generator call holds one
    chip's 16,384 rows and no mask-sized u32 is sliced."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.core.lowering import LowerContext
    from paddle_tpu.core.registry import get_op

    mesh = Mesh(np.array(v5e_topology.devices).reshape(4, 1),
                ("data", "model"))
    rows, width = 128 * 512, 768

    def fwd(x, key):
        ctx = LowerContext(rng=key, mesh=mesh)
        outs = get_op("dropout").lowering(
            ctx, {"X": [x]}, {"dropout_prob": 0.1,
                              "dropout_implementation": "upscale_in_train"})
        return outs["Out"][0], outs["Mask"][0]

    data, repl = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    with mesh:
        text = jax.jit(fwd, out_shardings=(data, data)).lower(
            jax.ShapeDtypeStruct((rows, width), BF16, sharding=data),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl)
        ).compile().as_text()
    assert _mask_sized(text, "rng-bit-generator") == [(rows // 4, width)]
    assert text.count(" rng-bit-generator(") == 1
    assert _mask_sized(text, "dynamic-slice") == []
    assert _mask_sized(text, "shift-right-logical") == []


def test_packed_flash_is_wrapped_with_rank3_specs_on_the_v5e_mesh(
        v5e_topology, compiled_kernels):
    """``fused_attention`` and its grad op over [B, S, H*D] operands on
    the four chips of the described 2x2, batch 128 sharded on the data
    axis as ``bert_train_s512_dp4`` has it: the wrap hands each chip its
    32 rows in the lanes layout, so a shard holds the four kernels and no
    transpose."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.core.lowering import LowerContext
    from paddle_tpu.core.registry import get_op

    mesh = Mesh(np.array(v5e_topology.devices).reshape(4, 1),
                ("data", "model"))
    B, S, H, D = 128, 512, 12, 64
    op = get_op("fused_attention")
    attrs = {"scale": D ** -0.5, "n_head": H, "dropout": 0.0}

    def step(q, k, v, bias, g):
        ctx = LowerContext(mesh=mesh)
        ins = {"Q": [q], "K": [k], "V": [v], "Bias": [bias]}
        out = op.lowering(ctx, ins, attrs)["Out"][0]
        grads = op.grad_lowering(ctx, dict(ins, **{"Out@GRAD": [g]}), attrs)
        return (out,) + tuple(grads[s][0]
                              for s in ("Q@GRAD", "K@GRAD", "V@GRAD"))

    data = NamedSharding(mesh, P("data"))
    act = jax.ShapeDtypeStruct((B, S, H * D), BF16, sharding=data)
    bias = jax.ShapeDtypeStruct((B, 1, 1, S), F32, sharding=data)
    before = _flash_plans_by_layout()
    with mesh:
        text = jax.jit(step, out_shardings=(data,) * 4).lower(
            act, act, act, bias, act).compile().as_text()
    now = _flash_plans_by_layout()
    assert {lay: now.get(lay, 0) - before.get(lay, 0)
            for lay in ("lanes", "heads")} == {"lanes": 4, "heads": 0}
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert " transpose(" not in text
    assert "bf16[%d,%d,%d]" % (B // 4, S, H * D) in text


def _xing():
    from paddle_tpu.models import gpt

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        conf = json.load(f)
    return gpt, conf["model"], conf["serving"]


def _mhc_plans():
    from paddle_tpu.observe.families import RESIDUAL_PLANS

    return {(op, kernel): RESIDUAL_PLANS.labels(
        form="mhc", op=op, kernel=kernel, streams="4").value
        for op in ("pre", "post") for kernel in ("pallas", "composed")}


def test_xing_serving_decode_step_compiles_for_v5e(v5e, compiled_kernels):
    """The whole ``xing4.0-29b-a4b`` serving decode step (32 slots of
    8,448 latent rows, four residual streams, all 64 experts, the whole
    vocabulary) for the described chip: ten ``mhc_pre`` and ten
    ``mhc_post`` Pallas calls (two sub-blocks a layer), five
    ``mla_decode`` calls, and 11.2 GB of arguments."""
    import paddle_tpu as fluid
    from paddle_tpu.kernels import mhc, mla_decode

    gpt, cfg, serving = _xing()
    B, S = serving["b_max"], serving["max_len"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        _logits, caches = gpt.build_serving_decode_step(cfg, batch=B,
                                                        max_len=S)
    assert caches == ["gpt_%d_cache_c" % i for i in range(5)]
    before = _mhc_plans()
    lowered, _ = _lower_step(
        main, {"token": (B, 1), "pos": (B, 1)}, gpt.NEXT_TOKEN_VAR, v5e)
    after = _mhc_plans()
    assert {k: after[k] - before[k] for k in after} == {
        ("pre", "pallas"): 10, ("post", "pallas"): 10,
        ("pre", "composed"): 0, ("post", "composed"): 0}
    compiled = lowered.compile()
    text = compiled.as_text()
    for name, n in ((mhc.KERNEL_PRE, 10), (mhc.KERNEL_POST, 10),
                    (mla_decode.KERNEL, 5)):
        assert len(set(re.findall(r"%%(%s[.\d]*) = " % name, text))) == n, \
            name
    mem = compiled.memory_analysis()
    assert 11.1e9 < mem.argument_size_in_bytes < 11.4e9, mem
    assert mem.temp_size_in_bytes < 1.0e9, mem
    print("xing decode step:", mem)


@pytest.mark.parametrize("P", [512, 8192])
def test_xing_prefill_compiles_for_v5e(P, v5e, compiled_kernels):
    """The batch=1 prefill of the shortest and the longest prompt of the
    mix for the described chip: the residual kernels and five flash
    forwards, a head on ONE row (no [P, vocab] logits where the plan
    fetches the token), the streams written in place, and temporaries
    that fit beside the 11.2 GB the engine holds."""
    import paddle_tpu as fluid
    from paddle_tpu.kernels import mhc

    gpt, cfg, serving = _xing()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.build_prefill_step(cfg, batch=1, prompt_len=P,
                               max_len=serving["max_len"])
    lowered, _ = _lower_step(main, {"tokens": (1, P)}, gpt.NEXT_TOKEN_VAR,
                             v5e)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%%(%s[.\d]*) = " % mhc.KERNEL_PRE,
                              text))) == 10
    assert len(set(re.findall(r"%%(%s[.\d]*) = " % mhc.KERNEL_POST,
                              text))) == 10
    assert "f32[1,%d,131072]" % P not in text
    assert "f32[%d,131072]" % P not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 3.5e9, mem
    print("xing prefill P=%d:" % P, mem)


def _nemotron():
    from paddle_tpu.models import gpt

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron-3-super-120b-a12b.json")) as f:
        conf = json.load(f)
    return gpt, conf["model"], conf["serving"]


def _ssm_plans():
    from paddle_tpu.observe.families import SSM_PLANS

    return {(op, kernel): SSM_PLANS.labels(
        op=op, kernel=kernel, chunk=chunk).value
        for op, chunk in (("scan", "128"), ("update", "1"))
        for kernel in ("pallas", "composed")}


def _gmm_plans():
    """``{(kernel, tile, form): lowerings}`` of the grouped matmul."""
    from paddle_tpu.observe import REGISTRY

    family = REGISTRY.snapshot()["metrics"].get(
        "paddle_moe_gmm_plans_total", {"samples": []})
    return {(s["labels"]["kernel"], s["labels"]["tile"],
             s["labels"]["form"]): s["value"] for s in family["samples"]}


def _assert_nemotron_gmm_plans(before, after):
    """Five expert layers: five Pallas plans a product, none composed,
    and the width of 2688 = 21 x 128 (the up product's columns, the down
    product's reduction) never cut in tiles of 128."""
    from paddle_tpu.kernels import moe_gmm

    new = {k: v - before.get(k, 0) for k, v in after.items()
           if v != before.get(k, 0)}
    assert {form for _k, _t, form in new} == {"pallas"}, new
    for kernel, axis in ((moe_gmm.KERNEL_UP, 2), (moe_gmm.KERNEL_DOWN, 1)):
        mine = {tile: n for (k, tile, _f), n in new.items() if k == kernel}
        assert sum(mine.values()) == 5, new
        assert all(int(tile.split("x")[axis]) > 128 for tile in mine), new


def test_nemotron_serving_decode_step_compiles_for_v5e(v5e,
                                                       compiled_kernels):
    """The whole ``nemotron-3-super-120b-a12b`` serving decode step (96
    slots: 2.07 GB of state, one attention slab, 128 of 512 latent
    experts a layer) for the described chip: five ``ssm_update`` Pallas
    calls, every state donated into its output and none copied, 11.9 GB
    of arguments and temporaries that fit beside them."""
    import paddle_tpu as fluid
    from paddle_tpu.kernels import ssm

    gpt, cfg, serving = _nemotron()
    B, S = serving["b_max"], serving["max_len"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        _logits, caches = gpt.build_serving_decode_step(cfg, batch=B,
                                                        max_len=S)
    assert [gpt.cache_kind(cfg, n, S) for n in caches] \
        == ["state"] * 10 + ["full"] * 2
    before, gmm_before = _ssm_plans(), _gmm_plans()
    lowered, mut_state = _lower_step(
        main, {"token": (B, 1), "pos": (B, 1)}, gpt.NEXT_TOKEN_VAR, v5e)
    after = _ssm_plans()
    _assert_nemotron_gmm_plans(gmm_before, _gmm_plans())
    assert {k: after[k] - before[k] for k in after} == {
        ("update", "pallas"): 5, ("update", "composed"): 0,
        ("scan", "pallas"): 0, ("scan", "composed"): 0}
    assert set(caches) <= set(mut_state)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%%(%s[.\d]*) = " % ssm.KERNEL_UPDATE,
                              text))) == 5
    # no second copy of a layer's state: 96 x 8 x 128 x 1024 float32
    assert _cache_sized(text, (B, 8, 128, 1024)) == []
    mem = compiled.memory_analysis()
    assert 11.8e9 < mem.argument_size_in_bytes < 12.1e9, mem
    assert mem.temp_size_in_bytes < 1.5e9, mem
    print("nemotron decode step:", mem)


@pytest.mark.parametrize("P", [128, 2048])
def test_nemotron_prefill_compiles_for_v5e(P, v5e, compiled_kernels):
    """The batch=1 prefill of the shortest and the longest prompt of the
    mix: five ``ssm_scan`` Pallas calls in chunks of 128, the flash
    forward of the one attention layer, a head on ONE row, and
    temporaries that fit beside the 11.9 GB the engine holds."""
    import paddle_tpu as fluid
    from paddle_tpu.kernels import ssm

    gpt, cfg, serving = _nemotron()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.build_prefill_step(cfg, batch=1, prompt_len=P,
                               max_len=serving["max_len"])
    before, gmm_before = _ssm_plans(), _gmm_plans()
    lowered, _ = _lower_step(main, {"tokens": (1, P)}, gpt.NEXT_TOKEN_VAR,
                             v5e)
    after = _ssm_plans()
    _assert_nemotron_gmm_plans(gmm_before, _gmm_plans())
    assert after[("scan", "pallas")] - before[("scan", "pallas")] == 5
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%%(%s[.\d]*) = " % ssm.KERNEL_SCAN,
                              text))) == 5
    assert "f32[1,%d,32768]" % P not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2.5e9, mem
    print("nemotron prefill P=%d:" % P, mem)


# (M, K, N, itemsize) of the cells' expert products at their longest
# prefill: pairs of the longest prompt x the stored width of a weight
LONGEST_PREFILL_GMM = {
    "olmoe_up": (4096, 2048, 1024, 4), "olmoe_down": (4096, 1024, 2048, 4),
    "trinity_up": (32768, 3072, 3072, 4),
    "trinity_down": (32768, 3072, 3072, 4),
    "pangu_up": (26624, 7680, 2048, 2), "pangu_down": (26624, 2048, 7680, 2),
    "xing_up": (32768, 3584, 1024, 2), "xing_down": (32768, 1024, 3584, 2),
}


def test_widest_whole_reduction_plan_compiles_for_v5e(v5e, compiled_kernels):
    """PR 42: of the plans the cells' expert products take at their
    longest prefill, the one that keeps the most VMEM by the kernel's own
    count holds its reduction whole; the expert layer at that shape (a
    share of 8 of 256 experts, bf16 matrices under float32 rows, gate and
    up in one call) lowers for the described chip under that plan — the
    counter is read round the lowering — and compiles."""
    from paddle_tpu.kernels import moe_gmm
    from paddle_tpu.ops.moe_ops import _experts

    def reckoned(shape):
        return moe_gmm._vmem_bytes(*moe_gmm.gmm_plan(*shape), shape[3])

    widest = max(LONGEST_PREFILL_GMM,
                 key=lambda n: reckoned(LONGEST_PREFILL_GMM[n]))
    assert widest == "pangu_up"
    M, D, F, item = LONGEST_PREFILL_GMM[widest]
    tm, tk, tn = moe_gmm.gmm_plan(M, D, F, item)
    assert (tm, tk) == (128, D)
    assert 32 << 20 < reckoned(LONGEST_PREFILL_GMM[widest]) \
        <= moe_gmm._VMEM_LIMIT_BYTES
    E, held, k = 256, 8, 8

    def layer(x, router, gate, up, down):
        out, _aux, sizes = _experts(x, gate, up, None, down, None, router,
                                    E, k, None, "swiglu", True, 0.0,
                                    share=(0, held))
        return out, sizes

    sds = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
           for shape, dtype in (((M // k, D), F32), ((D, E), BF16),
                                ((held, D, F), BF16), ((held, D, F), BF16),
                                ((held, F, D), BF16))]
    before = _gmm_plans()
    lowered = jax.jit(layer).lower(*sds)
    after = _gmm_plans()
    assert {key: v - before.get(key, 0) for key, v in after.items()
            if v != before.get(key, 0)} == {
        (moe_gmm.KERNEL_UP, "%dx%dx%d" % (tm, tk, tn), "pallas"): 1,
        (moe_gmm.KERNEL_DOWN,
         "%dx%dx%d" % moe_gmm.gmm_plan(*LONGEST_PREFILL_GMM["pangu_down"]),
         "pallas"): 1}
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert moe_gmm.KERNEL_UP in text and moe_gmm.KERNEL_DOWN in text


@pytest.mark.parametrize("n_rhs", [1, 2])
def test_whole_reduction_plan_matches_composed_on_ragged_groups(n_rhs):
    """The plan Xing's up product takes (the reduction of 3,584 held
    whole) in interpret mode against the composed form: a group that
    straddles a row tile, an empty group, a group inside another's tile,
    and rows past the last group, which come out zero."""
    from paddle_tpu.kernels import moe_gmm

    sizes = [130, 0, 150, 37, 0, 20]      # 337 of 400 rows are owned
    M, K, N = 400, 3584, 1024
    plan = moe_gmm.gmm_plan(M, K, N, 2)
    assert plan[:2] == (128, K)
    rng = np.random.default_rng(42)
    lhs = jnp.asarray(rng.standard_normal((M, K)), F32)
    rhs = tuple(jnp.asarray(rng.standard_normal((len(sizes), K, N))
                            / K ** 0.5, BF16) for _ in range(n_rhs))
    gs = jnp.asarray(sizes, jnp.int32)
    got = moe_gmm.gmm_pallas(lhs, rhs, gs, name=moe_gmm.KERNEL_UP,
                             plan=plan, interpret=True)
    want = moe_gmm.gmm_composed(lhs, rhs, gs)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    assert not np.asarray(got[sum(sizes):]).any()


# --------------------------------- what the bring-up found on the way
def test_fused_attention_dropout_is_off_in_a_for_test_clone(fresh_programs):
    """Found by chip_smoke's serve phase: the fused-attention op kept its
    output dropout in ``clone(for_test=True)`` and in the Predictor, so a
    served BERT answered with random masks."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    main, startup, scope = fresh_programs
    with fluid.program_guard(main, startup):
        x = layers.data("x", [2, 16, 8], dtype="float32")
        out = layers.fused_attention(x, x, x, scale=0.5, dropout=0.5)
        test_prog = main.clone(for_test=True)
    exe = fluid.Executor(fluid.TPUPlace())
    feed = {"x": np.random.RandomState(0).randn(3, 2, 16, 8)
            .astype("float32")}
    a, = exe.run(test_prog, feed=feed, fetch_list=[out])
    b, = exe.run(test_prog, feed=feed, fetch_list=[out])
    np.testing.assert_array_equal(a, b)
    t1, = exe.run(main, feed=feed, fetch_list=[out])
    assert not np.array_equal(a, t1)  # training still drops
    from paddle_tpu.ops.attention import composed_attention

    want = composed_attention(feed["x"], feed["x"], feed["x"], None, 0.5)
    np.testing.assert_allclose(a, np.asarray(want), atol=1e-5)
