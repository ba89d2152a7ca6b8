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

The whole step programs of the newer cells (the BERT train step, the mesh
wrappers, Xing, Nemotron, the grouped matmul's widest plan) are compiled in
``tests/test_chip_bringup_steps.py``, which imports this file's fixtures.
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
        out, _aux, sizes, _took, _most = _experts(
            x, gate, up, None, down, None, router, E, k, None, "swiglu",
            False, 0.0)
        return out, sizes

    sds = [jax.ShapeDtypeStruct(shape, F32, sharding=v5e) for shape in (
        (tokens, D), (D, E), (E, D, F), (E, D, F), (E, F, D))]
    text = jax.jit(layer).lower(*sds).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert moe_gmm.KERNEL_UP in text and moe_gmm.KERNEL_DOWN in text


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
        lambda c, u, p: kvw.kv_cache_write_pallas(c, u, p, interpret=False),
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


def _step_heads():
    """``{(kernel, single_pass, heads a step): flash lowerings so far}``
    off ``paddle_flash_step_heads_total``."""
    from paddle_tpu.observe import REGISTRY

    return {(s["labels"]["kernel"], s["labels"]["single_pass"],
             int(s["labels"]["heads"])): s["value"]
            for s in REGISTRY.snapshot()["metrics"][
                "paddle_flash_step_heads_total"]["samples"]}


def _new_step_heads(before):
    """What ``_step_heads`` counted since ``before``."""
    return {key: n - before.get(key, 0) for key, n in _step_heads().items()
            if n > before.get(key, 0)}


def _assert_multi_pass_heads(before, want):
    """The lowering since ``before`` counted ``want`` = {kernel: calls}
    multi-pass forwards, every one at more than one head a grid step: the
    compile that follows holds the count to the described chip's VMEM."""
    new = {(kernel, heads): n
           for (kernel, single, heads), n in _new_step_heads(before).items()
           if single == "0"}
    assert all(heads > 1 for _kernel, heads in new), new
    got = {}
    for (kernel, _heads), n in new.items():
        got[kernel] = got.get(kernel, 0) + n
    assert got == want, new


def _work_list_sources(text, kernel):
    """What computed the grid bound and the two tables (operands 0, 2 and
    3, before them ``pos``) of every ``kernel`` call in a compiled step's
    HLO, each followed back through the copies XLA hands a later call:
    ``[{names of the bound's sources}, {slot_of's}, {blk_of's}]``. One
    name a set = the work list is computed once a step, not once a
    layer."""
    made = {m.group(1): (m.group(2), m.group(3)) for m in re.finditer(
        r"^\s*%(\S+) = .*? ([a-z\-]+)\(%([^,)\s]+)", text, re.M)}

    def source(name):
        while made.get(name, ("", ""))[0] in ("copy", "copy-start",
                                              "copy-done", "bitcast"):
            name = made[name][1]
        return name

    calls = re.findall(r"%%%s[.\d]* = \S+ custom-call\(([^)]*)\)" % kernel,
                       text)
    operands = [[re.sub(r"/\*.*?\*/", "", o).strip().lstrip("%")
                 for o in c.split(",")] for c in calls]
    assert operands and all(len(o) == 6 for o in operands), operands
    return [{source(o[i]) for o in operands} for i in (0, 2, 3)]


def _slab_relaid(text, B, S, W=576):
    """A copy, transpose or fusion that writes a whole ``[B, S, W]`` latent
    slab or its S-minor view in a compiled step's HLO, or None."""
    return re.search(r"= \S+\[%d,(%d,%d|%d,%d)\]\S* "
                     r"(copy|transpose|fusion)\(" % (B, W, S, S, W), text)


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
    stepped = _step_heads()
    lowered, _ = _lower_step(main, {"tokens": (1, P)}, gpt.NEXT_TOKEN_VAR,
                             v5e)
    # 8,192: four banded layers and the full one, several query heads of
    # a group a step over ONE K/V block; 512 is a single pass
    _assert_multi_pass_heads(stepped, {A.KERNEL_FWD_WIN: 4, A.KERNEL_FWD: 1}
                             if P == 8192 else {})
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
                                          block="512 live",
                                          widths="576x512")
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
    # the five calls walk ONE work list: the count of live (slot, block)
    # pairs (the grid's traced bound) and both tables computed once
    assert [len(s) for s in _work_list_sources(
        text, mla_decode.KERNEL)] == [1, 1, 1]
    assert len(re.findall(r"%s[.\d]* = " % kvw.KERNEL, text)) == 5
    assert moe_gmm.KERNEL_UP in text and moe_gmm.KERNEL_DOWN in text
    # neither the slab nor its S-minor view is copied or relaid
    assert _cache_sized(text, (B, 1, S, 576)) == []
    assert _cache_sized(text, (B, 1, 576, S)) == []
    assert not _slab_relaid(text, B, S)
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
    and temporaries that fit beside the 9.84 GB the engine holds. Since
    PR 50 in the lanes layout with the shared key part: no head-major
    tensor ([1, 128, P, .] or its flattened [128, P', .]) and nothing
    padded to whole blocks is in the program, and the kernel's context
    [1, P, 128 * 128] float32 is the output projection's operand."""
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
                                    layout="lanes")
    heads = FLASH_BLOCK_PLANS.labels(kernel="flash_fwd", block=block,
                                     single_pass="0" if P == 3328 else "1",
                                     layout="heads")
    before = plan.value, heads.value
    stepped = _step_heads()
    lowered, _ = _lower_step(main, {"tokens": (1, P)}, gpt.NEXT_TOKEN_VAR,
                             v5e)
    assert (plan.value, heads.value) == (before[0] + 5, before[1])
    _assert_multi_pass_heads(stepped, {"flash_fwd": 5} if P == 3328 else {})
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 5
    assert text.count("flash_fwd") >= 5
    if P > 128:       # [1, 128, 128, 128] is also a head tensor's shape
        assert "f32[1,128,%d,%d]" % (P, P) not in text
        import re

        assert not re.search(r"\[1,128,%d,\d+\]|\[128,%d,\d+\]|\[1,3584,"
                             % (P, P), text)
    assert "f32[1,%d,16384]" % P in text
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
    """A phase that fails (here: attention cannot read its threshold)
    ends the run non-zero, with no result line."""
    out = _chip_smoke("--cpu-rehearsal", PADDLE_TPU_FLASH_MIN_SEQ="128k")
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
