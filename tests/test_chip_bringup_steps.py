"""Chip bring-up guards that need no chip, second file: the whole step
programs of the newer cells compiled for a DESCRIBED TPU v5e (the BERT
train step and its dropout masks, the mesh wrappers, the Xing and Nemotron
serving programs, the grouped matmul's widest plan). Split from
``tests/test_chip_bringup.py`` by its own sections (PR 43) so that no one
file holds a worker of the tier-1 run for most of its length; the
fixtures and helpers are that file's, imported, so both describe the chip
the same way (its ``v5e_topology`` lets two processes load the TPU's
library side by side).
"""

import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from test_chip_bringup import (BF16, F32, ROOT,  # noqa: F401
                               _assert_multi_pass_heads, _cache_sized,
                               _compile, _lower_step, _new_step_heads,
                               _slab_relaid, _step_heads,
                               _work_list_sources, compiled_kernels, v5e,
                               v5e_topology)


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
    flash_before, heads_before = _flash_plans_by_layout(), _step_heads()
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
    # PR 57 left the single-pass count alone: four heads a step in each
    # of the S 512 step's four kernels a layer, as on its parent
    assert _new_step_heads(heads_before) == (
        {(kern, "1", 4): n_layer for kern in (
            "flash_fwd", "flash_refwd", "flash_bwd_dkv", "flash_bwd_dq")}
        if n_calls else {})
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
    # 33 blocks of 256 rows a slot: one work list for the five calls, and
    # no relayout of a slab or of its S-minor view round them
    assert [len(s) for s in _work_list_sources(
        text, mla_decode.KERNEL)] == [1, 1, 1]
    assert not _slab_relaid(text, B, S)
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
    stepped = _step_heads()
    lowered, _ = _lower_step(main, {"tokens": (1, P)}, gpt.NEXT_TOKEN_VAR,
                             v5e)
    _assert_multi_pass_heads(stepped, {"flash_fwd": 5} if P == 8192 else {})
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


def _assert_nemotron_gmm_plans(before, after, plans=5):
    """Five expert layers: five Pallas plans a product (PR 45: a prefill
    long enough to carry a bound holds the layer cut and at full length
    inside ONE jit that its five layers share, so two plans a product),
    none composed, and the width of 2688 = 21 x 128 (the up product's
    columns, the down product's reduction) never cut in tiles of
    128."""
    from paddle_tpu.kernels import moe_gmm

    new = {k: v - before.get(k, 0) for k, v in after.items()
           if v != before.get(k, 0)}
    assert {form for _k, _t, form in new} == {"pallas"}, new
    for kernel, axis in ((moe_gmm.KERNEL_UP, 2), (moe_gmm.KERNEL_DOWN, 1)):
        mine = {tile: n for (k, tile, _f), n in new.items() if k == kernel}
        assert sum(mine.values()) == plans, new
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
    from paddle_tpu.ops.moe_ops import compact_rows

    gpt, cfg, serving = _nemotron()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.build_prefill_step(cfg, batch=1, prompt_len=P,
                               max_len=serving["max_len"])
    before, gmm_before = _ssm_plans(), _gmm_plans()
    stepped = _step_heads()
    lowered, _ = _lower_step(main, {"tokens": (1, P)}, gpt.NEXT_TOKEN_VAR,
                             v5e)
    after = _ssm_plans()
    # sixteen query heads of 128 a key/value head: 2,048 keys are four
    # blocks (128 are under the threshold: composed)
    _assert_multi_pass_heads(stepped, {"flash_fwd": 1} if P == 2048 else {})
    # 2,048 tokens x 22 choices are over the threshold, 128 x 22 under
    bound = compact_rows(cfg["expert_top_k"] * P, cfg["n_expert"],
                         cfg["n_expert_local"])
    assert bound == {128: None, 2048: 22528}[P]
    _assert_nemotron_gmm_plans(gmm_before, _gmm_plans(),
                               plans=2 if bound else 5)
    assert after[("scan", "pallas")] - before[("scan", "pallas")] == 5
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%%(%s[.\d]*) = " % ssm.KERNEL_SCAN,
                              text))) == 5
    assert "f32[1,%d,32768]" % P not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2.5e9, mem
    print("nemotron prefill P=%d:" % P, mem)


def _lfm2():
    from paddle_tpu.models import gpt

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2-24b-a2b.json")) as f:
        conf = json.load(f)
    return gpt, conf["model"], conf["serving"]


def test_lfm2_serving_decode_step_compiles_for_v5e(v5e, compiled_kernels):
    """The whole ``lfm2-24b-a2b`` serving decode step (64 slots: four
    layers' two carried rows and ONE layer's slab of 17,408 positions,
    bf16 matrices, all 64 experts, the head tied to the table) for the
    described chip: the slab's two in-place Pallas writes, both grouped
    matmuls of the four expert layers, every carried-rows tensor and
    both tallies donated, no float32 copy of the table, and 9.97 GB of
    arguments."""
    import paddle_tpu as fluid
    from paddle_tpu.kernels import kv_cache_write as kvw
    from paddle_tpu.kernels import moe_gmm

    gpt, cfg, serving = _lfm2()
    B, S = serving["b_max"], serving["max_len"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        _logits, caches = gpt.build_serving_decode_step(cfg, batch=B,
                                                        max_len=S)
    shapes = {n: tuple(main.global_block().vars[n].shape) for n in caches}
    assert shapes == dict(
        {"gpt_%d_cache_x" % i: (B, 2, 2048) for i in (0, 2, 3, 4)},
        gpt_1_cache_k=(B, 8, S, 64), gpt_1_cache_v=(B, 8, S, 64))
    lowered, mut = _lower_step(
        main, {"token": (B, 1), "pos": (B, 1)}, gpt.NEXT_TOKEN_VAR, v5e)
    assert sorted(mut) == sorted(caches + [gpt.ROUTED_PAIRS_VAR,
                                           gpt.EXPERTS_TOUCHED_VAR])
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2 + 8
    assert len(set(re.findall(r"%%(%s[.\d]*) = " % kvw.KERNEL, text))) == 2
    assert moe_gmm.KERNEL_UP in text and moe_gmm.KERNEL_DOWN in text
    # neither the table (the head) nor an expert stack is widened whole
    assert not re.search(r"f32\[65536,2048\][^ ]* (copy|convert)\(", text)
    assert not re.search(r"f32\[64,2048,1536\][^ ]* (copy|convert)\(",
                         text)
    # the slab is read where it lies (the TPU stores it S-minor: a
    # bitcast inside the attention's fusions, no copy of 2.3 GB)
    assert [line for line in _cache_sized(text, (B, 8, S, 64))
            if "bitcast_fusion" not in line] == []
    mem = compiled.memory_analysis()
    # 5.40 GB of bf16 matrices, 4.56 GB of slab, 4.2 MB of carried rows
    assert 9.96e9 < mem.argument_size_in_bytes < 9.98e9, mem
    assert mem.temp_size_in_bytes < 1.0e9, mem
    print("lfm2 decode step:", mem)


@pytest.mark.parametrize("P", [2048, 16384])
def test_lfm2_prefill_compiles_for_v5e(P, v5e, compiled_kernels):
    """The batch=1 prefill of the shortest and the longest prompt of the
    mix: ONE flash forward at 32 query and 8 key-value heads of 64 (no
    [P, P] score tensor), four convolution layers that XLA fuses, a head
    on ONE row (the [P, 65536] logits would be 4.3 GB), and temporaries
    that fit beside the 9.97 GB the engine holds."""
    import paddle_tpu as fluid
    from paddle_tpu.observe.families import FLASH_BLOCK_PLANS

    gpt, cfg, serving = _lfm2()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.build_prefill_step(cfg, batch=1, prompt_len=P,
                               max_len=serving["max_len"])
    types = [op.type for op in main.global_block().ops]
    assert types.count("causal_conv") == 4
    assert types.count("fused_attention") == 1
    plan = FLASH_BLOCK_PLANS.labels(kernel="flash_fwd", block="512x512",
                                    single_pass="0", layout="heads")
    before = plan.value
    stepped = _step_heads()
    lowered, _ = _lower_step(main, {"tokens": (1, P)}, gpt.NEXT_TOKEN_VAR,
                             v5e)
    assert plan.value == before + 1
    _assert_multi_pass_heads(stepped, {"flash_fwd": 1})
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("flash_fwd") >= 1
    assert "f32[1,32,%d,%d]" % (P, P) not in text
    assert "f32[1,%d,65536]" % P not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4.5e9, mem
    print("lfm2 prefill P=%d:" % P, mem)


def _longcat():
    from paddle_tpu.models import gpt

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "longcat-flash-omni.json")) as f:
        conf = json.load(f)
    return gpt, conf["model"], conf["serving"]


def test_longcat_serving_decode_step_compiles_for_v5e(v5e,
                                                      compiled_kernels):
    """The whole ``longcat-flash-omni`` serving decode step (32 slots of
    4,096 latent rows in EIGHT slabs, two a published layer; bf16
    matrices; 8 of 512 experts and all 256 identity experts) for the
    described chip: eight ``mla_decode`` calls and eight in-place row
    writes, both grouped matmuls of the four routed branches, every slab
    and the three tallies donated, and 10.35 GB of arguments."""
    import paddle_tpu as fluid
    from paddle_tpu.kernels import mla_decode, moe_gmm

    gpt, cfg, serving = _longcat()
    B, S = serving["b_max"], serving["max_len"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        _logits, caches = gpt.build_serving_decode_step(cfg, batch=B,
                                                        max_len=S)
    assert caches == ["gpt_%d_cache_c" % j for j in range(8)]
    types = [op.type for op in main.global_block().ops]
    assert types.count("moe_ffn") == 4 and types.count("mla_decode") == 8
    lowered, mut = _lower_step(
        main, {"token": (B, 1), "pos": (B, 1)}, gpt.NEXT_TOKEN_VAR, v5e)
    assert sorted(mut) == sorted(caches + [
        gpt.ROUTED_PAIRS_VAR, gpt.EXPERTS_TOUCHED_VAR, gpt.ZERO_PAIRS_VAR])
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%%(%s[.\d]*) = " % mla_decode.KERNEL,
                              text))) == 8
    # one work list for the eight calls, no relayout of a slab
    assert [len(s) for s in _work_list_sources(
        text, mla_decode.KERNEL)] == [1, 1, 1]
    assert not _slab_relaid(text, B, S)
    assert moe_gmm.KERNEL_UP in text and moe_gmm.KERNEL_DOWN in text
    # no dense FFN matrix nor an expert stack is widened whole
    assert not re.search(r"f32\[6144,12288\][^ ]* (copy|convert)\(", text)
    assert not re.search(r"f32\[8,6144,2048\][^ ]* (copy|convert)\(", text)
    mem = compiled.memory_analysis()
    # 7.93 GB of bf16 matrices, 2.42 GB of latent slabs
    assert 10.3e9 < mem.argument_size_in_bytes < 10.4e9, mem
    assert mem.temp_size_in_bytes < 1.0e9, mem
    print("longcat decode step:", mem)


@pytest.mark.parametrize("P", [128, 3328])
def test_longcat_prefill_compiles_for_v5e(P, v5e, compiled_kernels):
    """The batch=1 prefill of the shortest and the longest prompt of the
    mix: eight flash forwards at 64 heads of 192 / 128 (no [P, P] score
    tensor), four routed branches — at 3,328 x 12 pair rows each behind
    ``compact_rows``' bound, reckoned over all 768 outputs of the router
    — a head on ONE row, and temporaries that fit beside the 10.35 GB
    the engine holds."""
    import paddle_tpu as fluid
    from paddle_tpu.ops.moe_ops import compact_rows

    gpt, cfg, serving = _longcat()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.build_prefill_step(cfg, batch=1, prompt_len=P,
                               max_len=serving["max_len"])
    types = [op.type for op in main.global_block().ops]
    assert types.count("fused_attention") == 8
    assert types.count("moe_ffn") == 4
    cap = compact_rows(P * 12, 768, 8)
    assert cap == (None if P == 128 else 896)
    assert (gpt.COMPACT_CALLS_VAR in main.global_block().vars) \
        == (cap is not None)
    stepped = _step_heads()
    lowered, _ = _lower_step(main, {"tokens": (1, P)}, gpt.NEXT_TOKEN_VAR,
                             v5e)
    _assert_multi_pass_heads(stepped, {"flash_fwd": 8} if P == 3328 else {})
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("flash_fwd") >= 8
    if P != 128:        # q_nope is [1, 64, P, 128] itself
        assert "f32[1,64,%d,%d]" % (P, P) not in text
    assert "f32[1,%d,16384]" % P not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 3.5e9, mem
    print("longcat prefill P=%d:" % P, mem)


# (M, K, N, itemsize) of the cells' expert products at their longest
# prefill: pairs of the longest prompt x the stored width of a weight
LONGEST_PREFILL_GMM = {
    "olmoe_up": (4096, 2048, 1024, 4), "olmoe_down": (4096, 1024, 2048, 4),
    "trinity_up": (32768, 3072, 3072, 4),
    "trinity_down": (32768, 3072, 3072, 4),
    "pangu_up": (26624, 7680, 2048, 2), "pangu_down": (26624, 2048, 7680, 2),
    "xing_up": (32768, 3584, 1024, 2), "xing_down": (32768, 1024, 3584, 2),
    "longcat_up": (39936, 6144, 2048, 2),
    "longcat_down": (39936, 2048, 6144, 2),
}


def test_widest_whole_reduction_plan_compiles_for_v5e(v5e, compiled_kernels):
    """PR 42: of the plans the cells' expert products take at their
    longest prefill, the one that keeps the most VMEM by the kernel's own
    count holds its reduction whole; the expert layer at that shape (a
    share of 8 of 256 experts, bf16 matrices under float32 rows, gate and
    up in one call) lowers for the described chip through the kernel and
    compiles. PR 45: a share's call of that length holds the layer twice
    under a ``conditional``, over the 1,664 rows its bound leaves and
    over all 26,624, and both take the same plans (a row tile is 128
    either way). What the lowering holds is read from its own text:
    ``paddle_moe_gmm_plans_total`` counts a plan where ``gmm`` is TRACED,
    and ``moe_ops._bounded`` is a jit of its own, so the counter stood
    still round this lowering whenever an earlier test of the process
    (``test_chip_bringup.py``'s Pangu prefill, on the same worker) had
    traced it at these shapes."""
    from paddle_tpu.kernels import moe_gmm
    from paddle_tpu.ops.moe_ops import _experts, compact_rows

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
        out, _aux, sizes, _took, _most = _experts(
            x, gate, up, None, down, None, router, E, k, None, "swiglu",
            True, 0.0, share=(0, held))
        return out, sizes

    sds = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
           for shape, dtype in (((M // k, D), F32), ((D, E), BF16),
                                ((held, D, F), BF16), ((held, D, F), BF16),
                                ((held, F, D), BF16))]
    lowered = jax.jit(layer).lower(*sds)
    # one up and one down product a body, each through the kernel (a
    # composed product is no such call), at the plans of these shapes
    stablehlo = lowered.as_text()
    for kernel in (moe_gmm.KERNEL_UP, moe_gmm.KERNEL_DOWN):
        assert stablehlo.count('kernel_name = "%s"' % kernel) == 2
    assert moe_gmm.gmm_plan(*LONGEST_PREFILL_GMM["pangu_down"])[:2] \
        == (128, F)
    cap = compact_rows(M, E, held)
    assert cap == 1664
    assert moe_gmm.gmm_plan(cap, D, F, item) == (tm, tk, tn)
    text = lowered.compile().as_text()
    assert " conditional(" in text
    assert text.count('custom_call_target="tpu_custom_call"') == 4
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


def _brumby():
    from paddle_tpu.models import gpt

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "brumby-14b-base.json")) as f:
        conf = json.load(f)
    return gpt, conf["model"], conf["serving"]


def _power_plans(family="paddle_power_plans_total"):
    from paddle_tpu.observe import REGISTRY

    family = REGISTRY.snapshot()["metrics"].get(family, {"samples": []})
    return {(s["labels"]["kernel"], s["labels"]["form"],
             s["labels"]["chunk"]): s["value"] for s in family["samples"]}


def _new_plans(before, family="paddle_power_plans_total"):
    return {k: v - before.get(k, 0)
            for k, v in _power_plans(family).items()
            if v != before.get(k, 0)}


def test_brumby_serving_decode_step_compiles_for_v5e(v5e, compiled_kernels):
    """The whole ``brumby-14b-base`` serving decode step (32 slots: 6.12
    GB of retention state and normaliser, no cache with a position axis,
    bf16 matrices, the untied head) for the described chip: five
    ``power_update`` Pallas calls, every state and normaliser donated into
    its output and none copied, and arguments equal to the static bytes
    the closed form reckons within 1%."""
    import paddle_tpu as fluid
    from benchmarks.lib import closed_forms_power
    from paddle_tpu.kernels import power

    gpt, cfg, serving = _brumby()
    B, S = serving["b_max"], serving["max_len"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        _logits, caches = gpt.build_serving_decode_step(cfg, batch=B,
                                                        max_len=S)
    assert [gpt.cache_kind(cfg, n, S) for n in caches] == ["state"] * 10
    before = _power_plans()
    lowered, mut_state = _lower_step(
        main, {"token": (B, 1), "pos": (B, 1)}, gpt.NEXT_TOKEN_VAR, v5e)
    assert _new_plans(before) == {("power_update", "pallas", "1"): 5}
    assert set(caches) <= set(mut_state)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%%(%s[.\d]*) = " % power.KERNEL_UPDATE,
                              text))) == 5
    # no second copy of a layer's state: 32 x 8 x 9,216 x 128 float32
    assert _cache_sized(text, (B, 8, 9216, 128)) == []
    assert not re.findall(r"%kv_cache_write[.\d]* = ", text)
    mem = compiled.memory_analysis()
    static = closed_forms_power.static_bytes(cfg, B, S, 4, 2)
    # (the token table is an argument too: the step looks its rows up)
    assert abs(mem.argument_size_in_bytes - static) < 0.01 * static, mem
    assert mem.alias_size_in_bytes >= closed_forms_power.state_bytes(cfg, B)
    assert mem.temp_size_in_bytes < 0.5e9, mem
    print("brumby decode step:", mem)


def test_brumby_prefill_compiles_for_v5e(v5e, compiled_kernels):
    """The batch=1 prefill of the longest prompt of the mix (8,192):
    five ``power_scan`` Pallas calls at the configuration's chunk, every
    state an output no copy follows, no flash forward and no cache write,
    a head on ONE row, and temporaries that fit beside the 12.5 GB the
    engine holds."""
    import paddle_tpu as fluid
    from paddle_tpu.kernels import power

    gpt, cfg, serving = _brumby()
    P = 8192
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.build_prefill_step(cfg, batch=1, prompt_len=P,
                               max_len=serving["max_len"])
    before = _power_plans()
    lowered, _ = _lower_step(main, {"tokens": (1, P)}, gpt.NEXT_TOKEN_VAR,
                             v5e)
    assert _new_plans(before) == {
        ("power_scan", "pallas", str(power.scan_chunk(P))): 5}
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%%(%s[.\d]*) = " % power.KERNEL_SCAN,
                              text))) == 5
    assert not re.findall(r"%(flash_fwd|kv_cache_write)[.\d]* = ", text)
    # a layer's state leaves its kernel as the program's output: nothing
    # copies or relays 8 x 9,216 x 128 float32 on the way (that the
    # kernel's scratch fits its VMEM limit is Mosaic's verdict here, and
    # tests/test_kernel_plans.py's from the plan's own numbers)
    assert _cache_sized(text, (1, 8, 9216, 128)) == []
    assert "f32[1,%d,151936]" % P not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2.5e9, mem
    print("brumby prefill P=%d:" % P, mem)


def _qwen3next():
    from paddle_tpu.models import gpt

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        conf = json.load(f)
    return gpt, conf["model"], conf["serving"]


DELTA_PLANS = "paddle_delta_plans_total"


def test_qwen3next_serving_decode_step_compiles_for_v5e(v5e,
                                                        compiled_kernels):
    """The whole ``qwen3-next-80b-a3b`` serving decode step (128 slots:
    2.53 GB of delta state and convolution rows beside 4.03 GB of slab in
    ONE lane, 64 of 512 experts a layer, bf16 matrices) for the described
    chip: nine ``delta_update`` Pallas calls, every state donated into its
    output and none copied, and arguments equal to the static bytes the
    closed form reckons within 1%."""
    import paddle_tpu as fluid
    from benchmarks.lib import closed_forms_delta
    from paddle_tpu.kernels import delta

    gpt, cfg, serving = _qwen3next()
    B, S = serving["b_max"], serving["max_len"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        _logits, caches = gpt.build_serving_decode_step(cfg, batch=B,
                                                        max_len=S)
    kinds = [gpt.cache_kind(cfg, n, S) for n in caches]
    assert kinds.count("state") == 18 and kinds.count("full") == 6
    before = _power_plans(DELTA_PLANS)
    lowered, mut_state = _lower_step(
        main, {"token": (B, 1), "pos": (B, 1)}, gpt.NEXT_TOKEN_VAR, v5e)
    assert _new_plans(before, DELTA_PLANS) == {
        ("delta_update", "pallas", "1"): 9}
    assert set(caches) <= set(mut_state)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%%(%s[.\d]*) = " % delta.KERNEL_UPDATE,
                              text))) == 9
    # no second copy of a layer's state: 128 x 32 x 128 x 128 float32
    assert _cache_sized(text, (B, 32, 128, 128)) == []
    mem = compiled.memory_analysis()
    static = closed_forms_delta.static_bytes(cfg, B, S, 4, 2)
    assert abs(mem.argument_size_in_bytes - static) < 0.01 * static, mem
    assert mem.alias_size_in_bytes >= closed_forms_delta.state_bytes(cfg, B)
    assert mem.temp_size_in_bytes < 1.5e9, mem
    print("qwen3-next decode step:", mem)


def test_qwen3next_prefill_compiles_for_v5e(v5e, compiled_kernels):
    """The batch=1 prefill of the longest prompt of the mix (2,048): nine
    ``delta_scan`` Pallas calls at the kernel's chunk, the flash forward
    at a head of 256 in the three full layers, a head on ONE row, and
    temporaries that fit beside the 12.4 GB the engine holds."""
    import paddle_tpu as fluid
    from paddle_tpu.kernels import delta

    gpt, cfg, serving = _qwen3next()
    P = 2048
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.build_prefill_step(cfg, batch=1, prompt_len=P,
                               max_len=serving["max_len"])
    before = _power_plans(DELTA_PLANS)
    stepped = _step_heads()
    lowered, _ = _lower_step(main, {"tokens": (1, P)}, gpt.NEXT_TOKEN_VAR,
                             v5e)
    assert _new_plans(before, DELTA_PLANS) == {
        ("delta_scan", "pallas", str(delta.scan_chunk(P))): 9}
    # eight query heads of 256 a key/value head, four key blocks
    _assert_multi_pass_heads(stepped, {"flash_fwd": 3})
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%%(%s[.\d]*) = " % delta.KERNEL_SCAN,
                              text))) == 9
    assert len(set(re.findall(r"%(flash_fwd[.\d]*) = ", text))) == 3
    assert "f32[1,%d,18992]" % P not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.5e9, mem
    print("qwen3-next prefill P=%d:" % P, mem)


def _jamba():
    from paddle_tpu.models import gpt

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ai21-jamba2-3b.json")) as f:
        conf = json.load(f)
    return gpt, conf["model"], conf["serving"]


MAMBA_PLANS = "paddle_mamba_plans_total"


def _mamba_plans():
    from paddle_tpu.observe import REGISTRY

    family = REGISTRY.snapshot()["metrics"].get(MAMBA_PLANS, {"samples": []})
    return {(s["labels"]["kernel"], s["labels"]["form"],
             s["labels"]["block"]): s["value"] for s in family["samples"]}


def _new_mamba_plans(before):
    return {k: v - before.get(k, 0) for k, v in _mamba_plans().items()
            if v != before.get(k, 0)}


def test_jamba_serving_decode_step_compiles_for_v5e(v5e, compiled_kernels):
    """The whole ``ai21-jamba2-3b`` serving decode step (32 slots: 0.32 GB
    of selective-scan state and convolution rows beside 1.09 GB of slab in
    ONE lane, all 28 layers, the whole token table as the head, bf16
    matrices) for the described chip: 26 ``mamba_update`` Pallas calls,
    every state donated into its output and none copied, and arguments
    equal to the static bytes the closed form reckons within 1%."""
    import paddle_tpu as fluid
    from benchmarks.lib import closed_forms_mamba
    from paddle_tpu.kernels import mamba

    gpt, cfg, serving = _jamba()
    B, S = serving["b_max"], serving["max_len"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        _logits, caches = gpt.build_serving_decode_step(cfg, batch=B,
                                                        max_len=S)
    kinds = [gpt.cache_kind(cfg, n, S) for n in caches]
    assert kinds.count("state") == 52 and kinds.count("full") == 4
    before = _mamba_plans()
    lowered, mut_state = _lower_step(
        main, {"token": (B, 1), "pos": (B, 1)}, gpt.NEXT_TOKEN_VAR, v5e)
    assert _new_mamba_plans(before) == {("mamba_update", "pallas", "1"): 26}
    assert set(caches) <= set(mut_state)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%%(%s[.\d]*) = " % mamba.KERNEL_UPDATE,
                              text))) == 26
    # no second copy of a layer's state: 32 x 1 x 16 x 5120 float32
    assert _cache_sized(text, (B, 1, 16, 5120)) == []
    mem = compiled.memory_analysis()
    static = closed_forms_mamba.static_bytes(cfg, B, S, 4, 2)
    assert abs(mem.argument_size_in_bytes - static) < 0.01 * static, mem
    assert static >= 0.25 * 16e9
    assert mem.alias_size_in_bytes >= closed_forms_mamba.state_bytes(cfg, B)
    assert mem.temp_size_in_bytes < 1.5e9, mem
    print("jamba decode step:", mem)


def test_jamba_prefill_compiles_for_v5e(v5e, compiled_kernels):
    """The batch=1 prefill of the longest prompt of the mix (16,384): 26
    ``mamba_scan`` Pallas calls at the kernel's block, the flash forward
    at 20 query heads over one key-value head in the two full layers, a
    head on ONE row, NO ``[T, 5120, 16]`` tensor (``exp(dt A)`` is formed
    in the kernel's registers: 5.4 GB a layer were it written), 26
    ``conv_prefill`` Pallas calls that read ``W_in``'s ``[T, 10240]``
    product where it lies (no slice of its first 5,120 columns is made in
    front of them), the residual stream written after every layer (28 ``materialize`` ops:
    without them XLA keeps all 56 sub-block outputs to the end, 10.6 GB
    of temporaries for a live set of 2.7), and temporaries that fit
    beside the 7.5 GB the engine holds."""
    import paddle_tpu as fluid
    from paddle_tpu.kernels import mamba, ssm
    from paddle_tpu.observe.families import CONV_PLANS

    gpt, cfg, serving = _jamba()
    P = 16384
    conv = CONV_PLANS.labels(kernel="pallas", chunk=str(ssm._CONV_BLOCK))
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.build_prefill_step(cfg, batch=1, prompt_len=P,
                               max_len=serving["max_len"])
    assert [op.type for op in main.global_block().ops].count(
        "materialize") == 28
    before, conv_before = _mamba_plans(), conv.value
    lowered, _ = _lower_step(main, {"tokens": (1, P)}, gpt.NEXT_TOKEN_VAR,
                             v5e)
    assert _new_mamba_plans(before) == {
        ("mamba_scan", "pallas", str(mamba.scan_block(P))): 26}
    assert conv.value == conv_before + 26
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%%(%s[.\d]*) = " % mamba.KERNEL_SCAN,
                              text))) == 26
    assert len(set(re.findall(r"%(flash_fwd[.\d]*) = ", text))) == 2
    calls = [line for line in text.splitlines()
             if re.search(r"%%%s[.\d]* = " % ssm.KERNEL_CONV, line)]
    assert len(calls) == 26
    assert all("operand_layout_constraints={f32[1,%d,10240]" % P in line
               for line in calls)
    assert "slice={[0:1], [0:%d], [0:5120]}" % P not in text
    for dims in ("%d,5120,16" % P, "%d,16,5120" % P, "5120,16,%d" % P,
                 "16,5120,%d" % P, "5120,%d,16" % P, "16,%d,5120" % P):
        assert "f32[1,%s]" % dims not in text and "f32[%s]" % dims \
            not in text, dims
    assert "f32[1,%d,65536]" % P not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 3.5e9, mem
    print("jamba prefill P=%d:" % P, mem)
