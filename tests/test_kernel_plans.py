"""Every shape a benchmark cell runs has a legal kernel plan.

A kernel's form is decided beside the kernel, from its operands
(docs/KERNELS.md). This file walks the programs the benchmark's
configurations really build — the decode step at ``b_max`` slots and a
prefill at each prompt length of the configuration's traffic
(``benchmarks/configs/``, ``benchmarks/traffic/``, paired by
``BENCHMARK.json``), the BERT train step at each cell's sequence — and,
for every op a kernel stands behind, calls the kernel's own plan
function with the op's own shapes: the plan divides or pads as the kernel
states, fits the byte caps and the VMEM reckoning the kernel itself
uses, and IS a plan (not ``None``, the composed fallback) wherever the
ledger's device breakdown shows the kernel running in that cell.

The programs are built as IR only and the plan functions are called
directly: nothing is traced, compiled or run. (PR 40's miss was a width
of 21 x 128 in a branch no cell had entered before; a case here would
have said so on the CPU.)
"""

import functools
import json
import os
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


MANIFEST = _json("BENCHMARK.json")
CONFIG_FILES = {c["name"]: c["file"] for c in MANIFEST["configs"]}

# the ops of each kind of program that a kernel stands behind; the test
# holds a program to exactly its row, so a builder that starts (or stops)
# reaching a kernel has to say so here
REACHES = {
    "bert-base": {"train": ("fused_attention",)},
    "gpt2-medium": {"decode": ("kv_cache_write",),
                    "prefill": ("kv_cache_write",)},
    "olmoe-1b-7b": {"decode": ("kv_cache_write", "moe_ffn"),
                    "prefill": ("kv_cache_write", "moe_ffn")},
    "trinity-large-preview": {
        "decode": ("kv_cache_write", "moe_ffn"),
        "prefill": ("fused_attention", "kv_cache_write", "moe_ffn")},
    "openpangu-ultra-moe-718b": {
        "decode": ("kv_cache_write", "mla_decode", "moe_ffn"),
        "prefill": ("fused_attention", "kv_cache_write", "moe_ffn")},
    "xing4.0-29b-a4b": {
        "decode": ("kv_cache_write", "mhc_post", "mhc_pre", "mla_decode",
                   "moe_ffn"),
        "prefill": ("fused_attention", "kv_cache_write", "mhc_post",
                    "mhc_pre", "moe_ffn")},
    "nemotron-3-super-120b-a12b": {
        "decode": ("kv_cache_write", "moe_ffn", "ssm_update"),
        "prefill": ("causal_conv", "fused_attention", "kv_cache_write",
                    "moe_ffn", "ssm_scan")},
    # a gated convolution layer's gates are element-wise; its prompt's
    # convolution (no silu, no bias, three taps) is the one op
    # ``causal_conv`` every mixer's is, over a product no kernel reads in
    # place: composed (a token's, ``causal_conv_step``, has no kernel)
    "lfm2-24b-a2b": {
        "decode": ("kv_cache_write", "moe_ffn"),
        "prefill": ("causal_conv", "fused_attention", "kv_cache_write",
                    "moe_ffn")},
    # two latent attentions a published layer (flash at 64 heads of
    # 192 / 128 in the prefill, mla_decode in the step), one routed
    # branch (the grouped matmul at 6144 x 2048)
    "longcat-flash-omni": {
        "decode": ("kv_cache_write", "mla_decode", "moe_ffn"),
        "prefill": ("fused_attention", "kv_cache_write", "moe_ffn")},
    # every layer power retention: no cache write, no flash forward, no
    # expert: the first configuration that reaches neither
    "brumby-14b-base": {"decode": ("power_update",),
                        "prefill": ("power_scan",)},
    # nine delta layers (the in-place update, the chunked scan) beside
    # three gated attention layers of 16 heads over 2 of 256, the widest
    # head the flash forward is given, over a share of the experts
    "qwen3-next-80b-a3b": {
        "decode": ("delta_update", "kv_cache_write", "moe_ffn"),
        "prefill": ("causal_conv", "delta_scan", "fused_attention",
                    "kv_cache_write", "moe_ffn")},
    # 26 mamba layers (the in-place update whose decay is a block, the
    # scan that walks time inside the kernel) beside two attention layers
    # of 20 query heads over ONE key-value head: the widest group the
    # flash forward's grouped multi-pass plan is given
    "ai21-jamba2-3b": {
        "decode": ("kv_cache_write", "mamba_update"),
        "prefill": ("causal_conv", "fused_attention", "kv_cache_write",
                    "mamba_scan")},
}
KERNEL_OPS = frozenset(t for kinds in REACHES.values()
                       for types in kinds.values() for t in types)

# (configuration, op) whose kernel the ledger's ``breakdown.device_ops``
# names in that configuration's cells (PERF_LEDGER.jsonl, PR 42): there
# the plan may not be None. Elsewhere a plan is legal or None.
RUNS_ON_THE_CHIP = {
    ("bert-base", "fused_attention"),             # flash_fwd/refwd/bwd_*
    ("gpt2-medium", "kv_cache_write"),
    ("olmoe-1b-7b", "kv_cache_write"), ("olmoe-1b-7b", "moe_ffn"),
    ("trinity-large-preview", "fused_attention"),  # flash_fwd(_win)
    ("trinity-large-preview", "moe_ffn"),
    ("openpangu-ultra-moe-718b", "fused_attention"),
    ("openpangu-ultra-moe-718b", "mla_decode"),
    ("openpangu-ultra-moe-718b", "moe_ffn"),
    ("xing4.0-29b-a4b", "fused_attention"), ("xing4.0-29b-a4b", "mla_decode"),
    ("xing4.0-29b-a4b", "mhc_pre"), ("xing4.0-29b-a4b", "mhc_post"),
    ("xing4.0-29b-a4b", "moe_ffn"),
    ("nemotron-3-super-120b-a12b", "moe_ffn"),
    ("nemotron-3-super-120b-a12b", "ssm_scan"),
    ("nemotron-3-super-120b-a12b", "ssm_update"),
    # my chip runs, PR 44: flash_fwd 512x512, kv_cache_write pallas
    # rows=1, moe_gmm_up / moe_gmm_down in facts' plan counters
    ("lfm2-24b-a2b", "fused_attention"), ("lfm2-24b-a2b", "kv_cache_write"),
    ("lfm2-24b-a2b", "moe_ffn"),
    # my chip runs, PR 47: flash_fwd, mla_decode, moe_gmm_up / down in
    # the facts' plan counters and the device breakdown
    ("longcat-flash-omni", "fused_attention"),
    ("longcat-flash-omni", "mla_decode"), ("longcat-flash-omni", "moe_ffn"),
    # my chip runs, PR 51: power_scan pallas chunk=1024 and power_update
    # pallas in the facts' plan counter, both names in the device trace
    ("brumby-14b-base", "power_scan"), ("brumby-14b-base", "power_update"),
    # my chip runs, PR 53: delta_scan pallas chunk=64 and delta_update
    # pallas in the facts' plan counter, flash_fwd at a head of 256,
    # moe_gmm_up / moe_gmm_down, all in the device trace
    ("qwen3-next-80b-a3b", "delta_scan"),
    ("qwen3-next-80b-a3b", "delta_update"),
    ("qwen3-next-80b-a3b", "fused_attention"),
    ("qwen3-next-80b-a3b", "moe_ffn"),
    # my chip runs, PR 58: mamba_scan pallas block=256 and mamba_update
    # pallas in the facts' plan counter, flash_fwd at 20 heads over one,
    # all in the device trace
    ("ai21-jamba2-3b", "mamba_scan"), ("ai21-jamba2-3b", "mamba_update"),
    ("ai21-jamba2-3b", "fused_attention"),
    ("ai21-jamba2-3b", "kv_cache_write"),
    # my chip runs, PR 60: paddle_conv_plans_total pallas chunk=512
    # at every prompt of the mix (2,048-16,384), ``conv_prefill``
    # in the device trace
    ("ai21-jamba2-3b", "causal_conv"),
}


def _programs(config):
    """The program ids of one configuration, from the traffic files of
    its cells: ``train_s<S>`` a train cell, else ``decode`` and a
    ``prefill_p<P>`` a prompt length."""
    ids = []
    for w in MANIFEST["workloads"]:
        if w["config"] != config:
            continue
        traffic = _json("benchmarks", "traffic", w["traffic"] + ".json")
        if "seq" in traffic:
            ids.append("train_s%d" % traffic["seq"])
        else:
            ids.append("decode")
            ids += ["prefill_p%d" % int(p)
                    for p in sorted(traffic["prompt_lengths"], key=int)]
    return sorted(set(ids), key=lambda i: (i.split("_")[0], _length(i)))


def _length(program):
    """512 of ``train_s512`` / ``prefill_p512``; 0 of ``decode``."""
    return int("".join(c for c in program if c.isdigit()) or 0)


CASES = [(config, program, op)
         for config in sorted(REACHES) for program in _programs(config)
         for op in REACHES[config][program.split("_")[0]]]


@functools.lru_cache(maxsize=None)
def _built(config, program):
    """(the program's IR, the batch its ``-1`` stands for): built exactly
    as the benchmark's kinds build it, nothing lowered."""
    import paddle_tpu as fluid
    from paddle_tpu.models import bert, gpt

    conf = _json(CONFIG_FILES[config])
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        if program.startswith("train"):
            traffic = next(
                t for t in (_json("benchmarks", "traffic",
                                  w["traffic"] + ".json")
                            for w in MANIFEST["workloads"]
                            if w["config"] == config)
                if "train_s%d" % t["seq"] == program)
            loss, _ = bert.build(dict(conf["model"]), seq_len=traffic["seq"],
                                 max_mask=traffic["max_mask"])
            fluid.optimizer.Adam(
                learning_rate=conf["train"]["learning_rate"]).minimize(loss)
            return main, traffic["batch"]
        cfg = dict(gpt.base_config(), **conf["model"])
        b_max, max_len = conf["serving"]["b_max"], conf["serving"]["max_len"]
        if program == "decode":
            gpt.build_serving_decode_step(cfg, batch=b_max, max_len=max_len)
            return main, b_max
        gpt.build_prefill_step(cfg, batch=1,
                               prompt_len=_length(program),
                               max_len=max_len)
        return main, 1


def _operand(block, op, slot, batch):
    """(shape with the batch filled in, numpy dtype) of an op's input."""
    import jax.numpy as jnp

    var = block.vars[op.inputs[slot][0]]
    return (tuple(batch if d < 0 else int(d) for d in var.shape),
            jnp.dtype(var.dtype))


@pytest.fixture
def on_the_chip(monkeypatch):
    """The dispatch predicates as a TPU process evaluates them: kernels
    compile (no interpret mode) and the sequence threshold is the
    program's own, not the suite's "always the kernel"."""
    monkeypatch.setenv("PADDLE_TPU_FLASH_INTERPRET", "0")
    monkeypatch.delenv("PADDLE_TPU_FLASH_MIN_SEQ", raising=False)
    monkeypatch.delenv("PADDLE_TPU_KERNELS", raising=False)


# ------------------------------------------------------- one check an op
def _check_kv_cache_write(block, op, batch, must):
    from paddle_tpu.kernels import kv_cache_write as kvw
    from paddle_tpu.kernels.common import mosaic_ok

    shape, dtype = _operand(block, op, "Cache", batch)
    rows = _operand(block, op, "Update", batch)[0][2]
    per_slot = int(np.prod(_operand(block, op, "Pos", batch)[0])) > 1
    plan = kvw.write_plan(shape, dtype)
    if not (per_slot and rows == 1):
        # a prefill's slab write (or a scalar position): composed by the
        # dispatch's own rule, whatever the plan
        assert not must or rows > 1
        return "composed"
    if plan is None:
        assert not must, "no block plan for the cache %s %s" % (shape, dtype)
        return "composed"
    form, blk = plan
    B, H, S, D = shape
    seen = (B, H, D, S) if form == "cols" else shape
    assert form in ("rows", "cols") and mosaic_ok(blk, seen)
    assert all(a % b == 0 for a, b in zip(seen, blk)), (seen, blk)
    assert int(np.prod(blk)) * dtype.itemsize <= kvw._MAX_BLOCK_BYTES
    return form


def _check_moe_ffn(block, op, batch, must):
    from paddle_tpu.kernels import moe_gmm
    from paddle_tpu.kernels.common import ceil_to, mosaic_ok

    x_shape, _ = _operand(block, op, "X", batch)
    M = int(op.attrs["top_k"]) * int(np.prod(x_shape[:-1]))
    got = []
    for slot in ("W1", "W2"):
        (_E, K, N), dtype = _operand(block, op, slot, batch)
        plan = moe_gmm.gmm_plan(M, K, N, dtype.itemsize)
        assert plan is not None or not must, (M, K, N, dtype)
        if plan is None:
            continue
        tm, tk, tn = plan
        assert tm % 8 == 0 and tm <= 128
        # padding rhs would copy every expert's weights: whole divisors
        assert K % tk == 0 and N % tn == 0
        assert (tk % 128 == 0 or tk == K) and (tn % 128 == 0 or tn == N)
        assert mosaic_ok((1, tk, tn), (1, K, N))
        assert mosaic_ok((tm, tk), (ceil_to(M, tm), K))
        assert tk * tn * dtype.itemsize <= moe_gmm._MAX_RHS_BLOCK_BYTES
        assert moe_gmm._vmem_bytes(tm, tk, tn, dtype.itemsize) \
            <= moe_gmm._VMEM_LIMIT_BYTES
        got.append(plan)
    return got


def _check_mla_decode(block, op, batch, must):
    from paddle_tpu.kernels import mla_decode as K

    shape, dtype = _operand(block, op, "Cache", batch)
    H = _operand(block, op, "QNope", batch)[0][2]
    bs = K.decode_plan(shape, dtype, H)
    assert bs is not None or not must, (shape, dtype, H)
    if bs is not None:
        S, W = shape[2], shape[3]
        assert bs in K._BLOCK_CHOICES and S % bs == 0 and H % 8 == 0
        assert bs * -(-W // 128) * 128 * dtype.itemsize \
            <= K._MAX_BLOCK_BYTES
    return bs


def _check_mhc(block, op, batch, must):
    from paddle_tpu.kernels import mhc
    from paddle_tpu.kernels.common import ceil_to, mosaic_ok

    shape, _ = _operand(block, op, "X", batch)
    n, width, R = int(op.attrs["n"]), shape[-1], int(np.prod(shape[:-1]))
    # ``_takes_kernel`` reads x's shape alone
    takes = mhc._takes_kernel(types.SimpleNamespace(shape=(R, width)), n)
    assert takes or not must, (shape, n)
    if takes:
        tr = mhc.block_rows(R)
        assert tr % 8 == 0 and tr <= mhc._BLOCK_ROWS
        assert mosaic_ok((tr, width), (ceil_to(R, tr), width))
        # x in and out and the projected vector, float32, double-buffered
        assert 2 * tr * (2 * width + width // n) * 4 \
            <= mhc._VMEM_LIMIT_BYTES
    return takes


def _check_ssm_update(block, op, batch, must):
    from paddle_tpu.kernels import ssm
    from paddle_tpu.kernels.common import mosaic_ok

    shape, _ = _operand(block, op, "State", batch)
    blk = ssm._update_plan(shape)
    assert blk is not None or not must, shape
    if blk is not None:
        assert mosaic_ok(blk, shape)
        assert all(a % b == 0 for a, b in zip(shape, blk))
    return blk


def _check_ssm_scan(block, op, batch, must):
    from paddle_tpu.kernels import ssm

    (_B, T, HP), _ = _operand(block, op, "X", batch)
    H, G, N = (int(op.attrs[k]) for k in ("heads", "groups", "state"))
    chunk = int(op.attrs["chunk"])
    Q = ssm._scan_plan(T, H, HP // H, G, N, chunk)
    assert Q is not None or not must, (T, H, HP // H, G, N, chunk)
    if Q is not None:
        assert Q == chunk and Q % 128 == 0 and HP // H <= Q
    return Q


def _check_fused_attention(block, op, batch, must):
    """The forward's plan (banded and latent forms included) and, where
    the program holds the grad op, both backward kernels'."""
    import jax.numpy as jnp

    from paddle_tpu.ops import attention as A

    q, dtype = _operand(block, op, "Q", batch)
    k, _ = _operand(block, op, "K", batch)
    v, _ = _operand(block, op, "V", batch)
    attrs = op.attrs
    lanes, Dr = None, 0
    if len(q) == 3:                                  # [B, S, H*D]
        H = int(attrs["n_head"])
        S, Sk, D = q[1], k[1], q[2] // H
        Dv, Hkv = D, H
        if op.inputs.get("KR"):
            # the shared key part: k IS v, a head's keys beside its values
            Dr = _operand(block, op, "KR", batch)[0][2]
            Dv = k[2] // H - D
            assert k == v and Dv > 0
        assert A._lanes_ok(H, D)
        lanes = A._Lanes(D)
    else:
        _, H, S, D = q
        Hkv, Sk, Dv = k[1], k[2], v[3]
    causal = bool(attrs.get("causal", False))
    window = int(attrs.get("window", 0) or 0) or None
    if not A._flash_decision(S, Sk, attrs.get("flash_min_seq") or None):
        # under the threshold (256, latent attention's own 128): composed
        assert max(S, Sk) < (attrs.get("flash_min_seq") or 256)
        return "composed"
    assert H % Hkv == 0 and (not causal or S == Sk)
    if window is not None and window >= S:
        window = None
    Sp, Skp, bq, bk = A._forward_plan(S, Sk, D, dtype, causal, window)
    plans = {A.KERNEL_FWD: (Sp, Skp, bq, bk)}
    trains = any(o.type == "fused_attention_grad" for o in block.ops)
    if trains:
        for kern in (A.KERNEL_BWD_DKV, A.KERNEL_BWD_DQ):
            plans[kern] = A._padded_plan(kern, S, Sk, D, dtype, causal,
                                         False)
    for kern, (Sp, Skp, bq, bk) in plans.items():
        # pads to lane tiles, or (the causal forward) to whole 512 blocks
        most = A._MAX_BLOCK if causal and kern == A.KERNEL_FWD else A._LANE
        assert 0 <= Sp - S < most and 0 <= Skp - Sk < most, (kern, Sp, Skp)
        assert Sp % bq == 0 and Skp % bk == 0, (kern, bq, bk)
        assert bq % 8 == 0 and (bk % A._LANE == 0 or bk == Skp)
        # one float32 score tile: what every kernel's VMEM account counts
        assert bq * bk <= A._MAX_BLOCK * A._MAX_BLOCK
        if window is not None and kern == A.KERNEL_FWD:
            assert bk <= A._pad_len(window, A._LANE)
    # heads a grid step: the forward's one rule at the call's own shapes,
    # and what such a step holds against the VMEM it is compiled under
    Sp, Skp, bq, bk = plans[A.KERNEL_FWD]
    group, single = H // Hkv, Skp == bk
    itemsize = jnp.dtype(attrs.get("mxu_dtype") or dtype).itemsize
    shape = dict(lanes=lanes, Dr=Dr)
    heads = A._forward_heads(H, group, bq, bk, single, None, D, Dv,
                             itemsize, dtype.itemsize, **shape)
    assert (group if group > 1 else H) % heads == 0
    assert 1 <= heads <= A._HEADS_PER_STEP
    assert lanes is None or heads % lanes.per == 0
    held = A._forward_vmem(heads, bq, bk, single, D, Dv, itemsize,
                           dtype.itemsize, one_kv=group > 1, **shape)
    assert held <= A._VMEM_LIMIT_BYTES, (heads, held)
    return plans


def _check_power_update(block, op, batch, must):
    from paddle_tpu.kernels import power
    from paddle_tpu.kernels.common import mosaic_ok

    shape, _ = _operand(block, op, "State", batch)
    norm, _ = _operand(block, op, "Norm", batch)
    takes = power._update_plan(shape, int(op.attrs["heads"]))
    assert takes or not must, shape
    if takes:
        # a (slot, key-value head) block of the state and of the normaliser
        assert mosaic_ok((1, 1) + shape[2:], shape)
        assert mosaic_ok((1, 1) + norm[2:], norm)
        assert 4 * int(np.prod(shape[2:])) * 4 <= power._VMEM_LIMIT_BYTES
    return takes


def _check_power_scan(block, op, batch, must):
    from paddle_tpu.kernels import power

    (_B, T, HD), _ = _operand(block, op, "Q", batch)
    H, G = int(op.attrs["heads"]), int(op.attrs["groups"])
    chunk = power.scan_chunk(T)
    Q = power._scan_plan(H, G, HD // H, chunk)
    assert Q is not None or not must, (T, H, G, HD // H, chunk)
    if Q is not None:
        # every prompt of the cell is whole chunks: nothing is padded
        assert Q == chunk and Q % 128 == 0 and T % Q == 0
        # what a grid step holds in VMEM: the kernel's scratch (state,
        # normaliser, denominators, the read's accumulator, phi of one
        # tile column) and every block twice (queries and output [J, D,
        # Q], k and v both ways round, four gate sums a lane or sublane
        # tile wide, the state and the normaliser as outputs)
        J, D = H // G, HD // H
        scratch = power._scan_scratch(J, Q)
        R = power.phi_plan(D)[2]
        assert scratch[0] == (R, D) and scratch[-1] == (16 * D, Q)
        blocks = 2 * J * D * Q + 4 * Q * D + 2 * Q * 128 + 2 * 8 * Q \
            + R * D + D * D
        held = 4 * (sum(int(np.prod(s)) for s in scratch) + 2 * blocks)
        assert held <= power._VMEM_LIMIT_BYTES // 2, held
    return Q


def _check_delta_update(block, op, batch, must):
    from paddle_tpu.kernels import delta
    from paddle_tpu.kernels.common import mosaic_ok

    shape, _ = _operand(block, op, "State", batch)
    takes = delta._update_plan(shape)
    assert takes or not must, shape
    if takes:
        # all of a slot's heads a step, their keys and queries the
        # columns of ONE lane tile; in and out, double-buffered
        Hv, Dk, Dv = shape[1:]
        assert takes == (1,) + tuple(shape[1:]) and 2 * Hv <= 128
        assert mosaic_ok(takes, shape)
        assert mosaic_ok((1, 3 * Hv, Dv), (shape[0], 3 * Hv, Dv))
        assert mosaic_ok((1, Dk, 128), (shape[0], Dk, 128))
        held = 4 * (4 * Hv * Dk * Dv + 2 * (3 * Hv * Dv + Dk * 128
                                            + Hv * Dv))
        assert held <= delta._VMEM_LIMIT_BYTES // 2, held
    return takes


def _check_delta_scan(block, op, batch, must):
    from paddle_tpu.kernels import delta

    (_B, T, KD), _ = _operand(block, op, "Q", batch)
    (_B, _T, VD), _ = _operand(block, op, "V", batch)
    Hk, Hv = int(op.attrs["k_heads"]), int(op.attrs["v_heads"])
    Q = delta.scan_chunk(T)
    takes = delta._scan_plan(Hk, KD // Hk, Hv, VD // Hv, Q)
    assert takes or not must, (T, Hk, Hv, KD // Hk, VD // Hv, Q)
    if takes:
        # every prompt of the cell is whole chunks: nothing is padded
        assert Q == delta.CHUNK and T % Q == 0
        J, Dk, Dv = Hv // Hk, KD // Hk, VD // Hv
        # q, k, k turned, v and y of a chunk, the narrow operands padded
        # to a lane tile, double-buffered; the states in scratch and out;
        # a handful of [Q, Q] and [Q, Dv] temporaries a head
        blocks = 3 * Q * Dk + 2 * J * Q * Dv + 3 * 8 * 128 + Q * 128
        held = 4 * (2 * blocks + 3 * J * Dk * Dv + 8 * Q * Q + 6 * Q * Dv)
        assert held <= delta._VMEM_LIMIT_BYTES // 2, held
    return takes


def _check_mamba_update(block, op, batch, must):
    from paddle_tpu.kernels import mamba
    from paddle_tpu.kernels.common import mosaic_ok

    shape, _ = _operand(block, op, "State", batch)
    tile = mamba._update_plan(shape)
    assert tile or not must, shape
    if tile:
        # a slot's [N, tile] of the state in and out and of A^T, the
        # token's rows [8, tile] and y [1 -> 8, tile], double-buffered
        B, G, N, C = shape
        assert G == 1 and C % tile == 0
        assert mosaic_ok((1, 1, N, tile), shape)
        assert mosaic_ok((N, tile), (N, C))
        assert mosaic_ok((1, 8, tile), (B, 8, C))
        assert mosaic_ok((1, N, 8), (B, N, 8))
        held = 4 * 2 * (3 * N * tile + 2 * 8 * tile + N * 128)
        assert held <= mamba._VMEM_LIMIT_BYTES // 2, held
    return tile


def _check_mamba_scan(block, op, batch, must):
    from paddle_tpu.kernels import mamba

    (_B, T, C), _ = _operand(block, op, "X", batch)
    (_C, N), _ = _operand(block, op, "ALog", batch)
    takes = mamba._scan_plan(T, C, N)
    assert takes or not must, (T, C, N)
    if takes:
        Q, lanes, unroll = takes
        # every prompt of the cell is whole blocks: nothing is padded
        assert Q == mamba.BLOCK and T % Q == 0 and Q % unroll == 0
        assert C % (8 * lanes) == 0 and N * lanes // 128 <= 80
        # u, dt and y of a block of positions, double-buffered, A^T's
        # tile twice, the state in scratch and out; B and C in SMEM
        held = 4 * (2 * 3 * Q * 8 * lanes + 4 * N * 8 * lanes)
        assert held <= mamba._VMEM_LIMIT_BYTES // 2, held
        assert 4 * 2 * 2 * N * Q <= 1 << 18        # SMEM: 256 KiB
    return takes


def _check_causal_conv(block, op, batch, must):
    from paddle_tpu.kernels import ssm
    from paddle_tpu.kernels.common import mosaic_ok

    (B, T, Cx), dtype = _operand(block, op, "X", batch)
    (C, K), _ = _operand(block, op, "W", batch)
    lo, hi = op.attrs.get("columns", (0, Cx))
    assert hi - lo == C and dtype == np.float32
    plan = ssm._conv_plan(T, C, K, lo, in_place="columns" in op.attrs)
    assert plan is not None or not must, (T, C, K, lo)
    if plan is not None:
        Q, tile = plan
        assert T >= Q and Q % 8 == 0 and tile % 128 == 0
        assert C % tile == 0 and lo % tile == 0
        assert mosaic_ok((1, Q, tile), (B, T, Cx))
        assert mosaic_ok((1, Q, tile), (B, T, C))
        # a block of x and of out, double-buffered; taps and bias twice;
        # the seam
        assert 4 * (4 * Q * tile + 2 * 8 * tile + 16 * tile) \
            <= ssm._VMEM_LIMIT_BYTES // 2
    return plan


CHECKS = {
    "causal_conv": _check_causal_conv,
    "mamba_scan": _check_mamba_scan, "mamba_update": _check_mamba_update,
    "delta_scan": _check_delta_scan, "delta_update": _check_delta_update,
    "fused_attention": _check_fused_attention,
    "kv_cache_write": _check_kv_cache_write,
    "mhc_post": _check_mhc, "mhc_pre": _check_mhc,
    "mla_decode": _check_mla_decode,
    "moe_ffn": _check_moe_ffn,
    "ssm_scan": _check_ssm_scan, "ssm_update": _check_ssm_update,
    "power_scan": _check_power_scan, "power_update": _check_power_update,
}


@pytest.mark.parametrize("config,program,op_type", CASES,
                         ids=["%s-%s-%s" % c for c in CASES])
def test_every_cell_shape_has_a_legal_plan(config, program, op_type,
                                           on_the_chip):
    main, batch = _built(config, program)
    block = main.global_block()
    kind = program.split("_")[0]
    # the program reaches the kernels its row says, and no other
    assert tuple(sorted({op.type for op in block.ops} & KERNEL_OPS)) \
        == REACHES[config][kind]
    ops = [op for op in block.ops if op.type == op_type]
    must = (config, op_type) in RUNS_ON_THE_CHIP
    got = [CHECKS[op_type](block, op, batch, must) for op in ops]
    if op_type == "kv_cache_write":
        # a prefill writes its slab composed; the decode step writes one
        # row a slot, the kernel's case
        if kind == "prefill":
            assert all(g == "composed" for g in got)
        elif must:
            assert all(g in ("rows", "cols") for g in got)
    if op_type == "fused_attention" and must:
        # the kernel runs at every prompt of the cell's traffic that
        # reaches its threshold, and at the S 512 cell; S 128 is composed
        S = _length(program)
        least = 128 if "flash_min_seq" in ops[0].attrs else 256
        assert all((g == "composed") == (S < least) for g in got)


def test_the_case_list_covers_every_configuration_of_the_manifest():
    assert sorted(REACHES) == sorted(CONFIG_FILES)
    assert {c for c, _p, _o in CASES} == set(CONFIG_FILES)
    # every cell's traffic contributed its programs
    for w in MANIFEST["workloads"]:
        assert _programs(w["config"])


# ------------------------------------- the prompt's convolution (PR 60)
@pytest.mark.parametrize("T,C,K,lo,in_place,takes", [
    (16384, 5120, 4, 0, True, True),     # Jamba's longest prompt
    (2048, 5120, 4, 0, True, True),      # and its shortest
    (512, 5120, 4, 0, True, True),       # exactly one block
    (511, 5120, 4, 0, True, False),      # a prompt under one block
    (2048, 10240, 4, 0, False, False),   # Nemotron's longest, cut out of
    (2048, 8192, 4, 0, False, False),    # proj; Qwen3-Next's q, k, v
    (16384, 2048, 3, 0, False, False),   # LFM2: a product, three taps
    (16384, 2048, 3, 0, True, True),     # (were it read in place)
    (4096, 5000, 4, 0, True, False),     # no whole number of lane tiles
    (4096, 5120, 4, 64, True, False),    # columns that start inside one
    (4096, 5120, 9, 0, True, False),     # more taps than the seam holds
    (4096, 5120, 1, 0, True, False),     # no past to carry
    (4096, 384, 4, 128, True, True),     # a tile as narrow as the offset
])
def test_the_prompt_convolution_takes_the_shapes_it_says(T, C, K, lo,
                                                         in_place, takes):
    from paddle_tpu.kernels import ssm

    plan = ssm._conv_plan(T, C, K, lo, in_place)
    assert (plan is not None) == takes
    if plan is not None:
        Q, tile = plan
        assert Q == ssm._CONV_BLOCK and T >= Q
        assert tile % 128 == 0 and tile <= ssm._CONV_TILE
        assert C % tile == 0 and lo % tile == 0
        # the widest tile the rule allows: nothing wider divides both
        assert not [t for t in range(tile + 128, ssm._CONV_TILE + 1, 128)
                    if C % t == 0 and lo % t == 0]


def _conv_counts():
    from paddle_tpu.observe import REGISTRY

    got = REGISTRY.snapshot()["metrics"].get(
        "paddle_conv_plans_total", {"samples": []})
    return {(s["labels"]["kernel"], s["labels"]["chunk"]): s["value"]
            for s in got["samples"]}


@pytest.mark.parametrize("where,T,C,dtype,columns,want", [
    ("cpu", 1024, 256, "float32", True, ("composed", "0")),
    ("chip", 1024, 256, "float32", True, ("pallas", "512")),
    ("chip", 1024, 256, "float32", False, ("composed", "0")),
    ("chip", 16384, 256, "float32", False, ("composed", "0")),
    ("chip", 1024, 200, "float32", True, ("composed", "0")),
    ("chip", 300, 256, "float32", True, ("composed", "0")),
    ("chip", 1024, 256, "bfloat16", True, ("composed", "0")),
    ("chip_kernels_off", 1024, 256, "float32", True, ("composed", "0")),
])
def test_a_convolutions_lowering_counts_the_form_it_took(
        where, T, C, dtype, columns, want, monkeypatch):
    """``paddle_conv_plans_total``: one count a lowering, under
    the form and the block ``conv_prefill`` chose from the shapes — as the
    CPU decides and as a TPU process does (traced only: nothing runs)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import ssm

    if where != "cpu":
        monkeypatch.setenv("PADDLE_TPU_FLASH_INTERPRET", "0")
    if where == "chip_kernels_off":
        monkeypatch.setenv("PADDLE_TPU_KERNELS", "0")
    else:
        monkeypatch.delenv("PADDLE_TPU_KERNELS", raising=False)
    before = _conv_counts()
    x = jax.ShapeDtypeStruct((2, T, 2 * C if columns else C),
                             jnp.dtype(dtype))
    w = jax.ShapeDtypeStruct((C, 4), jnp.float32)
    b = jax.ShapeDtypeStruct((C,), jnp.float32)
    out, rows = jax.eval_shape(
        lambda x, w, b: ssm.conv_prefill(
            x, w, b, columns=(C, 2 * C) if columns else None), x, w, b)
    assert out.shape == (2, T, C) and rows.shape == (2, 3, C)
    assert out.dtype == rows.dtype == jnp.float32
    after = _conv_counts()
    assert {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)} == {want: 1}
