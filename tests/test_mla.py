"""openPangu-Ultra-MoE's layer (``model_type`` pangu_ultra_moe) through
the system's normal path, against the plain reference
(tests/references/pangu_mla.py): latent attention with a one-tensor
latent cache — expanded in the training build and the prefill, absorbed in
the decode steps (op ``mla_decode``) — sandwich norm, sigmoid top-k
routing over one chip's share of the experts plus a shared expert, and
matrices stored in bfloat16 beside float32 activations."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models import gpt

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path):
    spec = importlib.util.spec_from_file_location(
        "ref_" + os.path.basename(path).replace("-", "_")[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load(os.path.join(HERE, "references", "pangu_mla.py"))


def tiny_cfg(**over):
    """Hidden 48, 4 heads of 16 + 8 (q/k) and 16 (v) over a latent of 32
    (queries through a latent of 24), one dense layer then three expert
    layers of 16 experts of width 24 top-4 with one shared, vocabulary
    97."""
    cfg = dict(d_model=48, n_head=4, n_layer=4, vocab=97, max_length=64,
               dropout=0.0, pos_emb="rope", rope_theta=10000.0,
               norm="rms", norm_eps=1e-5, attn="mla", q_lora_rank=24,
               kv_lora_rank=32, d_nope=16, d_rope=8, d_v=16,
               sandwich_norm=True, ffn_act="swiglu", d_ff=96,
               n_dense_layer=1, n_expert=16, expert_top_k=4, d_expert=24,
               n_shared_expert=1, router_score="sigmoid", norm_topk=True,
               route_scale=2.5)
    cfg.update(over)
    return cfg


def _bf16_valued(a):
    return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))


def seeded_params(cfg, seed, bf16_valued=False):
    """Every parameter drawn from the seed, float32 arrays: matrices
    within Xavier limits (``bf16_valued``: each rounded to a value
    bfloat16 holds), every norm scale uniform in 0.5-1.5."""
    cfg = {k: v for k, v in cfg.items() if k != "weight_dtype"}
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        gpt.build_serving_decode_step(cfg, batch=1, max_len=16)
    rng = np.random.default_rng(seed)
    out = {}
    for p in sorted(prog.global_block().all_parameters(),
                    key=lambda p: p.name):
        shape = tuple(p.shape)
        if len(shape) == 1:
            out[p.name] = rng.uniform(0.5, 1.5, shape).astype("float32")
        else:
            lim = (6.0 / (shape[-2] + shape[-1])) ** 0.5
            w = rng.uniform(-lim, lim, shape).astype("float32")
            out[p.name] = _bf16_valued(w) if bf16_valued else w
    return out


def _ref_logits(params, cfg, ids):
    return np.asarray(reference.forward(params, cfg, jnp.asarray(ids)))


def test_check_cfg_knows_the_new_keys():
    gpt._check_cfg(tiny_cfg())
    gpt._check_cfg(tiny_cfg(weight_dtype="bfloat16"))
    for bad, match in (
            (dict(q_lora_rank=None), "q_lora_rank"),
            (dict(attn="latent"), "attn"),
            (dict(weight_dtype="float16"), "weight_dtype"),
            (dict(kv_lora_rank=0), "kv_lora_rank"),
            (dict(d_rope=7), "even"),
            (dict(pos_emb="learned"), "rope"),
            (dict(n_kv_head=2), "n_kv_head"),
            (dict(attn_gate=True), "attn_gate"),
            (dict(layer_types=["full"] * 4), "layer_types")):
        with pytest.raises(ValueError, match=match):
            gpt._check_cfg(tiny_cfg(**bad))
    plain = dict(d_model=32, d_ff=64, n_head=4, n_layer=1, vocab=50,
                 max_length=16, dropout=0.0)
    for key in gpt._MLA_KEYS:
        with pytest.raises(ValueError, match="needs cfg\\['attn'\\]"):
            gpt._check_cfg(dict(plain, **{key: 8}))
    with pytest.raises(ValueError, match="serving programs"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            gpt.build(tiny_cfg(weight_dtype="bfloat16"), seq_len=8)


def test_every_parameter_is_named_and_has_no_bias():
    cfg = tiny_cfg()
    p = seeded_params(cfg, 0)
    want = {"gpt_word_emb", "gpt_out_proj.w_0", "gpt_ln_f_s"}
    for i in range(4):
        nm = "gpt_%d_" % i
        want |= {nm + s for s in reference.LAYER_PARAMS["attn"]}
        want |= {nm + s for s in reference.LAYER_PARAMS[
            "dense" if i == 0 else "moe"]}
    assert set(p) == want
    assert p["gpt_1_att_qa.w_0"].shape == (48, 24)
    assert p["gpt_1_att_qb.w_0"].shape == (24, 4 * 24)
    assert p["gpt_1_att_kva.w_0"].shape == (48, 40)
    assert p["gpt_1_att_kva_ln_s"].shape == (32,)
    assert p["gpt_1_att_kvb.w_0"].shape == (32, 4 * 32)
    assert p["gpt_1_att_o.w_0"].shape == (64, 48)


@pytest.mark.parametrize("S,q_rank", [(6, 24), (20, 24), (9, 16)])
def test_training_build_logits_match_reference(S, q_rank):
    """The training build composes the expanded form, from the
    parameters the serving programs name."""
    cfg = tiny_cfg(q_lora_rank=q_rank)
    params = seeded_params(cfg, 3)
    prog, start = fluid.Program(), fluid.Program()
    scope = fluid.core.scope.Scope()
    with fluid.core.scope.scope_guard(scope):
        with fluid.program_guard(prog, start):
            gpt.build(cfg, seq_len=S, is_test=True)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(start, scope=scope)
        for n, v in params.items():
            assert scope.find_var(n) is not None, n
            scope.set_var(n, v)
        (ce,) = [op for op in prog.global_block().ops
                 if op.type == "softmax_with_cross_entropy"]
        ids = np.random.default_rng(5).integers(1, 97, (2, S))
        (got,) = exe.run(prog, feed={"ids": ids.astype("int64")},
                         fetch_list=[ce.inputs["Logits"][0]], scope=scope)
    for b in range(2):
        np.testing.assert_allclose(got[b], _ref_logits(params, cfg, ids[b]),
                                   atol=1e-4, rtol=0)


def test_training_build_has_gradients_for_every_parameter():
    cfg = tiny_cfg()
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        loss, _ = gpt.build(cfg, seq_len=12)
        fluid.optimizer.SGD(0.1).minimize(loss)
    scope = fluid.core.scope.Scope()
    with fluid.core.scope.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(start, scope=scope)
        before = {p.name: np.array(scope.find_var(p.name))
                  for p in prog.global_block().all_parameters()}
        ids = np.random.default_rng(9).integers(1, 97, (3, 12))
        exe.run(prog, feed={"ids": ids.astype("int64")},
                fetch_list=[loss], scope=scope)
        still = [n for n, v in before.items()
                 if np.array_equal(v, np.asarray(scope.find_var(n)))]
    assert not still, still


def _engine(cfg, params, b_max, max_len=64, **kw):
    from paddle_tpu.serving import DecodeEngine

    return DecodeEngine(cfg, params=params, b_max=b_max, max_len=max_len,
                        **kw)


def _decode_in_company(eng, prompts, n_new, slots=None):
    """Prefill each prompt into its slot, then decode ``n_new`` greedy
    tokens with all slots riding the same steps. Returns per slot
    (tokens, the logits row that chose each generated token)."""
    lane = eng._lane
    slots = list(range(len(prompts))) if slots is None else slots
    toks = [list(p) for p in prompts]
    rows = [[] for _ in prompts]
    for s, p in zip(slots, prompts):
        _, last = lane.prefill_insert(s, np.asarray(p, "int64"))
        rows[slots.index(s)].append(np.asarray(last))
        toks[slots.index(s)].append(int(np.argmax(last)))
    for _ in range(n_new - 1):
        token = np.zeros((eng.b_max, 1), "int64")
        pos = np.zeros((eng.b_max, 1), "int64")
        for s, t in zip(slots, toks):
            token[s, 0], pos[s, 0] = t[-1], len(t) - 1
        logits = lane.decode(token, pos)
        for j, s in enumerate(slots):
            rows[j].append(np.asarray(logits[s, 0]))
            toks[j].append(int(np.argmax(logits[s, 0])))
    return toks, rows


def _assert_matches_reference(cfg, params, prompts, toks, rows, atol=1e-4):
    for p, t, r in zip(prompts, toks, rows):
        want = _ref_logits(params, cfg, np.asarray(t[:-1]))
        np.testing.assert_allclose(np.stack(r), want[len(p) - 1:],
                                   atol=atol, rtol=0)


def test_prefill_then_decode_through_the_latent_cache():
    """Prefill (expanded) then cached decode (absorbed), four slots in
    company, against the reference's full forward on logits. Tolerance
    1e-4 absolute on logits of magnitude ~1: both sides are float32 at
    the highest matmul precision here, and the absorbed form reorders
    two contractions (q W_uk^T . c instead of q . c W_uk), which moves
    the last few bits only."""
    cfg = tiny_cfg()
    params = seeded_params(cfg, 7)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 97, n) for n in (5, 8, 13, 19)]
    eng = _engine(cfg, params, 4)
    lane = eng._lane
    assert lane.cache_names == ["gpt_%d_cache_c" % i for i in range(4)]
    for n in lane.cache_names:       # ONE tensor a layer: c | k_r
        assert np.asarray(lane.scope.find_var(n)).shape == (4, 1, 64, 40)
    toks, rows = _decode_in_company(eng, prompts, 24)
    _assert_matches_reference(cfg, params, prompts, toks, rows)
    tally = eng.routed_pairs()
    assert tally.sum(axis=1).tolist() == [0] + [23 * 4 * 4] * 3


def test_a_readmitted_slot_sees_no_row_of_its_previous_tenant():
    cfg = tiny_cfg()
    params = seeded_params(cfg, 13)
    rng = np.random.default_rng(17)
    eng = _engine(cfg, params, 2)
    _decode_in_company(eng, [rng.integers(1, 97, 19)], 12, slots=[1])
    _decode_in_company(eng, [rng.integers(1, 97, 21)], 2, slots=[0])
    new = [rng.integers(1, 97, 3)]
    toks, rows = _decode_in_company(eng, new, 16, slots=[1])
    _assert_matches_reference(cfg, params, new, toks, rows)


def test_expanded_and_absorbed_forms_agree():
    """The same positions once through the prefill (expanded) and once
    through decode steps over the latent cache (absorbed)."""
    cfg = tiny_cfg()
    params = seeded_params(cfg, 19)
    ids = np.random.default_rng(23).integers(1, 97, 20)
    eng = _engine(cfg, params, 1)
    lane = eng._lane
    absorbed = []
    lane.prefill_insert(0, ids[:4].astype("int64"))
    for t in range(4, 20):
        logits = lane.decode(ids[t:t + 1].reshape(1, 1).astype("int64"),
                             np.full((1, 1), t, "int64"))
        absorbed.append(np.asarray(logits[0, 0]))
    for t in (9, 15, 19):
        _, last = _engine(cfg, params, 1)._lane.prefill_insert(
            0, ids[:t + 1].astype("int64"))
        np.testing.assert_allclose(np.asarray(last), absorbed[t - 4],
                                   atol=1e-4, rtol=0)


def test_engine_is_bitwise_generate_for_greedy_riders():
    """With one step in flight: the engine's tokens are ``generate()``'s
    over the same programs, rider by rider."""
    from paddle_tpu.observe.families import SERVING_STEP_DISPATCHES

    cfg = tiny_cfg()
    params = seeded_params(cfg, 29)
    rng = np.random.default_rng(31)
    prompts = [rng.integers(1, 97, n) for n in (4, 11, 20, 7)]
    ahead = SERVING_STEP_DISPATCHES.labels(dispatch="ahead")
    before = ahead.value
    eng = _engine(cfg, params, 4).start()
    try:
        got = [r.result(timeout=300) for r in
               [eng.submit(np.asarray(p, "int64"), 12) for p in prompts]]
    finally:
        eng.stop()
    assert ahead.value > before
    for p, g in zip(prompts, got):
        scope = fluid.core.scope.Scope()
        with fluid.core.scope.scope_guard(scope):
            dec, dstart = fluid.Program(), fluid.Program()
            with fluid.program_guard(dec, dstart):
                logits, _ = gpt.build_decode_step(cfg, batch=1, max_len=64)
            pre, pstart = fluid.Program(), fluid.Program()
            with fluid.program_guard(pre, pstart):
                plogits, _ = gpt.build_prefill_step(
                    cfg, batch=1, prompt_len=len(p), max_len=64)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(dstart, scope=scope)
            exe.run(pstart, scope=scope)
            for n, v in params.items():
                scope.set_var(n, v)
            want = gpt.generate(exe, dec, logits, p[None].astype("int64"),
                                12, scope, prefill_prog=pre,
                                prefill_logits=plogits)
        assert g.tolist() == want[0].tolist()


def test_generate_refuses_to_run_past_the_latent_cache():
    cfg = tiny_cfg()
    scope = fluid.core.scope.Scope()
    with fluid.core.scope.scope_guard(scope):
        dec, dstart = fluid.Program(), fluid.Program()
        with fluid.program_guard(dec, dstart):
            logits, names = gpt.build_decode_step(cfg, batch=1, max_len=16)
        assert names == ["gpt_%d_cache_c" % i for i in range(4)]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(dstart, scope=scope)
        with pytest.raises(ValueError, match="max_len=16"):
            gpt.generate(exe, dec, logits, np.ones((1, 10), "int64"), 8,
                         scope)


# ------------------------------------------------- matrices in bfloat16
def test_bf16_stored_program_holds_bf16_matrices_and_float32_else():
    cfg = tiny_cfg(weight_dtype="bfloat16")
    for build in (
            lambda: gpt.build_serving_decode_step(cfg, batch=2, max_len=16),
            lambda: gpt.build_prefill_step(cfg, batch=1, prompt_len=8,
                                           max_len=16)):
        prog, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, start):
            build()
        block = prog.global_block()
        params = {p.name: p for p in block.all_parameters()}
        for name, p in params.items():
            assert p.dtype == ("bfloat16" if len(p.shape) >= 2
                               else "float32"), (name, p.dtype)
        assert params["gpt_1_moe_gate.w_0"].dtype == "bfloat16"
        assert params["gpt_word_emb"].dtype == "bfloat16"
        rows = {n for op in block.ops if op.type == "lookup_table"
                for n in op.outputs["Out"]}      # widened by the next op
        others = [v for n, v in block.vars.items()
                  if n not in params and n not in rows
                  and v.dtype in ("float32", "bfloat16", "float16")]
        assert others and all(v.dtype == "float32" for v in others), \
            [(v.name, v.dtype) for v in others if v.dtype != "float32"]
        # the startup program draws them in the dtype they are stored in
        scope = fluid.core.scope.Scope()
        with fluid.core.scope.scope_guard(scope):
            fluid.Executor(fluid.CPUPlace()).run(start, scope=scope)
            assert jnp.asarray(scope.find_var(
                "gpt_1_att_kvb.w_0")).dtype == jnp.bfloat16
            assert jnp.asarray(scope.find_var(
                "gpt_1_att_kva_ln_s")).dtype == jnp.float32
            assert jnp.asarray(scope.find_var(
                "gpt_0_cache_c")).dtype == jnp.float32


def test_bf16_stored_program_answers_as_float32_over_the_same_values():
    """bf16-stored matrices widened where they multiply = the float32
    program over the same bfloat16-valued weights, on logits, through
    prefill and cached decode; and both are the reference's."""
    f32 = tiny_cfg()
    stored = tiny_cfg(weight_dtype="bfloat16")
    params = seeded_params(f32, 37, bf16_valued=True)
    narrow = {n: jnp.asarray(v, jnp.bfloat16) if v.ndim >= 2 else v
              for n, v in params.items()}
    rng = np.random.default_rng(41)
    prompts = [rng.integers(1, 97, n) for n in (6, 17)]
    toks_a, rows_a = _decode_in_company(_engine(f32, params, 2), prompts, 12)
    eng = _engine(stored, narrow, 2)
    for n, v in narrow.items():
        assert jnp.asarray(eng._lane.scope.find_var(n)).dtype == v.dtype, n
    toks_b, rows_b = _decode_in_company(eng, prompts, 12)
    assert toks_a == toks_b
    for ra, rb in zip(rows_a, rows_b):
        np.testing.assert_allclose(np.stack(ra), np.stack(rb), atol=1e-5,
                                   rtol=0)
    # the reference takes the narrow arrays themselves
    _assert_matches_reference(stored, narrow, prompts, toks_b, rows_b)


def test_weight_and_cache_bytes_are_counted_by_dtype_and_kind():
    from paddle_tpu.observe import REGISTRY

    cfg = tiny_cfg(weight_dtype="bfloat16")
    eng = _engine(cfg, None, 2, max_len=32)
    snap = REGISTRY.snapshot()["metrics"]
    cache = {s["labels"]["kind"]: s["value"]
             for s in snap["paddle_serving_cache_bytes"]["samples"]}
    assert cache["latent"] == 4 * 2 * 32 * 40 * 4
    assert cache["ring"] == 0 and cache["full"] == 0
    held = {s["labels"]["dtype"]: s["value"]
            for s in snap["paddle_serving_weight_bytes"]["samples"]}
    block = eng._lane._decode_prog.global_block()
    mats = sum(int(np.prod(p.shape)) for p in block.all_parameters()
               if len(p.shape) >= 2)
    vecs = sum(int(np.prod(p.shape)) for p in block.all_parameters()
               if len(p.shape) == 1)
    assert held["bfloat16"] == 2 * mats and held["float32"] == 4 * vecs
    # the admission guard's byte model follows the stored dtype
    wide = _engine(tiny_cfg(), None, 2, max_len=32)
    saved = wide._mem["resident"] - eng._mem["resident"]
    assert 0.9 * 2 * mats <= saved <= 1.1 * 2 * mats


# --------------------------------------------------------- the kernels
MLA_DECODE_CASES = [
    # B, H, S, d_c, d_r, positions
    (3, 8, 256, 128, 64, (0, 130, 255)),          # rows layout, one block
    (2, 16, 512, 64, 8, (5, 511)),                # S-minor layout
    (2, 8, 1024, 512, 64, (300, 1023)),           # the published widths
    (4, 8, 128, 32, 8, (0, 1, 64, 127)),          # one block
    # the work list's edges (PR 48), three blocks of 128 unless said:
    (3, 8, 384, 128, 64, (127, 128, 383)),        # bs - 1, bs, S - 1
    (4, 8, 384, 64, 8, (0, 0, 0, 0)),             # total == B
    (3, 8, 384, 64, 8, (383, 383, 383)),          # total == B x nblk
    (3, 8, 640, 128, 64, (600, 0, 639)),          # a free slot between
    (2, 8, 384, 512, 64, (200, 383)),             # Xing's odd count, scaled
    (3, 8, 1536, 512, 64, (511, 512, 1100)),      # 512-row blocks' edges
    (5, 16, 768, 64, 8, (255, 767, 0, 256, 511)),  # 256-row blocks, S-minor
]


@pytest.mark.parametrize("B,H,S,dc,dr,at", MLA_DECODE_CASES)
def test_mla_decode_kernel_matches_composed(B, H, S, dc, dr, at):
    """The Pallas kernel (interpret mode) against the composed form and
    against the definition; the kernel rounds its operands to bfloat16
    for the MXU, so the comparison is on bfloat16-valued operands, where
    only the rounding of p is left: 2e-2 of values ~1."""
    from paddle_tpu.kernels import mla_decode as K

    rs = np.random.RandomState(S + dc)
    q = _bf16_valued(rs.randn(B, H, dc + dr).astype("float32"))
    cache = _bf16_valued(rs.randn(B, 1, S, dc + dr).astype("float32"))
    pos = jnp.asarray(at, jnp.int32)
    scale = (dc + dr) ** -0.5
    assert K.decode_plan(cache.shape, cache.dtype, H) in (128, 256, 512)
    got = K.mla_decode_pallas(jnp.asarray(q), jnp.asarray(cache), pos,
                              d_c=dc, scale=scale, interpret=True)
    want = K.mla_decode_composed(jnp.asarray(q), jnp.asarray(cache), pos,
                                 d_c=dc, scale=scale)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=0)
    for b, p in enumerate(at):
        s = np.einsum("hw,sw->hs", q[b], cache[b, 0, :p + 1]) * scale
        w = np.exp(s - s.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        np.testing.assert_allclose(want[b], w @ cache[b, 0, :p + 1, :dc],
                                   atol=1e-5, rtol=0)


WORK_LIST_CASES = [
    # bs, nblk, positions
    (128, 3, (127, 128, 383)),
    (128, 3, (0, 0, 0, 0)),
    (128, 3, (383, 383, 383)),
    (128, 5, (600, 0, 639)),
    (256, 33, (8447, 0, 3000, 255, 256)),
    (512, 8, (1190,) * 7 + (4095,)),
    (128, 1, (0, 5, 127)),
]


@pytest.mark.parametrize("bs,nblk,at", WORK_LIST_CASES)
def test_work_list_is_the_plain_enumeration_of_live_blocks(bs, nblk, at):
    """Slot by slot, a slot's blocks ascending, nothing else counted; the
    entries past the count stay inside the tables' range."""
    from paddle_tpu.kernels import mla_decode as K

    slot_of, blk_of, total = K.work_list(jnp.asarray(at, jnp.int32), bs,
                                         nblk)
    want = [(b, j) for b, p in enumerate(at) for j in range(p // bs + 1)]
    assert total.shape == (1,) and int(total[0]) == len(want)
    assert slot_of.shape == blk_of.shape == (len(at) * nblk,)
    got = list(zip(np.asarray(slot_of).tolist(), np.asarray(blk_of).tolist()))
    assert got[:len(want)] == want
    assert set(got[len(want):]) <= {want[-1]}
    live, grid = K.blocks_of(np.asarray(at).reshape((-1, 1)), bs, bs * nblk)
    assert (live, grid) == (len(want), len(at) * nblk)


def test_mla_decode_sweep_rehearses_on_the_cpu(tmp_path):
    """``tools/mla_decode_sweep.py`` (the tool behind docs/KERNELS.md's
    table) runs its cases at a tiny size in interpret mode, writes no
    time and leaves the kernel's block choices as they were."""
    import json
    import sys

    from paddle_tpu.kernels import mla_decode as K

    sys.path.insert(0, os.path.join(HERE, os.pardir, "tools"))
    try:
        import mla_decode_sweep
    finally:
        sys.path.pop(0)
    out = tmp_path / "sweep.json"
    choices = K._BLOCK_CHOICES
    assert mla_decode_sweep.main(["--rehearse", "--only", "xing", "--draws",
                                  "1", "--out", str(out)]) == 0
    assert K._BLOCK_CHOICES == choices
    rows = json.loads(out.read_text())["rows"]
    assert [(r["positions"], r["blocks_live"], r["blocks_grid"])
            for r in rows[1:]] == [("all_0", 4, 12), ("all_full", 12, 12)]
    assert rows[0]["positions"] == "traffic" and 4 <= rows[0]["blocks_live"]
    assert all("device_ms" not in r and r["max_abs_diff_vs_composed"] < 2e-2
               for r in rows)


def test_mla_prefill_sweep_rehearses_on_the_cpu(tmp_path):
    """``tools/mla_prefill_sweep.py`` (the tool behind the table of
    docs/KERNELS.md "Operand layouts of the flash kernels") runs its five
    cases at a tiny ragged length in interpret mode and writes no time:
    the two forms of the sub-block agree, there and at the lengths it
    checks."""
    import json
    import sys

    sys.path.insert(0, os.path.join(HERE, os.pardir, "tools"))
    try:
        import mla_prefill_sweep
    finally:
        sys.path.pop(0)
    out = tmp_path / "sweep.json"
    assert mla_prefill_sweep.main(["--rehearse", "--only", "longcat",
                                   "--out", str(out)]) == 0
    rows = json.loads(out.read_text())["rows"]
    assert [r.get("case") for r in rows[:5]] == [
        "block_heads", "heads_f32", "heads_bf16", "block_lanes",
        "lanes_bf16"]
    assert all("device_ms" not in r for r in rows)
    assert rows[3]["max_abs_diff_vs_block_heads"] < 2e-5
    # the kernel alone at the rule's count and at 1, 2 and 4 heads a
    # multi-pass step (1,300 keys), every one equal to the first
    stepped = [r for r in rows if "equal_to_the_rule" in r]
    assert [(r.get("rule_heads"), r.get("heads")) for r in stepped] == [
        (4, None), (None, 1), (None, 2), (None, 4)]
    assert all(r["equal_to_the_rule"] for r in stepped)
    checks = [r for r in rows if "check" in r]
    assert rows == rows[:5] + stepped + checks
    assert [(r["check"], r["finite"]) for r in checks] == [
        (136, True), (200, True)]
    assert all(r["max_abs_diff"] < 2e-5 for r in checks)


def test_engine_counts_the_blocks_its_latent_steps_walk():
    """``paddle_mla_decode_blocks_total``: a step adds, for every latent
    layer, the (slot, block) pairs its positions hold (a free slot one)
    and the grid of whole slabs; a lane whose slab has no plan counts
    nothing."""
    from paddle_tpu.observe import REGISTRY

    def blocks():
        got = REGISTRY.snapshot()["metrics"].get(
            "paddle_mla_decode_blocks_total", {"samples": []})
        return {s["labels"]["kind"]: s["value"] for s in got["samples"]}

    cfg = tiny_cfg(n_head=8, n_layer=2)
    eng = _engine(cfg, seeded_params(cfg, 53), 3, max_len=384).start()
    assert eng._lane.latent_walk == (2, 128)
    before = blocks()
    try:
        eng.submit(np.arange(1, 127), 5).result(timeout=300)
    finally:
        eng.stop()
    after = blocks()
    # alone in the engine: 4 steps at positions 126 .. 129 (the first
    # token is the prefill's), two of them in the second block; the two
    # free slots keep a block each; two latent layers
    assert after["live"] - before.get("live", 0) == 2 * (3 + 3 + 4 + 4)
    assert after["grid"] - before.get("grid", 0) == 2 * 4 * 3 * 3
    # four heads: the kernel has no plan, the lane no walk
    assert _engine(tiny_cfg(), None, 2, max_len=384)._lane.latent_walk \
        is None


def test_mla_decode_plan_and_counter():
    from paddle_tpu.kernels import mla_decode as K
    from paddle_tpu.kernels.kv_cache_write import _s_minor, write_plan
    from paddle_tpu.observe.families import MLA_ATTENTION_PLANS

    # the cell's cache: 512-row blocks of a slab the TPU stores S-minor,
    # and the cache write's column form over the same view
    assert K.decode_plan((64, 1, 4096, 576), jnp.float32, 128) == 512
    assert _s_minor(4096, 576)
    assert write_plan((64, 1, 4096, 576), jnp.float32) == \
        ("cols", (1, 1, 576, 128))
    assert K.decode_plan((2, 2, 256, 64), jnp.float32, 8) is None
    assert K.decode_plan((2, 1, 100, 64), jnp.float32, 8) is None
    c = MLA_ATTENTION_PLANS.labels(form="absorbed", kernel="composed",
                                   block="-", widths="40x32")
    before = c.value
    K.mla_decode(jnp.zeros((1, 4, 40)), jnp.zeros((1, 1, 16, 40)),
                 jnp.zeros((1,), jnp.int32), d_c=32, scale=1.0)
    assert c.value == before + 1


def test_the_plan_label_carries_the_walk(monkeypatch):
    """Where the kernel is lowered the ``block`` label says the rows of a
    step AND that only live blocks are walked (``"128 live"``)."""
    from paddle_tpu.kernels import mla_decode as K
    from paddle_tpu.observe.families import MLA_ATTENTION_PLANS

    monkeypatch.setattr(K, "use_interpret", lambda: False)
    taken = {}
    monkeypatch.setattr(
        K, "mla_decode_pallas",
        lambda q, cache, pos, **kw: taken.update(kw) or "lowered")
    c = MLA_ATTENTION_PLANS.labels(form="absorbed", kernel="pallas",
                                   block="128 live", widths="40x32")
    before = c.value
    got = K.mla_decode(jnp.zeros((2, 8, 40)), jnp.zeros((2, 1, 384, 40)),
                       jnp.zeros((2,), jnp.int32), d_c=32, scale=1.0)
    assert got == "lowered" and taken["interpret"] is False
    assert c.value == before + 1


FLASH_WIDTH_CASES = [(300, 4, 24, 16), (640, 2, 192, 128), (128, 4, 24, 16)]


@pytest.mark.parametrize("S,H,dk,dv", FLASH_WIDTH_CASES)
def test_flash_forward_takes_a_value_width_of_its_own(S, H, dk, dv):
    from paddle_tpu.ops import attention as A

    rs = np.random.RandomState(S + dk)
    q, k = (jnp.asarray(rs.randn(1, H, S, dk).astype("float32"))
            for _ in range(2))
    v = jnp.asarray(rs.randn(1, H, S, dv).astype("float32"))
    got = A.flash_attention(q, k, v, None, dk ** -0.5, causal=True)
    assert got.shape == (1, H, S, dv)
    want = A.composed_attention(q, k, v, None, dk ** -0.5, True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k)) \
        * dk ** -0.5
    s = np.where(np.tril(np.ones((S, S), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    np.testing.assert_allclose(
        got, np.einsum("bhqk,bhkd->bhqd", p, np.asarray(v)), atol=2e-5,
        rtol=0)


def test_prefill_attention_is_the_flash_forward_at_every_length():
    """The prefill asks for the kernel from one lane tile on
    (``flash_min_seq=128``: under the name flash_fwd, whatever the static
    threshold says) and for bfloat16 MXU operands, both as attributes of
    its own ops, and the form counter counts the layers built."""
    from paddle_tpu.observe.families import (FLASH_BLOCK_PLANS,
                                             MLA_ATTENTION_PLANS)

    cfg = tiny_cfg(n_layer=2)
    params = seeded_params(cfg, 43)
    flash = FLASH_BLOCK_PLANS.labels(kernel="flash_fwd", block="128x128",
                                     single_pass="1", layout="heads")
    form = MLA_ATTENTION_PLANS.labels(form="expanded",
                                      kernel="fused_attention", block="-",
                                      widths="24x16")
    before = flash.value, form.value
    prompt = np.random.default_rng(47).integers(1, 97, 128)
    eng = _engine(cfg, params, 1, max_len=160)
    toks, rows = _decode_in_company(eng, [prompt], 6)
    # two layers: two kernels in the compiled prefill, and two counted
    # each time the engine built the program (with logits, with a token)
    built = form.value - before[1]
    assert flash.value - before[0] == 2 and built >= 2 and built % 2 == 0
    _assert_matches_reference(cfg, params, [prompt], toks, rows)
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        gpt.build_prefill_step(cfg, batch=1, prompt_len=128, max_len=160)
    ops = [op for op in prog.global_block().ops
           if op.type == "fused_attention"]
    assert [(op.attrs["mxu_dtype"], op.attrs["flash_min_seq"])
            for op in ops] == [("bfloat16", 128)] * 2


def _between(types, first, last):
    """The op types after the ``first``-th op and before the next ``last``."""
    return types[first + 1:first + 1 + types[first + 1:].index(last)]


@pytest.mark.parametrize("cfg_of", ["pangu", "mscale"])
def test_the_prefill_builds_no_heads_keys_or_values(cfg_of):
    """Between ``kvb``'s projection and the fused-attention op a latent
    prefill holds no ``transpose2``, ``expand`` or ``concat`` (no head's
    keys or values are built, q is rotated where it lies), the op takes
    ``kvb``'s output as both K and V beside the shared key part, and its
    output goes to the output projection as it is."""
    cfg = tiny_cfg(n_layer=2) if cfg_of == "pangu" else tiny_cfg(
        n_layer=2, mla_scale_q_lora=True, mla_scale_kv_lora=True)
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        gpt.build_prefill_step(cfg, batch=1, prompt_len=24, max_len=32)
    ops = prog.global_block().ops
    types = [op.type for op in ops]
    for i in range(2):
        kvb = [n for n, op in enumerate(ops) if op.type == "mul"
               and "gpt_%d_att_kvb.w_0" % i in op.inputs["Y"]][0]
        made = _between(types, kvb, "fused_attention")
        assert not {"transpose2", "expand", "concat"} & set(made), made
        att = ops[kvb + 1 + len(made)]
        assert att.inputs["K"] == att.inputs["V"] == ops[kvb].outputs["Out"]
        assert sorted(att.inputs) == ["K", "KR", "Q", "QR", "V"]
        assert (att.attrs["n_head"], att.attrs["mxu_dtype"],
                att.attrs["flash_min_seq"]) == (4, "bfloat16", 128)
        # straight into the output projection: nothing is transposed back
        after = types[kvb + 2 + len(made):]
        assert after[:after.index("mul")] == []


def test_a_prefill_at_whole_lane_tiles_runs_the_lanes_layout():
    """At widths that are whole lane tiles (the three configurations':
    128 + 64 and 128) the prefill's kernel reads the projections' outputs
    in the lanes layout — one plan a layer, none in the heads layout —
    and the engine answers as the reference does."""
    from test_attention import _lane_plans

    from paddle_tpu.observe.families import MLA_ATTENTION_PLANS

    cfg = tiny_cfg(n_layer=2, n_head=2, d_nope=128, d_rope=64, d_v=128)
    params = seeded_params(cfg, 61)
    form = MLA_ATTENTION_PLANS.labels(form="expanded",
                                      kernel="fused_attention", block="-",
                                      widths="192x128")
    before, built = _lane_plans(), form.value
    prompt = np.random.default_rng(67).integers(1, 97, 136)
    eng = _engine(cfg, params, 1, max_len=160)
    toks, rows = _decode_in_company(eng, [prompt], 4)
    got = {k: n - before.get(k, 0) for k, n in _lane_plans().items()
           if n > before.get(k, 0)}
    assert got == {("flash_fwd", "lanes"): 2}
    assert form.value - built >= 2
    _assert_matches_reference(cfg, params, [prompt], toks, rows)


@pytest.mark.parametrize("dk,dv", [(16, 16), (24, 16)])
def test_a_causal_length_with_no_whole_block_pads_to_whole_blocks(dk, dv):
    """1,280 = 10 lane tiles divides by no block over 256 and is past one
    key block: the plan pads it to three 512-blocks (the causal mask
    hides the padded keys) and holds the K/V index at the diagonal,
    whatever the widths; 640 fits one key block and stays as it was."""
    from paddle_tpu.observe.families import FLASH_BLOCK_PLANS
    from paddle_tpu.ops import attention as A

    whole = FLASH_BLOCK_PLANS.labels(kernel="flash_fwd", block="512x512",
                                     single_pass="0", layout="heads")
    one = FLASH_BLOCK_PLANS.labels(kernel="flash_fwd", block="128x640",
                                   single_pass="1", layout="heads")
    before = whole.value, one.value
    rs = np.random.RandomState(dk)
    for S in (1280, 640):
        q, k = (jnp.asarray(rs.randn(1, 2, S, dk).astype("float32"))
                for _ in range(2))
        v = jnp.asarray(rs.randn(1, 2, S, dv).astype("float32"))
        got = A._forward_pallas(q, k, v, None, dk ** -0.5, causal=True)[0]
        np.testing.assert_allclose(
            got, A.composed_attention(q, k, v, None, dk ** -0.5, True),
            atol=2e-5, rtol=0)
    assert (whole.value - before[0], one.value - before[1]) == (1, 1)


HEADS_A_STEP = {
    # case: (H, group, single_pass, D, Dv, itemsize, extra, heads a step)
    # a single-pass plan: four at one lane tile of width, as every older
    # caller has them
    "single_pass_one_tile": (128, 1, True, 128, 128, 2, {}, 4),
    "single_pass_d64": (12, 1, True, 64, 64, 2, {}, 4),
    # q/k 192 wide take 256 lanes in VMEM: two
    "single_pass_192_wide": (128, 1, True, 192, 128, 2, {}, 2),
    "single_pass_576_wide": (128, 1, True, 576, 512, 2, {}, 1),
    # a multi-pass plan took one head until PR 57: now what its VMEM
    # account lets in, at most four
    "multi_pass_d64": (128, 1, False, 64, 64, 2, {}, 4),
    # the latent prefill past 1,024 keys (Pangu, Xing, LongCat): bf16
    # operands, the shared key part a lane tile, a float32 context
    "multi_pass_latent": (128, 1, False, 128, 128, 2, {"Dr": 64}, 4),
    # float32 operands at a head of 64: 17.1 MiB at four
    "multi_pass_f32_d64": (16, 1, False, 64, 64, 4, {}, 2),
    # grouped float32 heads share ONE K/V block: a divisor of the group
    "multi_pass_group_6": (48, 6, False, 128, 128, 4, {}, 3),
    "multi_pass_group_4_d64": (32, 4, False, 64, 64, 4, {}, 4),
    "multi_pass_group_16": (32, 16, False, 128, 128, 4, {}, 4),
    # a head of two lane tiles
    "multi_pass_group_8_d256": (16, 8, False, 256, 256, 4, {}, 2),
    # a grouped single-pass call keeps its one head
    "single_pass_grouped": (32, 4, True, 64, 64, 4, {}, 1),
}


@pytest.mark.parametrize("case", sorted(HEADS_A_STEP))
def test_heads_a_step_follow_the_operands_width(case):
    """``_forward_heads``, the forward's one rule, at 512 x 512 blocks
    with a float32 output; the count fits the VMEM a call is compiled
    under by the rule's own account, and the next divisor does not (or
    is past the most a step takes)."""
    from paddle_tpu.ops import attention as A

    H, group, single, D, Dv, itemsize, extra, want = HEADS_A_STEP[case]
    got = A._forward_heads(H, group, 512, 512, single, None, D, Dv,
                           itemsize, 4, **extra)
    assert got == want and (group if group > 1 else H) % got == 0
    held = lambda g: A._forward_vmem(  # noqa: E731
        g, 512, 512, single, D, Dv, itemsize, 4, one_kv=group > 1, **extra)
    assert held(got) <= A._VMEM_LIMIT_BYTES
    if not single and got < A._HEADS_PER_STEP:
        more = min(g for g in range(got + 1, 2 * A._HEADS_PER_STEP)
                   if (group if group > 1 else H) % g == 0)
        assert more > A._HEADS_PER_STEP or held(more) > A._VMEM_LIMIT_BYTES


def test_a_full_bias_and_the_backward_keep_one_head_a_multi_pass_step():
    from paddle_tpu.ops import attention as A

    full = jnp.zeros((1, 1, 2048, 2048), jnp.float32)
    assert A._forward_heads(16, 1, 512, 512, False, full, 64, 64, 2,
                            2) == 1
    key_mask = jnp.zeros((2, 1, 1, 2048), jnp.float32)
    assert A._forward_heads(16, 1, 512, 512, False, key_mask, 64, 64, 2,
                            2) == 4
    # the backward kernels' rule is what it was
    assert A._heads_per_step(128, False, None, width=64) == 1
    assert A._heads_per_step(12, True, None, width=64) == 4


# ------------------------------------------------------------ the share
def test_the_shares_parts_add_up_to_the_uncut_layer():
    """Two chips of 8 experts each: each computes the shared expert whole
    and ITS part of the routed sum (``gpt._mlp`` over a share, the
    program's own layer); the parts, with the shared expert counted once,
    are the uncut reference's expert layer."""
    cfg = tiny_cfg(n_layer=2, n_dense_layer=0)
    params = seeded_params(cfg, 53)
    ids = np.random.default_rng(59).integers(1, 97, 11)
    full = {n: params["gpt_0_" + n] for n in reference.LAYER_PARAMS["moe"]}
    with jax.default_matmul_precision("highest"):
        m = reference._rms_norm(jnp.asarray(params["gpt_word_emb"])[ids],
                                jnp.asarray(params["gpt_0_pre2_ln_s"]),
                                1e-5)
        routed, _ = reference.experts(
            m, full["moe_router.w_0"], full["moe_gate.w_0"],
            full["moe_up.w_0"], full["moe_down.w_0"], 4, True, 2.5)
        shared = reference.swiglu(m, full["moe_shared_gate.w_0"],
                                  full["moe_shared_up.w_0"],
                                  full["moe_shared_down.w_0"])
    parts = []
    for first in (0, 8):
        share = dict(cfg, n_expert_local=8, expert_first=first)
        prog, start = fluid.Program(), fluid.Program()
        scope = fluid.core.scope.Scope()
        with fluid.core.scope.scope_guard(scope):
            with fluid.program_guard(prog, start):
                x = fluid.layers.data("m", [11, 48], dtype="float32",
                                      append_batch_size=False)
                out = gpt._mlp(share, fluid.layers.reshape(x, [1, 11, 48]),
                               "gpt_0", 0)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(start, scope=scope)
            for n, w in full.items():
                held = n in ("moe_gate.w_0", "moe_up.w_0", "moe_down.w_0")
                scope.set_var("gpt_0_" + n,
                              w[first:first + 8] if held else w)
            (got,) = exe.run(prog, feed={"m": np.asarray(m)},
                             fetch_list=[out], scope=scope)
        parts.append(got[0])
    np.testing.assert_allclose(parts[0] + parts[1] - np.asarray(shared),
                               np.asarray(routed + shared), atol=1e-5,
                               rtol=0)
    assert np.abs(np.asarray(routed)).max() > 1e-3
    assert np.abs(parts[0] - parts[1]).max() > 1e-3


# ---------------------------------------------------------- the refusals
def test_prefix_store_speculation_and_multi_token_step_refuse_a_latent():
    from paddle_tpu.serving import PrefixStore

    cfg = tiny_cfg()
    with pytest.raises(ValueError, match="latent"):
        _engine(cfg, None, 2, prefix_store=PrefixStore(1 << 20))
    with pytest.raises(ValueError, match="latent"):
        _engine(cfg, None, 2, prefix_cache_bytes=1 << 20)
    dense = dict(d_model=32, d_ff=64, n_head=4, n_layer=1, vocab=97,
                 max_length=64, dropout=0.0)
    with pytest.raises(ValueError, match="speculative.*latent"):
        _engine(cfg, None, 2, draft_cfg=dense, spec_k=2)
    with pytest.raises(ValueError, match="draft model.*latent"):
        _engine(dense, None, 2, draft_cfg=cfg, spec_k=2)
    with pytest.raises(ValueError, match="latent cache"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            gpt.build_multi_token_decode_step(cfg, batch=1, steps=2,
                                              max_len=16)


def test_expanded_flash_call_pads_to_whole_blocks(monkeypatch):
    """A causal length past one key block whose lane tiles divide by no
    block over half the longest (3,328 = 26 tiles: 256x256) is padded to
    whole blocks, for the two-width call and for equal widths alike: the
    plan goes by the shapes."""
    from paddle_tpu.observe.families import FLASH_BLOCK_PLANS
    from paddle_tpu.ops import attention as A

    assert A._block_plan(A.KERNEL_FWD, 3328, 3328, 192, jnp.float32,
                         True) == (256, 256)
    wide = FLASH_BLOCK_PLANS.labels(kernel="flash_fwd", block="512x512",
                                    single_pass="0", layout="heads")
    before = wide.value
    S, H = 640, 2
    monkeypatch.setattr(A, "_MAX_BLOCK", 256)   # 640 = 5 tiles: 128-blocks
    rs = np.random.RandomState(3)
    q, k = (jnp.asarray(rs.randn(1, H, S, 24).astype("float32"))
            for _ in range(2))
    v = jnp.asarray(rs.randn(1, H, S, 16).astype("float32"))
    padded = FLASH_BLOCK_PLANS.labels(kernel="flash_fwd", block="256x256",
                                      single_pass="0", layout="heads")
    b0 = padded.value
    got = A.flash_attention(q, k, v, None, 0.2, causal=True)
    assert padded.value == b0 + 1 and wide.value == before
    want = A.composed_attention(q, k, v, None, 0.2, True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    got = A._forward_pallas(q, k, q, None, 0.2, causal=True)[0]
    assert padded.value == b0 + 2
    np.testing.assert_allclose(
        got, A.composed_attention(q, k, q, None, 0.2, True), atol=2e-5,
        rtol=0)
    # not causal: the padded keys would need a mask of their own
    plain = FLASH_BLOCK_PLANS.labels(kernel="flash_fwd", block="128x128",
                                     single_pass="0", layout="heads")
    p0 = plain.value
    A._forward_pallas(q, k, q, None, 0.2, causal=False)
    assert plain.value == p0 + 1


# -------------------------------------------------------- the reference
def test_the_reference_copies_agree_to_the_last_bit():
    with open(reference.__file__, "rb") as f:
        want = f.read()
    for copy in (("benchmarks", "references", "openpangu-ultra-moe-718b.py"),
                 ("tests", "benchmarks", "references", "tiny-mla.py")):
        with open(os.path.join(ROOT, *copy), "rb") as f:
            assert f.read() == want, copy
    copy = _load(os.path.join(ROOT, "benchmarks", "references",
                              "openpangu-ultra-moe-718b.py"))
    cfg = tiny_cfg(n_expert_local=8, expert_first=0)
    params = seeded_params(cfg, 67)
    ids = jnp.asarray(np.random.default_rng(71).integers(1, 97, 27))
    a, ga = reference.forward(params, cfg, ids, with_gaps=True)
    b, gb = copy.forward(params, cfg, ids, with_gaps=True)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(ga), np.asarray(gb))


def test_reference_gaps_control_and_widening():
    cfg = tiny_cfg()
    params = seeded_params(cfg, 73, bf16_valued=True)
    ids = jnp.asarray(np.random.default_rng(79).integers(1, 97, 30))
    logits, gaps = reference.forward(params, cfg, ids, with_gaps=True)
    assert gaps.shape == (30,) and (np.asarray(gaps) >= 0).all()
    # bfloat16 arrays are widened where they multiply: the same numbers
    narrow = {n: jnp.asarray(v, jnp.bfloat16) if v.ndim >= 2 else v
              for n, v in params.items()}
    np.testing.assert_array_equal(
        np.asarray(reference.forward(narrow, cfg, ids)), np.asarray(logits))
    # the control: activations (and the float32 norm scales) in bfloat16
    # over the same bfloat16-valued matrices
    low = reference.forward(params, cfg, ids, 7, 7)
    assert 1e-3 < float(jnp.abs(low - logits).max()) < 1.0
    margins, g = reference.greedy_margin_fn(params, cfg, 16, ((7, 7),))(
        np.asarray(ids), 10)
    assert len(margins) == 2 and margins[0].shape == (20,) == g.shape
    saved = reference.QUERY_BLOCK
    try:
        reference.QUERY_BLOCK = 7      # the blocked attention is exact
        again = reference.forward(params, cfg, ids)
    finally:
        reference.QUERY_BLOCK = saved
    np.testing.assert_allclose(again, logits, atol=1e-5, rtol=0)


def test_analysis_rules_know_the_new_op_and_the_two_widths():
    """Shapes and bytes of a latent cfg's programs: the new op's output,
    the flash call at two widths, and a byte model that follows the
    stored dtypes."""
    from paddle_tpu.analysis.memory import MemoryAnalysis

    cfg = tiny_cfg(weight_dtype="bfloat16")
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        gpt.build_serving_decode_step(cfg, batch=2, max_len=32)
    block = prog.global_block()
    op = [o for o in block.ops if o.type == "mla_decode"][0]
    assert tuple(block.var(op.outputs["Out"][0]).shape) == (-1, 1, 4 * 16)
    ma = MemoryAnalysis(prog, site="serving")
    assert ma.tensors["gpt_1_att_kvb.w_0"].poly.at(1) == 32 * 4 * 32 * 2
    assert ma.tensors["gpt_0_cache_c"].poly.at(1) == 2 * 32 * 40 * 4
    pre, pstart = fluid.Program(), fluid.Program()
    with fluid.program_guard(pre, pstart):
        gpt.build_prefill_step(cfg, batch=1, prompt_len=12, max_len=32)
    block = pre.global_block()
    att = [o for o in block.ops if o.type == "fused_attention"][0]
    # the operands where the projections wrote them (PR 50): four heads
    # of 16 + 8 and 16 + 16 lanes, kvb's output as both K and V
    assert [tuple(block.var(att.inputs[s][0]).shape)
            for s in ("Q", "QR", "K", "V", "KR")] == [
        (-1, 12, 64), (-1, 12, 32), (-1, 12, 128), (-1, 12, 128),
        (-1, 12, 8)]
    assert att.inputs["K"] == att.inputs["V"]
    assert tuple(block.var(att.outputs["Out"][0]).shape) == (-1, 12, 4 * 16)
    # the rules take the form: nothing to report, and a head's scores
    # contract over 16 + 8 where its values are 16 wide
    from paddle_tpu.analysis.cost import CostAnalysis
    from paddle_tpu.analysis.infer import verify_program

    assert not [f for f in verify_program(pre, fill=False)
                if f.severity == "error" or (
                    f.severity == "warning" and "fused_attention" in f.message)]
    cost = CostAnalysis(pre)
    assert not cost.unruled
