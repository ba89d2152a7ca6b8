"""Xing4.0-29B-A4B's layer (``model_type`` xing4_0) through the system's
normal path, against the benchmark's own plain reference
(benchmarks/references/xing4.0-29b-a4b.py, imported, not copied): four
residual streams a token (manifold-constrained hyper-connections: ops
``mhc_pre`` / ``mhc_post``, kernels/mhc.py) round latent attention with
YaRN-scaled rotation and whole-held experts with a selection bias."""

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.kernels import mhc
from paddle_tpu.models import gpt

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path):
    spec = importlib.util.spec_from_file_location(
        "ref_" + os.path.basename(path).replace("-", "_")
        .replace(".", "_")[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load(os.path.join(ROOT, "benchmarks", "references",
                               "xing4.0-29b-a4b.py"))

YARN = dict(type="yarn", factor=8, beta_fast=32, beta_slow=1, mscale=1,
            mscale_all_dim=1, original_max_position_embeddings=16)


def tiny_cfg(**over):
    """Four streams of 128, 4 heads of 16 + 8 (q/k) and 16 (v) over a
    latent of 32 (queries through a latent of 24), YaRN of factor 8 past
    an original context of 16, one dense layer then two expert layers of
    16 experts of width 24 top-4 with a selection bias and one shared,
    vocabulary 97."""
    cfg = dict(d_model=128, n_head=4, n_layer=3, vocab=97, max_length=256,
               dropout=0.0, pos_emb="rope", rope_theta=10000.0,
               rope_scaling=dict(YARN), norm="rms", norm_eps=1e-6,
               attn="mla", q_lora_rank=24, kv_lora_rank=32, d_nope=16,
               d_rope=8, d_v=16, residual="mhc", hc_mult=4,
               hc_sinkhorn_iters=20, hc_eps=1e-6, hc_res_clamp=(-30, 30),
               ffn_act="swiglu", d_ff=96, n_dense_layer=1, n_expert=16,
               expert_top_k=4, d_expert=24, n_shared_expert=1,
               router_score="sigmoid", router_bias=True, norm_topk=True,
               route_scale=2.0, n_expert_local=16, expert_first=0)
    cfg.update(over)
    return cfg


def seeded_params(cfg, seed):
    """Every parameter drawn from the seed, float32 arrays: matrices
    within Xavier limits, norm scales and the mappings' gates in 0.5-1.5,
    the mappings' biases within 1 of zero and the selection bias within
    0.01, as the benchmark's kind draws them."""
    cfg = {k: v for k, v in cfg.items() if k != "weight_dtype"}
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        gpt.build_serving_decode_step(cfg, batch=1, max_len=16)
    rng = np.random.default_rng(seed)
    out = {}
    for p in sorted(prog.global_block().all_parameters(),
                    key=lambda p: p.name):
        shape = tuple(p.shape)
        if p.name.endswith("_router_bias"):
            out[p.name] = rng.uniform(-0.01, 0.01, shape).astype("float32")
        elif p.name.endswith(("_hc1_b", "_hc2_b")):
            out[p.name] = rng.uniform(-1, 1, shape).astype("float32")
        elif len(shape) == 1:
            out[p.name] = rng.uniform(0.5, 1.5, shape).astype("float32")
        else:
            lim = (6.0 / (shape[-2] + shape[-1])) ** 0.5
            out[p.name] = rng.uniform(-lim, lim, shape).astype("float32")
    return out


def _ref_logits(params, cfg, ids, **kw):
    return np.asarray(reference.forward(params, cfg, jnp.asarray(ids), **kw))


# ------------------------------------------------------------- the two ops
HC = dict(n=4, eps=1e-6, iters=20, hc_eps=1e-6, clamp=(-30.0, 30.0))


def _operands(seed, R, C, dtype=jnp.float32, scale=1.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    n = HC["n"]
    lim = (6.0 / (n * C + n * (n + 2))) ** 0.5
    return (jax.random.normal(k[0], (R, n * C)) * scale,
            jax.random.uniform(k[1], (n * C, n * (n + 2)), minval=-lim,
                               maxval=lim).astype(dtype),
            jax.random.uniform(k[2], (3,), minval=0.5, maxval=1.5),
            jax.random.uniform(k[3], (n * (n + 2),), minval=-1, maxval=1),
            jax.random.normal(k[4], (R, C)))


def _ref_pre(x, phi, alpha, b):
    n = HC["n"]
    X = x.reshape(x.shape[0], n, -1)
    with jax.default_matmul_precision("highest"):
        return reference.mhc_pre(X, phi.astype(jnp.float32), alpha, b,
                                 HC["eps"], HC["iters"], HC["hc_eps"],
                                 HC["clamp"])


def test_composed_ops_follow_the_references_lines():
    """Tolerance 2e-5 on values of magnitude ~1: the same float32
    arithmetic in another order (coefficient-major mappings here,
    row-major in the reference)."""
    x, phi, alpha, b, y = _operands(0, 37, 256, scale=3.0)
    h, coef, dev = mhc.mhc_pre_composed(x, phi, alpha, b, **HC)
    want_h, want_post, want_res = _ref_pre(x, phi, alpha, b)
    np.testing.assert_allclose(h, want_h, atol=2e-5, rtol=0)
    np.testing.assert_allclose(coef[:, 4:8], want_post, atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(coef[:, 8:]).reshape(37, 4, 4),
                               want_res, atol=2e-5, rtol=0)
    out = mhc.mhc_post_composed(x, y, coef, n=4)
    want = reference.mhc_post(x.reshape(37, 4, 256), y, want_post, want_res)
    np.testing.assert_allclose(out, np.asarray(want).reshape(37, -1),
                               atol=1e-4, rtol=0)
    assert 0 <= float(dev) < 1e-2


@pytest.mark.parametrize("R,C,dtype", [(37, 128, jnp.float32),
                                       (64, 256, jnp.bfloat16),
                                       (150, 128, jnp.bfloat16),
                                       (8, 384, jnp.float32)])
def test_pallas_kernels_match_composed(R, C, dtype):
    """Interpret mode. The kernel's projection carries 16 mantissa bits
    of X (a bfloat16 head and remainder) where the composed form has 24:
    1e-4 on mappings and values of magnitude ~1-10."""
    x, phi, alpha, b, y = _operands(R + C, R, C, dtype, scale=3.0)
    h, coef, dev = mhc.mhc_pre_composed(x, phi, alpha, b, **HC)
    h2, coef2, dev2 = mhc.mhc_pre_pallas(x, phi, alpha, b, interpret=True,
                                         **HC)
    assert h2.shape == (R, C) and coef2.shape == (R, 24)
    np.testing.assert_allclose(h2, h, atol=2e-4, rtol=0)
    np.testing.assert_allclose(coef2, coef, atol=1e-4, rtol=0)
    assert abs(float(dev) - float(dev2)) < 1e-4
    out = mhc.mhc_post_composed(x, y, coef, n=4)
    out2 = mhc.mhc_post_pallas(x, y, coef, n=4, interpret=True)
    np.testing.assert_allclose(out2, out, atol=1e-5, rtol=0)
    assert mhc.block_rows(R) % 8 == 0 and mhc.block_rows(R) <= 64


def test_res_mapping_is_doubly_stochastic_and_keeps_the_streams_sum():
    x, phi, alpha, b, y = _operands(5, 200, 128)
    _h, coef, dev = mhc.mhc_pre_composed(x, phi, alpha, b, **HC)
    res = np.asarray(coef[:, 8:]).reshape(-1, 4, 4)
    assert (res >= 0).all()
    # rows are normalised last: exact to rounding; columns to the twenty
    # rounds' error
    assert np.abs(res.sum(axis=2) - 1).max() < 1e-5
    cols = np.abs(res.sum(axis=1) - 1).max()
    assert cols < 5e-3
    assert float(dev) == pytest.approx(max(
        cols, np.abs(res.sum(axis=2) - 1).max()), abs=1e-6)
    # with Y = 0 the sum over the streams is kept: sum_i (H_res X)[i] =
    # sum_j (column sum j) X[j]
    out = np.asarray(mhc.mhc_post_composed(x, jnp.zeros_like(y), coef, n=4))
    got = out.reshape(-1, 4, 128).sum(axis=1)
    want = np.asarray(x).reshape(-1, 4, 128).sum(axis=1)
    np.testing.assert_allclose(got, want, atol=5e-3 * 4 * 4, rtol=0)


@pytest.mark.parametrize("form", ["composed", "pallas"])
def test_the_clamp_holds_at_huge_inputs(form):
    x, phi, alpha, b, _y = _operands(9, 16, 128)
    b = b.at[8:].set(jnp.asarray([1e4, -1e4] * 8))
    fn = mhc.mhc_pre_composed if form == "composed" else \
        lambda *a, **k: mhc.mhc_pre_pallas(*a, interpret=True, **k)
    h, coef, dev = fn(x * 1e4, phi, alpha, b, **HC)
    assert np.isfinite(np.asarray(h)).all()
    assert np.isfinite(np.asarray(coef)).all() and np.isfinite(float(dev))
    assert (np.asarray(coef[:, 8:]) <= 1 + 1e-5).all()


# ----------------------------------------------------------------- rotation
def _run_rope(x, pos, **kw):
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        xv = fluid.layers.data("x", list(x.shape[1:]), dtype="float32")
        pv = fluid.layers.data("pos", list(pos.shape), dtype="int64",
                               append_batch_size=False)
        out = fluid.layers.rope(xv, pv, **kw)
    exe = fluid.Executor(fluid.CPUPlace())
    return exe.run(prog, feed={"x": x, "pos": pos}, fetch_list=[out])[0]


def test_rope_takes_a_yarn_table():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((2, 4, 40, 8)).astype("float32")
    pos = np.arange(40, dtype="int64")
    cfg = tiny_cfg()
    yarn = gpt._yarn(cfg, 8)
    assert yarn["factor"] == 8 and 0 <= yarn["low"] <= yarn["high"] <= 3
    got = _run_rope(x, pos, base=10000.0, yarn=yarn)
    want = reference._rope(jnp.asarray(x), 10000.0, YARN)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    plain = _run_rope(x, pos, base=10000.0)
    assert np.abs(got - plain).max() > 0.1          # it is another table
    one = _run_rope(x, pos, base=10000.0,
                    yarn=dict(yarn, factor=1.0))
    assert np.array_equal(one, plain)               # bit for bit
    # the published sizes: 64 rotated dims, factor 64 over 4,096
    big = gpt._yarn(dict(rope_scaling=dict(YARN, factor=64,
                    original_max_position_embeddings=4096)), 64)
    assert (big["low"], big["high"], big["mscale"]) == (10, 23, 1.0)
    assert gpt._mla_scale(dict(d_nope=128, d_rope=64, rope_scaling=dict(
        YARN, factor=64))) == pytest.approx(1.4158883 ** 2 / 192 ** 0.5,
                                            rel=1e-6)


# ---------------------------------------------------- through the engine
def _engine(cfg, params, b_max, max_len=64, **kw):
    from paddle_tpu.serving import DecodeEngine

    return DecodeEngine(cfg, params=params, b_max=b_max, max_len=max_len,
                        **kw)


def _decode_in_company(eng, prompts, n_new):
    lane = eng._lane
    toks = [list(p) for p in prompts]
    rows = [[] for _ in prompts]
    for s, p in enumerate(prompts):
        _, last = lane.prefill_insert(s, np.asarray(p, "int64"))
        rows[s].append(np.asarray(last))
        toks[s].append(int(np.argmax(last)))
    for _ in range(n_new - 1):
        token = np.zeros((eng.b_max, 1), "int64")
        pos = np.zeros((eng.b_max, 1), "int64")
        for s, t in enumerate(toks):
            token[s, 0], pos[s, 0] = t[-1], len(t) - 1
        logits = lane.decode(token, pos)
        for s in range(len(prompts)):
            rows[s].append(np.asarray(logits[s, 0]))
            toks[s].append(int(np.argmax(logits[s, 0])))
    return toks, rows


def test_prefill_then_decode_against_the_references_forward():
    """Prefill then cached decode, three slots in company, against the
    reference's full forward on logits, positions past YaRN's original
    context of 16 among them. Tolerance 2e-4 absolute on logits of
    magnitude ~1: both sides are float32 at the highest matmul precision
    here, the absorbed attention reorders two contractions and the
    mappings are computed coefficient-major. It is tight enough that
    bfloat16 activations fail it (the reference's own control reads
    over 1e-2)."""
    cfg = tiny_cfg()
    params = seeded_params(cfg, 7)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 97, n) for n in (5, 13, 30)]
    eng = _engine(cfg, params, 4)
    toks, rows = _decode_in_company(eng, prompts, 20)
    worst = 0.0
    for p, t, r in zip(prompts, toks, rows):
        ids = np.asarray(t[:-1])
        want = _ref_logits(params, cfg, ids)[len(p) - 1:]
        np.testing.assert_allclose(np.stack(r), want, atol=2e-4, rtol=0)
        low = _ref_logits(params, cfg, ids, mantissa_bits=7,
                          activation_bits=7)[len(p) - 1:]
        worst = max(worst, float(np.abs(low - want).max()))
    assert worst > 1e-2
    dev = eng.mhc_res_deviation()
    assert dev is not None and 0 <= dev < 0.1
    from paddle_tpu.observe.families import MHC_RES_DEVIATION
    assert MHC_RES_DEVIATION.value == pytest.approx(dev)


def test_lane_holds_the_latent_cache_and_no_stream():
    """The streams live inside a program: what the lane keeps between
    steps is the latent cache PR 32 built, the tallies and one number."""
    cfg = tiny_cfg()
    eng = _engine(cfg, seeded_params(cfg, 1), 2)
    lane = eng._lane
    assert lane.cache_names == ["gpt_%d_cache_c" % i for i in range(3)]
    for n in lane.cache_names:
        assert np.asarray(lane.scope.find_var(n)).shape == (2, 1, 64, 40)
    wide = cfg["hc_mult"] * cfg["d_model"]
    block = lane.decode_prog.global_block() if hasattr(lane, "decode_prog") \
        else None
    progs = [p for p in vars(lane).values()
             if isinstance(p, fluid.Program)]
    assert progs
    for prog in progs if block is None else [lane.decode_prog]:
        blk = prog.global_block()
        params = {p.name for p in blk.all_parameters()}
        kept = [v for v in blk.vars.values()
                if v.persistable and v.name not in params]
        assert {v.name for v in kept} >= set(lane.cache_names)
        assert not [v.name for v in kept if wide in tuple(v.shape)]


def test_engine_tokens_are_generates_and_row_local():
    cfg = tiny_cfg()
    params = seeded_params(cfg, 29)
    rng = np.random.default_rng(31)
    prompts = [rng.integers(1, 97, n) for n in (4, 11, 24, 7)]
    eng = _engine(cfg, params, 4).start()
    try:
        got = [r.result(timeout=300) for r in
               [eng.submit(np.asarray(p, "int64"), 12) for p in prompts]]
        # row-locality: alone, a request answers as it did in company
        alone = [eng.submit(np.asarray(p, "int64"), 12).result(timeout=300)
                 for p in prompts[:2]]
    finally:
        eng.stop()
    for a, g in zip(alone, got):
        assert a.tolist() == g.tolist()
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


def test_the_prefill_runs_its_head_on_the_fetched_row():
    """The row an admission fetches comes from a head of ONE row; the
    [P, vocab] logits exist only for a plan that fetches them."""
    cfg = tiny_cfg()
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        logits, _ = gpt.build_prefill_step(cfg, batch=1, prompt_len=12,
                                           max_len=32)
    block = prog.global_block()
    heads = [op for op in block.ops
             if "gpt_out_proj.w_0" in sum(op.inputs.values(), [])]
    shapes = sorted(tuple(block.var(op.outputs["Out"][0]).shape)[1:]
                    for op in heads)
    assert shapes == [(1, 97), (12, 97)]
    assert tuple(logits.shape)[1:] == (12, 97)
    last = block.var(gpt.LAST_LOGITS_VAR)
    assert tuple(last.shape)[1:] == (97,)
    # the older configurations keep their one head, cut after it
    plain = {k: v for k, v in cfg.items()
             if k not in ("residual", "hc_mult", "hc_sinkhorn_iters",
                          "hc_eps", "hc_res_clamp")}
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        gpt.build_prefill_step(plain, batch=1, prompt_len=12, max_len=32)
    assert len([op for op in prog.global_block().ops
                if "gpt_out_proj.w_0" in sum(op.inputs.values(), [])]) == 1


# ------------------------------------------------------------- refusals
def test_check_cfg_refuses_by_name():
    gpt._check_cfg(tiny_cfg())
    gpt._check_cfg(tiny_cfg(weight_dtype="bfloat16"))
    for bad, match in (
            (dict(residual="hyper"), "residual"),
            (dict(hc_mult=0), "hc_mult"),
            (dict(norm="layer"), "norm='rms'"),
            (dict(pos_emb="learned"), "pos_emb='rope'"),
            (dict(hc_res_clamp=(3, -3)), "hc_res_clamp"),
            (dict(rope_scaling=dict(YARN, type="linear")), "YaRN"),
            (dict(rope_scaling=dict(YARN, factor=0.5)), "YaRN")):
        with pytest.raises(ValueError, match=match):
            gpt._check_cfg(tiny_cfg(**bad))
    plain = dict(d_model=32, d_ff=64, n_head=4, n_layer=1, vocab=50,
                 max_length=16, dropout=0.0)
    for key in ("hc_mult", "hc_sinkhorn_iters", "hc_eps", "hc_res_clamp"):
        with pytest.raises(ValueError, match="needs cfg\\['residual'\\]"):
            gpt._check_cfg(dict(plain, **{key: 4}))
    with pytest.raises(ValueError, match="rope_scaling.*attn"):
        gpt._check_cfg(dict(plain, pos_emb="rope", rope_scaling=dict(YARN)))


def test_training_prefix_store_speculation_and_multi_token_step_refuse():
    from paddle_tpu.serving import PrefixStore

    # streams over plain attention: the residual key alone is refused
    cfg = dict(d_model=128, d_ff=64, n_head=4, n_layer=1, vocab=97,
               max_length=64, dropout=0.0, norm="rms", pos_emb="rope",
               residual="mhc", hc_mult=4)
    dense = {k: v for k, v in cfg.items()
             if k not in ("residual", "hc_mult")}
    with pytest.raises(ValueError, match="prefix store.*residual streams"):
        _engine(cfg, None, 2, prefix_store=PrefixStore(1 << 20))
    with pytest.raises(ValueError, match="speculative.*residual streams"):
        _engine(cfg, None, 2, draft_cfg=dense, spec_k=2)
    with pytest.raises(ValueError, match="draft model.*residual streams"):
        _engine(dense, None, 2, draft_cfg=cfg, spec_k=2)
    with pytest.raises(ValueError,
                       match="build_multi_token_decode_step.*streams"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            gpt.build_multi_token_decode_step(cfg, batch=1, steps=2,
                                              max_len=16)
    with pytest.raises(ValueError, match="build:.*no backward"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            gpt.build(cfg, seq_len=8)
    # and it serves: plain attention under four streams
    eng = _engine(cfg, None, 2).start()
    try:
        out = eng.submit(np.arange(1, 6, dtype="int64"), 4).result(
            timeout=300)
    finally:
        eng.stop()
    assert out.shape == (9,)


# --------------------------------------------- the older configurations
_OLD_CFGS = {
    "gpt2m": dict(d_model=64, d_ff=256, n_head=4, n_layer=2, vocab=211,
                  max_length=64, dropout=0.0, ffn_act="gelu",
                  tie_embeddings=True),
    "olmoe": dict(d_model=64, n_head=4, n_layer=2, vocab=97, max_length=64,
                  dropout=0.0, pos_emb="rope", norm="rms", norm_eps=1e-5,
                  rope_theta=10000.0, qk_norm=True, n_expert=8,
                  expert_top_k=2, d_expert=32, norm_topk=False),
    "trinity": dict(d_model=64, n_head=4, n_kv_head=2, d_head=16, n_layer=4,
                    vocab=97, max_length=64, dropout=0.0, pos_emb="rope",
                    rope_theta=10000.0, rope_layers="sliding",
                    layer_types=["sliding", "sliding", "sliding", "full"],
                    window=8, norm="rms", norm_eps=1e-5, qk_norm="head",
                    attn_gate=True, sandwich_norm=True, emb_scale=8.0,
                    ffn_act="swiglu", d_ff=96, n_dense_layer=1, n_expert=16,
                    expert_top_k=4, d_expert=24, n_shared_expert=1,
                    router_score="sigmoid", router_bias=True,
                    norm_topk=True, route_scale=2.448, n_expert_local=8,
                    expert_first=0),
    "pangu": dict(d_model=48, n_head=4, n_layer=4, vocab=97, max_length=64,
                  dropout=0.0, pos_emb="rope", rope_theta=10000.0,
                  norm="rms", norm_eps=1e-5, attn="mla", q_lora_rank=24,
                  kv_lora_rank=32, d_nope=16, d_rope=8, d_v=16,
                  sandwich_norm=True, ffn_act="swiglu", d_ff=96,
                  n_dense_layer=1, n_expert=16, expert_top_k=4, d_expert=24,
                  n_shared_expert=1, router_score="sigmoid", norm_topk=True,
                  route_scale=2.5, n_expert_local=4, expert_first=4,
                  weight_dtype="bfloat16"),
}
_BUILDS = {
    "serving_decode": lambda c: gpt.build_serving_decode_step(
        c, batch=4, max_len=32),
    "decode": lambda c: gpt.build_decode_step(c, batch=2, max_len=32),
    "prefill": lambda c: gpt.build_prefill_step(
        c, batch=1, prompt_len=8, max_len=32),
    "prefill_long": lambda c: gpt.build_prefill_step(
        c, batch=1, prompt_len=24, max_len=32),
    "train": lambda c: gpt.build(
        {k: v for k, v in c.items() if k != "weight_dtype"}, seq_len=16,
        is_test=True, use_fused_attention=False),
}


@pytest.mark.parametrize("build", sorted(_BUILDS))
@pytest.mark.parametrize("shape", sorted(_OLD_CFGS))
def test_a_cfg_without_the_new_keys_builds_the_parents_program(shape, build):
    """Op for op — type, slots and attributes — against the digests taken
    from the parent commit (63bdea2) by this same function, for a tiny
    cfg of each decoder configuration the benchmark had (gpt2-medium,
    olmoe-1b-7b, trinity-large-preview, openpangu-ultra-moe-718b;
    bert-base builds through models/bert.py, which holds none of the
    helpers this PR touched). PR 50 re-based pangu's two prefill entries
    and nothing else (201 -> 153 ops over four layers: the latent prefill
    hands the fused-attention op ``kvb``'s output, the shared key part
    and q rotated where it lies; twelve transposes, slices, expands and
    concats a layer went). PR 58's
    residual pins (one ``materialize`` a layer of every prefill: a barrier,
    no arithmetic) are left out of the list, and counted by
    ``tests/test_gpt_programs_pinned.py``."""
    with open(os.path.join(HERE, "references",
                           "gpt_op_lists_pr35.json")) as f:
        want = json.load(f)[shape][build]
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        _BUILDS[build](_OLD_CFGS[shape])
    ops = [[op.type, sorted(op.inputs), sorted(op.outputs),
            sorted((k, repr(v)) for k, v in op.attrs.items()
                   if not k.startswith("_") and k != "op_callstack")]
           for op in prog.global_block().ops if op.type != "materialize"]
    assert len(ops) == want["n_ops"]
    assert hashlib.sha256(json.dumps(ops, sort_keys=True).encode()) \
        .hexdigest() == want["sha256"]


# ------------------------------------------------------------- analysis
def test_analysis_rules_know_the_two_ops():
    from paddle_tpu.analysis.memory import MemoryAnalysis

    cfg = tiny_cfg(weight_dtype="bfloat16")
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        gpt.build_serving_decode_step(cfg, batch=2, max_len=32)
    block = prog.global_block()
    pre = [o for o in block.ops if o.type == "mhc_pre"]
    post = [o for o in block.ops if o.type == "mhc_post"]
    assert len(pre) == len(post) == 2 * cfg["n_layer"]
    assert tuple(block.var(pre[0].outputs["H"][0]).shape) == (-1, 1, 128)
    assert tuple(block.var(pre[0].outputs["Coef"][0]).shape) == (-1, 1, 24)
    assert tuple(block.var(post[0].outputs["Out"][0]).shape) == (-1, 1, 512)
    assert pre[0].outputs["DevOut"] == [gpt.MHC_RES_DEV_VAR]
    ma = MemoryAnalysis(prog, site="serving")
    assert ma.tensors["gpt_1_hc1_phi.w_0"].poly.at(1) == 512 * 24 * 2
    assert ma.tensors["gpt_1_hc1_b"].poly.at(1) == 24 * 4
    from paddle_tpu.analysis import range_rules  # noqa: F401
    from paddle_tpu.core.registry import OPS
    from paddle_tpu.analysis.cost_rules import COST_RULES
    from paddle_tpu.analysis.memory import FOOTPRINT_RULES
    from paddle_tpu.analysis.ranges import RANGE_RULES
    for name in ("mhc_pre", "mhc_post"):
        assert name in COST_RULES and name in RANGE_RULES
        assert OPS[name].infer_shape is not None
    assert "mhc_pre" in FOOTPRINT_RULES


def test_plan_counter_counts_each_lowering():
    from paddle_tpu.observe.families import RESIDUAL_PLANS

    def reads():
        return {(op, k): RESIDUAL_PLANS.labels(
            form="mhc", op=op, kernel=k, streams="4").value
            for op in ("pre", "post") for k in ("pallas", "composed")}

    x, phi, alpha, b, y = _operands(1, 8, 128)
    before = reads()
    _h, coef, _d = mhc.mhc_pre(x, phi, alpha, b, **HC)
    mhc.mhc_post(x, y, coef, n=4)
    after = reads()
    assert {k: after[k] - before[k] for k in after} == {
        ("pre", "composed"): 1, ("post", "composed"): 1,
        ("pre", "pallas"): 0, ("post", "pallas"): 0}
