"""LFM2's layer kinds (``model_type`` lfm2_moe) through the system's
normal path, against the benchmark's own plain reference
(benchmarks/references/lfm2-24b-a2b.py, imported, not copied): a gated
short convolution as a layer's first sub-block, whose slot is ``K - 1``
carried rows (``layers.causal_conv(act=False, bias=False)``), beside
grouped-head attention slabs, inside attention-then-FFN layers with one
leading dense layer and experts behind it, the head tied to the table."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.kernels import ssm
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
                               "lfm2-24b-a2b.py"))


def tiny_cfg(**over):
    """Published layers 1-5 in small: ``conv`` (dense FFN of 96), ``full``
    (4 query and 2 key-value heads of 16, head norm, RoPE), three
    ``conv`` with 8 SwiGLU experts of width 24, top-2 by sigmoid score
    with a selection bias; 3 taps; the head tied to the table."""
    cfg = dict(d_model=64, n_head=4, n_kv_head=2, d_head=16, n_layer=5,
               vocab=97, max_length=256, dropout=0.0, pos_emb="rope",
               rope_theta=1000000.0, norm="rms", norm_eps=1e-5,
               qk_norm="head", tie_embeddings=True,
               layer_types=["conv", "full", "conv", "conv", "conv"],
               conv_taps=3, ffn_act="swiglu", d_ff=96, n_dense_layer=1,
               n_expert=8, expert_top_k=2, d_expert=24,
               router_score="sigmoid", router_bias=True, norm_topk=True,
               norm_topk_eps=1e-6, n_expert_local=8, expert_first=0)
    cfg.update(over)
    return cfg


def seeded_params(cfg, seed):
    """Every parameter drawn from the seed, float32: matrices within
    Xavier limits, the taps within 1 / sqrt(3), the selection bias
    within 0.01, the other vectors in 0.5-1.5."""
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
            v = rng.uniform(-0.01, 0.01, shape)
        elif p.name.endswith("_conv.w_0"):
            v = rng.uniform(-3 ** -0.5, 3 ** -0.5, shape)
        elif len(shape) == 1:
            v = rng.uniform(0.5, 1.5, shape)
        else:
            lim = (6.0 / (shape[-2] + shape[-1])) ** 0.5
            v = rng.uniform(-lim, lim, shape)
        out[p.name] = v.astype("float32")
    return out


def _ref_logits(params, cfg, ids, **kw):
    return np.asarray(reference.forward(params, cfg, jnp.asarray(ids), **kw))


# ------------------------------------------------------------ the sub-block
def _sub_block(u, w_in, taps, w_out, rows=None):
    """The program's arithmetic of the sub-block over ``u [B, T, D]``:
    the two gates round the carried-rows convolution with neither bias
    nor silu. ``rows`` None is the prompt's form."""
    D = u.shape[-1]
    with jax.default_matmul_precision("highest"):
        proj = u @ w_in
        v = proj[..., :D] * proj[..., 2 * D:]
        if rows is None:
            c, rows = ssm.conv_prefill(v, taps, None, act=False)
        else:
            c, rows = ssm.conv_step(v, rows, taps, None, act=False)
        return (proj[..., D:2 * D] * c) @ w_out, rows


@pytest.mark.parametrize("T", [1, 2, 3, 9])
def test_sub_block_is_the_references_three_shifted_products(T):
    """Lengths shorter than, equal to and longer than the taps; then
    ``n`` one-token steps from the carried rows continue the whole
    sequence's sub-block bit for bit, and the rows are the last two
    positions of ``v`` (zeros in front of a shorter prompt)."""
    rs = np.random.RandomState(T)
    D, K, n = 16, 3, 5
    u = jnp.asarray(rs.randn(2, T + n, D), jnp.float32)
    w_in = jnp.asarray(rs.randn(D, 3 * D) * 0.3, jnp.float32)
    taps = jnp.asarray(rs.uniform(-0.57, 0.57, (D, K)), jnp.float32)
    w_out = jnp.asarray(rs.randn(D, D) * 0.3, jnp.float32)
    whole, _ = _sub_block(u, w_in, taps, w_out)
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            np.testing.assert_allclose(
                whole[b], reference.gated_conv(u[b], w_in, taps, w_out),
                atol=1e-5)
    out, rows = _sub_block(u[:, :T], w_in, taps, w_out)
    with jax.default_matmul_precision("highest"):
        proj = np.asarray(u @ w_in)
    v = proj[..., :D] * proj[..., 2 * D:]
    want = np.zeros((2, K - 1, D), np.float32)
    keep = min(T, K - 1)
    want[:, K - 1 - keep:] = v[:, T - keep:T]
    np.testing.assert_array_equal(rows, want)
    outs = [out]
    for t in range(T, T + n):
        o, rows = _sub_block(u[:, t:t + 1], w_in, taps, w_out, rows)
        outs.append(o)
    np.testing.assert_array_equal(jnp.concatenate(outs, 1), whole)


def test_causal_conv_with_its_old_defaults_builds_the_program_it_built():
    """``act`` and ``bias`` default to what the layer hard-wired, so a
    state-space layer's program holds the op it held: a bias input and
    ``act`` on; the gated convolution's has neither, and no ``.b_0``."""
    from paddle_tpu import layers

    def build(**kw):
        prog = fluid.Program()
        with fluid.program_guard(prog, fluid.Program()):
            x = layers.data("x", [5, 12], dtype="float32")
            rows = prog.global_block().create_var(
                name="rows", shape=(1, 3, 12), dtype="float32",
                persistable=True)
            layers.causal_conv(x, 4, "cc", rows, **kw)
        (op,) = [o for o in prog.global_block().ops
                 if o.type.startswith("causal_conv")]
        return op, {p.name for p in prog.global_block().all_parameters()}

    op, names = build()
    assert op.type == "causal_conv" and op.attrs == {"act": True}
    assert list(op.inputs) == ["X", "W", "Bias"]
    assert names == {"cc.w_0", "cc.b_0"}
    op, names = build(step=True)
    assert op.type == "causal_conv_step"
    assert list(op.inputs) == ["X", "W", "Bias", "Rows"]
    op, names = build(act=False, bias=False)
    assert op.attrs == {"act": False} and list(op.inputs) == ["X", "W"]
    assert names == {"cc.w_0"}


def test_the_rules_follow_act():
    """The cost of the convolution without silu leaves the silu's five
    operations a value out; its range is the symmetric sum's, with no
    floor at silu's minimum."""
    from paddle_tpu import layers
    from paddle_tpu.analysis.cost import CostAnalysis
    from paddle_tpu.analysis.ranges import RangeAnalysis

    flops, lows = [], []
    for act in (True, False):
        prog = fluid.Program()
        with fluid.program_guard(prog, fluid.Program()):
            x = layers.data("x", [6, 8], dtype="float32")
            rows = prog.global_block().create_var(
                name="rows", shape=(1, 2, 8), dtype="float32",
                persistable=True)
            out = layers.causal_conv(layers.tanh(x), 3, "cc", rows,
                                     act=act, bias=False)
        flops.append(CostAnalysis(prog).flops(1))
        lows.append(RangeAnalysis(prog).value_of(out.name).lo)
    assert flops[0] - flops[1] == 6 * 8 * 5
    assert lows[0] == pytest.approx(-0.2785) and lows[1] < -0.2785


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_analyses_rule_every_op_of_the_programs(which):
    from paddle_tpu.analysis.cost import CostAnalysis
    from paddle_tpu.analysis.infer import verify_program
    from paddle_tpu.analysis.memory import MemoryAnalysis
    from paddle_tpu.analysis.ranges import RangeAnalysis

    cfg = tiny_cfg()
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        if which == "decode":
            out, _ = gpt.build_serving_decode_step(cfg, batch=3, max_len=64)
        else:
            out, _ = gpt.build_prefill_step(cfg, batch=1, prompt_len=20,
                                            max_len=64)
    ours = ("causal_conv", "moe_ffn", "fused_attention", "kv_cache_write")
    findings = verify_program(prog, fetch_list=[out.name], fill=False)
    bad = [f for f in findings if f.severity == "error"
           or (f.severity == "warning"
               and any(t in f.message for t in ours))]
    assert not bad, bad
    types = [op.type for op in prog.global_block().ops]
    assert types.count("causal_conv_step" if which == "decode"
                       else "causal_conv") == 4
    assert ("fused_attention" in types) == (which == "prefill")
    assert not set(RangeAnalysis(prog).widened) & set(ours)
    assert not CostAnalysis(prog).unruled
    assert MemoryAnalysis(prog).peak_bytes(1) > 0


# --------------------------------------------------------------- the cfg
@pytest.mark.parametrize("over,needle", [
    (dict(conv_taps=None), "a 'conv' layer needs cfg['conv_taps'] >= 2"),
    (dict(conv_taps=1), "a 'conv' layer needs cfg['conv_taps'] >= 2"),
    (dict(layer_types=["full"] * 5), "cfg['conv_taps'] needs a 'conv'"),
    (dict(layer_types=["conv", "hyena", "conv", "conv", "conv"]),
     "must name one of"),
    (dict(residual="mhc", hc_mult=2),
     "a 'conv' layer takes no cfg['residual']"),
    (dict(window=8), "cfg['window'] needs a 'sliding' layer"),
    (dict(mixers=["attention"] * 5), "takes no cfg['layer_types']"),
    (dict(attn="mla", q_lora_rank=8, kv_lora_rank=8, d_nope=8, d_rope=8,
          d_v=8, n_kv_head=None, d_head=None, qk_norm=None),
     "a 'conv' layer takes no cfg['attn']"),
    (dict(n_expert=None, expert_top_k=None, d_expert=None,
          n_dense_layer=None, router_score=None, router_bias=None,
          norm_topk=None, n_expert_local=None, expert_first=None),
     "cfg['norm_topk_eps'] needs cfg['n_expert']"),
])
def test_check_cfg_says_which_key_needs_which(over, needle):
    cfg = {k: v for k, v in tiny_cfg(**over).items() if v is not None}
    with pytest.raises(ValueError) as err:
        gpt._check_cfg(cfg)
    assert needle in str(err.value)


def test_a_sliding_layer_beside_a_conv_layer_is_a_cfg():
    gpt._check_cfg(tiny_cfg(
        layer_types=["conv", "sliding", "conv", "full", "conv"], window=8))


def test_the_three_refusals_name_the_carried_rows():
    from paddle_tpu.serving import DecodeEngine, PrefixStore

    cfg = tiny_cfg()
    with pytest.raises(ValueError, match="'conv' layers.*no backward"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            gpt.build(cfg, seq_len=8)
    with pytest.raises(ValueError, match="last 2 rows of a gated conv"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            gpt.build_multi_token_decode_step(cfg, batch=1, steps=2,
                                              max_len=16)
    for kw in (dict(prefix_store=PrefixStore(1 << 20)),
               dict(prefix_cache_bytes=1 << 20),
               dict(draft_cfg=cfg, spec_k=2)):
        with pytest.raises(ValueError, match="cfg\\['layer_types'\\] holds "
                           "'conv' layers.*gpt_<i>_cache_x"):
            DecodeEngine(cfg, b_max=2, max_len=32, **kw)
    dense = dict(d_model=32, d_ff=64, n_head=2, n_layer=1, vocab=50,
                 max_length=32, dropout=0.0)
    with pytest.raises(ValueError, match="a draft model.*'conv' layers"):
        DecodeEngine(dense, b_max=2, max_len=32, draft_cfg=cfg, spec_k=2)


def test_cache_kinds_and_state_layers_whichever_key_brought_them():
    cfg = tiny_cfg()
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        _, names = gpt.build_serving_decode_step(cfg, batch=2, max_len=32)
    assert names == ["gpt_0_cache_x", "gpt_1_cache_k", "gpt_1_cache_v",
                     "gpt_2_cache_x", "gpt_3_cache_x", "gpt_4_cache_x"]
    kinds = [gpt.cache_kind(cfg, n, 32) for n in names]
    assert kinds == ["state", "full", "full"] + ["state"] * 3
    assert gpt.has_state(cfg) and gpt.state_layers(cfg) == [0, 2, 3, 4]
    assert not gpt.has_rings(cfg, 32)
    ssm_cfg = dict(n_layer=3, mixers=["ssm", "attention", "ssm"])
    assert gpt.state_layers(ssm_cfg) == [0, 2]
    assert not gpt.has_state(dict(n_layer=2, layer_types=["full"] * 2))
    # rope_layers rotates attention layers only
    assert [gpt._rotates(cfg, i) for i in range(5)] == \
        [False, True, False, False, False]


# ------------------------------------------------- the program, the model
def _programs(cfg, P, max_len, batch=1):
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        logits, _ = gpt.build_prefill_step(cfg, batch=batch, prompt_len=P,
                                           max_len=max_len)
    dprog, dstart = fluid.Program(), fluid.Program()
    with fluid.program_guard(dprog, dstart):
        dlogits, _ = gpt.build_decode_step(cfg, batch=batch,
                                           max_len=max_len)
    return (prog, start, logits), (dprog, dstart, dlogits)


def _scope_with(exe, starts, params):
    from paddle_tpu.core.scope import Scope

    scope = Scope()
    for start in starts:
        exe.run(start, scope=scope)
    for n, v in params.items():
        scope.set_var(n, v)
    return scope


@pytest.mark.parametrize("P", [2, 21])
def test_prefill_matches_the_reference(P):
    """The whole forward of the program against the reference, at
    prompts shorter than the taps too: 2e-4 on logits of magnitude ~1."""
    cfg = tiny_cfg()
    params = seeded_params(cfg, 0)
    (prog, start, logits), _ = _programs(cfg, P, 64)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = _scope_with(exe, [start], params)
    ids = np.random.default_rng(1).integers(0, 97, (1, P))
    (got,) = exe.run(prog, feed={"tokens": ids}, fetch_list=[logits],
                     scope=scope)
    np.testing.assert_allclose(got[0], _ref_logits(params, cfg, ids[0]),
                               atol=2e-4)


@pytest.mark.parametrize("P,n", [(13, 11)])
def test_prefill_then_decode_matches_the_full_forward(P, n):
    """Prefill ``P`` then ``n`` decode steps through the rows and the
    slab: every step's logits are the reference's full forward over
    ``P + n`` at that position."""
    cfg = tiny_cfg()
    params = seeded_params(cfg, 2)
    (prog, start, logits), (dprog, dstart, dlogits) = _programs(cfg, P, 64)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = _scope_with(exe, [start, dstart], params)
    ids = np.random.default_rng(3).integers(0, 97, (1, P + n))
    want = _ref_logits(params, cfg, ids[0])
    (got,) = exe.run(prog, feed={"tokens": ids[:, :P]}, fetch_list=[logits],
                     scope=scope)
    np.testing.assert_allclose(got[0], want[:P], atol=2e-4)
    for t in range(P, P + n):
        (step,) = exe.run(dprog, feed={"token": ids[:, t:t + 1],
                                       "pos": np.array([t], "int64")},
                          fetch_list=[dlogits], scope=scope)
        np.testing.assert_allclose(step[0, 0], want[t], atol=2e-4)


def test_bf16_stored_matrices_and_a_tied_head_give_the_float32_tokens():
    """cfg['weight_dtype'] with ``tie_embeddings``: the table stored in
    bfloat16 widens in the lookup AND in the head, the taps stay
    float32, and the program answers as the float32 program over the
    same (bfloat16-valued) numbers."""
    cfg = tiny_cfg()
    params = seeded_params(cfg, 5)

    def matrix(n, v):
        return v.ndim >= 2 and not n.endswith("_conv.w_0")

    rounded = {n: (np.asarray(jnp.asarray(v, jnp.bfloat16)
                              .astype(jnp.float32)) if matrix(n, v) else v)
               for n, v in params.items()}
    stored = {n: (jnp.asarray(v, jnp.bfloat16) if matrix(n, v) else v)
              for n, v in params.items()}
    ids = np.random.default_rng(6).integers(0, 97, (1, 12))
    exe = fluid.Executor(fluid.CPUPlace())
    outs = []
    for c, p in ((cfg, rounded), (dict(cfg, weight_dtype="bfloat16"),
                                  stored)):
        (prog, start, logits), _ = _programs(c, 12, 32)
        dtypes = {q.name: str(q.dtype)
                  for q in prog.global_block().all_parameters()}
        assert dtypes["gpt_0_conv.w_0"] == "float32"
        assert dtypes["gpt_word_emb"] == c.get("weight_dtype", "float32")
        assert "gpt_out_proj.w_0" not in dtypes
        scope = _scope_with(exe, [start], p)
        (got,) = exe.run(prog, feed={"tokens": ids}, fetch_list=[logits],
                         scope=scope)
        outs.append(got)
    np.testing.assert_allclose(outs[1], outs[0], atol=1e-5)


def test_the_bf16_control_moves_the_logits():
    """The reference's control (weights and everything a layer hands on
    rounded to 7 mantissa bits) is not its float32 self."""
    cfg = tiny_cfg()
    params = seeded_params(cfg, 7)
    ids = np.random.default_rng(8).integers(0, 97, 24)
    hi = _ref_logits(params, cfg, ids)
    lo = _ref_logits(params, cfg, ids, mantissa_bits=7, activation_bits=7)
    # a flipped expert moves a whole term of a row: the median tells
    assert 1e-4 < np.median(np.abs(hi - lo)) < 0.1
    rows = _ref_logits(params, cfg, ids, rows=(5, 9))
    np.testing.assert_allclose(rows, hi[5:9], atol=1e-6)


# ---------------------------------------------------------- flash forward
@pytest.mark.parametrize("S,H,Hkv", [(256, 8, 2), (384, 4, 1), (300, 8, 2)])
def test_flash_forward_at_grouped_heads_of_64_matches_composed(
        S, H, Hkv, monkeypatch):
    """The served model's head grouping (4 query heads a key-value head,
    heads of 64) through the flash forward in interpret mode, against
    the composed attention and the definition."""
    from paddle_tpu.ops import attention as A

    monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_SEQ", "0")
    rs = np.random.RandomState(S + H)
    q, k, v = (jnp.asarray(rs.randn(1, n, S, 64).astype("float32"))
               for n in (H, Hkv, Hkv))
    got = A.flash_attention(q, k, v, None, 0.125, causal=True)
    want = A.composed_attention(q, k, v, None, 0.125, True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    kr, vr = (np.repeat(np.asarray(t), H // Hkv, axis=1) for t in (k, v))
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), kr) * 0.125
    s = np.where(np.tril(np.ones((S, S), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    np.testing.assert_allclose(got, np.einsum("bhqk,bhkd->bhqd", p, vr),
                               atol=2e-5, rtol=0)


def test_the_prefill_hands_the_kernel_rank_4_operands(monkeypatch):
    """The attention layers' prefill keeps the heads layout ([B, H, S, D]
    operands, grouped heads through the block index): the lanes layout
    of the forward-only call is the latent prefill's alone (PR 50)."""
    from test_attention import _lane_plans

    monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_SEQ", "0")
    cfg = tiny_cfg()
    (prog, start, logits), _ = _programs(cfg, 21, 64)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = _scope_with(exe, [start], seeded_params(cfg, 0))
    before = _lane_plans()
    exe.run(prog, feed={"tokens": np.ones((1, 21), "int64")},
            fetch_list=[logits], scope=scope)
    n_att = sum(1 for i in range(cfg["n_layer"])
                if gpt.kind_of(cfg, i).name != "conv")
    assert {k: n - before.get(k, 0) for k, n in _lane_plans().items()
            if n > before.get(k, 0)} == {("flash_fwd", "heads"): n_att}


# ----------------------------------------------------------------- engine
@pytest.fixture(scope="module")
def served():
    from paddle_tpu.serving import DecodeEngine

    cfg = tiny_cfg()
    params = seeded_params(cfg, 11)
    engine = DecodeEngine(cfg, params=params, b_max=3, max_len=48,
                          place=fluid.CPUPlace())
    engine.start()
    yield cfg, params, engine
    engine.stop()


_GENERATE = {}      # the programs of ``_generate``, compiled once each


def _generate(cfg, params, prompt, n_new, max_len=48):
    P = len(prompt)
    if "exe" not in _GENERATE:
        _GENERATE["exe"] = fluid.Executor(fluid.CPUPlace())
        _GENERATE["decode"] = _programs(cfg, 1, max_len)[1]
    if P not in _GENERATE:
        _GENERATE[P] = _programs(cfg, P, max_len)[0]
    exe = _GENERATE["exe"]
    (prog, start, logits), (dprog, dstart, dlogits) = \
        _GENERATE[P], _GENERATE["decode"]
    scope = _scope_with(exe, [start, dstart], params)
    return gpt.generate(exe, dprog, dlogits, np.asarray(prompt)[None],
                        n_new, scope, prefill_prog=prog,
                        prefill_logits=logits)[0]


def test_engine_tokens_equal_generates_and_are_row_local(served):
    """Requests of different lengths (one of a single token: shorter
    than the taps) in company through the engine's slots answer as
    ``generate`` answers each alone, and as the float32 reference
    chooses."""
    cfg, params, engine = served
    rng = np.random.default_rng(12)
    asks = [(rng.integers(0, 97, size=P), n)
            for P, n in ((9, 8), (1, 10), (17, 5), (9, 3))]
    handles = [engine.submit(p, n) for p, n in asks]
    outs = [h.result(timeout=300) for h in handles]
    for (prompt, n), out in zip(asks, outs):
        np.testing.assert_array_equal(out, _generate(cfg, params, prompt, n))
    prompt, n = asks[0]
    logits = _ref_logits(params, cfg, outs[0][:-1])
    picked = logits[len(prompt) - 1:].argmax(-1)
    assert (picked == outs[0][len(prompt):]).mean() >= 0.9


def test_a_reused_slot_shows_nothing_of_its_previous_tenant(served):
    """b_max long requests fill every slot's rows and slab; a shorter
    request (shorter than the taps: its rows start as zeros) then takes
    a slot one of them left: its answer is what it is alone."""
    cfg, params, engine = served
    rng = np.random.default_rng(13)
    long_ = [engine.submit(rng.integers(0, 97, size=17), 16)
             for _ in range(3)]
    for h in long_:
        h.result(timeout=300)
    for _ in range(3):      # whichever slot it is given
        short = rng.integers(0, 97, size=1)
        got = engine.submit(short, 10).result(timeout=300)
        np.testing.assert_array_equal(got,
                                      _generate(cfg, params, short, 10))


def test_engine_counts_both_kinds_the_positions_and_spans_the_state(served):
    from paddle_tpu.observe import REGISTRY
    from paddle_tpu.observe import trace as flight

    def positions():
        got = REGISTRY.snapshot()["metrics"].get(
            "paddle_serving_positions_total", {"samples": []})
        return {s["labels"]["kind"]: s["value"] for s in got["samples"]}

    def steps():
        got = REGISTRY.snapshot()["metrics"][
            "paddle_serving_decode_steps_total"]
        return got["samples"][0]["value"]

    cfg, params, engine = served
    got = REGISTRY.snapshot()["metrics"]["paddle_serving_cache_bytes"]
    held = {s["labels"]["kind"]: s["value"] for s in got["samples"]}
    assert held["state"] == 4 * 3 * 2 * 64 * 4      # 4 layers x 3 slots
    assert held["full"] == 2 * 3 * 2 * 48 * 16 * 4
    assert held["ring"] == held["latent"] == 0
    before, steps0 = positions(), steps()
    engine.submit(np.arange(1, 18), 5).result(timeout=300)
    after, n_steps = positions(), steps() - steps0
    # alone in the engine: 4 steps at lengths 18 .. 21 (the first token
    # is the prefill's), each over b_max x max_len held rows
    assert n_steps == 4
    assert after["live"] - before.get("live", 0) == 18 + 19 + 20 + 21
    assert after["held"] - before.get("held", 0) == 4 * 3 * 48
    spans = [e for e in flight.recorder().events()
             if e["site"] == "serving.engine.prefill"
             and (e.get("attrs") or {}).get("prompt_len") == 17]
    assert spans and spans[-1]["attrs"]["state_layers"] == 4
    assert "chunks" not in spans[-1]["attrs"]
    assert engine.routed_pairs().shape == (5, 8)
    assert engine.experts_touched().shape == (5, 8)
    foot = engine._lane.memory_footprint()
    assert foot["resident"] > held["full"] and foot["prefill_extra_hi"] > 0
    assert engine.predicted_bytes(40) >= engine.predicted_resident_bytes()
