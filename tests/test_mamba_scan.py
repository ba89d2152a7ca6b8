"""Jamba's layer kinds (``model_type`` jamba) through the system's normal
path, against the plain reference (tests/references/jamba.py, of which
benchmarks/references/ai21-jamba2-3b.py is a bit-equal copy): a Mamba-1
mixer as a layer's first sub-block, whose decay is one number a channel
AND state (``layers.mamba_mix``, kernels/mamba.py), beside multi-query
attention without positions, a dense FFN in every layer and the token
table as the head — the blocked and in-place forms the system runs
against the token-by-token recurrence the reference runs."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.kernels import mamba
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


reference = _load(os.path.join(HERE, "references", "jamba.py"))


def tiny_cfg(**over):
    """Jamba in small, in Jamba's order: of six layers the fourth is
    attention (5 query heads over ONE key-value head of 16, no position),
    the others Mamba mixers of 128 channels of 8 states with a ``dt`` of
    rank 6; a dense FFN in every layer; the table is the head."""
    cfg = dict(d_model=48, n_head=5, n_kv_head=1, d_head=16, n_layer=6,
               vocab=97, max_length=256, dropout=0.0, pos_emb="none",
               norm="rms", norm_eps=1e-6, tie_embeddings=True,
               layer_types=["full" if i % 6 == 3 else "mamba"
                            for i in range(6)],
               mamba_inner=128, mamba_state=8, mamba_dt_rank=6,
               ssm_conv=4, ffn_act="swiglu", d_ff=96)
    cfg.update(over)
    return cfg


def seeded_params(cfg, seed):
    """Every parameter drawn from the seed, float32: matrices within
    Xavier limits, the recurrence's own where the published
    initialisation puts them (``softplus(dt_b)`` log-uniform over
    0.001-0.1, ``exp(a_log)`` uniform over 1-16, the taps and their bias
    within 0.5), the other vectors in 0.5-1.5."""
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        gpt.build_serving_decode_step(cfg, batch=1, max_len=16)
    rng = np.random.default_rng(seed)
    out = {}
    for p in sorted(prog.global_block().all_parameters(),
                    key=lambda p: p.name):
        shape = tuple(p.shape)
        if p.name.endswith("_mamba_dt_b"):
            v = np.log(np.expm1(np.exp(rng.uniform(
                np.log(1e-3), np.log(0.1), shape))))
        elif p.name.endswith("_mamba_a_log"):
            v = np.log(rng.uniform(1.0, 16.0, shape))
        elif "_mamba_conv." in p.name:
            v = rng.uniform(-0.5, 0.5, shape)
        elif len(shape) == 1:
            v = rng.uniform(0.5, 1.5, shape)
        else:
            lim = (6.0 / (shape[-2] + shape[-1])) ** 0.5
            v = rng.uniform(-lim, lim, shape)
        out[p.name] = v.astype("float32")
    return out


def _ref_logits(params, cfg, ids, **kw):
    return np.asarray(reference.forward(params, cfg, jnp.asarray(ids), **kw))


def _operands(seed, B, T, C, N, dt):
    """``(u, dt, a, bm, cm)`` of a scan, ``dt`` log-uniform in the given
    range and ``A`` uniform over -16..-1."""
    rs = np.random.RandomState(seed)
    u = rs.randn(B, T, C).astype("float32")
    d = np.exp(rs.uniform(np.log(dt[0]), np.log(dt[1]), (B, T, C)))
    a = -rs.uniform(1.0, 16.0, (C, N)).astype("float32")
    bm = rs.randn(B, T, N).astype("float32")
    cm = rs.randn(B, T, N).astype("float32")
    return tuple(jnp.asarray(t) for t in (u, d.astype("float32"), a, bm, cm))


def _recurrence(u, dt, a, bm, cm):
    """The reference's token-by-token form, a sequence at a time, as ``(y
    [B, T, C], state [B, 1, N, C])``."""
    ys, ss = zip(*(reference.selective_scan(u[b], dt[b], a, bm[b], cm[b])
                   for b in range(u.shape[0])))
    return jnp.stack(ys), jnp.swapaxes(jnp.stack(ss), 1, 2)[:, None]


# ------------------------------------------------------------- the core
@pytest.mark.parametrize("dt", [(0.0008, 0.0012), (0.08, 0.12)],
                         ids=["dt_near_0.001", "dt_near_0.1"])
@pytest.mark.parametrize("block", [1, 16, 128])
def test_blocked_form_is_the_recurrence(block, dt):
    """The composed scan at blocks of 1, 16 and 128 positions over a
    prompt of two and a half blocks of 16 against the recurrence one
    token at a time: slow and fast decays, the state carried across the
    blocks."""
    ops = _operands(block, 2, 40, 128, 8, dt)
    want_y, want_s = _recurrence(*ops)
    y, s = mamba.mamba_scan_composed(*ops, block=block)
    np.testing.assert_allclose(y, want_y, atol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5)


def test_scan_kernel_matches_composed_and_the_recurrence():
    """The time-walking kernel in interpret mode over a ragged prompt of
    two and a half blocks of 128 (the padding neither decays nor feeds
    the state), one tile of 8 x 128 channels."""
    ops = _operands(3, 1, 320, 1024, 16, (0.001, 0.1))
    want_y, want_s = _recurrence(*ops)
    y, s = mamba.mamba_scan_pallas(*ops, block=128, unroll=2,
                                   interpret=True)
    yc, sc = mamba.mamba_scan_composed(*ops, block=128)
    for got_y, got_s in ((y, s), (yc, sc)):
        np.testing.assert_allclose(got_y, want_y, atol=2e-5)
        np.testing.assert_allclose(got_s, want_s, atol=2e-5)
    assert s.shape == mamba.state_shape(1, 1024, 16)


def test_update_kernel_matches_composed_in_place():
    """One token a slot on top of a scanned state: the kernel in
    interpret mode, the composed form and the recurrence's next step
    agree, and the state keeps its layout."""
    u, dt, a, bm, cm = _operands(4, 3, 9, 256, 16, (0.001, 0.1))
    _y, state = mamba.mamba_scan_composed(u[:, :8], dt[:, :8], a,
                                          bm[:, :8], cm[:, :8], block=8)
    want_y, want_s = _recurrence(u, dt, a, bm, cm)
    last = (u[:, 8], dt[:, 8], a, bm[:, 8], cm[:, 8])
    for form in (mamba.mamba_update_composed,
                 lambda *t: mamba.mamba_update_pallas(*t, interpret=True)):
        y, new = form(state, *last)
        np.testing.assert_allclose(y, want_y[:, 8], atol=2e-5)
        np.testing.assert_allclose(new, want_s, atol=2e-5)
        assert new.shape == state.shape == (3, 1, 16, 256)


def test_plans_say_when_the_kernels_take_a_shape():
    # Jamba2-3B's: all 5,120 channels one tile of 8 x 640 (80 registers
    # of state), blocks of 256 positions, eight a loop body
    assert mamba._scan_plan(16384, 5120, 16) == (mamba.BLOCK, 640, 8)
    assert mamba._scan_plan(16384, 5120, 16, lanes=128, unroll=2) \
        == (256, 128, 2)
    assert mamba._tile_lanes(10240, 16) == 640 and \
        mamba._tile_lanes(1024, 128) == 128
    assert mamba._scan_plan(64, 5120, 16) is None     # under a lane tile
    assert mamba._scan_plan(2048, 5000, 16) is None
    assert mamba._update_plan((32, 1, 16, 5120)) == 5120
    assert mamba._update_plan((32, 1, 16, 100)) is None
    assert mamba.scan_block(16384) == mamba.BLOCK and mamba.scan_block(21) \
        == 24


def test_attention_at_twenty_heads_over_one_without_positions():
    """A model of ONE attention layer (a Mamba layer in front so that
    ``pos_emb='none'`` stands) at 5 query heads over one key-value head:
    the prefill's logits are a hand-written causal softmax's, no rotation
    anywhere, and permuting the prompt permutes nothing else."""
    cfg = tiny_cfg(n_layer=2, layer_types=["mamba", "full"])
    params = seeded_params(cfg, 5)
    ids = np.random.default_rng(6).integers(0, 97, (9,))
    want = _ref_logits(params, cfg, ids)
    # the attention layer by hand, on the reference's own input to it
    x = jnp.asarray(params["gpt_word_emb"])[ids]
    pre = {k[len("gpt_0_"):]: v for k, v in params.items()
           if k.startswith("gpt_0_")}
    items = reference._hashable(cfg)
    x = reference.dense(pre, reference.mamba(pre, x, items), items)
    h = reference._rms_norm(x, params["gpt_1_pre1_ln_s"], 1e-6)
    q = (h @ params["gpt_1_att_q.w_0"]).reshape(9, 5, 16)
    k, v = h @ params["gpt_1_att_k.w_0"], h @ params["gpt_1_att_v.w_0"]
    ctx = np.zeros((9, 5, 16), "float32")
    for t in range(9):
        for j in range(5):
            sc = np.asarray(k[:t + 1] @ q[t, j]) / 4.0
            w = np.exp(sc - sc.max())
            ctx[t, j] = (w / w.sum()) @ np.asarray(v[:t + 1])
    mine = x + ctx.reshape(9, 80) @ params["gpt_1_att_o.w_0"]
    post = {k[len("gpt_1_"):]: v for k, v in params.items()
            if k.startswith("gpt_1_")}
    mine = reference.dense(post, mine, items)
    logits = reference._rms_norm(mine, params["gpt_ln_f_s"], 1e-6) \
        @ params["gpt_word_emb"].T
    np.testing.assert_allclose(logits, want, atol=2e-4)
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        out, _ = gpt.build_prefill_step(cfg, batch=1, prompt_len=9,
                                        max_len=16)
    assert "rope" not in [op.type for op in prog.global_block().ops]
    exe, scope = _scope_with(params, [(prog, start)])
    (got,) = exe.run(prog, feed={"tokens": ids[None]}, fetch_list=[out],
                     scope=scope)
    np.testing.assert_allclose(got[0], want, atol=2e-4)


def _scope_with(params, progs):
    from paddle_tpu.core.scope import Scope

    exe, scope = fluid.Executor(fluid.CPUPlace()), Scope()
    for _prog, start in progs:
        exe.run(start, scope=scope)
    for name, val in params.items():
        scope.set_var(name, val)
    return exe, scope


def test_tied_head_reads_the_token_table():
    """No ``gpt_out_proj``: the head is the table, widened where it is
    stored in bfloat16."""
    cfg = tiny_cfg(weight_dtype="bfloat16")
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        gpt.build_serving_decode_step(cfg, batch=2, max_len=16)
    block = prog.global_block()
    names = {p.name: str(p.dtype) for p in block.all_parameters()}
    assert "gpt_out_proj.w_0" not in names
    assert names["gpt_word_emb"] == "bfloat16"
    # A_log [C, N] is rank 2 and stays float32, like the taps
    assert names["gpt_0_mamba_a_log"] == "float32"
    assert names["gpt_0_mamba_conv.w_0"] == "float32"
    assert names["gpt_0_mamba_in.w_0"] == "bfloat16"


# ------------------------------------------------------------ the engine
@pytest.fixture(scope="module")
def served():
    """(cfg, params, a started engine of three slots), its prefills
    scanned in blocks of 8 so that tiny prompts cross block boundaries."""
    from paddle_tpu.serving import DecodeEngine

    cfg = tiny_cfg()
    params = seeded_params(cfg, 11)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(mamba, "BLOCK", 8)
        engine = DecodeEngine(cfg, params=params, b_max=3, max_len=96)
        engine.start()
        yield cfg, params, engine
        engine.stop()


def _worst_margin(params, cfg, out, plen):
    logits = _ref_logits(params, cfg, out)[plen - 1:-1]
    chosen = logits[np.arange(len(logits)), out[plen:]]
    return float((logits.max(-1) - chosen).max())


@pytest.mark.parametrize("plen", [5, 8, 21])
def test_engine_prefill_then_decode_is_the_references_forward(served, plen):
    """Prompts shorter than, equal to and of several blocks on a lane of
    Mamba and full layers in Jamba's order; the answer runs on through
    the in-place update and the slab. Every generated token is the argmax
    of the reference's full forward pass over the whole sequence (or
    within float32 rounding of it)."""
    cfg, params, engine = served
    prompt = np.random.RandomState(plen).randint(0, cfg["vocab"], (plen,))
    out = engine.submit(prompt.astype("int64"), 14).result(timeout=300)
    assert out.shape == (plen + 14,)
    assert _worst_margin(params, cfg, out, plen) < 1e-3


def test_prefill_then_decode_logits_are_the_full_forwards():
    """The two programs by hand over one scope: the prefill's logits at
    every prompt position and each decode step's are the reference's
    full forward over the whole sequence at that position."""
    cfg, P, n = tiny_cfg(), 13, 6
    params = seeded_params(cfg, 2)
    progs = []
    for build, kw in ((gpt.build_prefill_step, {"prompt_len": P}),
                      (gpt.build_decode_step, {})):
        prog, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, start):
            logits, _ = build(cfg, batch=1, max_len=32, **kw)
        progs.append((prog, start, logits))
    exe, scope = _scope_with(params, [p[:2] for p in progs])
    ids = np.random.default_rng(3).integers(0, 97, (1, P + n))
    want = _ref_logits(params, cfg, ids[0])
    (got,) = exe.run(progs[0][0], feed={"tokens": ids[:, :P]},
                     fetch_list=[progs[0][2]], scope=scope)
    np.testing.assert_allclose(got[0], want[:P], atol=2e-4)
    for t in range(P, P + n):
        (step,) = exe.run(progs[1][0], feed={
            "token": ids[:, t:t + 1], "pos": np.array([t], "int64")},
            fetch_list=[progs[1][2]], scope=scope)
        np.testing.assert_allclose(step[0, 0], want[t], atol=2e-4)


def test_lane_holds_states_and_slabs_together(served):
    """Two state tensors a Mamba layer and a slab pair for the full one,
    in ONE lane; the decode step holds five in-place updates, five
    convolution steps and the full layer's cache writes, the mixer under
    its scope; the gauges and counters read them."""
    from paddle_tpu.observe import REGISTRY

    cfg, _params, engine = served
    engine.submit(np.arange(9, dtype="int64"), 2).result(timeout=300)
    lane = engine._lane
    mixers = [i for i in range(6) if i != 3]
    assert lane.cache_names == [
        "gpt_%d_cache_%s" % (i, c) for i in range(3) for c in "xs"] \
        + ["gpt_3_cache_k", "gpt_3_cache_v"] + [
        "gpt_%d_cache_%s" % (i, c) for i in (4, 5) for c in "xs"]
    assert [gpt.cache_kind(cfg, n, 96) for n in lane.cache_names] \
        == ["state"] * 6 + ["full"] * 2 + ["state"] * 4
    block = lane._decode_prog.global_block()
    ops = [op.type for op in block.ops]
    assert ops.count("mamba_update") == ops.count("causal_conv_step") == 5
    assert ops.count("kv_cache_write") == 2
    assert [op.name_scope for op in block.ops if op.type == "mamba_update"] \
        == ["L%d/mixer" % i for i in mixers]
    assert {op.name_scope for op in block.ops if op.type == "mul"
            and "_ffn" in op.input_names()[1]} \
        == {"L%d/ffn" % i for i in range(6)}
    snap = REGISTRY.snapshot()["metrics"]
    held = 3 * 5 * (8 * 128 + 3 * 128) * 4
    assert snap["paddle_mamba_state_bytes"]["samples"][0]["value"] == held
    kinds = {s["labels"]["kind"]: s["value"]
             for s in snap["paddle_serving_cache_bytes"]["samples"]}
    assert kinds["state"] == held
    assert kinds["full"] == 3 * 2 * 1 * 96 * 16 * 4
    seen = {(s["labels"]["kernel"], s["labels"]["form"],
             s["labels"]["block"])
            for s in snap["paddle_mamba_plans_total"]["samples"]
            if s["value"]}
    assert ("mamba_update", "composed", "1") in seen
    assert ("mamba_scan", "composed", "8") in seen
    assert snap["paddle_mamba_chunks_total"]["samples"][0]["value"] > 0


def test_reused_slot_shows_nothing_of_its_previous_tenant(served):
    """Fill every slot, let them finish, then ask the same question
    again: the answer is the first one's, whatever state, convolution
    rows and key-value rows the slot's last tenant left."""
    cfg, _params, engine = served
    rs = np.random.RandomState(5)
    prompt = rs.randint(0, cfg["vocab"], (11,)).astype("int64")
    first = engine.submit(prompt, 9).result(timeout=300)
    others = [engine.submit(rs.randint(0, cfg["vocab"], (n,))
                            .astype("int64"), 12) for n in (19, 7, 23, 30)]
    for handle in others:
        handle.result(timeout=300)
    again = engine.submit(prompt, 9).result(timeout=300)
    np.testing.assert_array_equal(again, first)


# ----------------------------------------------------------- the refusals
def test_training_build_refuses_by_name():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        with pytest.raises(ValueError, match="'mamba' layers.*state of 8 "
                           "states a channel over 128 channels"
                           ".*gpt_<i>_cache_x.*a layer that "
                           "carries a state has no backward"):
            gpt.build(tiny_cfg(), seq_len=8)


def test_multi_token_step_refuses_by_name():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        with pytest.raises(ValueError, match="build_multi_token_decode_step"
                           ".*'mamba' layers.*selective-scan state"):
            gpt.build_multi_token_decode_step(tiny_cfg(), batch=1, steps=2,
                                              max_len=16)


@pytest.mark.parametrize("lever", ["prefix_store", "draft"])
def test_engine_levers_refuse_by_name(lever):
    from paddle_tpu.serving import DecodeEngine, PrefixStore

    kw = {"prefix_store": PrefixStore(1 << 20)} \
        if lever == "prefix_store" \
        else {"draft_cfg": tiny_cfg(n_layer=1, layer_types=["mamba"]),
              "spec_k": 2}
    with pytest.raises(ValueError, match="'mamba' layers.*"
                       "gpt_<i>_cache_s, gpt_<i>_cache_x"):
        DecodeEngine(tiny_cfg(), b_max=2, max_len=32, **kw)


@pytest.mark.parametrize("over, match", [
    ({"mamba_chunk": 8}, "unknown gpt cfg key.*mamba_chunk"),
    ({"mamba_expand": 2}, "unknown gpt cfg key.*mamba_expand"),
    ({"mamba_state": None}, "a 'mamba' layer needs cfg\\['mamba_state'\\]"),
    ({"ssm_conv": 1}, ">= 2 taps"),
    ({"d_ff": None, "n_expert": 4, "expert_top_k": 2, "d_expert": 8},
     "cfg\\['d_ff'\\] \\(the dense FFN behind the mixer\\)"),
    ({"layer_types": ["full"] * 6, "pos_emb": "rope"},
     "cfg\\['ssm_conv'\\] needs an 'ssm' layer in cfg\\['mixers'\\] or a "
     "'mamba' layer in cfg\\['layer_types'\\]"),
    ({"layer_types": ["full"] * 6, "pos_emb": "rope", "ssm_conv": None},
     "cfg\\['mamba_inner'\\] needs a 'mamba' layer"),
    ({"residual": "mhc", "hc_mult": 2, "pos_emb": "rope"},
     "takes no cfg\\['residual'\\]"),
    ({"layer_types": ["full"] * 6, "mamba_inner": None, "mamba_state": None,
      "mamba_dt_rank": None, "ssm_conv": None},
     "cfg\\['pos_emb'\\]='none' needs an 'ssm' layer in cfg\\['mixers'\\] "
     "or a 'mamba' layer in cfg\\['layer_types'\\]"),
])
def test_check_cfg_refuses(over, match):
    cfg = {k: v for k, v in tiny_cfg(**over).items() if v is not None}
    with pytest.raises(ValueError, match=match):
        gpt._check_cfg(cfg)


def test_pos_emb_none_stands_beside_the_layers_that_order_the_tokens():
    """Read from the table: the rows with ``orders``, and no others."""
    assert {k.name for k in gpt.LAYER_KINDS.values() if k.orders} \
        == {"ssm", "mamba"}
    gpt._check_cfg(tiny_cfg())
    cfg = tiny_cfg()
    assert gpt.state_layers(cfg) == [0, 1, 2, 4, 5] and gpt.has_state(cfg)
    assert [gpt._rotates(cfg, i) for i in range(6)] == [False] * 6
    assert gpt.mamba_widths(cfg) == (128, 8, 6, 4)


def test_analysis_rules_know_the_two_ops():
    """Shape, cost, range and footprint rules of ``mamba_scan`` and
    ``mamba_update`` on the tiny cfg's programs: the declared state
    shapes are the inferred ones, nothing is left to a default, and the
    state is counted as what it is."""
    from paddle_tpu.analysis.cost import CostAnalysis
    from paddle_tpu.analysis.cost_rules import COST_RULES
    from paddle_tpu.analysis.infer import verify_program
    from paddle_tpu.analysis.memory import FOOTPRINT_RULES, MemoryAnalysis
    from paddle_tpu.analysis.ranges import RANGE_RULES

    for table in (COST_RULES, RANGE_RULES, FOOTPRINT_RULES):
        assert "mamba_scan" in table and "mamba_update" in table
    cfg = tiny_cfg()
    for build, kw, op_type in (
            (gpt.build_prefill_step, {"prompt_len": 24}, "mamba_scan"),
            (gpt.build_serving_decode_step, {}, "mamba_update")):
        prog = fluid.Program()
        with fluid.program_guard(prog, fluid.Program()):
            build(cfg, batch=2, max_len=32, **kw)
        block = prog.global_block()
        ops = [op for op in block.ops if op.type == op_type]
        assert len(ops) == 5
        for op in ops:
            assert tuple(block.var(op.outputs["StateOut"][0]).shape) \
                == (2, 1, 8, 128)
        assert not [f for f in verify_program(prog, fill=False)
                    if f.severity == "error"]
        assert not CostAnalysis(prog).unruled
        ma = MemoryAnalysis(prog, site="serving")
        assert ma.tensors["gpt_1_cache_s"].poly.at(1) == 2 * 8 * 128 * 4
        assert ma.tensors["gpt_1_cache_x"].poly.at(1) == 2 * 3 * 128 * 4


# tests/test_gpt_programs_pinned.py::digest of ``ai21-jamba2-3b`` at the
# parent of PR 60 (commit 756e27c): the two decode steps, which that PR's
# convolution kernel is not to move
_PARENT_STEPS = {
    "serving_decode": {
        "n_ops": 720, "n_params": 462,
        "sha256": "be50a8f97c2c8c9e4398415d3ee9486e"
                  "17317fc5f8499e5075bd40798b077241",
        "params": "6de14a13dc50fff1951464ea2c9237ba"
                  "1383206a33ace899db6b9bd57194ab4b",
        "startup": "755a45c7d35278ed4503c516ca882a12"
                   "4ac607cb754230fd34ab26ae8add0575"},
    "decode": {
        "n_ops": 721, "n_params": 462,
        "sha256": "41221257a978bef00cd64cdd091d2971"
                  "fa29e5bfc8163fdaa80af819837dd87c",
        "params": "6de14a13dc50fff1951464ea2c9237ba"
                  "1383206a33ace899db6b9bd57194ab4b",
        "startup": "1ba32a1c647733af40b104da3ca78859"
                   "41bbedee0d406d26fc7ddabbb85ef278"},
}


@pytest.mark.parametrize("build", ["serving_decode", "decode", "prefill"])
def test_published_programs_round_the_prompts_convolution(build):
    """At the published widths: the decode steps are the parent's op for
    op (a token's convolution is cut out of ``proj`` and stepped as it
    was); the prefill holds one ``causal_conv`` a mamba layer that reads
    its 5,120 columns where ``W_in`` wrote them (attr ``columns``, which no
    other configuration's op carries: tests/test_gpt_programs_pinned.py)
    and no slice of ``proj`` in front of it."""
    from test_gpt_programs_pinned import digest

    if build != "prefill":
        got = digest(("ai21-jamba2-3b", build, None))
        assert got["pins"] == 0
        assert {k: got[k] for k in _PARENT_STEPS[build]} \
            == _PARENT_STEPS[build]
        return
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ai21-jamba2-3b.json")) as f:
        conf = json.load(f)
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        gpt.build_prefill_step(conf["model"], batch=1, prompt_len=2048,
                               max_len=conf["serving"]["max_len"])
    block = prog.global_block()
    convs = [op for op in block.ops if op.type == "causal_conv"]
    mamba = [i for i, t in enumerate(conf["model"]["layer_types"])
             if t == "mamba"]
    assert [op.name_scope for op in convs] == ["L%d/mixer" % i for i in mamba]
    assert len(convs) == 26
    made_by = {n: op for op in block.ops
               for names in op.outputs.values() for n in names}
    for op in convs:
        assert op.attrs == {"act": True, "columns": [0, 5120]}
        x = op.inputs["X"][0]
        assert tuple(block.var(x).shape)[1:] == (2048, 10240)
        assert made_by[x].type != "slice"
        assert tuple(block.var(op.outputs["Out"][0]).shape)[1:] \
            == (2048, 5120)


def test_reference_copies_are_bit_equal():
    with open(os.path.join(HERE, "references", "jamba.py"), "rb") as a, \
            open(os.path.join(ROOT, "benchmarks", "references",
                              "ai21-jamba2-3b.py"), "rb") as b:
        assert a.read() == b.read()
