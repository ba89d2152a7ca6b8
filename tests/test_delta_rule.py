"""Qwen3-Next's layer kinds (``model_type`` qwen3_next) through the
system's normal path, against the plain reference
(tests/references/qwen3_next.py, of which
benchmarks/references/qwen3-next-80b-a3b.py is a bit-equal copy): the
gated delta rule as a layer's first sub-block, whose slot is a state that
is READ before it is written (``layers.delta_rule``, kernels/delta.py),
beside gated attention whose heads rotate in part, over a share of the
experts with a gated shared expert — the chunked and in-place forms the
system runs against the token-by-token recurrence the reference runs."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.kernels import delta
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


reference = _load(os.path.join(HERE, "references", "qwen3_next.py"))


def tiny_cfg(**over):
    """Qwen3-Next in small: one period (three delta layers of 4 value
    heads over 2 key heads of 16, one gated attention layer of 4 heads
    over 2 of 32 that rotate 8 of them), 8 experts at top-2 with a gated
    shared expert, an untied head."""
    cfg = dict(d_model=64, n_head=4, n_kv_head=2, d_head=32, n_layer=4,
               vocab=97, max_length=256, dropout=0.0, pos_emb="rope",
               rope_theta=10000000.0, rope_dim=8, norm="rms",
               norm_eps=1e-6, qk_norm="head", attn_gate=True,
               tie_embeddings=False,
               layer_types=["delta", "delta", "delta", "full"],
               delta_k_heads=2, delta_v_heads=4, delta_k_dim=16,
               delta_v_dim=16, ffn_act="swiglu", n_expert=8,
               expert_top_k=2, d_expert=32, n_shared_expert=1,
               shared_expert_gate=True, router_score="softmax",
               norm_topk=True)
    cfg.update(over)
    return cfg


def seeded_params(cfg, seed):
    """Every parameter drawn from the seed, float32: matrices within
    Xavier limits, the decay's two vectors where the published
    initialisation puts them (``softplus(dt_b)`` log-uniform over
    0.001-0.1, ``exp(a_log)`` uniform over 1-16), the taps within 0.5,
    the other vectors in 0.5-1.5."""
    cfg = {k: v for k, v in cfg.items() if k != "weight_dtype"}
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        gpt.build_serving_decode_step(cfg, batch=1, max_len=16)
    rng = np.random.default_rng(seed)
    out = {}
    for p in sorted(prog.global_block().all_parameters(),
                    key=lambda p: p.name):
        shape = tuple(p.shape)
        if p.name.endswith("_delta_dt_b"):
            v = np.log(np.expm1(np.exp(rng.uniform(
                np.log(1e-3), np.log(0.1), shape))))
        elif p.name.endswith("_delta_a_log"):
            v = np.log(rng.uniform(1.0, 16.0, shape))
        elif p.name.endswith("_delta_conv.w_0"):
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


def _operands(seed, B, T, Hk, Hv, D, decay, beta, alike=0.0):
    """``(q, k, v, g, beta)`` of a scan: decays and writing strengths
    uniform in the given ranges, the keys drawn toward one direction by
    ``alike``."""
    rs = np.random.RandomState(seed)
    q = rs.randn(B, T, Hk, D).astype("float32")
    k = rs.randn(B, T, Hk, D).astype("float32") \
        + alike * rs.randn(B, 1, Hk, D).astype("float32")
    v = rs.randn(B, T, Hv, D).astype("float32")
    g = np.log(rs.uniform(decay[0], decay[1], (B, T, Hv))).astype("float32")
    b = rs.uniform(beta[0], beta[1], (B, T, Hv)).astype("float32")
    q, k = delta.normed(jnp.asarray(q), jnp.asarray(k))
    return q, k, jnp.asarray(v), jnp.asarray(g), jnp.asarray(b)


def _recurrence(q, k, v, g, beta):
    """The reference's token-by-token form, a sequence at a time."""
    J = v.shape[2] // q.shape[2]
    with jax.default_matmul_precision("highest"):
        return jnp.stack([reference.delta_rule(
            jnp.repeat(q[b], J, axis=1), jnp.repeat(k[b], J, axis=1), v[b],
            g[b], beta[b]) for b in range(q.shape[0])])


# ------------------------------------------------------------- the core
@pytest.mark.parametrize("beta", [(0.01, 0.05), (0.95, 0.999)],
                         ids=["beta_near_0", "beta_near_1"])
@pytest.mark.parametrize("decay", [(0.99, 0.9995), (0.45, 0.55)],
                         ids=["gate_near_1", "gate_0.5"])
@pytest.mark.parametrize("chunk", [1, 16, 64])
def test_chunked_form_is_the_recurrence(chunk, decay, beta):
    """One layer's core, value heads 2 : 1 over the key heads, T = 37:
    chunk 1 is token by token, 16 leaves a ragged last chunk, 64 is one
    chunk. The scan's output and the token-by-token update's agree with
    the reference's recurrence, and both leave the same state."""
    ops = _operands(3, 2, 37, 2, 4, 16, decay, beta, alike=0.5)
    want = np.asarray(_recurrence(*ops))
    y, S = delta.delta_scan_composed(*ops, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-6)
    s = jnp.zeros(delta.state_shape(2, 4, 16, 16), jnp.float32)
    for t in range(37):
        y1, s = delta.delta_update_composed(s, *(o[:, t] for o in ops))
        np.testing.assert_allclose(np.asarray(y1), want[:, t], atol=2e-6)
    np.testing.assert_allclose(np.asarray(S), np.asarray(s), atol=2e-6)


@pytest.mark.parametrize("chunk", [32, 64])
@pytest.mark.parametrize("case", ["ragged_two_chunks", "repeating_keys"])
def test_scan_kernel_matches_composed_and_the_recurrence(case, chunk):
    """The Pallas scan in interpret mode at heads of 128, a prompt of 100
    over two chunks of 64 or four of 32 (the last ragged: positions of
    padding that neither decay nor feed the state): against its composed
    form and against the reference, state and all. ``repeating_keys`` is
    the draw the solve was chosen on: keys within a hundredth of one
    direction, ``beta`` at 0.999 and no decay — where the nilpotent
    product form the chip sweep also timed lost every digit
    (docs/KERNELS.md) and the inverse by halves stays at rounding."""
    if case == "repeating_keys":
        ops = _operands(5, 1, 100, 1, 2, 128, (1.0, 1.0), (0.999, 0.999),
                        alike=100.0)
    else:
        ops = _operands(4, 2, 100, 1, 2, 128, (0.9, 0.999), (0.3, 0.7))
    y, S = delta.delta_scan_composed(*ops, chunk=64)
    yp, Sp = delta.delta_scan_pallas(*ops, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(y), atol=2e-6)
    np.testing.assert_allclose(np.asarray(Sp), np.asarray(S), atol=1e-5)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(_recurrence(*ops)),
                               atol=2e-6)


def test_update_kernel_matches_composed_in_place():
    """The Pallas update in interpret mode over three slots of 4 value
    heads of 128 x 128: ``y`` and the new state are the composed form's,
    which are the reference's recurrence continued by one token."""
    ops = _operands(7, 3, 9, 2, 4, 128, (0.9, 0.999), (0.3, 0.7))
    _y, S = delta.delta_scan_composed(*(o[:, :8] for o in ops), chunk=8)
    last = tuple(o[:, 8] for o in ops)
    y, s = delta.delta_update_composed(S, *last)
    yp, sp = delta.delta_update_pallas(S, *last, interpret=True)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(y), atol=1e-6)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(s), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(yp), np.asarray(_recurrence(*ops))[:, 8], atol=2e-6)
    assert delta._update_plan((128, 32, 128, 128)) == (1, 32, 128, 128)
    assert delta._update_plan((2, 4, 16, 16)) is None     # lanes of 128


# --------------------------------------------- what came with the layer
def test_rope_below_the_head_width_is_a_hand_written_rotation():
    """``rotary_dim`` 8 of a head of 32: pairs ``(x_j, x_{j+4})`` for j
    < 4 rotate by ``pos * theta^(-j / 4)``, values 8..31 pass."""
    from paddle_tpu import layers

    x = np.random.RandomState(0).randn(2, 5, 3, 32).astype("float32")
    pos = np.arange(5, dtype="int64")
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        xv = layers.data("x", [5, 3, 32], dtype="float32")
        pv = layers.data("pos", [5], dtype="int64", append_batch_size=False)
        out = layers.rope(xv, pv, base=1e7, heads_last=True, rotary_dim=8)
    (got,) = fluid.Executor(fluid.CPUPlace()).run(
        prog, feed={"x": x, "pos": pos}, fetch_list=[out])
    want = x.copy()
    for p in range(5):
        for j in range(4):
            ang = p * 1e7 ** (-j / 4.0)
            a, b = x[:, p, :, j], x[:, p, :, j + 4]
            want[:, p, :, j] = a * np.cos(ang) - b * np.sin(ang)
            want[:, p, :, j + 4] = a * np.sin(ang) + b * np.cos(ang)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    with pytest.raises(ValueError, match="rotary_dim must be even"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            layers.rope(layers.data("x", [5, 3, 32], dtype="float32"),
                        layers.data("p", [5], dtype="int64"), rotary_dim=7)


def _prefill_logits(cfg, params, ids, max_len=64):
    from paddle_tpu.core.scope import Scope

    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        logits, _ = gpt.build_prefill_step(cfg, batch=1,
                                           prompt_len=len(ids),
                                           max_len=max_len)
    exe, scope = fluid.Executor(fluid.CPUPlace()), Scope()
    exe.run(start, scope=scope)
    for n, v in params.items():
        if scope.has_var(n):
            scope.set_var(n, v)
    (got,) = exe.run(prog, feed={"tokens": np.asarray(ids)[None]},
                     fetch_list=[logits], scope=scope)
    return got[0]


def test_shared_experts_gate_scales_its_sum_by_the_tokens_sigmoid():
    """With the gate's matrix at zero the shared expert counts half
    (``sigmoid(0)``): the program without a gate over a shared expert of
    half the size; and the program with its gate follows the reference."""
    cfg = tiny_cfg()
    params = seeded_params(cfg, 3)
    ids = np.random.default_rng(4).integers(0, 97, 12)
    want = _ref_logits(params, cfg, ids)
    np.testing.assert_allclose(_prefill_logits(cfg, params, ids), want,
                               atol=2e-4)
    halved = {n: (np.zeros_like(v) if n.endswith("_shared_sgate.w_0") else
                  (0.5 * v if n.endswith("_shared_down.w_0") else v))
              for n, v in params.items()}
    # sigmoid(0) = 1/2 of the shared expert = the ungated half-size one
    zeroed = {n: (np.zeros_like(v) if n.endswith("_shared_sgate.w_0") else v)
              for n, v in params.items()}
    np.testing.assert_allclose(
        _prefill_logits(cfg, zeroed, ids),
        _prefill_logits(tiny_cfg(shared_expert_gate=False), halved, ids),
        atol=2e-5)


def test_eight_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """The share test: an expert layer of 16 experts cut in eight shares
    of two. Each share's routed part (the reference's ``experts`` given
    ``expert_first``) summed over the eight, plus the gated shared expert
    counted once, is the uncut layer — through the reference, whose
    shares the program's ``moe_ffn`` gives share by share."""
    from paddle_tpu import layers

    rs = np.random.RandomState(0)
    D, F, E, k = 32, 16, 16, 4
    m = jnp.asarray(rs.randn(9, D).astype("float32"))
    router = jnp.asarray(rs.randn(D, E).astype("float32") * 0.3)
    gate, up = (rs.randn(E, D, F).astype("float32") * 0.2 for _ in range(2))
    down = rs.randn(E, F, D).astype("float32") * 0.2

    def part(first, n):
        with jax.default_matmul_precision("highest"):
            return np.asarray(reference.experts(
                m, router, lambda j: (jnp.asarray(gate[first:first + n])[j],
                                      jnp.asarray(up[first:first + n])[j],
                                      jnp.asarray(down[first:first + n])[j]),
                first, n, k, True)[0])

    whole = part(0, E)
    shares = [part(first, 2) for first in range(0, E, 2)]
    np.testing.assert_allclose(sum(shares), whole, atol=1e-5)
    assert sum(np.abs(s).max() > 1e-3 for s in shares) >= 4

    def program(first, n):
        prog, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, start):
            x = layers.data("x", [D], dtype="float32")
            out, _aux = layers.moe_ffn(
                x, E, F, top_k=k, act="swiglu", dropless=True,
                norm_topk=True, param_prefix="t_moe",
                n_expert_local=n, expert_first=first)
        from paddle_tpu.core.scope import Scope

        exe, scope = fluid.Executor(fluid.CPUPlace()), Scope()
        exe.run(start, scope=scope)
        for name, val in (("router", np.asarray(router)),
                          ("gate", gate[first:first + n]),
                          ("up", up[first:first + n]),
                          ("down", down[first:first + n])):
            scope.set_var("t_moe_%s.w_0" % name, val)
        (got,) = exe.run(prog, feed={"x": np.asarray(m)}, fetch_list=[out],
                         scope=scope)
        return got

    # the program's share is the reference's, share by share (two of the
    # eight: a compile each)
    for n in (0, 5):
        np.testing.assert_allclose(program(2 * n, 2), shares[n], atol=2e-4)


# ------------------------------------------------------------ the engine
@pytest.fixture(scope="module")
def served():
    """(cfg, params, a started engine of three slots), its prefills
    scanned in chunks of 8 so that tiny prompts cross chunk boundaries."""
    from paddle_tpu.serving import DecodeEngine

    cfg = tiny_cfg()
    params = seeded_params(cfg, 11)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(delta, "CHUNK", 8)
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
    """Prompts shorter than, equal to and of several chunks on a mixed
    lane of three delta layers and one full one; the answer runs on
    through the in-place update and the slab. Every generated token is
    the argmax of the reference's full forward pass over the whole
    sequence (or within float32 rounding of it)."""
    cfg, params, engine = served
    prompt = np.random.RandomState(plen).randint(0, cfg["vocab"], (plen,))
    out = engine.submit(prompt.astype("int64"), 14).result(timeout=300)
    assert out.shape == (plen + 14,)
    assert _worst_margin(params, cfg, out, plen) < 1e-3


def test_prefill_then_decode_logits_are_the_full_forwards():
    """The two programs by hand over one scope: the prefill's logits at
    every prompt position and each decode step's are the reference's
    full forward over the whole sequence at that position."""
    from paddle_tpu.core.scope import Scope

    cfg, P, n = tiny_cfg(), 13, 6
    params = seeded_params(cfg, 2)
    progs = []
    for build, kw in ((gpt.build_prefill_step, {"prompt_len": P}),
                      (gpt.build_decode_step, {})):
        prog, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, start):
            logits, _ = build(cfg, batch=1, max_len=32, **kw)
        progs.append((prog, start, logits))
    exe, scope = fluid.Executor(fluid.CPUPlace()), Scope()
    for _prog, start, _ in progs:
        exe.run(start, scope=scope)
    for name, val in params.items():
        scope.set_var(name, val)
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
    """Two state tensors a delta layer and a slab pair for the full one,
    in ONE lane; the decode step holds three in-place updates, three
    convolution steps and the full layer's cache writes; the gauges read
    their bytes."""
    from paddle_tpu.observe import REGISTRY

    cfg, _params, engine = served
    engine.submit(np.arange(9, dtype="int64"), 2).result(timeout=300)
    lane = engine._lane
    assert lane.cache_names == [
        "gpt_%d_cache_%s" % (i, c) for i in range(3) for c in "xs"] \
        + ["gpt_3_cache_k", "gpt_3_cache_v"]
    assert [gpt.cache_kind(cfg, n, 96) for n in lane.cache_names] \
        == ["state"] * 6 + ["full"] * 2
    ops = [op.type for op in lane._decode_prog.global_block().ops]
    assert ops.count("delta_update") == ops.count("causal_conv_step") == 3
    assert ops.count("kv_cache_write") == 2
    snap = REGISTRY.snapshot()["metrics"]
    held = 3 * 3 * (4 * 16 * 16 + 3 * (2 * 2 * 16 + 4 * 16)) * 4
    assert snap["paddle_delta_state_bytes"]["samples"][0]["value"] == held
    kinds = {s["labels"]["kind"]: s["value"]
             for s in snap["paddle_serving_cache_bytes"]["samples"]}
    assert kinds["state"] == held
    assert kinds["full"] == 3 * 2 * 2 * 96 * 32 * 4
    seen = {(s["labels"]["kernel"], s["labels"]["form"],
             s["labels"]["chunk"])
            for s in snap["paddle_delta_plans_total"]["samples"]
            if s["value"]}
    assert ("delta_update", "composed", "1") in seen
    assert ("delta_scan", "composed", "8") in seen
    assert snap["paddle_delta_chunks_total"]["samples"][0]["value"] > 0


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
        with pytest.raises(ValueError, match="'delta' layers.*state of 16 x "
                           "16 a value head.*gpt_<i>_cache_x.*a layer that "
                           "carries a state has no backward"):
            gpt.build(tiny_cfg(), seq_len=8)


def test_multi_token_step_refuses_by_name():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        with pytest.raises(ValueError, match="build_multi_token_decode_step"
                           ".*'delta' layers.*delta-rule state"):
            gpt.build_multi_token_decode_step(tiny_cfg(), batch=1, steps=2,
                                              max_len=16)


@pytest.mark.parametrize("lever", ["prefix_store", "draft"])
def test_engine_levers_refuse_by_name(lever):
    from paddle_tpu.serving import DecodeEngine, PrefixStore

    kw = {"prefix_store": PrefixStore(1 << 20)} \
        if lever == "prefix_store" \
        else {"draft_cfg": tiny_cfg(n_layer=1, layer_types=["delta"]),
              "spec_k": 2}
    with pytest.raises(ValueError, match="'delta' layers.*"
                       "gpt_<i>_cache_s, gpt_<i>_cache_x"):
        DecodeEngine(tiny_cfg(), b_max=2, max_len=32, **kw)


@pytest.mark.parametrize("over, match", [
    ({"delta_chunk": 8}, "unknown gpt cfg key.*delta_chunk"),
    ({"delta_conv": 4}, "unknown gpt cfg key.*delta_conv"),
    ({"delta_v_dim": None}, "a 'delta' layer needs cfg\\['delta_v_dim'\\]"),
    ({"delta_v_heads": 3}, "must divide cfg\\['delta_v_heads'\\]"),
    ({"layer_types": ["full"] * 4}, "needs a 'delta' layer"),
    ({"residual": "mhc", "hc_mult": 2}, "takes no cfg\\['residual'\\]"),
    ({"rope_dim": 7}, "cfg\\['rope_dim'\\] is how many"),
    ({"rope_dim": 64}, "cfg\\['rope_dim'\\] is how many"),
    ({"n_shared_expert": 0}, "cfg\\['shared_expert_gate'\\] gates"),
])
def test_check_cfg_refuses(over, match):
    with pytest.raises(ValueError, match=match):
        gpt._check_cfg(tiny_cfg(**over))


def test_state_layer_table_names_every_state_bearing_type():
    """One table of the layer types that keep a state: the helpers that
    used to test each type by name all go by it."""
    cfg = tiny_cfg()
    assert {k.name for k in gpt.LAYER_KINDS.values()
            if k.kept and k.key == "layer_types"} \
        == {"conv", "retention", "delta", "mamba"}
    assert gpt.state_layers(cfg) == [0, 1, 2] and gpt.has_state(cfg)
    assert ["rows" in gpt.kind_of(cfg, i).caches.values()
            for i in range(4)] == [False] * 3 + [True]
    assert [gpt._rotates(cfg, i) for i in range(4)] == [False] * 3 + [True]
    assert gpt.delta_widths(cfg) == (2, 16, 4, 16, 128)


def test_analysis_rules_know_the_two_ops():
    """Shape, cost, range and footprint rules of ``delta_scan`` and
    ``delta_update`` on the tiny cfg's programs: the declared state
    shapes are the inferred ones, nothing is left to a default, and the
    state is counted as what it is."""
    from paddle_tpu.analysis.cost import CostAnalysis
    from paddle_tpu.analysis.cost_rules import COST_RULES
    from paddle_tpu.analysis.infer import verify_program
    from paddle_tpu.analysis.memory import FOOTPRINT_RULES, MemoryAnalysis
    from paddle_tpu.analysis.ranges import RANGE_RULES

    for table in (COST_RULES, RANGE_RULES, FOOTPRINT_RULES):
        assert "delta_scan" in table and "delta_update" in table
    cfg = tiny_cfg()
    for build, kw, op_type in (
            (gpt.build_prefill_step, {"prompt_len": 24}, "delta_scan"),
            (gpt.build_serving_decode_step, {}, "delta_update")):
        prog = fluid.Program()
        with fluid.program_guard(prog, fluid.Program()):
            build(cfg, batch=2, max_len=32, **kw)
        block = prog.global_block()
        ops = [op for op in block.ops if op.type == op_type]
        assert len(ops) == 3
        for op in ops:
            assert tuple(block.var(op.outputs["StateOut"][0]).shape) \
                == (2, 4, 16, 16)
        assert not [f for f in verify_program(prog, fill=False)
                    if f.severity == "error"]
        assert not CostAnalysis(prog).unruled
        ma = MemoryAnalysis(prog, site="serving")
        assert ma.tensors["gpt_1_cache_s"].poly.at(1) == 2 * 4 * 16 * 16 * 4
        assert ma.tensors["gpt_1_cache_x"].poly.at(1) == 2 * 3 * 128 * 4


def test_reference_copies_are_bit_equal():
    with open(os.path.join(HERE, "references", "qwen3_next.py"), "rb") as a, \
            open(os.path.join(ROOT, "benchmarks", "references",
                              "qwen3-next-80b-a3b.py"), "rb") as b:
        assert a.read() == b.read()
