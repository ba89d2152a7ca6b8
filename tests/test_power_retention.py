"""Brumby's layer kind (``model_type`` brumby) through the system's normal
path, against the plain reference (tests/references/brumby.py, of which
benchmarks/references/brumby-14b-base.py is a bit-equal copy): power
retention of degree 2 as a layer's first sub-block, whose slot is a
state with no position axis (``layers.power_retention``, kernels/power.py)
— the recurrent form the system runs against the attention form the
reference runs."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.kernels import power
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


reference = _load(os.path.join(HERE, "references", "brumby.py"))


def tiny_cfg(**over):
    """Brumby in small: 10 query heads over 2 key-value heads (5 : 1, as
    published) of 16, head norm, RoPE, a gate with a bias, SwiGLU, an
    untied head; two layers."""
    cfg = dict(d_model=64, n_head=10, n_kv_head=2, d_head=16, n_layer=2,
               vocab=97, max_length=256, dropout=0.0, pos_emb="rope",
               rope_theta=1000000.0, norm="rms", norm_eps=1e-6,
               qk_norm="head", tie_embeddings=False,
               layer_types=["retention"] * 2, ffn_act="swiglu", d_ff=96)
    cfg.update(over)
    return cfg


def seeded_params(cfg, seed, gate=(0.99, 0.9995)):
    """Every parameter drawn from the seed, float32: matrices within
    Xavier limits, the gate's bias so that its sigmoid lies in ``gate``,
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
        if p.name.endswith("_att_gamma.b_0"):
            g = rng.uniform(gate[0], gate[1], shape)
            v = np.log(g / (1.0 - g))
        elif len(shape) == 1:
            v = rng.uniform(0.5, 1.5, shape)
        else:
            lim = (6.0 / (shape[-2] + shape[-1])) ** 0.5
            v = rng.uniform(-lim, lim, shape)
        out[p.name] = v.astype("float32")
    return out


def _ref_logits(params, cfg, ids, **kw):
    return np.asarray(reference.forward(params, cfg, jnp.asarray(ids), **kw))


def _operands(seed, B, T, H, G, D, gate):
    rs = np.random.RandomState(seed)
    q = rs.randn(B, T, H, D).astype("float32")
    k = rs.randn(B, T, G, D).astype("float32")
    v = rs.randn(B, T, G, D).astype("float32")
    lg = np.log(rs.uniform(gate[0], gate[1], (B, T, G))).astype("float32")
    return tuple(jnp.asarray(t) for t in (q, k, v, lg))


def _attention_form(q, k, v, lg):
    """The reference's attention form, a sequence at a time."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            reference.retention(q[b].transpose(1, 0, 2),
                                k[b].transpose(1, 0, 2),
                                v[b].transpose(1, 0, 2), lg[b].T, 1e-6)
            for b in range(q.shape[0])]).reshape(q.shape)


# ------------------------------------------------------------- the core
@pytest.mark.parametrize("D", [8, 16, 32])
def test_phi_inner_product_is_the_scaled_square(D):
    """``<phi(a), phi(b)> = (a . b)^2 / D`` for the kept (tiled) square
    and for the reference's whole one; the kept rows are ``phi_plan``'s
    (one whole tile at 8 and 16, three tiles of 256 at 32)."""
    rs = np.random.RandomState(D)
    a, b = (jnp.asarray(rs.randn(7, D), jnp.float32) for _ in range(2))
    want = np.asarray(jnp.sum(a * b, -1)) ** 2 / D
    np.testing.assert_allclose(
        np.asarray(jnp.sum(power.phi(a) * power.phi(b), -1)), want,
        rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(jnp.sum(reference.phi(a) * reference.phi(b), (-1, -2))),
        want, rtol=2e-5)
    assert power.phi(a).shape[-1] == power.phi_plan(D)[2] \
        == {8: 64, 16: 256, 32: 768}[D]
    assert reference.phi(a).shape[-2:] == (D, D)     # the whole square
    assert power.phi_plan(128) == (16, 8, 9216)


@pytest.mark.parametrize("gate", [(0.99, 0.9995), (0.5, 0.5)],
                         ids=["gate_near_1", "gate_0.5"])
@pytest.mark.parametrize("chunk", [1, 8, 16, 64])
def test_recurrent_form_is_the_attention_form(chunk, gate):
    """One layer's core, degree 2, grouped heads 5 : 1, T = 37: chunk 1
    is token by token, 8 and 16 leave a ragged last chunk, 64 is one
    chunk. The scan's output and the token-by-token update's agree with
    the reference's attention form, and both leave the same state."""
    q, k, v, lg = _operands(3, 2, 37, 10, 2, 16, gate)
    want = np.asarray(_attention_form(q, k, v, lg))
    y, S, Z = power.power_scan_composed(q, k, v, lg, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-4, rtol=2e-4)
    s = jnp.zeros(power.state_shape(2, 2, 16), jnp.float32)
    z = jnp.zeros(power.norm_shape(2, 2, 16), jnp.float32)
    for t in range(37):
        y1, s, z = power.power_update_composed(s, z, q[:, t], k[:, t],
                                               v[:, t], lg[:, t])
        np.testing.assert_allclose(np.asarray(y1), want[:, t], atol=2e-4,
                                   rtol=2e-4)
    np.testing.assert_allclose(np.asarray(S), np.asarray(s), atol=1e-4)
    np.testing.assert_allclose(np.asarray(Z), np.asarray(z), atol=1e-4)


def test_the_kernels_are_their_composed_forms():
    """Both Pallas kernels (interpret mode) at the one head size they
    have a plan for, a prompt of one chunk and a ragged second: the
    scan's output, state and normaliser (two query heads), then one
    update of that state read by the five heads of a group."""
    q, k, v, lg = _operands(5, 1, 160, 2, 1, 128, (0.99, 0.9995))
    y, S, Z = power.power_scan_composed(q, k, v, lg, chunk=128)
    yp, Sp, Zp = power.power_scan_pallas(q, k, v, lg, chunk=128,
                                         interpret=True)
    scale = float(jnp.abs(S).max())
    np.testing.assert_allclose(np.asarray(yp), np.asarray(y), atol=1e-5)
    np.testing.assert_allclose(np.asarray(Sp), np.asarray(S),
                               atol=1e-6 * scale)
    np.testing.assert_allclose(np.asarray(Zp), np.asarray(Z),
                               atol=1e-6 * scale)
    q1, k1, v1, l1 = (t[:, 0] for t in _operands(6, 1, 1, 5, 1, 128,
                                                 (0.99, 0.9995)))
    want = power.power_update_composed(S, Z, q1, k1, v1, l1)
    got = power.power_update_pallas(S, Z, q1, k1, v1, l1, interpret=True)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=1e-5)
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-6 * scale)
    assert power._update_plan(S.shape, 5)
    assert not power._update_plan(S.shape, 6)
    assert power._scan_plan(40, 8, 128, 256) == 256
    assert power._scan_plan(40, 8, 128, 100) is None
    assert power._scan_plan(40, 8, 64, 128) is None
    # the chunk follows from the prompt: whole lanes, at most 1,024
    assert [power.scan_chunk(T) for T in (1, 128, 200, 1024, 1100, 8192)] \
        == [128, 128, 256, 1024, 1024, 1024]


SCAN_CASES = {
    # id: (B, T, chunk, J, G, gate). The state has all eight tile columns
    # at D 128 whatever the rest, so every read and feed crosses them.
    "one_chunk_no_state_read": (1, 128, 128, 5, 1, (0.99, 0.9995)),
    "three_chunks": (1, 384, 128, 5, 1, (0.99, 0.9995)),
    "ragged_prompt_two_groups": (2, 300, 128, 1, 2, (0.99, 0.9995)),
    "eight_heads_gate_0.5": (1, 256, 128, 8, 1, (0.5, 0.5)),
    # longer chunks, so that a chunk's scores are several blocks a side
    "three_score_blocks_gate_0.5": (1, 700, 384, 1, 1, (0.5, 0.5)),
    "four_score_blocks": (1, 1024, 512, 1, 1, (0.99, 0.9995)),
}


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_the_scan_kernel_is_the_composed_and_the_attention_form(case):
    """``power_scan_pallas`` (interpret mode) where its walk over whole
    tiles of the kept rows and its score blocks can go wrong: no state
    read, a read and a feed a chunk, padded positions, one, five and
    eight query heads a key-value head, both gates — against the
    composed form (output, state, normaliser) and against the
    reference's attention form over the whole sequence (output; 5e-5
    there: at a gate of 0.5 the COMPOSED form stands 3e-5 from it, the
    recurrence summing in another order under small denominators)."""
    B, T, chunk, J, G, gate = SCAN_CASES[case]
    q, k, v, lg = _operands(len(case), B, T, J * G, G, 128, gate)
    y, S, Z = power.power_scan_composed(q, k, v, lg, chunk=chunk)
    yp, Sp, Zp = power.power_scan_pallas(q, k, v, lg, chunk=chunk,
                                         interpret=True)
    scale = float(jnp.abs(S).max())
    np.testing.assert_allclose(np.asarray(yp), np.asarray(y), atol=1e-5)
    np.testing.assert_allclose(np.asarray(Sp), np.asarray(S),
                               atol=1e-6 * scale)
    np.testing.assert_allclose(np.asarray(Zp), np.asarray(Z),
                               atol=1e-6 * scale)
    np.testing.assert_allclose(
        np.asarray(yp), np.asarray(_attention_form(q, k, v, lg)), atol=5e-5)


def _kernel_dots(jaxpr, scope="", times=1):
    """``(scopes, trips, eqn)`` of every ``dot_general`` a kernel's
    jaxpr holds: the ``jax.named_scope``s round it and how often the
    loops round it run it."""
    for eqn in jaxpr.eqns:
        here = "%s/%s" % (scope, eqn.source_info.name_stack)
        if eqn.primitive.name == "dot_general":
            yield here, times, eqn
            continue
        trips = times * eqn.params.get("length", 1) \
            if eqn.primitive.name == "scan" else times
        for val in eqn.params.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _kernel_dots(sub, here, trips)


@pytest.mark.parametrize("Q", [128, 512, 640, 1024])
def test_the_scan_multiplies_the_kept_rows_and_the_lower_triangle(Q):
    """One head and one chunk of the kernel, read off its jaxpr: the
    state's read contracts over 9,216 rows and the feed writes 9,216 (128
    padded blocks of 128 were 16,384), each in whole ``_RUN``s, and the
    scores inside the chunk are the blocks at or under the diagonal."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn.params["jaxpr"]
            for val in eqn.params.values():
                sub = getattr(val, "jaxpr", val)
                found = find(sub) if hasattr(sub, "eqns") else None
                if found is not None:
                    return found

    sd = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    kernel = find(jax.make_jaxpr(lambda *a: power.power_scan_pallas(
        *a, chunk=Q, interpret=True))(
            sd(1, 2 * Q, 1, 128), sd(1, 2 * Q, 1, 128),
            sd(1, 2 * Q, 1, 128), sd(1, 2 * Q, 1)).jaxpr)
    read = feed = pairs = inside = 0
    for scope, trips, eqn in _kernel_dots(kernel):
        lhs, rhs = (v.aval.shape for v in eqn.invars)
        (lc, rc), _batch = eqn.params["dimension_numbers"]
        if "read" in scope and lc == (0,):
            assert lhs == (power._RUN, 128) and rhs == (power._RUN, Q)
            read += trips * lhs[0]
        elif "feed" in scope:
            assert lhs == (power._RUN, Q) and rhs == (Q, 128)
            feed += trips * lhs[0]
        elif "inside" in scope:
            # k [s, D] x q^T [D, t], then v^T [D, s] x a [s, t]: the
            # (key, query) pairs of either are its products over D
            pairs += trips * lhs[0] * lhs[1] * rhs[1] // 128
            inside += 1
    assert read == feed == power.phi_plan(128)[2] == 9216
    assert inside == 2 * -(-Q // power._SUB)
    pairs //= 2
    # whole blocks at or under the diagonal, and no more
    assert Q * (Q + 128) // 2 <= pairs <= Q * (Q + power._SUB) // 2


# ------------------------------------------------------------ the engine
@pytest.fixture(scope="module")
def served():
    """(cfg, params, a started engine of three slots), its prefills
    scanned in chunks of 8 so that tiny prompts cross chunk boundaries."""
    from paddle_tpu.serving import DecodeEngine

    cfg = tiny_cfg()
    params = seeded_params(cfg, 11)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(power, "_CHUNK_MAX", 8)
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
    """Prompts shorter than, equal to and of several chunks; the answer
    runs past further chunk boundaries. Every generated token is the
    argmax of the reference's full forward pass over the whole sequence
    (or within float32 rounding of it)."""
    cfg, params, engine = served
    prompt = np.random.RandomState(plen).randint(0, cfg["vocab"], (plen,))
    out = engine.submit(prompt.astype("int64"), 14).result(timeout=300)
    assert out.shape == (plen + 14,)
    assert _worst_margin(params, cfg, out, plen) < 1e-3


def test_lane_holds_states_only(served):
    """Two caches a layer, both of kind ``state``; no bias over cache
    rows and no ``kv_cache_write`` in the decode step; the gauge reads
    their bytes."""
    from paddle_tpu.observe import REGISTRY

    cfg, _params, engine = served
    lane = engine._lane
    assert lane.cache_names == [
        "gpt_%d_cache_%s" % (i, c) for i in range(2) for c in "sz"]
    assert {gpt.cache_kind(cfg, n, 96) for n in lane.cache_names} \
        == {"state"}
    ops = [op.type for op in lane._decode_prog.global_block().ops]
    assert ops.count("power_update") == 2
    assert "kv_cache_write" not in ops and "softmax" not in ops
    snap = REGISTRY.snapshot()["metrics"]
    held = 3 * 2 * 2 * (256 * 16 + 16 * 16) * 4
    assert snap["paddle_power_state_bytes"]["samples"][0]["value"] == held
    kinds = {s["labels"]["kind"]: s["value"]
             for s in snap["paddle_serving_cache_bytes"]["samples"]}
    assert kinds["state"] == held and kinds["full"] == 0


def test_a_slot_reused_holds_nothing_of_its_previous_tenant(served):
    """Fill every slot, let them finish, then serve one prompt again in
    whichever slot is handed out: the same tokens as the first time,
    when the state was fresh — and the spliced state is the prefill's,
    every value of it."""
    cfg, params, engine = served
    rs = np.random.RandomState(2)
    first = rs.randint(0, cfg["vocab"], (9,)).astype("int64")
    alone = engine.submit(first, 10).result(timeout=300)
    crowd = [engine.submit(rs.randint(0, cfg["vocab"], (n,))
                           .astype("int64"), 12) for n in (17, 6, 30, 11)]
    for h in crowd:
        h.result(timeout=300)
    again = engine.submit(first, 10).result(timeout=300)
    np.testing.assert_array_equal(alone, again)
    lane = engine._lane
    small = np.asarray(lane.prefill_var("gpt_0_cache_s"))
    big = np.asarray(lane.scope.find_var("gpt_0_cache_s"))
    assert small.shape[0] == 1 and big.shape[0] == 3
    assert np.abs(small).max() > 0


def test_counters_name_the_form_and_the_chunks(served):
    from paddle_tpu.observe import REGISTRY

    cfg, _params, engine = served
    before = REGISTRY.snapshot()["metrics"]["paddle_power_chunks_total"][
        "samples"][0]["value"]
    engine.submit(np.arange(19, dtype="int64"), 2).result(timeout=300)
    snap = REGISTRY.snapshot()["metrics"]
    # two layers x ceil(19 / 8) chunks
    assert snap["paddle_power_chunks_total"]["samples"][0]["value"] \
        - before == 6
    seen = {(s["labels"]["kernel"], s["labels"]["form"],
             s["labels"]["chunk"])
            for s in snap["paddle_power_plans_total"]["samples"]
            if s["value"]}
    assert ("power_update", "composed", "1") in seen
    assert ("power_scan", "composed", "8") in seen


# ----------------------------------------------------------- the refusals
def test_training_build_refuses_by_name():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        with pytest.raises(ValueError, match="'retention' layers.*256 rows"
                           ".*gpt_<i>_cache_z.*a layer that carries a "
                           "state has no backward"):
            gpt.build(tiny_cfg(), seq_len=8)


def test_multi_token_step_refuses_by_name():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        with pytest.raises(ValueError, match="build_multi_token_decode_step"
                           ".*'retention' layers.*power-retention state"):
            gpt.build_multi_token_decode_step(tiny_cfg(), batch=1, steps=2,
                                              max_len=16)


@pytest.mark.parametrize("lever", ["prefix_store", "draft"])
def test_engine_levers_refuse_by_name(lever):
    from paddle_tpu.serving import DecodeEngine, PrefixStore

    kw = {"prefix_store": PrefixStore(1 << 20)} \
        if lever == "prefix_store" \
        else {"draft_cfg": tiny_cfg(n_layer=1, layer_types=["retention"]),
              "spec_k": 2}
    with pytest.raises(ValueError, match="'retention' layers.*"
                       "gpt_<i>_cache_s, gpt_<i>_cache_z"):
        DecodeEngine(tiny_cfg(), b_max=2, max_len=32, **kw)


@pytest.mark.parametrize("over, match", [
    ({"retention_degree": 3}, "unknown gpt cfg key.*retention_degree"),
    ({"retention_chunk": 8}, "unknown gpt cfg key.*retention_chunk"),
    ({"attn": "mla"}, "takes no cfg\\['attn'\\]"),
    ({"residual": "mhc", "hc_mult": 2}, "takes no cfg\\['residual'\\]"),
    ({"shortcut_moe": True, "n_expert": 4, "expert_top_k": 2,
      "d_expert": 32}, "takes no cfg\\['shortcut_moe'\\]"),
])
def test_check_cfg_refuses(over, match):
    with pytest.raises(ValueError, match=match):
        gpt._check_cfg(tiny_cfg(**over))


def test_analysis_rules_know_the_two_ops():
    """Shape, cost, range and footprint rules of ``power_scan`` and
    ``power_update`` on the tiny cfg's programs: the declared state
    shapes are the inferred ones, nothing is left to a default, and the
    state is counted as what it is."""
    from paddle_tpu.analysis.cost import CostAnalysis
    from paddle_tpu.analysis.cost_rules import COST_RULES
    from paddle_tpu.analysis.infer import verify_program
    from paddle_tpu.analysis.memory import FOOTPRINT_RULES, MemoryAnalysis
    from paddle_tpu.analysis.ranges import RANGE_RULES

    for table in (COST_RULES, RANGE_RULES, FOOTPRINT_RULES):
        assert "power_scan" in table and "power_update" in table
    cfg = tiny_cfg()
    for build, kw, op_type in (
            (gpt.build_prefill_step, {"prompt_len": 24}, "power_scan"),
            (gpt.build_serving_decode_step, {}, "power_update")):
        prog = fluid.Program()
        with fluid.program_guard(prog, fluid.Program()):
            build(cfg, batch=2, max_len=32, **kw)
        block = prog.global_block()
        ops = [op for op in block.ops if op.type == op_type]
        assert len(ops) == 2
        for op in ops:
            assert tuple(block.var(op.outputs["StateOut"][0]).shape) \
                == (2, 2, 256, 16)
            assert tuple(block.var(op.outputs["NormOut"][0]).shape) \
                == (2, 2, 16, 16)
        assert not [f for f in verify_program(prog, fill=False)
                    if f.severity == "error"]
        assert not CostAnalysis(prog).unruled
        ma = MemoryAnalysis(prog, site="serving")
        assert ma.tensors["gpt_1_cache_s"].poly.at(1) == 2 * 2 * 256 * 16 * 4
        assert ma.tensors["gpt_1_cache_z"].poly.at(1) == 2 * 2 * 16 * 16 * 4
