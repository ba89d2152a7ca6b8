"""LongCat-Flash's layer (``model_type`` longcat_flash) through the
system's normal path, against the plain reference
(tests/references/longcat_scmoe.py, bit-equal to
benchmarks/references/longcat-flash-omni.py): shortcut-connected experts
— two latent-attention sub-blocks and two dense FFNs a published layer,
ONE routed branch that forks after the first attention and joins after
the second FFN — a softmax router over experts with weights and identity
(zero-compute) experts behind them, and the two latent scale factors."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models import gpt
from paddle_tpu.ops import moe_ops
from test_mla import _decode_in_company, _engine, _load

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REFERENCE = os.path.join(HERE, "references", "longcat_scmoe.py")
reference = _load(REFERENCE)

E, Z, K = 16, 8, 6


def tiny_cfg(**over):
    """Hidden 48, 4 heads of 16 + 8 (q/k) and 16 (v) over a latent of 32
    (queries through a latent of 24), two published layers = four
    sub-layers with a dense SwiGLU of 96 each, a router 24 wide over 16
    experts of width 24 and 8 identity experts, top-6, gates 6 p,
    vocabulary 97."""
    cfg = dict(d_model=48, n_head=4, n_layer=4, vocab=97, max_length=64,
               dropout=0.0, pos_emb="rope", rope_theta=10000000.0,
               norm="rms", norm_eps=1e-5, attn="mla", q_lora_rank=24,
               kv_lora_rank=32, d_nope=16, d_rope=8, d_v=16,
               mla_scale_q_lora=True, mla_scale_kv_lora=True,
               ffn_act="swiglu", d_ff=96, shortcut_moe=True,
               n_expert=E, n_zero_expert=Z, expert_top_k=K, d_expert=24,
               router_score="softmax", router_bias=True, norm_topk=False,
               route_scale=6.0)
    cfg.update(over)
    return cfg


def seeded_params(cfg, seed):
    """Every parameter drawn from the seed, float32: matrices within
    Xavier limits, norm scales uniform in 0.5-1.5, the router's selection
    term within 0.01 of zero (softmax over 24 outputs leaves neighbouring
    probabilities about that far apart: a correction, not a decision)."""
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
        elif len(shape) == 1:
            out[p.name] = rng.uniform(0.5, 1.5, shape).astype("float32")
        else:
            lim = (6.0 / (shape[-2] + shape[-1])) ** 0.5
            out[p.name] = rng.uniform(-lim, lim, shape).astype("float32")
    return out


def _ref_logits(params, cfg, ids):
    return np.asarray(reference.forward(params, cfg, jnp.asarray(ids)))


def _assert_matches_reference(cfg, params, prompts, toks, rows, atol=1e-4):
    for p, t, r in zip(prompts, toks, rows):
        want = _ref_logits(params, cfg, np.asarray(t[:-1]))
        np.testing.assert_allclose(np.stack(r), want[len(p) - 1:],
                                   atol=atol, rtol=0)


def test_the_two_copies_of_the_reference_are_bit_equal():
    with open(REFERENCE, "rb") as a, open(os.path.join(
            ROOT, "benchmarks", "references", "longcat-flash-omni.py"),
            "rb") as b:
        assert a.read() == b.read()


def test_check_cfg_knows_the_new_keys():
    gpt._check_cfg(tiny_cfg())
    gpt._check_cfg(tiny_cfg(n_expert_local=4, expert_first=12))
    gpt._check_cfg(tiny_cfg(shortcut_moe=False, n_dense_layer=1))
    for bad, match in (
            (dict(n_layer=3), "even"),
            (dict(mixers=["attention"] * 4), "mixers|shortcut_moe"),
            (dict(sandwich_norm=True), "sandwich_norm"),
            (dict(n_dense_layer=1), "n_dense_layer"),
            (dict(n_shared_expert=1), "n_shared_expert"),
            (dict(expert_top_k=E + Z + 1), "expert_top_k"),
            (dict(d_expert_in=8), "d_expert_in"),
            (dict(n_expert_local=4, expert_first=E - 3), "share")):
        with pytest.raises(ValueError, match=match):
            gpt._check_cfg(tiny_cfg(**bad))
    plain = dict(d_model=32, d_ff=64, n_head=4, n_layer=2, vocab=50,
                 max_length=16, dropout=0.0)
    for key in ("shortcut_moe", "n_zero_expert"):
        with pytest.raises(ValueError, match="needs cfg\\['n_expert'\\]"):
            gpt._check_cfg(dict(plain, **{key: 2}))
    for key in ("mla_scale_q_lora", "mla_scale_kv_lora"):
        with pytest.raises(ValueError, match="needs cfg\\['attn'\\]"):
            gpt._check_cfg(dict(plain, **{key: True}))


def test_what_cannot_carry_the_branch_refuses_it_by_name():
    """The training build, the multi-token step, a prefix store and a
    draft model name the shortcut layer, as they do 'mixers' and 'conv'."""
    from paddle_tpu.serving import DecodeEngine

    cfg = tiny_cfg()
    for build in (lambda: gpt.build(cfg, seq_len=8),
                  lambda: gpt.build_multi_token_decode_step(
                      cfg, batch=1, steps=2, max_len=16)):
        with pytest.raises(ValueError, match="shortcut_moe"):
            with fluid.program_guard(fluid.Program(), fluid.Program()):
                build()
    dense = dict(d_model=32, d_ff=64, n_head=4, n_layer=1, vocab=97,
                 max_length=64, dropout=0.0)
    for kw in (dict(prefix_cache_bytes=1 << 20),
               dict(draft_cfg=dense, spec_k=2)):
        with pytest.raises(ValueError, match="shortcut_moe"):
            DecodeEngine(cfg, b_max=1, max_len=16, **kw)
    with pytest.raises(ValueError, match="shortcut_moe"):
        DecodeEngine(dense, b_max=1, max_len=16, draft_cfg=cfg, spec_k=2)


def test_parameter_and_cache_names_are_the_same_in_every_build():
    cfg = tiny_cfg()
    want = {"gpt_word_emb", "gpt_out_proj.w_0", "gpt_ln_f_s"}
    for j in range(4):
        kinds = ("attn", "dense") + (("branch",) if j % 2 == 0 else ())
        want |= {"gpt_%d_%s" % (j, s) for kind in kinds
                 for s in reference.SUBLAYER_PARAMS[kind]}
    builds = (
        lambda: gpt.build_serving_decode_step(cfg, batch=2, max_len=16),
        lambda: gpt.build_decode_step(cfg, batch=1, max_len=16),
        lambda: gpt.build_prefill_step(cfg, batch=1, prompt_len=8,
                                       max_len=16))
    for build in builds:
        prog, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, start):
            _logits, caches = build()
        params = {p.name: tuple(p.shape)
                  for p in prog.global_block().all_parameters()}
        assert set(params) == want
        # two latent slabs a published layer: sub-layers 2 l and 2 l + 1
        assert caches == ["gpt_%d_cache_c" % j for j in range(4)]
        assert params["gpt_0_moe_router.w_0"] == (48, E + Z)
        assert params["gpt_2_moe_router_bias"] == (E + Z,)
        assert params["gpt_0_moe_gate.w_0"] == (E, 48, 24)
        assert params["gpt_1_ffn1.w_0"] == (48, 96)
        # ONE routed branch a published layer
        ops = [op.type for op in prog.global_block().ops]
        assert ops.count("moe_ffn") == 2
    assert gpt.expert_rows(cfg) == 2
    assert gpt.cache_kind(cfg, "gpt_3_cache_c", 16) == "latent"


def test_the_branch_forks_off_the_first_norm_and_joins_after_the_second_ffn():
    """In the program: branch ``l`` reads the norm the even sub-layer's
    dense FFN reads and is added to the ODD sub-layer's dense FFN output,
    the last thing before that sub-layer's residual add."""
    cfg = tiny_cfg()
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        gpt.build_prefill_step(cfg, batch=1, prompt_len=8, max_len=16)
    ops = prog.global_block().ops
    made_by = {n: op for op in ops for names in op.outputs.values()
               for n in names}

    def matrix_behind(var):
        """The parameter of the matmul that made ``var``."""
        op = made_by[var]
        while not any(n.endswith(".w_0") for names in op.inputs.values()
                      for n in names):
            op = made_by[next(n for names in op.inputs.values()
                              for n in names if n in made_by)]
        return next(n for names in op.inputs.values() for n in names
                    if n.endswith(".w_0"))

    branches = [op for op in ops if op.type == "moe_ffn"]
    for l, branch in enumerate(branches):
        (m,) = branch.inputs["X"]
        assert made_by[m].type == "rms_norm"
        assert made_by[m].inputs["Scale"] == ["gpt_%d_pre2_ln_s" % (2 * l)]
        (s,) = branch.outputs["Out"]
        (join,) = [op for op in ops if any(
            s in names for names in op.inputs.values())]
        assert join.type == "elementwise_add"
        (other,) = [n for names in join.inputs.values() for n in names
                    if n != s]
        assert matrix_behind(other) == "gpt_%d_ffn2.w_0" % (2 * l + 1)


def _final(params, cfg, x):
    x = reference._rms_norm(x, params["gpt_ln_f_s"], cfg["norm_eps"])
    return np.asarray(jnp.dot(x, params["gpt_out_proj.w_0"],
                              precision="highest"))


def test_a_branch_added_after_the_first_ffn_would_be_caught():
    """One published layer, the system against the reference's own
    pieces put together both ways: the second attention reads ``b0``
    without the branch (late join, the model) or ``b0 + s`` (early)."""
    cfg = tiny_cfg(n_layer=2)
    params = seeded_params(cfg, 41)
    ids = np.random.default_rng(43).integers(1, 97, 12)
    items = reference._hashable(cfg)
    sub = {j: {n[len("gpt_%d_" % j):]: v for n, v in params.items()
               if n.startswith("gpt_%d_" % j)} for j in range(2)}
    x = jnp.asarray(params["gpt_word_emb"][ids])
    b0, s, _gap, _z = reference.sublayer(sub[0], x, None, items, True)
    late = _final(params, cfg,
                  reference.sublayer(sub[1], b0, s, items, False)[0])
    early = _final(params, cfg, reference.sublayer(
        sub[1], b0 + s, jnp.zeros_like(s), items, False)[0])
    assert np.abs(late - early).max() > 1e-2
    np.testing.assert_allclose(_ref_logits(params, cfg, ids), late,
                               atol=1e-5, rtol=0)
    _, last = _engine(cfg, params, 1)._lane.prefill_insert(
        0, ids.astype("int64"))
    np.testing.assert_allclose(np.asarray(last), late[-1], atol=1e-4, rtol=0)


@pytest.mark.parametrize("share", [None, (4, 8)], ids=["whole", "share"])
def test_prefill_then_decode_through_the_eight_slabs(share):
    """Prefill (expanded) then cached decode (absorbed) through both
    slabs of every published layer, four slots in company, against the
    reference's full forward on logits — whole and as one chip's share.
    Tolerance 1e-4 absolute on logits of magnitude ~3: both sides are
    float32 at the highest matmul precision here, and the absorbed form
    reorders two contractions, which moves the last few bits only."""
    cfg = tiny_cfg() if share is None else tiny_cfg(
        n_expert_local=share[0], expert_first=share[1])
    params = seeded_params(cfg, 7)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 97, n) for n in (5, 8, 13, 19)]
    eng = _engine(cfg, params, 4)
    lane = eng._lane
    assert lane.cache_names == ["gpt_%d_cache_c" % j for j in range(4)]
    for n in lane.cache_names:
        assert np.asarray(lane.scope.find_var(n)).shape == (4, 1, 64, 40)
    toks, rows = _decode_in_company(eng, prompts, 24)
    _assert_matches_reference(cfg, params, prompts, toks, rows)
    # the tallies against a count on the host: the reference's identity
    # pairs at the positions the 23 decode steps of each slot computed
    zero = eng.zero_pairs()
    want = np.zeros(2, np.int64)
    most = 0
    for p, t in zip(prompts, toks):
        _l, z = reference.forward(params, cfg, jnp.asarray(t[:-1]),
                                  with_zero=True)
        z = np.asarray(z)[:, len(p):]
        want += z.sum(axis=1)
        most = max(most, int((K - z).max()))
    assert zero[:, 0].tolist() == want.tolist()
    assert 0 < want.min() and zero[:, 1].max() == most <= K
    routed = eng.routed_pairs()
    assert routed.shape == (2, E)      # a row a branch, the real experts
    assert (routed.sum(axis=1) + zero[:, 0]).tolist() == [23 * 4 * K] * 2
    touched = eng.experts_touched()
    assert (touched is None) == (share is None)
    if share is not None:
        assert touched.shape == (2, share[0])


def test_a_readmitted_slot_sees_no_row_of_its_previous_tenant():
    """In either slab of a published layer: a long first tenant, then a
    short second one in the same slot, judged on logits."""
    cfg = tiny_cfg()
    params = seeded_params(cfg, 13)
    rng = np.random.default_rng(17)
    eng = _engine(cfg, params, 2)
    _decode_in_company(eng, [rng.integers(1, 97, 19)], 12, slots=[1])
    for n in eng._lane.cache_names:    # the first tenant filled both slabs
        assert np.abs(np.asarray(
            eng._lane.scope.find_var(n))[1, 0, 20:29]).min(axis=-1).max() > 0
    _decode_in_company(eng, [rng.integers(1, 97, 21)], 2, slots=[0])
    new = [rng.integers(1, 97, 3)]
    toks, rows = _decode_in_company(eng, new, 16, slots=[1])
    _assert_matches_reference(cfg, params, new, toks, rows)


def test_row_locality_alone_and_in_company():
    """Company in the batch changes neither a token's experts nor its
    answer: the same request alone and among three others, bitwise."""
    cfg = tiny_cfg(n_expert_local=4, expert_first=0)
    params = seeded_params(cfg, 29)
    rng = np.random.default_rng(31)
    prompts = [rng.integers(1, 97, n) for n in (4, 11, 20, 7)]
    eng = _engine(cfg, params, 4).start()
    try:
        together = [r.result(timeout=300) for r in
                    [eng.submit(np.asarray(p, "int64"), 12)
                     for p in prompts]]
        alone = [eng.submit(np.asarray(p, "int64"), 12).result(timeout=300)
                 for p in prompts]
    finally:
        eng.stop()
    for a, b in zip(together, alone):
        assert a.tolist() == b.tolist()


@pytest.mark.parametrize("drop", ["mla_scale_q_lora", "mla_scale_kv_lora"])
def test_each_latent_scale_is_applied(drop):
    """The system with both scales equals the reference with both; the
    reference without either one does not (so a program that dropped it
    would fail the comparison above)."""
    cfg = tiny_cfg()
    params = seeded_params(cfg, 47)
    ids = np.random.default_rng(53).integers(1, 97, 14)
    _, last = _engine(cfg, params, 1)._lane.prefill_insert(
        0, ids.astype("int64"))
    np.testing.assert_allclose(np.asarray(last),
                               _ref_logits(params, cfg, ids)[-1],
                               atol=1e-4, rtol=0)
    without = _ref_logits(params, tiny_cfg(**{drop: False}), ids)[-1]
    assert np.abs(np.asarray(last) - without).max() > 1e-2


def test_the_cache_row_holds_the_scaled_latent_and_the_unscaled_key_part():
    """Sub-layer 0's slab after a prefill, against numpy by hand: ``c``
    times sqrt(d_model / kv_lora_rank) after its norm, ``k_r`` rotated
    and NOT scaled."""
    cfg = tiny_cfg()
    params = seeded_params(cfg, 59)
    ids = np.random.default_rng(61).integers(1, 97, 10)
    eng = _engine(cfg, params, 1)
    eng._lane.prefill_insert(0, ids.astype("int64"))
    row = np.asarray(eng._lane.scope.find_var("gpt_0_cache_c"))[0, 0, :10]

    def rms(x, s):
        x = x.astype("float64")
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * s

    h = rms(params["gpt_word_emb"][ids], params["gpt_0_pre1_ln_s"])
    kv = h @ params["gpt_0_att_kva.w_0"].astype("float64")
    c = rms(kv[:, :32], params["gpt_0_att_kva_ln_s"]) * (48 / 32.0) ** 0.5
    inv = 10000000.0 ** (-np.arange(4) * 2.0 / 8)
    ang = np.arange(10)[:, None] * inv[None, :]
    a, b = kv[:, 32:36], kv[:, 36:]
    k_r = np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                          b * np.cos(ang) + a * np.sin(ang)], axis=-1)
    np.testing.assert_allclose(row[:, :32], c, atol=2e-5, rtol=0)
    np.testing.assert_allclose(row[:, 32:], k_r, atol=2e-5, rtol=0)
    assert np.abs(row[:, 32:] - k_r * (48 / 32.0) ** 0.5).max() > 1e-2


# ----------------------------------------------------- the routed branch
def _branch_program(T, share=None, tally=False):
    """``layers.moe_ffn`` alone, as ``gpt._routed`` calls it."""
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        x = fluid.layers.data("x", [T, 48], append_batch_size=False)
        kw = {}
        if tally:
            block = prog.global_block()
            kw = dict(
                counts=block.create_var(name="counts", shape=(1, E),
                                        dtype="int32", persistable=True),
                zero_pairs=block.create_var(name="zero", shape=(1, 2),
                                            dtype="int32", persistable=True))
        if share is not None:
            kw.update(n_expert_local=share[0], expert_first=share[1])
        out, _aux = fluid.layers.moe_ffn(
            x, E, 24, top_k=K, act="swiglu", dropless=True, norm_topk=False,
            param_prefix="b_moe", router_score="softmax", router_bias=True,
            route_scale=6.0, n_zero_expert=Z, **kw)
    return prog, start, out


def _branch_weights(seed):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        lim = (6.0 / (shape[-2] + shape[-1])) ** 0.5
        return rng.uniform(-lim, lim, shape).astype("float32")

    return {"b_moe_router.w_0": draw(48, E + Z),
            "b_moe_router_bias": rng.uniform(-0.01, 0.01, E + Z).astype(
                "float32"),
            "b_moe_gate.w_0": draw(E, 48, 24), "b_moe_up.w_0": draw(E, 48, 24),
            "b_moe_down.w_0": draw(E, 24, 48)}


def _run_branch(weights, x, share=None, tally=False):
    prog, start, out = _branch_program(x.shape[0], share, tally)
    scope = fluid.core.scope.Scope()
    with fluid.core.scope.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(start, scope=scope)
        lo, n = (0, E) if share is None else (share[1], share[0])
        for name, v in weights.items():
            stacked = name.endswith(("gate.w_0", "up.w_0", "down.w_0"))
            scope.set_var(name, v[lo:lo + n] if stacked else v)
        if tally:
            scope.set_var("counts", np.zeros((1, E), "int32"))
            scope.set_var("zero", np.zeros((1, 2), "int32"))
        (got,) = exe.run(prog, feed={"x": x}, fetch_list=[out], scope=scope)
        tallies = (np.asarray(scope.find_var("counts")),
                   np.asarray(scope.find_var("zero"))) if tally else None
    return np.asarray(got), tallies


def _ref_branch(weights, x, lo=0, n=E):
    out, _gap, zero = reference.routed(
        jnp.asarray(x), jnp.asarray(weights["b_moe_router.w_0"]),
        jnp.asarray(weights["b_moe_router_bias"]),
        jnp.asarray(weights["b_moe_gate.w_0"][lo:lo + n]),
        jnp.asarray(weights["b_moe_up.w_0"][lo:lo + n]),
        jnp.asarray(weights["b_moe_down.w_0"][lo:lo + n]),
        E, K, 6.0, lo)
    return np.asarray(out), np.asarray(zero)


def test_the_shares_add_up_with_the_identity_part_counted_once():
    """Four shares of four experts: each returns its experts' part PLUS
    the identity part (what every chip computes alike for its own
    tokens); their sum less three identity parts is the uncut layer."""
    weights = _branch_weights(67)
    x = np.random.default_rng(71).standard_normal((40, 48)).astype("float32")
    with jax.default_matmul_precision("highest"):
        whole, zero = _ref_branch(weights, x)
        identity, _ = _ref_branch(weights, x, 0, 0)
    assert 0 < zero.min() + 1 and zero.max() > 0
    parts = [_run_branch(weights, x, (4, lo))[0] for lo in (0, 4, 8, 12)]
    np.testing.assert_allclose(sum(parts) - 3 * identity, whole,
                               atol=2e-5, rtol=0)
    np.testing.assert_allclose(_run_branch(weights, x)[0], whole,
                               atol=2e-5, rtol=0)
    for lo, part in zip((0, 4, 8, 12), parts):
        with jax.default_matmul_precision("highest"):
            want, _ = _ref_branch(weights, x, lo, 4)
        np.testing.assert_allclose(part, want, atol=2e-5, rtol=0)


def test_a_token_of_identity_experts_only_and_one_of_real_experts_only():
    """The router reads the class of a token from its first feature:
    class A's six are all identity experts (the branch returns ``sum w``
    times the token and no expert computes), class B's six all have
    weights."""
    weights = _branch_weights(73)
    rng = np.random.default_rng(79)
    x = rng.standard_normal((8, 48)).astype("float32")
    x[:, 0] = [1, -1] * 4
    router = np.zeros((48, E + Z), "float32")
    router[0, E:] = 6.0 + 0.05 * np.arange(Z)
    router[0, :E] = -6.0 - 0.05 * np.arange(E)
    weights["b_moe_router.w_0"] = router
    got, (counts, zero) = _run_branch(weights, x, tally=True)
    with jax.default_matmul_precision("highest"):
        want, n_zero = _ref_branch(weights, x)
    assert n_zero.tolist() == [K, 0] * 4
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    # class A: exactly w times the token
    p = np.asarray(jax.nn.softmax(jnp.asarray(x[::2] @ router), axis=-1))
    w = 6.0 * np.sort(p, axis=-1)[:, -K:].sum(axis=-1)
    np.testing.assert_allclose(got[::2], w[:, None] * x[::2], atol=2e-5,
                               rtol=0)
    assert zero.tolist() == [[4 * K, K]]
    assert counts.sum() == 4 * K      # class B's pairs, all with weights


def test_identity_pairs_reach_no_group_and_no_cut_row(monkeypatch):
    """What the grouped matmuls are handed: group sizes that add up to
    the pairs with weights alone (an identity pair is in no group's
    rows), and under a share's bound a gather over ``cap`` rows, fewer
    than the call's pairs — reckoned over all the router's outputs."""
    from paddle_tpu.kernels import moe_gmm

    seen = []
    real_gmm = moe_gmm.gmm

    def spy(lhs, rhs, sizes, *, name):
        # inside the bounded call's own jit the sizes are tracers
        seen.append((name, lhs.shape[0],
                     None if isinstance(sizes, jax.core.Tracer)
                     else int(sizes.sum())))
        return real_gmm(lhs, rhs, sizes, name=name)

    monkeypatch.setattr(moe_gmm, "gmm", spy)
    monkeypatch.setattr(moe_ops, "_COMPACT_MIN_PAIRS", 256)
    rng = np.random.default_rng(83)
    T, D, F = 64, 16, 24
    w = {k: jnp.asarray(rng.standard_normal(s) * 0.2, jnp.float32)
         for k, s in (("w1", (2, D, F)), ("w1v", (2, D, F)),
                      ("w2", (2, F, D)), ("gate_w", (D, E + Z)))}
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)

    def call(share, w1, w1v, w2):
        return moe_ops._experts(
            x, w1, w1v, None, w2, None, w["gate_w"], E, K, None, "swiglu",
            False, 0.0, {"route_scale": 6.0}, share, None, Z)

    # whole: every real pair is in a group, no identity pair is
    big = {k: jnp.tile(w[k], (E // 2, 1, 1)) for k in ("w1", "w1v", "w2")}
    out, _aux, routed, took, most = call(None, big["w1"], big["w1v"],
                                         big["w2"])
    assert took is None and routed.shape == (E + Z,)
    n_zero = int(routed[E:].sum())
    assert 0 < n_zero < T * K and int(routed.sum()) == T * K
    assert len(seen) == 2
    for _name, rows, in_groups in seen:
        assert rows == T * K and in_groups == T * K - n_zero
    assert 0 < int(most) <= K
    # a share of 2 in 16 (+ 8 identity): the bound is over 24 outputs
    del seen[:]
    cap = moe_ops.compact_rows(T * K, E + Z, 2)
    assert cap == 128 < T * K
    out, _aux, routed, took, _most = call((4, 2), w["w1"], w["w1v"],
                                          w["w2"])
    assert int(took) == 1 and int(routed[4:6].sum()) <= cap
    # the cut branch's two grouped matmuls run over cap rows (the other
    # two traced calls are the full-length fallback's)
    assert sorted(rows for _name, rows, _n in seen) \
        == [cap, cap, T * K, T * K]
    assert int(routed[E:].sum()) > 0 and int(routed.sum()) == T * K


def test_bf16_stored_matrices_serve_the_same_tokens():
    """``weight_dtype='bfloat16'``: every matrix stored so (the router
    and the stacked experts too), vectors and the selection term float32;
    over bfloat16-valued weights the tokens are the float32 program's."""
    cfg = tiny_cfg(weight_dtype="bfloat16")
    params = seeded_params(cfg, 89)
    valued = {n: np.asarray(jnp.asarray(v, jnp.bfloat16).astype(jnp.float32))
              if v.ndim > 1 else v for n, v in params.items()}
    stored = {n: jnp.asarray(v, jnp.bfloat16) if v.ndim > 1 else v
              for n, v in valued.items()}
    rng = np.random.default_rng(97)
    prompts = [rng.integers(1, 97, n) for n in (6, 15)]
    out = []
    for c, p in ((cfg, stored), (tiny_cfg(), valued)):
        eng = _engine(c, p, 2).start()
        try:
            out.append([r.result(timeout=300).tolist() for r in
                        [eng.submit(np.asarray(q, "int64"), 10)
                         for q in prompts]])
        finally:
            eng.stop()
    assert out[0] == out[1]
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        gpt.build_serving_decode_step(cfg, batch=2, max_len=16)
    for p in prog.global_block().all_parameters():
        assert str(p.dtype) == ("float32" if len(p.shape) == 1
                                else "bfloat16"), p.name


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_analysis_engines_know_the_programs(which):
    """Shape inference, the lint, the range engine, the cost model and
    the memory model run over the programs without an unknown-op gap:
    the router and its selection term are ``n_expert + n_zero_expert``
    wide, the decode step holds three tallies."""
    from paddle_tpu.analysis.cost import CostAnalysis
    from paddle_tpu.analysis.infer import verify_program
    from paddle_tpu.analysis.memory import MemoryAnalysis
    from paddle_tpu.analysis.ranges import RangeAnalysis

    cfg = tiny_cfg(n_expert_local=4, expert_first=4)
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        if which == "decode":
            out, _ = gpt.build_serving_decode_step(cfg, batch=3, max_len=64)
        else:
            out, _ = gpt.build_prefill_step(cfg, batch=1, prompt_len=20,
                                            max_len=64)
    widened = ("moe_ffn", "fused_attention", "kv_cache_write", "rms_norm",
               "mla_decode")
    findings = verify_program(prog, fetch_list=[out.name], fill=False)
    bad = [f for f in findings if f.severity == "error"
           or (f.severity == "warning"
               and any(t in f.message for t in widened))]
    assert not bad, bad
    branches = [op for op in prog.global_block().ops
                if op.type == "moe_ffn"]
    assert len(branches) == 2
    assert ("ZeroOut" in branches[0].outputs) == (which == "decode")
    ra = RangeAnalysis(prog)
    assert not set(ra.widened) & set(widened), ra.widened
    assert not CostAnalysis(prog).unruled
    assert MemoryAnalysis(prog).peak_bytes(1) > 0
    # a router narrower than its outputs is refused by the shape rule
    branches[0].attrs["n_zero"] = Z - 1
    msgs = [f.message for f in verify_program(
        prog, fetch_list=[out.name], raise_on_error=False, fill=False)
        if f.severity == "error"]
    assert any("Gate is" in m for m in msgs), msgs
