"""Trinity (``model_type`` afmoe) through the system's normal path, against
the plain reference (tests/references/afmoe.py): window and full
attention layers over a cache of two shapes (rings beside slabs), gated
attention, per-head q/k norm, sandwich norm, sigmoid top-k routing with a
selection bias over one chip's share of the experts plus a shared expert
— training build, prefill and decode through DecodeEngine's caches, the
windowed flash forward, the share adding up."""

import hashlib
import importlib.util
import json
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


reference = _load(os.path.join(HERE, "references", "afmoe.py"))
WINDOW = 8


def tiny_cfg(**over):
    """Hidden 48, 6 heads and 2 key/value heads of 16, window 8, one
    dense layer then s, s, s, f expert layers, 16 experts of width 24
    top-4 with one shared, vocabulary 97."""
    cfg = dict(d_model=48, n_head=6, n_kv_head=2, d_head=16, n_layer=5,
               vocab=97, max_length=64, dropout=0.0, pos_emb="rope",
               rope_theta=10000.0, rope_layers="sliding",
               layer_types=["sliding"] * 4 + ["full"], window=WINDOW,
               norm="rms", norm_eps=1e-5, qk_norm="head", attn_gate=True,
               sandwich_norm=True, emb_scale=48 ** 0.5, ffn_act="swiglu",
               d_ff=96, n_dense_layer=1, n_expert=16, expert_top_k=4,
               d_expert=24, n_shared_expert=1, router_score="sigmoid",
               router_bias=True, norm_topk=True, route_scale=2.448)
    cfg.update(over)
    return cfg


def seeded_params(cfg, seed):
    """Every parameter drawn from the seed: matrices within Xavier
    limits, every norm scale uniform in 0.5-1.5, the router's selection
    bias within +-0.2 (sigmoid scores of seeded routers lie within a few
    tenths of one half: large enough to change some selections)."""
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        gpt.build_serving_decode_step(cfg, batch=1, max_len=16)
    rng = np.random.default_rng(seed)
    out = {}
    for p in sorted(prog.global_block().all_parameters(),
                    key=lambda p: p.name):
        shape = tuple(p.shape)
        if p.name.endswith("_router_bias"):
            out[p.name] = rng.uniform(-0.2, 0.2, shape).astype("float32")
        elif len(shape) == 1:
            out[p.name] = rng.uniform(0.5, 1.5, shape).astype("float32")
        else:
            lim = (6.0 / (shape[-2] + shape[-1])) ** 0.5
            out[p.name] = rng.uniform(-lim, lim, shape).astype("float32")
    return out


def _ref_logits(params, cfg, ids):
    return np.asarray(reference.forward(params, cfg, jnp.asarray(ids)))


def test_every_parameter_is_named_and_has_no_bias():
    """The parameters the reference's docstring lists are the program's,
    and nothing else is a parameter (an fc bias left on would be drawn in
    0.5-1.5 by the benchmark)."""
    cfg = tiny_cfg()
    names = set(seeded_params(cfg, 0))
    want = {"gpt_word_emb", "gpt_out_proj.w_0", "gpt_ln_f_s"}
    for i in range(5):
        nm = "gpt_%d_" % i
        want |= {nm + s for s in (
            "pre1_ln_s", "post1_ln_s", "pre2_ln_s", "post2_ln_s",
            "att_q.w_0", "att_k.w_0", "att_v.w_0", "att_g.w_0",
            "att_o.w_0", "att_qnorm_s", "att_knorm_s")}
        want |= {nm + s for s in (
            ("ffn1.w_0", "ffn1v.w_0", "ffn2.w_0") if i == 0 else
            ("moe_router.w_0", "moe_router_bias", "moe_gate.w_0",
             "moe_up.w_0", "moe_down.w_0", "moe_shared_gate.w_0",
             "moe_shared_up.w_0", "moe_shared_down.w_0"))}
    assert names == want
    p = seeded_params(cfg, 0)
    assert p["gpt_1_att_qnorm_s"].shape == (16,)
    assert p["gpt_1_att_q.w_0"].shape == (48, 96)
    assert p["gpt_1_att_k.w_0"].shape == (48, 32)
    assert p["gpt_1_att_o.w_0"].shape == (96, 48)


@pytest.mark.parametrize("S", [6, 20])
def test_training_build_logits_match_reference(S):
    """Shorter and longer than the window: the band is a bias here."""
    cfg = tiny_cfg()
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
    # the selection bias has no gradient by construction (it never
    # touches a gate): everything else moved
    assert all(n.endswith("_router_bias") for n in still), still


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


def _assert_matches_reference(cfg, params, prompts, toks, rows):
    for p, t, r in zip(prompts, toks, rows):
        want = _ref_logits(params, cfg, np.asarray(t[:-1]))
        np.testing.assert_allclose(np.stack(r), want[len(p) - 1:],
                                   atol=1e-4, rtol=0)


def test_prefill_then_decode_through_rings_that_wrap_twice():
    """3 x window tokens decoded after prompts shorter than, equal to and
    longer than the window (one past two windows), four slots in
    company: every ring wraps at least twice on the compared path, and
    the long prompts leave a wrapped ring behind their prefill."""
    cfg = tiny_cfg()
    params = seeded_params(cfg, 7)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 97, n) for n in (5, WINDOW, 13, 19)]
    eng = _engine(cfg, params, 4)
    shapes = {n: np.asarray(eng._lane.scope.find_var(n)).shape
              for n in eng._lane.cache_names}
    assert shapes["gpt_0_cache_k"] == (4, 2, WINDOW, 16)
    assert shapes["gpt_3_cache_v"] == (4, 2, WINDOW, 16)
    assert shapes["gpt_4_cache_k"] == (4, 2, 64, 16)
    toks, rows = _decode_in_company(eng, prompts, 3 * WINDOW)
    assert all(len(t) == len(p) + 3 * WINDOW
               for p, t in zip(prompts, toks))
    _assert_matches_reference(cfg, params, prompts, toks, rows)
    # the tallies: 23 decode steps x 4 slots x 4 pairs on each of the
    # four expert layers, none on the dense layer
    tally = eng.routed_pairs()
    assert tally.shape == (5, 16)
    assert tally.sum(axis=1).tolist() == [0] + [23 * 4 * 4] * 4
    assert eng.experts_touched() is None        # every expert is held


def test_a_readmitted_slot_sees_no_row_of_its_previous_tenant():
    """Slot 1 serves a long sequence (its rings wrapped), retires, and
    is given a prompt shorter than the window: the stale ring rows beyond
    the new sequence's position hold the old tenant's keys and must stay
    invisible until overwritten."""
    cfg = tiny_cfg()
    params = seeded_params(cfg, 13)
    rng = np.random.default_rng(17)
    eng = _engine(cfg, params, 2)
    old = [rng.integers(1, 97, 19)]
    _decode_in_company(eng, old, 12, slots=[1])
    # make the prefill scope's rings stale too: another long prompt went
    # through it into slot 0 since
    _decode_in_company(eng, [rng.integers(1, 97, 21)], 2, slots=[0])
    new = [rng.integers(1, 97, 3)]
    toks, rows = _decode_in_company(eng, new, 2 * WINDOW, slots=[1])
    _assert_matches_reference(cfg, params, new, toks, rows)


def test_engine_serves_greedy_requests_like_the_lane():
    cfg = tiny_cfg()
    params = seeded_params(cfg, 19)
    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, 97, n) for n in (4, 11, 20, 7)]
    toks, _ = _decode_in_company(_engine(cfg, params, 4), prompts, 12)
    eng = _engine(cfg, params, 4).start()
    try:
        got = [r.result(timeout=300) for r in
               [eng.submit(np.asarray(p, "int64"), 12) for p in prompts]]
    finally:
        eng.stop()
    for g, t in zip(got, toks):
        assert g.tolist() == [int(x) for x in t]


def test_prefill_through_the_flash_kernel_matches_reference(monkeypatch):
    """With the kernel forced at every length, the prefill of a cfg with
    two kinds of layer runs the flash forward (interpret mode here):
    grouped heads in both kinds, the band in the sliding layers under the
    name flash_fwd_win."""
    from paddle_tpu.observe.families import FLASH_BLOCK_PLANS

    monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_SEQ", "0")
    cfg = tiny_cfg()
    params = seeded_params(cfg, 29)
    prompt = np.random.default_rng(31).integers(1, 97, 21)
    win = FLASH_BLOCK_PLANS.labels(kernel="flash_fwd_win",
                                   block="21x21 1of1", single_pass="1",
                                   layout="heads")
    full = FLASH_BLOCK_PLANS.labels(kernel="flash_fwd", block="21x21",
                                    single_pass="1", layout="heads")
    before = win.value, full.value
    eng = _engine(cfg, params, 1)
    toks, rows = _decode_in_company(eng, [prompt], 10)
    assert (win.value - before[0], full.value - before[1]) == (4, 1)
    _assert_matches_reference(cfg, params, [prompt], toks, rows)


FLASH_WIN_CASES = [
    # S, window, H, Hkv: windows smaller than, equal to and not a
    # multiple of the block; S not a multiple of the block; grouped heads
    (384, 64, 4, 2), (384, 128, 6, 2), (300, 100, 4, 2), (520, 200, 2, 1),
    (700, 512, 6, 2), (256, 256, 4, 4), (640, 129, 6, 3),
]


@pytest.mark.parametrize("S,window,H,Hkv", FLASH_WIN_CASES)
def test_windowed_flash_forward_matches_composed(S, window, H, Hkv,
                                                 monkeypatch):
    from paddle_tpu.ops import attention as A

    monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_SEQ", "0")
    rs = np.random.RandomState(S + window)
    q, k, v = (jnp.asarray(rs.randn(1, n, S, 16).astype("float32"))
               for n in (H, Hkv, Hkv))
    got = A.flash_attention(q, k, v, None, 0.25, causal=True, window=window)
    want = A.composed_attention(q, k, v, None, 0.25, True, window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    # against the definition, not only the other implementation
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    keep = (j <= i) & (i - j < window)
    kr, vr = (np.repeat(np.asarray(t), H // Hkv, axis=1) for t in (k, v))
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), kr) * 0.25
    s = np.where(keep, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    np.testing.assert_allclose(got, np.einsum("bhqk,bhkd->bhqd", p, vr),
                               atol=2e-5, rtol=0)


def test_window_moves_the_block_plan_and_the_plan_is_counted():
    from paddle_tpu.ops import attention as A

    # the cell's longest prompt: 512x512 blocks, the band's blocks only
    plan = A._block_plan(A.KERNEL_FWD, 8192, 8192, 128, jnp.float32, True,
                         False, 4096)
    assert plan == (512, 512)
    assert A._band_blocks(16, 16, 512, 512, 4096) == \
        sum(min(iq, 8) + 1 for iq in range(16))          # 100 of 256
    # a band narrower than a key axis that would fit one block cuts it
    assert A._block_plan(A.KERNEL_FWD, 384, 384, 16, jnp.float32, True,
                         False, 100) == (384, 128)
    # no window, or one that covers the keys: the parent's plan
    for S in (512, 640, 1024, 2048):
        assert A._block_plan(A.KERNEL_FWD, S, S, 64, jnp.float32, True,
                             False, None) == \
            A._block_plan(A.KERNEL_FWD, S, S, 64, jnp.float32, True,
                          False, 4096)
    with pytest.raises(ValueError, match="causal"):
        A.flash_attention(jnp.zeros((1, 2, 8, 16)), jnp.zeros((1, 2, 8, 16)),
                          jnp.zeros((1, 2, 8, 16)), window=4)


def _layer_output(cfg, params, x, layer=1):
    """One expert layer's ``moe_ffn`` (routed part + shared expert) on
    ``x [T, D]`` through the layers API."""
    prog, start = fluid.Program(), fluid.Program()
    scope = fluid.core.scope.Scope()
    with fluid.core.scope.scope_guard(scope):
        with fluid.program_guard(prog, start):
            h = fluid.layers.data("h", [x.shape[1]], dtype="float32")
            out = gpt._mlp(cfg, h, "gpt_%d" % layer, layer)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(start, scope=scope)
        for p in prog.global_block().all_parameters():
            scope.set_var(p.name, params[p.name])
        (got,) = exe.run(prog, feed={"h": x}, fetch_list=[out], scope=scope)
    return got


def _ref_layer(params, cfg, x, layer=1, shared=True):
    nm = "gpt_%d_moe_" % layer
    w = {k[len(nm):]: jnp.asarray(v) for k, v in params.items()
         if k.startswith(nm)}
    with jax.default_matmul_precision("highest"):
        out, _gap = reference.experts(
            jnp.asarray(x), w["router.w_0"], w.get("router_bias"),
            w["gate.w_0"], w["up.w_0"], w["down.w_0"], cfg["expert_top_k"],
            bool(cfg.get("norm_topk")), float(cfg.get("route_scale") or 1),
            int(cfg.get("expert_first") or 0))
        if shared:
            out = out + reference.swiglu(
                jnp.asarray(x), w["shared_gate.w_0"], w["shared_up.w_0"],
                w["shared_down.w_0"])
    return np.asarray(out)


def _share_of(params, first, n, layer=1):
    """The parameters one chip of the deployment holds: experts
    ``first .. first + n - 1`` of the stacked weights, all else whole."""
    out = dict(params)
    for part in ("gate", "up", "down"):
        name = "gpt_%d_moe_%s.w_0" % (layer, part)
        out[name] = params[name][first:first + n]
    return out


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that all n_expert / n_expert_local shares give,
    with the shared expert counted once, sum to the uncut reference's
    layer output."""
    cfg = tiny_cfg()
    params = seeded_params(cfg, 37)
    x = np.random.default_rng(41).standard_normal((11, 48)) \
        .astype("float32")
    whole = _ref_layer(params, cfg, x)
    np.testing.assert_allclose(_layer_output(cfg, params, x), whole,
                               atol=1e-5, rtol=0)
    shared = _ref_layer(params, cfg, x) - _ref_layer(params, cfg, x,
                                                     shared=False)
    total = np.zeros_like(whole)
    for first in range(0, 16, 4):
        part_cfg = tiny_cfg(n_expert_local=4, expert_first=first)
        part = _share_of(params, first, 4)
        got = _layer_output(part_cfg, part, x)
        # the reference takes the same share and leaves the same out
        np.testing.assert_allclose(got, _ref_layer(part, part_cfg, x),
                                   atol=1e-5, rtol=0)
        total += got - shared
    np.testing.assert_allclose(total + shared, whole, atol=1e-5, rtol=0)


def test_a_share_through_the_engine_and_its_touched_tally():
    """One chip's share served end to end against the reference given
    the same share; the device counts, per held expert, the steps in
    which it received a pair."""
    from paddle_tpu.observe.families import MOE_EXPERTS_TOUCHED

    cfg = tiny_cfg(n_expert_local=4, expert_first=8)
    full = seeded_params(tiny_cfg(), 43)
    params = dict(full)
    for layer in range(1, 5):
        params = _share_of(params, 8, 4, layer)
    rng = np.random.default_rng(47)
    prompts = [rng.integers(1, 97, n) for n in (6, 12)]
    eng = _engine(cfg, params, 2)
    toks, rows = _decode_in_company(eng, prompts, 10)
    _assert_matches_reference(cfg, params, prompts, toks, rows)
    routed, touched = eng.routed_pairs(), eng.experts_touched()
    assert routed.shape == (5, 16) and touched.shape == (5, 4)
    assert routed.sum(axis=1).tolist() == [0] + [9 * 2 * 4] * 4
    assert (touched[0] == 0).all() and (touched <= 9).all()
    # an expert that was given pairs was touched in at least one step and
    # in no more steps than it has pairs
    held = routed[:, 8:12]
    assert ((touched > 0) == (held > 0)).all() and (touched <= held).all()
    assert MOE_EXPERTS_TOUCHED.labels(layer="2", expert="1").value \
        == int(touched[2, 1])


def test_selection_bias_changes_who_is_selected_and_never_the_weights():
    from paddle_tpu.parallel.moe import router

    rng = np.random.default_rng(53)
    x = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 12)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.uniform(-0.3, 0.3, 12), jnp.float32)
    e0, g0, _ = router(x, w, 12, 3, norm_topk=False, score="sigmoid")
    e1, g1, _ = router(x, w, 12, 3, norm_topk=False, score="sigmoid",
                       bias=bias)
    assert (np.asarray(e0) != np.asarray(e1)).any()      # who
    s = np.asarray(jax.nn.sigmoid(jnp.dot(x, w, precision="highest")))
    np.testing.assert_allclose(                          # never the weights
        np.asarray(g1), np.take_along_axis(s, np.asarray(e1).T, 1).T,
        atol=1e-6)
    # a bias that lifts one expert over everything selects it everywhere
    # at its own unbiased score
    lift = jnp.zeros(12).at[5].set(10.0)
    e2, g2, _ = router(x, w, 12, 3, norm_topk=False, score="sigmoid",
                       bias=lift)
    assert (np.asarray(e2)[0] == 5).all()
    np.testing.assert_allclose(np.asarray(g2)[0], s[:, 5], atol=1e-6)
    # route_norm with route_scale: the k gates sum to the scale
    _, g3, _ = router(x, w, 12, 3, norm_topk=True, score="sigmoid",
                      bias=bias, route_scale=2.448)
    np.testing.assert_allclose(np.asarray(g3).sum(axis=0), 2.448,
                               rtol=1e-5)
    with pytest.raises(ValueError, match="score"):
        router(x, w, 12, 3, score="tanh")


def test_a_router_forced_onto_one_expert_stays_dropless():
    """Every token's four experts are 0..3 (a zero router ties every
    score and the bias breaks the tie): 4 slots a step on each of four
    experts, where a capacity would drop; the answers still match."""
    cfg = tiny_cfg()
    params = seeded_params(cfg, 59)
    for i in range(1, 5):
        params["gpt_%d_moe_router.w_0" % i][:] = 0.0
        params["gpt_%d_moe_router_bias" % i][:] = \
            np.where(np.arange(16) < 4, 0.3, 0.0)
    rng = np.random.default_rng(61)
    prompts = [rng.integers(1, 97, n) for n in (5, 9, 3, 12)]
    eng = _engine(cfg, params, 4)
    toks, rows = _decode_in_company(eng, prompts, 12)
    _assert_matches_reference(cfg, params, prompts, toks, rows)
    tally = eng.routed_pairs()
    assert (tally[1:, 4:] == 0).all() and (tally[1:, :4] == 44).all()


@pytest.mark.parametrize("n_rhs", [1, 2])
@pytest.mark.parametrize("sizes,M", [
    # a share's decode step: 64 pairs, most for absent experts, most
    # held groups empty; none at all; a prefill's many idle row tiles
    ([0, 0, 3, 0, 0, 0, 2, 0], 64), ([0] * 8, 64),
    ([5, 0, 0, 130, 0, 0, 0, 9], 1024),
])
def test_gmm_kernel_with_empty_groups_and_rows_of_no_group(sizes, M, n_rhs):
    """The grouped matmul as one chip's share calls it: the kernel
    (interpret mode) against the composed form where most rows belong to
    no held group, so most work tiles of the static grid are idle."""
    from paddle_tpu.kernels import moe_gmm

    rng = np.random.default_rng(sum(sizes) + M)
    lhs = jnp.asarray(rng.standard_normal((M, 256)), jnp.float32)
    rhs = tuple(jnp.asarray(rng.standard_normal((8, 256, 128)) * 0.1,
                            jnp.float32) for _ in range(n_rhs))
    gs = jnp.asarray(sizes, jnp.int32)
    got = moe_gmm.gmm_pallas(lhs, rhs, gs, name=moe_gmm.KERNEL_UP,
                             interpret=True)
    want = moe_gmm.gmm_composed(lhs, rhs, gs)
    owned = int(sum(sizes))
    np.testing.assert_allclose(got[:owned], want[:owned], atol=1e-4,
                               rtol=0)
    assert not np.asarray(got[owned:]).any()


# ------------------------------------------------------- the parent's cfgs
_OLD_CFGS = {
    "gpt2m": dict(d_model=64, d_ff=256, n_head=4, n_layer=2, vocab=211,
                  max_length=64, dropout=0.0, ffn_act="gelu",
                  tie_embeddings=True),
    "olmoe": dict(d_model=64, n_head=4, n_layer=2, vocab=97, max_length=64,
                  dropout=0.0, pos_emb="rope", norm="rms", norm_eps=1e-5,
                  rope_theta=10000.0, qk_norm=True, n_expert=8,
                  expert_top_k=2, d_expert=32, norm_topk=False),
}
_BUILDS = {
    "serving_decode": lambda c: gpt.build_serving_decode_step(
        c, batch=4, max_len=32),
    "decode": lambda c: gpt.build_decode_step(c, batch=2, max_len=32),
    "prefill": lambda c: gpt.build_prefill_step(
        c, batch=1, prompt_len=8, max_len=32),
    "multi_token": lambda c: gpt.build_multi_token_decode_step(
        c, batch=2, steps=3, max_len=32),
    "train": lambda c: gpt.build(c, seq_len=16, is_test=True,
                                 use_fused_attention=False),
    "train_fused": lambda c: gpt.build(c, seq_len=16,
                                       use_fused_attention=True),
}


@pytest.mark.parametrize("build", sorted(_BUILDS))
@pytest.mark.parametrize("shape", sorted(_OLD_CFGS))
def test_a_cfg_without_the_new_keys_builds_the_parents_program(shape, build):
    """Op for op — type, slots and attributes — against the digests taken
    from the parent commit (28e8576) by this same function. One was taken
    again by PR 38: ``gpt2m`` / ``train_fused`` has neither rotation nor
    grouped heads, so its two layers hand q, k, v to ``fused_attention``
    as [B, S, H*D] with ``n_head`` and lost their eight ``reshape2`` and
    eight ``transpose2`` (75 ops -> 59); ``olmoe``'s rotates and keeps
    them. PR 58's
    residual pins (one ``materialize`` a layer of every prefill: a barrier,
    no arithmetic) are left out of the list, and counted by
    ``tests/test_gpt_programs_pinned.py``."""
    with open(os.path.join(HERE, "references",
                           "gpt_op_lists_parent.json")) as f:
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


def test_the_two_reference_copies_agree_to_the_last_bit():
    copy = _load(os.path.join(ROOT, "benchmarks", "references",
                              "trinity-large-preview.py"))
    with open(reference.__file__, "rb") as a, open(copy.__file__, "rb") as b:
        assert a.read() == b.read()
    cfg = tiny_cfg(n_expert_local=8, expert_first=0)
    params = seeded_params(cfg, 67)
    ids = jnp.asarray(np.random.default_rng(71).integers(1, 97, 27))
    a, ga = reference.forward(params, cfg, ids, with_gaps=True)
    b, gb = copy.forward(params, cfg, ids, with_gaps=True)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(ga), np.asarray(gb))


def test_reference_gaps_and_bfloat16_control():
    cfg = tiny_cfg()
    params = seeded_params(cfg, 73)
    ids = jnp.asarray(np.random.default_rng(79).integers(1, 97, 30))
    logits, gaps = reference.forward(params, cfg, ids, with_gaps=True)
    assert gaps.shape == (30,) and (np.asarray(gaps) >= 0).all()
    low = reference.forward(params, cfg, ids, 7, 7)
    err = float(jnp.abs(low - logits).max())
    assert 1e-3 < err < 1.0           # bfloat16 is visibly not float32
    margins, g = reference.greedy_margin_fn(params, cfg, 16, ((7, 7),))(
        np.asarray(ids), 10)
    assert len(margins) == 2 and margins[0].shape == (20,) == g.shape
    # the blocked attention does not depend on the block
    saved = reference.QUERY_BLOCK
    try:
        reference.QUERY_BLOCK = 7
        again = reference.forward(params, cfg, ids)
    finally:
        reference.QUERY_BLOCK = saved
    np.testing.assert_allclose(again, logits, atol=1e-5, rtol=0)


# --------------------------------------------------- refusals and the rest
@pytest.mark.parametrize("over,drop,match", [
    (dict(layer_types=["sliding"] * 4), (), "layer_types"),
    (dict(layer_types=["sliding"] * 4 + ["global"]), (), "layer_types"),
    ({}, ("window",), "window"),
    ({}, ("layer_types",), "window.*layer_types"),
    (dict(qk_norm="heads"), (), "qk_norm"),
    (dict(router_score="tanh"), (), "router_score"),
    (dict(rope_layers="full"), (), "rope_layers"),
    (dict(rope_layers="sliding", pos_emb="learned"), (), "rope_layers"),
    (dict(n_expert_local=8, expert_first=12), (), "share"),
    (dict(n_dense_layer=6), (), "n_dense_layer"),
    ({}, ("n_expert", "expert_top_k", "d_expert", "n_dense_layer"),
     "needs cfg\\['n_expert'\\]"),
    (dict(d_head=15), (), "even"),
    (dict(windows=8), (), "unknown"),
])
def test_check_cfg_rejects_bad_new_keys(over, drop, match):
    cfg = {k: v for k, v in tiny_cfg(**over).items() if k not in drop}
    with pytest.raises(ValueError, match=match):
        gpt._check_cfg(cfg)


def test_prefix_store_and_speculative_lane_refuse_a_ring_cfg_by_name():
    from paddle_tpu.serving import DecodeEngine, PrefixStore

    cfg = tiny_cfg()
    with pytest.raises(ValueError, match="prefix store.*rings"):
        DecodeEngine(cfg, b_max=2, max_len=64,
                     prefix_store=PrefixStore(1 << 20))
    with pytest.raises(ValueError, match="prefix store.*rings"):
        DecodeEngine(cfg, b_max=2, max_len=64, prefix_cache_bytes=1 << 20)
    with pytest.raises(ValueError, match="speculative.*rings"):
        DecodeEngine(cfg, b_max=2, max_len=64, draft_cfg=tiny_cfg(),
                     spec_k=2)
    with pytest.raises(ValueError, match="multi_token.*rings"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            gpt.build_multi_token_decode_step(cfg, batch=1, steps=2,
                                              max_len=64)
    # a window that covers max_len leaves slabs only: both levers serve
    wide = tiny_cfg(window=64)
    assert not gpt.has_rings(wide, 64) and gpt.has_rings(wide, 128)
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        gpt.build_multi_token_decode_step(wide, batch=1, steps=2,
                                          max_len=64)


def test_cache_bytes_gauge_and_footprint_follow_each_tensors_shape():
    from paddle_tpu.observe.families import SERVING_CACHE_BYTES

    cfg = tiny_cfg()
    eng = _engine(cfg, None, 3, max_len=64)
    ring = 4 * 2 * 3 * 2 * WINDOW * 16 * 4
    full = 1 * 2 * 3 * 2 * 64 * 16 * 4
    assert SERVING_CACHE_BYTES.labels(kind="ring").value == ring
    assert SERVING_CACHE_BYTES.labels(kind="full").value == full
    uniform = 5 * 2 * 3 * 2 * 64 * 16 * 4
    weights = sum(int(np.prod(p.shape)) * 4 for p in
                  eng._lane._decode_prog.global_block().all_parameters())
    resident = eng.predicted_resident_bytes()
    assert weights + ring + full <= resident < weights + uniform
    assert eng.predicted_bytes(40) > resident


@pytest.mark.parametrize("which", ["decode", "prefill", "train"])
def test_analysis_engines_know_the_programs(which):
    """Shape inference, the lint, the range engine, the cost model and
    the memory model run over the Trinity programs without an unknown-op
    gap (the prefill holds the fused attention op with a window and
    grouped heads, the decode step both tallies)."""
    from paddle_tpu.analysis.cost import CostAnalysis
    from paddle_tpu.analysis.infer import verify_program
    from paddle_tpu.analysis.memory import MemoryAnalysis
    from paddle_tpu.analysis.ranges import RangeAnalysis

    cfg = tiny_cfg(n_expert_local=4, expert_first=4)
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        if which == "decode":
            out, _ = gpt.build_serving_decode_step(cfg, batch=3, max_len=64)
        elif which == "prefill":
            out, _ = gpt.build_prefill_step(cfg, batch=1, prompt_len=20,
                                            max_len=64)
        else:
            out, _ = gpt.build(cfg, seq_len=20, is_test=True)
    widened = ("moe_ffn", "fused_attention", "kv_cache_write", "rms_norm",
               "elementwise_mod")
    findings = verify_program(prog, fetch_list=[out.name], fill=False)
    bad = [f for f in findings if f.severity == "error"
           or (f.severity == "warning"
               and any(t in f.message for t in widened))]
    assert not bad, bad
    types = {op.type for op in prog.global_block().ops}
    assert "moe_ffn" in types
    assert ("fused_attention" in types) == (which == "prefill")
    ra = RangeAnalysis(prog)
    assert not set(ra.widened) & set(widened), ra.widened
    ca = CostAnalysis(prog)
    assert not ca.unruled, ca.unruled
    assert MemoryAnalysis(prog).peak_bytes(1) > 0


def test_shape_rules_reject_a_bad_window_and_a_bad_share():
    from paddle_tpu.analysis.infer import verify_program

    cfg = tiny_cfg(n_expert_local=4, expert_first=4)
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        out, _ = gpt.build_prefill_step(cfg, batch=1, prompt_len=20,
                                        max_len=64)
    for op in prog.global_block().ops:
        if op.type == "fused_attention" and op.attrs.get("window"):
            op.attrs["causal"] = False
        if op.type == "moe_ffn":
            op.attrs["expert_first"] = 14
    msgs = [f.message for f in verify_program(
        prog, fetch_list=[out.name], raise_on_error=False, fill=False)
        if f.severity == "error"]
    assert any("window needs causal" in m for m in msgs), msgs
    assert any("not a share" in m for m in msgs), msgs
