"""OLMoE through the system's normal path, against the plain reference
(tests/references/olmoe.py): dropless top-k routing over SwiGLU experts,
q/k RMSNorm, RoPE, the untied head — training build, prefill and decode
through DecodeEngine's cache, the grouped-matmul kernel, gradients."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.kernels import moe_gmm
from paddle_tpu.models import gpt

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path):
    spec = importlib.util.spec_from_file_location(
        "ref_" + os.path.basename(path).replace("-", "_")[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load(os.path.join(HERE, "references", "olmoe.py"))


def tiny_cfg(top_k=2, **over):
    cfg = dict(d_model=64, n_head=4, n_layer=2, vocab=97, max_length=64,
               dropout=0.0, pos_emb="rope", norm="rms", norm_eps=1e-5,
               rope_theta=10000.0, qk_norm=True, n_expert=8,
               expert_top_k=top_k, d_expert=32, norm_topk=False)
    cfg.update(over)
    return cfg


def seeded_params(cfg, seed):
    """Every parameter of the model drawn from the seed: matrices within
    Xavier limits, the RMSNorm scales uniform in 0.5-1.5 (a scale left
    at one tests nothing)."""
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
            out[p.name] = rng.uniform(-lim, lim, shape).astype("float32")
    return out


def _ref_logits(params, cfg, ids):
    return np.asarray(reference.forward(params, cfg, jnp.asarray(ids)))


@pytest.mark.parametrize("top_k", [2, 8])
def test_training_build_logits_match_reference(top_k):
    cfg, S = tiny_cfg(top_k), 12
    params = seeded_params(cfg, 3)
    prog, start = fluid.Program(), fluid.Program()
    scope = fluid.core.scope.Scope()
    with fluid.core.scope.scope_guard(scope):
        with fluid.program_guard(prog, start):
            gpt.build(cfg, seq_len=S, is_test=True,
                      use_fused_attention=False)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(start, scope=scope)
        for n, v in params.items():
            assert scope.find_var(n) is not None, n
            scope.set_var(n, v)
        (ce,) = [op for op in prog.global_block().ops
                 if op.type == "softmax_with_cross_entropy"]
        logits_name = ce.inputs["Logits"][0]
        ids = np.random.default_rng(5).integers(1, 97, (2, S))
        (got,) = exe.run(prog, feed={"ids": ids.astype("int64")},
                         fetch_list=[logits_name], scope=scope)
    for b in range(2):
        np.testing.assert_allclose(got[b], _ref_logits(params, cfg, ids[b]),
                                   atol=1e-4, rtol=0)


def _decode_in_company(cfg, params, prompts, n_new):
    """Prefill each prompt into its slot of one DecodeEngine, then decode
    ``n_new`` greedy tokens with all slots riding the same steps. Returns
    per slot (tokens, the logits row that chose each generated token)."""
    from paddle_tpu.serving import DecodeEngine

    eng = DecodeEngine(cfg, params=params, b_max=len(prompts), max_len=64)
    lane = eng._lane
    toks = [list(p) for p in prompts]
    rows = [[] for _ in prompts]
    for s, p in enumerate(prompts):
        _, last = lane.prefill_insert(s, np.asarray(p, "int64"))
        rows[s].append(np.asarray(last))
        toks[s].append(int(np.argmax(last)))
    for _ in range(n_new - 1):
        token = np.array([[t[-1]] for t in toks], "int64")
        pos = np.array([[len(t) - 1] for t in toks], "int64")
        logits = lane.decode(token, pos)
        for s in range(len(prompts)):
            rows[s].append(np.asarray(logits[s, 0]))
            toks[s].append(int(np.argmax(logits[s, 0])))
    return eng, toks, rows


def _assert_cache_path_matches(cfg, params):
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 97, n) for n in (5, 9, 3, 7)]
    eng, toks, rows = _decode_in_company(cfg, params, prompts, 12)
    for p, t, r in zip(prompts, toks, rows):
        assert len(t) == len(p) + 12
        want = _ref_logits(params, cfg, np.asarray(t[:-1]))
        np.testing.assert_allclose(np.stack(r), want[len(p) - 1:],
                                   atol=1e-4, rtol=0)
    return eng


@pytest.mark.parametrize("top_k", [2, 8])
def test_prefill_then_decode_through_cache_matches_reference(top_k):
    cfg = tiny_cfg(top_k)
    eng = _assert_cache_path_matches(cfg, seeded_params(cfg, 7))
    # the device-side tally: 11 decode steps x 4 slots x top_k pairs a
    # layer, and the engine reads it on request only
    tally = eng.routed_pairs()
    assert tally.shape == (2, 8) and tally.dtype == np.int32
    assert tally.sum(axis=1).tolist() == [11 * 4 * top_k] * 2
    from paddle_tpu.observe.families import MOE_ROUTED_PAIRS
    assert MOE_ROUTED_PAIRS.labels(layer="1", expert="0").value \
        == int(tally[1, 0])


def test_engine_greedy_tokens_are_the_logits_paths_argmax():
    """At OLMoE's vocabulary (50,304, untied head) the ids the engine's
    programs choose on the device are the argmax of the logits the same
    programs hand back: four requests served greedy through
    ``submit`` (ids fetched) against the same prompts driven through
    the lane with the logits fetched and ``np.argmax`` on the host."""
    from paddle_tpu.observe.families import SERVING_FETCHES

    cfg = tiny_cfg(8, vocab=50304)
    assert not cfg.get("tie_embeddings")
    params = seeded_params(cfg, 17)
    assert params["gpt_out_proj.w_0"].shape == (64, 50304)
    rng = np.random.default_rng(19)
    prompts = [rng.integers(1, 50304, n) for n in (5, 9, 3, 7)]
    eng, toks, _rows = _decode_in_company(cfg, params, prompts, 10)
    assert max(map(max, toks)) > 97     # the wide head is in play
    ids = {(site, fetch): SERVING_FETCHES.labels(site=site, fetch=fetch)
           for site in ("step", "admit") for fetch in ("tokens", "logits")}
    before = {k: c.value for k, c in ids.items()}
    eng.start()
    try:
        got = [r.result(timeout=300) for r in
               [eng.submit(np.asarray(p, "int64"), 10) for p in prompts]]
    finally:
        eng.stop()
    for g, t in zip(got, toks):
        assert g.tolist() == [int(x) for x in t]
    moved = {k: c.value - before[k] for k, c in ids.items()}
    assert moved[("admit", "tokens")] == 4 and moved[("step", "tokens")] > 0
    assert moved[("admit", "logits")] == moved[("step", "logits")] == 0


def test_dropless_every_token_to_the_same_experts():
    """A router of zeros ties every probability, so every token's two
    experts are 0 and 1: 16 and more pairs on each of two experts where a
    Switch capacity would keep ceil(2*T*k/E) and zero the rest."""
    cfg = tiny_cfg(2)
    params = seeded_params(cfg, 13)
    for i in range(cfg["n_layer"]):
        params["gpt_%d_moe_router.w_0" % i][:] = 0.0
    eng = _assert_cache_path_matches(cfg, params)
    tally = eng.routed_pairs()
    assert (tally[:, 2:] == 0).all() and (tally[:, :2] == 44).all()


def test_dense_engine_has_no_routing_tally():
    from paddle_tpu.serving import DecodeEngine

    cfg = dict(d_model=32, d_ff=64, n_head=2, n_layer=1, vocab=50,
               max_length=16, dropout=0.0)
    assert DecodeEngine(cfg, b_max=2, max_len=16).routed_pairs() is None


def test_engine_startup_skips_only_the_initialisers_of_given_names():
    """``_run_startup`` runs a pruned COPY: the program it was handed
    keeps its ops, an op without outputs stays, and so does the
    initialiser of a name nobody supplies."""
    import types

    from paddle_tpu.core.program import Operator
    from paddle_tpu.serving.engine import _Lane

    start = fluid.Program()
    block = start.global_block()
    for out in ("given_w", "drawn_w"):
        block.ops.append(Operator(block, "fill_constant", None,
                                  {"Out": [out]}, {}))
    block.ops.append(Operator(block, "barrier", None, None, {}))
    block.ops.append(Operator(block, "scale", {"X": ["drawn_w"]},
                              {"Out": ["given_w"]}, {}))
    ran = []
    lane = types.SimpleNamespace(_exe=types.SimpleNamespace(
        run=lambda prog, scope: ran.append(prog)))
    _Lane._run_startup(lane, start, None, {"given_w"}.__contains__)
    (pruned,) = ran
    assert pruned is not start and len(block.ops) == 4
    kept = [(op.type, op.output_names())
            for op in pruned.global_block().ops]
    assert kept == [("fill_constant", ["drawn_w"]), ("barrier", []),
                    ("scale", ["given_w"])]


def test_capacity_mode_drops_what_dropless_keeps():
    """One op, two modes: with every token on one expert a capacity of 1
    keeps one token, dropless keeps all."""
    def run(**kw):
        prog, start = fluid.Program(), fluid.Program()
        scope = fluid.core.scope.Scope()
        with fluid.core.scope.scope_guard(scope):
            with fluid.program_guard(prog, start):
                x = fluid.layers.data("x", [8], dtype="float32")
                out, _ = fluid.layers.moe_ffn(x, n_experts=4, d_hidden=16,
                                              **kw)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(start, scope=scope)
            feed = np.tile(np.linspace(0.1, 0.8, 8, dtype="float32"),
                           (6, 1))
            (got,) = exe.run(prog, feed={"x": feed}, fetch_list=[out],
                             scope=scope)
        return got

    kept = np.abs(run(capacity=1)).sum(axis=1) > 0
    assert kept.sum() == 1
    full = run(dropless=True)
    assert (np.abs(full).sum(axis=1) > 0).all()
    np.testing.assert_allclose(full, np.tile(full[:1], (6, 1)), atol=1e-6)
    with pytest.raises(ValueError):
        run(dropless=True, capacity=3)


GROUPS = {
    "empty_groups": (10, 64, 32, [0, 3, 0, 4, 0, 0, 3, 0]),
    "one_group_holds_all": (300, 64, 64, [0, 300, 0, 0]),
    "sizes_not_multiples_of_the_tile": (300, 256, 128,
                                        [17, 130, 1, 0, 140]),
    "rows_past_the_groups": (40, 64, 32, [5, 5, 5]),
    "reduction_in_two_tiles": (20, 2048, 128, [7, 0, 13], (24, 1024, 128)),
    "reduction_of_2048_whole": (20, 2048, 128, [7, 0, 13]),
    # a width of 21 x 128 cut by its own divisors, under an explicit plan
    "reduction_of_2688_in_three_tiles": (40, 2688, 128,
                                         [0, 9, 0, 17, 5, 0], (40, 896, 128)),
    "columns_of_2688_in_three_tiles": (40, 64, 2688,
                                       [0, 9, 0, 17, 5, 0], (40, 64, 896)),
}


@pytest.mark.parametrize("case", sorted(GROUPS))
@pytest.mark.parametrize("n_rhs", [1, 2])
def test_gmm_kernel_matches_composed_on_ragged_groups(case, n_rhs):
    M, K, N, sizes, *plan = GROUPS[case]
    rng = np.random.default_rng(len(case))
    lhs = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    rhs = tuple(jnp.asarray(rng.standard_normal((len(sizes), K, N))
                            / K ** 0.5, jnp.float32)
                for _ in range(n_rhs))
    gs = jnp.asarray(sizes, jnp.int32)
    got = moe_gmm.gmm_pallas(lhs, rhs, gs, name=moe_gmm.KERNEL_UP,
                             plan=plan[0] if plan else None, interpret=True)
    want = moe_gmm.gmm_composed(lhs, rhs, gs)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    assert not np.asarray(got[sum(sizes):]).any()


# (M, K, N, itemsize) -> plan: both products of every benchmark
# configuration's expert layer, at the decode step and at the longest
# prompt. PR 42: the reduction is held whole wherever its weight block
# fits 8 MiB and VMEM, at 512 columns or else at 256; Nemotron's plans
# (a width of 2688 = 21 x 128 takes its own largest divisor, never 128)
# were whole already and are PR 41's string for string.
PLANS = {
    "olmoe_up": ((256, 2048, 1024, 4), (128, 2048, 512)),
    "olmoe_down": ((256, 1024, 2048, 4), (128, 1024, 512)),
    "olmoe_up_prefill": ((4096, 2048, 1024, 4), (128, 2048, 512)),
    "trinity_up": ((64, 3072, 3072, 4), (64, 3072, 512)),
    "trinity_down_prefill": ((8192, 3072, 3072, 4), (128, 3072, 512)),
    "pangu_up": ((512, 7680, 2048, 2), (128, 7680, 512)),
    "pangu_down": ((512, 2048, 7680, 2), (128, 2048, 512)),
    "pangu_up_prefill": ((26624, 7680, 2048, 2), (128, 7680, 512)),
    "pangu_down_prefill": ((26624, 2048, 7680, 2), (128, 2048, 512)),
    "xing_up": ((128, 3584, 1024, 2), (128, 3584, 512)),
    "xing_down": ((128, 1024, 3584, 2), (128, 1024, 512)),
    "xing_up_prefill": ((32768, 3584, 1024, 2), (128, 3584, 512)),
    "nemotron_up": ((2112, 1024, 2688, 2), (128, 1024, 896)),
    "nemotron_down": ((2112, 2688, 1024, 2), (128, 2688, 512)),
    "nemotron_up_prefill": ((45056, 1024, 2688, 2), (128, 1024, 896)),
    "nemotron_down_prefill": ((45056, 2688, 1024, 2), (128, 2688, 512)),
    # float32 experts of that width: 5.25 MiB a block, inside the cap
    "width_2688_float32_down": ((2112, 2688, 1024, 4), (128, 2688, 512)),
    "both_axes_2688": ((2112, 2688, 2688, 2), (128, 2688, 896)),
    # the cap and the VMEM reckoning: a block of exactly 8 MiB is held
    # whole (float32 needs 256 columns for it); past the cap at 512 and
    # at 256 columns the reduction falls to its largest divisor that is
    # a multiple of 128 and fits (8320 = 65 x 128: 13 x 128); a bf16
    # block of 8 MiB at 256 columns whose float32 rows overrun VMEM
    # falls to half the reduction
    "whole_just_inside_the_cap": ((512, 8192, 1024, 2), (128, 8192, 512)),
    "whole_float32_at_256_columns": ((512, 8192, 1024, 4),
                                     (128, 8192, 256)),
    "just_outside_falls_to_the_largest_cut": ((512, 8320, 1024, 4),
                                              (128, 1664, 512)),
    "whole_overruns_vmem": ((512, 16384, 1024, 2), (128, 8192, 512)),
    "five_lanes_taken_whole": ((64, 640, 256, 4), (64, 640, 256)),
    "no_lane_multiple_taken_whole": ((10, 64, 32, 4), (16, 64, 32)),
    "block_too_large": ((10, 3000, 4000, 4), None),
}


@pytest.mark.parametrize("case", sorted(PLANS))
def test_gmm_plan(case):
    args, want = PLANS[case]
    assert moe_gmm.gmm_plan(*args) == want
    if want is not None:
        # 128 is the last resort of an axis no power of two divides
        assert 128 not in [t for t, axis in zip(want[1:], args[1:3])
                           if axis == 2688]


def test_gmm_sweep_rehearses_on_the_cpu(tmp_path):
    """``tools/gmm_sweep.py`` (the tool behind docs/KERNELS.md's table)
    runs its cases at a tiny size in interpret mode and writes no time."""
    import json
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    try:
        import gmm_sweep
    finally:
        sys.path.pop(0)
    out = tmp_path / "sweep.json"
    assert gmm_sweep.main(["--rehearse", "--only", "nemotron_down_decode",
                           "--out", str(out)]) == 0
    rows = json.loads(out.read_text())["rows"]
    assert [r["plan"] for r in rows] == ["128x128x512", "128x384x512"]
    assert all("device_ms" not in r and "refused" not in r
               and r["max_abs_diff_vs_first"] < 1e-5 for r in rows)


def test_gmm_plan_and_counter():
    from paddle_tpu.observe.families import MOE_GMM_PLANS

    child = MOE_GMM_PLANS.labels(kernel=moe_gmm.KERNEL_DOWN, tile="-",
                                 form="composed")
    before = child.value
    moe_gmm.gmm(jnp.ones((4, 8)), jnp.ones((2, 8, 8)),
                jnp.asarray([1, 3], jnp.int32), name=moe_gmm.KERNEL_DOWN)
    assert child.value == before + 1   # the CPU holds the composed form


def test_expert_layer_gradients_match_reference():
    from paddle_tpu.ops.moe_ops import _experts

    rng = np.random.default_rng(17)
    T, D, F, E, k = 9, 16, 12, 6, 3
    args = [jnp.asarray(rng.standard_normal(s) * sc, jnp.float32)
            for s, sc in (((T, D), 1.0), ((D, E), 0.5), ((E, D, F), 0.3),
                          ((E, D, F), 0.3), ((E, F, D), 0.3))]

    def ours(x, r, g, u, d):
        out = _experts(x, g, u, None, d, None, r, E, k, None, "swiglu",
                       False, 0.0)[0]
        return jnp.sum(out * jnp.cos(out))

    def ref(x, r, g, u, d):
        with jax.default_matmul_precision("highest"):
            out, _gap = reference.experts(x, r, g, u, d, k, False)
        return jnp.sum(out * jnp.cos(out))

    got = jax.grad(ours, argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(ref, argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-3, rtol=0)


def test_training_build_has_gradients_for_every_expert_parameter():
    cfg = tiny_cfg(2, n_layer=1)
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        loss, _ = gpt.build(cfg, seq_len=8, use_fused_attention=False)
        fluid.optimizer.SGD(0.1).minimize(loss)
    grads = {n for n in prog.global_block().vars if n.endswith("@GRAD")}
    for part in ("gate", "up", "down", "router"):
        assert "gpt_0_moe_%s.w_0@GRAD" % part in grads
    scope = fluid.core.scope.Scope()
    with fluid.core.scope.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(start, scope=scope)
        before = np.asarray(scope.find_var("gpt_0_moe_gate.w_0")).copy()
        ids = np.random.default_rng(0).integers(1, 97, (2, 8))
        (l0,) = exe.run(prog, feed={"ids": ids.astype("int64")},
                        fetch_list=[loss], scope=scope)
        after = np.asarray(scope.find_var("gpt_0_moe_gate.w_0"))
    assert np.isfinite(l0).all() and not np.array_equal(before, after)


def test_the_two_reference_copies_agree_to_the_last_bit():
    bench = _load(os.path.join(ROOT, "benchmarks", "references",
                               "olmoe-1b-7b.py"))
    cfg = tiny_cfg(2)
    params = seeded_params(cfg, 23)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 97, 10))
    a = np.asarray(reference.forward(params, cfg, ids))
    b = np.asarray(bench.forward(params, cfg, ids))
    assert np.array_equal(a, b)


def test_round_mantissa_at_7_bits_is_bfloat16():
    x = jnp.asarray(np.random.default_rng(5).standard_normal(4096) * 3.0,
                    jnp.float32)
    got = np.asarray(reference.round_mantissa(x, 7))
    want = np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32))
    # the two differ only in how an exact tie rounds (away from zero
    # here, to even in the cast): never by more than one bfloat16 step
    assert np.mean(got == want) > 0.99
    np.testing.assert_allclose(got, want, rtol=2.0 ** -7, atol=0)
    np.testing.assert_allclose(got, np.asarray(x), rtol=2.0 ** -8, atol=0)


def test_reference_router_gaps_and_bfloat16_control():
    cfg = tiny_cfg(2)
    params = seeded_params(cfg, 23)
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 97, 24))
    logits, gaps = reference.forward(params, cfg, ids, with_gaps=True)
    np.testing.assert_array_equal(
        np.asarray(logits), np.asarray(reference.forward(params, cfg, ids)))
    gaps = np.asarray(gaps)
    assert gaps.shape == (24,) and (gaps >= 0).all() and gaps.max() < 5
    # every expert chosen: nothing to tie with
    _, all_in = reference.forward(params, tiny_cfg(8), ids, with_gaps=True)
    assert np.isinf(np.asarray(all_in)).all()
    # the control: bfloat16 weights alone move the logits, activations
    # and cache rounded as well move them further
    exact = np.asarray(logits)
    w_only = np.abs(np.asarray(
        reference.forward(params, cfg, ids, 7)) - exact).mean()
    w_act = np.abs(np.asarray(
        reference.forward(params, cfg, ids, 7, 7)) - exact).mean()
    assert 0 < w_only < w_act < 0.1


def test_greedy_margins_do_not_depend_on_the_padding():
    cfg = tiny_cfg(2)
    params = seeded_params(cfg, 29)
    rng = np.random.default_rng(4)
    tokens = rng.integers(0, 97, 13)
    exact = _ref_logits(params, cfg, tokens)
    tokens[5:] = exact[4:12].argmax(-1)      # greedy from position 5 on ...
    tokens[9] = (tokens[9] + 1) % 97         # ... but for one token
    exact = _ref_logits(params, cfg, tokens)
    want = exact[4:12].max(-1) - exact[4:12][np.arange(8), tokens[5:]]
    for multiple in (8, 16, 13):
        (got, control), gaps = reference.greedy_margin_fn(
            params, cfg, multiple, controls=((7, 7),))(tokens, 5)
        np.testing.assert_allclose(got, want, atol=1e-5)
        assert gaps.shape == control.shape == (8,) and (control >= 0).all()
    assert (want > 0).sum() >= 1 and want[4] > 0


@pytest.mark.parametrize("bad", [dict(n_expert=8, expert_top_k=9),
                                 dict(n_expert=8, d_expert=None),
                                 dict(n_experts=8)])
def test_check_cfg_rejects_bad_expert_keys(bad):
    cfg = tiny_cfg(2)
    cfg.update(bad)
    with pytest.raises(ValueError):
        gpt._check_cfg({k: v for k, v in cfg.items() if v is not None})


@pytest.mark.parametrize("which", ["prefill", "decode", "train"])
def test_analysis_engines_know_the_expert_op(which):
    """Shape inference, the lint, the range engine, the cost model and
    the memory model walk every program of the model without an
    unknown-op hole at moe_ffn."""
    from paddle_tpu.analysis.cost import CostAnalysis
    from paddle_tpu.analysis.infer import verify_program
    from paddle_tpu.analysis.lint import lint_program
    from paddle_tpu.analysis.memory import MemoryAnalysis
    from paddle_tpu.analysis.ranges import RangeAnalysis

    cfg = tiny_cfg(2)
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        if which == "prefill":
            out, _ = gpt.build_prefill_step(cfg, batch=1, prompt_len=8,
                                            max_len=16)
        elif which == "decode":
            out, _ = gpt.build_serving_decode_step(cfg, batch=4,
                                                   max_len=16)
        else:
            out, _ = gpt.build(cfg, seq_len=8, use_fused_attention=False)
    assert sum(op.type == "moe_ffn"
               for op in prog.global_block().ops) == cfg["n_layer"]
    verify_program(prog, fetch_list=[out])
    bad = [f for f in lint_program(prog, fetch_names=[out.name])
           if f.severity == "error"
           or (f.severity == "warning" and "moe_ffn" in f.message)]
    assert not bad, bad
    ra = RangeAnalysis(prog)
    assert "moe_ffn" not in ra.widened
    ca = CostAnalysis(prog)
    assert "moe_ffn" not in ca.unruled
    moe = [c for c in ca.op_costs if c.op_type == "moe_ffn"]
    # the batch dim is symbolic: a polynomial of B, read at the batch
    batch, tokens = {"prefill": (1, 8), "decode": (4, 4),
                     "train": (1, 8)}[which]
    D, F, E, k = 64, 32, 8, 2
    assert moe[0].flops.at(batch) \
        == tokens * (k * 6 * D * F + 2 * D * E)
    ma = MemoryAnalysis(prog)
    assert ma.peak_bytes(1) > 3 * E * D * F * 4 * cfg["n_layer"]


def test_shape_rule_rejects_mismatched_expert_weights():
    from paddle_tpu.analysis.infer import ProgramVerifyError, verify_program

    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        x = fluid.layers.data("x", [8], dtype="float32")
        out, _ = fluid.layers.moe_ffn(x, n_experts=4, d_hidden=16,
                                      act="swiglu", dropless=True)
    (op,) = [o for o in prog.global_block().ops if o.type == "moe_ffn"]
    op.attrs["n_experts"] = 5
    with pytest.raises(ProgramVerifyError):
        verify_program(prog, fetch_list=[out])
