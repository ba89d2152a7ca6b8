"""NVIDIA-Nemotron-3-Super's three layer kinds (``model_type``
nemotron_h) through the system's normal path, against the benchmark's
own plain reference (benchmarks/references/
nemotron-3-super-120b-a12b.py, imported, not copied): state-space layers
whose slot is a constant-size state (ops ``ssm_scan`` / ``ssm_update``,
``causal_conv`` / ``causal_conv_step``, kernels/ssm.py), layers that are
one mixer alone, attention without positions, and un-gated relu² experts
that work in a latent of the token."""

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
                               "nemotron-3-super-120b-a12b.py"))


def tiny_cfg(**over):
    """``MEM*E``: two state-space layers of 4 heads of 8 in 2 groups of
    state 16 under 4 taps (chunks of 8, so a prompt spans several),
    attention of 4 query and 2 key-value heads of 16 without positions,
    two expert layers of 16 relu² experts of width 24 in a latent of 32,
    top-4 by sigmoid score with a selection bias, a shared expert of
    40."""
    cfg = dict(d_model=64, n_head=4, n_kv_head=2, d_head=16, n_layer=5,
               vocab=97, max_length=256, dropout=0.0, pos_emb="none",
               norm="rms", norm_eps=1e-5,
               mixers=["ssm", "experts", "ssm", "attention", "experts"],
               ssm_heads=4, ssm_head_dim=8, ssm_groups=2, ssm_state=16,
               ssm_conv=4, ssm_chunk=8, ffn_act="relu2", n_expert=16,
               expert_top_k=4, d_expert=24, d_expert_in=32,
               d_shared_expert=40, router_score="sigmoid",
               router_bias=True, norm_topk=True, route_scale=5.0,
               n_expert_local=16, expert_first=0)
    cfg.update(over)
    return cfg


def seeded_params(cfg, seed):
    """Every parameter drawn from the seed, float32: matrices within
    Xavier limits, the convolution within 0.5, ``dt_b`` so that ``dt``
    falls in 0.01-0.5, ``a_log`` in log 1-8, the selection bias within
    0.01, the other vectors in 0.5-1.5."""
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
        elif "_ssm_conv." in p.name:
            v = rng.uniform(-0.5, 0.5, shape)
        elif p.name.endswith("_ssm_dt_b"):
            dt = np.exp(rng.uniform(np.log(0.01), np.log(0.5), shape))
            v = dt + np.log(-np.expm1(-dt))
        elif p.name.endswith("_ssm_a_log"):
            v = np.log(rng.uniform(1.0, 8.0, shape))
        elif len(shape) == 1:
            v = rng.uniform(0.5, 1.5, shape)
        else:
            lim = (6.0 / (shape[-2] + shape[-1])) ** 0.5
            v = rng.uniform(-lim, lim, shape)
        out[p.name] = v.astype("float32")
    return out


def _ref_logits(params, cfg, ids, **kw):
    return np.asarray(reference.forward(params, cfg, jnp.asarray(ids), **kw))


# ----------------------------------------------------------------- the ops
def _operands(seed, B, T, H, P, G, N):
    rs = np.random.RandomState(seed)

    def f(*s):
        return jnp.asarray(rs.randn(*s), jnp.float32)

    return (f(B, T, H * P), jnp.abs(f(B, T, H)) * 0.3,
            -jnp.abs(f(H)) - 0.1, f(B, T, G, N), f(B, T, G, N))


def _to_layout(S, G):
    """The reference's ``[H, P, N]`` state as the program keeps it."""
    H, P, N = S.shape
    return np.transpose(np.asarray(S).reshape(G, H // G, P, N),
                        (0, 3, 1, 2)).reshape(G, N, H // G * P)


def _ref_scan(x, dt, a, bm, cm):
    """The reference's token-by-token recurrence a batch row."""
    B, T = x.shape[:2]
    H = dt.shape[-1]
    ys, Ss = [], []
    with jax.default_matmul_precision("highest"):
        for b in range(B):
            y, S = reference.recurrence(
                x[b].reshape(T, H, -1), dt[b], jnp.log(-a), bm[b], cm[b],
                jnp.zeros((H,), jnp.float32))
            ys.append(np.asarray(y).reshape(T, -1))
            Ss.append(_to_layout(S, bm.shape[2]))
    return np.stack(ys), np.stack(Ss)


@pytest.mark.parametrize("T,chunk", [(32, 8), (37, 8), (5, 8), (64, 16),
                                     (100, 128), (128, 128)])
def test_scan_matches_the_token_by_token_reference(T, chunk):
    """Lengths that are and are not multiples of the chunk, shorter than
    one chunk and exactly one. 1e-4 on values up to ~30: the same
    float32 products summed chunk-wise and not token by token."""
    ops = _operands(T, 2, T, 4, 8, 2, 16)
    y, S = ssm.ssm_scan_composed(*ops, chunk=chunk)
    want_y, want_S = _ref_scan(*ops)
    np.testing.assert_allclose(y, want_y, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(S, want_S, atol=1e-4, rtol=1e-5)


@pytest.mark.parametrize("P,n", [(24, 9), (7, 3), (16, 1)])
def test_prefill_then_updates_equal_the_scan(P, n):
    """A scan over ``P`` then ``n`` one-token updates is the scan over
    ``P + n``."""
    x, dt, a, bm, cm = _operands(P, 2, P + n, 4, 8, 2, 16)
    want_y, want_S = ssm.ssm_scan_composed(x, dt, a, bm, cm, chunk=8)
    y, S = ssm.ssm_scan_composed(x[:, :P], dt[:, :P], a, bm[:, :P],
                                 cm[:, :P], chunk=8)
    ys = [y]
    for t in range(P, P + n):
        yt, S = ssm.ssm_update_composed(S, x[:, t], dt[:, t], a, bm[:, t],
                                        cm[:, t])
        ys.append(yt[:, None])
    np.testing.assert_allclose(jnp.concatenate(ys, 1), want_y, atol=1e-4)
    np.testing.assert_allclose(S, want_S, atol=1e-4)


@pytest.mark.parametrize("T", [1, 2, 3, 9])
def test_convolution_carries_its_rows(T):
    """The prompt's convolution equals the reference's; the rows it
    leaves are the last K - 1 positions (zeros in front of a shorter
    prompt); steps from them continue the whole sequence's convolution
    bit for bit."""
    rs = np.random.RandomState(T)
    C, K, n = 12, 4, 5
    x = jnp.asarray(rs.randn(2, T + n, C), jnp.float32)
    w = jnp.asarray(rs.randn(C, K) * 0.5, jnp.float32)
    b = jnp.asarray(rs.randn(C) * 0.1, jnp.float32)
    whole, _ = ssm.conv_prefill(x, w, b)
    for row in range(2):
        np.testing.assert_allclose(whole[row], reference.conv(x[row], w, b),
                                   atol=1e-6)
    out, rows = ssm.conv_prefill(x[:, :T], w, b)
    want = np.zeros((2, K - 1, C), np.float32)
    keep = min(T, K - 1)
    want[:, K - 1 - keep:] = np.asarray(x[:, T - keep:T])
    np.testing.assert_array_equal(rows, want)
    outs = [out]
    for t in range(T, T + n):
        o, rows = ssm.conv_step(x[:, t:t + 1], rows, w, b)
        outs.append(o)
    np.testing.assert_array_equal(jnp.concatenate(outs, 1), whole)


# what each case of the convolution's kernel test changes of: batch 2, 70
# positions in blocks of 32 (two whole blocks and a ragged third), 256
# channels in tiles of 128, four taps, a bias and the silu
_CONV_KERNEL_CASES = {
    "one_block": dict(T=32),
    "several_blocks": dict(T=96),
    "ragged_last_block": dict(),
    "seam_from_the_previous_block": dict(seam=True, act=False, bias=False),
    "three_taps": dict(K=3),
    "no_silu": dict(act=False),
    "no_bias": dict(bias=False),
    "one_tile_of_all_channels": dict(tile=256),
    "columns_of_a_wider_x": dict(wide=640, lo=128),
    "columns_without_bias_or_silu": dict(wide=512, lo=256, K=3, act=False,
                                         bias=False),
}


@pytest.mark.parametrize("case", sorted(_CONV_KERNEL_CASES))
def test_convolution_kernel_is_the_composed_form_bit_for_bit(case):
    """Interpret mode against the composed form under ``jit`` (what a
    program runs: XLA's CPU backend contracts a fused multiply and add,
    op-by-op dispatch does not), ``assert_array_equal``: the taps are
    summed in ``conv_prefill_composed``'s order, which is ``conv_step``'s,
    so steps from the kernel's rows continue the kernel's own convolution
    of the whole sequence bit for bit."""
    kw = dict(T=70, K=4, act=True, bias=True, tile=128, wide=256, lo=0,
              seam=False)
    kw.update(_CONV_KERNEL_CASES[case])
    T, K, Q, C, n = kw["T"], kw["K"], 32, 256, 5
    lo, act = kw["lo"], kw["act"]
    rs = np.random.RandomState(sum(map(ord, case)))
    x = rs.randn(2, T + n, kw["wide"]).astype(np.float32)
    if kw["seam"]:
        # one value, in the last row of the first block: the next K - 1
        # rows can have it from nowhere but the carried seam
        x[:] = 0.0
        x[:, Q - 1] = rs.randn(2, kw["wide"]) + 3.0
    x = jnp.asarray(x)
    w = jnp.asarray(rs.randn(C, K) * 0.5, jnp.float32)
    b = jnp.asarray(rs.randn(C) * 0.1, jnp.float32) if kw["bias"] else None
    columns = None if kw["wide"] == C else (lo, lo + C)

    def kernel(x):
        return ssm.conv_prefill_pallas(x, w, b, act=act, columns=columns,
                                       plan=(Q, kw["tile"]), interpret=True)

    composed = jax.jit(lambda x: ssm.conv_prefill_composed(
        x, w, b, act=act, columns=columns))
    step = jax.jit(lambda x, rows: ssm.conv_step(x, rows, w, b, act=act))
    out, rows = kernel(x[:, :T])
    want, want_rows = composed(x[:, :T])
    np.testing.assert_array_equal(out, want)
    np.testing.assert_array_equal(rows, want_rows)
    np.testing.assert_array_equal(rows, x[:, T - (K - 1):T, lo:lo + C])
    if kw["seam"]:
        assert np.abs(np.asarray(out[:, Q:Q + K - 1])).min() > 0
        assert not np.asarray(out[:, Q + K - 1:]).any()
    whole, _ = kernel(x)
    outs = [out]
    for t in range(T, T + n):
        o, rows = step(x[:, t:t + 1, lo:lo + C], rows)
        outs.append(o)
    np.testing.assert_array_equal(jnp.concatenate(outs, 1), whole)


@pytest.mark.parametrize("T", [128, 300])
def test_scan_kernel_matches_composed(T):
    """Interpret mode, at the smallest shapes the kernel has a plan for
    (state 128, chunk 128): a whole chunk and a ragged last one."""
    ops = _operands(T, 2, T, 4, 64, 2, 128)
    y, S = ssm.ssm_scan_pallas(*ops, chunk=128, interpret=True)
    want_y, want_S = ssm.ssm_scan_composed(*ops, chunk=128)
    scale = float(jnp.abs(want_y).max())
    np.testing.assert_allclose(y, want_y, atol=2e-6 * scale)
    np.testing.assert_allclose(S, want_S, atol=2e-5)


def test_update_kernel_matches_composed():
    x, dt, a, bm, cm = _operands(3, 3, 1, 4, 64, 2, 128)
    state = jnp.asarray(np.random.RandomState(4).randn(3, 2, 128, 128),
                        jnp.float32)
    args = (state, x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0])
    want_y, want_S = ssm.ssm_update_composed(*args)
    y, S = ssm.ssm_update_pallas(*args, interpret=True)
    np.testing.assert_allclose(y, want_y, atol=1e-4)
    np.testing.assert_allclose(S, want_S, atol=1e-6)


def test_dispatch_counts_the_form_and_chunk_it_took():
    from paddle_tpu.observe import REGISTRY

    def count():
        got = REGISTRY.snapshot()["metrics"].get(
            "paddle_ssm_plans_total", {"samples": []})
        return {(s["labels"]["op"], s["labels"]["kernel"],
                 s["labels"]["chunk"]): s["value"] for s in got["samples"]}

    before = count()
    x, dt, a, bm, cm = _operands(0, 1, 16, 4, 8, 2, 16)
    _, S = ssm.ssm_scan(x, dt, a, bm, cm, chunk=8)
    ssm.ssm_update(S, x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0])
    after = count()
    assert after[("scan", "composed", "8")] \
        == before.get(("scan", "composed", "8"), 0) + 1
    assert after[("update", "composed", "1")] \
        == before.get(("update", "composed", "1"), 0) + 1


# --------------------------------------------------------------- the cfg
@pytest.mark.parametrize("over,needle", [
    (dict(mixers=["ssm", "experts"]), "for each of the 5 layers"),
    (dict(mixers=["ssm", "mlp", "ssm", "attention", "experts"]),
     "must name one of"),
    (dict(ssm_heads=None), "an 'ssm' layer needs cfg['ssm_heads']"),
    (dict(ssm_groups=3), "must divide"),
    (dict(ssm_conv=1), ">= 2 taps"),
    (dict(attn="mla"), "takes no cfg['attn']"),
    (dict(residual="mhc"), "takes no cfg['residual']"),
    (dict(n_shared_expert=1), "takes no cfg['n_shared_expert']"),
    (dict(mixers=["attention"] * 5, ssm_heads=None, ssm_head_dim=None,
          ssm_groups=None, ssm_state=None, ssm_conv=None, ssm_chunk=None),
     "cfg['pos_emb']='none' needs an 'ssm' layer"),
    (dict(mixers=None), "needs an 'ssm' layer in cfg['mixers']"),
    (dict(n_expert=None), "needs cfg['n_expert']"),
    (dict(pos_emb="sinusoid"), "cfg['pos_emb'] must be one of"),
])
def test_check_cfg_says_which_key_needs_which(over, needle):
    cfg = {k: v for k, v in tiny_cfg(**over).items() if v is not None}
    with pytest.raises(ValueError) as err:
        gpt._check_cfg(cfg)
    assert needle in str(err.value)


def test_the_refusals_name_the_state():
    from paddle_tpu.serving import DecodeEngine, PrefixStore

    cfg = tiny_cfg()
    with pytest.raises(ValueError, match="cfg\\['mixers'\\]"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            gpt.build(cfg, seq_len=8)
    with pytest.raises(ValueError, match="recurrent state"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            gpt.build_multi_token_decode_step(cfg, batch=1, steps=2,
                                              max_len=16)
    for kw in (dict(prefix_store=PrefixStore(1 << 20)),
               dict(prefix_cache_bytes=1 << 20),
               dict(draft_cfg=cfg, spec_k=2)):
        with pytest.raises(ValueError, match="recurrent state"):
            DecodeEngine(cfg, b_max=2, max_len=32, **kw)
    dense = dict(d_model=32, d_ff=64, n_head=2, n_layer=1, vocab=50,
                 max_length=32, dropout=0.0)
    with pytest.raises(ValueError, match="recurrent state"):
        DecodeEngine(dense, b_max=2, max_len=32, draft_cfg=cfg, spec_k=2)


def test_cache_kinds_are_told_by_name_and_layer():
    cfg = tiny_cfg()
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        _, names = gpt.build_serving_decode_step(cfg, batch=2, max_len=32)
    assert names == ["gpt_0_cache_x", "gpt_0_cache_s", "gpt_2_cache_x",
                     "gpt_2_cache_s", "gpt_3_cache_k", "gpt_3_cache_v"]
    kinds = [gpt.cache_kind(cfg, n, 32) for n in names]
    assert kinds == ["state"] * 4 + ["full"] * 2
    sliding = dict(d_model=32, d_ff=64, n_head=2, n_layer=2, vocab=50,
                   max_length=64, dropout=0.0, pos_emb="rope",
                   layer_types=["sliding", "full"], window=8)
    assert gpt.cache_kind(sliding, "gpt_0_cache_k", 32) == "ring"
    assert gpt.cache_kind(sliding, "gpt_1_cache_v", 32) == "full"
    assert gpt.cache_kind(sliding, "gpt_0_cache_k", 8) == "full"
    assert gpt.cache_kind(dict(attn="mla"), "gpt_0_cache_c", 8) == "latent"


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


@pytest.mark.parametrize("P", [21, 8])
def test_prefill_matches_the_reference(P):
    """The whole forward of the program (a scan in chunks of 8 over a
    prompt that is and is not a multiple) against the reference's token
    by token: 2e-4 on logits of magnitude ~1."""
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


def test_prefill_then_decode_matches_the_full_forward():
    """Prefill 13 then 11 decode steps through the state, the rows and
    the slab: every step's logits are the reference's full forward at
    that position."""
    cfg = tiny_cfg()
    params = seeded_params(cfg, 2)
    P, n = 13, 11
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


def test_bf16_stored_matrices_give_the_float32_programs_tokens():
    """cfg['weight_dtype']: the matrices stored in bfloat16 and widened
    where they multiply answer as the float32 program over the same
    (bfloat16-valued) numbers."""
    cfg = tiny_cfg()
    params = seeded_params(cfg, 5)
    rounded = {n: (np.asarray(jnp.asarray(v, jnp.bfloat16)
                              .astype(jnp.float32)) if v.ndim >= 2 else v)
               for n, v in params.items()}
    stored = {n: (jnp.asarray(v, jnp.bfloat16) if v.ndim >= 2 else v)
              for n, v in params.items()}
    ids = np.random.default_rng(6).integers(0, 97, (1, 12))
    exe = fluid.Executor(fluid.CPUPlace())
    outs = []
    for c, p in ((cfg, rounded), (dict(cfg, weight_dtype="bfloat16"),
                                  stored)):
        (prog, start, logits), _ = _programs(c, 12, 32)
        scope = _scope_with(exe, [start], p)
        (got,) = exe.run(prog, feed={"tokens": ids}, fetch_list=[logits],
                         scope=scope)
        outs.append(got)
    np.testing.assert_allclose(outs[1], outs[0], atol=1e-5)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Over the four shares of a tiny latent-expert layer, the routed
    parts (each through W_up) plus the shared expert counted ONCE add up
    to the uncut reference's layer; the program's share equals the
    reference's share."""
    cfg = tiny_cfg(mixers=["ssm", "experts"], n_layer=2)
    params = seeded_params(cfg, 7)
    rng = np.random.default_rng(8)
    u = jnp.asarray(rng.normal(size=(9, 64)), jnp.float32)
    p = {k[len("gpt_1_"):]: jnp.asarray(v) for k, v in params.items()
         if k.startswith("gpt_1_")}
    wide = lambda t: jnp.asarray(t, jnp.float32)   # noqa: E731
    with jax.default_matmul_precision("highest"):
        whole, _ = reference.experts(u, p, cfg, wide)
        shared = reference.relu2(u, p["moe_shared_up.w_0"],
                                 p["moe_shared_down.w_0"])
        parts = []
        for first in range(0, 16, 4):
            share = dict(p, **{"moe_up.w_0": p["moe_up.w_0"][first:first + 4],
                               "moe_down.w_0":
                               p["moe_down.w_0"][first:first + 4]})
            out, _ = reference.experts(
                u, share, dict(cfg, n_expert_local=4, expert_first=first),
                wide)
            parts.append(out - shared)
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-5)
    # and the program's own share of the same layer
    scfg = dict(cfg, n_expert_local=4, expert_first=8)
    sparams = dict(params)
    for part in ("up", "down"):
        sparams["gpt_1_moe_%s.w_0" % part] = \
            params["gpt_1_moe_%s.w_0" % part][8:12]
    ids = rng.integers(0, 97, (1, 10))
    (prog, start, logits), _ = _programs(scfg, 10, 32)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = _scope_with(exe, [start], sparams)
    (got,) = exe.run(prog, feed={"tokens": ids}, fetch_list=[logits],
                     scope=scope)
    np.testing.assert_allclose(got[0], _ref_logits(sparams, scfg, ids[0]),
                               atol=2e-4)


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


def _generate(cfg, params, prompt, n_new, max_len=48):
    (prog, start, logits), (dprog, dstart, dlogits) = _programs(
        cfg, len(prompt), max_len)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = _scope_with(exe, [start, dstart], params)
    return gpt.generate(exe, dprog, dlogits, np.asarray(prompt)[None],
                        n_new, scope, prefill_prog=prog,
                        prefill_logits=logits)[0]


def test_engine_tokens_equal_generates_and_are_row_local(served):
    """Requests of different lengths in company through the engine's
    slots answer as ``generate`` answers each alone, and as the float32
    reference chooses."""
    cfg, params, engine = served
    rng = np.random.default_rng(12)
    asks = [(rng.integers(0, 97, size=P), n)
            for P, n in ((9, 12), (17, 7), (5, 20), (24, 5), (11, 9))]
    handles = [engine.submit(p, n) for p, n in asks]
    outs = [h.result(timeout=300) for h in handles]
    for (prompt, n), out in zip(asks, outs):
        np.testing.assert_array_equal(out, _generate(cfg, params, prompt, n))
    prompt, n = asks[0]
    logits = _ref_logits(params, cfg, outs[0][:-1])
    picked = logits[len(prompt) - 1:].argmax(-1)
    assert (picked == outs[0][len(prompt):]).mean() >= 0.9


def test_a_reused_slot_shows_nothing_of_its_previous_tenant(served):
    """b_max long requests fill every slot's state, rows and slab; a
    shorter request then takes a slot one of them left: its answer is
    what it is alone in a fresh engine."""
    cfg, params, engine = served
    rng = np.random.default_rng(13)
    long_ = [engine.submit(rng.integers(0, 97, size=30), 16)
             for _ in range(3)]
    for h in long_:
        h.result(timeout=300)
    short = rng.integers(0, 97, size=4)
    got = engine.submit(short, 10).result(timeout=300)
    np.testing.assert_array_equal(got, _generate(cfg, params, short, 10))


def test_engine_counts_the_state_and_spans_the_chunks(served):
    from paddle_tpu.observe import REGISTRY
    from paddle_tpu.observe import trace as flight

    cfg, params, engine = served
    got = REGISTRY.snapshot()["metrics"]["paddle_serving_cache_bytes"]
    held = {s["labels"]["kind"]: s["value"] for s in got["samples"]}
    H, P, G, N, K, d_in, d_conv = gpt.ssm_widths(cfg)
    assert held["state"] == 2 * 3 * 4 * (G * N * (H // G) * P
                                         + (K - 1) * d_conv)
    assert held["full"] == 2 * 3 * 2 * 48 * 16 * 4
    assert held["ring"] == held["latent"] == 0
    engine.submit(np.arange(1, 20), 2).result(timeout=300)
    spans = [e for e in flight.recorder().events()
             if e["site"] == "serving.engine.prefill"
             and (e.get("attrs") or {}).get("prompt_len") == 19]
    assert spans and spans[-1]["attrs"]["chunks"] == 3
    assert engine.routed_pairs().shape == (5, 16)
    assert engine.experts_touched().shape == (5, 16)
    foot = engine._lane.memory_footprint()
    assert foot["resident"] > held["state"] and foot["prefill_extra_hi"] > 0
