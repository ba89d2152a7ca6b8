"""Decoder-only causal language model (GPT-style).

Beyond-reference model family: the reference era (Fluid v1.3) predates
decoder-only LMs, but the long-context story this framework is built
around (causal flash attention with above-diagonal block skipping, ring
attention under an sp mesh, recompute boundaries) is exactly a
decoder-only workload — this model is its showcase. Built from the same
fluid-style layer calls as models/transformer.py (whose provenance is
/root/reference/python/paddle/fluid/tests/unittests/dist_transformer.py).

Feeds: ids [B, S] int64 tokens; the loss is next-token cross entropy
with the final position dropped (labels are ids shifted left), pad id 0
masked out of the loss.
"""

import functools
import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

from .. import layers
from ..core.program import name_scope
from ..layer_helper import stored_dtype
from ..observe.families import (DELTA_CHUNKS, DELTA_STATE_BYTES,
                                MAMBA_CHUNKS, MAMBA_STATE_BYTES,
                                POWER_CHUNKS, POWER_STATE_BYTES)
from ..param_attr import ParamAttr
from .transformer import (_causal_bias, _ffn, _pad_bias, _prenorm,
                          multi_head_attention, qk_norm)

__all__ = ["base_config", "build"]


def base_config():
    """Optional modern-decoder knobs (all compose, train AND decode):
    ``n_kv_head`` (< n_head, dividing it) — grouped-query attention:
    smaller k/v projections and an H/Hkv-times smaller KV cache;
    ``pos_emb='rope'`` — rotary positions instead of the learned
    table; ``norm='rms'`` — RMSNorm (scale-only, f32 rsqrt);
    ``ffn_act='swiglu'`` — the gated FFN; ``tie_embeddings=True`` — one table serves lookup and LM head.

    Sparse experts in place of the dense FFN (``layers.moe_ffn``,
    dropless, SwiGLU experts without biases): ``n_expert`` experts of
    width ``d_expert``, ``expert_top_k`` a token. ``norm_topk`` says
    whether the k router probabilities of a token are renormalised to
    sum to one: OLMoE does not renormalise the eight (``False``, the
    default here); most later models do. ``qk_norm=True`` — RMSNorm on
    the projected q and k before the head split; ``norm_eps`` — the
    RMSNorm epsilon (default 1e-6); ``rope_theta`` — the RoPE base
    (default 10000). OLMoE-1B-7B, as a worked example (published widths,
    all 16 layers; ``d_ff`` is not read when ``n_expert`` is set)::

        dict(d_model=2048, n_head=16, n_layer=16, vocab=50304,
             max_length=4096, dropout=0.0, pos_emb="rope", norm="rms",
             norm_eps=1e-5, rope_theta=10000.0, qk_norm=True,
             n_expert=64, expert_top_k=8, d_expert=1024,
             norm_topk=False)

    Expert weights are stacked parameters ``gpt_<i>_moe_{gate,up,down}
    .w_0`` ([E, D, F], [E, D, F], [E, F, D]) and ``gpt_<i>_moe_router
    .w_0`` ([D, E]), the same names in every build.

    Two kinds of attention layer in one model, and what came with them
    (every key optional; a cfg without them builds what it built):

    * ``d_head`` — a head size of its own (``n_head * d_head`` need not
      be ``d_model``); ``layer_types`` — one of ``"sliding"``/``"full"``
      a layer, with ``window``: in a sliding layer key j is visible to
      query i iff ``0 <= i - j < window``, and its decode cache is a
      RING of ``min(window, max_len)`` rows (position p lives in row
      ``p mod window``) where a full layer keeps a slab of ``max_len``;
      ``rope_layers`` — ``"all"`` (default) or ``"sliding"``: which
      layers rotate q and k under ``pos_emb='rope'`` (the others carry
      no position at all);
    * ``qk_norm="head"`` — RMSNorm of q and k per head over ``d_head``,
      one ``[d_head]`` scale shared by the heads (``True`` stays the
      whole-vector form); ``attn_gate`` — ``ctx * sigmoid(h Wg)`` before
      the output projection (``gpt_<i>_att_g.w_0``); ``sandwich_norm``
      — a second norm on each sub-block's OUTPUT before the residual
      add (``gpt_<i>_post{1,2}_ln_s``); ``emb_scale`` — a number the
      embedding row is multiplied by;
    * ``n_dense_layer`` — that many leading layers keep the dense FFN
      of width ``d_ff`` (bias-free once any key of this list is set)
      before the expert layers; ``n_shared_expert`` — always-on SwiGLU
      experts of width ``d_expert`` beside the routed ones;
      ``router_score`` ``"softmax"``|``"sigmoid"``, ``router_bias`` (a
      ``[n_expert]`` bias for the selection only), ``route_scale``;
    * the share of an expert-parallel deployment: ``n_expert`` stays
      the router's width while ``n_expert_local`` and ``expert_first``
      say which experts THIS chip holds; the layer computes their part
      (``layers.moe_ffn``), with the shared expert whole.

    Trinity-Large-Preview (``model_type`` afmoe), as the worked example
    — published widths, all 60 layers, every expert::

        dict(d_model=3072, n_head=48, n_kv_head=8, d_head=128,
             n_layer=60, vocab=200192, max_length=262144, dropout=0.0,
             pos_emb="rope", rope_theta=10000.0, rope_layers="sliding",
             layer_types=["sliding", "sliding", "sliding", "full"] * 15,
             window=4096, norm="rms", norm_eps=1e-5, qk_norm="head",
             attn_gate=True, sandwich_norm=True, emb_scale=3072 ** 0.5,
             ffn_act="swiglu", d_ff=12288, n_dense_layer=6,
             n_expert=256, expert_top_k=4, d_expert=3072,
             n_shared_expert=1, router_score="sigmoid",
             router_bias=True, norm_topk=True, route_scale=2.448)

    (one chip's share adds ``n_expert_local=8, expert_first=0``).

    Latent attention (``attn="mla"``; every key below is checked by
    ``_check_cfg``, and a cfg without them builds what it built):

    * ``q_lora_rank``, ``kv_lora_rank`` (``d_c``), ``d_nope``, ``d_rope``
      and ``d_v``. Of a token the layer keeps ONE row
      ``[c | k_r]``: ``c = RMSNorm(h W_dkv[:, :d_c])`` and the one rotated
      key part ``k_r = RoPE(h W_dkv[:, d_c:])`` all heads share — the
      decode cache is one tensor a layer, ``gpt_<i>_cache_c [B, 1,
      max_len, d_c + d_rope]``, not a K/V pair. Queries are
      ``RMSNorm(h W_dq) W_uq``, per
      head ``[q_nope | q_rope]`` with the rope part rotated; the softmax
      scale is ``(d_nope + d_rope) ** -0.5``.
    * two forms of the same numbers. EXPANDED (the prefill, through the
      flash forward at q/k ``d_nope + d_rope`` and v ``d_v`` wide, and
      the training build, composed): per head ``[k_nope | v] = c W_ukv``,
      ``k = [k_nope | k_r]``. ABSORBED (the decode steps, op
      ``mla_decode``): ``q_lat = q_nope W_uk^T``, score ``q_lat . c +
      q_rope . k_r``, ``o = sum p c``, ``ctx = o W_uv`` — keys and values
      are read out of the latent row itself (``kernels/mla_decode.py``).
    * parameters ``gpt_<i>_att_qa.w_0 [D, q_lora_rank]``,
      ``gpt_<i>_att_qa_ln_s``, ``gpt_<i>_att_qb.w_0 [q_lora_rank,
      H (d_nope + d_rope)]``, ``gpt_<i>_att_kva.w_0 [D, d_c + d_rope]``,
      ``gpt_<i>_att_kva_ln_s [d_c]``, ``gpt_<i>_att_kvb.w_0 [d_c,
      H (d_nope + d_v)]``, ``gpt_<i>_att_o.w_0 [H d_v, D]``, the same
      names in every build.
    * it needs ``pos_emb="rope"`` and takes none of ``n_kv_head``,
      ``d_head``, ``layer_types``/``window``, ``qk_norm``, ``attn_gate``.

    ``weight_dtype`` (``"float32"`` | ``"bfloat16"``): the dtype the
    SERVING programs (prefill, decode steps) store their matrices in —
    every float32 parameter of rank >= 2: projections, stacked experts,
    routers, the token table and the head. Norm scales, activations,
    router scores and the caches stay float32; a product widens the
    matrix where it multiplies and accumulates in float32. The training
    build refuses it.

    openPangu-Ultra-MoE-718B (``model_type`` pangu_ultra_moe), as the
    worked example — published widths, all 61 layers, every expert::

        dict(d_model=7680, n_head=128, n_layer=61, vocab=153600,
             max_length=131072, dropout=0.0, pos_emb="rope",
             rope_theta=25600000.0, norm="rms", norm_eps=1e-5,
             attn="mla", q_lora_rank=1536, kv_lora_rank=512, d_nope=128,
             d_rope=64, d_v=128, sandwich_norm=True, ffn_act="swiglu",
             d_ff=18432, n_dense_layer=3, n_expert=256, expert_top_k=8,
             d_expert=2048, n_shared_expert=1, router_score="sigmoid",
             norm_topk=True, route_scale=2.5, weight_dtype="bfloat16")

    A changed residual path (``residual="mhc"``: manifold-constrained
    hyper-connections, arXiv:2512.24880; serving programs only). A
    token's state is ``hc_mult`` streams of ``d_model`` values, kept as
    ONE ``[B, S, hc_mult * d_model]`` tensor (stream i in lanes ``i *
    d_model ..``). It starts as the embedding row copied to every
    stream; each sub-block (attention, then the FFN or the experts)
    reads ``h = sum_i H_pre[i] X[i]`` through the layer's own pre-norm
    and writes ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y`` (ops
    ``mhc_pre`` / ``mhc_post``, kernels/mhc.py); the final norm reads
    the sum of the streams. ``H_pre = sigmoid(.)``, ``H_post = 2
    sigmoid(.)`` and ``H_res`` — ``hc_sinkhorn_iters`` (20) Sinkhorn
    rounds, columns then rows with ``hc_eps`` (1e-6) in each divisor,
    of ``exp(clip(., *hc_res_clamp))`` (``(-30, 30)``) — come per token
    from ``alpha * (x~ phi) + b`` with ``x~`` the row RMS-normalised
    over all its streams (``norm_eps``). Parameters a sub-block k in
    {1, 2}: ``gpt_<i>_hc<k>_phi.w_0 [hc_mult d_model, hc_mult (hc_mult +
    2)]`` (``[phi_pre | phi_post | phi_res]``), ``gpt_<i>_hc<k>_alpha
    [3]``, ``gpt_<i>_hc<k>_b [hc_mult (hc_mult + 2)]``. The decode
    caches are whatever the attention keeps: the streams live inside a
    program.

    ``rope_scaling`` (with ``attn="mla"``): ``dict(type="yarn", factor,
    original_max_position_embeddings, beta_fast=32, beta_slow=1,
    mscale=1, mscale_all_dim=0)`` as DeepSeek-V3's public implementation
    reads it — per-dimension frequencies (``layers.rope(yarn=)``), cos
    and sin times ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
    mscale_all_dim)`` and the softmax scale times ``yarn_mscale(factor,
    mscale_all_dim) ** 2``, ``yarn_mscale(f, m) = 0.1 m ln f + 1``.

    Xing4.0-29B-A4B (``model_type`` xing4_0), as the worked example —
    published widths, all 40 layers::

        dict(d_model=3584, n_head=32, n_layer=40, vocab=131072,
             max_length=262144, dropout=0.0, pos_emb="rope",
             rope_theta=10000.0, rope_scaling=dict(
                 type="yarn", factor=64, beta_fast=32, beta_slow=1,
                 mscale=1, mscale_all_dim=1,
                 original_max_position_embeddings=4096),
             norm="rms", norm_eps=1e-6, attn="mla", q_lora_rank=768,
             kv_lora_rank=512, d_nope=128, d_rope=64, d_v=128,
             residual="mhc", hc_mult=4, hc_sinkhorn_iters=20,
             hc_eps=1e-6, hc_res_clamp=(-30, 30), ffn_act="swiglu",
             d_ff=9216, n_dense_layer=2, n_expert=64, expert_top_k=4,
             d_expert=1024, n_shared_expert=1, router_score="sigmoid",
             router_bias=True, norm_topk=True, route_scale=2.0,
             weight_dtype="bfloat16")

    One mixer a layer (``mixers``; serving programs only): a list of
    ``"ssm"`` | ``"attention"`` | ``"experts"``, one a layer, and each
    layer is ``x + mixer(norm(x))`` behind its ONE pre-norm
    (``gpt_<i>_pre1_ln_s``) — no attention-then-FFN pair.

    * ``"ssm"`` — a Mamba-2 state-space mixer (arXiv:2405.21060):
      ``ssm_heads`` (H) heads of ``ssm_head_dim`` (P) in ``ssm_groups``
      (G) groups with ``ssm_state`` (N) values of state a head and
      value, a causal depth-wise convolution of ``ssm_conv`` (K) taps,
      the prompt scanned in chunks of ``ssm_chunk`` (default 128). With
      ``u`` the normed input: ``[z | xBC | dt] = u W_in`` (``H P | H P +
      2 G N | H``), ``xBC <- silu(conv(xBC) + b)``, split into ``x``,
      ``B``, ``C``; ``dt = softplus(dt + dt_b)``; per head ``S <- exp(dt
      A) S + dt x (x) B``, ``y = S C + D x`` with ``A = -exp(a_log)``;
      then RMSNorm of ``y * silu(z)`` over each of the G groups of ``H P
      / G`` values, times a scale ``[H P]``, and ``W_out``. What a
      sequence KEEPS of such a layer has no position axis: the state
      ``gpt_<i>_cache_s [B, G, N, (H / G) P]`` (kernels/ssm.py says why
      it lies so) and the last ``K - 1`` rows of the un-convolved
      ``xBC``, ``gpt_<i>_cache_x [B, K - 1, H P + 2 G N]`` — the same
      bytes whatever its length (``cache_kind`` calls both ``state``).
      The prefill scans the prompt (op ``ssm_scan``) and overwrites
      both; a decode step updates them in place (``ssm_update``,
      ``causal_conv_step``). Parameters ``gpt_<i>_ssm_in.w_0``,
      ``gpt_<i>_ssm_conv.{w,b}_0``, ``gpt_<i>_ssm_{a_log,d,dt_b}``,
      ``gpt_<i>_ssm_norm_s``, ``gpt_<i>_ssm_out.w_0``.
    * ``"attention"`` — the grouped-head attention of the keys above
      (``n_head``, ``n_kv_head``, ``d_head``; no biases), its slab and
      the flash forward in the prefill. ``pos_emb="none"`` adds no
      position anywhere (no table, no rotation): the recurrence of the
      state-space layers orders the tokens, so it needs one.
    * ``"experts"`` — the routed experts of the keys above with three
      more: ``ffn_act="relu2"`` makes an expert ``relu(x W1)^2 W2`` with
      neither a gate nor biases; ``d_expert_in`` is the width the
      routed experts read and write — ``l = h W_lat_down`` (``d_model ->
      d_expert_in``), the routed sum over experts of ``[d_expert_in,
      d_expert]`` matrices, then ``W_lat_up`` back to ``d_model``
      (``gpt_<i>_moe_lat_{down,up}.w_0``) — while the router and the
      shared expert read the full ``h``; ``d_shared_expert`` is the
      width of the ONE shared expert (of ``ffn_act``'s kind,
      ``gpt_<i>_moe_shared_{up,down}.w_0``).

    It takes none of ``attn``, ``residual``, ``layer_types``,
    ``n_dense_layer``, ``sandwich_norm``; ``d_ff`` is not read. The
    training build, the multi-token step, a prefix store and a draft
    model refuse a cfg with a state-space layer by name.

    NVIDIA-Nemotron-3-Super-120B-A12B (``model_type`` nemotron_h), as
    the worked example — published widths, all 88 layers::

        dict(d_model=4096, n_head=32, n_kv_head=2, d_head=128,
             n_layer=88, vocab=131072, max_length=262144, dropout=0.0,
             pos_emb="none", norm="rms", norm_eps=1e-5,
             mixers=[{"M": "ssm", "*": "attention", "E": "experts"}[c]
                     for c in hybrid_override_pattern],
             ssm_heads=128, ssm_head_dim=64, ssm_groups=8,
             ssm_state=128, ssm_conv=4, ssm_chunk=128,
             ffn_act="relu2", n_expert=512, expert_top_k=22,
             d_expert=2688, d_expert_in=1024, d_shared_expert=5376,
             router_score="sigmoid", router_bias=True, norm_topk=True,
             route_scale=5.0, weight_dtype="bfloat16")

    A gated short convolution as a layer's FIRST sub-block
    (``layer_types`` entry ``"conv"``; serving programs only): the layer
    stays the ordinary pair — ``h = x + conv(norm(x))``, then ``h +
    ffn(norm(h))`` with the dense FFN or the experts — and only what
    stands where attention stood changes. With ``u`` the normed input
    and ``conv_taps`` (K, >= 2) taps: ``[B | C | X] = u W_in`` (``d_model
    -> 3 d_model``, no bias, split in that order), ``v = B * X``, ``c_t
    = sum_j w[:, j] v[t - K + 1 + j]`` (depth-wise, causal, zeros before
    the sequence, neither bias nor activation), ``y = C * c``, output
    ``y W_out``. The layer carries no position. What a sequence KEEPS
    of it is the last ``K - 1`` rows of ``v``: ``gpt_<i>_cache_x [B, K -
    1, d_model]`` (``cache_kind`` calls it ``state``: the same bytes
    whatever the length), overwritten whole by a prefill and shifted by
    a decode step (``layers.causal_conv(act=False, bias=False)``).
    Parameters ``gpt_<i>_conv_in.w_0 [D, 3 D]``, ``gpt_<i>_conv.w_0 [D,
    K]`` (the taps: float32 whatever ``weight_dtype``),
    ``gpt_<i>_conv_out.w_0 [D, D]``. ``rope_layers`` rotates attention
    layers only. It takes none of ``attn``, ``residual``, ``mixers``,
    nor a ``window`` without a ``"sliding"`` layer; the training build,
    the multi-token step, a prefix store and a draft model refuse it by
    name. ``norm_topk_eps`` is what a sigmoid router adds to the sum of
    the chosen scores it divides by (1e-20 where not given).

    LFM2-24B-A2B (``model_type`` lfm2_moe), as the worked example —
    published widths, all 40 layers (30 ``conv``, 10 ``full``)::

        dict(d_model=2048, n_head=32, n_kv_head=8, d_head=64,
             n_layer=40, vocab=65536, max_length=128000, dropout=0.0,
             pos_emb="rope", rope_theta=1000000.0, norm="rms",
             norm_eps=1e-5, qk_norm="head", tie_embeddings=True,
             layer_types=["conv", "conv"]
             + ["full", "conv", "conv", "conv"] * 9 + ["full", "conv"],
             conv_taps=3, ffn_act="swiglu", d_ff=11776, n_dense_layer=2,
             n_expert=64, expert_top_k=4, d_expert=1536,
             router_score="sigmoid", router_bias=True, norm_topk=True,
             norm_topk_eps=1e-6, weight_dtype="bfloat16")

    Power retention of degree 2 as a layer's FIRST sub-block
    (``layer_types`` entry ``"retention"``, arXiv:2507.04239; serving
    programs only): the layer stays the ordinary pair and the projections
    stay attention's — ``_qkv``, the per-head q/k norm, the rotation,
    ``_attn_out`` — with one new core between them and one gate
    projection, ``g = sigmoid(u W_g + b_g)`` (``gpt_<i>_att_gamma.{w,b}_0
    [D, n_kv_head]``: one decay a key-value head). The core is attention
    whose weight is the SQUARE of the scaled score under the decay, ``a_tj
    = (q_t . k_j / sqrt(d_head))^2 prod_{j<l<=t} g_l``, normalised by
    ``sum_j a_tj + 1e-6`` (``kernels.power.EPS``): no softmax, no maximum. A
    square is an inner product of symmetric squares, so what a sequence
    KEEPS of a layer has no position axis: ``gpt_<i>_cache_s [B, n_kv, R,
    d_head]`` (``R`` = 9,216 rows at ``d_head`` 128 for the exact 8,256
    pairs: kernels/power.py says how they are laid out) and the
    normaliser ``gpt_<i>_cache_z [B, n_kv, d_head, d_head]``
    (``cache_kind`` calls both ``state``). The prefill scans the prompt
    in chunks that follow from its length (``kernels.power.scan_chunk``;
    op ``power_scan``) and overwrites both; a decode step updates them
    in place and reads its query heads out of the new state
    (``power_update``). The layer has no cfg key of its own: the degree
    is 2. It stands beside none of ``attn``,
    ``residual``, ``mixers``, ``shortcut_moe``; the training build, the
    multi-token step, a prefix store and a draft model refuse it by name.

    Brumby-14B-Base (``model_type`` brumby), as the worked example —
    published widths, all 40 layers::

        dict(d_model=5120, n_head=40, n_kv_head=8, d_head=128,
             n_layer=40, vocab=151936, max_length=32768, dropout=0.0,
             pos_emb="rope", rope_theta=1000000.0, norm="rms",
             norm_eps=1e-6, qk_norm="head", tie_embeddings=False,
             layer_types=["retention"] * 40, ffn_act="swiglu",
             d_ff=17408, weight_dtype="bfloat16")

    The gated delta rule as a layer's FIRST sub-block (``layer_types``
    entry ``"delta"``, arXiv:2412.06464; serving programs only) beside
    ``"full"`` attention layers: the layer stays the ordinary pair and
    only what stands where attention stood changes. ``delta_k_heads``
    (Hk) key heads of ``delta_k_dim`` (Dk), ``delta_v_heads`` (Hv, a
    multiple of Hk) value heads of ``delta_v_dim`` (Dv); value head ``j``
    reads key head ``j // (Hv / Hk)``. With ``u`` the normed input: ``[q |
    k | v | z] = u W_in`` (``Hk Dk | Hk Dk | Hv Dv | Hv Dv``), ``[b | a] =
    u W_ba`` (``Hv | Hv``); ``[q | k | v]`` pass a causal depth-wise
    convolution of ``kernels.delta.CONV_TAPS`` (4) taps without bias and
    a silu, ``z`` does not. Per head ``q <- q / sqrt(sum q^2 + 1e-6) /
    sqrt(Dk)``, ``k <- k / sqrt(sum k^2 + 1e-6)``, ``beta = sigmoid(b)``,
    ``g = -exp(a_log) softplus(a + dt_b)``, and with ``S [Dk, Dv]`` a
    value head: ``S <- exp(g) S``; ``S <- S + k (beta (v - S^T k))^T``;
    ``o = S^T q`` — the state is READ at the key before it is written.
    Then ``y = scale * (o / rms(o)) * silu(z)`` over each head's ``Dv``
    values (one ``[Dv]`` scale the heads share) and ``W_out``. What a
    sequence KEEPS of such a layer has no position axis: the state
    ``gpt_<i>_cache_s [B, Hv, Dk, Dv]`` (kernels/delta.py says why it
    lies so) and the last three rows of the un-convolved ``[q | k | v]``,
    ``gpt_<i>_cache_x [B, 3, 2 Hk Dk + Hv Dv]`` (``cache_kind`` calls
    both ``state``), in ONE lane with the slabs of the ``"full"`` layers.
    The prefill scans the prompt in chunks (op ``delta_scan``) and
    overwrites both; a decode step updates them in place
    (``delta_update``, ``causal_conv_step``). Parameters
    ``gpt_<i>_delta_in.w_0``, ``gpt_<i>_delta_ba.w_0``,
    ``gpt_<i>_delta_conv.w_0 [C, 4]`` (the taps: float32 whatever
    ``weight_dtype``), ``gpt_<i>_delta_{a_log,dt_b} [Hv]``,
    ``gpt_<i>_delta_norm_s [Dv]``, ``gpt_<i>_delta_out.w_0``. It takes
    none of ``attn``, ``residual``, ``mixers``, ``shortcut_moe``; the
    training build, the multi-token step, a prefix store and a draft
    model refuse it by name. Two keys came with it for the attention
    layers and the experts: ``rope_dim`` (even, at most ``d_head``) — only
    the first that many values of a head rotate, rotate-half inside them;
    ``shared_expert_gate`` — the shared experts' sum times the token's
    ``sigmoid(h w)`` (``gpt_<i>_moe_shared_sgate.w_0 [D, 1]``).

    Qwen3-Next-80B-A3B-Instruct (``model_type`` qwen3_next), as the
    worked example — published widths, all 48 layers, every expert (the
    published RMSNorm multiplies by ``1 + w``: one parameterisation with
    the scale here)::

        dict(d_model=2048, n_head=16, n_kv_head=2, d_head=256,
             n_layer=48, vocab=151936, max_length=262144, dropout=0.0,
             pos_emb="rope", rope_theta=10000000.0, rope_dim=64,
             norm="rms", norm_eps=1e-6, qk_norm="head", attn_gate=True,
             tie_embeddings=False,
             layer_types=["delta", "delta", "delta", "full"] * 12,
             delta_k_heads=16, delta_v_heads=32, delta_k_dim=128,
             delta_v_dim=128, ffn_act="swiglu", n_expert=512,
             expert_top_k=10, d_expert=512, n_shared_expert=1,
             shared_expert_gate=True, router_score="softmax",
             norm_topk=True, weight_dtype="bfloat16")

    (one chip's share adds ``n_expert_local=64, expert_first=0``).

    A Mamba-1 mixer as a layer's FIRST sub-block (``layer_types`` entry
    ``"mamba"``, arXiv:2312.00752 as Jamba has it, arXiv:2403.19887;
    serving programs only) beside ``"full"`` attention layers: the layer
    stays the ordinary pair — the mixer, then the FFN of ``d_ff`` — and
    only what stands where attention stood changes. ``mamba_inner`` (C)
    channels of ``mamba_state`` (N) states each, a ``dt`` projection of
    rank ``mamba_dt_rank`` (R), ``ssm_conv`` (K) taps. With ``h`` the
    normed input: ``[u | z] = h W_in`` (``C | C``); ``u <- silu(conv_K(u)
    + b_conv)``, causal and depth-wise (``z`` does not pass it); ``[delta
    | B | C] = u W_x`` (``R | N | N``) of the CONVOLVED ``u``, each
    through an RMSNorm of its own (one scale vector each, the cfg's
    ``norm_eps``); ``dt = softplus(delta W_dt + b_dt)``; ``A =
    -exp(A_log) [C, N]``; a channel ``c`` and state ``n``: ``S[c, n] <-
    exp(dt[c] A[c, n]) S[c, n] + dt[c] B[n] u[c]``, ``y[c] = sum_n S[c, n]
    C[n] + D[c] u[c]``; ``out = (y * silu(z)) W_out``. The decay is one
    number a channel AND state, so — unlike the ``'ssm'`` mixer's, one
    scalar a head — no chunk of the recurrence is a matrix product: the
    prefill walks the prompt position by position inside its kernel (op
    ``mamba_scan``, kernels/mamba.py). What a sequence KEEPS of such a
    layer has no position axis: the state ``gpt_<i>_cache_s [B, 1, N, C]``
    and the last ``K - 1`` rows of the un-convolved ``u``,
    ``gpt_<i>_cache_x [B, K - 1, C]`` (``cache_kind`` calls both
    ``state``), in ONE lane with the slabs of the ``"full"`` layers. The
    prefill overwrites both; a decode step updates them in place
    (``mamba_update``, ``causal_conv_step``). Parameters
    ``gpt_<i>_mamba_{in,x,dt,out}.w_0``, ``gpt_<i>_mamba_conv.{w,b}_0``
    and, float32 whatever ``weight_dtype``, ``gpt_<i>_mamba_a_log [C,
    N]``, ``gpt_<i>_mamba_{d,dt_b} [C]``,
    ``gpt_<i>_mamba_{dt,b,c}norm_s``. ``pos_emb="none"`` stands beside
    such a layer as beside an ``'ssm'`` mixer: the recurrence orders the
    tokens. It takes none of ``attn``, ``residual``, ``mixers``,
    ``shortcut_moe``; the training build, the multi-token step, a prefix
    store and a draft model refuse it by name.

    AI21-Jamba2-3B (``model_type`` jamba), as the worked example —
    published widths, all 28 layers, attention at layers 7 and 21
    (``attn_layer_period`` 14, ``attn_layer_offset`` 7), 20 query heads
    over ONE key-value head, the token table as the head::

        dict(d_model=2560, n_head=20, n_kv_head=1, d_head=128,
             n_layer=28, vocab=65536, max_length=262144, dropout=0.0,
             pos_emb="none", norm="rms", norm_eps=1e-6,
             tie_embeddings=True,
             layer_types=["full" if i % 14 == 7 else "mamba"
                          for i in range(28)],
             mamba_inner=5120, mamba_state=16, mamba_dt_rank=160,
             ssm_conv=4, ffn_act="swiglu", d_ff=8192,
             weight_dtype="bfloat16")

    Shortcut-connected experts (``shortcut_moe``; serving programs
    only): a published layer is attention, dense FFN, attention, dense
    FFN, with ONE routed branch that reads the first attention's
    post-norm and joins the residual only at the END of the second dense
    FFN (``N`` a norm with its own scale each time)::

        a0 = x  + attn[l,0](N(x));   m = N(a0);   s = routed[l](m)
        b0 = a0 + ffn[l,0](m)
        a1 = b0 + attn[l,1](N(b0))
        y  = a1 + ffn[l,1](N(a1)) + s

    * ``n_layer`` counts SUB-LAYERS, two a published layer: sub-layer
      ``j = 2 l + k`` is the ordinary pair — ``gpt_<j>_pre1_ln_s``, the
      attention's parameters, ``gpt_<j>_pre2_ln_s``, the dense FFN
      ``gpt_<j>_ffn{1,1v,2}.w_0`` of ``d_ff`` — with a cache of its own
      (``gpt_<j>_cache_c`` under ``attn="mla"``: two latent slabs a
      published layer, numbered ``2 l`` and ``2 l + 1``). The branch of
      published layer ``l`` carries the EVEN sub-layer's number:
      ``gpt_<2l>_moe_{router,gate,up,down}.w_0``,
      ``gpt_<2l>_moe_router_bias``. The same names in every build. It is
      computed once, from the norm the even sub-layer's dense FFN reads,
      and added once, to the odd sub-layer's dense FFN output. The
      routing tallies have a row a BRANCH (``expert_rows``).
    * ``n_zero_expert`` identity (zero-compute) experts stand behind the
      ``n_expert`` with weights: the router and its selection bias are
      ``n_expert + n_zero_expert`` wide, ``expert_top_k`` is taken over
      all of them, and a chosen identity expert returns the token — a
      token's identity gates add up to one weight, ``s += w m``
      (``layers.moe_ffn``). ``n_expert_local`` / ``expert_first`` are a
      share of the experts WITH weights; the identity part is whole on
      every chip. ``ZERO_PAIRS_VAR`` tallies them in the serving decode
      step. (The key needs no ``shortcut_moe``.)
    * ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` (with ``attn="mla"``):
      ``[q_nope | q_rope]`` times ``sqrt(d_model / q_lora_rank)`` before
      the rotation; the normed latent ``c`` times ``sqrt(d_model /
      kv_lora_rank)`` (``k_r`` is not scaled). The cache row holds the
      scaled ``c``.
    * it needs an even ``n_layer`` and ``d_ff`` and takes none of
      ``mixers``, ``residual``, ``sandwich_norm``, ``n_dense_layer``,
      shared experts, ``d_expert_in`` nor a ``"conv"`` layer; the
      training build, the multi-token step, a prefix store and a draft
      model refuse it by name.

    LongCat-Flash (``model_type`` longcat_flash; the language model of
    LongCat-Flash-Omni), as the worked example — published widths, all
    28 layers (56 sub-layers), every expert::

        dict(d_model=6144, n_head=64, n_layer=2 * 28, vocab=131072,
             max_length=131072, dropout=0.0, pos_emb="rope",
             rope_theta=10000000.0, norm="rms", norm_eps=1e-5,
             attn="mla", q_lora_rank=1536, kv_lora_rank=512, d_nope=128,
             d_rope=64, d_v=128, mla_scale_q_lora=True,
             mla_scale_kv_lora=True, ffn_act="swiglu", d_ff=12288,
             shortcut_moe=True, n_expert=512, n_zero_expert=256,
             expert_top_k=12, d_expert=2048, router_score="softmax",
             router_bias=True, norm_topk=False, route_scale=6.0,
             weight_dtype="bfloat16")

    (one chip's share adds ``n_expert_local=8, expert_first=0``)."""
    return dict(d_model=768, d_ff=3072, n_head=12, n_layer=12,
                vocab=50304, max_length=1024, dropout=0.1)


_CFG_KEYS = frozenset([
    "d_model", "d_ff", "n_head", "n_layer", "vocab", "max_length",
    "dropout", "n_kv_head", "pos_emb", "norm", "ffn_act",
    "tie_embeddings", "n_expert", "expert_top_k", "d_expert",
    "norm_topk", "qk_norm", "norm_eps", "rope_theta",
    "d_head", "layer_types", "window", "rope_layers", "attn_gate",
    "sandwich_norm", "emb_scale", "n_dense_layer", "n_shared_expert",
    "router_score", "router_bias", "route_scale", "n_expert_local",
    "expert_first",
    "attn", "q_lora_rank", "kv_lora_rank", "d_nope", "d_rope", "d_v",
    "weight_dtype",
    "residual", "hc_mult", "hc_sinkhorn_iters", "hc_eps", "hc_res_clamp",
    "rope_scaling",
    "mixers", "ssm_heads", "ssm_head_dim", "ssm_groups", "ssm_state",
    "ssm_conv", "ssm_chunk", "d_expert_in", "d_shared_expert",
    "conv_taps", "norm_topk_eps",
    "shortcut_moe", "n_zero_expert", "mla_scale_q_lora",
    "mla_scale_kv_lora",
    "delta_k_heads", "delta_v_heads", "delta_k_dim", "delta_v_dim",
    "rope_dim", "shared_expert_gate",
    "mamba_inner", "mamba_state", "mamba_dt_rank",
])
_SSM_KEYS = ("ssm_heads", "ssm_head_dim", "ssm_groups", "ssm_state",
             "ssm_conv")
MIXER_KINDS = ("ssm", "attention", "experts")
LAYER_TYPES = ("sliding", "full", "conv", "retention", "delta", "mamba")
_DELTA_KEYS = ("delta_k_heads", "delta_v_heads", "delta_k_dim",
               "delta_v_dim")
_MAMBA_KEYS = ("mamba_inner", "mamba_state", "mamba_dt_rank", "ssm_conv")
_MLA_KEYS = ("q_lora_rank", "kv_lora_rank", "d_nope", "d_rope", "d_v")
# the keys after which a dense FFN carries no biases and the training
# build composes its attention here (``_attention``)
_NEW_LAYER_KEYS = frozenset([
    "d_head", "layer_types", "attn_gate", "sandwich_norm", "emb_scale",
    "n_dense_layer", "rope_layers", "attn",
])

# the device-side tally of routed (token, expert) pairs the serving
# decode step adds to: [n_layer, n_expert] int32, persistable
ROUTED_PAIRS_VAR = "gpt_moe_routed_pairs"
# beside it, per layer and HELD expert, the steps in which the expert
# was given at least one pair: [n_layer, n_expert_local] int32 (the
# grouped matmul fetches no weights for an empty group, so the bytes a
# step streams follow this tally, not the count of experts)
EXPERTS_TOUCHED_VAR = "gpt_moe_experts_touched"
# the tally of the prefill programs whose expert calls are long enough
# to carry a bound on the held pairs (``ops/moe_ops.py::compact_rows``:
# a cfg that holds a share of its experts): per layer, the calls that
# cut their rows at it (column 0) and that took the full length
# (column 1): [n_layer, 2] int32
COMPACT_CALLS_VAR = "gpt_moe_compact_calls"
# with identity experts (cfg['n_zero_expert']) the serving decode step
# also tallies, per expert branch, the pairs that chose one (column 0,
# summed: they cost nothing) and the most experts WITH weights one token
# chose (column 1, a running maximum: the straggler's width):
# [expert_rows, 2] int32
ZERO_PAIRS_VAR = "gpt_moe_zero_pairs"

# the largest |row sum - 1| or |column sum - 1| any residual mapping
# H_res has shown in the serving decode step (cfg['residual'] = 'mhc'):
# [1] float32, persistable, a running maximum kept on the device
MHC_RES_DEV_VAR = "gpt_mhc_res_dev"

# what the decode and the prefill step choose on the device, under names
# a caller fetches INSTEAD of the logits (the builders keep returning
# those): the greedy next token a row ([B] int32: the decode step's one
# position, the prefill's LAST prompt position), and — prefill only —
# that last position's logits row [B, vocab] for a host-side sampler.
# A plan that fetches neither holds neither (DCE)
NEXT_TOKEN_VAR = "gpt_next_token"
LAST_LOGITS_VAR = "gpt_last_logits"


def _check_cfg(cfg):
    """Knob typos must fail at build time, not silently fall back to
    the default architecture — covers both bad VALUES for the string
    knobs and unknown KEYS (e.g. 'tied_embeddings') that would
    otherwise be ignored."""
    unknown = set(cfg) - _CFG_KEYS
    if unknown:
        raise ValueError("unknown gpt cfg key(s) %s — known keys: %s"
                         % (sorted(unknown), sorted(_CFG_KEYS)))
    for key, allowed in (("pos_emb", ("learned", "rope", "none")),
                         ("norm", ("layer", "rms")),
                         ("ffn_act", ("relu", "gelu", "swish",
                                      "swiglu", "relu2")),
                         ("qk_norm", (True, False, "head")),
                         ("rope_layers", ("all", "sliding")),
                         ("router_score", ("softmax", "sigmoid")),
                         ("attn", ("mla",)),
                         ("residual", ("mhc",)),
                         ("weight_dtype", ("float32", "bfloat16"))):
        val = cfg.get(key)
        if val is not None and val not in allowed:
            raise ValueError("cfg[%r] must be one of %s; got %r"
                             % (key, allowed, val))
    if cfg.get("n_expert"):
        for key in ("expert_top_k", "d_expert"):
            if not cfg.get(key):
                raise ValueError("cfg['n_expert'] needs cfg[%r]" % key)
        if not 1 <= cfg["expert_top_k"] <= router_width(cfg):
            raise ValueError(
                "cfg['expert_top_k'] must be in [1, n_expert]; got %r of "
                "%r" % (cfg["expert_top_k"], cfg["n_expert"]))
        n_local = cfg.get("n_expert_local") or cfg["n_expert"]
        first = cfg.get("expert_first") or 0
        if not (1 <= n_local <= cfg["n_expert"]
                and 0 <= first <= cfg["n_expert"] - n_local):
            raise ValueError(
                "cfg['expert_first']=%r with cfg['n_expert_local']=%r is "
                "not a share of n_expert=%r"
                % (first, n_local, cfg["n_expert"]))
        if not 0 <= (cfg.get("n_dense_layer") or 0) <= cfg["n_layer"]:
            raise ValueError("cfg['n_dense_layer'] must be in [0, n_layer]"
                             "; got %r" % (cfg["n_dense_layer"],))
        if cfg.get("n_dense_layer") and "d_ff" not in cfg:
            raise ValueError("cfg['n_dense_layer'] needs cfg['d_ff']")
        if cfg.get("shared_expert_gate") and not cfg.get("n_shared_expert"):
            raise ValueError("cfg['shared_expert_gate'] gates the sum of "
                             "cfg['n_shared_expert'] shared experts")
        if cfg.get("d_shared_expert") and cfg.get("n_shared_expert"):
            raise ValueError(
                "cfg['d_shared_expert'] is the width of the ONE shared "
                "expert: it takes no cfg['n_shared_expert']")
    else:
        if "d_ff" not in cfg and not cfg.get("mixers"):
            raise ValueError("cfg needs 'd_ff' (or 'n_expert' experts)")
        for key in ("n_dense_layer", "n_shared_expert", "router_score",
                    "router_bias", "route_scale", "n_expert_local",
                    "expert_first", "d_expert_in", "d_shared_expert",
                    "norm_topk_eps", "n_zero_expert", "shortcut_moe",
                    "shared_expert_gate"):
            if cfg.get(key):
                raise ValueError("cfg[%r] needs cfg['n_expert']" % key)
    if cfg.get("ffn_act") == "relu2" and (
            not cfg.get("n_expert") or cfg.get("n_dense_layer")):
        raise ValueError(
            "cfg['ffn_act']='relu2' is the experts' (relu(x W1)^2 W2, no "
            "gate, no biases): it needs cfg['n_expert'] and takes no "
            "cfg['n_dense_layer'] (no dense FFN of that kind is built)")
    _check_mixers(cfg)
    _check_shortcut(cfg)
    if has_streams(cfg):
        if not int(cfg.get("hc_mult") or 0) >= 1:
            raise ValueError("cfg['residual']='mhc' needs cfg['hc_mult'] "
                             ">= 1 streams")
        if cfg.get("norm", "layer") != "rms":
            raise ValueError("cfg['residual']='mhc' needs norm='rms': the "
                             "mappings read the RMS-normalised streams")
        if cfg.get("pos_emb", "learned") != "rope":
            raise ValueError("cfg['residual']='mhc' needs pos_emb='rope': "
                             "a learned position row has no stream to go to")
        lo, hi = _hc_clamp(cfg)
        if not lo < hi:
            raise ValueError("cfg['hc_res_clamp'] must be (min, max) with "
                             "min < max; got %r" % (cfg["hc_res_clamp"],))
    else:
        for key in ("hc_mult", "hc_sinkhorn_iters", "hc_eps",
                    "hc_res_clamp"):
            if cfg.get(key):
                raise ValueError("cfg[%r] needs cfg['residual']='mhc'" % key)
    if cfg.get("rope_scaling"):
        rs = cfg["rope_scaling"]
        if not has_latent(cfg):
            raise ValueError("cfg['rope_scaling'] needs cfg['attn']='mla': "
                             "no other attention here scales its softmax")
        if rs.get("type") != "yarn" or not float(rs.get("factor") or 0) \
                >= 1 or not int(rs.get(
                    "original_max_position_embeddings") or 0) >= 1:
            raise ValueError(
                "cfg['rope_scaling'] must be a YaRN dict (type='yarn', "
                "factor >= 1, original_max_position_embeddings); got %r"
                % (rs,))
    types = cfg.get("layer_types")
    if types is not None:
        if len(types) != cfg["n_layer"] or \
                any(t not in LAYER_TYPES for t in types):
            raise ValueError(
                "cfg['layer_types'] must name one of %s for each of the %d "
                "layers; got %r" % (LAYER_TYPES, cfg["n_layer"], types))
        if "sliding" in types and not int(cfg.get("window") or 0) >= 1:
            raise ValueError("a 'sliding' layer needs cfg['window'] >= 1")
    elif cfg.get("window"):
        raise ValueError("cfg['window'] needs cfg['layer_types']")
    _check_kinds(cfg, "layer_types")
    if cfg.get("rope_dim") is not None:
        dim = int(cfg["rope_dim"])
        if cfg.get("pos_emb", "learned") != "rope" or has_latent(cfg) \
                or cfg.get("rope_scaling") \
                or dim % 2 or not 0 < dim <= _d_head(cfg):
            raise ValueError(
                "cfg['rope_dim'] is how many of a head's cfg['d_head'] "
                "values rotate: even, in (0, %d], with pos_emb='rope' and "
                "neither attn='mla' (whose d_rope it is) nor rope_scaling; "
                "got %r" % (_d_head(cfg), cfg["rope_dim"]))
    if cfg.get("rope_layers", "all") != "all" \
            and cfg.get("pos_emb", "learned") != "rope":
        raise ValueError("cfg['rope_layers'] needs pos_emb='rope'")
    if has_latent(cfg):
        for key in _MLA_KEYS:
            if not int(cfg.get(key) or 0) >= 1:
                raise ValueError("cfg['attn']='mla' needs cfg[%r] >= 1"
                                 % key)
        if cfg.get("pos_emb", "learned") != "rope" or cfg["d_rope"] % 2:
            raise ValueError("cfg['attn']='mla' needs pos_emb='rope' and "
                             "an even cfg['d_rope']; got %r, %r"
                             % (cfg.get("pos_emb"), cfg["d_rope"]))
        for key in ("n_kv_head", "d_head", "layer_types", "window",
                    "qk_norm", "attn_gate"):
            if cfg.get(key):
                raise ValueError("cfg['attn']='mla' takes no cfg[%r]" % key)
        return
    for key in _MLA_KEYS + ("mla_scale_q_lora", "mla_scale_kv_lora"):
        if cfg.get(key):
            raise ValueError("cfg[%r] needs cfg['attn']='mla'" % key)
    if _d_head(cfg) % 2 and cfg.get("pos_emb", "learned") == "rope":
        raise ValueError("rope needs an even head size; got %d"
                         % _d_head(cfg))


def _check_mixers(cfg):
    """cfg['mixers'] as a whole (``base_config``); what each of its
    kinds needs is ``_check_kinds``'."""
    kinds = cfg.get("mixers") or ()
    _check_kinds(cfg, "mixers")
    if cfg.get("pos_emb") == "none" and not any(
            kind.orders and kind.name in (cfg.get(kind.key) or ())
            for kind in LAYER_KINDS.values()):
        raise ValueError(
            "cfg['pos_emb']='none' needs %s: nothing else here orders the "
            "tokens" % " or ".join(
                "%s in cfg[%r]" % (kind.layer, kind.key)
                for kind in LAYER_KINDS.values() if kind.orders))
    if not kinds:
        return
    if len(kinds) != cfg["n_layer"] \
            or any(k not in MIXER_KINDS for k in kinds):
        raise ValueError(
            "cfg['mixers'] must name one of %s for each of the %d layers; "
            "got %r" % (MIXER_KINDS, cfg["n_layer"], kinds))
    for key in ("attn", "residual", "layer_types", "n_dense_layer",
                "sandwich_norm", "n_shared_expert"):
        if cfg.get(key):
            raise ValueError("cfg['mixers'] takes no cfg[%r]" % key)


def _rows_naming(k):
    """The rows of ``LAYER_KINDS`` that name cfg key ``k`` (``ssm_conv``
    is the taps of an ``'ssm'`` mixer's AND of a ``'mamba'`` layer's
    convolution, one ``causal_conv``; every other key has one row)."""
    return [kind for kind in LAYER_KINDS.values()
            if k in kind.needs + kind.extra]


def _check_kinds(cfg, key):
    """The rows of ``LAYER_KINDS`` that an entry of cfg[``key``] names
    (``base_config``), each by the same three rules: a key of the row's
    needs a layer of it (of a row that names it); such a layer needs the row's keys >= 1, passes
    the row's own check and takes none of what the row refuses."""
    held = cfg.get(key) or ()
    for kind in LAYER_KINDS.values():
        if kind.key != key:
            continue
        if kind.name not in held:
            for k in kind.needs + kind.extra:
                rows = _rows_naming(k)
                if cfg.get(k) and not any(
                        row.name in (cfg.get(row.key) or ()) for row in rows):
                    raise ValueError("cfg[%r] needs %s" % (k, " or ".join(
                        "%s in cfg[%r]" % (row.layer, row.key)
                        for row in rows)))
            continue
        for k in kind.needs:
            if not int(cfg.get(k) or 0) >= 1:
                raise ValueError("%s needs cfg[%r] >= 1" % (kind.layer, k))
        if kind.check is not None:
            kind.check(cfg)
        for k, why in kind.refuses:
            if cfg.get(k):
                raise ValueError("%s takes no cfg[%r]: %s"
                                 % (kind.layer, k, why))


def _check_ssm(cfg):
    if cfg["ssm_heads"] % cfg["ssm_groups"] or cfg["ssm_conv"] < 2:
        raise ValueError(
            "cfg['ssm_groups']=%r must divide cfg['ssm_heads']=%r, and "
            "cfg['ssm_conv']=%r be >= 2 taps"
            % (cfg["ssm_groups"], cfg["ssm_heads"], cfg["ssm_conv"]))


def _check_experts(cfg):
    if not cfg.get("n_expert"):
        raise ValueError("an 'experts' layer needs cfg['n_expert']")


def _check_conv(cfg):
    if not int(cfg.get("conv_taps") or 0) >= 2:
        raise ValueError("a 'conv' layer needs cfg['conv_taps'] >= 2 (the "
                         "taps of its causal depth-wise convolution)")
    if cfg.get("window") and "sliding" not in cfg["layer_types"]:
        raise ValueError(
            "cfg['window'] needs a 'sliding' layer: a 'conv' layer keeps "
            "its last cfg['conv_taps'] - 1 rows and has no window")


def _check_delta(cfg):
    if cfg["delta_v_heads"] % cfg["delta_k_heads"]:
        raise ValueError(
            "cfg['delta_k_heads']=%r must divide cfg['delta_v_heads']=%r"
            % (cfg["delta_k_heads"], cfg["delta_v_heads"]))


def _check_mamba(cfg):
    if cfg["ssm_conv"] < 2 or "d_ff" not in cfg:
        raise ValueError(
            "a 'mamba' layer needs cfg['ssm_conv']=%r >= 2 taps and "
            "cfg['d_ff'] (the dense FFN behind the mixer)"
            % (cfg["ssm_conv"],))


def _check_shortcut(cfg):
    """cfg['shortcut_moe'] and cfg['n_zero_expert'] (``base_config``)."""
    if cfg.get("n_zero_expert") and (cfg.get("d_expert_in")
                                     or cfg.get("ffn_act") == "relu2"):
        raise ValueError(
            "cfg['n_zero_expert'] (identity experts) takes neither "
            "cfg['d_expert_in'] nor ffn_act='relu2': an identity expert "
            "returns the token the router scored, beside SwiGLU experts")
    if not has_shortcut(cfg):
        return
    if cfg["n_layer"] % 2 or "d_ff" not in cfg:
        raise ValueError(
            "cfg['shortcut_moe'] needs an even cfg['n_layer'] (it counts "
            "attention-then-FFN sub-layers, two a published layer) and "
            "cfg['d_ff'] (each keeps its dense FFN); got n_layer=%r"
            % (cfg["n_layer"],))
    for key, why in (
            ("mixers", "one mixer a layer has no dense FFN to fork beside"),
            ("residual", "the branch has no mapping onto several streams"),
            ("sandwich_norm", "no norm is stated for the joined sum"),
            ("n_dense_layer", "every sub-layer keeps its dense FFN"),
            ("n_shared_expert", "the dense FFNs stand where one would"),
            ("d_shared_expert", "the dense FFNs stand where one would"),
            ("d_expert_in", "the branch reads the token itself")):
        if cfg.get(key):
            raise ValueError("cfg['shortcut_moe'] takes no cfg[%r]: %s"
                             % (key, why))
    if "conv" in (cfg.get("layer_types") or ()):
        raise ValueError("cfg['shortcut_moe'] takes no 'conv' layer: the "
                         "branch forks behind an attention sub-block")


def _lm_head(cfg, x):
    """Final projection to vocab logits. ``tie_embeddings=True`` reuses
    the input embedding (logits = x @ word_emb^T — no gpt_out_proj
    parameter; gradients accumulate into the one table from both the
    lookup and the head), the standard LM weight-tying."""
    with name_scope("head"):
        if cfg.get("tie_embeddings"):
            from ..core.program import default_main_program

            emb = default_main_program().global_block().var("gpt_word_emb")
            if emb.dtype != "float32":
                # a table stored in cfg['weight_dtype'] widens where it
                # multiplies, as ``fc`` widens its matrix
                emb = layers.cast(emb, "float32")
            return layers.matmul(x, emb, transpose_y=True)
        return layers.fc(x, cfg["vocab"], num_flatten_dims=2,
                         bias_attr=False,
                         param_attr=ParamAttr(name="gpt_out_proj.w_0"))


def _expose(var, name):
    """Bind ``var`` to the well-known ``name`` in the main program."""
    from ..core.program import default_main_program

    out = default_main_program().global_block().create_var(
        name=name, dtype=var.dtype, shape=var.shape)
    return layers.assign(var, output=out)


def _greedy_token(rows):
    """``NEXT_TOKEN_VAR``: argmax over the vocabulary of float32 logits
    ``rows`` [B, vocab]. The first maximum wins and a NaN counts as one,
    as in ``sample_token``'s ``np.argmax`` over the float64 cast (exact
    and monotone, so the same index, ties and all)."""
    with name_scope("head"):
        return _expose(layers.argmax(rows, axis=1), NEXT_TOKEN_VAR)


def _rms_eps(cfg):
    return cfg.get("norm_eps") or 1e-6


def _d_head(cfg):
    """The head size: cfg['d_head'], else ``d_model // n_head``."""
    return int(cfg.get("d_head") or cfg["d_model"] // cfg["n_head"])


def has_latent(cfg):
    """Whether the cfg's attention is latent (``attn='mla'``): its decode
    cache is one ``[B, 1, max_len, kv_lora_rank + d_rope]`` tensor a
    layer (``gpt_<i>_cache_c``) and not a K/V pair."""
    return cfg.get("attn") == "mla"


def latent_width(cfg):
    """Values a token leaves in a latent cache: ``c`` and ``k_r``."""
    return int(cfg["kv_lora_rank"]) + int(cfg["d_rope"])


def has_streams(cfg):
    """Whether a token's state is ``hc_mult`` residual streams
    (``residual='mhc'``) and not one vector."""
    return cfg.get("residual") == "mhc"


def has_shortcut(cfg):
    """Whether the routed experts are a shortcut branch
    (``shortcut_moe``): cfg['n_layer'] counts attention-then-dense-FFN
    sub-layers, two a published layer, and ONE routed branch a published
    layer forks at the even sub-layer's post-attention norm and joins
    the residual at the END of the odd one."""
    return bool(cfg.get("shortcut_moe"))


def router_width(cfg):
    """Outputs of the router: the experts with weights and, behind them,
    cfg['n_zero_expert'] identity experts."""
    return cfg["n_expert"] + int(cfg.get("n_zero_expert") or 0)


def expert_rows(cfg):
    """Rows of the routing tallies: one an expert branch — a layer, or a
    PAIR of sub-layers under ``shortcut_moe`` (branch ``l`` forks in
    sub-layer ``2 l``)."""
    return cfg["n_layer"] // 2 if has_shortcut(cfg) else cfg["n_layer"]


def _refuse_shortcut(cfg, who, why):
    if has_shortcut(cfg):
        raise ValueError(
            "%s: cfg['shortcut_moe'] builds layers of two attention "
            "sub-blocks and two dense FFNs with one routed branch that "
            "forks after the first and joins after the second, %s"
            % (who, why))


def kind_of(cfg, i):
    """Layer ``i``'s row of ``LAYER_KINDS``: what its first sub-block is,
    by its entry of cfg['mixers'] or cfg['layer_types'] (the two refuse
    each other). An attention layer (``'attention'``, ``'sliding'``,
    ``'full'``, or neither key) is ``'latent'`` under ``attn='mla'``."""
    entries = cfg.get("mixers") or cfg.get("layer_types")
    kind = LAYER_KINDS.get(entries[i] if entries else None)
    if kind is None:
        kind = LAYER_KINDS["latent" if has_latent(cfg) else "attention"]
    return kind


def _retention_kept(cfg):
    from ..kernels.power import phi_plan

    return ("a power-retention state of %d rows a key-value head and its "
            "normaliser with no position axis (gpt_<i>_cache_s, "
            "gpt_<i>_cache_z)" % phi_plan(_d_head(cfg))[2])


def _delta_kept(cfg):
    from ..kernels.delta import CONV_TAPS

    return ("a delta-rule state of %d x %d a value head and the last %d "
            "rows of its convolution's input with no position axis "
            "(gpt_<i>_cache_s, gpt_<i>_cache_x)"
            % (int(cfg["delta_k_dim"]), int(cfg["delta_v_dim"]),
               CONV_TAPS - 1))


def _mamba_kept(cfg):
    C, N, _R, K = mamba_widths(cfg)
    return ("a selective-scan state of %d states a channel over %d "
            "channels and the last %d rows of its convolution's input with "
            "no position axis (gpt_<i>_cache_s, gpt_<i>_cache_x)"
            % (N, C, K - 1))


def _conv_kept(cfg):
    return ("the last %d rows of a gated convolution's input with no "
            "position axis (gpt_<i>_cache_x)" % (int(cfg["conv_taps"]) - 1))


def _ssm_kept(cfg):
    return ("a recurrent state with no position axis (gpt_<i>_cache_s, "
            "gpt_<i>_cache_x)")


def state_layers(cfg):
    """The layers that keep a constant-size state and not rows a
    position, whichever key brought them: an ``'ssm'`` entry of
    cfg['mixers'] or a ``'conv'``, ``'retention'``, ``'delta'`` or
    ``'mamba'`` entry of cfg['layer_types'] (the rows of ``LAYER_KINDS``
    with ``kept``)."""
    return [i for i in range(cfg["n_layer"]) if kind_of(cfg, i).kept]


def has_state(cfg):
    """Whether some layer keeps a state and not rows a position (an
    ``'ssm'`` mixer, a ``'conv'``, a ``'retention'``, a ``'delta'`` or a
    ``'mamba'`` layer): its caches, ``gpt_<i>_cache_s``, ``gpt_<i>_cache_x`` and
    ``gpt_<i>_cache_z``, have no position axis, so nothing can be cut out
    of them at a prefix's length nor rolled back by a position."""
    return bool(state_layers(cfg))


def delta_widths(cfg):
    """``(Hk, Dk, Hv, Dv, the convolution's width)`` of a 'delta'
    layer."""
    Hk, Dk = int(cfg["delta_k_heads"]), int(cfg["delta_k_dim"])
    Hv, Dv = int(cfg["delta_v_heads"]), int(cfg["delta_v_dim"])
    return Hk, Dk, Hv, Dv, 2 * Hk * Dk + Hv * Dv


def mamba_widths(cfg):
    """``(C, N, R, K)`` of a 'mamba' layer: inner channels, states a
    channel, the rank of ``dt``'s projection, the convolution's taps."""
    return tuple(int(cfg[k]) for k in _MAMBA_KEYS)


def ssm_widths(cfg):
    """``(H, P, G, N, K, d_inner, conv width)`` of an 'ssm' layer."""
    H, P = int(cfg["ssm_heads"]), int(cfg["ssm_head_dim"])
    G, N = int(cfg["ssm_groups"]), int(cfg["ssm_state"])
    return H, P, G, N, int(cfg["ssm_conv"]), H * P, H * P + 2 * G * N


def cache_kind(cfg, name, max_len):
    """What kind of cache tensor ``name`` (one of a builder's
    ``cache_names``) is, from its layer's row of ``LAYER_KINDS`` and its
    suffix: ``'state'`` (a state-space layer's state or convolution
    rows, a gated convolution's carried rows, a retention or delta
    layer's state, normaliser or rows: no position axis), ``'latent'`` (a
    latent layer's one tensor), ``'ring'`` (a sliding layer's, shorter
    than ``max_len``) or ``'full'`` (a slab)."""
    layer = int(name.split("_")[1])
    kind = kind_of(cfg, layer).caches[name[name.index("_cache_"):]]
    if kind != "rows":
        return kind
    return "ring" if cache_rows(cfg, layer, max_len) < max_len else "full"


def state_refusal(cfg):
    """What to say of a cfg whose layers keep a state, by the first row
    of ``LAYER_KINDS`` that it holds: ``"<the layers>, whose caches are
    <what> (<names>)"``."""
    held = {kind_of(cfg, i).name for i in state_layers(cfg)}
    kind = next(k for k in LAYER_KINDS.values() if k.name in held)
    return "cfg[%r] holds %r layers, whose caches are %s" \
        % (kind.key, kind.name, kind.kept(cfg))


def _refuse_state(cfg, who, why):
    if has_state(cfg):
        raise ValueError("%s: %s, %s" % (who, state_refusal(cfg), why))


def _hc_clamp(cfg):
    lo, hi = cfg.get("hc_res_clamp") or (-30.0, 30.0)
    return float(lo), float(hi)


def _refuse_streams(cfg, who, why):
    if has_streams(cfg):
        raise ValueError(
            "%s: cfg['residual']='mhc' keeps %d residual streams a token "
            "(ops mhc_pre / mhc_post), %s"
            % (who, int(cfg["hc_mult"]), why))


def _stores_weights(builder):
    """Run ``builder(cfg, ...)`` with its matrices created in
    cfg['weight_dtype'] (``layer_helper.stored_dtype``)."""
    @functools.wraps(builder)
    def build_stored(cfg=None, *args, **kw):
        with stored_dtype((cfg or {}).get("weight_dtype")):
            return builder(cfg, *args, **kw)
    return build_stored


def _new_style(cfg):
    """Whether the cfg holds a key that only this file's own layer
    (``_block``) builds for training."""
    return any(cfg.get(k) for k in _NEW_LAYER_KEYS) \
        or cfg.get("qk_norm") == "head"


def layer_window(cfg, i):
    """Layer ``i``'s attention window, None for a full layer."""
    types = cfg.get("layer_types")
    if types and types[i] == "sliding":
        return int(cfg["window"])
    return None


def cache_rows(cfg, i, max_len):
    """Rows of layer ``i``'s decode cache: a sliding layer keeps a ring
    of its window (never more than ``max_len``), a full layer a slab of
    ``max_len``."""
    window = layer_window(cfg, i)
    return max_len if window is None else min(window, max_len)


def has_rings(cfg, max_len=None):
    """Whether some layer's cache is a ring shorter than ``max_len``
    (any sliding layer, where ``max_len`` is not given)."""
    return any(layer_window(cfg, i) is not None
               and (max_len is None or layer_window(cfg, i) < max_len)
               for i in range(cfg["n_layer"]))


def _rotates(cfg, i):
    """Whether layer ``i`` rotates q and k (``pos_emb='rope'``, and the
    layer's kind among cfg['rope_layers'])."""
    if cfg.get("pos_emb", "learned") != "rope":
        return False
    if not kind_of(cfg, i).merged:
        return False        # no heads of q and k, so nothing to rotate
    return cfg.get("rope_layers", "all") == "all" \
        or layer_window(cfg, i) is not None


def _layer_scope(cfg, i):
    """The ``name_scope`` every op of layer ``i`` is built under: ``L<i>``,
    and under cfg['shortcut_moe'] ``L<l>.<k>``, sub-layer ``k`` of the
    published layer ``l`` as ``expert_rows`` counts them. The helpers
    below add the sub-block's class (``attn.qkv``, ``attn.core``,
    ``attn.out``, ``ffn``, ``moe.experts``, ``moe.shared``, ``mixer``,
    ``conv``, ``mhc``, ``norm``; outside the layers ``embed``, ``head``,
    ``loss``; ``core/program.py::SCOPE_CLASSES`` is the list): what a
    device operation answers to
    (``core/lowering.py::op_scope``, ``observe/device_names.py``). A
    scope is no attr: op lists, parameter and cache names stay as they
    were."""
    if has_shortcut(cfg):
        return name_scope("L%d.%d" % (i // 2, i % 2))
    return name_scope("L%d" % i)


def _embed(cfg, tokens, shape):
    """The token rows as ``shape`` (lookup_table squeezes a trailing-1 id
    dim, so the layout is restored explicitly), times cfg['emb_scale']."""
    with name_scope("embed"):
        word = layers.embedding(tokens, [cfg["vocab"], cfg["d_model"]],
                                param_attr=ParamAttr(name="gpt_word_emb"))
        if word.dtype != "float32":
            # a table stored in cfg['weight_dtype']: the row widens here
            word = layers.cast(word, "float32")
        word = layers.reshape(word, shape)
        if cfg.get("emb_scale"):
            word = layers.scale(word, scale=float(cfg["emb_scale"]))
        if has_streams(cfg):
            # every stream starts as the embedding row
            word = layers.expand(word, [1] * (len(shape) - 1)
                                 + [int(cfg["hc_mult"])])
        return word


def _qkv(cfg, h, nm):
    """The three bias-free projections of the normed input, q at
    ``n_head * d_head`` wide, k and v at ``n_kv * d_head``, and the
    whole-vector q/k norm where the cfg asks for it."""
    n_kv, _g = _kv_heads_of(cfg)
    d_head = _d_head(cfg)
    with name_scope("attn.qkv"):
        q = layers.fc(h, cfg["n_head"] * d_head, num_flatten_dims=2,
                      bias_attr=False,
                      param_attr=ParamAttr(name=nm + "_att_q.w_0"))
        k = layers.fc(h, n_kv * d_head, num_flatten_dims=2,
                      bias_attr=False,
                      param_attr=ParamAttr(name=nm + "_att_k.w_0"))
        v = layers.fc(h, n_kv * d_head, num_flatten_dims=2,
                      bias_attr=False,
                      param_attr=ParamAttr(name=nm + "_att_v.w_0"))
        q, k = _qk_norm(cfg, q, k, nm)
    return q, k, v


def _head_norm(cfg, t, nm, which):
    """cfg['qk_norm'] == 'head': RMSNorm of ``t [..., d_head]`` (q or k
    already split into heads) over its last axis, with the one
    ``[d_head]`` scale ``<nm>_att_{q,k}norm_s`` all heads share."""
    if cfg.get("qk_norm") != "head":
        return t
    return layers.rms_norm(
        t, begin_norm_axis=len(t.shape) - 1, epsilon=_rms_eps(cfg),
        param_attr=ParamAttr(name="%s_att_%snorm_s" % (nm, which)))


def _heads(cfg, t, nm, S, n, which=None):
    """``t [B, S, n * d_head]`` as ``n`` heads ``[B, n, S, d_head]``; q or
    k (``which``) normed a head first where the cfg asks (``_head_norm``)."""
    t = layers.reshape(t, [-1, S, n, _d_head(cfg)])
    if which:
        t = _head_norm(cfg, t, nm, which)
    return layers.transpose(t, perm=[0, 2, 1, 3])


def _attn_out(cfg, h, ctxv, nm):
    """The attention sub-block's tail on the merged heads ``ctxv
    [B, S, n_head * d_head]``: cfg['attn_gate'] multiplies by
    ``sigmoid(h Wg)``, then the output projection."""
    with name_scope("attn.out"):
        if cfg.get("attn_gate"):
            gate = layers.fc(h, cfg["n_head"] * _d_head(cfg),
                             num_flatten_dims=2, bias_attr=False,
                             param_attr=ParamAttr(name=nm + "_att_g.w_0"))
            ctxv = layers.elementwise_mul(ctxv, layers.sigmoid(gate))
        return layers.fc(ctxv, cfg["d_model"], num_flatten_dims=2,
                         bias_attr=False,
                         param_attr=ParamAttr(name=nm + "_att_o.w_0"))


def _fc(x, width, name):
    return layers.fc(x, width, num_flatten_dims=2, bias_attr=False,
                     param_attr=ParamAttr(name=name))


def _mla_q(cfg, h, nm, S, pos, in_place=False):
    """Latent attention's queries of ``h [B, S, D]`` as ``[B, S, H,
    d_nope]`` and ``[B, S, H, d_rope]``, the second rotated at ``pos``
    ([S], or per-slot [B, 1] where S is 1: the angles then broadcast
    over the head axis). ``in_place`` rotates the heads where the
    projection left them (no transpose either side of the rotation)."""
    n_head, dn, dr = cfg["n_head"], cfg["d_nope"], cfg["d_rope"]
    with name_scope("attn.qkv"):
        h = layers.rms_norm(
            _fc(h, cfg["q_lora_rank"], nm + "_att_qa.w_0"),
            begin_norm_axis=2, epsilon=_rms_eps(cfg),
            param_attr=ParamAttr(name=nm + "_att_qa_ln_s"))
        q = _fc(h, n_head * (dn + dr), nm + "_att_qb.w_0")
        if cfg.get("mla_scale_q_lora"):
            # both parts of every head, before the rotation
            q = layers.scale(
                q, scale=(cfg["d_model"] / float(cfg["q_lora_rank"])) ** 0.5)
    with name_scope("attn.core"):
        q = layers.reshape(q, [-1, S, n_head, dn + dr])
        q_nope = layers.slice(q, axes=[3], starts=[0], ends=[dn])
        q_rope = layers.slice(q, axes=[3], starts=[dn], ends=[dn + dr])
        if in_place:
            q_rope = _rope(cfg, q_rope, pos, heads_last=True)
        elif S > 1:
            # positions index the axis before the last: [B, H, S, d_rope]
            q_rope = layers.transpose(
                _rope(cfg, layers.transpose(q_rope, perm=[0, 2, 1, 3]), pos),
                perm=[0, 2, 1, 3])
        else:
            q_rope = _rope(cfg, q_rope, pos)
    return q_nope, q_rope


def _mla_latent(cfg, h, nm, S, pos):
    """The two parts of a latent layer's cache row over ``h [B, S, D]``:
    the normed latent ``c [B, S, d_c]`` and the one rotated key part all
    heads share, ``k_r [B, 1, S, d_rope]``."""
    dc, dr = cfg["kv_lora_rank"], cfg["d_rope"]
    with name_scope("attn.qkv"):
        kv = _fc(h, dc + dr, nm + "_att_kva.w_0")
        c = layers.rms_norm(
            layers.slice(kv, axes=[2], starts=[0], ends=[dc]),
            begin_norm_axis=2, epsilon=_rms_eps(cfg),
            param_attr=ParamAttr(name=nm + "_att_kva_ln_s"))
        if cfg.get("mla_scale_kv_lora"):
            # the latent only: k_r is not scaled
            c = layers.scale(c, scale=(cfg["d_model"] / float(dc)) ** 0.5)
    with name_scope("attn.core"):
        k_r = _rope(cfg, layers.reshape(
            layers.slice(kv, axes=[2], starts=[dc], ends=[dc + dr]),
            [-1, 1, S, dr]), pos)
    return c, k_r


def _mla_row(cfg, c, k_r, S):
    """What a latent layer keeps of each token: ``[B, 1, S, d_c +
    d_rope]`` = the normed latent ``c`` beside the one rotated key part
    ``k_r`` all heads share (``_mla_latent``'s two)."""
    with name_scope("attn.core"):
        return layers.concat(
            [layers.reshape(c, [-1, 1, S, cfg["kv_lora_rank"]]), k_r],
            axis=3)


def _mla_expanded(cfg, h, nm, S, pos):
    """The expanded form's operands over ``h [B, S, D]``: ``(q, k, v,
    row)`` with q and k ``[B, H, S, d_nope + d_rope]``, v ``[B, H, S,
    d_v]`` (every head's keys and values rebuilt from the latent) and
    ``row`` the cache rows ``_mla_row`` gives. The training build's
    composed attention takes these; the prefill takes ``_mla_packed``'s."""
    n_head, dn, dv = cfg["n_head"], cfg["d_nope"], cfg["d_v"]
    dc, dr = cfg["kv_lora_rank"], cfg["d_rope"]
    row = _mla_row(cfg, *_mla_latent(cfg, h, nm, S, pos), S)
    with name_scope("attn.qkv"):
        c = layers.reshape(
            layers.slice(row, axes=[3], starts=[0], ends=[dc]), [-1, S, dc])
        kv = layers.transpose(layers.reshape(
            _fc(c, n_head * (dn + dv), nm + "_att_kvb.w_0"),
            [-1, S, n_head, dn + dv]), perm=[0, 2, 1, 3])  # [B,H,S,dn+dv]
    with name_scope("attn.core"):
        k_r = layers.slice(row, axes=[3], starts=[dc], ends=[dc + dr])
        k = layers.concat([
            layers.slice(kv, axes=[3], starts=[0], ends=[dn]),
            layers.expand(k_r, [1, n_head, 1, 1])], axis=3)
        v = layers.slice(kv, axes=[3], starts=[dn], ends=[dn + dv])
    q_nope, q_rope = _mla_q(cfg, h, nm, S, pos)
    with name_scope("attn.core"):
        q = layers.transpose(layers.concat([q_nope, q_rope], axis=3),
                             perm=[0, 2, 1, 3])
    return q, k, v, row


def _mla_packed(cfg, h, nm, S, pos):
    """The expanded form's operands over ``h [B, S, D]`` where the
    projections wrote them, for the fused-attention op's shared key
    part: ``(q [B, S, H d_nope], q_r [B, S, H d_rope], kv [B, S, H
    (d_nope + d_v)], k_r [B, S, d_rope], row)``. ``kv`` is ``kvb``'s
    output as it stands, head ``h``'s keys beside its values; no head's
    keys or values are built, and nothing is transposed."""
    n_head, dn, dv, dr = (cfg[k] for k in ("n_head", "d_nope", "d_v",
                                           "d_rope"))
    c, k_r = _mla_latent(cfg, h, nm, S, pos)
    row = _mla_row(cfg, c, k_r, S)
    with name_scope("attn.qkv"):
        kv = _fc(c, n_head * (dn + dv), nm + "_att_kvb.w_0")
    q_nope, q_rope = _mla_q(cfg, h, nm, S, pos, in_place=True)
    with name_scope("attn.core"):
        return (layers.reshape(q_nope, [-1, S, n_head * dn]),
                layers.reshape(q_rope, [-1, S, n_head * dr]), kv,
                layers.reshape(k_r, [-1, S, dr]), row)


def _mla_scale(cfg):
    """``1 / sqrt(d_nope + d_rope)``, times YaRN's ``yarn_mscale(factor,
    mscale_all_dim) ** 2`` under cfg['rope_scaling']."""
    scale = float(cfg["d_nope"] + cfg["d_rope"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        scale *= _yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def _note_mla_expanded(cfg, kernel):
    """A layer of the expanded form was built: counted beside the
    absorbed form's calls (``kernels/mla_decode.py``). ``kernel`` is the
    op the layer's attention went into; which flash kernel and block that
    op lowers to is ``paddle_flash_block_plans_total``'s to say."""
    from ..observe.families import MLA_ATTENTION_PLANS

    MLA_ATTENTION_PLANS.labels(
        form="expanded", kernel=kernel, block="-",
        widths="%dx%d" % (cfg["d_nope"] + cfg["d_rope"], cfg["d_v"])).inc()


def _layer_tail(cfg, x, y, nm, i, mix=None, dev=None, branch=None,
                first="attn.out", **tally):
    """A layer after its first sub-block's output ``y`` (attention's
    projection, a gated convolution's, a lone mixer's): the residual
    (``mix``: that sub-block's mappings from ``_sub_input``), then the
    FFN or the experts (``tally``: ``_mlp``'s counts) and theirs.
    ``branch`` (cfg['shortcut_moe']) is the builder's one dict that
    carries the routed branch from the even sub-layer, where it forks off
    the norm the dense FFN reads, to the end of the odd one, where it
    joins: computed once, added once.
    ``first`` is the scope class of that first sub-block, which its
    residual add stands under; the second's stands under its own."""
    x = _residual(cfg, x, y, nm + "_post1", mix, first)
    if cfg.get("mixers"):
        return x            # the first sub-block was the layer's one mixer
    h2, mix2 = _sub_input(cfg, x, nm, 2, dev)
    if branch is not None and i % 2 == 0:
        branch["s"] = _routed(cfg, h2, nm, i // 2, **tally)
    f = _mlp(cfg, h2, nm, i, **tally)
    if branch is not None and i % 2:
        with name_scope("moe.experts"):
            f = layers.elementwise_add(f, branch.pop("s"))
    return _residual(cfg, x, f, nm + "_post2", mix2,
                     "ffn" if _is_dense(cfg, i) else "moe.experts")


def _gated_conv(cfg, step, h, nm, i):
    """The gated short convolution over the normed ``h [B, T, D]``
    (``base_config`` has the equations): ``(out [B, T, D], [the cache's
    name])``. In the decode form (``T`` = 1) the carried rows are read
    and shifted in place; otherwise the prompt overwrites them."""
    D, K = cfg["d_model"], int(cfg["conv_taps"])
    with name_scope("conv"):
        proj = _fc(h, 3 * D, nm + "_conv_in.w_0")

        def cut(k):
            return layers.slice(proj, axes=[2], starts=[k * D],
                                ends=[(k + 1) * D])

        rows = step.helper.create_global_variable(
            name=nm + "_cache_x", shape=(step.batch, K - 1, D))
        with stored_dtype(None):      # the taps stay float32, as a vector
            c = layers.causal_conv(
                layers.elementwise_mul(cut(0), cut(2)), K, nm + "_conv",
                rows, step=step.decode, act=False, bias=False)
        return _fc(layers.elementwise_mul(cut(1), c), D,
                   nm + "_conv_out.w_0"), [rows.name]


def _retention(cfg, step, h, nm, i):
    """Power retention over the normed ``h [B, T, D]`` (``base_config``
    has the equations): ``(the merged heads [B, T, n_head * d_head], the
    caches' names)`` for ``_attn_out``. Attention's projections, head
    norm and rotation (at ``step.pos``) in front of the one new core; in
    the decode form (``T`` = 1) state and normaliser are updated in
    place, otherwise the prompt overwrites them."""
    from ..kernels.power import norm_shape, state_shape

    helper, batch, T = step.helper, step.batch, step.T
    n_head, d_head = cfg["n_head"], _d_head(cfg)
    n_kv, _g = _kv_heads_of(cfg)
    q, k, v = _qkv(cfg, h, nm)
    with name_scope("attn.qkv"):
        gate = layers.fc(h, n_kv, num_flatten_dims=2,
                         param_attr=ParamAttr(name=nm + "_att_gamma.w_0"),
                         bias_attr=ParamAttr(name=nm + "_att_gamma.b_0"))
    state = helper.create_global_variable(
        name=nm + "_cache_s", shape=state_shape(batch, n_kv, d_head))
    norm = helper.create_global_variable(
        name=nm + "_cache_z", shape=norm_shape(batch, n_kv, d_head))

    def heads(t, n, which):
        # normed and rotated where the projection's reshape leaves them
        t = _head_norm(cfg, layers.reshape(t, [-1, T, n, d_head]), nm,
                       which)
        if _rotates(cfg, i):
            t = _rope(cfg, t, step.pos, heads_last=True)
        return layers.reshape(t, [-1, T, n * d_head])

    with name_scope("mixer"):
        y = layers.power_retention(
            heads(q, n_head, "q"), heads(k, n_kv, "k"), v, gate, state,
            norm, n_head, n_kv, step=step.decode)
    return y, [state.name, norm.name]


def _delta_mixer(cfg, step, h, nm, i):
    """The gated delta rule over the normed ``h [B, T, D]``
    (``base_config`` has the equations): ``(out [B, T, D], [the two cache
    names])``. In the decode form (``T`` = 1) the state and the
    convolution rows are read and updated in place; otherwise the prompt
    is scanned from a zero state and both are overwritten."""
    from ..kernels.delta import CONV_TAPS, state_shape

    helper, batch, T = step.helper, step.batch, step.T
    Hk, Dk, Hv, Dv, d_conv = delta_widths(cfg)
    with name_scope("mixer"):
        proj = _fc(h, d_conv + Hv * Dv, nm + "_delta_in.w_0")
        ba = _fc(h, 2 * Hv, nm + "_delta_ba.w_0")

        def cut(t, lo, hi):
            return layers.slice(t, axes=[2], starts=[lo], ends=[hi])

        rows = helper.create_global_variable(
            name=nm + "_cache_x", shape=(batch, CONV_TAPS - 1, d_conv))
        state = helper.create_global_variable(
            name=nm + "_cache_s", shape=state_shape(batch, Hv, Dk, Dv))
        with stored_dtype(None):      # the taps stay float32, as a vector
            qkv = layers.causal_conv(cut(proj, 0, d_conv), CONV_TAPS,
                                     nm + "_delta_conv", rows,
                                     step=step.decode, bias=False)
        y = layers.delta_rule(
            cut(qkv, 0, Hk * Dk), cut(qkv, Hk * Dk, 2 * Hk * Dk),
            cut(qkv, 2 * Hk * Dk, d_conv), cut(ba, 0, Hv),
            cut(ba, Hv, 2 * Hv), state, Hk, Hv, nm + "_delta",
            step=step.decode)
        # the norm a head, then the gate: one [Dv] scale the heads share
        y = layers.rms_norm(
            layers.reshape(y, [-1, T, Hv, Dv]), begin_norm_axis=3,
            epsilon=_rms_eps(cfg),
            param_attr=ParamAttr(name=nm + "_delta_norm_s"))
        y = layers.elementwise_mul(
            layers.reshape(y, [-1, T, Hv * Dv]),
            layers.swish(cut(proj, d_conv, d_conv + Hv * Dv)))
        return _fc(y, cfg["d_model"], nm + "_delta_out.w_0"), \
            [rows.name, state.name]


def _mamba_mixer(cfg, step, h, nm, i):
    """A Mamba-1 mixer over the normed ``h [B, T, D]`` (``base_config``
    has the equations): ``(out [B, T, D], [the two cache names])``. In the
    decode form (``T`` = 1) the state and the convolution rows are read
    and updated in place; otherwise the prompt is scanned from a zero
    state, position by position, and both are overwritten."""
    from ..kernels.mamba import state_shape

    helper, batch = step.helper, step.batch
    C, N, R, K = mamba_widths(cfg)
    with name_scope("mixer"):
        proj = _fc(h, 2 * C, nm + "_mamba_in.w_0")

        def cut(t, lo, hi):
            return layers.slice(t, axes=[2], starts=[lo], ends=[hi])

        def inner_norm(t, which):
            return layers.rms_norm(
                t, begin_norm_axis=2, epsilon=_rms_eps(cfg),
                param_attr=ParamAttr(name="%s_mamba_%snorm_s" % (nm, which)))

        rows = helper.create_global_variable(
            name=nm + "_cache_x", shape=(batch, K - 1, C))
        state = helper.create_global_variable(
            name=nm + "_cache_s", shape=state_shape(batch, C, N))
        with stored_dtype(None):      # the taps stay float32, as a vector
            u = layers.causal_conv(proj, K, nm + "_mamba_conv", rows,
                                   step=step.decode, columns=(0, C))
        # [delta | B | C] of the CONVOLVED u, each through its own norm
        dbc = _fc(u, R + 2 * N, nm + "_mamba_x.w_0")
        dt = _fc(inner_norm(cut(dbc, 0, R), "dt"), C, nm + "_mamba_dt.w_0")
        y = layers.mamba_mix(
            u, dt, inner_norm(cut(dbc, R, R + N), "b"),
            inner_norm(cut(dbc, R + N, R + 2 * N), "c"), state, N,
            nm + "_mamba", step=step.decode)
        # the gate does not pass the convolution; no norm behind the scan
        y = layers.elementwise_mul(y, layers.swish(cut(proj, C, 2 * C)))
        return _fc(y, cfg["d_model"], nm + "_mamba_out.w_0"), \
            [rows.name, state.name]


def _sub_input(cfg, x, nm, k, dev=None):
    """What sub-block ``k`` (1: attention, 2: the FFN or the experts) of
    layer ``nm`` reads: ``(the layer's pre-norm of it, mix)``. One
    vector a token: the norm of ``x``, and ``mix`` None. With
    cfg['residual']='mhc' the norm of the learnt mixture of the streams
    (``layers.mhc_pre``), and ``mix`` the token's mappings for
    ``_residual`` to write back with; ``dev`` is the step's health
    reading (``MHC_RES_DEV_VAR``)."""
    mix = None
    if has_streams(cfg):
        with name_scope("mhc"):
            x, mix = layers.mhc_pre(
                x, cfg["hc_mult"], _rms_eps(cfg),
                int(cfg.get("hc_sinkhorn_iters") or 20),
                float(cfg.get("hc_eps") or 1e-6), _hc_clamp(cfg),
                "%s_hc%d" % (nm, k), dev=dev)
    return _norm_of(cfg, x, "%s_pre%d" % (nm, k)), mix


def _residual(cfg, x, y, prefix, mix=None, scope="ffn"):
    """``x + y``, with cfg['sandwich_norm'] the sub-block's output
    normed first (``<prefix>_ln_s``); over streams (``mix`` from
    ``_sub_input``) every stream takes its doubly stochastic share of
    the others and its own share of ``y`` (``layers.mhc_post``).
    ``scope`` is the class of the sub-block that made ``y``: the plain
    add is its last op."""
    if cfg.get("sandwich_norm"):
        y = _norm_of(cfg, y, prefix)
    if mix is not None:
        with name_scope("mhc"):
            return layers.mhc_post(x, y, mix, cfg["hc_mult"])
    with name_scope(scope):
        return layers.elementwise_add(x, y)


def _visibility_bias(ar_rows, pos, lead):
    """[lead, 1, 1, rows] additive bias of a decode step: row r of a
    cache is visible iff ``r <= pos``. For a slab that is "positions up
    to mine"; for a ring of W rows it is the same test, because row r
    holds position ``pos - ((pos - r) mod W)``, which is >= 0 exactly
    when ``r <= pos`` (every row, once pos >= W - 1) — so a row a
    previous tenant of the slot wrote is never visible before this
    sequence has overwritten it."""
    vis = layers.cast(layers.less_equal(ar_rows, pos), "float32")
    bias = layers.scale(layers.elementwise_sub(
        layers.fill_constant([1], "float32", 1.0), vis), scale=-1e9)
    return layers.reshape(bias, [lead, 1, 1, int(ar_rows.shape[-1])])


def _rope_base(cfg):
    return cfg.get("rope_theta") or 10000.0


def _yarn_mscale(factor, mscale):
    return 0.1 * float(mscale) * math.log(factor) + 1.0 \
        if factor > 1 else 1.0


def _yarn(cfg, dim):
    """``layers.rope``'s ``yarn`` for cfg['rope_scaling'] over ``dim``
    rotated dimensions, None without it: the correction range as
    DeepSeek-V3's ``yarn_find_correction_range`` finds it (the dimension
    that turns ``beta`` times within the original context), clamped to
    the ``dim / 2`` frequencies."""
    rs = cfg.get("rope_scaling")
    if not rs:
        return None
    base, orig = _rope_base(cfg), int(rs["original_max_position_embeddings"])

    def at(beta):
        return dim * math.log(orig / (beta * 2 * math.pi)) \
            / (2 * math.log(base))

    top = dim // 2 - 1
    low = min(max(math.floor(at(float(rs.get("beta_fast", 32)))), 0), top)
    high = min(max(math.ceil(at(float(rs.get("beta_slow", 1)))), 0), top)
    factor = float(rs["factor"])
    return dict(factor=factor, low=low, high=high,
                mscale=_yarn_mscale(factor, rs.get("mscale", 1))
                / _yarn_mscale(factor, rs.get("mscale_all_dim", 0)))


def _rope(cfg, x, pos, heads_last=False):
    return layers.rope(x, pos, base=_rope_base(cfg),
                       yarn=_yarn(cfg, int(x.shape[-1])),
                       heads_last=heads_last, rotary_dim=cfg.get("rope_dim"))


def _qk_norm(cfg, q, k, nm):
    """cfg['qk_norm'] is True: RMSNorm of the projected q and k over
    their whole width, before the head split (inference graphs;
    parameter names as multi_head_attention). 'head' is ``_head_norm``'s."""
    if cfg.get("qk_norm") is not True:
        return q, k
    return qk_norm(q, k, nm + "_att", _rms_eps(cfg))


def _routed_pairs_var(cfg, helper):
    """The persistable tally the serving decode step's expert layers add
    to, or None for a dense model."""
    if not cfg.get("n_expert"):
        return None
    return helper.create_global_variable(
        name=ROUTED_PAIRS_VAR, shape=(expert_rows(cfg), cfg["n_expert"]),
        dtype="int32")


def _zero_pairs_var(cfg, helper):
    """The serving decode step's tally of the pairs that cost nothing
    (``ZERO_PAIRS_VAR``), None without identity experts."""
    if not cfg.get("n_zero_expert"):
        return None
    return helper.create_global_variable(
        name=ZERO_PAIRS_VAR, shape=(expert_rows(cfg), 2), dtype="int32")


def _mhc_dev_var(cfg, helper):
    """The serving decode step's health reading of its residual
    mappings (``MHC_RES_DEV_VAR``), None without streams."""
    if not has_streams(cfg):
        return None
    return helper.create_global_variable(name=MHC_RES_DEV_VAR, shape=(1,),
                                         dtype="float32")


def _experts_touched_var(cfg, helper):
    """The second tally of the serving decode step, for a cfg that holds
    a share of its experts (None otherwise)."""
    if not cfg.get("n_expert") or not cfg.get("n_expert_local"):
        return None
    return helper.create_global_variable(
        name=EXPERTS_TOUCHED_VAR,
        shape=(expert_rows(cfg), cfg["n_expert_local"]), dtype="int32")


def _compact_calls_var(cfg, helper, tokens):
    """The tally of a prefill program whose expert calls (``tokens`` a
    call) carry a bound on the held pairs: a cfg that holds a share of
    its experts, at a prompt long enough. None otherwise: such a
    program is the one it was."""
    from ..ops.moe_ops import compact_rows

    if not cfg.get("n_expert") or not cfg.get("n_expert_local") \
            or compact_rows(tokens * cfg["expert_top_k"],
                            router_width(cfg),
                            cfg["n_expert_local"]) is None:
        return None
    return helper.create_global_variable(
        name=COMPACT_CALLS_VAR, shape=(expert_rows(cfg), 2), dtype="int32")


def _mlp(cfg, h, nm, layer, **tally):
    """The block's second half, behind every builder's one call: the
    dense FFN (every layer of a dense model, the first
    cfg['n_dense_layer'] of a sparse one, every sub-layer under
    cfg['shortcut_moe'], whose experts are ``_layer_tail``'s branch), or
    — cfg['n_expert'] — the routed experts (``_routed``; ``tally``: its
    counts)."""
    if _is_dense(cfg, layer):
        return _ffn(h, cfg["d_model"], cfg["d_ff"], nm,
                    act=cfg.get("ffn_act", "relu"),
                    bias=not _new_style(cfg))
    return _routed(cfg, h, nm, layer, **tally)


def _is_dense(cfg, layer):
    """Whether ``_mlp`` of ``layer`` is the dense FFN (scope class
    ``ffn``) and not the routed experts (``moe.*``)."""
    return not cfg.get("n_expert") or has_shortcut(cfg) \
        or layer < (cfg.get("n_dense_layer") or 0)


def _routed(cfg, h, nm, row, counts=None, touched=None, compact=None,
            zero=None):
    """Dropless top-k routing over SwiGLU experts on the normed ``h``,
    with the shared expert, the router's scoring, the identity experts
    and the share of the experts this chip holds (the load-balancing
    loss is not part of the LM loss here). ``row`` is the tallies'."""
    extra = {k: cfg[k] for k in ("router_score", "router_bias",
                                 "route_scale", "n_expert_local",
                                 "expert_first", "n_shared_expert",
                                 "shared_expert_gate", "norm_topk_eps",
                                 "n_zero_expert")
             if cfg.get(k)}
    act = "relu2" if cfg.get("ffn_act") == "relu2" else "swiglu"
    # one op holds the router, the sort and the grouped matmuls: its
    # lowering says which is which (``moe.router`` inside ``moe.experts``)
    with name_scope("moe.experts"):
        if cfg.get("d_expert_in"):
            # the routed experts work in a latent of the token; the router
            # (and the shared expert) read the token itself
            extra["expert_input"] = _fc(h, int(cfg["d_expert_in"]),
                                        nm + "_moe_lat_down.w_0")
        out, _aux = layers.moe_ffn(
            h, cfg["n_expert"], cfg["d_expert"], top_k=cfg["expert_top_k"],
            act=act, dropless=True,
            norm_topk=bool(cfg.get("norm_topk", False)),
            param_prefix=nm + "_moe", counts=counts, counts_row=row,
            touched=touched, compact_calls=compact, zero_pairs=zero, **extra)
        if cfg.get("d_expert_in"):
            out = _fc(out, cfg["d_model"], nm + "_moe_lat_up.w_0")
    if cfg.get("d_shared_expert"):
        with name_scope("moe.shared"):
            out = layers.elementwise_add(out,
                                         _shared_expert(cfg, h, nm, act))
    return out


def _shared_expert(cfg, h, nm, act):
    """The one always-on expert of width cfg['d_shared_expert'] on the
    token itself, of the routed experts' kind."""
    wide = int(cfg["d_shared_expert"])
    up = _fc(h, wide, nm + "_moe_shared_up.w_0")
    if act == "relu2":
        hid = layers.square(layers.relu(up))
    else:
        hid = layers.elementwise_mul(
            layers.swish(_fc(h, wide, nm + "_moe_shared_gate.w_0")), up)
    return _fc(hid, cfg["d_model"], nm + "_moe_shared_down.w_0")


@name_scope("mixer")
def _ssm_mixer(cfg, step, h, nm, i):
    """A state-space mixer over the normed ``h [B, T, D]`` (``base_config``
    has the equations): ``(out [B, T, D], [the two cache names])``. In the
    decode form (``T`` = 1) the state and the convolution rows are read
    and updated in place; otherwise the prompt is scanned from a zero
    state and both are overwritten."""
    from ..initializer import Constant
    from ..kernels.ssm import state_shape

    helper, batch, T = step.helper, step.batch, step.T
    H, P, G, N, K, d_in, d_conv = ssm_widths(cfg)
    proj = _fc(h, 2 * d_in + 2 * G * N + H, nm + "_ssm_in.w_0")

    def cut(t, lo, hi):
        return layers.slice(t, axes=[2], starts=[lo], ends=[hi])

    z = cut(proj, 0, d_in)
    rows = helper.create_global_variable(
        name=nm + "_cache_x", shape=(batch, K - 1, d_conv))
    state = helper.create_global_variable(
        name=nm + "_cache_s", shape=state_shape(batch, H, P, G, N))
    xbc = layers.causal_conv(cut(proj, d_in, d_in + d_conv), K,
                             nm + "_ssm_conv", rows, step=step.decode)
    y = layers.ssm_mix(
        cut(xbc, 0, d_in), cut(proj, d_in + d_conv, d_in + d_conv + H),
        cut(xbc, d_in, d_in + G * N), cut(xbc, d_in + G * N, d_conv),
        state, H, G, N, nm + "_ssm",
        chunk=_ssm_chunk(cfg), step=step.decode)
    # the gate BEFORE the norm, and the norm a group of d_in / G values
    y = layers.reshape(layers.elementwise_mul(y, layers.swish(z)),
                       [-1, T, G, d_in // G])
    inv = layers.rsqrt(layers.scale(
        layers.reduce_mean(layers.square(y), dim=[3], keep_dim=True),
        bias=float(_rms_eps(cfg))))
    y = layers.reshape(layers.elementwise_mul(y, inv), [-1, T, d_in])
    scale = layers.create_parameter(
        [d_in], "float32", name=nm + "_ssm_norm_s",
        default_initializer=Constant(1.0))
    y = layers.elementwise_mul(y, scale)
    return _fc(y, cfg["d_model"], nm + "_ssm_out.w_0"), \
        [rows.name, state.name]


@name_scope("head")
def _final_norm(cfg, x):
    """The shared final norm (training build + decode step use the SAME
    parameter names, so decode can overwrite by name); of the SUM of the
    streams where a token has several."""
    if has_streams(cfg):
        d = cfg["d_model"]
        parts = [layers.slice(x, axes=[2], starts=[i * d],
                              ends=[(i + 1) * d])
                 for i in range(int(cfg["hc_mult"]))]
        x = parts[0]
        for part in parts[1:]:
            x = layers.elementwise_add(x, part)
    if cfg.get("norm", "layer") == "rms":
        return layers.rms_norm(x, begin_norm_axis=2,
                               epsilon=_rms_eps(cfg),
                               param_attr=ParamAttr(name="gpt_ln_f_s"))
    return layers.layer_norm(x, begin_norm_axis=2,
                             param_attr=ParamAttr(name="gpt_ln_f_s"),
                             bias_attr=ParamAttr(name="gpt_ln_f_b"))


def _norm_of(cfg, t, prefix):
    """Per-layer norm for the inference graphs (decode + prefill),
    matching the training build's _prenorm parameter names."""
    with name_scope("norm"):
        if cfg.get("norm", "layer") == "rms":
            return layers.rms_norm(
                t, begin_norm_axis=2, epsilon=_rms_eps(cfg),
                param_attr=ParamAttr(name=prefix + "_ln_s"))
        return layers.layer_norm(
            t, begin_norm_axis=2,
            param_attr=ParamAttr(name=prefix + "_ln_s"),
            bias_attr=ParamAttr(name=prefix + "_ln_b"))


def _kv_heads_of(cfg):
    """(n_kv, group size) with the divisibility contract enforced —
    one check shared by every build path."""
    n_head = cfg["n_head"]
    n_kv = cfg.get("n_kv_head") or n_head
    if n_head % n_kv:
        raise ValueError("n_head %d must divide by n_kv_head %d"
                         % (n_head, n_kv))
    return n_kv, n_head // n_kv


def build(cfg=None, seq_len=256, is_test=False, use_fused_attention=None,
          checkpoints=None, packed=False):
    """Causal LM training graph; returns (avg_loss, feed_names).

    On the fused path, decoder self-attention uses the kernel's causal
    mask with above-diagonal block skipping; the composed path folds a
    dense causal bias. checkpoints collects per-layer recompute
    boundaries for RecomputeOptimizer.

    ``packed=True`` trains on PACKED rows (multiple documents per
    [B, S] row — ``reader.pack_sequences`` builds them): two extra
    feeds, ``segment_ids`` [B, S] (0 = padding; equal ids attend) and
    ``pos_ids`` [B, S] (within-segment positions, driving RoPE or the
    learned table); attention is block-diagonal-causal, and next-token
    targets never cross a segment boundary. Padding-free long-context
    training — no FLOPs spent on pad rows.
    """
    cfg = cfg or base_config()
    _check_cfg(cfg)
    if cfg.get("weight_dtype", "float32") != "float32":
        raise ValueError(
            "cfg['weight_dtype']=%r is the serving programs' (prefill, "
            "decode steps): the training build keeps float32 parameters"
            % (cfg["weight_dtype"],))
    _refuse_streams(cfg, "build", "which have no backward: the training "
                    "build cannot take them")
    if cfg.get("mixers"):
        raise ValueError(
            "build: cfg['mixers'] (one mixer a layer: %s) is the serving "
            "programs' (prefill, decode steps) — the scan of an 'ssm' "
            "layer has no backward and the training build keeps the "
            "attention-then-FFN pair" % (MIXER_KINDS,))
    _refuse_state(cfg, "build", "which is the serving programs' (prefill, "
                  "decode steps): a layer that carries a state has no "
                  "backward")
    _refuse_shortcut(cfg, "build", "which is the serving programs' "
                     "(prefill, decode steps): the training build keeps "
                     "one attention and one FFN-or-experts a layer")
    new_style = _new_style(cfg)
    if new_style:
        # the layers of ``_NEW_LAYER_KEYS`` train on COMPOSED attention
        # (``_block``): a sliding layer's band is a bias there and
        # autodiff gives its gradients; the flash backward kernels have
        # no band (the serving prefill needs the forward only)
        use_fused_attention = False
    if use_fused_attention is None:
        from ..ops.attention import fused_attention_enabled

        use_fused_attention = fused_attention_enabled()
    ids = layers.data("ids", [seq_len], dtype="int64")
    seg = pos_feed = None
    if packed:
        seg = layers.data("segment_ids", [seq_len], dtype="int64")
        pos_feed = layers.data("pos_ids", [seq_len], dtype="int64")
    with name_scope("attn.core"):      # the masks every layer shares
        self_bias, self_causal, self_seg, pack_bias = _train_masks(
            ids, seg, seq_len, packed, use_fused_attention)

    use_rope = cfg.get("pos_emb", "learned") == "rope"
    with name_scope("embed"):
        x, rope_pos = _train_embed(cfg, ids, pos_feed, seq_len, packed,
                                   use_rope, is_test)

    norm = cfg.get("norm", "layer")
    band_bias = {None: self_bias}     # window -> pack + band bias
    for i in range(cfg["n_layer"]):
        nm = "gpt_%d" % i
        if new_style:
            window = layer_window(cfg, i)
            if window is not None and window >= seq_len:
                window = None
            if window not in band_bias:
                with name_scope("attn.core"):
                    band_bias[window] = layers.elementwise_add(
                        pack_bias, _band_bias(seq_len, window))
            with _layer_scope(cfg, i):
                x = _block(cfg, x, i, seq_len, band_bias[window], rope_pos,
                           is_test)
            if checkpoints is not None:
                checkpoints.append(x)
            continue
        with _layer_scope(cfg, i):
            x = _prenorm(x, lambda h, nm=nm: multi_head_attention(
                h, h, self_bias, cfg["d_model"], cfg["n_head"],
                cfg["dropout"], is_test, nm + "_att", use_fused_attention,
                causal=self_causal, n_kv_head=cfg.get("n_kv_head"),
                rope_pos=rope_pos, segment_ids=self_seg,
                qk_norm_eps=_rms_eps(cfg) if cfg.get("qk_norm") else None,
                rope_base=_rope_base(cfg)),
                cfg["dropout"], is_test, nm + "_pre1", norm=norm,
                rms_eps=_rms_eps(cfg), tail="attn.out")
            x = _prenorm(x, lambda h, nm=nm, i=i: _mlp(cfg, h, nm, i),
                         cfg["dropout"], is_test, nm + "_pre2", norm=norm,
                         rms_eps=_rms_eps(cfg),
                         tail="ffn" if _is_dense(cfg, i) else "moe.experts")
        if checkpoints is not None:
            checkpoints.append(x)
    x = _final_norm(cfg, x)

    logits = _lm_head(cfg, x)
    with name_scope("loss"):
        avg = _train_loss(logits, ids, seg, seq_len, packed)
    return avg, (["ids", "segment_ids", "pos_ids"] if packed
                 else ["ids"])


def _train_masks(ids, seg, seq_len, packed, use_fused_attention):
    """``build``'s attention masks: ``(self_bias, self_causal, self_seg,
    pack_bias)``; the last is the pad or pack mask alone, which a
    sliding layer's band is added to."""
    self_seg = pack_bias = None
    if use_fused_attention:
        if packed:
            # the fused op takes the segment ids DIRECTLY — no [S,S]
            # pack bias is ever materialized; single-device it folds to
            # a mask once, under an sp mesh the ids ride the ring
            # (ops/attention.py SegmentIds, ring_attention seg=)
            self_bias, self_causal, self_seg = None, True, seg
        else:
            self_bias, self_causal = _pad_bias(ids), True
    else:
        if packed:
            # composed fallback: materialized same-segment visibility
            # (and key must be real): [B, 1, S, S]
            a = layers.reshape(seg, [-1, 1, seq_len, 1])
            b = layers.reshape(seg, [-1, 1, 1, seq_len])
            same = layers.cast(layers.equal(a, b), "float32")
            realk = layers.cast(layers.greater_than(
                b, layers.fill_constant([1], "int64", 0)), "float32")
            keep = layers.elementwise_mul(same, realk)
            pack_bias = layers.scale(layers.elementwise_sub(
                layers.fill_constant([1], "float32", 1.0), keep),
                scale=-1e9)
        else:
            pack_bias = _pad_bias(ids)
        self_bias = layers.elementwise_add(pack_bias,
                                           _causal_bias(seq_len))
        self_causal = False
    return self_bias, self_causal, self_seg, pack_bias


def _train_embed(cfg, ids, pos_feed, seq_len, packed, use_rope, is_test):
    """``build``'s input rows: ``(x [B, S, D], rope_pos or None)``."""
    word = layers.embedding(ids, [cfg["vocab"], cfg["d_model"]],
                            param_attr=ParamAttr(name="gpt_word_emb"))
    if cfg.get("emb_scale"):
        word = layers.scale(word, scale=float(cfg["emb_scale"]))
    rope_pos = None
    if use_rope:
        # positions enter through the per-layer q/k rotation instead of
        # an additive learned table; packed rows reset per segment
        x = word
        rope_pos = (pos_feed if packed
                    else layers.range(0, seq_len, 1, "int64"))
    else:
        pos_ids = (pos_feed if packed
                   else layers.reshape(
                       layers.range(0, seq_len, 1, "int64"),
                       [1, seq_len]))
        pos = layers.embedding(pos_ids,
                               [cfg["max_length"], cfg["d_model"]],
                               param_attr=ParamAttr(name="gpt_pos_emb"))
        x = layers.elementwise_add(word, pos)
    if cfg["dropout"]:
        x = layers.dropout(x, cfg["dropout"], is_test=is_test)
    return x, rope_pos


def _train_loss(logits, ids, seg, seq_len, packed):
    """``build``'s next-token cross entropy over the valid positions."""
    def shift_left(t):
        # t[:, 1:] with a 0 (pad) in the vacated last column
        return layers.concat([
            layers.slice(t, axes=[1], starts=[1], ends=[seq_len]),
            layers.fill_constant_batch_size_like(t, [-1, 1], "int64", 0),
        ], axis=1)

    # next-token targets: ids shifted left; the last position has no
    # target, and pad positions (id 0) are masked out of the loss
    labels = shift_left(ids)
    cost = layers.softmax_with_cross_entropy(
        logits, layers.reshape(labels, [-1, seq_len, 1]))
    valid = layers.cast(
        layers.greater_than(
            labels, layers.fill_constant([1], "int64", 0)), "float32")
    if packed:
        # a target in a DIFFERENT segment (the next document's first
        # token) must not train this position
        same_seg = layers.cast(layers.equal(shift_left(seg), seg),
                               "float32")
        valid = layers.elementwise_mul(valid, same_seg)
    valid = layers.reshape(valid, [-1, seq_len, 1])
    total = layers.reduce_sum(layers.elementwise_mul(cost, valid))
    count = layers.elementwise_max(
        layers.reduce_sum(valid), layers.fill_constant([1], "float32", 1.0))
    return layers.elementwise_div(total, count)


def _band_bias(seq_len, window):
    """[1,1,S,S] additive bias of a sliding layer: 0 where key j is among
    query i's last ``window`` positions (``0 <= i - j < window``),
    -1e9 elsewhere."""
    r = layers.range(0, seq_len, 1, "int64")
    row = layers.unsqueeze(r, [1])           # [S,1] query index i
    col = layers.unsqueeze(r, [0])           # [1,S] key index j
    behind = layers.elementwise_sub(row, col)            # i - j  [S,S]
    allowed = layers.elementwise_mul(
        layers.cast(layers.less_equal(col, row), "float32"),
        layers.cast(layers.less_than(
            behind, layers.fill_constant([1], "int64", int(window))),
            "float32"))
    bias = layers.scale(layers.elementwise_sub(
        layers.fill_constant([1], "float32", 1.0), allowed), scale=-1e9)
    return layers.unsqueeze(layers.unsqueeze(bias, [0]), [0])


def _block(cfg, x, i, seq_len, bias, rope_pos, is_test):
    """One layer of the training build for a cfg with the newer keys,
    from the same helpers (and so the same parameter names) as the
    prefill: composed attention under ``bias`` (pad or pack mask plus the
    layer's causal triangle or band), then the FFN or the experts."""
    from .transformer import repeat_kv_heads

    nm = "gpt_%d" % i
    n_head, d_head = cfg["n_head"], _d_head(cfg)
    n_kv, _g = _kv_heads_of(cfg)
    drop = cfg["dropout"]

    def dropped(t):
        return layers.dropout(t, drop, is_test=is_test) if drop else t

    h = _norm_of(cfg, x, nm + "_pre1")
    if has_latent(cfg):
        q, k, v, _row = _mla_expanded(cfg, h, nm, seq_len, rope_pos)
        scale, d_head = _mla_scale(cfg), cfg["d_v"]
        _note_mla_expanded(cfg, "composed")
    else:
        q, k, v = _qkv(cfg, h, nm)
        with name_scope("attn.core"):
            q = _heads(cfg, q, nm, seq_len, n_head, "q")
            k = _heads(cfg, k, nm, seq_len, n_kv, "k")
            v = _heads(cfg, v, nm, seq_len, n_kv)
            if _rotates(cfg, i):
                q, k = _rope(cfg, q, rope_pos), _rope(cfg, k, rope_pos)
            k = repeat_kv_heads(k, n_kv, n_head, seq_len, d_head)
            v = repeat_kv_heads(v, n_kv, n_head, seq_len, d_head)
        scale = d_head ** -0.5
    with name_scope("attn.core"):
        scores = layers.elementwise_add(
            layers.matmul(q, k, transpose_y=True, alpha=scale), bias)
        ctxv = layers.matmul(dropped(layers.softmax(scores)), v)
        ctxv = layers.reshape(layers.transpose(ctxv, perm=[0, 2, 1, 3]),
                              [-1, seq_len, n_head * d_head])
    y = _attn_out(cfg, h, ctxv, nm)
    with name_scope("attn.out"):
        y = dropped(y)
    x = _residual(cfg, x, y, nm + "_post1", scope="attn.out")
    dense = "ffn" if _is_dense(cfg, i) else "moe.experts"
    f = _mlp(cfg, _norm_of(cfg, x, nm + "_pre2"), nm, i)
    with name_scope(dense):
        f = dropped(f)
    return _residual(cfg, x, f, nm + "_post2", scope=dense)


class _Step(NamedTuple):
    """What a builder hands every layer of its program: the decode form
    (``T`` = 1, caches updated in place at ``pos``) or the prefill
    (``T`` = the prompt's length, caches overwritten from row 0)."""
    helper: Any
    batch: int
    T: int
    decode: bool
    max_len: int
    pos: Any            # the step's position feed; the prompt's range
    tally: dict         # ``_mlp``'s counts
    branch: Optional[dict]      # ``_layer_tail``'s, under shortcut_moe
    cache_names: list           # every layer's, as ``_layer`` adds them
    zero: Any = None    # prefill: the cache writes' row 0
    fused: bool = False         # prefill: attention through the fused op
    bias: Any = None    # prefill: the causal bias where it is composed
    biases: Any = None          # decode: cache rows -> visibility bias
    ring_pos: Any = None        # decode: ring rows -> the row of ``pos``
    dev: Any = None     # decode: ``MHC_RES_DEV_VAR``


@_stores_weights
def build_prefill_step(cfg=None, batch=1, prompt_len=8, max_len=None):
    """Prompt prefill as ONE dispatch: forward over the whole [B, P]
    prompt with causal attention, writing every layer's K/V slab into
    the caches at positions 0..P-1 (dynamic_update_slice of the full
    slab — one in-place write per layer, not P), and returning logits
    [B, P, vocab]. Pair with ``build_decode_step`` over the SAME scope
    (shared cache/weight names) and drive both via ``generate(...,
    prefill_prog=...)`` — prompt latency drops from P dispatches to 1.

    Returns (logits_var, cache_names). The program also holds the last
    prompt position's row as ``LAST_LOGITS_VAR`` [B, vocab] and its
    argmax as ``NEXT_TOKEN_VAR`` [B]: an admission fetches one of the
    two by name and the [B, P, vocab] logits never leave the device."""
    cfg = cfg or base_config()
    _check_cfg(cfg)
    if max_len is None:
        max_len = cfg["max_length"]
    P = int(prompt_len)
    assert 0 < P <= max_len, (P, max_len)
    _kv_heads_of(cfg)
    from ..layer_helper import LayerHelper

    helper = LayerHelper("gpt_prefill")
    tokens = layers.data("tokens", [P], dtype="int64")
    with name_scope("attn.core"):       # the cache writes' row 0
        zero = layers.fill_constant([1], "int64", 0)

    learned = cfg.get("pos_emb", "learned") == "learned"
    word = _embed(cfg, tokens, [-1, P, cfg["d_model"]])
    with name_scope("embed"):
        pos_range = layers.range(0, P, 1, "int64")
        if not learned:
            x = word
        else:
            pos = layers.reshape(
                layers.embedding(layers.reshape(pos_range, [1, P]),
                                 [cfg["max_length"], cfg["d_model"]],
                                 param_attr=ParamAttr(name="gpt_pos_emb")),
                [1, P, cfg["d_model"]])
            x = layers.elementwise_add(word, pos)

    # a cfg with two kinds of layer prefills through the fused attention
    # op (causal, a window where the prompt is longer than it, grouped
    # heads): the flash forward at P >= flash_min_seq, whose [P, P]
    # scores never exist. Every other cfg composes them, as it did
    # (so does latent attention: its expanded form, q and k wider than v)
    fused = bool(cfg.get("layer_types")) or has_latent(cfg) \
        or bool(cfg.get("mixers"))
    with name_scope("attn.core"):
        bias = None if fused else _causal_bias(P)
    # only the serving decode step tallies its routing; a share's long
    # prefill tallies which length its expert calls ran at
    tally = {"compact": _compact_calls_var(cfg, helper, batch * P)}
    step = _Step(helper, batch, T=P, decode=False, max_len=max_len,
                 pos=pos_range, tally=tally,
                 branch={} if has_shortcut(cfg) else None, cache_names=[],
                 zero=zero, fused=fused, bias=bias)
    for i in range(cfg["n_layer"]):
        with _layer_scope(cfg, i):
            x = _layer(cfg, step, x, i)

    x = _final_norm(cfg, x)
    logits = _lm_head(cfg, x)
    with name_scope("head"):
        if has_streams(cfg) or has_state(cfg) or cfg.get("mixers") \
                or has_shortcut(cfg):
            # the one row an admission needs, cut BEFORE the head: a plan
            # that fetches the row or its argmax holds a [1, vocab] head,
            # and only one that fetches ``logits`` (``generate``) the
            # [P, vocab] one (DCE) — 4.3 GB at P 8,192 and 131,072 ids
            last = _lm_head(cfg, layers.slice(x, axes=[1], starts=[P - 1],
                                              ends=[P]))
        else:
            # the older configurations' (their op lists are pinned): cut
            # AFTER the head, the same numbers as logits[:, P - 1]
            # whatever order the head reduces in
            last = layers.slice(logits, axes=[1], starts=[P - 1], ends=[P])
        last = _expose(layers.reshape(last, [-1, cfg["vocab"]]),
                       LAST_LOGITS_VAR)
    _greedy_token(last)
    return logits, step.cache_names


def _kv_caches(cfg, step, nm, i):
    """Layer ``i``'s key and value caches and their rows (a slab's or a
    ring's). GQA: they store n_kv heads — H/Hkv-times less decode HBM,
    the whole point of grouped-query attention at inference."""
    rows = cache_rows(cfg, i, step.max_len)
    shape = (step.batch, _kv_heads_of(cfg)[0], rows, _d_head(cfg))
    return [step.helper.create_global_variable(name=nm + sfx, shape=shape)
            for sfx in ("_cache_k", "_cache_v")] + [rows]


def _attention_prompt(cfg, step, h, nm, i):
    """Keys-and-values attention over a prompt's normed ``h [B, P, D]``:
    ``(the merged heads [B, P, n_head * d_head], the two caches'
    names)``, the prompt's rotated keys and its values left in the
    layer's slab or ring."""
    from .transformer import repeat_kv_heads

    n_head, d_head, P = cfg["n_head"], _d_head(cfg), step.T
    n_kv, _g = _kv_heads_of(cfg)
    ck, cv, rows = _kv_caches(cfg, step, nm, i)
    q, k, v = _qkv(cfg, h, nm)
    with name_scope("attn.core"):
        q = _heads(cfg, q, nm, P, n_head, "q")
        k = _heads(cfg, k, nm, P, n_kv, "k")
        v = _heads(cfg, v, nm, P, n_kv)
        if _rotates(cfg, i):
            q = _rope(cfg, q, step.pos)
            k = _rope(cfg, k, step.pos)
        # one slab write per layer: the cache holds rotated keys
        _prefill_cache_write(ck, k, P, rows, step.zero)
        _prefill_cache_write(cv, v, P, rows, step.zero)
        if step.fused:
            window = layer_window(cfg, i)
            ctxv = layers.fused_attention(
                q, k, v, scale=d_head ** -0.5, causal=True,
                window=window if window is not None and window < P
                else None)
        else:
            kr = repeat_kv_heads(k, n_kv, n_head, P, d_head)
            vr = repeat_kv_heads(v, n_kv, n_head, P, d_head)
            scores = layers.matmul(q, kr, transpose_y=True,
                                   alpha=d_head ** -0.5)   # [B,H,P,P]
            scores = layers.elementwise_add(scores, step.bias)
            w = layers.softmax(scores)
            ctxv = layers.matmul(w, vr)                    # [B,H,P,Dh]
        ctxv = layers.transpose(ctxv, perm=[0, 2, 1, 3])
        ctxv = layers.reshape(ctxv, [-1, P, n_head * d_head])
    return ctxv, [ck.name, cv.name]


def _latent_prompt(cfg, step, h, nm, i):
    """Latent attention over a prompt's normed ``h [B, P, D]``, the
    expanded form through the flash forward: ``(the merged heads, [the
    cache's name])``. What stays of the prompt is ONE slab of latent
    rows."""
    P, rows = step.T, cache_rows(cfg, i, step.max_len)
    cc = step.helper.create_global_variable(
        name=nm + "_cache_c",
        shape=(step.batch, 1, rows, latent_width(cfg)))
    q, q_r, kv, k_r, row = _mla_packed(cfg, h, nm, P, step.pos)
    with name_scope("attn.core"):
        _prefill_cache_write(cc, row, P, rows, step.zero)
        # compute-bound at 128 heads: the kernel from one lane tile
        # on (every prompt length of a cell runs, and is measured
        # as, the one attention form) and on bfloat16 MXU operands,
        # as kernels/moe_gmm.py rounds its float32 ones. The op reads
        # q, k and v where the projections wrote them and writes the
        # context where the output projection reads it
        ctxv = layers.fused_attention(
            q, kv, kv, scale=_mla_scale(cfg), causal=True,
            mxu_dtype="bfloat16", flash_min_seq=128, n_head=cfg["n_head"],
            q_r=q_r, k_r=k_r)
        _note_mla_expanded(cfg, "fused_attention")
    return ctxv, [cc.name]


def _prefill_cache_write(cache, kv, P, rows, zero):
    """Leave a prompt's keys (or values) ``kv [B, n_kv, P, Dh]`` in a
    cache of ``rows`` rows. They fit a slab, and a ring no shorter than
    the prompt, from row 0 on. A prompt longer than the ring leaves its
    LAST ``rows`` positions, each in the row the decode step will look
    for it: position p in row ``p mod rows`` — a rotation of the tail
    by ``(P - rows) mod rows``, static for a prompt length."""
    if P > rows:
        kv = layers.slice(kv, axes=[2], starts=[P - rows], ends=[P])
        shift = (P - rows) % rows
        if shift:
            kv = layers.concat([
                layers.slice(kv, axes=[2], starts=[rows - shift],
                             ends=[rows]),
                layers.slice(kv, axes=[2], starts=[0],
                             ends=[rows - shift])], axis=2)
    layers.kv_cache_write(cache, kv, zero)


@_stores_weights
def build_decode_step(cfg=None, batch=1, max_len=None,
                      per_slot_pos=False):
    """Incremental decoding step graph with donated KV caches.

    Feeds: token [B, 1] int64 (the current position's input token) and
    pos int64 — a [1] scalar shared by every row (the classic lockstep
    loop, default) or, with ``per_slot_pos=True``, a [B, 1] per-row
    position so each cache slot advances independently (the serving
    engine's continuous-batching step — see
    ``build_serving_decode_step``). Per-layer K/V caches live as
    persistable [B, n_kv_head (default n_head), max_len, Dh] state the
    executor DONATES — the `kv_cache_write` update is in-place on
    device, so a decode step moves O(1) data (GQA shrinks the cache
    H/Hkv-fold; RoPE caches store rotated keys). Weights share the
    training graph's parameter names
    (gpt_*), so after running this program's startup, overwrite them
    with trained values (same names) — see `generate`.

    Returns (logits_var, cache_names). Fetch logits [B, 1, vocab], or
    ``NEXT_TOKEN_VAR`` [B] int32, their argmax a row, by name.
    """
    cfg = cfg or base_config()
    _check_cfg(cfg)
    if max_len is None:
        max_len = cfg["max_length"]
    learned = cfg.get("pos_emb", "learned") == "learned"
    if learned and max_len > cfg["max_length"]:
        # the learned gpt_pos_emb table has cfg['max_length'] rows;
        # positions past it would CLAMP in the lookup (XLA gather) and
        # silently corrupt every token after that point
        raise ValueError(
            "max_len=%d exceeds the learned position table "
            "(cfg['max_length']=%d) — raise max_length or use "
            "pos_emb='rope'" % (max_len, cfg["max_length"]))
    d_model = cfg["d_model"]
    from ..layer_helper import LayerHelper

    helper = LayerHelper("gpt_decode")
    token = layers.data("token", [1], dtype="int64")
    if per_slot_pos:
        pos = layers.data("pos", [1], dtype="int64")   # batched: [B, 1]
    else:
        pos = layers.data("pos", [1], dtype="int64",
                          append_batch_size=False)     # one shared [1]

    # [B,1] ids -> the [B,1,D] step layout
    word = _embed(cfg, token, [-1, 1, d_model])
    if not learned:
        x = word            # positions rotate q/k below, or are not added
    else:
        with name_scope("embed"):
            pos_ids = pos if per_slot_pos else layers.reshape(pos, [1, 1])
            posv = layers.reshape(
                layers.embedding(pos_ids, [cfg["max_length"], d_model],
                                 param_attr=ParamAttr(name="gpt_pos_emb")),
                [-1, 1, d_model] if per_slot_pos else [1, 1, d_model])
            x = layers.elementwise_add(word, posv)    # [B, 1, D]

    # visibility over cache rows: positions <= pos attend, later rows
    # mask out — zeros from init in the lockstep loop; per-slot, row b
    # attends to `cache row <= pos[b]`, so a retired neighbor's stale
    # rows never leak into a live slot's attention. One bias a cache
    # shape: a slab's over max_len rows, a ring's over its window
    # (``_visibility_bias`` says why the same test serves a ring; a
    # latent layer's visibility is ``mla_decode``'s own)
    pos_b, biases, ring_pos = None, {}, {}
    for rows in dict.fromkeys(
            cache_rows(cfg, i, max_len) for i in range(cfg["n_layer"])
            if kind_of(cfg, i).name == "attention"):
        with name_scope("attn.core"):    # one a step, for all its layers
            ar = layers.reshape(layers.range(0, rows, 1, "int64"),
                                [1, rows])
            if pos_b is None:
                pos_b = pos if per_slot_pos \
                    else layers.reshape(pos, [1, 1])
            biases[rows] = _visibility_bias(ar, pos_b,
                                            -1 if per_slot_pos else 1)
            if rows < max_len:
                # the ring row a position lives in
                ring_pos[rows] = layers.elementwise_mod(
                    pos, layers.fill_constant([1], "int64", rows))

    _kv_heads_of(cfg)
    routed = _routed_pairs_var(cfg, helper) if per_slot_pos else None
    touched = _experts_touched_var(cfg, helper) if per_slot_pos else None
    dev = _mhc_dev_var(cfg, helper) if per_slot_pos else None
    # only the serving step tallies its routing
    tally = {"counts": routed, "touched": touched,
             "zero": _zero_pairs_var(cfg, helper) if per_slot_pos else None}
    step = _Step(helper, batch, T=1, decode=True, max_len=max_len, pos=pos,
                 tally=tally, branch={} if has_shortcut(cfg) else None,
                 cache_names=[], biases=biases, ring_pos=ring_pos, dev=dev)
    for i in range(cfg["n_layer"]):
        with _layer_scope(cfg, i):
            x = _layer(cfg, step, x, i)

    x = _final_norm(cfg, x)
    logits = _lm_head(cfg, x)
    with name_scope("head"):
        _greedy_token(layers.reshape(logits, [-1, cfg["vocab"]]))
    return logits, step.cache_names


def _latent_step(cfg, step, h, nm, i):
    """Latent attention of a decode step over the normed ``h [B, 1,
    D]``, the absorbed form: one latent row written, and every head
    reads keys AND values out of the slot's one slab."""
    pos = step.pos
    cc = step.helper.create_global_variable(
        name=nm + "_cache_c",
        shape=(step.batch, 1, cache_rows(cfg, i, step.max_len),
               latent_width(cfg)))
    row = _mla_row(cfg, *_mla_latent(cfg, h, nm, 1, pos), 1)
    with name_scope("attn.core"):
        cc = layers.kv_cache_write(cc, row, pos)
    q_nope, q_rope = _mla_q(cfg, h, nm, 1, pos)
    with name_scope("attn.core"):
        ctxv = layers.mla_decode(
            q_nope, q_rope, cc, pos,
            [cfg["kv_lora_rank"],
             cfg["n_head"] * (cfg["d_nope"] + cfg["d_v"])],
            d_v=cfg["d_v"], scale=_mla_scale(cfg),
            param_attr=ParamAttr(name=nm + "_att_kvb.w_0"))
    return ctxv, [cc.name]


def _attention_step(cfg, step, h, nm, i):
    """Keys-and-values attention of a decode step over the normed ``h
    [B, 1, D]``: ``(the merged heads [B, 1, n_head * d_head], the two
    caches' names)``."""
    n_head, d_head, pos = cfg["n_head"], _d_head(cfg), step.pos
    n_kv, g = _kv_heads_of(cfg)
    ck, cv, rows = _kv_caches(cfg, step, nm, i)
    names = [ck.name, cv.name]
    q, k, v = _qkv(cfg, h, nm)
    with name_scope("attn.core"):
        k = _heads(cfg, k, nm, 1, n_kv, "k")            # [B,Hkv,1,Dh]
        v = _heads(cfg, v, nm, 1, n_kv)
        rotates = _rotates(cfg, i)
        if rotates:
            # rotate at THIS position; the cache stores rotated keys,
            # so dot products against it are relative-position exact.
            # Per-slot [B, 1] positions broadcast per-row angles over
            # the head axis — each slot rotates at ITS position
            k = _rope(cfg, k, pos)
        at = step.ring_pos.get(rows, pos)
        ck = layers.kv_cache_write(ck, k, at)    # per-slot rows when
        cv = layers.kv_cache_write(cv, v, at)    # pos is [B]/[B, 1]
        # GQA grouped attention: query heads fold as [B, Hkv, g, Dh]
        # (h = kv*g + j, row-major — the same h//g mapping as
        # transformer.repeat_kv_heads) and batch-matmul DIRECTLY
        # against the n_kv-head cache: no H-head repeated cache is
        # ever materialized, so the per-step working set stays at the
        # n_kv size too. g == 1 degenerates to plain MHA.
        q = _head_norm(cfg, layers.reshape(q, [-1, n_kv, g, d_head]),
                       nm, "q")
        if rotates:
            # a [1] pos yields [1, Dh/2] sin/cos that broadcast over
            # every leading layout ([B, 1] per-slot pos: [B,1,1,Dh/2])
            # — rotating the folded q directly is exact: all g query
            # heads of a row sit at that row's position
            q = _rope(cfg, q, pos)
        scores = layers.matmul(q, ck, transpose_y=True,
                               alpha=d_head ** -0.5)    # [B,Hkv,g,S]
        scores = layers.elementwise_add(scores, step.biases[rows])
        w = layers.softmax(scores)
        ctxv = layers.matmul(w, cv)                     # [B,Hkv,g,Dh]
        ctxv = layers.reshape(ctxv, [-1, 1, n_head * d_head])
    return ctxv, names


def _by_form(prompt, decode):
    """A row's builder where the two forms share no op: ``prompt`` over
    a whole prompt, ``decode`` over one token against the caches."""
    def build(cfg, step, h, nm, i):
        return (decode if step.decode else prompt)(cfg, step, h, nm, i)
    return build


def _experts_mixer(cfg, step, h, nm, i):
    return _mlp(cfg, h, nm, i, **step.tally), []


def _ssm_chunk(cfg, P=None):
    return int(cfg.get("ssm_chunk") or 128)


def _power_chunk(cfg, P):
    from ..kernels.power import scan_chunk

    return scan_chunk(P)


def _delta_chunk(cfg, P):
    from ..kernels.delta import scan_chunk

    return scan_chunk(P)


def _mamba_chunk(cfg, P):
    from ..kernels.mamba import scan_block

    return scan_block(P)


def _mamba_state(update, made):
    """The state, and the rows of the convolution whose output the update
    reads."""
    return update.input("State") + made[update.input("X")[0]].input("Rows")


def _power_state(update, made):
    return update.input("State") + update.input("Norm")


def _delta_state(update, made):
    """The state, and the rows of the convolution whose output the
    update reads (through the slice that cuts q)."""
    cut = made[update.input("Q")[0]]
    return update.input("State") \
        + made[cut.input_names()[0]].input("Rows")


class _LayerKind(NamedTuple):
    """One kind of first sub-block a layer can have: a row of
    ``LAYER_KINDS``."""
    name: str           # its entry of cfg[key]
    layer: str          # what the checks call a layer of it
    key: Optional[str]  # 'mixers' | 'layer_types'; None: without an entry
    build: Callable     # (cfg, step, normed input, nm, i) -> (y, [caches])
    scope: str          # the class its residual add stands under
    merged: bool        # y is the merged heads: ``_attn_out`` projects it
    caches: Dict[str, str]      # suffix -> 'rows' | 'latent' | 'state'
    kept: Optional[Callable] = None     # (cfg) -> state_refusal's words
    needs: Tuple[str, ...] = ()         # cfg keys a layer needs >= 1
    extra: Tuple[str, ...] = ()         # its other keys: none without it
    refuses: Tuple[Tuple[str, str], ...] = ()   # (key it takes none of, why)
    check: Optional[Callable] = None    # (cfg): its own arithmetic
    chunk: Optional[Callable] = None    # (cfg, P) -> its prefill scan's chunk
    chunks: Any = None                  # the counter of chunks scanned
    state_bytes: Any = None             # the gauge of its state's bytes
    update_op: Optional[str] = None     # the decode op that updates the state
    state: Optional[Callable] = None    # (that op, {variable: the op that
    #                                     made it}) -> the state's variables
    orders: bool = False    # a recurrence whose model carries no position:
    #                         beside it ``pos_emb='none'`` stands


_NO_KINDS = ("attn", "latent attention has no layer kinds")
_NO_FIRST = ("mixers", "one mixer a layer has no first sub-block")
_NO_FORK = ("shortcut_moe", "the branch forks behind an attention sub-block")
LAYER_KINDS = {kind.name: kind for kind in (
    _LayerKind("attention", "an attention layer", None,
               _by_form(_attention_prompt, _attention_step), "attn.out",
               True, {"_cache_k": "rows", "_cache_v": "rows"}),
    _LayerKind("latent", "a latent attention layer", None,
               _by_form(_latent_prompt, _latent_step), "attn.out", True,
               {"_cache_c": "latent"}),
    _LayerKind("ssm", "an 'ssm' layer", "mixers", _ssm_mixer, "mixer", False,
               {"_cache_x": "state", "_cache_s": "state"}, _ssm_kept,
               needs=_SSM_KEYS, extra=("ssm_chunk",), check=_check_ssm,
               chunk=_ssm_chunk, orders=True),
    _LayerKind("experts", "an 'experts' layer", "mixers", _experts_mixer,
               "moe.experts", False, {}, check=_check_experts),
    _LayerKind("retention", "a 'retention' layer", "layer_types", _retention,
               "attn.out", True, {"_cache_s": "state", "_cache_z": "state"},
               _retention_kept,
               refuses=(_NO_KINDS,
                        ("residual", "the retention core is not written over "
                         "several residual streams"), _NO_FORK),
               chunk=_power_chunk, chunks=POWER_CHUNKS,
               state_bytes=POWER_STATE_BYTES, update_op="power_update",
               state=_power_state),
    _LayerKind("delta", "a 'delta' layer", "layer_types", _delta_mixer,
               "mixer", False, {"_cache_x": "state", "_cache_s": "state"},
               _delta_kept, needs=_DELTA_KEYS, check=_check_delta,
               refuses=(_NO_KINDS,
                        ("residual", "the delta rule is not written over "
                         "several residual streams"), _NO_FIRST, _NO_FORK),
               chunk=_delta_chunk, chunks=DELTA_CHUNKS,
               state_bytes=DELTA_STATE_BYTES, update_op="delta_update",
               state=_delta_state),
    _LayerKind("mamba", "a 'mamba' layer", "layer_types", _mamba_mixer,
               "mixer", False, {"_cache_x": "state", "_cache_s": "state"},
               _mamba_kept, needs=_MAMBA_KEYS, check=_check_mamba,
               refuses=(_NO_KINDS,
                        ("residual", "the selective scan is not written "
                         "over several residual streams"), _NO_FIRST,
                        _NO_FORK),
               chunk=_mamba_chunk, chunks=MAMBA_CHUNKS,
               state_bytes=MAMBA_STATE_BYTES, update_op="mamba_update",
               state=_mamba_state, orders=True),
    _LayerKind("conv", "a 'conv' layer", "layer_types", _gated_conv, "conv",
               False, {"_cache_x": "state"}, _conv_kept,
               extra=("conv_taps",), check=_check_conv,
               refuses=(_NO_KINDS,
                        ("residual", "the gated convolution is not written "
                         "over several residual streams"), _NO_FIRST)),
)}


def _layer(cfg, step, x, i):
    """Layer ``i`` of ``build_prefill_step`` and of ``build_decode_step``
    over ``x [B, T, D]``: ``x`` after it, its caches' names added to
    ``step.cache_names``. The layer's row of ``LAYER_KINDS`` builds the
    first sub-block from the normed input; the rest is every layer's
    (under cfg['mixers'] ``_sub_input`` is the plain norm and
    ``_layer_tail`` ends at the first residual)."""
    kind, nm = kind_of(cfg, i), "gpt_%d" % i
    h, mix = _sub_input(cfg, x, nm, 1, step.dev)
    y, kept = kind.build(cfg, step, h, nm, i)
    step.cache_names.extend(kept)
    if kind.merged:
        y = _attn_out(cfg, h, y, nm)
    x = _layer_tail(cfg, x, y, nm, i, mix, step.dev, step.branch,
                    kind.scope, **step.tally)
    if not step.decode:
        # a prefill writes its residual stream after every layer: XLA
        # fuses the stream's adds into every reader, the LAST one then
        # re-adds all ``2 n_layer`` sub-block outputs from the embedding
        # up and each ``[batch, P, d_model]`` float32 lives to the end of
        # the program (9.4 GB at 28 layers of 2,560 and 16,384 positions,
        # where the live set is 2.7 GB)
        with name_scope("norm"):    # no instruction: the next norm's input
            x = layers.materialize(x)
    return x


@_stores_weights
def build_multi_token_decode_step(cfg=None, batch=1, steps=2,
                                  max_len=None):
    """S tokens per slot in ONE dispatch, against the decode caches.

    The fixed-shape primitive the fleet tier composes twice
    (serving/engine.py):

    * **speculative verification** — the target model scores a slot's
      current token plus its k draft tokens (S = k + 1) in one
      dispatch; greedy acceptance walks the S logits rows.
    * **suffix prefill after a prefix-cache hit** — a prompt whose
      first L tokens were spliced from the prefix store prefills only
      its S = P - L suffix (batch=1).

    Feeds: ``token`` [B, S] int64 and ``pos`` [B, S] int64 where every
    row MUST be contiguous ascending (``pos[b] = start_b + arange(S)``)
    — the per-layer cache write is one vmapped slab update at
    ``pos[:, 0]``, so non-contiguous rows would silently write the slab
    at the wrong rows. The caller also guarantees
    ``pos[b, -1] < max_len`` for every row: ``dynamic_update_slice``
    CLAMPS an overflowing start and would shift the write window down
    over valid rows (the engine degrades to plain single-token steps
    near the cache end for exactly this reason).

    Per-slot semantics match ``build_serving_decode_step``: query row
    (b, s) sees cache rows ``<= pos[b, s]`` (later rows — including the
    speculative K/V this very dispatch writes — are masked to exact
    zeros), every op is row-local, and cache/parameter names are shared
    with ``build_decode_step``. Attention is computed PER POSITION with
    exactly the decode step's shapes (q folded [B, n_kv, g, Dh], one
    M=g matmul against the n_kv cache, per-position visibility bias):
    the S-wide GEMM variant is NOT bitwise the step's M=g form on CPU
    (a GEMV reduces in a different order than a GEMM), and the fleet
    tier's whole contract is that a verified/suffix-prefilled token
    stream is bitwise ``generate``'s — so position s's logits are the
    plain step's BY CONSTRUCTION, not by tolerance. S stays small in
    both uses (k+1 drafts, the un-cached prompt suffix), so the op
    count is bounded.

    A cfg with a latent cache (``attn='mla'``) is REFUSED here, as is
    one with a layer that keeps a state (an ``'ssm'`` entry of
    ``mixers``, a ``'conv'``, ``'retention'`` or ``'delta'`` entry of
    ``layer_types``: a state has no position to resume at or rewind to)
    and a
    cfg with ring caches (a sliding layer whose window is shorter than
    ``max_len``): the one slab write at
    ``pos[:, 0]`` would run over a ring's end, and a stored prefix or a
    rejected draft cannot be cut out of a ring that has wrapped.

    Returns (logits_var, cache_names); fetch logits [B, S, vocab]."""
    cfg = cfg or base_config()
    _check_cfg(cfg)
    if max_len is None:
        max_len = cfg["max_length"]
    _refuse_shortcut(cfg, "build_multi_token_decode_step",
                     "which the multi-token step (suffix prefill after a "
                     "prefix hit, speculative verification) does not carry "
                     "from one sub-layer to the next")
    if has_latent(cfg):
        raise ValueError(
            "build_multi_token_decode_step: cfg['attn']='mla' keeps a "
            "latent cache (one [B, 1, max_len, %d] tensor a layer), which "
            "the multi-token step (suffix prefill after a prefix hit, "
            "speculative verification) does not read or write"
            % latent_width(cfg))
    _refuse_streams(cfg, "build_multi_token_decode_step",
                    "which the multi-token step (suffix prefill after a "
                    "prefix hit, speculative verification) does not carry")
    _refuse_state(cfg, "build_multi_token_decode_step",
                  "which the multi-token step (suffix prefill after a "
                  "prefix hit, speculative verification) can neither "
                  "resume at a prefix's length nor rewind past a rejected "
                  "draft")
    if cfg.get("mixers"):
        raise ValueError(
            "build_multi_token_decode_step: cfg['mixers'] (one mixer a "
            "layer) is built by the prefill and the decode steps only")
    if has_rings(cfg, max_len):
        raise ValueError(
            "build_multi_token_decode_step: cfg['layer_types'] holds "
            "sliding layers whose window %d is shorter than max_len=%d; "
            "their caches are rings, which the multi-token step (suffix "
            "prefill after a prefix hit, speculative verification) does "
            "not write" % (cfg["window"], max_len))
    S = int(steps)
    assert 0 < S <= max_len, (S, max_len)
    use_rope = cfg.get("pos_emb", "learned") == "rope"
    if not use_rope and max_len > cfg["max_length"]:
        raise ValueError(
            "max_len=%d exceeds the learned position table "
            "(cfg['max_length']=%d) — raise max_length or use "
            "pos_emb='rope'" % (max_len, cfg["max_length"]))
    d_model, n_head, d_head = cfg["d_model"], cfg["n_head"], _d_head(cfg)
    n_kv, g = _kv_heads_of(cfg)
    from ..layer_helper import LayerHelper

    helper = LayerHelper("gpt_multi_decode")
    token = layers.data("token", [S], dtype="int64")   # [B, S]
    pos = layers.data("pos", [S], dtype="int64")       # [B, S]

    # explicit [B, S, D] reshape: lookup_table squeezes trailing-1 id
    # dims, so S=1 (a one-token suffix) would otherwise come out [B, D]
    word = _embed(cfg, token, [-1, S, d_model])
    if use_rope:
        x = word                             # positions rotate q/k below
    else:
        with name_scope("embed"):
            posv = layers.reshape(
                layers.embedding(pos, [cfg["max_length"], d_model],
                                 param_attr=ParamAttr(name="gpt_pos_emb")),
                [-1, S, d_model])
            x = layers.elementwise_add(word, posv)

    # per-position [B, 1] position columns + the decode step's exact
    # visibility bias per position: query (b, s) attends cache rows
    # <= pos[b, s]; everything later — a neighbor's rows, this
    # dispatch's own still-speculative writes — masks to an exact zero
    # after softmax
    pos_cols, biases = [], []
    with name_scope("attn.core"):       # one a dispatch, for all its layers
        ar = layers.reshape(layers.range(0, max_len, 1, "int64"),
                            [1, max_len])
        for s in range(S):
            ps = layers.slice(pos, axes=[1], starts=[s], ends=[s + 1])
            pos_cols.append(ps)                              # [B, 1]
            biases.append(_visibility_bias(ar, ps, -1))

    routed = None
    cache_names = []
    for i in range(cfg["n_layer"]):
        nm = "gpt_%d" % i
        ck = helper.create_global_variable(
            name=nm + "_cache_k", shape=(batch, n_kv, max_len, d_head))
        cv = helper.create_global_variable(
            name=nm + "_cache_v", shape=(batch, n_kv, max_len, d_head))
        cache_names += [ck.name, cv.name]
        with _layer_scope(cfg, i):
            h = _norm_of(cfg, x, nm + "_pre1")
            q, k, v = _qkv(cfg, h, nm)
            with name_scope("attn.core"):
                k = _heads(cfg, k, nm, S, n_kv, "k")    # [B,n_kv,S,Dh]
                v = _heads(cfg, v, nm, S, n_kv)
                rotates = _rotates(cfg, i)
                if rotates:
                    # [B, S] positions -> per-(row, step) angles broadcast
                    # over the kv-head axis (elementwise — bitwise the
                    # per-position rotation); the cache stores rotated keys
                    k = _rope(cfg, k, pos)
                # ONE vmapped slab write per cache tensor at the per-row
                # start (rows are contiguous by contract)
                ck = layers.kv_cache_write(ck, k, pos_cols[0])
                cv = layers.kv_cache_write(cv, v, pos_cols[0])
                # attention per position, in the decode step's exact
                # shapes: q_s folds to [B, n_kv, g, Dh] and batch-matmuls
                # the n_kv cache directly — scores/softmax/ctx of position
                # s are the single-token step's bit for bit (an S-wide
                # GEMM would not be)
                ctxs = []
                for s in range(S):
                    q_s = _head_norm(cfg, layers.reshape(
                        layers.slice(q, axes=[1], starts=[s], ends=[s + 1]),
                        [-1, n_kv, g, d_head]), nm, "q")
                    if rotates:
                        q_s = _rope(cfg, q_s, pos_cols[s])
                    scores = layers.matmul(
                        q_s, ck, transpose_y=True,
                        alpha=d_head ** -0.5)             # [B,n_kv,g,S']
                    scores = layers.elementwise_add(scores, biases[s])
                    w = layers.softmax(scores)
                    ctxs.append(layers.reshape(layers.matmul(w, cv),
                                               [-1, 1, n_head * d_head]))
                ctxv = ctxs[0] if S == 1 else layers.concat(ctxs, axis=1)
            x = _layer_tail(cfg, x, _attn_out(cfg, h, ctxv, nm), nm, i,
                            counts=routed)

    x = _final_norm(cfg, x)
    logits = _lm_head(cfg, x)
    return logits, cache_names


def build_serving_decode_step(cfg=None, batch=1, max_len=None):
    """Continuous-batching decode step: ``build_decode_step`` with
    PER-SLOT positions. Feeds are token [B, 1] int64 (each slot's
    current input token) and pos [B, 1] int64 (each slot's own sequence
    position), so the B cache slots advance independently — the serving
    engine (serving/engine.py) admits a new sequence into a free slot
    mid-flight while its neighbors keep decoding, and retires finished
    slots without draining the batch. Every per-slot op is row-local
    (embedding lookup, fc = per-row dots, rope with [B, 1] positions,
    per-row visibility bias, per-slot kv_cache_write), so an active
    slot's logits are bitwise those of the same tokens run through a
    smaller-batch ``build_decode_step`` — the engine's parity contract
    with ``generate`` rests on it.

    Cache/parameter names match ``build_decode_step``; caches are
    [B, n_kv, max_len, Dh] (a sliding layer's: [B, n_kv, window, Dh], a
    ring written at ``pos mod window``) donated state whose batch rows
    the engine
    treats as independent slots (a free slot's rows are garbage until
    the next prefill-then-insert overwrites them; the per-row mask
    ``cache row <= pos[b]`` keeps garbage out of every live slot's
    attention). Returns (logits_var, cache_names)."""
    return build_decode_step(cfg, batch=batch, max_len=max_len,
                             per_slot_pos=True)


def sample_token(logits_row, rng, temperature=0.0, top_k=0):
    """Sample ONE next token from a single row of logits: float64
    softmax(logits/temperature), optional top-k truncation, seeded
    choice; temperature=0 is greedy argmax. The ONE sampling
    implementation shared by ``generate`` (applied per batch row, in
    row order, on one RandomState) and the serving engine's per-slot
    sampler (its own RandomState per request) — sharing it is what
    makes the engine's outputs bitwise ``generate``'s by construction,
    not just by test."""
    import numpy as np

    lg = logits_row.astype("float64")
    if temperature > 0:
        lg = lg / float(temperature)
        if top_k and top_k > 0:
            k = min(int(top_k), lg.shape[-1])
            kth = np.partition(lg, -k)[-k]
            lg = np.where(lg < kth, -np.inf, lg)
        p = np.exp(lg - lg.max())
        p = p / p.sum()
        return int(rng.choice(p.shape[0], p=p))
    return int(np.argmax(lg))


def generate(exe, decode_prog, logits_var, prompt_ids, n_new, scope,
             temperature=0.0, top_k=0, seed=0, prefill_prog=None,
             prefill_logits=None):
    """Autoregressive generation with the KV-cache decode step.

    prompt_ids: [B, P] int array. Prefills the caches (P one-token
    steps through the decode executable — or ONE dispatch when a
    ``build_prefill_step`` program for this prompt length is passed as
    ``prefill_prog``/``prefill_logits``), then runs n_new sampling
    steps. Returns [B, P + n_new] ids.

    temperature=0 (default) is greedy argmax; temperature>0 samples from
    softmax(logits / temperature), optionally truncated to the top_k
    most likely tokens. Sampling happens host-side (numpy, seeded) —
    the device step stays deterministic and cache-compatible.
    """
    import numpy as np

    ids = np.asarray(prompt_ids, dtype="int64")
    B, P = ids.shape
    max_len = None     # the slabs' rows: a ring is shorter and wraps
    for v in decode_prog.global_block().vars.values():
        if v.name.endswith(("_cache_k", "_cache_c")):
            max_len = max(max_len or 0, v.shape[2])
    if max_len is not None and P + n_new > max_len:
        raise ValueError(
            "generate: prompt (%d) + new tokens (%d) exceeds the decode "
            "step's max_len=%d — positions past the cache silently clamp "
            "(dynamic_update_slice) and would corrupt output" %
            (P, n_new, max_len))
    if temperature < 0:
        raise ValueError("temperature must be >= 0 (0 = greedy); got %r"
                         % (temperature,))
    rng = np.random.RandomState(seed)

    def sample(lg):
        # one shared sampler applied row by row (draw order = batch
        # order on the one RandomState) — see sample_token
        return np.array([sample_token(lg[b], rng, temperature, top_k)
                         for b in range(B)], dtype="int64")

    out = [ids[:, i] for i in range(P)]
    start = 0
    if prefill_prog is not None and n_new > 0:
        # the prefill program is compiled for ONE prompt length (its
        # 'tokens' feed: [-1, P]); check before dispatch so a mismatch
        # raises this message, not an opaque executor feed-shape error
        tok_var = prefill_prog.global_block().vars.get("tokens")
        if tok_var is not None and int(tok_var.shape[-1]) != P:
            raise ValueError(
                "generate: prefill_prog was built for prompt_len=%d but "
                "prompt_ids has P=%d — rebuild with "
                "build_prefill_step(prompt_len=%d) or pad the prompt"
                % (int(tok_var.shape[-1]), P, P))
        # one dispatch fills positions 0..P-1 and yields the first
        # sampled token from the last prompt position's logits
        (full,) = exe.run(prefill_prog, feed={"tokens": ids},
                          fetch_list=[prefill_logits], scope=scope)
        out.append(sample(full[:, P - 1]))
        start = P
    for t in range(start, P + n_new - 1):
        tok = out[t][:, None]
        (logits,) = exe.run(
            decode_prog,
            feed={"token": tok, "pos": np.array([t], dtype="int64")},
            fetch_list=[logits_var], scope=scope)
        if t + 1 < P:
            continue  # prefill: only the cache write matters
        out.append(sample(logits[:, 0]))
    return np.stack(out, axis=1)
