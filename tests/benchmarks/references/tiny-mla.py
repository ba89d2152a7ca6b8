"""The plain reference of openPangu-Ultra-MoE (``model_type``
pangu_ultra_moe, FreedomIntelligence/openPangu-Ultra-MoE-718B): its
forward pass in straightforward ``jax.numpy`` and float32 at the highest
matmul precision — latent attention in its EXPANDED form over the whole
sequence, no cache, no batching, no kernel, every held expert computed
densely on every token and selected by a mask — after the layer as its
public implementation has it. For token state ``x``:

* ``h = RMSNorm_in(x)``; ``c_q = RMSNorm_q(h W_dq)`` (``q_lora_rank``);
  ``[q_nope | q_rope] = c_q W_uq``, ``n_head`` heads of ``d_nope +
  d_rope``; ``q_rope = RoPE(q_rope, pos)`` (rotate-half over the
  ``d_rope`` dims, base ``rope_theta``, no scaling).
* ``[c | k_r] = h W_dkv`` (``kv_lora_rank + d_rope``); ``c =
  RMSNorm_kv(c)``; ``k_r = RoPE(k_r, pos)``, ONE rotated key part a token
  that all heads share. ``[c | k_r]`` is all the layer keeps of a token.
* per head ``[k_nope | v] = c W_ukv`` (``d_nope + d_v``), ``k = [k_nope |
  k_r]``, ``p = softmax_causal(q k^T / sqrt(d_nope + d_rope))``, ``ctx =
  p v``, ``a = concat(ctx) W_o``.
* ``x = x + RMSNorm_post_attn(a)``; ``m = RMSNorm_pre_mlp(x)``; one of the
  first ``n_dense_layer`` layers: ``f = (silu(m Wg1) * (m Wu1)) Wd1``; an
  expert layer: ``s = sigmoid(m W_r)`` in float32 over all ``n_expert``,
  the ``expert_top_k`` largest, ``w = s[sel] / (sum s[sel] + 1e-20) *
  route_scale``, ``f = shared(m) + sum_{e in sel} w_e expert_e(m)``, each a
  bias-free SwiGLU, no token ever dropped; ``x = x + RMSNorm_post_mlp(f)``.
  After the last layer ``logits = RMSNorm_f(x) W_head``.

Departures from the published model: the weights are whatever the caller
hands in (the benchmark draws them from a seed) — bfloat16-valued arrays,
as the checkpoint is published, each WIDENED to float32 where it
multiplies; activations are float32 where the published model computes in
bfloat16; the next-token-prediction layer is absent; ties among the
scores resolve as ``jax.lax.top_k`` resolves them (lowest index first);
attention is computed a block of queries at a time (the same numbers).
The forward pass runs A LAYER AT A TIME (one jitted function a layer
kind): the widened copy of one layer's matrices is all that stands beside
the caller's own arrays, so the reference fits on the chip next to the
engine it judges. THE SHARE: with ``n_expert_local`` < ``n_expert`` the
weights hold only the experts ``expert_first .. expert_first +
n_expert_local - 1``; the router still scores, selects among and
normalises over all ``n_expert``, and what the absent experts would add is
left out — the layer's output is the shared expert plus this chip's part
of the routed sum.

``weights`` maps the program's parameter names to arrays:
``gpt_word_emb [V, D]``, ``gpt_out_proj.w_0 [D, V]``, ``gpt_ln_f_s [D]``
and per layer ``gpt_<i>_{pre1,post1,pre2,post2}_ln_s [D]``,
``gpt_<i>_att_qa.w_0 [D, q_lora_rank]``, ``gpt_<i>_att_qa_ln_s``,
``gpt_<i>_att_qb.w_0 [q_lora_rank, H (d_nope + d_rope)]``,
``gpt_<i>_att_kva.w_0 [D, d_c + d_rope]``, ``gpt_<i>_att_kva_ln_s [d_c]``,
``gpt_<i>_att_kvb.w_0 [d_c, H (d_nope + d_v)]``, ``gpt_<i>_att_o.w_0
[H d_v, D]``, a dense layer's ``gpt_<i>_ffn{1,1v}.w_0 [D, F]`` and
``gpt_<i>_ffn2.w_0 [F, D]``, an expert layer's ``gpt_<i>_moe_router.w_0
[D, E]``, ``gpt_<i>_moe_{gate,up}.w_0 [E_local, D, F]``,
``gpt_<i>_moe_down.w_0 [E_local, F, D]`` and
``gpt_<i>_moe_shared_{gate,up}.w_0 [D, F_s]``,
``gpt_<i>_moe_shared_down.w_0 [F_s, D]``. ``cfg`` is ``models/gpt.py``'s.
``mantissa_bits`` rounds every weight to that many explicit mantissa bits
as it is used (7 is bfloat16: nothing moves for bfloat16-valued weights);
``activation_bits`` also rounds every tensor the layer hands on — the
embedding row, each normalised vector, both latents, q, k and v (so the
latent row a cache would hold), the scores, the attention weights, every
matmul's output, the residual stream after each add, the router's scores,
the chosen gates and the final logits — the way a model kept in that
precision computes (norms, softmax and sigmoid in float32 inside, their
results rounded). That is the control: what the precision the checkpoint
is published in would answer where the engine keeps float32. The rounding
is done on the bits, not by a cast there and back, which the TPU compiler
is free to drop as excess precision."""

import functools

import numpy as np

QUERY_BLOCK = 512   # queries a step of the blocked attention


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * scale


def _rope(t, theta):
    """Rotate-half RoPE on ``t [..., T, Dr]`` at positions 0..T-1."""
    import jax.numpy as jnp

    T, dr = t.shape[-2:]
    half = dr // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dr)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = t[..., :half], t[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def round_mantissa(t, bits):
    """float32 ``t`` rounded to ``bits`` explicit mantissa bits (nearest,
    ties away from zero), by integer arithmetic on its representation."""
    import jax
    import jax.numpy as jnp

    drop = 23 - int(bits)
    u = jax.lax.bitcast_convert_type(t, jnp.uint32)
    u = (u + jnp.uint32(1 << (drop - 1))) & jnp.uint32(
        ~((1 << drop) - 1) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def attention(q, k, v, scale, rnd=lambda t: t):
    """Causal softmax attention of ``q [H, T, Dk]`` over ``k [H, T, Dk]``
    and ``v [H, T, Dv]``, a block of ``QUERY_BLOCK`` queries at a time
    against the keys up to the block's end. Returns ``[T, H Dv]``."""
    import jax
    import jax.numpy as jnp

    H, T, _ = q.shape
    out = []
    for lo in range(0, T, QUERY_BLOCK):
        hi = min(T, lo + QUERY_BLOCK)
        keep = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        scores = rnd(q[:, lo:hi] @ k[:, :hi].transpose(0, 2, 1) * scale)
        scores = jnp.where(keep[None], scores, -jnp.inf)
        out.append(rnd(rnd(jax.nn.softmax(scores, axis=-1)) @ v[:, :hi]))
    ctx = jnp.concatenate(out, axis=1)                     # [H, T, Dv]
    return ctx.transpose(1, 0, 2).reshape(T, -1)


def swiglu(m, w_gate, w_up, w_down, rnd=lambda t: t):
    import jax

    return rnd(rnd(jax.nn.silu(rnd(m @ w_gate)) * rnd(m @ w_up)) @ w_down)


def route(m, router_w, top_k, norm_topk, route_scale, rnd=lambda t: t):
    """The router on ``m [T, D]``: (the chosen experts ``[T, k]``, their
    gates ``[T, k]``, per token how far the last chosen score stands over
    the first rejected one). Sigmoid scores over all the experts, no
    group limit and no selection bias."""
    import jax
    import jax.numpy as jnp

    n_expert = router_w.shape[1]
    s = rnd(jax.nn.sigmoid((m @ router_w).astype(jnp.float32)))
    _, sel = jax.lax.top_k(s, top_k)                       # [T, k]
    if top_k < n_expert:
        ranked = jax.lax.top_k(s, top_k + 1)[0]
        gap = ranked[:, top_k - 1] - ranked[:, top_k]
    else:
        gap = jnp.full(m.shape[:1], jnp.inf, jnp.float32)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return sel, rnd(w * route_scale), gap


def experts(m, router_w, w_gate, w_up, w_down, top_k, norm_topk,
            route_scale, expert_first=0, rnd=lambda t: t):
    """The routed part of the expert layer on ``m [T, D]``: every HELD
    expert (``w_gate [E_local, D, F]``: experts ``expert_first ..``) on
    every token, the token's chosen ones selected by a mask of gates; a
    chosen expert that is not held adds nothing. Returns (the sum,
    ``route``'s gap)."""
    import jax.numpy as jnp

    sel, w, gap = route(m, router_w, top_k, norm_topk, route_scale, rnd)
    out = jnp.zeros_like(m)
    for e in range(w_gate.shape[0]):
        gate = jnp.sum(jnp.where(sel == expert_first + e, w, 0.0), axis=1)
        out = out + swiglu(m, w_gate[e], w_up[e], w_down[e], rnd) \
            * gate[:, None]
    return rnd(out), gap


LAYER_PARAMS = {
    "attn": ("pre1_ln_s", "att_qa.w_0", "att_qa_ln_s", "att_qb.w_0",
             "att_kva.w_0", "att_kva_ln_s", "att_kvb.w_0", "att_o.w_0",
             "post1_ln_s", "pre2_ln_s", "post2_ln_s"),
    "dense": ("ffn1.w_0", "ffn1v.w_0", "ffn2.w_0"),
    "moe": ("moe_router.w_0", "moe_gate.w_0", "moe_up.w_0", "moe_down.w_0",
            "moe_shared_gate.w_0", "moe_shared_up.w_0",
            "moe_shared_down.w_0"),
}


def layer(p, x, cfg_items, dense, mantissa_bits=None, activation_bits=None):
    """One layer on the token states ``x [T, D]``: (the states it hands
    on, ``[T]`` the router's gap, inf for a dense layer). ``p`` maps the
    layer's parameter names WITHOUT their ``gpt_<i>_`` prefix to the
    caller's own arrays; each is widened to float32 here."""
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_items)

    def w(name):
        t = jnp.asarray(p[name], jnp.float32)
        return t if mantissa_bits is None \
            else round_mantissa(t, mantissa_bits)

    def r(t):
        return t if activation_bits is None \
            else round_mantissa(t, activation_bits)

    H = cfg["n_head"]
    dn, dr, dv = cfg["d_nope"], cfg["d_rope"], cfg["d_v"]
    dc = cfg["kv_lora_rank"]
    eps = cfg.get("norm_eps") or 1e-6
    theta = cfg.get("rope_theta") or 10000.0
    T = x.shape[0]
    with jax.default_matmul_precision("highest"):
        h = r(_rms_norm(x, w("pre1_ln_s"), eps))
        c_q = r(_rms_norm(r(h @ w("att_qa.w_0")), w("att_qa_ln_s"), eps))
        q = r(c_q @ w("att_qb.w_0")).reshape(T, H, dn + dr)
        q = q.transpose(1, 0, 2)                           # [H, T, dn+dr]
        q = jnp.concatenate([q[..., :dn], r(_rope(q[..., dn:], theta))],
                            axis=-1)
        kv = r(h @ w("att_kva.w_0"))                       # [T, dc + dr]
        c = r(_rms_norm(kv[:, :dc], w("att_kva_ln_s"), eps))
        k_r = r(_rope(kv[:, dc:], theta))                  # [T, dr]
        kvb = r(c @ w("att_kvb.w_0")).reshape(T, H, dn + dv)
        kvb = kvb.transpose(1, 0, 2)                       # [H, T, dn+dv]
        k = jnp.concatenate(
            [kvb[..., :dn], jnp.broadcast_to(k_r[None], (H, T, dr))],
            axis=-1)
        ctx = attention(q, k, kvb[..., dn:], (dn + dr) ** -0.5, r)
        att = r(ctx @ w("att_o.w_0"))
        x = r(x + r(_rms_norm(att, w("post1_ln_s"), eps)))
        m = r(_rms_norm(x, w("pre2_ln_s"), eps))
        gap = jnp.full((T,), jnp.inf, jnp.float32)
        if dense:
            f = swiglu(m, w("ffn1.w_0"), w("ffn1v.w_0"), w("ffn2.w_0"), r)
        else:
            f, gap = experts(
                m, w("moe_router.w_0"), w("moe_gate.w_0"), w("moe_up.w_0"),
                w("moe_down.w_0"), cfg["expert_top_k"],
                bool(cfg.get("norm_topk", False)),
                float(cfg.get("route_scale") or 1.0),
                int(cfg.get("expert_first") or 0), r)
            f = r(f + swiglu(m, w("moe_shared_gate.w_0"),
                             w("moe_shared_up.w_0"),
                             w("moe_shared_down.w_0"), r))
        x = r(x + r(_rms_norm(f, w("post2_ln_s"), eps)))
    return x, gap


def _hashable(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.lru_cache(maxsize=None)
def _compiled(cfg_items, dense, mantissa_bits, activation_bits):
    import jax

    return jax.jit(functools.partial(
        layer, cfg_items=cfg_items, dense=dense,
        mantissa_bits=mantissa_bits, activation_bits=activation_bits))


def forward(weights, cfg, ids, mantissa_bits=None, activation_bits=None,
            with_gaps=False):
    """Logits ``[T, vocab]`` of the causal forward pass over ``ids [T]``,
    computed at the highest matmul precision, a layer at a time.
    ``with_gaps`` also returns ``[T]``: the smallest router gap
    (``route``) of the position over the expert layers."""
    import jax
    import jax.numpy as jnp

    def w(name):
        t = jnp.asarray(weights[name], jnp.float32)
        return t if mantissa_bits is None \
            else round_mantissa(t, mantissa_bits)

    def r(t):
        return t if activation_bits is None \
            else round_mantissa(t, activation_bits)

    items = _hashable(cfg)
    eps = cfg.get("norm_eps") or 1e-6
    ids = jnp.asarray(ids)
    gaps = jnp.full(ids.shape[:1], jnp.inf, jnp.float32)
    x = r(jnp.asarray(weights["gpt_word_emb"])[ids].astype(jnp.float32)
          * float(cfg.get("emb_scale") or 1.0))
    if mantissa_bits is not None:
        x = r(round_mantissa(x, mantissa_bits))
    for i in range(cfg["n_layer"]):
        dense = not cfg.get("n_expert") \
            or i < (cfg.get("n_dense_layer") or 0)
        names = LAYER_PARAMS["attn"] + LAYER_PARAMS[
            "dense" if dense else "moe"]
        p = {n: weights["gpt_%d_%s" % (i, n)] for n in names
             if "gpt_%d_%s" % (i, n) in weights}
        x, gap = _compiled(items, dense, mantissa_bits, activation_bits)(
            p, x)
        gaps = jnp.minimum(gaps, gap)
    with jax.default_matmul_precision("highest"):
        x = r(_rms_norm(x, w("gpt_ln_f_s"), eps))
        logits = r(x @ w("gpt_out_proj.w_0"))
    return (logits, gaps) if with_gaps else logits


def greedy_margin_fn(weights, cfg, pad_multiple, controls=()):
    """``margins(tokens, prompt_len)``: how far the reference disagrees
    with a greedy answer. For every generated token, the reference's
    largest logit at that position minus its logit for the token chosen
    (0 where they agree). The answer is teacher-forced through ONE
    forward pass, padded to the next multiple of ``pad_multiple`` so that
    the probes share a few executables (causal attention keeps the
    padding out of the positions that count).

    Returns ``(margins, gaps)``. ``margins`` is a list of arrays: first
    the system's own tokens judged so, then, for each entry of
    ``controls`` (``(mantissa_bits, activation_bits)``), the tokens the
    reference itself would choose at each position of the same sequence
    computed so — the reading a limit has to leave outside. ``gaps`` is
    the reference's smallest router gap at each of those positions."""
    import jax.numpy as jnp

    def margins(tokens, prompt_len):
        T = len(tokens)
        ids = np.zeros(-(-T // pad_multiple) * pad_multiple, np.int64)
        ids[:T] = tokens
        at, gaps = forward(weights, cfg, ids, with_gaps=True)
        at = np.asarray(at[prompt_len - 1:T - 1])
        gaps = np.asarray(gaps[prompt_len - 1:T - 1])
        choices = [np.asarray(tokens[prompt_len:T])] + [
            np.asarray(jnp.argmax(forward(weights, cfg, ids, wb, ab),
                                  axis=-1)[prompt_len - 1:T - 1])
            for wb, ab in controls]
        return [at.max(axis=-1) - at[np.arange(len(c)), c]
                for c in choices], gaps

    return margins
