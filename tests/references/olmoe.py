"""The plain reference of OLMoE (``model_type`` olmoe, allenai/OLMoE-1B-7B):
its forward pass in straightforward ``jax.numpy`` and float32 at the
highest matmul precision — no cache, no batching, no kernel, every expert
computed densely on every token and selected by a mask — after the
published layer:

* ``h = RMSNorm(x)``; ``q, k, v = h Wq, h Wk, h Wv`` (no bias);
  ``q = RMSNorm_q(q)``, ``k = RMSNorm_k(k)`` over the whole projected
  width, before the split into heads; rotate-half RoPE on ``q`` and ``k``;
  causal softmax attention at scale ``d_head ** -0.5``; ``x = x + ctx Wo``.
* ``h2 = RMSNorm(x)``; ``p = softmax(h2 Wr)`` over the experts in
  float32; the ``k`` largest ``p`` and their experts, NOT renormalised
  (``norm_topk`` false); ``y = sum_k p_k (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e``,
  no token dropped; ``x = x + y``.
* final RMSNorm, then the untied head.

Departures from the published model: the weights are whatever the caller
hands in (the benchmark draws them from a seed), in float32 where the
published checkpoint is bfloat16; ``clip_qkv`` is null in the published
config and absent here; ties among router probabilities resolve as
``jax.lax.top_k`` resolves them (lowest index first).

``weights`` maps the program's parameter names to arrays:
``gpt_word_emb [V, D]``, ``gpt_out_proj.w_0 [D, V]``, ``gpt_ln_f_s [D]``
and per layer ``gpt_<i>_pre{1,2}_ln_s [D]``, ``gpt_<i>_att_{q,k,v,o}.w_0
[D, D]``, ``gpt_<i>_att_{q,k}norm_s [D]``, ``gpt_<i>_moe_router.w_0
[D, E]``, ``gpt_<i>_moe_{gate,up}.w_0 [E, D, F]``,
``gpt_<i>_moe_down.w_0 [E, F, D]``. ``cfg`` is ``models/gpt.py``'s.
``mantissa_bits`` rounds every weight to that many explicit mantissa bits
as it is used (7 is bfloat16, 2 a float8_e5m2 without its narrow
exponent); ``activation_bits`` also rounds every tensor the layer hands
on — the embedding row, each normalised vector, q, k and v (so the
cache), the scores, the attention weights, every matmul's output, the
residual stream after each add, the router's logits, the chosen gates
and the final logits — the way a model kept in that precision computes
(norms and softmax in float32 inside, their results rounded). Together
they are the control: what the nearest precision below float32 would
answer. The rounding is done on the bits, not by a cast there and back,
which the TPU compiler is free to drop as excess precision (and did for
float8: my chip run, PR 26)."""

import numpy as np


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * scale


def _rope(t, theta):
    """Rotate-half RoPE on ``t [H, T, Dh]`` at positions 0..T-1."""
    import jax.numpy as jnp

    _, T, dh = t.shape
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
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


def experts(h2, router_w, w_gate, w_up, w_down, top_k, norm_topk,
            rnd=lambda t: t):
    """The expert layer on ``h2 [T, D]``: every expert on every token,
    the token's ``top_k`` selected by a mask of router probabilities.
    Returns the layer's output and, per token, how far the router's
    last chosen logit stands over its first rejected one (``inf`` where
    every expert is chosen): the near-ties that rounding can flip.
    ``rnd`` rounds what the layer hands on (``forward``'s
    ``activation_bits``)."""
    import jax
    import jax.numpy as jnp

    n_expert = router_w.shape[1]
    logits = rnd((h2 @ router_w).astype(jnp.float32))
    p = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(p, top_k)                 # [T, k]
    if top_k < n_expert:
        ranked = jax.lax.top_k(logits, top_k + 1)[0]
        gap = ranked[:, top_k - 1] - ranked[:, top_k]
    else:
        gap = jnp.full(h2.shape[:1], jnp.inf, jnp.float32)
    if norm_topk:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    top_p = rnd(top_p)
    chosen = top_e[:, :, None] == jnp.arange(n_expert)[None, None, :]
    weight = jnp.sum(jnp.where(chosen, top_p[:, :, None], 0.0), axis=1)
    g = rnd(jnp.einsum("td,edf->etf", h2, w_gate))
    u = rnd(jnp.einsum("td,edf->etf", h2, w_up))
    y = rnd(jnp.einsum("etf,efd->etd", rnd(jax.nn.silu(g) * u), w_down))
    return rnd(jnp.einsum("etd,te->td", y, weight)), gap


def forward(weights, cfg, ids, mantissa_bits=None, activation_bits=None,
            with_gaps=False):
    """Logits ``[T, vocab]`` of the causal forward pass over ``ids [T]``,
    computed at the highest matmul precision. ``with_gaps`` also returns
    ``[T]``: the smallest router gap (``experts``) of the position over
    the layers."""
    import jax
    import jax.numpy as jnp

    def w(name):
        t = jnp.asarray(weights[name], jnp.float32)
        return t if mantissa_bits is None \
            else round_mantissa(t, mantissa_bits)

    def r(t):
        return t if activation_bits is None \
            else round_mantissa(t, activation_bits)

    n_head = cfg["n_head"]
    eps = cfg.get("norm_eps") or 1e-6
    theta = cfg.get("rope_theta") or 10000.0
    T = ids.shape[0]
    gaps = jnp.full((T,), jnp.inf, jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = r(w("gpt_word_emb")[ids])
        causal = jnp.tril(jnp.ones((T, T), bool))
        for i in range(cfg["n_layer"]):
            nm = "gpt_%d" % i
            h = r(_rms_norm(x, w(nm + "_pre1_ln_s"), eps))
            q = r(_rms_norm(r(h @ w(nm + "_att_q.w_0")),
                            w(nm + "_att_qnorm_s"), eps))
            k = r(_rms_norm(r(h @ w(nm + "_att_k.w_0")),
                            w(nm + "_att_knorm_s"), eps))
            v = r(h @ w(nm + "_att_v.w_0"))

            def heads(t):
                return t.reshape(T, n_head, -1).transpose(1, 0, 2)

            q, k, v = r(_rope(heads(q), theta)), r(_rope(heads(k), theta)), \
                heads(v)
            scores = r(q @ k.transpose(0, 2, 1) * (q.shape[-1] ** -0.5))
            scores = jnp.where(causal[None], scores, -jnp.inf)
            ctx = r(r(jax.nn.softmax(scores, axis=-1)) @ v)
            ctx = ctx.transpose(1, 0, 2).reshape(T, -1)
            x = r(x + r(ctx @ w(nm + "_att_o.w_0")))
            h2 = r(_rms_norm(x, w(nm + "_pre2_ln_s"), eps))
            y, gap = experts(
                h2, w(nm + "_moe_router.w_0"), w(nm + "_moe_gate.w_0"),
                w(nm + "_moe_up.w_0"), w(nm + "_moe_down.w_0"),
                cfg["expert_top_k"], bool(cfg.get("norm_topk", False)), r)
            x = r(x + y)
            gaps = jnp.minimum(gaps, gap)
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
    import jax
    import jax.numpy as jnp

    logits_of = jax.jit(lambda w, ids: forward(w, cfg, ids,
                                               with_gaps=True))
    lows = [jax.jit(lambda w, ids, wb=wb, ab=ab: jnp.argmax(
        forward(w, cfg, ids, wb, ab), axis=-1)) for wb, ab in controls]

    def margins(tokens, prompt_len):
        T = len(tokens)
        ids = np.zeros(-(-T // pad_multiple) * pad_multiple, np.int64)
        ids[:T] = tokens
        ids = jnp.asarray(ids)
        at, gaps = logits_of(weights, ids)
        at = np.asarray(at[prompt_len - 1:T - 1])
        gaps = np.asarray(gaps[prompt_len - 1:T - 1])
        choices = [np.asarray(tokens[prompt_len:T])] + [
            np.asarray(low(weights, ids)[prompt_len - 1:T - 1])
            for low in lows]
        return [at.max(axis=-1) - at[np.arange(len(c)), c]
                for c in choices], gaps

    return margins
