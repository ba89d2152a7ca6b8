"""The plain reference of Trinity (``model_type`` afmoe, arcee-ai/
Trinity-Large-Preview): its forward pass in straightforward ``jax.numpy``
and float32 at the highest matmul precision — no cache, no batching, no
kernel, every held expert computed densely on every token and selected by
a mask — after the layer as its public implementation has it:

* ``x = E[token] * emb_scale`` (the muP embedding scale, sqrt(hidden)).
* ``a = RMSNorm_in(x)``; ``q, k, v, g = a Wq, a Wk, a Wv, a Wg`` (no
  bias; q and g ``n_head * d_head`` wide, k and v ``n_kv * d_head``);
  q and k RMS-normalised PER HEAD over ``d_head`` with one ``[d_head]``
  scale the heads share.
* a ``"sliding"`` layer: rotate-half RoPE on q and k, and key j is
  visible to query i iff ``0 <= i - j < window``. A ``"full"`` layer: no
  positional rotation at all and every ``j <= i`` is visible.
* ``ctx = softmax(q k^T / sqrt(d_head)) v``, ``n_head / n_kv`` query
  heads a key/value head; ``ctx = ctx * sigmoid(g)``;
  ``x = x + RMSNorm_post_att(ctx Wo)``.
* ``m = RMSNorm_pre_mlp(x)``. One of the first ``n_dense_layer`` layers:
  ``f = (silu(m Wg1) * (m Wu1)) Wd1``. An expert layer:
  ``s = sigmoid(m Wr)`` in float32 over all ``n_expert``; the
  ``expert_top_k`` experts with the largest ``s + b`` (``b``: a bias used
  for the SELECTION only); ``w = s[sel] / (sum s[sel] + 1e-20) *
  route_scale``; ``f = shared(m) + sum_{e in sel} w_e expert_e(m)``, each
  a bias-free SwiGLU, no token ever dropped.
* ``x = x + RMSNorm_post_mlp(f)``; after the last layer
  ``logits = RMSNorm_f(x) W_head``.

Departures from the published model: the weights are whatever the caller
hands in (the benchmark draws them from a seed), in float32 where the
published checkpoint is bfloat16; ``rope_scaling`` is null in the
published config and absent here; ties among ``s + b`` resolve as
``jax.lax.top_k`` resolves them (lowest index first); attention is
computed a block of queries at a time (the same numbers, and 8,448
positions fit beside the weights on one chip). THE SHARE: with
``n_expert_local`` < ``n_expert`` the weights hold only the experts
``expert_first .. expert_first + n_expert_local - 1``; the router still
scores, selects among and normalises over all ``n_expert``, and what the
absent experts would add is left out — the layer's output is the shared
expert plus this chip's part of the routed sum, as one chip of an
expert-parallel deployment computes it before the parts are added up.

``weights`` maps the program's parameter names to arrays:
``gpt_word_emb [V, D]``, ``gpt_out_proj.w_0 [D, V]``, ``gpt_ln_f_s [D]``
and per layer ``gpt_<i>_{pre1,post1,pre2,post2}_ln_s [D]``,
``gpt_<i>_att_{q,g}.w_0 [D, H Dh]``, ``gpt_<i>_att_{k,v}.w_0
[D, Hkv Dh]``, ``gpt_<i>_att_o.w_0 [H Dh, D]``, ``gpt_<i>_att_{q,k}norm_s
[Dh]``, a dense layer's ``gpt_<i>_ffn{1,1v}.w_0 [D, F]`` and
``gpt_<i>_ffn2.w_0 [F, D]``, an expert layer's ``gpt_<i>_moe_router.w_0
[D, E]``, ``gpt_<i>_moe_router_bias [E]``, ``gpt_<i>_moe_{gate,up}.w_0
[E_local, D, F]``, ``gpt_<i>_moe_down.w_0 [E_local, F, D]`` and
``gpt_<i>_moe_shared_{gate,up}.w_0 [D, F_s]``,
``gpt_<i>_moe_shared_down.w_0 [F_s, D]``. ``cfg`` is ``models/gpt.py``'s.
``mantissa_bits`` rounds every weight to that many explicit mantissa bits
as it is used (7 is bfloat16); ``activation_bits`` also rounds every
tensor the layer hands on — the embedding row, each normalised vector,
q, k, v and the gate (so the cache), the scores, the attention weights,
every matmul's output, the residual stream after each add, the router's
scores, the chosen gates and the final logits — the way a model kept in
that precision computes (norms, softmax and sigmoid in float32 inside,
their results rounded). Together they are the control: what the nearest
precision below float32 would answer. The rounding is done on the bits,
not by a cast there and back, which the TPU compiler is free to drop as
excess precision."""

import numpy as np

QUERY_BLOCK = 512   # queries a step of the blocked attention


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


def attention(q, k, v, window, rnd=lambda t: t):
    """Causal softmax attention of ``q [H, T, Dh]`` over ``k, v
    [Hkv, T, Dh]`` (``H / Hkv`` query heads a key/value head), a block of
    ``QUERY_BLOCK`` queries at a time against the keys up to the block's
    end. ``window`` None sees every ``j <= i``, else ``0 <= i - j <
    window``. Returns ``[T, H Dh]``."""
    import jax
    import jax.numpy as jnp

    H, T, dh = q.shape
    g = H // k.shape[0]
    k, v = jnp.repeat(k, g, axis=0), jnp.repeat(v, g, axis=0)
    out = []
    for lo in range(0, T, QUERY_BLOCK):
        hi = min(T, lo + QUERY_BLOCK)
        first = 0 if window is None else max(0, lo - window + 1)
        i = jnp.arange(lo, hi)[:, None]
        j = jnp.arange(first, hi)[None, :]
        keep = j <= i
        if window is not None:
            keep = jnp.logical_and(keep, i - j < window)
        scores = rnd(q[:, lo:hi] @ k[:, first:hi].transpose(0, 2, 1)
                     * (dh ** -0.5))
        scores = jnp.where(keep[None], scores, -jnp.inf)
        out.append(rnd(rnd(jax.nn.softmax(scores, axis=-1))
                       @ v[:, first:hi]))
    ctx = jnp.concatenate(out, axis=1)                     # [H, T, Dh]
    return ctx.transpose(1, 0, 2).reshape(T, H * dh)


def swiglu(m, w_gate, w_up, w_down, rnd=lambda t: t):
    import jax

    return rnd(rnd(jax.nn.silu(rnd(m @ w_gate)) * rnd(m @ w_up)) @ w_down)


def route(m, router_w, router_b, top_k, norm_topk, route_scale,
          rnd=lambda t: t):
    """The router on ``m [T, D]``: (the chosen experts ``[T, k]``, their
    gates ``[T, k]``, per token how far the last chosen ``s + b`` stands
    over the first rejected one). Sigmoid scores over all the experts;
    the bias moves the selection and never a gate."""
    import jax
    import jax.numpy as jnp

    n_expert = router_w.shape[1]
    s = rnd(jax.nn.sigmoid((m @ router_w).astype(jnp.float32)))
    biased = s if router_b is None else s + router_b[None, :]
    _, sel = jax.lax.top_k(biased, top_k)                  # [T, k]
    if top_k < n_expert:
        ranked = jax.lax.top_k(biased, top_k + 1)[0]
        gap = ranked[:, top_k - 1] - ranked[:, top_k]
    else:
        gap = jnp.full(m.shape[:1], jnp.inf, jnp.float32)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return sel, rnd(w * route_scale), gap


def experts(m, router_w, router_b, w_gate, w_up, w_down, top_k, norm_topk,
            route_scale, expert_first=0, rnd=lambda t: t):
    """The routed part of the expert layer on ``m [T, D]``: every HELD
    expert (``w_gate [E_local, D, F]``: experts ``expert_first ..``) on
    every token, the token's chosen ones selected by a mask of gates;
    a chosen expert that is not held adds nothing. Returns (the sum,
    ``route``'s gap)."""
    import jax.numpy as jnp

    sel, w, gap = route(m, router_w, router_b, top_k, norm_topk,
                        route_scale, rnd)
    out = jnp.zeros_like(m)
    for e in range(w_gate.shape[0]):
        gate = jnp.sum(jnp.where(sel == expert_first + e, w, 0.0), axis=1)
        out = out + swiglu(m, w_gate[e], w_up[e], w_down[e], rnd) \
            * gate[:, None]
    return rnd(out), gap


def forward(weights, cfg, ids, mantissa_bits=None, activation_bits=None,
            with_gaps=False):
    """Logits ``[T, vocab]`` of the causal forward pass over ``ids [T]``,
    computed at the highest matmul precision. ``with_gaps`` also returns
    ``[T]``: the smallest router gap (``route``) of the position over the
    expert layers."""
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
    n_kv = cfg.get("n_kv_head") or n_head
    eps = cfg.get("norm_eps") or 1e-6
    theta = cfg.get("rope_theta") or 10000.0
    types = cfg.get("layer_types") or ["full"] * cfg["n_layer"]
    rope_all = cfg.get("rope_layers", "all") == "all"
    T = ids.shape[0]
    gaps = jnp.full((T,), jnp.inf, jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = r(w("gpt_word_emb")[ids] * float(cfg.get("emb_scale") or 1.0))
        for i in range(cfg["n_layer"]):
            nm = "gpt_%d" % i
            sliding = types[i] == "sliding"
            a = r(_rms_norm(x, w(nm + "_pre1_ln_s"), eps))

            def heads(t, n, scale=None):
                t = t.reshape(T, n, -1)
                if scale is not None:
                    t = r(_rms_norm(t, scale, eps))
                return t.transpose(1, 0, 2)                # [n, T, Dh]

            q = heads(r(a @ w(nm + "_att_q.w_0")), n_head,
                      w(nm + "_att_qnorm_s"))
            k = heads(r(a @ w(nm + "_att_k.w_0")), n_kv,
                      w(nm + "_att_knorm_s"))
            v = heads(r(a @ w(nm + "_att_v.w_0")), n_kv)
            if sliding or rope_all:
                q, k = r(_rope(q, theta)), r(_rope(k, theta))
            ctx = attention(q, k, v, cfg["window"] if sliding else None, r)
            gate = r(jax.nn.sigmoid(r(a @ w(nm + "_att_g.w_0"))))
            att = r(r(ctx * gate) @ w(nm + "_att_o.w_0"))
            x = r(x + r(_rms_norm(att, w(nm + "_post1_ln_s"), eps)))
            m = r(_rms_norm(x, w(nm + "_pre2_ln_s"), eps))
            if i < (cfg.get("n_dense_layer") or 0):
                f = swiglu(m, w(nm + "_ffn1.w_0"), w(nm + "_ffn1v.w_0"),
                           w(nm + "_ffn2.w_0"), r)
            else:
                f, gap = experts(
                    m, w(nm + "_moe_router.w_0"),
                    w(nm + "_moe_router_bias"), w(nm + "_moe_gate.w_0"),
                    w(nm + "_moe_up.w_0"), w(nm + "_moe_down.w_0"),
                    cfg["expert_top_k"], bool(cfg.get("norm_topk", False)),
                    float(cfg.get("route_scale") or 1.0),
                    int(cfg.get("expert_first") or 0), r)
                f = r(f + swiglu(m, w(nm + "_moe_shared_gate.w_0"),
                                 w(nm + "_moe_shared_up.w_0"),
                                 w(nm + "_moe_shared_down.w_0"), r))
                gaps = jnp.minimum(gaps, gap)
            x = r(x + r(_rms_norm(f, w(nm + "_post2_ln_s"), eps)))
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
