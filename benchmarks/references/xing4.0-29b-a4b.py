"""The plain reference of Xing4.0-29B-A4B (``model_type`` xing4_0,
XingChen-AGI/Xing4.0-29B-A4B): its forward pass in straightforward
``jax.numpy`` and float32 at the highest matmul precision — a
four-stream residual path (manifold-constrained hyper-connections, mHC,
arXiv:2512.24880) round latent attention in its EXPANDED form over the
whole sequence and round whole-held experts, no cache, no batching, no
kernel, every held expert computed densely on every token and selected
by a mask. It imports nothing from ``paddle_tpu``. Its keys other than
the ``hc_*`` / ``mhc_*`` ones are read as DeepSeek-V3's public
implementation defines them.

*Residual stream* (``n = hc_mult``, ``C = d_model``). A token's state is
``X [n, C]``; it starts as the embedding row copied to every stream. For
each sub-block ``F`` of a layer (attention, then the FFN or the experts)
with its own ``phi [n C, n + n + n^2]`` (``[phi_pre | phi_post |
phi_res]``), ``alpha [3]`` and ``b [n + n + n^2]``::

    x~     = vec(X) / sqrt(mean(vec(X)^2) + norm_eps)      # all n C values
    H~     = alpha (by group) * (x~ phi) + b
    H_pre  = sigmoid(H~[:n]);  H_post = 2 sigmoid(H~[n:2n])
    M      = exp(clip(mat(H~[2n:]), clamp_min, clamp_max))
    hc_sinkhorn_iters times:  M = M / (column sums + hc_eps)
                              M = M / (row sums + hc_eps)
    H_res  = M
    h      = sum_i H_pre[i] X[i]
    y      = F(RMSNorm_l(h))            # the layer's own pre-norm
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] y

After the last layer ``logits = RMSNorm_f(sum_i X[i]) W_head``, untied.

*Attention*: latent (MLA), as openPangu-Ultra-MoE's reference has it
(``c_q = RMSNorm(h W_dq)``, ``[q_nope | q_rope] = c_q W_uq``; ``[c | k_r]
= h W_dkv``, ``c = RMSNorm(c)``; per head ``[k_nope | v] = c W_ukv``, ``k
= [k_nope | k_r]`` with the one rotated ``k_r`` all heads share), with no
sandwich norm and with YaRN on the ``d_rope`` rotated dimensions:
``theta_i = base^(-2i/d_rope)``, ``low = floor(d_rope ln(orig / (beta_fast
2 pi)) / (2 ln base))``, ``high = ceil(d_rope ln(orig / (beta_slow 2 pi)) /
(2 ln base))``, both clamped to ``[0, d_rope/2 - 1]``, ``r_i = clip((i -
low) / (high - low), 0, 1)``, ``inv_freq_i = theta_i (1 - r_i) + (theta_i
/ factor) r_i``; cos and sin times ``yarn_mscale(factor, mscale) /
yarn_mscale(factor, mscale_all_dim)``; the softmax scale is
``yarn_mscale(factor, mscale_all_dim)^2 / sqrt(d_nope + d_rope)``,
``yarn_mscale(f, m) = 0.1 m ln f + 1``. Rotate-half layout.

*Router and experts*: ``s = sigmoid(m W_r)`` in float32 over all
``n_expert``; the ``expert_top_k`` largest of ``s + bias`` (the selection
bias ``e_score_correction_bias``; one group, so no group limit); ``w =
s[sel] / (sum s[sel] + 1e-20) * route_scale``; every pair computed; one
always-on shared expert beside the routed sum; the first
``n_dense_layer`` layers a dense SwiGLU of ``d_ff``. All bias-free.

Departures from the published model: the weights are whatever the caller
hands in (the benchmark draws them from a seed) — bfloat16-valued arrays,
as the checkpoint is published, each WIDENED to float32 where it
multiplies, an expert at a time; activations are float32 where the
published model computes in bfloat16; the next-token-prediction layer is
absent; ties among the scores resolve as ``jax.lax.top_k`` resolves them
(lowest index first); attention is computed a block of queries at a time
and the experts one after another, each under one traced body (the same
numbers, and a program that compiles in seconds at 8,448 rows). The
forward pass runs A LAYER AT A TIME (one jitted function a layer kind),
and ``greedy_margin_fn`` runs the head over the answer's rows only (the
whole ``[T, vocab]`` at 8,448 rows and 131,072 ids would be 4.4 GB), so
the reference fits on the chip next to the engine it judges.

``weights`` maps the program's parameter names to arrays:
``gpt_word_emb [V, D]``, ``gpt_out_proj.w_0 [D, V]``, ``gpt_ln_f_s [D]``
and per layer ``gpt_<i>_pre{1,2}_ln_s [D]``, ``gpt_<i>_hc{1,2}_phi.w_0
[n D, n (n + 2)]``, ``gpt_<i>_hc{1,2}_alpha [3]``, ``gpt_<i>_hc{1,2}_b
[n (n + 2)]``, the attention's ``gpt_<i>_att_{qa,qb,kva,kvb,o}.w_0`` and
``gpt_<i>_att_{qa,kva}_ln_s``, a dense layer's ``gpt_<i>_ffn{1,1v,2}.w_0``,
an expert layer's ``gpt_<i>_moe_router.w_0 [D, E]``,
``gpt_<i>_moe_router_bias [E]``, ``gpt_<i>_moe_{gate,up}.w_0 [E, D, F]``,
``gpt_<i>_moe_down.w_0 [E, F, D]`` and ``gpt_<i>_moe_shared_{gate,up,
down}.w_0``. ``cfg`` is ``models/gpt.py``'s.

``mantissa_bits`` rounds every weight to that many explicit mantissa bits
as it is used (7 is bfloat16: nothing moves for bfloat16-valued weights);
``activation_bits`` also rounds every tensor the layer hands on — the
embedding row, the streams after each write, each mixed and normalised
vector, both latents, q, k and v, the scores, the attention weights,
every matmul's output, the router's scores, the chosen gates, the final
logits — AND the mHC mappings: ``x~``, the projection, ``H~``, the two
sigmoids, the exponential and every Sinkhorn round are rounded as they
are computed, the way a model kept in that precision computes them. That
is the control: what the precision the checkpoint is published in would
answer where the engine keeps float32. The rounding is done on the bits,
not by a cast there and back, which the TPU compiler is free to drop as
excess precision."""

import functools
import math

import numpy as np

QUERY_BLOCK = 256   # queries a step of the blocked attention


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * scale


def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim, base, scaling):
    """The ``dim / 2`` rotation frequencies (module docstring) as a
    float32 array, and the number cos and sin are multiplied by."""
    import jax.numpy as jnp

    half = dim // 2
    i = jnp.arange(half, dtype=jnp.float32)
    theta = base ** (-i * 2.0 / dim)
    if not scaling:
        return theta, 1.0
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def at(beta):
        return dim * math.log(orig / (beta * 2 * math.pi)) \
            / (2 * math.log(base))

    low = min(max(math.floor(at(float(scaling.get("beta_fast", 32)))), 0),
              half - 1)
    high = min(max(math.ceil(at(float(scaling.get("beta_slow", 1)))), 0),
               half - 1)
    r = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = theta * (1.0 - r) + (theta / factor) * r
    return inv, yarn_mscale(factor, float(scaling.get("mscale", 1))) \
        / yarn_mscale(factor, float(scaling.get("mscale_all_dim", 0)))


def _rope(t, theta, scaling=None):
    """Rotate-half RoPE on ``t [..., T, Dr]`` at positions 0..T-1."""
    import jax.numpy as jnp

    T, dr = t.shape[-2:]
    half = dr // 2
    inv, m = yarn_inv_freq(dr, theta, scaling)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
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


# ------------------------------------------------------ the residual path
def mhc_mappings(X, phi, alpha, b, eps, iters, hc_eps, clamp,
                 rnd=lambda t: t):
    """``(H_pre [T, n], H_post [T, n], H_res [T, n, n])`` of the streams
    ``X [T, n, C]``."""
    import jax
    import jax.numpy as jnp

    T, n, C = X.shape
    flat = X.reshape(T, n * C)
    xt = rnd(flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                             + eps))
    proj = rnd(xt @ phi)                                   # [T, n(n+2)]
    gate = jnp.concatenate([jnp.full((n,), 1.0) * alpha[0],
                            jnp.full((n,), 1.0) * alpha[1],
                            jnp.full((n * n,), 1.0) * alpha[2]])
    ht = rnd(proj * gate + b)
    h_pre = rnd(jax.nn.sigmoid(ht[:, :n]))
    h_post = rnd(2.0 * jax.nn.sigmoid(ht[:, n:2 * n]))
    m = rnd(jnp.exp(jnp.clip(ht[:, 2 * n:], clamp[0], clamp[1])))
    m = m.reshape(T, n, n)                                 # [T, i, j]
    for _ in range(iters):
        m = rnd(m / (jnp.sum(m, axis=1, keepdims=True) + hc_eps))  # cols
        m = rnd(m / (jnp.sum(m, axis=2, keepdims=True) + hc_eps))  # rows
    return h_pre, h_post, m


def mhc_pre(X, phi, alpha, b, eps, iters, hc_eps, clamp, rnd=lambda t: t):
    """What the sub-block reads, and the mappings to write back with."""
    import jax.numpy as jnp

    h_pre, h_post, h_res = mhc_mappings(X, phi, alpha, b, eps, iters,
                                        hc_eps, clamp, rnd)
    h = rnd(jnp.einsum("ti,tic->tc", h_pre, X))
    return h, h_post, h_res


def mhc_post(X, y, h_post, h_res, rnd=lambda t: t):
    import jax.numpy as jnp

    return rnd(jnp.einsum("tij,tjc->tic", h_res, X)
               + h_post[:, :, None] * y[:, None, :])


# ------------------------------------------------------------ sub-blocks
def attention(q, k, v, scale, rnd=lambda t: t):
    """Causal softmax attention of ``q [H, T, Dk]`` over ``k [H, T, Dk]``
    and ``v [H, T, Dv]``, a block of ``QUERY_BLOCK`` queries at a time
    against all the keys under the causal mask (one body for every
    block: ``jax.lax.map``). Returns ``[T, H Dv]``."""
    import jax
    import jax.numpy as jnp

    H, T, _ = q.shape
    qb = min(QUERY_BLOCK, T)
    blocks = -(-T // qb)
    q = jnp.pad(q, ((0, 0), (0, blocks * qb - T), (0, 0)))
    kt = k.transpose(0, 2, 1)

    def block(lo):
        rows = jax.lax.dynamic_slice_in_dim(q, lo, qb, axis=1)
        keep = jnp.arange(T)[None, :] <= (lo + jnp.arange(qb))[:, None]
        scores = rnd(rows @ kt * scale)
        scores = jnp.where(keep[None], scores, -jnp.inf)
        return rnd(rnd(jax.nn.softmax(scores, axis=-1)) @ v)

    out = jax.lax.map(block, jnp.arange(blocks) * qb)      # [n, H, qb, Dv]
    ctx = out.transpose(1, 0, 2, 3).reshape(H, blocks * qb, -1)[:, :T]
    return ctx.transpose(1, 0, 2).reshape(T, -1)


def swiglu(m, w_gate, w_up, w_down, rnd=lambda t: t):
    import jax

    return rnd(rnd(jax.nn.silu(rnd(m @ w_gate)) * rnd(m @ w_up)) @ w_down)


def route(m, router_w, bias, top_k, norm_topk, route_scale,
          rnd=lambda t: t):
    """The router on ``m [T, D]``: (the chosen experts ``[T, k]``, their
    gates ``[T, k]``, per token how far the last chosen ``s + bias``
    stands over the first rejected one)."""
    import jax
    import jax.numpy as jnp

    n_expert = router_w.shape[1]
    s = rnd(jax.nn.sigmoid((m @ router_w).astype(jnp.float32)))
    biased = s if bias is None else s + bias
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


def experts(m, router_w, bias, expert_w, n_held, top_k, norm_topk,
            route_scale, expert_first=0, rnd=lambda t: t):
    """The routed part of the expert layer on ``m [T, D]``: every HELD
    expert on every token, the token's chosen ones selected by a mask of
    gates, one expert after another (one body for every expert:
    ``jax.lax.scan``). ``expert_w(e)`` gives expert ``e``'s three float32
    matrices, widened one expert at a time. Returns (the sum, ``route``'s
    gap)."""
    import jax
    import jax.numpy as jnp

    sel, w, gap = route(m, router_w, bias, top_k, norm_topk, route_scale,
                        rnd)

    def add(out, e):
        gate = jnp.sum(jnp.where(sel == expert_first + e, w, 0.0), axis=1)
        return out + swiglu(m, *expert_w(e), rnd) * gate[:, None], None

    out, _ = jax.lax.scan(add, jnp.zeros_like(m), jnp.arange(n_held))
    return rnd(out), gap


LAYER_PARAMS = {
    "attn": ("pre1_ln_s", "hc1_phi.w_0", "hc1_alpha", "hc1_b",
             "att_qa.w_0", "att_qa_ln_s", "att_qb.w_0", "att_kva.w_0",
             "att_kva_ln_s", "att_kvb.w_0", "att_o.w_0",
             "pre2_ln_s", "hc2_phi.w_0", "hc2_alpha", "hc2_b"),
    "dense": ("ffn1.w_0", "ffn1v.w_0", "ffn2.w_0"),
    "moe": ("moe_router.w_0", "moe_router_bias", "moe_gate.w_0",
            "moe_up.w_0", "moe_down.w_0", "moe_shared_gate.w_0",
            "moe_shared_up.w_0", "moe_shared_down.w_0"),
}


def layer(p, X, cfg_items, dense, mantissa_bits=None, activation_bits=None):
    """One layer on the token streams ``X [T, n, D]``: (the streams it
    hands on, ``[T]`` the router's gap, inf for a dense layer). ``p``
    maps the layer's parameter names WITHOUT their ``gpt_<i>_`` prefix to
    the caller's own arrays; each is widened to float32 here."""
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_items)
    scaling = dict(cfg["rope_scaling"]) if cfg.get("rope_scaling") else None

    def wide(t):
        t = jnp.asarray(t, jnp.float32)
        return t if mantissa_bits is None \
            else round_mantissa(t, mantissa_bits)

    def w(name):
        return wide(p[name])

    def r(t):
        return t if activation_bits is None \
            else round_mantissa(t, activation_bits)

    H = cfg["n_head"]
    dn, dr, dv = cfg["d_nope"], cfg["d_rope"], cfg["d_v"]
    dc = cfg["kv_lora_rank"]
    eps = cfg.get("norm_eps") or 1e-6
    theta = cfg.get("rope_theta") or 10000.0
    hc = (eps, int(cfg.get("hc_sinkhorn_iters") or 20),
          float(cfg.get("hc_eps") or 1e-6),
          tuple(float(c) for c in (cfg.get("hc_res_clamp")
                                   or (-30.0, 30.0))))
    scale = (dn + dr) ** -0.5
    if scaling and scaling.get("mscale_all_dim"):
        scale *= yarn_mscale(float(scaling["factor"]),
                             float(scaling["mscale_all_dim"])) ** 2
    T = X.shape[0]
    with jax.default_matmul_precision("highest"):
        # ---- attention, round the first hyper-connection
        h, h_post, h_res = mhc_pre(X, w("hc1_phi.w_0"), p["hc1_alpha"],
                                   p["hc1_b"], *hc, rnd=r)
        h = r(_rms_norm(h, w("pre1_ln_s"), eps))
        c_q = r(_rms_norm(r(h @ w("att_qa.w_0")), w("att_qa_ln_s"), eps))
        q = r(c_q @ w("att_qb.w_0")).reshape(T, H, dn + dr)
        q = q.transpose(1, 0, 2)                           # [H, T, dn+dr]
        q = jnp.concatenate(
            [q[..., :dn], r(_rope(q[..., dn:], theta, scaling))], axis=-1)
        kv = r(h @ w("att_kva.w_0"))                       # [T, dc + dr]
        c = r(_rms_norm(kv[:, :dc], w("att_kva_ln_s"), eps))
        k_r = r(_rope(kv[:, dc:], theta, scaling))         # [T, dr]
        kvb = r(c @ w("att_kvb.w_0")).reshape(T, H, dn + dv)
        kvb = kvb.transpose(1, 0, 2)                       # [H, T, dn+dv]
        k = jnp.concatenate(
            [kvb[..., :dn], jnp.broadcast_to(k_r[None], (H, T, dr))],
            axis=-1)
        ctx = attention(q, k, kvb[..., dn:], scale, r)
        X = mhc_post(X, r(ctx @ w("att_o.w_0")), h_post, h_res, r)
        # ---- the FFN or the experts, round the second
        h, h_post, h_res = mhc_pre(X, w("hc2_phi.w_0"), p["hc2_alpha"],
                                   p["hc2_b"], *hc, rnd=r)
        m = r(_rms_norm(h, w("pre2_ln_s"), eps))
        gap = jnp.full((T,), jnp.inf, jnp.float32)
        if dense:
            f = swiglu(m, w("ffn1.w_0"), w("ffn1v.w_0"), w("ffn2.w_0"), r)
        else:
            def expert_w(e):
                return tuple(wide(p[name][e]) for name in (
                    "moe_gate.w_0", "moe_up.w_0", "moe_down.w_0"))

            router_w = w("moe_router.w_0")
            bias = p.get("moe_router_bias")
            shared = tuple(w("moe_shared_%s.w_0" % part)
                           for part in ("gate", "up", "down"))
            f, gap = experts(
                m, router_w, bias, expert_w, p["moe_gate.w_0"].shape[0],
                cfg["expert_top_k"], bool(cfg.get("norm_topk", False)),
                float(cfg.get("route_scale") or 1.0),
                int(cfg.get("expert_first") or 0), r)
            f = r(f + swiglu(m, *shared, r))
        X = mhc_post(X, f, h_post, h_res, r)
    return X, gap


def _hashable(cfg):
    out = []
    for k, v in cfg.items():
        if isinstance(v, dict):
            v = tuple(sorted(v.items()))
        elif isinstance(v, (list, tuple)):
            v = tuple(v)
        elif not isinstance(v, (int, float, str, bool)):
            continue
        out.append((k, v))
    return tuple(sorted(out))


@functools.lru_cache(maxsize=None)
def _compiled(cfg_items, dense, mantissa_bits, activation_bits):
    import jax

    return jax.jit(functools.partial(
        layer, cfg_items=cfg_items, dense=dense,
        mantissa_bits=mantissa_bits, activation_bits=activation_bits))


def hidden(weights, cfg, ids, mantissa_bits=None, activation_bits=None):
    """``(x [T, D], gaps [T])``: the sum of the streams after the last
    layer, before the final norm, and the smallest router gap of each
    position over the expert layers."""
    import jax.numpy as jnp

    def r(t):
        return t if activation_bits is None \
            else round_mantissa(t, activation_bits)

    items = _hashable(cfg)
    n = int(cfg["hc_mult"])
    ids = jnp.asarray(ids)
    gaps = jnp.full(ids.shape[:1], jnp.inf, jnp.float32)
    x = r(jnp.asarray(weights["gpt_word_emb"])[ids].astype(jnp.float32)
          * float(cfg.get("emb_scale") or 1.0))
    if mantissa_bits is not None:
        x = r(round_mantissa(x, mantissa_bits))
    X = jnp.broadcast_to(x[:, None, :], (x.shape[0], n, x.shape[1]))
    for i in range(cfg["n_layer"]):
        dense = not cfg.get("n_expert") \
            or i < (cfg.get("n_dense_layer") or 0)
        names = LAYER_PARAMS["attn"] + LAYER_PARAMS[
            "dense" if dense else "moe"]
        p = {nm: weights["gpt_%d_%s" % (i, nm)] for nm in names
             if "gpt_%d_%s" % (i, nm) in weights}
        X, gap = _compiled(items, dense, mantissa_bits, activation_bits)(
            p, X)
        gaps = jnp.minimum(gaps, gap)
    return r(jnp.sum(X, axis=1)), gaps


@functools.lru_cache(maxsize=None)
def _head(eps, mantissa_bits, activation_bits):
    import jax
    import jax.numpy as jnp

    def head(x, scale, w_head):
        def wide(t):
            t = jnp.asarray(t, jnp.float32)
            return t if mantissa_bits is None \
                else round_mantissa(t, mantissa_bits)

        def r(t):
            return t if activation_bits is None \
                else round_mantissa(t, activation_bits)

        with jax.default_matmul_precision("highest"):
            return r(r(_rms_norm(x, wide(scale), eps)) @ wide(w_head))

    return jax.jit(head)


def forward(weights, cfg, ids, mantissa_bits=None, activation_bits=None,
            with_gaps=False, rows=None):
    """Logits ``[T, vocab]`` of the causal forward pass over ``ids [T]``
    (``rows = (lo, hi)``: of positions ``lo .. hi - 1`` only), computed
    at the highest matmul precision, a layer at a time. ``with_gaps``
    also returns the smallest router gap (``route``) of each of those
    positions over the expert layers."""
    x, gaps = hidden(weights, cfg, ids, mantissa_bits, activation_bits)
    if rows is not None:
        x, gaps = x[rows[0]:rows[1]], gaps[rows[0]:rows[1]]
    logits = _head(cfg.get("norm_eps") or 1e-6, mantissa_bits,
                   activation_bits)(x, weights["gpt_ln_f_s"],
                                    weights["gpt_out_proj.w_0"])
    return (logits, gaps) if with_gaps else logits


def greedy_margin_fn(weights, cfg, pad_multiple, controls=()):
    """``margins(tokens, prompt_len)``: how far the reference disagrees
    with a greedy answer. For every generated token, the reference's
    largest logit at that position minus its logit for the token chosen
    (0 where they agree). The answer is teacher-forced through ONE
    forward pass, padded to the next multiple of ``pad_multiple`` so that
    the probes share a few executables (causal attention keeps the
    padding out of the positions that count); the head runs over the
    answer's positions only.

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
        rows = (prompt_len - 1, T - 1)
        at, gaps = forward(weights, cfg, ids, with_gaps=True, rows=rows)
        at, gaps = np.asarray(at), np.asarray(gaps)
        choices = [np.asarray(tokens[prompt_len:T])] + [
            np.asarray(jnp.argmax(forward(weights, cfg, ids, wb, ab,
                                          rows=rows), axis=-1))
            for wb, ab in controls]
        return [at.max(axis=-1) - at[np.arange(len(c)), c]
                for c in choices], gaps

    return margins
