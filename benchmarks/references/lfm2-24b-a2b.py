"""The plain reference of LFM2-24B-A2B (``model_type`` lfm2_moe,
LiquidAI/LFM2-24B-A2B): its forward pass in straightforward ``jax.numpy``
and float32 at the highest matmul precision — no cache, no carried rows,
no batching, no kernel, every expert computed densely on every token and
selected by a mask. It imports nothing from ``paddle_tpu``.

Layer ``i`` (RMSNorm ``eps`` = ``norm_eps`` everywhere)::

    h  = x + Op_i(RMSNorm(x; g1_i))
    x' = h + FFN_i(RMSNorm(h; g2_i))

and after the last layer ``logits = RMSNorm(x; g_f) E^T``: the head is
the token table transposed. ``Op_i`` is chosen by ``layer_types[i]``,
``FFN_i`` is dense for ``i < n_dense_layer`` and the experts after.

*``conv``, the gated short convolution.* With ``u [T, D]`` the normed
input and ``K = conv_taps`` (3)::

    [B | C | X] = u W_in           # D -> 3 D, no bias, split in this order
    v_t = B_t * X_t
    c_t = w[:, 0] v_{t-2} + w[:, 1] v_{t-1} + w[:, 2] v_t
    y_t = C_t * c_t;   out = y W_out

depth-wise over the ``D`` channels, causal, zeros before the sequence,
neither bias nor activation: tap ``K - 1`` meets the position itself.
Written here as ``K`` shifted products over the whole sequence; the
layer carries no position.

*``full``, attention.* ``q = u W_q`` (``n_head`` heads of ``d_head``),
``k = u W_k``, ``v = u W_v`` (``n_kv_head`` heads), no biases; RMSNorm of
q and of k over each head's ``d_head`` values with one learned
``[d_head]`` scale each; rotate-half RoPE on q and k over the whole head
(``rope_theta``, default type); causal softmax at ``1 / sqrt(d_head)``,
query head ``h`` reads key-value head ``h // (n_head / n_kv_head)``;
``W_o``.

*Dense FFN*: ``W2 (silu(W1 u) * W3 u)``, no biases. *Experts*: ``s =
sigmoid(u W_r)`` in float32 over all ``n_expert``; the ``expert_top_k``
with the largest ``s + b`` (``b`` moves the selection only); ``w = s[sel]
/ (sum s[sel] + norm_topk_eps) * route_scale``; the layer's output is
``sum_{e in sel} w_e expert_e(u)``, each a bias-free SwiGLU; no shared
expert, no token dropped.

Departures from the published model: the weights are whatever the caller
hands in (the benchmark draws them from a seed) — bfloat16-valued
matrices, as the checkpoint is published, each WIDENED to float32 where
it multiplies, an expert at a time; activations are float32 where the
published model computes in bfloat16; ties among the scores resolve as
``jax.lax.top_k`` resolves them (lowest index first); attention is
computed a block of queries at a time and the experts one after another,
each under one traced body (the same numbers, and a program that
compiles in seconds at 17,408 rows). The forward pass runs A LAYER AT A
TIME (one jitted function a layer kind), and ``greedy_margin_fn`` runs
the head over the answer's rows only (the whole ``[T, vocab]`` at 17,408
rows and 65,536 ids would be 4.6 GB), so the reference fits on the chip
next to the engine it judges.

``weights`` maps the program's parameter names to arrays:
``gpt_word_emb [V, D]`` (table and head), ``gpt_ln_f_s [D]`` and per
layer ``gpt_<i>_pre{1,2}_ln_s [D]``; a convolution layer's
``gpt_<i>_conv_in.w_0 [D, 3 D]``, ``gpt_<i>_conv.w_0 [D, K]``,
``gpt_<i>_conv_out.w_0 [D, D]``; an attention layer's
``gpt_<i>_att_{q,k,v,o}.w_0`` and ``gpt_<i>_att_{q,k}norm_s [d_head]``;
a dense layer's ``gpt_<i>_ffn{1,1v,2}.w_0``; an expert layer's
``gpt_<i>_moe_router.w_0 [D, E]``, ``gpt_<i>_moe_router_bias [E]``,
``gpt_<i>_moe_{gate,up}.w_0 [E, D, F]``, ``gpt_<i>_moe_down.w_0
[E, F, D]``. ``cfg`` is ``models/gpt.py``'s.

``mantissa_bits`` rounds every weight to that many explicit mantissa bits
as it is used (7 is bfloat16: nothing moves for bfloat16-valued matrices;
the float32 taps, scales and selection bias do); ``activation_bits`` also
rounds every tensor the layer hands on — the embedding row, each
normalised vector, the three parts of the convolution's projection, ``v``
(what a sequence would carry as rows), the convolution's sum and its
gated output, q, k and v (so the slab), the scores, the attention
weights, every matmul's output, the residual stream after each add, the
router's scores, the chosen gates and the final logits — the way a model
kept in that precision computes (norms, softmax and sigmoid in float32
inside, their results rounded). Together they are the control: what the
precision the checkpoint is published in would answer where the engine
keeps float32. The rounding is done on the bits, not by a cast there and
back, which the TPU compiler is free to drop as excess precision."""

import functools

import numpy as np

QUERY_BLOCK = 256   # queries a step of the blocked attention


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


# ------------------------------------------------------------ sub-blocks
def gated_conv(u, w_in, taps, w_out, rnd=lambda t: t):
    """The gated short convolution on the normed ``u [T, D]`` (module
    docstring): ``taps [D, K]``, tap ``K - 1`` on the position itself."""
    import jax.numpy as jnp

    T, D = u.shape
    K = taps.shape[1]
    proj = rnd(u @ w_in)
    b, c, x = proj[:, :D], proj[:, D:2 * D], proj[:, 2 * D:]
    v = rnd(b * x)
    past = jnp.concatenate([jnp.zeros((K - 1, D), v.dtype), v])
    # K shifted products: v_{t-K+1} w_0 + ... + v_t w_{K-1}
    conv = past[0:T] * taps[:, 0]
    for j in range(1, K):
        conv = conv + past[j:j + T] * taps[:, j]
    return rnd(rnd(c * rnd(conv)) @ w_out)


def attention(q, k, v, rnd=lambda t: t):
    """Causal softmax attention of ``q [H, T, Dh]`` over ``k, v [Hkv, T,
    Dh]`` (``H / Hkv`` query heads a key-value head), a block of
    ``QUERY_BLOCK`` queries at a time against all the keys under the
    causal mask (one body for every block: ``jax.lax.map``). Returns
    ``[T, H Dh]``."""
    import jax
    import jax.numpy as jnp

    H, T, dh = q.shape
    g = H // k.shape[0]
    k, v = jnp.repeat(k, g, axis=0), jnp.repeat(v, g, axis=0)
    qb = min(QUERY_BLOCK, T)
    blocks = -(-T // qb)
    q = jnp.pad(q, ((0, 0), (0, blocks * qb - T), (0, 0)))
    kt = k.transpose(0, 2, 1)

    def block(lo):
        rows = jax.lax.dynamic_slice_in_dim(q, lo, qb, axis=1)
        keep = jnp.arange(T)[None, :] <= (lo + jnp.arange(qb))[:, None]
        scores = rnd(rows @ kt * (dh ** -0.5))
        scores = jnp.where(keep[None], scores, -jnp.inf)
        return rnd(rnd(jax.nn.softmax(scores, axis=-1)) @ v)

    out = jax.lax.map(block, jnp.arange(blocks) * qb)      # [n, H, qb, Dh]
    ctx = out.transpose(1, 0, 2, 3).reshape(H, blocks * qb, dh)[:, :T]
    return ctx.transpose(1, 0, 2).reshape(T, H * dh)


def swiglu(m, w_gate, w_up, w_down, rnd=lambda t: t):
    import jax

    return rnd(rnd(jax.nn.silu(rnd(m @ w_gate)) * rnd(m @ w_up)) @ w_down)


def route(m, router_w, bias, top_k, norm_topk, route_scale, norm_eps,
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
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + norm_eps)
    return sel, rnd(w * route_scale), gap


def experts(m, router_w, bias, expert_w, n_held, top_k, norm_topk,
            route_scale, norm_eps, rnd=lambda t: t):
    """The expert layer on ``m [T, D]``: every expert on every token, the
    token's chosen ones selected by a mask of gates, one expert after
    another (one body for every expert: ``jax.lax.scan``). ``expert_w(e)``
    gives expert ``e``'s three float32 matrices, widened one expert at a
    time. Returns (the sum, ``route``'s gap)."""
    import jax
    import jax.numpy as jnp

    sel, w, gap = route(m, router_w, bias, top_k, norm_topk, route_scale,
                        norm_eps, rnd)

    def add(out, e):
        gate = jnp.sum(jnp.where(sel == e, w, 0.0), axis=1)
        return out + swiglu(m, *expert_w(e), rnd) * gate[:, None], None

    out, _ = jax.lax.scan(add, jnp.zeros_like(m), jnp.arange(n_held))
    return rnd(out), gap


LAYER_PARAMS = {
    "conv": ("pre1_ln_s", "conv_in.w_0", "conv.w_0", "conv_out.w_0",
             "pre2_ln_s"),
    "full": ("pre1_ln_s", "att_q.w_0", "att_k.w_0", "att_v.w_0",
             "att_o.w_0", "att_qnorm_s", "att_knorm_s", "pre2_ln_s"),
    "dense": ("ffn1.w_0", "ffn1v.w_0", "ffn2.w_0"),
    "moe": ("moe_router.w_0", "moe_router_bias", "moe_gate.w_0",
            "moe_up.w_0", "moe_down.w_0"),
}


def layer(p, x, cfg_items, kind, dense, mantissa_bits=None,
          activation_bits=None):
    """One layer on ``x [T, D]``: (what it hands on, ``[T]`` the router's
    gap, inf for a dense layer). ``kind`` is the layer's entry of
    cfg['layer_types']; ``p`` maps the layer's parameter names WITHOUT
    their ``gpt_<i>_`` prefix to the caller's own arrays; each is widened
    to float32 here."""
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_items)

    def wide(t):
        t = jnp.asarray(t, jnp.float32)
        return t if mantissa_bits is None \
            else round_mantissa(t, mantissa_bits)

    def w(name):
        return wide(p[name])

    def r(t):
        return t if activation_bits is None \
            else round_mantissa(t, activation_bits)

    eps = cfg.get("norm_eps") or 1e-6
    T = x.shape[0]
    with jax.default_matmul_precision("highest"):
        u = r(_rms_norm(x, w("pre1_ln_s"), eps))
        if kind == "conv":
            y = gated_conv(u, w("conv_in.w_0"), w("conv.w_0"),
                           w("conv_out.w_0"), r)
        else:
            n_head = cfg["n_head"]
            n_kv = cfg.get("n_kv_head") or n_head
            theta = cfg.get("rope_theta") or 10000.0

            def heads(t, n, scale=None):
                t = t.reshape(T, n, -1)
                if scale is not None:
                    t = r(_rms_norm(t, scale, eps))
                return t.transpose(1, 0, 2)                # [n, T, Dh]

            q = heads(r(u @ w("att_q.w_0")), n_head, w("att_qnorm_s"))
            k = heads(r(u @ w("att_k.w_0")), n_kv, w("att_knorm_s"))
            v = heads(r(u @ w("att_v.w_0")), n_kv)
            q, k = r(_rope(q, theta)), r(_rope(k, theta))
            y = r(attention(q, k, v, r) @ w("att_o.w_0"))
        h = r(x + y)
        m = r(_rms_norm(h, w("pre2_ln_s"), eps))
        gap = jnp.full((T,), jnp.inf, jnp.float32)
        if dense:
            f = swiglu(m, w("ffn1.w_0"), w("ffn1v.w_0"), w("ffn2.w_0"), r)
        else:
            def expert_w(e):
                return tuple(wide(p[name][e]) for name in (
                    "moe_gate.w_0", "moe_up.w_0", "moe_down.w_0"))

            bias = p.get("moe_router_bias")
            f, gap = experts(
                m, w("moe_router.w_0"),
                None if bias is None else wide(bias), expert_w,
                p["moe_gate.w_0"].shape[0], cfg["expert_top_k"],
                bool(cfg.get("norm_topk", False)),
                float(cfg.get("route_scale") or 1.0),
                float(cfg.get("norm_topk_eps") or 1e-20), r)
        return r(h + f), gap


def _hashable(cfg):
    out = []
    for k, v in cfg.items():
        if isinstance(v, (list, tuple)):
            v = tuple(v)
        elif not isinstance(v, (int, float, str, bool)):
            continue
        out.append((k, v))
    return tuple(sorted(out))


@functools.lru_cache(maxsize=None)
def _compiled(cfg_items, kind, dense, mantissa_bits, activation_bits):
    import jax

    return jax.jit(functools.partial(
        layer, cfg_items=cfg_items, kind=kind, dense=dense,
        mantissa_bits=mantissa_bits, activation_bits=activation_bits))


def hidden(weights, cfg, ids, mantissa_bits=None, activation_bits=None):
    """``(x [T, D], gaps [T])``: the residual stream after the last
    layer, before the final norm, and for each position the smallest
    router gap among the routings it depends on at full weight: its own
    in every expert layer, and those of the positions each later
    convolution layer reads beside it (``reach``)."""
    import jax.numpy as jnp

    def r(t):
        return t if activation_bits is None \
            else round_mantissa(t, activation_bits)

    items = _hashable(cfg)
    types = cfg.get("layer_types") or ["full"] * cfg["n_layer"]
    ids = jnp.asarray(ids)
    gaps = jnp.full(ids.shape[:1], jnp.inf, jnp.float32)
    x = jnp.asarray(weights["gpt_word_emb"])[ids].astype(jnp.float32)
    if mantissa_bits is not None:
        x = round_mantissa(x, mantissa_bits)
    x = r(x)
    for i in range(cfg["n_layer"]):
        dense = not cfg.get("n_expert") \
            or i < (cfg.get("n_dense_layer") or 0)
        names = LAYER_PARAMS[types[i]] + LAYER_PARAMS[
            "dense" if dense else "moe"]
        p = {nm: weights["gpt_%d_%s" % (i, nm)] for nm in names
             if "gpt_%d_%s" % (i, nm) in weights}
        if types[i] == "conv":
            gaps = reach(gaps, int(cfg["conv_taps"]))
        x, gap = _compiled(items, types[i], dense, mantissa_bits,
                           activation_bits)(p, x)
        gaps = jnp.minimum(gaps, gap)
    return x, gaps


def reach(gaps, taps):
    """The gaps ``[T]`` of the routings a position has depended on so
    far, carried through a convolution layer: position ``t`` reads
    positions ``t - taps + 1 .. t`` at weights of order one, so an
    expert flipped at any of them in an earlier layer moves ``t`` as its
    own would (attention also reads other positions, but as an average
    over all of them: one position's flip is a thousandth of it)."""
    import jax.numpy as jnp

    T = gaps.shape[0]
    wide = jnp.concatenate([jnp.full((taps - 1,), jnp.inf, gaps.dtype),
                            gaps])
    out = gaps
    for j in range(taps - 1):
        out = jnp.minimum(out, wide[j:j + T])
    return out


@functools.lru_cache(maxsize=None)
def _head(eps, mantissa_bits, activation_bits):
    import jax
    import jax.numpy as jnp

    def head(x, scale, table):
        def wide(t):
            t = jnp.asarray(t, jnp.float32)
            return t if mantissa_bits is None \
                else round_mantissa(t, mantissa_bits)

        def r(t):
            return t if activation_bits is None \
                else round_mantissa(t, activation_bits)

        with jax.default_matmul_precision("highest"):
            # the head is the token table transposed
            return r(r(_rms_norm(x, wide(scale), eps)) @ wide(table).T)

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
                                    weights["gpt_word_emb"])
    return (logits, gaps) if with_gaps else logits


def greedy_margin_fn(weights, cfg, pad_multiple, controls=()):
    """``margins(tokens, prompt_len)``: how far the reference disagrees
    with a greedy answer. For every generated token, the reference's
    largest logit at that position minus its logit for the token chosen
    (0 where they agree). The answer is teacher-forced through ONE
    forward pass, padded to the next multiple of ``pad_multiple`` so that
    the probes share a few executables (causal attention and a causal
    convolution keep the padding out of the positions that count); the
    head runs over the answer's positions only.

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
