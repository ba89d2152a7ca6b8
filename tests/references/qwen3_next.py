"""The plain reference of Qwen3-Next-80B-A3B-Instruct (``model_type``
qwen3_next, Qwen/Qwen3-Next-80B-A3B-Instruct; gated delta rule,
arXiv:2412.06464): its forward pass in straightforward ``jax.numpy`` and
float32 at the highest matmul precision — the delta rule TOKEN BY TOKEN
(one ``lax.scan`` over the positions), attention as a full causal softmax,
no chunk, no cache, no batching, no kernel. It imports nothing from
``paddle_tpu``.

Layer ``i`` (RMSNorm ``eps`` = ``norm_eps`` everywhere, every projection
bias-free)::

    h  = x + mixer_i(RMSNorm(x; g1_i))
    x' = h + moe_i(RMSNorm(h; g2_i))

and after the last layer ``logits = RMSNorm(x; g_f) W_head`` (untied).

*Linear layer* (``layer_types[i] == "delta"``: ``Hk`` key heads of ``Dk``,
``Hv`` value heads of ``Dv``, value head ``j`` reads key head ``j // (Hv /
Hk)``). With ``u [T, D]`` the normed input: ``[q | k | v | z] = u W_in``
(``Hk Dk | Hk Dk | Hv Dv | Hv Dv``), ``[b | a] = u W_ba`` (``Hv | Hv``).
``[q | k | v]`` pass a causal depth-wise convolution of ``K`` = 4 taps
(``taps [C, K]``, tap ``K - 1`` on the position itself, zeros before the
sequence, no bias), then silu; ``z`` does not. Per head ``q <- q /
sqrt(sum q^2 + 1e-6) / sqrt(Dk)``, ``k <- k / sqrt(sum k^2 + 1e-6)``;
``beta_t = sigmoid(b_t)``; ``g_t = -exp(a_log) softplus(a_t + dt_b)``.
With ``S [Dk, Dv]`` a value head, zero before the sequence::

    S <- exp(g_t) S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T
    o_t = S^T q_t

then ``y = scale * (o / rms(o)) * silu(z)`` over each head's ``Dv`` values
(one ``[Dv]`` scale the heads share), heads merged, ``W_out``.

*Full layer* (``"full"``): ``q = u W_q``, ``gate = u W_g``, ``k = u W_k``,
``v = u W_v``; RMSNorm of q and of k over each head's ``d_head`` (one
scale each); rotate-half RoPE at ``rope_theta`` on the first ``rope_dim``
values of a head, the others pass; causal softmax attention at ``1 /
sqrt(d_head)``, ``H / Hkv`` query heads a key-value head; ``ctx <- ctx *
sigmoid(gate)``, then ``W_o``.

*Experts.* ``p = softmax(m W_r)`` in float32 over all ``n_expert``; the
``expert_top_k`` largest; ``w = p[sel] / sum p[sel]`` (``norm_topk``);
expert ``e`` is ``W_d (silu(W_g m) * W_u m)``; of them THIS share holds
``expert_first .. expert_first + n_expert_local - 1`` (all without the
keys) and sums those, one expert after another over every token, the
token's gate 0 where it did not choose the expert. Plus the shared
expert, whole on every share: ``sigmoid(m . w_sg) * E_shared(m)``.

Departures from the published model: the weights are whatever the caller
hands in (the benchmark draws them from a seed) — bfloat16-valued
matrices, as the checkpoint is published, each WIDENED to float32 where
it multiplies; activations and the state are float32 where the published
model computes in bfloat16; the published ``q_proj`` holds a head's query
and gate side by side and ``in_proj_qkvz`` / ``in_proj_ba`` interleave
their parts by key head, where ``W_q`` / ``W_g`` are two matrices and
``W_in`` / ``W_ba`` hold their parts in the order above (a permutation of
columns); the published RMSNorm multiplies by ``1 + w``, this one by a
scale (one parameterisation); the next-token-prediction module is not
part of the served model. ``greedy_margin_fn`` runs the head over the
answer's rows only and a block of the vocabulary at a time, so the
reference fits on the chip next to the engine it judges.

``weights`` maps the program's parameter names to arrays: ``gpt_word_emb
[V, D]``, ``gpt_out_proj.w_0 [D, V]``, ``gpt_ln_f_s [D]`` and per layer
``gpt_<i>_pre{1,2}_ln_s [D]``; a linear layer's ``gpt_<i>_delta_in.w_0``,
``gpt_<i>_delta_ba.w_0``, ``gpt_<i>_delta_conv.w_0 [C, 4]``,
``gpt_<i>_delta_{a_log,dt_b} [Hv]``, ``gpt_<i>_delta_norm_s [Dv]``,
``gpt_<i>_delta_out.w_0``; a full layer's ``gpt_<i>_att_{q,g,k,v,o}.w_0``
and ``gpt_<i>_att_{q,k}norm_s [d_head]``; every layer's
``gpt_<i>_moe_router.w_0 [D, E]``, ``gpt_<i>_moe_{gate,up}.w_0 [held, D,
F]``, ``gpt_<i>_moe_down.w_0 [held, F, D]``,
``gpt_<i>_moe_shared_{gate,up,down}.w_0`` and
``gpt_<i>_moe_shared_sgate.w_0 [D, 1]``. ``cfg`` is ``models/gpt.py``'s.

*The control.* ``mantissa_bits`` rounds every weight to that many
explicit mantissa bits as it is used (7 is bfloat16: nothing moves for
bfloat16-valued matrices; the float32 taps, scales and decay parameters
do); ``activation_bits`` also rounds every tensor a layer hands on — the
embedding row, each normalised vector, every projection's output, the
convolution's sum, q, k, v after their norms, both gates, the scores and
the attention weights, the router's probabilities and the chosen gates,
the residual stream after each add and the logits — AND THE DELTA STATE
AFTER EVERY TOKEN (after the decay and after the correction, and what is
read out of it), the way a model kept in that precision computes (norms,
softmax, sigmoid and the decay in float32 inside, their results rounded).
Together they are the control: what the precision below the float32 the
configuration states would answer. The rounding is done on the bits, not
by a cast there and back, which the TPU compiler is free to drop as
excess precision."""

import functools

import numpy as np

QUERY_BLOCK = 256     # queries a step of the blocked attention
VOCAB_BLOCK = 16384   # ids a step of the head
CONV_TAPS = 4
L2_EPS = 1e-6         # under the root of q's and k's l2 norm


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * scale


def _rope(t, theta, dim):
    """Rotate-half RoPE on the first ``dim`` values of ``t [H, T, Dh]`` at
    positions 0..T-1; the other values pass."""
    import jax.numpy as jnp

    _, T, _dh = t.shape
    half = dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dim)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = t[..., :half], t[..., half:dim]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            t[..., dim:]], axis=-1)


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
def causal_conv(x, taps):
    """``out[t] = sum_j taps[:, j] x[t - K + 1 + j]`` over ``x [T, C]``,
    zeros before the sequence."""
    import jax.numpy as jnp

    T, K = x.shape[0], taps.shape[1]
    past = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    out = past[0:T] * taps[:, 0]
    for j in range(1, K):
        out = out + past[j:j + T] * taps[:, j]
    return out


def delta_rule(q, k, v, g, beta, rnd=lambda t: t):
    """The recurrence of the module docstring, one token after another:
    ``q``, ``k`` ``[T, Hv, Dk]`` (normalised, each value head's own copy),
    ``v [T, Hv, Dv]``, ``g``, ``beta`` ``[T, Hv]``. Returns ``[T, Hv,
    Dv]``."""
    import jax
    import jax.numpy as jnp

    def step(S, t):
        qt, kt, vt, gt, bt = t
        S = rnd(S * jnp.exp(gt)[:, None, None])
        u = rnd(bt[:, None] * (vt - rnd(jnp.einsum("hkv,hk->hv", S, kt))))
        S = rnd(S + kt[:, :, None] * u[:, None, :])
        return S, rnd(jnp.einsum("hkv,hk->hv", S, qt))

    S0 = jnp.zeros(q.shape[1:] + v.shape[-1:], jnp.float32)
    _, out = jax.lax.scan(step, S0, (q, k, v, g, beta))
    return out


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


def route(m, router_w, top_k, norm_topk, rnd=lambda t: t):
    """The router on ``m [T, D]``: (the chosen experts ``[T, k]``, their
    gates ``[T, k]``, per token how far the last chosen probability
    stands over the first rejected one)."""
    import jax
    import jax.numpy as jnp

    p = rnd(jax.nn.softmax((m @ router_w).astype(jnp.float32), axis=-1))
    ranked, sel = jax.lax.top_k(p, min(top_k + 1, p.shape[1]))
    if top_k < p.shape[1]:
        gap = ranked[:, top_k - 1] - ranked[:, top_k]
    else:
        gap = jnp.full(m.shape[:1], jnp.inf, jnp.float32)
    sel, w = sel[:, :top_k], ranked[:, :top_k]
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return sel, rnd(w), gap


def experts(m, router_w, expert_w, first, n_held, top_k, norm_topk,
            rnd=lambda t: t):
    """The routed part of THIS share on ``m [T, D]``: every held expert
    on every token, the token's chosen ones selected by a mask of gates,
    one expert after another (one body for every expert:
    ``jax.lax.scan``). ``expert_w(j)`` gives the ``j``-th held expert's
    three float32 matrices (expert ``first + j`` of the router's), widened
    one expert at a time. Returns (the sum, ``route``'s gap)."""
    import jax
    import jax.numpy as jnp

    sel, w, gap = route(m, router_w, top_k, norm_topk, rnd)

    def add(out, j):
        gate = jnp.sum(jnp.where(sel == first + j, w, 0.0), axis=1)
        return out + swiglu(m, *expert_w(j), rnd) * gate[:, None], None

    out, _ = jax.lax.scan(add, jnp.zeros_like(m), jnp.arange(n_held))
    return rnd(out), gap


LAYER_PARAMS = {
    "delta": ("pre1_ln_s", "delta_in.w_0", "delta_ba.w_0",
              "delta_conv.w_0", "delta_a_log", "delta_dt_b",
              "delta_norm_s", "delta_out.w_0"),
    "full": ("pre1_ln_s", "att_q.w_0", "att_g.w_0", "att_k.w_0",
             "att_v.w_0", "att_o.w_0", "att_qnorm_s", "att_knorm_s"),
    "moe": ("pre2_ln_s", "moe_router.w_0", "moe_gate.w_0", "moe_up.w_0",
            "moe_down.w_0", "moe_shared_gate.w_0", "moe_shared_up.w_0",
            "moe_shared_down.w_0", "moe_shared_sgate.w_0"),
}


def _widen(mantissa_bits, activation_bits):
    """``(wide, r)``: an array widened to float32 (and rounded), and the
    rounding of a tensor a layer hands on."""
    import jax.numpy as jnp

    def wide(t):
        t = jnp.asarray(t, jnp.float32)
        return t if mantissa_bits is None \
            else round_mantissa(t, mantissa_bits)

    def r(t):
        return t if activation_bits is None \
            else round_mantissa(t, activation_bits)

    return wide, r


def mixer(p, x, cfg_items, kind, mantissa_bits=None, activation_bits=None):
    """A layer's first sub-block with its residual on ``x [T, D]``."""
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_items)
    wide, r = _widen(mantissa_bits, activation_bits)

    def w(name):
        return wide(p[name])

    eps = cfg.get("norm_eps") or 1e-6
    T = x.shape[0]
    with jax.default_matmul_precision("highest"):
        u = r(_rms_norm(x, w("pre1_ln_s"), eps))
        if kind == "delta":
            Hk, Dk = cfg["delta_k_heads"], cfg["delta_k_dim"]
            Hv, Dv = cfg["delta_v_heads"], cfg["delta_v_dim"]
            kw, vw = Hk * Dk, Hv * Dv
            proj = r(u @ w("delta_in.w_0"))
            ba = r(u @ w("delta_ba.w_0"))
            qkv = r(jax.nn.silu(r(causal_conv(proj[:, :2 * kw + vw],
                                              w("delta_conv.w_0")))))
            z = proj[:, 2 * kw + vw:]

            def unit(t):
                t = t.reshape(T, Hk, Dk)
                t = t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + L2_EPS)
                return jnp.repeat(t, Hv // Hk, axis=1)     # [T, Hv, Dk]

            q = r(unit(qkv[:, :kw]) * Dk ** -0.5)
            k = r(unit(qkv[:, kw:2 * kw]))
            v = qkv[:, 2 * kw:].reshape(T, Hv, Dv)
            beta = r(jax.nn.sigmoid(ba[:, :Hv]))
            g = -jnp.exp(w("delta_a_log")) * jax.nn.softplus(
                ba[:, Hv:] + w("delta_dt_b"))
            o = delta_rule(q, k, v, g, beta, r)
            o = r(_rms_norm(o, w("delta_norm_s"), eps)).reshape(T, vw)
            y = r(r(o * r(jax.nn.silu(z))) @ w("delta_out.w_0"))
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
            dim = int(cfg.get("rope_dim") or q.shape[-1])
            q, k = r(_rope(q, theta, dim)), r(_rope(k, theta, dim))
            gate = r(jax.nn.sigmoid(r(u @ w("att_g.w_0"))))
            y = r(r(attention(q, k, v, r) * gate) @ w("att_o.w_0"))
        return r(x + y)


def moe(p, h, cfg_items, mantissa_bits=None, activation_bits=None):
    """A layer's second sub-block with its residual on ``h [T, D]``:
    (what it hands on, ``[T]`` the router's gap)."""
    import jax

    cfg = dict(cfg_items)
    wide, r = _widen(mantissa_bits, activation_bits)

    def w(name):
        return wide(p[name])

    eps = cfg.get("norm_eps") or 1e-6
    with jax.default_matmul_precision("highest"):
        m = r(_rms_norm(h, w("pre2_ln_s"), eps))

        def expert_w(j):
            return tuple(wide(p[name][j]) for name in (
                "moe_gate.w_0", "moe_up.w_0", "moe_down.w_0"))

        f, gap = experts(
            m, w("moe_router.w_0"), expert_w,
            int(cfg.get("expert_first") or 0), p["moe_gate.w_0"].shape[0],
            cfg["expert_top_k"], bool(cfg.get("norm_topk", False)), r)
        shared = swiglu(m, w("moe_shared_gate.w_0"), w("moe_shared_up.w_0"),
                        w("moe_shared_down.w_0"), r)
        if cfg.get("shared_expert_gate"):
            shared = r(shared * r(jax.nn.sigmoid(
                r(m @ w("moe_shared_sgate.w_0")))))
        return r(h + r(f + shared)), gap


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
def _compiled(piece, cfg_items, mantissa_bits, activation_bits, **kw):
    import jax

    return jax.jit(functools.partial(
        piece, cfg_items=cfg_items, mantissa_bits=mantissa_bits,
        activation_bits=activation_bits, **kw))


def hidden(weights, cfg, ids, mantissa_bits=None, activation_bits=None):
    """``(x [T, D], gaps [T])``: the residual stream after the last
    layer, before the final norm, and for each position the smallest
    router gap of its own routings over the layers."""
    import jax.numpy as jnp

    items = _hashable(cfg)
    ids = jnp.asarray(ids)
    gaps = jnp.full(ids.shape[:1], jnp.inf, jnp.float32)
    x = jnp.asarray(weights["gpt_word_emb"])[ids].astype(jnp.float32)
    if mantissa_bits is not None:
        x = round_mantissa(x, mantissa_bits)
    if activation_bits is not None:
        x = round_mantissa(x, activation_bits)
    for i, kind in enumerate(cfg["layer_types"]):
        def held(names):
            return {nm: weights["gpt_%d_%s" % (i, nm)] for nm in names
                    if "gpt_%d_%s" % (i, nm) in weights}

        x = _compiled(mixer, items, mantissa_bits, activation_bits,
                      kind=kind)(held(LAYER_PARAMS[kind]), x)
        x, gap = _compiled(moe, items, mantissa_bits, activation_bits)(
            held(LAYER_PARAMS["moe"]), x)
        gaps = jnp.minimum(gaps, gap)
    return x, gaps


@functools.lru_cache(maxsize=None)
def _head(eps, mantissa_bits, activation_bits):
    import jax

    def head(x, scale, w_block):
        wide, r = _widen(mantissa_bits, activation_bits)
        with jax.default_matmul_precision("highest"):
            return r(r(_rms_norm(x, wide(scale), eps)) @ wide(w_block))

    return jax.jit(head)


def logits_of(weights, cfg, x, mantissa_bits=None, activation_bits=None):
    """``[rows, vocab]`` logits of the residual rows ``x``, the head a
    block of ``VOCAB_BLOCK`` ids at a time (a list of blocks)."""
    head = _head(cfg.get("norm_eps") or 1e-6, mantissa_bits,
                 activation_bits)
    w = weights["gpt_out_proj.w_0"]
    return [head(x, weights["gpt_ln_f_s"], w[:, lo:lo + VOCAB_BLOCK])
            for lo in range(0, w.shape[1], VOCAB_BLOCK)]


def forward(weights, cfg, ids, mantissa_bits=None, activation_bits=None,
            with_gaps=False, rows=None):
    """Logits ``[T, vocab]`` of the causal forward pass over ``ids [T]``
    (``rows = (lo, hi)``: of positions ``lo .. hi - 1`` only), computed
    at the highest matmul precision, a sub-block at a time. ``with_gaps``
    also returns the smallest router gap (``route``) of each of those
    positions over the layers."""
    import jax.numpy as jnp

    x, gaps = hidden(weights, cfg, ids, mantissa_bits, activation_bits)
    if rows is not None:
        x, gaps = x[rows[0]:rows[1]], gaps[rows[0]:rows[1]]
    logits = jnp.concatenate(logits_of(weights, cfg, x, mantissa_bits,
                                       activation_bits), axis=-1)
    return (logits, gaps) if with_gaps else logits


def greedy_margin_fn(weights, cfg, pad_multiple, controls=()):
    """``margins(tokens, prompt_len)``: how far the reference disagrees
    with a greedy answer. For every generated token, the reference's
    largest logit at that position minus its logit for the token chosen
    (0 where they agree). The answer is teacher-forced through ONE
    forward pass, padded to the next multiple of ``pad_multiple`` so that
    the probes share a few executables (causal attention, a causal
    convolution and a recurrence keep the padding out of the positions
    that count); the head runs over the answer's positions only.

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
