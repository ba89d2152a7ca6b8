"""The plain reference of NVIDIA-Nemotron-3-Super-120B-A12B (``model_type``
nemotron_h, nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16): its forward
pass in straightforward ``jax.numpy`` and float32 at the highest matmul
precision — no cache, no batching, no kernel, no chunking: the
state-space recurrence runs TOKEN BY TOKEN (``jax.lax.scan`` over the
positions), attention over the whole sequence, every held expert
computed densely on every token and selected by a mask. It imports
nothing from ``paddle_tpu``.

Every layer ``i`` is ONE mixer behind one pre-norm, ``x <- x +
Mixer_i(RMSNorm(x; g_i))``, chosen by ``cfg['mixers'][i]``; after the
last, one RMSNorm and the untied head. ``eps`` is ``norm_eps``
everywhere.

*``ssm`` (Mamba-2)*. ``H`` heads of ``P`` in ``G`` groups, state ``N``,
``K`` convolution taps, ``d_in = H P``. With ``u`` the normed input::

    [z | xBC | dt] = u W_in              # d_in | d_in + 2 G N | H
    xBC_t = silu(sum_j w_conv[:, j] xBC_{t-K+1+j} + b_conv)   # zeros before 0
    x_t [H, P], B_t [G, N], C_t [G, N] = split(xBC_t);  g = h // (H / G)
    dt_t,h = softplus(dt_t,h + dt_bias_h);  a_t,h = exp(dt_t,h A_h),
    A_h = -exp(A_log_h)
    S_t,h = a_t,h S_t-1,h + dt_t,h x_t,h (x) B_t,g      # [P, N], zero before 0
    y_t,h = S_t,h C_t,g + D_h x_t,h
    y_t   = RMSNorm over each of the G groups of d_in / G values of
            (y_t * silu(z_t)), times a scale [d_in]      # gate BEFORE norm
    out   = y_t W_out

*``attention``*. ``n_head`` query heads and ``n_kv_head`` key-value heads
of ``d_head``, no biases, causal, scale ``d_head ** -0.5``, NO position
added anywhere (``pos_emb = 'none'``: the ``nemotron_h`` modelling code
applies no rotary embedding; the recurrence orders the tokens).

*``experts`` (in a latent)*. ``s = sigmoid(u W_r)`` in float32 over all
``n_expert``; the ``expert_top_k`` largest of ``s + b`` (one group);
``w = route_scale s[sel] / (sum s[sel] + 1e-20)``. ``l = u W_down``
(``d_model -> d_expert_in``); expert ``e``: ``W2_e relu(W1_e l)^2``, no
gate, no bias; routed part ``W_up sum_e w_e f_e(l)``; the shared expert
``W2_s relu(W1_s u)^2`` on the full ``u``; the mixer's output is their
sum. A SHARE of the experts (``n_expert_local``, ``expert_first``)
computes its held experts' part of the routed sum — ``W_up`` applied to
that partial sum — and the shared expert whole, as the program does.

Departures from the published model: the weights are whatever the caller
hands in (the benchmark draws them from a seed) — bfloat16-valued arrays,
as the checkpoint is published, each WIDENED to float32 where it
multiplies, an expert at a time; activations, the state and the keys and
values are float32 where the published model computes in bfloat16; the
next-token-prediction layer is absent; ties among the scores resolve as
``jax.lax.top_k`` resolves them (lowest index first); attention is
computed a block of queries at a time and the experts one after another
under one traced body (the same numbers). The forward pass runs A LAYER
AT A TIME (one jitted function a mixer kind), and ``greedy_margin_fn``
runs the head over the answer's rows only.

``weights`` maps the program's parameter names to arrays: ``gpt_word_emb
[V, D]``, ``gpt_out_proj.w_0 [D, V]``, ``gpt_ln_f_s [D]`` and per layer
``gpt_<i>_pre1_ln_s [D]`` and, by its kind, ``gpt_<i>_ssm_in.w_0 [D, 2
d_in + 2 G N + H]``, ``gpt_<i>_ssm_conv.w_0 [d_in + 2 G N, K]``,
``gpt_<i>_ssm_conv.b_0``, ``gpt_<i>_ssm_{a_log,d,dt_b} [H]``,
``gpt_<i>_ssm_norm_s [d_in]``, ``gpt_<i>_ssm_out.w_0 [d_in, D]``;
``gpt_<i>_att_{q,k,v,o}.w_0``; ``gpt_<i>_moe_router.w_0 [D, E]``,
``gpt_<i>_moe_router_bias [E]``, ``gpt_<i>_moe_lat_down.w_0 [D, L]``,
``gpt_<i>_moe_up.w_0 [held, L, F]``, ``gpt_<i>_moe_down.w_0 [held, F,
L]``, ``gpt_<i>_moe_lat_up.w_0 [L, D]``, ``gpt_<i>_moe_shared_{up,
down}.w_0``. ``cfg`` is ``models/gpt.py``'s.

``mantissa_bits`` rounds every weight to that many explicit mantissa bits
as it is used (7 is bfloat16: nothing moves for bfloat16-valued weights);
``activation_bits`` also rounds every tensor a layer hands on — the
embedding row, the residual after each layer, each normed vector, every
matmul's output, the convolution's output, ``dt`` and the decay, THE
STATE AFTER EVERY TOKEN and each ``y``, the gated and normed vector; q, k
and v (what a cache in that precision would hold), the scores and the
attention weights; the router's scores and gates, the latent, each
expert's hidden and output, the final logits. That is the control: what
the precision the checkpoint is published in would answer where the
engine keeps float32. The rounding is done on the bits, not by a cast
there and back, which the TPU compiler is free to drop as excess
precision."""

import functools

import numpy as np

QUERY_BLOCK = 256   # queries a step of the blocked attention


def _rms_norm(x, scale, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


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


# ------------------------------------------------------- the three mixers
def conv(xbc, w, b, rnd=lambda t: t):
    """``silu`` of the causal depth-wise convolution of ``xbc [T, C]``
    under ``w [C, K]`` and ``b [C]``, zeros before the sequence."""
    import jax
    import jax.numpy as jnp

    T, K = xbc.shape[0], w.shape[1]
    wide = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    acc = sum(wide[j:j + T] * w[:, j] for j in range(K)) + b
    return rnd(jax.nn.silu(rnd(acc)))


def recurrence(x, dt, a_log, bm, cm, d, rnd=lambda t: t):
    """The selective recurrence token by token: ``x [T, H, P]``, ``dt
    [T, H]`` (positive), ``bm`` / ``cm`` ``[T, G, N]``. Returns ``(y [T,
    H, P], the state after the last token [H, P, N])``."""
    import jax
    import jax.numpy as jnp

    T, H, P = x.shape
    G, N = bm.shape[1:]
    decay = rnd(jnp.exp(dt * -jnp.exp(a_log)))              # [T, H]

    def step(S, c):
        a_t, dt_t, x_t, b_t, c_t = c
        b_h = jnp.repeat(b_t, H // G, axis=0)               # [H, N]
        c_h = jnp.repeat(c_t, H // G, axis=0)
        S = rnd(a_t[:, None, None] * S
                + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        y = jnp.sum(S * c_h[:, None, :], axis=-1) + d[:, None] * x_t
        return S, rnd(y)

    S, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (decay, dt, x, bm, cm))
    return y, S


def ssm(u, p, widths, eps, wide, rnd=lambda t: t):
    """The state-space mixer on the normed ``u [T, D]``."""
    import jax
    import jax.numpy as jnp

    H, P, G, N = widths
    d_in, T = H * P, u.shape[0]
    proj = rnd(u @ wide(p["ssm_in.w_0"]))
    z, xbc, dt = jnp.split(proj, [d_in, 2 * d_in + 2 * G * N], axis=-1)
    xbc = conv(xbc, wide(p["ssm_conv.w_0"]), wide(p["ssm_conv.b_0"]), rnd)
    x, bm, cm = jnp.split(xbc, [d_in, d_in + G * N], axis=-1)
    dt = rnd(jax.nn.softplus(dt + wide(p["ssm_dt_b"])))
    y, _ = recurrence(x.reshape(T, H, P), dt, wide(p["ssm_a_log"]),
                      bm.reshape(T, G, N), cm.reshape(T, G, N),
                      wide(p["ssm_d"]), rnd)
    g = rnd(y.reshape(T, d_in) * jax.nn.silu(z)).reshape(T, G, d_in // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    g = rnd(g.reshape(T, d_in) * wide(p["ssm_norm_s"]))
    return rnd(g @ wide(p["ssm_out.w_0"]))


def attention(u, p, heads, wide, rnd=lambda t: t):
    """Causal grouped-head attention without positions on the normed
    ``u [T, D]``, a block of ``QUERY_BLOCK`` queries at a time."""
    import jax
    import jax.numpy as jnp

    n_head, n_kv, d_head = heads
    T = u.shape[0]
    q = rnd(u @ wide(p["att_q.w_0"])).reshape(T, n_head, d_head)
    k = rnd(u @ wide(p["att_k.w_0"])).reshape(T, n_kv, d_head)
    v = rnd(u @ wide(p["att_v.w_0"])).reshape(T, n_kv, d_head)
    k = jnp.repeat(k, n_head // n_kv, axis=1)
    v = jnp.repeat(v, n_head // n_kv, axis=1)
    blk = min(QUERY_BLOCK, T)
    Tp = -(-T // blk) * blk
    qp = jnp.pad(q, ((0, Tp - T), (0, 0), (0, 0))).reshape(
        Tp // blk, blk, n_head, d_head)
    cols = jnp.arange(T)

    def block(c):
        qb, start = c
        s = rnd(jnp.einsum("qhd,thd->hqt", qb, k) * d_head ** -0.5)
        rows = start + jnp.arange(blk)
        s = jnp.where(cols[None, None, :] <= rows[None, :, None], s,
                      -jnp.inf)
        w = rnd(jax.nn.softmax(s, axis=-1))
        return rnd(jnp.einsum("hqt,thd->qhd", w, v))

    ctx = jax.lax.map(block, (qp, jnp.arange(Tp // blk) * blk))
    ctx = ctx.reshape(Tp, n_head * d_head)[:T]
    return rnd(ctx @ wide(p["att_o.w_0"]))


def relu2(m, w_up, w_down, rnd=lambda t: t):
    import jax

    return rnd(rnd(jax.numpy.square(jax.nn.relu(rnd(m @ w_up)))) @ w_down)


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


def experts(u, p, cfg, wide, rnd=lambda t: t):
    """The expert mixer on the normed ``u [T, D]``: ``(the held share's
    part of the routed sum through W_up plus the shared expert, route's
    gap)``. Every HELD expert on every token, the token's chosen ones
    selected by a mask of gates, one expert after another."""
    import jax
    import jax.numpy as jnp

    n_held = int(cfg.get("n_expert_local") or cfg["n_expert"])
    first = int(cfg.get("expert_first") or 0)
    bias = p.get("moe_router_bias")
    sel, w, gap = route(u, wide(p["moe_router.w_0"]),
                        None if bias is None else wide(bias),
                        int(cfg["expert_top_k"]),
                        bool(cfg.get("norm_topk", False)),
                        float(cfg.get("route_scale") or 1.0), rnd)
    lat = rnd(u @ wide(p["moe_lat_down.w_0"])) \
        if "moe_lat_down.w_0" in p else u

    def add(out, e):
        gate = jnp.sum(jnp.where(sel == first + e, w, 0.0), axis=1)
        f = relu2(lat, wide(p["moe_up.w_0"][e]), wide(p["moe_down.w_0"][e]),
                  rnd)
        return out + f * gate[:, None], None

    out, _ = jax.lax.scan(add, jnp.zeros_like(lat), jnp.arange(n_held))
    out = rnd(out)
    if "moe_lat_up.w_0" in p:
        out = rnd(out @ wide(p["moe_lat_up.w_0"]))
    if "moe_shared_up.w_0" in p:
        out = rnd(out + relu2(u, wide(p["moe_shared_up.w_0"]),
                              wide(p["moe_shared_down.w_0"]), rnd))
    return out, gap


LAYER_PARAMS = {
    "ssm": ("ssm_in.w_0", "ssm_conv.w_0", "ssm_conv.b_0", "ssm_a_log",
            "ssm_d", "ssm_dt_b", "ssm_norm_s", "ssm_out.w_0"),
    "attention": ("att_q.w_0", "att_k.w_0", "att_v.w_0", "att_o.w_0"),
    "experts": ("moe_router.w_0", "moe_router_bias", "moe_lat_down.w_0",
                "moe_up.w_0", "moe_down.w_0", "moe_lat_up.w_0",
                "moe_shared_up.w_0", "moe_shared_down.w_0"),
}


def layer(p, x, cfg_items, kind, mantissa_bits=None, activation_bits=None):
    """One layer on ``x [T, D]``: (``x + Mixer(RMSNorm(x))``, ``[T]`` the
    router's gap, inf for a layer without one). ``p`` maps the layer's
    parameter names WITHOUT their ``gpt_<i>_`` prefix to arrays."""
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_items)
    eps = cfg.get("norm_eps") or 1e-6

    def wide(t):
        t = jnp.asarray(t, jnp.float32)
        return t if mantissa_bits is None \
            else round_mantissa(t, mantissa_bits)

    def r(t):
        return t if activation_bits is None \
            else round_mantissa(t, activation_bits)

    gap = jnp.full(x.shape[:1], jnp.inf, jnp.float32)
    with jax.default_matmul_precision("highest"):
        u = r(_rms_norm(x, wide(p["pre1_ln_s"]), eps))
        if kind == "ssm":
            y = ssm(u, p, tuple(int(cfg[k]) for k in (
                "ssm_heads", "ssm_head_dim", "ssm_groups", "ssm_state")),
                eps, wide, r)
        elif kind == "attention":
            n_head = int(cfg["n_head"])
            y = attention(u, p, (
                n_head, int(cfg.get("n_kv_head") or n_head),
                int(cfg.get("d_head") or cfg["d_model"] // n_head)),
                wide, r)
        else:
            y, gap = experts(u, p, cfg, wide, r)
        return r(x + y), gap


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
def _compiled(cfg_items, kind, mantissa_bits, activation_bits):
    import jax

    return jax.jit(functools.partial(
        layer, cfg_items=cfg_items, kind=kind,
        mantissa_bits=mantissa_bits, activation_bits=activation_bits))


def hidden(weights, cfg, ids, mantissa_bits=None, activation_bits=None):
    """``(x [T, D], gaps [T])``: the residual after the last layer,
    before the final norm, and the smallest router gap of each position
    over the expert layers."""
    import jax.numpy as jnp

    def r(t):
        return t if activation_bits is None \
            else round_mantissa(t, activation_bits)

    items = _hashable(cfg)
    ids = jnp.asarray(ids)
    gaps = jnp.full(ids.shape[:1], jnp.inf, jnp.float32)
    x = jnp.asarray(weights["gpt_word_emb"])[ids].astype(jnp.float32)
    if mantissa_bits is not None:
        x = round_mantissa(x, mantissa_bits)
    x = r(x)
    for i, kind in enumerate(cfg["mixers"]):
        names = ("pre1_ln_s",) + LAYER_PARAMS[kind]
        p = {nm: weights["gpt_%d_%s" % (i, nm)] for nm in names
             if "gpt_%d_%s" % (i, nm) in weights}
        x, gap = _compiled(items, kind, mantissa_bits, activation_bits)(
            p, x)
        gaps = jnp.minimum(gaps, gap)
    return x, gaps


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
    the probes share a few executables (the recurrence and causal
    attention keep the padding out of the positions that count); the
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
