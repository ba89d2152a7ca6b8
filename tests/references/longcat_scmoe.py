"""The plain reference of LongCat-Flash's language model (``model_type``
longcat_flash, meituan-longcat/LongCat-Flash-Omni): its forward pass in
straightforward ``jax.numpy`` and float32 at the highest matmul precision
— latent attention in its EXPANDED form over the whole sequence, no
cache, no batching, no kernel, every held expert computed densely on
every token and selected by a mask, the identity experts as ``w * m`` —
after the layer as its public implementation (``transformers``
``modeling_longcat_flash.py``) has it. A published layer ``l`` on the
token state ``x`` (shortcut-connected experts: ONE routed branch forks
after the first attention and joins after the second dense FFN)::

    a0 = x  + MLA[l,0](N_in0(x))
    m  = N_post0(a0)
    s  = Routed[l](m)
    b0 = a0 + SwiGLU[l,0](m)
    a1 = b0 + MLA[l,1](N_in1(b0))
    y  = a1 + SwiGLU[l,1](N_post1(a1)) + s

* ``MLA`` on ``h``: ``c_q = RMSNorm_q(h W_dq)`` (``q_lora_rank``); ``[q_nope
  | q_rope] = (c_q W_uq) * sqrt(d_model / q_lora_rank)`` (both parts:
  ``mla_scale_q_lora``), ``n_head`` heads of ``d_nope + d_rope``; ``q_rope
  = RoPE(q_rope, pos)`` (rotate-half over the ``d_rope`` dims, base
  ``rope_theta``, no scaling). ``[c | k_r] = h W_dkv`` (``kv_lora_rank +
  d_rope``); ``c = RMSNorm_kv(c) * sqrt(d_model / kv_lora_rank)``
  (``mla_scale_kv_lora``; ``k_r`` is NOT scaled); ``k_r = RoPE(k_r, pos)``,
  one rotated key part a token that all heads share. Per head ``[k_nope |
  v] = c W_ukv``, ``k = [k_nope | k_r]``, ``p = softmax_causal(q k^T /
  sqrt(d_nope + d_rope))``, output ``concat(p v) W_o``.
* ``SwiGLU``: ``(silu(m Wg) * (m Wu)) Wd``, no biases.
* ``Routed`` on ``m``: ``p = softmax(m W_r)`` in float32 over ALL ``n_expert
  + n_zero_expert`` outputs; the ``expert_top_k`` largest of ``p + b`` (``b``
  the selection-only correction term); ``w_e = route_scale * p_e`` for the
  chosen, NOT renormalised; ``s = sum_{chosen e < n_expert} w_e SwiGLU_e(m)
  + (sum_{chosen e >= n_expert} w_e) m``: an identity (zero-compute) expert
  returns the token itself. No shared expert, no token ever dropped.
* After the last layer ``logits = RMSNorm_f(x) W_head``, untied; the
  embedding row is not scaled.

The program numbers SUB-LAYERS (``models/gpt.py`` under ``shortcut_moe``):
``cfg['n_layer']`` is twice the published layers, sub-layer ``2 l + k`` is
``MLA[l,k]`` and ``SwiGLU[l,k]`` with its two norms, and the branch's
parameters carry the even sub-layer's number.

Departures from the published model: the weights are whatever the caller
hands in (the benchmark draws them from a seed) — bfloat16-valued arrays,
as the checkpoint is published, each WIDENED to float32 where it
multiplies; activations are float32 where the published model computes in
bfloat16; the audio and vision encoders and the codec decoder of the Omni
model are absent (this is its language model); ties among ``p + b``
resolve as ``jax.lax.top_k`` resolves them (lowest index first); attention
is computed a block of queries at a time (the same numbers). The forward
pass runs A PIECE OF A SUB-LAYER AT A TIME (attention, the dense FFN, the
branch: one jitted function each): the widened copy of one piece's
matrices is all that stands beside the caller's own arrays, so the
reference fits on the chip next to the engine it judges. THE SHARE: with
``n_expert_local`` < ``n_expert`` the weights hold only the experts ``expert_first .. expert_first + n_expert_local - 1``; the
router still scores and selects among all ``n_expert + n_zero_expert``, and
what the absent experts would add is left out — the branch is this chip's
part of the routed sum plus the identity part, which every chip of a
deployment computes alike for its own tokens.

``weights`` maps the program's parameter names to arrays: ``gpt_word_emb
[V, D]``, ``gpt_out_proj.w_0 [D, V]``, ``gpt_ln_f_s [D]`` and per sub-layer
``gpt_<j>_{pre1,pre2}_ln_s [D]``, ``gpt_<j>_att_qa.w_0 [D, q_lora_rank]``,
``gpt_<j>_att_qa_ln_s``, ``gpt_<j>_att_qb.w_0 [q_lora_rank, H (d_nope +
d_rope)]``, ``gpt_<j>_att_kva.w_0 [D, d_c + d_rope]``,
``gpt_<j>_att_kva_ln_s [d_c]``, ``gpt_<j>_att_kvb.w_0 [d_c, H (d_nope +
d_v)]``, ``gpt_<j>_att_o.w_0 [H d_v, D]``, ``gpt_<j>_ffn{1,1v}.w_0 [D, F]``,
``gpt_<j>_ffn2.w_0 [F, D]``, and for an even ``j`` ``gpt_<j>_moe_router.w_0
[D, E + Z]``, ``gpt_<j>_moe_router_bias [E + Z]``, ``gpt_<j>_moe_{gate,up}
.w_0 [E_local, D, F_e]``, ``gpt_<j>_moe_down.w_0 [E_local, F_e, D]``.
``cfg`` is ``models/gpt.py``'s. ``mantissa_bits`` rounds every weight to
that many explicit mantissa bits as it is used (7 is bfloat16: nothing
moves for bfloat16-valued weights); ``activation_bits`` also rounds every
tensor the layer hands on — the embedding row, each normalised vector,
both latents, q, k and v (so the latent row a cache would hold), the
scores, the attention weights, every matmul's output, the residual stream
after each add, the router's probabilities, the chosen gates, the branch
and the final logits — the way a model kept in that precision computes
(norms and softmax in float32 inside, their results rounded). That is the
control: what the precision the checkpoint is published in would answer
where the engine keeps float32. The rounding is done on the bits, not by a
cast there and back, which the TPU compiler is free to drop as excess
precision."""

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


def route(m, router_w, bias, top_k, route_scale, rnd=lambda t: t):
    """The router on ``m [T, D]``: (the chosen outputs ``[T, k]``, their
    gates ``[T, k]``, per token how far the last chosen ``p + b`` stands
    over the first rejected one). Softmax over all the router's outputs;
    ``bias`` moves the selection only; the gates are ``route_scale`` times
    the chosen probabilities, not renormalised."""
    import jax
    import jax.numpy as jnp

    wide = router_w.shape[1]
    p = rnd(jax.nn.softmax((m @ router_w).astype(jnp.float32), axis=-1))
    picked = p if bias is None else p + bias[None, :]
    _, sel = jax.lax.top_k(picked, top_k)                  # [T, k]
    if top_k < wide:
        ranked = jax.lax.top_k(picked, top_k + 1)[0]
        gap = ranked[:, top_k - 1] - ranked[:, top_k]
    else:
        gap = jnp.full(m.shape[:1], jnp.inf, jnp.float32)
    w = jnp.take_along_axis(p, sel, axis=-1)
    return sel, rnd(w * route_scale), gap


def routed(m, router_w, bias, w_gate, w_up, w_down, n_expert, top_k,
           route_scale, expert_first=0, rnd=lambda t: t):
    """The shortcut branch on ``m [T, D]``: every HELD expert (``w_gate
    [E_local, D, F]``: experts ``expert_first ..``) on every token, the
    token's chosen ones selected by a mask of gates; a chosen expert with
    weights that is not held adds nothing; every chosen output from
    ``n_expert`` on is an identity expert and adds its gate times the
    token. Returns (the sum, ``route``'s gap, the identity pairs a token
    ``[T]``)."""
    import jax.numpy as jnp

    sel, w, gap = route(m, router_w, bias, top_k, route_scale, rnd)
    out = jnp.zeros_like(m)
    for e in range(w_gate.shape[0]):
        gate = jnp.sum(jnp.where(sel == expert_first + e, w, 0.0), axis=1)
        out = out + swiglu(m, w_gate[e], w_up[e], w_down[e], rnd) \
            * gate[:, None]
    zero = sel >= n_expert
    out = out + jnp.sum(jnp.where(zero, w, 0.0), axis=1)[:, None] * m
    return rnd(out), gap, jnp.sum(zero, axis=1)


SUBLAYER_PARAMS = {
    "attn": ("pre1_ln_s", "att_qa.w_0", "att_qa_ln_s", "att_qb.w_0",
             "att_kva.w_0", "att_kva_ln_s", "att_kvb.w_0", "att_o.w_0",
             "pre2_ln_s"),
    "dense": ("ffn1.w_0", "ffn1v.w_0", "ffn2.w_0"),
    "branch": ("moe_router.w_0", "moe_router_bias", "moe_gate.w_0",
               "moe_up.w_0", "moe_down.w_0"),
}


def _widen(p, mantissa_bits, activation_bits):
    """``(w, r)``: a parameter of ``p`` widened to float32 (and rounded
    to ``mantissa_bits``), a tensor rounded to ``activation_bits``."""
    import jax.numpy as jnp

    def w(name):
        t = jnp.asarray(p[name], jnp.float32)
        return t if mantissa_bits is None \
            else round_mantissa(t, mantissa_bits)

    def r(t):
        return t if activation_bits is None \
            else round_mantissa(t, activation_bits)

    return w, r


def attend(p, x, cfg_items, mantissa_bits=None, activation_bits=None):
    """The attention sub-block of one sub-layer on the token states ``x
    [T, D]``: (``x + MLA(N_in(x))``, its post-attention norm ``m``)."""
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_items)
    w, r = _widen(p, mantissa_bits, activation_bits)
    H, D = cfg["n_head"], cfg["d_model"]
    dn, dr, dv = cfg["d_nope"], cfg["d_rope"], cfg["d_v"]
    dc = cfg["kv_lora_rank"]
    eps = cfg.get("norm_eps") or 1e-6
    theta = cfg.get("rope_theta") or 10000.0
    q_scale = (D / float(cfg["q_lora_rank"])) ** 0.5 \
        if cfg.get("mla_scale_q_lora") else 1.0
    kv_scale = (D / float(dc)) ** 0.5 \
        if cfg.get("mla_scale_kv_lora") else 1.0
    T = x.shape[0]
    with jax.default_matmul_precision("highest"):
        h = r(_rms_norm(x, w("pre1_ln_s"), eps))
        c_q = r(_rms_norm(r(h @ w("att_qa.w_0")), w("att_qa_ln_s"), eps))
        q = r(r(c_q @ w("att_qb.w_0")) * q_scale).reshape(T, H, dn + dr)
        q = q.transpose(1, 0, 2)                           # [H, T, dn+dr]
        q = jnp.concatenate([q[..., :dn], r(_rope(q[..., dn:], theta))],
                            axis=-1)
        kv = r(h @ w("att_kva.w_0"))                       # [T, dc + dr]
        c = r(r(_rms_norm(kv[:, :dc], w("att_kva_ln_s"), eps)) * kv_scale)
        k_r = r(_rope(kv[:, dc:], theta))                  # [T, dr]
        kvb = r(c @ w("att_kvb.w_0")).reshape(T, H, dn + dv)
        kvb = kvb.transpose(1, 0, 2)                       # [H, T, dn+dv]
        k = jnp.concatenate(
            [kvb[..., :dn], jnp.broadcast_to(k_r[None], (H, T, dr))],
            axis=-1)
        ctx = attention(q, k, kvb[..., dn:], (dn + dr) ** -0.5, r)
        x = r(x + r(ctx @ w("att_o.w_0")))
        m = r(_rms_norm(x, w("pre2_ln_s"), eps))
    return x, m


def dense(p, m, cfg_items, mantissa_bits=None, activation_bits=None):
    """One sub-layer's dense SwiGLU on the normed ``m [T, D]``."""
    import jax

    w, r = _widen(p, mantissa_bits, activation_bits)
    with jax.default_matmul_precision("highest"):
        return swiglu(m, w("ffn1.w_0"), w("ffn1v.w_0"), w("ffn2.w_0"), r)


def branch(p, m, cfg_items, mantissa_bits=None, activation_bits=None):
    """A published layer's routed branch on the normed ``m [T, D]`` of its
    even sub-layer: ``routed``'s (sum, router gap, identity pairs)."""
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_items)
    w, r = _widen(p, mantissa_bits, activation_bits)
    with jax.default_matmul_precision("highest"):
        return routed(
            m, w("moe_router.w_0"),
            jnp.asarray(p["moe_router_bias"], jnp.float32)
            if "moe_router_bias" in p else None,
            w("moe_gate.w_0"), w("moe_up.w_0"), w("moe_down.w_0"),
            cfg["n_expert"], cfg["expert_top_k"],
            float(cfg.get("route_scale") or 1.0),
            int(cfg.get("expert_first") or 0), r)


PIECES = {"attend": (attend, SUBLAYER_PARAMS["attn"]),
          "dense": (dense, SUBLAYER_PARAMS["dense"]),
          "branch": (branch, SUBLAYER_PARAMS["branch"])}


def _hashable(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.lru_cache(maxsize=None)
def _compiled(piece, cfg_items, mantissa_bits, activation_bits):
    import jax

    return jax.jit(functools.partial(
        PIECES[piece][0], cfg_items=cfg_items, mantissa_bits=mantissa_bits,
        activation_bits=activation_bits))


def sublayer(p, x, s, cfg_items, fork, mantissa_bits=None,
             activation_bits=None):
    """One attention-then-dense-FFN sub-layer on the token states ``x [T,
    D]``, A PIECE AT A TIME (attention, the dense FFN, the branch: one
    jitted function each, so that one piece's widened matrices are all
    that stands beside the caller's own). ``fork`` (the even sub-layer of
    a published layer): the routed branch is computed from the
    post-attention norm and handed on, ``s`` is not read; otherwise (the
    odd one) ``s`` joins the residual at the end. Returns (the states
    handed on, the branch — ``s`` itself for a join —, ``[T]`` the
    router's gap, inf for a join, ``[T]`` the identity pairs). ``p`` maps
    the sub-layer's parameter names WITHOUT their ``gpt_<j>_`` prefix to
    the caller's own arrays; each is widened to float32 where it is
    used."""
    import jax.numpy as jnp

    def run(piece, *args):
        names = PIECES[piece][1]
        return _compiled(piece, cfg_items, mantissa_bits, activation_bits)(
            {n: p[n] for n in names if n in p}, *args)

    def r(t):
        return t if activation_bits is None \
            else round_mantissa(t, activation_bits)

    x, m = run("attend", x)
    f = run("dense", m)
    gap = jnp.full(x.shape[:1], jnp.inf, jnp.float32)
    zero = jnp.zeros(x.shape[:1], jnp.int32)
    if fork:
        s, gap, zero = run("branch", m)
    else:
        f = r(f + s)
    return r(x + f), s, gap, zero


def forward(weights, cfg, ids, mantissa_bits=None, activation_bits=None,
            with_gaps=False, with_zero=False):
    """Logits ``[T, vocab]`` of the causal forward pass over ``ids [T]``,
    computed at the highest matmul precision, a sub-layer at a time.
    ``with_gaps`` also returns ``[T]``: the smallest router gap
    (``route``) of the position over the branches; ``with_zero`` ``[L, T]``:
    the identity pairs of each position in each branch."""
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
    x = r(jnp.asarray(weights["gpt_word_emb"])[ids].astype(jnp.float32))
    if mantissa_bits is not None:
        x = r(round_mantissa(x, mantissa_bits))
    s, zeros = None, []
    for j in range(cfg["n_layer"]):
        fork = j % 2 == 0
        prefix = "gpt_%d_" % j
        p = {n[len(prefix):]: v for n, v in weights.items()
             if n.startswith(prefix)}
        x, s, gap, zero = sublayer(p, x, s, items, fork, mantissa_bits,
                                   activation_bits)
        gaps = jnp.minimum(gaps, gap)
        if fork:
            zeros.append(zero)
    with jax.default_matmul_precision("highest"):
        x = r(_rms_norm(x, w("gpt_ln_f_s"), eps))
        logits = r(x @ w("gpt_out_proj.w_0"))
    out = (logits,) + ((gaps,) if with_gaps else ()) \
        + ((jnp.stack(zeros),) if with_zero else ())
    return out if len(out) > 1 else logits


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
