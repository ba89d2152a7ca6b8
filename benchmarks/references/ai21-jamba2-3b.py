"""The plain reference of AI21-Jamba2-3B (``model_type`` jamba,
ai21labs/AI21-Jamba2-3B; Mamba, arXiv:2312.00752, as Jamba has it,
arXiv:2403.19887): its forward pass in straightforward ``jax.numpy`` and
float32 at the highest matmul precision — the selective recurrence TOKEN
BY TOKEN (one ``lax.scan`` over the positions, the ``[C, N]`` state in the
carry), the convolution as four shifted products, attention as a full
causal softmax, no block, no cache, no batching, no kernel. It imports
nothing from ``paddle_tpu``.

Layer ``i`` (RMSNorm ``eps`` = ``norm_eps`` everywhere, every projection
bias-free except where said)::

    h  = x + mixer_i(RMSNorm(x; g1_i))
    x' = h + W_down (silu(W_gate m) * W_up m),   m = RMSNorm(h; g2_i)

and after the last layer ``logits = RMSNorm(x; g_f) E^T`` with ``E`` the
token table itself (``tie_embeddings``).

*Mamba layer* (``layer_types[i] == "mamba"``: ``C`` = ``mamba_inner``
channels, ``N`` = ``mamba_state`` states a channel, ``R`` =
``mamba_dt_rank``, ``K`` = ``ssm_conv`` taps). With ``h [T, D]`` the
normed input: ``[u | z] = h W_in`` (``C | C``); ``u <- silu(conv_K(u) +
b_conv)``, causal and depth-wise (``taps [C, K]``, tap ``K - 1`` on the
position itself, zeros before the sequence); ``z`` does not pass the
convolution. ``[delta | B | C] = u W_x`` (``R | N | N``) of the CONVOLVED
``u``, each through an RMSNorm of its own (Jamba's three inner norms, one
scale vector each). ``dt = softplus(delta W_dt + b_dt)``; ``A =
-exp(A_log) [C, N]``. With ``S [C, N]`` zero before the sequence::

    S_t[c, n] = exp(dt_t[c] A[c, n]) S_t-1[c, n] + dt_t[c] B_t[n] u_t[c]
    y_t[c]    = sum_n S_t[c, n] C_t[n] + D[c] u_t[c]

then ``out = (y * silu(z)) W_out``. No norm behind the scan.

*Attention layer* (``"full"``): ``q = h W_q`` at ``n_head`` heads of
``d_head``, ``k = h W_k`` and ``v = h W_v`` at ``n_kv_head``; NO rotary
or other position (the recurrences order the tokens); causal softmax at
``1 / sqrt(d_head)``, ``H / Hkv`` query heads a key-value head; ``W_o``.
Computed a block of ``QUERY_BLOCK`` queries at a time against all the
keys so that 17,408 positions fit; the FFN ``ROW_BLOCK`` rows at a time.

Departures from the published model: the weights are whatever the caller
hands in (the benchmark draws them from a seed) — bfloat16-valued
matrices, as the checkpoint is published, each WIDENED to float32 where
it multiplies; activations and the state are float32 where the published
model computes in bfloat16; the layer order (attention where ``i mod 14
== 7``) is the caller's ``layer_types``; ``num_experts`` 1 makes every
FFN the dense one. ``greedy_margin_fn`` runs the head over the answer's
rows only and a block of the vocabulary at a time, so the reference fits
on the chip next to the engine it judges.

``weights`` maps the program's parameter names to arrays: ``gpt_word_emb
[V, D]``, ``gpt_ln_f_s [D]`` and per layer ``gpt_<i>_pre{1,2}_ln_s [D]``,
``gpt_<i>_ffn{1,1v,2}.w_0`` (gate, up, down); a mamba layer's
``gpt_<i>_mamba_in.w_0 [D, 2 C]``, ``gpt_<i>_mamba_conv.{w,b}_0 [C, K]``
/ ``[C]``, ``gpt_<i>_mamba_x.w_0 [C, R + 2 N]``,
``gpt_<i>_mamba_{dt,b,c}norm_s``, ``gpt_<i>_mamba_dt.w_0 [R, C]``,
``gpt_<i>_mamba_dt_b [C]``, ``gpt_<i>_mamba_a_log [C, N]``,
``gpt_<i>_mamba_d [C]``, ``gpt_<i>_mamba_out.w_0 [C, D]``; a full layer's
``gpt_<i>_att_{q,k,v,o}.w_0``. ``cfg`` is ``models/gpt.py``'s.

*The control.* ``mantissa_bits`` rounds every weight to that many
explicit mantissa bits as it is used (7 is bfloat16: nothing moves for
bfloat16-valued matrices; the float32 taps, scales, ``A_log``, ``D`` and
biases do); ``activation_bits`` also rounds every tensor a layer hands on
— the embedding row, each normalised vector, every projection's output,
the convolution's sum, ``dt``, the scores and the attention weights, the
residual stream after each add and the logits — AND THE RECURRENT STATE
AFTER EVERY TOKEN (and what is read out of it), the way a model kept in
that precision computes (norms, softmax, softplus and the decay in
float32 inside, their results rounded). Together they are the control:
what the precision below the float32 the configuration states would
answer. The rounding is done on the bits, not by a cast there and back,
which the TPU compiler is free to drop as excess precision."""

import functools

import numpy as np

QUERY_BLOCK = 256     # queries a step of the blocked attention
ROW_BLOCK = 2048      # rows a step of the FFN
VOCAB_BLOCK = 16384   # ids a step of the head


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * scale


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
    zeros before the sequence: ``K`` shifted products."""
    import jax.numpy as jnp

    T, K = x.shape[0], taps.shape[1]
    past = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    out = past[0:T] * taps[:, 0]
    for j in range(1, K):
        out = out + past[j:j + T] * taps[:, j]
    return out


def selective_scan(u, dt, a, bm, cm, rnd=lambda t: t):
    """The recurrence of the module docstring, one token after another:
    ``u``, ``dt`` ``[T, C]`` (``dt`` positive), ``a [C, N]`` (negative),
    ``bm``, ``cm`` ``[T, N]``. Returns ``(y [T, C]`` without the skip,
    the state after the last position ``[C, N])``."""
    import jax
    import jax.numpy as jnp

    def step(S, t):
        ut, dtt, bt, ct = t
        S = rnd(jnp.exp(dtt[:, None] * a) * S
                + (dtt * ut)[:, None] * bt[None, :])
        return S, rnd(jnp.sum(S * ct[None, :], axis=-1))

    S0 = jnp.zeros(a.shape, jnp.float32)
    return jax.lax.scan(step, S0, (u, dt, bm, cm))[::-1]


def attention(q, k, v, rnd=lambda t: t):
    """Causal softmax attention of ``q [H, T, Dh]`` over ``k, v [Hkv, T,
    Dh]`` (``H / Hkv`` query heads a key-value head) WITHOUT positions, a
    block of ``QUERY_BLOCK`` queries at a time against all the keys under
    the causal mask (one body for every block: ``jax.lax.map``). Returns
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
        # (the barrier is no arithmetic: without it the TPU compiler fuses
        # the scores' product through the softmax into this one at some
        # lengths, and its cost model of that nest overflows its stack)
        probs = jax.lax.optimization_barrier(
            rnd(jax.nn.softmax(scores, axis=-1)))
        return rnd(probs @ v)

    out = jax.lax.map(block, jnp.arange(blocks) * qb)      # [n, H, qb, Dh]
    ctx = out.transpose(1, 0, 2, 3).reshape(H, blocks * qb, dh)[:, :T]
    return ctx.transpose(1, 0, 2).reshape(T, H * dh)


def _precision(mantissa_bits, activation_bits):
    """The matmul precision a piece computes at: the highest, but for a
    control whose every operand is rounded to bfloat16's 7 bits or fewer
    — there ONE bfloat16 pass multiplies the operands exactly and sums in
    float32, the same products at a sixth of the time."""
    rounded = [b for b in (mantissa_bits, activation_bits) if b is not None]
    return "default" if len(rounded) == 2 and max(rounded) <= 7 \
        else "highest"


def _widen(p, mantissa_bits, activation_bits):
    """``(w, r)``: parameter ``name`` of ``p`` widened to float32 (and
    rounded), and the rounding of a tensor a layer hands on."""
    import jax.numpy as jnp

    def w(name):
        t = jnp.asarray(p[name], jnp.float32)
        return t if mantissa_bits is None \
            else round_mantissa(t, mantissa_bits)

    def r(t):
        return t if activation_bits is None \
            else round_mantissa(t, activation_bits)

    return w, r


def mamba(p, x, cfg_items, mantissa_bits=None, activation_bits=None):
    """The Mamba mixer with its residual on ``x [T, D]``."""
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_items)
    w, r = _widen(p, mantissa_bits, activation_bits)
    eps = cfg.get("norm_eps") or 1e-6
    C, N, R = cfg["mamba_inner"], cfg["mamba_state"], cfg["mamba_dt_rank"]
    with jax.default_matmul_precision(
            _precision(mantissa_bits, activation_bits)):
        h = r(_rms_norm(x, w("pre1_ln_s"), eps))
        proj = r(h @ w("mamba_in.w_0"))
        z = proj[:, C:]
        u = r(jax.nn.silu(r(causal_conv(proj[:, :C], w("mamba_conv.w_0"))
                            + w("mamba_conv.b_0"))))
        dbc = r(u @ w("mamba_x.w_0"))
        delta = r(_rms_norm(dbc[:, :R], w("mamba_dtnorm_s"), eps))
        bm = r(_rms_norm(dbc[:, R:R + N], w("mamba_bnorm_s"), eps))
        cm = r(_rms_norm(dbc[:, R + N:], w("mamba_cnorm_s"), eps))
        dt = r(jax.nn.softplus(r(delta @ w("mamba_dt.w_0"))
                               + w("mamba_dt_b")))
        y, _state = selective_scan(u, dt, -jnp.exp(w("mamba_a_log")), bm,
                                   cm, r)
        y = r(y + w("mamba_d") * u)
        out = r(r(y * r(jax.nn.silu(z))) @ w("mamba_out.w_0"))
        return r(x + out)


def attend(p, x, cfg_items, mantissa_bits=None, activation_bits=None):
    """The attention sub-block with its residual on ``x [T, D]``."""
    import jax

    cfg = dict(cfg_items)
    w, r = _widen(p, mantissa_bits, activation_bits)
    eps = cfg.get("norm_eps") or 1e-6
    n_head = cfg["n_head"]
    n_kv = cfg.get("n_kv_head") or n_head
    T = x.shape[0]
    with jax.default_matmul_precision(
            _precision(mantissa_bits, activation_bits)):
        h = r(_rms_norm(x, w("pre1_ln_s"), eps))

        def heads(t, n):
            return t.reshape(T, n, -1).transpose(1, 0, 2)  # [n, T, Dh]

        q = heads(r(h @ w("att_q.w_0")), n_head)
        k = heads(r(h @ w("att_k.w_0")), n_kv)
        v = heads(r(h @ w("att_v.w_0")), n_kv)
        return r(x + r(attention(q, k, v, r) @ w("att_o.w_0")))


def dense(p, h, cfg_items, mantissa_bits=None, activation_bits=None):
    """The SwiGLU FFN with its residual on ``h [T, D]``, ``ROW_BLOCK``
    rows at a time."""
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_items)
    w, r = _widen(p, mantissa_bits, activation_bits)
    eps = cfg.get("norm_eps") or 1e-6
    T, D = h.shape
    rb = min(ROW_BLOCK, T)
    blocks = -(-T // rb)
    with jax.default_matmul_precision(
            _precision(mantissa_bits, activation_bits)):
        scale = w("pre2_ln_s")
        w_gate, w_up, w_down = (w("ffn1.w_0"), w("ffn1v.w_0"),
                                w("ffn2.w_0"))

        def block(rows):
            m = r(_rms_norm(rows, scale, eps))
            f = r(r(jax.nn.silu(r(m @ w_gate)) * r(m @ w_up)) @ w_down)
            return r(rows + f)

        out = jax.lax.map(block, jnp.pad(
            h, ((0, blocks * rb - T), (0, 0))).reshape(blocks, rb, D))
        return out.reshape(blocks * rb, D)[:T]


MIXERS = {
    "mamba": (mamba, ("pre1_ln_s", "mamba_in.w_0", "mamba_conv.w_0",
                      "mamba_conv.b_0", "mamba_x.w_0", "mamba_dtnorm_s",
                      "mamba_bnorm_s", "mamba_cnorm_s", "mamba_dt.w_0",
                      "mamba_dt_b", "mamba_a_log", "mamba_d",
                      "mamba_out.w_0")),
    "full": (attend, ("pre1_ln_s", "att_q.w_0", "att_k.w_0", "att_v.w_0",
                      "att_o.w_0")),
}
DENSE = ("pre2_ln_s", "ffn1.w_0", "ffn1v.w_0", "ffn2.w_0")


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
def _compiled(piece, cfg_items, mantissa_bits, activation_bits):
    import jax

    return jax.jit(functools.partial(
        piece, cfg_items=cfg_items, mantissa_bits=mantissa_bits,
        activation_bits=activation_bits))


def hidden(weights, cfg, ids, mantissa_bits=None, activation_bits=None):
    """``x [T, D]``: the residual stream after the last layer, before the
    final norm."""
    import jax.numpy as jnp

    items = _hashable(cfg)
    x = jnp.asarray(weights["gpt_word_emb"])[jnp.asarray(ids)] \
        .astype(jnp.float32)
    if mantissa_bits is not None:
        x = round_mantissa(x, mantissa_bits)
    if activation_bits is not None:
        x = round_mantissa(x, activation_bits)
    for i, kind in enumerate(cfg["layer_types"]):
        piece, names = MIXERS[kind]
        for piece, names in ((piece, names), (dense, DENSE)):
            p = {nm: weights["gpt_%d_%s" % (i, nm)] for nm in names}
            x = _compiled(piece, items, mantissa_bits, activation_bits)(p, x)
    return x


@functools.lru_cache(maxsize=None)
def _head(eps, mantissa_bits, activation_bits):
    import jax

    def head(x, scale, rows):
        w, r = _widen({"s": scale, "e": rows}, mantissa_bits,
                      activation_bits)
        with jax.default_matmul_precision(
                _precision(mantissa_bits, activation_bits)):
            return r(r(_rms_norm(x, w("s"), eps)) @ w("e").T)

    return jax.jit(head)


def logits_of(weights, cfg, x, mantissa_bits=None, activation_bits=None):
    """``[rows, vocab]`` logits of the residual rows ``x`` over the token
    table itself, a block of ``VOCAB_BLOCK`` ids at a time (a list of
    blocks)."""
    head = _head(cfg.get("norm_eps") or 1e-6, mantissa_bits,
                 activation_bits)
    table = weights["gpt_word_emb"]
    return [head(x, weights["gpt_ln_f_s"], table[lo:lo + VOCAB_BLOCK])
            for lo in range(0, table.shape[0], VOCAB_BLOCK)]


def forward(weights, cfg, ids, mantissa_bits=None, activation_bits=None,
            rows=None):
    """Logits ``[T, vocab]`` of the causal forward pass over ``ids [T]``
    (``rows = (lo, hi)``: of positions ``lo .. hi - 1`` only), computed
    at the highest matmul precision, a piece of a layer at a time."""
    import jax.numpy as jnp

    x = hidden(weights, cfg, ids, mantissa_bits, activation_bits)
    if rows is not None:
        x = x[rows[0]:rows[1]]
    return jnp.concatenate(logits_of(weights, cfg, x, mantissa_bits,
                                     activation_bits), axis=-1)


@functools.lru_cache(maxsize=None)
def _reducers():
    """Jitted ``(top, pick)`` over ONE block of the head's logits ``[r,
    ids]`` that starts at id ``lo``: each row's largest logit with its id,
    and each row's logit for a given id (0 where the id lies in another
    block). One executable a shape: no gather of a run's own length."""
    import jax
    import jax.numpy as jnp

    def top(block, lo):
        return block.max(axis=-1), block.argmax(axis=-1).astype(
            jnp.int32) + lo

    def pick(block, lo, ids):
        local = ids - lo
        inside = (local >= 0) & (local < block.shape[1])
        got = jnp.take_along_axis(
            block, jnp.clip(local, 0, block.shape[1] - 1)[:, None],
            axis=1)[:, 0]
        return jnp.where(inside, got, 0.0)

    return jax.jit(top), jax.jit(pick)


def _best(blocks):
    """(each row's largest logit, its id) over the head's blocks."""
    import jax.numpy as jnp

    top, _pick = _reducers()
    tops, args = zip(*(top(b, jnp.int32(n * VOCAB_BLOCK))
                       for n, b in enumerate(blocks)))
    tops, args = jnp.stack(tops), jnp.stack(args)           # [n, r]
    first = tops.argmax(axis=0)[None]
    return (jnp.take_along_axis(tops, first, axis=0)[0],
            jnp.take_along_axis(args, first, axis=0)[0])


def _chosen(blocks, ids):
    """Each row's logit for its id ``ids [r]``, over the head's blocks."""
    import jax.numpy as jnp

    _top, pick = _reducers()
    return sum(pick(b, jnp.int32(n * VOCAB_BLOCK), ids)
               for n, b in enumerate(blocks))


def greedy_margin_fn(weights, cfg, pad_multiple, controls=()):
    """``margins(tokens, prompt_len)``: how far the reference disagrees
    with a greedy answer. For every generated token, the reference's
    largest logit at that position minus its logit for the token chosen
    (0 where they agree). The answer is teacher-forced through ONE
    forward pass, padded to the next multiple of ``pad_multiple`` so that
    the probes share a few executables (causal attention, a causal
    convolution and a recurrence keep the padding out of the positions
    that count); the head runs over the answer's positions only, a block
    of the vocabulary at a time, and only each row's best logit, its
    index and the chosen token's logit are kept.

    Returns ``(margins, gaps)``. ``margins`` is a list of arrays: first
    the system's own tokens judged so, then, for each entry of
    ``controls`` (``(mantissa_bits, activation_bits)``), the tokens the
    reference itself would choose at each position of the same sequence
    computed so (the state rounded after every token) — the reading a
    limit has to leave outside. ``gaps`` is inf at every position: the
    model has no router whose near-ties would have to be left out."""
    import jax.numpy as jnp

    def margins(tokens, prompt_len):
        T = len(tokens)
        ids = np.zeros(-(-T // pad_multiple) * pad_multiple, np.int64)
        ids[:T] = tokens
        lo, hi = prompt_len - 1, T - 1
        # the controls' choices first, each pass's logits dropped before
        # the next pass: only one ``[rows, vocab]`` is ever held
        choices = [jnp.asarray(tokens[prompt_len:T], jnp.int32)]
        for wb, ab in controls:
            x = hidden(weights, cfg, ids, wb, ab)
            choices.append(_best(logits_of(weights, cfg, x[lo:hi], wb,
                                           ab))[1])
        x = None
        blocks = logits_of(weights, cfg, hidden(weights, cfg, ids)[lo:hi])
        top = np.asarray(_best(blocks)[0])
        return [top - np.asarray(_chosen(blocks, c)) for c in choices], \
            np.full(hi - lo, np.inf, np.float32)

    return margins
