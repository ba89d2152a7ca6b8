"""The plain reference of Brumby-14B-Base (``model_type`` brumby,
manifestai/Brumby-14B-Base; power retention, arXiv:2507.04239): its
forward pass in straightforward ``jax.numpy`` and float32 at the highest
matmul precision, in the ATTENTION form — no state, no cache, no
batching, no kernel, no chunk. It imports nothing from ``paddle_tpu``.

Layer ``i`` (RMSNorm ``eps`` = ``norm_eps`` everywhere)::

    h  = x + W_o retention(RMSNorm(x; g1_i))
    x' = h + W_down (silu(W_gate m) * W_up m),   m = RMSNorm(h; g2_i)

and after the last layer ``logits = RMSNorm(x; g_f) W_head`` (untied).

*Retention, degree 2.* With ``u [T, D]`` the normed input, ``n_head``
query heads and ``n_kv_head`` key-value heads of ``d_head``: ``q = u
W_q``, ``k = u W_k``, ``v = u W_v``, no biases; RMSNorm of q and of k
over each head's ``d_head`` values with one learned ``[d_head]`` scale
each; rotate-half RoPE on q and k over the whole head (``rope_theta``);
one gate a key-value head, ``g_t = sigmoid(u_t w_g + b_g)``. Query head
``h`` reads key-value head ``h // (n_head / n_kv_head)``, and for ``j <=
t``::

    a_tj = (q_t . k_j / sqrt(d_head))^2 * exp(c_t - c_j),
    c_t  = sum_{l <= t} log g_l
    o_t  = sum_j a_tj v_j / (sum_j a_tj + eps),    eps = RETENTION_EPS

No softmax and no maximum: the square is the kernel. Computed a block of
``QUERY_BLOCK`` queries at a time against all the keys under the causal
mask (one body for every block: ``jax.lax.map``).

Departures from the published model: the weights are whatever the caller
hands in (the benchmark draws them from a seed) — bfloat16-valued
matrices, as the checkpoint is published, each WIDENED to float32 where
it multiplies; activations are float32 where the published model
computes in bfloat16. The forward pass runs one WIDENED PIECE of a layer
at a time (the retention sub-block, then the FFN a block of rows at a
time: one jitted function each), and ``greedy_margin_fn`` runs the head
over the answer's rows only and a block of the vocabulary at a time (the
whole ``[T, vocab]`` at 9,216 rows and 151,936 ids would be 5.6 GB, the
widened head 3.1 GB), so the reference fits on the chip next to the
engine it judges.

``weights`` maps the program's parameter names to arrays: ``gpt_word_emb
[V, D]``, ``gpt_out_proj.w_0 [D, V]``, ``gpt_ln_f_s [D]`` and per layer
``gpt_<i>_pre{1,2}_ln_s [D]``, ``gpt_<i>_att_{q,k,v,o}.w_0``,
``gpt_<i>_att_{q,k}norm_s [d_head]``, ``gpt_<i>_att_gamma.w_0 [D,
n_kv_head]``, ``gpt_<i>_att_gamma.b_0 [n_kv_head]``,
``gpt_<i>_ffn{1,1v,2}.w_0`` (gate, up, down). ``cfg`` is
``models/gpt.py``'s.

*The control.* ``mantissa_bits`` rounds every weight to that many
explicit mantissa bits as it is used (7 is bfloat16: nothing moves for
bfloat16-valued matrices; the float32 scales and the gate's bias do);
``activation_bits`` also rounds every tensor a layer hands on — the
embedding row, each normalised vector, q, k, v and the gate, the scores,
the weights ``a``, every matmul's output, the residual stream after each
add and the logits — the way a model kept in that precision computes
(norms, sigmoid and the decay in float32 inside, their results rounded).
A model kept in that precision would also keep its STATE in it, which
the attention form cannot show, so with ``activation_bits`` the positions
from ``tail_from`` on are computed in the RECURRENT form: the state and
the normaliser after position ``tail_from - 1`` are summed from the
attention form's operands and rounded, and each later token decays them,
adds its ``phi(k) v^T`` and ``phi(k)``, ROUNDS both, and reads ``phi(q)^T
S / (phi(q)^T z + eps)`` (every ``phi`` rounded too) — ``phi(a)`` here ALL the products ``a_i a_j /
sqrt(d_head)``, whose inner product is the symmetric half's of 8,256
(``c_ij a_i a_j`` over ``i <= j``, ``c_ii`` = 1, ``c_ij`` = sqrt 2) without
a table of pairs. Together
they are the control: what the precision below the float32 the
configuration states would answer. The rounding is done on the bits, not
by a cast there and back, which the TPU compiler is free to drop as
excess precision."""

import functools

import numpy as np

QUERY_BLOCK = 128     # queries a step of the blocked attention form
ROW_BLOCK = 1024      # rows a step of the FFN
VOCAB_BLOCK = 16384   # ids a step of the head
RETENTION_EPS = 1e-6  # added to the normaliser


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
def retention(q, k, v, lg, eps, rnd=lambda t: t):
    """The attention form (module docstring) of ``q [H, T, Dh]`` over
    ``k, v [G, T, Dh]`` under the log gates ``lg [G, T]``, a block of
    ``QUERY_BLOCK`` queries at a time. Returns ``[T, H Dh]``."""
    import jax
    import jax.numpy as jnp

    H, T, dh = q.shape
    G = k.shape[0]
    c = jnp.cumsum(lg, axis=1)                              # [G, T]
    qb = min(QUERY_BLOCK, T)
    blocks = -(-T // qb)
    pad = blocks * qb - T
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0))).reshape(G, H // G, -1, dh)
    cq = jnp.pad(c, ((0, 0), (0, pad)))

    def block(lo):
        rows = jax.lax.dynamic_slice_in_dim(q, lo, qb, axis=2)
        at = jax.lax.dynamic_slice_in_dim(cq, lo, qb, axis=1)  # [G, qb]
        keep = jnp.arange(T)[None, :] <= (lo + jnp.arange(qb))[:, None]
        s = rnd(jnp.einsum("gjqd,gkd->gjqk", rows, k) * (dh ** -0.5))
        decay = jnp.exp(jnp.where(keep[None], at[:, :, None]
                                  - c[:, None, :], -jnp.inf))
        a = rnd(s * s * decay[:, None])                     # [G, J, qb, T]
        num = rnd(jnp.einsum("gjqk,gkd->gjqd", a, v))
        return rnd(num / (jnp.sum(a, axis=-1, keepdims=True) + eps))

    out = jax.lax.map(block, jnp.arange(blocks) * qb)   # [n, G, J, qb, Dh]
    ctx = jnp.moveaxis(out, 0, 2).reshape(H, blocks * qb, dh)[:, :T]
    return ctx.transpose(1, 0, 2).reshape(T, H * dh)


def phi(a):
    """The square of ``a [..., Dh]`` as ``[..., Dh, Dh]``: ALL the
    products ``a_i a_j / sqrt(Dh)``, so that ``<phi(a), phi(b)> = (a .
    b)^2 / Dh``. The symmetric half of it (``i <= j`` with sqrt 2 off the
    diagonal: ``Dh (Dh + 1) / 2`` = 8,256 values at 128) has the same
    inner product and is what a system would keep; the whole square needs
    no table of pairs."""
    return a[..., :, None] * a[..., None, :] * a.shape[-1] ** -0.5


def retention_tail(q, k, v, lg, eps, start, rnd):
    """The recurrent form from position ``start`` on, the state and the
    normaliser rounded after every token (module docstring: the
    control's). Returns ``[T - start, H Dh]``."""
    import jax
    import jax.numpy as jnp

    H, T, dh = q.shape
    G = k.shape[0]
    c = jnp.cumsum(lg, axis=1)
    # what positions < start leave: decayed to position start - 1
    w = jnp.exp(c[:, start - 1:start] - c[:, :start])      # [G, start]
    sb = min(QUERY_BLOCK, start)
    nb = -(-start // sb)

    def blocked(t):         # [G, start, ...] -> [nb, G, sb, ...], zeros past
        t = jnp.pad(t, ((0, 0), (0, nb * sb - start)) + ((0, 0),)
                    * (t.ndim - 2))
        return jnp.moveaxis(t.reshape((G, nb, sb) + t.shape[2:]), 1, 0)

    def gather(carry, b):
        kb, wb, vb = b
        pk = rnd(phi(kb) * wb[..., None, None])             # [G, sb, Dh, Dh]
        return (carry[0] + jnp.einsum("gsab,gsd->gabd", pk, vb),
                carry[1] + jnp.sum(pk, axis=1)), None

    (S, z), _ = jax.lax.scan(
        gather, (jnp.zeros((G, dh, dh, dh), jnp.float32),
                 jnp.zeros((G, dh, dh), jnp.float32)),
        (blocked(k[:, :start]), blocked(w), blocked(v[:, :start])))
    S, z = rnd(S), rnd(z)

    def step(carry, t):
        S, z = carry
        qt, kt, vt, g = t               # [G, J, Dh], [G, Dh], [G, Dh], [G]
        pkt = rnd(phi(kt))                                  # [G, Dh, Dh]
        S = rnd(g[:, None, None, None] * S
                + pkt[..., None] * vt[:, None, None, :])
        z = rnd(g[:, None, None] * z + pkt)
        pq = rnd(phi(qt))                                   # [G, J, Dh, Dh]
        num = rnd(jnp.einsum("gjab,gabd->gjd", pq, S))
        den = jnp.einsum("gjab,gab->gj", pq, z)
        return (S, z), rnd(num / (den[..., None] + eps))

    qs = q[:, start:].reshape(G, H // G, T - start, dh).transpose(2, 0, 1, 3)
    _, out = jax.lax.scan(step, (S, z), (
        qs, k[:, start:].transpose(1, 0, 2), v[:, start:].transpose(1, 0, 2),
        jnp.exp(lg[:, start:]).T))
    return out.reshape(T - start, H * dh)


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


def attend(p, x, cfg_items, mantissa_bits=None, activation_bits=None,
           tail_from=None):
    """The retention sub-block with its residual on ``x [T, D]``."""
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_items)
    w, r = _widen(p, mantissa_bits, activation_bits)
    eps = cfg.get("norm_eps") or 1e-6
    n_head = cfg["n_head"]
    n_kv = cfg.get("n_kv_head") or n_head
    theta = cfg.get("rope_theta") or 10000.0
    T = x.shape[0]
    with jax.default_matmul_precision(
            _precision(mantissa_bits, activation_bits)):
        u = r(_rms_norm(x, w("pre1_ln_s"), eps))

        def heads(t, n, scale=None):
            t = t.reshape(T, n, -1)
            if scale is not None:
                t = r(_rms_norm(t, scale, eps))
            return t.transpose(1, 0, 2)                    # [n, T, Dh]

        q = heads(r(u @ w("att_q.w_0")), n_head, w("att_qnorm_s"))
        k = heads(r(u @ w("att_k.w_0")), n_kv, w("att_knorm_s"))
        v = heads(r(u @ w("att_v.w_0")), n_kv)
        q, k = r(_rope(q, theta)), r(_rope(k, theta))
        gate = r(u @ w("att_gamma.w_0") + w("att_gamma.b_0"))   # [T, G]
        lg = jax.nn.log_sigmoid(gate).T
        o = retention(q, k, v, lg, RETENTION_EPS, r)
        if tail_from is not None and activation_bits is not None:
            o = jnp.concatenate([o[:tail_from], retention_tail(
                q, k, v, lg, RETENTION_EPS, tail_from, r)])
        return r(x + r(o @ w("att_o.w_0")))


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


PIECES = {
    attend: ("pre1_ln_s", "att_q.w_0", "att_k.w_0", "att_v.w_0",
             "att_o.w_0", "att_qnorm_s", "att_knorm_s", "att_gamma.w_0",
             "att_gamma.b_0"),
    dense: ("pre2_ln_s", "ffn1.w_0", "ffn1v.w_0", "ffn2.w_0"),
}


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


def hidden(weights, cfg, ids, mantissa_bits=None, activation_bits=None,
           tail_from=None):
    """``x [T, D]``: the residual stream after the last layer, before the
    final norm."""
    import jax.numpy as jnp

    if any(t != "retention" for t in cfg["layer_types"]):
        raise ValueError("this reference is retention of degree 2 in "
                         "every layer")
    items = _hashable(cfg)
    x = jnp.asarray(weights["gpt_word_emb"])[jnp.asarray(ids)] \
        .astype(jnp.float32)
    if mantissa_bits is not None:
        x = round_mantissa(x, mantissa_bits)
    if activation_bits is not None:
        x = round_mantissa(x, activation_bits)
    for i in range(cfg["n_layer"]):
        for piece, names in PIECES.items():
            p = {nm: weights["gpt_%d_%s" % (i, nm)] for nm in names}
            kw = {"tail_from": tail_from} if piece is attend else {}
            x = _compiled(piece, items, mantissa_bits, activation_bits,
                          **kw)(p, x)
    return x


@functools.lru_cache(maxsize=None)
def _head(eps, mantissa_bits, activation_bits):
    import jax
    import jax.numpy as jnp

    def head(x, scale, w_block):
        w, r = _widen({"s": scale, "w": w_block}, mantissa_bits,
                      activation_bits)
        with jax.default_matmul_precision(
            _precision(mantissa_bits, activation_bits)):
            return r(r(_rms_norm(x, w("s"), eps)) @ w("w"))

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
            rows=None, tail_from=None):
    """Logits ``[T, vocab]`` of the causal forward pass over ``ids [T]``
    (``rows = (lo, hi)``: of positions ``lo .. hi - 1`` only), computed
    at the highest matmul precision, a piece of a layer at a time."""
    import jax.numpy as jnp

    x = hidden(weights, cfg, ids, mantissa_bits, activation_bits, tail_from)
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
    the probes share a few executables (the causal mask keeps the padding
    out of the positions that count); the head runs over the answer's
    positions only, a block of the vocabulary at a time, and only each
    row's best logit, its index and the chosen token's logit are kept.

    Returns ``(margins, gaps)``. ``margins`` is a list of arrays: first
    the system's own tokens judged so, then, for each entry of
    ``controls`` (``(mantissa_bits, activation_bits)``), the tokens the
    reference itself would choose at each position of the same sequence
    computed so (the answer's positions in the recurrent form, the state
    rounded after every token) — the reading a limit has to leave
    outside. ``gaps`` is inf at every position: the model has no router
    whose near-ties would have to be left out."""
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
            x = hidden(weights, cfg, ids, wb, ab, tail_from=prompt_len)
            choices.append(_best(logits_of(weights, cfg, x[lo:hi], wb,
                                           ab))[1])
        x = None
        blocks = logits_of(weights, cfg, hidden(weights, cfg, ids)[lo:hi])
        top = np.asarray(_best(blocks)[0])
        return [top - np.asarray(_chosen(blocks, c)) for c in choices], \
            np.full(hi - lo, np.inf, np.float32)

    return margins
