"""The plain reference of the decoder the serving cells run: GPT-2's
forward pass in straightforward ``jax.numpy`` and float32 — no cache, no
batching, no kernel — after the published description (pre-LayerNorm
blocks, learned positions, exact GELU, tied output head), with the
departures ``benchmarks/configs/gpt2-medium.json`` lists: no biases on the
attention projections, and LayerNorm scales 1, LayerNorm and FFN biases 0
(what the startup program leaves in every vector).

``weights`` maps the program's parameter names to the matrices
(``gpt_word_emb``, ``gpt_pos_emb``, ``gpt_<i>_att_{q,k,v,o}.w_0``,
``gpt_<i>_ffn{1,2}.w_0``)."""

import numpy as np


def _layer_norm(x, eps=1e-5):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps)


def forward(weights, cfg, ids):
    """Logits ``[T, vocab]`` of the causal forward pass over ``ids [T]``,
    computed at the highest matmul precision."""
    import jax
    import jax.numpy as jnp

    n_head = cfg["n_head"]
    T = ids.shape[0]
    with jax.default_matmul_precision("highest"):
        x = weights["gpt_word_emb"][ids] + weights["gpt_pos_emb"][:T]
        causal = jnp.tril(jnp.ones((T, T), bool))
        for i in range(cfg["n_layer"]):
            nm = "gpt_%d" % i
            h = _layer_norm(x)

            def heads(t):
                return t.reshape(T, n_head, -1).transpose(1, 0, 2)

            q = heads(h @ weights[nm + "_att_q.w_0"])
            k = heads(h @ weights[nm + "_att_k.w_0"])
            v = heads(h @ weights[nm + "_att_v.w_0"])
            scores = q @ k.transpose(0, 2, 1) * (q.shape[-1] ** -0.5)
            scores = jnp.where(causal[None], scores, -jnp.inf)
            ctx = jax.nn.softmax(scores, axis=-1) @ v
            ctx = ctx.transpose(1, 0, 2).reshape(T, -1)
            x = x + ctx @ weights[nm + "_att_o.w_0"]
            h = _layer_norm(x)
            h = jax.nn.gelu(h @ weights[nm + "_ffn1.w_0"],
                            approximate=False)
            x = x + h @ weights[nm + "_ffn2.w_0"]
        return _layer_norm(x) @ weights["gpt_word_emb"].T


def greedy_margin_fn(weights, cfg, pad_to):
    """``margin(tokens, prompt_len)``: how far the reference disagrees
    with a greedy answer. For every generated token, the reference's
    largest logit at that position minus its logit for the token the
    system chose (0 where they agree). The answer is teacher-forced
    through ONE forward pass, padded to ``pad_to`` so that every probe
    shares the one executable (causal attention keeps the padding out of
    the positions that count)."""
    import jax
    import jax.numpy as jnp

    logits_of = jax.jit(lambda w, ids: forward(w, cfg, ids))

    def margin(tokens, prompt_len):
        T = len(tokens)
        ids = np.zeros(pad_to, np.int64)
        ids[:T] = tokens
        logits = logits_of(weights, jnp.asarray(ids))
        at = np.asarray(logits[prompt_len - 1:T - 1])
        chosen = np.asarray(tokens[prompt_len:T])
        return at.max(axis=-1) - at[np.arange(len(chosen)), chosen]

    return margin
