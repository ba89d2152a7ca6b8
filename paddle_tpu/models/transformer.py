"""Transformer-base for WMT-style seq2seq.

Reference: the fluid transformer model used by the distributed tests and
benchmarks (/root/reference/python/paddle/fluid/tests/unittests/
dist_transformer.py; benchmark/fluid/models/machine_translation.py is the
older RNN seq2seq). The reference composes attention from matmul/softmax/
elementwise layer calls (SURVEY §5 — no fused attention op); here the same
layer-level composition is used, and XLA fuses the QK^T->softmax->V chain.
Pallas flash attention is available as a drop-in via use_fused_attention.

TPU-first choices vs the reference:
  - fixed max_length padding + in-graph masks instead of LoD ragged batches
  - pre-norm residual blocks (stable without warmup games)
  - sinusoid position table baked as a frozen parameter
"""

import numpy as np

from .. import layers
from ..core.program import name_scope
from ..param_attr import ParamAttr
from ..initializer import NumpyArrayInitializer

__all__ = ["encoder", "decoder", "build", "base_config"]


def base_config():
    """Transformer-base (Vaswani et al.): the dist_transformer config."""
    return dict(d_model=512, d_ff=2048, n_head=8, n_layer=6,
                src_vocab=30000, trg_vocab=30000, max_length=256,
                dropout=0.1)


def _position_table(max_length, d_model):
    pos = np.arange(max_length)[:, None].astype("float64")
    inv = 1.0 / np.power(10000.0, np.arange(0, d_model, 2) / d_model)
    tab = np.zeros((max_length, d_model), dtype="float32")
    tab[:, 0::2] = np.sin(pos * inv)
    tab[:, 1::2] = np.cos(pos * inv)
    return tab


def _embed(ids, vocab, d_model, max_length, dropout, is_test, name):
    """token embedding * sqrt(d) + sinusoid position embedding."""
    emb = layers.embedding(
        ids, size=[vocab, d_model],
        param_attr=ParamAttr(name=name + "_word_emb"))
    emb = layers.scale(emb, scale=d_model ** 0.5)
    seq_len = ids.shape[1]
    pos_tab = _position_table(max_length, d_model)[:seq_len]
    pos = layers.create_parameter(
        [seq_len, d_model], "float32", name=name + "_pos_enc",
        default_initializer=NumpyArrayInitializer(pos_tab))
    pos.stop_gradient = True
    out = layers.elementwise_add(emb, pos)
    if dropout:
        out = layers.dropout(out, dropout, is_test=is_test)
    return out


def _split_heads(x, seq_len, n_head, d_head):
    x = layers.reshape(x, [-1, seq_len, n_head, d_head])
    return layers.transpose(x, perm=[0, 2, 1, 3])


def repeat_kv_heads(x, n_kv_head, n_head, seq_len, d_head):
    """GQA group-repeat: [B, Hkv, S, Dh] -> [B, H, S, Dh] where query
    head h reads kv head h // (H/Hkv) — stack g copies on a new axis
    next to the head axis, then fold."""
    g = n_head // n_kv_head
    if g == 1:
        return x
    x = layers.stack([x] * g, axis=2)          # [B, Hkv, g, S, Dh]
    return layers.reshape(x, [-1, n_head, seq_len, d_head])


def qk_norm(q, k, name, eps):
    """RMSNorm of the projected q and k ([B, S, width]) before the head
    split; parameters ``<name>_qnorm_s`` / ``<name>_knorm_s``, shared by
    name between the training build and the inference graphs."""
    q = layers.rms_norm(q, begin_norm_axis=2, epsilon=eps,
                        param_attr=ParamAttr(name=name + "_qnorm_s"))
    k = layers.rms_norm(k, begin_norm_axis=2, epsilon=eps,
                        param_attr=ParamAttr(name=name + "_knorm_s"))
    return q, k


def multi_head_attention(q_in, kv_in, bias, d_model, n_head, dropout,
                         is_test, name, use_fused_attention=False,
                         causal=False, n_kv_head=None, rope_pos=None,
                         segment_ids=None, qk_norm_eps=None,
                         rope_base=10000.0):
    """causal=True only affects the fused path (in-kernel triangular
    mask + above-diagonal block skipping); the composed path expects the
    causal mask folded into `bias` as before. The fused path without a
    rotation or grouped heads hands the three projections [B, S, H*D] to
    ``layers.fused_attention`` as they are and its result to the output
    projection: the kernels index the heads themselves, and the layer
    holds no transpose. Every other path splits the heads to
    [B, H, S, D] first (rotation and the group repeat work there) and
    merges them after. ``n_kv_head < n_head``
    is grouped-query attention (GQA): k/v project to fewer heads and
    group-repeat before the scores — fewer kv-projection FLOPs and,
    on the decode path (models/gpt.py build_decode_step), an
    H/Hkv-times smaller KV cache. ``rope_pos`` (a [S] int position
    var) applies rotary position embeddings to q and k after the head
    split (self-attention only: the positions index both sides), at
    ``rope_base``. ``qk_norm_eps`` (a number) applies RMSNorm with that
    epsilon to the projected q and k over their whole width, before
    the head split, each with a scale of its own (OLMoE)."""
    n_kv_head = n_kv_head or n_head
    if n_head % n_kv_head:
        raise ValueError("n_head %d must divide by n_kv_head %d"
                         % (n_head, n_kv_head))
    if segment_ids is not None and not use_fused_attention:
        # the composed path has no id-aware masking — silently dropping
        # the pack mask would train on cross-document attention
        raise ValueError(
            "segment_ids requires use_fused_attention=True; the "
            "composed path needs the pack mask folded into `bias` "
            "(models/gpt.py builds it that way)")
    d_head = d_model // n_head
    seq_q = q_in.shape[1]
    seq_kv = kv_in.shape[1]
    with name_scope("attn.qkv"):
        q = layers.fc(q_in, d_model, num_flatten_dims=2, bias_attr=False,
                      param_attr=ParamAttr(name=name + "_q.w_0"))
        k = layers.fc(kv_in, n_kv_head * d_head, num_flatten_dims=2,
                      bias_attr=False,
                      param_attr=ParamAttr(name=name + "_k.w_0"))
        v = layers.fc(kv_in, n_kv_head * d_head, num_flatten_dims=2,
                      bias_attr=False,
                      param_attr=ParamAttr(name=name + "_v.w_0"))
        if qk_norm_eps is not None:
            q, k = qk_norm(q, k, name, qk_norm_eps)
    with name_scope("attn.core"):
        ctxv = _attention_core(q, k, v, bias, d_model, n_head, n_kv_head,
                               seq_q, seq_kv, dropout, is_test,
                               use_fused_attention, causal, rope_pos,
                               segment_ids, rope_base)
    with name_scope("attn.out"):
        return layers.fc(ctxv, d_model, num_flatten_dims=2, bias_attr=False,
                         param_attr=ParamAttr(name=name + "_o.w_0"))


def _attention_core(q, k, v, bias, d_model, n_head, n_kv_head, seq_q, seq_kv,
                    dropout, is_test, use_fused_attention, causal, rope_pos,
                    segment_ids, rope_base):
    """``multi_head_attention`` between its projections: the merged heads
    ``[B, S, d_model]`` of the three projected inputs."""
    d_head = d_model // n_head
    if use_fused_attention and rope_pos is None and n_kv_head == n_head:
        return layers.fused_attention(q, k, v, bias, scale=d_head ** -0.5,
                                      dropout=dropout if not is_test else 0.0,
                                      causal=causal, n_head=n_head,
                                      segment_ids=segment_ids)
    q = _split_heads(q, seq_q, n_head, d_head)
    k = _split_heads(k, seq_kv, n_kv_head, d_head)
    v = _split_heads(v, seq_kv, n_kv_head, d_head)
    if rope_pos is not None:
        # per-head-dim rotation, head-count blind: rotate k at its
        # n_kv_head width, before any GQA repeat
        q = layers.rope(q, rope_pos, base=rope_base)
        k = layers.rope(k, rope_pos, base=rope_base)
    k = repeat_kv_heads(k, n_kv_head, n_head, seq_kv, d_head)
    v = repeat_kv_heads(v, n_kv_head, n_head, seq_kv, d_head)
    if use_fused_attention:
        ctxv = layers.fused_attention(q, k, v, bias, scale=d_head ** -0.5,
                                      dropout=dropout if not is_test else 0.0,
                                      causal=causal,
                                      segment_ids=segment_ids)
    else:
        scores = layers.matmul(q, k, transpose_y=True, alpha=d_head ** -0.5)
        if bias is not None:
            scores = layers.elementwise_add(scores, bias)
        weights = layers.softmax(scores)
        if dropout:
            weights = layers.dropout(weights, dropout, is_test=is_test)
        ctxv = layers.matmul(weights, v)
    ctxv = layers.transpose(ctxv, perm=[0, 2, 1, 3])
    return layers.reshape(ctxv, [-1, seq_q, d_model])


def _ffn(x, d_model, d_ff, name, act="relu", bias=True):
    """act='swiglu' is the gated variant (LLaMA-style): swish(x W_g)
    elementwise-times (x W_v), then the down projection — two up
    projections instead of one, all three still plain MXU matmuls.
    ``bias=False`` leaves the three biases out."""
    b = None if bias else False
    with name_scope("ffn"):
        if act == "swiglu":
            g = layers.fc(x, d_ff, num_flatten_dims=2, act="swish",
                          bias_attr=b,
                          param_attr=ParamAttr(name=name + "_ffn1.w_0"))
            u = layers.fc(x, d_ff, num_flatten_dims=2, bias_attr=b,
                          param_attr=ParamAttr(name=name + "_ffn1v.w_0"))
            h = layers.elementwise_mul(g, u)
        else:
            h = layers.fc(x, d_ff, num_flatten_dims=2, act=act, bias_attr=b,
                          param_attr=ParamAttr(name=name + "_ffn1.w_0"))
        return layers.fc(h, d_model, num_flatten_dims=2, bias_attr=b,
                         param_attr=ParamAttr(name=name + "_ffn2.w_0"))


def _prenorm(x, sub_fn, dropout, is_test, name, norm="layer",
             rms_eps=1e-6, tail="ffn"):
    """``x + dropout(sub_fn(norm(x)))``. ``sub_fn`` names its own ops'
    scopes; ``tail`` is the scope class of the dropout and the residual
    add after it (the sub-block's last: ``attn.out``, ``ffn``, ...)."""
    with name_scope("norm"):
        if norm == "rms":
            h = layers.rms_norm(x, begin_norm_axis=2, epsilon=rms_eps,
                                param_attr=ParamAttr(name=name + "_ln_s"))
        else:
            h = layers.layer_norm(x, begin_norm_axis=2,
                                  param_attr=ParamAttr(name=name + "_ln_s"),
                                  bias_attr=ParamAttr(name=name + "_ln_b"))
    h = sub_fn(h)
    with name_scope(tail):
        if dropout:
            h = layers.dropout(h, dropout, is_test=is_test)
        return layers.elementwise_add(x, h)


def encoder(src_emb, self_bias, cfg, is_test=False, use_fused_attention=False,
            checkpoints=None):
    """checkpoints: pass a list to collect per-layer outputs — the
    recompute boundaries RecomputeOptimizer stores (everything between
    two of them is rematerialized in the backward pass)."""
    x = src_emb
    for i in range(cfg["n_layer"]):
        nm = "enc_%d" % i
        with name_scope("L%d" % i):
            x = _prenorm(x, lambda h, nm=nm: multi_head_attention(
                h, h, self_bias, cfg["d_model"], cfg["n_head"],
                cfg["dropout"], is_test, nm + "_att", use_fused_attention),
                cfg["dropout"], is_test, nm + "_pre1", tail="attn.out")
            x = _prenorm(x, lambda h, nm=nm: _ffn(h, cfg["d_model"],
                                                  cfg["d_ff"], nm),
                         cfg["dropout"], is_test, nm + "_pre2")
        if checkpoints is not None:
            checkpoints.append(x)
    with name_scope("norm"):
        return layers.layer_norm(x, begin_norm_axis=2)


def decoder(trg_emb, enc_out, self_bias, cross_bias, cfg, is_test=False,
            use_fused_attention=False, checkpoints=None,
            self_causal=False):
    """self_causal=True: the fused kernel applies the causal mask itself
    (self_bias then carries only the pad mask) and skips above-diagonal
    blocks — build() picks this automatically on the fused path."""
    x = trg_emb
    for i in range(cfg["n_layer"]):
        nm = "dec_%d" % i
        with name_scope("L%d" % i):
            x = _prenorm(x, lambda h, nm=nm: multi_head_attention(
                h, h, self_bias, cfg["d_model"], cfg["n_head"],
                cfg["dropout"], is_test, nm + "_satt", use_fused_attention,
                causal=self_causal),
                cfg["dropout"], is_test, nm + "_pre1", tail="attn.out")
            x = _prenorm(x, lambda h, nm=nm: multi_head_attention(
                h, enc_out, cross_bias, cfg["d_model"], cfg["n_head"],
                cfg["dropout"], is_test, nm + "_xatt", use_fused_attention),
                cfg["dropout"], is_test, nm + "_pre2", tail="attn.out")
            x = _prenorm(x, lambda h, nm=nm: _ffn(h, cfg["d_model"],
                                                  cfg["d_ff"], nm),
                         cfg["dropout"], is_test, nm + "_pre3")
        if checkpoints is not None:
            checkpoints.append(x)
    with name_scope("norm"):
        return layers.layer_norm(x, begin_norm_axis=2)


def _pad_bias(ids, pad_idx=0):
    """[B,S] ids -> [B,1,1,S] additive attention bias (-1e9 at pads)."""
    pad = layers.fill_constant([1], "int64", pad_idx)
    mask = layers.cast(layers.equal(ids, pad), "float32")
    bias = layers.scale(mask, scale=-1e9)
    return layers.unsqueeze(layers.unsqueeze(bias, [1]), [1])


def _causal_bias(seq_len):
    """[1,1,S,S] additive bias: -1e9 above the diagonal."""
    r = layers.range(0, seq_len, 1, "int64")
    row = layers.unsqueeze(r, [1])           # [S,1] query index i
    col = layers.unsqueeze(r, [0])           # [1,S] key index j
    allowed = layers.cast(layers.less_equal(col, row), "float32")
    bias = layers.scale(layers.elementwise_sub(
        layers.fill_constant([1], "float32", 1.0), allowed), scale=-1e9)
    return layers.unsqueeze(layers.unsqueeze(bias, [0]), [0])


def build(cfg=None, seq_len=64, is_test=False, label_smooth_eps=0.1,
          use_fused_attention=None, checkpoints=None):
    """Full training graph. Returns (avg_cost, feeds).

    use_fused_attention defaults to the PADDLE_TPU_FUSED_ATTENTION env
    flag (default on) so hardware A/B runs need no code edit.
    checkpoints: pass a list to collect per-layer recompute boundaries
    for RecomputeOptimizer (memory for FLOPs at long context)."""
    if use_fused_attention is None:
        from ..ops.attention import fused_attention_enabled

        use_fused_attention = fused_attention_enabled()
    cfg = cfg or base_config()
    src = layers.data("src_ids", [seq_len], dtype="int64")
    trg = layers.data("trg_ids", [seq_len], dtype="int64")
    lbl = layers.data("lbl_ids", [seq_len], dtype="int64")

    src_bias = _pad_bias(src)
    if use_fused_attention:
        # the flash kernel applies causality in-kernel and skips the
        # above-diagonal key blocks — only the pad mask rides as a bias
        trg_bias, trg_causal = _pad_bias(trg), True
    else:
        trg_bias = layers.elementwise_add(_pad_bias(trg),
                                          _causal_bias(seq_len))
        trg_causal = False

    src_emb = _embed(src, cfg["src_vocab"], cfg["d_model"], cfg["max_length"],
                     cfg["dropout"], is_test, "src")
    trg_emb = _embed(trg, cfg["trg_vocab"], cfg["d_model"], cfg["max_length"],
                     cfg["dropout"], is_test, "trg")

    enc_out = encoder(src_emb, src_bias, cfg, is_test, use_fused_attention,
                      checkpoints=checkpoints)
    dec_out = decoder(trg_emb, enc_out, trg_bias, src_bias, cfg, is_test,
                      use_fused_attention, checkpoints=checkpoints,
                      self_causal=trg_causal)

    logits = layers.fc(dec_out, cfg["trg_vocab"], num_flatten_dims=2,
                       bias_attr=False,
                       param_attr=ParamAttr(name="out_proj.w_0"))
    if label_smooth_eps:
        soft = layers.label_smooth(
            layers.one_hot(layers.reshape(lbl, [-1, seq_len, 1]),
                           cfg["trg_vocab"]),
            epsilon=label_smooth_eps)
        cost = layers.softmax_with_cross_entropy(logits, soft, soft_label=True)
    else:
        cost = layers.softmax_with_cross_entropy(
            logits, layers.reshape(lbl, [-1, seq_len, 1]))
    # mask pad positions out of the loss, normalize by real token count
    pad = layers.fill_constant([1], "int64", 0)
    nonpad = layers.cast(layers.not_equal(lbl, pad), "float32")
    cost = layers.elementwise_mul(layers.reshape(cost, [-1, seq_len]), nonpad)
    avg_cost = layers.elementwise_div(
        layers.reduce_sum(cost), layers.reduce_sum(nonpad))
    return avg_cost, [src, trg, lbl]
