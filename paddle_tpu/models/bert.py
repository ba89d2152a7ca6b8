"""BERT-base masked-LM pretraining graph.

The benchmark's training configuration (``benchmarks/configs/bert-base.json``
builds it). The reference repo has no BERT in-tree; this is
built from the same fluid-style layer calls its transformer test uses
(/root/reference/python/paddle/fluid/tests/unittests/dist_transformer.py),
with the standard BERT embedding sum (word+position+segment) and a
gather-based MLM head over statically-shaped masked positions.
"""

from .. import layers
from ..core.program import name_scope
from ..param_attr import ParamAttr
from .transformer import encoder

__all__ = ["base_config", "build"]


def base_config():
    return dict(d_model=768, d_ff=3072, n_head=12, n_layer=12,
                vocab=30522, type_vocab=2, max_length=512, dropout=0.1)


def _bert_embed(src_ids, sent_ids, cfg, seq_len, is_test):
    word = layers.embedding(src_ids, [cfg["vocab"], cfg["d_model"]],
                            param_attr=ParamAttr(name="word_embedding"))
    # learned positions: ids 0..S-1, [1,S,D] broadcasts over the batch
    pos_ids = layers.reshape(layers.range(0, seq_len, 1, "int64"),
                             [1, seq_len])
    pos = layers.embedding(pos_ids, [cfg["max_length"], cfg["d_model"]],
                           param_attr=ParamAttr(name="pos_embedding"))
    sent = layers.embedding(sent_ids, [cfg["type_vocab"], cfg["d_model"]],
                            param_attr=ParamAttr(name="sent_embedding"))
    emb = layers.elementwise_add(layers.elementwise_add(word, pos), sent)
    emb = layers.layer_norm(emb, begin_norm_axis=2,
                            param_attr=ParamAttr(name="emb_ln_s"),
                            bias_attr=ParamAttr(name="emb_ln_b"))
    if cfg["dropout"]:
        emb = layers.dropout(emb, cfg["dropout"], is_test=is_test)
    return emb


def build(cfg=None, seq_len=128, max_mask=20, is_test=False,
          use_fused_attention=None, checkpoints=None):
    """MLM training graph. Feeds: src_ids/sent_ids [B,S] int64,
    input_mask [B,S] float (1=real token), mask_pos [B,max_mask] int64
    (flattened B*S positions), mask_label [B,max_mask] int64 (pad rows
    point at position 0 with weight 0 via mask_weight).
    use_fused_attention defaults to the PADDLE_TPU_FUSED_ATTENTION env
    flag (default on) so hardware A/B runs need no code edit."""
    if use_fused_attention is None:
        from ..ops.attention import fused_attention_enabled

        use_fused_attention = fused_attention_enabled()
    cfg = cfg or base_config()
    src_ids = layers.data("src_ids", [seq_len], dtype="int64")
    sent_ids = layers.data("sent_ids", [seq_len], dtype="int64")
    input_mask = layers.data("input_mask", [seq_len], dtype="float32")
    mask_pos = layers.data("mask_pos", [max_mask], dtype="int64")
    mask_label = layers.data("mask_label", [max_mask], dtype="int64")
    mask_weight = layers.data("mask_weight", [max_mask], dtype="float32")

    # every op stands under a name_scope its device operations answer to
    # (docs/OBSERVABILITY.md "Which part of the model an operation is")
    with name_scope("attn.core"):
        # [B,S] 0/1 -> [B,1,1,S] additive bias
        neg = layers.scale(input_mask, scale=1e9, bias=-1e9)  # 1->0, 0->-1e9
        attn_bias = layers.unsqueeze(layers.unsqueeze(neg, [1]), [1])

    with name_scope("embed"):
        emb = _bert_embed(src_ids, sent_ids, cfg, seq_len, is_test)
    enc = encoder(emb, attn_bias, cfg, is_test, use_fused_attention,
                  checkpoints=checkpoints)

    with name_scope("head"):
        # MLM head: gather masked positions from the flattened sequence
        flat = layers.reshape(enc, [-1, cfg["d_model"]])          # [B*S, D]
        picked = layers.gather(flat,
                               layers.reshape(mask_pos, [-1]))  # [B*M, D]
        h = layers.fc(picked, cfg["d_model"], act="gelu",
                      param_attr=ParamAttr(name="mlm_trans.w_0"))
        h = layers.layer_norm(h, begin_norm_axis=1,
                              param_attr=ParamAttr(name="mlm_ln_s"),
                              bias_attr=ParamAttr(name="mlm_ln_b"))
        logits = layers.fc(h, cfg["vocab"],
                           param_attr=ParamAttr(name="mlm_out.w_0"))
    with name_scope("loss"):
        cost = layers.softmax_with_cross_entropy(
            logits, layers.reshape(mask_label, [-1, 1]))       # [B*M, 1]
        w = layers.reshape(mask_weight, [-1, 1])
        loss = layers.elementwise_div(
            layers.reduce_sum(layers.elementwise_mul(cost, w)),
            layers.elementwise_add(
                layers.reduce_sum(w),
                layers.fill_constant([1], "float32", 1e-6)))
    feeds = [src_ids, sent_ids, input_mask, mask_pos, mask_label, mask_weight]
    return loss, feeds
