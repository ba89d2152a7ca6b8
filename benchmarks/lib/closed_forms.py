"""Operations and bytes the algorithms need, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change the
arithmetic its gain is counted in. Recomputed operations never count.
"""


def bert_matmul_params(cfg):
    """Encoder weights that multiply every token: per layer the four
    attention projections and the two FFN matrices."""
    d, dff = cfg["d_model"], cfg["d_ff"]
    return cfg["n_layer"] * (4 * d * d + 2 * d * dff)


def bert_train_flops_per_token(cfg, seq, max_mask):
    """Forward + backward FLOPs per input token of one masked-LM step.

    6 x parameters for the encoder matmuls (2 forward, 4 backward);
    attention scores and context are 2 x 2*S*d forward per layer, x3 with
    the backward; the MLM head (transform + vocabulary projection) runs
    on ``max_mask`` of ``seq`` positions only."""
    d = cfg["d_model"]
    dense = 6 * bert_matmul_params(cfg)
    attention = cfg["n_layer"] * 3 * 4 * seq * d
    head = 6 * (d * d + d * cfg["vocab"]) * max_mask / float(seq)
    return {"dense": dense, "attention": attention, "head": head,
            "total": dense + attention + head}


def gpt_param_count(cfg):
    """Every stored weight of the decoder as models/gpt.py builds it:
    token and position tables (the LM head is the tied token table),
    per layer four bias-free attention projections, the FFN with biases
    and two LayerNorms, and the final LayerNorm."""
    d, dff = cfg["d_model"], cfg["d_ff"]
    per_layer = 4 * d * d + 2 * d * dff + dff + d + 4 * d
    tables = cfg["vocab"] * d + cfg["max_length"] * d
    head = 0 if cfg.get("tie_embeddings") else cfg["vocab"] * d
    return tables + head + cfg["n_layer"] * per_layer + 2 * d


def gpt_cache_elements_per_slot(cfg, max_len):
    """K and V rows one sequence holds: layers x 2 x heads x max_len x
    head size."""
    d_head = cfg["d_model"] // cfg["n_head"]
    n_kv = cfg.get("n_kv_head", cfg["n_head"])
    return cfg["n_layer"] * 2 * n_kv * max_len * d_head


def gpt_decode_step_bytes(cfg, b_max, max_len, cache_itemsize,
                          weight_itemsize):
    """Bytes one decode step must stream: every weight once (the position
    table is only looked up, so it is left out) and both cache slabs of
    all ``b_max`` slots, whatever the occupancy — the step reads the
    slabs whole."""
    weights = (gpt_param_count(cfg)
               - cfg["max_length"] * cfg["d_model"]) * weight_itemsize
    cache = gpt_cache_elements_per_slot(cfg, max_len) * b_max \
        * cache_itemsize
    return {"weights": weights, "cache": cache, "total": weights + cache}


def flash_train_roofline(batch, n_head, seq, d_head, n_layer, itemsize,
                         peaks):
    """Least seconds per train step for the attention kernels of all
    layers, forward and backward, and which peak bounds it.

    Operations: 2 matmuls forward (QK^T, PV) and 4 backward (dV, dP, dQ,
    dK) of 2*S*S*d_head each per head; the backward's recomputation of
    the scores is not counted. Bytes: forward reads Q, K, V and writes O;
    backward reads Q, K, V, O, dO and writes dQ, dK, dV."""
    per_matmul = 2.0 * batch * n_head * seq * seq * d_head
    flops = n_layer * 6 * per_matmul
    tensor = batch * n_head * seq * d_head * itemsize
    nbytes = n_layer * 12 * tensor
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes,
            "seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
