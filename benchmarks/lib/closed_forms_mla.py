"""Operations and bytes of a decoder with latent attention (MLA), bf16
matrices and one chip's share of its experts (openPangu-Ultra-MoE,
``model_type`` pangu_ultra_moe, as ``paddle_tpu/models/gpt.py`` builds it
under ``attn="mla"``), from shapes alone. Kept with the benchmark, like
``closed_forms_afmoe.py``, so that no PR that claims a gain can change
the arithmetic its gain is counted in.

What differs from ``closed_forms_afmoe``: a layer keeps ONE latent row a
token (``kv_lora_rank + d_rope`` values, keys and values both read out of
it) and not a K/V pair a head; attention has five matrices (two down,
two up, the output) and two latent norms; the matrices are counted at
the itemsize they are STORED in, the cache at its own; and the decode
step's attention reads only the rows its slots have reached, so the
cache is counted by the rows visible and not by the slab."""


def n_dense(cfg):
    return int(cfg.get("n_dense_layer") or 0) if cfg.get("n_expert") \
        else cfg["n_layer"]


def held_experts(cfg):
    return int(cfg.get("n_expert_local") or cfg["n_expert"])


def latent_width(cfg):
    """Values a token leaves in a layer's cache: ``c`` and ``k_r``."""
    return int(cfg["kv_lora_rank"]) + int(cfg["d_rope"])


def attention_matrix_params(cfg):
    """W_dq, W_uq, W_dkv, W_ukv, W_o of one layer."""
    d, h = cfg["d_model"], cfg["n_head"]
    dn, dr, dv, dc = cfg["d_nope"], cfg["d_rope"], cfg["d_v"], \
        cfg["kv_lora_rank"]
    q_rank = cfg["q_lora_rank"]
    return d * q_rank + q_rank * h * (dn + dr) + d * (dc + dr) \
        + dc * h * (dn + dv) + h * dv * d


def attention_vector_params(cfg):
    """The four block norms (two without sandwich_norm) and the two
    latent norms."""
    return (4 if cfg.get("sandwich_norm") else 2) * cfg["d_model"] \
        + cfg["q_lora_rank"] + cfg["kv_lora_rank"]


def expert_params(cfg):
    """Gate, up and down of ONE expert: 3 D F."""
    return 3 * cfg["d_model"] * cfg["d_expert"]


def layer_matrix_params(cfg, layer, experts):
    """A layer's matrices with ``experts`` routed experts: attention and
    either the dense SwiGLU or the shared expert, the router over ALL
    ``n_expert`` and the routed experts."""
    if layer < n_dense(cfg):
        return attention_matrix_params(cfg) \
            + 3 * cfg["d_model"] * cfg["d_ff"]
    return attention_matrix_params(cfg) \
        + int(cfg.get("n_shared_expert") or 0) * expert_params(cfg) \
        + cfg["d_model"] * cfg["n_expert"] + experts * expert_params(cfg)


def matrix_params(cfg, experts=None):
    """Every stored matrix with ``experts`` routed experts a layer (the
    held ones by default): the token table, the untied head, the
    layers."""
    experts = held_experts(cfg) if experts is None else experts
    d = cfg["d_model"]
    head = 0 if cfg.get("tie_embeddings") else cfg["vocab"] * d
    return cfg["vocab"] * d + head + sum(
        layer_matrix_params(cfg, i, experts) for i in range(cfg["n_layer"]))


def vector_params(cfg):
    return cfg["d_model"] + cfg["n_layer"] * attention_vector_params(cfg)


def param_count(cfg, experts=None):
    return matrix_params(cfg, experts) + vector_params(cfg)


def cache_bytes_per_token(cfg, cache_itemsize):
    """What one token keeps over all layers (11,520 B at the published
    widths, five layers, float32)."""
    return cfg["n_layer"] * latent_width(cfg) * cache_itemsize


def cache_bytes(cfg, b_max, max_len, cache_itemsize):
    return b_max * max_len * cache_bytes_per_token(cfg, cache_itemsize)


def static_bytes(cfg, b_max, max_len, cache_itemsize, weight_itemsize):
    """Matrices at the stored itemsize, vectors in float32, the cache."""
    return matrix_params(cfg) * weight_itemsize + vector_params(cfg) * 4 \
        + cache_bytes(cfg, b_max, max_len, cache_itemsize)


def decode_step_bytes(cfg, b_max, max_len, cache_itemsize, weight_itemsize,
                      touched_mean, rows_visible=None):
    """Bytes one decode step must stream: every matrix but the token
    table and the routed experts once, ``touched_mean`` experts a layer
    (the mean number of held experts given a pair in a step, from the
    program's tally), and the latent cache ONCE over ``rows_visible``
    rows (summed over the slots; every row of every slot where None:
    the kernel walks a slot's rows up to its position, so what a step
    must read is what its slots have reached)."""
    attention = cfg["n_layer"] * attention_matrix_params(cfg) \
        * weight_itemsize
    others = (matrix_params(cfg, 0) - cfg["vocab"] * cfg["d_model"]) \
        * weight_itemsize - attention + vector_params(cfg) * 4
    experts = (cfg["n_layer"] - n_dense(cfg)) * touched_mean \
        * expert_params(cfg) * weight_itemsize
    rows = b_max * max_len if rows_visible is None else rows_visible
    cache = rows * cache_bytes_per_token(cfg, cache_itemsize)
    return {"attention": attention, "others": others, "experts": experts,
            "weights": attention + others, "cache": cache,
            "rows_visible": rows,
            "total": attention + others + experts + cache}


def mla_decode_roofline(cfg, rows_visible, cache_itemsize, peaks):
    """Least seconds for the absorbed attention kernels of ONE decode
    step (one call a layer) over ``rows_visible`` cache rows (summed over
    the slots), and which peak bounds it. Operations: per row and head a
    score over the row's whole width and a value sum over ``d_c``, 2 x
    (width + d_c). Bytes: each visible row once (it is key and value
    both), q in and o out."""
    w, dc, h = latent_width(cfg), cfg["kv_lora_rank"], cfg["n_head"]
    flops = cfg["n_layer"] * rows_visible * h * 2 * (w + dc)
    nbytes = cfg["n_layer"] * rows_visible * w * cache_itemsize
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes,
            "seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}


def mla_flash_roofline(cfg, prompt_len, itemsize, peaks):
    """Least seconds for the expanded attention of ALL layers of one
    prefill of ``prompt_len`` tokens, and which peak bounds it.
    Operations: QK^T over ``d_nope + d_rope`` and PV over ``d_v`` for the
    causal pairs, 2 x (d_nope + d_rope + d_v) a pair and head — the true
    widths, whatever the kernel pads them to. Bytes: q and k at ``d_nope
    + d_rope``, v and o at ``d_v``, every head."""
    P, h = int(prompt_len), cfg["n_head"]
    dk, dv = cfg["d_nope"] + cfg["d_rope"], cfg["d_v"]
    pairs = P * (P + 1) // 2
    flops = cfg["n_layer"] * pairs * h * 2 * (dk + dv)
    nbytes = cfg["n_layer"] * P * h * 2 * (dk + dv) * itemsize
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"pairs": pairs, "flops": flops, "bytes": nbytes,
            "seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
