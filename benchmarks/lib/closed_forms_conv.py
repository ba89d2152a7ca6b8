"""Operations and bytes of a decoder whose layers are the ordinary pair
with a GATED SHORT CONVOLUTION or grouped-head attention as the first
sub-block and a dense FFN or routed experts as the second (LFM2,
``model_type`` lfm2_moe, as ``paddle_tpu/models/gpt.py`` builds it under
``layer_types`` with ``"conv"`` entries) — from shapes alone. Kept with
the benchmark, like ``closed_forms_ssm.py``, so that no PR that claims a
gain can change the arithmetic its gain is counted in.

A convolution layer of ``K`` taps holds ``W_in [D, 3 D]``, the taps
``[D, K]`` (float32) and ``W_out [D, D]``; a sequence keeps ``(K - 1) D``
values of it, whatever its length. An attention layer holds q and o at
``n_head`` heads of ``d_head`` and k and v at ``n_kv_head``, and two
``[d_head]`` norm scales; a sequence keeps ``2 n_kv_head d_head`` values
a position. The first ``n_dense_layer`` layers hold a SwiGLU of ``d_ff``
(three matrices), the others a router ``[D, E]`` with its selection bias
and ``E`` SwiGLU experts of ``d_expert``. The head is the token table."""


def d_head(cfg):
    return int(cfg.get("d_head") or cfg["d_model"] // cfg["n_head"])


def n_kv(cfg):
    return int(cfg.get("n_kv_head") or cfg["n_head"])


def count(cfg, kind):
    return sum(1 for k in cfg["layer_types"] if k == kind)


def n_dense(cfg):
    return int(cfg.get("n_dense_layer") or 0)


def n_expert_layers(cfg):
    return cfg["n_layer"] - n_dense(cfg)


def held_experts(cfg):
    return int(cfg.get("n_expert_local") or cfg["n_expert"])


# ------------------------------------------------------------- parameters
def conv_matrix_params(cfg):
    """``W_in`` and ``W_out`` of ONE convolution layer."""
    return 4 * cfg["d_model"] ** 2


def attention_params(cfg):
    return cfg["d_model"] * d_head(cfg) * (2 * cfg["n_head"] + 2 * n_kv(cfg))


def dense_ffn_params(cfg):
    return 3 * cfg["d_model"] * cfg["d_ff"]


def expert_params(cfg):
    """ONE routed expert: gate, up and down."""
    return 3 * cfg["d_model"] * cfg["d_expert"]


def matrix_params(cfg, experts=None):
    """Every parameter stored in cfg['weight_dtype'] with ``experts``
    (default: the held ones) an expert layer; the table once (the head
    is the table)."""
    experts = held_experts(cfg) if experts is None else experts
    head = 0 if cfg.get("tie_embeddings") else cfg["vocab"] * cfg["d_model"]
    return cfg["vocab"] * cfg["d_model"] + head \
        + count(cfg, "conv") * conv_matrix_params(cfg) \
        + count(cfg, "full") * attention_params(cfg) \
        + n_dense(cfg) * dense_ffn_params(cfg) \
        + n_expert_layers(cfg) * (cfg["d_model"] * cfg["n_expert"]
                                  + experts * expert_params(cfg))


def vector_params(cfg):
    """What stays float32: two norm scales a layer and the final one,
    the q and k norm scales of an attention layer, the taps, the
    routers' selection biases."""
    bias = n_expert_layers(cfg) * cfg["n_expert"] \
        if cfg.get("router_bias") else 0
    return (2 * cfg["n_layer"] + 1) * cfg["d_model"] \
        + count(cfg, "full") * 2 * d_head(cfg) \
        + count(cfg, "conv") * cfg["d_model"] * int(cfg["conv_taps"]) + bias


def param_count(cfg, experts=None):
    return matrix_params(cfg, experts) + vector_params(cfg)


# ------------------------------------------------------------------ caches
def rows_values_per_slot(cfg):
    """What one sequence keeps of ALL the convolution layers."""
    return count(cfg, "conv") * (int(cfg["conv_taps"]) - 1) * cfg["d_model"]


def rows_bytes(cfg, b_max, itemsize=4):
    return b_max * rows_values_per_slot(cfg) * itemsize


def slab_bytes_per_position(cfg, itemsize=4):
    return count(cfg, "full") * 2 * n_kv(cfg) * d_head(cfg) * itemsize


def slab_bytes(cfg, b_max, max_len, itemsize=4):
    return b_max * max_len * slab_bytes_per_position(cfg, itemsize)


def static_bytes(cfg, b_max, max_len, cache_itemsize, weight_itemsize):
    """Matrices at the stored itemsize, vectors in float32, the carried
    rows and the key-value slabs."""
    return matrix_params(cfg) * weight_itemsize + vector_params(cfg) * 4 \
        + rows_bytes(cfg, b_max, cache_itemsize) \
        + slab_bytes(cfg, b_max, max_len, cache_itemsize)


# ------------------------------------------------------- the flash forward
def causal_pairs(prompt_len):
    return int(prompt_len) * (int(prompt_len) + 1) // 2


def gqa_flash_roofline(cfg, prompt_len, itemsize, peaks):
    """Least seconds for the causal attention of ALL attention layers of
    one prefill of ``prompt_len`` tokens, and which peak bounds it.
    Operations: two matmuls (QK^T, PV) over the causal pairs, 4 x d_head
    a pair and query head. Bytes: q and o at ``n_head`` heads, k and v
    at ``n_kv_head`` (grouped heads are read once, not repeated)."""
    layers = count(cfg, "full")
    flops = layers * causal_pairs(prompt_len) * 4 * d_head(cfg) \
        * cfg["n_head"]
    nbytes = layers * prompt_len * d_head(cfg) * itemsize \
        * 2 * (cfg["n_head"] + n_kv(cfg))
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"layers": layers, "pairs": causal_pairs(prompt_len),
            "flops": flops, "bytes": nbytes,
            "seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}


# ---------------------------------------------------------- a decode step
def decode_step_bytes(cfg, b_max, max_len, cache_itemsize, weight_itemsize,
                      touched_mean):
    """Bytes one decode step must stream: every matrix once (the table
    as the head; its lookup is ``b_max`` rows) but the experts, of which
    only the TOUCHED ones (``touched_mean`` a layer: the grouped matmul
    fetches no weights for an empty group); the carried rows of all
    ``b_max`` slots TWICE (read and shifted); the key-value slabs whole,
    whatever the slots' lengths (the composed attention of the step
    reads them so)."""
    experts = n_expert_layers(cfg) * touched_mean * expert_params(cfg) \
        * weight_itemsize
    others = matrix_params(cfg, 0) * weight_itemsize \
        + vector_params(cfg) * 4
    rows = 2 * rows_bytes(cfg, b_max, cache_itemsize)
    cache = slab_bytes(cfg, b_max, max_len, cache_itemsize)
    return {"weights": others + experts, "experts": experts,
            "others": others, "rows": rows, "cache": cache,
            "total": others + experts + rows + cache}
