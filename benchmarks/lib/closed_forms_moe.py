"""Operations and bytes of a decoder with sparse experts (OLMoE as
``paddle_tpu/models/gpt.py`` builds it), from shapes alone. Kept with the
benchmark, like ``closed_forms.py``, so that no PR that claims a gain can
change the arithmetic its gain is counted in.

Every expert's weights are counted as read once a call. That is the upper
bound of "the experts touched": with 32 rows of 8 experts each over 64
experts a step touches 1 - (1 - 8/64)**32 = 98.6% of them in expectation,
so at the decode step's size the count overstates the expected bytes by
1.4%; a 512-token prefill touches them all."""


def expert_params_per_layer(cfg):
    """Gate, up and down of every expert: 3 E D F."""
    return 3 * cfg["n_expert"] * cfg["d_model"] * cfg["d_expert"]


def param_count(cfg):
    """Every stored weight: the token table and the untied head, per
    layer four bias-free attention projections, the q and k RMSNorm
    scales, the two block norms, the router and the experts, and the
    final norm."""
    d = cfg["d_model"]
    per_layer = 4 * d * d + 4 * d + d * cfg["n_expert"] \
        + expert_params_per_layer(cfg)
    head = 0 if cfg.get("tie_embeddings") else cfg["vocab"] * d
    return cfg["vocab"] * d + head + cfg["n_layer"] * per_layer + d


def cache_elements_per_slot(cfg, max_len):
    """K and V rows one sequence holds: layers x 2 x heads x max_len x
    head size."""
    d_head = cfg["d_model"] // cfg["n_head"]
    n_kv = cfg.get("n_kv_head") or cfg["n_head"]
    return cfg["n_layer"] * 2 * n_kv * max_len * d_head


def decode_step_bytes(cfg, b_max, max_len, cache_itemsize, weight_itemsize):
    """Bytes one decode step must stream: every weight once (the token
    table is only looked up, so it is left out; every expert is counted,
    see the head of this file) and both cache slabs of all ``b_max``
    slots, whatever the occupancy — the step reads the slabs whole."""
    weights = (param_count(cfg) - cfg["vocab"] * cfg["d_model"]) \
        * weight_itemsize
    experts = cfg["n_layer"] * expert_params_per_layer(cfg) * weight_itemsize
    cache = cache_elements_per_slot(cfg, max_len) * b_max * cache_itemsize
    return {"weights": weights, "experts": experts, "cache": cache,
            "total": weights + cache}


def gmm_flops(pairs, cfg):
    """The two grouped matmuls of one layer over ``pairs`` (token,
    expert) pairs: gate, up and down are 2 D F each, 6 D F a pair."""
    return pairs * 6 * cfg["d_model"] * cfg["d_expert"]


def gmm_bytes(cfg, itemsize):
    """Expert weights one layer's two grouped matmuls read: all of them,
    once (the rows they multiply are a thousandth of that)."""
    return expert_params_per_layer(cfg) * itemsize


def gmm_step_roofline(cfg, rows, itemsize, peaks):
    """Least seconds for the grouped matmuls of all layers of one step
    over ``rows`` tokens, and which peak bounds it: the larger of the
    pairs' operations over the bf16 peak and the expert bytes over the
    HBM peak."""
    flops = cfg["n_layer"] * gmm_flops(rows * cfg["expert_top_k"], cfg)
    nbytes = cfg["n_layer"] * gmm_bytes(cfg, itemsize)
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes,
            "seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
