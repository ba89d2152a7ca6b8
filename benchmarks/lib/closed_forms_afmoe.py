"""Operations and bytes of a decoder with two kinds of attention layer
and one chip's share of its experts (Trinity, ``model_type`` afmoe, as
``paddle_tpu/models/gpt.py`` builds it), from shapes alone. Kept with the
benchmark, like ``closed_forms.py``, so that no PR that claims a gain can
change the arithmetic its gain is counted in.

What differs from ``closed_forms_moe``: the caches have two shapes (a
sliding layer keeps a ring of ``window`` rows, a full layer a slab of
``max_len``), the head size is the cfg's own, a layer has four norms, a
gate projection and a shared expert, the leading layers are dense, and
the EXPERTS ARE COUNTED BY THE NUMBER TOUCHED: with 16 rows of 4 experts
each over 256 experts a held expert sees 0.25 pairs a step, most groups
of the grouped matmul are empty and an empty group fetches no weights —
an every-expert count would put the step's bytes over what the chip can
read. The mean number touched a step comes from the program's own tally
(``gpt_moe_experts_touched``)."""


def d_head(cfg):
    return int(cfg.get("d_head") or cfg["d_model"] // cfg["n_head"])


def n_kv(cfg):
    return int(cfg.get("n_kv_head") or cfg["n_head"])


def n_dense(cfg):
    return int(cfg.get("n_dense_layer") or 0) if cfg.get("n_expert") \
        else cfg["n_layer"]


def held_experts(cfg):
    return int(cfg.get("n_expert_local") or cfg["n_expert"])


def attention_params(cfg):
    """q, the gate and o at ``n_head * d_head`` wide, k and v at
    ``n_kv * d_head``; the four block norms and the two per-head scales."""
    d, wide = cfg["d_model"], cfg["n_head"] * d_head(cfg)
    gate = wide if cfg.get("attn_gate") else 0
    norms = (4 if cfg.get("sandwich_norm") else 2) * d \
        + (2 * d_head(cfg) if cfg.get("qk_norm") == "head" else 0)
    return d * (2 * wide + gate) + 2 * d * n_kv(cfg) * d_head(cfg) + norms


def expert_params(cfg):
    """Gate, up and down of ONE expert: 3 D F."""
    return 3 * cfg["d_model"] * cfg["d_expert"]


def dense_layer_params(cfg):
    return attention_params(cfg) + 3 * cfg["d_model"] * cfg["d_ff"]


def expert_layer_params(cfg, experts):
    """An expert layer holding ``experts`` routed experts: attention, the
    shared expert, the router over ALL ``n_expert`` with its selection
    bias, and the routed experts."""
    router = cfg["d_model"] * cfg["n_expert"] \
        + (cfg["n_expert"] if cfg.get("router_bias") else 0)
    shared = int(cfg.get("n_shared_expert") or 0) * expert_params(cfg)
    return attention_params(cfg) + shared + router \
        + experts * expert_params(cfg)


def param_count(cfg, experts=None):
    """Every stored weight with ``experts`` routed experts a layer (the
    held ones by default): the token table, the untied head, the final
    norm and the layers."""
    experts = held_experts(cfg) if experts is None else experts
    d = cfg["d_model"]
    head = 0 if cfg.get("tie_embeddings") else cfg["vocab"] * d
    dense = n_dense(cfg)
    return cfg["vocab"] * d + head + d \
        + dense * dense_layer_params(cfg) \
        + (cfg["n_layer"] - dense) * expert_layer_params(cfg, experts)


def cache_rows(cfg, layer, max_len):
    """Rows of a layer's decode cache: a sliding layer's ring is its
    window (never more than ``max_len``), a full layer's slab
    ``max_len``."""
    types = cfg.get("layer_types")
    if types and types[layer] == "sliding":
        return min(int(cfg["window"]), max_len)
    return max_len


def cache_elements_per_slot(cfg, max_len):
    """K and V elements one sequence holds, by cache kind."""
    per_row = 2 * n_kv(cfg) * d_head(cfg)
    out = {"ring": 0, "full": 0}
    for layer in range(cfg["n_layer"]):
        rows = cache_rows(cfg, layer, max_len)
        out["ring" if rows < max_len else "full"] += rows * per_row
    return out


def decode_step_bytes(cfg, b_max, max_len, cache_itemsize, weight_itemsize,
                      touched_mean):
    """Bytes one decode step must stream: every weight but the token
    table and the routed experts once, ``touched_mean`` experts a layer
    (the mean number of held experts that were given a pair in a step,
    from the program's tally) and both kinds of cache of all ``b_max``
    slots, whatever the occupancy — the step reads rings and slabs
    whole."""
    weights = (param_count(cfg, 0) - cfg["vocab"] * cfg["d_model"]) \
        * weight_itemsize
    experts = (cfg["n_layer"] - n_dense(cfg)) * touched_mean \
        * expert_params(cfg) * weight_itemsize
    per_slot = cache_elements_per_slot(cfg, max_len)
    cache = {kind: n * b_max * cache_itemsize
             for kind, n in per_slot.items()}
    return {"weights": weights, "experts": experts,
            "cache_ring": cache["ring"], "cache_full": cache["full"],
            "cache": cache["ring"] + cache["full"],
            "total": weights + experts + cache["ring"] + cache["full"]}


def banded_pairs(prompt_len, window):
    """Visible (query, key) pairs of a causal window over a prompt: query
    i sees ``min(i + 1, window)`` keys."""
    w = min(int(window), int(prompt_len))
    return w * (w + 1) // 2 + (int(prompt_len) - w) * w


def flash_win_roofline(cfg, prompt_len, itemsize, peaks):
    """Least seconds for the banded attention of ALL sliding layers of
    one prefill of ``prompt_len`` tokens, and which peak bounds it.
    Operations: two matmuls (QK^T, PV) over the visible pairs, 4 x d_head
    a pair and query head. Bytes: q and o at ``n_head`` heads, k and v
    at ``n_kv`` (grouped heads are read once, not repeated)."""
    # a prompt no longer than the window runs the unbanded kernel
    layers = 0 if prompt_len <= int(cfg["window"]) else \
        sum(1 for kind in cfg["layer_types"] if kind == "sliding")
    pairs = banded_pairs(prompt_len, cfg["window"])
    flops = layers * pairs * 4 * d_head(cfg) * cfg["n_head"]
    nbytes = layers * prompt_len * d_head(cfg) * itemsize \
        * 2 * (cfg["n_head"] + n_kv(cfg))
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"layers": layers, "pairs": pairs, "flops": flops,
            "bytes": nbytes, "seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
