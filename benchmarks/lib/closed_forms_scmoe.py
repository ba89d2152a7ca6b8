"""Operations and bytes of a decoder with shortcut-connected experts
(LongCat-Flash, ``model_type`` longcat_flash, as
``paddle_tpu/models/gpt.py`` builds it under ``shortcut_moe``), from
shapes alone. Kept with the benchmark, like ``closed_forms_mla.py``, so
that no PR that claims a gain can change the arithmetic its gain is
counted in.

``cfg['n_layer']`` counts SUB-LAYERS, two a published layer: each is one
latent attention (``closed_forms_mla``'s five matrices and two latent
norms, one latent slab) and one dense SwiGLU of ``d_ff`` with its two
block norms. A published layer has ONE routed branch besides: a router
``n_expert + n_zero_expert`` wide with a selection term as wide, and the
experts with weights — the identity experts have none, so they add
nothing to hold and nothing to stream. What a decode step must read of
the experts follows the touched tally, as in ``closed_forms_mla``; the
latent cache is counted by the rows visible over all ``n_layer`` slabs."""

from benchmarks.lib import closed_forms_mla

held_experts = closed_forms_mla.held_experts
latent_width = closed_forms_mla.latent_width
attention_matrix_params = closed_forms_mla.attention_matrix_params
expert_params = closed_forms_mla.expert_params
cache_bytes_per_token = closed_forms_mla.cache_bytes_per_token
cache_bytes = closed_forms_mla.cache_bytes


def branches(cfg):
    """Routed branches: one a published layer, a pair of sub-layers."""
    return cfg["n_layer"] // 2


def router_width(cfg):
    return cfg["n_expert"] + int(cfg.get("n_zero_expert") or 0)


def dense_params(cfg):
    """Gate, up and down of ONE sub-layer's dense SwiGLU: 3 D d_ff."""
    return 3 * cfg["d_model"] * cfg["d_ff"]


def sublayer_matrix_params(cfg):
    return attention_matrix_params(cfg) + dense_params(cfg)


def branch_matrix_params(cfg, experts):
    """A branch with ``experts`` experts with weights: the router over
    ALL its outputs and the experts."""
    return cfg["d_model"] * router_width(cfg) + experts * expert_params(cfg)


def published_layer_params(cfg, experts=0):
    """The matrices of one published layer: two sub-layers, one branch."""
    return 2 * sublayer_matrix_params(cfg) \
        + branch_matrix_params(cfg, experts)


def matrix_params(cfg, experts=None):
    """Every stored matrix with ``experts`` experts a branch (the held
    ones by default): the token table, the untied head, the layers."""
    experts = held_experts(cfg) if experts is None else experts
    return 2 * cfg["vocab"] * cfg["d_model"] \
        + branches(cfg) * published_layer_params(cfg, experts)


def vector_params(cfg):
    """The final norm; a sub-layer's two block norms and two latent
    norms; a branch's selection term."""
    per_sub = 2 * cfg["d_model"] + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
    bias = router_width(cfg) if cfg.get("router_bias") else 0
    return cfg["d_model"] + cfg["n_layer"] * per_sub + branches(cfg) * bias


def param_count(cfg, experts=None):
    return matrix_params(cfg, experts) + vector_params(cfg)


def static_bytes(cfg, b_max, max_len, cache_itemsize, weight_itemsize):
    """Matrices at the stored itemsize, vectors in float32, the cache
    (``n_layer`` latent slabs)."""
    return matrix_params(cfg) * weight_itemsize + vector_params(cfg) * 4 \
        + cache_bytes(cfg, b_max, max_len, cache_itemsize)


def decode_step_bytes(cfg, b_max, max_len, cache_itemsize, weight_itemsize,
                      touched_mean, rows_visible=None):
    """Bytes one decode step must stream: every matrix but the token
    table and the experts once (two attentions, two dense FFNs and a
    router a published layer, the head), ``touched_mean`` experts a
    branch (the mean number of held experts given a pair in a step, from
    the program's tally: an identity pair touches none), and the latent
    cache ONCE over ``rows_visible`` rows of each of the ``n_layer``
    slabs (summed over the slots; every row of every slot where None)."""
    attention = cfg["n_layer"] * attention_matrix_params(cfg) \
        * weight_itemsize
    others = (matrix_params(cfg, 0) - cfg["vocab"] * cfg["d_model"]) \
        * weight_itemsize - attention + vector_params(cfg) * 4
    experts = branches(cfg) * touched_mean * expert_params(cfg) \
        * weight_itemsize
    rows = b_max * max_len if rows_visible is None else rows_visible
    cache = rows * cache_bytes_per_token(cfg, cache_itemsize)
    return {"attention": attention, "others": others, "experts": experts,
            "weights": attention + others, "cache": cache,
            "rows_visible": rows,
            "total": attention + others + experts + cache}


def prefill_flops_per_token(cfg, experts_per_token):
    """Operations a prompt token costs outside attention's (query, key)
    pairs: 2 a multiply-add over the sub-layers' matrices, the routers
    and ``experts_per_token`` experts with weights a branch (the mean
    this chip computes a token: an identity pair costs none)."""
    return 2 * (cfg["n_layer"] * sublayer_matrix_params(cfg)
                + branches(cfg) * (cfg["d_model"] * router_width(cfg)
                                   + experts_per_token
                                   * expert_params(cfg)))
