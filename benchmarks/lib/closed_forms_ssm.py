"""Operations and bytes of a decoder whose layers are ONE mixer each —
state-space (Mamba-2), grouped-head attention without positions, or
un-gated relu² experts that work in a latent of the token
(NVIDIA-Nemotron-3-Super, ``model_type`` nemotron_h, as
``paddle_tpu/models/gpt.py`` builds it under ``mixers``) — from shapes
alone. Kept with the benchmark, like ``closed_forms_mla.py``, so that no
PR that claims a gain can change the arithmetic its gain is counted in.

A state-space layer of ``H`` heads of ``P`` in ``G`` groups with state
``N`` and ``K`` taps (``d_in = H P``, convolution width ``C = d_in + 2 G
N``) holds ``W_in [D, 2 d_in + 2 G N + H]``, the convolution ``[C, K]``
and its bias, three ``[H]`` vectors, the gated norm's scale ``[d_in]``
and ``W_out [d_in, D]``; a sequence keeps ``H P N`` values of state and
``(K - 1) C`` convolution rows, whatever its length.

* the decode update of ONE layer over ``rows`` slots moves the state
  twice (read, written) and the token's ``x``, ``B``, ``C``, ``dt`` in
  and ``y`` out: ``rows (2 H P N + 2 H P + 2 G N + H)`` float32 values;
  five operations a value of state. Bound by memory.
* the scan of ONE layer over a prompt of ``T`` positions in chunks of
  ``Q``: a position and head ``2 Q P`` (the masked ``C B^T`` against
  ``x``) + ``4 N P`` (the state read out and fed) operations, a position
  and group ``2 Q N`` (``C B^T``); bytes ``x`` in and ``y`` out, ``B``,
  ``C``, ``dt`` and the final state.

An expert layer holds the router ``[D, E]`` and its bias, ``W_down [D,
L]``, ``W_up [L, D]``, per HELD expert ``[L, F]`` and ``[F, L]``, and the
shared expert ``[D, Fs]``, ``[Fs, D]``."""


def ssm_widths(cfg):
    H, P = int(cfg["ssm_heads"]), int(cfg["ssm_head_dim"])
    G, N = int(cfg["ssm_groups"]), int(cfg["ssm_state"])
    return H, P, G, N, int(cfg["ssm_conv"]), H * P, H * P + 2 * G * N


def count(cfg, kind):
    return sum(1 for k in cfg["mixers"] if k == kind)


def held_experts(cfg):
    return int(cfg.get("n_expert_local") or cfg["n_expert"])


def d_head(cfg):
    return int(cfg.get("d_head") or cfg["d_model"] // cfg["n_head"])


# ------------------------------------------------------------- parameters
def ssm_matrix_params(cfg):
    H, P, G, N, K, d_in, conv = ssm_widths(cfg)
    return cfg["d_model"] * (2 * d_in + 2 * G * N + H) + conv * K \
        + d_in * cfg["d_model"]


def ssm_vector_params(cfg):
    H, P, G, N, K, d_in, conv = ssm_widths(cfg)
    return conv + 3 * H + d_in


def attention_params(cfg):
    n_kv = int(cfg.get("n_kv_head") or cfg["n_head"])
    return cfg["d_model"] * d_head(cfg) * (2 * cfg["n_head"] + 2 * n_kv)


def expert_params(cfg):
    """ONE routed expert: up and down in the latent."""
    return 2 * int(cfg.get("d_expert_in") or cfg["d_model"]) \
        * cfg["d_expert"]


def expert_layer_other_params(cfg):
    """An expert layer's matrices that every step reads: the router, the
    two latent projections and the shared expert."""
    d, lat = cfg["d_model"], int(cfg.get("d_expert_in") or 0)
    return d * cfg["n_expert"] + 2 * d * lat \
        + 2 * d * int(cfg.get("d_shared_expert") or 0)


def matrix_params(cfg, experts=None):
    """Every parameter of rank >= 2 with ``experts`` (default: the held
    ones) an expert layer."""
    experts = held_experts(cfg) if experts is None else experts
    return 2 * cfg["vocab"] * cfg["d_model"] \
        + count(cfg, "ssm") * ssm_matrix_params(cfg) \
        + count(cfg, "attention") * attention_params(cfg) \
        + count(cfg, "experts") * (expert_layer_other_params(cfg)
                                   + experts * expert_params(cfg))


def vector_params(cfg):
    """Norm scales (one a layer and the final one), the state-space
    layers' vectors, the routers' selection biases."""
    bias = count(cfg, "experts") * cfg["n_expert"] \
        if cfg.get("router_bias") else 0
    return (cfg["n_layer"] + 1) * cfg["d_model"] \
        + count(cfg, "ssm") * ssm_vector_params(cfg) + bias


def param_count(cfg, experts=None):
    return matrix_params(cfg, experts) + vector_params(cfg)


# ------------------------------------------------------------------ state
def state_values_per_slot(cfg):
    """What one sequence keeps of ALL the state-space layers."""
    H, P, G, N, K, d_in, conv = ssm_widths(cfg)
    return count(cfg, "ssm") * (H * P * N + (K - 1) * conv)


def state_bytes(cfg, b_max, itemsize=4):
    return b_max * state_values_per_slot(cfg) * itemsize


def slab_bytes(cfg, b_max, max_len, itemsize=4):
    n_kv = int(cfg.get("n_kv_head") or cfg["n_head"])
    return count(cfg, "attention") * b_max * 2 * n_kv * max_len \
        * d_head(cfg) * itemsize


def static_bytes(cfg, b_max, max_len, cache_itemsize, weight_itemsize):
    """Matrices at the stored itemsize, vectors in float32, the state
    and the key-value slabs."""
    return matrix_params(cfg) * weight_itemsize + vector_params(cfg) * 4 \
        + state_bytes(cfg, b_max, cache_itemsize) \
        + slab_bytes(cfg, b_max, max_len, cache_itemsize)


# ----------------------------------------------------------- the kernels
def update_bytes(cfg, rows, itemsize=4):
    """ONE layer's decode update over ``rows`` slots (module doc)."""
    H, P, G, N, K, d_in, conv = ssm_widths(cfg)
    return rows * (2 * H * P * N + 2 * d_in + 2 * G * N + H) * itemsize


def update_flops(cfg, rows):
    H, P, G, N, K, d_in, conv = ssm_widths(cfg)
    return rows * 5 * H * P * N


def update_roofline(cfg, rows, peaks, itemsize=4):
    """Least seconds for the decode updates of all the state-space
    layers of one step over ``rows`` slots."""
    layers = count(cfg, "ssm")
    nbytes = layers * update_bytes(cfg, rows, itemsize)
    flops = layers * update_flops(cfg, rows)
    return _least(flops, nbytes, peaks)


def scan_flops(cfg, T, chunk=None):
    """ONE layer's scan over ``T`` positions (module doc); a ragged last
    chunk is computed whole."""
    H, P, G, N, K, d_in, conv = ssm_widths(cfg)
    Q = int(chunk or cfg.get("ssm_chunk") or 128)
    Tp = -(-T // Q) * Q
    return Tp * (H * (2 * Q * P + 4 * N * P) + G * 2 * Q * N)


def scan_bytes(cfg, T, itemsize=4):
    H, P, G, N, K, d_in, conv = ssm_widths(cfg)
    return (T * (2 * d_in + 2 * G * N + H) + H * P * N) * itemsize


def scan_roofline(cfg, T, peaks, itemsize=4):
    """Least seconds for the scans of all the state-space layers of one
    prefill of ``T`` positions, and which peak bounds it."""
    layers = count(cfg, "ssm")
    return _least(layers * scan_flops(cfg, T),
                  layers * scan_bytes(cfg, T, itemsize), peaks)


def _least(flops, nbytes, peaks):
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes,
            "seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}


# ---------------------------------------------------------- a decode step
def decode_step_bytes(cfg, b_max, max_len, cache_itemsize, weight_itemsize,
                      touched_mean):
    """Bytes one decode step must stream: every matrix once but the
    token table (looked up) and the experts, of which only the TOUCHED
    ones (``touched_mean`` a layer: the grouped matmul fetches no weights
    for an empty group); the state of all ``b_max`` slots TWICE (read
    and written, with the convolution rows); the key-value slabs whole
    (the composed attention of the step reads them so)."""
    experts = count(cfg, "experts") * touched_mean * expert_params(cfg) \
        * weight_itemsize
    others = (matrix_params(cfg, 0) - cfg["vocab"] * cfg["d_model"]) \
        * weight_itemsize + vector_params(cfg) * 4
    state = 2 * state_bytes(cfg, b_max, cache_itemsize)
    cache = slab_bytes(cfg, b_max, max_len, cache_itemsize)
    return {"weights": others + experts, "experts": experts,
            "others": others, "state": state, "cache": cache,
            "total": others + experts + state + cache}
