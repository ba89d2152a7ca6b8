"""Operations and bytes of a decoder whose layers are the ordinary pair
with a GATED DELTA RULE or gated grouped-head attention as the first
sub-block and routed experts with a gated shared expert as the second
(Qwen3-Next, ``model_type`` qwen3_next, as ``paddle_tpu/models/gpt.py``
builds it under ``layer_types`` with ``"delta"`` entries) — from shapes
alone. Kept with the benchmark, like ``closed_forms_power.py``, so that no
PR that claims a gain can change the arithmetic its gain is counted in.

A delta layer of ``Hk`` key heads of ``Dk`` and ``Hv`` value heads of
``Dv`` holds ``W_in [D, 2 Hk Dk + 2 Hv Dv]`` (q, k, v and the output
gate z), ``W_ba [D, 2 Hv]``, ``W_out [Hv Dv, D]`` and, in float32, the
taps ``[C, 4]`` over the ``C = 2 Hk Dk + Hv Dv`` convolved channels, the
decay's two ``[Hv]`` vectors and one ``[Dv]`` norm scale. A sequence keeps
``Hv Dk Dv`` values of state and ``3 C`` of convolution rows, whatever its
length. An attention layer holds q, its gate and o at ``n_head`` heads of
``d_head`` and k and v at ``n_kv_head``, and two ``[d_head]`` norm scales;
a sequence keeps ``2 n_kv_head d_head`` values a position. Every layer
holds a router ``[D, E]``, the HELD experts of ``d_expert`` (three
matrices each), ``n_shared_expert`` shared ones and their gate ``[D, 1]``.
Table and head are two matrices.

The token-by-token recurrence, a token and value head: the decay (``Dk
Dv``), ``S^T k`` (``2 Dk Dv``), the rank-one correction (``2 Dk Dv``) and
``S^T q`` (``2 Dk Dv``): ``7 Dk Dv`` operations. ``scan_flops`` counts
THAT, whatever chunk or triangular solve implements it: a chunked form
does more than this count, never less, so a share on it cannot pass
100%."""

TAPS = 4


def d_head(cfg):
    return int(cfg.get("d_head") or cfg["d_model"] // cfg["n_head"])


def n_kv(cfg):
    return int(cfg.get("n_kv_head") or cfg["n_head"])


def count(cfg, kind):
    return sum(1 for k in cfg["layer_types"] if k == kind)


def held_experts(cfg):
    return int(cfg.get("n_expert_local") or cfg["n_expert"])


def widths(cfg):
    """``(Hk, Dk, Hv, Dv, C)`` of a delta layer, ``C`` the convolved
    channels."""
    hk, dk = int(cfg["delta_k_heads"]), int(cfg["delta_k_dim"])
    hv, dv = int(cfg["delta_v_heads"]), int(cfg["delta_v_dim"])
    return hk, dk, hv, dv, 2 * hk * dk + hv * dv


# ------------------------------------------------------------- parameters
def delta_matrix_params(cfg):
    """``W_in``, ``W_ba`` and ``W_out`` of ONE delta layer."""
    _hk, _dk, hv, dv, c = widths(cfg)
    return cfg["d_model"] * (c + hv * dv + 2 * hv) + hv * dv * cfg["d_model"]


def attention_params(cfg):
    """q, its gate and o at ``n_head`` heads, k and v at ``n_kv``."""
    return cfg["d_model"] * d_head(cfg) * (3 * cfg["n_head"] + 2 * n_kv(cfg))


def expert_params(cfg):
    """ONE expert: gate, up and down."""
    return 3 * cfg["d_model"] * cfg["d_expert"]


def moe_fixed_params(cfg):
    """ONE layer's router, shared experts and their gate."""
    shared = int(cfg.get("n_shared_expert") or 0)
    return cfg["d_model"] * cfg["n_expert"] + shared * expert_params(cfg) \
        + (cfg["d_model"] if cfg.get("shared_expert_gate") else 0)


def matrix_params(cfg, experts=None):
    """Every parameter stored in cfg['weight_dtype'] with ``experts``
    (default: the held ones) routed experts a layer; table and head."""
    experts = held_experts(cfg) if experts is None else experts
    return 2 * cfg["vocab"] * cfg["d_model"] \
        + count(cfg, "delta") * delta_matrix_params(cfg) \
        + count(cfg, "full") * attention_params(cfg) \
        + cfg["n_layer"] * (moe_fixed_params(cfg)
                            + experts * expert_params(cfg))


def vector_params(cfg):
    """What stays float32: two norm scales a layer and the final one, the
    q and k norm scales of an attention layer, a delta layer's taps,
    decay vectors and norm scale."""
    _hk, _dk, hv, dv, c = widths(cfg)
    return (2 * cfg["n_layer"] + 1) * cfg["d_model"] \
        + count(cfg, "full") * 2 * d_head(cfg) \
        + count(cfg, "delta") * (c * TAPS + 2 * hv + dv)


def param_count(cfg, experts=None):
    return matrix_params(cfg, experts) + vector_params(cfg)


# ------------------------------------------------------------------ caches
def state_values_per_slot(cfg):
    """What one sequence keeps of ALL the delta layers' states."""
    _hk, dk, hv, dv, _c = widths(cfg)
    return count(cfg, "delta") * hv * dk * dv


def rows_values_per_slot(cfg):
    """... and of their convolutions' carried rows."""
    return count(cfg, "delta") * (TAPS - 1) * widths(cfg)[4]


def state_bytes(cfg, b_max, itemsize=4):
    """State and convolution rows of ``b_max`` slots: what the gauge
    ``paddle_delta_state_bytes`` reads."""
    return b_max * (state_values_per_slot(cfg)
                    + rows_values_per_slot(cfg)) * itemsize


def slab_bytes_per_position(cfg, itemsize=4):
    return count(cfg, "full") * 2 * n_kv(cfg) * d_head(cfg) * itemsize


def slab_bytes(cfg, b_max, max_len, itemsize=4):
    return b_max * max_len * slab_bytes_per_position(cfg, itemsize)


def static_bytes(cfg, b_max, max_len, cache_itemsize, weight_itemsize):
    """Matrices at the stored itemsize, vectors in float32, the states
    with their rows and the key-value slabs."""
    return matrix_params(cfg) * weight_itemsize + vector_params(cfg) * 4 \
        + state_bytes(cfg, b_max, cache_itemsize) \
        + slab_bytes(cfg, b_max, max_len, cache_itemsize)


# ----------------------------------------------------------- the kernels
def update_bytes(cfg, rows, itemsize=4):
    """ONE layer's ``delta_update`` over ``rows`` slots: the state read
    and written once, the token's q and k a key head, v, the two gates a
    value head in and ``y`` out. (The convolution's carried rows are
    shifted by the step's ``causal_conv_step`` outside the kernel: their
    bytes are ``decode_step_bytes``'s, not this kernel's.)"""
    hk, dk, hv, dv, _c = widths(cfg)
    return rows * (2 * hv * dk * dv + 2 * hk * dk + 2 * hv * dv + 2 * hv) \
        * itemsize


def update_flops(cfg, rows):
    _hk, dk, hv, dv, _c = widths(cfg)
    return rows * hv * 7 * dk * dv


def update_roofline(cfg, rows, peaks, itemsize=4):
    """Least seconds for the updates of all the delta layers of one step
    over ``rows`` slots."""
    n = count(cfg, "delta")
    return _least(n * update_flops(cfg, rows),
                  n * update_bytes(cfg, rows, itemsize), peaks)


def scan_flops(cfg, T):
    """ONE layer's scan over ``T`` positions, counted as the
    token-by-token recurrence (module docstring)."""
    _hk, dk, hv, dv, _c = widths(cfg)
    return T * hv * 7 * dk * dv


def scan_bytes(cfg, T, itemsize=4):
    """q and k a key head, v and ``y`` a value head and the two gates a
    position, and the final state."""
    hk, dk, hv, dv, _c = widths(cfg)
    return (T * (2 * hk * dk + 2 * hv * dv + 2 * hv) + hv * dk * dv) \
        * itemsize


def scan_roofline(cfg, T, peaks, itemsize=4):
    """Least seconds for the scans of all the delta layers of one prefill
    of ``T`` positions, and which peak bounds it."""
    n = count(cfg, "delta")
    return _least(n * scan_flops(cfg, T), n * scan_bytes(cfg, T, itemsize),
                  peaks)


def solve_products(chunk):
    """``[Q, Q] x [Q, Q]`` products the scan's inverse by halves takes a
    chunk and value head (``kernels/delta.py``): two a
    doubling from blocks of 2 up."""
    n, b = 0, 2
    while b < chunk:
        n, b = n + 2, 2 * b
    return n


def chunked_flops(cfg, T, chunk):
    """ONE layer's scan as the kernel computes it in chunks of ``chunk``
    (before its precision passes): a chunk's ``K K^T`` and ``Q K^T`` a key
    head; a value head's reads of the state (``K S``, ``Q S``), inverse,
    its use, the readout inside the chunk and the state's feed."""
    hk, dk, hv, dv, _c = widths(cfg)
    q = int(chunk)
    chunks = -(-T // q)
    shared = 2 * 2 * q * q * dk
    a_head = 2 * 2 * q * dk * dv + solve_products(q) * 2 * q ** 3 \
        + 2 * 2 * q * q * dv + 2 * q * dk * dv
    return chunks * (hk * shared + hv * a_head)


def _least(flops, nbytes, peaks):
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes,
            "seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}


# ---------------------------------------------------------- a decode step
def decode_step_bytes(cfg, b_max, max_len, cache_itemsize, weight_itemsize,
                      touched_mean):
    """Bytes one decode step must stream: every matrix once (of the token
    table the ``b_max`` rows looked up) but the routed experts, of which
    only the TOUCHED ones (``touched_mean`` a layer: the grouped matmul
    fetches no weights for an empty group); the delta states and their
    convolution rows of all ``b_max`` slots TWICE (read and written); the
    key-value slabs whole, whatever the slots' lengths (the composed
    attention of the step reads them so)."""
    experts = cfg["n_layer"] * touched_mean * expert_params(cfg) \
        * weight_itemsize
    others = (matrix_params(cfg, 0) - (cfg["vocab"] - b_max)
              * cfg["d_model"]) * weight_itemsize + vector_params(cfg) * 4
    state = 2 * state_bytes(cfg, b_max, cache_itemsize)
    cache = slab_bytes(cfg, b_max, max_len, cache_itemsize)
    return {"weights": others + experts, "experts": experts,
            "others": others, "state": state, "cache": cache,
            "total": others + experts + state + cache}
