"""Operations and bytes of a decoder whose layers are the ordinary pair
with a MAMBA-1 MIXER or position-free multi-query attention as the first
sub-block and a dense SwiGLU FFN as the second, under a tied head (Jamba,
``model_type`` jamba, as ``paddle_tpu/models/gpt.py`` builds it under
``layer_types`` with ``"mamba"`` entries) — from shapes alone. Kept with
the benchmark, like ``closed_forms_delta.py``, so that no PR that claims a
gain can change the arithmetic its gain is counted in.

A mamba layer of ``C`` inner channels, ``N`` states a channel and a
``dt`` rank ``R`` holds ``W_in [D, 2 C]`` (u and the gate z), ``W_x [C, R
+ 2 N]``, ``W_dt [R, C]``, ``W_out [C, D]`` and, in float32, the taps
``[C, K]`` with their bias, ``A_log [C, N]``, ``D`` and ``b_dt`` ``[C]``
and the three inner norm scales (``R + 2 N``). A sequence keeps ``C N``
values of state and ``(K - 1) C`` of convolution rows, whatever its
length. An attention layer holds q and o at ``n_head`` heads of
``d_head`` and k and v at ``n_kv_head``; a sequence keeps ``2 n_kv_head
d_head`` values a position. Every layer holds a dense FFN of three ``[D,
d_ff]`` matrices and two norm scales. The token table is the head.

The token-by-token recurrence, a token, channel and state: ``dt A`` (1),
the exponential (1), the decay times the state, ``B`` times ``dt u`` and
their sum (3), the product with ``C`` and its sum (2); ``dt u`` and ``D
u`` with its sum are a channel's and are counted with its states (2):
``9 C N`` a token and layer (ISSUE 58's count, ``scan_flops``). None of
it is a matrix product, so the peak that bounds it is THE VECTOR UNIT'S
(``vector_ops_per_s``), and against that peak the count has to be one no
implementation can do less than: ``recurrence_vector_ops``, ``6 C N + 2
C`` — the six of a state without the exponential (which has a slot of its
own beside the unit's ALUs) and ``dt u`` and ``D u`` once a channel. Both
counts are of the mathematics, whatever tile, block or fusion implements
it, so a share on them cannot pass 100%."""


def d_head(cfg):
    return int(cfg.get("d_head") or cfg["d_model"] // cfg["n_head"])


def n_kv(cfg):
    return int(cfg.get("n_kv_head") or cfg["n_head"])


def count(cfg, kind):
    return sum(1 for k in cfg["layer_types"] if k == kind)


def widths(cfg):
    """``(C, N, R, K)`` of a mamba layer: inner channels, states a
    channel, the ``dt`` rank, the convolution's taps."""
    return (int(cfg["mamba_inner"]), int(cfg["mamba_state"]),
            int(cfg["mamba_dt_rank"]), int(cfg["ssm_conv"]))


# ------------------------------------------------------------- parameters
def mamba_matrix_params(cfg):
    """``W_in``, ``W_x``, ``W_dt`` and ``W_out`` of ONE mamba layer."""
    c, n, r, _k = widths(cfg)
    return cfg["d_model"] * 2 * c + c * (r + 2 * n) + r * c \
        + c * cfg["d_model"]


def mamba_vector_params(cfg):
    """What ONE mamba layer keeps float32: taps and bias, ``A_log``,
    ``D``, ``b_dt``, the three inner norm scales."""
    c, n, r, k = widths(cfg)
    return c * k + c + c * n + 2 * c + r + 2 * n


def attention_params(cfg):
    """q and o at ``n_head`` heads, k and v at ``n_kv``."""
    return cfg["d_model"] * d_head(cfg) * 2 * (cfg["n_head"] + n_kv(cfg))


def ffn_params(cfg):
    """ONE dense SwiGLU FFN: gate, up and down."""
    return 3 * cfg["d_model"] * cfg["d_ff"]


def matrix_params(cfg):
    """Every parameter stored in cfg['weight_dtype']; the token table
    once (it is the head)."""
    tables = 1 if cfg.get("tie_embeddings", True) else 2
    return tables * cfg["vocab"] * cfg["d_model"] \
        + count(cfg, "mamba") * mamba_matrix_params(cfg) \
        + count(cfg, "full") * attention_params(cfg) \
        + cfg["n_layer"] * ffn_params(cfg)


def vector_params(cfg):
    """What stays float32: two norm scales a layer and the final one, a
    mamba layer's own."""
    return (2 * cfg["n_layer"] + 1) * cfg["d_model"] \
        + count(cfg, "mamba") * mamba_vector_params(cfg)


def param_count(cfg):
    return matrix_params(cfg) + vector_params(cfg)


# ------------------------------------------------------------------ caches
def state_values_per_slot(cfg):
    """What one sequence keeps of ALL the mamba layers' states."""
    c, n, _r, _k = widths(cfg)
    return count(cfg, "mamba") * c * n


def rows_values_per_slot(cfg):
    """... and of their convolutions' carried rows."""
    c, _n, _r, k = widths(cfg)
    return count(cfg, "mamba") * (k - 1) * c


def state_bytes(cfg, b_max, itemsize=4):
    """State and convolution rows of ``b_max`` slots: what the gauge
    ``paddle_mamba_state_bytes`` reads."""
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
    """ONE layer's ``mamba_update`` over ``rows`` slots: the state read
    and written once a slot, ``A`` once a CALL, the step's vectors (u and
    ``dt`` in, ``y`` out at ``C`` wide, ``B`` and ``C_t`` at ``N``). (The
    convolution's carried rows are shifted by the step's
    ``causal_conv_step`` outside the kernel: their bytes are
    ``decode_step_bytes``'s, not this kernel's.)"""
    c, n, _r, _k = widths(cfg)
    return (rows * (2 * c * n + 3 * c + 2 * n) + c * n) * itemsize


def update_flops(cfg, rows):
    c, n, _r, _k = widths(cfg)
    return rows * 9 * c * n


def recurrence_vector_ops(cfg, tokens):
    """What of ONE layer's recurrence over ``tokens`` tokens nothing but
    the vector unit can do (module docstring): ``6 C N + 2 C`` a token."""
    c, n, _r, _k = widths(cfg)
    return tokens * (6 * c * n + 2 * c)


def vector_ops_per_s(peaks):
    """What the chip's vector unit can do in a second. A v5e's TensorCore
    has four matrix units of 128 x 128 multiply-accumulators beside one
    vector unit (Google Cloud documentation, "TPU v5e", system
    architecture: https://cloud.google.com/tpu/docs/v5e, the page
    ``peaks.py`` takes the bf16 peak from), so the published peak is 2 x 4
    x 128 x 128 operations a cycle and the clock 1.5e9; the vector unit is
    8 sublanes of 128 lanes with four independent ALUs each (Austin et
    al., "How to Scale Your Model", 2025, chapter "How to Think About
    TPUs", https://jax-ml.github.io/scaling-book/tpus — written of the
    v5p's core, taken here for the v5e's): 4 x 8 x 128 operations a
    cycle, A THIRTY-SECOND OF THE BF16 PEAK, 6.2e12 on a v5e.
    ``benchmarks/lib/peaks.py`` has no row for it and no PR that adds a
    cell may edit that file (PERF.md section 7 asks a ``benchmark`` PR to
    move it there)."""
    return peaks["bf16_flops_per_s"] / 32.0


def update_roofline(cfg, rows, peaks, itemsize=4):
    """Least seconds the ``mamba_update`` ops of one step over ``rows``
    slots can take: what nothing but the vector unit can do of them
    (``recurrence_vector_ops`` a slot and layer) over the vector unit's
    peak. Not their bytes over the HBM peak (``hbm_seconds``, given beside
    it): a layer's states are 10 MB at 32 slots, small enough that XLA
    hands the kernel its state IN VMEM — 22 of the 26 calls of the
    compiled step, moved there and back by async copies that run under
    other operations — so no time that can be laid at the update's door
    holds that traffic and a share of the byte term read 213% (PERF.md
    section 6, PR 58). ``decode_bw_pct`` holds the step's bytes whole."""
    m = count(cfg, "mamba")
    ops = m * recurrence_vector_ops(cfg, rows)
    nbytes = m * update_bytes(cfg, rows, itemsize)
    return {"flops": m * update_flops(cfg, rows), "vector_ops": ops,
            "bytes": nbytes,
            "hbm_seconds": nbytes / peaks["hbm_bytes_per_s"],
            "seconds": ops / vector_ops_per_s(peaks), "bound": "vector"}


def scan_flops(cfg, T):
    """ONE layer's scan over ``T`` positions, counted as the
    token-by-token recurrence (module docstring)."""
    c, n, _r, _k = widths(cfg)
    return T * 9 * c * n


def scan_bytes(cfg, T, itemsize=4):
    """The fewest bytes any form must move: u in and ``y`` out at ``C``
    wide, ``B`` and ``C_t`` at ``N``, the ``R``-wide ``delta`` that ``dt``
    is a projection of, a position; the last state once."""
    c, n, r, _k = widths(cfg)
    return (T * (2 * c + 2 * n + r) + c * n) * itemsize


def scan_roofline(cfg, T, peaks, itemsize=4):
    """Least seconds for the scans of all the mamba layers of one prefill
    of ``T`` positions, and which peak bounds it: the largest of ISSUE
    58's two terms (``scan_flops`` over the bf16 peak, ``scan_bytes`` over
    the HBM peak) and of ``recurrence_vector_ops`` over the vector unit's
    peak, the floor ``update_roofline`` is held against."""
    m = count(cfg, "mamba")
    terms = {"compute": m * scan_flops(cfg, T) / peaks["bf16_flops_per_s"],
             "memory": m * scan_bytes(cfg, T, itemsize)
             / peaks["hbm_bytes_per_s"],
             "vector": m * recurrence_vector_ops(cfg, T)
             / vector_ops_per_s(peaks)}
    bound = max(terms, key=terms.get)
    return {"flops": m * scan_flops(cfg, T),
            "vector_ops": m * recurrence_vector_ops(cfg, T),
            "bytes": m * scan_bytes(cfg, T, itemsize),
            "seconds": terms[bound], "bound": bound}


def discretised_bytes(cfg, T, itemsize=4):
    """What ``exp(dt A)`` of ONE layer would take in HBM were it ever
    written: ``[T, C, N]`` (the kernel forms it in registers)."""
    c, n, _r, _k = widths(cfg)
    return T * c * n * itemsize


# ---------------------------------------------------------- a decode step
def decode_step_bytes(cfg, b_max, max_len, cache_itemsize, weight_itemsize):
    """Bytes one decode step must stream: every matrix once — the token
    table WHOLE, since the tied head reads all of it —; the mamba states
    and their convolution rows of all ``b_max`` slots TWICE (read and
    written); the key-value slabs whole, whatever the slots' lengths (the
    composed attention of the step reads them so)."""
    weights = matrix_params(cfg) * weight_itemsize + vector_params(cfg) * 4
    state = 2 * state_bytes(cfg, b_max, cache_itemsize)
    cache = slab_bytes(cfg, b_max, max_len, cache_itemsize)
    return {"weights": weights, "state": state, "cache": cache,
            "total": weights + state + cache}
