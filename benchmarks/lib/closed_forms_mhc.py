"""Operations and bytes of a decoder whose residual path is ``n`` streams
a token (manifold-constrained hyper-connections, mHC; Xing4.0-29B-A4B,
``model_type`` xing4_0, as ``paddle_tpu/models/gpt.py`` builds it under
``residual="mhc"``) round latent attention and whole-held experts, from
shapes alone. Kept with the benchmark, like ``closed_forms_mla.py``, so
that no PR that claims a gain can change the arithmetic its gain is
counted in.

What it adds to ``closed_forms_mla`` (whose counts of the latent
attention, the experts and the cache it calls): per sub-block (two a
layer) the mapping matrix ``phi [n C, n (n + 2)]``, its three gates and
``n (n + 2)`` biases, a selection bias an expert layer; and what the two
ops of a sub-block must move a row:

* ``mhc_pre``: read the stream ``X`` (``n C`` values), write the mixed
  vector ``h`` (``C``) and the row's ``n (n + 2)`` coefficients;
* ``mhc_post``: read ``X``, the sub-block's output ``y`` (``C``) and the
  coefficients, write ``X'`` (``n C``);

``(3 n + 2) C + 2 n (n + 2)`` values a row and sub-block (200,896 B at
n 4, C 3584, float32), and ``phi`` once a call. Both ops are bound by
memory: per row ``mhc_pre`` does 2 x n C x n (n + 2) operations of
projection (0.69 MFLOP) beside 57 KB read."""

from benchmarks.lib import closed_forms_mla

held_experts = closed_forms_mla.held_experts
n_dense = closed_forms_mla.n_dense


def coefficients(cfg):
    n = int(cfg["hc_mult"])
    return n * (n + 2)


def hc_matrix_params(cfg):
    """``phi`` of ONE sub-block."""
    return int(cfg["hc_mult"]) * cfg["d_model"] * coefficients(cfg)


def hc_vector_params(cfg):
    """The three gates and the biases of ONE sub-block."""
    return 3 + coefficients(cfg)


def matrix_params(cfg, experts=None):
    return closed_forms_mla.matrix_params(cfg, experts) \
        + 2 * cfg["n_layer"] * hc_matrix_params(cfg)


def vector_params(cfg):
    bias = (cfg["n_layer"] - n_dense(cfg)) * cfg["n_expert"] \
        if cfg.get("router_bias") else 0
    return closed_forms_mla.vector_params(cfg) + bias \
        + 2 * cfg["n_layer"] * hc_vector_params(cfg)


def param_count(cfg, experts=None):
    return matrix_params(cfg, experts) + vector_params(cfg)


def static_bytes(cfg, b_max, max_len, cache_itemsize, weight_itemsize):
    """Matrices at the stored itemsize, vectors in float32, the cache."""
    return matrix_params(cfg) * weight_itemsize + vector_params(cfg) * 4 \
        + closed_forms_mla.cache_bytes(cfg, b_max, max_len, cache_itemsize)


def stream_values_per_row(cfg):
    """Values the two ops of ONE sub-block move a row (module doc)."""
    n = int(cfg["hc_mult"])
    return (3 * n + 2) * cfg["d_model"] + 2 * coefficients(cfg)


def mhc_bytes(cfg, rows, itemsize, phi_itemsize):
    """Bytes every ``mhc_pre`` and ``mhc_post`` call of ONE pass of the
    model over ``rows`` rows must move: two sub-blocks a layer, each the
    rows' values and its ``phi`` once."""
    calls = 2 * cfg["n_layer"]
    return calls * (rows * stream_values_per_row(cfg) * itemsize
                    + hc_matrix_params(cfg) * phi_itemsize)


def mhc_roofline(cfg, rows, itemsize, phi_itemsize, peaks):
    """Least seconds for the residual ops of one pass over ``rows`` rows
    (a prefill of that many tokens, a decode step of that many slots),
    and which peak bounds it."""
    n = int(cfg["hc_mult"])
    nbytes = mhc_bytes(cfg, rows, itemsize, phi_itemsize)
    flops = 2 * cfg["n_layer"] * rows * n * cfg["d_model"] * (
        2 * coefficients(cfg) + 4 + 2 * (n + 1))
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes,
            "seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}


def decode_step_bytes(cfg, b_max, max_len, cache_itemsize, weight_itemsize,
                      touched_mean, rows_visible=None):
    """``closed_forms_mla.decode_step_bytes`` (matrices once, the touched
    experts, the latent rows the slots have reached) and beside it what
    the residual path moves: the streams of ``b_max`` rows through two
    ops a sub-block, every ``phi``, the gates and the biases."""
    out = closed_forms_mla.decode_step_bytes(
        cfg, b_max, max_len, cache_itemsize, weight_itemsize, touched_mean,
        rows_visible)
    streams = mhc_bytes(cfg, b_max, 4, weight_itemsize)
    extra = (vector_params(cfg) - closed_forms_mla.vector_params(cfg)) * 4
    out.update(streams=streams, others=out["others"] + extra,
               weights=out["weights"] + extra,
               total=out["total"] + streams + extra)
    return out
