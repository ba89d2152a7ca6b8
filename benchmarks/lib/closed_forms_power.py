"""Operations and bytes of a decoder whose layers' first sub-block is
power retention of degree 2 (Brumby-14B-Base, ``model_type`` brumby, as
``paddle_tpu/models/gpt.py`` builds it under ``layer_types`` entries
``"retention"``) — from shapes alone. Kept with the benchmark, like
``closed_forms_ssm.py``, so that no PR that claims a gain can change the
arithmetic its gain is counted in.

A layer of ``H`` query heads and ``G`` key-value heads of ``d`` holds
``W_q [D, H d]``, ``W_k``, ``W_v`` ``[D, G d]``, ``W_o [H d, D]``, the
gate ``[D, G]`` with its bias ``[G]``, the two head-norm scales ``[d]``,
the two RMSNorm scales ``[D]`` and the SwiGLU FFN's three ``[D, F]``
matrices. A sequence keeps, for each key-value head, the state ``S =
sum decay phi(k) v^T`` and the normaliser ``z = sum decay phi(k)`` with
``phi`` the symmetric square: ``PAIRS = d (d + 1) / 2`` values (8,256 at
``d`` 128), so ``PAIRS (d + 1)`` values a head whatever the length.

EVERY ROOFLINE COUNTS THE EXACT ``PAIRS``. The system keeps more
(``kept_rows``: the square in tiles of 16 with the diagonal tiles whole,
9,216 rows at ``d`` 128, and the normaliser as a ``[d, d]`` matrix) and
``state_bytes`` / ``static_bytes`` reckon MEMORY from what is kept; the
difference is the kernel's own cost, as the padded tiles of
``closed_forms_ssm`` are.

* the decode update of ONE layer over ``rows`` slots moves the state and
  the normaliser twice (read, written) and the token's q, k, v and gate
  in and ``y`` out: ``rows (2 G PAIRS (d + 1) + 2 H d + 2 G d + G)``
  float32 values; three operations a value of state (decay, the outer
  product fed in) and two a value and query head of its group (the
  readout). Bound by memory — ONE WRITE OF THE STATE A TOKEN: a decode
  form that folds tokens into the state a chunk at a time would move
  less, and needs this form changed first (PERF.md section 7).
* the scan of ONE layer over a prompt of ``T`` positions in chunks of
  ``Q`` (``scan_chunk``: the system's rule, restated here): a position
  and head ``4 Q d`` (the squared scores against the
  chunk's keys, their product with its values) and, past the first
  chunk, ``2 PAIRS d`` (the state read); a position and key-value head
  ``2 PAIRS d`` (the state fed); bytes q in and ``y`` out, k, v, the
  gate, and the final state and normaliser."""


def d_head(cfg):
    return int(cfg.get("d_head") or cfg["d_model"] // cfg["n_head"])


def kv_heads(cfg):
    return int(cfg.get("n_kv_head") or cfg["n_head"])


def layers(cfg):
    return sum(1 for t in cfg["layer_types"] if t == "retention")


def pairs(cfg):
    """The exact size of the symmetric square of a head."""
    d = d_head(cfg)
    return d * (d + 1) // 2


def kept_rows(cfg, tile=16):
    """Rows of the state as the system keeps them: the tile pairs ``I <=
    J`` of tiles of ``tile``, each whole."""
    d = d_head(cfg)
    tile = tile if d % tile == 0 else d
    n = d // tile
    return tile * tile * n * (n + 1) // 2


# ------------------------------------------------------------- parameters
def layer_matrix_params(cfg):
    d, h, g = d_head(cfg), cfg["n_head"], kv_heads(cfg)
    return cfg["d_model"] * (2 * h * d + 2 * g * d + g) \
        + 3 * cfg["d_model"] * cfg["d_ff"]


def layer_vector_params(cfg):
    return 2 * cfg["d_model"] + 2 * d_head(cfg) + kv_heads(cfg)


def matrix_params(cfg):
    """Every parameter of rank >= 2: the token table, the untied head,
    the layers' matrices."""
    return 2 * cfg["vocab"] * cfg["d_model"] \
        + cfg["n_layer"] * layer_matrix_params(cfg)


def vector_params(cfg):
    return cfg["n_layer"] * layer_vector_params(cfg) + cfg["d_model"]


def param_count(cfg):
    return matrix_params(cfg) + vector_params(cfg)


# ------------------------------------------------------------------ state
def state_values_per_slot(cfg, exact=False):
    """What one sequence keeps of ALL the layers: as kept, or the exact
    count of pairs."""
    d, g = d_head(cfg), kv_heads(cfg)
    a_head = pairs(cfg) * (d + 1) if exact \
        else kept_rows(cfg) * d + d * d
    return layers(cfg) * g * a_head


def state_bytes(cfg, b_max, itemsize=4, exact=False):
    return b_max * state_values_per_slot(cfg, exact) * itemsize


def static_bytes(cfg, b_max, max_len, cache_itemsize, weight_itemsize):
    """Matrices at the stored itemsize, vectors in float32, the state as
    kept. ``max_len`` sizes nothing: no cache has a position axis."""
    return matrix_params(cfg) * weight_itemsize + vector_params(cfg) * 4 \
        + state_bytes(cfg, b_max, cache_itemsize)


# ----------------------------------------------------------- the kernels
def update_bytes(cfg, rows, itemsize=4):
    """ONE layer's decode update over ``rows`` slots (module doc)."""
    d, h, g = d_head(cfg), cfg["n_head"], kv_heads(cfg)
    return rows * (2 * g * pairs(cfg) * (d + 1) + 2 * h * d + 2 * g * d
                   + g) * itemsize


def update_flops(cfg, rows):
    d, h, g = d_head(cfg), cfg["n_head"], kv_heads(cfg)
    return rows * pairs(cfg) * d * (3 * g + 2 * h)


def update_roofline(cfg, rows, peaks, itemsize=4):
    """Least seconds for the decode updates of all the layers of one
    step over ``rows`` slots."""
    n = layers(cfg)
    return _least(n * update_flops(cfg, rows),
                  n * update_bytes(cfg, rows, itemsize), peaks)


def scan_chunk(T):
    """The chunk the system scans a prompt of ``T`` positions in: the
    prompt rounded up to 128, at most 1,024."""
    return min(1024, -(-T // 128) * 128)


def scan_flops(cfg, T, chunk=None):
    """ONE layer's scan over ``T`` positions in chunks of ``chunk``
    (``scan_chunk(T)`` where not given; module doc); a ragged last chunk
    is computed whole and the first chunk reads no state."""
    d, h, g = d_head(cfg), cfg["n_head"], kv_heads(cfg)
    Q = int(chunk or scan_chunk(T))
    Tp = -(-T // Q) * Q
    return h * (Tp * 4 * Q * d + (Tp - Q) * 2 * pairs(cfg) * d) \
        + g * Tp * 2 * pairs(cfg) * d


def scan_bytes(cfg, T, itemsize=4):
    d, h, g = d_head(cfg), cfg["n_head"], kv_heads(cfg)
    return (T * (2 * h * d + 2 * g * d + g)
            + g * pairs(cfg) * (d + 1)) * itemsize


def scan_roofline(cfg, T, peaks, itemsize=4, chunk=None):
    """Least seconds for the scans of all the layers of one prefill of
    ``T`` positions, and which peak bounds it."""
    n = layers(cfg)
    return _least(n * scan_flops(cfg, T, chunk),
                  n * scan_bytes(cfg, T, itemsize), peaks)


def _least(flops, nbytes, peaks):
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes,
            "seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}


# ---------------------------------------------------------- a decode step
def decode_step_bytes(cfg, b_max, max_len, cache_itemsize, weight_itemsize):
    """Bytes one decode step must stream: every matrix once but the
    token table (looked up), the vectors, and the state and normaliser of
    all ``b_max`` slots TWICE (read and written) at the EXACT count of
    pairs. ``max_len`` moves nothing."""
    weights = (matrix_params(cfg) - cfg["vocab"] * cfg["d_model"]) \
        * weight_itemsize + vector_params(cfg) * 4
    state = 2 * state_bytes(cfg, b_max, cache_itemsize, exact=True)
    return {"weights": weights, "state": state, "cache": 0,
            "total": weights + state}
