"""Power retention of degree 2 (arXiv:2507.04239) in the two forms a
served sequence needs (models/gpt.py ``layer_types`` entry
``"retention"``).

A layer has ``H`` query heads of ``D`` values in ``G`` key-value heads;
query head ``h`` reads group ``h // (H / G)``. With ``g_t`` the gate of
the group (0 < g <= 1, handed in as ``lg = log g``) the layer is
attention whose weight is the SQUARE of the scaled score under a decay::

    a_tj = (q_t . k_j / sqrt(D))^2  prod_{j < l <= t} g_l        (j <= t)
    o_t  = sum_j a_tj v_j / (sum_j a_tj + EPS)

A square is an inner product of the operands' symmetric squares, ``<phi(a),
phi(b)> = (a . b)^2 / D``, so a sequence keeps, whatever its length::

    S_t = g_t S_t-1 + phi(k_t) v_t^T        [R, D]  (the state)
    z_t = g_t z_t-1 + phi(k_t)              (the normaliser)
    o_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + EPS)

``phi`` is kept in TILES of 16: the pairs ``(i, j)`` of every tile pair
``I <= J``, diagonal tiles whole (coefficient 1 there, sqrt 2 above:
each unordered pair is counted twice either way), all over ``sqrt(D)``.
At ``D = 128`` that is 36 tile pairs of 256 = ``R`` = 9,216 rows for the
exact 8,256. The rows are ordered ``(J, b, i)``: tile column ``J``, then
``j = 16 J + b``, then ``i < 16 (J + 1)`` — so the rows of one ``j`` are a
contiguous ``[16 (J + 1), D]`` block whose ``phi`` is a lane prefix of
the operand times one of its columns (``phi_index``). The state is
``[B, G, R, D]``; the normaliser is kept as the ``[D, D]`` matrix ``z[j,
i]`` (zero where ``i``'s tile is past ``j``'s): ``[B, G, D, D]``.

* ``power_update`` — ONE token a slot (the decode step). The Pallas
  kernel has a grid over (slot, group); a step reads the group's state
  once, writes it once INTO THE SAME BUFFER (``input_output_aliases``)
  and reads the group's query heads out of the new state in the same
  pass, sixteen rows at a time on the VPU: the state never meets the MXU.
  The token's rows (queries, key, value, gate) arrive as one ``[8, D]``
  tile and its columns as one ``[D, 8]`` tile. Bound by bytes.
* ``power_scan`` — a whole prompt (the prefill), CHUNKED: inside a chunk
  of ``Q`` positions the attention form (scores squared under the decay
  and the causal mask, two matrix products a head and block of queries,
  against the keys at or before the block), across chunks the state in
  VMEM scratch: read by the chunk's queries (not by the first chunk's)
  and then fed the chunk's keys and values. Both walk the state's 9,216
  rows in whole MXU tiles: ``phi`` of a tile column of the queries — of
  the keys — is built once into scratch, its blocks packed as the
  state's are, and multiplied against the column's rows ``_RUN`` at a
  time, so no product holds a row that the layout keeps at zero. The
  grid is (slot x group, chunk), chunks innermost and sequential. ``Q``
  follows from the prompt's length (``scan_chunk``: the largest the
  kernel takes, since the chip sweep found the largest chunk the fastest
  at every length, docs/KERNELS.md). A prompt that is no multiple of
  ``Q`` is padded with positions of ``k = v = 0`` and gate 1, which
  neither decay nor feed the state.

Each has a composed ``jax.numpy`` form with the same signature and the
same state layout: what the CPU runs, what ``PADDLE_TPU_KERNELS=0`` runs
on the chip, and what the tests compare the kernels with (the attention
form over the whole sequence is the reference's, tests/references/).
``paddle_power_plans_total`` counts which form and which chunk each
lowering took.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .common import ceil_to, checked_pallas_call, pad_axis, use_interpret

__all__ = ["power_update", "power_scan", "power_update_composed",
           "power_update_pallas", "power_scan_composed", "power_scan_pallas",
           "phi_plan", "phi_index", "phi", "state_shape", "norm_shape",
           "scan_chunk", "KERNEL_UPDATE", "KERNEL_SCAN", "TILE", "EPS"]

# the names the device trace and the HLO show the calls under
KERNEL_UPDATE = "power_update"
KERNEL_SCAN = "power_scan"

TILE = 16
# what the normaliser is added to (the published config states none)
EPS = 1e-6
_LANES = 128
# the longest chunk of a scan: the largest the chip sweep tried (128 ...
# 1,024) and the fastest at every prompt length (docs/KERNELS.md)
_CHUNK_MAX = 1024
# the rows of the state one product of the scan's read or feed takes: two
# MXU tiles, which every tile column (256 (Jt + 1) rows) is a whole
# number of (the chip sweep: 128 is 4% slower at a prompt of 8,192; a
# whole tile column 3.5% faster at three times the compile time and code)
_RUN = 256
# the positions a side of the blocks a chunk's scores are cut in (the
# same sweep: 36.6 / 36.9 / 37.7 / 39.1 ms at 128 / 256 / 512 / uncut;
# 128 is twice the blocks to trace and lower for its 0.8%, and a prefill
# program lowers the kernel five times on the host's clock: 256)
_SUB = 256
_VMEM_LIMIT_BYTES = 100 << 20
_HI = jax.lax.Precision.HIGHEST
_SQRT2 = 2.0 ** 0.5
# the update's token tile ``[8, D]``: up to five query heads, then these
_ROW_K, _ROW_V, _ROW_GATE = 5, 6, 7


def phi_plan(d):
    """``(tile, tiles, rows)`` of the symmetric square of ``d`` values:
    tiles of 16 (one tile of ``d`` where 16 does not divide it)."""
    d = int(d)
    tile = TILE if d % TILE == 0 else d
    tiles = d // tile
    return tile, tiles, tile * tile * tiles * (tiles + 1) // 2


@functools.lru_cache(maxsize=None)
def phi_index(d):
    """``(i, j, coef)`` ``[R]`` each: row ``r`` of the state holds the
    pair ``coef_r a_i a_j`` (module docstring's order and coefficients)."""
    tile, tiles, rows = phi_plan(d)
    ii, jj, cc = [], [], []
    for J in range(tiles):
        for b in range(tile):
            for i in range(tile * (J + 1)):
                ii.append(i)
                jj.append(tile * J + b)
                cc.append(1.0 if i // tile == J else _SQRT2)
    assert len(ii) == rows
    return (np.asarray(ii, np.int32), np.asarray(jj, np.int32),
            np.asarray(cc, np.float32) * np.float32(d ** -0.5))


@functools.lru_cache(maxsize=None)
def _pair_coef(d):
    """``cm[j, i]``: the coefficient of the pair in the normaliser's
    ``[D, D]`` form, zero where ``i``'s tile is past ``j``'s."""
    tile = phi_plan(d)[0]
    t = np.arange(d) // tile
    cm = np.where(t[None, :] == t[:, None], 1.0,
                  np.where(t[None, :] < t[:, None], _SQRT2, 0.0))
    return (cm * d ** -0.5).astype(np.float32)


def phi(a):
    """The kept symmetric square of ``a [..., D]``: ``[..., R]``."""
    ii, jj, coef = phi_index(a.shape[-1])
    return a[..., ii] * a[..., jj] * coef


def state_shape(batch, groups, d_head):
    """``[B, G, R, D]``: the layout a layer's state is kept in."""
    return (int(batch), int(groups), phi_plan(d_head)[2], int(d_head))


def norm_shape(batch, groups, d_head):
    """``[B, G, D, D]``: the normaliser's."""
    return (int(batch), int(groups), int(d_head), int(d_head))


def _dims(q, k):
    """(H, G, J, D) of a call from ``q [..., H, D]`` and ``k [..., G,
    D]``."""
    H, D = q.shape[-2:]
    G = k.shape[-2]
    if H % G or k.shape[-1] != D:
        raise ValueError("power retention: q %s is not %d groups of heads "
                         "over k %s" % (q.shape, G, k.shape))
    return H, G, H // G, D


# ------------------------------------------------------------ one token
def power_update_composed(state, norm, q, k, v, lg):
    """``(y [B, H, D], state', norm')``: ``state [B, G, R, D]``, ``norm
    [B, G, D, D]``, ``q [B, H, D]``, ``k``/``v`` ``[B, G, D]``, ``lg [B,
    G]`` (log of the gate)."""
    H, G, J, D = _dims(q, k)
    B = q.shape[0]
    g = jnp.exp(lg.astype(jnp.float32))[..., None, None]
    cm = jnp.asarray(_pair_coef(D))
    new = state * g + phi(k)[..., None] * v[:, :, None, :]
    nz = norm * g + cm * k[..., :, None] * k[..., None, :]
    qg = q.reshape(B, G, J, D)
    num = jnp.einsum("bgjr,bgrd->bgjd", phi(qg), new, precision=_HI)
    den = jnp.einsum("bgjm,bgjn,mn,bgmn->bgj", qg, qg, cm, nz,
                     precision=_HI)
    y = num / (den[..., None] + EPS)
    return y.reshape(B, H, D), new, nz


def _tile_coef(j_tile, i_tile, D):
    """The pair coefficient from the tiles of ``j`` and ``i`` (arrays
    that broadcast, or one of them a number): 1 in ``j``'s own tile, sqrt
    2 before it, 0 past it, over ``sqrt(D)``. ``_pair_coef`` as a kernel
    builds it."""
    return jnp.where(i_tile == j_tile, 1.0,
                     jnp.where(i_tile < j_tile, _SQRT2, 0.0)) \
        * (D ** -0.5)


def _update_kernel(s_ref, z_ref, r_ref, c_ref, so_ref, zo_ref, num_ref,
                   den_ref, bc_ref, *, J, unroll):
    from jax.experimental import pallas as pl

    D = _LANES
    tiles = D // TILE
    rows, cols = r_ref[0, 0], c_ref[0, 0]          # [8, D], [D, 8]
    K = _ROW_K
    g, v, krow = rows[_ROW_GATE:], rows[_ROW_V:_ROW_V + 1], rows[K:K + 1]
    # bc[h][j, :] = the operand's value j on every lane
    for h in list(range(J)) + [K]:
        bc_ref[h] = jnp.broadcast_to(cols[:, h:h + 1], (D, D))
    jt = jax.lax.broadcasted_iota(jnp.int32, (D, D), 0) // TILE
    it = jax.lax.broadcasted_iota(jnp.int32, (D, D), 1) // TILE
    cm = _tile_coef(jt, it, D)
    nz = z_ref[0, 0] * g + cm * bc_ref[K] * krow
    zo_ref[0, 0] = nz
    num_ref[0, 0] = jnp.zeros((8, D), jnp.float32)
    den_ref[0, 0] = jnp.zeros((8, D), jnp.float32)
    czn = cm * nz
    for h in range(J):
        # summed over j here, over the lanes outside
        den_ref[0, 0, h:h + 1, :] = jnp.sum(
            czn * bc_ref[h] * rows[h:h + 1], axis=0, keepdims=True)
    nums = [jnp.zeros((1, D), jnp.float32) for _ in range(J)]
    for Jt in range(tiles):
        base, stride = TILE * TILE * Jt * (Jt + 1) // 2, TILE * (Jt + 1)
        for isub in range(Jt + 1):
            coef = (1.0 if isub == Jt else _SQRT2) * D ** -0.5
            lo = TILE * isub
            kv = bc_ref[K, lo:lo + TILE, :] * coef * v      # [16, D]

            def body(b, accs, base=base, stride=stride, lo=lo, kv=kv,
                     Jt=Jt):
                j = TILE * Jt + b
                r0 = pl.multiple_of(base + b * stride + lo, TILE)
                new = s_ref[0, 0, pl.ds(r0, TILE), :] * g \
                    + bc_ref[K, pl.ds(j, 1), :] * kv
                so_ref[0, 0, pl.ds(r0, TILE), :] = new
                return tuple(acc + bc_ref[h, pl.ds(j, 1), :] * new
                             for h, acc in enumerate(accs))

            accs = jax.lax.fori_loop(
                0, TILE, body,
                tuple(jnp.zeros((TILE, D), jnp.float32) for _ in range(J)),
                unroll=unroll)
            for h in range(J):
                nums[h] = nums[h] + jnp.sum(
                    accs[h] * (bc_ref[h, lo:lo + TILE, :] * coef),
                    axis=0, keepdims=True)
    for h in range(J):
        num_ref[0, 0, h:h + 1, :] = nums[h]


def _update_plan(state_shape_, heads):
    """Whether the in-place kernel takes a state of this shape under
    ``heads`` query heads: whole lane tiles and the group's heads, the
    key, the value and the gate in one ``[8, D]`` tile."""
    B, G, R, D = (int(d) for d in state_shape_)
    return D == _LANES and int(heads) % G == 0 and int(heads) // G <= 5 \
        and R == phi_plan(D)[2]


def power_update_pallas(state, norm, q, k, v, lg, *, interpret=None):
    """One token a slot into ``state`` and ``norm``, in place: a grid
    over (slot, group), each step one read and one write of the group's
    ``[R, D]`` block and ``[D, D]`` normaliser (``input_output_aliases``
    ties both to the outputs) and the group's heads read out of the new
    state in the same pass."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, G, J, D = _dims(q, k)
    if not _update_plan(state.shape, H):
        raise ValueError("power_update: no block plan for a state %s under "
                         "%d heads" % (state.shape, H))
    B, R = state.shape[0], state.shape[2]
    if interpret is None:
        interpret = use_interpret()
    f32 = jnp.float32
    gate = jnp.broadcast_to(jnp.exp(lg.astype(f32))[..., None, None],
                            (B, G, 1, D))
    rows = jnp.concatenate([
        pad_axis(q.astype(f32).reshape(B, G, J, D), 2, _ROW_K),
        k.astype(f32)[:, :, None], v.astype(f32)[:, :, None], gate], axis=2)
    cols = jnp.swapaxes(rows, 2, 3)                     # [B, G, D, 8]
    blk = lambda *tail: pl.BlockSpec((1, 1) + tail,
                                     lambda b, g: (b, g, 0, 0))
    new, nz, num, den = checked_pallas_call(
        # (the interpreter traces every unrolled body: a loop there)
        functools.partial(_update_kernel, J=J, unroll=not interpret),
        name=KERNEL_UPDATE, grid=(B, G),
        in_specs=[blk(R, D), blk(D, D), blk(8, D), blk(D, 8)],
        operands=(state, norm, rows, cols),
        out_specs=[blk(R, D), blk(D, D), blk(8, D), blk(8, D)],
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(norm.shape, norm.dtype),
                   jax.ShapeDtypeStruct((B, G, 8, D), f32),
                   jax.ShapeDtypeStruct((B, G, 8, D), f32)],
        scratch_shapes=[pltpu.VMEM((_ROW_K + 1, D, D), f32)],
        interpret=interpret,
        input_output_aliases={0: 0, 1: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES))
    y = num[:, :, :J] / (jnp.sum(den[:, :, :J], axis=-1, keepdims=True)
                         + EPS)
    return y.reshape(B, H, D), new, nz


# --------------------------------------------------------- whole prompt
def _chunked(q, k, v, lg, chunk):
    """The scan's operands padded to whole chunks (``k = v = 0`` and
    gate 1 there) and the within-chunk inclusive sums of the log gate:
    ``(q, k, v, lc [B, Tp, G], T)``."""
    T = q.shape[1]
    Tp = ceil_to(T, chunk)
    q, k, v, lg = (pad_axis(t.astype(jnp.float32), 1, Tp)
                   for t in (q, k, v, lg))
    lc = jnp.cumsum(lg.reshape(lg.shape[0], Tp // chunk, chunk, -1),
                    axis=2).reshape(lg.shape)
    return q, k, v, lc, T


def power_scan_composed(q, k, v, lg, *, chunk):
    """``(y [B, T, H, D], state [B, G, R, D], norm [B, G, D, D])`` from
    ``q [B, T, H, D]``, ``k``/``v`` ``[B, T, G, D]``, ``lg [B, T, G]``,
    state and normaliser zero before the sequence. Chunked as the kernel
    is, a ``lax.scan`` over the chunks."""
    H, G, J, D = _dims(q, k)
    B = q.shape[0]
    Q = max(1, min(int(chunk), q.shape[1]))
    q, k, v, lc, T = _chunked(q, k, v, lg, Q)
    nc = q.shape[1] // Q
    cm = jnp.asarray(_pair_coef(D))

    def per_chunk(t, tail):       # [B, Tp, ...] -> [nc, B, Q, ...]
        return jnp.moveaxis(t.reshape((B, nc, Q) + tail), 1, 0)

    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def step(carry, c):
        S, Z = carry
        qc, kc, vc, lcc = c            # [B,Q,G,J,D] [B,Q,G,D] .. [B,Q,G]
        sc = jnp.einsum("btgjd,bsgd->bgjts", qc, kc,
                        precision=_HI) * D ** -0.5
        diff = jnp.moveaxis(lcc[:, :, None] - lcc[:, None], 3, 1)
        decay = jnp.exp(jnp.where(tri, diff, -jnp.inf))   # [B, G, t, s]
        a = sc * sc * decay[:, :, None]
        num = jnp.einsum("bgjts,bsgd->btgjd", a, vc, precision=_HI)
        den = jnp.moveaxis(jnp.sum(a, axis=-1), 3, 1)     # [B, t, G, J]
        # what the state before the chunk still gives each position
        elc = jnp.exp(lcc)[..., None]                     # [B, Q, G, 1]
        num = num + elc[..., None] * jnp.einsum(
            "btgjr,bgrd->btgjd", phi(qc), S, precision=_HI)
        den = den + elc * jnp.einsum(
            "btgjm,btgjn,mn,bgmn->btgj", qc, qc, cm, Z, precision=_HI)
        tot = lcc[:, -1]                                  # [B, G]
        w = jnp.exp(tot[:, None] - lcc)                   # [B, Q, G]
        etot = jnp.exp(tot)[..., None, None]
        S = etot * S + jnp.einsum("bsgr,bsg,bsgd->bgrd", phi(kc), w, vc,
                                  precision=_HI)
        Z = etot * Z + cm * jnp.einsum("bsgm,bsg,bsgn->bgmn", kc, w, kc,
                                       precision=_HI)
        return (S, Z), num / (den[..., None] + EPS)

    init = (jnp.zeros(state_shape(B, G, D), jnp.float32),
            jnp.zeros(norm_shape(B, G, D), jnp.float32))
    (S, Z), ys = jax.lax.scan(step, init, (
        per_chunk(q.reshape(B, -1, G, J, D), (G, J, D)),
        per_chunk(k, (G, D)), per_chunk(v, (G, D)), per_chunk(lc, (G,))))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, nc * Q, H, D)[:, :T]
    return y, S, Z


def _column(Jt):
    """(first row, rows a block, rows) of tile column ``Jt`` of the
    state: block ``b`` is that of ``j = 16 Jt + b``, and the column's 16
    blocks are contiguous, ``256 (Jt + 1)`` rows from a multiple of 256."""
    n = TILE * (Jt + 1)
    return TILE * TILE * Jt * (Jt + 1) // 2, n, TILE * n


def _scan_scratch(J, Q, D=_LANES):
    """The scan's VMEM scratch: the group's state and normaliser, a
    head's denominators, the read's accumulator, and ``phi`` of one tile
    column of the chunk's queries or keys (the widest: ``16 D`` rows)."""
    return [(phi_plan(D)[2], D), (D, D), (J, 1, Q), (D, Q), (TILE * D, Q)]


def _scan_kernel(qt_ref, k_ref, kt_ref, v_ref, vt_ref, lcol_ref, lrow_ref,
                 tcol_ref, trow_ref, yt_ref, so_ref, zo_ref, s_ref, z_ref,
                 den_ref, acc_ref, phi_ref, *, J, Q):
    """One chunk of one group, TRANSPOSED (positions along the lanes):
    the queries and the output ``[J, D, Q]``, the scores ``[s, t]``. So
    the column of ``q`` or ``k`` that a block of the state is built from
    is a ROW here, cut at a dynamic sublane. The state's rows are
    contiguous over tile columns and blocks, so ``phi`` of a whole tile
    column is built once into ``phi_ref`` (block ``b`` at the sublane
    offset ``b n``) and meets the state's rows ``_RUN`` at a time: no
    row of a product is a zero the layout is known to hold."""
    from jax.experimental import pallas as pl

    D = _LANES
    tiles = D // TILE
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    def dot(a, b, lhs_dim=1):
        return jax.lax.dot_general(
            a, b, (((lhs_dim,), (0,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32)

    def loop(n, body):
        """``body(i)`` for ``i < n``, as a loop: the kernel's code grows
        with neither the group's heads nor the state's rows."""
        def step(i, _):
            body(i)
            return 0

        jax.lax.fori_loop(0, n, step, 0)

    def expand(rows_of, Jt):
        """``phi`` of tile column ``Jt`` into ``phi_ref``'s first rows,
        from ``rows_of(slice)`` of the transposed operand ``[D(i), Q]``:
        block ``b`` is the operand's first ``n`` rows, under the pair
        coefficient, times its row ``16 Jt + b``."""
        _base, n, _rows = _column(Jt)
        # (``_tile_coef`` of a static ``Jt`` as ONE compare: the host
        # traces and lowers this kernel five times a prefill program, on
        # the clock of the cell's set-up)
        row = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
        m = rows_of(pl.ds(0, n)) * jnp.where(
            row < TILE * Jt, _SQRT2 * D ** -0.5, D ** -0.5)

        def block(b):
            phi_ref[pl.ds(pl.multiple_of(b * n, TILE), n), :] = \
                m * rows_of(pl.ds(TILE * Jt + b, 1))

        loop(TILE, block)

    def runs(Jt, body):
        """``body(row of the state, row of phi_ref)`` for every run of
        ``_RUN`` rows of tile column ``Jt``."""
        base, _n, rows = _column(Jt)

        def run(p):
            r = pl.multiple_of(p * _RUN, _RUN)
            body(base + r, r)

        loop(rows // _RUN, run)

    cm = _tile_coef(*(jax.lax.shift_right_logical(
        jax.lax.broadcasted_iota(jnp.int32, (D, D), axis),
        TILE.bit_length() - 1) for axis in (0, 1)), D)
    lcol, lrow = lcol_ref[0], lrow_ref[0]              # [Q, 1], [1, Q]
    # a chunk's square of scores [s, t] in blocks of ``_SUB`` positions:
    # a block of queries meets the keys at or before it, under plain
    # ``exp(lrow - lcol)`` below the diagonal block and the causal mask
    # on it
    cuts = [(t0, min(t0 + _SUB, Q)) for t0 in range(0, Q, _SUB)]
    decays, causal = [], {}
    for t0, t1 in cuts:
        w = t1 - t0
        if w not in causal:
            causal[w] = jax.lax.broadcasted_iota(jnp.int32, (w, w), 1) \
                >= jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
        lr = lrow_ref[0, :, t0:t1]
        on = jnp.exp(jnp.where(causal[w], lr - lcol_ref[0, t0:t1, :],
                               -1e30))
        decays.append(on if t0 == 0 else jnp.concatenate(
            [jnp.exp(lr - lcol_ref[0, :t0, :]), on], axis=0))

    def inside(h):
        for (t0, t1), decay in zip(cuts, decays):
            at = pl.ds(t0, t1 - t0)
            s = dot(k_ref[0, :t1, :], qt_ref[0, h, :, at]) * D ** -0.5
            a = s * s * decay                          # [s <= t1, t]
            yt_ref[0, h, :, at] = dot(vt_ref[0, :, :t1], a)
            den_ref[h, :, at] = jnp.sum(a, axis=0, keepdims=True)

    with jax.named_scope("inside"):
        loop(J, inside)

    @pl.when(c > 0)
    def _():
        # what the state before the chunk still gives each position
        elc = jnp.exp(lrow)
        cz = cm * z_ref[...]

        def before(h):
            qt = qt_ref[0, h]                          # [D(i), Q]
            acc_ref[...] = jnp.zeros_like(acc_ref)

            def read(r_state, r_phi):
                acc_ref[...] += dot(s_ref[pl.ds(r_state, _RUN), :],
                                    phi_ref[pl.ds(r_phi, _RUN), :],
                                    lhs_dim=0)

            for Jt in range(tiles):
                expand(lambda rows: qt_ref[0, h, rows, :], Jt)
                runs(Jt, read)
            yt_ref[0, h] = yt_ref[0, h] + elc * acc_ref[...]
            den_ref[h] = den_ref[h] + elc * jnp.sum(
                qt * dot(cz, qt), axis=0, keepdims=True)

        with jax.named_scope("read"):
            loop(J, before)

    def normalise(h):
        yt_ref[0, h] = yt_ref[0, h] / (den_ref[h] + EPS)

    loop(J, normalise)

    # the chunk's keys and values into the state
    k, kt = k_ref[0], kt_ref[0]
    wv = jnp.exp(tcol_ref[0] - lcol) * v_ref[0]        # [Q, D]
    trow = trow_ref[0]                                 # [1, Q]
    etot = jnp.exp(trow[:, :D])

    def feed(r_state, r_phi):
        at = pl.ds(r_state, _RUN)
        s_ref[at, :] = etot * s_ref[at, :] \
            + dot(phi_ref[pl.ds(r_phi, _RUN), :], wv)

    with jax.named_scope("feed"):
        for Jt in range(tiles):
            expand(lambda rows: kt_ref[0, rows, :], Jt)
            runs(Jt, feed)
    z_ref[...] = etot * z_ref[...] + cm * dot(kt * jnp.exp(trow - lrow), k)

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        so_ref[0] = s_ref[...]
        zo_ref[0] = z_ref[...]


def scan_chunk(T):
    """The chunk a prompt of ``T`` positions is scanned in: the prompt
    rounded up to whole lanes, at most ``_CHUNK_MAX``."""
    return min(_CHUNK_MAX, ceil_to(int(T), _LANES))


def _scan_plan(H, G, D, chunk):
    """``chunk`` where the kernel has a block plan for it, else None."""
    if D != _LANES or H % G or H // G > 8 or int(chunk) % _LANES:
        return None
    return int(chunk)


def power_scan_pallas(q, k, v, lg, *, chunk, interpret=None):
    """The chunked scan of a whole prompt (module docstring): a grid
    over (batch x group, chunk), chunks innermost and sequential with
    the group's state and normaliser in VMEM scratch."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, G, J, D = _dims(q, k)
    Q = _scan_plan(H, G, D, chunk)
    if Q is None:
        raise ValueError("power_scan: no block plan for q %s in %d groups "
                         "at chunk %r" % (q.shape, G, chunk))
    if interpret is None:
        interpret = use_interpret()
    B = q.shape[0]
    q, k, v, lc, T = _chunked(q, k, v, lg, Q)
    Tp = q.shape[1]
    nc, BG, R = Tp // Q, B * G, phi_plan(D)[2]

    def grouped(t, tail):          # [B, Tp, G, ...] -> [B G, Tp, ...]
        return jnp.moveaxis(t.reshape((B, Tp, G) + tail), 2, 1) \
            .reshape((BG, Tp) + tail)

    turned = lambda t: jnp.swapaxes(t, -1, -2)
    qt = jnp.transpose(grouped(q, (J, D)), (0, 2, 3, 1))  # [BG, J, D, Tp]
    kg, vg = grouped(k, (D,)), grouped(v, (D,))         # [BG, Tp, D]
    lcol = grouped(lc, ())[..., None]                   # [BG, Tp, 1]
    tcol = jnp.repeat(lcol.reshape(BG, nc, Q, 1)[:, :, -1:], Q, axis=2) \
        .reshape(BG, Tp, 1)
    wide = pl.BlockSpec((1, Q, D), lambda g, c: (g, c, 0))
    tall = pl.BlockSpec((1, D, Q), lambda g, c: (g, 0, c))
    col = pl.BlockSpec((1, Q, 1), lambda g, c: (g, c, 0))
    row = pl.BlockSpec((1, 1, Q), lambda g, c: (g, 0, c))
    heads = pl.BlockSpec((1, J, D, Q), lambda g, c: (g, 0, 0, c))
    yt, s, z = checked_pallas_call(
        functools.partial(_scan_kernel, J=J, Q=Q),
        name=KERNEL_SCAN, grid=(BG, nc),
        in_specs=[heads, wide, tall, wide, tall, col, row, col, row],
        operands=(qt, kg, turned(kg), vg, turned(vg), lcol, turned(lcol),
                  tcol, turned(tcol)),
        out_specs=[heads,
                   pl.BlockSpec((1, R, D), lambda g, c: (g, 0, 0)),
                   pl.BlockSpec((1, D, D), lambda g, c: (g, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((BG, J, D, Tp), jnp.float32),
                   jax.ShapeDtypeStruct((BG, R, D), jnp.float32),
                   jax.ShapeDtypeStruct((BG, D, D), jnp.float32)],
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32)
                        for shape in _scan_scratch(J, Q)],
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES))
    y = jnp.transpose(yt.reshape(B, G, J, D, Tp), (0, 4, 1, 2, 3)) \
        .reshape(B, Tp, H, D)[:, :T]
    return y, s.reshape(B, G, R, D), z.reshape(B, G, D, D)


# ------------------------------------------------------------- dispatch
def _note_plan(kernel, form, chunk):
    from ..observe.families import POWER_PLANS

    POWER_PLANS.labels(kernel=kernel, form=form, chunk=str(chunk)).inc()


def _kernels_on():
    from . import kernels_enabled

    return kernels_enabled() and not use_interpret()


def power_update(state, norm, q, k, v, lg):
    """The one-token update in whichever form this lowering can take:
    the in-place kernel where Pallas compiles (a TPU) and the state has a
    block plan, the composed form elsewhere."""
    if _kernels_on() and _update_plan(state.shape, q.shape[-2]):
        _note_plan(KERNEL_UPDATE, "pallas", 1)
        return power_update_pallas(state, norm, q, k, v, lg,
                                   interpret=False)
    _note_plan(KERNEL_UPDATE, "composed", 1)
    return power_update_composed(state, norm, q, k, v, lg)


def power_scan(q, k, v, lg):
    """The scan of a whole prompt, in chunks of ``scan_chunk`` of its
    length, in whichever form this lowering can take (as
    ``power_update``)."""
    H, G, _J, D = _dims(q, k)
    chunk = scan_chunk(q.shape[1])
    if _kernels_on() and _scan_plan(H, G, D, chunk):
        _note_plan(KERNEL_SCAN, "pallas", chunk)
        return power_scan_pallas(q, k, v, lg, chunk=chunk, interpret=False)
    _note_plan(KERNEL_SCAN, "composed", chunk)
    return power_scan_composed(q, k, v, lg, chunk=chunk)
