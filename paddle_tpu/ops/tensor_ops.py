"""Tensor-manipulation ops: reshape/transpose/concat/split/slice/gather/...

Parity targets: /root/reference/paddle/fluid/operators/reshape_op.cc,
transpose_op.cc, concat_op.cc, split_op.cc, squeeze_op.cc, unsqueeze_op.cc,
flatten_op.cc, stack_op.cc, slice_op.cc, gather_op.cc, scatter_op.cc,
expand_op.cc, pad_op.cc, pad2d_op.cc, crop_op.cc, reverse_op.cc,
where (select), shard_index. The *2 variants also emit XShape for the grad
path, matching the reference's inplace-friendly op pairs.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..core.registry import register_op


def _infer_reshape(x, shape):
    shape = list(shape)
    out = []
    neg = -1
    known = 1
    for i, s in enumerate(shape):
        if s == -1:
            neg = i
            out.append(-1)
        elif s == 0:
            out.append(x.shape[i])
            known *= x.shape[i]
        else:
            out.append(int(s))
            known *= int(s)
    if neg >= 0:
        out[neg] = int(x.size // known)
    return tuple(out)


@register_op("reshape", diff_inputs=["X"])
def _reshape(ctx, ins, attrs):
    x = ins["X"][0]
    return {"Out": [x.reshape(_infer_reshape(x, attrs["shape"]))]}


@register_op("reshape2", diff_inputs=["X"])
def _reshape2(ctx, ins, attrs):
    x = ins["X"][0]
    out = x.reshape(_infer_reshape(x, attrs["shape"]))
    return {"Out": [out], "XShape": [jnp.zeros((0,) + x.shape, dtype=x.dtype)]}


@register_op("transpose", diff_inputs=["X"])
def _transpose(ctx, ins, attrs):
    return {"Out": [jnp.transpose(ins["X"][0], attrs["axis"])]}


@register_op("transpose2", diff_inputs=["X"])
def _transpose2(ctx, ins, attrs):
    x = ins["X"][0]
    return {
        "Out": [jnp.transpose(x, attrs["axis"])],
        "XShape": [jnp.zeros((0,) + x.shape, dtype=x.dtype)],
    }


@register_op("concat", diff_inputs=["X"])
def _concat(ctx, ins, attrs):
    xs = [x for x in ins["X"] if x is not None]
    return {"Out": [jnp.concatenate(xs, axis=attrs.get("axis", 0))]}


@register_op("split", diff_inputs=["X"])
def _split(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 0)
    num = attrs.get("num", 0)
    sections = attrs.get("sections", [])
    if num:
        parts = jnp.split(x, num, axis=axis)
    else:
        idx = []
        acc = 0
        for s in sections[:-1]:
            acc += s
            idx.append(acc)
        parts = jnp.split(x, idx, axis=axis)
    return {"Out": parts}


@register_op("squeeze", diff_inputs=["X"])
def _squeeze(ctx, ins, attrs):
    x = ins["X"][0]
    axes = attrs.get("axes", [])
    axes = [a % x.ndim for a in axes] or [i for i, s in enumerate(x.shape) if s == 1]
    return {"Out": [jnp.squeeze(x, tuple(a for a in axes if x.shape[a] == 1))]}


@register_op("squeeze2", diff_inputs=["X"])
def _squeeze2(ctx, ins, attrs):
    x = ins["X"][0]
    out = _squeeze(ctx, ins, attrs)["Out"][0]
    return {"Out": [out], "XShape": [jnp.zeros((0,) + x.shape, dtype=x.dtype)]}


@register_op("unsqueeze", diff_inputs=["X"])
def _unsqueeze(ctx, ins, attrs):
    x = ins["X"][0]
    for a in sorted(attrs["axes"]):
        x = jnp.expand_dims(x, a)
    return {"Out": [x]}


@register_op("unsqueeze2", diff_inputs=["X"])
def _unsqueeze2(ctx, ins, attrs):
    x = ins["X"][0]
    out = _unsqueeze(ctx, ins, attrs)["Out"][0]
    return {"Out": [out], "XShape": [jnp.zeros((0,) + x.shape, dtype=x.dtype)]}


@register_op("flatten", diff_inputs=["X"])
def _flatten(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 1)
    lead = 1
    for s in x.shape[:axis]:
        lead *= s
    return {"Out": [x.reshape(lead, -1)]}


@register_op("flatten2", diff_inputs=["X"])
def _flatten2(ctx, ins, attrs):
    x = ins["X"][0]
    out = _flatten(ctx, ins, attrs)["Out"][0]
    return {"Out": [out], "XShape": [jnp.zeros((0,) + x.shape, dtype=x.dtype)]}


@register_op("stack", diff_inputs=["X"])
def _stack(ctx, ins, attrs):
    return {"Y": [jnp.stack(ins["X"], axis=attrs.get("axis", 0))]}


@register_op("unstack", diff_inputs=["X"])
def _unstack(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 0)
    parts = jnp.split(x, x.shape[axis], axis=axis)
    return {"Y": [jnp.squeeze(p, axis) for p in parts]}


@register_op("slice", diff_inputs=["Input"])
def _slice(ctx, ins, attrs):
    x = ins["Input"][0]
    axes = attrs["axes"]
    starts = attrs["starts"]
    ends = attrs["ends"]
    idx = [slice(None)] * x.ndim
    for a, s, e in zip(axes, starts, ends):
        dim = x.shape[a]
        s = max(s + dim, 0) if s < 0 else min(s, dim)
        e = max(e + dim, 0) if e < 0 else min(e, dim)
        idx[a] = slice(s, e)
    return {"Out": [x[tuple(idx)]]}


@register_op("strided_slice", diff_inputs=["Input"])
def _strided_slice(ctx, ins, attrs):
    x = ins["Input"][0]
    idx = [slice(None)] * x.ndim
    for a, s, e, st in zip(attrs["axes"], attrs["starts"], attrs["ends"], attrs["strides"]):
        idx[a] = slice(s, e, st)
    return {"Out": [x[tuple(idx)]]}


@register_op("gather", diff_inputs=["X"])
def _gather(ctx, ins, attrs):
    x, idx = ins["X"][0], ins["Index"][0]
    if idx.ndim == 2 and idx.shape[1] == 1:
        idx = idx[:, 0]
    return {"Out": [jnp.take(x, idx.astype(jnp.int32), axis=attrs.get("axis", 0))]}


@register_op("gather_nd", diff_inputs=["X"])
def _gather_nd(ctx, ins, attrs):
    x, idx = ins["X"][0], ins["Index"][0]
    idx = idx.astype(jnp.int32)
    return {"Out": [x[tuple(jnp.moveaxis(idx, -1, 0))]]}


@register_op("scatter", diff_inputs=["X", "Updates"])
def _scatter(ctx, ins, attrs):
    x, ids, upd = ins["X"][0], ins["Ids"][0], ins["Updates"][0]
    ids = ids.astype(jnp.int32)
    if ids.ndim == 2 and ids.shape[1] == 1:
        ids = ids[:, 0]
    if attrs.get("overwrite", True):
        out = x.at[ids].set(upd)
    else:
        out = x.at[ids].add(upd)
    return {"Out": [out]}


@register_op("expand", diff_inputs=["X"])
def _expand(ctx, ins, attrs):
    x = ins["X"][0]
    times = attrs["expand_times"]
    return {"Out": [jnp.tile(x, tuple(times))]}


@register_op("expand_as", diff_inputs=["X"])
def _expand_as(ctx, ins, attrs):
    x, target = ins["X"][0], ins["target_tensor"][0]
    reps = tuple(t // s for t, s in zip(target.shape, x.shape))
    return {"Out": [jnp.tile(x, reps)]}


@register_op("tile", diff_inputs=["X"])
def _tile(ctx, ins, attrs):
    return {"Out": [jnp.tile(ins["X"][0], tuple(attrs["repeat_times"]))]}


@register_op("pad", diff_inputs=["X"])
def _pad(ctx, ins, attrs):
    x = ins["X"][0]
    p = attrs["paddings"]
    pairs = [(p[2 * i], p[2 * i + 1]) for i in range(x.ndim)]
    return {"Out": [jnp.pad(x, pairs, constant_values=attrs.get("pad_value", 0.0))]}


@register_op("pad2d", diff_inputs=["X"])
def _pad2d(ctx, ins, attrs):
    x = ins["X"][0]
    p = attrs["paddings"]  # [top, bottom, left, right]
    mode = attrs.get("mode", "constant")
    pairs = [(0, 0), (0, 0), (p[0], p[1]), (p[2], p[3])]
    if mode == "constant":
        return {"Out": [jnp.pad(x, pairs, constant_values=attrs.get("pad_value", 0.0))]}
    jmode = {"reflect": "reflect", "edge": "edge"}[mode]
    return {"Out": [jnp.pad(x, pairs, mode=jmode)]}


@register_op("crop", diff_inputs=["X"])
def _crop(ctx, ins, attrs):
    x = ins["X"][0]
    offsets = attrs["offsets"]
    shape = attrs["shape"]
    idx = tuple(slice(o, o + s) for o, s in zip(offsets, shape))
    return {"Out": [x[idx]]}


@register_op("reverse", diff_inputs=["X"])
def _reverse(ctx, ins, attrs):
    x = ins["X"][0]
    for a in attrs["axis"]:
        x = jnp.flip(x, a)
    return {"Out": [x]}


@register_op("where_op", diff_inputs=["X", "Y"])
def _where(ctx, ins, attrs):
    cond, x, y = ins["Condition"][0], ins["X"][0], ins["Y"][0]
    return {"Out": [jnp.where(cond, x, y)]}


@register_op("shard_index", no_grad=True)
def _shard_index(ctx, ins, attrs):
    x = ins["X"][0]
    index_num = attrs["index_num"]
    nshards = attrs["nshards"]
    shard_id = attrs["shard_id"]
    ignore = attrs.get("ignore_value", -1)
    size = (index_num + nshards - 1) // nshards
    in_shard = (x // size) == shard_id
    return {"Out": [jnp.where(in_shard, x % size, ignore)]}


@register_op("roll", diff_inputs=["X"])
def _roll(ctx, ins, attrs):
    x = ins["X"][0]
    return {"Out": [jnp.roll(x, attrs["shifts"], attrs.get("axis"))]}


@register_op("meshgrid", diff_inputs=["X"])
def _meshgrid(ctx, ins, attrs):
    outs = jnp.meshgrid(*ins["X"], indexing="ij")
    return {"Out": list(outs)}


@register_op("kv_cache_write", no_grad=True)
def _kv_cache_write(ctx, ins, attrs):
    """Write a decode step's K or V rows into a [B, H, S, D] cache at a
    runtime position — the incremental-decoding primitive (models/gpt.py
    decode step). The cache is persistable state: the executor donates
    it, so the update is in-place on device. Inference-only (no_grad).

    Pos is a [1] scalar (every batch row writes the same position — the
    classic lockstep decode step, one lax.dynamic_update_slice on the
    sequence axis) or [B]/[B, 1] per-row positions (each cache slot
    advances independently — the continuous-batching serving step,
    models/gpt.py build_serving_decode_step): one in-place Pallas call
    where a slot writes one row and Pallas compiles, the slice update
    vmapped over the batch axis elsewhere (kernels/kv_cache_write.py
    chooses and counts which)."""
    from ..kernels.kv_cache_write import kv_cache_write

    return {"Out": [kv_cache_write(ins["Cache"][0], ins["Update"][0],
                                   ins["Pos"][0])]}


@register_op("mla_decode", no_grad=True)
def _mla_decode(ctx, ins, attrs):
    """Latent attention's absorbed form for one decode position
    (models/gpt.py cfg['attn']='mla'; kernels/mla_decode.py holds the
    attention itself, a Pallas kernel on the TPU). The up-projection
    ``W [d_c, H (d_nope + d_v)]`` is folded in on both sides: ``q_lat =
    q_nope W_uk^T`` before, ``ctx = o W_uv`` after, each widened to the
    activations' dtype where it multiplies; what lies between reads
    keys and values out of the one latent slab. Inference-only."""
    from ..kernels.mla_decode import mla_decode

    qn, qr = ins["QNope"][0], ins["QRope"][0]      # [B, 1, H, dn | dr]
    cache, pos, w = ins["Cache"][0], ins["Pos"][0], ins["W"][0]
    B, _, H, dn = qn.shape
    dc, dv = w.shape[0], int(attrs["d_v"])
    w = w.reshape(dc, H, dn + dv).astype(qn.dtype)
    q_lat = jnp.einsum("bhd,chd->bhc", qn[:, 0], w[:, :, :dn])
    q = jnp.concatenate([q_lat, qr[:, 0]], axis=-1)  # [B, H, dc + dr]
    if pos.size == 1:                                # one shared position
        pos = jnp.broadcast_to(pos.reshape(()), (B,))
    o = mla_decode(q, cache, pos, d_c=dc, scale=float(attrs["scale"]))
    out = jnp.einsum("bhc,chd->bhd", o.astype(qn.dtype), w[:, :, dn:])
    return {"Out": [out.reshape(B, 1, H * dv)]}


@register_op("mhc_pre", no_grad=True)
def _mhc_pre(ctx, ins, attrs):
    """What a sub-block reads of a token's ``n`` residual streams
    (models/gpt.py cfg['residual']='mhc'; kernels/mhc.py holds the
    arithmetic, a Pallas kernel on the TPU): ``H`` = the mixed vector
    ``sum_i H_pre[i] X[i]`` and ``Coef`` = ``[H_pre | H_post | H_res]`` a
    row, from ``X [..., n C]`` (stream ``i`` in lanes ``i C ..``). With
    ``Dev`` (a persistable ``[1]``) the largest deviation of any
    ``H_res`` row or column sum from one is kept as a running maximum.
    Inference-only."""
    from ..kernels.mhc import mhc_pre

    x = ins["X"][0]
    n = int(attrs["n"])
    h, coef, dev = mhc_pre(
        x.reshape(-1, x.shape[-1]), ins["Phi"][0], ins["Alpha"][0],
        ins["B"][0], n=n, eps=float(attrs["epsilon"]),
        iters=int(attrs["sinkhorn_iters"]), hc_eps=float(attrs["hc_eps"]),
        clamp=(float(attrs["clamp_min"]), float(attrs["clamp_max"])))
    lead = x.shape[:-1]
    outs = {"H": [h.reshape(lead + (x.shape[-1] // n,))],
            "Coef": [coef.reshape(lead + (n * (n + 2),))]}
    if ins.get("Dev"):
        seen = ins["Dev"][0]
        outs["DevOut"] = [jnp.maximum(seen, dev.astype(seen.dtype))]
    return outs


@register_op("mhc_post", no_grad=True)
def _mhc_post(ctx, ins, attrs):
    """What a sub-block writes back to the ``n`` streams: ``Out[i] =
    sum_j H_res[i, j] X[j] + H_post[i] Y`` with ``Coef`` as ``mhc_pre``
    left it; on the TPU in place, into ``X``'s buffer (kernels/mhc.py).
    Inference-only."""
    from ..kernels.mhc import mhc_post

    x, y, coef = ins["X"][0], ins["Y"][0], ins["Coef"][0]
    out = mhc_post(x.reshape(-1, x.shape[-1]), y.reshape(-1, y.shape[-1]),
                   coef.reshape(-1, coef.shape[-1]), n=int(attrs["n"]))
    return {"Out": [out.reshape(x.shape)]}


@register_op("rope", diff_inputs=["X"])
def _rope(ctx, ins, attrs):
    """Rotary position embedding (rotate-half convention) on [..., S, D]
    head tensors: pairs (x_i, x_{i+D/2}) rotate by pos * base^(-2i/D).
    Positions arrive as an INPUT ([S] int, or [1] for a decode step at
    a runtime offset) so one compiled executable serves every position;
    the gradient comes mechanically from jax.vjp of this lowering (a
    rotation's vjp is the inverse rotation). No reference counterpart
    (Fluid v1.3 predates RoPE); the modern-decoder position scheme the
    GPT family uses with cfg['pos_emb']='rope'."""
    x, pos = ins["X"][0], ins["Pos"][0]
    base = float(attrs.get("base", 10000.0))
    # attr ``rotary_dim``: only the first that many values of a head
    # rotate (rotate-half inside them), the others pass
    whole = x
    d = int(attrs.get("rotary_dim", 0) or 0) or x.shape[-1]
    if d < x.shape[-1]:
        x = x[..., :d]
    half = d // 2
    inv = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    factor = float(attrs.get("yarn_factor", 0.0) or 0.0)
    if factor:
        # YaRN: dimension i keeps its frequency below ``yarn_low``, has
        # it divided by ``factor`` above ``yarn_high`` and a linear blend
        # between: theta_i (1 - r_i) + (theta_i / factor) r_i, written so
        # that factor 1 leaves every frequency as it was, bit for bit
        low, high = float(attrs["yarn_low"]), float(attrs["yarn_high"])
        ramp = jnp.clip((jnp.arange(0, half, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        inv = inv * (1.0 - ramp * (1.0 - 1.0 / factor))
    if pos.ndim == 2:
        # per-row positions [B, S] (packed sequences: positions reset
        # at segment starts): angles [B, 1, S, half] broadcast over
        # the head axis of x [B, H, S, Dh] — 4-D x only (a 3-D x
        # would broadcast into a wrong [B, B, ...] result silently)
        if x.ndim != 4:
            raise ValueError(
                "rope with [B, S] positions needs a [B, H, S, D] "
                "head tensor; got x rank %d" % x.ndim)
        ang = pos.astype(jnp.float32)[..., None] * inv
        sin = jnp.sin(ang).astype(x.dtype)
        cos = jnp.cos(ang).astype(x.dtype)
        if not attrs.get("heads_last"):
            sin, cos = sin[:, None], cos[:, None]
    else:
        ang = pos.reshape(-1).astype(jnp.float32)[:, None] * inv[None, :]
        sin = jnp.sin(ang).astype(x.dtype)  # [S, half]
        cos = jnp.cos(ang).astype(x.dtype)
    mscale = float(attrs.get("yarn_mscale", 1.0) or 1.0)
    if mscale != 1.0:
        sin, cos = sin * mscale, cos * mscale
    if attrs.get("heads_last"):
        # x [B, S, H, D], as a projection leaves it: the head axis stands
        # between the positions and the pairs, and nothing is transposed
        sin, cos = sin[..., None, :], cos[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x1 * sin + x2 * cos]
                          + ([whole[..., d:]] if d < whole.shape[-1] else []),
                          axis=-1)
    return {"Out": [out]}
