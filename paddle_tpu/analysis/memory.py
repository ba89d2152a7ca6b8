"""Static peak-HBM estimation: the fourth analysis engine.

PR 5 proves shapes, PR 11 dataflow hazards, PR 13 value ranges — this
module models **bytes**: a liveness-based peak-device-memory estimator
that walks the global block over the shared :class:`~paddle_tpu.
analysis.dataflow.Dataflow` facts with per-op footprint rules, so every
memory decision in the framework (window-tune candidates, serving
admission, quantization payoff, "does this batch size fit at all")
can be made BEFORE paying for a compile or an OOM. The reference
framework's ``memory_usage(program, batch_size)`` existed for exactly
this; TVM (arXiv:1802.04799) makes the same point one level down — a
cost model that prunes the candidate space before a measurement.

The model, per analyzed program:

* **persistable** bytes (parameters, optimizer slots, decode-cache
  slabs, scope-backed write-back state) are resident for the whole
  step;
* **feed** bytes (``is_data`` vars) are resident for the whole step and
  multiply by ``steps_per_call`` — whole-loop compilation stacks K host
  batches into ONE device-resident window (core/pipeline.py);
* **activations** live from their defining op to their last reader
  (the Dataflow liveness facts; fetched/pinned names live to the block
  end), so two temps whose lifetimes never overlap never sum;
* **workspace** bytes are per-op annotations for the known
  non-streaming ops (matmul operand copies, conv im2col patches, the
  attention score matrix, softmax/xent temps), registered via
  :func:`register_footprint_rule` — the TPP shape (arXiv:2104.05755):
  compose the whole-program estimate from per-primitive analyses.

Every tensor's bytes are a :class:`BytesPoly` — a small polynomial in
the batch size (symbolic ``-1`` dims each contribute one degree), so
ONE analysis answers every batch size and ``max_safe_batch`` solves
"the largest B that fits" from the closed form instead of re-analyzing.

**Honesty note** (docs/ANALYSIS.md "Memory engine" has the long form):
the estimate cannot see XLA's buffer reuse, fusion (which deletes
intermediates entirely), rematerialization or donation — it brackets
the compiled peak from above on the activation side while XLA's
``memory_analysis()`` (``contrib.memory_usage_calc.
compiled_memory_usage``) is the authoritative post-compile number. The
model-zoo gate in tests/test_memory.py holds the static estimate within
a stated factor (``ZOO_GATE_FACTOR``) of XLA's own answer so the
estimate stays anchored to ground truth, not vibes.

Consumers: the memory lint rules (``analysis/lint.py``:
memory-over-budget / max-safe-batch / dead-persistable), the serving
engine's predicted-bytes admission guard (``serving/engine.py``) and
``tools/memory_report.py``. ``paddle_analysis_memory_*`` observe families
count analyses and wall time.
"""

from __future__ import annotations

import os
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

from ..core.program import Program
from .dataflow import Dataflow

__all__ = [
    "BytesPoly",
    "DTYPE_BYTES",
    "FOOTPRINT_RULES",
    "MemoryAnalysis",
    "ZOO_GATE_FACTOR",
    "decode_cache_bytes",
    "device_budget",
    "dtype_bytes",
    "estimate_peak_bytes",
    "format_bytes",
    "parse_bytes",
    "register_footprint_rule",
]

# THE dtype size table (contrib/memory_usage_calc.py delegates here);
# an unknown dtype warns and falls back to 4 bytes instead of silently
# under/over-counting
DTYPE_BYTES = {
    "float64": 8, "float32": 4, "float16": 2, "bfloat16": 2,
    "int64": 8, "int32": 4, "int16": 2, "int8": 1, "uint8": 1,
    "uint16": 2, "uint32": 4, "uint64": 8, "bool": 1,
}

# the stated factor of the model-zoo ground-truth gate: the static
# estimate must sit within [xla/F, xla*F] of XLA memory_analysis() on
# >= 9/11 train programs (tests/test_memory.py pins it; measured
# ratios on the CPU backend span 0.87-1.34x, so 2x is honest headroom
# for what a pre-compile estimate can promise — it cannot see XLA's
# buffer reuse or fusion, and XLA cannot be out-guessed on layout)
ZOO_GATE_FACTOR = 2.0


def dtype_bytes(dtype, warn: bool = True) -> int:
    """Bytes per element of ``dtype``; unknown dtypes warn (once per
    process per dtype via the warnings registry) and assume 4."""
    size = DTYPE_BYTES.get(str(dtype))
    if size is None:
        if warn:
            warnings.warn(
                "unknown dtype %r in memory estimate: assuming 4 "
                "bytes/element (add it to analysis.memory.DTYPE_BYTES)"
                % (dtype,), stacklevel=2)
        return 4
    return size


# --------------------------------------------------------------- polynomial
class BytesPoly:
    """Bytes as a polynomial of the batch size.

    A tensor shape's concrete dims multiply into the coefficient; each
    symbolic ``-1`` dim raises the degree by one (``[-1, 784]`` f32 is
    ``3136*B`` bytes; a rank-2 ``[-1, -1]`` attention score block would
    be degree 2). Coefficients are non-negative, so every poly — and
    any max over polys — is monotone in B, which is what lets
    ``max_safe_batch`` binary-search the closed form."""

    __slots__ = ("terms",)

    def __init__(self, terms: Optional[Dict[int, float]] = None):
        self.terms: Dict[int, float] = {
            int(d): float(c) for d, c in (terms or {}).items() if c}

    @classmethod
    def const(cls, n: float) -> "BytesPoly":
        return cls({0: float(n)})

    @classmethod
    def from_dims(cls, dims: Sequence, elem_bytes: int) -> "BytesPoly":
        """Poly for a tensor of ``dims`` (-1/None = one batch factor)
        at ``elem_bytes`` per element."""
        coeff, degree = float(elem_bytes), 0
        for d in dims:
            if d is None or int(d) < 0:
                degree += 1
            else:
                coeff *= int(d)
        return cls({degree: coeff})

    @classmethod
    def from_shape(cls, shape, dtype,
                   warn: bool = False) -> Optional["BytesPoly"]:
        """Poly for a var's (shape, dtype); None when the rank itself
        is unknown (the caller counts it as an unknown tensor)."""
        if shape is None:
            return None
        return cls.from_dims(tuple(shape), dtype_bytes(dtype, warn=warn))

    # ------------------------------------------------------------ algebra
    def __add__(self, other) -> "BytesPoly":
        if isinstance(other, (int, float)):
            other = BytesPoly.const(other)
        out = dict(self.terms)
        for d, c in other.terms.items():
            out[d] = out.get(d, 0.0) + c
        return BytesPoly(out)

    __radd__ = __add__

    def __sub__(self, other) -> "BytesPoly":
        if isinstance(other, (int, float)):
            other = BytesPoly.const(other)
        out = dict(self.terms)
        for d, c in other.terms.items():
            out[d] = out.get(d, 0.0) - c
        return BytesPoly(out)

    def scaled(self, k: float) -> "BytesPoly":
        return BytesPoly({d: c * k for d, c in self.terms.items()})

    def at(self, batch_size: int) -> int:
        """Evaluate at a concrete batch size (B >= 1)."""
        b = max(1, int(batch_size))
        return int(round(sum(c * (b ** d)
                             for d, c in self.terms.items())))

    @property
    def degree(self) -> int:
        return max(self.terms, default=0)

    @property
    def is_const(self) -> bool:
        return self.degree == 0

    def describe(self) -> str:
        """Human form, constant term first: ``"4096 + 3136*B"``."""
        if not self.terms:
            return "0"
        parts = []
        for d in sorted(self.terms):
            c = self.terms[d]
            n = "%d" % round(c) if float(c).is_integer() else "%.6g" % c
            parts.append(n if d == 0 else
                         ("%s*B" % n if d == 1 else "%s*B^%d" % (n, d)))
        return " + ".join(parts)

    def __repr__(self):
        return "BytesPoly(%s)" % self.describe()


def format_bytes(n: float) -> str:
    for unit, scale in (("GB", 1 << 30), ("MB", 1 << 20), ("KB", 1 << 10)):
        if abs(n) >= scale:
            return "%.2f %s" % (n / scale, unit)
    return "%d B" % round(n)


def parse_bytes(text) -> int:
    """``"16G"``/``"512M"``/``"4096"`` -> bytes (K/M/G/T suffixes,
    binary multiples); ints pass through."""
    if isinstance(text, (int, float)):
        return int(text)
    s = str(text).strip().upper()
    mult = 1
    for suffix, m in (("T", 1 << 40), ("G", 1 << 30), ("M", 1 << 20),
                      ("K", 1 << 10)):
        if s.endswith(suffix + "B"):
            s, mult = s[:-2], m
            break
        if s.endswith(suffix):
            s, mult = s[:-1], m
            break
    try:
        return int(float(s) * mult)
    except ValueError:
        raise ValueError("unparseable byte count %r (use e.g. 16G, "
                         "512M, 4096)" % (text,)) from None


def device_budget() -> Optional[int]:
    """The configured device-HBM budget in bytes, or None (the memory
    lint rules and the window-tune/serving guards are all silent
    without one). ``PADDLE_TPU_DEVICE_HBM_BYTES`` takes a byte count
    with an optional K/M/G/T suffix; a malformed value fails loudly —
    a budget silently ignored would un-guard every consumer at once."""
    raw = os.environ.get("PADDLE_TPU_DEVICE_HBM_BYTES", "").strip()
    if not raw:
        return None
    n = parse_bytes(raw)
    if n <= 0:
        raise ValueError(
            "PADDLE_TPU_DEVICE_HBM_BYTES must be positive, got %r" % raw)
    return n


# --------------------------------------------------------- footprint rules
class FootprintContext:
    """What a footprint rule sees: the op plus shape/dtype lookups
    resolved through the analyzed program (inference-filled shapes).
    Rules return a workspace :class:`BytesPoly` (bytes the op needs
    BEYOND its declared inputs/outputs while it runs) or None/0."""

    def __init__(self, op, analysis: "MemoryAnalysis"):
        self.op = op
        self._an = analysis

    def input_shape(self, slot: str, idx: int = 0):
        names = self.op.inputs.get(slot) or []
        if idx >= len(names) or not names[idx]:
            return None
        return self._an.shape_of(names[idx])

    def input_dtype(self, slot: str, idx: int = 0):
        names = self.op.inputs.get(slot) or []
        if idx >= len(names) or not names[idx]:
            return None
        return self._an.dtype_of(names[idx])

    def output_shape(self, slot: str, idx: int = 0):
        names = self.op.outputs.get(slot) or []
        if idx >= len(names) or not names[idx]:
            return None
        return self._an.shape_of(names[idx])

    def input_poly(self, slot: str, idx: int = 0) -> Optional[BytesPoly]:
        shape = self.input_shape(slot, idx)
        if shape is None:
            return None
        return BytesPoly.from_dims(shape,
                                   dtype_bytes(self.input_dtype(slot, idx)
                                               or "float32", warn=False))

    def attr(self, name, default=None):
        return self.op.attrs.get(name, default)


FOOTPRINT_RULES: Dict[str, object] = {}


def register_footprint_rule(*op_types):
    """Attach a workspace-byte rule to one or more op types (the
    ``register_shape_rule`` idiom). The rule takes a
    :class:`FootprintContext` and returns a :class:`BytesPoly` (or
    None). Ops without a rule get zero workspace — their footprint is
    fully described by their declared inputs/outputs; a rule exists
    precisely for the ops known to materialize MORE than that."""

    def deco(fn):
        for t in op_types:
            FOOTPRINT_RULES[t] = fn
        return fn

    return deco


@register_footprint_rule("matmul", "matmul_v2", "mul", "bmm")
def _fp_matmul(ctx):
    """GEMM lowering may materialize a layout-transposed copy of an
    operand: budget both operands' bytes as workspace. The SUM (not
    the max of the two) keeps the workspace a true polynomial of B —
    "whichever is larger" flips with the batch size, which would make
    the estimate disagree between a symbolic-batch program and the
    same program built at a concrete batch."""
    polys = [p for p in (ctx.input_poly("X"), ctx.input_poly("Y")) if p]
    if not polys:
        return None
    return sum(polys, BytesPoly())


@register_footprint_rule("conv2d", "conv2d_transpose", "conv3d",
                         "depthwise_conv2d")
def _fp_conv(ctx):
    """Implicit-GEMM/im2col patch buffer: output spatial positions x
    (kernel window x input channels) elements."""
    xs = ctx.input_shape("Input")
    ws = ctx.input_shape("Filter")
    outs = ctx.output_shape("Output") or ctx.output_shape("Out")
    if xs is None or ws is None or outs is None or len(ws) < 3:
        return None
    kernel_window = 1
    for d in ws[2:]:
        kernel_window *= max(1, int(d))
    c_in = max(1, int(ws[1]))
    # [batch, spatial...] of the output, channels replaced by the
    # im2col row width
    dims = (outs[0],) + tuple(outs[2:])
    patch = BytesPoly.from_dims(
        dims, dtype_bytes(ctx.input_dtype("Input") or "float32",
                          warn=False))
    return patch.scaled(kernel_window * c_in)


@register_footprint_rule("fused_attention")
def _fp_attention(ctx):
    """The attention score matrix [*, Sq, Sk] — the classic
    non-streaming temp (a flash kernel streams it, but the estimate
    budgets the composed path: an upper bracket either way)."""
    qs, ks = ctx.input_shape("Q"), ctx.input_shape("K")
    if qs is None or ks is None or len(qs) < 2 or len(ks) < 2:
        return None
    dims = tuple(qs[:-1]) + (ks[-2],)
    if len(qs) == 3:   # [B, S, H*D] operands: a score matrix a head
        dims = (int(ctx.attr("n_head", 1) or 1),) + dims
    return BytesPoly.from_dims(dims, 4)


@register_footprint_rule("mla_decode")
def _fp_mla_decode(ctx):
    """The composed form's score tensor [B, H, S] (the Pallas kernel
    streams it: an upper bracket either way) beside the absorbed query
    and output [B, H, d_c + d_rope] and [B, H, d_c]."""
    qn, cs = ctx.input_shape("QNope"), ctx.input_shape("Cache")
    if qn is None or cs is None or len(qn) != 4 or len(cs) != 4:
        return None
    return BytesPoly.from_dims((qn[0], qn[2], cs[2] + 2 * cs[3]), 4)


@register_footprint_rule("mhc_pre")
def _fp_mhc_pre(ctx):
    """The composed form's normalised copy of X (the Pallas kernel keeps
    a block of it in VMEM: an upper bracket either way) beside the raw
    mappings of every row."""
    xs = ctx.input_shape("X")
    if xs is None:
        return None
    n = int(ctx.attr("n", 1) or 1)
    return BytesPoly.from_dims(tuple(xs), 4) \
        + BytesPoly.from_dims(tuple(xs[:-1]) + (2 * n * (n + 2),), 4)


@register_footprint_rule("ssm_scan")
def _fp_ssm_scan(ctx):
    """The operands regrouped and padded to whole chunks (x twice: in
    and out), and a chunk's [Q, Q] masks a head of the composed form
    (the Pallas kernel keeps one in VMEM: an upper bracket either way)."""
    xs = ctx.input_shape("X")
    if xs is None:
        return None
    H = int(ctx.attr("heads", 1) or 1)
    Q = int(ctx.attr("chunk", 128) or 128)
    return BytesPoly.from_dims(tuple(xs), 4).scaled(2) \
        + BytesPoly.from_dims((xs[0], Q, Q, H), 4)


@register_footprint_rule("ssm_update")
def _fp_ssm_update(ctx):
    """The per-lane rows and the columns the kernel reads beside the
    state: [B, G, 8, L] and [B, G, N, 8]; the state itself is updated in
    place."""
    st = ctx.input_shape("State")
    if st is None or len(st) != 4:
        return None
    return BytesPoly.from_dims((st[0], st[1], 8, st[3]), 4) \
        + BytesPoly.from_dims((st[0], st[1], st[2], 8), 4)


@register_footprint_rule("power_scan")
def _fp_power_scan(ctx):
    """The operands regrouped, turned and padded to whole chunks (q
    twice: in and out; k and v twice: both ways round), and a chunk's
    [Q, Q] scores a head (the Pallas kernel keeps them in VMEM: an upper
    bracket either way)."""
    from ..kernels.power import scan_chunk

    qs, ks = ctx.input_shape("Q"), ctx.input_shape("K")
    if qs is None or ks is None or qs[1] < 0:
        return None
    H = int(ctx.attr("heads", 1) or 1)
    Q = scan_chunk(qs[1])
    return BytesPoly.from_dims(tuple(qs), 4).scaled(2) \
        + BytesPoly.from_dims(tuple(ks), 4).scaled(4) \
        + BytesPoly.from_dims((qs[0], Q, Q, H), 4)


@register_footprint_rule("power_update")
def _fp_power_update(ctx):
    """The token's rows and columns the kernel reads beside the state
    ([B, G, 8, D] and [B, G, D, 8]) and the numerators and partial
    normalisers it leaves ([B, G, 8, D] each); state and normaliser are
    updated in place."""
    st = ctx.input_shape("State")
    if st is None or len(st) != 4:
        return None
    return BytesPoly.from_dims((st[0], st[1], 8, st[3]), 4).scaled(4)


@register_footprint_rule("delta_scan")
def _fp_delta_scan(ctx):
    """The operands regrouped by key head and padded to whole chunks (q
    once, k twice: both ways round; v and ``Y`` once each), and a chunk's
    [Q, Q] matrices a head (the Pallas kernel keeps them in VMEM: an
    upper bracket either way)."""
    from ..kernels.delta import scan_chunk

    qs, vs = ctx.input_shape("Q"), ctx.input_shape("V")
    if qs is None or vs is None or qs[1] < 0:
        return None
    Hv = int(ctx.attr("v_heads", 1) or 1)
    Q = scan_chunk(qs[1])
    return BytesPoly.from_dims(tuple(qs), 4).scaled(3) \
        + BytesPoly.from_dims(tuple(vs), 4).scaled(2) \
        + BytesPoly.from_dims((qs[0], Q, Q, Hv), 4).scaled(4)


@register_footprint_rule("delta_update")
def _fp_delta_update(ctx):
    """The token's rows ([B, 3 Hv, Dv]) and columns ([B, Dk, 128]) the
    kernel reads beside the state; the state is updated in place."""
    st = ctx.input_shape("State")
    if st is None or len(st) != 4:
        return None
    return BytesPoly.from_dims((st[0], 3 * st[1], st[3]), 4) \
        + BytesPoly.from_dims((st[0], st[2], 128), 4)


@register_footprint_rule("mamba_scan")
def _fp_mamba_scan(ctx):
    """The operands padded to whole blocks and regrouped into channel
    tiles (``u``, ``dt`` and ``Y`` once each), ``B`` and ``C`` turned
    positions-last; ``exp(dt A)`` — ``[T, C, N]`` — is formed in the
    kernel's registers and takes nothing (the composed form holds a block
    of it: ``[block, C, N]`` three times over)."""
    from ..kernels.mamba import scan_block

    xs, al = ctx.input_shape("X"), ctx.input_shape("ALog")
    if xs is None or al is None or xs[1] < 0 or len(al) != 2:
        return None
    return BytesPoly.from_dims(tuple(xs), 4).scaled(3) \
        + BytesPoly.from_dims((xs[0], xs[1], 2 * al[1]), 4) \
        + BytesPoly.from_dims((xs[0], scan_block(xs[1]), al[0], al[1]),
                              4).scaled(3)


@register_footprint_rule("mamba_update")
def _fp_mamba_update(ctx):
    """The token's rows ([B, 8, C]) and columns ([B, N, 8]) and ``A``
    turned ([N, C]) that the kernel reads beside the state; the state is
    updated in place."""
    st = ctx.input_shape("State")
    if st is None or len(st) != 4:
        return None
    return BytesPoly.from_dims((st[0], 8, st[3]), 4) \
        + BytesPoly.from_dims((st[0], st[2], 8), 4) \
        + BytesPoly.from_dims((st[2], st[3]), 4)


@register_footprint_rule("moe_ffn")
def _fp_moe_ffn(ctx):
    """The sorted pairs: top_k copies of the tokens at width D (the
    gathered rows and the down-projection's output before the gated
    sum) and at the experts' width F (the hidden rows; swiglu's two
    up-projections fuse in the kernel and count once more for the
    composed form). The stacked [E, D, F] parameters are persistable
    state the baseline already holds."""
    xs, w1 = ctx.input_shape("X"), ctx.input_shape("W1")
    if xs is None or w1 is None or len(w1) != 3 or len(xs) < 1:
        return None
    k = int(ctx.op.attrs.get("top_k", 1) or 1)
    tokens = tuple(xs[:-1])
    D, F = int(w1[1]), int(w1[2])
    per_row = 2 * D + (3 if ctx.op.inputs.get("W1V") else 2) * F
    return BytesPoly.from_dims(tokens + (k * per_row,), 4)


@register_footprint_rule("softmax", "log_softmax",
                         "softmax_with_cross_entropy", "cross_entropy")
def _fp_softmax(ctx):
    """One input-sized temp (the exp/normalizer buffer)."""
    return ctx.input_poly("X") or ctx.input_poly("Logits")


# ------------------------------------------------------------------ engine
class _TensorInfo:
    __slots__ = ("name", "kind", "poly", "shape", "dtype", "provenance")

    def __init__(self, name, kind, poly, shape, dtype, provenance):
        self.name = name
        self.kind = kind          # "persistable" | "feed" | "activation"
        self.poly = poly          # BytesPoly or None (unknown shape)
        self.shape = shape
        self.dtype = dtype
        self.provenance = provenance  # (name_scope, def_site) or None


class MemoryAnalysis:
    """Liveness-based peak-HBM estimate of one program's global block.

    Walks the block once over a (shared or private) :class:`Dataflow`,
    classifies every name as persistable / feed / activation, assigns
    each a :class:`BytesPoly`, and builds a per-op live-byte timeline:
    baseline (persistables + K x feeds) plus the activations whose
    liveness interval covers the op plus the op's registered workspace.
    Queries evaluate the polynomial timeline at a concrete batch size;
    the analysis itself is batch-size-free.

    ``steps_per_call`` (default 1) is the whole-loop-compilation window
    K: the pipelined loop stacks K host batches into one device-resident
    window, so feed bytes multiply by K (``core/pipeline.py``); queries
    take an override so window-tune can score every candidate K from
    ONE analysis. ``scope`` resolves undeclared scope-backed names as
    persistable write-back state, exactly like the executor's
    ``analyze_block``.
    """

    def __init__(self, program: Program, fetch_names: Sequence[str] = (),
                 scope=None, steps_per_call: int = 1, infer: bool = True,
                 dataflow: Optional[Dataflow] = None, site: str = "api"):
        import time

        from ..observe.families import (ANALYSIS_MEMORY_PROGRAMS,
                                        ANALYSIS_MEMORY_SECONDS)

        t0 = time.perf_counter()
        self.program = program
        self.scope = scope
        self.steps_per_call = max(1, int(steps_per_call))
        if infer:
            from .infer import infer_program_shapes

            infer_program_shapes(program, findings=[], fill=True)
        self.df = dataflow if dataflow is not None else Dataflow(
            program, fetch_names=fetch_names, scope=scope)
        self.fetch = set(fetch_names or ())
        self.tensors: Dict[str, _TensorInfo] = {}
        self.unknown: List[str] = []  # names with unknowable bytes
        self._classify()
        self._build_timeline()
        ANALYSIS_MEMORY_PROGRAMS.labels(site=site).inc()
        ANALYSIS_MEMORY_SECONDS.observe(time.perf_counter() - t0)

    # ---------------------------------------------------------- facts
    def shape_of(self, name: str):
        v = self.df.var_of(name)
        return None if v is None else v.shape

    def dtype_of(self, name: str):
        v = self.df.var_of(name)
        return None if v is None else v.dtype

    def _provenance(self, name: str):
        """(name_scope, def_site) of the op that defines ``name`` —
        its first writer, else its first reader (a parameter's
        provenance is the layer that consumes it)."""
        pos = self.df.write_positions(name) or self.df.read_positions(name)
        if not pos:
            return None
        op = self.df.ops[pos[0]]
        scope_name = getattr(op, "name_scope", "") or ""
        site = getattr(op, "def_site", None)
        if not scope_name and site is None:
            return None
        return (scope_name, site)

    def _classify(self) -> None:
        df = self.df
        names = set()
        for i in range(len(df.ops)):
            names.update(df.reads[i])
            names.update(df.writes[i])
        # declared-but-untouched persistables are still resident (the
        # dead-persistable lint rule's subject): walk declarations too
        for block in self.program.blocks:
            for n, v in block.vars.items():
                if v.persistable or v.is_data:
                    names.add(n)
        for name in sorted(names):
            if not name:
                continue
            v = df.var_of(name)
            if v is not None and v.persistable:
                kind = "persistable"
            elif v is not None and v.is_data:
                kind = "feed"
            elif v is None and self.scope is not None \
                    and self.scope.has_var(name):
                kind = "persistable"  # scope-backed write-back state
            else:
                kind = "activation"
            shape = v.shape if v is not None else None
            dtype = v.dtype if v is not None else None
            if shape is None and kind == "persistable" \
                    and self.scope is not None \
                    and self.scope.has_var(name):
                val = self.scope.find_var(name)
                shape = tuple(getattr(val, "shape", ()) or ())
                dtype = str(getattr(val, "dtype", "float32"))
            poly = BytesPoly.from_shape(shape, dtype or "float32")
            if poly is None:
                self.unknown.append(name)
            self.tensors[name] = _TensorInfo(
                name, kind, poly, shape, dtype, self._provenance(name))

    def _live_interval(self, name: str) -> Tuple[int, int]:
        """[start, end] op positions an activation occupies memory:
        first definition (0 for externally-supplied values) to last
        read; fetched or structurally pinned names survive to the
        block's end."""
        df = self.df
        writes = df.write_positions(name)
        reads = df.read_positions(name)
        start = writes[0] if writes else 0
        end = max(reads[-1] if reads else start,
                  writes[-1] if writes else start)
        if name in self.fetch or name in df.pinned:
            end = max(end, len(df.ops) - 1)
        return start, end

    def _build_timeline(self) -> None:
        df = self.df
        n_ops = len(df.ops)
        zero = BytesPoly()
        self.persist_poly = zero
        self.feed_poly = zero  # ONE window's worth (pre-K)
        for t in self.tensors.values():
            if t.poly is None:
                continue
            if t.kind == "persistable":
                self.persist_poly = self.persist_poly + t.poly
            elif t.kind == "feed":
                self.feed_poly = self.feed_poly + t.poly
        # activation liveness via a delta sweep
        delta: List[BytesPoly] = [BytesPoly() for _ in range(n_ops + 1)]
        self._live_at: Dict[int, List[str]] = {}
        intervals: Dict[str, Tuple[int, int]] = {}
        for t in self.tensors.values():
            if t.kind != "activation" or t.poly is None:
                continue
            start, end = self._live_interval(t.name)
            if n_ops == 0:
                continue
            start = min(max(start, 0), n_ops - 1)
            end = min(max(end, start), n_ops - 1)
            intervals[t.name] = (start, end)
            delta[start] = delta[start] + t.poly
            delta[end + 1] = delta[end + 1] - t.poly
        self._intervals = intervals
        self.activation_polys: List[BytesPoly] = []
        self.workspace_polys: List[BytesPoly] = []
        running = BytesPoly()
        for i in range(n_ops):
            running = running + delta[i]
            self.activation_polys.append(running)
            rule = FOOTPRINT_RULES.get(df.ops[i].type)
            ws = rule(FootprintContext(df.ops[i], self)) if rule else None
            self.workspace_polys.append(ws if ws is not None
                                        else BytesPoly())

    # --------------------------------------------------------- queries
    def op_bytes_poly(self, pos: int,
                      steps_per_call: Optional[int] = None) -> BytesPoly:
        """Total live bytes at op ``pos`` as a polynomial of B."""
        k = self.steps_per_call if steps_per_call is None \
            else max(1, int(steps_per_call))
        return (self.persist_poly + self.feed_poly.scaled(k)
                + self.activation_polys[pos] + self.workspace_polys[pos])

    def peak(self, batch_size: int = 1,
             steps_per_call: Optional[int] = None
             ) -> Tuple[int, int]:
        """(peak bytes, op position) at a concrete batch size; position
        is -1 for an op-less program (baseline only)."""
        k = self.steps_per_call if steps_per_call is None \
            else max(1, int(steps_per_call))
        base = (self.persist_poly + self.feed_poly.scaled(k)).at(batch_size)
        best, pos = base, -1
        for i in range(len(self.df.ops)):
            n = self.op_bytes_poly(i, steps_per_call=k).at(batch_size)
            if n > best:
                best, pos = n, i
        return best, pos

    def peak_bytes(self, batch_size: int = 1,
                   steps_per_call: Optional[int] = None) -> int:
        return self.peak(batch_size, steps_per_call=steps_per_call)[0]

    def peak_op(self, batch_size: int = 1):
        """The op at the peak (None for an op-less program)."""
        pos = self.peak(batch_size)[1]
        return None if pos < 0 else self.df.ops[pos]

    def peak_poly(self, batch_size: int = 1,
                  steps_per_call: Optional[int] = None) -> BytesPoly:
        """The PEAK OP's byte polynomial — the linear(ish) batch form
        the max-safe-batch answer and the CLI's closed form quote.
        (The peak op can shift with B; this is the form AT the peak op
        for the given batch size.)"""
        pos = self.peak(batch_size, steps_per_call=steps_per_call)[1]
        k = self.steps_per_call if steps_per_call is None \
            else max(1, int(steps_per_call))
        if pos < 0:
            return self.persist_poly + self.feed_poly.scaled(k)
        return self.op_bytes_poly(pos, steps_per_call=k)

    def live_tensors(self, pos: int, batch_size: int = 1,
                     steps_per_call: Optional[int] = None,
                     top_k: Optional[int] = None) -> List[dict]:
        """The tensors resident at op ``pos`` (persistables + feeds +
        live activations), largest first, each with kind, bytes at
        ``batch_size``, and PR 5 provenance."""
        k = self.steps_per_call if steps_per_call is None \
            else max(1, int(steps_per_call))
        out = []
        for t in self.tensors.values():
            if t.poly is None:
                continue
            if t.kind == "activation":
                iv = self._intervals.get(t.name)
                if iv is None or not iv[0] <= pos <= iv[1]:
                    continue
                n = t.poly.at(batch_size)
            elif t.kind == "feed":
                n = t.poly.scaled(k).at(batch_size)
            else:
                n = t.poly.at(batch_size)
            out.append({"name": t.name, "kind": t.kind, "bytes": n,
                        "shape": t.shape, "dtype": t.dtype,
                        "name_scope": (t.provenance or ("", None))[0],
                        "def_site": (t.provenance or ("", None))[1]})
        out.sort(key=lambda d: (-d["bytes"], d["name"]))
        return out[:top_k] if top_k else out

    def top_tensors(self, batch_size: int = 1, k: int = 5,
                    steps_per_call: Optional[int] = None) -> List[dict]:
        """Top-k live tensors AT THE PEAK op."""
        pos = self.peak(batch_size, steps_per_call=steps_per_call)[1]
        return self.live_tensors(max(pos, 0), batch_size,
                                 steps_per_call=steps_per_call, top_k=k)

    def breakdown(self, batch_size: int = 1,
                  steps_per_call: Optional[int] = None) -> Dict[str, int]:
        """{persistable, feed, activation_peak, workspace_peak, peak}
        bytes at ``batch_size`` (activation/workspace at the peak op)."""
        k = self.steps_per_call if steps_per_call is None \
            else max(1, int(steps_per_call))
        peak, pos = self.peak(batch_size, steps_per_call=k)
        return {
            "persistable": self.persist_poly.at(batch_size),
            "feed": self.feed_poly.scaled(k).at(batch_size),
            "activation_peak": (self.activation_polys[pos].at(batch_size)
                                if pos >= 0 else 0),
            "workspace_peak": (self.workspace_polys[pos].at(batch_size)
                               if pos >= 0 else 0),
            "peak": peak,
        }

    def timeline(self, batch_size: int = 1,
                 steps_per_call: Optional[int] = None) -> List[dict]:
        """Per-op live-byte timeline at ``batch_size``."""
        k = self.steps_per_call if steps_per_call is None \
            else max(1, int(steps_per_call))
        out = []
        for i, op in enumerate(self.df.ops):
            out.append({"pos": i, "op_type": op.type,
                        "live_bytes": self.op_bytes_poly(
                            i, steps_per_call=k).at(batch_size)})
        return out

    def batch_dependent(self) -> bool:
        """Does the peak depend on the batch size at all? (False for a
        startup program whose every shape is concrete.)"""
        if not self.feed_poly.is_const:
            return True
        return any(not (a + w).is_const for a, w in
                   zip(self.activation_polys, self.workspace_polys))

    def max_safe_batch(self, budget: int,
                       steps_per_call: Optional[int] = None,
                       cap: int = 1 << 22) -> Optional[int]:
        """Largest B with ``peak(B) <= budget``: 0 when even B=1 does
        not fit, None when the peak never reaches the budget below
        ``cap`` (batch-independent or effectively unbounded). Monotone
        because every coefficient is non-negative, so a plain binary
        search solves the closed form."""
        if self.peak_bytes(1, steps_per_call=steps_per_call) > budget:
            return 0
        if self.peak_bytes(cap, steps_per_call=steps_per_call) <= budget:
            return None
        lo, hi = 1, cap  # peak(lo) fits, peak(hi) does not
        while hi - lo > 1:
            mid = (lo + hi) // 2
            if self.peak_bytes(mid,
                               steps_per_call=steps_per_call) <= budget:
                lo = mid
            else:
                hi = mid
        return lo


def estimate_peak_bytes(program: Program, batch_size: int = 1,
                        fetch_names: Sequence[str] = (), scope=None,
                        steps_per_call: int = 1,
                        site: str = "api") -> int:
    """One-call convenience: the static peak-HBM estimate in bytes."""
    return MemoryAnalysis(program, fetch_names=fetch_names, scope=scope,
                          steps_per_call=steps_per_call,
                          site=site).peak_bytes(batch_size)


# --------------------------------------------------------- serving helper
def decode_cache_bytes(cfg: dict, batch: int, max_len: int,
                       dtype: str = "float32") -> int:
    """Bytes of a decode lane's ``2L`` KV-cache slab tensors: per layer
    one K and one V slab of ``[batch, n_kv, max_len, head_dim]`` — the
    serving engine's dominant resident allocation (models/gpt.py
    build_decode_step). The closed form the engine's admission guard
    and capacity planning share."""
    n_head = int(cfg.get("n_head", 1))
    n_kv = int(cfg.get("n_kv_head", n_head) or n_head)
    d_model = int(cfg.get("d_model", 0))
    head_dim = d_model // max(1, n_head)
    n_layer = int(cfg.get("n_layer", 0))
    return (2 * n_layer * int(batch) * n_kv * int(max_len) * head_dim
            * dtype_bytes(dtype, warn=False))
