"""Graph-program IR: Program / Block / Operator / Variable.

TPU-native analog of the reference's ProgramDesc stack
(/root/reference/paddle/fluid/framework/framework.proto:43-187 and
/root/reference/python/paddle/fluid/framework.py: Program:2349, Block:1056,
Operator:599, Variable:242).

Design difference from the reference: the desc layer here is *the* program
representation (no separate C++ desc mirror); the Executor lowers a whole
Block to a single XLA computation instead of interpreting op-by-op, so ops
never carry kernels — only lowering rules registered in core.registry.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import sys
import threading
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

__all__ = [
    "Variable",
    "Parameter",
    "Operator",
    "Block",
    "Program",
    "op_effects",
    "default_main_program",
    "default_startup_program",
    "program_guard",
    "unique_name",
    "SCOPE_CLASSES",
    "grad_var_name",
    "switch_main_program",
    "switch_startup_program",
]

GRAD_SUFFIX = "@GRAD"


def grad_var_name(name: str) -> str:
    return name + GRAD_SUFFIX


class UniqueNameGenerator:
    """Analog of python/paddle/fluid/unique_name.py."""

    def __init__(self):
        self._ids: Dict[str, int] = {}
        self._lock = threading.Lock()

    def generate(self, prefix: str = "tmp") -> str:
        with self._lock:
            idx = self._ids.get(prefix, 0)
            self._ids[prefix] = idx + 1
        return "%s_%d" % (prefix, idx)

    @contextlib.contextmanager
    def guard(self):
        old = self._ids
        self._ids = {}
        try:
            yield
        finally:
            self._ids = old


unique_name = UniqueNameGenerator()


_NAME_SCOPE_STACK = threading.local()


@contextlib.contextmanager
def name_scope(prefix=None):
    """Debug-name nesting for ops (reference framework.py name_scope):
    layers created inside get `scope1/scope2/...` prefixed unique names.
    Purely cosmetic — grouping for visualization/profiling."""
    stack = getattr(_NAME_SCOPE_STACK, "stack", None)
    if stack is None:
        stack = _NAME_SCOPE_STACK.stack = []
    stack.append(str(prefix or "scope"))
    try:
        yield
    finally:
        stack.pop()


# The classes of model sub-block a ``name_scope`` may declare, for every
# layer that builds, lowers or reads one (models/, ops/moe_ops.py's
# ``moe.router``, layers/parallel_ext.py's ``moe.shared``,
# observe/device_names.py): an op built under ``L3/attn.core`` is layer
# 3's attention core, and the class of a scope path is the LAST of its
# components that is one of these, so a lowering may refine its op's
# class (``moe.router`` inside the ``moe_ffn`` op under ``moe.experts``).
# core/lowering.py::op_scope carries the path to the device's operations.
SCOPE_CLASSES = ("embed", "attn.qkv", "attn.core", "attn.out", "ffn",
                 "moe.router", "moe.experts", "moe.shared", "mixer", "conv",
                 "mhc", "norm", "head", "loss", "opt")


def current_name_scope() -> str:
    stack = getattr(_NAME_SCOPE_STACK, "stack", None) or []
    return "/".join(stack)


def _normalize_dtype(dtype) -> str:
    if dtype is None:
        return "float32"
    if isinstance(dtype, str):
        if dtype == "bool":
            return "bool"
        return str(np.dtype(dtype))
    return str(np.dtype(dtype))


class Variable:
    """A named, typed tensor slot in a Block (reference framework.py:242).

    Shape may contain -1 for data vars (batch dim); concrete shapes come from
    feeds at compile time. `persistable` vars live in the Scope across steps
    (parameters, optimizer state, RNG state); temporaries are SSA values
    inside the lowered computation.
    """

    def __init__(
        self,
        block: "Block",
        name: str,
        shape: Optional[Sequence[int]] = None,
        dtype=None,
        persistable: bool = False,
        stop_gradient: bool = False,
        is_data: bool = False,
        lod_level: int = 0,
        initializer=None,
    ):
        self.block = block
        self.name = name
        self.shape = tuple(int(s) for s in shape) if shape is not None else None
        self.dtype = _normalize_dtype(dtype)
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.is_data = is_data
        self.lod_level = lod_level
        self.initializer = initializer

    # -- math operator sugar (math_op_patch.py analog), filled in by layers --
    def _binary(self, other, op, reverse=False):
        from ..layers import math_op  # lazy: avoids import cycle

        return math_op(self, other, op, reverse)

    def __add__(self, o):
        return self._binary(o, "elementwise_add")

    def __radd__(self, o):
        return self._binary(o, "elementwise_add", True)

    def __sub__(self, o):
        return self._binary(o, "elementwise_sub")

    def __rsub__(self, o):
        return self._binary(o, "elementwise_sub", True)

    def __mul__(self, o):
        return self._binary(o, "elementwise_mul")

    def __rmul__(self, o):
        return self._binary(o, "elementwise_mul", True)

    def __truediv__(self, o):
        return self._binary(o, "elementwise_div")

    def __rtruediv__(self, o):
        return self._binary(o, "elementwise_div", True)

    def __pow__(self, o):
        return self._binary(o, "elementwise_pow")

    def __neg__(self):
        from ..layers import scale

        return scale(self, scale=-1.0)

    def __lt__(self, o):
        return self._binary(o, "less_than")

    def __le__(self, o):
        return self._binary(o, "less_equal")

    def __gt__(self, o):
        return self._binary(o, "greater_than")

    def __ge__(self, o):
        return self._binary(o, "greater_equal")

    def astype(self, dtype):
        from ..layers import cast

        return cast(self, dtype)

    @property
    def grad_name(self) -> str:
        return grad_var_name(self.name)

    def __repr__(self):
        return "Variable(%s, shape=%s, dtype=%s%s)" % (
            self.name,
            self.shape,
            self.dtype,
            ", persistable" if self.persistable else "",
        )

    def to_dict(self):
        return {
            "name": self.name,
            "shape": list(self.shape) if self.shape is not None else None,
            "dtype": self.dtype,
            "persistable": self.persistable,
            "stop_gradient": self.stop_gradient,
            "is_data": self.is_data,
            "lod_level": self.lod_level,
        }


class Parameter(Variable):
    """Trainable variable (reference framework.py:2982): persistable, with
    optimizer-facing attributes."""

    def __init__(self, block, name, shape, dtype, **kw):
        self.trainable = kw.pop("trainable", True)
        self.regularizer = kw.pop("regularizer", None)
        self.gradient_clip_attr = kw.pop("gradient_clip_attr", None)
        # reference ParamAttr defaults do_model_average=True (params join
        # ModelAverage unless explicitly opted out)
        self.do_model_average = kw.pop("do_model_average", True)
        kw.setdefault("persistable", True)
        kw.setdefault("stop_gradient", not self.trainable)
        super().__init__(block, name, shape, dtype, **kw)


# ---- op definition-site provenance (for analysis.ProgramVerifyError) ----
# Frames inside the framework's op-appending machinery are skipped when
# recording where an op was built, so the verifier reports the line of the
# model/test code (or models/ builder) that called the layer — the closest
# analog of the reference's per-op InferShape failing AT the op that built
# it. PADDLE_TPU_PROVENANCE=0 disables the (cheap) per-op frame walk.
_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_MACHINERY_PREFIXES = (
    os.path.join(_PKG_ROOT, "core") + os.sep,
    os.path.join(_PKG_ROOT, "layers") + os.sep,
    # dygraph capture: ops recorded by imperative/capture.py must carry
    # the USER's eager line, not the trace_op/record_op plumbing
    os.path.join(_PKG_ROOT, "imperative") + os.sep,
)
_MACHINERY_FILES = frozenset(
    os.path.join(_PKG_ROOT, f)
    for f in ("layer_helper.py", "nets.py", "optimizer.py", "regularizer.py",
              "clip.py", "backward.py", "initializer.py")
)
_PROVENANCE = os.environ.get(
    "PADDLE_TPU_PROVENANCE", "1").lower() not in ("0", "false", "off")


def _op_def_site() -> Optional[str]:
    """file:line of the nearest stack frame OUTSIDE the layer machinery."""
    try:
        f = sys._getframe(2)  # skip _op_def_site and Operator.__init__
    except ValueError:  # pragma: no cover - interpreter without caller
        return None
    fallback = None
    depth = 0
    while f is not None and depth < 32:
        fn = f.f_code.co_filename
        if fallback is None:
            fallback = "%s:%d" % (fn, f.f_lineno)
        if not (fn.startswith(_MACHINERY_PREFIXES) or fn in _MACHINERY_FILES):
            return "%s:%d" % (fn, f.f_lineno)
        f = f.f_back
        depth += 1
    return fallback


class Operator:
    """One op node: type + named input/output slots + attrs
    (reference framework.py:599 / OpDesc in framework.proto:43)."""

    def __init__(
        self,
        block: "Block",
        type: str,
        inputs: Optional[Dict[str, Any]] = None,
        outputs: Optional[Dict[str, Any]] = None,
        attrs: Optional[Dict[str, Any]] = None,
    ):
        self.block = block
        self.type = type
        self.inputs: Dict[str, List[str]] = _slot_names(inputs)
        self.outputs: Dict[str, List[str]] = _slot_names(outputs)
        self.attrs: Dict[str, Any] = dict(attrs or {})
        # ops built under Program.op_role_guard inherit that role (the
        # reference threads op_role the same way, framework.py op_role attr)
        role = getattr(block.program, "_op_role", None)
        if role and role != "forward":
            self.attrs.setdefault("__op_role__", role)
        self.name_scope = current_name_scope()
        self.def_site = _op_def_site() if _PROVENANCE else None

    def input_names(self) -> List[str]:
        return [n for ns in self.inputs.values() for n in ns if n]

    def output_names(self) -> List[str]:
        return [n for ns in self.outputs.values() for n in ns if n]

    def input(self, slot: str) -> List[str]:
        return self.inputs.get(slot, [])

    def output(self, slot: str) -> List[str]:
        return self.outputs.get(slot, [])

    def __repr__(self):
        return "Op(%s, in=%s, out=%s)" % (self.type, self.inputs, self.outputs)

    def to_dict(self):
        return {
            "type": self.type,
            "inputs": self.inputs,
            "outputs": self.outputs,
            "attrs": {
                k: v for k, v in self.attrs.items() if _jsonable(v)
            },
        }


def _jsonable(v):
    """True iff json.dump can round-trip v: scalars, and containers of
    jsonable values (grad ops carry dict attrs like __fwd_in_slots__;
    py_func-style ops carry callables that must be dropped even when
    nested in a list)."""
    if isinstance(v, dict):
        return all(isinstance(k, str) and _jsonable(x) for k, x in v.items())
    if isinstance(v, (list, tuple)):
        return all(_jsonable(x) for x in v)
    return isinstance(v, (int, float, str, bool, type(None)))


def _slot_names(slots) -> Dict[str, List[str]]:
    out: Dict[str, List[str]] = {}
    if not slots:
        return out
    for slot, vs in slots.items():
        if vs is None:
            out[slot] = []
            continue
        if not isinstance(vs, (list, tuple)):
            vs = [vs]
        out[slot] = [v.name if isinstance(v, Variable) else v for v in vs]
    return out


class Block:
    """An ordered list of ops + a var table (reference framework.py:1056 /
    BlockDesc framework.proto:171). Sub-blocks back control-flow ops."""

    def __init__(self, program: "Program", idx: int, parent_idx: int = -1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars: Dict[str, Variable] = {}
        self.ops: List[Operator] = []

    # ---- vars ----
    def create_var(self, name=None, **kw) -> Variable:
        if name is None:
            name = unique_name.generate("tmp")
        if name in self.vars:
            return self.vars[name]
        v = Variable(self, name, **kw)
        self.vars[name] = v
        self.program._bump()
        return v

    def create_parameter(self, name=None, shape=None, dtype="float32", **kw) -> Parameter:
        if name is None:
            name = unique_name.generate("param")
        p = Parameter(self, name, shape, dtype, **kw)
        self.vars[name] = p
        self.program._bump()
        return p

    def var(self, name: str) -> Variable:
        v = self._find_var_recursive(name)
        if v is None:
            raise KeyError("Variable %r not found in block %d" % (name, self.idx))
        return v

    def has_var(self, name: str) -> bool:
        return self._find_var_recursive(name) is not None

    def _find_var_recursive(self, name: str) -> Optional[Variable]:
        blk: Optional[Block] = self
        while blk is not None:
            if name in blk.vars:
                return blk.vars[name]
            blk = self.program.block(blk.parent_idx) if blk.parent_idx >= 0 else None
        return None

    def all_parameters(self) -> List[Parameter]:
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    # ---- ops ----
    def append_op(self, type: str, inputs=None, outputs=None, attrs=None) -> Operator:
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.append(op)
        self.program._bump()
        return op

    def prepend_op(self, type: str, inputs=None, outputs=None, attrs=None) -> Operator:
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.insert(0, op)
        self.program._bump()
        return op

    def insert_op(self, index: int, type: str, inputs=None, outputs=None, attrs=None) -> Operator:
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.insert(index, op)
        self.program._bump()
        return op

    @property
    def parent_block(self) -> Optional["Block"]:
        if self.parent_idx < 0:
            return None
        return self.program.block(self.parent_idx)

    def to_dict(self):
        return {
            "idx": self.idx,
            "parent_idx": self.parent_idx,
            "vars": {n: v.to_dict() for n, v in self.vars.items()},
            "ops": [op.to_dict() for op in self.ops],
        }


class Program:
    """The whole program: a list of Blocks (reference framework.py:2349 /
    ProgramDesc framework.proto:184). block 0 is the global block."""

    _next_serial = itertools.count()

    def __init__(self):
        self.blocks: List[Block] = [Block(self, 0)]
        self.current_block_idx = 0
        self.random_seed: Optional[int] = None
        # process-unique identity for compile caches: id() can be reused
        # after GC, aliasing a stale compiled plan to a new Program
        self._serial = next(Program._next_serial)
        self._version = 0  # bumped on any mutation; keys the compile cache
        self._op_role = "forward"
        self._is_distributed = False
        self.amp = False  # bf16 compute policy (core/amp.py); set via set_amp
        self.grad_accum_steps = 1  # microbatch scan count (set_gradient_accumulation)
        # bitwise-parity execution mode (imperative capture sets this):
        # the executor skips the fusing pass pipeline and runs the
        # lowered step UNJITTED — the same per-primitive dispatch eager
        # mode uses — so replaying the program reproduces the eager
        # sequence bit for bit (whole-graph XLA compilation contracts
        # mul+add into fma across op boundaries and cannot be held back)
        self.exact_numerics = False

    # ---- mutation tracking ----
    def _bump(self):
        self._version += 1

    def op_role_guard(self, role: str):
        """Context manager: ops appended inside get __op_role__=`role`
        (used by LR schedulers and apply-side builders so the gradient-
        accumulation partition can tell update logic from compute)."""
        import contextlib

        @contextlib.contextmanager
        def _guard():
            prev = self._op_role
            self._op_role = role
            try:
                yield
            finally:
                self._op_role = prev

        return _guard()

    def set_gradient_accumulation(self, num_microbatches: int) -> "Program":
        """Split each fed batch into `num_microbatches` slices, run
        forward+backward per slice under an in-step lax.scan, average the
        gradients, and apply the optimizer once — the TPU-native analog of
        the reference's multi_batch_merge pass
        (/root/reference/paddle/fluid/framework/ir/multi_batch_merge_pass.cc).
        The fed batch's leading dim must be divisible by num_microbatches."""
        k = int(num_microbatches)
        if k < 1:
            raise ValueError("num_microbatches must be >= 1, got %d" % k)
        if getattr(self, "grad_accum_steps", 1) != k:
            self.grad_accum_steps = k
            self._bump()
        return self

    def set_amp(self, enabled: bool = True) -> "Program":
        """Enable bfloat16 mixed-precision lowering for this program (f32
        master weights stay in the Scope; see core/amp.py). Returns self."""
        if self.amp != bool(enabled):
            self.amp = bool(enabled)
            self._bump()
        return self

    @property
    def version(self) -> int:
        return self._version

    # ---- block management ----
    def global_block(self) -> Block:
        return self.blocks[0]

    def current_block(self) -> Block:
        return self.blocks[self.current_block_idx]

    def block(self, idx: int) -> Block:
        return self.blocks[idx]

    def create_block(self, parent_idx: Optional[int] = None) -> Block:
        parent = self.current_block_idx if parent_idx is None else parent_idx
        b = Block(self, len(self.blocks), parent)
        self.blocks.append(b)
        self.current_block_idx = b.idx
        self._bump()
        return b

    def rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    # ---- cloning / pruning ----
    def clone(self, for_test: bool = False) -> "Program":
        """Structural deep-copy. With for_test=True, switch train-mode attrs
        off (dropout/batch_norm is_test), matching reference Program.clone."""
        import copy

        p = Program()
        p.random_seed = self.random_seed
        p.amp = self.amp
        p.grad_accum_steps = self.grad_accum_steps
        p.exact_numerics = self.exact_numerics
        p.blocks = []
        for b in self.blocks:
            nb = Block(p, b.idx, b.parent_idx)
            for name, v in b.vars.items():
                kw = dict(
                    shape=v.shape,
                    dtype=v.dtype,
                    persistable=v.persistable,
                    stop_gradient=v.stop_gradient,
                    is_data=v.is_data,
                    lod_level=v.lod_level,
                )
                if isinstance(v, Parameter):
                    nv = Parameter(nb, name, v.shape, v.dtype, trainable=v.trainable,
                                   persistable=v.persistable)
                else:
                    nv = Variable(nb, name, **kw)
                nb.vars[name] = nv
            for op in b.ops:
                attrs = copy.deepcopy(op.attrs)
                if for_test and "is_test" in attrs:
                    attrs["is_test"] = True
                if for_test and op.type == "dropout":
                    attrs["is_test"] = True
                nop = Operator(nb, op.type, None, None, attrs)
                nop.inputs = {k: list(v) for k, v in op.inputs.items()}
                nop.outputs = {k: list(v) for k, v in op.outputs.items()}
                # keep the ORIGINAL build site through clones: a verifier
                # finding on a cloned (for_test/pruned) program must point
                # at the line that built the op, not at clone()
                nop.name_scope = op.name_scope
                nop.def_site = op.def_site
                nb.ops.append(nop)
            p.blocks.append(nb)
        p.current_block_idx = 0
        return p

    def list_vars(self):
        for b in self.blocks:
            yield from b.vars.values()

    # ---- static verification (analysis/: shape inference + IR lint) ----
    def validate(self, fetch_list=None, scope=None, raise_on_error: bool = True):
        """Run the static program verifier over this program: whole-block
        shape/dtype inference (per-op rules registered on the OpDef
        ``infer_shape`` hook; inferred shapes are filled back onto
        Variables) plus the IR lint pass suite. Returns the list of
        ``analysis.Finding``s; with ``raise_on_error`` (default) raises
        ``analysis.ProgramVerifyError`` on any error-severity finding,
        carrying the offending op's type, name-scope and definition site.
        The Executor runs the same check at prepare time when
        ``PADDLE_TPU_VALIDATE=1`` (on by default under tests)."""
        from ..analysis import verify_program

        return verify_program(self, fetch_list=fetch_list, scope=scope,
                              raise_on_error=raise_on_error)

    def _prune(self, targets: Sequence[Variable]) -> "Program":
        """Backward-slice to the ops needed for `targets`
        (reference framework/prune.cc)."""
        p = self.clone()
        blk = p.global_block()
        needed = {t.name if isinstance(t, Variable) else t for t in targets}
        keep: List[Operator] = []
        for op in reversed(blk.ops):
            if any(n in needed for n in op.output_names()):
                keep.append(op)
                needed.update(op.input_names())
        blk.ops = list(reversed(keep))
        p._bump()
        return p

    def to_dict(self):
        return {
            "random_seed": self.random_seed,
            "amp": self.amp,
            "grad_accum_steps": self.grad_accum_steps,
            "blocks": [b.to_dict() for b in self.blocks],
        }

    def __str__(self):
        lines = []
        for b in self.blocks:
            lines.append("-- block %d (parent %d) --" % (b.idx, b.parent_idx))
            for op in b.ops:
                lines.append("  " + repr(op))
        return "\n".join(lines)


def op_effects(program: Program, op: Operator):
    """(reads, writes) of one op, recursing into control-flow sub-blocks
    (while_op/conditional_block carry their body's reads/writes — the
    analog of while_op.cc's input/output lists). Names bound by the op
    itself inside its body (``__sub_bound__``, e.g. the recurrent op's
    per-step inputs and pre-state slots) are not external reads.

    THE single definition of control-flow read/write semantics — shared
    by the executor's block analysis (core/executor.py analyze_block)
    and the IR lint suite (analysis/lint.py), so the two can never
    disagree on what a while/recurrent/recompute op touches. Tolerant of
    an invalid ``sub_block`` index (the lint sub-block rule reports it;
    recursion is simply skipped)."""
    reads = list(op.input_names())
    writes = list(op.output_names())
    sub_idx = op.attrs.get("sub_block")
    if isinstance(sub_idx, int) and 0 <= sub_idx < len(program.blocks):
        sub = program.block(sub_idx)
        sub_produced = set(op.attrs.get("__sub_bound__", ()))
        for sop in sub.ops:
            r, w = op_effects(program, sop)
            reads.extend(n for n in r if n not in sub_produced)
            writes.extend(w)
            sub_produced.update(w)
        cond = op.attrs.get("condition")
        if cond:
            reads.append(cond)
    return reads, writes


# ---- default program registry (framework.py:3066-3134 analog) ----
_main_program = Program()
_startup_program = Program()


def default_main_program() -> Program:
    return _main_program


def default_startup_program() -> Program:
    return _startup_program


def switch_main_program(p: Program) -> Program:
    global _main_program
    old, _main_program = _main_program, p
    return old


def switch_startup_program(p: Program) -> Program:
    global _startup_program
    old, _startup_program = _startup_program, p
    return old


@contextlib.contextmanager
def program_guard(main_program: Program, startup_program: Optional[Program] = None):
    old_main = switch_main_program(main_program)
    old_start = None
    if startup_program is not None:
        old_start = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(old_main)
        if old_start is not None:
            switch_startup_program(old_start)
