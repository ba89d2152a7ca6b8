"""Which Program op and which part of the model a device operation is.

``core/lowering.py::lower_op`` runs every op's lowering under
``jax.named_scope("<op.name_scope>/<op.type>")``, so every instruction of
a plan's compiled HLO — those inside fusions, loop bodies and branches
too — says in its ``metadata={op_name="..."}`` where the program made it.
A device profile names an operation by its HLO instruction's name and
nothing more (``fusion.123``); this module is the join between the two:

    table(plan) -> {"names": {instruction: scope path},
                    "fused": {fusion: [scope paths inside it]},
                    "inherited": [instructions placed by their user],
                    "entry": [[a program's ENTRY instructions, in the
                               order they run], ...],
                    "source": "ran" | "compiled",
                    "same_names": the executable that ran has them too,
                    "names_differ": [names only one of the two has]}

``plan`` is what an ``executor.dispatch`` span carries as ``plan``. The
scope path is the ``op_name`` with JAX's own wrappers taken off
(``jit(step)/``, ``jvp(...)``, ``transpose(...)``, ``while/body/``), cut
after the Program op's type: ``L3/attn.core/softmax``. A fusion stands
under its ROOT's path; an instruction XLA made for nobody (a weight's
sliced prefetch, a layout copy) under the path of the one that uses it.

Nothing here costs a dispatch anything. The FIRST dispatch of a plan
signature registers a thunk over the jitted function and the abstract
arguments it was handed (``register``, from ``_dispatch_guard``); nothing
is lowered or compiled until somebody asks for the table, and with
``PADDLE_TPU_TRACE=0`` nothing is registered.

The table is read from the executable that RAN (``source`` ``"ran"``):
``jitted.lower(...).compile()`` hands back that very executable, JAX's
memo of the lowering, so the names are by construction the profile's and
nothing is compiled for a table. One trap: the persistent cache's key
holds no metadata, so the executable may have been loaded from an entry
that a checkout from before the scopes compiled, and its text then names
no class. Only then (``source`` ``"compiled"``) does ``optimized_text``
compile this tree's lowering on its own, reading no entry and leaving
none behind, and the table says whether the two texts hold the same
instruction names (``same_names``, ``names_differ``): XLA names
instructions from the module's structure, not from its metadata, but the
table does not take its word for it.
"""

from __future__ import annotations

import contextlib
import functools
import re
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..core.program import SCOPE_CLASSES as CLASSES
from . import trace as _tr

__all__ = ["CLASSES", "register", "table", "tables", "optimized_text",
           "parse_hlo", "scope_path", "scope_class", "layer_of", "reset"]

_LAYER = re.compile(r"^L(\d+)(?:\.(\d+))?$")

# (plan tag, dispatch signature) -> thunk() -> (text, None or the
# instruction names only one of an own compile and the executable that
# ran has), oldest first
_THUNKS: "OrderedDict[Tuple[str, Any], Callable[[], Tuple[str, Any]]]" \
    = OrderedDict()
KEEP = 64
_TABLES: Dict[str, dict] = {}


# ------------------------------------------------------------ registering
def _abstract(a):
    """Shape, dtype and (of a committed array) sharding: what the jitted
    function was specialised on, without the buffer."""
    import jax

    sharding = a.sharding if getattr(a, "committed", False) else None
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)


def register(plan, sig, fn, args, key="optimized", within=None) -> None:
    """Note how to get the compiled text of ``plan``'s dispatch signature
    ``sig`` later: ``fn`` (the jitted function the dispatch calls) over
    the abstract ``args``, lowered inside ``within`` (the mesh engine's
    mesh). ``key`` is where the text is kept in ``plan.hlo_text``: the
    slot ``lowered_hlo`` fills for the same program. Called at a
    signature's first dispatch; does nothing with tracing off or for a
    function that is not jitted (an ``exact_numerics`` plan)."""
    if not _tr.trace_enabled() or plan.sig is None \
            or not hasattr(fn, "lower"):
        return
    import jax

    shapes = jax.tree.map(_abstract, args)

    def thunk():
        def lower():
            with within or contextlib.nullcontext():
                return fn.lower(*shapes)

        return (optimized_text(plan, key, lower),
                plan.hlo_text.get((key, "names_differ")))

    # held strongly: whoever asks does so after the run, when the engine
    # and its executor are gone. The price is the last KEEP signatures'
    # jitted functions (an evicted plan's executable among them) kept
    # alive; the oldest go first
    _THUNKS.pop((plan.sig, sig), None)
    _THUNKS[(plan.sig, sig)] = thunk
    while len(_THUNKS) > KEEP:
        gone, _ = _THUNKS.popitem(last=False)
        _TABLES.pop(gone[0], None)
    _TABLES.pop(plan.sig, None)


def _names_a_class(text: str, quoted=None) -> bool:
    """Whether an ``op_name`` of an HLO text (a ``loc("...")`` of a
    lowered module's, with ``quoted`` ``_LOC``) stands under a class."""
    return any(scope_class(scope_path(m.group(1))) is not None
               for m in (quoted or _OP_NAME).finditer(text))


def optimized_text(plan, key, lower) -> str:
    """The optimized HLO text of one of ``plan``'s programs, kept in
    ``plan.hlo_text[key]``: the ONE way to it (``lowered_hlo`` and the
    name table both come here). ``lower()`` gives the ``jax.stages
    .Lowered``, whose plain ``compile()`` returns the executable the
    dispatch made, memoized (JAX keeps the lowered module and its
    executable a traced function): its text is the answer, and nothing
    is compiled for it.

    Unless that text names no class where the lowering does: the
    executable was loaded from a persistent-cache entry that a checkout
    from before the scopes compiled. Then the text is a compile of this
    tree's lowering: with an option at its default (which keeps JAX's
    memo away) and, for that one call, the metadata in the cache's key
    (it reads no old entry) and no compile slow enough to be written (a
    second copy of every executable would push what the next process
    needs out of a small cache). The two flags are the process's: a
    compile another thread makes meanwhile looks under the other key and
    writes nothing, once. ``plan.hlo_text[(key, "names_differ")]`` then
    holds the instruction names only one of the two texts has."""
    text = plan.hlo_text.get(key)
    if text is None:
        text = lower().compile().as_text()
        if not _names_a_class(text) and _names_a_class(
                lower().as_text(debug_info=True), _LOC):
            import jax

            ran = _instruction_names(text)
            own = {"jax_compilation_cache_include_metadata_in_key": True,
                   "jax_persistent_cache_min_compile_time_secs":
                   float("inf")}
            before = {flag: getattr(jax.config, flag) for flag in own}
            for flag, value in own.items():
                jax.config.update(flag, value)
            try:
                text = lower().compile(compiler_options={
                    "xla_dump_max_hlo_modules": -1}).as_text()
            finally:
                for flag, value in before.items():
                    jax.config.update(flag, value)
            plan.hlo_text[(key, "names_differ")] = sorted(
                ran ^ _instruction_names(text))
        plan.hlo_text[key] = text
    return text


# ---------------------------------------------------------------- reading
def table(plan_sig: str) -> Optional[dict]:
    """The name table of the plan an ``executor.dispatch`` span names, or
    None for a plan that never dispatched in this process (or did with
    tracing off). A plan dispatched under several signatures (``run`` and
    a K-step scan) has several programs: an instruction name they place
    differently maps to None."""
    hit = _TABLES.get(plan_sig)
    if hit is not None:
        return hit
    thunks = [t for (tag, _sig), t in _THUNKS.items() if tag == plan_sig]
    if not thunks:
        return None
    names: Dict[str, Optional[str]] = {}
    fused: Dict[str, List[str]] = {}
    inherited, entry, differ, own = set(), [], [], False
    for thunk in thunks:
        text, not_shared = thunk()
        own = own or not_shared is not None
        differ += not_shared or []
        got_names, got_fused, got_inherited, got_entry = parse_hlo(text)
        entry.append(got_entry)
        for k, v in got_names.items():
            names[k] = v if names.get(k, v) == v else None
        for k, v in got_fused.items():
            fused[k] = sorted(set(fused.get(k, ())) | set(v))
        inherited.update(got_inherited)
    out = {"names": names, "fused": fused,
           "inherited": sorted(inherited), "entry": entry,
           "source": "compiled" if own else "ran",
           "same_names": not differ, "names_differ": differ}
    _TABLES[plan_sig] = out
    return out


def tables() -> Dict[str, Optional[dict]]:
    """Every plan that dispatched in this process (with tracing on), each
    with its table where one has been asked for, else None: listing
    compiles nothing."""
    return {tag: _TABLES.get(tag) for tag, _sig in _THUNKS}


def reset() -> None:
    """Test isolation: forget every thunk and table."""
    _THUNKS.clear()
    _TABLES.clear()


# ---------------------------------------------------------------- parsing
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_LOC = re.compile(r'loc\("([^"]*)"')
_CALLS = re.compile(r"\bfusion\(.*\bcalls=%?([\w.\-]+)")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
# what JAX itself puts on the name stack round a traced function's ops
_STRUCTURE = frozenset(["while", "body", "cond", "body_fun", "cond_fun",
                        "checkpoint", "remat", "rematted_computation",
                        "closed_call", "core_call", "custom_jvp_call",
                        "custom_vjp_call", "custom_vjp_call_jaxpr",
                        "shard_map", "pallas_call"])
_BRANCH = re.compile(r"^branch_\d+_fun$")


def _instruction_names(text: str) -> set:
    return {m.group(2) for m in map(_INSTRUCTION.match, text.splitlines())
            if m}


def _is_op_type(name: str) -> bool:
    from ..core.registry import OPS

    return name not in CLASSES and (
        name in OPS or (name.endswith("_grad") and name[:-5] in OPS))


def _unwrapped(op_name: str) -> List[str]:
    """The name stack's own components: ``jvp(L3)`` is ``L3``, and a
    traced function's name (``jit(step)``), an empty wrapper
    (``transpose(jvp())``) and JAX's control-flow words are nothing."""
    out = []
    for comp in op_name.split(";")[0].split("/"):
        m = _WRAPPED.match(comp)
        while m is not None and m.group(1) not in ("jit", "pjit"):
            comp = m.group(2)
            m = _WRAPPED.match(comp)
        if m is None and comp and comp not in _STRUCTURE \
                and not _BRANCH.match(comp):
            out.append(comp)
    return out


@functools.lru_cache(maxsize=1 << 16)     # a module repeats its op_names
def scope_path(op_name: str) -> Optional[str]:
    """``<model scope>/<op type>`` of an HLO ``op_name`` (the first of
    several XLA joined with ``;``), None where it names no Program op (a
    parameter, an instruction XLA made). A class a lowering enters of its
    own (``moe.router``) follows the op type; the primitives' names
    after it say nothing more and are dropped."""
    comps = _unwrapped(op_name)
    for i, comp in enumerate(comps):
        if _is_op_type(comp):
            inner = [c for c in comps[i + 1:] if c in CLASSES]
            return "/".join(comps[:i + 1] + inner[-1:])
    return None


def scope_class(path: Optional[str]) -> Optional[str]:
    """The declared class a scope path stands under, None for none."""
    if path:
        for comp in reversed(path.split("/")):
            if comp in CLASSES:
                return comp
    return None


def layer_of(path: Optional[str]) -> Optional[str]:
    """The ``L<i>`` (``L<i>.<k>``) component of a scope path, or None."""
    if path:
        for comp in path.split("/"):
            if _LAYER.match(comp):
                return comp
    return None


_ATTR_REFS = re.compile(
    r"\b(?:calls|to_apply|body|condition|branch_computations|"
    r"called_computations|select|scatter)=\{?[^,}]*\}?")
_REF = re.compile(r"%([\w.\-]+)")


def parse_hlo(text: str):
    """``(names, fused, inherited, entry)`` of an optimized HLO module's
    text: every instruction of every computation -> its scope path (None
    where it has none), a fusion standing under its body's ROOT's path
    where that has one; every fusion -> the scope paths of the
    instructions inside it; the names of the instructions that took
    their path from the instruction that USES them; and the ENTRY
    computation's instructions in the text's order, which is the order
    a scheduled module runs them in (a reader tells one program's run
    from its neighbour's by it). The inherited are what XLA made and
    named for nobody (the ``slice-start`` / ``slice-done`` pair of a
    weight prefetched in slices, a parameter's layout copy, a bitcast):
    moved for their consumer's sake, they answer to its scope."""
    names: Dict[str, Optional[str]] = {}
    bodies: Dict[str, List[Tuple[str, Optional[str], bool]]] = {}
    fusions: Dict[str, str] = {}
    users: Dict[str, List[str]] = {}
    body = entry = None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                body = bodies.setdefault(c.group(2), [])
                entry = body if c.group(1) else entry
            continue
        name = m.group(2)
        found = _OP_NAME.search(line)
        path = scope_path(found.group(1)) if found else None
        names[name] = path
        if body is not None:
            body.append((name, path, bool(m.group(1))))
        called = _CALLS.search(line)
        if called is not None:
            fusions[name] = called.group(1)
        operands = line[m.end():].split(", metadata=")[0]
        for used in _REF.findall(_ATTR_REFS.sub("", operands)):
            users.setdefault(used, []).append(name)
    fused: Dict[str, List[str]] = {}
    for name, comp in fusions.items():
        inside = bodies.get(comp, ())
        fused[name] = sorted({p for _n, p, _r in inside if p})
        root = next((p for _n, p, is_root in inside if is_root and p), None)
        if root is not None:
            names[name] = root
        elif names[name] is None and fused[name]:
            # a tuple at the root (several outputs) has no op_name
            names[name] = fused[name][0]
    inherited = []
    for name in [n for n, p in names.items() if p is None]:
        seen, front = {name}, [name]
        for _hop in range(8):         # start -> done -> bitcast -> fusion
            front = [u for n in front for u in users.get(n, ())
                     if u not in seen]
            seen.update(front)
            got = next((names[u] for u in front
                        if names.get(u) is not None
                        and u not in inherited), None)
            if got is not None or not front:
                break
        if got is not None:
            names[name] = got
            inherited.append(name)
    return names, fused, sorted(inherited), \
        [name for name, _path, _root in entry or ()]
