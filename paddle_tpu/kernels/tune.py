"""Per-shape kernel autotuner with a persisted winner cache.

The TVM thesis applied to the kernel tier (PAPERS.md, arxiv 1802.04799;
Tensor Processing Primitives, arxiv 2104.05755): instead of a fixed
heuristic picking between a Pallas kernel and the composed-XLA math, the
choice is MEASURED per (op, input signature) over a small grid of
Mosaic-legal block-shape candidates plus the composed path, and the
winner is persisted so no process ever pays the measurement twice.

Cache layout (``PADDLE_TPU_KERNEL_CACHE_DIR``; persistence is OFF unless
it names a directory, so dispatch never depends on a file outside the
checkout that nobody asked for): one JSON file ``tuned_kernels.json``::

    {"version": 1,
     "entries": {"layernorm_residual|float32,4096,512":
                 {"choice": "pallas", "cfg": [64], "seconds": 1.2e-4}}}

Writes are atomic tmp+rename (the tensor_store pattern: unique staging
name per writer, ``os.replace`` is last-writer-wins, never a torn file)
with a read-merge-write cycle so concurrent tuners don't torch each
other's entries. Corrupt files and version-skewed entries degrade to
cache MISSES (re-tune), never crashes.

Measurement: jit + block_until_ready, best-of-``PADDLE_TPU_KERNEL_TUNE_
REPEATS`` (default 3) after one warmup call per candidate. Setting
``PADDLE_TPU_KERNEL_TUNE_DETERMINISTIC=<seed>`` replaces wall-clock
timing with a stable hash of (seed, op, sig, candidate) — tier-1 tests
pin tuner BEHAVIOR (selection, persistence, counters) without ever
flaking on timing; Mosaic legality is still asserted for every candidate
either way.

Counters: ``paddle_kernel_tuner_hits_total{tier=memory|disk}``,
``paddle_kernel_tuner_misses_total``, ``paddle_kernel_tune_seconds``,
``paddle_kernel_winners_total{op,choice}`` (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import hashlib
import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["CACHE_VERSION", "cache_dir", "cache_path", "tune_enabled",
           "deterministic_seed", "lookup", "peek", "tune", "set_entry",
           "load_disk_entries", "persist_entry", "reset", "config_key",
           "sig_key", "export_entries", "import_entries"]

CACHE_VERSION = 1
CACHE_FILE = "tuned_kernels.json"

_MEM: Dict[str, Dict[str, Any]] = {}
_LOCK = threading.RLock()
_DISK_LOADED_FOR: Optional[str] = None  # the path entries were loaded from
_EPOCH = 0  # bumps whenever the decision table changes (plan-cache key)
_TMP_SEQ = itertools.count(1)


def cache_dir() -> Optional[str]:
    """Winner-cache directory, or None when persistence is off
    (``PADDLE_TPU_KERNEL_CACHE_DIR`` unset, empty or ``0``)."""
    raw = os.environ.get("PADDLE_TPU_KERNEL_CACHE_DIR", "").strip()
    return None if raw in ("", "0") else raw


def cache_path() -> Optional[str]:
    d = cache_dir()
    return os.path.join(d, CACHE_FILE) if d else None


def tune_enabled() -> bool:
    """``PADDLE_TPU_KERNEL_TUNE=1`` arms tune-on-miss at dispatch time
    (default OFF: an untuned process always takes the composed path —
    bitwise the pre-tier behavior — and tuning happens explicitly via
    ``tools/kernel_tune.py`` or the env opt-in)."""
    return os.environ.get("PADDLE_TPU_KERNEL_TUNE", "0") == "1"


def deterministic_seed() -> Optional[int]:
    raw = os.environ.get("PADDLE_TPU_KERNEL_TUNE_DETERMINISTIC", "")
    if raw == "":
        return None
    try:
        return int(raw)
    except ValueError:
        raise ValueError(
            "PADDLE_TPU_KERNEL_TUNE_DETERMINISTIC must be a decimal "
            "integer seed; got %r" % (raw,)) from None


def _repeats() -> int:
    try:
        return max(1, int(os.environ.get(
            "PADDLE_TPU_KERNEL_TUNE_REPEATS", "3")))
    except ValueError:
        return 3


def sig_key(op: str, sig: Tuple) -> str:
    return "%s|%s" % (op, ",".join(str(s) for s in sig))


# ------------------------------------------------------------------ disk
def load_disk_entries(path: Optional[str] = None) -> Dict[str, Dict]:
    """Entries from the winner file; corrupt JSON, a non-dict payload, or
    a version-skewed file all read as EMPTY (misses — the tuner re-tunes
    and the next persist rewrites the file at the current version)."""
    path = path or cache_path()
    if not path or not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            data = json.load(f)
    except (ValueError, OSError):
        return {}
    if not isinstance(data, dict) or data.get("version") != CACHE_VERSION:
        return {}
    entries = data.get("entries")
    if not isinstance(entries, dict):
        return {}
    return {k: v for k, v in entries.items()
            if isinstance(v, dict) and v.get("choice") in
            ("pallas", "composed")}


def _ensure_disk_loaded() -> None:
    """One-shot promote of the disk winners into the in-memory table
    (per cache path — switching PADDLE_TPU_KERNEL_CACHE_DIR mid-process
    reloads). Loading bumps the epoch so the executor's plan-cache key
    sees the table change."""
    global _DISK_LOADED_FOR, _EPOCH
    path = cache_path()
    with _LOCK:
        if _DISK_LOADED_FOR == path:
            return
        _DISK_LOADED_FOR = path
        if path:
            loaded = load_disk_entries(path)
            for k, v in loaded.items():
                _MEM.setdefault(k, dict(v, source="disk"))
            if loaded:
                _EPOCH += 1


def persist_entry(key: str, decision: Dict[str, Any],
                  path: Optional[str] = None) -> None:
    """Read-merge-write the winner file atomically (tmp+rename, unique
    staging name per writer): concurrent writers merge through the
    re-read; the final ``os.replace`` can lose a same-instant sibling's
    newest entry but never corrupts the file — the loser re-tunes."""
    path = path or cache_path()
    if not path:
        return
    os.makedirs(os.path.dirname(path), exist_ok=True)
    entries = load_disk_entries(path)
    entries[key] = {k: v for k, v in decision.items() if k != "source"}
    tmp = "%s.tmp.%d.%d" % (path, os.getpid(), next(_TMP_SEQ))
    with open(tmp, "w") as f:
        json.dump({"version": CACHE_VERSION, "entries": entries}, f,
                  indent=1, sort_keys=True)
    os.replace(tmp, path)


# ---------------------------------------------------------------- lookup
def lookup(op: str, sig: Tuple) -> Optional[Dict[str, Any]]:
    """Tuned decision for (op, sig), or None (miss). Memory first, then
    the one-shot disk load; every call moves exactly one hit or miss
    counter — the end-to-end acceptance test pins 'second process serves
    everything from disk, zero tune invocations' on these."""
    from ..observe.families import KERNEL_TUNER_HITS, KERNEL_TUNER_MISSES

    key = sig_key(op, sig)
    with _LOCK:
        dec = _MEM.get(key)
        if dec is not None:
            KERNEL_TUNER_HITS.labels(
                tier="disk" if dec.get("source") == "disk"
                else "memory").inc()
            return dec
        _ensure_disk_loaded()
        dec = _MEM.get(key)
        if dec is not None:
            KERNEL_TUNER_HITS.labels(tier="disk").inc()
            return dec
    KERNEL_TUNER_MISSES.inc()
    return None


def peek(op: str, sig: Tuple) -> Optional[Dict[str, Any]]:
    """``lookup`` without the hit/miss counters: the resolution probe
    for callers that consult the table on EVERY loop entry (the
    windowed train loop's steps_per_call auto-resolution) — a per-loop
    probe must not inflate the lookup counters whose exact movement the
    kernel-tier acceptance tests pin. Dispatch decisions that act on
    the answer still count through ``lookup``/``decide_and_note``."""
    key = sig_key(op, sig)
    with _LOCK:
        dec = _MEM.get(key)
        if dec is not None:
            return dec
        _ensure_disk_loaded()
        return _MEM.get(key)


def set_entry(op: str, sig: Tuple, decision: Dict[str, Any],
              persist: bool = False, bump: bool = True) -> None:
    """Install a decision directly (tests inject winners; the CLI's
    ``--set`` escape hatch). Bumps the epoch so cached executor plans
    compiled under the old table re-prepare.

    ``bump=False`` is for tune-on-miss at DISPATCH time: the plan being
    traced is the one that just picked the winner up, and a sibling
    plan cached earlier with this signature was lowered when no entry
    existed — it keeps its (always-correct) composed choice; bumping
    would only force a byte-identical recompile of the triggering plan
    on its next run (jit traces lazily, AFTER the plan was keyed)."""
    global _EPOCH
    key = sig_key(op, sig)
    with _LOCK:
        _MEM[key] = dict(decision)
        if bump:
            _EPOCH += 1
    if persist:
        persist_entry(key, decision)


def export_entries(keys=None) -> Dict[str, Dict[str, Any]]:
    """Portable slice of the decision table for a deployable artifact
    (``paddle_tpu.export``): entries stripped of process-local fields
    (``source``) and measurement noise (``timings``/``errors``) so the
    slice is stable across hosts. ``keys`` filters to the given sig_keys
    or, for strings ending in ``|``, to every entry under that op prefix
    (``"matmul|"`` takes all matmul signatures); None exports the whole
    table (memory + the one-shot disk load)."""
    with _LOCK:
        _ensure_disk_loaded()
        out: Dict[str, Dict[str, Any]] = {}
        for k, v in _MEM.items():
            if keys is not None:
                if not any(k == f or (f.endswith("|") and k.startswith(f))
                           for f in keys):
                    continue
            out[k] = {f: x for f, x in v.items()
                      if f in ("choice", "cfg", "seconds")}
        return out


def import_entries(entries: Dict[str, Dict[str, Any]]) -> int:
    """Install an exported slice into the in-memory table (artifact
    load). Grammar-checked like ``load_disk_entries`` (bad entries are
    skipped, never crash); existing in-memory winners are NOT
    overwritten — a live tuned decision beats a frozen one. One epoch
    bump for the whole batch so plans keyed under the old table
    re-prepare exactly once. Returns the number installed."""
    global _EPOCH
    n = 0
    with _LOCK:
        for k, v in (entries or {}).items():
            if not isinstance(k, str) or not isinstance(v, dict):
                continue
            if v.get("choice") not in ("pallas", "composed"):
                continue
            if k not in _MEM:
                _MEM[k] = dict(v, source="artifact")
                n += 1
        if n:
            _EPOCH += 1
    return n


def reset() -> None:
    """Forget every in-memory decision and the disk-loaded flag (tests).
    The epoch still advances: a plan compiled before reset must not be
    served after it."""
    global _DISK_LOADED_FOR, _EPOCH
    with _LOCK:
        _MEM.clear()
        _DISK_LOADED_FOR = None
        _EPOCH += 1


def config_key() -> tuple:
    """Everything that changes WHICH implementation dispatch would pick,
    for the executor's plan-cache key: the tune-on-miss arm, the cache
    dir, and the decision-table epoch (bumped by tune/set_entry/reset
    and the one-shot disk load, which this call forces so steady-state
    keys are stable)."""
    _ensure_disk_loaded()
    return (1 if tune_enabled() else 0, cache_dir() or "", _EPOCH)


# ------------------------------------------------------------ measurement
def _fake_seconds(seed: int, op: str, sig: Tuple, label: str) -> float:
    """Deterministic stand-in timing: a stable hash of (seed, op, sig,
    candidate label) mapped into (1, 2) ms. Selection becomes a pure
    function of the inputs — tier-1 tests never flake on timing."""
    h = hashlib.sha256(
        ("%d|%s|%s|%s" % (seed, op, ",".join(map(str, sig)), label))
        .encode()).hexdigest()
    return 1e-3 * (1.0 + int(h[:8], 16) / 0xffffffff)


def _measure(fn, args, attrs, repeats: int) -> float:
    import jax

    wrapped = jax.jit(lambda *a: fn(*a, **attrs))

    def once() -> float:
        t0 = time.perf_counter()
        out = wrapped(*args)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    once()  # warmup: compile + first dispatch
    return min(once() for _ in range(repeats))


def tune(op: str, sig: Tuple, attrs: Optional[Dict[str, Any]] = None,
         candidates=None) -> Dict[str, Any]:
    """Measure every Mosaic-legal candidate of ``op`` at ``sig`` plus the
    composed fallback, persist the winner, and return the decision.
    ``candidates`` overrides the KernelDef's grid (the CLI's
    ``--candidates`` escape hatch).

    Every candidate's block legality is ASSERTED (``KernelDef.check``)
    before anything runs — including in deterministic mode — so an
    illegal grid entry fails the tune loudly instead of being silently
    skipped (``tools/kernel_tune.py`` exits non-zero on it). A candidate
    that crashes during measurement is recorded with infinite cost (it
    can never win) and reported in the decision's ``errors``."""
    from ..observe import trace as _tr
    from ..observe.families import KERNEL_TUNE_SECONDS, KERNEL_WINNERS
    from .registry import get_kernel

    kdef = get_kernel(op)
    attrs = dict(attrs or {})
    seed = deterministic_seed()
    repeats = _repeats()
    t0 = time.perf_counter()
    with _tr.trace_span("kernel.tune", op=op, sig=str(sig)):
        cands = list(candidates if candidates is not None
                     else kdef.candidates(sig))
        for cfg in cands:
            kdef.check(cfg, sig)  # Mosaic legality, asserted for EVERY one
        timings: List[Dict[str, Any]] = []
        costs: List[float] = []
        errors: List[str] = []
        args = None
        if seed is None:
            import numpy as np

            args = kdef.make_inputs(sig, np.random.RandomState(0))
        for cfg in cands:
            label = "pallas:%s" % (list(cfg),)
            if seed is not None:
                secs = _fake_seconds(seed, op, sig, label)
            else:
                try:
                    secs = _measure(
                        lambda *a, _c=cfg, **kw: kdef.pallas(_c, *a, **kw),
                        args, attrs, repeats)
                except Exception as e:  # crashed candidate loses, only
                    errors.append("%s: %s: %s"
                                  % (label, type(e).__name__, e))
                    secs = float("inf")
            # crashed candidates persist seconds=null, never Infinity:
            # the winner file must stay strict RFC-8259 JSON for
            # non-Python consumers (jq, dashboards)
            timings.append({"label": label, "cfg": list(cfg),
                            "choice": "pallas",
                            "seconds": secs if secs != float("inf")
                            else None})
            costs.append(secs)
        if seed is not None:
            secs = _fake_seconds(seed, op, sig, "composed")
        else:
            secs = _measure(kdef.fallback, args, attrs, repeats)
        timings.append({"label": "composed", "cfg": None,
                        "choice": "composed", "seconds": secs})
        costs.append(secs)
        best = timings[costs.index(min(costs))]
        decision: Dict[str, Any] = {
            "choice": best["choice"], "cfg": best["cfg"],
            "seconds": best["seconds"], "source": "tuned",
            "timings": timings,
        }
        if errors:
            decision["errors"] = errors
        # no epoch bump: a tune is only ever triggered by the plan that
        # immediately consumes the winner (see set_entry's bump=False)
        set_entry(op, sig, decision, persist=True, bump=False)
    KERNEL_TUNE_SECONDS.observe(time.perf_counter() - t0)
    KERNEL_WINNERS.labels(op=op, choice=best["choice"]).inc()
    return decision
