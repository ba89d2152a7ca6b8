"""Kernel tier: tuned Pallas alternatives beside composed-XLA lowerings.

The layer-4 subsystem (PAPER.md) the op layer composes over: hot ops —
fused attention, layernorm+residual, the flattened optimizer sweep —
carry a Pallas implementation AND a composed fallback in one registry
(``registry.py``), and an autotuner (``tune.py``) picks between them per
(op, dtype, shape signature) by measurement, persisting winners to a
JSON cache so only the first process ever pays the search.

Dispatch contract:

* ``PADDLE_TPU_KERNELS=0`` bypasses the tier wholesale — every dispatch
  takes the composed fallback and provably moves ZERO ``paddle_kernel_*``
  counters (pinned by tests).
* With the tier on but no tuned entry, dispatch takes the composed path
  (bitwise the pre-tier behavior) and counts a tuner miss; it only tunes
  inline when ``PADDLE_TPU_KERNEL_TUNE=1`` (measurement at lowering
  time, once per plan-cache miss per signature).
* A tuned entry decides: ``pallas`` runs the kernel at the winning block
  config, ``composed`` pins the fallback. Flash attention's
  ``flash_min_seq`` dispatch consults the same table (precedence:
  explicit env > tuned entry > static threshold — ops/attention.py).

Every decision taken since the last ``reset_decisions()`` is recorded in
``decisions_seen()`` — chip_smoke.py and benchmarks/run.py report the
map so a regression is attributable to a specific kernel choice. See
docs/KERNELS.md.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, Optional, Tuple

from . import tune
from .common import (assert_mosaic_ok, checked_pallas_call,  # noqa: F401
                     mosaic_ok, use_interpret)
from .registry import (KERNELS, KernelDef, all_kernels,  # noqa: F401
                       get_kernel, has_kernel, register_kernel)
from . import (kv_cache_write, layernorm,  # noqa: F401  (register entries)
               optimizer_update, ssm)

__all__ = [
    "kernels_enabled", "run_kernel", "decide", "decide_and_note",
    "tuned_choice",
    "decisions_seen", "note_decision", "reset_decisions", "config_key",
    "register_kernel", "get_kernel", "has_kernel", "all_kernels",
    "assert_mosaic_ok", "mosaic_ok", "checked_pallas_call",
    "use_interpret", "KernelDef",
]

_DEC_LOCK = threading.Lock()
_DECISIONS: Dict[str, Dict[str, Any]] = {}


def kernels_enabled() -> bool:
    """``PADDLE_TPU_KERNELS`` master switch (default on). Off = every
    dispatch takes the composed fallback, no counter moves — the A/B
    bypass lever the perf pins compare against."""
    return os.environ.get("PADDLE_TPU_KERNELS", "1") != "0"


def note_decision(op: str, choice: str, tuned: bool = False) -> None:
    """Record a dispatch decision (the ``kernel_tier`` map a run
    reports). Last decision per op wins within a run; ``tuned`` marks
    choices that came from a tuner entry rather than the default
    path."""
    with _DEC_LOCK:
        _DECISIONS[op] = {"choice": choice, "tuned": bool(tuned)}


def decisions_seen() -> Dict[str, Dict[str, Any]]:
    """op -> {"choice", "tuned"} for every kernel-tier dispatch since
    the last ``reset_decisions()``."""
    with _DEC_LOCK:
        return {k: dict(v) for k, v in _DECISIONS.items()}


def reset_decisions() -> None:
    with _DEC_LOCK:
        _DECISIONS.clear()


def decide(op: str, sig: Tuple,
           attrs: Optional[Dict[str, Any]] = None) -> Optional[Dict]:
    """The dispatch decision for (op, sig): the tuned entry when one
    exists (memory or disk), an inline tune when ``PADDLE_TPU_KERNEL_
    TUNE=1``, else None (caller takes its composed/static default).
    Never called with the tier bypassed — callers gate on
    ``kernels_enabled()`` first so the bypass moves no counters."""
    dec = tune.lookup(op, sig)
    if dec is None and tune.tune_enabled():
        dec = tune.tune(op, sig, attrs)
    return dec


def tuned_choice(op: str, sig: Tuple) -> Optional[str]:
    """'pallas' / 'composed' from the tuned table, or None when no entry
    exists (or the tier is bypassed). The flash_min_seq precedence hook:
    never tunes inline — attention tuning is an explicit CLI/env act."""
    if not kernels_enabled():
        return None
    dec = tune.lookup(op, sig)
    return dec["choice"] if dec else None


def decide_and_note(op: str, sig: Tuple,
                    attrs: Optional[Dict[str, Any]] = None):
    """THE shared dispatch protocol — tuned-decision lookup (+ inline
    tune under PADDLE_TPU_KERNEL_TUNE=1), decision-ledger note
    ('pallas:<cfg>' / 'composed', tuned flag), and the
    per-compile ``paddle_kernel_dispatches_total`` count — used by
    ``run_kernel`` and every fused-op lowering so the three sites can
    never drift on ledger format or counter semantics. Returns
    ``("pallas", cfg_or_None)`` or ``("composed", None)``. Callers gate
    on ``kernels_enabled()`` first (the bypass must move nothing)."""
    from ..observe.families import KERNEL_DISPATCHES

    dec = decide(op, sig, attrs)
    if dec is not None and dec["choice"] == "pallas":
        cfg = tuple(dec.get("cfg") or ())
        note_decision(op, "pallas:%s" % ",".join(map(str, cfg)),
                      tuned=True)
        KERNEL_DISPATCHES.labels(op=op, impl="pallas").inc()
        return "pallas", (cfg or None)
    note_decision(op, "composed", tuned=dec is not None)
    KERNEL_DISPATCHES.labels(op=op, impl="composed").inc()
    return "composed", None


def run_kernel(name: str, args: Tuple,
               attrs: Optional[Dict[str, Any]] = None):
    """Dispatch one kernel-tier op: tuned pallas winner when the table
    says so, composed fallback otherwise (and always under
    ``PADDLE_TPU_KERNELS=0``). ``args``/``attrs`` must match the
    registered implementation pair's shared signature."""
    kdef = get_kernel(name)
    attrs = dict(attrs or {})
    if not kernels_enabled():
        note_decision(name, "bypass")
        return kdef.fallback(*args, **attrs)
    choice, cfg = decide_and_note(name, kdef.signature(args), attrs)
    if choice == "pallas":
        return kdef.pallas(cfg, *args, **attrs)
    return kdef.fallback(*args, **attrs)


def config_key() -> tuple:
    """Everything that changes which implementation a dispatch picks —
    part of the executor's plan-cache key, so a plan lowered under one
    kernel-tier config never serves another (same deal as the optimizer
    pipeline's config_key). The flash dispatch env knobs ride along in
    EVERY mode — precedence tier 1 (PADDLE_TPU_FLASH_MIN_SEQ, the
    documented absolute A/B lever) and the block sizes apply even with
    the tier bypassed, and a cached plan must never silently outvote
    them."""
    flash = (os.environ.get("PADDLE_TPU_FLASH_MIN_SEQ", ""),
             os.environ.get("PADDLE_TPU_FLASH_BQ", ""),
             os.environ.get("PADDLE_TPU_FLASH_BK", ""))
    if not kernels_enabled():
        return (0,) + flash
    return (1,) + tune.config_key() + flash
