"""Pallas kernels beside the composed-XLA lowerings they stand in for.

Each module here holds one kernel, its composed form, and the function
that plans the kernel's blocks from what the operands show (shapes, a
byte cap, a VMEM reckoning): ``moe_gmm.gmm_plan``,
``kv_cache_write.write_plan``, ``mla_decode``, ``mhc``, ``ssm``; flash
attention and its ``_block_plan`` live in ``ops/attention.py``. The
lowering that uses a kernel asks that plan and nothing else: a plan of
``None`` (or a platform on which Pallas does not compile) is the
composed form. Nothing is measured, persisted or looked up.

``PADDLE_TPU_KERNELS=0`` takes the composed form of ``kv_cache_write``,
``mhc``, ``ssm`` and ``mla_decode``: the A/B lever on the chip, and the
side those kernels' tests compare against. (Flash against composed
attention is a threshold on the sequence, ``ops.attention.flash_effective``;
the grouped matmul has no switch beside its plan.)

Every choice a lowering noted since the last ``reset_decisions()`` is in
``decisions_seen()``: chip_smoke.py and benchmarks/run.py report the map
so a regression is attributable to a kernel choice. See docs/KERNELS.md.
"""

from __future__ import annotations

import os
import threading
from typing import Dict

from .common import (assert_mosaic_ok, checked_pallas_call,  # noqa: F401
                     mosaic_ok, use_interpret)

__all__ = [
    "kernels_enabled", "decisions_seen", "note_decision",
    "reset_decisions", "config_key", "assert_mosaic_ok", "mosaic_ok",
    "checked_pallas_call", "use_interpret",
]

_DEC_LOCK = threading.Lock()
_DECISIONS: Dict[str, Dict[str, str]] = {}


def kernels_enabled() -> bool:
    """``PADDLE_TPU_KERNELS`` (default on). Off = the lowerings that ask
    take their composed form (module docstring)."""
    return os.environ.get("PADDLE_TPU_KERNELS", "1") != "0"


def note_decision(op: str, choice: str) -> None:
    """Record which form a lowering took (the ``kernel_tier`` map a run
    reports). The last decision an op made within a run wins."""
    with _DEC_LOCK:
        _DECISIONS[op] = {"choice": choice}


def decisions_seen() -> Dict[str, Dict[str, str]]:
    """op -> {"choice"} for every decision noted since the last
    ``reset_decisions()``."""
    with _DEC_LOCK:
        return {k: dict(v) for k, v in _DECISIONS.items()}


def reset_decisions() -> None:
    with _DEC_LOCK:
        _DECISIONS.clear()


def config_key() -> tuple:
    """The environment that changes which form a lowering picks: part of
    the executor's plan-cache key, so a plan lowered under one setting
    never serves another. (Flash against composed attention does not
    ask ``PADDLE_TPU_KERNELS``, so the threshold rides along either way.)"""
    return (int(kernels_enabled()),
            os.environ.get("PADDLE_TPU_FLASH_MIN_SEQ", ""))
