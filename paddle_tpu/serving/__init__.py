"""Serving scheduler: request queue, dynamic micro-batching, continuous
batching for autoregressive decode — and the fleet tier above them.

The layer between callers and compiled executables that the reference
framework delegates to an external server (SURVEY §1) — a TPU-native
framework owns it, because batch occupancy is the difference between
~1/B and full utilisation on a dispatch-latency-bound device. Five
pieces (docs/SERVING.md has the architecture):

* ``queue``   — bounded admission queue: backpressure (reject-when-
  full, counted), per-request deadlines, cancellation, per-request
  futures, per-tenant outcome labels.
* ``batcher`` — dynamic micro-batching for ``Predictor`` workloads:
  coalesce within a max-wait window, ride the Predictor's
  warmup-bucket router (no steady-state recompiles), slice per-request
  results back out.
* ``engine``  — continuous batching for GPT decode: one fixed-b_max
  decode executable whose per-slot KV caches admit new sequences at
  step boundaries (prefill-then-insert) and retire finished ones
  immediately; optionally speculative (draft model + one-dispatch
  greedy verification) and prefix-cached.
* ``prefix``  — the prefix/KV-cache store: shared prompt heads prefill
  ONCE; later prompts splice the cached rows and prefill only their
  suffix, bitwise-identically.
* ``router``  — SLO-aware multi-replica routing: per-tenant quotas,
  reject-early admission against projected queue wait, and supervised
  replica health (a wedged replica is drained, its requests re-admitted
  elsewhere, and restarted).

All five report through ``paddle_tpu.observe`` (queue depth,
time-in-queue, occupancy, padding waste, tokens/sec, prefix hit rate,
speculative acceptance, router restarts); the benchmark's serving cells
(``benchmarks/run.py``) drive ``DecodeEngine`` on the chip.
"""

from __future__ import annotations

from .batcher import MicroBatcher
from .engine import DecodeEngine
from .engine import MemoryBudgetExceeded
from .prefix import PrefixStore
from .queue import (Cancelled, DeadlineExpired, QueueFull, RequestQueue,
                    ServingRequest)
from .router import ReplicaRouter, TenantQuotaExceeded

__all__ = ["Cancelled", "DeadlineExpired", "DecodeEngine",
           "MemoryBudgetExceeded", "MicroBatcher", "PrefixStore",
           "QueueFull", "ReplicaRouter", "RequestQueue",
           "ServingRequest", "TenantQuotaExceeded"]
