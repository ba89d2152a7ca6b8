"""The feed-to-run gap stamp: how long a produced batch waited for the
executor. (Timed scopes are ``observe.trace.trace_span``: one span type,
recorded in the flight recorder and, under a ``jax.profiler`` trace, in
the profile's host plane.)
"""

from __future__ import annotations

import threading
import time

from .families import FEED_TO_RUN_GAP_SECONDS

__all__ = ["mark_batch_produced", "observe_feed_gap"]


# ------------------------------------------------------- feed-to-run gap
# The input pipeline stamps "a batch was handed to this thread"
# (mark_batch_produced, from reader.batch / MultiSlotDataFeed /
# DevicePrefetcher hand-off); the executor reads-and-clears the stamp at
# dispatch entry (observe_feed_gap). The observed gap separates
# input-bound from compute-bound steady states without a profiler run.
# THREAD-LOCAL: a background fill thread (buffered(), DevicePrefetcher)
# runs the wrapped reader concurrently with the consumer's step loop —
# a shared stamp would let batch N+1's production overwrite batch N's
# hand-off between stamp and observe, recording a gap against the wrong
# batch. Thread-wrapping readers re-stamp at hand-off in the consumer.
_batch_stamp = threading.local()


def mark_batch_produced() -> None:
    _batch_stamp.ts = time.perf_counter()


def observe_feed_gap() -> None:
    ts = getattr(_batch_stamp, "ts", None)
    if ts is not None:
        _batch_stamp.ts = None
        FEED_TO_RUN_GAP_SECONDS.observe(time.perf_counter() - ts)


def _clear_batch_stamp() -> None:
    _batch_stamp.ts = None
