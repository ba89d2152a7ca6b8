"""Device busy time of one decode step: for every ``serving.engine.step``
span of the traced stretch, the time an operation ran on the chip inside
the span (the step fetches its logits, so its device work lies inside);
median over the steps. The span's host clock is mapped onto the
profiler's by the offset taken when the trace's window opened."""

import bisect

from benchmarks.lib import xplane
from benchmarks.lib.stats import median

LAYER = "model step on the device"
UNIT = "ms"
MOVES = "req_tok_ms_p50"
SOURCE = "device_trace"


def busy_per_step(record):
    trace = record.get("trace")
    steps = record.get("spans", {}).get("serving.engine.step")
    if trace is None or not steps:
        return None
    busy = xplane.union(trace["ops"][min(trace["ops"])])
    starts = [a for a, _b in busy]
    off = trace["host_offset_s"]
    out = []
    for end, dur in steps:
        lo, hi = end - dur + off, end + off
        if lo < trace["t0"] or hi > trace["t1"]:
            continue
        total = 0.0
        i = max(0, bisect.bisect_right(starts, lo) - 1)
        while i < len(busy) and busy[i][0] < hi:
            total += max(0.0, min(hi, busy[i][1]) - max(lo, busy[i][0]))
            i += 1
        out.append(total)
    return out or None


def read(record):
    per_step = busy_per_step(record)
    return None if per_step is None else median(per_step) * 1e3
