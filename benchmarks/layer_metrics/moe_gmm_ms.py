"""Device time of the expert layer's grouped matmuls in one decode step:
for every ``serving.engine.step`` span of the traced stretch, the summed
time of the first chip's operations that started inside the span and
whose name holds ``moe_gmm_up`` or ``moe_gmm_down`` — the names
``paddle_tpu/kernels/moe_gmm.py`` gives its two Pallas calls — median
over the steps. A prefill holds the same kernels; it runs outside the
step spans and is not counted. ``None`` where the traced stretch holds no
operation of either name (a program without the kernel)."""

import bisect

from benchmarks.lib.stats import median

LAYER = "Pallas kernels"
UNIT = "ms"
MOVES = "req_tok_ms_p50"
SOURCE = "device_trace"
KERNELS = ("moe_gmm_up", "moe_gmm_down")


def seconds_per_step(record):
    trace = record.get("trace")
    steps = record.get("spans", {}).get("serving.engine.step")
    if trace is None or not steps or trace.get("host_offset_s") is None:
        return None
    events = sorted((e[1], e[2]) for e in trace["ops"][min(trace["ops"])]
                    if any(k in e[0] for k in KERNELS))
    if not events:
        return None
    starts = [s for s, _d in events]
    off = trace["host_offset_s"]
    out = []
    for end, dur in steps:
        lo, hi = end - dur + off, end + off
        if lo < trace["t0"] or hi > trace["t1"]:
            continue
        i, j = bisect.bisect_left(starts, lo), bisect.bisect_left(starts, hi)
        out.append(sum(d for _s, d in events[i:j]))
    return median(out) if out else None


def read(record):
    secs = seconds_per_step(record)
    return None if secs is None else secs * 1e3
