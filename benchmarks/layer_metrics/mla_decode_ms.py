"""Device time of the absorbed latent attention in one decode step: for
every ``serving.engine.step`` span of the traced stretch, the summed time
of the first chip's operations that started inside the span and whose
name holds ``mla_decode`` — the name ``paddle_tpu/kernels/mla_decode.py``
gives its Pallas call, one a layer — median over the steps. ``None``
where the traced stretch holds no operation of that name (a program
without the kernel)."""

import bisect

from benchmarks.lib.stats import median

LAYER = "Pallas kernels"
UNIT = "ms"
MOVES = "req_tok_ms_p50"
SOURCE = "device_trace"
KERNEL = "mla_decode"


def seconds_per_step(record):
    trace = record.get("trace")
    steps = (record.get("spans") or {}).get("serving.engine.step")
    if trace is None or not steps or trace.get("host_offset_s") is None:
        return None
    events = sorted((e[1], e[2]) for e in trace["ops"][min(trace["ops"])]
                    if KERNEL in e[0])
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
