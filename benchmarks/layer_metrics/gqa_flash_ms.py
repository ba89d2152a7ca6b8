"""Device time of the grouped-head flash forward in one admission of the
longest prompt: for every ``serving.engine.prefill`` span of the traced
stretch whose ``prompt_len`` is the traffic's longest (16,384 in
``lfm2_serve_long_ctx``), the summed time of the first chip's operations
that started inside the span and whose name holds ``flash_fwd`` (and not
``flash_fwd_win``) — the flash forward at 32 query and 8 key-value heads
of 64, one call an attention layer — median over the admissions, as
``mla_flash_ms`` reads the latent one. ``None`` where the record is not
such a cell's (no ``facts.gqa_flash``), or the traced stretch holds no
such admission or no operation of that name."""

import bisect

from benchmarks.lib import program_spans
from benchmarks.lib.stats import median

LAYER = "Pallas kernels"
UNIT = "ms"
MOVES = "serve_tok_s"
SOURCE = "device_trace"
KERNEL, NOT = "flash_fwd", "flash_fwd_win"
SITE = "serving.engine.prefill"


def seconds_per_admission(record):
    trace = record.get("trace")
    facts = record.get("facts") or {}
    longest = facts.get("longest_prompt")
    if trace is None or longest is None or "gqa_flash" not in facts \
            or trace.get("host_offset_s") is None:
        return None
    events = sorted((e[1], e[2]) for e in trace["ops"][min(trace["ops"])]
                    if KERNEL in e[0] and NOT not in e[0])
    if not events:
        return None
    starts = [s for s, _d in events]
    off = trace["host_offset_s"]
    out = []
    for ev in program_spans.finished(record):
        if ev["site"] != SITE \
                or (ev.get("attrs") or {}).get("prompt_len") != longest:
            continue
        lo, hi = ev["t"] - ev["dur"] + off, ev["t"] + off
        if lo < trace["t0"] or hi > trace["t1"]:
            continue
        i, j = bisect.bisect_left(starts, lo), bisect.bisect_left(starts, hi)
        if j > i:
            out.append(sum(d for _s, d in events[i:j]))
    return median(out) if out else None


def read(record):
    secs = seconds_per_admission(record)
    return None if secs is None else secs * 1e3
