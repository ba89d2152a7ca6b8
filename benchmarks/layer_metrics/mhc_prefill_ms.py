"""Device time of the residual path's two kernels in one admission of the
longest prompt: for every ``serving.engine.prefill`` span of the traced
stretch whose ``prompt_len`` is the traffic's longest (8,192 in
``xing_serve_docs``), the summed time of the first chip's operations that
started inside the span and whose name holds ``mhc_pre`` or ``mhc_post``
— the names ``paddle_tpu/kernels/mhc.py`` gives its two Pallas calls, one
of each a sub-block, two sub-blocks a layer — median over the admissions.
The mappings between the two (sigmoids and Sinkhorn rounds on 24 values a
row) run as XLA's own fusions under names of XLA's choosing and are not
in it. ``None`` where the record is not of a cell with residual streams
(no ``facts.mhc``), or the traced stretch holds no such admission or no
operation of either name (a composed plan)."""

import bisect

from benchmarks.lib import program_spans
from benchmarks.lib.stats import median

LAYER = "Pallas kernels"
UNIT = "ms"
MOVES = "serve_tok_s"
SOURCE = "device_trace"
KERNELS = ("mhc_pre", "mhc_post")
SITE = "serving.engine.prefill"


def kernel_events(record):
    """The first chip's operations under either kernel's name as sorted
    ``(start, dur)``; None where there is none."""
    trace = record.get("trace")
    if trace is None or "mhc" not in (record.get("facts") or {}) \
            or trace.get("host_offset_s") is None:
        return None
    return sorted((e[1], e[2]) for e in trace["ops"][min(trace["ops"])]
                  if any(k in e[0] for k in KERNELS)) or None


def median_inside(record, events, spans):
    """Median over ``spans`` (``(end, dur)`` on the host's clock) of the
    summed time of the ``events`` that started inside each; spans that
    reach outside the traced stretch, or hold no event, are left out."""
    trace = record["trace"]
    off = trace["host_offset_s"]
    starts = [s for s, _d in events]
    out = []
    for end, dur in spans:
        lo, hi = end - dur + off, end + off
        if lo < trace["t0"] or hi > trace["t1"]:
            continue
        i, j = bisect.bisect_left(starts, lo), bisect.bisect_left(starts, hi)
        if j > i:
            out.append(sum(d for _s, d in events[i:j]))
    return median(out) if out else None


def seconds_per_admission(record):
    events = kernel_events(record)
    longest = (record.get("facts") or {}).get("longest_prompt")
    if events is None or longest is None:
        return None
    return median_inside(record, events, [
        (ev["t"], ev["dur"]) for ev in program_spans.finished(record)
        if ev["site"] == SITE
        and (ev.get("attrs") or {}).get("prompt_len") == longest])


def read(record):
    secs = seconds_per_admission(record)
    return None if secs is None else secs * 1e3
