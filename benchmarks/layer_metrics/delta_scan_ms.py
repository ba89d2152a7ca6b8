"""Device time of the chunked delta-rule scan in the traced stretch, a
second of it: the summed time of the first chip's operations whose name
holds ``delta_scan`` — the name ``paddle_tpu/kernels/delta.py`` gives the
Pallas call that scans a whole prompt, one a delta layer and admission —
that started inside a ``serving.engine.prefill`` span of the traced
stretch, over the stretch's length. What the admissions' scans take of a
second the decode steps do not have. ``None`` where the record is not of
a cell with delta layers (no ``facts.delta``), or the traced stretch
holds no admission or no operation of that name (a composed plan, a
program without the kernel)."""

import bisect

from benchmarks.lib import program_spans

LAYER = "Pallas kernels"
UNIT = "ms/s"
MOVES = "serve_tok_s"
SOURCE = "device_trace"
KERNEL = "delta_scan"
SITE = "serving.engine.prefill"


def kernel_events(record, kernel):
    """The first chip's operations under ``kernel``'s name as sorted
    ``(start, dur)``; None where there is none."""
    trace = record.get("trace")
    if trace is None or "delta" not in (record.get("facts") or {}) \
            or trace.get("host_offset_s") is None:
        return None
    return sorted((e[1], e[2]) for e in trace["ops"][min(trace["ops"])]
                  if kernel in e[0]) or None


def admissions(record):
    """``[(prompt_len, seconds of the scan's operations inside it)]`` for
    every admission that lies inside the traced stretch and holds such
    an operation; None where nothing can be read."""
    events = kernel_events(record, KERNEL)
    if events is None:
        return None
    trace = record["trace"]
    off = trace["host_offset_s"]
    starts = [s for s, _d in events]
    out = []
    for ev in program_spans.finished(record):
        plen = (ev.get("attrs") or {}).get("prompt_len")
        if ev["site"] != SITE or plen is None:
            continue
        lo, hi = ev["t"] - ev["dur"] + off, ev["t"] + off
        if lo < trace["t0"] or hi > trace["t1"]:
            continue
        i, j = bisect.bisect_left(starts, lo), bisect.bisect_left(starts, hi)
        if j > i:
            out.append((int(plen), sum(d for _s, d in events[i:j])))
    return out or None


def read(record):
    found = admissions(record)
    if not found:
        return None
    trace = record["trace"]
    return 1e3 * sum(secs for _p, secs in found) / (trace["t1"] - trace["t0"])
