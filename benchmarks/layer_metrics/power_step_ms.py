"""Device time of the retention update in one decode step: for every
``serving.engine.step`` span of the traced stretch, the summed time of
the first chip's operations that started inside the span and whose name
holds ``power_update`` — the name ``paddle_tpu/kernels/power.py`` gives
the Pallas call that reads and writes every slot's state once and reads
the query heads out of it, one a retention layer — median over the
steps. ``None`` where the record is not of a cell with retention layers
or the traced stretch holds no operation of that name (a composed
plan)."""

from benchmarks.lib.readers import sibling

LAYER = "Pallas kernels"
UNIT = "ms"
MOVES = "req_tok_ms_p50"
SOURCE = "device_trace"
KERNEL = "power_update"


def seconds_per_step(record):
    events = sibling(__file__, "power_scan_ms").kernel_events(record, KERNEL)
    steps = (record.get("spans") or {}).get("serving.engine.step")
    if events is None or not steps:
        return None
    return sibling(__file__, "mhc_prefill_ms").median_inside(
        record, events, steps)


def read(record):
    secs = seconds_per_step(record)
    return None if secs is None else secs * 1e3
