"""Device time of the delta-rule update in one decode step: for every
``serving.engine.step`` span of the traced stretch, the summed time of
the first chip's operations that started inside the span and whose name
holds ``delta_update`` — the name ``paddle_tpu/kernels/delta.py`` gives
the Pallas call that reads every slot's state, corrects it from its own
``S^T k``, writes it back in place and reads ``S^T q`` out of the new
state, one a delta layer — median over the steps. ``None`` where the
record is not of a cell with delta layers or the traced stretch holds no
operation of that name (a composed plan)."""

from benchmarks.lib.readers import sibling

LAYER = "Pallas kernels"
UNIT = "ms"
MOVES = "req_tok_ms_p50"
SOURCE = "device_trace"
KERNEL = "delta_update"


def seconds_per_step(record):
    events = sibling(__file__, "delta_scan_ms").kernel_events(record, KERNEL)
    steps = (record.get("spans") or {}).get("serving.engine.step")
    if events is None or not steps:
        return None
    return sibling(__file__, "mhc_prefill_ms").median_inside(
        record, events, steps)


def read(record):
    secs = seconds_per_step(record)
    return None if secs is None else secs * 1e3
