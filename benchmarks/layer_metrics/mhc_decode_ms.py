"""Device time of the residual path's two kernels in one decode step: for
every ``serving.engine.step`` span of the traced stretch, the summed time
of the first chip's operations that started inside the span and whose
name holds ``mhc_pre`` or ``mhc_post`` (``paddle_tpu/kernels/mhc.py``:
one of each a sub-block, two sub-blocks a layer, over the step's
``b_max`` rows) — median over the steps. ``None`` where the record is not
of a cell with residual streams or the traced stretch holds no operation
of either name (a composed plan)."""

from benchmarks.lib.readers import sibling

LAYER = "Pallas kernels"
UNIT = "ms"
MOVES = "req_tok_ms_p50"
SOURCE = "device_trace"


def seconds_per_step(record):
    prefill = sibling(__file__, "mhc_prefill_ms")
    events = prefill.kernel_events(record)
    steps = (record.get("spans") or {}).get("serving.engine.step")
    if events is None or not steps:
        return None
    return prefill.median_inside(record, events, steps)


def read(record):
    secs = seconds_per_step(record)
    return None if secs is None else secs * 1e3
