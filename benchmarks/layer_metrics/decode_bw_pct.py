"""The decode step's share of the HBM peak: the bytes one step must
stream (every weight once and both cache slabs of all ``b_max`` slots,
from shapes: ``closed_forms.gpt_decode_step_bytes``) over the published
bytes per second, over the measured ``decode_dev_ms``. The step is
bound by memory: at 32 rows its matmuls are far under the compute peak."""

from benchmarks.lib.readers import sibling

LAYER = "model step on the device"
UNIT = "%"
MOVES = "req_tok_ms_p50"
SOURCE = "device_trace"


def read(record):
    ms = sibling(__file__, "decode_dev_ms").read(record)
    if not ms:
        return None
    least = record["facts"]["decode_step_bytes"]["total"] \
        / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (ms * 1e-3)
