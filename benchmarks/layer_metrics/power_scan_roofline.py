"""The chunked retention scan's share of its roofline: the least time the
chip could take for the scans of the admissions of the traced stretch
(``closed_forms_power.scan_roofline`` of each admission's prompt length
at the chunk ``Q`` its length gives, ``closed_forms_power.scan_chunk``:
per position and head ``4 Q d`` operations inside the chunk and, past
the first chunk, ``2 PAIRS d`` for the state read, per key-value head
``2 PAIRS d`` for the state fed, over the bf16 peak, against q, ``y``, k, v, the gate and the final state over
the HBM peak, the larger, times the retention layers; the EXACT 8,256
pairs a head) over the time their ``power_scan`` operations took
(``power_scan_ms``'s admissions). The kernel multiplies float32 operands
at the highest precision (six bfloat16 passes a product) and keeps 9,216
rows in blocks of 16-128 for the 8,256, so the share of the bf16 peak it
can reach is under a sixth. ``None`` where that reader finds nothing."""

from benchmarks.lib import closed_forms_power
from benchmarks.lib.readers import sibling

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "serve_tok_s"
SOURCE = "device_trace"


def read(record):
    found = sibling(__file__, "power_scan_ms").admissions(record)
    if not found:
        return None
    power = record["facts"]["power"]
    least = sum(closed_forms_power.scan_roofline(
        power["cfg"], plen, record["peaks"], power["itemsize"])["seconds"]
        for plen, _secs in found)
    return 100.0 * least / sum(secs for _plen, secs in found)
