"""The chunked scan's share of its roofline: the least time the chip
could take for the scans of the admissions of the traced stretch
(``closed_forms_ssm.scan_roofline`` of each admission's prompt length:
per position and head ``2 Q P + 4 N P`` operations and per group ``2 Q
N`` over the bf16 peak, against ``x``, ``y``, ``B``, ``C``, ``dt`` and
the final state over the HBM peak, the larger, times the state-space
layers) over the time their ``ssm_scan`` operations took
(``ssm_scan_ms``'s admissions). The kernel multiplies float32 operands at
the highest precision (six bfloat16 passes a product), so the share of
the bf16 peak it can reach is a sixth. ``None`` where that reader finds
nothing."""

from benchmarks.lib import closed_forms_ssm
from benchmarks.lib.readers import sibling

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "serve_tok_s"
SOURCE = "device_trace"


def read(record):
    found = sibling(__file__, "ssm_scan_ms").admissions(record)
    if not found:
        return None
    ssm = record["facts"]["ssm"]
    least = sum(closed_forms_ssm.scan_roofline(
        ssm["cfg"], plen, record["peaks"], ssm["itemsize"])["seconds"]
        for plen, _secs in found)
    return 100.0 * least / sum(secs for _plen, secs in found)
