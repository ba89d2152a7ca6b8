"""The chunked delta-rule scan's share of its roofline: the least time
the chip could take for the scans of the admissions of the traced stretch
(``closed_forms_delta.scan_roofline`` of each admission's prompt length:
the operations of THE TOKEN-BY-TOKEN RECURRENCE — decay, ``S^T k``, the
rank-one correction, ``S^T q``: ``7 Dk Dv`` a token and value head — over
the bf16 peak, against q, k, v, the gates, ``y`` and the final state over
the HBM peak, the larger, times the delta layers) over the time their
``delta_scan`` operations took (``delta_scan_ms``'s admissions). The
count is of the mathematics, the same work whatever chunk or triangular
solve implements it: the chunked kernel does more than it (the chunk's
products, the inverse by halves) and multiplies float32 operands at the
highest precision (six bfloat16 passes a product), so the share it can
reach is a few per cent. ``None`` where that reader finds nothing."""

from benchmarks.lib import closed_forms_delta
from benchmarks.lib.readers import sibling

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "serve_tok_s"
SOURCE = "device_trace"


def read(record):
    found = sibling(__file__, "delta_scan_ms").admissions(record)
    if not found:
        return None
    delta = record["facts"]["delta"]
    least = sum(closed_forms_delta.scan_roofline(
        delta["cfg"], plen, record["peaks"], delta["itemsize"])["seconds"]
        for plen, _secs in found)
    return 100.0 * least / sum(secs for _plen, secs in found)
