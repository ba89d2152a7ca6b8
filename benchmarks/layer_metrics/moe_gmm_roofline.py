"""The grouped matmuls' share of their roofline in a decode step: the
least time the chip could take for them (the larger of the pairs'
operations over the bf16 peak and every expert's bytes over the HBM peak,
``closed_forms_moe.gmm_step_roofline``; at 32 rows the bytes bound it)
over the measured ``moe_gmm_ms``."""

from benchmarks.lib import closed_forms_moe
from benchmarks.lib.readers import sibling

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "req_tok_ms_p50"
SOURCE = "device_trace"


def read(record):
    secs = sibling(__file__, "moe_gmm_ms").seconds_per_step(record)
    moe = record.get("facts", {}).get("moe")
    if not secs or not moe:
        return None
    least = closed_forms_moe.gmm_step_roofline(
        moe["cfg"], moe["rows"], moe["weight_itemsize"], record["peaks"])
    return 100.0 * least["seconds"] / secs
