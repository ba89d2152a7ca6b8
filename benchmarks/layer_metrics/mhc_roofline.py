"""The residual path's kernels' share of their roofline: the least time
the chip could take for the ``mhc_pre`` and ``mhc_post`` calls of one
admission of the longest prompt (``closed_forms_mhc.mhc_roofline``: per
row and sub-block the stream read twice and written once, the mixed
vector, the sub-block's output and the 24 coefficients, ``(3 n + 2) C +
2 n (n + 2)`` float32 values, and each sub-block's ``phi`` once, over the
HBM peak — both ops are bound by memory) over the measured
``mhc_prefill_ms``. ``None`` where that is: a composed plan has no
operation under the kernels' names, and reports nothing rather than a
guess."""

from benchmarks.lib import closed_forms_mhc
from benchmarks.lib.readers import sibling

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "serve_tok_s"
SOURCE = "device_trace"


def read(record):
    secs = sibling(__file__, "mhc_prefill_ms").seconds_per_admission(record)
    mhc = (record.get("facts") or {}).get("mhc")
    if not secs or not mhc:
        return None
    least = closed_forms_mhc.mhc_roofline(
        mhc["cfg"], record["facts"]["longest_prompt"], mhc["itemsize"],
        mhc["phi_itemsize"], record["peaks"])
    return 100.0 * least["seconds"] / secs
