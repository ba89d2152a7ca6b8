"""The grouped-head flash forward's share of its roofline: the least time
the chip could take for the attention of one admission of the longest
prompt (``closed_forms_conv.gqa_flash_roofline``: the causal (query, key)
pairs x 4 x 64 x 32 query heads over the bf16 peak against the bytes of
q and o at 32 heads and k and v at 8 over the HBM peak, the larger, times
the attention layers) over the measured ``gqa_flash_ms``."""

from benchmarks.lib import closed_forms_conv
from benchmarks.lib.readers import sibling

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "serve_tok_s"
SOURCE = "device_trace"


def read(record):
    secs = sibling(__file__, "gqa_flash_ms").seconds_per_admission(record)
    facts = record.get("facts") or {}
    if not secs or "gqa_flash" not in facts:
        return None
    least = closed_forms_conv.gqa_flash_roofline(
        facts["gqa_flash"]["cfg"], facts["longest_prompt"],
        facts["gqa_flash"]["itemsize"], record["peaks"])
    return 100.0 * least["seconds"] / secs
