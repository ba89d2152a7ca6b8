"""The expanded latent attention's share of its roofline: the least time
the chip could take for the attention of one admission of the longest
prompt (``closed_forms_mla.mla_flash_roofline``: the causal (query, key)
pairs x 2 x (192 + 128) x 128 heads over the bf16 peak against the bytes
of q, k, v and o over the HBM peak, the larger, times the layers — the
true widths, whatever the kernel pads) over the measured
``mla_flash_ms``."""

from benchmarks.lib import closed_forms_mla
from benchmarks.lib.readers import sibling

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "serve_tok_s"
SOURCE = "device_trace"


def read(record):
    secs = sibling(__file__, "mla_flash_ms").seconds_per_admission(record)
    facts = record.get("facts") or {}
    if not secs or "mla" not in facts:
        return None
    least = closed_forms_mla.mla_flash_roofline(
        facts["mla"]["cfg"], facts["longest_prompt"],
        facts["mla"]["flash_itemsize"], record["peaks"])
    return 100.0 * least["seconds"] / secs
