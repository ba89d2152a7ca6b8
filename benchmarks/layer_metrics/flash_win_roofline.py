"""The banded flash forward's share of its roofline: the least time the
chip could take for the windowed attention of one admission of the
longest prompt (``closed_forms_afmoe.flash_win_roofline``: the visible
(query, key) pairs x 4 x head size x query heads over the bf16 peak
against the bytes of q, k, v and o over the HBM peak, the larger, times
the sliding layers) over the measured ``flash_win_ms``."""

from benchmarks.lib import closed_forms_afmoe
from benchmarks.lib.readers import sibling

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "serve_tok_s"
SOURCE = "device_trace"


def read(record):
    secs = sibling(__file__, "flash_win_ms").seconds_per_admission(record)
    facts = record.get("facts", {})
    if not secs or "flash_win" not in facts:
        return None
    least = closed_forms_afmoe.flash_win_roofline(
        facts["flash_win"]["cfg"], facts["longest_prompt"],
        facts["flash_win"]["itemsize"], record["peaks"])
    return 100.0 * least["seconds"] / secs
