"""The absorbed latent attention's share of its roofline: the least time
the chip could take for one step's ``mla_decode`` calls
(``closed_forms_mla.mla_decode_roofline``: per visible cache row and head
2 x (576 + 512) operations over the bf16 peak against the row's bytes,
read once as key and value both, over the HBM peak, the larger, times the
layers) over the measured ``mla_decode_ms``. The rows are those the
window's decode steps saw on average, summed over the slots
(``facts.mla.rows_visible_mean``): the kernel walks a slot's rows up to
its position, so the whole slab would count bytes no step has to read."""

from benchmarks.lib import closed_forms_mla
from benchmarks.lib.readers import sibling

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "req_tok_ms_p50"
SOURCE = "device_trace"


def read(record):
    secs = sibling(__file__, "mla_decode_ms").seconds_per_step(record)
    mla = (record.get("facts") or {}).get("mla")
    if not secs or not mla or not mla.get("rows_visible_mean"):
        return None
    least = closed_forms_mla.mla_decode_roofline(
        mla["cfg"], mla["rows_visible_mean"], mla["cache_itemsize"],
        record["peaks"])
    return 100.0 * least["seconds"] / secs
