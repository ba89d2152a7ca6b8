"""The flash kernels' share of their roofline: the least time the chip
could take for the attention of one train step (the larger of operations
over the bf16 peak and bytes over the HBM peak, from
``closed_forms.flash_train_roofline``; at S512 and head size 64 the
operations bound it) over the measured ``flash_ms.train``."""

from benchmarks.lib import closed_forms
from benchmarks.lib.readers import sibling

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "train_tok_s"
SOURCE = "device_trace"


def read(record):
    secs = sibling(__file__, "flash_ms.train").kernel_seconds_per_step(record)
    if not secs:
        return None
    f = record["facts"]
    least = closed_forms.flash_train_roofline(
        f["batch_per_chip"], f["n_head"], f["seq"], f["d_head"],
        f["n_layer"], 2, record["peaks"])
    return 100.0 * least["seconds"] / secs
