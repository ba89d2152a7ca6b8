"""The Mamba-1 selective scan's share of its roofline: the least time the
chip could take for the scans of one admission of the longest prompt of
the traced stretch (``closed_forms_mamba.scan_roofline`` at that length,
all mamba layers) over the time the operations under the program's op
``mamba_scan`` took in it (``mamba_scan_ms``: the kernel and the relayout
round it). The least time is the largest of three: the operations of THE
TOKEN-BY-TOKEN RECURRENCE (``9 C N`` a token and layer, ISSUE 58's count)
over the bf16 peak; the operations of it that nothing but the vector unit
can do (``6 C N + 2 C``: the exponential has a slot of its own) over THE
VECTOR UNIT'S PEAK, the floor ``mamba_step_roofline`` is held against
too; and the fewest bytes any form must move (u in and ``y`` out at ``C``
wide, ``B``, ``C_t``, the ``R``-wide delta, the last state once) over the
HBM peak. The counts are of the mathematics, the same whatever tile,
block or fusion implements it, so the share cannot pass 100%; the vector
term is the bound. ``None`` where that reader finds nothing."""

from benchmarks.lib import closed_forms_mamba
from benchmarks.lib.readers import sibling

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "serve_tok_s"
SOURCE = "device_trace"


def read(record):
    scan = sibling(__file__, "mamba_scan_ms")
    got = scan.op_seconds(record, scan.SITE, scan.OP)
    if got is None or not got["prompt_len"]:
        return None
    mamba = record["facts"]["mamba"]
    least = closed_forms_mamba.scan_roofline(
        mamba["cfg"], got["prompt_len"], record["peaks"], mamba["itemsize"])
    return 100.0 * least["seconds"] / got["seconds"]
