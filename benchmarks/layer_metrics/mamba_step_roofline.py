"""The Mamba-1 update's share of its roofline in a decode step: the least
time the operations of the ``mamba_update`` ops of all the mamba layers
can take over the step's ``b_max`` slots
(``closed_forms_mamba.update_roofline``: what nothing but the vector unit
can do of the recurrence, ``6 C N + 2 C`` operations a slot and layer,
over THE VECTOR UNIT'S PEAK, the floor ``mamba_scan_roofline`` is held
against too) over the measured ``mamba_step_ms``. Not the state's bytes
over the HBM peak, ``ssm_step_roofline``'s convention: at 10 MB a layer
XLA hands most of these kernels their state in VMEM, moved by async
copies that run under the step's other operations, so no time that can
be laid at the update's door holds that traffic and a share of the byte
term read 213% (``decode_bw_pct`` holds the step's bytes whole)."""

from benchmarks.lib import closed_forms_mamba
from benchmarks.lib.readers import sibling

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "req_tok_ms_p50"
SOURCE = "device_trace"


def read(record):
    secs = sibling(__file__, "mamba_step_ms").seconds_per_step(record)
    facts = record.get("facts") or {}
    if not secs or "mamba" not in facts:
        return None
    least = closed_forms_mamba.update_roofline(
        facts["mamba"]["cfg"], facts["b_max"], record["peaks"],
        facts["mamba"]["itemsize"])
    return 100.0 * least["seconds"] / secs
