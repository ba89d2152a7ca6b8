"""The state update's share of its roofline in a decode step: the least
time the chip could take for the updates of all the state-space layers
over the step's ``b_max`` slots (``closed_forms_ssm.update_roofline``:
the state read and written once, the token's ``x``, ``B``, ``C``, ``dt``
in and ``y`` out, over the HBM peak — five operations a value of state
leave it bound by memory) over the measured ``ssm_step_ms``. The padded
tiles the kernel reads beside the state (its rows and columns) are not
counted: they are the kernel's own cost."""

from benchmarks.lib import closed_forms_ssm
from benchmarks.lib.readers import sibling

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "req_tok_ms_p50"
SOURCE = "device_trace"


def read(record):
    secs = sibling(__file__, "ssm_step_ms").seconds_per_step(record)
    facts = record.get("facts") or {}
    if not secs or "ssm" not in facts:
        return None
    least = closed_forms_ssm.update_roofline(
        facts["ssm"]["cfg"], facts["b_max"], record["peaks"],
        facts["ssm"]["itemsize"])
    return 100.0 * least["seconds"] / secs
