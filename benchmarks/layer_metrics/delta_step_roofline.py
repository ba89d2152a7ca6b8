"""The delta-rule update's share of its roofline in a decode step: the
least time the chip could take for the updates of all the delta layers
over the step's ``b_max`` slots (``closed_forms_delta.update_roofline``:
the state read and written once, the token's q, k, v and gates in and
``y`` out, over the HBM peak — seven operations a value of state leave it
bound by memory, ``ssm_step_roofline``'s convention) over the measured
``delta_step_ms``. The token's padded tiles (rows ``[3 Hv, Dv]``, columns
``[Dk, 128]`` a slot) are not counted: they are the kernel's own cost;
nor are the convolution's carried rows, which the step shifts outside the
kernel (``decode_mixer_ms`` holds that time)."""

from benchmarks.lib import closed_forms_delta
from benchmarks.lib.readers import sibling

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "req_tok_ms_p50"
SOURCE = "device_trace"


def read(record):
    secs = sibling(__file__, "delta_step_ms").seconds_per_step(record)
    facts = record.get("facts") or {}
    if not secs or "delta" not in facts:
        return None
    least = closed_forms_delta.update_roofline(
        facts["delta"]["cfg"], facts["b_max"], record["peaks"],
        facts["delta"]["itemsize"])
    return 100.0 * least["seconds"] / secs
