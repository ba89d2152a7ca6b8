"""The retention update's share of its roofline in a decode step: the
least time the chip could take for the updates of all the retention
layers over the step's ``b_max`` slots
(``closed_forms_power.update_roofline``: the state and the normaliser
read and written once at the EXACT 8,256 pairs a key-value head, the
token's q, k, v and gate in and ``y`` out, over the HBM peak — three
operations a value of state and two a value and query head leave it bound
by memory, ``ssm_step_roofline``'s convention) over the measured
``power_step_ms``. What the kernel keeps beyond the exact pairs (9,216
rows, a ``[d, d]`` normaliser) and the token's padded tiles are not
counted: they are the kernel's own cost. The form counts ONE WRITE OF
THE STATE A TOKEN (PERF.md section 7)."""

from benchmarks.lib import closed_forms_power
from benchmarks.lib.readers import sibling

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "req_tok_ms_p50"
SOURCE = "device_trace"


def read(record):
    secs = sibling(__file__, "power_step_ms").seconds_per_step(record)
    facts = record.get("facts") or {}
    if not secs or "power" not in facts:
        return None
    least = closed_forms_power.update_roofline(
        facts["power"]["cfg"], facts["b_max"], record["peaks"],
        facts["power"]["itemsize"])
    return 100.0 * least["seconds"] / secs
