"""Mean share of the ``b_max`` slots that held a live sequence, over the
decode steps of the window (``paddle_serving_slot_occupancy_ratio``)."""

LAYER = "decode engine"
UNIT = "%"
MOVES = "serve_tok_s"
SOURCE = "program_counter"


def read(record):
    occ = record.get("counters", {}).get("occupancy_mean")
    return None if occ is None else 100.0 * occ
