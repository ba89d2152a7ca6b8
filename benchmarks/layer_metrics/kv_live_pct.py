"""The share of the key-value slabs' rows that a decode step's slots had
reached: over the window's decode steps, the lengths of the live slots
summed over ``b_max x max_len``, the rows the slabs hold and the composed
attention of the step reads, live or not
(``paddle_serving_positions_total{kind="live"|"held"}``, read at the two
edges of the window). A yardstick, not a target: one less this share is
what an attention over live lengths would leave unread. ``None`` for a
program without the counter."""

LAYER = "decode engine"
UNIT = "%"
MOVES = "req_tok_ms_p50"
SOURCE = "program_counter"


def read(record):
    seen = (record.get("counters") or {}).get("positions") or {}
    if not seen.get("held"):
        return None
    return 100.0 * seen.get("live", 0) / seen["held"]
