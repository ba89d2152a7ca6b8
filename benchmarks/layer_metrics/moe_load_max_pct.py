"""How uneven the routing was: the busiest expert's (token, expert) pairs
over the mean of its layer's experts, in percent, the largest over the
layers (100 is a perfectly even load). Read from the device-side tally
the decode step adds to (``DecodeEngine.routed_pairs``), as the
difference between the end of warm-up and the end of the drain: the ramp,
the window and the drain, decode steps only. ``None`` where the engine
has no tally."""

LAYER = "expert routing"
UNIT = "%"
MOVES = "serve_tok_s"
SOURCE = "program_counter"


def read(record):
    routed = record.get("counters", {}).get("routed_pairs")
    if not routed:
        return None
    worst = None
    for row in routed:
        total = sum(row)
        if total:
            share = 100.0 * max(row) * len(row) / total
            worst = share if worst is None else max(worst, share)
    return worst
