"""How many of the experts this chip holds a decode step reaches: the
mean, over the expert layers and the decode steps between the end of
warm-up and the end of the drain, of the share of held experts that were
given at least one (token, expert) pair — from the device-side tally the
decode step adds to beside its routed pairs
(``DecodeEngine.experts_touched``). The grouped matmul fetches no weights
for an empty group, so this share is the share of the held experts' bytes
a step streams. ``None`` where the engine has no such tally (every expert
held, or a program from before it)."""

LAYER = "expert routing"
UNIT = "%"
MOVES = "req_tok_ms_p50"
SOURCE = "program_counter"


def read(record):
    counters = record.get("counters", {})
    mean, held = counters.get("experts_touched_mean"), \
        counters.get("experts_held")
    if mean is None or not held:
        return None
    return 100.0 * mean / held
