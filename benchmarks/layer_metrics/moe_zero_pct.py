"""The share of the routed pairs that cost nothing: of the (token,
expert) pairs the decode steps between the end of warm-up and the end of
the drain routed, over the expert branches, those that chose an IDENTITY
(zero-compute) expert — from the device-side tally the decode step keeps
beside its routed pairs (``DecodeEngine.zero_pairs``, column 0; the
routed-pairs rows hold the experts with weights). The even share is
``n_zero_expert / (n_expert + n_zero_expert)``, a third for LongCat-Flash
(256 of 768: the published average is 4 of a token's 12). What a step
streams and computes follows the rest; a change that moves this share has
changed the routing, not sped it up. ``None`` where the engine has no
such tally (a model without identity experts, or a program from before
it)."""

LAYER = "expert routing"
UNIT = "%"
MOVES = "req_tok_ms_p50"
SOURCE = "program_counter"


def read(record):
    return (record.get("counters") or {}).get("zero_pairs_pct")
