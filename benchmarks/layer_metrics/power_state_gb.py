"""What the retention layers' caches hold: the bytes of every slot's
state and normaliser in every retention layer, as the engine counted
them where it built its caches (``paddle_power_state_bytes``: the rows
AS KEPT, 9,216 a key-value head and a ``[d, d]`` normaliser), in GB.
Constant in the sequences' lengths: it is what lets 32 sequences of
8,192 and more positions decode together where their keys and values
would not fit, and what every decode step reads and writes once. ``None``
for a program without the gauge or a model without such a layer."""

LAYER = "decode engine"
UNIT = "GB"
MOVES = "serve_tok_s"
SOURCE = "program_counter"


def read(record):
    nbytes = (record.get("counters") or {}).get("power_state_bytes")
    return None if not nbytes else nbytes / 1e9
