"""What the state-space layers' caches hold: the bytes of every slot's
recurrent state and convolution rows, as the engine counted them where
it built its caches (``paddle_serving_cache_bytes{kind="state"}``), in
GB. Constant in the sequences' lengths: it is what lets many sequences
decode together, and what every decode step reads and writes once.
``None`` for a program without the series or a model without such a
layer."""

LAYER = "decode engine"
UNIT = "GB"
MOVES = "serve_tok_s"
SOURCE = "program_counter"


def read(record):
    nbytes = (record.get("counters") or {}).get("state_cache_bytes")
    return None if not nbytes else nbytes / 1e9
