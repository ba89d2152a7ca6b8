"""What the gated convolution layers' caches hold: the bytes of every
slot's carried rows, as the engine counted them where it built its caches
(``paddle_serving_cache_bytes{kind="state"}``), in MB (10^6 bytes).
Constant in the sequences' lengths — 4 layers x 64 slots x 2 rows x 2,048
float32 values read 4.19 in ``lfm2_serve_long_ctx`` — and a guard of that
constant: a cache that grew a position axis would read a thousand times
more. ``None`` for a program without the series or a model without such
a layer."""

LAYER = "decode engine"
UNIT = "MB"
MOVES = "serve_tok_s"
SOURCE = "program_counter"


def read(record):
    nbytes = (record.get("counters") or {}).get("state_cache_bytes")
    return None if not nbytes else nbytes / 1e6
