"""What the delta layers' caches hold: the bytes of every slot's state
and convolution rows in every delta layer, as the engine counted them
where it built its caches (``paddle_delta_state_bytes``: what its decode
program's ``delta_update`` and the convolution in front of it read and
write), in GB. Constant in the sequences' lengths: 19.8 MB a slot at the
published widths and nine layers, beside the slabs of the attention
layers that do grow. ``None`` for a program without the gauge or a model
without such a layer."""

LAYER = "decode engine"
UNIT = "GB"
MOVES = "serve_tok_s"
SOURCE = "program_counter"


def read(record):
    nbytes = (record.get("counters") or {}).get("delta_state_bytes")
    return None if not nbytes else nbytes / 1e9
