"""What the mamba layers' caches hold: the bytes of every slot's state
and convolution rows in every mamba layer, as the engine counted them
where it built its caches (``paddle_mamba_state_bytes``: what its decode
program's ``mamba_update`` and the convolution in front of it read and
write), in GB. Constant in the sequences' lengths: 10.1 MB a slot at the
published widths and 26 layers, beside the slabs of the two attention
layers that do grow. ``None`` for a program without the gauge or a model
without such a layer."""

LAYER = "decode engine"
UNIT = "GB"
MOVES = "serve_tok_s"
SOURCE = "program_counter"


def read(record):
    nbytes = (record.get("counters") or {}).get("mamba_state_bytes")
    return None if not nbytes else nbytes / 1e9
