"""Peak device memory of the fullest chip: the runtime's
``peak_bytes_in_use`` + ``peak_bytes_reserved`` (buffers + the programs'
temporaries, see ``benchmarks/run.py``), in GB (1e9 bytes)."""

LAYER = "device"
UNIT = "GB"
MOVES = "train_tok_s"
SOURCE = "program_counter"


def read(record):
    peak = record.get("memory_peak_bytes")
    return None if peak is None else peak / 1e9
