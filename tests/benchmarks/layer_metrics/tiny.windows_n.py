"""A per-layer metric added from outside ``benchmarks/``: the harness
finds it by the name in the manifest. Tests only."""

LAYER = "tests only"
UNIT = "count"
MOVES = "train_tok_s"
SOURCE = "program_counter"


def read(record):
    return record["facts"].get("windows")
