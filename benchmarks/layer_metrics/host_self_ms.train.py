"""Host time of a train call that no phase span names: the
``executor.call`` span less its children (gather, place, dispatch,
complete, write_back), median over the window's calls."""

from benchmarks.lib import program_spans

LAYER = "host dispatch"
UNIT = "ms"
MOVES = "train_tok_s"
SOURCE = "program_span"


def read(record):
    return program_spans.self_median_ms(record, "executor.call")
