"""Host time of handing one K-step executable to the runtime: the
program's ``executor.dispatch`` spans, median over the window's calls."""

from benchmarks.lib import program_spans

LAYER = "host dispatch"
UNIT = "ms"
MOVES = "train_tok_s"
SOURCE = "program_span"


def read(record):
    return program_spans.median_ms(record, "executor.dispatch",
                                   inside="executor.call")
