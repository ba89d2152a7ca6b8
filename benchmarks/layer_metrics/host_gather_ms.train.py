"""Host time of a train window spent gathering the call's arguments: the
program's ``executor.gather`` spans (plan-cache lookup, the scope lookup
of every state variable, feed conversion; the ``executor.h2d`` transfer
nests in it and is included), median over the window's calls."""

from benchmarks.lib import program_spans

LAYER = "host dispatch"
UNIT = "ms"
MOVES = "train_tok_s"
SOURCE = "program_span"


def read(record):
    return program_spans.median_ms(record, "executor.gather",
                                   inside="executor.call")
