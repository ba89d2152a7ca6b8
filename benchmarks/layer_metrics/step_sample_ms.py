"""Host time of a decode step spent sampling a token for every rider and
retiring the finished ones: the program's ``serving.engine.sample``
spans inside ``serving.engine.step`` (an admission's first-token sample
is not among them), median."""

from benchmarks.lib import program_spans

LAYER = "decode engine"
UNIT = "ms"
MOVES = "req_tok_ms_p50"
SOURCE = "program_span"


def read(record):
    return program_spans.median_ms(record, "serving.engine.sample",
                                   inside="serving.engine.step")
