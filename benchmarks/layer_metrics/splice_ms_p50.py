"""One admission's cache splice: the program's ``serving.engine.splice``
spans (the prefilled rows written into the slot of the big caches, one
donated dispatch), median."""

from benchmarks.lib import program_spans

LAYER = "decode engine"
UNIT = "ms"
MOVES = "req_tok_ms_p95"
SOURCE = "program_span"


def read(record):
    return program_spans.median_ms(record, "serving.engine.splice")
