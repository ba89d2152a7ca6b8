"""One admission (prefill, first-token sample, splice into the slot),
during which the decode loop stands still: the program's
``serving.engine.admit`` spans, median."""

from benchmarks.lib.readers import span_median_ms

LAYER = "decode engine"
UNIT = "ms"
MOVES = "req_tok_ms_p95"
SOURCE = "program_span"
SITE = "serving.engine.admit"


def read(record):
    return span_median_ms(record, SITE)
