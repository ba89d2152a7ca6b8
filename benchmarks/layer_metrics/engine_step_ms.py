"""One continuous-batching step as the engine's thread sees it (feeds,
dispatch, logits fetched, host sampling): the program's
``serving.engine.step`` spans, median."""

from benchmarks.lib.readers import span_median_ms

LAYER = "decode engine"
UNIT = "ms"
MOVES = "req_tok_ms_p50"
SOURCE = "program_span"
SITE = "serving.engine.step"


def read(record):
    return span_median_ms(record, SITE)
