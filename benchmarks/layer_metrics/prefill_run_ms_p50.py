"""One admission's prefill run: the program's ``serving.engine.prefill``
spans (the prefill executable of the prompt's length, logits fetched),
median. ``prefill_ms_p50`` times the whole admission round it."""

from benchmarks.lib import program_spans

LAYER = "decode engine"
UNIT = "ms"
MOVES = "req_tok_ms_p95"
SOURCE = "program_span"


def read(record):
    return program_spans.median_ms(record, "serving.engine.prefill")
