"""Host time of a decode step that no child span names: the
``serving.engine.step`` span less its direct children (feeds, the
Executor's call, sample), median. What the Executor's call leaves
unnamed is inside ``executor.call``, a child, and so not here."""

from benchmarks.lib import program_spans

LAYER = "decode engine"
UNIT = "ms"
MOVES = "req_tok_ms_p50"
SOURCE = "program_span"


def read(record):
    return program_spans.self_median_ms(record, "serving.engine.step")
