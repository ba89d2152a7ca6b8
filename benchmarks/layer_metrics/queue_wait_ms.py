"""Time a request waited in the admission queue: the program's
``serving.queue.wait`` flight-recorder spans that ended in the window,
median."""

from benchmarks.lib.readers import span_median_ms

LAYER = "request queue"
UNIT = "ms"
MOVES = "req_tok_ms_p95"
SOURCE = "program_span"
SITE = "serving.queue.wait"


def read(record):
    return span_median_ms(record, SITE)
