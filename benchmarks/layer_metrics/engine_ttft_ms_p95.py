"""Time to first token as the engine stamps it: the durations of the
program's ``serving.request.first_token`` spans (submit to the end of
the admission, when the first token exists on the host), 95th percentile
over the requests admitted in the window. From the engine's side: what a
client adds (its own scheduling) is not in it."""

from benchmarks.lib import program_spans

LAYER = "decode engine"
UNIT = "ms"
MOVES = "req_tok_ms_p95"
SOURCE = "program_span"


def read(record):
    return program_spans.percentile_ms(
        record, "serving.request.first_token", 95)
