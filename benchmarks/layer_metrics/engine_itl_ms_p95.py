"""Gap between consecutive tokens of a request as the engine produced
them, 95th percentile over every gap of every request admitted in the
window. The engine keeps no per-token event; a request's emission times
are rebuilt from the end of its ``serving.engine.admit`` span (first
token) and the ends of the ``serving.engine.step`` spans that list its
trace among their riders (``program_spans.token_times``). Read only
where the program stamps ``serving.request.first_token``, so that this
and ``engine_ttft_ms_p95`` appear together."""

from benchmarks.lib import program_spans
from benchmarks.lib.stats import percentile

LAYER = "decode engine"
UNIT = "ms"
MOVES = "req_tok_ms_p95"
SOURCE = "program_span"


def read(record):
    if program_spans.durations_ms(
            record, "serving.request.first_token") is None:
        return None
    gaps = program_spans.token_gaps_ms(record)
    return None if gaps is None else percentile(gaps, 95)
