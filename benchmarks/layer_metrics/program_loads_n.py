"""Programs JAX made executable during set-up (compiled, or loaded from
the persistent cache): the program's ``executor.load.backend`` spans that
ended before the window opened."""

from benchmarks.lib import setup_spans

LAYER = "passes and plan cache, XLA compile and persistent cache"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    stages = setup_spans.backend(record)
    return None if stages is None else len(stages)
