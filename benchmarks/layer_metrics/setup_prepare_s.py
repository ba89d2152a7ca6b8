"""Seconds of set-up on the plan-cache miss path (verification, the pass
pipeline, block analysis): the union of the program's
``executor.prepare`` spans that ended before the window opened."""

from benchmarks.lib import setup_spans

LAYER = "passes and plan cache, XLA compile and persistent cache"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    return setup_spans.of_sites(record, ("executor.prepare",))
