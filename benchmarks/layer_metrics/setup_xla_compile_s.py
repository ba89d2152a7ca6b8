"""Seconds of set-up XLA spent compiling: the union of the program's
``executor.load.backend`` spans with ``cache`` ``miss`` or ``off`` that
ended before the window opened; 0 in a warm run."""

from benchmarks.lib import setup_spans

LAYER = "passes and plan cache, XLA compile and persistent cache"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    return setup_spans.of_sites(
        record, ("executor.load.backend",),
        keep=lambda ev: setup_spans.attr(ev, "cache") != "hit")
