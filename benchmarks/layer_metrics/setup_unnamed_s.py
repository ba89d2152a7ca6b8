"""Seconds of set-up under no span of the program at all: ``setup_s``
less the union of every program span that ended before the window
opened — imports, backend start, and the benchmark's own batches,
weights and ramp."""

from benchmarks.lib import setup_spans

LAYER = "passes and plan cache, XLA compile and persistent cache"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    return setup_spans.unnamed_s(record)
