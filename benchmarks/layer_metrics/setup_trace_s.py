"""Seconds of set-up JAX spent tracing functions to jaxprs: the union of
the program's ``executor.load.trace`` spans that ended before the window
opened (a function traced inside another's trace is counted once)."""

from benchmarks.lib import setup_spans

LAYER = "passes and plan cache, XLA compile and persistent cache"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    return setup_spans.of_sites(record, ("executor.load.trace",))
