"""Seconds of set-up spent loading executables from JAX's persistent
cache: the union of the program's ``executor.load.backend`` spans with
``cache="hit"`` that ended before the window opened."""

from benchmarks.lib import setup_spans

LAYER = "passes and plan cache, XLA compile and persistent cache"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    return setup_spans.of_sites(
        record, ("executor.load.backend",),
        keep=lambda ev: setup_spans.attr(ev, "cache") == "hit")
