"""Backend stages of set-up in which a plan loaded its program AGAIN:
the program's ``executor.load.backend`` spans with ``nth`` >= 2 (the
dispatch span says why: ``uncommitted``, ``resharded``)."""

from benchmarks.lib import setup_spans

LAYER = "passes and plan cache, XLA compile and persistent cache"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    stages = setup_spans.backend(record)
    if stages is None:
        return None
    return sum(1 for ev in stages if setup_spans.attr(ev, "nth", 1) >= 2)
