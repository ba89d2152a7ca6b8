"""Seconds of set-up spent tracing, lowering and loading programs a
second time: the union of the ``executor.load.*`` spans of every dispatch
whose backend stage had ``nth`` >= 2 — what loading each plan once would
give back."""

from benchmarks.lib import setup_spans

LAYER = "passes and plan cache, XLA compile and persistent cache"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    spans = setup_spans.reloaded(record)
    return None if spans is None else setup_spans.union_s(spans)
