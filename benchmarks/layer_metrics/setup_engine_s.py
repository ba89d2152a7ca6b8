"""Seconds of set-up the serving engine spent on its own: building its
programs (``serving.engine.build``: decode, one prefill a prompt length,
the footprint's two) and placing the given weights
(``serving.engine.load_params``), union."""

from benchmarks.lib import setup_spans

LAYER = "decode engine"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(record):
    return setup_spans.of_sites(record, setup_spans.ENGINE)
