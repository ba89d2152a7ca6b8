"""Seconds JAX spent tracing, lowering, compiling and loading programs
from its persistent cache during set-up (``jax.monitoring``)."""

LAYER = "passes and plan cache, XLA compile and persistent cache"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(record):
    return record["setup_counts"]["compile_s"]
