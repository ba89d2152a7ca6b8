"""Host time of a train window spent placing feeds, state and the RNG key
onto their shardings before a mesh dispatch: the program's
``executor.place`` spans of ``ParallelEngine._execute`` (one
``jax.device_put`` an array, whether or not it moves), median over the
window's calls."""

from benchmarks.lib import program_spans

LAYER = "host dispatch"
UNIT = "ms"
MOVES = "train_tok_s"
SOURCE = "program_span"


def read(record):
    return program_spans.median_ms(record, "executor.place",
                                   inside="executor.call")
