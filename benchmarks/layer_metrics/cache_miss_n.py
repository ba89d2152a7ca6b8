"""Programs JAX had to compile and write to its persistent cache in this
run (``/jax/compilation_cache/cache_misses``); 0 in a warm run."""

LAYER = "passes and plan cache, XLA compile and persistent cache"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(record):
    return (record["setup_counts"]["cache_misses"]
            + record["window_counts"]["cache_misses"])
