"""What several per-layer readers share."""

import os

from benchmarks.lib.manifest import load_path
from benchmarks.lib.stats import median


def sibling(reader_file, name):
    """Another reader of the same directory, loaded by path."""
    return load_path(os.path.join(
        os.path.dirname(os.path.abspath(reader_file)), name + ".py"))


def span_median_ms(record, site):
    """Median duration, in ms, of the program's flight-recorder spans of
    one site that ended in the window; None where none was recorded."""
    spans = record.get("spans", {}).get(site)
    if not spans:
        return None
    return median([dur for _end, dur in spans]) * 1e3
