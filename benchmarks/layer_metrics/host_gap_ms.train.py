"""Host dispatch: wall time of a train window minus the time the device
was busy in it — the median window's wall time over the traced windows,
less the traced busy time per window."""

from benchmarks.lib.stats import median

LAYER = "host dispatch"
UNIT = "ms"
MOVES = "train_tok_s"
SOURCE = "device_trace"


def read(record):
    trace, n = record.get("trace"), record["facts"]["windows_traced"]
    if trace is None or not n:
        return None
    wall = median(record["samples"]["window_wall_s"][:n])
    return (wall - trace["busy_mean_s"] / n) * 1e3
