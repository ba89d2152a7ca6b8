"""Device time per train step in collectives (all-reduce, all-gather,
reduce-scatter and their kin, synchronous or asynchronous) on the first
chip: the union of their intervals."""

from benchmarks.lib import xplane

LAYER = "SPMD engine"
UNIT = "ms"
MOVES = "train_tok_s"
SOURCE = "device_trace"


def read(record):
    trace, f = record.get("trace"), record["facts"]
    if trace is None or not f["windows_traced"]:
        return None
    events = trace["ops"][min(trace["ops"])]
    steps = f["windows_traced"] * f["steps_per_window"]
    coll = xplane.collectives(events) + xplane.collectives(
        trace["async_ops"].get(min(trace["ops"]), []))
    return xplane.busy_seconds(coll) / steps * 1e3
