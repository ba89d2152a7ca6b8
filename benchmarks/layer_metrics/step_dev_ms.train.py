"""Device busy time per train step: the union of the intervals in which
an operation ran, mean over the chips, over the traced steps."""

LAYER = "device step"
UNIT = "ms"
MOVES = "train_tok_s"
SOURCE = "device_trace"


def read(record):
    trace, f = record.get("trace"), record["facts"]
    if trace is None or not f["windows_traced"]:
        return None
    steps = f["windows_traced"] * f["steps_per_window"]
    return trace["busy_mean_s"] / steps * 1e3
