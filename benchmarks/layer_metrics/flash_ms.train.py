"""Device time per train step inside the Pallas attention kernels: the
summed durations of the ``custom-call`` operations (a Pallas kernel is
one: ``tpu_custom_call``) on the first chip. 0 where attention is
composed (S < 256)."""

LAYER = "Pallas kernels"
UNIT = "ms"
MOVES = "train_tok_s"
SOURCE = "device_trace"
KERNEL_OPCODE = "custom-call"


def kernel_seconds_per_step(record):
    trace, f = record.get("trace"), record["facts"]
    if trace is None or not f["windows_traced"]:
        return None
    events = trace["ops"][min(trace["ops"])]
    steps = f["windows_traced"] * f["steps_per_window"]
    return sum(e[2] for e in events if e[3] == KERNEL_OPCODE) / steps


def read(record):
    secs = kernel_seconds_per_step(record)
    return None if secs is None else secs * 1e3
