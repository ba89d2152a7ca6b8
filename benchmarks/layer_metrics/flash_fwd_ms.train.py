"""Device time per train step of the flash attention forward kernel in the
forward ops: the operations of the first chip whose name holds
``flash_fwd``, the name ``paddle_tpu/ops/attention.py`` gives that Pallas
call (``KERNEL_FWD``)."""

from benchmarks.lib import program_spans

LAYER = "Pallas kernels"
UNIT = "ms"
MOVES = "train_tok_s"
SOURCE = "device_trace"
KERNELS = ("flash_fwd",)


def read(record):
    secs = program_spans.kernel_seconds_per_step(record, KERNELS)
    return None if secs is None else secs * 1e3
