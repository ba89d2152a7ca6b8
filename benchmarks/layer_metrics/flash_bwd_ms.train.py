"""Device time per train step of the two flash attention backward kernels
(dK/dV and dQ): the operations of the first chip whose name holds
``flash_bwd_dkv`` or ``flash_bwd_dq`` (``KERNEL_BWD_DKV``,
``KERNEL_BWD_DQ`` of ``paddle_tpu/ops/attention.py``)."""

from benchmarks.lib import program_spans

LAYER = "Pallas kernels"
UNIT = "ms"
MOVES = "train_tok_s"
SOURCE = "device_trace"
KERNELS = ("flash_bwd_dkv", "flash_bwd_dq")


def read(record):
    secs = program_spans.kernel_seconds_per_step(record, KERNELS)
    return None if secs is None else secs * 1e3
