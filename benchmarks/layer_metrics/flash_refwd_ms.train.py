"""Device time per train step of the flash attention forward kernel run
AGAIN inside the grad ops (the ``jax.vjp`` of the forward lowering runs
the forward rule for its residuals): the operations of the first chip
whose name holds ``flash_refwd`` (``KERNEL_REFWD`` of
``paddle_tpu/ops/attention.py``). 0 once the recomputation is gone."""

from benchmarks.lib import program_spans

LAYER = "Pallas kernels"
UNIT = "ms"
MOVES = "train_tok_s"
SOURCE = "device_trace"
KERNELS = ("flash_refwd",)


def read(record):
    secs = program_spans.kernel_seconds_per_step(record, KERNELS)
    return None if secs is None else secs * 1e3
