"""Device time of one train step that answers to attention, forward and
``_grad`` ops alike (scope classes ``attn.qkv``, ``attn.core``,
``attn.out``: the projections, the flash kernels or the composed scores,
the output projection with its dropout and residual): over the traced
stretch, divided by its steps (``windows_traced x steps_per_window``, as
``flash_ms.train`` divides), the first chip's leaf operations that started
inside it, classed by the scope the program lowered them under
(``benchmarks/lib/device_scopes.py``). ``None`` where the program keeps no
name table, the stretch holds no such span, or no plan under the spans
holds the class."""

from benchmarks.lib import device_scopes

LAYER = "model step on the device"
UNIT = "ms"
MOVES = "train_tok_s"
SOURCE = "device_trace"
SITE = "train"
CLASSES = ('attn.qkv', 'attn.core', 'attn.out')


def read(record):
    return device_scopes.read_ms(record, SITE, CLASSES)
