"""Device time of one train step that answers to the LayerNorms, forward and
``_grad`` ops alike (scope class ``norm``): over the traced stretch,
divided by its steps (``windows_traced x steps_per_window``, as
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
CLASSES = ('norm',)


def read(record):
    return device_scopes.read_ms(record, SITE, CLASSES)
