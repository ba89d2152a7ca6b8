"""Device time of one decode step that answers to the layers that keep a
state (scope classes ``mixer``, a state-space layer's convolution, update
and gates, and ``conv``, the gated short convolution): for every
``serving.engine.step`` span of the traced stretch, the first chip's leaf
operations that started inside it, classed by the scope the program
lowered them under (``benchmarks/lib/device_scopes.py``). ``None`` where
the program keeps no name table, the stretch holds no such span, or no
plan under the spans holds the class."""

from benchmarks.lib import device_scopes

LAYER = "model step on the device"
UNIT = "ms"
MOVES = "req_tok_ms_p50"
SOURCE = "device_trace"
SITE = "decode"
CLASSES = ('mixer', 'conv')


def read(record):
    return device_scopes.read_ms(record, SITE, CLASSES)
