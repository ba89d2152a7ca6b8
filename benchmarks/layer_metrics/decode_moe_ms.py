"""Device time of one decode step that answers to the expert layer (scope
classes ``moe.router``, ``moe.experts`` and ``moe.shared``: the router,
the sort, the gather, the grouped matmuls, the way back and the shared
expert; less ``moe_gmm_ms`` it is what the layer pays round its two
kernels): for every ``serving.engine.step`` span of the traced stretch,
the first chip's leaf operations that started inside it, classed by the
scope the program lowered them under
(``benchmarks/lib/device_scopes.py``). ``None`` where the program keeps no
name table, the stretch holds no such span, or no plan under the spans
holds the class."""

from benchmarks.lib import device_scopes

LAYER = "model step on the device"
UNIT = "ms"
MOVES = "req_tok_ms_p50"
SOURCE = "device_trace"
SITE = "decode"
CLASSES = ('moe.router', 'moe.experts', 'moe.shared')


def read(record):
    return device_scopes.read_ms(record, SITE, CLASSES)
