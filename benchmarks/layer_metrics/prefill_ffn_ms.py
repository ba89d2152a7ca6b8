"""Device time of one admission of the longest prompt that answers to the
dense FFN (scope class ``ffn``): for every ``serving.engine.prefill`` span of
the traced stretch whose ``prompt_len`` is the longest the stretch holds, the
first chip's leaf operations of the admission's OWN program's run inside it
(the tail of the decode step in flight is left out), classed by the scope the
program lowered them under (``benchmarks/lib/device_scopes.py``): the twin of
``decode_ffn_ms`` at the prefill's site. ``None`` where the program keeps no
name table, the stretch holds no such span, or no plan under the spans holds
the class."""

from benchmarks.lib import device_scopes

LAYER = "model step on the device"
UNIT = "ms"
MOVES = "serve_tok_s"
SOURCE = "device_trace"
SITE = "prefill"
CLASSES = ('ffn',)


def read(record):
    return device_scopes.read_ms(record, SITE, CLASSES)
