"""Device time of the Mamba-1 update in one decode step: for every
``serving.engine.step`` span of the traced stretch, the first chip's leaf
operations that the program made UNDER ITS OP ``mamba_update`` (scope path
``L<i>/mixer/mamba_update``, joined by ``benchmarks/lib/device_scopes.py``
as ``decode_mixer_ms`` joins its class), all mamba layers together,
median over the steps. That is the Pallas call that reads every slot's
``[N, C]`` state, decays it by ``exp(dt (.) A)`` formed in the kernel,
feeds it and writes it back in place, AND what stands round it: the
``[8, C]`` rows and ``[N, 8]`` columns stacked for it, ``A`` transposed,
and the ``slice-start`` / ``slice-done`` of the async copies by which XLA
hands most of the calls their state in VMEM (the table places an
instruction XLA made for nobody under the one that uses it). ``None``
where the record is not of a cell with mamba layers, the program keeps no
name table, or the steps hold no operation under that op."""

from benchmarks.lib.readers import sibling

LAYER = "Pallas kernels"
UNIT = "ms"
MOVES = "req_tok_ms_p50"
SOURCE = "device_trace"
OP = "mamba_update"
SITE = "decode"


def seconds_per_step(record, tables=None):
    got = sibling(__file__, "mamba_scan_ms").op_seconds(record, SITE, OP,
                                                        tables)
    return None if got is None else got["seconds"]


def read(record):
    secs = seconds_per_step(record)
    return None if secs is None else secs * 1e3
