"""Device time of the Mamba-1 selective scan in one admission of the
longest prompt: for every ``serving.engine.prefill`` span of the traced
stretch whose ``prompt_len`` is the longest the stretch holds, the first
chip's leaf operations of the admission's own program's run that the
program made UNDER ITS OP ``mamba_scan`` (scope path ``L<i>/mixer/
mamba_scan``, joined by ``benchmarks/lib/device_scopes.py`` as
``prefill_mixer_ms`` joins its class), all mamba layers together, median
over the spans. That is the Pallas call that walks the prompt position by
position AND what its layout costs round it: XLA relaying ``u``, ``dt``
and ``y`` between ``[T, C]`` and the kernel's ``[T, 1, 8, C / 8]`` tiles,
``B_t`` / ``C_t`` transposed for SMEM, the padding to a block — work a
later change could move out of the kernel's own name, so it is counted
here (a fusion stands under its root's scope, so a relayout fused into
what made ``u`` brings that with it: the reading errs high). The kernel
alone (operations named ``mamba_scan``) is printed on stderr beside it.
``None`` where the record is not of a cell with mamba layers (no
``facts.mamba``), the program keeps no name table, or the stretch holds
no admission or no operation under that op."""

import bisect
import sys

from benchmarks.lib import device_scopes
from benchmarks.lib.stats import median

LAYER = "Pallas kernels"
UNIT = "ms"
MOVES = "serve_tok_s"
SOURCE = "device_trace"
OP = "mamba_scan"
SITE = "prefill"


def op_seconds(record, site, op_type, tables=None):
    """``{"seconds": the median device seconds a span of ``site`` under
    the program's op ``op_type``, "kernel_seconds": of them the operations
    named so, "prompt_len": the admissions' (prefill)}``, or None.
    ``tables`` are the program's unless a test hands its own. Computed
    once a record, site and op."""
    cache = record.setdefault("_mamba_op_seconds", {})
    if (site, op_type) not in cache:
        cache[site, op_type] = _op_seconds(record, site, op_type, tables)
    return cache[site, op_type]


def _op_seconds(record, site, op_type, tables):
    trace = record.get("trace")
    if trace is None or "mamba" not in (record.get("facts") or {}) \
            or trace.get("host_offset_s") is None:
        return None
    found, _longest = device_scopes.targets(record, device_scopes.SITES[site])
    if not found:
        return None
    if tables is None:
        names = device_scopes._device_names()
        if names is None:
            return None
        tables = {plan: device_scopes._table(names, plan) for plan in
                  sorted(set().union(*(p for _lo, _hi, p in found)))}

    def classify(path):
        return op_type if path and op_type in path.split("/") else None

    # (a copy: ``split`` keeps one result a record and site, the classes')
    got = device_scopes.split(dict(record, _device_scopes={}), site, tables,
                              classify)
    if got is None or not got["by_class"].get(op_type):
        return None
    events = sorted((e[1], e[2]) for e in trace["ops"][min(trace["ops"])]
                    if op_type in e[0])
    starts = [s for s, _d in events]
    kernel = median([sum(d for _s, d in events[
        bisect.bisect_left(starts, lo):bisect.bisect_left(starts, hi)])
        for lo, hi, _plans in found])
    print("%s: %s under the op %.4f ms a span, the kernel's own %.4f"
          % (op_type, site, got["by_class"][op_type] * 1e3, kernel * 1e3),
          file=sys.stderr)
    return {"seconds": got["by_class"][op_type], "kernel_seconds": kernel,
            "prompt_len": got["prompt_len"]}


def read(record):
    got = op_seconds(record, SITE, OP)
    return None if got is None else got["seconds"] * 1e3
