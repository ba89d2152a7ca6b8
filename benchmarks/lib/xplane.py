"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics and the last line's ``device`` and ``breakdown`` need.

Read with ``jax.profiler.ProfileData`` alone. A TPU trace has one plane
per chip (``/device:TPU:<n>``) whose ``XLA Ops`` line holds one event per
executed HLO operation, and a host plane (``/host:CPU``) whose thread
lines hold ``jax.profiler.TraceAnnotation`` spans; both are on one clock.
All times here are seconds on that clock.

An event is ``(name, start_s, dur_s, opcode)``. On the TPU the profiler
names an operation by its whole HLO line (``%fusion.12 = f32[...]
fusion(...)``): ``name`` is the instruction's name (``fusion.12``) and
``opcode`` its HLO opcode (``fusion``, ``custom-call``, ``all-reduce``,
``while``). Elsewhere the name is kept and the opcode is its group.
"""

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# the benchmark's own annotations all start with this
ANNOTATION_PREFIX = "bench."
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")
# operations that only contain others (a scan's loop holds every step)
CONTAINERS = frozenset(["while", "conditional", "call"])
_SUFFIX = re.compile(r"[.\d]+$")
_HLO_NAME = re.compile(r"^%(\S+) = ")
_HLO_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def parse_event_name(raw):
    """``(name, opcode)`` of a profiler event name (see the module doc)."""
    m = _HLO_NAME.match(raw)
    if not m:
        return raw, op_group(raw)
    op = _HLO_OPCODE.search(raw, m.end() - 1)
    return m.group(1), op.group(1) if op else op_group(m.group(1))


def find_xplane(trace_dir):
    """The newest ``.xplane.pb`` under a ``jax.profiler`` output dir."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return found[-1]


def load(path):
    """``{plane name: {line name: [event, ...]}}``. Lines of one name in
    one plane (host threads) are merged."""
    from jax.profiler import ProfileData

    planes = {}
    parsed = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                raw = ev.name
                if raw not in parsed:
                    parsed[raw] = parse_event_name(raw)
                name, opcode = parsed[raw]
                events.append((name, ev.start_ns * 1e-9,
                               ev.duration_ns * 1e-9, opcode))
    return planes


def device_ops(planes, line=OPS_LINE):
    """``{device index: [event, ...]}`` of one line of every TPU plane
    (the executed operations by default), sorted by start."""
    out = {}
    for name, lines in planes.items():
        m = DEVICE_PLANE.match(name)
        if m and line in lines:
            out[int(m.group(1))] = sorted(lines[line], key=lambda e: e[1])
    return out


def leaves(events):
    """The events that are operations themselves, not containers."""
    return [e for e in events if e[3] not in CONTAINERS]


def collectives(events):
    return [e for e in events if COLLECTIVE.match(e[3])]


def annotations(planes, prefix=ANNOTATION_PREFIX):
    """The host spans whose name starts with ``prefix``, sorted by
    start, from every thread of the host plane."""
    found = []
    for events in planes.get(HOST_PLANE, {}).values():
        found.extend(e for e in events if e[0].startswith(prefix))
    return sorted(found, key=lambda e: e[1])


def clip(events, t0, t1):
    """Events cut to the window [t0, t1]; those outside are dropped."""
    out = []
    for ev in events:
        lo, hi = max(ev[1], t0), min(ev[1] + ev[2], t1)
        if hi > lo:
            out.append((ev[0], lo, hi - lo) + tuple(ev[3:]))
    return out


def union(events):
    """Merged, sorted ``[(start, end), ...]`` of the events' intervals."""
    merged = []
    for ev in sorted(events, key=lambda e: e[1]):
        start, end = ev[1], ev[1] + ev[2]
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def busy_seconds(events):
    return sum(b - a for a, b in union(events))


def op_group(name):
    """Events of one HLO op family share a name up to a numeric suffix:
    ``fusion.123`` and ``fusion.7`` are both ``fusion``."""
    return _SUFFIX.sub("", name) or name


def per_op_seconds(events):
    """``{op group: (seconds, count)}`` over the events."""
    acc = defaultdict(lambda: [0.0, 0])
    for ev in events:
        slot = acc[op_group(ev[0])]
        slot[0] += ev[2]
        slot[1] += 1
    return {k: (v[0], v[1]) for k, v in acc.items()}


def exposed_seconds(subset, others):
    """Seconds of ``subset``'s intervals during which nothing in
    ``others`` runs (both on one device)."""
    cover = union(others)
    exposed = 0.0
    i = 0
    for a, b in union(subset):
        left = b - a
        while i < len(cover) and cover[i][1] <= a:
            i += 1
        j = i
        while j < len(cover) and cover[j][0] < b:
            left -= min(b, cover[j][1]) - max(a, cover[j][0])
            j += 1
        exposed += max(left, 0.0)
    return exposed


def idle_gaps(events, host_spans, t0, t1, top=10, least=1e-6):
    """The longest gaps of the window (of ``least`` seconds or more) in
    which no operation ran on the device, each named by the host span
    that covers most of it (``between_annotations`` where none does):
    ``[(name, seconds)]``."""
    gaps = []
    cursor = t0
    for a, b in union(clip(events, t0, t1)) + [(t1, t1)]:
        if a - cursor >= least:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for a, b in gaps[:top]:
        best, best_key = "between_annotations", (0.0, 0.0)
        for span in host_spans:
            cover = min(b, span[1] + span[2]) - max(a, span[1])
            # the most cover wins; among equals the shortest, innermost
            key = (cover, -span[2])
            if cover > 0 and key > best_key:
                best, best_key = span[0], key
        named.append((best, b - a))
    return named


def reduce(path, window_annotation="bench.window", top=10,
           host_spans=(), host_t0=None):
    """The whole reduction of one trace: per device the operations inside
    the traced window, their busy time, and the breakdown.

    The window is the span of the host annotation ``window_annotation``
    (the benchmark wraps its traced stretch in one). ``host_spans`` are
    further ``(name, start, dur)`` spans on the host's ``perf_counter``
    clock (the program's flight-recorder spans), mapped onto the
    profiler's clock by ``host_t0``, the ``perf_counter`` reading taken
    when the window annotation was entered; idle gaps are named by them
    and by the annotations alike."""
    planes = load(path)
    spans = annotations(planes)
    windows = [s for s in spans if s[0] == window_annotation]
    if not windows:
        raise ValueError("trace %s holds no %r annotation"
                         % (path, window_annotation))
    t0 = windows[0][1]
    t1 = t0 + windows[0][2]
    ops = {d: clip(ev, t0, t1) for d, ev in device_ops(planes).items()}
    async_ops = {d: clip(ev, t0, t1)
                 for d, ev in device_ops(planes, ASYNC_LINE).items()}
    if not ops:
        raise ValueError("trace %s holds no TPU plane with an %r line "
                         "(planes: %s)" % (path, OPS_LINE, sorted(planes)))
    busy = {d: busy_seconds(ev) for d, ev in ops.items()}
    inner = [s for s in spans if s[0] != window_annotation]
    offset = None if host_t0 is None else t0 - host_t0
    if offset is not None:
        inner += [(n, start + offset, dur) for n, start, dur in host_spans]
    first = ops[min(ops)]
    totals = per_op_seconds(leaves(first))
    device_top = sorted(((("%s_x%d" % (k, n)), s)
                         for k, (s, n) in totals.items()),
                        key=lambda kv: -kv[1])[:top]
    return {
        "t0": t0, "t1": t1, "window_s": t1 - t0, "host_offset_s": offset,
        "ops": ops, "async_ops": async_ops, "busy_s": busy,
        "busy_mean_s": sum(busy.values()) / len(busy),
        "spans": inner,
        "breakdown": {
            "device_ops": [[k, s] for k, s in device_top],
            "idle_gaps": [[k, s] for k, s in
                          idle_gaps(first, inner, t0, t1, top)],
        },
    }
