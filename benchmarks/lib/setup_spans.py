"""Set-up, as the program's own spans name it (``paddle_tpu.observe.trace``
"Program loads"): the ring's spans that ENDED before the measured window
opened, and the seconds their intervals cover.

Every second is counted once: a function traced inside another's trace is
a span of its own inside the outer span, and the union of the intervals
holds it once. ``None`` wherever the account cannot be closed: the record
has no window (a rehearsal), or the ring has dropped events since the
process started (a run longer than the ring: set-up fell off its back),
or the program recorded none of the spans asked for (a ``paddle_tpu``
from before these sites existed)."""

from benchmarks.lib import program_spans

STAGES = ("executor.load.trace", "executor.load.lower",
          "executor.load.backend")
ENGINE = ("serving.engine.build", "serving.engine.load_params")


def _dropped(record):
    """Whether the ring lost events: the record's own word where it
    carries the spans itself (a test's hand-made record), else the
    ring's lifetime count against what it holds."""
    if "program_spans_dropped" in record:
        return bool(record["program_spans_dropped"])
    try:
        from paddle_tpu.observe import trace as flight

        ring = flight.recorder()
        return ring.recorded > len(ring)
    except Exception:  # noqa: BLE001 — no program, no ring
        return True


def ended(record):
    """Every finished span that ended before the window opened, oldest
    first (the ring's ``E`` events as dicts), or ``None``."""
    lo = program_spans.window(record)[0]
    if lo is None or _dropped(record):
        return None
    if "program_spans" not in record:
        # snapshots the ring into the record, once for every reader
        program_spans.finished(record)
    return [ev for ev in record.get("program_spans", ())
            if ev.get("ph") == "E" and ev["t"] <= lo]


def union_s(spans, lo=None):
    """Seconds covered by the spans' intervals, each second once; with
    ``lo``, only what lies after it."""
    intervals = [(ev["t"] - ev["dur"] if lo is None
                  else max(lo, ev["t"] - ev["dur"]), ev["t"])
                 for ev in spans if lo is None or ev["t"] > lo]
    return program_spans._union_s(intervals)


def of_sites(record, sites, keep=None):
    """Union, in seconds, of set-up's spans of ``sites`` (those ``keep``
    accepts); ``None`` where set-up holds no span of these sites at
    all, 0.0 where it holds some and ``keep`` takes none."""
    spans = ended(record)
    if spans is None:
        return None
    found = [ev for ev in spans if ev["site"] in sites]
    if not found:
        return None
    return union_s([ev for ev in found if keep is None or keep(ev)])


def attr(ev, key, default=None):
    return (ev["attrs"] or {}).get(key, default)


def backend(record, spans=None):
    """Set-up's backend stages (XLA compile or persistent-cache load),
    or ``None`` where there is none to read."""
    spans = ended(record) if spans is None else spans
    if spans is None:
        return None
    return [ev for ev in spans if ev["site"] == STAGES[2]] or None


def reloaded(record):
    """The stage spans of every dispatch in which a plan loaded its
    program AGAIN (a backend stage with ``nth`` >= 2), or ``None``."""
    spans = ended(record)
    stages = backend(record, spans)
    if stages is None:
        return None
    again = {ev["parent"] for ev in stages if attr(ev, "nth", 1) >= 2}
    return [ev for ev in spans
            if ev["site"] in STAGES and ev["parent"] in again]


def unnamed_s(record):
    """``setup_s`` less the union of ALL the program's spans of set-up:
    what ran under no span at all. Set-up is the ``setup_s`` seconds
    before the window's opening."""
    spans = ended(record)
    if not spans or record.get("setup_s") is None:
        return None
    lo = program_spans.window(record)[0] - record["setup_s"]
    return max(0.0, record["setup_s"] - union_s(spans, lo))
