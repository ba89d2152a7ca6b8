"""The program's own spans (``paddle_tpu.observe.trace``), as the
per-layer readers of the host's phases take them.

The flight recorder's ring is still in the process when the readers run,
so they read it directly: every ``E`` event is one finished span with its
site, its trace and span id, its parent's span id, its end ``t`` on the
host's ``perf_counter`` clock, its duration and its attributes. The
window is the measured one: it opens at the ``perf_counter`` reading the
harness took on entering ``bench.window`` (``trace.t0`` less
``trace.host_offset_s`` of the reduced trace) and lasts the ``window_s``
or ``elapsed_s`` the kind put in ``facts``. A record may carry the spans
itself under ``record["program_spans"]`` (a kind that snapshots the ring,
a test's hand-made record); that list wins over the ring.

Every function returns ``None`` where the program recorded none of the
spans it asks for — a ``paddle_tpu`` from before these sites existed —
and raises nothing.
"""

from benchmarks.lib.stats import median, percentile


def window(record):
    """``(lo, hi)`` of the measured window on the host's clock, or
    ``(None, None)`` where the record cannot say (no reduced trace: a
    rehearsal), which cuts nothing."""
    given = record.get("program_window")
    if given is not None:
        return tuple(given)
    trace = record.get("trace") or {}
    if trace.get("host_offset_s") is None:
        return None, None
    lo = trace["t0"] - trace["host_offset_s"]
    facts = record.get("facts", {})
    length = facts.get("window_s", facts.get("elapsed_s"))
    if length is None:
        length = trace["window_s"]
    return lo, lo + length


def finished(record):
    """Every finished span that ended inside the window, oldest first:
    the ring's ``E`` events as dicts (``site``, ``trace``, ``span``,
    ``parent``, ``t`` = end, ``dur``, ``attrs``)."""
    events = record.get("program_spans")
    if events is None:
        try:
            from paddle_tpu.observe import trace as flight

            events = flight.recorder().events()
        except Exception:  # noqa: BLE001 — no program, no spans
            return []
        # one snapshot of the ring serves every reader of this record
        record["program_spans"] = events
    lo, hi = window(record)
    return [ev for ev in events if ev.get("ph") == "E"
            and (lo is None or lo <= ev["t"] <= hi)]


def durations_ms(record, site, inside=None):
    """Durations, in ms, of one site's spans; with ``inside``, only those
    with an ancestor span of that site. ``None`` for no span."""
    spans = finished(record)
    if inside is not None:
        spans = descendants(spans, inside)
    found = [ev["dur"] * 1e3 for ev in spans if ev["site"] == site]
    return found or None


def median_ms(record, site, inside=None):
    found = durations_ms(record, site, inside)
    return None if found is None else median(found)


def percentile_ms(record, site, q):
    found = durations_ms(record, site)
    return None if found is None else percentile(found, q)


def descendants(spans, site):
    """The spans that have an ancestor of ``site`` among ``spans``."""
    by_id = {ev["span"]: ev for ev in spans}
    out = []
    for ev in spans:
        up, hops = by_id.get(ev["parent"]), 0
        while up is not None and hops < 64:
            if up["site"] == site:
                out.append(ev)
                break
            up, hops = by_id.get(up["parent"]), hops + 1
    return out


def _union_s(intervals):
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def self_ms(record, site):
    """Self time, in ms, of every span of ``site``: its duration less the
    union of its direct children's intervals cut to its own, so children
    that overlap one another (a retroactive span beside a live one) are
    not taken off twice. ``None`` for no span of the site."""
    spans = finished(record)
    children = {}
    for ev in spans:
        children.setdefault(ev["parent"], []).append(ev)
    out = []
    for ev in spans:
        if ev["site"] != site:
            continue
        lo, hi = ev["t"] - ev["dur"], ev["t"]
        covered = _union_s(
            (max(lo, c["t"] - c["dur"]), min(hi, c["t"]))
            for c in children.get(ev["span"], ())
            if c["t"] > lo and c["t"] - c["dur"] < hi)
        out.append(max(0.0, ev["dur"] - covered) * 1e3)
    return out or None


def self_median_ms(record, site):
    found = self_ms(record, site)
    return None if found is None else median(found)


def token_times(record, step_sites=("serving.engine.step",
                                    "serving.engine.spec")):
    """``{request trace id: [emission time, ...]}`` on the host's clock:
    a request's first token at the end of its ``serving.engine.admit``
    span, each later one at the end of a step span that lists the
    request's trace among its riders (``attrs["traces"]``). The engine
    keeps no per-token event: this is how a request's token gaps are
    rebuilt from what it does keep. Only requests admitted inside the
    window are returned, so each list starts at the first token."""
    times = {}
    spans = finished(record)
    for ev in spans:
        if ev["site"] == "serving.engine.admit":
            times[ev["trace"]] = [ev["t"]]
    for ev in spans:
        if ev["site"] in step_sites:
            for rider in (ev["attrs"] or {}).get("traces", ()):
                if rider in times and ev["t"] > times[rider][0]:
                    times[rider].append(ev["t"])
    return {k: sorted(v) for k, v in times.items()}


def token_gaps_ms(record):
    """Every request's gaps between consecutive tokens, in ms, over all
    requests admitted in the window; ``None`` for no gap."""
    gaps = []
    for stamps in token_times(record).values():
        gaps.extend((b - a) * 1e3 for a, b in zip(stamps, stamps[1:]))
    return gaps or None


# the names paddle_tpu/ops/attention.py gives its Pallas calls
FLASH_KERNELS = ("flash_fwd", "flash_refwd", "flash_bwd_dkv",
                 "flash_bwd_dq")


def kernel_seconds_per_step(record, wanted, known=FLASH_KERNELS):
    """Device seconds per train step, on the first chip, of the
    operations whose name holds one of ``wanted``: the name the program
    gives a Pallas call reaches the HLO instruction's name
    (``flash_fwd.3``, ``jvp_flash_bwd_dq_.7``). 0.0 where other kernels
    of ``known`` ran and none of ``wanted`` (a recomputation that went
    away); ``None`` where no operation of the traced stretch carries any
    name of ``known``: a program whose kernels are not named yet."""
    trace, facts = record.get("trace"), record.get("facts", {})
    if trace is None or not facts.get("windows_traced"):
        return None
    events = trace["ops"][min(trace["ops"])]
    steps = facts["windows_traced"] * facts["steps_per_window"]
    if not any(k in e[0] for e in events for k in known):
        return None
    return sum(e[2] for e in events
               if any(k in e[0] for k in wanted)) / steps
