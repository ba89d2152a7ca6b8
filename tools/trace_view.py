#!/usr/bin/env python
"""trace_view: summarize / validate / export a flight-recorder dump.

The flight recorder (paddle_tpu/observe/trace.py) dumps its ring on
wedge, fault-plan crash and atexit (``PADDLE_TPU_FLIGHT_RECORDER_PATH``).
This is the post-mortem reader:

    python tools/trace_view.py flight.json            # summary
    python tools/trace_view.py flight.json --trace ID # one trace's events
    python tools/trace_view.py flight.json --validate # pairing/site checks
    python tools/trace_view.py flight.json --chrome out.json
                                                      # chrome://tracing
    python tools/trace_view.py flight.json --xplane run.xplane.pb
                                        # device time by model scope, gaps

The summary leads with what a wedge post-mortem needs first: the dump
reason, the recorded wedge/fault context, and every OPEN span (a ``B``
with no matching ``E`` — the operation that never returned), each with
its trace id, site, tags and how long it had been open when the dump
landed. Then per-site span counts/totals, so "where did the time go"
falls out of the same file, and the "program loads" table: one row a
dispatch in which JAX traced, lowered and compiled or loaded a program
(plan, function, ``nth`` = which load of the plan's signature, cache
hit/miss/off, seconds a stage, and why a plan loaded AGAIN).

``--xplane`` joins the dump with a kept ``jax.profiler`` trace of the
same process (``benchmarks/run.py --keep-trace DIR``): (a) for every
program the device ran (a module of the ``XLA Modules`` line), its device
time by scope class and by layer, through the name tables the dump
carries (``extra.device_names``: the tables somebody asked
``observe/device_names.py`` for before the dump; the plan is the one
whose table holds ALL the module's instructions: a sibling program nobody
asked the table of, another prompt length's prefill, can pass for it); (b) the ten longest idle
gaps of the first chip, each named by the INNERMOST program span over it
(every span is a profiler annotation of its site) and the chain of spans
that contain it.

``--validate`` holds the dump to the recorder's own grammar: every
``E`` has a matching ``B``, durations are non-negative and consistent
with the B/E timestamps, and every site name is declared in
``observe/families.py:TRACE_SITES`` (the same centralized-schema rule
tools/repo_lint.py enforces on the code). Exit 1 on violations.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict

# runnable from any cwd: the repo root (parent of tools/) owns paddle_tpu
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def load_dump(path: str) -> dict:
    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rt") as f:
            d = json.load(f)
    else:
        with open(path) as f:
            d = json.load(f)
    if "events" not in d:
        raise ValueError("%s is not a flight-recorder dump "
                         "(no 'events' key)" % path)
    return d


def open_spans(dump: dict):
    """B events with no matching E — the operations still in flight
    when the dump landed (a wedged dispatch shows up exactly here)."""
    ended = {e["span"] for e in dump["events"] if e["ph"] == "E"}
    t_end = dump.get("dumped_at_perf")
    out = []
    for e in dump["events"]:
        if e["ph"] == "B" and e["span"] not in ended:
            age = (t_end - e["t"]) if t_end is not None else None
            out.append(dict(e, open_age_s=age))
    return out


_LOAD = "executor.load."


def _covered(spans) -> float:
    """Seconds the spans' intervals cover, each second once (a function
    traced inside another's trace is a span inside the outer span)."""
    total, end = 0.0, None
    for a, b in sorted((e["t"] - e["dur"], e["t"]) for e in spans):
        if end is None or a > end:
            total, end = total + b - a, b
        elif b > end:
            total, end = total + b - end, b
    return total


def program_loads(dump: dict):
    """One row a dispatch in which JAX made a program executable, oldest
    first: the plan, JAX's name of the program, which loading dispatch
    of the plan's signature it was (``nth``; 2 = loaded AGAIN), where
    the executable came from (``cache``), the seconds of each stage, how
    many loose state arrays the dispatch committed to the executor's
    place before the call (``committed``: a first load's), and for a
    second load what the dispatch span says differed
    (``uncommitted``/``resharded`` arguments). Programs loaded outside
    any dispatch (no plan: eager helpers, a benchmark's own jits) are
    one row a function, at the end."""
    ends = [e for e in dump["events"]
            if e["ph"] == "E" and e.get("dur") is not None]
    dispatch = {e["span"]: e for e in ends
                if e["site"] == "executor.dispatch"}
    groups, loose = {}, {}
    for e in ends:
        if not e["site"].startswith(_LOAD):
            continue
        if e.get("parent") in dispatch:
            groups.setdefault(e["parent"], []).append(e)
        else:
            fun = (e["attrs"] or {}).get("fun", "?")
            if fun.startswith("jit(") and fun.endswith(")"):
                fun = fun[4:-1]   # the trace stage says f, the others jit(f)
            loose.setdefault(fun, []).append(e)
    rows = []
    for key, stages in list(groups.items()) + list(loose.items()):
        back = [e for e in stages if e["site"] == _LOAD + "backend"]
        if not back:
            continue   # traced or lowered only (lowered_hlo, a cached jit)
        last = back[-1]["attrs"] or {}
        why = dispatch.get(key, {}).get("attrs") or {}
        rows.append({
            "t": min(e["t"] - e["dur"] for e in stages),
            "plan": last.get("plan", "-"),
            "fun": last.get("fun", "?"), "loads": len(back),
            "nth": last.get("nth", "-"),
            "cache": "/".join(sorted({(e["attrs"] or {}).get("cache", "?")
                                      for e in back})),
            "trace_s": _covered([e for e in stages
                                 if e["site"] == _LOAD + "trace"]),
            "lower_s": _covered([e for e in stages
                                 if e["site"] == _LOAD + "lower"]),
            "backend_s": _covered(back),
            "committed": why.get("committed", 0),
            "why": " ".join("%s=%s" % (k, why[k]) for k in
                            ("uncommitted", "resharded") if k in why)})
    rows.sort(key=lambda r: (r["plan"] == "-", r["t"]))
    return rows


def print_program_loads(dump: dict, out=sys.stdout) -> None:
    rows = program_loads(dump)
    if not rows:
        return
    print("\nprogram loads (JAX trace / lower / backend stages, "
          "observe/trace.py):", file=out)
    print("%-9s %-28s %5s %4s %-8s %9s %9s %10s %9s  %s"
          % ("plan", "fun", "loads", "nth", "cache", "trace(s)",
             "lower(s)", "backend(s)", "committed", "why again"), file=out)
    for r in rows:
        print("%-9s %-28s %5d %4s %-8s %9.3f %9.3f %10.3f %9d  %s"
              % (r["plan"], r["fun"][:28], r["loads"], r["nth"],
                 r["cache"], r["trace_s"], r["lower_s"], r["backend_s"],
                 r["committed"], r["why"]), file=out)


# ------------------------------------------------ a kept device profile
def _inside(starts, events, lo, hi):
    import bisect

    return events[bisect.bisect_left(starts, lo):
                  bisect.bisect_left(starts, hi)]


def device_by_scope(dump: dict, planes) -> list:
    """One row a program the first chip ran (a name of its ``XLA
    Modules`` line), longest first: the plan whose name table knows the
    module's instructions and places most of them (None where the dump
    carries no such table: nobody asked for that plan's), the runs, the device seconds of its leaf
    operations, the share of them the table places, and those seconds by
    scope class and by layer."""
    from benchmarks.lib import xplane
    from paddle_tpu.observe.device_names import layer_of, scope_class

    tables = (dump.get("extra") or {}).get("device_names") or {}
    ops = xplane.device_ops(planes)
    runs = xplane.device_ops(planes, "XLA Modules")
    if not ops:
        return []
    chip = min(ops)
    leaves = xplane.leaves(ops[chip])
    starts = [e[1] for e in leaves]
    by_module = defaultdict(list)
    for name, start, dur, _op in runs.get(chip, ()):
        by_module[name].append((start, start + dur))
    rows = []
    for module, spans in by_module.items():
        events = [e for lo, hi in spans
                  for e in _inside(starts, leaves, lo, hi)]
        if not events:
            continue
        names = {e[0] for e in events}
        plan, held = None, 0
        for tag, table in tables.items():
            # the module's own table knows (nearly) all of its names
            known = sum(1 for x in names if x in table["names"])
            n = sum(1 for x in names if table["names"].get(x) is not None)
            if known == len(names) and n > held:
                plan, held = tag, n
        placed = tables[plan]["names"] if plan is not None else {}
        by_class, by_layer = defaultdict(float), defaultdict(float)
        for name, _start, dur, _op in events:
            path = placed.get(name)
            by_class[scope_class(path) or "unscoped"] += dur
            by_layer[layer_of(path) or "-"] += dur
        rows.append({"module": module, "plan": plan, "runs": len(spans),
                     "seconds": sum(e[2] for e in events),
                     "placed_pct": 100.0 * held / len(names),
                     "by_class": dict(by_class),
                     "by_layer": dict(by_layer)})
    rows.sort(key=lambda r: -r["seconds"])
    return rows


def idle_gaps(planes, top: int = 10) -> list:
    """The ``top`` longest stretches of the traced window (the harness's
    ``bench.window`` annotation; the whole profile without one) in which
    the first chip ran nothing: ``(seconds, innermost, chain)``, the
    program spans that hold the gap's middle from the outermost in, the
    last of them the innermost."""
    from benchmarks.lib import xplane
    from paddle_tpu.observe.families import TRACE_SITES

    ops = xplane.device_ops(planes)
    if not ops:
        return []
    events = ops[min(ops)]
    spans = [s for s in xplane.annotations(planes, "")
             if s[0] in TRACE_SITES or s[0].startswith("bench.")]
    window = [s for s in spans if s[0] == "bench.window"]
    t0 = window[0][1] if window else events[0][1]
    t1 = t0 + window[0][2] if window else events[-1][1] + events[-1][2]
    gaps, cursor = [], t0
    for a, b in xplane.union(xplane.clip(events, t0, t1)) + [(t1, t1)]:
        if a > cursor:
            gaps.append((a - cursor, cursor, a))
        cursor = max(cursor, b)
    out = []
    for length, a, b in sorted(gaps, reverse=True)[:top]:
        mid = (a + b) / 2
        chain = sorted((s for s in spans if s[0] != "bench.window"
                        and s[1] <= mid <= s[1] + s[2]),
                       key=lambda s: -s[2])
        out.append((length, chain[-1][0] if chain else "-",
                    [s[0] for s in chain]))
    return out


def print_device_view(dump: dict, xplane_path: str, out=sys.stdout) -> None:
    from benchmarks.lib import xplane

    planes = xplane.load(xplane_path)
    rows = device_by_scope(dump, planes)
    print("device time by model scope, first chip (%s):" % xplane_path,
          file=out)
    for r in rows:
        print("\n%s  plan=%s  runs=%d  %.6f s  (%.1f%% of its "
              "instructions placed)" % (r["module"], r["plan"], r["runs"],
                                        r["seconds"], r["placed_pct"]),
              file=out)
        for title, part in (("class", r["by_class"]),
                            ("layer", r["by_layer"])):
            print("  %-14s %12s %8s %12s" % (title, "seconds", "share",
                                             "ms a run"), file=out)
            for k in sorted(part, key=lambda k: -part[k]):
                print("  %-14s %12.6f %7.1f%% %12.4f"
                      % (k, part[k], 100 * part[k] / r["seconds"],
                         part[k] / r["runs"] * 1e3), file=out)
    print("\nlongest idle gaps of the first chip, by the innermost "
          "program span over each:", file=out)
    print("  %10s  %-28s %s" % ("seconds", "innermost", "inside"),
          file=out)
    for length, inner, chain in idle_gaps(planes):
        print("  %10.6f  %-28s %s" % (length, inner, " > ".join(chain)),
              file=out)


def summarize(dump: dict, out=sys.stdout) -> None:
    evs = dump["events"]
    print("flight recorder dump: pid=%s reason=%s events=%d "
          "(of %s recorded, ring capacity %s)"
          % (dump.get("pid"), dump.get("reason"), len(evs),
             dump.get("recorded_total"), dump.get("capacity")), file=out)
    extra = dump.get("extra") or {}
    for k, v in sorted(extra.items()):
        if k == "device_names":     # whole tables: --xplane reads them
            v = {plan: "%d instructions (%s)" % (len(t["names"]),
                                                 t["source"])
                 for plan, t in v.items()}
        print("  %s: %s" % (k, json.dumps(v, sort_keys=True)), file=out)
    opens = open_spans(dump)
    if opens:
        print("\nOPEN spans (started, never finished — the wedge "
              "suspects):", file=out)
        for e in opens:
            age = ("%.3fs" % e["open_age_s"]
                   if e.get("open_age_s") is not None else "?")
            print("  %-24s trace=%s span=%d open %s  %s"
                  % (e["site"], e["trace"], e["span"], age,
                     json.dumps(e["attrs"] or {}, sort_keys=True)),
                  file=out)
    per_site = defaultdict(lambda: [0, 0.0])  # site -> [spans, total_s]
    instants = defaultdict(int)
    for e in evs:
        if e["ph"] == "E" and e.get("dur") is not None:
            per_site[e["site"]][0] += 1
            per_site[e["site"]][1] += e["dur"]
        elif e["ph"] == "I":
            instants[e["site"]] += 1
    if per_site:
        print("\n%-24s %8s %12s %12s" % ("span site", "count",
                                         "total(s)", "mean(s)"), file=out)
        for site in sorted(per_site, key=lambda s: -per_site[s][1]):
            n, tot = per_site[site]
            print("%-24s %8d %12.6f %12.6f" % (site, n, tot, tot / n),
                  file=out)
    if instants:
        print("\n%-24s %8s" % ("instant site", "count"), file=out)
        for site in sorted(instants):
            print("%-24s %8d" % (site, instants[site]), file=out)
    print_program_loads(dump, out)
    traces = {e["trace"] for e in evs}
    print("\n%d distinct trace(s)" % len(traces), file=out)


def show_trace(dump: dict, trace_id: str, out=sys.stdout) -> None:
    evs = [e for e in dump["events"] if e["trace"] == trace_id]
    if not evs:
        print("no events for trace %s" % trace_id, file=out)
        return
    # sort by timestamp, not ring-append order: retroactive spans
    # (serving.queue.wait) are appended AFTER later-timestamped events
    # by construction, and a timeline must read as a timeline
    evs.sort(key=lambda e: e["t"])
    t0 = evs[0]["t"]
    print("trace %s: %d events" % (trace_id, len(evs)), file=out)
    for e in evs:
        dur = " dur=%.6fs" % e["dur"] if e.get("dur") is not None else ""
        print("  +%.6fs %-2s %-24s span=%-6d%s %s"
              % (e["t"] - t0, e["ph"], e["site"], e["span"], dur,
                 json.dumps(e["attrs"] or {}, sort_keys=True)), file=out)


def validate(dump: dict, out=sys.stdout):
    """Grammar check; returns a list of problem strings (empty = ok)."""
    from paddle_tpu.observe.families import TRACE_SITES

    problems = []
    begins = {}
    # a ring that wrapped legitimately evicted old B events, so
    # E-without-B is only a grammar violation in complete dumps
    cap = dump.get("capacity")
    complete = cap is None or dump.get("recorded_total", 0) <= cap
    for i, e in enumerate(dump["events"]):
        for field in ("t", "ph", "site", "trace", "span"):
            if field not in e:
                problems.append("event %d: missing field %r" % (i, field))
        if e.get("ph") not in ("B", "E", "I"):
            problems.append("event %d: bad phase %r" % (i, e.get("ph")))
            continue
        if e["site"] not in TRACE_SITES:
            problems.append("event %d: site %r not declared in "
                            "observe/families.py TRACE_SITES"
                            % (i, e["site"]))
        if e["ph"] == "B":
            begins[e["span"]] = e
        elif e["ph"] == "E":
            b = begins.pop(e["span"], None)
            if b is None and complete:
                problems.append("event %d: E for span %d with no B "
                                "(dump is complete, so this is not ring "
                                "eviction)" % (i, e["span"]))
            dur = e.get("dur")
            if dur is None or dur < 0:
                problems.append("event %d: E missing/negative dur" % i)
            elif b is not None and abs((e["t"] - b["t"]) - dur) > 1e-6:
                problems.append("event %d: dur %.9f disagrees with B/E "
                                "timestamps (%.9f)"
                                % (i, dur, e["t"] - b["t"]))
    return problems


def export_chrome(dump: dict, path: str) -> None:
    from paddle_tpu.observe.trace import to_chrome_events

    trace = to_chrome_events(dump["events"], pid=dump.get("pid"))
    with open(path, "w") as f:
        json.dump({"traceEvents": trace, "displayTimeUnit": "ms"}, f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="summarize/validate a flight-recorder dump")
    ap.add_argument("dump", help="path to a flight-recorder JSON dump")
    ap.add_argument("--trace", default=None, metavar="TRACE_ID",
                    help="print one trace's events, time-ordered")
    ap.add_argument("--validate", action="store_true",
                    help="check B/E pairing, durations and declared "
                         "sites; exit 1 on violations")
    ap.add_argument("--xplane", default=None, metavar="XPLANE_PB",
                    help="a kept jax.profiler trace of the same process: "
                         "device time by model scope, and the longest "
                         "idle gaps by their innermost program span")
    ap.add_argument("--chrome", default=None, metavar="OUT",
                    help="write chrome://tracing JSON (open B spans "
                         "render as dangling slices — the wedge)")
    args = ap.parse_args(argv)

    try:
        dump = load_dump(args.dump)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print("error: %s" % e, file=sys.stderr)
        return 2

    if args.validate:
        problems = validate(dump)
        for p in problems:
            print(p)
        print("%d problem(s)" % len(problems))
        return 1 if problems else 0
    if args.chrome:
        export_chrome(dump, args.chrome)
        print("wrote %s (%d events)" % (args.chrome, len(dump["events"])))
        return 0
    if args.trace:
        show_trace(dump, args.trace)
        return 0
    if args.xplane:
        print_device_view(dump, args.xplane)
        return 0
    summarize(dump)
    return 0


if __name__ == "__main__":
    sys.exit(main())
