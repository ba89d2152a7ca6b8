"""Device time by the part of the model that made it.

The program runs every op's lowering under a scope it chose
(``paddle_tpu/core/lowering.py``: ``L3/attn.core/softmax``) and keeps,
for every plan that dispatched, a table from the compiled program's
instruction names to those scopes (``paddle_tpu/observe/device_names.py``).
A device profile names an operation by its instruction; this is the join.

For every span of one site (a decode step, an admission's prefill, the
whole traced stretch of a train cell) the first chip's leaf operations
that STARTED inside the span (host clock mapped by ``host_offset_s``, as
``moe_gmm_ms`` maps it) are looked up in the tables of the plans whose
``executor.dispatch`` spans lie under that span (``attrs.plan``), classed
by the last declared class of their scope path, summed a class, and the
median over the spans is taken a class. A fusion stands under its ROOT's
scope (the table says so). An instruction no table holds, one that two of
the span's plans place differently, and one whose scope names no class
count as unscoped (class ``None``): the honesty remainder.

An admission's span also holds the tail of the decode step that was in
flight when it began: another program's operations, which the prefill's
table would class under its own scopes (two programs number their
fusions alike). They are left out: an admission counts only the RUN of
its own program (``own_run``), told from what ran before it by the order
of the program's ENTRY instructions, which the table keeps. The span ends
with the read of the prefill's result, so its last operation is the
program's last; walking back, every operation that is not inside a loop
or a branch is an ENTRY instruction, each earlier in the ENTRY than the
one after it, and the first that is not belongs to another program. The
line on stderr says how much was left out (``foreign_ms``), how many
ENTRY operations a run held (``run_ops``: least and most; a program runs
the same ones every time, and a few that last less than a tick of the
profiler's clock fall in or out of a loop's interval) and the device
time of a run (``run_ms``: least and most over the spans, which agree
to a fraction of a percent when the walk found every run's beginning).

Two shares of the classed time are printed on stderr with the split, to
say how far the attribution can be wrong: ``mixed_pct`` sits in fusions
whose body holds more than one class (the root's wins), and
``inherited_pct`` in instructions XLA made for nobody, placed by the one
that uses them (a weight's ``slice-done``, a layout ``copy``).

Everything returns ``None`` and raises nothing where there is nothing to
read: no reduced trace (a rehearsal), a ``paddle_tpu`` without the name
tables (the parent of the PR that added them), no span of the site in the
traced stretch, no plan under the spans.
"""

import bisect
import json
import sys
import time

from benchmarks.lib import program_spans, xplane
from benchmarks.lib.stats import median

DISPATCH = "executor.dispatch"
SITES = {"decode": "serving.engine.step",
         "prefill": "serving.engine.prefill",
         "train": None}             # the whole traced stretch
# the sites whose span ends with the read of its own program's result
# and may begin with the end of another's
OWN_RUN = frozenset(["prefill"])


def _device_names():
    try:
        from paddle_tpu.observe import device_names
    except Exception:  # noqa: BLE001 — a program from before the tables
        return None
    return device_names


def _table(names, plan):
    """The plan's name table, None (and a word on stderr) where the
    program cannot make it: a reader reports nothing, it never fails the
    run it reads."""
    try:
        return names.table(plan)
    except Exception as exc:  # noqa: BLE001
        print("device_scopes: no table for plan %s: %s: %s"
              % (plan, type(exc).__name__, exc), file=sys.stderr)
        return None


def targets(record, site):
    """``[(lo, hi, plans)]`` on the profiler's clock: the spans of
    ``site`` that lie inside the traced stretch, each with the plan tags
    of the dispatches under it. For the prefill site only the admissions
    of the longest ``prompt_len`` the stretch holds (the traffic's
    longest whenever it holds one). ``site`` None is the stretch itself
    with every dispatch that ended in the window."""
    trace = record["trace"]
    off = trace["host_offset_s"]
    spans = program_spans.finished(record)
    by_id = {ev["span"]: ev for ev in spans}
    if site is None:
        plans = {(ev.get("attrs") or {}).get("plan") for ev in spans
                 if ev["site"] == DISPATCH}
        plans.discard(None)
        return [(trace["t0"], trace["t1"], frozenset(plans))], None
    found = {}
    for ev in spans:
        if ev["site"] != site:
            continue
        lo, hi = ev["t"] - ev["dur"] + off, ev["t"] + off
        if lo >= trace["t0"] and hi <= trace["t1"]:
            found[ev["span"]] = [lo, hi, set(), ev]
    for ev in spans:
        plan = (ev.get("attrs") or {}).get("plan")
        if ev["site"] != DISPATCH or plan is None:
            continue
        up, hops = by_id.get(ev["parent"]), 0
        while up is not None and hops < 64:
            if up["span"] in found:
                found[up["span"]][2].add(plan)
                break
            up, hops = by_id.get(up["parent"]), hops + 1
    chosen = list(found.values())
    longest = None
    if site == SITES["prefill"] and chosen:
        longest = max((t[3].get("attrs") or {}).get("prompt_len") or 0
                      for t in chosen)
        chosen = [t for t in chosen if
                  ((t[3].get("attrs") or {}).get("prompt_len") or 0)
                  == longest]
    return [(lo, hi, frozenset(plans)) for lo, hi, plans, _ev in chosen
            if plans], longest


def lookup(tables, plans, classify):
    """``{instruction: (class, mixed, inherited)}`` over the tables of
    ``plans``: ``class`` None for a name the plans place differently or
    whose scope names no class, ``mixed`` whether it is a fusion whose
    body holds more than one class, ``inherited`` whether its user's
    scope placed it."""
    out = {}
    for plan in plans:
        table = tables.get(plan)
        if table is None:
            continue
        handed = set(table.get("inherited", ()))
        for name, path in table["names"].items():
            inside = {classify(p) for p in table["fused"].get(name, ())}
            inside.discard(None)
            entry = (path, classify(path), len(inside) > 1, name in handed)
            if name in out and out[name][0] != entry[0]:
                entry = (None, None, False, False)         # ambiguous
            out[name] = entry
    return {k: v[1:] for k, v in out.items()}


def own_run(events, orders):
    """``(start, n)``: where the last program run among ``events`` began
    and how many ENTRY operations it held. ``events`` are a span's
    ``(start, dur, name, opcode)``, containers among them; ``orders`` the
    ENTRY instruction orders of the programs the span dispatched (a
    table's ``entry``). Walking back from the last event, one that starts
    inside a container (a loop's, a branch's) is passed over, and every
    other has to be an ENTRY instruction earlier in the order than the
    one after it: the first that is not ended another program's run.
    Events of one timestamp (the profiler's clock ticks coarser than a
    ``copy-done`` lasts) are taken in the ENTRY's order, the only one
    they can have run in. The order that explains the most events is the
    program that ran; ``(None, 0)`` where none explains the last one."""
    outer, open_until = [], float("-inf")
    for start, dur, _name, opcode in sorted(
            events, key=lambda e: (e[0], -e[1])):
        if opcode in xplane.CONTAINERS and start >= open_until:
            outer.append((start, start + dur))
            open_until = start + dur
    opened = [a for a, _b in outer]
    ticks = {}                          # start -> the names not inside
    for start, _dur, name, opcode in events:
        k = bisect.bisect_right(opened, start) - 1
        if k < 0 or start >= outer[k][1] or (
                opcode in xplane.CONTAINERS and start == outer[k][0]):
            ticks.setdefault(start, []).append(name)
    best = (None, 0)
    for order in orders:
        at = {name: i for i, name in enumerate(order)}
        began, n, before = None, 0, len(order)
        for start in sorted(ticks, reverse=True):
            places = sorted((at.get(name, len(order)) for name in
                             ticks[start]), reverse=True)
            if places[0] >= before or len(set(places)) < len(places):
                break
            began, n, before = start, n + len(places), places[-1]
        if n > best[1]:
            best = (began, n)
    return best


def _leaf_seconds(events):
    return sum(dur for _start, dur, _name, opcode in events
               if opcode not in xplane.CONTAINERS)


def split(record, which, tables=None, classify=None):
    """``{"by_class": {class or None: median seconds a span},
    "mixed_pct", "inherited_pct", "spans", "plans", "sources", "tables_s",
    "prompt_len", "foreign_s", "run_ops", "run_s",
    "classes": every class the tables hold}`` of site ``which``
    (``decode``, ``prefill``, ``train``), or None. Computed once a record
    and site. ``tables`` (``{plan tag: table}``) and ``classify`` are the
    program's (``device_names.table`` / ``scope_class``) unless a test
    hands its own."""
    cache = record.setdefault("_device_scopes", {})
    if which not in cache:
        cache[which] = _split(record, which, tables, classify)
    return cache[which]


def _split(record, which, tables, classify):
    trace = record.get("trace")
    if trace is None or trace.get("host_offset_s") is None:
        return None
    names = None
    if tables is None or classify is None:
        names = _device_names()
        if names is None:
            return None
        classify = names.scope_class
    found, prompt_len = targets(record, SITES[which])
    if not found:
        return None
    t_tables = time.perf_counter()
    if tables is None:
        wanted = set().union(*(plans for _lo, _hi, plans in found))
        tables = {plan: _table(names, plan) for plan in sorted(wanted)}
    tables = {k: v for k, v in tables.items() if v is not None}
    t_tables = time.perf_counter() - t_tables
    if not tables:
        return None
    events = sorted(((e[1], e[2], e[0], e[3])
                     for e in trace["ops"][min(trace["ops"])]),
                    key=lambda e: e[0])
    starts = [e[0] for e in events]
    lookups, per_span, mixed_s, classed_s, handed_s = {}, [], 0.0, 0.0, 0.0
    foreign, run_ops, run_s = [], [], []
    for lo, hi, plans in found:
        if plans not in lookups:
            lookups[plans] = lookup(tables, plans, classify)
        table = lookups[plans]
        i, j = bisect.bisect_left(starts, lo), bisect.bisect_left(starts, hi)
        orders = [order for plan in plans if plan in tables
                  for order in tables[plan].get("entry", ())]
        if which in OWN_RUN and orders:
            began, n = own_run(events[i:j], orders)
            if began is None:
                continue            # the span's end is not its program's
            cut = bisect.bisect_left(starts, began, i, j)
            foreign.append(_leaf_seconds(events[i:cut]))
            run_s.append(_leaf_seconds(events[cut:j]))
            run_ops.append(n)
            i = cut
        sums = {}
        for _start, dur, name, opcode in events[i:j]:
            if opcode in xplane.CONTAINERS:
                continue
            cls, mixed, handed = table.get(name, (None, False, False))
            sums[cls] = sums.get(cls, 0.0) + dur
            if cls is not None:
                classed_s += dur
                mixed_s += dur if mixed else 0.0
                handed_s += dur if handed else 0.0
        if sums:
            per_span.append(sums)
    if not per_span:
        return None
    classes = set().union(*(s.keys() for s in per_span))
    steps = 1
    if which == "train":
        facts = record.get("facts") or {}
        steps = (facts.get("windows_traced") or 0) \
            * (facts.get("steps_per_window") or 0)
        if not steps:
            return None
    by_class = {c: median([s.get(c, 0.0) for s in per_span]) / steps
                for c in classes}
    held = {classify(p) for t in tables.values()
            for p in list(t["names"].values())
            + [q for inside in t["fused"].values() for q in inside]}
    held.discard(None)
    out = {"by_class": by_class, "spans": len(per_span),
           "plans": sorted(tables), "prompt_len": prompt_len,
           "sources": sorted({t["source"] for t in tables.values()}),
           "same_names": all(t.get("same_names", True)
                             for t in tables.values()),
           "names_differ": sorted({n for t in tables.values()
                                   for n in t.get("names_differ", ())}),
           "tables_s": t_tables, "classes": sorted(held),
           "mixed_pct": 100.0 * mixed_s / classed_s if classed_s else 0.0,
           "inherited_pct": 100.0 * handed_s / classed_s
           if classed_s else 0.0,
           "foreign_s": median(foreign) if foreign else None,
           "run_ops": [min(run_ops), max(run_ops)] if run_ops else [],
           "run_s": [min(run_s), max(run_s)] if run_s else []}
    print("device_scopes: %s" % json.dumps(
        {"site": which, "spans": out["spans"], "plans": out["plans"],
         "sources": out["sources"], "same_names": out["same_names"],
         "names_differ": [len(out["names_differ"])]
         + out["names_differ"][:8],
         "tables_s": round(t_tables, 3),
         "prompt_len": prompt_len, "mixed_pct": round(out["mixed_pct"], 2),
         "inherited_pct": round(out["inherited_pct"], 2),
         "foreign_ms": None if out["foreign_s"] is None
         else round(out["foreign_s"] * 1e3, 4), "run_ops": out["run_ops"],
         "run_ms": [round(v * 1e3, 4) for v in out["run_s"]],
         "ms": {str(c): round(s * 1e3, 4)
                for c, s in sorted(by_class.items(), key=lambda kv: -kv[1])}
         }), file=sys.stderr)
    return out


def read_ms(record, which, classes):
    """Milliseconds a span (a train step) of the site's device time under
    ``classes`` (``(None,)`` is the unscoped remainder): the metric
    files' one call. None where ``split`` is, and where no table of the
    spans' plans holds any of ``classes`` (a cell without that part)."""
    got = split(record, which)
    if got is None:
        return None
    if None not in classes and not set(classes) & set(got["classes"]):
        return None
    return sum(got["by_class"].get(c, 0.0) for c in classes) * 1e3
