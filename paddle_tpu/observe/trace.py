"""End-to-end trace propagation + the crash flight recorder.

The metrics registry (metrics.py) answers *how much*; this module
answers *where did request X / step N spend its time* — and, when the
process wedges or is SIGKILLed by a fault plan, *what was it doing when
it died*. Two pieces:

* **Trace contexts** — a ``TraceContext`` is ``(trace_id, span_id)``.
  The current context is thread-local: entering a span installs its
  context for the ``with`` body, so nested instrumentation links up
  automatically. Crossing a thread boundary is EXPLICIT — capture
  ``current()`` (or mint ``new_trace()``) on the producing side and
  ``attach(ctx)`` on the consuming side (the hand-off
  ``run_pipelined`` does for the prefetch fill thread, and the serving
  queue does by pinning each request's root context on the request
  object). Crossing a PROCESS boundary rides message metadata:
  ``wire_metadata()`` serializes the current ids, ``from_wire()``
  revives them (distributed/rpc.py's name-suffix channel).

* **The flight recorder** — every span begin/end and instant event is
  appended to one bounded in-process ring buffer. It is NOT a log: old
  events fall off the back, so steady-state cost is O(1) memory and an
  append under a lock. Its value is the final window: the watchdog's
  wedge handler, the fault plane's crash sites and ``atexit`` each call
  ``dump_flight_recorder()``, atomically writing the last-N events to
  ``PADDLE_TPU_FLIGHT_RECORDER_PATH`` — so a wedged dispatch is
  diagnosable post-mortem from its open span (a ``B`` with no ``E``):
  trace id, site, plan signature, and the events leading up to it.
  ``tools/trace_view.py`` summarizes/validates a dump and exports
  chrome-trace; ``export_chrome_trace()`` writes the ring, which holds a
  profiling session's ``RecordEvent`` markers too (they are spans).

* **Program loads** — ``watch_program_loads()`` (called by the first
  ``Executor``/``ParallelEngine``, and by ``flags.enable_compile_cache``
  for an entry point's own programs) registers ONE ``jax.monitoring``
  listener. JAX calls it on the thread that ran a stage, at the stage's
  end, and it records a retroactive span for each: ``executor.load.trace``
  (a function traced to a jaxpr), ``executor.load.lower`` (jaxpr to an
  MLIR module), ``executor.load.backend`` (XLA compile, or a load from
  the persistent cache: ``cache`` says which). Recorded under the
  thread's current context they nest in the ``executor.dispatch`` that
  caused them and carry its ``plan``; a load outside any dispatch has no
  ``plan``. ``nth`` on a backend span says which loading dispatch of its
  plan signature this is: 2 means the plan loaded its program again.
  Nothing here runs in a steady dispatch.

* **The profiler's clock** — a span is also a
  ``jax.profiler.TraceAnnotation`` of its site name. Whenever anyone is
  taking a ``jax.profiler`` trace (``jax.profiler.trace``,
  ``profiler.start_profiler``, a benchmark harness), every span entered
  on any thread lands in the host plane of the same ``.xplane.pb`` as the
  device operations, nested as in the ring; with no trace being taken an
  annotation is a flag check inside the profiler. Retroactive spans
  (``record_span``) and instant events have no annotation: the profiler
  cannot be told of a time that has passed. ``jax`` is imported on the
  first span, so this package stays importable without it.

Event grammar (one dict per event in dumps; tuples in the ring):

    {"t": perf_counter_s, "ph": "B"|"E"|"I", "site": <TRACE_SITES name>,
     "trace": "16-hex", "span": int, "parent": int|None,
     "tid": thread_id, "dur": seconds (E only), "attrs": {...}|None}

Env knobs:

* ``PADDLE_TPU_TRACE=0`` disables tracing entirely; the hot-path guard
  is one module-global bool check, the ring stays empty, and span
  helpers return a shared no-op singleton (no per-step allocations).
* ``PADDLE_TPU_FLIGHT_RECORDER_PATH`` — dump destination; unset means
  dumps are skipped (the ring still records for in-process export).
* ``PADDLE_TPU_FLIGHT_RECORDER_EVENTS`` — ring capacity (default 4096,
  floor 16): how much history a dump retains.

Site NAMES are declared in ``families.TRACE_SITES`` — the repo lint
(tools/repo_lint.py) fails on a ``trace_span``/``trace_event``/
``record_span`` call whose literal site is undeclared, the same
centralized-schema contract the metric families carry.
"""

from __future__ import annotations

import atexit
import itertools
import json
import os
import random
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from .families import (PROGRAM_LOAD_SECONDS, PROGRAM_LOADS,  # noqa: F401
                       TRACE_DUMPS, TRACE_SITES)

__all__ = ["TraceContext", "FlightRecorder", "NOOP", "trace_enabled",
           "set_trace_enabled", "new_trace", "current", "attach",
           "trace_span", "trace_event", "record_span", "recorder",
           "dump_flight_recorder", "export_chrome_trace",
           "wire_metadata", "from_wire", "watch_program_loads",
           "open_loads", "close_loads"]

ENV_TRACE = "PADDLE_TPU_TRACE"
ENV_PATH = "PADDLE_TPU_FLIGHT_RECORDER_PATH"
ENV_EVENTS = "PADDLE_TPU_FLIGHT_RECORDER_EVENTS"
_DEFAULT_CAPACITY = 4096

_EVENT_FIELDS = ("t", "ph", "site", "trace", "span", "parent", "tid",
                 "dur", "attrs")


def _env_enabled() -> bool:
    return os.environ.get(ENV_TRACE, "1").strip() not in ("0", "false",
                                                          "off", "")


def _env_capacity() -> int:
    try:
        n = int(os.environ.get(ENV_EVENTS, str(_DEFAULT_CAPACITY)))
    except ValueError:
        n = _DEFAULT_CAPACITY
    return max(n, 16)


class TraceContext:
    """One position in a trace: ``trace_id`` names the request/step the
    work belongs to, ``span_id`` the specific operation. Immutable and
    cheap to hand across threads/processes."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: int):
        self.trace_id = trace_id
        self.span_id = span_id

    def __repr__(self):
        return "TraceContext(%s/%d)" % (self.trace_id, self.span_id)


class FlightRecorder:
    """Bounded ring of trace events (tuples, see ``_EVENT_FIELDS``).

    Appends are O(1) under one lock; the deque's maxlen evicts the
    oldest event so a long-running process holds exactly the last
    ``capacity`` events — the post-mortem window."""

    def __init__(self, capacity: int = _DEFAULT_CAPACITY):
        if capacity < 1:
            raise ValueError("FlightRecorder capacity must be >= 1")
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=capacity)
        self._recorded = 0

    @property
    def capacity(self) -> int:
        return self._ring.maxlen

    def resize(self, capacity: int) -> None:
        """Change the retained-event window (keeps the newest events)."""
        if capacity < 1:
            raise ValueError("FlightRecorder capacity must be >= 1")
        with self._lock:
            self._ring = deque(self._ring, maxlen=capacity)

    def record(self, t, ph, site, trace_id, span_id, parent_id, tid,
               dur=None, attrs=None) -> None:
        # shallow-COPY attrs: span attrs dicts stay mutable until the
        # span exits, and the ring must never hold a live reference a
        # concurrent dump could watch mutate mid-json.dump (the wedge
        # dump races the wedged thread by construction). A span's B
        # event therefore carries enter-time attrs; late-attached keys
        # land on the E event.
        if attrs:
            attrs = dict(attrs)
        else:
            attrs = None
        with self._lock:
            self._ring.append((t, ph, site, trace_id, span_id, parent_id,
                               tid, dur, attrs))
            self._recorded += 1

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    @property
    def recorded(self) -> int:
        """Lifetime events recorded (>= len(): the ring drops the back)."""
        with self._lock:
            return self._recorded

    def events(self) -> List[Dict[str, Any]]:
        """Snapshot of the ring, oldest first, as event dicts."""
        with self._lock:
            raw = list(self._ring)
        return [dict(zip(_EVENT_FIELDS, ev)) for ev in raw]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._recorded = 0

    def dump(self, path: str, reason: str = "manual",
             extra: Optional[dict] = None) -> dict:
        """Atomically write the ring as JSON to ``path``; returns the
        payload. Safe to call from a watchdog thread racing the main
        thread's atexit dump (pid+tid-unique tmp, os.replace)."""
        payload = {
            "version": 1,
            "pid": os.getpid(),
            "reason": reason,
            "dumped_at_unix": time.time(),
            "dumped_at_perf": time.perf_counter(),
            "capacity": self.capacity,
            "recorded_total": self.recorded,
            "extra": extra or {},
            "events": self.events(),
        }
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = os.path.join(d, ".%s.tmp.%d.%d" % (
            os.path.basename(path), os.getpid(), threading.get_ident()))
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True, default=repr)
        os.replace(tmp, path)
        TRACE_DUMPS.labels(reason=reason if reason in
                           ("wedge", "crash", "atexit", "signal")
                           else "manual").inc()
        return payload


# ------------------------------------------------------- module singletons
_ON = _env_enabled()
RECORDER = FlightRecorder(_env_capacity())
_tls = threading.local()
# span ids: itertools.count().__next__ is atomic under the GIL; trace ids
# get a per-process random prefix so dumps from two trainers never collide
_next_span_id = itertools.count(1).__next__
_TRACE_PREFIX = "%08x" % random.getrandbits(32)
_next_trace_seq = itertools.count(1).__next__


def trace_enabled() -> bool:
    """THE hot-path guard: one module-global bool. Per-step call sites
    (the executor dispatch window) check this before building any span
    arguments, so PADDLE_TPU_TRACE=0 costs one branch per step."""
    return _ON


def set_trace_enabled(on: bool) -> bool:
    """Flip tracing at runtime (tests); returns the prior state."""
    global _ON
    prior = _ON
    _ON = bool(on)
    return prior


def _reload_env() -> None:
    """Re-read ``PADDLE_TPU_TRACE`` / ring capacity from the environment
    (tests monkeypatch env then call this; production reads at import)."""
    global _ON
    _ON = _env_enabled()
    if RECORDER.capacity != _env_capacity():
        RECORDER.resize(_env_capacity())


def recorder() -> FlightRecorder:
    return RECORDER


def new_trace() -> TraceContext:
    """Mint a fresh root context (no event recorded): the identity a
    serving request / pipeline loop carries through its lifetime."""
    return TraceContext("%s%08x" % (_TRACE_PREFIX, _next_trace_seq()),
                        _next_span_id())


def current() -> Optional[TraceContext]:
    """This thread's active context (set by an enclosing span or an
    ``attach``), or None."""
    return getattr(_tls, "ctx", None)


class attach:
    """Explicit cross-thread hand-off: install ``ctx`` as this thread's
    current context for the ``with`` body. ``attach(None)`` is a no-op
    scope (so call sites need no branch)."""

    __slots__ = ("_ctx", "_prev")

    def __init__(self, ctx: Optional[TraceContext]):
        self._ctx = ctx

    def __enter__(self):
        self._prev = getattr(_tls, "ctx", None)
        if self._ctx is not None:
            _tls.ctx = self._ctx
        return self._ctx

    def __exit__(self, *exc):
        _tls.ctx = self._prev
        return False


class _NoopSpan:
    """Shared do-nothing span handed out while tracing is disabled.
    ``attrs`` is None so call sites can guard post-hoc attr writes with
    ``if sp.attrs is not None`` — nothing is allocated or retained."""

    __slots__ = ()
    attrs = None
    ctx = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP = _NoopSpan()

# jax.profiler.TraceAnnotation, looked up on the first span (False until
# then; None where jax cannot be imported: spans then go to the ring only)
_ANNOTATION: Any = False


def _annotation_type():
    global _ANNOTATION
    try:
        from jax.profiler import TraceAnnotation
    except Exception:  # noqa: BLE001 — observe works without jax
        TraceAnnotation = None
    _ANNOTATION = TraceAnnotation
    return TraceAnnotation


class Span:
    """A recorded span: ``B`` event at enter (so a dispatch that never
    returns is still visible in a dump as an OPEN span), ``E`` with the
    duration at exit. Entering installs the span's context thread-local
    so nested spans/events parent to it; ``attrs`` is mutable until exit
    (schedulers attach e.g. the per-step active trace list late). The
    span is a profiler annotation of its site name for the same stretch
    (module doc, "The profiler's clock")."""

    __slots__ = ("site", "ctx", "parent", "attrs", "_t0", "_prev", "_ann")

    def __init__(self, site: str, parent: Optional[TraceContext],
                 attrs: Optional[dict]):
        self.site = site
        if parent is None:
            self.ctx = new_trace()
            self.parent = None
        else:
            self.ctx = TraceContext(parent.trace_id, _next_span_id())
            self.parent = parent.span_id
        self.attrs = attrs if attrs else {}

    def __enter__(self) -> "Span":
        self._prev = getattr(_tls, "ctx", None)
        _tls.ctx = self.ctx
        ann = _ANNOTATION if _ANNOTATION is not False \
            else _annotation_type()
        if ann is not None:
            ann = ann(self.site)
            ann.__enter__()
        self._ann = ann
        self._t0 = time.perf_counter()
        RECORDER.record(self._t0, "B", self.site, self.ctx.trace_id,
                        self.ctx.span_id, self.parent,
                        threading.get_ident(),
                        attrs=self.attrs or None)
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        RECORDER.record(t1, "E", self.site, self.ctx.trace_id,
                        self.ctx.span_id, self.parent,
                        threading.get_ident(), dur=t1 - self._t0,
                        attrs=self.attrs or None)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _tls.ctx = self._prev
        return False


def trace_span(site: str, /, ctx: Optional[TraceContext] = None,
               **attrs):
    """Context manager for one traced operation. Parent = ``ctx`` when
    given, else the thread's current context, else a fresh root trace.
    Returns the shared ``NOOP`` singleton while tracing is disabled."""
    if not _ON:
        return NOOP
    return Span(site, ctx if ctx is not None else current(), attrs)


def trace_event(site: str, /, ctx: Optional[TraceContext] = None,
                **attrs) -> None:
    """Record one instant event under ``ctx`` (or the current context;
    a fresh root trace when neither exists)."""
    if not _ON:
        return
    parent = ctx if ctx is not None else current()
    if parent is None:
        parent = new_trace()
        RECORDER.record(time.perf_counter(), "I", site, parent.trace_id,
                        parent.span_id, None, threading.get_ident(),
                        attrs=attrs or None)
        return
    RECORDER.record(time.perf_counter(), "I", site, parent.trace_id,
                    _next_span_id(), parent.span_id,
                    threading.get_ident(), attrs=attrs or None)


def record_span(site: str, t0: float, dur: float, /,
                ctx: Optional[TraceContext] = None, **attrs) -> None:
    """Record a RETROACTIVE span (B/E pair) whose timing was measured
    out-of-band — e.g. queue wait, known only at pop time. ``t0`` is in
    ``time.perf_counter()`` terms."""
    if not _ON:
        return
    parent = ctx if ctx is not None else current()
    if parent is None:
        parent = new_trace()
        sid, pid = parent.span_id, None
    else:
        sid, pid = _next_span_id(), parent.span_id
    tid = threading.get_ident()
    a = attrs or None
    RECORDER.record(t0, "B", site, parent.trace_id, sid, pid, tid, attrs=a)
    RECORDER.record(t0 + dur, "E", site, parent.trace_id, sid, pid, tid,
                    dur=dur, attrs=a)


# -------------------------------------------------------- program loads
_JAX = "/jax/core/compile/"
# JAX's duration event -> (span site, the counter's ``stage`` label)
_LOAD_STAGES = {
    _JAX + "jaxpr_trace_duration": ("executor.load.trace", "trace"),
    _JAX + "jaxpr_to_mlir_module_duration": ("executor.load.lower",
                                             "lower"),
    _JAX + "backend_compile_duration": ("executor.load.backend",
                                        "backend"),
}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
# jnp's own helpers (add, where, _mean ...) are jitted functions, traced
# once a signature INSIDE a program's trace: some 10,000 events of a few
# microseconds each in a 12-layer BERT step, 0.2 s in all, which would
# turn the ring over five times. A trace shorter than this is neither
# recorded nor counted; the outer trace's span holds its time
_TRACE_FLOOR_S = 1e-3
_WATCHING = False
_LOAD_LOCK = threading.Lock()


class LoadScope:
    """What JAX loaded inside one ``executor.dispatch``: ``stages``
    counts the trace/lower/backend stages that ended in it (0 = a steady
    dispatch), ``backend`` the backend stages among them. ``nth`` is
    given by the opener: which loading dispatch of its plan signature
    this one is if a backend stage runs in it (2 = the plan loads its
    program again); every backend span of the dispatch carries it."""

    __slots__ = ("plan", "nth", "stages", "backend", "_prev")

    def __init__(self, plan, nth):
        self.plan = plan
        self.nth = nth
        self.stages = self.backend = 0


def open_loads(plan: Optional[str], nth: int = 1) -> Optional[LoadScope]:
    """Begin the dispatch's load scope on this thread (None while
    tracing is off: nothing listens, so nothing could be told)."""
    if not _ON:
        return None
    scope = LoadScope(plan, nth)
    scope._prev = getattr(_tls, "loads", None)
    _tls.loads = scope
    # a dispatch starts with no trace open on its thread: what the
    # nesting ledger holds can no longer be inside anything
    done = getattr(_tls, "traced", None)
    if done:
        done.clear()
    return scope


def close_loads(scope: LoadScope) -> None:
    _tls.loads = scope._prev


def _on_jax_event(event: str, **_kw) -> None:
    kind = _CACHE_EVENTS.get(event)
    if kind is not None and _ON:
        _tls.cache = kind


def _on_jax_duration(event: str, duration: float, **kw) -> None:
    if not _ON:
        return
    stage = _LOAD_STAGES.get(event)
    if stage is None:
        if event == _CACHE_RETRIEVAL:
            _tls.retrieval = duration
        return
    site, label = stage
    if label == "trace" and duration < _TRACE_FLOOR_S:
        return
    # JAX times a stage on the wall clock, which can step back; a
    # listener that raised would fail the compile it watches
    duration = max(duration, 0.0)
    t0 = time.perf_counter() - duration
    scope = getattr(_tls, "loads", None)
    attrs = {"fun": str(kw.get("fun_name"))}
    if scope is not None:
        scope.stages += 1
        if scope.plan is not None:
            attrs["plan"] = scope.plan
    counted = duration
    if label == "trace":
        # JAX reports a function traced inside another's trace on its
        # own AND inside the outer duration. The spans keep both (they
        # nest in time); the counter takes each second once: this
        # trace's duration less the recorded traces that began inside it
        done = getattr(_tls, "traced", None)
        if done is None:
            done = _tls.traced = []
        while done and done[-1][0] >= t0 - 5e-5:
            counted -= done.pop()[1]
        done.append((t0, duration))
        if len(done) > 1024:
            del done[:512]
        counted = max(counted, 0.0)
    elif label == "backend":
        attrs["cache"] = cache = getattr(_tls, "cache", None) or "off"
        retrieval = getattr(_tls, "retrieval", None)
        _tls.cache = _tls.retrieval = None
        if retrieval is not None:
            attrs["retrieval_s"] = retrieval
        again = "0"
        if scope is not None:
            # a program loaded by nobody's plan has no ``nth``: there
            # is nothing it could be the second load of
            scope.backend += 1
            attrs["nth"] = scope.nth
            again = "1" if scope.nth >= 2 else "0"
        PROGRAM_LOADS.labels(cache=cache, again=again).inc()
    PROGRAM_LOAD_SECONDS.labels(stage=label).inc(counted)
    record_span(site, t0, duration, **attrs)


def watch_program_loads() -> bool:
    """Register the program-load listener with ``jax.monitoring``, once
    a process (idempotent; the first ``Executor``/``ParallelEngine``
    and ``flags.enable_compile_cache`` call it, so importing ``observe``
    never imports jax). With tracing off nothing is registered; a later
    call with tracing on registers. Returns whether the listener is
    registered."""
    global _WATCHING
    if _WATCHING or not _ON:
        return _WATCHING
    with _LOAD_LOCK:
        if _WATCHING:
            return True
        try:
            from jax import monitoring
        except Exception:  # noqa: BLE001 — observe works without jax
            return False
        monitoring.register_event_listener(_on_jax_event)
        monitoring.register_event_duration_secs_listener(_on_jax_duration)
        _WATCHING = True
    return True


# -------------------------------------------------------- wire metadata
# serialized context for message-riding propagation (RPC name suffix);
# kept dense and separator-free so any framed string field can carry it
def wire_metadata(ctx: Optional[TraceContext] = None) -> Optional[str]:
    """``"t=<trace_id>,s=<span_id>"`` for the given/current context, or
    None when tracing is off or no context is active."""
    if not _ON:
        return None
    ctx = ctx if ctx is not None else current()
    if ctx is None:
        return None
    return "t=%s,s=%d" % (ctx.trace_id, ctx.span_id)


def from_wire(meta: Optional[str]) -> Optional[TraceContext]:
    """Parse ``wire_metadata()`` output; junk returns None (a peer on a
    different version must never crash the receiver)."""
    if not meta:
        return None
    trace_id, span_id = None, None
    for part in meta.split(","):
        if part.startswith("t="):
            trace_id = part[2:]
        elif part.startswith("s="):
            try:
                span_id = int(part[2:])
            except ValueError:
                return None
    if not trace_id or span_id is None:
        return None
    return TraceContext(trace_id, span_id)


# ------------------------------------------------------------- dumping
_CRITICAL_DUMPED = False  # a wedge/crash dump landed at the env path


def dump_flight_recorder(path: Optional[str] = None, reason: str = "manual",
                         extra: Optional[dict] = None) -> Optional[str]:
    """Write the ring to ``path`` (default: the
    ``PADDLE_TPU_FLIGHT_RECORDER_PATH`` env knob). Returns the path, or
    None when no destination is configured — callers on failure paths
    (watchdog, fault plane, atexit) call unconditionally and let this
    decide. Never raises: a post-mortem writer must not mask the fault
    being post-mortemed."""
    global _CRITICAL_DUMPED
    path = path or os.environ.get(ENV_PATH)
    if not path:
        return None
    try:
        names = sys.modules.get(__package__ + ".device_names")
        asked = {k: v for k, v in names.tables().items() if v} \
            if names is not None else None
        if asked:
            # the name tables somebody asked for go with the spans that
            # name their plans: tools/trace_view.py joins the two with a
            # kept device profile
            extra = dict(extra or {}, device_names=asked)
        RECORDER.dump(path, reason=reason, extra=extra)
        if reason in ("wedge", "crash"):
            _CRITICAL_DUMPED = True
        return path
    except Exception:
        return None


def _atexit_dump() -> None:
    # a wedge/crash dump is the evidence this machinery exists for: a
    # process that wedged, recovered and later exited cleanly must NOT
    # overwrite it with an uninformative clean-exit ring (the wedge
    # window has long since evicted by then)
    if len(RECORDER) and not _CRITICAL_DUMPED:
        dump_flight_recorder(reason="atexit")


atexit.register(_atexit_dump)


# -------------------------------------------------------- chrome export
def to_chrome_events(events: List[Dict[str, Any]],
                     pid: Optional[int] = None) -> List[dict]:
    """Convert event dicts to chrome://tracing entries. Matched B/E
    pairs (by span id) become complete ``X`` slices; an unmatched B —
    the wedged-dispatch signature — stays a ``B`` so it renders as an
    open slice; instants map to ``i``. The oldest event is ts=0."""
    base_t = min((e["t"] for e in events), default=0.0)
    pid = pid if pid is not None else os.getpid()
    ends = {e["span"]: e for e in events if e["ph"] == "E"}
    out = []
    for e in events:
        args = dict(e["attrs"] or {})
        common = {"name": e["site"], "cat": "trace", "pid": pid,
                  "tid": e["tid"], "ts": (e["t"] - base_t) * 1e6}
        if e["ph"] == "B":
            end = ends.get(e["span"])
            if end is not None:
                # the E event carries the FINAL attrs (late-attached
                # keys included) — prefer them for the complete slice
                args.update(end["attrs"] or {})
                args["trace"] = e["trace"]
                out.append(dict(common, ph="X", args=args,
                                dur=(end["t"] - e["t"]) * 1e6))
            else:
                args["trace"] = e["trace"]
                out.append(dict(common, ph="B", args=args))  # open: the
                #                                              wedge
            continue
        if e["ph"] == "I":
            args["trace"] = e["trace"]
            out.append(dict(common, ph="i", s="t", args=args))
    return out


def export_chrome_trace(path: str) -> str:
    """Write the ring as chrome://tracing JSON: the program's spans and
    a profiling session's ``RecordEvent`` markers are the same span
    type, so one list holds both."""
    trace = to_chrome_events(RECORDER.events())
    with open(path, "w") as f:
        json.dump({"traceEvents": trace, "displayTimeUnit": "ms"}, f)
    return path


def _reset() -> None:
    """Test isolation: clear the ring, the critical-dump latch and this
    thread's context (other threads' contexts die with their threads)."""
    global _CRITICAL_DUMPED
    RECORDER.clear()
    _CRITICAL_DUMPED = False
    _tls.ctx = None
    _tls.loads = None
