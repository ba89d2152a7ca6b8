"""Step-progress watchdog: detect a wedged dispatch, escalate by policy.

The failure this hunts: a dispatch enters a C call against a hung
device or runtime and never returns — no exception, no timeout, no
KeyboardInterrupt. The executor
stamps a process-wide :class:`Heartbeat` around every dispatch
(``begin`` before handing off to XLA, ``end`` when the call returns);
the :class:`Watchdog` thread polls those stamps and declares a WEDGE
when an operation has been ``busy`` past its deadline with no new stamp.

Two deadlines, because "slow" is not "wedged": a stamp opened with
``compiling=True`` (the plan's first dispatch per signature — jax trace
+ XLA compile, legitimately minutes for BERT-class programs) is judged
against ``compile_grace_s``; steady-state dispatches against the much
tighter ``deadline_s``. A wedge fires ONCE per stalled operation (not
once per poll) into ``paddle_resilience_wedges_detected_total{site}``
and then escalates through the policy ladder:

1. **log** — always: one stderr line with site/age/step.
2. **callback** — ``on_wedge(event)`` when given (the supervisor uses
   this to mark the step doomed before the fault surfaces).
3. **kill** — ``kill=True`` SIGKILLs the whole process GROUP, the only
   exit from a C-level hang (default off).

``run_with_deadline`` is the bounded-call primitive — run a
possibly-wedging callable on a daemon thread, give up at the deadline,
report which of ok/error/timeout happened and how long it took.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Callable, Optional

__all__ = ["Heartbeat", "Watchdog", "WedgeEvent", "heartbeat",
           "run_with_deadline"]


class Heartbeat:
    """Process-wide progress stamps. Every ``begin`` opens an operation
    (keyed by its returned token) and ``end`` closes it; ``snapshot``
    reports the OLDEST still-open operation. Tracking open operations —
    not just the latest stamp — is what keeps a concurrently stamping
    thread (a serving batcher dispatching while a training dispatch
    wedges) from masking the stall: the wedged operation stays open and
    stays oldest, so its age keeps growing no matter how many healthy
    stamps land around it."""

    __slots__ = ("_lock", "_seq", "_open", "_site", "_stamp")

    IDLE, BUSY = "idle", "busy"

    def __init__(self):
        self._lock = threading.Lock()
        self._seq = 0
        self._open: dict = {}  # token -> {site, step, compiling, t}
        self._site = None       # last site stamped (idle reporting)
        self._stamp = time.monotonic()

    def begin(self, site: str, step: Optional[int] = None,
              compiling: bool = False) -> int:
        """Open an operation; returns the token ``end`` should close."""
        with self._lock:
            self._seq += 1
            tok = self._seq
            self._open[tok] = {"site": site, "step": step,
                               "compiling": compiling,
                               "t": time.monotonic()}
            self._site = site
            self._stamp = self._open[tok]["t"]
            return tok

    def end(self, site: str, token: Optional[int] = None) -> None:
        """Close an operation (by token; without one, the newest open
        entry for ``site`` — a compatibility fallback for hand-rolled
        callers)."""
        with self._lock:
            self._seq += 1
            if token is not None:
                self._open.pop(token, None)
            else:
                for k in sorted(self._open, reverse=True):
                    if self._open[k]["site"] == site:
                        del self._open[k]
                        break
            self._site = site
            self._stamp = time.monotonic()

    def snapshot(self) -> dict:
        """Poller view: the OLDEST open operation (phase=busy), else the
        last stamp (phase=idle). ``seq`` identifies ONE operation, so
        the watchdog fires once per stall, and a new operation — even at
        the same site — re-arms it."""
        with self._lock:
            now = time.monotonic()
            if self._open:
                tok = min(self._open, key=lambda k: self._open[k]["t"])
                op = self._open[tok]
                return {"seq": tok, "site": op["site"],
                        "phase": Heartbeat.BUSY, "step": op["step"],
                        "compiling": op["compiling"],
                        "age_s": now - op["t"]}
            return {"seq": self._seq, "site": self._site,
                    "phase": Heartbeat.IDLE, "step": None,
                    "compiling": False, "age_s": now - self._stamp}


_HEARTBEAT = Heartbeat()


def heartbeat() -> Heartbeat:
    """The process-wide heartbeat the executor stamps."""
    return _HEARTBEAT


class WedgeEvent:
    """One detected wedge, handed to the policy callback."""

    __slots__ = ("site", "step", "age_s", "compiling", "seq")

    def __init__(self, site, step, age_s, compiling, seq):
        self.site, self.step = site, step
        self.age_s, self.compiling, self.seq = age_s, compiling, seq

    def __repr__(self):
        return ("WedgeEvent(site=%r, step=%r, age=%.3fs%s)"
                % (self.site, self.step, self.age_s,
                   ", compiling" if self.compiling else ""))


class Watchdog:
    """Poll the heartbeat; escalate on a stamp older than its deadline.

    ``deadline_s``       steady-state dispatch deadline.
    ``compile_grace_s``  deadline while the stamped op is a first-
                         signature compile (default ``10 * deadline_s``,
                         floored at 60s — compiles are legitimately slow).
    ``poll_s``           poll cadence (default ``deadline_s / 4``,
                         clamped to [10ms, 1s]).
    ``on_wedge``         policy callback, called with a WedgeEvent after
                         telemetry + the log line; its exceptions are
                         swallowed (a broken policy must not kill the
                         detector).
    ``kill``             escalate to SIGKILL of the process group —
                         opt-in, for unattended runs where a process
                         hung on the device is worse than a dead run.
    """

    def __init__(self, deadline_s: float, poll_s: Optional[float] = None,
                 compile_grace_s: Optional[float] = None,
                 on_wedge: Optional[Callable] = None, kill: bool = False,
                 heartbeat: Optional[Heartbeat] = None):
        if deadline_s <= 0:
            raise ValueError("deadline_s must be > 0, got %r" % deadline_s)
        self.deadline_s = float(deadline_s)
        self.compile_grace_s = (float(compile_grace_s)
                                if compile_grace_s is not None
                                else max(10.0 * deadline_s, 60.0))
        self.poll_s = (float(poll_s) if poll_s is not None
                       else min(max(deadline_s / 4.0, 0.01), 1.0))
        self.on_wedge = on_wedge
        self.kill = kill
        self.wedges: list = []  # every WedgeEvent this watchdog fired
        self._hb = heartbeat if heartbeat is not None else _HEARTBEAT
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._fired_seq = -1

    # ----------------------------------------------------------- control
    def start(self) -> "Watchdog":
        if self._thread is not None:
            raise RuntimeError("Watchdog already started")
        from ..observe.families import RESILIENCE_WATCHDOG_ARMED

        self._stop.clear()
        self._thread = threading.Thread(target=self._run,
                                        name="paddle-tpu-watchdog",
                                        daemon=True)
        self._thread.start()
        RESILIENCE_WATCHDOG_ARMED.set(1)
        return self

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=5.0)
        from ..observe.families import RESILIENCE_WATCHDOG_ARMED

        RESILIENCE_WATCHDOG_ARMED.set(0)

    def __enter__(self) -> "Watchdog":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False

    def watching(self):
        """Readable start/stop scope: ``with wd.watching(): ...``"""
        import contextlib

        @contextlib.contextmanager
        def scope():
            self.start()
            try:
                yield self
            finally:
                self.stop()

        return scope()

    # ------------------------------------------------------------- loop
    def _run(self) -> None:
        from ..observe.families import (RESILIENCE_HEARTBEAT_AGE,
                                        RESILIENCE_WEDGES)

        while not self._stop.wait(self.poll_s):
            snap = self._hb.snapshot()
            if snap["phase"] != Heartbeat.BUSY:
                # 0, not the last busy age: a gauge frozen at "55s"
                # after a long-but-healthy compile would trip any
                # age-threshold alert on an idle process forever
                RESILIENCE_HEARTBEAT_AGE.set(0)
                continue
            RESILIENCE_HEARTBEAT_AGE.set(snap["age_s"])
            limit = self.compile_grace_s if snap["compiling"] \
                else self.deadline_s
            if snap["age_s"] <= limit or snap["seq"] == self._fired_seq:
                continue
            self._fired_seq = snap["seq"]
            event = WedgeEvent(snap["site"], snap["step"], snap["age_s"],
                               snap["compiling"], snap["seq"])
            self.wedges.append(event)
            RESILIENCE_WEDGES.labels(site=str(snap["site"])).inc()
            print("[paddle_tpu.watchdog] WEDGE: %r stalled %.1fs "
                  "(deadline %.1fs)%s" % (snap["site"], snap["age_s"],
                                          limit,
                                          " — killing process group"
                                          if self.kill else ""),
                  file=sys.stderr, flush=True)
            # post-mortem evidence BEFORE escalating (callback, kill):
            # the wedge event lands in the ring, then the whole ring
            # (with the
            # stalled dispatch's still-OPEN span — its trace id, site
            # and plan tag) dumps to PADDLE_TPU_FLIGHT_RECORDER_PATH.
            # dump_flight_recorder never raises and is a no-op when no
            # path is configured, so the detector cannot die here.
            from ..observe import trace as _tr

            if _tr.trace_enabled():
                _tr.trace_event("resilience.wedge", site=str(snap["site"]),
                                step=snap["step"], age_s=snap["age_s"],
                                compiling=snap["compiling"])
            _tr.dump_flight_recorder(
                reason="wedge",
                extra={"wedge": {"site": snap["site"], "step": snap["step"],
                                 "age_s": snap["age_s"],
                                 "compiling": snap["compiling"],
                                 "deadline_s": limit}})
            if self.on_wedge is not None:
                try:
                    self.on_wedge(event)
                except Exception:  # noqa: BLE001 — policy must not kill us
                    pass
            if self.kill:
                os.killpg(os.getpgid(os.getpid()), 9)


def run_with_deadline(fn: Callable, timeout_s: float, poll_s: float = 0.25):
    """Run ``fn()`` on a daemon thread with a hard deadline — the
    bounded-call primitive for operations that can wedge inside C (jax
    backend init against an unreachable device). Returns
    ``(ok, value, dt)``:
    ``(True, result, dt)`` on success, ``(False, exception, dt)`` when
    fn raised, ``(False, TimeoutError, dt)`` when the deadline passed
    with fn still running (the thread is abandoned — it is unjoinable by
    construction; the caller decides whether to retry or die)."""
    out, err = [], []

    def work():
        try:
            out.append(fn())
        except BaseException as e:  # noqa: BLE001 — reported, not raised
            err.append(e)

    t0 = time.perf_counter()
    t = threading.Thread(target=work, daemon=True,
                         name="paddle-tpu-deadline-call")
    t.start()
    deadline = t0 + timeout_s
    # poll instead of one long join: an instant failure must not burn
    # the full wedge timeout
    while t.is_alive() and time.perf_counter() < deadline:
        t.join(min(poll_s, max(deadline - time.perf_counter(), 0.001)))
    dt = time.perf_counter() - t0
    if out:
        return True, out[0], dt
    if err:
        return False, err[0], dt
    return False, TimeoutError(
        "call did not complete within %gs" % timeout_s), dt
