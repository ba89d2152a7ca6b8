"""Deterministic, seeded fault injection for chaos testing the runtime.

Production failures — a dispatch that hangs inside the runtime, a
process killed mid-checkpoint, a dropped RPC — are unreproducible by
nature, so the recovery machinery (watchdog, supervisor, RPC retries)
needs a way to manufacture them ON DEMAND, deterministically, in CI.

A :class:`FaultPlan` arms named **sites** — fixed strings compiled into
the runtime's hot paths:

========================  ====================================================
site                      fires
========================  ====================================================
``executor.dispatch``     once per Executor.run/run_repeated/run_pipelined
                          step, BEFORE the XLA dispatch (state untouched)
``device_put``            once per host->device feed transfer
                          (``feeds_to_device``, incl. the prefetch thread)
``rpc.send``              once per RPCClient.send_var
``reader.next``           once per batch pulled by DevicePrefetcher's
                          fill thread
``checkpoint.write``      once per ``tensor_store.save_tensors``, BETWEEN
                          the staged tmp-file write and the atomic rename
                          (the exact crash window a torn checkpoint needs)
``trainer.heartbeat``     once per elastic-trainer heartbeat
                          (``membership.HeartbeatSender.beat`` — one at
                          join, then one per resolved step; ``crash`` here
                          is THE way to kill trainer k at step s)
``membership.join``       once per join/rejoin the membership registry
                          processes (supervisor side; ``raise`` simulates
                          a partitioned join — the announcement is dropped
                          and the trainer's next heartbeat retries)
========================  ====================================================

Each armed spec picks a **trigger** (explicit 1-based occurrence
numbers, ``N+`` = every occurrence from the Nth, ``*`` = every
occurrence, or ``p=F`` = per-occurrence probability drawn from the
plan's seeded RNG) and a **mode**:

* ``raise``    — raise :class:`InjectedFault` (a transient error)
* ``delay=S``  — sleep S seconds, then continue normally
* ``wedge=S``  — sleep S seconds (long enough for a watchdog to fire),
  then raise :class:`InjectedFault` — a hang that eventually surfaces
* ``crash``    — SIGKILL this process (no cleanup handlers run; the
  crash-mid-checkpoint tests depend on exactly that)

Install via context manager (``with plan: ...``) or, for subprocess
chaos tests, via the ``PADDLE_TPU_FAULT_PLAN`` env var, e.g.::

    PADDLE_TPU_FAULT_PLAN='executor.dispatch@6:wedge=0.5;rpc.send@1,3:raise;seed=7'

Every injected fault counts into
``paddle_resilience_faults_injected_total{site,mode}`` so chaos tests
assert on telemetry, not on trust. When no plan is installed,
``fault_point()`` is two attribute loads and a ``None`` check — cheap
enough to stay compiled into the hot paths unconditionally.
"""

from __future__ import annotations

import os
import random
import signal
import threading
import time
from typing import Dict, FrozenSet, List, Optional, Tuple

__all__ = ["FaultPlan", "FaultSpec", "InjectedFault", "fault_point",
           "active_plan"]

ENV_VAR = "PADDLE_TPU_FAULT_PLAN"
MODES = ("raise", "delay", "wedge", "crash")


class InjectedFault(RuntimeError):
    """A fault raised by an armed FaultPlan — the injection plane's
    stand-in for a transient runtime failure (wedged dispatch, dropped
    RPC, torn checkpoint write). ``resilient_train_loop`` treats it as
    retryable by default."""

    def __init__(self, site: str, occurrence: int, mode: str):
        self.site, self.occurrence, self.mode = site, occurrence, mode
        super().__init__(
            "injected fault at site %r (occurrence %d, mode %s)"
            % (site, occurrence, mode))


class FaultSpec:
    """One armed site: trigger (steps / from_step / every / p) + mode."""

    __slots__ = ("site", "mode", "seconds", "steps", "from_step", "every",
                 "p")

    def __init__(self, site: str, mode: str = "raise", seconds: float = 0.0,
                 steps: Tuple[int, ...] = (), from_step: Optional[int] = None,
                 every: bool = False, p: Optional[float] = None):
        if mode not in MODES:
            raise ValueError("fault mode must be one of %s; got %r"
                             % (MODES, mode))
        if mode in ("delay", "wedge") and seconds < 0:
            raise ValueError("fault %s seconds must be >= 0" % mode)
        if p is not None and not (0.0 <= p <= 1.0):
            raise ValueError("fault probability must be in [0, 1]; got %r"
                             % (p,))
        triggers = bool(steps) + (from_step is not None) + every + \
            (p is not None)
        if triggers != 1:
            raise ValueError(
                "fault spec for %r needs exactly ONE trigger (steps, "
                "from_step, every, or p)" % site)
        self.site = site
        self.mode = mode
        self.seconds = float(seconds)
        self.steps: FrozenSet[int] = frozenset(steps)
        self.from_step = from_step
        self.every = every
        self.p = p

    def matches(self, occurrence: int, rng: random.Random) -> bool:
        if self.every:
            return True
        if self.steps:
            return occurrence in self.steps
        if self.from_step is not None:
            return occurrence >= self.from_step
        # probabilistic: one seeded draw per occurrence of this spec's
        # site — the sequence is fully determined by (plan seed, spec
        # order, occurrence order)
        return rng.random() < self.p

    def __repr__(self):
        if self.every:
            trig = "*"
        elif self.steps:
            trig = ",".join(str(s) for s in sorted(self.steps))
        elif self.from_step is not None:
            trig = "%d+" % self.from_step
        else:
            trig = "p=%g" % self.p
        act = self.mode
        if self.mode in ("delay", "wedge"):
            act += "=%g" % self.seconds
        return "%s@%s:%s" % (self.site, trig, act)


class FaultPlan:
    """A set of :class:`FaultSpec`\\ s plus per-site occurrence counters.

    Occurrences are counted PER PLAN across its whole installed
    lifetime (not per install), so a supervisor retry that re-dispatches
    earlier steps keeps advancing the count — "fail occurrence 6" means
    the 6th time the site is reached in the process, which is what makes
    a chaos schedule deterministic across recoveries."""

    def __init__(self, specs: Optional[List[FaultSpec]] = None,
                 seed: int = 0):
        self.specs: List[FaultSpec] = list(specs or [])
        self.seed = seed
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        self._injected = 0

    # ------------------------------------------------------------ build
    def arm(self, site: str, mode: str = "raise", seconds: float = 0.0,
            steps: Tuple[int, ...] = (), from_step: Optional[int] = None,
            every: bool = False, p: Optional[float] = None) -> "FaultPlan":
        self.specs.append(FaultSpec(site, mode, seconds, steps, from_step,
                                    every, p))
        return self

    @classmethod
    def parse(cls, text: str) -> "FaultPlan":
        """Parse the ``PADDLE_TPU_FAULT_PLAN`` grammar (see module doc):
        ``;``-separated clauses, each ``site@trigger:action`` or
        ``seed=N``."""
        plan = cls()
        seed = 0
        for clause in text.split(";"):
            clause = clause.strip()
            if not clause:
                continue
            if clause.startswith("seed="):
                seed = int(clause[len("seed="):])
                continue
            try:
                site, rest = clause.split("@", 1)
                trigger, action = rest.split(":", 1)
            except ValueError:
                raise ValueError(
                    "bad fault clause %r: expected site@trigger:action "
                    "(e.g. executor.dispatch@3:wedge=0.5)" % clause)
            site = site.strip()
            kw: Dict[str, object] = {}
            trigger = trigger.strip()
            if trigger == "*":
                kw["every"] = True
            elif trigger.startswith("p="):
                kw["p"] = float(trigger[2:])
            elif trigger.endswith("+"):
                kw["from_step"] = int(trigger[:-1])
            else:
                kw["steps"] = tuple(int(t) for t in trigger.split(","))
            action = action.strip()
            if "=" in action:
                mode, arg = action.split("=", 1)
                kw["seconds"] = float(arg)
            else:
                mode = action
            plan.arm(site, mode=mode.strip(), **kw)
        plan.seed = seed
        plan._rng = random.Random(seed)
        return plan

    # ---------------------------------------------------------- install
    def install(self) -> "FaultPlan":
        global _ACTIVE
        with _INSTALL_LOCK:
            if _ACTIVE is not None and _ACTIVE is not self:
                raise RuntimeError(
                    "a FaultPlan is already installed (%r); uninstall it "
                    "first — nested plans would make occurrence counting "
                    "ambiguous" % (_ACTIVE,))
            _ACTIVE = self
        from ..observe.families import RESILIENCE_FAULT_SITES_ARMED

        RESILIENCE_FAULT_SITES_ARMED.set(len(self.specs))
        return self

    def uninstall(self) -> None:
        global _ACTIVE
        with _INSTALL_LOCK:
            if _ACTIVE is self:
                _ACTIVE = None
        from ..observe.families import RESILIENCE_FAULT_SITES_ARMED

        # an env-armed plan resumes routing once the explicit plan is
        # gone: the gauge must keep reporting ITS armed specs, not 0
        env = _env_plan()
        RESILIENCE_FAULT_SITES_ARMED.set(
            len(env.specs) if env is not None else 0)

    def __enter__(self) -> "FaultPlan":
        return self.install()

    def __exit__(self, *exc) -> bool:
        self.uninstall()
        return False

    # ------------------------------------------------------------ state
    @property
    def injected(self) -> int:
        with self._lock:
            return self._injected

    def occurrences(self, site: str) -> int:
        with self._lock:
            return self._counts.get(site, 0)

    def __repr__(self):
        return "FaultPlan(%s%s)" % (
            "; ".join(repr(s) for s in self.specs),
            ", seed=%d" % self.seed if self.seed else "")

    # ----------------------------------------------------------- firing
    def _hit(self, site: str) -> None:
        with self._lock:
            n = self._counts.get(site, 0) + 1
            self._counts[site] = n
            fired = None
            for spec in self.specs:
                if spec.site == site and spec.matches(n, self._rng):
                    fired = spec
                    break
            if fired is not None:
                self._injected += 1
        if fired is None:
            return
        from ..observe import trace as _tr
        from ..observe.families import RESILIENCE_FAULTS_INJECTED

        RESILIENCE_FAULTS_INJECTED.labels(site=site, mode=fired.mode).inc()
        # the injection is part of the story a flight-recorder dump
        # tells: record it BEFORE acting, so a wedge dump (taken while
        # this thread sleeps below) and a crash dump both contain it
        if _tr.trace_enabled():
            _tr.trace_event("resilience.fault", site=site,
                            mode=fired.mode, occurrence=n)
        # act OUTSIDE the lock: a wedge must not serialize other sites
        if fired.mode == "delay":
            time.sleep(fired.seconds)
            return
        if fired.mode == "wedge":
            time.sleep(fired.seconds)
            raise InjectedFault(site, n, "wedge")
        if fired.mode == "crash":
            # SIGKILL, not sys.exit: no finally blocks, no atexit — the
            # point is to leave the wreckage (staged tmp files, stale
            # manifests) that real power-loss/OOM-kill leaves. The ONE
            # exception: the flight recorder dumps first — that's its
            # whole reason to exist, and a real OOM-killed process
            # similarly leaves whatever its last dump wrote.
            _tr.dump_flight_recorder(
                reason="crash",
                extra={"fault": {"site": site, "occurrence": n}})
            os.kill(os.getpid(), signal.SIGKILL)
        raise InjectedFault(site, n, "raise")


_INSTALL_LOCK = threading.Lock()
_ACTIVE: Optional[FaultPlan] = None
_ENV_PLAN: Optional[FaultPlan] = None
_ENV_CHECKED = False


def _env_plan() -> Optional[FaultPlan]:
    """Parse PADDLE_TPU_FAULT_PLAN once per process (subprocess chaos
    tests arm their plan this way — no code changes in the victim).
    Check-and-parse runs under the install lock: two threads hitting
    their first fault_point concurrently (main dispatch + prefetch
    fill) must share ONE plan instance, or occurrence counts would
    split across copies and the schedule lose its determinism."""
    global _ENV_PLAN, _ENV_CHECKED
    if _ENV_CHECKED:
        return _ENV_PLAN
    fresh = False
    with _INSTALL_LOCK:
        if not _ENV_CHECKED:
            text = os.environ.get(ENV_VAR)
            _ENV_PLAN = FaultPlan.parse(text) if text else None
            _ENV_CHECKED = True
            fresh = _ENV_PLAN is not None
    if fresh:
        from ..observe.families import RESILIENCE_FAULT_SITES_ARMED

        RESILIENCE_FAULT_SITES_ARMED.set(len(_ENV_PLAN.specs))
    return _ENV_PLAN


def active_plan() -> Optional[FaultPlan]:
    """The plan faults currently route through (installed or env)."""
    return _ACTIVE if _ACTIVE is not None else _env_plan()


def fault_point(site: str) -> None:
    """Compiled-in injection site: no-op (two loads + a None check)
    unless a plan is installed or armed via PADDLE_TPU_FAULT_PLAN."""
    plan = _ACTIVE
    if plan is None:
        plan = _env_plan()
        if plan is None:
            return
    plan._hit(site)
