"""resilience: fault injection, wedge watchdog, checkpoint-resume.

The runtime layer that treats the platform as unreliable BY
CONSTRUCTION: a dispatch that never returns, a host that dies mid-step
and a lost RPC are all expected events. Three cooperating pieces:

* :mod:`~paddle_tpu.resilience.faults` — a deterministic, seeded
  fault-injection plane: a :class:`FaultPlan` arms named sites compiled
  into the hot paths (``executor.dispatch``, ``device_put``,
  ``rpc.send``, ``reader.next``, ``checkpoint.write``) to raise, delay,
  wedge or SIGKILL on chosen occurrences, installed via context manager
  or ``PADDLE_TPU_FAULT_PLAN``.
* :mod:`~paddle_tpu.resilience.watchdog` — heartbeat stamps from the
  executor's dispatch loop + a polling :class:`Watchdog` that tells a
  slow first-signature compile from a wedged dispatch and escalates
  log → callback → kill-process-group.
* :mod:`~paddle_tpu.resilience.supervisor` —
  :func:`resilient_train_loop`: periodic async checkpoints with an
  atomic manifest (latest-pointer, retain-last-K), jittered-backoff
  retry, and resume-from-latest that rebuilds the executor, reloads
  persistables + the RNG chain, and fast-forwards the reader so a
  crashed-and-restarted run is bitwise identical to an uninterrupted
  one.

Everything counts into the ``paddle_resilience_*`` observe families, so
chaos tests assert on telemetry. See docs/RESILIENCE.md.
"""

from .backoff import backoff_delay, millis_env  # noqa: F401
from .elastic import (ElasticJobResult, ElasticJobSupervisor,  # noqa: F401
                      demo_builder, demo_feed)
from .faults import (FaultPlan, FaultSpec, InjectedFault,  # noqa: F401
                     active_plan, fault_point)
from .supervisor import (MANIFEST_NAME, SupervisorResult,  # noqa: F401
                         latest_checkpoint_dir, read_manifest,
                         resilient_train_loop, write_manifest)
from .watchdog import (Heartbeat, Watchdog, WedgeEvent,  # noqa: F401
                       heartbeat, run_with_deadline)

__all__ = [
    "FaultPlan", "FaultSpec", "InjectedFault", "fault_point",
    "active_plan",
    "Heartbeat", "Watchdog", "WedgeEvent", "heartbeat",
    "run_with_deadline",
    "resilient_train_loop", "SupervisorResult", "read_manifest",
    "write_manifest", "latest_checkpoint_dir", "MANIFEST_NAME",
    "ElasticJobSupervisor", "ElasticJobResult", "demo_builder",
    "demo_feed",
    "backoff_delay", "millis_env",
]
