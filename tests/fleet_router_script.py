"""Subprocess body for the fleet telemetry demo: one serving-tier
process running a 2-replica ReplicaRouter, exporting live metrics.

Contract with the parent test (tests/test_fleet_telemetry.py):

* ``PADDLE_TPU_METRICS_PORT=0`` + ``PADDLE_TPU_METRICS_PORT_FILE`` —
  the standard exporter rendezvous (export.start_from_env).
* ``FLEET_ROUTER_SIDECAR`` — where to dump the registry snapshot
  AFTER all serving work is done and the router is closed, i.e. after
  every counter this process will ever move has stopped moving. From
  that point the process just holds ``/metrics`` open (only the
  exporter's own self-scrape counter moves), so a late scrape and the
  sidecar agree byte-for-byte on every other family.
* The parent kills the process when it is done with it.
"""

import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    from paddle_tpu.observe.export import start_from_env
    from paddle_tpu.observe.families import REGISTRY
    from paddle_tpu.serving import DecodeEngine, ReplicaRouter

    exporter = start_from_env()
    assert exporter is not None, "parent must set PADDLE_TPU_METRICS_PORT"

    cfg = dict(d_model=32, d_ff=64, n_head=2, n_layer=2, vocab=64,
               max_length=32, dropout=0.0)
    router = ReplicaRouter(
        lambda idx: DecodeEngine(cfg, b_max=2, max_len=32),
        n_replicas=2)
    try:
        rs = np.random.RandomState(11)
        reqs = [router.submit(rs.randint(1, 64, (4,)).astype("int64"), 4)
                for _ in range(4)]
        for r in reqs:
            r.result(timeout=120)
    finally:
        router.close()

    REGISTRY.dump(os.environ["FLEET_ROUTER_SIDECAR"])
    print("router ready: %s" % exporter.endpoint, flush=True)
    time.sleep(120)  # parent kills us; the exporter stays scrapeable
    return 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
