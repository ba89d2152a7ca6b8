#!/usr/bin/env python
"""Seeded open-loop load driver for the serving fleet tier.

Drives a :class:`paddle_tpu.serving.ReplicaRouter` with an open-loop
exponential arrival process (requests arrive on the clock regardless of
completion — queueing delay lands in latency instead of silently
throttling the generator), a configurable tenant mix, and a
shared-prefix share: a fraction of requests open with one shared
"system prompt" head so the prefix cache has something to reuse.

The ``drive()`` function is THE shared driver: the
``PADDLE_TPU_BENCH_SERVING=1`` bench mode's fleet row
(``bench.py:bench_serving_fleet``) and the router chaos test
(tests/test_serving_fleet.py) both call it, so the numbers the bench
reports and the behavior the chaos test pins come from one code path.

CLI: build a small synthetic-weight fleet and drive it, printing
p50/p99 latency, tokens/sec, outcome counts, prefix hit rate and
speculative acceptance::

    python tools/serving_load.py --requests 64 --replicas 2 \
        --prefix-share 0.8 --tenants default:0.9,burst:0.1

The fleet's replicas are threads of this one process, which therefore
owns the chip; it runs on the backend JAX finds (JAX_PLATFORMS=cpu for
a CPU rehearsal).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, Optional

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def _latency_hist(lat_s):
    """Fold raw latencies into a PRIVATE histogram (the declared
    request-latency family's bucket schema) so p50/p99 come from the
    shared ``Histogram.quantile`` — the same estimator every sidecar
    reader uses — instead of a hand-rolled percentile. Private
    registry on purpose: the engine already observes these requests
    into the process-wide ``paddle_serving_request_seconds``; folding
    them again there would double-count."""
    from paddle_tpu.observe.metrics import Registry

    hist = Registry().histogram("paddle_serving_request_seconds")
    for v in lat_s:
        hist.observe(v)
    return hist


def drive(router, n_requests: int, mean_gap_s: float, *,
          seed: int = 0, vocab: int = 64, prompt_len: int = 12,
          n_new: int = 8, prefix_share: float = 0.0,
          prefix_len: Optional[int] = None,
          tenant_mix: Optional[Dict[str, float]] = None,
          deadline_s: Optional[float] = None,
          timeout_s: float = 600.0) -> dict:
    """Open-loop drive of ``router``; returns a stats dict.

    ``prefix_share`` of the requests start with ONE shared
    ``prefix_len``-token head (drawn once from the seed) followed by a
    unique tail; the rest are fully unique. ``tenant_mix`` maps tenant
    id -> probability. Latency is completion minus SCHEDULED arrival
    (late submission counts against the server, as in any open-loop
    harness). Outcome counts come from the request futures themselves —
    a rejected/expired submit is an outcome, not an error of the
    driver. Prefix/speculative rates are read from the observe registry
    as deltas over the drive."""
    from paddle_tpu import observe
    from paddle_tpu.serving import (Cancelled, DeadlineExpired, QueueFull,
                                    TenantQuotaExceeded)

    rs = np.random.RandomState(seed)
    if prefix_len is None:
        prefix_len = max(1, prompt_len // 2)
    if not 0 <= prefix_share <= 1:
        raise ValueError("prefix_share must be in [0, 1]")
    if prefix_share and not 0 < prefix_len < prompt_len:
        raise ValueError("prefix_len must be in (0, prompt_len) when "
                         "prefix_share > 0")
    shared = rs.randint(1, vocab, (prefix_len,)).astype("int64")
    tenants = sorted((tenant_mix or {"default": 1.0}).items())
    t_names = [t for t, _ in tenants]
    t_probs = np.asarray([p for _, p in tenants], dtype="float64")
    t_probs = t_probs / t_probs.sum()

    plans = []
    for _ in range(n_requests):
        is_shared = rs.random_sample() < prefix_share
        if is_shared:
            tail = rs.randint(1, vocab,
                              (prompt_len - prefix_len,)).astype("int64")
            prompt, plen = np.concatenate([shared, tail]), prefix_len
        else:
            prompt, plen = rs.randint(1, vocab,
                                      (prompt_len,)).astype("int64"), None
        plans.append((prompt, plen,
                      t_names[int(rs.choice(len(t_names), p=t_probs))]))
    arrivals = np.cumsum(rs.exponential(mean_gap_s, size=n_requests))

    def _delta(name, before):
        total = 0.0
        for s in observe.snapshot()["metrics"][name]["samples"]:
            total += s.get("value", s.get("count", 0.0))
        return total - before

    def _total(name):
        return _delta(name, 0.0)

    before = {n: _total(n) for n in (
        "paddle_serving_prefix_hits_total",
        "paddle_serving_prefix_misses_total",
        "paddle_serving_prefix_tokens_saved_total",
        "paddle_serving_spec_proposed_tokens_total",
        "paddle_serving_spec_accepted_tokens_total")}

    reqs = [None] * n_requests
    done_at = [None] * n_requests
    outcomes: Dict[str, int] = {}
    t_start = time.perf_counter()
    for i, ((prompt, plen, tenant), at) in enumerate(zip(plans, arrivals)):
        dt = t_start + at - time.perf_counter()
        if dt > 0:
            time.sleep(dt)
        try:
            req = router.submit(prompt, n_new, tenant=tenant,
                                deadline_s=deadline_s,
                                prefix_len=plen)
        except (QueueFull, TenantQuotaExceeded, DeadlineExpired) as exc:
            kind = ("quota" if isinstance(exc, TenantQuotaExceeded)
                    else "slo" if isinstance(exc, DeadlineExpired)
                    else "rejected")
            outcomes[kind] = outcomes.get(kind, 0) + 1
            continue
        reqs[i] = req
        # completion stamped by the finishing thread, NOT at harvest:
        # a blocked early harvest must not inflate later latencies
        req.add_done_callback(
            lambda _r, i=i: done_at.__setitem__(i, time.perf_counter()))

    lat, tokens_done = [], 0
    for i, r in enumerate(reqs):
        if r is None:
            continue
        try:
            out = r.result(timeout=timeout_s)
            tokens_done += len(out) - len(plans[i][0])
            outcomes["ok"] = outcomes.get("ok", 0) + 1
            lat.append((done_at[i] or time.perf_counter())
                       - (t_start + arrivals[i]))
        except (Cancelled, DeadlineExpired) as exc:
            kind = ("expired" if isinstance(exc, DeadlineExpired)
                    else "cancelled")
            outcomes[kind] = outcomes.get(kind, 0) + 1
        except Exception:  # noqa: BLE001 — an errored request is an outcome
            outcomes["error"] = outcomes.get("error", 0) + 1
    wall = time.perf_counter() - t_start

    hits = _delta("paddle_serving_prefix_hits_total",
                  before["paddle_serving_prefix_hits_total"])
    misses = _delta("paddle_serving_prefix_misses_total",
                    before["paddle_serving_prefix_misses_total"])
    proposed = _delta("paddle_serving_spec_proposed_tokens_total",
                      before["paddle_serving_spec_proposed_tokens_total"])
    accepted = _delta("paddle_serving_spec_accepted_tokens_total",
                      before["paddle_serving_spec_accepted_tokens_total"])
    hist = _latency_hist(lat)
    return {
        "requests": n_requests,
        "wall_s": wall,
        "tokens": tokens_done,
        "tokens_per_sec": tokens_done / wall if wall > 0 else 0.0,
        "p50_ms": (1e3 * hist.quantile(0.50)) if lat else None,
        "p99_ms": (1e3 * hist.quantile(0.99)) if lat else None,
        "outcomes": outcomes,
        "prefix_hit_rate": (hits / (hits + misses)
                            if hits + misses else None),
        "prefix_tokens_saved": _delta(
            "paddle_serving_prefix_tokens_saved_total",
            before["paddle_serving_prefix_tokens_saved_total"]),
        "spec_accept_rate": (accepted / proposed) if proposed else None,
    }


def build_demo_router(n_replicas=2, b_max=4, prefix_cache=True,
                      spec=False, vocab=64, max_len=48,
                      stall_deadline_s=None, service_rate_tps=None,
                      tenant_quotas=None):
    """A small synthetic-weight fleet (startup-initialized GPT): the
    CLI's target, and the shape the bench/chaos-test routers follow."""
    from paddle_tpu.serving import DecodeEngine, PrefixStore, ReplicaRouter

    cfg = dict(d_model=32, d_ff=64, n_head=2, n_layer=2, vocab=vocab,
               max_length=max_len, dropout=0.0)
    draft = (dict(d_model=16, d_ff=32, n_head=2, n_layer=1, vocab=vocab,
                  max_length=max_len, dropout=0.0) if spec else None)
    store = PrefixStore(64 << 20) if prefix_cache else None

    def factory(idx):
        return DecodeEngine(cfg, params=None, b_max=b_max,
                            max_len=max_len, prefix_store=store,
                            draft_cfg=draft,
                            spec_k=3 if spec else 0)

    return ReplicaRouter(factory, n_replicas=n_replicas,
                         tenant_quotas=tenant_quotas,
                         service_rate_tps=service_rate_tps,
                         stall_deadline_s=stall_deadline_s)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="open-loop load driver for the serving fleet")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--b-max", type=int, default=4)
    ap.add_argument("--rate", type=float, default=None,
                    help="arrival rate (req/s); default self-calibrates")
    ap.add_argument("--prefix-share", type=float, default=0.8)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--prefix-len", type=int, default=None)
    ap.add_argument("--n-new", type=int, default=8)
    ap.add_argument("--tenants", default="default:1.0",
                    help="comma list of tenant:probability")
    ap.add_argument("--deadline-s", type=float, default=None)
    ap.add_argument("--spec", action="store_true",
                    help="attach a draft model (speculative decode)")
    ap.add_argument("--no-prefix-cache", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    from paddle_tpu.flags import enable_compile_cache

    enable_compile_cache()
    mix = {}
    for part in args.tenants.split(","):
        name, _, p = part.partition(":")
        mix[name.strip()] = float(p or 1.0)

    router = build_demo_router(n_replicas=args.replicas, b_max=args.b_max,
                               prefix_cache=not args.no_prefix_cache,
                               spec=args.spec)
    try:
        # warm the compile path (one request end to end), then
        # calibrate the arrival gap to ~saturate the fleet
        rs = np.random.RandomState(args.seed)
        warm = rs.randint(1, 64, (args.prompt_len,)).astype("int64")
        t0 = time.perf_counter()
        router.submit(warm, args.n_new).result(timeout=600)
        per_req = time.perf_counter() - t0
        if args.rate:
            gap = 1.0 / args.rate
        else:
            gap = max(per_req / (args.replicas * args.b_max), 1e-4)
        stats = drive(router, args.requests, gap, seed=args.seed,
                      prompt_len=args.prompt_len, n_new=args.n_new,
                      prefix_share=args.prefix_share,
                      prefix_len=args.prefix_len, tenant_mix=mix,
                      deadline_s=args.deadline_s)
    finally:
        router.close()
    if args.json:
        print(json.dumps(stats, indent=2, default=float))
    else:
        def _fmt(v, nd=3):
            return "n/a" if v is None else round(v, nd)

        print("requests      %d   wall %.2fs" % (stats["requests"],
                                                 stats["wall_s"]))
        print("tokens/sec    %.1f" % stats["tokens_per_sec"])
        print("latency       p50 %s ms   p99 %s ms"
              % (_fmt(stats["p50_ms"], 1), _fmt(stats["p99_ms"], 1)))
        print("outcomes      %s" % (stats["outcomes"],))
        print("prefix        hit_rate %s  tokens_saved %d"
              % (_fmt(stats["prefix_hit_rate"]),
                 stats["prefix_tokens_saved"]))
        print("speculative   accept_rate %s"
              % (_fmt(stats["spec_accept_rate"]),))
    return 0


if __name__ == "__main__":
    sys.exit(main())
