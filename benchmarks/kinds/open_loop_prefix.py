"""Runner kind ``open_loop_prefix``: ``open_loop_blocks``'s open loop over
a ``serving.DecodeEngine`` with its ``PrefixStore``, every prompt one
system prompt followed by a private tail.

The schedule, the generator, the window, the seeded weights and the
comparison that decides ``correct`` are ``open_loop_blocks``'s own,
imported: the traffic file's ``prompt_lengths`` are the TAILS, so the due
times and the order of lengths are ``chat_steady``'s for the same seed.
What differs: one system prompt of ``prefix_len`` tokens is drawn once a
run from the seed and put before every tail; the engine is built with a
prefix store and every request is submitted with ``prefix_len``; the
warm-up sends the run's own system prompt before each tail length, so the
store holds the prefix and every suffix program is compiled before the
ramp, and every admission of the run is a hit."""

import numpy as np

from benchmarks.kinds.open_loop_blocks import (SPAN_SITES, check, drive,
                                               seeded_params)
from benchmarks.lib import closed_forms, open_loop
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.stats import percentile


class WithPrefix:
    """The engine as the generator and the check see it: ``submit`` marks
    the system prompt as the reusable head."""

    def __init__(self, engine, prefix_len):
        self.engine, self.prefix_len = engine, int(prefix_len)
        self.queue = engine.queue

    def submit(self, prompt, n_new):
        return self.engine.submit(prompt, n_new, prefix_len=self.prefix_len)

    def stop(self):
        self.engine.stop()


def system_prompt(seed, vocab, length):
    """The run's one system prompt (a stream of its own)."""
    return np.random.default_rng([seed, 2]).integers(
        0, vocab, size=int(length), dtype=np.int64)


def prompts_of(requests, seed, vocab, system):
    """``system`` followed by each request's private tail
    (``open_loop.token_ids``: the tails are ``chat_steady``'s prompts)."""
    return [np.concatenate([system, tail])
            for tail in open_loop.token_ids(requests, seed, vocab)]


def warm_up(engine, traffic, vocab, monitor, system):
    """The run's system prompt before a tail of every length, alone, in
    rounds until one brings no compilation (two at least, four at most):
    the first request prefills whole and leaves the prefix in the store,
    every later one is a hit and compiles its suffix program."""
    rng = np.random.default_rng(0)
    for round_ in range(4):
        before = monitor.snapshot()
        for tail in sorted(int(k) for k in traffic["prompt_lengths"]):
            prompt = np.concatenate(
                [system, rng.integers(0, vocab, size=tail, dtype=np.int64)])
            engine.submit(prompt, 3).result(timeout=3000)
        if round_ and not monitor.since(before)["backend_compiles"]:
            break


def build_engine(cfg, serving, traffic, seed, monitor, system):
    from paddle_tpu.serving import DecodeEngine

    params = seeded_params(cfg, serving, seed)
    engine = DecodeEngine(cfg, params=params,
                          b_max=serving["b_max"],
                          max_len=serving["max_len"],
                          queue_capacity=traffic["queue_capacity"],
                          prefix_cache_bytes=traffic["prefix_cache_bytes"])
    engine.start()
    served = WithPrefix(engine, len(system))
    try:
        warm_up(served, traffic, cfg["vocab"], monitor, system)
    except BaseException:
        engine.stop()
        raise
    return served, params


def run(ctx):
    from paddle_tpu.observe import trace as flight
    from paddle_tpu.observe.families import (SERVING_PREFIX_HITS,
                                             SERVING_PREFIX_MISSES)

    tr, cfg = ctx.traffic, dict(ctx.config["model"])
    serving = ctx.config["serving"]
    horizon = tr["ramp_s"] + ctx.seconds
    prefix = int(tr["prefix_len"])
    tails = open_loop.schedule(tr, ctx.seed, horizon)
    system = system_prompt(ctx.seed, cfg["vocab"], prefix)
    prompts = prompts_of(tails, ctx.seed, cfg["vocab"], system)
    requests = [(due, prefix + tail, n_new) for due, tail, n_new in tails]
    if ctx.trace:
        flight.recorder().resize(1 << 18)
    engine, params = build_engine(cfg, serving, tr, ctx.seed, ctx.monitor,
                                  system)
    try:
        hits0 = (SERVING_PREFIX_HITS.labels().value,
                 SERVING_PREFIX_MISSES.labels().value)
        d = drive(engine, tr, requests, prompts, ctx.seconds, ctx)
        host_spans = ctx.flight_spans("serving.") if ctx.trace else []
        hits = SERVING_PREFIX_HITS.labels().value - hits0[0]
        misses = SERVING_PREFIX_MISSES.labels().value - hits0[1]
        # the reference pads to the longest WHOLE request
        whole = dict(tr, prompt_lengths={
            str(prefix + int(k)): v
            for k, v in tr["prompt_lengths"].items()})
        why_not, failed, compared = check(engine, params, cfg, whole,
                                          requests, prompts, d)
    finally:
        engine.stop()
    if misses:
        why_not.append("%d admission(s) of the run missed the prefix store"
                       % misses)

    gen, t_open, t_close = d["gen"], d["t_open"], d["t_close"]
    spans = {site: [] for site in SPAN_SITES}
    for site, start, dur in host_spans:
        if site in spans:
            # (end on the host's perf_counter clock, duration)
            spans[site].append((start + dur, dur))
    facts = {
        "rate": float(tr["rate"]), "requests_scheduled": len(requests),
        "requests_in_window": len(d["in_window"]),
        "completed_in_window": len(d["sample"]),
        "tokens_out": d["tokens_out"], "prefix_len": prefix,
        "prefix_hits": int(hits), "prefix_misses": int(misses),
        "offered_tok_s": open_loop.offered_tokens_per_s(requests, horizon),
        "decode_steps": d["decode_steps"], "b_max": serving["b_max"],
        "decode_step_bytes": closed_forms.gpt_decode_step_bytes(
            cfg, serving["b_max"], serving["max_len"], 4, 4),
        "window_s": t_close - t_open, **compared,
        "queue_at_close": d["queue_at_close"],
        "unfinished_at_close": d["unfinished_at_close"],
    }
    return {
        "correct": not why_not, "why_not": why_not,
        "attempted": len(d["in_window"]), "failed": failed,
        "end_to_end": {
            "serve_tok_s": d["tokens_out"] / (t_close - t_open),
            "req_tok_ms_p50": percentile(d["per_tok_ms"], 50),
            "req_tok_ms_p95": percentile(d["per_tok_ms"], 95),
        },
        "facts": facts,
        "samples": {"gen_late_ms": [gen.late[i] * 1e3
                                    for i in d["in_window"]
                                    if gen.late[i] is not None],
                    "req_tok_ms": d["per_tok_ms"]},
        "spans": spans,
        "counters": {"occupancy_mean": d["occupancy_mean"]},
        "peaks": None if ctx.rehearsal
        else peaks_for(ctx.devices[0].device_kind),
        "trace": ctx.reduce_trace(host_spans),
    }
