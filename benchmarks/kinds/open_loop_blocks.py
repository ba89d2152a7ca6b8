"""Runner kind ``open_loop_blocks``: a ``serving.DecodeEngine`` under an
open loop of independent users (``benchmarks/lib/open_loop.py``).

Set-up builds the engine, draws the weights from the seed, warms the
decode step, the splice and the prefill executable of every prompt length
of the mix, builds every prompt, then starts the schedule ``ramp_s``
before the window opens so that occupancy is steady at both edges. In the
window the generator thread does nothing but sleep to the next due time
and call ``submit``; completion times are stamped by the engine's
done-callback. The sample is every request that completed inside the
window, timed from its scheduled arrival."""

import contextlib
import threading
import time

import numpy as np

from benchmarks.lib import closed_forms, open_loop
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.stats import percentile

SPAN_SITES = ("serving.queue.wait", "serving.engine.admit",
              "serving.engine.step", "serving.engine.prefill",
              "serving.engine.splice")


def seeded_params(cfg, serving, seed):
    """Every named weight matrix of the decoder, drawn on the device in
    one jitted call with the startup program's own Xavier-uniform limits.
    The names and shapes come from an IR-only build (no compile)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import gpt

    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        gpt.build_serving_decode_step(cfg, batch=1,
                                      max_len=serving["max_len"])
    shapes = {p.name: tuple(p.shape)
              for p in prog.global_block().all_parameters()
              if len(p.shape) == 2 and p.name.startswith("gpt_")}
    names = sorted(shapes)

    @jax.jit
    def draw(key):
        out = {}
        for i, n in enumerate(names):
            fan_in, fan_out = shapes[n]
            lim = (6.0 / (fan_in + fan_out)) ** 0.5
            out[n] = jax.random.uniform(jax.random.fold_in(key, i),
                                        shapes[n], jnp.float32, -lim, lim)
        return out

    return draw(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


class Generator(threading.Thread):
    """Sleeps to each due time and submits. Everything it submits was
    built before it started."""

    def __init__(self, engine, requests, prompts, t0, queue_full):
        super().__init__(name="bench-generator", daemon=True)
        self.engine, self.requests, self.prompts = engine, requests, prompts
        self.t0, self.queue_full = t0, queue_full
        n = len(requests)
        self.late = [None] * n       # submit time minus due time
        self.done_at = [None] * n    # stamped on the engine's thread
        self.handles = [None] * n
        self.refused = 0
        self.error = None

    def _stamp(self, i):
        def on_done(_request):
            self.done_at[i] = time.perf_counter()
        return on_done

    def run(self):
        try:
            for i, (due, _plen, n_new) in enumerate(self.requests):
                wait = self.t0 + due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                now = time.perf_counter()
                try:
                    handle = self.engine.submit(self.prompts[i], n_new)
                except self.queue_full:
                    self.refused += 1
                    continue
                self.late[i] = now - (self.t0 + due)
                handle.add_done_callback(self._stamp(i))
                self.handles[i] = handle
        except BaseException as exc:  # noqa: BLE001 — reported by run()
            self.error = exc


def warm_up(engine, traffic, vocab, monitor):
    """One request of every prompt length, alone, in rounds: compiles (or
    loads) the decode step, the splice and one prefill executable per
    length, and nothing this cell's traffic does not use. A prefill
    program compiles a second time when its caches are a previous run's
    outputs and no longer the startup program's, so rounds go on until
    one brings no compilation: two at least, four at most."""
    rng = np.random.default_rng(0)
    for round_ in range(4):
        before = monitor.snapshot()
        for plen in sorted(int(k) for k in traffic["prompt_lengths"]):
            prompt = rng.integers(0, vocab, size=plen, dtype=np.int64)
            engine.submit(prompt, 3).result(timeout=1200)
        if round_ and not monitor.since(before)["backend_compiles"]:
            break


def build_engine(cfg, serving, traffic, seed, monitor):
    """(the started engine with every executable of this traffic warm,
    the seeded weight matrices it was given)."""
    from paddle_tpu.serving import DecodeEngine

    params = seeded_params(cfg, serving, seed)
    engine = DecodeEngine(cfg, params=params,
                          b_max=serving["b_max"],
                          max_len=serving["max_len"],
                          queue_capacity=traffic["queue_capacity"])
    engine.start()
    try:
        warm_up(engine, traffic, cfg["vocab"], monitor)
    except BaseException:
        engine.stop()
        raise
    return engine, params


class Window:
    """Window hooks for a caller without the harness context (the rate
    sweep): clock marks only, no profiler."""

    trace = False

    def open_window(self):
        return time.perf_counter()

    def close_window(self):
        return time.perf_counter()

    def traced(self):
        return contextlib.nullcontext()


def drive(engine, traffic, requests, prompts, seconds, ctx):
    """Ramp, window, drain. Returns the generator (with its stamps), the
    clock marks, the slot-occupancy counter at the window's edges and
    every completed request's output."""
    from paddle_tpu.observe.families import SERVING_OCCUPANCY
    from paddle_tpu.serving.queue import QueueFull

    occupancy = SERVING_OCCUPANCY.labels()

    t0 = time.perf_counter() + 0.05
    gen = Generator(engine, requests, prompts, t0, QueueFull)
    gen.start()
    time.sleep(max(0.0, t0 + traffic["ramp_s"] - time.perf_counter()))
    occ0 = (occupancy.sum, occupancy.count)
    t_open = ctx.open_window()
    with ctx.traced():
        if ctx.trace:
            time.sleep(min(traffic.get("trace_seconds", 3.0), seconds))
    time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
    t_close = ctx.close_window()
    steps = occupancy.count - occ0[1]
    occupancy_mean = (occupancy.sum - occ0[0]) / steps if steps else None
    waiting = len(engine.queue)

    # drain, off the clock
    gen.join(timeout=30)
    if gen.error is not None:
        raise gen.error
    deadline = time.perf_counter() + traffic["drain_timeout_s"]
    outputs, errors = {}, 0
    for i, handle in enumerate(gen.handles):
        if handle is None:
            continue
        try:
            outputs[i] = handle.result(
                timeout=max(0.1, deadline - time.perf_counter()))
        except Exception:  # noqa: BLE001 — any failed request counts
            errors += 1
    sample = [i for i in outputs if gen.done_at[i] is not None
              and t_open <= gen.done_at[i] <= t_close]
    per_tok_ms = [(gen.done_at[i] - (t0 + requests[i][0]))
                  / requests[i][2] * 1e3 for i in sample]
    in_window = [i for i, r in enumerate(requests)
                 if t_open <= t0 + r[0] <= t_close]
    return {
        "gen": gen, "t0": t0, "t_open": t_open, "t_close": t_close,
        "outputs": outputs, "errors": errors, "sample": sample,
        "per_tok_ms": per_tok_ms, "in_window": in_window,
        "tokens_out": sum(requests[i][2] for i in sample),
        "queue_at_close": waiting, "decode_steps": steps,
        "occupancy_mean": occupancy_mean,
        "unfinished_at_close": sum(
            1 for i, h in enumerate(gen.handles) if h is not None
            and (gen.done_at[i] is None or gen.done_at[i] > t_close)),
    }


def check(engine, params, cfg, traffic, requests, prompts, d):
    """(why the run is not correct, if it is not; failed requests; facts
    of the comparison)."""
    from benchmarks.lib import reference_gpt

    why_not = []
    for i, out in d["outputs"].items():
        plen, n_new = requests[i][1], requests[i][2]
        if out.shape[0] != plen + n_new:
            why_not.append("request %d returned %d tokens, asked %d"
                           % (i, out.shape[0] - plen, n_new))
            break
        if out.min() < 0 or out.max() >= cfg["vocab"]:
            why_not.append("request %d holds an id outside the vocabulary"
                           % i)
            break
    sample = d["sample"]
    if len(sample) < 2:
        why_not.append("%d request(s) completed inside the window"
                       % len(sample))
    # row-locality: company in the batch must not change a greedy answer,
    # so a probe replayed alone returns the same tokens
    probes = sample[:: max(1, len(sample) // max(1, traffic["probes"]))]
    probes = probes[:traffic["probes"]]
    mismatched = 0
    for i in probes:
        alone = engine.submit(prompts[i], requests[i][2]).result(timeout=600)
        if not np.array_equal(alone, d["outputs"][i]):
            mismatched += 1
    if mismatched:
        why_not.append("%d of %d probes answered differently alone than "
                       "in company" % (mismatched, len(probes)))
    # the plain float32 reference, teacher-forced over each probe's
    # answer: with random weights the largest logit can change on
    # rounding, so tokens are compared through the reference's logits —
    # the token the system chose may trail the reference's best by no more
    # than the tolerance the traffic file states
    pad_to = max(int(k) for k in traffic["prompt_lengths"]) \
        + max(int(k) for k in traffic["output_lengths"])
    margin_of = reference_gpt.greedy_margin_fn(params, cfg, pad_to)
    worst, disagree, compared = 0.0, 0, 0
    for i in probes:
        margin = margin_of(d["outputs"][i], requests[i][1])
        worst = max(worst, float(margin.max()))
        disagree += int((margin > 0).sum())
        compared += len(margin)
    if worst > traffic["reference_margin_tolerance"]:
        why_not.append("a chosen token trails the float32 reference's best "
                       "logit by %.4f (tolerance %.4f)"
                       % (worst, traffic["reference_margin_tolerance"]))
    failed = d["gen"].refused + d["errors"]
    if failed:
        why_not.append("%d request(s) refused or failed" % failed)
    return why_not, failed, {
        "probes": len(probes), "reference_tokens_compared": compared,
        "reference_tokens_not_argmax": disagree,
        "reference_worst_margin": worst}


def run(ctx):
    from paddle_tpu.observe import trace as flight

    tr, cfg = ctx.traffic, dict(ctx.config["model"])
    serving = ctx.config["serving"]
    horizon = tr["ramp_s"] + ctx.seconds
    requests = open_loop.schedule(tr, ctx.seed, horizon)
    prompts = open_loop.token_ids(requests, ctx.seed, cfg["vocab"])
    if ctx.trace:
        flight.recorder().resize(1 << 18)
    engine, params = build_engine(cfg, serving, tr, ctx.seed,
                                  ctx.monitor)
    try:
        d = drive(engine, tr, requests, prompts, ctx.seconds, ctx)
        host_spans = ctx.flight_spans("serving.") if ctx.trace else []
        why_not, failed, compared = check(engine, params, cfg, tr,
                                          requests, prompts, d)
    finally:
        engine.stop()

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
        "tokens_out": d["tokens_out"],
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
