"""Runner kind ``closed_loop``: a ``serving.DecodeEngine`` under a closed
loop of ``clients`` callers (``benchmarks/lib/closed_loop.py``), each
sending its next request the moment its reply arrives.

The engine, its warm-up, the seeded weights and the comparison that
decides ``correct`` are ``open_loop_blocks``'s own, imported: the two
kinds differ in the arrivals alone. Set-up builds the engine and every
prompt of the global sequence, then starts the clients ``ramp_s`` before
the window opens, so that the slots are full and out of step with one
another at both edges. A client is a thread that waits for its reply
and submits the next request of the sequence; completion times are
stamped by the engine's done-callback. The sample is every request that
completed inside the window, timed from its submit."""

import threading
import time

from benchmarks.kinds.open_loop_blocks import (  # noqa: F401
    SPAN_SITES, build_engine, check, seeded_params, warm_up)
from benchmarks.lib import closed_forms, closed_loop, open_loop
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.stats import percentile


class Clients:
    """``n`` client threads over one sequence of requests. Everything
    they submit was built before they started."""

    def __init__(self, engine, sequence, prompts, n, think_s, queue_full):
        self.engine, self.sequence, self.prompts = engine, sequence, prompts
        self.think_s, self.queue_full = think_s, queue_full
        total = len(sequence)
        self.submit_at = [None] * total
        self.done_at = [None] * total    # stamped on the engine's thread
        self.handles = [None] * total
        self.refused = 0
        self.exhausted = False
        self.error = None
        self._next = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.threads = [threading.Thread(target=self._client, daemon=True,
                                         name="bench-client-%d" % i)
                        for i in range(n)]

    def _stamp(self, i, replied):
        def on_done(_request):
            self.done_at[i] = time.perf_counter()
            replied.set()
        return on_done

    def _take(self):
        with self._lock:
            i = self._next
            if i >= len(self.sequence):
                self.exhausted = True
                return None
            self._next = i + 1
            return i

    def _client(self):
        try:
            while not self._stop.is_set():
                i = self._take()
                if i is None:
                    return
                replied = threading.Event()
                self.submit_at[i] = time.perf_counter()
                try:
                    handle = self.engine.submit(self.prompts[i],
                                                self.sequence[i][1])
                except self.queue_full:
                    self.refused += 1
                    continue
                self.handles[i] = handle
                handle.add_done_callback(self._stamp(i, replied))
                while not replied.wait(0.5):
                    if self._stop.is_set() and handle.done():
                        break
                if self.think_s:
                    time.sleep(self.think_s)
        except BaseException as exc:  # noqa: BLE001 — reported by drive()
            self.error = exc

    def start(self):
        for t in self.threads:
            t.start()

    def stop(self, timeout):
        """No client starts another request; each waits for the reply it
        has asked for."""
        self._stop.set()
        deadline = time.perf_counter() + timeout
        for t in self.threads:
            t.join(timeout=max(0.1, deadline - time.perf_counter()))

    @property
    def submitted(self):
        return self._next


def drive(engine, traffic, sequence, prompts, seconds, ctx):
    """Ramp, window, drain. Returns the clients (with their stamps), the
    clock marks, the slot-occupancy counter over the window and every
    completed request's output."""
    from paddle_tpu.observe.families import SERVING_OCCUPANCY
    from paddle_tpu.serving.queue import QueueFull

    occupancy = SERVING_OCCUPANCY.labels()
    clients = Clients(engine, sequence, prompts, int(traffic["clients"]),
                      float(traffic["think_time_s"]), QueueFull)
    t0 = time.perf_counter()
    clients.start()
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
    clients.stop(traffic["drain_timeout_s"])
    if clients.error is not None:
        raise clients.error
    outputs, errors = {}, 0
    for i, handle in enumerate(clients.handles):
        if handle is None:
            continue
        try:
            outputs[i] = handle.result(timeout=1.0)
        except Exception:  # noqa: BLE001 — any failed request counts
            errors += 1
    sample = [i for i in outputs if clients.done_at[i] is not None
              and t_open <= clients.done_at[i] <= t_close]
    per_tok_ms = [(clients.done_at[i] - clients.submit_at[i])
                  / sequence[i][1] * 1e3 for i in sample]
    in_window = [i for i, t in enumerate(clients.submit_at)
                 if t is not None and t_open <= t <= t_close]
    return {
        "gen": clients, "t_open": t_open, "t_close": t_close,
        "outputs": outputs, "errors": errors, "sample": sample,
        "per_tok_ms": per_tok_ms, "in_window": in_window,
        "tokens_out": sum(sequence[i][1] for i in sample),
        "queue_at_close": waiting, "decode_steps": steps,
        "occupancy_mean": occupancy_mean,
    }


def run(ctx):
    from paddle_tpu.observe import trace as flight

    tr, cfg = ctx.traffic, dict(ctx.config["model"])
    serving = ctx.config["serving"]
    sequence = closed_loop.sequence(
        tr, ctx.seed, closed_loop.sequence_length(tr, ctx.seconds))
    # check() and token_ids() take open_loop's (due, prompt_len, n_new)
    requests = [(0.0, plen, n_new) for plen, n_new in sequence]
    prompts = open_loop.token_ids(requests, ctx.seed, cfg["vocab"])
    if ctx.trace:
        flight.recorder().resize(1 << 18)
    engine, params = build_engine(cfg, serving, tr, ctx.seed, ctx.monitor)
    try:
        d = drive(engine, tr, sequence, prompts, ctx.seconds, ctx)
        host_spans = ctx.flight_spans("serving.") if ctx.trace else []
        why_not, failed, compared = check(engine, params, cfg, tr,
                                          requests, prompts, d)
    finally:
        engine.stop()
    if d["gen"].exhausted:
        why_not.append("the sequence of %d requests ran out: the system "
                       "completed more than max_req_s allows for"
                       % len(sequence))

    t_open, t_close = d["t_open"], d["t_close"]
    spans = {site: [] for site in SPAN_SITES}
    for site, start, dur in host_spans:
        if site in spans:
            # (end on the host's perf_counter clock, duration)
            spans[site].append((start + dur, dur))
    facts = {
        "clients": int(tr["clients"]),
        "requests_built": len(sequence),
        "requests_submitted": d["gen"].submitted,
        "requests_in_window": len(d["in_window"]),
        "completed_in_window": len(d["sample"]),
        "tokens_out": d["tokens_out"],
        "decode_steps": d["decode_steps"], "b_max": serving["b_max"],
        "decode_step_bytes": closed_forms.gpt_decode_step_bytes(
            cfg, serving["b_max"], serving["max_len"], 4, 4),
        "window_s": t_close - t_open, **compared,
        "queue_at_close": d["queue_at_close"],
    }
    return {
        "correct": not why_not, "why_not": why_not,
        "attempted": len(d["in_window"]), "failed": failed,
        "end_to_end": {
            "serve_tok_s": d["tokens_out"] / (t_close - t_open),
            "req_tok_ms_p50": percentile(d["per_tok_ms"], 50),
        },
        "facts": facts,
        "samples": {"req_tok_ms": d["per_tok_ms"]},
        "spans": spans,
        "counters": {"occupancy_mean": d["occupancy_mean"]},
        "peaks": None if ctx.rehearsal
        else peaks_for(ctx.devices[0].device_kind),
        "trace": ctx.reduce_trace(host_spans),
    }
