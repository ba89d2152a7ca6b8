"""Runner kind ``closed_loop_power``: ``closed_loop``'s clients over a
``serving.DecodeEngine`` whose model's layers are power retention of
degree 2 over a dense SwiGLU FFN — every cache of a lane a state with no
position axis — with bfloat16-stored matrices and an untied head.

The arrivals, the clients, the window and the warm-up are
``closed_loop``'s and ``open_loop_blocks``'s own; the parameters (drawn
in the stored dtype) and the exact count of the tokens made
``closed_loop_mla``'s; the yardstick of ``correct`` (``judge``)
``closed_loop_moe``'s; the choice of the judged answers by prompt length,
the row-locality probes and the plan counters ``closed_loop_afmoe``'s —
all imported, as ``closed_loop_ssm`` imports them. What differs:

* the gate is redrawn from streams of its own so that the state matters:
  its bias so that ``sigmoid(b)`` is uniform over ``gate_range`` and its
  projection within ``gate_weight_limit`` (the traffic file's
  ``gate_why``: drawn like the other vectors every gate sits near 0.5, a
  state that forgets a token after three);
* the primers end ``g / (2 clients)`` steps apart, half of
  ``closed_loop_mla.prime``'s distance (``prime``);
* the model has no router: ``reference_router_gap_floor`` is 0 and the
  reference reports an infinite gap at every position, so every
  generated token of the judged answers is compared;
* the answers the reference judges are chosen so that
  ``reference_probes_long`` of them follow prompts longer than
  ``reference_long_over`` tokens: scans of many chunks and states that
  stand for more positions than they have rows are on the compared path;
* the bytes of a decode step come from ``closed_forms_power``: the
  matrices once, the state twice, no cache with a position axis;
* the plan counter of the two kernels, the chunk counter and the state's
  bytes are read into the facts."""

import math
import time

from benchmarks.kinds import closed_loop_mla
from benchmarks.kinds.closed_loop import drive
from benchmarks.kinds.closed_loop_afmoe import check, plans
from benchmarks.kinds.closed_loop_mla import ITEMSIZE, tokens_made
from benchmarks.kinds.open_loop_blocks import SPAN_SITES, warm_up
from benchmarks.lib import closed_forms_power, closed_loop, open_loop
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.stats import percentile


def seeded_params(cfg, serving, traffic, seed):
    """``closed_loop_mla.seeded_params`` with the gate's bias and
    projection redrawn in ranges of their own (module docstring)."""
    import jax
    import jax.numpy as jnp

    params = closed_loop_mla.seeded_params(cfg, serving, seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                             2 ** 20)
    lo, hi = (float(v) for v in traffic["gate_range"])
    limit = float(traffic["gate_weight_limit"])
    for i, name in enumerate(sorted(params)):
        k = jax.random.fold_in(key, i)
        shape, dtype = params[name].shape, params[name].dtype
        if name.endswith("_att_gamma.b_0"):
            g = jax.random.uniform(k, shape, jnp.float32, lo, hi)
            new = jnp.log(g) - jnp.log1p(-g)         # sigmoid's inverse
        elif name.endswith("_att_gamma.w_0"):
            new = jax.random.uniform(k, shape, jnp.float32, -limit, limit)
        else:
            continue
        params[name] = new.astype(dtype)
    return params


def prime(engine, traffic, vocab, seed):
    """One primer a slot, submitted before the clients start: the
    shortest prompt of the traffic (ids from ``closed_loop_mla.prime``'s
    stream) and ``g + i g / (2 clients)`` new tokens for slot ``i``,
    ``g`` the largest length every answer is a multiple of: 256 + 4 i at
    32 clients. A request costs its slot exactly ``n_new`` steps, so the
    slots keep that phase: their admissions fall 4 steps apart over one
    half of every 256 steps and the other half has none. Returns the
    handles."""
    import numpy as np

    n = int(traffic["clients"])
    g = math.gcd(*(int(k) for k in traffic["output_lengths"]))
    plen = min(int(k) for k in traffic["prompt_lengths"])
    rng = np.random.default_rng([seed, 2])
    return [engine.submit(rng.integers(0, vocab, size=plen, dtype=np.int64),
                          g + i * g // (2 * n)) for i in range(n)]


def build_engine(cfg, serving, traffic, seed, monitor):
    """(the started engine with every executable of this traffic warm,
    the seeded parameters it was given)."""
    from paddle_tpu.serving import DecodeEngine

    params = seeded_params(cfg, serving, traffic, seed)
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


def run(ctx):
    from paddle_tpu.observe import trace as flight

    tr, cfg = ctx.traffic, dict(ctx.config["model"])
    serving = ctx.config["serving"]
    reference = ctx.manifest.load_module("references", ctx.cell["config"])
    sequence = closed_loop.sequence(
        tr, ctx.seed, closed_loop.sequence_length(tr, ctx.seconds))
    # check() and token_ids() take open_loop's (due, prompt_len, n_new)
    requests = [(0.0, plen, n_new) for plen, n_new in sequence]
    prompts = open_loop.token_ids(requests, ctx.seed, cfg["vocab"])
    if ctx.trace:
        flight.recorder().resize(1 << 18)
    engine, params = build_engine(cfg, serving, tr, ctx.seed, ctx.monitor)
    try:
        primers = prime(engine, tr, cfg["vocab"], ctx.seed)
        d = drive(engine, tr, sequence, prompts, ctx.seconds, ctx)
        for handle in primers:      # long done: the ramp outlasts them
            handle.result(timeout=1.0)
        host_spans = ctx.flight_spans("serving.") if ctx.trace else []
        # closed_loop_afmoe.check takes its "long" prompts as those past
        # cfg['window']
        t_check = time.perf_counter()
        why_not, failed, compared = check(
            reference, engine, params,
            dict(cfg, window=int(tr["reference_long_over"])), tr,
            requests, prompts, d)
        compared["check_s"] = time.perf_counter() - t_check
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
    w_item = ITEMSIZE[cfg.get("weight_dtype", "float32")]
    made = tokens_made(d, serving["b_max"])
    facts = {
        "clients": int(tr["clients"]),
        "primers": len(primers),
        "requests_built": len(sequence),
        "requests_submitted": d["gen"].submitted,
        "requests_in_window": len(d["in_window"]),
        "completed_in_window": len(d["sample"]),
        "tokens_out": d["tokens_out"],
        "tokens_made": made,
        "decode_steps": d["decode_steps"], "b_max": serving["b_max"],
        "decode_step_bytes": closed_forms_power.decode_step_bytes(
            cfg, serving["b_max"], serving["max_len"], 4, w_item),
        "static_bytes": closed_forms_power.static_bytes(
            cfg, serving["b_max"], serving["max_len"], 4, w_item),
        "param_count": closed_forms_power.param_count(cfg),
        "longest_prompt": max(int(k) for k in tr["prompt_lengths"]),
        "prompt_lengths": {str(k): int(v)
                           for k, v in tr["prompt_lengths"].items()},
        "power": {"cfg": {k: cfg[k] for k in (
            "d_model", "n_head", "n_kv_head", "d_head", "n_layer",
            "layer_types") if k in cfg},
            "itemsize": 4,
            "pairs": closed_forms_power.pairs(cfg),
            "kept_rows": closed_forms_power.kept_rows(cfg)},
        "power_plans": plans("paddle_power_plans_total",
                             "%(kernel)s %(form)s chunk=%(chunk)s"),
        "power_chunks": plans("paddle_power_chunks_total", "chunks"),
        "flash_plans": plans(
            "paddle_flash_block_plans_total",
            "%(kernel)s %(block)s single_pass=%(single_pass)s"),
        "kv_cache_write_plans": plans("paddle_kv_cache_write_plans_total",
                                      "%(form)s rows=%(rows)s"),
        "cache_bytes": plans("paddle_serving_cache_bytes", "%(kind)s"),
        "weight_bytes": plans("paddle_serving_weight_bytes", "%(dtype)s"),
        "window_s": t_close - t_open, **compared,
        "queue_at_close": d["queue_at_close"],
    }
    return {
        "correct": not why_not, "why_not": why_not,
        "attempted": len(d["in_window"]), "failed": failed,
        "end_to_end": {
            "serve_tok_s": made / (t_close - t_open),
            "req_tok_ms_p50": percentile(d["per_tok_ms"], 50),
        },
        "facts": facts,
        "samples": {"req_tok_ms": d["per_tok_ms"]},
        "spans": spans,
        "counters": {"occupancy_mean": d["occupancy_mean"],
                     "power_state_bytes": plans(
                         "paddle_power_state_bytes", "bytes").get("bytes")},
        "peaks": None if ctx.rehearsal
        else peaks_for(ctx.devices[0].device_kind),
        "trace": ctx.reduce_trace(host_spans),
    }
