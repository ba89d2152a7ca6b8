"""Runner kind ``closed_loop_mamba``: ``closed_loop``'s clients over a
``serving.DecodeEngine`` whose layers are the ordinary pair with a Mamba-1
mixer (a slot keeps an ``[N, C]`` state and three convolution rows) or
multi-query attention without positions (a slot keeps a slab) as the
first sub-block — states and slabs in ONE lane — and a dense SwiGLU FFN as
the second, bfloat16-stored matrices and the token table as the head.

The arrivals, the clients, the window and the warm-up are
``closed_loop``'s and ``open_loop_blocks``'s own; the parameters (drawn
in the stored dtype), the exact count of the tokens made and the primers
``closed_loop_mla``'s (``g + i g / clients`` new tokens for slot ``i``:
64 + 2 i at 32 clients, two steps apart); the yardstick of ``correct``
(``judge``) ``closed_loop_moe``'s; the choice of the judged answers by
prompt length, the row-locality probes and the plan counters
``closed_loop_afmoe``'s; the positions counter's tap
``closed_loop_conv``'s — all imported, as ``closed_loop_ssm`` imports
them. What differs:

* the recurrence's parameters are redrawn from streams of their own, where
  the layer's published initialisation puts them (the traffic file's
  ``mamba_why``: drawn like the other vectors every ``exp(dt A)`` would
  sit under 0.01, a state that forgets a token at once): ``dt_b`` so that
  ``softplus(dt_b)`` is log-uniform over ``mamba_dt_range``, ``W_dt``
  within ``R^-1/2``, ``a_log`` the log of a uniform over
  ``mamba_a_range``, ``D`` in ``mamba_d_range``, the taps and their bias
  within ``mamba_conv_limit``;
* the model has no router: ``reference_router_gap_floor`` is 0 and the
  reference reports an infinite gap at every position, so every generated
  token is compared;
* the bytes of a decode step come from ``closed_forms_mamba``: every
  matrix once with the table whole (it is the head), the states and their
  rows twice, the slabs whole;
* the plan counter of the two kernels, the counter of blocks scanned and
  the gauge of the state's bytes are read into the facts and counters."""

import time

from benchmarks.kinds import closed_loop_mla
from benchmarks.kinds.closed_loop import drive
from benchmarks.kinds.closed_loop_afmoe import check, plans
from benchmarks.kinds.closed_loop_conv import WindowTap
from benchmarks.kinds.closed_loop_mla import ITEMSIZE, prime, tokens_made
from benchmarks.kinds.open_loop_blocks import SPAN_SITES, warm_up
from benchmarks.lib import closed_forms_mamba, closed_loop, open_loop
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.stats import percentile


def seeded_params(cfg, serving, traffic, seed):
    """``closed_loop_mla.seeded_params`` with a mamba layer's ``dt_b``,
    ``W_dt``, ``a_log``, ``D`` and the taps with their bias redrawn in the
    traffic file's ranges (module docstring)."""
    import jax
    import jax.numpy as jnp

    params = closed_loop_mla.seeded_params(cfg, serving, seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                             2 ** 20)
    dt_lo, dt_hi = (float(v) for v in traffic["mamba_dt_range"])
    a_lo, a_hi = (float(v) for v in traffic["mamba_a_range"])
    d_lo, d_hi = (float(v) for v in traffic["mamba_d_range"])
    conv = float(traffic["mamba_conv_limit"])
    w_dt = int(cfg["mamba_dt_rank"]) ** -0.5
    for i, name in enumerate(sorted(params)):
        k = jax.random.fold_in(key, i)
        shape, dtype = params[name].shape, params[name].dtype

        def uniform(lo, hi):
            return jax.random.uniform(k, shape, jnp.float32, lo, hi)

        if name.endswith("_mamba_dt_b"):
            dt = jnp.exp(uniform(jnp.log(dt_lo), jnp.log(dt_hi)))
            new = dt + jnp.log(-jnp.expm1(-dt))      # softplus's inverse
        elif name.endswith("_mamba_a_log"):
            new = jnp.log(uniform(a_lo, a_hi))
        elif name.endswith("_mamba_d"):
            new = uniform(d_lo, d_hi)
        elif "_mamba_conv." in name:
            new = uniform(-conv, conv)
        elif name.endswith("_mamba_dt.w_0"):
            new = uniform(-w_dt, w_dt)
        else:
            continue
        params[name] = new.astype(dtype)
    return params


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
    tap = WindowTap(ctx)
    try:
        primers = prime(engine, tr, cfg["vocab"], ctx.seed)
        d = drive(engine, tr, sequence, prompts, ctx.seconds, tap)
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
    cache_bytes = plans("paddle_serving_cache_bytes", "%(kind)s")
    seen = tap.window_positions()
    shape = {k: cfg[k] for k in (
        "n_layer", "layer_types", "mamba_inner", "mamba_state",
        "mamba_dt_rank", "ssm_conv")}
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
        "decode_step_bytes": closed_forms_mamba.decode_step_bytes(
            cfg, serving["b_max"], serving["max_len"], 4, w_item),
        "static_bytes": closed_forms_mamba.static_bytes(
            cfg, serving["b_max"], serving["max_len"], 4, w_item),
        "param_count": closed_forms_mamba.param_count(cfg),
        "longest_prompt": max(int(k) for k in tr["prompt_lengths"]),
        "prompt_lengths": {str(k): int(v)
                           for k, v in tr["prompt_lengths"].items()},
        "mamba": {"cfg": shape, "itemsize": 4},
        "gqa_flash": {"cfg": {k: cfg[k] for k in (
            "n_layer", "n_head", "n_kv_head", "d_head", "layer_types")},
            "itemsize": 4},
        "mamba_plans": plans("paddle_mamba_plans_total",
                             "%(kernel)s %(form)s block=%(block)s"),
        "mamba_chunks": plans("paddle_mamba_chunks_total", "chunks"),
        "flash_plans": plans(
            "paddle_flash_block_plans_total",
            "%(kernel)s %(block)s single_pass=%(single_pass)s"),
        "kv_cache_write_plans": plans("paddle_kv_cache_write_plans_total",
                                      "%(form)s rows=%(rows)s"),
        "cache_bytes": cache_bytes,
        "weight_bytes": plans("paddle_serving_weight_bytes", "%(dtype)s"),
        "positions": seen,
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
                     "state_cache_bytes": cache_bytes.get("state"),
                     "mamba_state_bytes": plans(
                         "paddle_mamba_state_bytes", "bytes").get("bytes"),
                     "positions": seen},
        "peaks": None if ctx.rehearsal
        else peaks_for(ctx.devices[0].device_kind),
        "trace": ctx.reduce_trace(host_spans),
    }
