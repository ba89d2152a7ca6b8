"""Runner kind ``closed_loop_delta``: ``closed_loop``'s clients over a
``serving.DecodeEngine`` whose layers are the ordinary pair with a gated
delta rule (a slot keeps a ``[Dk, Dv]`` state a value head and three
convolution rows) or gated grouped-head attention (a slot keeps a slab)
as the first sub-block — states and slabs in ONE lane — and a share of
the routed experts with a gated shared expert as the second,
bfloat16-stored matrices and an untied head.

The arrivals, the clients, the window and the warm-up are
``closed_loop``'s and ``open_loop_blocks``'s own; the parameters (drawn
in the stored dtype), the exact count of the tokens made and the primers
``closed_loop_mla``'s (``g + i g / clients`` new tokens for slot ``i``:
128 + i at 128 clients, one step apart over every 128); the yardstick of
``correct`` (``judge``) and the routing tally ``closed_loop_moe``'s; the
choice of the judged answers by prompt length, the row-locality probes,
the touched tally and the plan counters ``closed_loop_afmoe``'s; the
positions counter's tap ``closed_loop_conv``'s — all imported, as
``closed_loop_ssm`` imports them. What differs:

* the decay's parameters and the convolution's taps are redrawn from
  streams of their own, where the layer's published initialisation puts
  them (the traffic file's ``delta_why``: drawn like the other vectors
  every ``exp(g)`` would sit under 0.01, a state that forgets a token at
  once);
* the bytes of a decode step come from ``closed_forms_delta``: the
  touched experts only, the states and their rows twice, the slabs whole;
* the plan counter of the two kernels, the chunk counter and the gauge of
  the state's bytes are read into the facts and counters."""

import time

from benchmarks.kinds import closed_loop_mla
from benchmarks.kinds.closed_loop import drive
from benchmarks.kinds.closed_loop_afmoe import (check, experts_touched,
                                                plans)
from benchmarks.kinds.closed_loop_conv import WindowTap
from benchmarks.kinds.closed_loop_mla import ITEMSIZE, prime, tokens_made
from benchmarks.kinds.closed_loop_moe import routed_pairs
from benchmarks.kinds.open_loop_blocks import SPAN_SITES, warm_up
from benchmarks.lib import closed_forms_delta, closed_loop, open_loop
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.stats import percentile


def seeded_params(cfg, serving, traffic, seed):
    """``closed_loop_mla.seeded_params`` with a delta layer's ``dt_b``,
    ``a_log``, the ``a`` columns of ``W_ba`` and the taps redrawn in the
    traffic file's ranges (module docstring)."""
    import jax
    import jax.numpy as jnp

    params = closed_loop_mla.seeded_params(cfg, serving, seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                             2 ** 20)
    dt_lo, dt_hi = (float(v) for v in traffic["delta_dt_range"])
    a_lo, a_hi = (float(v) for v in traffic["delta_a_range"])
    hv = int(cfg["delta_v_heads"])
    for i, name in enumerate(sorted(params)):
        k = jax.random.fold_in(key, i)
        shape, dtype = params[name].shape, params[name].dtype

        def uniform(lo, hi, shape=shape):
            return jax.random.uniform(k, shape, jnp.float32, lo, hi)

        if name.endswith("_delta_dt_b"):
            dt = jnp.exp(uniform(jnp.log(dt_lo), jnp.log(dt_hi)))
            new = jnp.log(jnp.expm1(dt))            # softplus's inverse
        elif name.endswith("_delta_a_log"):
            new = jnp.log(uniform(a_lo, a_hi))
        elif name.endswith("_delta_conv.w_0"):
            lim = float(traffic["delta_tap_limit"])
            new = uniform(-lim, lim)
        elif name.endswith("_delta_ba.w_0"):
            # [b | a]: the writing strength's columns stay as drawn
            lim = float(traffic["delta_a_weight_limit"])
            new = params[name].astype(jnp.float32).at[:, hv:].set(
                uniform(-lim, lim, (shape[0], hv)))
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
        routed0, touched0 = routed_pairs(engine), experts_touched(engine)
        primers = prime(engine, tr, cfg["vocab"], ctx.seed)
        d = drive(engine, tr, sequence, prompts, ctx.seconds, tap)
        for handle in primers:      # long done: the ramp outlasts them
            handle.result(timeout=1.0)
        routed1, touched1 = routed_pairs(engine), experts_touched(engine)
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
    routed = None if routed1 is None else (routed1 - routed0).tolist()
    # the tallies count every decode step between their two readings
    # (ramp, window and drain); the routed-pairs total over a layer is
    # b_max x top_k a step
    touched = touched_mean = steps_tallied = None
    if touched1 is not None and routed is not None:
        touched = (touched1 - touched0).tolist()
        steps_tallied = sum(routed[0]) \
            // (serving["b_max"] * cfg["expert_top_k"])
        if steps_tallied:
            touched_mean = sum(map(sum, touched)) \
                / float(steps_tallied * cfg["n_layer"])
    held = closed_forms_delta.held_experts(cfg)
    w_item = ITEMSIZE[cfg.get("weight_dtype", "float32")]
    made = tokens_made(d, serving["b_max"])
    cache_bytes = plans("paddle_serving_cache_bytes", "%(kind)s")
    seen = tap.window_positions()
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
        "decode_step_bytes": closed_forms_delta.decode_step_bytes(
            cfg, serving["b_max"], serving["max_len"], 4, w_item,
            held if touched_mean is None else touched_mean),
        "static_bytes": closed_forms_delta.static_bytes(
            cfg, serving["b_max"], serving["max_len"], 4, w_item),
        "param_count": closed_forms_delta.param_count(cfg),
        "experts_held": held,
        "experts_touched_mean": touched_mean,
        "steps_tallied": steps_tallied,
        "longest_prompt": max(int(k) for k in tr["prompt_lengths"]),
        "prompt_lengths": {str(k): int(v)
                           for k, v in tr["prompt_lengths"].items()},
        "delta": {"cfg": {k: cfg[k] for k in (
            "n_layer", "layer_types", "delta_k_heads", "delta_k_dim",
            "delta_v_heads", "delta_v_dim")}, "itemsize": 4},
        "gqa_flash": {"cfg": {k: cfg[k] for k in (
            "n_layer", "n_head", "n_kv_head", "d_head", "layer_types")},
            "itemsize": 4},
        "moe": {"cfg": {k: cfg[k] for k in (
            "n_expert", "expert_top_k", "d_model", "d_expert", "n_layer")},
            "rows": serving["b_max"], "weight_itemsize": w_item},
        "delta_plans": plans("paddle_delta_plans_total",
                             "%(kernel)s %(form)s chunk=%(chunk)s"),
        "delta_chunks": plans("paddle_delta_chunks_total", "chunks"),
        "moe_gmm_plans": plans("paddle_moe_gmm_plans_total",
                               "%(kernel)s %(tile)s %(form)s"),
        "flash_plans": plans(
            "paddle_flash_block_plans_total",
            "%(kernel)s %(block)s single_pass=%(single_pass)s"),
        "kv_cache_write_plans": plans("paddle_kv_cache_write_plans_total",
                                      "%(form)s rows=%(rows)s"),
        "cache_bytes": cache_bytes,
        "weight_bytes": plans("paddle_serving_weight_bytes", "%(dtype)s"),
        "positions": seen,
        "routed_pairs_total": None if routed is None
        else int(sum(map(sum, routed))),
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
                     "routed_pairs": routed,
                     "experts_touched": touched,
                     "experts_touched_mean": touched_mean,
                     "experts_held": held,
                     "state_cache_bytes": cache_bytes.get("state"),
                     "delta_state_bytes": plans(
                         "paddle_delta_state_bytes", "bytes").get("bytes"),
                     "positions": seen},
        "peaks": None if ctx.rehearsal
        else peaks_for(ctx.devices[0].device_kind),
        "trace": ctx.reduce_trace(host_spans),
    }
