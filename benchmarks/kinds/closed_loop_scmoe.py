"""Runner kind ``closed_loop_scmoe``: ``closed_loop``'s clients over a
``serving.DecodeEngine`` whose model has shortcut-connected experts — two
latent-attention sub-blocks and two dense FFNs a published layer, one
routed branch that forks after the first attention and joins after the
second FFN — a softmax router over one chip's share of the experts with
weights and all the identity (zero-compute) experts, and bfloat16-stored
matrices.

The arrivals, the clients, the window and the warm-up are
``closed_loop``'s and ``open_loop_blocks``'s own; the parameters (drawn
in the stored dtype), the exact count of the tokens made, the primers
and the estimate of the visible cache rows ``closed_loop_mla``'s; the
yardstick of ``correct`` (``judge``) and the routing tally
``closed_loop_moe``'s; the choice of the judged answers by prompt length,
the row-locality probes, the touched tally and the plan counters
``closed_loop_afmoe``'s — all imported. What differs:

* the engine and its parameters are ``closed_loop_mhc``'s, imported:
  every parameter drawn in its stored dtype and each router's selection
  term redrawn within ``router_bias_limit`` of zero from a stream of its
  own (``closed_loop_afmoe`` says why: drawn like a norm scale it would
  decide every selection; this model has no residual mappings for that
  kind's second redraw to find);
* the engine's tally of the pairs that cost nothing
  (``DecodeEngine.zero_pairs``: per branch the identity pairs, summed,
  and the most experts with weights one token chose) is read at the
  edges of the drive beside the routed pairs, whose rows now hold the
  pairs of the experts WITH weights only: a step routes ``b_max x
  expert_top_k`` pairs a branch, identity pairs among them, so the steps
  tallied are counted over both;
* the bytes of a decode step come from ``closed_forms_scmoe``: two
  attentions, two dense FFNs and a router over all its outputs a
  published layer, the touched experts by the tally, the visible rows
  over all the sub-layers' slabs; ``facts.mla.cfg`` carries the model's
  ``n_layer``, which counts sub-layers: the attention calls a step, not
  the published layers."""

from benchmarks.kinds.closed_loop import drive
from benchmarks.kinds.closed_loop_afmoe import (check, experts_touched,
                                                plans)
from benchmarks.kinds.closed_loop_mhc import build_engine
from benchmarks.kinds.closed_loop_mla import (ITEMSIZE, prime, rows_visible,
                                              tokens_made)
from benchmarks.kinds.closed_loop_moe import routed_pairs
from benchmarks.kinds.open_loop_blocks import SPAN_SITES
from benchmarks.lib import closed_forms_scmoe, closed_loop, open_loop
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.stats import percentile


def zero_pairs(engine):
    """The engine's tally ``[branches, 2]`` of identity pairs (column 0)
    and of the most experts with weights one token chose (column 1), or
    None where the program has none."""
    import numpy as np

    read = getattr(engine, "zero_pairs", None)
    tally = read() if read is not None else None
    return None if tally is None else np.asarray(tally, np.int64)


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
    longest = max(int(k) for k in tr["prompt_lengths"])
    if ctx.trace:
        flight.recorder().resize(1 << 18)
    engine, params = build_engine(cfg, serving, tr, ctx.seed, ctx.monitor)
    try:
        routed0, touched0 = routed_pairs(engine), experts_touched(engine)
        zero0 = zero_pairs(engine)
        primers = prime(engine, tr, cfg["vocab"], ctx.seed)
        d = drive(engine, tr, sequence, prompts, ctx.seconds, ctx)
        for handle in primers:      # long done: the ramp outlasts them
            handle.result(timeout=1.0)
        routed1, touched1 = routed_pairs(engine), experts_touched(engine)
        zero1 = zero_pairs(engine)
        host_spans = ctx.flight_spans("serving.") if ctx.trace else []
        # closed_loop_afmoe.check takes its "long" prompts as those past
        # cfg['window']: here, the traffic's longest
        why_not, failed, compared = check(
            reference, engine, params, dict(cfg, window=longest - 1), tr,
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
    routed = None if routed1 is None else (routed1 - routed0).tolist()
    # the tallies count every decode step between their two readings
    # (ramp, window and drain); a branch routes b_max x top_k pairs a
    # step, those that chose an identity expert among them
    zero = zero_pct = real_k_max = None
    if zero1 is not None:
        zero = (zero1[:, 0] - zero0[:, 0]).tolist()
        real_k_max = int(zero1[:, 1].max())   # since the engine was built
    touched = touched_mean = steps_tallied = None
    if routed is not None and zero is not None:
        pairs = sum(map(sum, routed)) + sum(zero)
        steps_tallied = (sum(routed[0]) + zero[0]) \
            // (serving["b_max"] * cfg["expert_top_k"])
        if pairs:
            zero_pct = 100.0 * sum(zero) / pairs
        if touched1 is not None and steps_tallied:
            touched = (touched1 - touched0).tolist()
            touched_mean = sum(map(sum, touched)) \
                / float(steps_tallied * len(routed))
    held = closed_forms_scmoe.held_experts(cfg)
    # AN ESTIMATE, as closed_loop_mla has it: the rows of the whole
    # requests that replied inside the window over the steps inside it
    rows_mean = rows_visible(requests, d["sample"]) \
        / float(d["decode_steps"]) if d["decode_steps"] else None
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
        "decode_step_bytes": closed_forms_scmoe.decode_step_bytes(
            cfg, serving["b_max"], serving["max_len"], 4, w_item,
            held if touched_mean is None else touched_mean, rows_mean),
        "static_bytes": closed_forms_scmoe.static_bytes(
            cfg, serving["b_max"], serving["max_len"], 4, w_item),
        "param_count": closed_forms_scmoe.param_count(cfg),
        "experts_held": held,
        "experts_touched_mean": touched_mean,
        "steps_tallied": steps_tallied,
        "zero_pairs_pct": zero_pct,
        "real_experts_max": real_k_max,
        "longest_prompt": longest,
        # n_layer counts sub-layers: the attention calls of a step
        "mla": {"cfg": {k: cfg[k] for k in (
            "n_layer", "n_head", "kv_lora_rank", "d_nope", "d_rope",
            "d_v")}, "cache_itemsize": 4, "flash_itemsize": 4,
            "rows_visible_mean": rows_mean},
        "mla_plans": plans("paddle_mla_attention_plans_total",
                           "%(form)s %(kernel)s %(block)s %(widths)s"),
        "moe_gmm_plans": plans("paddle_moe_gmm_plans_total",
                               "%(kernel)s %(tile)s %(form)s"),
        "flash_plans": plans(
            "paddle_flash_block_plans_total",
            "%(kernel)s %(block)s single_pass=%(single_pass)s"),
        "kv_cache_write_plans": plans("paddle_kv_cache_write_plans_total",
                                      "%(form)s rows=%(rows)s"),
        "cache_bytes": plans("paddle_serving_cache_bytes", "%(kind)s"),
        "weight_bytes": plans("paddle_serving_weight_bytes", "%(dtype)s"),
        "compact_calls": plans("paddle_moe_compact_calls",
                               "%(layer)s %(path)s"),
        "routed_pairs_total": None if routed is None
        else int(sum(map(sum, routed))),
        "zero_pairs_total": None if zero is None else int(sum(zero)),
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
                     "zero_pairs": zero,
                     "zero_pairs_pct": zero_pct,
                     "real_experts_max": real_k_max,
                     "experts_touched": touched,
                     "experts_touched_mean": touched_mean,
                     "experts_held": held},
        "peaks": None if ctx.rehearsal
        else peaks_for(ctx.devices[0].device_kind),
        "trace": ctx.reduce_trace(host_spans),
    }
