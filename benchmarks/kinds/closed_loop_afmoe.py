"""Runner kind ``closed_loop_afmoe``: ``closed_loop``'s clients over a
``serving.DecodeEngine`` whose model has two kinds of attention layer
(a cache of two shapes) and one chip's share of its experts.

The arrivals, the clients, the window and the warm-up are
``closed_loop``'s and ``open_loop_blocks``'s own, and the parameters, the
engine, the yardstick of ``correct`` (``judge``) and the routing tally
``closed_loop_moe``'s, all imported. What differs:

* the router's selection bias is a parameter of rank one and would be
  drawn like a norm scale (0.5-1.5), which would decide every selection
  by itself; it is drawn within ``router_bias_limit`` of zero instead
  (the traffic file says why);
* the answers judged by the reference are chosen by prompt length: at
  least ``reference_probes_long`` of them follow prompts longer than the
  attention window, so that the wrapped ring and the banded kernel are
  on the compared path;
* the bytes of a decode step come from ``closed_forms_afmoe`` — per-layer
  cache shapes, and the experts counted by the mean number the program's
  touched tally reports a step;
* the plan counters of the flash forward and of the cache write are read
  into the facts, and the ``serving.engine.prefill`` spans keep their
  prompt length for the readers of the banded kernel."""

import numpy as np

from benchmarks.kinds import closed_loop_moe
from benchmarks.kinds.closed_loop import drive
from benchmarks.kinds.closed_loop_moe import _spread, judge, routed_pairs
from benchmarks.kinds.open_loop_blocks import SPAN_SITES, warm_up
from benchmarks.lib import closed_forms_afmoe, closed_loop, open_loop
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.stats import percentile


def seeded_params(cfg, serving, traffic, seed):
    """``closed_loop_moe.seeded_params`` (every parameter of any rank
    from the seed), with each router's selection bias redrawn within
    ``router_bias_limit`` of zero from a stream of its own."""
    import jax
    import jax.numpy as jnp

    params = closed_loop_moe.seeded_params(cfg, serving, seed)
    lim = float(traffic["router_bias_limit"])
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                             2 ** 20)
    for i, name in enumerate(sorted(params)):
        if name.endswith("_router_bias"):
            params[name] = jax.random.uniform(
                jax.random.fold_in(key, i), params[name].shape,
                jnp.float32, -lim, lim)
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


def plans(family, fmt):
    """``{fmt % labels: lowerings}`` of one of the program's plan
    counters; empty for a program without it."""
    from paddle_tpu.observe import REGISTRY

    got = REGISTRY.snapshot()["metrics"].get(family, {"samples": []})
    return {fmt % s["labels"]: int(s["value"]) for s in got["samples"]
            if s["value"]}


def experts_touched(engine):
    """The engine's touched tally ``[n_layer, n_expert_local]``, or None
    where the program has none."""
    read = getattr(engine, "experts_touched", None)
    tally = read() if read is not None else None
    return None if tally is None else np.asarray(tally, np.int64)


def reference_answers(sample, requests, outputs, traffic, window):
    """The answers the reference judges: ``reference_probes_long`` that
    follow prompts longer than ``window`` and the rest of
    ``reference_probes`` from the others, each evenly spaced over the
    sample."""
    long_ = [i for i in sample if requests[i][1] > window]
    short = [i for i in sample if requests[i][1] <= window]
    n_long = int(traffic["reference_probes_long"])
    picked = _spread(long_, n_long) \
        + _spread(short, int(traffic["reference_probes"]) - n_long)
    return [(outputs[i], requests[i][1]) for i in picked], \
        sum(1 for i in picked if requests[i][1] > window)


def check(reference, engine, params, cfg, traffic, requests, prompts, d):
    """(why the run is not correct, if it is not; failed requests; facts
    of the comparison). ``closed_loop_moe.check`` with the judged
    answers chosen by prompt length."""
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
    # row-locality: company in the batch must not change a greedy answer
    # (nor which experts a token is sent to, nor what a ring holds), so a
    # probe replayed alone returns the same tokens
    probes = _spread(sample, traffic["probes"])
    mismatched = 0
    for i in probes:
        alone = engine.submit(prompts[i], requests[i][2]).result(timeout=600)
        if not np.array_equal(alone, d["outputs"][i]):
            mismatched += 1
    if mismatched:
        why_not.append("%d of %d probes answered differently alone than "
                       "in company" % (mismatched, len(probes)))
    answers, n_long = reference_answers(sample, requests, d["outputs"],
                                        traffic, int(cfg["window"]))
    if n_long < int(traffic["reference_probes_long"]):
        why_not.append("%d judged answer(s) follow a prompt longer than "
                       "the window, %d asked"
                       % (n_long, traffic["reference_probes_long"]))
    why, facts = judge(reference, params, cfg, traffic, answers)
    why_not.extend(why)
    failed = d["gen"].refused + d["errors"]
    if failed:
        why_not.append("%d request(s) refused or failed" % failed)
    return why_not, failed, dict(facts, probes=len(probes),
                                 reference_probes_long=n_long)


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
        routed0, touched0 = routed_pairs(engine), experts_touched(engine)
        d = drive(engine, tr, sequence, prompts, ctx.seconds, ctx)
        routed1, touched1 = routed_pairs(engine), experts_touched(engine)
        host_spans = ctx.flight_spans("serving.") if ctx.trace else []
        why_not, failed, compared = check(reference, engine, params, cfg,
                                          tr, requests, prompts, d)
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
    # (ramp, window and drain); so does the routed-pairs tally, whose
    # total over a layer is b_max x top_k a step
    touched = touched_mean = steps_tallied = None
    if touched1 is not None and routed is not None:
        touched = (touched1 - touched0).tolist()
        expert_layers = [row for row in routed if sum(row)]
        steps_tallied = sum(expert_layers[0]) \
            // (serving["b_max"] * cfg["expert_top_k"])
        if steps_tallied:
            touched_mean = sum(map(sum, touched)) \
                / float(steps_tallied * len(expert_layers))
    held = closed_forms_afmoe.held_experts(cfg)
    facts = {
        "clients": int(tr["clients"]),
        "requests_built": len(sequence),
        "requests_submitted": d["gen"].submitted,
        "requests_in_window": len(d["in_window"]),
        "completed_in_window": len(d["sample"]),
        "tokens_out": d["tokens_out"],
        "decode_steps": d["decode_steps"], "b_max": serving["b_max"],
        "decode_step_bytes": closed_forms_afmoe.decode_step_bytes(
            cfg, serving["b_max"], serving["max_len"], 4, 4,
            held if touched_mean is None else touched_mean),
        "experts_held": held,
        "experts_touched_mean": touched_mean,
        "steps_tallied": steps_tallied,
        "longest_prompt": max(int(k) for k in tr["prompt_lengths"]),
        "flash_win": {"cfg": {k: cfg[k] for k in (
            "n_layer", "n_head", "n_kv_head", "d_head", "window",
            "layer_types")}, "itemsize": 4},
        "moe_gmm_plans": plans("paddle_moe_gmm_plans_total",
                               "%(kernel)s %(tile)s %(form)s"),
        "flash_plans": plans(
            "paddle_flash_block_plans_total",
            "%(kernel)s %(block)s single_pass=%(single_pass)s"),
        "kv_cache_write_plans": plans("paddle_kv_cache_write_plans_total",
                                      "%(form)s rows=%(rows)s"),
        "cache_bytes": plans("paddle_serving_cache_bytes", "%(kind)s"),
        "routed_pairs_total": None if routed is None
        else int(sum(map(sum, routed))),
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
        "counters": {"occupancy_mean": d["occupancy_mean"],
                     "routed_pairs": routed,
                     "experts_touched": touched,
                     "experts_touched_mean": touched_mean,
                     "experts_held": held},
        "peaks": None if ctx.rehearsal
        else peaks_for(ctx.devices[0].device_kind),
        "trace": ctx.reduce_trace(host_spans),
    }
