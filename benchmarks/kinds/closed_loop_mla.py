"""Runner kind ``closed_loop_mla``: ``closed_loop``'s clients over a
``serving.DecodeEngine`` whose model has latent attention (one latent
tensor a layer for a cache), matrices stored in bfloat16 and one chip's
share of its experts.

The arrivals, the clients, the window and the warm-up are
``closed_loop``'s and ``open_loop_blocks``'s own, the yardstick of
``correct`` (``judge``) and the routing tally ``closed_loop_moe``'s, the
choice of the judged answers by prompt length, the row-locality probes,
the touched tally and the plan counters ``closed_loop_afmoe``'s, all
imported. What differs:

* every matrix is drawn in float32 and ROUNDED TO bfloat16 as it is
  drawn, one jitted call a parameter (3.4 B parameters would not fit the
  chip in float32 even for a moment): the engine and the reference are
  handed the same bfloat16 arrays;
* the answers the reference judges are chosen so that at least
  ``reference_probes_long`` follow the traffic's longest prompt: the
  flash forward at 3,328 and the absorbed kernel over the longest rows
  are on the compared path;
* the bytes of a decode step come from ``closed_forms_mla``: matrices at
  the stored itemsize, the experts by the touched tally, and the latent
  cache by the rows the step's slots have reached (the absorbed kernel
  walks a slot's rows up to its position);
* the plan counter of the two attention forms, the bytes by stored dtype
  and the latent cache's bytes are read into the facts;
* ``serve_tok_s`` is the tokens the engine PRODUCED inside the window,
  counted exactly: the live slot-steps of the window's decode steps
  (``drive``'s occupancy counter: a live slot makes one token a step)
  and one token for every request submitted in it (its prefill's; at
  think time 0 the admission follows the submit). The older closed-loop
  kinds count whole requests at their reply, which agrees over a long
  window; here the answers are 128 to 768 tokens and the 64 slots step
  together, so replies come in bursts about 128 steps (3 s) apart and a
  burst of 768-token replies just inside or outside an edge of the 45 s
  window moves that count by 6% either way with no work behind it
  (PERF.md section 6). The whole-request count stays in the facts
  (``tokens_out``);
* the slots are put OUT OF STEP before the clients start (``prime``).
  Every answer of this traffic is a multiple of 128 tokens and a request
  costs its slot exactly ``n_new`` steps (``n_new - 1`` rides and the
  step in flight while it is admitted), so 64 clients that start in the
  same instant finish on one grid for ever: every 128 steps about 22
  requests end together and their prefills stop all 64 slots for 1.3 s.
  The work inside a 45 s window then differs by a burst at an edge
  (1,797 to 1,920 steps over twelve seeds on the chip: a spread of 2.7%
  and 3.2% in two sets of six, PERF.md section 6), which is neither the
  steady mix of lengths in flight ISSUE 32 describes nor what
  ``closed_loop``'s ramp is for ("the slots are full and out of step
  with one another at both edges")."""

import math

from benchmarks.kinds.closed_loop import drive
from benchmarks.kinds.closed_loop_afmoe import (check, experts_touched,
                                                plans)
from benchmarks.kinds.closed_loop_moe import routed_pairs
from benchmarks.kinds.open_loop_blocks import SPAN_SITES, warm_up
from benchmarks.lib import closed_forms_mla, closed_loop, open_loop
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.stats import percentile

ITEMSIZE = {"float32": 4, "bfloat16": 2}


def seeded_params(cfg, serving, seed):
    """Every parameter of the decoder, drawn on the device from the seed,
    one jitted call a parameter, as ``closed_loop_moe.seeded_params``
    draws them (matrices within the Xavier-uniform limits of their last
    two axes, vectors in 0.5-1.5), each in the dtype the program stores
    it in: a matrix is rounded to cfg['weight_dtype'] inside its draw."""
    import functools

    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import gpt

    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        gpt.build_serving_decode_step(cfg, batch=1,
                                      max_len=serving["max_len"])
    stored = {p.name: (tuple(p.shape), str(p.dtype))
              for p in prog.global_block().all_parameters()}

    @functools.partial(jax.jit, static_argnums=(1, 4))
    def draw(key, shape, lo, hi, dtype):
        return jax.random.uniform(key, shape, jnp.float32, lo,
                                  hi).astype(dtype)

    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    out = {}
    for i, name in enumerate(sorted(stored)):
        shape, dtype = stored[name]
        if len(shape) == 1:
            lo, hi = 0.5, 1.5
        else:
            hi = (6.0 / (shape[-2] + shape[-1])) ** 0.5
            lo = -hi
        out[name] = draw(jax.random.fold_in(key, i), shape, lo, hi, dtype)
    return out


def build_engine(cfg, serving, traffic, seed, monitor):
    """(the started engine with every executable of this traffic warm,
    the seeded parameters it was given)."""
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


def prime(engine, traffic, vocab, seed):
    """One primer a slot, submitted before the clients start: the
    shortest prompt of the traffic and ``g + i g / clients`` new tokens
    for slot ``i``, ``g`` the largest length every answer is a multiple
    of. The primers are admitted together and end ``g / clients`` steps
    apart, the clients' first requests (queued behind them) take the
    slots in that order, and because a request costs its slot exactly
    ``n_new`` steps the slots stay that far apart: one admission every
    few steps instead of a burst every ``g``. Returns the handles."""
    import numpy as np

    n = int(traffic["clients"])
    g = math.gcd(*(int(k) for k in traffic["output_lengths"]))
    plen = min(int(k) for k in traffic["prompt_lengths"])
    rng = np.random.default_rng([seed, 2])
    return [engine.submit(rng.integers(0, vocab, size=plen, dtype=np.int64),
                          g + i * g // n) for i in range(n)]


def tokens_made(d, b_max):
    """Tokens the engine produced inside the window: the live slot-steps
    of its decode steps, exactly (the occupancy counter sums live slots
    over ``b_max`` a step), and one for every request submitted in it,
    whose prefill makes its first token."""
    steps = d["occupancy_mean"] * d["decode_steps"] * b_max \
        if d["decode_steps"] else 0.0
    return int(round(steps)) + len(d["in_window"])


def rows_visible(requests, sample):
    """Cache rows the decode steps of the sampled requests saw, summed: a
    request of prompt P and N new tokens rides N - 1 steps, at positions
    P .. P + N - 2, each seeing its position and all before."""
    return sum((n - 1) * plen + (n - 1) * n // 2
               for _due, plen, n in (requests[i] for i in sample))


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
        primers = prime(engine, tr, cfg["vocab"], ctx.seed)
        d = drive(engine, tr, sequence, prompts, ctx.seconds, ctx)
        for handle in primers:      # long done: the ramp outlasts them
            handle.result(timeout=1.0)
        routed1, touched1 = routed_pairs(engine), experts_touched(engine)
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
    # (ramp, window and drain); the routed-pairs total over a layer is
    # b_max x top_k a step
    touched = touched_mean = steps_tallied = None
    if touched1 is not None and routed is not None:
        touched = (touched1 - touched0).tolist()
        expert_layers = [row for row in routed if sum(row)]
        steps_tallied = sum(expert_layers[0]) \
            // (serving["b_max"] * cfg["expert_top_k"])
        if steps_tallied:
            touched_mean = sum(map(sum, touched)) \
                / float(steps_tallied * len(expert_layers))
    held = closed_forms_mla.held_experts(cfg)
    # rows a step's slots have reached, on average over the window's
    # steps: what the absorbed kernel has to read of the latent cache.
    # AN ESTIMATE: the rows of the whole requests that replied inside the
    # window over the steps inside it (the engine tallies no positions);
    # decode_bw_pct and mla_decode_roofline rest on it
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
        "decode_step_bytes": closed_forms_mla.decode_step_bytes(
            cfg, serving["b_max"], serving["max_len"], 4, w_item,
            held if touched_mean is None else touched_mean, rows_mean),
        "static_bytes": closed_forms_mla.static_bytes(
            cfg, serving["b_max"], serving["max_len"], 4, w_item),
        "experts_held": held,
        "experts_touched_mean": touched_mean,
        "steps_tallied": steps_tallied,
        "longest_prompt": longest,
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
                     "experts_held": held},
        "peaks": None if ctx.rehearsal
        else peaks_for(ctx.devices[0].device_kind),
        "trace": ctx.reduce_trace(host_spans),
    }
