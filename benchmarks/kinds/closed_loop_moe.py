"""Runner kind ``closed_loop_moe``: ``closed_loop``'s clients over a
``serving.DecodeEngine`` whose model has sparse experts.

The arrivals, the clients, the window and the warm-up are
``closed_loop``'s and ``open_loop_blocks``'s own, imported. What differs
is what those two hard-wire to the dense decoder: here every parameter of
any rank is drawn from the seed (stacked ``[E, D, F]`` expert weights,
and the RMSNorm scales uniform in 0.5-1.5, so that a scale cannot pass
untested as a one), the plain reference is found by the configuration's
name (``benchmarks/references/<config>.py``) and the answers are held
to it by ``judge`` (averages over many teacher-forced answers, with a
bfloat16 control that has to land outside the limit), the bytes of a
decode step come from ``closed_forms_moe``, and the engine's device-side
routing tally is read at the edges of the drive."""

import numpy as np

from benchmarks.kinds.closed_loop import drive
from benchmarks.kinds.open_loop_blocks import SPAN_SITES, warm_up
from benchmarks.lib import closed_forms_moe, closed_loop, open_loop
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.stats import percentile


def seeded_params(cfg, serving, seed):
    """Every parameter of the decoder, drawn on the device from the seed,
    one jitted call a parameter (the experts of one layer are 1.6 GB:
    drawn together they would need their temporaries together).
    Matrices, stacked or not, within the Xavier-uniform limits of their
    last two axes; vectors (all RMSNorm scales here) in 0.5-1.5. The
    names and shapes come from an IR-only build (no compile)."""
    import functools

    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import gpt

    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        gpt.build_serving_decode_step(cfg, batch=1,
                                      max_len=serving["max_len"])
    shapes = {p.name: tuple(p.shape)
              for p in prog.global_block().all_parameters()}

    @functools.partial(jax.jit, static_argnums=(1,))
    def draw(key, shape, lo, hi):
        return jax.random.uniform(key, shape, jnp.float32, lo, hi)

    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    out = {}
    for i, name in enumerate(sorted(shapes)):
        shape = shapes[name]
        if len(shape) == 1:
            lo, hi = 0.5, 1.5
        else:
            hi = (6.0 / (shape[-2] + shape[-1])) ** 0.5
            lo = -hi
        out[name] = draw(jax.random.fold_in(key, i), shape, lo, hi)
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


def gmm_plans():
    """``{"<kernel> <tile> <form>": lowerings}`` from the program's
    ``paddle_moe_gmm_plans_total``: which form of the grouped matmul and
    which tile every program compiled in this process holds. Empty for a
    program without the counter."""
    from paddle_tpu.observe import REGISTRY

    family = REGISTRY.snapshot()["metrics"].get(
        "paddle_moe_gmm_plans_total", {"samples": []})
    return {"%(kernel)s %(tile)s %(form)s" % s["labels"]: int(s["value"])
            for s in family["samples"]}


def routed_pairs(engine):
    """The engine's routing tally as a list of rows, or None where the
    program has none."""
    read = getattr(engine, "routed_pairs", None)
    tally = read() if read is not None else None
    return None if tally is None else np.asarray(tally, np.int64)


def check(reference, engine, params, cfg, traffic, requests, prompts, d):
    """(why the run is not correct, if it is not; failed requests; facts
    of the comparison). As ``open_loop_blocks.check`` up to the plain
    reference, which is handed in and judged by ``judge``."""
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
    # (nor which experts a token is sent to), so a probe replayed alone
    # returns the same tokens
    probes = _spread(sample, traffic["probes"])
    mismatched = 0
    for i in probes:
        alone = engine.submit(prompts[i], requests[i][2]).result(timeout=600)
        if not np.array_equal(alone, d["outputs"][i]):
            mismatched += 1
    if mismatched:
        why_not.append("%d of %d probes answered differently alone than "
                       "in company" % (mismatched, len(probes)))
    # the plain float32 reference, teacher-forced over the answers of
    # reference_probes requests of the sample (more than are replayed
    # alone: a forward pass is cheap and the readings are averages), and
    # its control: the reference's own choices over the same sequences
    # with weights, activations and cache rounded to bfloat16, the
    # precision below the float32 the configuration states
    why, facts = judge(reference, params, cfg, traffic,
                       [(d["outputs"][i], requests[i][1])
                        for i in _spread(sample,
                                         traffic["reference_probes"])])
    why_not.extend(why)
    failed = d["gen"].refused + d["errors"]
    if failed:
        why_not.append("%d request(s) refused or failed" % failed)
    return why_not, failed, dict(facts, probes=len(probes))


def _spread(items, n):
    """At most ``n`` of ``items``, evenly spaced."""
    return items[:: max(1, len(items) // max(1, n))][:n]


def judge(reference, params, cfg, traffic, answers):
    """(why the answers are not those of a float32 model, facts).

    ``answers`` are ``(tokens, prompt_len)``. Every generated token is
    judged through the reference's logits at its position: the margin by
    which it trails the reference's best (0 for the argmax). Positions
    where the reference's own router has a near-tie between its last
    chosen and first rejected expert (a logit gap under
    ``reference_router_gap_floor`` in any layer) are left out: there a
    rounding error of any size moves a whole expert's term, so they read
    alike at every precision. Over the rest, three readings: the mean
    margin, the share of tokens that are not the argmax, the worst
    margin. The system has to stay under the limit of each; the control
    (the reference itself in bfloat16: weights, activations, cache) has
    to land OUTSIDE the limit on the mean margin, or that limit has
    stopped telling float32 from the precision below."""
    labels = ("reference", "control_bf16")
    margins_of = reference.greedy_margin_fn(
        params, cfg, traffic["reference_pad_multiple"],
        controls=((7, 7),))  # explicit mantissa bits: bfloat16
    per = [margins_of(tokens, plen) for tokens, plen in answers]
    gaps = np.concatenate([g for _, g in per]) if per else np.zeros(0)
    sound = gaps >= traffic["reference_router_gap_floor"]
    facts = {"reference_probes": len(per),
             "reference_tokens_compared": int(sound.sum()),
             "reference_tokens_near_tied": int((~sound).sum())}
    if not sound.any():
        return ["no token was compared with the reference"], facts
    read = {}
    for k, label in enumerate(labels):
        m = np.concatenate([margins[k] for margins, _ in per])[sound]
        off = int((m > 0).sum())
        read[label] = {"mean_margin": float(m.mean()),
                       "not_argmax_pct": 100.0 * off / m.size,
                       "worst_margin": float(m.max())}
        facts["%s_tokens_not_argmax" % label] = off
        facts.update(("%s_%s" % (label, name), value)
                     for name, value in read[label].items())
    why_not = []
    for name, limit, what in (
            ("mean_margin", traffic["reference_mean_margin_limit"],
             "the chosen tokens trail the float32 reference's best logit "
             "by %.3g on average (limit %.3g)"),
            ("not_argmax_pct", traffic["reference_not_argmax_limit_pct"],
             "%.2f%% of the tokens are not the float32 reference's "
             "argmax (limit %.2f%%)"),
            ("worst_margin", traffic["reference_margin_tolerance"],
             "a chosen token trails the float32 reference's best logit "
             "by %.4f (tolerance %.4f)")):
        if read["reference"][name] > limit:
            why_not.append(what % (read["reference"][name], limit))
    if read["control_bf16"]["mean_margin"] \
            <= traffic["reference_mean_margin_limit"]:
        why_not.append(
            "the limit no longer bites: the reference in bfloat16 trails "
            "its float32 self by %.3g on average, inside the limit %.3g"
            % (read["control_bf16"]["mean_margin"],
               traffic["reference_mean_margin_limit"]))
    return why_not, facts


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
        routed0 = routed_pairs(engine)
        d = drive(engine, tr, sequence, prompts, ctx.seconds, ctx)
        routed1 = routed_pairs(engine)
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
    facts = {
        "clients": int(tr["clients"]),
        "requests_built": len(sequence),
        "requests_submitted": d["gen"].submitted,
        "requests_in_window": len(d["in_window"]),
        "completed_in_window": len(d["sample"]),
        "tokens_out": d["tokens_out"],
        "decode_steps": d["decode_steps"], "b_max": serving["b_max"],
        "decode_step_bytes": closed_forms_moe.decode_step_bytes(
            cfg, serving["b_max"], serving["max_len"], 4, 4),
        "moe": {"cfg": {k: cfg[k] for k in (
                    "n_layer", "n_expert", "expert_top_k", "d_model",
                    "d_expert")},
                "rows": serving["b_max"], "weight_itemsize": 4},
        "moe_gmm_plans": gmm_plans(),
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
                     "routed_pairs": routed},
        "peaks": None if ctx.rehearsal
        else peaks_for(ctx.devices[0].device_kind),
        "trace": ctx.reduce_trace(host_spans),
    }
