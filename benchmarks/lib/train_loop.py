"""The masked-LM train window both train kinds run: build the program from
the configuration file, make seeded batches, warm the one K-step
executable, then dispatch windows for ``--seconds`` and check the losses.

A kind supplies ``make_step(main, loss, scope, exe) -> callable(feed)``
that runs one K-step window and returns the fetched loss."""

import math
import time

import numpy as np

from benchmarks.lib import closed_forms
from benchmarks.lib.peaks import peaks_for


def build_program(ctx):
    """(model cfg, main, startup, loss) exactly as ``chip_smoke.py``
    builds BERT: ``bert.build`` -> ``Adam.minimize`` -> bf16 AMP."""
    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    cfg = dict(ctx.config["model"])
    tr = ctx.traffic
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = ctx.seed % (2 ** 31 - 1)
    with fluid.program_guard(main, startup):
        loss, _feeds = bert.build(cfg, seq_len=tr["seq"],
                                  max_mask=tr["max_mask"])
        fluid.optimizer.Adam(
            learning_rate=ctx.config["train"]["learning_rate"]
        ).minimize(loss)
    main.set_amp(ctx.config["train"]["amp"] == "bf16")
    return cfg, main, startup, loss


def make_windows(cfg, tr, seed):
    """``pool`` stacked feeds of K different batches each, drawn from the
    seed: every run of one seed trains on the same tokens."""
    rs = np.random.RandomState(seed % (2 ** 32))
    K, B, S, M = tr["steps_per_window"], tr["batch"], tr["seq"], \
        tr["max_mask"]
    windows = []
    for _ in range(tr["window_pool"]):
        windows.append({
            "src_ids": rs.randint(1, cfg["vocab"], (K, B, S)).astype("int64"),
            "sent_ids": rs.randint(0, 2, (K, B, S)).astype("int64"),
            "input_mask": np.ones((K, B, S), "float32"),
            "mask_pos": rs.randint(0, B * S, (K, B, M)).astype("int64"),
            "mask_label": rs.randint(0, cfg["vocab"],
                                     (K, B, M)).astype("int64"),
            "mask_weight": np.ones((K, B, M), "float32"),
        })
    return windows


def _loss_value(fetched):
    return float(np.asarray(fetched[0]).reshape(-1)[0])


def run(ctx, make_step):
    import paddle_tpu as fluid
    from paddle_tpu import kernels
    from paddle_tpu.core.scope import Scope, scope_guard
    from paddle_tpu.ops.attention import pallas_mode

    tr = ctx.traffic
    kernels.reset_decisions()
    cfg, main, startup, loss = build_program(ctx)
    windows = make_windows(cfg, tr, ctx.seed)
    K = tr["steps_per_window"]
    tokens_per_window = K * tr["batch"] * tr["seq"]
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace())
    why_not = []
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        step = make_step(main, loss, scope, exe)

        # warm-up: the window executable and nothing else. The first call
        # compiles (or loads); a fresh process may compile a second time
        # for the committed arrays the first call returned (PERF.md), so
        # go on until a window brings no compile, three at most
        warm_losses = []
        for i in range(3):
            before = ctx.monitor.snapshot()
            warm_losses.append(_loss_value(step(windows[i % len(windows)])))
            if i and not ctx.monitor.since(before)["backend_compiles"]:
                break

        # ------------------------------------------------ measured window
        losses, walls = [], []
        trace_s = min(tr.get("trace_seconds", 6.0), ctx.seconds)
        t0 = ctx.open_window()

        def one(i):
            with ctx.annotate("train_window", i=i):
                t = time.perf_counter()
                fetched = step(windows[i % len(windows)])
                walls.append(time.perf_counter() - t)
            losses.append(_loss_value(fetched))

        n = 0
        with ctx.traced():
            while time.perf_counter() - t0 < (trace_s if ctx.trace
                                              else ctx.seconds):
                one(n)
                n += 1
        n_traced = n if ctx.trace else 0
        while time.perf_counter() - t0 < ctx.seconds:
            one(n)
            n += 1
        t1 = ctx.close_window()

        # ------------------------------------------- correct, off the clock
        if not all(math.isfinite(v) for v in warm_losses + losses):
            why_not.append("non-finite loss")
        want = math.log(cfg["vocab"])
        if abs(warm_losses[0] - want) > tr["first_loss_tolerance"]:
            why_not.append("first window's loss %.4f is not within %.2f of "
                           "ln(vocab) = %.4f" % (warm_losses[0],
                                                 tr["first_loss_tolerance"],
                                                 want))
        n_calls = None
        if not ctx.rehearsal:
            if pallas_mode() != "compiled":
                why_not.append("Pallas kernels run in %r mode"
                               % pallas_mode())
            one_step = {k: v[0] for k, v in windows[0].items()}
            n_calls = step.hlo(one_step).count("tpu_custom_call")
            if n_calls != tr["expect_tpu_custom_calls"]:
                why_not.append("%d tpu_custom_call in the step, expected %d"
                               % (n_calls, tr["expect_tpu_custom_calls"]))
        why_not.extend(step.check(scope))

    elapsed = t1 - t0
    flops = closed_forms.bert_train_flops_per_token(cfg, tr["seq"],
                                                    tr["max_mask"])
    facts = {
        "windows": n, "windows_traced": n_traced, "steps_per_window": K,
        "tokens_per_window": tokens_per_window, "elapsed_s": elapsed,
        "first_losses": warm_losses, "last_loss": losses[-1],
        "tpu_custom_calls": n_calls,
        "attention_choice": kernels.decisions_seen().get(
            "attention", {}).get("choice"),
        "flops_per_token": flops, "seq": tr["seq"], "batch": tr["batch"],
        "batch_per_chip": tr["batch"] // ctx.chips,
        "n_head": cfg["n_head"], "n_layer": cfg["n_layer"],
        "d_head": cfg["d_model"] // cfg["n_head"],
    }
    return {
        "correct": not why_not, "why_not": why_not,
        "attempted": n, "failed": 0,
        "end_to_end": {"train_tok_s":
                       n * tokens_per_window / elapsed / ctx.chips},
        "facts": facts,
        "samples": {"window_wall_s": walls},
        "peaks": None if ctx.rehearsal
        else peaks_for(ctx.devices[0].device_kind),
        "trace": ctx.reduce_trace(ctx.flight_spans("executor."))
        if ctx.trace else None,
    }
