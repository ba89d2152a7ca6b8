#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the main path runs on the chip.

    python chip_smoke.py              # one TPU chip: kernels, train, serve
    python chip_smoke.py --chips 4    # one four-chip host: the mesh phase only
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal   # tests only

One process (a chip belongs to one process at a time), public API only
(``fluid.Program`` -> ``models.bert.build`` -> ``optimizer.minimize`` ->
``Executor(TPUPlace())``), BERT-base MLM exactly as ``models/bert.py``
publishes it (12 layers, d_model 768, d_ff 3072, 12 heads, vocab 30522)
at seq 512, bf16 AMP, Adam, batch 32, random weights from ``--seed``.

Default phases:

* ``kernels`` — flash attention forward + backward against the composed
  reference on the device (plain, causal, mask bias; f32 and bf16);
* ``train``   — startup, single steps through ``Executor.run``, one K-step
  window through ``run_repeated``; losses finite and falling on a fixed
  batch; the flash kernel compiled (not interpreted, not composed);
* ``serve``   — ``io.save_inference_model`` of the same network, an
  ``inference.Predictor`` answering requests at two batch sizes, checked
  against an ``is_test`` forward through the Executor.

``--chips 4`` runs only the ``mesh`` phase: the same step data-parallel x4
and on a 2x2 dp x tp mesh with ZeRO-1 through ``ParallelEngine``, compared
with the single-device loss curve.

Earlier lines are per-phase JSON (times there are smoke timings, not
metrics). The LAST line, printed only when every phase passed on a TPU, is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failure — no TPU included — exits non-zero without that line. The
rehearsal runs tiny sizes on whatever backend there is and never prints
an ``ok`` line at all.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

# (model overrides, seq, batch, max_mask, single steps, window K,
#  serve batch sizes, mesh steps)
FULL = dict(cfg={}, seq=512, batch=32, max_mask=80, steps=4, window=4,
            serve_batches=(1, 8), mesh_steps=3)
# rehearsal: control flow only — widths and depth cut to the bone
TINY = dict(cfg=dict(d_model=64, d_ff=128, n_head=2, n_layer=2, vocab=512),
            seq=256, batch=4, max_mask=8, steps=3, window=2,
            serve_batches=(1, 2), mesh_steps=2)


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _scalar(v):
    return float(np.asarray(v).reshape(-1)[0])


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _rel_err(got, want):
    """max |got - want| over max(1, max |want|), in float32."""
    got = np.asarray(got, dtype=np.float32)
    want = np.asarray(want, dtype=np.float32)
    return float(np.max(np.abs(got - want)) / max(1.0, np.max(np.abs(want))))


class CacheCounter:
    """Counts JAX's persistent-compilation-cache hits and misses."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _cache_entries(path):
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


# ------------------------------------------------------------------ kernels
def phase_kernels(size):
    """Flash attention on the device against ``composed_attention``."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.attention import (composed_attention,
                                          flash_attention, pallas_mode)

    S = size["seq"]
    cases = [
        # name, (B, H, S, D), dtype, causal, mask bias, fwd tol, bwd tol
        ("f32_plain", (2, 4, 256, 64), "float32", False, False, 2e-2, 5e-2),
        ("f32_causal", (2, 4, 256, 64), "float32", True, False, 2e-2, 5e-2),
        ("bf16_bert_maskbias", (2, 12, S, 64), "bfloat16", False, True,
         3e-2, 6e-2),
    ]
    rs = np.random.RandomState(0)
    report = {}
    for name, (B, H, s, D), dtype, causal, masked, tol_f, tol_b in cases:
        q, k, v = (jnp.asarray(rs.randn(B, H, s, D), dtype=dtype)
                   for _ in range(3))
        bias = None
        if masked:  # [B,1,1,S] padding mask: the last fifth of row 1 is pad
            m = np.zeros((B, 1, 1, s), "float32")
            m[1, :, :, -(s // 5):] = -1e9
            bias = jnp.asarray(m)
        scale = D ** -0.5

        def flash_loss(q, k, v):
            out = flash_attention(q, k, v, bias, scale, causal=causal)
            return jnp.sum(out.astype(jnp.float32) ** 2), out

        def ref_loss(q, k, v):
            out = composed_attention(q, k, v, bias, scale, causal)
            return jnp.sum(out.astype(jnp.float32) ** 2), out

        grad = lambda f: jax.jit(jax.value_and_grad(  # noqa: E731
            f, argnums=(0, 1, 2), has_aux=True))
        (_, o_f), g_f = grad(flash_loss)(q, k, v)
        with jax.default_matmul_precision("highest"):
            (_, o_r), g_r = grad(ref_loss)(q, k, v)
        err_f = _rel_err(o_f, o_r)
        err_b = max(_rel_err(a, b) for a, b in zip(g_f, g_r))
        report[name] = {"fwd_err": err_f, "bwd_err": err_b}
        if not (err_f <= tol_f and err_b <= tol_b):
            raise AssertionError(
                "flash attention %s disagrees with the composed reference: "
                "fwd %.3g (tol %.3g), bwd %.3g (tol %.3g)"
                % (name, err_f, tol_f, err_b, tol_b))
    emit("kernels", ok=True, pallas_mode=pallas_mode(), cases=report)


# -------------------------------------------------------------------- model
def _bert_cfg(size):
    from paddle_tpu.models import bert

    cfg = bert.base_config()
    cfg.update(size["cfg"])
    return cfg


def _build_bert(size, seed):
    """(main, startup, test_prog, loss, logits): the train program, and an
    ``is_test`` clone taken before the optimizer ops were appended."""
    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    cfg = _bert_cfg(size)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        loss, _feeds = bert.build(cfg, seq_len=size["seq"],
                                  max_mask=size["max_mask"])
        test_prog = main.clone(for_test=True)
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(loss)
    main.set_amp(True)
    test_prog.set_amp(True)
    # bert.build keeps its logits private: they are what the loss op reads
    xent = [op for op in main.global_block().ops
            if op.type == "softmax_with_cross_entropy"]
    logits = main.global_block().var(xent[0].inputs["Logits"][0])
    return cfg, main, startup, test_prog, loss, logits


def _batch(cfg, size, batch, seed):
    rs = np.random.RandomState(seed)
    S, M = size["seq"], size["max_mask"]
    return {
        "src_ids": rs.randint(1, cfg["vocab"], (batch, S)).astype("int64"),
        "sent_ids": rs.randint(0, 2, (batch, S)).astype("int64"),
        "input_mask": np.ones((batch, S), "float32"),
        "mask_pos": rs.randint(0, batch * S, (batch, M)).astype("int64"),
        "mask_label": rs.randint(0, cfg["vocab"], (batch, M)).astype("int64"),
        "mask_weight": np.ones((batch, M), "float32"),
    }


SERVE_FEEDS = ("src_ids", "sent_ids", "input_mask", "mask_pos")


def _assert_flash_compiled(hlo_text, n_layer, per_layer, rehearsal, what):
    """The flash kernel is in the program, compiled: not interpreted, not
    the composed math. ``per_layer`` is 4 for a train step (one forward
    and three backward kernels) and 1 for a forward."""
    from paddle_tpu import kernels
    from paddle_tpu.ops.attention import pallas_mode

    choice = kernels.decisions_seen().get("attention", {}).get("choice")
    if choice != "flash":
        raise AssertionError("%s: attention dispatched %r, not the flash "
                             "kernel" % (what, choice))
    if rehearsal:
        return None
    if pallas_mode() != "compiled":
        raise AssertionError("%s: Pallas kernels would run in %r mode"
                             % (what, pallas_mode()))
    n = hlo_text.count("tpu_custom_call")
    if n != per_layer * n_layer:
        raise AssertionError(
            "%s: %d tpu_custom_call in the step, expected %d (%d per layer "
            "x %d layers) — a layer fell to composed math"
            % (what, n, per_layer * n_layer, per_layer, n_layer))
    return n


# -------------------------------------------------------------------- train
def phase_train(size, seed, rehearsal, cache):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import kernels
    from paddle_tpu.core.scope import Scope, scope_guard

    kernels.reset_decisions()
    cfg, main, startup, test_prog, loss, logits = _build_bert(size, seed)
    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace())
    feed = _batch(cfg, size, size["batch"], seed)
    dev = jax.devices()[0]
    with scope_guard(scope):
        t0 = time.perf_counter()
        exe.run(startup, scope=scope)
        t_startup = time.perf_counter() - t0

        # Executor.run hands back numpy, so each step has completed on the
        # device (block_until_ready) before the clock is read
        losses, times = [], []
        for _ in range(size["steps"]):
            t0 = time.perf_counter()
            (lv,) = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
            times.append(time.perf_counter() - t0)
            losses.append(_scalar(lv))
        K = size["window"]
        win_times = []
        for _ in range(2):  # first call compiles the scan, second is steady
            t0 = time.perf_counter()
            (lv,) = exe.run_repeated(main, feed=feed, fetch_list=[loss],
                                     scope=scope, steps=K)
            win_times.append(time.perf_counter() - t0)
            losses.append(_scalar(lv))
        hlo = exe.lowered_hlo(main, feed=feed, fetch_list=[loss],
                              scope=scope, stage="stablehlo")
    if not all(np.isfinite(losses)):
        raise AssertionError("train: non-finite loss in %r" % (losses,))
    # dropout is on and the fetched loss is bf16-coarse, so "falling" is
    # judged over the whole run, not step to step
    if not losses[-1] < losses[0]:
        raise AssertionError("train: loss is not falling on a fixed batch: "
                             "%r" % (losses,))
    n_calls = _assert_flash_compiled(hlo, cfg["n_layer"], 4, rehearsal,
                                     "train step")
    emit("train", ok=True,
         model=dict(cfg, seq=size["seq"], batch=size["batch"],
                    max_mask=size["max_mask"], amp="bf16", optimizer="adam"),
         losses=losses, tpu_custom_calls=n_calls,
         kernel_tier=kernels.decisions_seen(),
         # the first call of each executable carries its compile
         smoke_timings_not_metrics=dict(
             startup_s=t_startup, step_s=times, window_k=K,
             window_s=win_times,
             compile_s_about=times[0] - min(times)
             + win_times[0] - win_times[1]),
         peak_bytes_in_use=_peak_bytes(dev),
         cache_hits=cache.hits, cache_misses=cache.misses)
    return cfg, main, test_prog, logits, scope, exe


# -------------------------------------------------------------------- serve
def phase_serve(size, seed, rehearsal, cache, trained):
    import paddle_tpu as fluid
    from paddle_tpu import kernels
    from paddle_tpu.core.scope import scope_guard
    from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor

    cfg, main, test_prog, logits, scope, exe = trained
    model_dir = tempfile.mkdtemp(prefix="chip_smoke_model_")
    try:
        with scope_guard(scope):
            fluid.io.save_inference_model(model_dir, list(SERVE_FEEDS),
                                          [logits], exe, main_program=main)
        kernels.reset_decisions()
        config = AnalysisConfig(model_dir=model_dir)
        config.warmup_batch_sizes = list(size["serve_batches"])
        t0 = time.perf_counter()
        predictor = create_paddle_predictor(config)
        t_load = time.perf_counter() - t0

        errs, req_times = {}, {}
        for b in size["serve_batches"]:
            for r in range(2):  # a few requests at each batch size
                feed = _batch(cfg, size, b, seed + 100 * b + r)
                request = {n: feed[n] for n in SERVE_FEEDS}
                t0 = time.perf_counter()
                (got,) = predictor.run(request)
                req_times["b%d_r%d" % (b, r)] = time.perf_counter() - t0
                with scope_guard(scope):
                    (want,) = exe.run(test_prog, feed=feed,
                                      fetch_list=[logits], scope=scope)
                got, want = np.asarray(got), np.asarray(want)
                want_shape = (b * size["max_mask"], cfg["vocab"])
                if got.shape != want_shape or not np.all(np.isfinite(got)):
                    raise AssertionError(
                        "serve: batch %d answered shape %s (expected %s), "
                        "finite=%s" % (b, got.shape, want_shape,
                                       bool(np.all(np.isfinite(got)))))
                errs["b%d_r%d" % (b, r)] = _rel_err(got, want)
        # both sides are bf16 AMP forwards of the same weights
        if max(errs.values()) > 2e-2:
            raise AssertionError("serve: Predictor disagrees with the "
                                 "is_test Executor forward: %r" % (errs,))
        feed = _batch(cfg, size, size["serve_batches"][-1], seed)
        hlo = predictor._exe.lowered_hlo(
            predictor.program, feed={n: feed[n] for n in SERVE_FEEDS},
            fetch_list=predictor.fetch_names, scope=predictor.scope,
            stage="stablehlo")
        n_calls = _assert_flash_compiled(hlo, cfg["n_layer"], 1, rehearsal,
                                         "serving forward")
    finally:
        shutil.rmtree(model_dir, ignore_errors=True)
    emit("serve", ok=True, batch_sizes=list(size["serve_batches"]),
         requests=len(errs), max_err_vs_executor=max(errs.values()),
         tpu_custom_calls=n_calls,
         smoke_timings_not_metrics=dict(load_and_warmup_s=t_load,
                                        request_s=req_times),
         cache_hits=cache.hits, cache_misses=cache.misses)


# --------------------------------------------------------------------- mesh
def _tp_rules(zero1):
    """Megatron-style tensor parallelism for the encoder: q/k/v and the
    first FFN matmul split by columns, the output projection and the
    second FFN matmul by rows; Adam moments ZeRO-1 over 'data'."""
    from paddle_tpu.parallel import ShardingRules
    from paddle_tpu.parallel.sharding import P

    return ShardingRules([
        (r"enc_\d+_att_[qkv]\.w_0$", P(None, "model")),
        (r"enc_\d+_att_o\.w_0$", P("model", None)),
        (r"enc_\d+_ffn1\.w_0$", P(None, "model")),
        (r"enc_\d+_ffn2\.w_0$", P("model", None)),
    ], zero1=zero1)


def _spread(scope, names, n_dev):
    """How the named scope arrays lie on the devices: every one must have
    a shard on each device; returns the smallest shard fraction seen."""
    frac = 1.0
    for n in names:
        arr = scope.find_var(n)
        shards = arr.addressable_shards
        if len({s.device for s in shards}) != n_dev:
            raise AssertionError("mesh: %s lives on %d of %d devices"
                                 % (n, len({s.device for s in shards}),
                                    n_dev))
        frac = min(frac, shards[0].data.size / max(1, arr.size))
    return frac


def phase_mesh(size, seed, rehearsal, cache):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import kernels
    from paddle_tpu.core.scope import Scope, scope_guard
    from paddle_tpu.parallel import ParallelEngine, ShardingRules
    from paddle_tpu.parallel.engine import make_mesh

    devs = jax.devices()
    if len(devs) != 4:
        raise AssertionError("mesh: --chips 4 needs exactly 4 devices, JAX "
                             "reports %d" % len(devs))
    steps = size["mesh_steps"]

    def run(label, mesh_shape, rules):
        """`steps` losses on the fixed batch from a fresh seeded init."""
        kernels.reset_decisions()
        cfg, main, startup, _test, loss, _logits = _build_bert(size, seed)
        feed = _batch(cfg, size, size["batch"], seed)
        scope = Scope()
        exe = fluid.Executor(fluid.TPUPlace())
        losses, times = [], []
        with scope_guard(scope):
            t0 = time.perf_counter()
            exe.run(startup, scope=scope)
            t_startup = time.perf_counter() - t0
            if mesh_shape is None:
                step = lambda: exe.run(  # noqa: E731
                    main, feed=feed, fetch_list=[loss], scope=scope)
            else:
                mesh = make_mesh(devs, ("data", "model"), mesh_shape)
                engine = ParallelEngine(main, loss_name=loss.name,
                                        mesh=mesh, rules=rules)
                step = lambda: engine.run(feed, [loss], scope)  # noqa: E731
            for _ in range(steps):
                t0 = time.perf_counter()
                (lv,) = step()
                times.append(time.perf_counter() - t0)
                losses.append(_scalar(lv))
            info = {"losses": losses,
                    "smoke_timings_not_metrics": dict(
                        startup_s=t_startup, step_s=times)}
            if mesh_shape is not None:
                weights = ["enc_0_att_q.w_0", "enc_0_ffn2.w_0",
                           "word_embedding"]
                # (slot names end in a per-process counter: ask the program)
                moments = [n for n in sorted(main._optimizer_slots)
                           if n.startswith(tuple(weights))
                           and "_moment" in n]
                info["moment_shard_fraction"] = _spread(scope, moments, 4)
                info["weight_shard_fraction"] = _spread(scope, weights, 4)
                t0 = time.perf_counter()
                hlo = engine.lowered_hlo(feed, [loss], scope,
                                         stage="optimized")
                info["smoke_timings_not_metrics"]["step_hlo_s"] = \
                    time.perf_counter() - t0
                info["all_reduce_ops"] = hlo.count("all-reduce(") \
                    + hlo.count("all-reduce-start(")
                if not info["all_reduce_ops"]:
                    raise AssertionError("mesh %s: no all-reduce in the "
                                         "step HLO" % label)
                if not rehearsal:
                    info["tpu_custom_calls"] = hlo.count("tpu_custom_call")
                    if info["tpu_custom_calls"] < 4 * cfg["n_layer"]:
                        raise AssertionError(
                            "mesh %s: %d tpu_custom_call in the step HLO, "
                            "expected at least %d"
                            % (label, info["tpu_custom_calls"],
                               4 * cfg["n_layer"]))
                info["bytes_in_use_per_device"] = [
                    (d.memory_stats() or {}).get("bytes_in_use")
                    for d in devs]
        # on its own line at once: a later failure must not lose it
        emit("mesh." + label, **info, cache_hits=cache.hits,
             cache_misses=cache.misses)
        if not all(np.isfinite(losses)):
            raise AssertionError("mesh %s: non-finite loss %r"
                                 % (label, losses))
        return info

    ref = run("single", None, None)
    dp = run("dp4", (4, 1), ShardingRules())
    dptp = run("dp2xtp2_zero1", (2, 2), _tp_rules(zero1=True))
    # the same bf16 step from the same weights on the same batch: only the
    # reduction order differs. The fetched loss is itself bf16, so the
    # curves may part by its last bit or two (1/16 at a loss near 10).
    gaps = {}
    for label, info in (("dp4", dp), ("dp2xtp2_zero1", dptp)):
        for got, want in zip(info["losses"], ref["losses"]):
            ulp = 2.0 ** (np.floor(np.log2(abs(want))) - 7)
            gaps[label] = max(gaps.get(label, 0.0), abs(got - want) / ulp)
        if gaps[label] > 2:
            raise AssertionError(
                "mesh %s: losses %r leave the single-device curve %r by "
                "%.3g bf16 ulps (tol 2)" % (label, info["losses"],
                                            ref["losses"], gaps[label]))
    if dp["weight_shard_fraction"] != 1.0:
        raise AssertionError("mesh dp4: weights should be replicated")
    if not (dptp["moment_shard_fraction"] <= 0.5
            and dptp["weight_shard_fraction"] <= 0.5):
        raise AssertionError(
            "mesh dp2xtp2_zero1: state is not spread (moment shard %.3g, "
            "weight shard %.3g of the whole)"
            % (dptp["moment_shard_fraction"], dptp["weight_shard_fraction"]))
    emit("mesh", ok=True, devices=len(devs),
         max_loss_gap_vs_single_in_bf16_ulps=gaps,
         all_reduce_ops={"dp4": dp["all_reduce_ops"],
                         "dp2xtp2_zero1": dptp["all_reduce_ops"]},
         state_shard_fraction={
             "dp4": [dp["weight_shard_fraction"],
                     dp["moment_shard_fraction"]],
             "dp2xtp2_zero1": [dptp["weight_shard_fraction"],
                               dptp["moment_shard_fraction"]]})


# --------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the mesh phase on a four-chip host")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and batches")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on any backend, for tests: checks the "
                         "control flow, never prints an ok line")
    args = ap.parse_args(argv)
    rehearsal = args.cpu_rehearsal
    size = TINY if rehearsal else FULL

    import jax

    from paddle_tpu.flags import enable_compile_cache

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not rehearsal and device["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU (platform %r); this script "
              "proves the chip path and has no CPU fallback"
              % device["platform"], file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    cache = CacheCounter()
    entries_before = _cache_entries(cache_dir)
    emit("device", **device, jax=jax.__version__, rehearsal=rehearsal,
         compile_cache_dir=cache_dir,
         compile_cache_entries_before=entries_before)

    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_mesh(size, args.seed, rehearsal, cache)
        else:
            phase_kernels(size)
            trained = phase_train(size, args.seed, rehearsal, cache)
            phase_serve(size, args.seed, rehearsal, cache, trained)
    except Exception:  # noqa: BLE001 — the boundary: report, exit non-zero
        import traceback

        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    emit("cache", compile_cache_dir=cache_dir,
         compile_cache_entries_before=entries_before,
         compile_cache_entries_after=_cache_entries(cache_dir),
         cache_hits=cache.hits, cache_misses=cache.misses,
         total_s=time.perf_counter() - t0)
    if rehearsal:
        print(json.dumps({"rehearsal": "passed", "device": device}),
              flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
