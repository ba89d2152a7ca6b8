"""Benchmark: the five BASELINE.md workloads on one chip, with MFU.

Prints one JSON line per workload:
  {"metric", "value", "unit", "vs_baseline", "mfu", "tflops_per_sec"}

The reference prints examples/sec from benchmark/fluid/fluid_benchmark.py
(print_train_time, :296-301) with no committed numbers (BASELINE.md), so
vs_baseline anchors on this repo's own round-1 measurements where they
exist and on 1.0 for first-time measurements. MFU uses XLA's own
cost_analysis() flop count for the compiled train step (no hand-derived
formulas) against the chip's peak bf16 FLOP/s (the "precision" field
records the compute dtype; XLA's default TPU matmul precision runs f32
dots at bf16 rate, so the bf16 peak is the comparable denominator).

All workloads train with bf16 AMP (f32 master weights) — the TPU-native
configuration; run with --fp32 to disable.

Isolation: the top-level process runs each workload in a KILLABLE
subprocess (``--worker``) with a per-workload deadline
(PADDLE_TPU_BENCH_WORKLOAD_TIMEOUT, seconds). A hung compile or a
crashed kernel therefore costs one row — never the file, and never the
later workloads (a call that blocks in C runs no signal handler, so
in-process try/except can't contain it). A chip belongs to one process
at a time: the parent never imports jax, and the workers run one after
another. A row that fails is an error row and makes the exit code
non-zero — a kernel the compiler refuses is never re-run on the
composed path.
"""

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np


def _log(msg):
    print("[bench %s] %s" % (time.strftime("%H:%M:%S"), msg),
          file=sys.stderr, flush=True)


def _telemetry_dir():
    return os.environ.get("PADDLE_TPU_TELEMETRY_DIR") or os.getcwd()


def _dump_telemetry(tag):
    """Write this process's metrics snapshot as a sidecar
    (BENCH_<tag>.telemetry.json). Called from the worker after every row
    — INCLUDING failed ones, and from the probe on a backend that failed
    to initialise — so a dead run still records how far init got (probe
    timing, RPC attempts, executor cache state) instead of a bare error
    string."""
    try:
        from paddle_tpu import observe

        path = os.path.join(_telemetry_dir(),
                            "BENCH_%s.telemetry.json" % tag)
        observe.dump(path)
        _log("telemetry sidecar: %s" % path)
        return path
    except Exception as exc:  # noqa: BLE001 — telemetry must never sink a row
        _log("telemetry dump failed: %s: %s" % (type(exc).__name__, exc))
        return None

# chip peak bf16 FLOP/s by device_kind substring (lowercase); a non-CPU
# device missing from the table is an error, never an assumed peak
PEAKS = {
    "v5p": 459e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v5litepod": 197e12,
    "v6e": 918e12,
    "v6": 918e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}

# Non-quick default for PADDLE_TPU_BENCH_STEPS_PER_CALL (and the mode
# pin_baselines treats as baseline-comparable). Module-level so tools
# parse ONE literal instead of pattern-matching an expression.
DEFAULT_STEPS_PER_CALL = 10

# Self-baseline: best committed measurement per workload from earlier
# rounds (the reference ships no absolute numbers — BASELINE.md). These
# predate most of the code and were never reproduced on it; the first
# benchmark PR replaces the table (ROADMAP.md Queue 3 item 1).
BASELINES = {
    "bert_base_mlm_train_tokens_per_sec_per_chip": 49514.0,
    "deepfm_train_examples_per_sec_per_chip": 95864.3,
    "gpt_causal_s1024_train_tokens_per_sec_per_chip": 81363.5,
    "resnet50_train_images_per_sec_per_chip": 2272.1,
    "transformer_base_s1024_train_tokens_per_sec_per_chip": 37901.8,
    "transformer_base_train_tokens_per_sec_per_chip": 103605.4,
    "vgg16_train_images_per_sec_per_chip": 509.8,
}

# steps_per_call mode each baseline was measured at: comparing a
# 10-steps/call run against a 1-step/call baseline would read the known
# ~2x dispatch-amortization gain as a spurious improvement (and mask
# real regressions of the same size). Rows whose spc differs from the
# baseline's mode anchor at 1.0 until re-pinned. pin_baselines
# rewrites this dict alongside BASELINES.
#
# KNOWN GAP: only resnet50 has been re-pinned at the spc=10 default, so
# regression tracking for the other six workloads is SUSPENDED (they
# anchor at 1.0) until a full bench + pin_baselines run lands.
BASELINE_SPC = {
    "bert_base_mlm_train_tokens_per_sec_per_chip": 1,
    "deepfm_train_examples_per_sec_per_chip": 1,
    "gpt_causal_s1024_train_tokens_per_sec_per_chip": 1,
    "resnet50_train_images_per_sec_per_chip": 10,
    "transformer_base_s1024_train_tokens_per_sec_per_chip": 1,
    "transformer_base_train_tokens_per_sec_per_chip": 1,
    "vgg16_train_images_per_sec_per_chip": 1,
}


def peak_flops():
    """Peak bf16 FLOP/s of the device the row ran on; None on the CPU
    (CI smoke rows carry no MFU). Any other device must be in PEAKS."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    kind = dev.device_kind.lower()
    for key, val in PEAKS.items():
        if key in kind:
            return val
    raise RuntimeError(
        "no peak FLOP/s known for device_kind %r — add it to bench.PEAKS "
        "with its source; an MFU against an assumed peak is not a "
        "measurement" % dev.device_kind)


def _round_nonzero(x, digits):
    """Round a MEASURED positive value for the row without ever
    producing a false 0.0: a tiny value keeps enough digits to stay
    nonzero (deepfm's 0.1% MFU must print as 0.001, and a 0.00004 must
    not collapse to 0.0)."""
    r = round(x, digits)
    while r == 0.0 and x > 0 and digits <= 12:
        digits += 2
        r = round(x, digits)
    return r if r != 0.0 else x


def _mfu_fields(step_flops, steps, dt, peak):
    """The ``tflops_per_sec``/``mfu`` row fields, with the null-never-
    zero contract: ``None`` (JSON null) when ``cost_analysis`` produced
    no flop count or the row ran on the CPU (no peak) — an UNMEASURED
    MFU must never masquerade as a measured 0.0. A measured value is
    never rounded to 0.0 either (``_round_nonzero``)."""
    if not step_flops or dt <= 0:
        return {"tflops_per_sec": None, "mfu": None}
    achieved = step_flops * steps / dt
    mfu = _round_nonzero(achieved / peak, 4) if peak else None
    if mfu is not None:
        # mirror the measured MFU into the live telemetry plane so a
        # fleet_top watching this process's exporter sees it
        from paddle_tpu.observe.families import BENCH_MFU

        BENCH_MFU.set(mfu)
    return {
        "tflops_per_sec": _round_nonzero(achieved / 1e12, 2),
        "mfu": mfu,
    }


def _peak_bytes_fields(main, feed, fetch_list, scope=None, spc=1,
                       exe=None):
    """``peak_bytes_predicted`` (the static liveness-based estimate,
    analysis/memory.py) next to ``peak_bytes_xla`` (XLA's own
    memory_analysis of the compiled step) — both number-or-null, NEVER
    0.0, per the PR 12 MFU convention: an unmeasurable value must not
    masquerade as a measured zero. Estimation failures null the field
    instead of failing the row."""
    out = {"peak_bytes_predicted": None, "peak_bytes_xla": None}
    try:
        from paddle_tpu.analysis.memory import MemoryAnalysis

        batch = 1
        for v in (feed or {}).values():
            shape = np.shape(v)
            if shape:
                batch = max(1, int(shape[0]))
                break
        names = [getattr(v, "name", str(v)) for v in (fetch_list or [])]
        pk = MemoryAnalysis(main, fetch_names=names, scope=scope,
                            site="bench").peak_bytes(
                                batch, steps_per_call=spc)
        out["peak_bytes_predicted"] = int(pk) or None
    except Exception:
        pass
    if exe is not None:
        try:
            from paddle_tpu.contrib.memory_usage_calc import \
                compiled_memory_usage

            xla = compiled_memory_usage(exe, main, feed,
                                        fetch_list=fetch_list,
                                        scope=scope)
            out["peak_bytes_xla"] = int(xla) if xla else None
        except Exception:
            pass
    return out


def _cost_fields(main, feed, fetch_list, scope=None, spc=1,
                 step_seconds=None):
    """The roofline columns (analysis/cost.py): ``predicted_seconds``
    (the model's per-step estimate at this row's batch and
    steps-per-call) and ``cost_model_ratio`` (predicted / measured —
    the quantity the zoo gate bounds at 4x). Returns ``(fields,
    analytic_flops)``; the analytic per-step FLOPs feed ``_mfu_fields``
    so MFU no longer depends on the backend's own ``cost_analysis``
    (which prices the whole compiled module, fusion artifacts
    included). Both columns are number-or-null, NEVER 0.0, per the
    PR 12 convention; ``PADDLE_TPU_COST_MODEL=0`` nulls them and moves
    no ``paddle_cost_*`` family."""
    fields = {"predicted_seconds": None, "cost_model_ratio": None}
    try:
        from paddle_tpu.analysis.cost import (CostAnalysis,
                                              cost_model_enabled)

        if not cost_model_enabled():
            return fields, None
        batch = 1
        for v in (feed or {}).values():
            shape = np.shape(v)
            if shape:
                batch = max(1, int(shape[0]))
                break
        names = [getattr(v, "name", str(v)) for v in (fetch_list or [])]
        ca = CostAnalysis(main, fetch_names=names, scope=scope,
                          site="bench")
        flops = ca.flops(batch)
        pred = ca.predicted_seconds(batch, steps_per_call=spc)
        if pred > 0:
            fields["predicted_seconds"] = _round_nonzero(pred, 6)
            if step_seconds and step_seconds > 0:
                fields["cost_model_ratio"] = _round_nonzero(
                    pred / step_seconds, 3)
        return fields, (flops if flops > 0 else None)
    except Exception:
        return fields, None


def _fused_attention_on():
    from paddle_tpu.ops.attention import fused_attention_enabled

    return fused_attention_enabled()


def _check_pallas_mode(uses_flash):
    """Returns the pallas mode for the row, or raises when a 'fused' row
    would actually run interpret mode on a non-CPU backend — an
    interpret fallback on hardware is catastrophically slow and must
    surface as a row failure, not a kernel-regression-shaped number
    (set PADDLE_TPU_BENCH_ALLOW_INTERPRET=1 to record it anyway)."""
    if not uses_flash:
        return None
    import jax
    from paddle_tpu.ops.attention import pallas_mode

    mode = pallas_mode()
    platform = jax.devices()[0].platform.lower()
    if (mode == "interpret" and platform != "cpu"
            and os.environ.get("PADDLE_TPU_BENCH_ALLOW_INTERPRET") != "1"):
        raise RuntimeError(
            "fused-attention workload would run Pallas INTERPRET mode on "
            "platform %r — not a fused measurement. Set "
            "PADDLE_TPU_FLASH_INTERPRET=0 to force the compiled path or "
            "PADDLE_TPU_BENCH_ALLOW_INTERPRET=1 to record it anyway."
            % platform)
    return mode


def _bscale():
    return max(1, int(os.environ.get("PADDLE_TPU_BENCH_BATCH_SCALE", "1")))


def _kernel_tier_fields():
    """Row fields for the kernel-tier decisions this workload actually
    exercised (paddle_tpu.kernels.decisions_seen(), reset per workload):

    * ``kernel_tier`` — op -> choice map ("flash"/"composed"/
      "pallas:<cfg>"/"bypass"), so a regression is attributable to a
      specific kernel choice instead of an opaque number;
    * ``kernel_tuned`` — True when any decision came from a TUNED cache
      entry rather than the static defaults (pin_baselines treats such
      rows as incomparable with the default-config baseline);
    * ``kernels: "off"`` — the PADDLE_TPU_KERNELS=0 bypass ran (also
      incomparable; the A/B lever's row marker).
    """
    from paddle_tpu import kernels

    fields = {}
    seen = kernels.decisions_seen()
    if seen:
        fields["kernel_tier"] = {op: d["choice"]
                                 for op, d in sorted(seen.items())}
        if any(d.get("tuned") for d in seen.values()):
            fields["kernel_tuned"] = True
    if not kernels.kernels_enabled():
        fields["kernels"] = "off"
    return fields


def _optimize_level():
    """Effective graph-optimizer level for this worker (core/passes)."""
    from paddle_tpu.core.passes import optimize_level

    return optimize_level()


def _batch(default, quick, quick_default):
    """Per-workload batch size: the non-quick default scales by
    PADDLE_TPU_BENCH_BATCH_SCALE (int, default 1) so hardware batch
    sweeps (MFU ladder step 3) are one env var, no code edit. Rows
    record batch_scale when it differs from 1."""
    if quick:
        return quick_default
    return default * _bscale()


class _beacon:
    """Compile-watchdog heartbeat: while a long phase (compile/warmup)
    runs, log every 60s that it is still alive — a post-mortem can then
    tell a slow-but-progressing compile from a hung process.
    Each beat also checkpoints the telemetry sidecar: when the
    orchestrator SIGKILLs a hung worker (no finally runs), the last
    checkpoint still records how far the phase got."""

    def __init__(self, name, phase, period=60):
        import threading

        self._stop = threading.Event()
        self._t = threading.Thread(
            target=self._loop, args=(name, phase, period), daemon=True)

    def _loop(self, name, phase, period):
        import time as _time

        t0 = _time.time()
        while not self._stop.wait(period):
            _log("%s: still in %s (%.0fs)" % (name, phase,
                                              _time.time() - t0))
            _dump_telemetry(name)

    def __enter__(self):
        self._t.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        # join: a beat mid-_dump_telemetry must not race the caller's own
        # final sidecar dump for the same tag (same tmp path)
        self._t.join(timeout=30)


def _run_workload(name, unit, items_per_batch, build_fn, feed_fn, amp,
                  steps=10, warmup=3, quick=False, recompute=False,
                  uses_flash=False, attention=False):
    """Build, warm up, time, and report one workload in its own Scope."""
    if quick:
        steps, warmup = 2, 1
    pallas = _check_pallas_mode(uses_flash)
    import paddle_tpu as fluid
    from paddle_tpu import kernels as _kernels
    from paddle_tpu.core.scope import Scope, scope_guard

    # per-workload decision ledger: the row must describe THIS run's
    # kernel choices, not a previous workload's leftovers
    _kernels.reset_decisions()

    main, startup = fluid.Program(), fluid.Program()
    scope = Scope()
    with scope_guard(scope):
        with fluid.program_guard(main, startup):
            loss = build_fn()
        if amp:
            main.set_amp(True)
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)

        feed = feed_fn()
        # PADDLE_TPU_BENCH_PIPELINE=1: drive the timed loop through the
        # pipelined engine (DevicePrefetcher H2D thread + run_pipelined's
        # async in-flight window) instead of pre-placed feeds + blocking
        # run() — the end-to-end input-pipeline configuration, feeds
        # starting HOST-side each step. Rows record "pipelined" so
        # pin_baselines never mixes the modes.
        pipelined = os.environ.get("PADDLE_TPU_BENCH_PIPELINE", "0") != "0"
        import jax.numpy as jnp

        if not pipelined:
            # place feeds on device once: the timed loop measures the
            # train step, not a repeated H2D of the same host arrays (a
            # real input pipeline overlaps transfer via the prefetcher)
            feed = {k: jnp.asarray(v) for k, v in feed.items()}
        # device-side K-step loop: one host dispatch per K steps
        # (run_repeated's lax.scan) instead of K round-trips — isolates
        # per-step host dispatch latency from the device step
        # time. Rows record steps_per_call so modes never mix.
        # default 10: real training drives the same way (run_repeated /
        # readers), so the per-step loop is the unrepresentative mode
        # (what a window buys on a directly attached chip is not
        # measured yet — ROADMAP.md Queue 1 item 3). Set =1 to measure
        # dispatch overhead.
        # Quick (CI smoke) mode defaults to 1: a 10-step scan would 5x
        # the smoke work and its rows never feed regression tracking.
        spc = int(os.environ.get(
            "PADDLE_TPU_BENCH_STEPS_PER_CALL",
            "1" if quick else str(DEFAULT_STEPS_PER_CALL)))
        if pipelined:
            # the pipelined mode drives the SAME windowed train_loop
            # real training uses: K batches per scanned dispatch
            # (whole-loop compilation), feeds starting host-side each
            # step, the prefetcher's H2D under the window's compute.
            # spc=1 (quick default) is the classic per-step loop.
            in_flight = int(os.environ.get("PADDLE_TPU_BENCH_IN_FLIGHT", "2"))
            depth = int(os.environ.get("PADDLE_TPU_BENCH_PREFETCH_DEPTH", "2"))
            steps = max(steps, spc)  # at least one full window
            # fresh array copies per step: the const-feed dedup cache must
            # not short-circuit the H2D this mode exists to measure; lazy
            # so peak host RSS holds only the prefetch window, not steps x
            # batch bytes
            host_batches = (
                {k: np.array(v, copy=True) for k, v in feed.items()}
                for _ in range(steps))
            _log("%s: compiling + %d warmup steps (pipelined)"
                 % (name, warmup))
            with _beacon(name, "compile/warmup"):
                for _ in range(warmup):
                    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
                if spc > 1:
                    # pay the K-step scan compile outside the timed
                    # loop, through the SAME windowed loop shape (the
                    # scan variant hangs off the per-step plan; a
                    # run_repeated warmup would compile a different,
                    # stacked-shape plan and leave this one cold)
                    warm_batches = (
                        {k: np.array(v, copy=True)
                         for k, v in feed.items()} for _ in range(spc))
                    exe.train_loop(
                        main, iter(warm_batches), fetch_list=[loss],
                        scope=scope, max_in_flight=in_flight,
                        prefetch_depth=depth, steps_per_call=spc)
            _log("%s: timing %d pipelined steps (steps_per_call=%d, "
                 "in_flight=%d, depth=%d)"
                 % (name, steps, spc, in_flight, depth))
            t0 = time.perf_counter()
            _n, vals = exe.train_loop(
                main, iter(host_batches), fetch_list=[loss], scope=scope,
                max_in_flight=in_flight, prefetch_depth=depth,
                steps_per_call=spc)
            float(np.asarray(vals[0]).reshape(-1)[0])  # block on the result
            dt = time.perf_counter() - t0
        elif spc > 1:
            steps = spc
            _log("%s: compiling K-step scan + warmup (%d steps/call)"
                 % (name, spc))
            with _beacon(name, "compile/warmup"):
                exe.run_repeated(main, feed=feed, fetch_list=[loss],
                                 scope=scope, steps=spc)
            _log("%s: timing one %d-step call" % (name, spc))
            t0 = time.perf_counter()
            vals = exe.run_repeated(main, feed=feed, fetch_list=[loss],
                                    scope=scope, steps=spc)
            float(np.asarray(vals[0]).reshape(-1)[0])  # block on the result
            dt = time.perf_counter() - t0
        else:
            _log("%s: compiling + %d warmup steps" % (name, warmup))
            with _beacon(name, "compile/warmup"):
                for _ in range(warmup):
                    exe.run(main, feed=feed, fetch_list=[loss],
                            scope=scope)

            _log("%s: timing %d steps" % (name, steps))
            t0 = time.perf_counter()
            for _ in range(steps):
                vals = exe.run(main, feed=feed, fetch_list=[loss],
                               scope=scope)
            float(np.asarray(vals[0]).reshape(-1)[0])  # block on the result
            dt = time.perf_counter() - t0

        throughput = items_per_batch * steps / dt
        _log("%s: cost_analysis" % name)
        # analytic FLOPs (analysis/cost.py) price the PROGRAM the row
        # ran, so MFU is comparable across backends and fusion
        # decisions; the backend's own cost_analysis remains the
        # fallback when the cost model is off or has no rule coverage
        cost_fields, analytic_flops = _cost_fields(
            main, feed, [loss], scope=scope, spc=spc,
            step_seconds=dt / steps)
        step_flops = analytic_flops or exe.cost_analysis(
            main, feed=feed, fetch_list=[loss], scope=scope).get("flops", 0.0)
        peak = peak_flops()
        import jax as _jax

        opt_level = _optimize_level()
        rec = {
            "metric": name,
            # which backend actually ran — a CPU row must never pass
            # for a hardware number (pin_baselines refuses platform
            # "cpu"; the judge can see it either way)
            "platform": _jax.devices()[0].platform.lower(),
            # smoke rows (tiny batches) must never pin as baselines
            **({"quick": True} if quick else {}),
            "precision": "bf16_amp" if amp else "f32",
            # recompute trades FLOPs for memory: mark the row so it is
            # never mistaken for (or regression-compared against) a
            # plain-activation baseline at the same batch size
            **({"recompute": True} if recompute else {}),
            # which flash-kernel path the row actually exercised:
            # "compiled" (Mosaic) / "interpret"; absent on non-attention
            # workloads and on composed-path (unfused) runs
            **({"pallas_mode": pallas} if pallas else {}),
            # the full kernel-tier decision map rides next to
            # pallas_mode on EVERY row (attention included), so a
            # regression is attributable to a specific kernel choice;
            # kernel_tuned / kernels="off" rows never pin as baselines
            **_kernel_tier_fields(),
            # attention workloads always say which attention math ran —
            # "flash" (Pallas kernel) or "composed" (XLA-fused dense
            # scores; via either the short-S dispatch or
            # PADDLE_TPU_FUSED_ATTENTION=0)
            **({"attention_path": "flash" if uses_flash else "composed"}
               if attention else {}),
            # a non-default dispatch threshold (e.g. the playbook's
            # forced-kernel S=128 A/B) marks the row so pin_baselines
            # never anchors a baseline to an override config
            **({"flash_min_seq": int(os.environ["PADDLE_TPU_FLASH_MIN_SEQ"])}
               if (attention and "PADDLE_TPU_FLASH_MIN_SEQ" in os.environ)
               else {}),
            # K steps per host dispatch (run_repeated/train_loop
            # lax.scan window) — recorded on EVERY train row (spc=1 =
            # the classic one-dispatch-per-step loop), so rows from
            # different dispatch modes can never be silently compared
            "steps_per_call": spc,
            # pipelined-engine rows (DevicePrefetcher + async in-flight
            # dispatch, host-side feeds each step) are their own mode:
            # never regression-compared against pre-placed-feed
            # baselines; the window/depth knobs shape the measurement,
            # so rows record them like every other non-default knob
            **({"pipelined": True, "in_flight": in_flight,
                "prefetch_depth": depth} if pipelined else {}),
            # a non-default PADDLE_TPU_OPTIMIZE level (the graph-pass
            # pipeline, docs/OPTIMIZER.md) marks the row: a level-0/1
            # run compiled a different program than the default config.
            # The sidecar's paddle_optimizer_* families carry the full
            # per-pass story (stats_dump --grep paddle_optimizer)
            **({"optimize_level": opt_level} if opt_level != 2 else {}),
            # batch multiplier (PADDLE_TPU_BENCH_BATCH_SCALE): scaled
            # rows never regression-compare against the default-batch
            # baseline silently
            **({"batch_scale": _bscale()}
               if (_bscale() > 1 and not quick) else {}),
            "value": round(throughput, 1),
            "unit": unit,
            # recompute / scaled-batch rows never compare against the
            # plain default-config baseline (different effective config)
            # — they anchor at 1.0 until a matching baseline exists
            "vs_baseline": round(throughput / BASELINES[name], 3)
            if (name in BASELINES and not recompute and _bscale() == 1
                and not pipelined
                and spc == BASELINE_SPC.get(name, 1)
                and not (attention
                         and "PADDLE_TPU_FLASH_MIN_SEQ" in os.environ))
            else 1.0,
            # null (never 0.0) when the backend produced no flop count
            # or the chip peak is unknown — see _mfu_fields
            **_mfu_fields(step_flops, steps, dt, peak),
            # static peak-HBM estimate next to XLA's compiled number
            # (analysis/memory.py; number-or-null, never 0.0)
            **_peak_bytes_fields(main, feed, [loss], scope=scope,
                                 spc=spc, exe=exe),
            # roofline prediction next to the measurement it models
            # (analysis/cost.py; number-or-null, never 0.0; purely
            # informational — pin_baselines provably ignores both)
            **cost_fields,
        }
        print(json.dumps(rec), flush=True)
        return rec


def _recompute_requested():
    return os.environ.get("PADDLE_TPU_RECOMPUTE", "0") != "0"


def _maybe_recompute(opt, checkpoints):
    """PADDLE_TPU_RECOMPUTE=1 trades FLOPs for activation memory via
    RecomputeOptimizer (per-layer boundaries) — the knob that buys batch
    size (hence MFU) on memory-bound long-context runs. Only workloads
    that thread checkpoints= through here are affected (and only their
    rows carry the "recompute" marker)."""
    if _recompute_requested() and checkpoints:
        import paddle_tpu as fluid

        opt = fluid.optimizer.RecomputeOptimizer(opt)
        opt._set_checkpoints(checkpoints)
    return opt


def bench_transformer(amp, quick, uses_flash=False):
    import paddle_tpu.models.transformer as transformer

    seq, batch = ATTENTION_SEQ["transformer"], _batch(256, quick, 8)
    cfg = transformer.base_config()
    cfg["max_length"] = seq

    def build():
        ckpts = []
        loss, _ = transformer.build(cfg, seq_len=seq, checkpoints=ckpts)
        import paddle_tpu as fluid

        opt = _maybe_recompute(
            fluid.optimizer.Adam(learning_rate=1e-4), ckpts)
        opt.minimize(loss)
        return loss

    def feed():
        rs = np.random.RandomState(0)
        return {
            "src_ids": rs.randint(1, cfg["src_vocab"], (batch, seq)).astype("int64"),
            "trg_ids": rs.randint(1, cfg["trg_vocab"], (batch, seq)).astype("int64"),
            "lbl_ids": rs.randint(1, cfg["trg_vocab"], (batch, seq)).astype("int64"),
        }

    return _run_workload("transformer_base_train_tokens_per_sec_per_chip",
                         "tokens/sec", batch * seq, build, feed, amp,
                         quick=quick, recompute=_recompute_requested(),
                         uses_flash=uses_flash, attention=True)


def bench_transformer_long(amp, quick, uses_flash=False):
    """Long-context variant (S=1024): the fused flash-attention path's
    showcase — the composed path materializes [S, S] scores per head."""
    import paddle_tpu.models.transformer as transformer

    seq, batch = ATTENTION_SEQ["transformer_long"], _batch(32, quick, 2)
    cfg = transformer.base_config()
    cfg["max_length"] = seq

    def build():
        ckpts = []
        loss, _ = transformer.build(cfg, seq_len=seq, checkpoints=ckpts)
        import paddle_tpu as fluid

        opt = _maybe_recompute(
            fluid.optimizer.Adam(learning_rate=1e-4), ckpts)
        opt.minimize(loss)
        return loss

    def feed():
        rs = np.random.RandomState(0)
        return {
            "src_ids": rs.randint(1, cfg["src_vocab"], (batch, seq)).astype("int64"),
            "trg_ids": rs.randint(1, cfg["trg_vocab"], (batch, seq)).astype("int64"),
            "lbl_ids": rs.randint(1, cfg["trg_vocab"], (batch, seq)).astype("int64"),
        }

    return _run_workload("transformer_base_s1024_train_tokens_per_sec_per_chip",
                         "tokens/sec", batch * seq, build, feed, amp,
                         quick=quick, recompute=_recompute_requested(),
                         uses_flash=uses_flash, attention=True)


def bench_resnet50(amp, quick, uses_flash=False):
    import paddle_tpu.models.resnet as resnet

    batch = _batch(128, quick, 4)

    def build():
        import paddle_tpu as fluid

        loss, _acc, _ = resnet.build(class_dim=1000, depth=50)
        fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9).minimize(loss)
        return loss

    def feed():
        rs = np.random.RandomState(0)
        return {
            "img": rs.rand(batch, 3, 224, 224).astype("float32"),
            "label": rs.randint(0, 1000, (batch, 1)).astype("int64"),
        }

    return _run_workload("resnet50_train_images_per_sec_per_chip",
                         "images/sec", batch, build, feed, amp, quick=quick)


def bench_vgg16(amp, quick, uses_flash=False):
    import paddle_tpu.models.vgg as vgg

    batch = _batch(128, quick, 4)

    def build():
        import paddle_tpu as fluid

        loss, _acc, _ = vgg.build(class_dim=1000)
        fluid.optimizer.Momentum(learning_rate=0.01, momentum=0.9).minimize(loss)
        return loss

    def feed():
        rs = np.random.RandomState(0)
        return {
            "img": rs.rand(batch, 3, 224, 224).astype("float32"),
            "label": rs.randint(0, 1000, (batch, 1)).astype("int64"),
        }

    return _run_workload("vgg16_train_images_per_sec_per_chip",
                         "images/sec", batch, build, feed, amp, quick=quick)


def bench_bert(amp, quick, uses_flash=False):
    import paddle_tpu.models.bert as bert

    seq, max_mask = ATTENTION_SEQ["bert"], 20
    batch = _batch(64, quick, 2)
    cfg = bert.base_config()

    def build():
        import paddle_tpu as fluid

        ckpts = []
        loss, _ = bert.build(cfg, seq_len=seq, max_mask=max_mask,
                             checkpoints=ckpts)
        opt = _maybe_recompute(
            fluid.optimizer.Adam(learning_rate=1e-4), ckpts)
        opt.minimize(loss)
        return loss

    def feed():
        rs = np.random.RandomState(0)
        return {
            "src_ids": rs.randint(1, cfg["vocab"], (batch, seq)).astype("int64"),
            "sent_ids": rs.randint(0, 2, (batch, seq)).astype("int64"),
            "input_mask": np.ones((batch, seq), dtype="float32"),
            "mask_pos": rs.randint(0, batch * seq, (batch, max_mask)).astype("int64"),
            "mask_label": rs.randint(0, cfg["vocab"], (batch, max_mask)).astype("int64"),
            "mask_weight": np.ones((batch, max_mask), dtype="float32"),
        }

    return _run_workload("bert_base_mlm_train_tokens_per_sec_per_chip",
                         "tokens/sec", batch * seq, build, feed, amp,
                         quick=quick, recompute=_recompute_requested(),
                         uses_flash=uses_flash, attention=True)


def bench_gpt_causal(amp, quick, uses_flash=False):
    """Decoder-only causal LM at S=1024: the causal flash kernel's
    block-skipping showcase (~2x the dense-causal step FLOPs)."""
    import paddle_tpu.models.gpt as gpt

    seq, batch = ATTENTION_SEQ["gpt_causal"], _batch(16, quick, 2)
    cfg = dict(d_model=512, d_ff=2048, n_head=8, n_layer=6, vocab=32000,
               max_length=seq, dropout=0.1)

    def build():
        import paddle_tpu as fluid

        ckpts = []
        loss, _ = gpt.build(cfg, seq_len=seq, checkpoints=ckpts)
        opt = _maybe_recompute(
            fluid.optimizer.Adam(learning_rate=1e-4), ckpts)
        opt.minimize(loss)
        return loss

    def feed():
        rs = np.random.RandomState(0)
        return {"ids": rs.randint(1, cfg["vocab"],
                                  (batch, seq)).astype("int64")}

    return _run_workload("gpt_causal_s1024_train_tokens_per_sec_per_chip",
                         "tokens/sec", batch * seq, build, feed, amp,
                         quick=quick, recompute=_recompute_requested(),
                         uses_flash=uses_flash, attention=True)


def bench_deepfm(amp, quick, uses_flash=False):
    import paddle_tpu.models.ctr as ctr

    batch = _batch(8192, quick, 256)
    n_fields, n_dense, vocab = 26, 13, 1000001

    def build():
        import paddle_tpu as fluid

        loss, _acc, _ = ctr.build("deepfm", n_fields, n_dense, vocab)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        return loss

    def feed():
        rs = np.random.RandomState(0)
        return {
            "sparse_ids": rs.randint(0, vocab, (batch, n_fields)).astype("int64"),
            "dense": rs.rand(batch, n_dense).astype("float32"),
            "label": rs.randint(0, 2, (batch, 1)).astype("int64"),
        }

    return _run_workload("deepfm_train_examples_per_sec_per_chip",
                         "examples/sec", batch, build, feed, amp, quick=quick)


def _deepfm_dist_build(distributed):
    """ONE graph for the distributed-CTR trainer AND its pservers (the
    transpiler requires both sides to transpile the identical program)."""
    import paddle_tpu as fluid
    import paddle_tpu.models.ctr as ctr

    n_fields, n_dense, vocab = 26, 13, 1000001
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss, _acc, _ = ctr.build("deepfm", n_fields, n_dense, vocab,
                                  distributed=distributed)
        fluid.optimizer.SGD(learning_rate=1e-3).minimize(loss)
    return main, startup, loss, (n_fields, n_dense, vocab)


def _deepfm_dist_transpile(main, startup, trainer_id=0):
    import paddle_tpu as fluid

    t = fluid.DistributeTranspiler()
    t.transpile(trainer_id=trainer_id, program=main,
                pservers=os.environ["PADDLE_PSERVER_ENDPOINTS"],
                trainers=int(os.environ.get("PADDLE_TRAINERS_NUM", "1")),
                sync_mode=True, startup_program=startup)
    return t


def _run_dist_ctr_pserver():
    """Hidden entry: one CPU pserver for bench_deepfm_dist. CPU-only by
    design: its launcher passes JAX_PLATFORMS=cpu in the environment, so
    it never claims the trainer's chip.

    Port assignment (no TOCTOU): this process binds port 0 ITSELF via a
    prebound RPCServer — the kernel assigns a free port that stays held
    from bind to serve — writes the real endpoint to
    PADDLE_TPU_PS_PORT_FILE, then waits for the launcher to publish the
    full cluster endpoint list (PADDLE_TPU_PS_ENDPOINTS_FILE) before
    transpiling. The old scheme (launcher binds/closes/reuses a port)
    could lose the port to another process and stall the trainer for the
    full RPC deadline."""
    import paddle_tpu as fluid
    from paddle_tpu.distributed import ps as ps_runtime
    from paddle_tpu.distributed.rpc import RPCServer

    port_file = os.environ.get("PADDLE_TPU_PS_PORT_FILE")
    if port_file:
        server = RPCServer(
            port=0,
            num_trainers=int(os.environ.get("PADDLE_TRAINERS_NUM", "1")),
            sync=True)
        ep = "127.0.0.1:%d" % server.port
        tmp = port_file + ".tmp.%d" % os.getpid()
        with open(tmp, "w") as f:
            f.write(ep)
        os.replace(tmp, port_file)  # atomic: launcher never reads a torn file
        endpoints = _wait_for_file(
            os.environ["PADDLE_TPU_PS_ENDPOINTS_FILE"],
            timeout_s=int(os.environ.get("PADDLE_TPU_PS_RENDEZVOUS_TIMEOUT",
                                         "120")))
        os.environ["PADDLE_PSERVER_ENDPOINTS"] = endpoints
        os.environ["PADDLE_CURRENT_ENDPOINT"] = ep
        ps_runtime.register_prebound_server(ep, server)

    main, startup, _loss, _dims = _deepfm_dist_build(distributed=True)
    t = _deepfm_dist_transpile(main, startup)
    ep = os.environ["PADDLE_CURRENT_ENDPOINT"]
    exe = fluid.Executor()
    exe.run(t.get_startup_program(ep))
    exe.run(t.get_pserver_program(ep))
    return 0


def _wait_for_file(path, timeout_s=120, poll_s=0.05, procs=()):
    """Poll until `path` exists and is non-empty; return its contents.
    Raises if the deadline passes or any process in `procs` died."""
    t0 = time.monotonic()
    while True:
        try:
            with open(path) as f:
                data = f.read().strip()
            if data:
                return data
        except OSError:
            pass
        for p in procs:
            if p.poll() is not None:
                raise RuntimeError(
                    "pserver child exited rc=%s before rendezvous"
                    % p.returncode)
        if time.monotonic() - t0 > timeout_s:
            raise RuntimeError("timed out after %ds waiting for %s"
                               % (timeout_s, path))
        time.sleep(poll_s)


def bench_deepfm_dist(amp, quick, uses_flash=False):
    """The reference's CTR benchmark is DISTRIBUTED (fluid_benchmark.py
    pserver mode + models/): sparse tables live only on pservers
    (prefetch + SelectedRows grads over the RPC stack), the dense half
    trains on this chip. Two localhost CPU pservers are spawned for the
    duration of the row; loss parity vs single-process is pinned CPU-side
    by tests/test_dist_ps.py::test_dist_ctr_sparse_table_cluster_*."""
    import tempfile

    batch = _batch(8192, quick, 256)
    n_ps = 2
    os.environ["PADDLE_TRAINERS_NUM"] = "1"
    os.environ["PADDLE_TRAINER_ID"] = "0"
    rdv = tempfile.mkdtemp(prefix="bench_ps_rdv_")
    port_files = [os.path.join(rdv, "ps%d.endpoint" % i)
                  for i in range(n_ps)]
    eps_file = os.path.join(rdv, "endpoints")
    pservers = []
    try:
        for pf in port_files:
            env = dict(os.environ)
            env.update({"JAX_PLATFORMS": "cpu",
                        "PADDLE_TPU_PS_PORT_FILE": pf,
                        "PADDLE_TPU_PS_ENDPOINTS_FILE": eps_file})
            # SAME process group as this worker (no start_new_session):
            # if the orchestrator deadline-kills a hung worker via
            # killpg, the pservers die with it instead of leaking as
            # orphans blocked in their serve loop
            pservers.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--dist-ctr-pserver"],
                env=env, stderr=sys.stderr))

        # each pserver binds port 0 itself and reports the REAL endpoint
        # back through its port file (no bind/close/reuse TOCTOU); the
        # assembled list is published to every child atomically
        endpoints = ",".join(
            _wait_for_file(pf, timeout_s=120, procs=pservers)
            for pf in port_files)
        tmp = eps_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(endpoints)
        os.replace(tmp, eps_file)
        os.environ["PADDLE_PSERVER_ENDPOINTS"] = endpoints

        import paddle_tpu as fluid
        from paddle_tpu.core.scope import Scope, scope_guard

        main, startup, loss, (n_fields, n_dense, vocab) = \
            _deepfm_dist_build(distributed=True)
        t = _deepfm_dist_transpile(main, startup)
        prog = t.get_trainer_program()
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor(fluid.TPUPlace())
            if amp:
                prog.set_amp(True)
            exe.run(t.get_trainer_startup_program(), scope=scope)
            rs = np.random.RandomState(0)
            feed = {
                "sparse_ids": rs.randint(
                    0, vocab, (batch, n_fields)).astype("int64"),
                "dense": rs.rand(batch, n_dense).astype("float32"),
                "label": rs.randint(0, 2, (batch, 1)).astype("int64"),
            }
            # device-resident feeds, same as _run_workload: the timed
            # loop measures the train step + RPC, not repeated H2D of
            # the same host arrays
            import jax.numpy as jnp

            feed = {k: jnp.asarray(v) for k, v in feed.items()}
            steps, warmup = (2, 1) if quick else (10, 3)
            _log("deepfm_dist: compiling + %d warmup steps" % warmup)
            with _beacon("deepfm_dist", "compile/warmup"):
                for _ in range(warmup):
                    exe.run(prog, feed=feed, fetch_list=[loss], scope=scope)
            _log("deepfm_dist: timing %d steps" % steps)
            t0 = time.perf_counter()
            for _ in range(steps):
                vals = exe.run(prog, feed=feed, fetch_list=[loss],
                               scope=scope)
            float(np.asarray(vals[0]).reshape(-1)[0])
            dt = time.perf_counter() - t0
            exe.close()  # Complete -> pservers drain and exit
        import jax as _jax

        rec = {
            "metric": "deepfm_dist_train_examples_per_sec_per_chip",
            "platform": _jax.devices()[0].platform.lower(),
            **({"quick": True} if quick else {}),
            "precision": "bf16_amp" if amp else "f32",
            "distributed": True,
            "pservers": n_ps,
            # per-step RPC callbacks make spc=1 THIS row's default mode
            # (recorded like every train row)
            "steps_per_call": 1,
            "value": round(batch * steps / dt, 1),
            "unit": "examples/sec",
            "vs_baseline": round(
                batch * steps / dt / BASELINES[
                    "deepfm_dist_train_examples_per_sec_per_chip"], 3)
            if "deepfm_dist_train_examples_per_sec_per_chip" in BASELINES
            else 1.0,
            # null, never 0.0: the sparse path is RPC-bound and its
            # dense-half flop count alone would be a lie — unmeasured
            "tflops_per_sec": None,
            "mfu": None,
            # trainer-side static estimate only (the PS-resident tables
            # live in other processes; no XLA number for the RPC step)
            **{k: v for k, v in _peak_bytes_fields(
                prog, feed, [loss], scope=scope).items()
               if k == "peak_bytes_predicted"},
        }
        print(json.dumps(rec), flush=True)
        return rec
    finally:
        for p in pservers:  # direct kill: children share our process group
            if p.poll() is None:
                p.kill()
                p.wait()
        import shutil

        shutil.rmtree(rdv, ignore_errors=True)


def _serving_row(name, value, unit, lat_s, extra):
    """One serving bench row: open-loop p50/p99 latency + throughput.
    Marked "serving": pin_baselines never pins these over training
    baselines (a scheduler-mode number is not a train-step number).
    p50/p99 come from the shared ``Histogram.quantile`` over the
    declared request-latency bucket schema (tools/serving_load.py
    folds its latencies the same way), so the bench's percentiles and
    every sidecar reader's agree by construction."""
    import jax as _jax

    _tools = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools")
    if _tools not in sys.path:
        sys.path.insert(0, _tools)
    from serving_load import _latency_hist

    hist = _latency_hist(lat_s)
    rec = {
        "metric": name,
        "platform": _jax.devices()[0].platform.lower(),
        "serving": True,
        "value": round(value, 1),
        "unit": unit,
        "p50_ms": round(1e3 * hist.quantile(0.50), 2) if lat_s else None,
        "p99_ms": round(1e3 * hist.quantile(0.99), 2) if lat_s else None,
        "vs_baseline": 1.0,
        "tflops_per_sec": None,  # scheduler-bound; MFU is not the story
        "mfu": None,
        # engines that expose a byte model override this via extra
        # (number-or-null, never 0.0 — the MFU convention)
        "peak_bytes_predicted": None,
    }
    rec.update(extra)
    print(json.dumps(rec), flush=True)
    return rec


def bench_serving_decode(amp, quick, uses_flash=False):
    """Continuous-batching GPT decode under a seeded open-loop load:
    requests arrive on an exponential clock regardless of completion
    (open loop — queueing delay shows up in latency instead of
    throttling the generator), the engine packs them into b_max slots.
    Reports aggregate tokens/sec + per-request p50/p99 latency; the
    telemetry sidecar carries the occupancy/queue histograms."""
    import threading

    from paddle_tpu.observe.families import SERVING_TOKENS_PER_SEC
    from paddle_tpu.serving import DecodeEngine

    cfg = dict(d_model=128, d_ff=512, n_head=4, n_layer=4, vocab=1024,
               max_length=128, dropout=0.0)
    b_max = 4 if quick else 8
    n_req = 8 if quick else 64
    P, n_new = 8, 8 if quick else 24
    rs = np.random.RandomState(0)
    prompts = [rs.randint(1, cfg["vocab"], (P,)).astype("int64")
               for _ in range(n_req)]

    engine = DecodeEngine(cfg, params=None, b_max=b_max,
                          max_len=P + n_new,
                          queue_capacity=max(64, 2 * n_req))
    engine.start()
    try:
        _log("serving_decode: compiling decode+prefill (warmup request)")
        with _beacon("serving_decode", "compile/warmup"):
            engine.submit(prompts[0], n_new).result(timeout=600)
            # calibrate the arrival rate to ~b_max concurrent streams:
            # per-token step time from a second, timed request
            t0 = time.perf_counter()
            engine.submit(prompts[0], n_new).result(timeout=600)
            per_token = (time.perf_counter() - t0) / n_new
        mean_gap = max(per_token * n_new / b_max, 1e-4)
        arrivals = np.cumsum(rs.exponential(mean_gap, size=n_req))

        from paddle_tpu import observe

        def _occ():
            s = observe.snapshot()["metrics"][
                "paddle_serving_slot_occupancy_ratio"]["samples"][0]
            return s["count"], s["sum"]

        # occupancy over the DRIVE interval only: the two solo
        # warmup/calibration requests decode at 1/b_max and would drag
        # a lifetime mean well below what the row claims to measure
        occ0 = _occ()
        done_at = [None] * n_req
        reqs = [None] * n_req
        t_start = time.perf_counter()

        def _drive():
            for i, (p, at) in enumerate(zip(prompts, arrivals)):
                dt = t_start + at - time.perf_counter()
                if dt > 0:
                    time.sleep(dt)
                reqs[i] = engine.submit(p, n_new)

        _log("serving_decode: open-loop drive (%d requests, mean gap "
             "%.1fms)" % (n_req, mean_gap * 1e3))
        driver = threading.Thread(target=_drive, daemon=True)
        driver.start()
        driver.join()
        for i, r in enumerate(reqs):
            r.result(timeout=600)
            done_at[i] = time.perf_counter()
        t_end = max(done_at)
        # open-loop latency: completion minus SCHEDULED arrival (late
        # submission counts against the server, as it would in a real
        # open-loop harness)
        lat = [d - (t_start + a) for d, a in zip(done_at, arrivals)]
        tokens = n_req * n_new
        tps = tokens / (t_end - t_start)
        SERVING_TOKENS_PER_SEC.set(tps)
        occ1 = _occ()
        steps = occ1[0] - occ0[0]
        return _serving_row(
            "serving_gpt_decode_tokens_per_sec", tps, "tokens/sec", lat,
            {"b_max": b_max, "requests": n_req, "n_new": n_new,
             **({"quick": True} if quick else {}),
             "peak_bytes_predicted": engine.predicted_resident_bytes(),
             "mean_occupancy": round((occ1[1] - occ0[1]) / steps, 3)
             if steps else None})
    finally:
        engine.stop()


def bench_serving_predictor(amp, quick, uses_flash=False):
    """Micro-batched Predictor serving under a seeded open-loop load:
    single-row requests coalesce in the max-wait window, pad to the
    warmup bucket, and ride one dispatch. Reports examples/sec +
    p50/p99; the sidecar carries batch-rows/padding-waste families."""
    import tempfile
    import threading

    import paddle_tpu as fluid
    from paddle_tpu.core.scope import Scope, scope_guard
    from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor
    from paddle_tpu.serving import MicroBatcher

    n_req = 64 if quick else 512
    bucket = 8 if quick else 32
    rs = np.random.RandomState(0)

    model_dir = tempfile.mkdtemp(prefix="bench_serving_pred_")
    scope = Scope()
    main, startup = fluid.Program(), fluid.Program()
    with scope_guard(scope):
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", [64], dtype="float32")
            h = fluid.layers.fc(x, 256, act="relu")
            h = fluid.layers.fc(h, 256, act="relu")
            pred = fluid.layers.fc(h, 16, act="softmax")
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        fluid.io.save_inference_model(model_dir, ["x"], [pred], exe,
                                      main_program=main)

    config = AnalysisConfig(model_dir=model_dir)
    config.warmup_batch_sizes = [1, bucket]
    _log("serving_predictor: warmup compiles (buckets %s)"
         % config.warmup_batch_sizes)
    with _beacon("serving_predictor", "compile/warmup"):
        predictor = create_paddle_predictor(config)
        # per-request step time at bucket occupancy 1 calibrates the
        # arrival rate (target: ~bucket/2 rows per window)
        one = {"x": rs.randn(1, 64).astype("float32")}
        t0 = time.perf_counter()
        for _ in range(5):
            predictor.run(one)
        per_run = (time.perf_counter() - t0) / 5
    max_wait = max(2 * per_run, 0.002)
    mean_gap = max(2 * max_wait / bucket, 1e-5)
    arrivals = np.cumsum(rs.exponential(mean_gap, size=n_req))
    feeds = [{"x": rs.randn(1, 64).astype("float32")}
             for _ in range(n_req)]

    batcher = MicroBatcher(predictor, max_rows=bucket,
                           max_wait_s=max_wait,
                           queue_capacity=max(256, 2 * n_req))
    try:
        reqs = [None] * n_req
        t_start = time.perf_counter()

        def _drive():
            for i, (f, at) in enumerate(zip(feeds, arrivals)):
                dt = t_start + at - time.perf_counter()
                if dt > 0:
                    time.sleep(dt)
                reqs[i] = batcher.submit(f)

        _log("serving_predictor: open-loop drive (%d requests, window "
             "%.1fms)" % (n_req, max_wait * 1e3))
        driver = threading.Thread(target=_drive, daemon=True)
        driver.start()
        driver.join()
        done_at = []
        for r in reqs:
            r.result(timeout=600)
            done_at.append(time.perf_counter())
        t_end = max(done_at)
        lat = [d - (t_start + a) for d, a in zip(done_at, arrivals)]
        eps = n_req / (t_end - t_start)
        from paddle_tpu import observe

        snap = observe.snapshot()["metrics"]
        rows = snap["paddle_serving_batch_rows"]["samples"][0]
        return _serving_row(
            "serving_predictor_examples_per_sec", eps, "examples/sec",
            lat,
            {"bucket": bucket, "requests": n_req,
             **({"quick": True} if quick else {}),
             "mean_batch_rows": round(rows["sum"] / rows["count"], 2)
             if rows["count"] else None})
    finally:
        batcher.close()
        import shutil

        shutil.rmtree(model_dir, ignore_errors=True)


def bench_serving_fleet(amp, quick, uses_flash=False):
    """Fleet-tier serving under a shared-prefix arrival mix: a
    2-replica router with a SHARED prefix store and a speculative
    draft model, driven by tools/serving_load.py's open-loop generator
    (80% of requests share one system-prompt head). Reports aggregate
    tokens/sec + p50/p99 and the two fleet rates — prefix_hit_rate and
    spec_accept_rate — that tell whether the cache and the draft are
    earning their keep. Rows are marked "fleet" (and "serving"):
    pin_baselines treats them as incomparable with non-fleet rows."""
    import sys as _sys

    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    try:
        from serving_load import drive
    finally:
        _sys.path.pop(0)
    from paddle_tpu.observe.families import (SERVING_SPEC_ACCEPT_RATE,
                                             SERVING_TOKENS_PER_SEC)
    from paddle_tpu.serving import DecodeEngine, PrefixStore, ReplicaRouter

    vocab, max_len = 1024, 160
    P, prefix_len, n_new = 96, 64, 8 if quick else 16
    n_req = 12 if quick else 64
    b_max = 2 if quick else 4
    cfg = dict(d_model=128, d_ff=512, n_head=4, n_layer=4, vocab=vocab,
               max_length=max_len, dropout=0.0)
    draft = dict(d_model=32, d_ff=128, n_head=2, n_layer=1, vocab=vocab,
                 max_length=max_len, dropout=0.0)
    store = PrefixStore(256 << 20)

    def factory(idx):
        return DecodeEngine(cfg, params=None, b_max=b_max,
                            max_len=max_len, prefix_store=store,
                            draft_cfg=draft, spec_k=3,
                            queue_capacity=max(64, 2 * n_req))

    router = ReplicaRouter(factory, n_replicas=2,
                           stall_deadline_s=30.0)
    try:
        _log("serving_fleet: warmup (compiles both replicas' prefill/"
             "decode/verify programs)")
        with _beacon("serving_fleet", "compile/warmup"):
            rs = np.random.RandomState(0)
            warm = rs.randint(1, vocab, (P,)).astype("int64")
            t0 = time.perf_counter()
            router.submit(warm, n_new,
                          prefix_len=prefix_len).result(timeout=600)
            per_req = time.perf_counter() - t0
            router.submit(warm, n_new,
                          prefix_len=prefix_len).result(timeout=600)
        mean_gap = max(per_req / (2 * b_max), 1e-4)
        _log("serving_fleet: open-loop drive (%d requests, 80%% shared "
             "%d-token prefix)" % (n_req, prefix_len))
        stats = drive(router, n_req, mean_gap, seed=1, vocab=vocab,
                      prompt_len=P, n_new=n_new, prefix_share=0.8,
                      prefix_len=prefix_len)
        SERVING_TOKENS_PER_SEC.set(stats["tokens_per_sec"])
        if stats["spec_accept_rate"] is not None:
            SERVING_SPEC_ACCEPT_RATE.set(stats["spec_accept_rate"])
        # drive() already measured completion-time percentiles: ride
        # them in through extra (update runs before the row prints)
        return _serving_row(
            "serving_fleet_tokens_per_sec", stats["tokens_per_sec"],
            "tokens/sec", [],
            {"fleet": True, "replicas": 2, "b_max": b_max,
             "requests": n_req, "n_new": n_new,
             **({"quick": True} if quick else {}),
             # per-replica resident bytes (replicas share the model
             # shape, so one replica's number describes each)
             "peak_bytes_predicted":
                 router.replicas[0].engine.predicted_resident_bytes(),
             "prefix_share": 0.8,
             "p50_ms": (None if stats["p50_ms"] is None
                        else round(stats["p50_ms"], 2)),
             "p99_ms": (None if stats["p99_ms"] is None
                        else round(stats["p99_ms"], 2)),
             "prefix_hit_rate": (None if stats["prefix_hit_rate"] is None
                                 else round(stats["prefix_hit_rate"], 3)),
             "spec_accept_rate": (None if stats["spec_accept_rate"] is None
                                  else round(stats["spec_accept_rate"],
                                             3)),
             "outcomes": stats["outcomes"]})
    finally:
        router.close()


def bench_elastic(amp, quick, uses_flash=False):
    """Elastic-training chaos row: an N-trainer local PS job loses one
    trainer mid-epoch (FaultPlan crash on its heartbeat site), the
    supervisor evicts it and reshards deterministically from the latest
    manifest, and the job still completes. The row reports end-to-end
    steps/sec THROUGH the failure plus the reshard cost — the number
    that says what a lost trainer costs in wall time, not just that
    recovery happened. Workers always run on CPU subprocesses (N
    processes cannot share one TPU), so the row is marked "elastic"
    and platform cpu: pin_baselines never compares it with training
    baselines."""
    import tempfile

    from paddle_tpu.resilience.elastic import ElasticJobSupervisor

    trainers = 2 if quick else 3
    steps = 6 if quick else 12
    kill_step = 3 if quick else 5
    workdir = tempfile.mkdtemp(prefix="bench_elastic_")
    _log("elastic: %d trainers, %d steps, kill trainer 1 at step %d"
         % (trainers, steps, kill_step))
    sup = ElasticJobSupervisor(
        workdir, trainers=trainers, steps_per_epoch=steps,
        checkpoint_every=2, lease_s=30.0,
        worker_env={1: {"PADDLE_TPU_FAULT_PLAN":
                        "trainer.heartbeat@%d:crash" % (kill_step + 1)}})
    t0 = time.perf_counter()
    with _beacon("elastic", "chaos job"):
        res = sup.run(timeout_s=420.0)
    wall = time.perf_counter() - t0
    if not res.completed:
        # keep the workdir: logs/, timeline.jsonl and telemetry/ are
        # exactly the forensics a failed chaos row needs
        raise RuntimeError("elastic chaos job failed: %r (artifacts "
                           "kept in %s)" % (res, workdir))
    import shutil

    shutil.rmtree(workdir, ignore_errors=True)
    rec = {
        "metric": "elastic_chaos_steps_per_sec",
        "platform": "cpu",  # worker subprocesses are CPU by design
        "elastic": True,
        "value": round(res.final_step / wall, 3),
        "unit": "steps/sec",
        "vs_baseline": 1.0,
        "tflops_per_sec": None,
        "mfu": None,
        # null, never 0.0: the demo programs live in worker
        # subprocesses — this process has nothing to analyze
        "peak_bytes_predicted": None,
        # elastic workers drive resilient_train_loop at its default
        # per-step dispatch (recorded like every train row)
        "steps_per_call": 1,
        "trainers": trainers,
        "steps": steps,
        "generations": res.generations,
        "evictions": res.evictions,
        "reshard_seconds": round(sum(r.get("seconds", 0.0)
                                     for r in res.reshards), 3),
        "wall_seconds": round(wall, 1),
        **({"quick": True} if quick else {}),
    }
    print(json.dumps(rec), flush=True)
    return rec


def bench_quantized(amp, quick, uses_flash=False):
    """Int8 PTQ rows (docs/OPTIMIZER.md "Post-training int8
    quantization"): for each of three model-zoo INFERENCE programs
    (forward-only, startup-initialized weights), measure steady-state
    steps/sec with the quantize pass opted in
    (PADDLE_TPU_OPTIMIZE_QUANT=1) and the accuracy delta vs the same
    program's unquantized run on identical feeds. Rows carry
    quantized:"int8" + accuracy_delta NEXT TO optimize_level —
    pin_baselines treats quantized rows as incomparable with the
    plain-config baselines (a different program compiled)."""
    import sys as _sys

    _sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import lint_program as _lint_cli

    import jax as _jax
    import paddle_tpu as fluid
    from paddle_tpu import observe as _observe
    from paddle_tpu.core.scope import Scope, scope_guard

    steps, warmup = (2, 1) if quick else (10, 3)
    batch = 2 if quick else 8
    models = ("mnist", "gpt", "resnet")
    rng = np.random.RandomState(0)

    def _feed_for(main):
        feed = {}
        for var in main.global_block().vars.values():
            if not var.is_data:
                continue
            shape = [batch if (s is None or s < 0) else int(s)
                     for s in (var.shape or [batch])]
            if var.dtype.startswith(("int", "uint")):
                # ids/labels: {0,1} is in-vocab for every zoo model
                # (bert's type_vocab=2 is the smallest table)
                feed[var.name] = rng.randint(0, 2, shape).astype("int64")
            else:
                feed[var.name] = rng.uniform(
                    -1, 1, shape).astype("float32")
        return feed

    def _quant_weight_count():
        fam = _observe.snapshot()["metrics"].get(
            "paddle_quant_weights_quantized_total", {})
        return sum(s["value"] for s in fam.get("samples", []))

    recs = []
    for model in models:
        with _beacon("quantized", model):
            main, startup, loss = _lint_cli.build_example(
                model, optimizer=False)
            scope = Scope()
            feed = _feed_for(main)
            with scope_guard(scope):
                exe = fluid.Executor(fluid.TPUPlace())
                exe.run(startup, scope=scope)
                _log("quantized/%s: unquantized reference run" % model)
                base, = exe.run(main, feed=feed, fetch_list=[loss],
                                scope=scope)
                base = np.asarray(base)
                before = _quant_weight_count()
                old = os.environ.get("PADDLE_TPU_OPTIMIZE_QUANT")
                os.environ["PADDLE_TPU_OPTIMIZE_QUANT"] = "1"
                try:
                    qexe = fluid.Executor(fluid.TPUPlace())
                    _log("quantized/%s: compiling + %d warmup steps"
                         % (model, warmup))
                    for _ in range(warmup):
                        qv, = qexe.run(main, feed=feed,
                                       fetch_list=[loss], scope=scope)
                    t0 = time.perf_counter()
                    for _ in range(steps):
                        qv, = qexe.run(main, feed=feed,
                                       fetch_list=[loss], scope=scope)
                    float(np.asarray(qv).reshape(-1)[0])  # block
                    dt = time.perf_counter() - t0
                    # inside the env window: the XLA number must come
                    # from the QUANTIZED plan (the config-keyed cache
                    # would re-prepare unquantized once the env resets)
                    peak_fields = _peak_bytes_fields(
                        main, feed, [loss], scope=scope, exe=qexe)
                finally:
                    if old is None:
                        os.environ.pop("PADDLE_TPU_OPTIMIZE_QUANT", None)
                    else:
                        os.environ["PADDLE_TPU_OPTIMIZE_QUANT"] = old
            qv = np.asarray(qv)
            delta = float(np.max(np.abs(qv.astype(np.float64)
                                        - base.astype(np.float64)))) \
                if qv.shape == base.shape else None
            n_weights = int(_quant_weight_count() - before)
            rec = {
                "metric": "quantized_%s" % model,
                "platform": _jax.devices()[0].platform.lower(),
                # the mode marker pin_baselines keys the skip on: a
                # quantized row compiled a DIFFERENT program than the
                # plain-config baseline
                "quantized": "int8",
                # metric delta vs the unquantized run on the same feeds
                # (max |diff| of the fetched metric; the stated pass
                # tolerance is the contract it must stay within)
                "accuracy_delta": delta,
                # always explicit next to the quantized marker, even at
                # the default level (the pass is level 2)
                "optimize_level": _optimize_level(),
                "weights_quantized": n_weights,
                "value": round(steps / dt, 1),
                "unit": "steps/sec",
                "steps_per_call": 1,
                "vs_baseline": 1.0,
                "tflops_per_sec": None,
                "mfu": None,
                # source-program static estimate next to the compiled
                # QUANTIZED plan's XLA number (captured inside the env
                # window above): the memory payoff of PTQ
                **peak_fields,
                **({"quick": True} if quick else {}),
            }
            print(json.dumps(rec), flush=True)
            recs.append(rec)
    return recs


def bench_dygraph(amp, quick, uses_flash=False):
    """Dygraph capture rows (docs/IMPERATIVE.md): ONE eager MLP train
    step (FC+dropout+FC, square loss, Adam) measured twice — op-by-op
    eager dispatch, then replayed through the Program that
    ``imperative.jit`` captured from it (``exact_numerics=False``: the
    whole-graph-compiled fast path; the bitwise default trades that
    fusion away and is pinned by tests, not benchmarked). Two rows,
    both marked "dygraph"; the replay row additionally ``captured:true``
    with the eager-relative speedup — pin_baselines never compares
    either with graph training baselines."""
    import jax as _jax

    from paddle_tpu import imperative
    from paddle_tpu.imperative import nn as inn
    from paddle_tpu.imperative import optimizer as iopt
    from paddle_tpu.imperative import trace_op

    steps = 10 if quick else 60
    warmup = 3 if quick else 10
    batch, width = (8, 32) if quick else (32, 64)
    rs = np.random.RandomState(0)
    X = rs.rand(batch, width).astype("float32")
    Y = rs.rand(batch, 1).astype("float32")

    def run_mode(captured):
        # parameter init draws GLOBAL numpy RNG — reseed so both modes
        # start from identical weights and the rate gap is pure dispatch
        np.random.seed(0)
        with imperative.guard(seed=0):
            fc1 = inn.FC("fc1", width, act="relu")
            fc2 = inn.FC("fc2", 1)
            adam = iopt.Adam(learning_rate=1e-3)

            def step(x, y):
                h = trace_op("dropout", {"X": [fc1(x)]},
                             {"dropout_prob": 0.2, "is_test": False})["Out"][0]
                d = trace_op("elementwise_sub",
                             {"X": [fc2(h)], "Y": [y]}, {})["Out"][0]
                sq = trace_op("square", {"X": [d]}, {})["Out"][0]
                loss = trace_op("reduce_mean", {"X": [sq]}, {})["Out"][0]
                loss.backward()
                adam.step(fc1.parameters() + fc2.parameters())
                return loss

            fn = imperative.jit(step, exact_numerics=False,
                                name="bench_dygraph") if captured else step
            vx = imperative.to_variable(X)
            vy = imperative.to_variable(Y)
            vx.stop_gradient = True
            vy.stop_gradient = True
            for _ in range(warmup):
                fn(vx, vy)
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = fn(vx, vy)
            float(np.asarray(loss.numpy()).reshape(-1)[0])  # block
            dt = time.perf_counter() - t0
            entry = fn._last_entry if captured else None
        return steps / dt, entry

    recs = []
    with _beacon("dygraph", "eager steps"):
        _log("dygraph: %d eager steps (batch %d, width %d)"
             % (steps, batch, width))
        eager_rate, _ = run_mode(False)
    with _beacon("dygraph", "capture + replay"):
        _log("dygraph: capture + %d replayed steps" % steps)
        cap_rate, entry = run_mode(True)
    platform = _jax.devices()[0].platform.lower()
    common = {
        "platform": platform,
        # the mode marker pin_baselines keys the skip on: dygraph rows
        # measure dispatch overhead, not a training baseline
        "dygraph": True,
        "unit": "steps/sec",
        "steps_per_call": 1,
        "vs_baseline": 1.0,
        "tflops_per_sec": None,
        "mfu": None,
        **({"quick": True} if quick else {}),
    }
    rec = {
        "metric": "dygraph_eager",
        "value": round(eager_rate, 1),
        # eager dispatch never builds a Program — nothing to analyze
        "peak_bytes_predicted": None,
        **common,
    }
    print(json.dumps(rec), flush=True)
    recs.append(rec)
    rec = {
        "metric": "dygraph_captured",
        "captured": True,
        "value": round(cap_rate, 1),
        # the replay-vs-eager ratio is the row's headline: what trace
        # capture buys over op-by-op dispatch on this workload
        "speedup_vs_eager": round(cap_rate / eager_rate, 2),
        "peak_bytes_predicted": (int(entry.predicted_bytes)
                                 if entry is not None
                                 and entry.predicted_bytes else None),
        **common,
    }
    print(json.dumps(rec), flush=True)
    recs.append(rec)
    return recs


def bench_artifact(amp, quick, uses_flash=False):
    """Deployable-artifact cold-start rows (docs/DEPLOYMENT.md): for
    each of three model-zoo INFERENCE programs, measure
    cold-start-to-first-token twice — from scratch (fresh Executor:
    verify + optimize + analyze + XLA compile + first batch) and from a
    frozen artifact (load_artifact + seeded predictor + first batch;
    with a live AOT section the first token never touches XLA
    lowering). Rows carry artifact:true + from_scratch_s +
    speedup_vs_scratch — pin_baselines treats them as incomparable
    with the training baselines (a load path, not a training
    config)."""
    import sys as _sys

    _sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import tempfile

    import lint_program as _lint_cli

    import jax as _jax
    import paddle_tpu as fluid
    from paddle_tpu import export as _export
    from paddle_tpu.core.scope import Scope, scope_guard

    batch = 2 if quick else 8
    models = ("mnist",) if quick else ("mnist", "ctr", "stacked_lstm")
    rng = np.random.RandomState(0)

    def _feed_for(main):
        feed = {}
        for var in main.global_block().vars.values():
            if not var.is_data:
                continue
            shape = [batch if (s is None or s < 0) else int(s)
                     for s in (var.shape or [batch])]
            if var.dtype.startswith(("int", "uint")):
                feed[var.name] = rng.randint(0, 2, shape).astype("int64")
            else:
                feed[var.name] = rng.uniform(
                    -1, 1, shape).astype("float32")
        return feed

    recs = []
    for model in models:
        with _beacon("artifact", model):
            main, startup, loss = _lint_cli.build_example(
                model, optimizer=False)
            scope = Scope()
            feed = _feed_for(main)
            feed_names = sorted(feed)
            with scope_guard(scope):
                exe0 = fluid.Executor(fluid.TPUPlace())
                exe0.run(startup, scope=scope)
                # from-scratch cold start: a fresh Executor pays the
                # whole prepare pipeline + XLA compile for this first
                # batch (plan caches are per-Executor)
                _log("artifact/%s: from-scratch cold start" % model)
                t0 = time.perf_counter()
                exe = fluid.Executor(fluid.TPUPlace())
                ref, = exe.run(main, feed=feed, fetch_list=[loss],
                               scope=scope)
                ref = np.asarray(ref)
                dt_scratch = time.perf_counter() - t0
                # freeze ONCE (the expensive half; deliberately outside
                # both timed windows — deployment pays it at build time)
                path = os.path.join(tempfile.mkdtemp(prefix="pt_art_"),
                                    "%s.pdz" % model)
                _log("artifact/%s: save_artifact" % model)
                _export.save_artifact(
                    main, path, feed_names=feed_names,
                    fetch_names=[loss.name], scope=scope,
                    batch_sizes=(batch,), name=model)
            # artifact cold start: validate + rehydrate + seeded first
            # batch — the serving process's actual startup path
            _log("artifact/%s: artifact cold start" % model)
            t0 = time.perf_counter()
            art = _export.load_artifact(path)
            pred = art.predictor()
            out = np.asarray(pred.run(feed)[0])
            dt_art = time.perf_counter() - t0
            rec = {
                "metric": "artifact_%s" % model,
                "platform": _jax.devices()[0].platform.lower(),
                # the mode marker pin_baselines keys the skip on:
                # cold-start seconds, not a training throughput
                "artifact": True,
                "value": round(dt_art, 3),
                "unit": "cold_start_seconds",
                "from_scratch_s": round(dt_scratch, 3),
                "speedup_vs_scratch": round(dt_scratch / dt_art, 2)
                if dt_art > 0 else None,
                "aot": sorted(art.aot) or None,
                "tuned_imported": art.tuned_imported,
                "bitwise_vs_scratch": bool(np.array_equal(ref, out)),
                "peak_bytes_predicted": art.predicted_bytes(batch),
                "steps_per_call": 1,
                "vs_baseline": 1.0,
                "tflops_per_sec": None,
                "mfu": None,
                **({"quick": True} if quick else {}),
            }
            print(json.dumps(rec), flush=True)
            recs.append(rec)
    return recs


WORKLOADS = {
    "transformer": bench_transformer,
    "transformer_long": bench_transformer_long,
    "resnet50": bench_resnet50,
    "vgg16": bench_vgg16,
    "bert": bench_bert,
    "deepfm": bench_deepfm,
    "deepfm_dist": bench_deepfm_dist,
    "gpt_causal": bench_gpt_causal,
}

# PADDLE_TPU_BENCH_SERVING=1 swaps the workload list for the serving
# schedulers (docs/SERVING.md): open-loop load through the
# micro-batched Predictor and the continuous-batching decode engine.
# Rows are marked "serving" and never pin as training baselines.
SERVING_ORDER = ["serving_predictor", "serving_decode", "serving_fleet"]
SERVING_WORKLOADS = {
    "serving_predictor": bench_serving_predictor,
    "serving_decode": bench_serving_decode,
    "serving_fleet": bench_serving_fleet,
}
WORKLOADS.update(SERVING_WORKLOADS)


# PADDLE_TPU_BENCH_ELASTIC=1 swaps the workload list for the elastic
# chaos workload (docs/RESILIENCE.md "Elastic jobs"). Rows are marked
# "elastic" and never pin as training baselines.
ELASTIC_ORDER = ["elastic"]
ELASTIC_WORKLOADS = {"elastic": bench_elastic}
WORKLOADS.update(ELASTIC_WORKLOADS)

# PADDLE_TPU_BENCH_QUANT=1 swaps the workload list for the int8 PTQ
# rows (docs/OPTIMIZER.md). Rows are marked quantized:"int8" and never
# pin as training baselines.
QUANT_ORDER = ["quantized"]
QUANT_WORKLOADS = {"quantized": bench_quantized}
WORKLOADS.update(QUANT_WORKLOADS)

# PADDLE_TPU_BENCH_DYGRAPH=1 swaps the workload list for the dygraph
# capture rows (docs/IMPERATIVE.md): eager vs captured-replay steps/sec.
# Rows are marked "dygraph" (replay also captured:true) and never pin
# as training baselines.
DYGRAPH_ORDER = ["dygraph"]
DYGRAPH_WORKLOADS = {"dygraph": bench_dygraph}
WORKLOADS.update(DYGRAPH_WORKLOADS)

# PADDLE_TPU_BENCH_ARTIFACT=1 swaps the workload list for the deployable
# artifact cold-start rows (docs/DEPLOYMENT.md): time-to-first-token
# from an artifact load vs building the same serving path from scratch.
# Rows are marked "artifact" and never pin as training baselines.
ARTIFACT_ORDER = ["artifact"]
ARTIFACT_WORKLOADS = {"artifact": bench_artifact}
WORKLOADS.update(ARTIFACT_WORKLOADS)


def _serving_mode():
    return os.environ.get("PADDLE_TPU_BENCH_SERVING", "0") != "0"


def _elastic_mode():
    return os.environ.get("PADDLE_TPU_BENCH_ELASTIC", "0") != "0"


def _quant_mode():
    return os.environ.get("PADDLE_TPU_BENCH_QUANT", "0") != "0"


def _dygraph_mode():
    return os.environ.get("PADDLE_TPU_BENCH_DYGRAPH", "0") != "0"


def _artifact_mode():
    return os.environ.get("PADDLE_TPU_BENCH_ARTIFACT", "0") != "0"

# Safe (no custom-kernel) workloads first: if a Pallas compile hangs
# partway through, the rows already printed stand.
# deepfm_dist LAST: it spawns localhost pserver subprocesses, so a
# half-cleaned failure can't disturb the single-process rows.
ORDER = ["resnet50", "vgg16", "deepfm", "transformer", "bert",
         "transformer_long", "gpt_causal", "deepfm_dist"]

# Workloads with fused_attention ops in the graph, with their sequence
# length. Whether the Pallas kernel ACTUALLY runs is flash_effective(S):
# below PADDLE_TPU_FLASH_MIN_SEQ the op lowers to the composed XLA math,
# and the row's attention_path records which one was measured.
ATTENTION_SEQ = {"transformer": 128, "transformer_long": 1024,
                 "bert": 128, "gpt_causal": 1024}
ATTENTION_WORKLOADS = frozenset(ATTENTION_SEQ)

assert set(ORDER) | set(SERVING_ORDER) | set(ELASTIC_ORDER) \
    | set(QUANT_ORDER) | set(DYGRAPH_ORDER) | set(ARTIFACT_ORDER) \
    == set(WORKLOADS), \
    "ORDER/SERVING_ORDER/ELASTIC_ORDER/QUANT_ORDER/DYGRAPH_ORDER/" \
    "ARTIFACT_ORDER out of sync with WORKLOADS"


def _probe_backend(timeout_s=None, attempts=None, probe_fn=None):
    """Fail fast (with a diagnosable JSON row AND a telemetry sidecar) if
    jax backend init fails or hangs — a hung init blocks inside a C call
    that no KeyboardInterrupt reaches, so a deadline-bounded daemon
    thread (resilience.watchdog.run_with_deadline) + os._exit is the
    only way out. On a sealed machine a backend that does not
    initialise is an ERROR, not weather to wait out.

    The probe still retries (kept for the first benchmark PR to
    collapse, ROADMAP.md Queue 3 item 1): up to
    ``PADDLE_TPU_BENCH_INIT_ATTEMPTS`` (default 3) attempts run with
    full-jitter backoff between them
    (``PADDLE_TPU_BENCH_INIT_BACKOFF_MS`` base, doubling, capped 30s)
    before the run is declared dead. Every attempt's wall time lands
    in the ``paddle_backend_probe_attempt_seconds`` histogram and its
    outcome in ``paddle_backend_probe_attempts_total{outcome}``, so a
    post-mortem distinguishes "hung 300s, hung 300s, ok in 4s"
    from "failed instantly with a config error". Worst-case wall is
    ``attempts * timeout`` + backoff — the parent's subprocess guard
    budgets for that."""
    from paddle_tpu.observe.families import (BACKEND_PROBE_ATTEMPT_SECONDS,
                                             BACKEND_PROBE_ATTEMPTS,
                                             BACKEND_PROBE_OK,
                                             BACKEND_PROBE_SECONDS,
                                             RESILIENCE_WEDGES)
    from paddle_tpu.resilience.backoff import backoff_delay, millis_env
    from paddle_tpu.resilience.watchdog import run_with_deadline

    timeout_s = timeout_s or int(
        os.environ.get("PADDLE_TPU_BENCH_INIT_TIMEOUT", "300"))
    attempts = max(1, attempts or int(
        os.environ.get("PADDLE_TPU_BENCH_INIT_ATTEMPTS", "3")))
    if probe_fn is None:
        def probe_fn():
            import jax

            return str(jax.devices())

    base_s = millis_env("PADDLE_TPU_BENCH_INIT_BACKOFF_MS", 2000)
    detail = ""
    for attempt in range(attempts):
        ok, val, dt = run_with_deadline(probe_fn, timeout_s)
        BACKEND_PROBE_SECONDS.set(dt)
        BACKEND_PROBE_ATTEMPT_SECONDS.observe(dt)
        if ok:
            BACKEND_PROBE_ATTEMPTS.labels(outcome="ok").inc()
            BACKEND_PROBE_OK.set(1)
            return
        hung = isinstance(val, TimeoutError)
        BACKEND_PROBE_ATTEMPTS.labels(
            outcome="timeout" if hung else "error").inc()
        if hung:
            RESILIENCE_WEDGES.labels(site="backend.probe").inc()
            detail = "did not complete within %ds" % timeout_s
        else:
            detail = ("%s: %s" % (type(val).__name__, val))[:300]
        if attempt + 1 < attempts:
            delay = backoff_delay(attempt, base_s, 30.0)
            _log("backend probe attempt %d/%d failed (%s); retrying in "
                 "%.1fs" % (attempt + 1, attempts, detail, delay))
            time.sleep(delay)
    BACKEND_PROBE_OK.set(0)
    print(json.dumps({
        "metric": "backend_init",
        "error": "jax backend init failed after %d attempts: %s"
                 % (attempts, detail),
    }), flush=True)
    _dump_telemetry("probe")
    os._exit(1)


def _fit_probe_attempts(budget_s, timeout_s, attempts):
    """Probe attempts that FIT inside ``budget_s``: each attempt costs
    up to ``timeout_s`` plus a capped-30s backoff, and 60s of slack is
    reserved for the worker's own startup/teardown. A worker whose
    probe retries outlived its workload deadline would be SIGKILLed
    mid-probe — losing the diagnosable backend_init row and sidecar
    the probe exists to write."""
    fit = max(1, int((budget_s - 60) // (timeout_s + 30)))
    return max(1, min(attempts, fit))


def _run_worker(name, amp, quick):
    """In-process single-workload run (the ``--worker`` entry)."""
    from paddle_tpu.flags import enable_compile_cache

    # workers share one persistent compile cache (JAX_COMPILATION_CACHE_DIR
    # or <checkout>/.jax_cache): a second run of a row replays its compile
    enable_compile_cache()
    # in-worker probe retries must fit the parent's per-workload
    # deadline (the default 3 x 300s budget would outlive the 900s
    # workload timeout and get this worker killed mid-probe)
    _probe_backend(attempts=_fit_probe_attempts(
        int(os.environ.get("PADDLE_TPU_BENCH_WORKLOAD_TIMEOUT", "900")),
        int(os.environ.get("PADDLE_TPU_BENCH_INIT_TIMEOUT", "300")),
        int(os.environ.get("PADDLE_TPU_BENCH_INIT_ATTEMPTS", "3"))))
    from paddle_tpu.observe.families import BENCH_ROWS

    try:
        # single source of truth for "this row exercises the flash
        # kernel": the ATTENTION_WORKLOADS set + the fused-attention
        # env knob — per-call-site kwargs would drift (and default off)
        # — AND the short-S dispatch (flash_effective): a fused op that
        # lowers to composed math must not be labeled a kernel row
        fused = name in ATTENTION_WORKLOADS and _fused_attention_on()
        uses_flash = fused
        if fused:
            from paddle_tpu.ops.attention import (flash_effective,
                                                  pallas_mode)

            uses_flash = flash_effective(ATTENTION_SEQ[name])
            if uses_flash:
                _log("%s: flash-attention pallas mode = %s"
                     % (name, pallas_mode()))
            else:
                _log("%s: S=%d below flash_min_seq — fused op dispatches "
                     "to the composed XLA path"
                     % (name, ATTENTION_SEQ[name]))
        WORKLOADS[name](amp, quick, uses_flash=uses_flash)
        BENCH_ROWS.labels(status="ok").inc()
        return 0
    except Exception as exc:  # noqa: BLE001
        import traceback

        BENCH_ROWS.labels(status="error").inc()
        tb = traceback.format_exc().strip().splitlines()
        print(json.dumps({
            "metric": name,
            "error": f"{type(exc).__name__}: {exc}"[:400],
            "traceback_tail": " | ".join(tb[-3:])[:400],
        }), flush=True)
        return 1
    finally:
        # the sidecar rides along even when the row failed: it holds the
        # executor cache state, RPC attempt counters and probe timings a
        # post-mortem needs
        _dump_telemetry(name)


def _spawn_workload(name, args, timeout_s):
    """Run one workload in a killable subprocess; relay its JSON rows.

    Returns True iff the child exited 0 and printed at least one
    non-error row. A deadline overrun kills the whole process
    group (a call blocked in C shrugs off SIGTERM delivered to Python)
    and synthesizes an error row.
    """
    cmd = [sys.executable, "-u", os.path.abspath(__file__),
           "--worker", name]
    if args.fp32:
        cmd.append("--fp32")
    if args.quick:
        cmd.append("--quick")
    _log("spawn %s (timeout %ds)" % (name, timeout_s))
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=sys.stderr,
                            start_new_session=True, text=True)
    rows = []
    import signal
    import threading

    def _relay():
        for line in proc.stdout:  # EOF terminates the thread
            line = line.strip()
            if not line:
                continue
            print(line, flush=True)  # relay verbatim
            try:
                parsed = json.loads(line)
            except ValueError:
                continue
            if isinstance(parsed, dict):  # stray scalar prints aren't rows
                rows.append(parsed)

    reader = threading.Thread(target=_relay, daemon=True)
    reader.start()
    timed_out = False
    try:
        proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        timed_out = True
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            pass
        proc.wait()
    reader.join(timeout=10)
    if timed_out:
        print(json.dumps({
            "metric": name,
            "error": "workload exceeded %ds deadline (hung compile or "
                     "hung device); subprocess killed" % timeout_s,
        }), flush=True)
        return False
    ok = proc.returncode == 0 and any("error" not in r for r in rows)
    if not ok and not any("error" in r for r in rows):
        # child died without printing anything (segfault, OOM kill):
        # the metric must not silently vanish from the output
        print(json.dumps({
            "metric": name,
            "error": "worker exited rc=%s with no result row"
                     % proc.returncode}), flush=True)
    return ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=sorted(WORKLOADS), default=None,
                    help="run a single workload")
    ap.add_argument("--fp32", action="store_true", help="disable bf16 AMP")
    ap.add_argument("--quick", action="store_true",
                    help="tiny batches (smoke test)")
    ap.add_argument("--worker", choices=sorted(WORKLOADS), default=None,
                    help=argparse.SUPPRESS)  # internal: in-process child
    ap.add_argument("--probe", action="store_true",
                    help=argparse.SUPPRESS)  # internal: backend-init check
    ap.add_argument("--in-process", action="store_true",
                    help="no subprocess isolation (debugging)")
    ap.add_argument("--dist-ctr-pserver", action="store_true",
                    help=argparse.SUPPRESS)  # internal: CPU pserver child
    args = ap.parse_args()

    if args.dist_ctr_pserver:
        return _run_dist_ctr_pserver()

    if args.probe:
        _probe_backend()
        import jax

        _log("probe ok: %s" % jax.devices())
        _dump_telemetry("probe")
        return 0

    # PADDLE_TPU_BENCH_SERVING=1 / PADDLE_TPU_BENCH_ELASTIC=1 /
    # PADDLE_TPU_BENCH_QUANT=1 / PADDLE_TPU_BENCH_DYGRAPH=1 swap the
    # default workload list; --only still picks any single workload
    default_order = (ARTIFACT_ORDER if _artifact_mode()
                     else DYGRAPH_ORDER if _dygraph_mode()
                     else QUANT_ORDER if _quant_mode()
                     else ELASTIC_ORDER if _elastic_mode()
                     else SERVING_ORDER if _serving_mode() else ORDER)
    if args.worker:
        return _run_worker(args.worker, not args.fp32, args.quick)
    if args.in_process:
        names = [args.only] if args.only else default_order
        failed = sum(
            _run_worker(name, not args.fp32, args.quick) != 0
            for name in names)
        return 1 if failed else 0  # same contract as the default path

    names = [args.only] if args.only else default_order
    per_workload = int(os.environ.get(
        "PADDLE_TPU_BENCH_WORKLOAD_TIMEOUT", "900"))
    budget = int(os.environ.get("PADDLE_TPU_BENCH_TOTAL_BUDGET", "7200"))
    t_start = time.time()

    # fail fast on a backend that does not initialise: one subprocess
    # probe up front instead of 6 workers independently burning the
    # init timeout each. In a child, so that this parent never imports
    # jax and never holds the chip its workers need.
    init_timeout = int(os.environ.get("PADDLE_TPU_BENCH_INIT_TIMEOUT", "300"))
    init_attempts = max(1, int(os.environ.get(
        "PADDLE_TPU_BENCH_INIT_ATTEMPTS", "3")))
    import signal as _signal

    probe = subprocess.Popen(
        [sys.executable, "-u", os.path.abspath(__file__), "--probe"],
        stdout=subprocess.DEVNULL, stderr=sys.stderr,
        start_new_session=True)
    try:
        # budget for the probe's own retries: attempts x per-attempt
        # timeout, plus its (capped-30s) backoff sleeps and startup slack
        probe_rc = probe.wait(
            timeout=init_attempts * (init_timeout + 30) + 60)
    except subprocess.TimeoutExpired:
        probe_rc = -1
        try:
            os.killpg(probe.pid, _signal.SIGKILL)
        except OSError:
            pass
        probe.wait()
    if probe_rc != 0:
        for name in names:
            print(json.dumps({
                "metric": name,
                "error": "backend init probe failed (rc=%s): jax found "
                         "no usable backend; no workloads attempted"
                         % probe_rc,
            }), flush=True)
        return 1
    failed = 0
    for name in names:
        left = budget - (time.time() - t_start)
        if left < 60:
            print(json.dumps({
                "metric": name,
                "error": "total bench budget (%ds) exhausted before this "
                         "workload ran" % budget,
            }), flush=True)
            failed += 1
            continue
        if not _spawn_workload(name, args, min(per_workload, int(left))):
            failed += 1
    # every requested row must have succeeded: a failed row is never
    # re-run on another path, and never hidden behind one that passed
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
