"""Executor: compile-and-run a Program block as one XLA computation.

Analog of /root/reference/paddle/fluid/framework/executor.cc:191 (Run),
:362 (Prepare, here = trace+jit with a cache), :411 (RunPreparedContext,
here = calling the compiled step). The reference interprets ops one-by-one
and syncs the device stream each run (executor.cc:461); here the entire
block becomes a single jitted function:

    inputs  = feed vars + persistable state read from the Scope
    outputs = fetch vars + persistable state written by ops + PRNG key

so a whole train step (forward + backward + optimizer update) is one XLA
executable with donated state buffers — the TPU-idiomatic replacement for
per-op dispatch, implicit data transform, and the eager-deletion GC.
"""

from __future__ import annotations

import contextlib
import functools
import os
import time
import warnings
import zlib
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .lowering import LowerContext, as_jax_dtype, lower_block
from .passes import optimize_for_execution
from .passes import config_key as _optimizer_config_key
from .program import Program, Variable, default_main_program, op_effects
from .registry import get_op, has_op
from .scope import Scope, global_scope
# hoisted out of the per-step guards: resilience's module-level imports
# never touch core (no cycle), and the dispatch window must carry no
# avoidable bytecode on the 2-core throttled CI box
from ..observe import trace as _tr
from ..resilience.faults import fault_point
from ..resilience.watchdog import heartbeat

__all__ = ["Executor"]

RNG_VAR = "@RNG_STATE@"


class _Plan:
    """Prepared context for one (program, feed-signature) pair — the analog
    of the reference's ExecutorPrepareContext (executor.cc:362)."""

    def __init__(self, feed_names, fetch_names, const_state, mut_state,
                 pure_written, needs_rng, fn, step=None):
        self.feed_names = feed_names
        self.fetch_names = fetch_names
        self.const_state = const_state      # read-only scope vars
        self.mut_state = mut_state          # read+written scope vars (donated)
        self.pure_written = pure_written    # written-only persistables
        self.needs_rng = needs_rng
        self.fn = fn
        self.step = step   # the raw (unjitted) step — run_repeated wraps
        #                    it in a device-side lax.scan
        self.multi = {}    # (steps, feed_stacked) -> jitted K-step
        #                    executable
        self.cost = None  # cost_analysis() result, filled on first request
        self.exact = False  # exact_numerics program: fn is the UNJITTED
        #                    step (per-primitive dispatch, bitwise the
        #                    eager sequence) and K-step variants use a
        #                    Python loop instead of a compiled lax.scan
        self.hlo_text = {}  # stage -> lowered_hlo() text (AOT compiles
        #                     can't reuse the jit cache; amortize them)
        self.compiled_sigs = set()  # dispatch signatures dispatched
        #                    before: what the heartbeat must guess BEFORE
        #                    the call (a first dispatch compiles: the
        #                    watchdog's longer grace). What a dispatch did
        #                    load is the listener's to say (_dispatch_guard)
        self.loads = {}    # dispatch signature -> dispatches of it in
        #                    which JAX ran a backend stage (XLA compile or
        #                    cache load): 2 = the program loaded again
        self.load_args = {}  # dispatch signature -> how its arguments sat
        #                    (committed, sharding) at its last load: what a
        #                    second load is compared with
        self.sig = None   # short hex of the plan-cache key — stamped on
        #                    every dispatch/complete trace span so per-op
        #                    cost attribution falls out of a trace dump


class Executor:
    """User-facing executor (python/paddle/fluid/executor.py:262 analog).

    ``cache_size`` caps the plan cache (LRU): each cached plan pins a
    jitted executable (and, via ``plan.multi``, its K-step scan
    variants), so a shape-churning workload must not hold every stale
    executable alive. Default from ``PADDLE_TPU_EXECUTOR_CACHE_SIZE``
    (32); evictions count into
    ``paddle_executor_plan_cache_evictions_total``.
    """

    def __init__(self, place=None, cache_size: Optional[int] = None):
        self.place = place
        if cache_size is None:
            cache_size = int(os.environ.get(
                "PADDLE_TPU_EXECUTOR_CACHE_SIZE", "32"))
        if cache_size < 1:
            raise ValueError("cache_size must be >= 1, got %d" % cache_size)
        self._cache_size = cache_size
        self._cache: "OrderedDict[Tuple, _Plan]" = OrderedDict()
        _tr.watch_program_loads()

    # ------------------------------------------------------------------ run
    def run(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
    ):
        # CompiledProgram (data-parallel engine) delegates to its own runner
        from ..compiler import CompiledProgram

        if isinstance(program, CompiledProgram):
            return program._run(self, feed, fetch_list, scope, return_numpy)

        program = program if program is not None else default_main_program()
        scope = scope if scope is not None else global_scope()

        # a pserver program is one listen_and_serv op: enter the PS loop
        # (the reference enters ListenAndServOp::RunImpl the same way)
        ops0 = program.global_block().ops
        if ops0 and ops0[0].type == "listen_and_serv":
            from ..distributed.ps import run_pserver_loop

            run_pserver_loop(ops0[0].attrs, scope, executor=self)
            return []

        with _call_span("run", 1):
            return self._run(program, feed, fetch_list, scope,
                             return_numpy)

    def _run(self, program, feed, fetch_list, scope, return_numpy):
        plan, feeds, const_state, mut_state, rng = self._gather(
            program, feed, fetch_list, scope)
        from ..observe import observe_feed_gap

        observe_feed_gap()
        t0 = time.perf_counter()
        with _dispatch_guard(plan, "run",
                             (feeds, const_state, mut_state, rng),
                             scope, self.place, plan.fn,
                             "optimized") as (loads, args):
            fetches, new_mut, new_pure, new_rng = plan.fn(*args)
        steady = _record_dispatch(plan, "run", "run", 1,
                                  time.perf_counter() - t0, loads)

        return self._finish(plan, scope, fetches, new_mut, new_pure,
                            new_rng, return_numpy, "",
                            completion=(steady, "run", t0))

    @staticmethod
    def _finish(plan, scope, fetches, new_mut, new_pure, new_rng,
                return_numpy, nan_suffix, completion=None):
        """Shared run()/run_repeated() epilogue: state write-back, RNG
        store, numpy conversion, FLAGS_check_nan_inf. ``completion`` is
        ``(steady, site, t0)``: when the numpy conversion blocks on the
        result, the dispatch-to-ready latency is observed as the
        ``complete`` phase. ``run_pipelined`` reuses the same two helpers
        from its loop and ``FetchHandle.result()`` so the paths cannot
        drift."""
        _write_back_state(plan, scope, new_mut, new_pure, new_rng)

        if return_numpy:
            if fetches:
                # the conversion is the host block where a wedged device
                # hangs a run — keep it heartbeat-stamped
                with _wait_guard():
                    out = [np.asarray(v) for v in fetches]
            else:
                out = []
            # `complete` only when the conversion actually blocked on a
            # result: an empty fetch_list never waits, and recording it
            # would fill the histogram with dispatch-only samples
            if out and completion is not None:
                _record_completion(completion[0], completion[1],
                                   time.perf_counter() - completion[2])
            _check_fetches_finite(plan.fetch_names, out, nan_suffix)
            return out
        return list(fetches)

    def run_repeated(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        steps: int = 1,
        return_numpy: bool = True,
        feed_stacked: bool = False,
        reduce_fetches: str = "last",
    ):
        """Run ``steps`` train iterations as ONE device-side executable
        (a ``lax.scan`` over the whole-block step, donated state carry):
        a single host dispatch per K steps instead of K round-trips —
        the in-device analog of the reference's AsyncExecutor /
        multi-iteration trainer loop (async_executor.cc), and the lever
        that removes per-step host dispatch latency from the
        steady-state training path (its size is not measured on the
        current code — PERF.md).

        Semantics: identical to calling ``run`` ``steps`` times — state
        (params, optimizer slots) and the RNG chain advance exactly as
        in the unrolled sequence (dropout masks differ per iteration);
        returned fetches are the LAST step's.

        With ``feed_stacked=False`` the same feed dict is re-used every
        step — steady-state measurement and synthetic-data loops. With
        ``feed_stacked=True`` every feed value carries a leading
        ``steps`` axis and the scan consumes one slice per iteration —
        K *different* minibatches per dispatch, the shape a PyReader /
        DataLoader hands over when it batches K microbatches ahead
        (``paddle_tpu.reader.stack_feed_window`` builds it).
        ``reduce_fetches="mean"|"sum"`` aggregates float fetches across
        the K steps (window-mean loss, summed eval metrics) instead of
        returning the last step's values."""
        _check_reduce(reduce_fetches)
        if steps <= 1:
            if feed_stacked:
                feed = unstack_singleton_feed(feed)
            return self.run(program, feed, fetch_list, scope,
                            return_numpy=return_numpy)
        from ..compiler import CompiledProgram

        if isinstance(program, CompiledProgram):
            # data-parallel: the engine owns the sharded K-step scan
            return program._run_repeated(self, feed, fetch_list, scope,
                                         steps, return_numpy, feed_stacked,
                                         reduce_fetches)
        program = program if program is not None else default_main_program()
        scope = scope if scope is not None else global_scope()
        with _call_span("run_repeated", steps):
            return self._run_repeated(program, feed, fetch_list, scope,
                                      steps, return_numpy, feed_stacked,
                                      reduce_fetches)

    def _run_repeated(self, program, feed, fetch_list, scope, steps,
                      return_numpy, feed_stacked, reduce_fetches):
        plan, feeds, const_state, mut_state, rng = self._gather(
            program, feed, fetch_list, scope)
        if feed_stacked:
            validate_stacked_feeds(plan.feed_names, feeds, steps)
        key = (steps, feed_stacked, reduce_fetches)
        fn = plan.multi.get(key)
        if fn is None:
            fn = _make_multi_fn(plan, steps, feed_stacked, reduce_fetches)
            plan.multi[key] = fn

        from ..observe import observe_feed_gap

        observe_feed_gap()
        sig = ("run_repeated",) + key
        t0 = time.perf_counter()
        with _dispatch_guard(plan, sig,
                             (feeds, const_state, mut_state, rng),
                             scope, self.place, fn) as (loads, args):
            fetches, new_mut, new_pure, new_rng = fn(*args)
        steady = _record_dispatch(plan, sig, "run_repeated",
                                  steps, time.perf_counter() - t0, loads)
        return self._finish(plan, scope, fetches, new_mut, new_pure,
                            new_rng, return_numpy,
                            " after %d scanned steps" % steps,
                            completion=(steady, "run_repeated", t0))

    # -------------------------------------------------------- pipelined
    def run_pipelined(
        self,
        program: Optional[Program] = None,
        reader=None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        max_in_flight: int = 2,
        prefetch_depth: Optional[int] = None,
        return_numpy: bool = True,
        const_feed_names: Sequence[str] = (),
        const_dedup: Optional[bool] = None,
        steps_per_call: Optional[int] = None,
        reduce_fetches: str = "last",
    ):
        """Fully overlapped step loop: generator of ``FetchHandle``s.

        ``reader`` yields feed dicts (a zero-arg callable returning an
        iterable, an iterable, or an already-constructed
        ``DevicePrefetcher``). A background thread converts each batch
        and ``device_put``s it committed to this executor's place
        (``prefetch_depth`` batches ahead), so the step loop receives
        device-resident feeds; each step is DISPATCHED without blocking
        on its results — JAX async dispatch then overlaps step N's
        compute with step N+1's H2D and step N-1's D2H. The in-flight
        window (``max_in_flight``) bounds dispatched-but-unresolved
        steps: before dispatching past the cap, the OLDEST handle is
        waited on, capping live device buffers at
        ``max_in_flight * (feeds + fetches)`` plus the prefetch queue.

        Semantics are identical to calling ``run`` once per batch —
        state/RNG advance the same way; fetch values are numerically
        identical (``tests/test_device_pipeline.py`` pins parity).
        Feeds repeated across steps (same ndarray object, or names in
        ``const_feed_names``) skip re-transfer via the const-feed dedup
        cache — see ``ConstFeedCache`` for the in-place-mutation
        invalidation rule. Pass ``const_dedup=False`` when the reader
        refills ONE preallocated ndarray in place each step (constant
        object identity, changing data): identity dedup would serve
        stale batches there; ``const_feed_names`` still cache by name.

        **Whole-loop compilation** (``steps_per_call=K > 1``): the
        prefetch thread accumulates K host batches, stacks them
        host-side (``reader.stack_feed_window``'s layout) into one
        ``WindowFeed`` with a SINGLE ``device_put`` per window, and the
        loop dispatches ONE ``run_repeated``-style K-step ``lax.scan``
        executable per window — a single host round-trip AND a single
        H2D call per K steps, amortizing per-step dispatch
        latency while the prefetcher keeps window N+1's H2D under
        window N's compute (``prefetch_depth`` then counts windows, so
        device memory is depth x K batches). A caller-constructed
        ``DevicePrefetcher`` hands over per-step device feeds, so the
        loop windows them via ``jnp.stack`` instead — the dispatch half
        still amortizes, the per-batch H2D does not.
        Semantics stay BITWISE the per-step loop's: params, optimizer
        slots and the RNG chain advance exactly as unrolled (dropout
        masks differ per step, identically in both modes); each window
        yields ONE handle whose values follow ``reduce_fetches``
        ("last" default / "mean" / "sum" over the window's float
        fetches) and whose ``step`` is the window's LAST step index. A
        ragged final window (reader ran dry, or a batch's shapes broke
        the window in progress) falls back to the per-step path rather
        than compiling a second scan length. ``steps_per_call=None``
        is ``PADDLE_TPU_STEPS_PER_CALL`` if set, else 1.

        Abandoning the generator (break / close) stops the prefetch
        thread and drains in-flight work. The analog of the reference's
        async_executor.cc multi-threaded trainer loop, recast for ONE
        XLA executable with async dispatch instead of per-op threads.
        """
        from .pipeline import DevicePrefetcher

        program = program if program is not None else default_main_program()
        scope = scope if scope is not None else global_scope()
        if reader is None:
            raise ValueError("run_pipelined needs a reader of feed dicts")
        if max_in_flight < 1:
            raise ValueError("max_in_flight must be >= 1, got %d"
                             % max_in_flight)
        _check_reduce(reduce_fetches)
        # a bad argument or a malformed PADDLE_TPU_STEPS_PER_CALL raises
        # HERE, with the other argument validation — not from the
        # prefetch fill thread (or mid-iteration) at the first batch
        window = _resolve_steps_per_call(steps_per_call)
        if isinstance(reader, DevicePrefetcher):
            prefetcher = reader
            if prefetcher._closed:
                # iter() would raise this too, but only at first next();
                # a caller-supplied spent prefetcher must fail HERE
                raise RuntimeError(
                    "DevicePrefetcher is single-use: it was already closed "
                    "or fully consumed; construct a new one per epoch")
            if prefetch_depth is not None \
                    and prefetch_depth != prefetcher._depth:
                # silently running at the prefetcher's depth would make
                # the tuning knob a no-op; surface the conflict eagerly
                raise ValueError(
                    "prefetch_depth=%d conflicts with the already-"
                    "constructed DevicePrefetcher(depth=%d); set depth "
                    "when constructing it" % (prefetch_depth,
                                              prefetcher._depth))
            exe_dev = self._jax_device()
            if prefetcher._device is not None and exe_dev is not None \
                    and prefetcher._device != exe_dev:
                # feeds committed to the wrong device would only fail at
                # the first dispatch (or silently misplace) mid-training
                raise ValueError(
                    "DevicePrefetcher commits feeds to %s but this "
                    "executor's place is %s; construct the prefetcher "
                    "with place=executor.place" % (prefetcher._device,
                                                   exe_dev))
            if const_dedup is not None \
                    and const_dedup != prefetcher._dedup_unmarked:
                raise ValueError(
                    "const_dedup=%r conflicts with the already-"
                    "constructed DevicePrefetcher(const_dedup=%r); set it "
                    "when constructing it" % (const_dedup,
                                              prefetcher._dedup_unmarked))
            if const_feed_names:
                prefetcher.const_cache.mark_constant(*const_feed_names)
        else:
            prefetcher = DevicePrefetcher(
                reader, place=self.place, program=program,
                depth=2 if prefetch_depth is None else prefetch_depth,
                const_feed_names=const_feed_names,
                const_dedup=True if const_dedup is None else const_dedup,
                # whole-loop compilation: for K > 1 the fill thread
                # stacks K batches into ONE WindowFeed with a single
                # device_put per window — per-batch H2D call overhead
                # amortizes alongside the scan's dispatch overhead
                window_resolver=lambda feed: (window, None))
        # validation + prefetcher setup are eager; only the loop itself is
        # a generator (a never-iterated result must not defer ValueErrors).
        # iter() stays lazy — it starts the fill thread, which must not
        # run for a generator that is never iterated
        return self._pipelined_loop(program, prefetcher, fetch_list, scope,
                                    max_in_flight, return_numpy,
                                    window, reduce_fetches)

    def _pipelined_loop(self, program, prefetcher, fetch_list, scope,
                        max_in_flight, return_numpy, steps_per_call=1,
                        reduce_fetches="last"):
        from .pipeline import FetchHandle, WindowFeed
        from ..observe import observe_feed_gap
        from ..observe.families import (PIPELINE_IN_FLIGHT,
                                        PIPELINE_OVERLAP_RATIO,
                                        PIPELINE_WAIT_SECONDS,
                                        PIPELINE_WINDOW_RAGGED,
                                        PIPELINE_WINDOW_SECONDS,
                                        PIPELINE_WINDOW_SIZE,
                                        PIPELINE_WINDOW_STEPS)

        window: deque = deque()
        blocked = 0.0
        step_i = 0
        t_loop = time.perf_counter()
        loop_ctx = None
        if _tr.trace_enabled():
            # ONE trace for the whole loop: the caller's context when
            # attached, else a fresh loop trace. The fill thread gets it
            # by explicit hand-off (pinned BEFORE iter() starts the
            # thread); the consumer side re-attaches it around each
            # step's dispatch/wait below — attach() cannot span the
            # yields (the thread-local would leak into whatever the
            # consumer runs between steps), so the scope is per-step.
            loop_ctx = _tr.current() or prefetcher.trace_ctx \
                or _tr.new_trace()
            if prefetcher.trace_ctx is None:
                prefetcher.trace_ctx = loop_ctx
        # attach(None) is a no-op scope, and one attach object is
        # reusable (sequential enter/exit on the same thread) — no
        # per-step allocation when tracing is off
        att = _tr.attach(loop_ctx)

        def wait_oldest():
            # drain the window BEFORE dispatching past the cap: the wait
            # must not sit between the prefetcher's hand-off stamp and
            # the dispatch (it would pollute the feed->run gap), and
            # the prefetch thread keeps filling during it either way
            nonlocal blocked
            tw = time.perf_counter()
            with att, _wait_guard(step_i):
                window.popleft().wait()
            dt = time.perf_counter() - tw
            blocked += dt
            PIPELINE_WAIT_SECONDS.observe(dt)
            PIPELINE_IN_FLIGHT.set(len(window))

        def dispatch_step(feeds):
            # ONE per-step dispatch (the classic loop body; also the
            # ragged-window fallback)
            nonlocal step_i
            with att:
                plan, feed_list, const_state, mut_state, rng = \
                    self._gather(program, feeds, fetch_list, scope)
                t0 = time.perf_counter()
                with _dispatch_guard(plan, "run",
                                     (feed_list, const_state, mut_state,
                                      rng), scope, self.place, plan.fn,
                                     "optimized") as (loads, args):
                    fetches, new_mut, new_pure, new_rng = plan.fn(*args)
                # sig "run": same executable as run(), so a run()
                # warmup already paid this signature's compile
                steady = _record_dispatch(plan, "run",
                                          "run_pipelined", 1,
                                          time.perf_counter() - t0, loads)
            # state write-back WITHOUT blocking: the new arrays are
            # futures; the next dispatch chains on them device-side
            _write_back_state(plan, scope, new_mut, new_pure, new_rng)
            # the handle records the `complete` phase when it first
            # blocks (wait()/result()) — dispatch-start to ready
            handle = FetchHandle(step_i, plan.fetch_names, fetches,
                                 return_numpy,
                                 completion=(steady, "run_pipelined",
                                             t0),
                                 block_on=() if fetches else
                                 _completion_probe(plan, new_mut,
                                                   new_pure, new_rng),
                                 window=k or 1)
            window.append(handle)
            PIPELINE_IN_FLIGHT.set(len(window))
            step_i += 1
            return handle

        def dispatch_window(stacked, k, plan_feed):
            # ONE K-step scanned dispatch over a stacked window: the
            # same make_scan_fn executable run_repeated jits (shared
            # plan.multi cache + compile-attribution sig). ``stacked``
            # maps feed name -> [K, ...] device array (pre-stacked by a
            # windowed prefetcher, or jnp.stack'd by the loop-side
            # fallback below); ``plan_feed`` is a per-step-shaped feed
            # dict that keys the SAME plan the per-step path uses
            nonlocal step_i
            with att:
                plan, _fl, const_state, mut_state, rng = self._gather(
                    program, plan_feed, fetch_list, scope)
                feed_list = [stacked[n] for n in plan.feed_names]
                key = (k, True, reduce_fetches)
                fn = plan.multi.get(key)
                if fn is None:
                    fn = _make_multi_fn(plan, k, True, reduce_fetches)
                    plan.multi[key] = fn
                sig = ("run_repeated",) + key
                t0 = time.perf_counter()
                with _dispatch_guard(plan, sig,
                                     (feed_list, const_state, mut_state,
                                      rng), scope, self.place,
                                     fn) as (loads, args):
                    fetches, new_mut, new_pure, new_rng = fn(*args)
                dt = time.perf_counter() - t0
                steady = _record_dispatch(plan, sig, "run_pipelined",
                                          k, dt, loads)
                if steady:
                    PIPELINE_WINDOW_SECONDS.labels(
                        phase="dispatch").observe(dt)
                PIPELINE_WINDOW_STEPS.observe(k)
            _write_back_state(plan, scope, new_mut, new_pure, new_rng)
            obs = PIPELINE_WINDOW_SECONDS.labels(phase="complete") \
                .observe if steady else None
            handle = FetchHandle(step_i + k - 1, plan.fetch_names,
                                 fetches, return_numpy,
                                 completion=(steady, "run_pipelined",
                                             t0),
                                 block_on=() if fetches else
                                 _completion_probe(plan, new_mut,
                                                   new_pure, new_rng),
                                 steps=k, window_obs=obs)
            window.append(handle)
            PIPELINE_IN_FLIGHT.set(len(window))
            step_i += k
            return handle

        def note_k(kk, _src=None):
            nonlocal k
            k = kk
            PIPELINE_WINDOW_SIZE.set(kk)

        def flush_ragged(fs):
            # the per-step fallback for batches that never filled a
            # window (reader dry, or a shape change broke the window in
            # progress) — never a second compiled scan length; shared
            # by both flush sites so cap-draining and ragged counting
            # can't diverge
            for f in fs:
                if len(window) >= max_in_flight:
                    wait_oldest()
                PIPELINE_WINDOW_RAGGED.inc()
                yield dispatch_step(f)

        k = None          # resolved from the FIRST hand-off
        buf: list = []    # loop-side window (caller-supplied prefetcher)
        buf_sig = None    # per-feed shape signature of the open window
        feed_iter = iter(prefetcher)
        try:
            while True:
                if len(window) >= max_in_flight:
                    wait_oldest()
                feeds = next(feed_iter, None)
                if feeds is None:
                    yield from flush_ragged(buf)
                    buf = []
                    break
                # observe the hand-off gap IMMEDIATELY: the batch is
                # already device-resident, so unlike run() there is no
                # conversion left between hand-off and dispatch worth
                # including (and on oversubscribed hosts every extra
                # bytecode in this window collects scheduler noise)
                observe_feed_gap()
                if isinstance(feeds, WindowFeed):
                    # a windowed prefetcher stacked K host batches into
                    # ONE device feed (single H2D per window) — dispatch
                    # straight, no loop-side buffering; the per-step
                    # plan is keyed by a [0]-sliced per-step-shaped feed
                    if k is None:
                        note_k(*prefetcher.resolved_window)
                    yield dispatch_window(
                        feeds.feeds, feeds.steps,
                        {n: v[0] for n, v in feeds.feeds.items()})
                    continue
                if k is None:
                    if prefetcher.resolved_window is not None:
                        note_k(*prefetcher.resolved_window)
                    else:
                        note_k(steps_per_call)
                if k == 1:
                    yield dispatch_step(feeds)
                    continue
                if prefetcher.resolved_window is not None:
                    # the prefetcher owns windowing: a plain per-step
                    # feed from it IS a ragged step (reader ran dry
                    # mid-window, or a shape change broke the window)
                    PIPELINE_WINDOW_RAGGED.inc()
                    yield dispatch_step(feeds)
                    continue
                # caller-supplied (unwindowed) prefetcher: window the
                # already-device-resident feeds loop-side via jnp.stack
                sig = {n: np.shape(v) for n, v in feeds.items()}
                if buf and sig != buf_sig:
                    # a shape change flushes the open window through the
                    # per-step path (stacking never mixes shapes)
                    yield from flush_ragged(buf)
                    buf = []
                buf_sig = sig
                buf.append(feeds)
                if len(buf) == k:
                    block = program.global_block()
                    stacked = {
                        n: jnp.stack([_feed_to_device(n, b[n],
                                                      block.vars.get(n))
                                      for b in buf])
                        for n in buf[0]}
                    handle = dispatch_window(stacked, k, buf[0])
                    buf = []
                    yield handle
        finally:
            prefetcher.close()
            # the drain waits are window waits too: a loop with
            # steps <= max_in_flight never stalls IN the loop, so
            # excluding these would report ~1.0 overlap for a run that
            # was fully serialized on its fetch waits
            while window:
                tw = time.perf_counter()
                with att, _wait_guard(step_i):
                    window.popleft().wait()
                dt = time.perf_counter() - tw
                blocked += dt
                PIPELINE_WAIT_SECONDS.observe(dt)
            PIPELINE_IN_FLIGHT.set(0)
            wall = time.perf_counter() - t_loop
            if step_i and wall > 0:
                PIPELINE_OVERLAP_RATIO.set(max(0.0, 1.0 - blocked / wall))

    def train_loop(
        self,
        program: Optional[Program] = None,
        reader=None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        max_in_flight: int = 2,
        prefetch_depth: Optional[int] = None,
        return_numpy: bool = True,
        const_feed_names: Sequence[str] = (),
        const_dedup: Optional[bool] = None,
        on_step=None,
        steps_per_call: Optional[int] = None,
        reduce_fetches: str = "last",
    ):
        """Drive ``run_pipelined`` over the whole reader; returns
        ``(n_steps, last_fetch_values)``. ``on_step(step_i, values)`` is
        called per resolved DISPATCH in order — one call per step in the
        classic loop, one call per window with ``steps_per_call=K > 1``
        (``step_i`` is then the window's last step index and ``values``
        follow ``reduce_fetches``). Resolution trails dispatch by the
        in-flight window, so the callback never serializes the
        pipeline. ``n_steps`` counts STEPS, not dispatches — windowed
        and per-step runs over the same reader report the same count."""
        pending: deque = deque()
        last = None
        n = 0

        def _resolve(h):
            vals = h.result()
            if on_step is not None:
                on_step(h.step, vals)
            return vals

        for h in self.run_pipelined(
                program, reader, fetch_list, scope,
                max_in_flight=max_in_flight, prefetch_depth=prefetch_depth,
                return_numpy=return_numpy,
                const_feed_names=const_feed_names, const_dedup=const_dedup,
                steps_per_call=steps_per_call,
                reduce_fetches=reduce_fetches):
            n += h.steps
            pending.append(h)
            if len(pending) > max_in_flight:
                last = _resolve(pending.popleft())
        while pending:
            last = _resolve(pending.popleft())
        return n, last

    def cost_analysis(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
    ) -> Dict[str, float]:
        """XLA cost analysis (flops, bytes accessed, ...) of the compiled
        step for this (program, feed-signature) — the whole-program analog
        of the reference's per-op profiler tables and
        contrib/memory_usage_calc.py. Returns the compiler's own estimate,
        so benchmark MFU numbers don't rely on hand-derived formulas.
        Cached per plan: repeat calls with the same signature are free."""
        from ..compiler import CompiledProgram

        if isinstance(program, CompiledProgram):
            program = program._program
        program = program if program is not None else default_main_program()
        scope = scope if scope is not None else global_scope()
        plan, feeds, const_state, mut_state, rng = self._gather(
            program, feed, fetch_list, scope)
        if plan.cost is None:
            lowered = plan.fn.lower(feeds, const_state, mut_state, rng)
            # pre-optimization estimate: avoids a second full XLA
            # compile (run() already compiled via the jit cache, which
            # AOT .compile() cannot reuse); dot/conv flops are the same
            # pre- and post-fusion. jax returns None where the backend
            # has no client-side estimate; the compiled executable's
            # analysis is then the authority.
            cost = _first_computation(lowered.cost_analysis())
            if not cost.get("flops"):
                cost = _first_computation(
                    lowered.compile().cost_analysis()) or cost
            if cost.get("flops"):
                plan.cost = dict(cost)
            return dict(cost)
        return dict(plan.cost)

    def lowered_hlo(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        stage: str = "optimized",
    ) -> str:
        """Text of the compiled step for this (program, feed-signature):
        ``stage="stablehlo"`` is the pre-XLA lowering, ``"optimized"`` the
        post-pass HLO module (fusions, buffer donation aliasing, SPMD
        collectives). This is the self-measurement surface SURVEY §6
        prescribes — golden-structure tests pin invariants on it (no host
        callbacks in a train step, donation aliasing present, one scan for
        grad accumulation) so perf regressions surface without TPU
        hardware, the way the reference pins transpiled program structure
        in test_dist_transpiler.py."""
        if stage not in ("stablehlo", "optimized"):
            raise ValueError("stage must be 'stablehlo' or 'optimized', "
                             "got %r" % (stage,))
        from ..compiler import CompiledProgram

        if isinstance(program, CompiledProgram):
            program = program._program
        program = program if program is not None else default_main_program()
        scope = scope if scope is not None else global_scope()
        plan, feeds, const_state, mut_state, rng = self._gather(
            program, feed, fetch_list, scope)
        args = (feeds, const_state, mut_state, rng)
        if stage == "optimized":
            from ..observe import device_names

            # the plan's name table reads the same text from the same slot
            return device_names.optimized_text(
                plan, stage, lambda: plan.fn.lower(*args))
        if stage not in plan.hlo_text:
            plan.hlo_text[stage] = plan.fn.lower(*args).as_text()
        return plan.hlo_text[stage]

    def _gather(self, program, feed, fetch_list, scope):
        """Shared run()/cost_analysis() plumbing: feed conversion, plan
        cache lookup, and state/RNG argument gathering — the
        ``executor.gather`` span (``executor.h2d`` nests in it)."""
        with _tr.trace_span("executor.gather"):
            return self._gather_args(program, feed, fetch_list, scope)

    def _gather_args(self, program, feed, fetch_list, scope):
        feed = feed or {}
        if feed and _FEED_OBSERVERS:
            # calibration hook (analysis/ranges.Calibration.attach):
            # observers see the raw host feed dict before conversion.
            # Observer exceptions propagate — a broken calibrator must
            # fail loudly, not silently record nothing
            for _obs in list(_FEED_OBSERVERS):
                _obs(feed)
        fetch_names = [
            v.name if isinstance(v, Variable) else str(v) for v in (fetch_list or [])
        ]
        block = program.global_block()
        feed_vals, _ = feeds_to_device(feed, block.vars.get,
                                       self._jax_device())
        key = self._cache_key(program, feed_vals, fetch_names)
        plan = self._cache.get(key)
        if plan is None:
            from ..observe.families import (EXECUTOR_CACHE_EVICTIONS,
                                            EXECUTOR_CACHE_MISSES,
                                            EXECUTOR_PREPARE_SECONDS)

            EXECUTOR_CACHE_MISSES.inc()
            t0 = time.perf_counter()
            # stable within-process tag for this (program, feed-sig,
            # fetch) plan: the trace spans' per-op attribution key
            sig = plan_tag(key)
            with _prepare_span(sig, program) as sp:
                plan = self._prepare(program, feed_vals, fetch_names,
                                     scope, span=sp)
            plan.sig = sig
            EXECUTOR_PREPARE_SECONDS.observe(time.perf_counter() - t0)
            self._cache[key] = plan
            while len(self._cache) > self._cache_size:
                self._cache.popitem(last=False)
                EXECUTOR_CACHE_EVICTIONS.inc()
        else:
            from ..observe.families import EXECUTOR_CACHE_HITS

            EXECUTOR_CACHE_HITS.inc()
            self._cache.move_to_end(key)
        const_state = [_require(scope, n) for n in plan.const_state]
        mut_state = [_require(scope, n) for n in plan.mut_state]
        if not plan.needs_rng:
            # the step takes a key and never looks at it: one constant,
            # so what happens to the scope's key (loose from a startup
            # program, committed by a train step) is no other signature
            rng = _unused_key()
        else:
            rng = scope.find_var(RNG_VAR)
            if rng is None:
                seed = program.random_seed \
                    if program.random_seed is not None else 0
                rng = jax.random.PRNGKey(seed)
        feeds = [feed_vals[n] for n in plan.feed_names]
        return plan, feeds, const_state, mut_state, rng

    def close(self):
        """Release cached executables and tell any connected pservers this
        trainer is done (Executor.close → SendComplete analog,
        executor.py:388-405 / rpc_client.h:86)."""
        self._cache.clear()
        from ..ops.distributed_ops import complete_and_reset

        complete_and_reset()

    def _jax_device(self):
        """Concrete jax.Device for this executor's place (None = default)."""
        return self.place.jax_device() if self.place is not None else None

    # -------------------------------------------------------------- prepare
    def _cache_key(self, program, feed_vals, fetch_names):
        sig = tuple(sorted((n, v.shape, str(v.dtype)) for n, v in feed_vals.items()))
        # the optimizer config (level + every output-changing knob) keys
        # the cache too: a plan compiled from the optimized clone must
        # never serve a differently-configured run. Same deal for the
        # kernels' environment (PADDLE_TPU_KERNELS, FLASH_MIN_SEQ)
        from .. import kernels as _kernels

        return (program._serial, program.version, _optimizer_config_key(),
                _kernels.config_key(), sig, tuple(fetch_names))

    def _prepare(self, program: Program, feed_vals, fetch_names, scope,
                 span=_tr.NOOP) -> _Plan:
        from ..analysis import validation_enabled, verify_program

        if validation_enabled():
            # opt-in prepare-time verification (PADDLE_TPU_VALIDATE=1; on
            # by default under tests): a bad program fails HERE with op
            # provenance instead of as a JAX trace error inside
            # lower_block. Once per plan — cache hits never re-verify.
            # Runs on the USER program (before optimization) so findings
            # carry the original build-site provenance.
            verify_program(program, fetch_list=fetch_names, scope=scope,
                           raise_on_error=True, site="prepare")
        exact = getattr(program, "exact_numerics", False)
        if not exact and not getattr(program, "_pre_optimized", False):
            # graph-optimizing pass pipeline (core/passes): fold/copy-
            # prop/CSE/DCE/fusion on a CLONE, so the optimized plan is
            # what gets cached and the user's program is untouched.
            # Level 0 bypasses entirely (the level is part of the plan-
            # cache key). Once per plan-cache miss, like verification.
            # exact_numerics programs (dygraph capture's bitwise-parity
            # mode) skip it: fusion passes rewrite the op sequence and
            # would break replay-equals-eager at the ULP level.
            # _pre_optimized programs (export/ artifacts) already ran
            # the pipeline, TV-checked, at save time — re-running it
            # here would break the artifact's zero-optimize cold-start
            # contract (and the config_key load check guarantees the
            # frozen pipeline config matches this process's).
            program = optimize_for_execution(program, fetch_names, scope=scope)
        if span.attrs is not None:
            span.attrs["ops_out"] = len(program.global_block().ops)
        feed_names = sorted(feed_vals)
        (feed_names, fetch_names, const_state, mut_state, pure_written,
         needs_rng, step) = analyze_block(program, feed_names, fetch_names, scope)
        # exact_numerics: run the lowered step UNJITTED. Whole-graph XLA
        # compilation contracts mul+add across op boundaries into FMAs
        # (and no compiler_options combination restores parity without
        # breaking dot emission — backend opt level 0 swaps Eigen dots
        # for naive loops), so the only faithful executable is the same
        # per-primitive dispatch sequence eager mode runs. Still one
        # host call per step through the SAME plan cache, with all the
        # framework Python (tape, VarBase wrapping) stripped.
        fn = step if exact else jax.jit(step, donate_argnums=(2,))
        plan = _Plan(feed_names, fetch_names, const_state, mut_state,
                     pure_written, needs_rng, fn, step=step)
        plan.exact = exact
        return plan

    def seed_plan(self, program: Program, feed, fetch_list,
                  scope: Optional[Scope] = None) -> bool:
        """Install a prepared plan for (program, feed-signature,
        fetches) WITHOUT counting a plan-cache miss — the artifact
        cold-start path (paddle_tpu/export): a loaded artifact seeds
        every covered signature so its first real run is a cache HIT,
        and the cold-start acceptance test pins that loading moves
        zero ``paddle_executor_cache_misses_total``. Compilation stays
        lazy (jax.jit traces at first dispatch), so seeding costs one
        analyze pass per signature, not a compile. Returns True when a
        plan was installed, False when the signature was already
        cached."""
        scope = scope if scope is not None else global_scope()
        fetch_names = [v.name if isinstance(v, Variable) else str(v)
                       for v in (fetch_list or [])]
        block = program.global_block()
        feed_vals, _ = feeds_to_device(feed or {}, block.vars.get,
                                       self._jax_device())
        key = self._cache_key(program, feed_vals, fetch_names)
        if key in self._cache:
            return False
        from ..observe.families import (EXECUTOR_CACHE_EVICTIONS,
                                        EXECUTOR_PREPARE_SECONDS)

        t0 = time.perf_counter()
        sig = plan_tag(key)
        with _prepare_span(sig, program) as sp:
            plan = self._prepare(program, feed_vals, fetch_names, scope,
                                 span=sp)
        plan.sig = sig
        EXECUTOR_PREPARE_SECONDS.observe(time.perf_counter() - t0)
        self._cache[key] = plan
        while len(self._cache) > self._cache_size:
            self._cache.popitem(last=False)
            EXECUTOR_CACHE_EVICTIONS.inc()
        return True


@functools.cache
def _unused_key():
    """The key argument of every plan whose program draws nothing."""
    return jax.random.PRNGKey(0)


def _first_computation(cost) -> dict:
    """``cost_analysis()`` returns a dict, a list with one dict per
    computation, or None (no estimate on this backend)."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    return dict(cost or {})


def plan_tag(cache_key) -> str:
    """Stable within-process tag of a plan-cache key: what the
    ``executor.dispatch`` span carries as ``plan``."""
    return "%08x" % (zlib.crc32(repr(cache_key).encode()) & 0xffffffff)


def _resolve_steps_per_call(explicit: Optional[int] = None) -> int:
    """The windowed loop's K: the argument if given, else
    ``PADDLE_TPU_STEPS_PER_CALL`` if set, else 1. A value under 1 or not
    an integer raises from either."""
    if explicit is not None:
        if int(explicit) < 1:
            raise ValueError("steps_per_call must be >= 1, got %r"
                             % (explicit,))
        return int(explicit)
    raw = os.environ.get("PADDLE_TPU_STEPS_PER_CALL", "").strip()
    if not raw:
        return 1
    try:
        k = int(raw)
    except ValueError:
        raise ValueError(
            "PADDLE_TPU_STEPS_PER_CALL must be an integer; got %r"
            % (raw,)) from None
    if k < 1:
        raise ValueError(
            "PADDLE_TPU_STEPS_PER_CALL must be >= 1, got %d" % k)
    return k


def _prepare_span(sig, program):
    """The ``executor.prepare`` span of one plan-cache miss (or one
    ``seed_plan``): verification, the pass pipeline and block analysis,
    parent of the ``optimizer.*`` spans. ``ops_in`` is the program as
    given; ``_prepare`` adds ``ops_out``, what is left to lower."""
    return _tr.trace_span("executor.prepare", plan=sig,
                          ops_in=len(program.global_block().ops))


def _call_span(site, steps):
    """The ``executor.call`` span of one run()/run_repeated()/
    ParallelEngine._execute call: parent of the call's phase spans
    (gather, h2d, place, dispatch, complete, write_back), so its
    duration less theirs is the host time nobody has named yet."""
    return _tr.trace_span("executor.call", site=site, steps=steps)


@contextlib.contextmanager
def _wait_guard(step=None):
    """Heartbeat around a HOST BLOCK on device results (profiled
    block_until_ready, the numpy fetch conversion, pipelined window
    waits). Dispatch is async, so a wedged device manifests exactly
    here — without this stamp the watchdog would read a hung device as
    host idleness and never fire. Doubles as the ``executor.complete``
    trace span (dispatch-to-results-ready, the host's real wait)."""
    hb = heartbeat()
    tok = hb.begin("executor.wait", step=step)
    sp = _tr.trace_span("executor.complete", step=step) \
        if _tr.trace_enabled() else None
    if sp is not None:
        sp.__enter__()
    try:
        yield
    finally:
        if sp is not None:
            sp.__exit__(None, None, None)
        hb.end("executor.wait", tok)


@contextlib.contextmanager
def _dispatch_guard(plan, sig, args=(), scope=None, place=None, fn=None,
                    hlo_key=None, within=None):
    """Resilience wrapper around ONE XLA dispatch, shared by run()/
    run_repeated()/run_pipelined(): stamps the process heartbeat (with
    ``compiling=True`` for a plan's first dispatch per signature, so
    the watchdog judges it against the compile grace deadline, not the
    steady-state one) and passes through the ``executor.dispatch``
    fault-injection site. The fault fires AFTER the begin stamp —
    an injected wedge must look to the watchdog exactly like a real
    one — and the end stamp lands even when the fault raises, so the
    watchdog re-arms once the error has surfaced. The trace span opens
    BEFORE the fault point for the same reason: a wedged dispatch must
    sit in the flight recorder as an OPEN ``executor.dispatch`` span
    (tagged with the plan signature) when the dump lands. Tracing
    disabled is one bool check — no span, no allocations.

    Yields ``(loads, args)``. ``loads`` is the dispatch's ``LoadScope``
    (None with tracing off): what JAX traced, lowered, compiled or
    loaded inside it, as the listener of observe/trace.py saw it.
    ``args`` are the call's arguments, ``(feeds, const_state, mut_state,
    rng)``: as given, but for the FIRST dispatch of a signature by an
    executor with a ``place``, whose loose state arrays come back
    committed to it (``_commit_loose``; the span then says how many:
    ``committed``). They are looked at only then and when a backend
    stage ran (``_note_load``).

    ``fn`` is the jitted function the caller is about to call with
    ``args``: a signature's first dispatch (with tracing on) notes the
    two, abstractly, as the way to the plan's name table
    (``observe/device_names.py``: ``hlo_key`` the slot of
    ``plan.hlo_text``, ``within`` the mesh to lower in). Nothing is
    lowered for it here, and a steady dispatch runs none of it."""
    hb = heartbeat()
    first = sig not in plan.compiled_sigs
    tok = hb.begin("executor.dispatch", compiling=first)
    sp = _tr.trace_span("executor.dispatch", plan=plan.sig) \
        if _tr.trace_enabled() else None
    loads = None
    if sp is not None:
        sp.__enter__()
        loads = _tr.open_loads(plan.sig, plan.loads.get(sig, 0) + 1)
    try:
        if first and place is not None:
            args, n = _commit_loose(plan, scope, args, place.jax_device())
            if n and sp is not None:
                sp.attrs["committed"] = n
        if first and sp is not None and fn is not None:
            from ..observe import device_names

            device_names.register(plan, sig, fn, args,
                                  hlo_key or ("optimized", sig), within)
        fault_point("executor.dispatch")
        yield loads, args
    finally:
        if loads is not None:
            _tr.close_loads(loads)
            if loads.backend:
                plan.loads[sig] = loads.nth
                _note_load(plan, sig, args, loads.nth, sp.attrs)
        if sp is not None:
            sp.__exit__(None, None, None)
        hb.end("executor.dispatch", tok)


def _is_loose(a) -> bool:
    """Not committed to a device: a host array, or a ``jax.Array`` that
    sits where JAX put it by default (what a jit without committed
    arguments returns) and follows whatever it is computed with."""
    return not getattr(a, "committed", False)


def _commit_loose(plan, scope, args, device):
    """The first dispatch of a plan signature hands ``jax.jit`` the
    argument signature every later one has. A step given any committed
    argument (a feed: ``feeds_to_device`` commits) returns COMMITTED
    state, so state that arrives loose — what a program with no feeds
    wrote (a startup program, the scratch startup of a prefill length),
    weights a caller's own jit drew, host arrays a checkpoint restored —
    would make the second dispatch another signature, and JAX would
    lower and load (cold: compile) the same program again. So commit the
    loose state arrays to the executor's ``device`` with ONE
    ``jax.device_put`` (no copy for an array already there: the same
    buffer under a committed array) and put them back in the scope, so
    the scope holds what is donated and every other plan over the same
    state finds it committed.

    Decided from the arrays alone, and only where the committed
    arguments already say ``device``: with one committed anywhere else
    (somebody's decision: it stays, and the loose ones go on following
    it) or none at all (a startup program returns loose arrays again:
    nothing flips, and what it writes reaches a ``ParallelEngine`` as it
    always did) the arguments are left as they are, as they are for an
    executor without a place (``device`` None). The key is state only
    for a plan that draws (``_gather_args`` hands the others one
    constant key). Returns ``(args, n)``, ``n`` the arrays committed.
    Never runs in a steady dispatch."""
    feeds, const_state, mut_state, rng = args
    names = plan.const_state + plan.mut_state
    state = const_state + mut_state
    if plan.needs_rng:
        names, state = names + [RNG_VAR], state + [rng]
    loose = [i for i, a in enumerate(state) if _is_loose(a)]
    held = {d for a in feeds + state if not _is_loose(a)
            for d in a.devices()}
    if not loose or held != {device}:
        return args, 0
    for i, a in zip(loose, jax.device_put([state[i] for i in loose],
                                          device)):
        state[i] = a
        # a key made for this call (the scope holds none yet) is the
        # call's alone: the step's own key is written back after it
        if names[i] != RNG_VAR or scope.find_var(RNG_VAR) is not None:
            scope.set_var(names[i], a)
    n_const, n_mut = len(const_state), len(mut_state)
    return (feeds, state[:n_const], state[n_const:n_const + n_mut],
            state[-1] if plan.needs_rng else rng), len(loose)


def _note_load(plan, sig, args, nth, attrs):
    """A backend stage ran in this dispatch: keep how the arguments sat
    (committed or not, on which sharding: readable of a donated array
    too) and, when the program had been loaded before (``nth`` >= 2),
    put on the dispatch span what differs from the last load —
    ``uncommitted``: arguments whose committed flag changed,
    ``resharded``: arguments on another sharding or device. A fresh
    process's second step is no such load any more (``_commit_loose``
    hands the first dispatch what the second will see); what is left is
    a change made to a live process: state put into the scope loose or
    on another device between two dispatches, an executor with no place
    whose caller feeds committed arrays. Never runs in a steady
    dispatch."""
    now = []
    for group in args:
        for a in (group if isinstance(group, (list, tuple)) else (group,)):
            now.append((bool(getattr(a, "committed", False)),
                        getattr(a, "sharding", None)))
    then = plan.load_args.get(sig)
    plan.load_args[sig] = now
    if nth < 2:
        return
    attrs["nth"] = nth
    if then is not None and len(then) == len(now):
        attrs["uncommitted"] = sum(a[0] != b[0] for a, b in zip(then, now))
        attrs["resharded"] = sum(a[1] != b[1] for a, b in zip(then, now))


def _loaded(plan, sig, loads):
    """Whether the dispatch just made loaded its program: what the
    listener saw (``loads.stages``), or, with tracing off (``loads`` is
    None), whether it was the first of its signature. Notes the
    signature as dispatched either way (the heartbeat's guess for the
    next one)."""
    first = sig not in plan.compiled_sigs
    if first:
        plan.compiled_sigs.add(sig)
    return bool(loads.stages) if loads is not None else first


def _record_dispatch(plan, sig, site, steps, dt, loads=None):
    """Telemetry shared by run()/run_repeated()/run_pipelined(): count the
    steps and route the wall time — a dispatch in which JAX traced,
    lowered, compiled or loaded a program (``loads.stages``: the first
    of a signature, and any later one that loaded again) lands in the
    compile histogram; every other lands in the run histogram's
    ``dispatch`` phase (the async hand-off the host actually pays per
    step). With tracing off nothing listens (``loads`` is None) and the
    first dispatch of a signature is taken for the loading one. Returns
    True for a steady-state dispatch so the caller knows whether a
    matching ``complete`` observation belongs in the run histogram (a
    compile event's completion would fatten the run tail with compile
    time)."""
    from ..observe.families import (EXECUTOR_COMPILE_SECONDS,
                                    EXECUTOR_RUN_SECONDS, EXECUTOR_STEPS)

    EXECUTOR_STEPS.inc(steps)
    if _loaded(plan, sig, loads):
        EXECUTOR_COMPILE_SECONDS.observe(dt)
        return False
    EXECUTOR_RUN_SECONDS.labels(site=site, phase="dispatch").observe(dt)
    return True


def _completion_probe(plan, new_mut, new_pure, new_rng):
    """Something safe for an empty-fetch FetchHandle to block on. The
    mut-state outputs are DONATED to the NEXT dispatch (argnum 2 of the
    jitted step), so holding them would block_until_ready deleted
    buffers on donation-honoring backends (TPU/GPU; CPU ignores
    donation, which is why tests alone can't catch this). new_rng and
    new_pure are never donated — prefer the smallest of those; when the
    step writes ONLY mut state, a tiny device-side copy completes with
    the step (data dependency) and belongs to nobody's donation."""
    nbytes = lambda a: getattr(a, "nbytes", 0)  # noqa: E731
    safe = ([new_rng] if plan.needs_rng else []) + list(new_pure)
    if safe:
        return (min(safe, key=nbytes),)
    if new_mut:
        return (jnp.copy(min(new_mut, key=nbytes)),)
    return ()  # a no-output step has no device work to bound


def _write_back_state(plan, scope, new_mut, new_pure, new_rng):
    """Post-dispatch scope write-back shared by run()'s _finish and
    _pipelined_loop — the arrays may still be futures; the next dispatch
    chains on them device-side."""
    with _tr.trace_span("executor.write_back"):
        for n, v in zip(plan.mut_state, new_mut):
            scope.set_var(n, v)
        for n, v in zip(plan.pure_written, new_pure):
            scope.set_var(n, v)
        if plan.needs_rng:
            scope.set_var(RNG_VAR, new_rng)


def _check_fetches_finite(fetch_names, values, suffix=""):
    """FLAGS_check_nan_inf guard shared by _finish and
    FetchHandle.result(); no-op when the flag is off."""
    from ..flags import get_flag

    if not get_flag("check_nan_inf"):
        return
    for name, v in zip(fetch_names, values):
        if np.issubdtype(v.dtype, np.floating) and \
                not np.isfinite(v).all():
            raise FloatingPointError(
                "NaN/Inf in fetched var %r%s "
                "(FLAGS_check_nan_inf)" % (name, suffix))


def _record_completion(steady, site, dt):
    """The ``complete`` phase: dispatch-start to results-ready, observed
    only when the host actually blocked (profiled runs, numpy fetch
    conversion). Both phases recorded in BOTH profiled and unprofiled
    paths — PR 1 recorded async-dispatch time unprofiled but blocked
    completion profiled, silently under-reporting run latency."""
    if not steady:
        return
    from ..observe.families import EXECUTOR_RUN_SECONDS

    EXECUTOR_RUN_SECONDS.labels(site=site, phase="complete").observe(dt)


def validate_stacked_feeds(feed_names, feeds, steps):
    """feed_stacked contract: every feed carries a leading ``steps`` axis."""
    for n, f in zip(feed_names, feeds):
        shape = np.shape(f)
        if not shape or shape[0] != steps:
            raise ValueError(
                "feed_stacked=True: feed %r must carry a leading "
                "steps axis of %d (got shape %s) — stack K "
                "per-step batches with reader.stack_feed_window"
                % (n, steps, (shape,)))


def unstack_singleton_feed(feed):
    """steps<=1 with feed_stacked: a window of length 1 still carries the
    leading axis — validate it IS length 1 (a K>1 window with steps=1
    must raise, never silently train on slice 0) and drop it."""
    for n, v in (feed or {}).items():
        shape = np.shape(v)
        if not shape or shape[0] != 1:
            raise ValueError(
                "feed_stacked=True with steps=1: feed %r must carry a "
                "leading axis of 1 (got shape %s)" % (n, (shape,)))
    return {k: v[0] if hasattr(v, "ndim") else np.asarray(v)[0]
            for k, v in (feed or {}).items()}


def _check_reduce(reduce_fetches):
    if reduce_fetches not in ("last", "mean", "sum"):
        raise ValueError("reduce_fetches must be last|mean|sum; got %r"
                         % (reduce_fetches,))


def _make_multi_fn(plan, steps, feed_stacked, reduce_fetches):
    """The K-step executable for one plan: a jitted lax.scan normally, a
    Python loop over the unjitted step for exact_numerics plans (a scan
    would compile — and re-fuse — the body, breaking bitwise parity)."""
    if plan.exact:
        return make_loop_fn(plan.step, steps, feed_stacked, reduce_fetches)
    return jax.jit(make_scan_fn(plan.step, steps, feed_stacked,
                                reduce_fetches),
                   donate_argnums=(2,))


def make_loop_fn(raw_step, steps, feed_stacked, reduce_fetches="last"):
    """Python-loop twin of ``make_scan_fn`` with the same contract
    (carried state/RNG, last-or-reduced fetches). Used for
    exact_numerics plans, where each step must stay the per-primitive
    dispatch sequence eager mode runs."""
    _check_reduce(reduce_fetches)

    def _acc(old, new):
        if reduce_fetches == "last" or not jnp.issubdtype(
                jnp.asarray(new).dtype, jnp.floating):
            return new
        return old + new

    def multi(feeds, const_vals, mut_vals, rng_key):
        mut, key = mut_vals, rng_key
        facc = pures = None
        for i in range(steps):
            step_feeds = [f[i] for f in feeds] if feed_stacked else feeds
            fetches, mut, pures, key = raw_step(step_feeds, const_vals,
                                                mut, key)
            facc = (fetches if facc is None
                    else [_acc(o, n) for o, n in zip(facc, fetches)])
        if reduce_fetches == "mean":
            facc = [f / steps if jnp.issubdtype(f.dtype, jnp.floating)
                    else f for f in facc]
        return facc, mut, pures, key

    return multi


def make_scan_fn(raw_step, steps, feed_stacked, reduce_fetches="last"):
    """The (unjitted) K-step ``lax.scan`` wrapper over a whole-block step
    — ONE set of scan semantics shared by ``Executor.run_repeated`` and
    ``ParallelEngine`` (which adds mesh shardings when jitting it):
    donated state + RNG chain ride the carry exactly as the unrolled
    sequence would thread them; with ``feed_stacked`` the feeds are the
    scanned xs (one real minibatch per iteration), else they close over
    the body as constants.

    ``reduce_fetches``: "last" (default) returns the final iteration's
    fetch values; "mean"/"sum" accumulate float fetches ACROSS the K
    steps in the carry (window-mean loss for logging, aggregated eval
    metrics) — non-float fetches always report the last step's value."""
    _check_reduce(reduce_fetches)

    def _acc(old, new):
        if reduce_fetches == "last" or not jnp.issubdtype(
                jnp.asarray(new).dtype, jnp.floating):
            return new
        return old + new

    def multi(feeds, const_vals, mut_vals, rng_key):
        # fetches/pure ride the CARRY (init zeros of the step's output
        # shapes), not stacked scan ys: only the last step's values are
        # wanted (or a running reduction), and a [K, ...] stacked
        # buffer per fetch would shrink the usable batch size
        step_feeds = [f[0] for f in feeds] if feed_stacked else feeds
        out_sh = jax.eval_shape(raw_step, step_feeds, const_vals,
                                mut_vals, rng_key)
        zeros = lambda tree: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), tree)

        def body(carry, xs):
            mut, key, facc, _p = carry
            fetches, new_mut, new_pure, new_key = raw_step(
                xs if feed_stacked else feeds, const_vals, mut, key)
            facc = [_acc(o, n) for o, n in zip(facc, fetches)]
            return (new_mut, new_key, facc, new_pure), None

        (mut, key, fetches, pures), _ = jax.lax.scan(
            body, (mut_vals, rng_key, zeros(out_sh[0]),
                   zeros(out_sh[2])),
            feeds if feed_stacked else None, length=steps)
        if reduce_fetches == "mean":
            fetches = [f / steps if jnp.issubdtype(f.dtype, jnp.floating)
                       else f for f in fetches]
        return fetches, mut, pures, key

    return multi


def analyze_block(program: Program, feed_names, fetch_names, scope,
                  mesh=None, data_axis="data", model_axis="model",
                  seq_axis="seq"):
    """Classify block vars into feeds / read-only state / read-write state /
    write-only persistables, and build the pure whole-block step function.
    Shared by the single-device Executor and the mesh ParallelEngine — the
    analog of Executor::Prepare (executor.cc:362) + the var-creation pass
    (executor.cc:154), done once per (program, feed signature).

    Returns (feed_names, fetch_names, const_state, mut_state, pure_written,
    needs_rng, step) where step(feeds, const_vals, mut_vals, rng) ->
    (fetches, new_mut, new_pure, new_rng) is jit-able.
    """
    block = program.global_block()
    feed_names = sorted(feed_names)

    produced = set(feed_names)
    external: List[str] = []
    needs_rng = False

    # read/write semantics (incl. control-flow sub-blocks) live in ONE
    # place — core/program.py op_effects — shared with analysis/lint.py

    def op_uses_rng(op):
        if get_op(op.type).uses_rng:
            return True
        if "sub_block" in op.attrs:
            return any(op_uses_rng(s) for s in
                       program.block(op.attrs["sub_block"]).ops)
        return False

    all_blocks_ops = [(block, op) for op in block.ops]
    for blk, op in all_blocks_ops:
        if not has_op(op.type):
            raise KeyError("op %r has no registered lowering" % op.type)
        if op_uses_rng(op):
            needs_rng = True
        reads, writes = op_effects(program, op)
        for n in reads:
            if n not in produced and n not in external:
                external.append(n)
        produced.update(writes)

    def _find_var(name):
        v = block.vars.get(name)
        if v is not None:
            return v
        for b in program.blocks:
            if name in b.vars:
                return b.vars[name]
        return None

    written = []
    seen_w = set()
    for blk, op in all_blocks_ops:
        for n in op_effects(program, op)[1]:
            if n in seen_w:
                continue
            var = _find_var(n)
            persist = (var is not None and var.persistable) or (
                var is None and scope.has_var(n)
            )
            if persist:
                written.append(n)
                seen_w.add(n)

    for n in fetch_names:
        if n not in produced and n not in external:
            external.append(n)  # fetch straight from scope state

    missing = [n for n in external if not scope.has_var(n)]
    if missing:
        raise RuntimeError(
            "uninitialized variables %s: run the startup program first" % missing
        )

    mut_state = [n for n in external if n in seen_w]
    const_state = [n for n in external if n not in seen_w]
    pure_written = [n for n in written if n not in external]

    amp = bool(getattr(program, "amp", False))
    accum = int(getattr(program, "grad_accum_steps", 1))

    if accum > 1:
        step = _accum_step(program, block, feed_names, fetch_names,
                           const_state, mut_state, pure_written, amp, accum,
                           mesh, data_axis, model_axis, seq_axis)
    else:
        def step(feeds, const_vals, mut_vals, rng):
            env: Dict[str, Any] = {}
            env.update(zip(const_state, const_vals))
            env.update(zip(mut_state, mut_vals))
            env.update(zip(feed_names, feeds))
            ctx = LowerContext(block, rng, amp=amp, mesh=mesh,
                               data_axis=data_axis, model_axis=model_axis,
                               seq_axis=seq_axis)
            lower_block(ctx, block, env)
            missing_f = [n for n in fetch_names if n not in env]
            if missing_f:
                raise KeyError(
                    "fetch vars %s were not produced at the top level — a "
                    "var internal to a recompute/control-flow sub-block "
                    "cannot be fetched; fetch a segment output or disable "
                    "recompute for this run" % missing_f)
            fetches = [env[n] for n in fetch_names]
            new_mut = [env[n] for n in mut_state]
            new_pure = [env[n] for n in pure_written]
            out_rng = ctx.final_rng() if ctx.rng_used else rng
            return fetches, new_mut, new_pure, out_rng

    return (feed_names, fetch_names, const_state, mut_state, pure_written,
            needs_rng, step)


def _accum_step(program, block, feed_names, fetch_names, const_state,
                mut_state, pure_written, amp, k, mesh=None,
                data_axis="data", model_axis="model", seq_axis="seq"):
    """Gradient-accumulation step: lax.scan the compute ops (forward +
    backward) over k microbatch slices of the feeds, average the float
    values crossing into the optimize-role ops (the gradients), and run
    those ops once. TPU-native analog of the reference's
    ir/multi_batch_merge_pass.cc (which clones the forward k times and
    inserts grad-averaging ops into the graph instead)."""
    from .lowering import lower_ops

    scan_ops = [op for op in block.ops
                if op.attrs.get("__op_role__") != "optimize"]
    apply_ops = [op for op in block.ops
                 if op.attrs.get("__op_role__") == "optimize"]

    written_scan = {n for op in scan_ops for n in op.output_names()}
    read_apply = {n for op in apply_ops for n in op.input_names()}
    # values flowing compute -> update (gradients, plus anything else the
    # apply side reads that the scan side computes)
    boundary = sorted(read_apply & written_scan)
    # gradients are exactly the backward-role outputs (append_backward tags
    # every grad op — core/backward.py); only those get microbatch-averaged.
    # Other crossing values (metric/counter state an optimize op happens to
    # read) keep their final-microbatch value instead of a silent average.
    grad_names = {n for op in scan_ops
                  if op.attrs.get("__op_role__") == "backward"
                  for n in op.output_names()}
    scan_fetch = [n for n in fetch_names
                  if n in written_scan and n not in boundary]
    scan_pure = [n for n in pure_written if n in written_scan]
    ys_names = boundary + scan_fetch + scan_pure

    def step(feeds, const_vals, mut_vals, rng):
        mb_feeds = []
        mb_size = None
        for name, f in zip(feed_names, feeds):
            b = f.shape[0] if f.ndim else 0
            if f.ndim == 0 or b % k:
                raise ValueError(
                    "feed %r batch dim %s is not divisible by "
                    "gradient accumulation steps %d" % (name, b, k))
            mb_size = b // k
            mb_feeds.append(f.reshape((k, b // k) + f.shape[1:]))

        def body(carry, xs):
            rng_c, mut_c = carry
            env = {}
            env.update(zip(const_state, const_vals))
            env.update(zip(mut_state, mut_c))
            env.update(zip(feed_names, xs))
            ctx = LowerContext(block, rng_c, amp=amp, mesh=mesh,
                               data_axis=data_axis, model_axis=model_axis,
                               seq_axis=seq_axis)
            lower_ops(ctx, scan_ops, env)
            new_rng = ctx.final_rng() if ctx.rng_used else rng_c
            new_mut = [env.get(n, m) for n, m in zip(mut_state, mut_c)]
            ys = [env[n] for n in ys_names]
            return (new_rng, new_mut), ys

        (rng, scan_mut), ys = jax.lax.scan(body, (rng, list(mut_vals)),
                                           mb_feeds)

        env = {}
        env.update(zip(const_state, const_vals))
        env.update(zip(mut_state, scan_mut))
        env.update(zip(feed_names, feeds))  # full batch, if apply reads one
        for name, stacked in zip(ys_names, ys):
            # gradients average over microbatches (the global-batch mean,
            # since each microbatch loss is a mean); per-example fetches
            # ([k, mb, ...]) concatenate back to full-batch order; scalar
            # float fetches average (reported global-batch mean); stateful
            # leftovers (counters, metric states) keep the last value
            if name in scan_fetch and stacked.ndim >= 2 and \
                    stacked.shape[1] == mb_size:
                # per-example concat wins over grad-averaging: a fetched
                # *activation* gradient keeps its full-batch examples
                env[name] = stacked.reshape((-1,) + stacked.shape[2:])
            elif name in grad_names:
                env[name] = jnp.mean(stacked, axis=0)
            elif name in scan_fetch and \
                    jnp.issubdtype(stacked.dtype, jnp.floating):
                env[name] = jnp.mean(stacked, axis=0)
            else:
                env[name] = stacked[-1]

        ctx = LowerContext(block, rng, amp=amp, mesh=mesh,
                           data_axis=data_axis, model_axis=model_axis,
                           seq_axis=seq_axis)
        lower_ops(ctx, apply_ops, env)
        fetches = [env[n] for n in fetch_names]
        new_mut = [env[n] for n in mut_state]
        new_pure = [env[n] for n in pure_written]
        out_rng = ctx.final_rng() if ctx.rng_used else rng
        return fetches, new_mut, new_pure, out_rng

    return step


def _feed_host_array(name: str, val, var) -> np.ndarray:
    """Host-side half of feed conversion: dtype coercion to the on-device
    dtype with the explicit int64 range check (instead of jnp's silent
    truncation warning). The result is ready for a batched
    ``jax.device_put``."""
    want = as_jax_dtype(var.dtype) if var is not None else None
    arr = np.asarray(val)
    if arr.size and arr.dtype.itemsize == 8:
        if var is not None and var.dtype in ("int64", "uint64"):
            dev_dt = "int32" if var.dtype == "int64" else "uint32"
        elif var is None and arr.dtype.kind in "iu":
            # no var info (e.g. DevicePrefetcher without `program`): x64
            # is disabled so device_put will narrow int64->int32 anyway;
            # range-check here too instead of silent wraparound
            dev_dt = "int32" if arr.dtype.kind == "i" else "uint32"
        else:
            dev_dt = None
        if dev_dt is not None:
            info = np.iinfo(dev_dt)
            lo, hi = arr.min(), arr.max()
            if lo < info.min or hi > info.max:
                raise OverflowError(
                    "feed %r has values in [%d, %d], outside the device "
                    "%s range [%d, %d]; ids this large need the "
                    "distributed sparse table path "
                    "(distributed/transpiler.py)"
                    % (name, lo, hi, dev_dt, info.min, info.max))
    if want is not None and arr.dtype != want:
        arr = np.asarray(arr, dtype=want)
    return arr


def _feed_to_device(name: str, val, var):
    """Convert ONE feed to a device array at its on-device dtype (kept for
    per-array callers: the pipelined loop's window stacking, and the
    ParallelEngine for feeds that are device arrays already; the
    executor's own hot path batches via feeds_to_device)."""
    want = as_jax_dtype(var.dtype) if var is not None else None
    if isinstance(val, jax.Array):
        # right dtype passes through; wrong dtype casts DEVICE-side —
        # never a host round-trip (matching feeds_to_device)
        return val if (want is None or val.dtype == want) \
            else jnp.asarray(val, dtype=want)
    return jnp.asarray(_feed_host_array(name, val, var), dtype=want)


# feed-observer hook: callables invoked with every raw feed dict an
# Executor converts (run/run_repeated/cost_analysis — once per _gather).
# The consumer is value-range calibration (analysis/ranges.Calibration
# records observed per-var min/max over N feed batches); anything else
# wanting a data-shaped tap can register too. Process-wide, like the
# default scope.
_FEED_OBSERVERS: List[Any] = []


def add_feed_observer(fn) -> None:
    """Register ``fn(feed_dict)`` to be called with every raw feed an
    executor in this process converts. Pair with
    ``remove_feed_observer`` (or use ``Calibration.attach()``)."""
    _FEED_OBSERVERS.append(fn)


def remove_feed_observer(fn) -> None:
    """Unregister a feed observer (no-op if not registered)."""
    try:
        _FEED_OBSERVERS.remove(fn)
    except ValueError:
        pass


def feeds_to_device(feed: Dict[str, Any], var_lookup, device=None):
    """Convert a whole feed dict with ONE ``jax.device_put`` pytree call
    (one transfer program instead of a blocking ``jnp.asarray`` per
    array), committed to ``device`` when given. Values already on device
    at the right dtype pass through untouched; device arrays at the
    wrong dtype cast device-side. Returns ``(dict, h2d_bytes)`` — bytes
    actually staged for transfer (pass-throughs cost nothing). Shared by
    ``Executor._gather`` and ``core.pipeline.DevicePrefetcher``."""
    out: Dict[str, Any] = {}
    host: Dict[str, np.ndarray] = {}
    for n, v in feed.items():
        var = var_lookup(n)
        want = as_jax_dtype(var.dtype) if var is not None else None
        if isinstance(v, jax.Array):
            # device-side cast when needed; never a host round-trip
            out[n] = v if (want is None or v.dtype == want) \
                else jnp.asarray(v, dtype=want)
        else:
            host[n] = _feed_host_array(n, v, var)
    nbytes = sum(a.nbytes for a in host.values())
    if host:
        fault_point("device_put")
        if _tr.trace_enabled():
            with _tr.trace_span("executor.h2d", bytes=nbytes,
                                feeds=len(host)):
                out.update(jax.device_put(host, device))
        else:
            out.update(jax.device_put(host, device))
    return out, nbytes


def _require(scope: Scope, name: str):
    v = scope.find_var(name)
    if v is None:
        raise RuntimeError("variable %r is not initialized in scope" % name)
    return v


warnings.filterwarnings(
    "ignore", message=".*donated.*", category=UserWarning, module="jax"
)
