"""ParallelEngine: sharded whole-step execution over a device Mesh.

Reference analog: ParallelExecutor (parallel_executor.cc:184) + the SSA
executors (details/threaded_ssa_graph_executor.cc). The reference keeps one
scope per device, threads per op, NCCL comm per device, and a dataflow
scheduler; here ONE jitted step function is compiled with sharding
annotations and the XLA SPMD partitioner + runtime replace all of it:

  - per-device scopes           -> sharded jax.Arrays (one logical value)
  - BCastParamsToDevices        -> replicated NamedSharding on state
  - AllReduceOpHandle / NCCL    -> compiler-inserted ICI all-reduce (psum)
  - ThreadedSSAGraphExecutor    -> XLA schedule inside one executable
  - ScaleLossGradOpHandle (1/N) -> not needed: the step computes the global
                                   -batch mean, sharded over the data axis
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.executor import (RNG_VAR, Executor, _call_span,
                             _dispatch_guard, _feed_host_array,
                             _feed_to_device, _loaded, _prepare_span,
                             analyze_block,
                             make_scan_fn, plan_tag, unstack_singleton_feed,
                             validate_stacked_feeds)
from ..core.program import Program, Variable
from ..core.scope import Scope, global_scope
from ..observe import trace as _tr
from .sharding import ShardingRules

__all__ = ["ParallelEngine", "make_mesh"]


def make_mesh(devices=None, axis_names: Tuple[str, ...] = ("data",),
              shape: Optional[Tuple[int, ...]] = None) -> Mesh:
    """Build a device mesh (NCCLContextMap analog, nccl_helper.h:86 — but a
    logical topology handed to the compiler, not a table of comms/streams)."""
    devices = list(devices) if devices is not None else list(jax.devices())
    if shape is None:
        shape = (len(devices),) + (1,) * (len(axis_names) - 1)
    arr = np.array(devices).reshape(shape)
    return Mesh(arr, axis_names)


class _ParallelPlan:
    def __init__(self, feed_names, fetch_names, const_state, mut_state,
                 pure_written, needs_rng, fn, feed_shardings, state_shardings):
        self.feed_names = feed_names
        self.fetch_names = fetch_names
        self.const_state = const_state
        self.mut_state = mut_state
        self.pure_written = pure_written
        self.needs_rng = needs_rng
        self.fn = fn
        self.feed_shardings = feed_shardings      # name -> NamedSharding
        self.state_shardings = state_shardings    # name -> NamedSharding
        self.hlo_text = {}  # stage -> lowered_hlo() text cache
        self.step = None   # raw (unjitted) step — run_repeated scans it
        self.multi = {}    # (steps, feed_stacked) -> jitted K-step fn
        self.feed_shapes = {}  # name -> shape the plan was prepared with
        # what Executor's _dispatch_guard reads of a plan: the tag the
        # executor.dispatch span carries, the signatures dispatched
        # before (a first dispatch compiles: the watchdog's longer grace),
        # its loading dispatches so far and how its arguments sat at the
        # last of them
        self.sig = None
        self.compiled_sigs = set()
        self.loads = {}
        self.load_args = {}


class ParallelEngine:
    def __init__(self, program: Program, loss_name: Optional[str] = None,
                 build_strategy=None, places=None, mesh: Optional[Mesh] = None,
                 rules: Optional[ShardingRules] = None):
        self.program = program
        self.loss_name = loss_name
        self.build_strategy = build_strategy
        if mesh is None:
            devices = list(jax.devices())
            if places is not None and len(places) > 0 and len(places) <= len(devices):
                devices = devices[: len(places)]
            mesh = make_mesh(devices)
        self.mesh = mesh
        self.rules = rules or ShardingRules()
        self._cache: Dict[Tuple, _ParallelPlan] = {}
        from ..observe.families import ENGINE_DEVICES

        ENGINE_DEVICES.set(self.device_count)
        _tr.watch_program_loads()

    @property
    def device_count(self) -> int:
        return int(np.prod(list(self.mesh.shape.values())))

    # ------------------------------------------------------------------ run
    def run(self, feed, fetch_list, scope: Optional[Scope] = None,
            return_numpy: bool = True):
        scope = scope if scope is not None else global_scope()
        with _call_span("run", 1):
            plan, feeds, const_state, mut_state, rng = self._gather(
                feed, fetch_list, scope)
            return self._execute(plan, plan.fn,
                                 [plan.feed_shardings[n]
                                  for n in plan.feed_names],
                                 feeds, const_state, mut_state, rng, scope,
                                 return_numpy, "", steps=1)

    def run_repeated(self, feed, fetch_list, scope: Optional[Scope] = None,
                     steps: int = 1, return_numpy: bool = True,
                     feed_stacked: bool = False,
                     reduce_fetches: str = "last"):
        """K sharded train steps as ONE SPMD executable (`lax.scan` over
        the partitioned whole-block step, donated state carry) — one
        host dispatch per K steps, composed with the engine's mesh
        sharding. Semantics match K sequential ``run`` calls exactly
        (state, RNG chain; fetches are the last step's, or the window
        mean/sum with ``reduce_fetches``) — see
        ``Executor.run_repeated``. With ``feed_stacked=True`` every feed
        carries a leading ``steps`` axis (one REAL minibatch per
        iteration, ``reader.stack_feed_window`` builds it); the stacked
        axis is unsharded and each per-step slice keeps the feed's data-
        axis sharding."""
        from ..core.executor import _check_reduce

        _check_reduce(reduce_fetches)
        scope = scope if scope is not None else global_scope()
        if steps <= 1:
            if feed_stacked:
                feed = unstack_singleton_feed(feed)
            return self.run(feed, fetch_list, scope, return_numpy)
        with _call_span("run_repeated", steps):
            plan, feeds, const_state, mut_state, rng = self._gather(
                feed, fetch_list, scope)
            if feed_stacked:
                validate_stacked_feeds(plan.feed_names, feeds, steps)
            fn, feed_in = self._multi_fn(plan, steps, feed_stacked,
                                         reduce_fetches)
            return self._execute(plan, fn, feed_in, feeds, const_state,
                                 mut_state, rng, scope, return_numpy,
                                 " after %d scanned steps" % steps,
                                 steps=steps)

    def _multi_fn(self, plan, steps, feed_stacked,
                  reduce_fetches="last"):
        """The jitted sharded K-step scan for a plan plus the feed
        shardings its inputs expect — the (fn, feed_in) pair is cached
        per (steps, feed_stacked, reduce) so the steady-state dispatch
        is a dict lookup, not a per-call respec of the feed
        shardings."""
        cached = plan.multi.get((steps, feed_stacked, reduce_fetches))
        if cached is not None:
            return cached
        mesh, repl = self.mesh, NamedSharding(self.mesh, P())
        if feed_stacked:
            # leading K axis unsharded; per-step slices take the spec of
            # their UNSTACKED shape — plan.feed_shardings was computed
            # from the stacked [K, ...] shapes, where batch-dim-0
            # sharding falls back to replicated (K rarely divides the
            # mesh), which would silently serialize data parallelism
            feed_in = [
                NamedSharding(mesh, P(None, *self.rules.feed_spec(
                    plan.feed_shapes[n][1:], mesh, name=n)))
                for n in plan.feed_names
            ]
        else:
            feed_in = [plan.feed_shardings[n] for n in plan.feed_names]
        in_shardings = (
            feed_in,
            [plan.state_shardings[n] for n in plan.const_state],
            [plan.state_shardings[n] for n in plan.mut_state],
            repl,
        )
        out_shardings = (
            [repl for _ in plan.fetch_names],
            [plan.state_shardings[n] for n in plan.mut_state],
            [repl for _ in plan.pure_written],
            repl,
        )
        with mesh:
            fn = jax.jit(make_scan_fn(plan.step, steps, feed_stacked,
                                      reduce_fetches),
                         in_shardings=in_shardings,
                         out_shardings=out_shardings,
                         donate_argnums=(2,))
        plan.multi[(steps, feed_stacked, reduce_fetches)] = (fn, feed_in)
        return fn, feed_in

    def _execute(self, plan, fn, feed_shardings, feeds, const_state,
                 mut_state, rng, scope, return_numpy, nan_suffix,
                 steps=1):
        """Place what is not yet where the plan wants it (a host feed
        goes to its sharding in one transfer; an array already committed
        to the plan's sharding, as everything a step wrote is, IS the
        argument), run one compiled dispatch, write the new state back
        to the scope. The dispatch goes through the
        Executor's guard (heartbeat, ``executor.dispatch`` fault point
        and span: a wedged mesh dispatch must be as visible to the
        watchdog as a one-chip one), and the epilogue (state write-back,
        numpy conversion, FLAGS_check_nan_inf) is the Executor's too —
        the mesh path must not lose the NaN tripwire the plain path
        has."""
        from ..observe import observe_feed_gap
        from ..observe.families import (ENGINE_DISPATCHES,
                                        ENGINE_RUN_SECONDS,
                                        EXECUTOR_COMPILE_SECONDS,
                                        EXECUTOR_STEPS)

        observe_feed_gap()
        site = "run_repeated" if steps > 1 else "run"
        ENGINE_DISPATCHES.labels(site=site).inc()
        EXECUTOR_STEPS.inc(steps)
        t_dispatch = time.perf_counter()
        with _tr.trace_span("executor.place") as sp:
            count = [0, 0, 0]  # arrays and bytes placed, arrays resident

            def put(v, sharding):
                # the previous call's outputs carry the out_shardings
                # they are now put to: device_put would hand the same
                # object back, after its dispatch overhead
                if isinstance(v, jax.Array) and v.sharding == sharding:
                    count[2] += 1
                    return v
                count[0] += 1
                count[1] += int(getattr(v, "nbytes", 0))
                return jax.device_put(v, sharding)

            def put_kept(name, v, sharding):
                # what no step writes stays in the scope as placed, so
                # the next call finds it resident
                placed = put(v, sharding)
                if placed is not v:
                    scope.set_var(name, placed)
                return placed

            feeds = [put(v, s) for v, s in zip(feeds, feed_shardings)]
            const_state = [put_kept(n, v, plan.state_shardings[n])
                           for n, v in zip(plan.const_state, const_state)]
            mut_state = [put(v, plan.state_shardings[n])
                         for n, v in zip(plan.mut_state, mut_state)]
            rng = put_kept(RNG_VAR, rng, NamedSharding(self.mesh, P()))
            if sp.attrs is not None:
                (sp.attrs["arrays"], sp.attrs["bytes"],
                 sp.attrs["resident"]) = count

        # one executable per jitted fn of a plan: its first dispatch
        # compiles, which the heartbeat tells the watchdog
        sig = (site, fn)
        t_call = time.perf_counter()
        # the first dispatch also notes the way to the plan's name table
        # (observe/device_names.py): the one step's text shares
        # lowered_hlo's slot
        with _dispatch_guard(plan, sig, (feeds, const_state, mut_state,
                                         rng), fn=fn,
                             hlo_key=("optimized", 1, False)
                             if steps <= 1 else None,
                             within=self.mesh) as (loads, _):
            fetches, new_mut, new_pure, new_rng = fn(
                feeds, const_state, mut_state, rng)
        t_done = time.perf_counter()
        # a dispatch that loaded its program is a compile-time sample,
        # as on the one-chip path
        if _loaded(plan, sig, loads):
            EXECUTOR_COMPILE_SECONDS.observe(t_done - t_call)
        ENGINE_RUN_SECONDS.labels(site=site).observe(t_done - t_dispatch)
        return Executor._finish(plan, scope, fetches, new_mut, new_pure,
                                new_rng, return_numpy, nan_suffix)

    def lowered_hlo(self, feed, fetch_list, scope: Optional[Scope] = None,
                    stage: str = "optimized", steps: int = 1,
                    feed_stacked: bool = False) -> str:
        """Post-SPMD-partitioner HLO text of the sharded step (or the
        pre-XLA ``"stablehlo"``). Golden-structure tests assert the
        data-parallel gradient all-reduces are present — the CPU-side
        tripwire for a dropped sharding rule (see Executor.lowered_hlo).
        ``steps > 1`` lowers the K-step ``run_repeated`` scan instead
        (pass the stacked feed when ``feed_stacked``) — collectives and
        donation must survive inside the scan body too."""
        if stage not in ("stablehlo", "optimized"):
            raise ValueError("stage must be 'stablehlo' or 'optimized', "
                             "got %r" % (stage,))
        if steps <= 1 and feed_stacked:
            raise ValueError(
                "steps=1 with feed_stacked has no scanned executable "
                "(run_repeated unstacks and runs the plain step) — "
                "lower the unstacked feed instead")
        scope = scope if scope is not None else global_scope()
        plan, feeds, const_state, mut_state, rng = self._gather(
            feed, fetch_list, scope)
        fn = plan.fn
        if steps > 1:
            if feed_stacked:
                validate_stacked_feeds(plan.feed_names, feeds, steps)
            fn, _ = self._multi_fn(plan, steps, feed_stacked)
        key = (stage, steps, feed_stacked)

        args = (feeds, const_state, mut_state, rng)
        if stage == "optimized":
            from ..observe import device_names

            def lower():
                with self.mesh:
                    return fn.lower(*args)

            return device_names.optimized_text(plan, key, lower)
        if key not in plan.hlo_text:
            with self.mesh:
                plan.hlo_text[key] = fn.lower(*args).as_text()
        return plan.hlo_text[key]

    def _with_ext_rules(self) -> ShardingRules:
        return merged_ext_rules(self.program, self.mesh, self.rules)

    def _gather(self, feed, fetch_list, scope):
        """Shared run()/lowered_hlo() plumbing: feed conversion (a host
        feed stays on the host at its device dtype), plan cache lookup,
        state/RNG gathering (the scope's own objects; ``_execute`` then
        places what is not where the plan wants it) — the
        ``executor.gather`` span."""
        with _tr.trace_span("executor.gather"):
            return self._gather_args(feed, fetch_list, scope)

    def _gather_args(self, feed, fetch_list, scope):
        feed = feed or {}
        fetch_names = [
            v.name if isinstance(v, Variable) else str(v)
            for v in (fetch_list or [])
        ]
        block = self.program.global_block()
        feed_vals = {
            n: _feed_for_placement(n, v, block.vars.get(n))
            for n, v in feed.items()
        }
        key = self._cache_key(feed_vals, fetch_names)
        plan = self._cache.get(key)
        if plan is None:
            from ..observe.families import (EXECUTOR_CACHE_MISSES,
                                            EXECUTOR_PREPARE_SECONDS)

            EXECUTOR_CACHE_MISSES.inc()
            t0 = time.perf_counter()
            sig = plan_tag(key)
            with _prepare_span(sig, self.program) as sp:
                plan = self._prepare(feed_vals, fetch_names, scope)
                if sp.attrs is not None:
                    # the mesh engine runs no pass pipeline: the block
                    # is lowered as given
                    sp.attrs["ops_out"] = sp.attrs["ops_in"]
            plan.sig = sig
            EXECUTOR_PREPARE_SECONDS.observe(time.perf_counter() - t0)
            self._cache[key] = plan
        feeds = [feed_vals[n] for n in plan.feed_names]
        const_state = [_require(scope, n) for n in plan.const_state]
        mut_state = [_require(scope, n) for n in plan.mut_state]
        rng = scope.find_var(RNG_VAR)
        if rng is None:
            seed = (self.program.random_seed
                    if self.program.random_seed is not None else 0)
            rng = jax.random.PRNGKey(seed)
        return plan, feeds, const_state, mut_state, rng

    # -------------------------------------------------------------- prepare
    def _cache_key(self, feed_vals, fetch_names):
        sig = tuple(sorted((n, v.shape, str(v.dtype)) for n, v in feed_vals.items()))
        return (self.program._serial, self.program.version, sig,
                tuple(fetch_names))

    def _prepare(self, feed_vals, fetch_names, scope) -> _ParallelPlan:
        (feed_names, fetch_names, const_state, mut_state, pure_written,
         needs_rng, step) = analyze_block(
            self.program, sorted(feed_vals), fetch_names, scope,
            mesh=self.mesh, data_axis=self.rules.data_axis,
            model_axis=getattr(self.rules, "model_axis", "model"),
            seq_axis=getattr(self.rules, "seq_axis", "seq"))

        mesh = self.mesh
        repl = NamedSharding(mesh, P())
        feed_shardings = {
            n: NamedSharding(mesh, self.rules.feed_spec(
                feed_vals[n].shape, mesh, name=n))
            for n in feed_names
        }
        rules = self._with_ext_rules()
        state_shardings = {}
        for n in const_state + mut_state:
            v = scope.find_var(n)
            shape = getattr(v, "shape", None)
            state_shardings[n] = NamedSharding(mesh, rules.spec_for(n, shape, mesh))

        in_shardings = (
            [feed_shardings[n] for n in feed_names],
            [state_shardings[n] for n in const_state],
            [state_shardings[n] for n in mut_state],
            repl,
        )
        out_shardings = (
            [repl for _ in fetch_names],
            [state_shardings[n] for n in mut_state],
            [repl for _ in pure_written],
            repl,
        )
        with mesh:
            fn = jax.jit(step, in_shardings=in_shardings,
                         out_shardings=out_shardings, donate_argnums=(2,))
        plan = _ParallelPlan(feed_names, fetch_names, const_state, mut_state,
                             pure_written, needs_rng, fn,
                             feed_shardings, state_shardings)
        plan.step = step
        plan.feed_shapes = {n: tuple(feed_vals[n].shape) for n in feed_names}
        return plan


def merged_ext_rules(program, mesh, rules: ShardingRules) -> ShardingRules:
    """User rules + automatic stage/expert sharding: parameters the
    `layers.pipeline` / `layers.moe_ffn` layers created stacked are
    sharded over the 'pipe' / 'expert' mesh axis (leading dim), and —
    via prefix match — so are their optimizer accumulator slots (named
    '<param>_<slot>'; slots whose shape the axis doesn't divide, like
    beta-pow scalars, fall back to replicated inside spec_for). User
    rules are matched first, so an explicit rule for a stacked param
    wins. Module-level so the TPU-lowering tests shard state exactly
    the way the engine compiles it (works with AbstractMesh too)."""
    import re as _re

    ext = []
    for attr, axis in (("_pipeline_params", "pipe"),
                       ("_expert_params", "expert")):
        if axis not in mesh.axis_names:
            continue
        for pname in getattr(program, attr, ()):
            ext.append(("^" + _re.escape(pname), P(axis)))
    # ZeRO-1: one exact-name rule per RECORDED optimizer accumulator
    # (optimizer.py _add_accumulator fills Program._optimizer_slots) —
    # scoping by the program's own records means a user parameter that
    # happens to be named '*_moment_0' can never be swept in. Appended
    # after user rules, so an explicit rule for a slot wins; slots the
    # axis doesn't divide (beta-pow scalars, odd dims) fall back to
    # replicated inside spec_for.
    if getattr(rules, "zero1", False) \
            and rules.data_axis in mesh.axis_names:
        for sname in sorted(getattr(program, "_optimizer_slots", ())):
            ext.append(("^" + _re.escape(sname) + "$",
                        P(rules.data_axis)))
    if not ext:
        return rules
    merged = ShardingRules(data_axis=rules.data_axis,
                           model_axis=getattr(rules, "model_axis", "model"),
                           seq_axis=getattr(rules, "seq_axis", "seq"),
                           zero1=getattr(rules, "zero1", False))
    merged.rules = list(rules.rules) + [
        (_re.compile(pat), spec) for pat, spec in ext]
    merged.feed_rules = list(rules.feed_rules)
    return merged


def _feed_for_placement(name, val, var):
    """ONE feed at its on-device dtype, ready for ``_execute`` to place:
    a ``jax.Array`` as ``_feed_to_device`` leaves it (cast on the device
    if the dtype differs); anything else converted on the HOST (the
    int64 range check included) and left there, so its placement is one
    transfer to the feed's sharding and not one to chip 0 and a scatter
    from there."""
    if isinstance(val, jax.Array):
        return _feed_to_device(name, val, var)
    arr = _feed_host_array(name, val, var)
    # the dtype jnp.asarray would give it (x64 is off: 64 bits narrow),
    # which is what the plan cache keys
    return arr.astype(jax.dtypes.canonicalize_dtype(arr.dtype), copy=False)


def _require(scope, name):
    v = scope.find_var(name)
    if v is None:
        raise RuntimeError("variable %r is not initialized in scope" % name)
    return v
