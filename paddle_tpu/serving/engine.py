"""Continuous batching for autoregressive GPT decode.

``models/gpt.py:generate`` drives one fixed-batch decode loop per
caller: requests that arrive mid-generation wait for the whole loop,
and a finished row idles its slot until the LONGEST request in the
batch completes. On a dispatch-latency-bound device that is the
difference between ~1/B and full utilisation. This engine owns the
batch instead:

* ONE decode executable at fixed ``b_max``
  (``gpt.build_serving_decode_step``): per-slot token/position feeds,
  per-slot visibility masks, per-slot (vmapped) KV-cache writes. The
  B cache rows are B independent slots.
* **Admission** happens at step boundaries, prefill-then-insert: a new
  prompt prefills through a batch=1 ``build_prefill_step`` executable
  (one dispatch, its own scope sharing the weight arrays by name),
  then the slot's cache rows are spliced into the big caches with one
  ``dynamic_update_slice`` per layer tensor, each by its own shape: a
  model with sliding-window layers (``cfg['layer_types']``) keeps
  ``[b_max, n_kv, window, Dh]`` RINGS for those beside the full layers'
  ``[b_max, n_kv, max_len, Dh]`` slabs (docs/SERVING.md "The cache").
  Prefill executables are cached per prompt length
  (``paddle_serving_prefill_programs_total`` counts compiles).
* **Retirement** is immediate: a sequence that hits EOS or its token
  budget frees its slot at that step boundary
  (``paddle_serving_slots_retired_total``); the next queued request is
  admitted into it while the rest of the batch keeps decoding.

Two fleet-tier levers ride the same machinery (docs/SERVING.md "The
fleet tier"):

* **Prefix/KV-cache reuse** — with a :class:`PrefixStore` attached, a
  prompt whose head matches a stored prefix splices the cached K/V
  rows (serving/prefix.py) and prefills only its suffix through ONE
  ``gpt.build_multi_token_decode_step`` dispatch; shared system
  prompts prefill once per fleet, not once per request.
* **Speculative decoding** — with a draft model attached
  (``draft_cfg``/``draft_params``/``spec_k``), greedy requests draft k
  tokens through the draft's own fixed-shape decode executable and the
  target verifies all k in ONE multi-token dispatch; accepted drafts
  advance the slot several tokens per target dispatch. Verification is
  greedy-exact, so outputs stay bitwise ``generate()``'s; speculative
  and plain (sampled) rows coexist in one batch — plain slots ride the
  verify dispatch using only its first position.

**One step in flight** (docs/SERVING.md): while every rider is greedy
and no draft lane is attached, the loop dispatches step n+1 from the
ids step n left on the device and only then reads step n, so feeds,
gather, dispatch and the bookkeeping of a step run while the chip
computes. A sampled rider, a draft lane and every admission keep their
synchronous read.

Requests enter through a bounded ``RequestQueue`` (backpressure,
deadlines over queue time, cancellation — serving/queue.py). The greedy
choice is made on the device (the step's and the prefill's own argmax,
``gpt.NEXT_TOKEN_VAR``): a plain step whose riders are all at
temperature 0 fetches ``b_max`` ids, an admission of a greedy request
one id; the logits cross to the host only for a sampled rider (the
step's ``[b_max, 1, vocab]``, an admission's one last row), whose
sampling is host-side and per-request (its own seeded RandomState)
— ``paddle_serving_fetches_total`` counts which. Either way a
request's output is bitwise what ``generate()`` would produce for it
alone — tests/test_serving.py, tests/test_serving_token_fetch.py and
tests/test_serving_fleet.py pin that
parity with the fleet levers on and off. Occupancy telemetry:
``paddle_serving_slot_occupancy_ratio`` per decode step,
``paddle_serving_slots_active``, tokens/steps/spec/prefix counters
(docs/SERVING.md).
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from ..observe import trace as _tr
from .queue import QueueFull, RequestQueue

__all__ = ["DecodeEngine", "MemoryBudgetExceeded"]


class MemoryBudgetExceeded(QueueFull):
    """Raised at submit when the predicted-bytes admission guard
    refuses a prompt: engine-resident bytes (weights + the decode
    caches, each tensor at its own shape) plus the prompt's predicted
    prefill peak exceed
    the engine's device budget (``device_budget=`` or
    ``PADDLE_TPU_DEVICE_HBM_BYTES``). A ``QueueFull`` subclass so the
    router's per-replica retry treats it like backpressure — but with
    its own counter (``paddle_serving_memory_admissions_denied_total``)
    and router rejection reason (``memory``)."""


# ``program_guard`` switches the PROCESS's default programs: two engines
# of one process (a router's replicas) that build an admission's program
# at the same moment would write their ops into each other's
_BUILD_LOCK = threading.Lock()


@contextlib.contextmanager
def _null_mark(site, compiling):
    """Busy-marker no-op for lanes without a supervising engine."""
    yield


class _Slot:
    """One live sequence bound to a cache row."""

    __slots__ = ("request", "tokens", "target_len", "eos_id",
                 "temperature", "top_k", "rng", "spec", "aboard")

    def __init__(self, request, prompt, n_new, eos_id, temperature,
                 top_k, seed, spec=False):
        self.request = request
        self.tokens = [int(t) for t in prompt]
        self.target_len = len(prompt) + int(n_new)
        self.eos_id = eos_id
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.rng = np.random.RandomState(seed)
        # speculative slots are GREEDY requests while a draft lane is
        # attached: greedy verification is exact, sampled requests
        # take plain per-token steps in the same batch
        self.spec = bool(spec) and self.temperature == 0
        # 1 while a step dispatched for this slot has not been read: its
        # token is still on the device, its position already taken
        self.aboard = 0

    def sample(self, logits_row) -> int:
        """THE sampler generate() uses, applied to this slot's row with
        its private RandomState — a slot decodes bitwise like a B=1
        generate() with the same seed by construction."""
        from ..models.gpt import sample_token

        return sample_token(logits_row, self.rng, self.temperature,
                            self.top_k)

    def finished(self, last_token: int) -> bool:
        return (len(self.tokens) >= self.target_len
                or (self.eos_id is not None and last_token == self.eos_id))

    def ends_by_length(self) -> bool:
        """The steps dispatched for this slot fill its budget: known on
        the host when the last of them goes out, before any is read."""
        return len(self.tokens) + self.aboard >= self.target_len


class _Lane:
    """One model's compiled decode surface: the fixed-``b_max``
    per-slot decode executable, cached per-length prefill and
    multi-token programs, and the donated cache splice. The engine
    holds one lane for the target model and, under speculative
    decoding, a second for the draft — slot i of the draft lane
    mirrors slot i of the target."""

    def __init__(self, fluid, exe, cfg, b_max, max_len, params,
                 scope_guard, gpt, mark=None, role=""):
        from ..core.scope import Scope

        self._fluid, self._exe, self._gpt = fluid, exe, gpt
        self._role = role   # "draft_" on the draft lane: the prefix of
        #                     its serving.engine.build spans' ``program``
        self._scope_guard = scope_guard
        self._mark = mark if mark is not None else _null_mark
        self._warm: set = set()   # (program id, fetch) dispatched once
        self.cfg = cfg
        self._state_layers = len(gpt.state_layers(cfg))
        # (kind, its layers) of the kinds whose prefill scans the prompt
        # in chunks
        held = [gpt.kind_of(cfg, i).name for i in range(cfg["n_layer"])]
        self._scans = [(kind, held.count(kind.name))
                       for kind in gpt.LAYER_KINDS.values()
                       if kind.chunk is not None and kind.name in held]
        self.b_max, self.max_len = b_max, max_len
        self.scope = Scope()
        self._prefill_scope = Scope()
        self._prefill: Dict[int, object] = {}  # P -> prog
        self._suffix: Dict[int, tuple] = {}    # S -> (prog, logits_var)
        self._multi: Dict[int, tuple] = {}     # S -> (prog, logits_var)
        self._decode_prog = fluid.Program()
        dec_start = fluid.Program()
        with scope_guard(self.scope):
            with self._build_span("decode", self._decode_prog), \
                    fluid.program_guard(self._decode_prog, dec_start):
                self._logits, self.cache_names = \
                    gpt.build_serving_decode_step(
                        cfg, batch=b_max, max_len=max_len)
            declared = self._decode_prog.global_block().vars
            given = {n: v for n, v in (params or {}).items()
                     if n in declared}
            self._run_startup(dec_start, self.scope, given.__contains__)
            with _tr.trace_span("serving.engine.load_params") as sp:
                for n, v in given.items():
                    self.scope.set_var(n, v)
                if sp.attrs is not None:
                    sp.attrs["arrays"] = len(given)
                    sp.attrs["bytes"] = sum(
                        int(getattr(v, "nbytes", 0)) for v in given.values())
                    sp.attrs["dtype"] = ",".join(sorted(
                        {str(getattr(v, "dtype", "?"))
                         for v in given.values()}))
        self._note_cache_bytes()
        self._note_weight_bytes()
        import jax

        def _splice(bigs, smalls, idx):
            # each update is the prefill scope's whole batch=1 tensor, so
            # the start is the slot and zeros, at the tensor's own rank
            return [jax.lax.dynamic_update_slice(
                        b, s.astype(b.dtype), (idx,) + (0,) * (b.ndim - 1))
                    for b, s in zip(bigs, smalls)]

        # one compiled dispatch splices a prefilled slot into ALL the
        # big caches; donating them makes the update in-place on device
        self._splice = jax.jit(_splice, donate_argnums=0)

        def _prefix_splice(smalls, rows):
            return [jax.lax.dynamic_update_slice(
                        s, r.astype(s.dtype), (0,) * s.ndim)
                    for s, r in zip(smalls, rows)]

        # same trick on the prefill-scope caches: one donated dispatch
        # writes a stored prefix's rows before the suffix prefill reads
        # them (recompiled per distinct prefix length, like the suffix
        # programs themselves)
        self._prefix_splice = jax.jit(_prefix_splice, donate_argnums=0)

        def _carry(prev_ids, fresh):
            return jax.numpy.where(fresh >= 0, fresh, prev_ids[:, None])

        # the [b_max, 1] token feed of the step after the one that leaves
        # ``prev_ids`` [b_max], built on the device: no id crosses to the
        # host and back. ``fresh`` (int32) holds -1 for a slot that rode
        # that step and the host's token for every other (a slot admitted
        # since: the first token its admission fetched)
        self.carry = jax.jit(_carry)

    def _note_cache_bytes(self) -> None:
        """``paddle_serving_cache_bytes{kind}``: what the caches this
        lane just built hold, by each tensor's name and layer
        (``gpt.cache_kind``): a state-space layer's state and
        convolution rows and a gated convolution's carried rows (no
        position axis), a latent layer's one tensor, rings (shorter than
        ``max_len``) and full slabs."""
        from ..kernels.mla_decode import decode_plan
        from ..observe.families import SERVING_CACHE_BYTES

        block = self._decode_prog.global_block()

        def size(n):
            var = block.var(n)
            return int(np.prod(var.shape)) * np.dtype(var.dtype).itemsize

        held = {"ring": 0, "full": 0, "latent": 0, "state": 0}
        blocks = []     # the absorbed kernel's block, a latent layer
        for n in self.cache_names:
            var = block.var(n)
            kind = self._gpt.cache_kind(self.cfg, n, self.max_len)
            held[kind] += size(n)
            if kind == "latent":
                blocks.append(decode_plan(var.shape, var.dtype,
                                          self.cfg["n_head"]))
        for kind, nbytes in held.items():
            SERVING_CACHE_BYTES.labels(kind=kind).set(nbytes)
        # of the state: what the updates of each kind that has a gauge
        # read and write (0 for a model without such layers)
        made = {n: op for op in block.ops for n in op.output_names()}
        for kind in self._gpt.LAYER_KINDS.values():
            if kind.state_bytes is not None:
                kind.state_bytes.set(sum(
                    size(n) for op in block.ops if op.type == kind.update_op
                    for n in kind.state(op, made)))
        # (latent layers, rows of a block) for
        # paddle_mla_decode_blocks_total: the slabs are of one shape; None
        # where no cache is latent or the kernel has no plan for it
        self.latent_walk = (len(blocks), blocks[0]) \
            if blocks and blocks[0] is not None else None

    def _note_weight_bytes(self) -> None:
        """``paddle_serving_weight_bytes{dtype}``: the decode program's
        parameters by the dtype each is stored in."""
        from ..analysis.memory import dtype_bytes
        from ..observe.families import SERVING_WEIGHT_BYTES

        held: Dict[str, int] = {}
        for p in self._decode_prog.global_block().all_parameters():
            held[str(p.dtype)] = held.get(str(p.dtype), 0) \
                + int(np.prod(p.shape)) * dtype_bytes(str(p.dtype),
                                                      warn=False)
        for dtype, nbytes in held.items():
            SERVING_WEIGHT_BYTES.labels(dtype=dtype).set(nbytes)

    @contextlib.contextmanager
    def _build_span(self, program, prog, **attrs):
        """``serving.engine.build`` round one program's construction
        (the IR only; its startup run is an ``executor.call``):
        ``program`` names which, ``ops`` what came of it."""
        with _tr.trace_span("serving.engine.build",
                            program=self._role + program, **attrs) as sp:
            yield
            if sp.attrs is not None:
                sp.attrs["ops"] = len(prog.global_block().ops)

    def _run_startup(self, start, scope, supplied) -> None:
        """Run a copy of a startup program without its initialisers of
        the names ``supplied(name)`` says the caller is about to set: a
        model whose weights are most of the chip (OLMoE's experts) cannot
        hold a drawn copy beside the given one, even for a moment. An
        initialiser is an op with no inputs whose outputs (it has some)
        are all supplied; every other op stays, and ``start`` itself is
        left as it was built."""
        def skipped(op):
            outs = [n for names in op.outputs.values() for n in names]
            return bool(outs) and all(map(supplied, outs)) and not any(
                n for names in op.inputs.values() for n in names)

        pruned = start.clone()
        block = pruned.global_block()
        block.ops = [op for op in block.ops if not skipped(op)]
        self._exe.run(pruned, scope=scope)

    # ---------------------------------------------------------- dispatch
    def _cold(self, prog, fetch=None) -> bool:
        """True on the FIRST dispatch of a program (with this fetch)
        through this lane (jax trace + XLA compile ride it) — the busy
        marker's compiling-grace signal for replica supervision."""
        key = (id(prog), fetch)
        if key in self._warm:
            return False
        self._warm.add(key)
        return True

    def decode(self, token, pos, greedy=False):
        """Dispatch one plain per-slot decode step and return what it
        will leave on the device, un-awaited (``read`` blocks on it):
        logits [B, 1, vocab], or — ``greedy`` — only the [B] token ids
        the program chose from them (``gpt.NEXT_TOKEN_VAR``). One
        program either way: the fetch list is part of the Executor's
        plan key, and the plan that fetches the ids holds no logits
        output. Each plan compiles at its first use, so ``_cold`` is
        asked per (program, fetch). ``token`` is a host array or the
        device array ``carry`` made: the same shape and dtype on the
        device, so the same plan."""
        fetch = self._gpt.NEXT_TOKEN_VAR if greedy else self._logits
        with self._mark("decode",
                        self._cold(self._decode_prog, greedy)):
            with self._scope_guard(self.scope):
                (out,) = self._exe.run(
                    self._decode_prog, feed={"token": token, "pos": pos},
                    fetch_list=[fetch], scope=self.scope,
                    return_numpy=False)
        return out

    @staticmethod
    def read(out) -> np.ndarray:
        """Block until a dispatched step's fetch is on the host: the
        wait the synchronous ``Executor.run`` makes, under the same
        heartbeat and ``executor.complete`` span."""
        from ..core.executor import _wait_guard

        with _wait_guard():
            return np.asarray(out)

    def multi_decode(self, token, pos):
        """One multi-token step over the big caches (speculative
        verification); logits [B, S, vocab]. ``pos`` rows must be
        contiguous ascending and in-range — the scheduler's fit
        predicate guarantees it."""
        prog, logits_var = self._multi_program(token.shape[1])
        with self._mark("verify", self._cold(prog)):
            with self._scope_guard(self.scope):
                (logits,) = self._exe.run(
                    prog, feed={"token": token, "pos": pos},
                    fetch_list=[logits_var], scope=self.scope)
        return logits

    # ----------------------------------------------------------- prefill
    def prefill_insert(self, slot_idx, prompt, prefix_store=None,
                       prefix_len=None, greedy=False):
        """Admission prefill: fill the prefill scope's batch=1 cache
        rows for the whole prompt — via one full-prompt dispatch, or,
        on a prefix-store hit, a donated splice of the stored rows plus
        one suffix dispatch — then splice the rows into the big caches
        at ``slot_idx`` (ONE jitted donated dispatch for all 2*n_layer
        tensors, rings and slabs alike: each update is the prefill
        scope's whole batch=1 tensor of the same trailing shape; a
        state-space layer's state and convolution rows and a gated
        convolution's carried rows go the same way, so a slot's new
        tenant overwrites ALL of what the last one left).
        Registers ``prompt[:prefix_len]`` with the store on first
        sighting. Returns ``(fetch, value)``, what it brought to
        the host for the first token: ``("tokens", id)``, the last
        prompt position's argmax chosen by the program, when ``greedy``;
        else ``("logits", row)``, that position's logits row for the
        caller's sampler. Every other position's logits stay on the
        device. (The suffix dispatch of a prefix hit still fetches its
        whole [1, S, vocab] and hands back the row.)"""
        import jax.numpy as jnp

        P = prompt.shape[0]
        hit = prefix_store.lookup(prompt) if prefix_store is not None \
            else None
        if hit is not None:
            L, rows = hit
            with _tr.trace_span("serving.engine.suffix_prefill",
                                prompt_len=P, prefix_len=L):
                with self._scope_guard(self._prefill_scope):
                    # the suffix program must exist BEFORE the splice:
                    # its (scratch-scope) startup materializes the
                    # prefill-scope caches on first use, and the
                    # spliced rows must land in the live arrays after
                    prog, logits_var = self._suffix_program(P - L)
                    smalls = [jnp.asarray(self._prefill_scope.find_var(n))
                              for n in self.cache_names]
                    for n, out in zip(
                            self.cache_names,
                            self._prefix_splice(
                                smalls, [jnp.asarray(r) for r in rows])):
                        self._prefill_scope.set_var(n, out)
                    pos = (L + np.arange(P - L,
                                         dtype="int64"))[None, :]
                    (full,) = self._exe.run(
                        prog, feed={"token": prompt[None, L:],
                                    "pos": pos},
                        fetch_list=[logits_var],
                        scope=self._prefill_scope)
            fetch, last = "logits", full[0, P - L - 1]
        else:
            prog = self._prefill_program(P)
            fetch, var = (("tokens", self._gpt.NEXT_TOKEN_VAR) if greedy
                          else ("logits", self._gpt.LAST_LOGITS_VAR))
            attrs = {"prompt_len": P}
            if self._state_layers:
                # the layers whose part of the slot this prefill
                # overwrites whole, whatever the prompt's length
                attrs["state_layers"] = self._state_layers
            for kind, n_layers in self._scans:
                # the chunks each such layer scans the prompt in
                attrs["chunks"] = -(-P // kind.chunk(self.cfg, P))
                if kind.chunks is not None:
                    kind.chunks.inc(n_layers * attrs["chunks"])
            with _tr.trace_span("serving.engine.prefill", **attrs):
                with self._scope_guard(self._prefill_scope):
                    (out,) = self._exe.run(
                        prog, feed={"tokens": prompt[None, :]},
                        fetch_list=[var], scope=self._prefill_scope)
            last = out[0]
        if prefix_store is not None and prefix_len:
            key = prompt[:prefix_len]
            if not prefix_store.contains(key):
                prefix_store.insert(
                    key,
                    [np.asarray(self._prefill_scope.find_var(n))
                     [:, :, :prefix_len]
                     for n in self.cache_names])
        with _tr.trace_span("serving.engine.splice", slot=slot_idx):
            bigs = [jnp.asarray(self.scope.find_var(n))
                    for n in self.cache_names]
            smalls = [jnp.asarray(self._prefill_scope.find_var(n))
                      for n in self.cache_names]
            for n, out in zip(self.cache_names,
                              self._splice(bigs, smalls, slot_idx)):
                self.scope.set_var(n, out)
        return fetch, last

    def prefill_var(self, name):
        """What the prefill programs keep under ``name`` in their own
        scope (None before the first of them ran its startup)."""
        return self._prefill_scope.find_var(name)

    # ---------------------------------------------------------- programs
    def _prefill_program(self, P: int):
        """Batch=1 prefill executable for prompt length P, cached. All
        P's share ONE prefill scope: each layer's [1, n_kv, rows, Dh]
        cache has the same shape for every P, and weights are (re)copied
        from the engine scope after each new program's startup."""
        hit = self._prefill.get(P)
        if hit is not None:
            return hit
        from ..observe.families import SERVING_PREFILL_PROGRAMS

        fluid = self._fluid
        prog, start = fluid.Program(), fluid.Program()
        with self._scope_guard(self._prefill_scope):
            with self._build_span("prefill", prog, P=P), \
                    _BUILD_LOCK, fluid.program_guard(prog, start):
                self._gpt.build_prefill_step(
                    self.cfg, batch=1, prompt_len=P, max_len=self.max_len)
            kept = set(self._shared_names(prog, {"tokens"}))
            tally = self._gpt.COMPACT_CALLS_VAR
            if self._prefill_scope.find_var(tally) is not None:
                # what the earlier prefill programs counted stays
                kept.add(tally)
            self._run_startup(start, self._prefill_scope, kept.__contains__)
            self._share_weights(prog, skip={"tokens"})
        SERVING_PREFILL_PROGRAMS.inc()
        self._prefill[P] = prog
        return prog

    def _suffix_program(self, S: int):
        """Batch=1 multi-token executable for suffix length S, cached
        per S (the prefix hit's un-cached tail). Runs in the SAME
        prefill scope as the full-prompt programs — the splice path is
        identical downstream. The engine's weights are shared in
        EXPLICITLY: a fresh engine whose first admission hits a shared
        prefix store (replica N of a fleet, a restarted replica) has
        never built a full-prefill program, so the scratch-startup
        copy in _build_multi would otherwise leave freshly-initialized
        weights in the prefill scope and silently break the
        bitwise-generate() contract."""
        hit = self._suffix.get(S)
        if hit is not None:
            return hit
        from ..observe.families import SERVING_PREFILL_PROGRAMS

        prog, logits_var = self._build_multi(1, S, self._prefill_scope)
        self._share_weights(prog, skip={"token", "pos"})
        SERVING_PREFILL_PROGRAMS.inc()
        self._suffix[S] = (prog, logits_var)
        return self._suffix[S]

    def _multi_program(self, S: int):
        """Batch=b_max multi-token executable (speculative verify),
        cached per S, sharing the ENGINE scope's live caches and
        weights."""
        hit = self._multi.get(S)
        if hit is not None:
            return hit
        self._multi[S] = self._build_multi(self.b_max, S, self.scope)
        return self._multi[S]

    def _build_multi(self, batch, S, scope):
        """Build a multi-token program against ``scope``, initializing
        ONLY its program-private vars (the unnamed fc biases a fresh
        build mints): its startup runs in a scratch scope and the
        missing vars are copied over — running it in ``scope`` directly
        would re-initialize live weights and zero the caches."""
        from ..core.scope import Scope

        fluid = self._fluid
        prog, start = fluid.Program(), fluid.Program()
        with self._scope_guard(scope):
            with self._build_span("multi", prog, P=S), \
                    _BUILD_LOCK, fluid.program_guard(prog, start):
                logits_var, _ = self._gpt.build_multi_token_decode_step(
                    self.cfg, batch=batch, steps=S, max_len=self.max_len)
        scratch = Scope()
        with self._scope_guard(scratch):
            self._exe.run(start, scope=scratch)
        for n in prog.global_block().vars:
            if scope.find_var(n) is None \
                    and scratch.find_var(n) is not None:
                scope.set_var(n, np.asarray(scratch.find_var(n)))
        return prog, logits_var

    def _share_weights(self, prog, skip):
        """Point the prefill scope at the engine scope's weight ARRAYS
        by name (cheap reference copies); never the caches — their
        batch dim differs."""
        for n in self._shared_names(prog, skip):
            self._prefill_scope.set_var(n, self.scope.find_var(n))

    def _shared_names(self, prog, skip):
        """The program's names the engine scope already holds, the
        caches and ``skip`` apart."""
        skip = set(self.cache_names) | set(skip)
        return [n for n in prog.global_block().vars
                if n not in skip and self.scope.find_var(n) is not None]

    # ------------------------------------------------- memory estimation
    def memory_footprint(self) -> dict:
        """Static byte model of this lane (analysis/memory.py), built
        ONCE at engine construction — never from the submit path, so
        the process-global ``program_guard`` is only ever entered from
        the thread that is already building this engine's programs.

        ``resident``: predicted peak of the decode-step program
        (weights + the 2L cache tensors, each at its declared shape — a
        sliding layer's ring is ``window`` rows, a full layer's slab
        ``max_len`` — + one step's activations).
        ``prefill_extra_lo``/``_hi``: the
        NON-shared bytes a batch=1 prefill adds on top (its own caches
        + activations + the P x P attention scores; weights shared with
        the decode scope are excluded) at the two endpoint prompt
        lengths ``p_lo``/``p_hi`` — prefill cost is convex in P, so the
        chord through the endpoints brackets every P from above (the
        admission guard's per-P form)."""
        from ..analysis.memory import MemoryAnalysis

        decode = MemoryAnalysis(self._decode_prog, site="serving")
        resident = decode.peak_bytes(1)
        persist = {n for n, t in decode.tensors.items()
                   if t.kind == "persistable"}
        p_lo, p_hi = 1, max(2, self.max_len - 1)

        def extra(P: int) -> int:
            fluid = self._fluid
            prog, start = fluid.Program(), fluid.Program()
            with self._build_span("footprint", prog, P=P), \
                    fluid.program_guard(prog, start):
                # IR only: no startup run, no compile — the analysis
                # walks the graph, the throwaway programs are dropped
                self._gpt.build_prefill_step(
                    self.cfg, batch=1, prompt_len=P,
                    max_len=self.max_len)
            ma = MemoryAnalysis(prog, site="serving")
            shared = sum(t.poly.at(1) for n, t in ma.tensors.items()
                         if n in persist and t.kind == "persistable"
                         and t.poly is not None)
            return max(0, ma.peak_bytes(1) - shared)

        return {"resident": resident, "p_lo": p_lo, "p_hi": p_hi,
                "prefill_extra_lo": extra(p_lo),
                "prefill_extra_hi": extra(p_hi)}


class DecodeEngine:
    """Continuous-batching scheduler over one ``b_max`` decode
    executable.

    ``params`` maps parameter name -> array (the training scope's
    persistables, ``gpt_*`` names); None keeps the startup
    initialization (synthetic runs). ``submit`` returns a
    ``ServingRequest`` whose ``result()`` is the full int64 token
    sequence ``[P + generated]`` (budget ``n_new``, or shorter when
    ``eos_id`` is sampled — the EOS token is included). Deadlines
    bound QUEUE time; once a sequence holds a slot it runs to
    completion. ``start()`` launches the scheduler thread; ``stop()``
    finishes no request — sequences mid-generation and queued requests
    fail with ``Cancelled`` (a decode step already dispatched is awaited
    and its ids dropped, so nothing stays queued on the device).

    Fleet-tier knobs (both default off; docs/SERVING.md):

    * ``prefix_store`` (a ``serving.PrefixStore``, shareable across
      replicas of one model) or ``prefix_cache_bytes`` (build a
      private store) enable prefix/KV-cache reuse; callers mark the
      reusable boundary per request via ``submit(prefix_len=...)``.
    * ``draft_cfg``/``draft_params`` + ``spec_k >= 1`` enable
      speculative decoding for greedy requests: the draft model drafts
      ``spec_k`` tokens per iteration, the target verifies them in one
      multi-token dispatch. The draft lane shares ``b_max``/``max_len``
      so its slots mirror the target's.

    Both are REFUSED at construction for a model whose caches hold rings
    (``cfg['layer_types']`` with a window shorter than ``max_len``): a
    stored prefix cannot be cut out of, nor a rejected draft rolled back
    in, a ring that has wrapped (``gpt.build_multi_token_decode_step``);
    for a model whose cache is latent (``cfg['attn']='mla'``), which the
    multi-token step neither reads nor writes; and for a model whose
    tokens are several residual streams (``cfg['residual']='mhc'``),
    which it does not carry; and for a model with a layer that keeps a
    state (``'ssm'`` in ``cfg['mixers']``, ``'conv'`` in
    ``cfg['layer_types']``), which has no position to cut a prefix at or
    rewind a draft to.
    """

    def __init__(self, cfg, params: Optional[Dict[str, np.ndarray]] = None,
                 b_max: int = 4, max_len: Optional[int] = None,
                 queue_capacity: int = 64, eos_id: Optional[int] = None,
                 place=None, prefix_store=None, prefix_cache_bytes: int = 0,
                 draft_cfg=None,
                 draft_params: Optional[Dict[str, np.ndarray]] = None,
                 spec_k: int = 0, device_budget: Optional[int] = None):
        import paddle_tpu as fluid
        from ..models import gpt
        from ..core.scope import scope_guard
        from .prefix import PrefixStore

        if b_max < 1:
            raise ValueError("b_max must be >= 1")
        if spec_k < 0:
            raise ValueError("spec_k must be >= 0; got %r" % (spec_k,))
        if spec_k and draft_cfg is None:
            raise ValueError(
                "spec_k=%d needs a draft model (draft_cfg=...) to "
                "propose tokens" % spec_k)
        self.cfg = dict(cfg) if cfg else gpt.base_config()
        self.b_max = b_max
        self.max_len = (self.cfg["max_length"] if max_len is None
                        else int(max_len))
        self.eos_id = eos_id
        gpt._check_cfg(self.cfg)
        multi = []            # the levers that need the multi-token step
        if prefix_store is not None or prefix_cache_bytes > 0:
            multi.append((self.cfg, "a prefix store (prefix_store= / "
                          "prefix_cache_bytes=)"))
        if draft_cfg is not None:
            multi += [(self.cfg, "speculative decoding (draft_cfg=)"),
                      (draft_cfg, "a draft model (draft_cfg=)")]
        for model, lever in multi:
            gpt._refuse_shortcut(
                model, "DecodeEngine: %s" % lever,
                "and the multi-token step does not carry the branch from "
                "one sub-layer to the next")
            if gpt.has_latent(model):
                raise ValueError(
                    "DecodeEngine: %s cannot serve a model with "
                    "cfg['attn']='mla': its cache is latent (one [b_max, "
                    "1, max_len, %d] tensor a layer), and the multi-token "
                    "step does not read or write a latent cache"
                    % (lever, gpt.latent_width(model)))
            if gpt.has_streams(model):
                raise ValueError(
                    "DecodeEngine: %s cannot serve a model with "
                    "cfg['residual']='mhc': its tokens are %d residual "
                    "streams, and the multi-token step does not carry "
                    "streams" % (lever, int(model["hc_mult"])))
            if gpt.has_state(model):
                raise ValueError(
                    "DecodeEngine: %s cannot serve a model where %s — a "
                    "stored prefix cannot be cut out of one at its "
                    "length, nor a rejected draft rolled back in one"
                    % (lever, gpt.state_refusal(model)))
            if gpt.has_rings(model, self.max_len):
                raise ValueError(
                    "DecodeEngine: %s cannot serve a model with "
                    "cfg['layer_types'] sliding layers of window %d < "
                    "max_len %d: their caches are rings, and the "
                    "multi-token step does not write rings"
                    % (lever, model["window"], self.max_len))
        self._exe = fluid.Executor(place if place is not None
                                   else fluid.TPUPlace())
        # busy-state stack for replica supervision (scheduler thread
        # writes, the router's monitor reads): a frame marked
        # compiling=True buys the engine the router's compile grace —
        # the Watchdog's wedge-vs-slow-compile distinction, replica-local
        self._busy_frames: list = []
        self._lane = _Lane(fluid, self._exe, self.cfg, b_max,
                           self.max_len, params, scope_guard, gpt,
                           mark=self._busy_mark)
        self.spec_k = int(spec_k)
        self._draft = None
        if draft_cfg is not None and self.spec_k >= 1:
            self._draft = _Lane(fluid, self._exe, dict(draft_cfg), b_max,
                                self.max_len, draft_params, scope_guard,
                                gpt, mark=self._busy_mark, role="draft_")
        if prefix_store is None and prefix_cache_bytes > 0:
            prefix_store = PrefixStore(prefix_cache_bytes)
        self.prefix_store = prefix_store
        # predicted-bytes admission guard (analysis/memory.py): the
        # byte model is built HERE, in the one thread already building
        # this engine's programs, never from submit — and a failed
        # estimate disables the guard instead of sinking construction
        from ..analysis.memory import device_budget as _env_budget

        self.device_budget = (_env_budget() if device_budget is None
                              else int(device_budget))
        try:
            self._mem = self._lane.memory_footprint()
            if self._draft is not None:
                self._mem["resident"] += \
                    self._draft.memory_footprint()["resident"]
        except Exception:
            self._mem = None
        self.queue = RequestQueue(queue_capacity)
        self._slots: list = [None] * b_max
        self._n_active = 0
        # decode steps dispatched and not yet read, oldest first, each
        # (what it leaves on the device, {slot index: rider}, greedy).
        # One between loop iterations while the loop runs ahead, none
        # otherwise; two only inside an iteration, between the dispatch
        # of step n+1 and the read of step n
        self._flights: collections.deque = collections.deque()
        self._gauge_contrib = 0
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._loop,
                                        name="DecodeEngine", daemon=True)
        self._started = False
        # scheduler-progress stamp for replica supervision: the router
        # declares this engine wedged when it holds active slots and
        # the stamp goes stale (serving/router.py)
        self.last_progress = time.monotonic()
        # perf_counter at the end of the last emission (a decode step or
        # an admission's first token): the next step's end less this is
        # the token gap its riders saw
        self._last_emit: Optional[float] = None

    @classmethod
    def from_artifact(cls, artifact, **overrides) -> "DecodeEngine":
        """Build an engine from a deployable artifact
        (``export.save_artifact(..., serving={"cfg": ..., ...})``):
        the artifact's serving record supplies ``cfg``/``b_max``/
        ``max_len``/``eos_id``, its params section supplies the
        weights (already per-var checksummed at load). ``artifact`` is
        a path or a ``LoadedArtifact``; ``overrides`` pass through to the
        constructor (``queue_capacity``, ``prefix_store``, ``place``,
        ...). The engine is built but NOT started, matching the
        router's ``engine_factory`` contract."""
        from ..export import ArtifactError, LoadedArtifact, load_artifact
        from ..observe.families import ARTIFACT_DEGRADED

        art = (artifact if isinstance(artifact, LoadedArtifact)
               else load_artifact(artifact))
        if art.serving is None:
            ARTIFACT_DEGRADED.labels(section="serving",
                                     reason="absent").inc()
            raise ArtifactError(
                "artifact %r carries no serving section — export it "
                "with serving={'cfg': ...} to build engines from it"
                % art.path)
        kw = dict(cfg=art.serving.get("cfg"),
                  params={n: np.asarray(v)
                          for n, v in art.params.items()} or None)
        for k in ("b_max", "max_len", "eos_id", "spec_k"):
            if art.serving.get(k) is not None:
                kw[k] = art.serving[k]
        kw.update(overrides)
        return cls(**kw)

    # ------------------------------------------------------------ caller
    def submit(self, prompt_ids, n_new: int, eos_id: Optional[int] = None,
               temperature: float = 0.0, top_k: int = 0, seed: int = 0,
               deadline_s: Optional[float] = None, tenant: str = "default",
               prefix_len: Optional[int] = None, trace_ctx=None,
               report: bool = True):
        """Enqueue one generation request (thread-safe). ``prompt_ids``
        is a 1-D (or [1, P]) int array; raises ``QueueFull`` under
        backpressure, ``ValueError`` on a budget that overruns the
        cache (the same check as ``generate``). ``prefix_len`` marks
        the prompt's reusable head (a shared system prompt) for the
        prefix store — ignored without one. ``tenant`` labels the
        request's terminal outcome; ``trace_ctx``/``report`` are the
        router's propagation knobs (serving/queue.py)."""
        if self._error is not None:
            raise RuntimeError("DecodeEngine failed") from self._error
        prompt = np.asarray(prompt_ids, dtype="int64").reshape(-1)
        P = prompt.shape[0]
        if P < 1:
            raise ValueError("empty prompt")
        if n_new < 1:
            raise ValueError("n_new must be >= 1; got %r" % (n_new,))
        if P + n_new > self.max_len:
            raise ValueError(
                "prompt (%d) + new tokens (%d) exceeds the engine's "
                "max_len=%d — positions past the cache would clamp and "
                "corrupt output" % (P, n_new, self.max_len))
        if temperature < 0:
            raise ValueError("temperature must be >= 0; got %r"
                             % (temperature,))
        if prefix_len is not None and not 0 < prefix_len <= P:
            raise ValueError(
                "prefix_len=%r must be in [1, prompt length %d]"
                % (prefix_len, P))
        budget = self.device_budget
        if budget is not None:
            predicted = self.predicted_bytes(P)
            if predicted is not None:
                from ..observe.families import SERVING_MEMORY_HEADROOM

                # the live headroom signal the fleet dashboard and the
                # roadmap's autoscaler watch (negative = this denial)
                SERVING_MEMORY_HEADROOM.set(budget - predicted)
            if predicted is not None and predicted > budget:
                from ..observe.families import SERVING_MEMORY_DENIED

                SERVING_MEMORY_DENIED.inc()
                raise MemoryBudgetExceeded(
                    "predicted bytes %d (resident %d + prefill(P=%d) "
                    "%d) exceed the engine's device budget %d — "
                    "admission refused before any prefill compile"
                    % (predicted, self._mem["resident"], P,
                       predicted - self._mem["resident"], budget))
        payload = dict(prompt=prompt, n_new=int(n_new),
                       eos_id=self.eos_id if eos_id is None else eos_id,
                       temperature=float(temperature), top_k=int(top_k),
                       seed=int(seed),
                       prefix_len=int(prefix_len) if prefix_len else None)
        return self.queue.submit(payload, deadline_s=deadline_s,
                                 tenant=tenant, trace_ctx=trace_ctx,
                                 report=report)

    def routed_pairs(self) -> Optional[np.ndarray]:
        """``[n_layer, n_expert]`` (token, expert) pairs the decode step
        has routed since the engine was built, for a model with sparse
        experts (None for a dense one; a row an expert BRANCH under
        ``shortcut_moe``, ``n_layer / 2`` of them, here and in the two
        tallies below; identity experts are ``zero_pairs()``'s). The
        step adds to the tally on the device and nothing fetches it a
        step: this call is the one transfer, and it refreshes
        ``paddle_moe_routed_pairs``. Rows of
        free slots are routed like any other, so at low occupancy the
        tally holds their garbage too."""
        from ..models.gpt import ROUTED_PAIRS_VAR
        from ..observe.families import MOE_ROUTED_PAIRS

        tally = self._refresh_tally(ROUTED_PAIRS_VAR, MOE_ROUTED_PAIRS)
        self.experts_touched()
        self.compact_calls()
        self.zero_pairs()
        return tally

    def zero_pairs(self) -> Optional[np.ndarray]:
        """``[expert branches, 2]`` for a cfg with identity experts
        (``n_zero_expert``; None otherwise): column 0 the (token, expert)
        pairs of the decode steps that chose an identity expert — they
        cost nothing, and with ``routed_pairs()``'s row they add up to
        ``b_max x expert_top_k`` a step —, column 1 the most experts WITH
        weights one token of a step chose since the engine was built.
        Kept on the device; this call (and ``routed_pairs()``) is the one
        transfer and refreshes ``paddle_moe_zero_pairs`` and
        ``paddle_moe_real_experts_max``."""
        from ..models.gpt import ZERO_PAIRS_VAR
        from ..observe.families import MOE_REAL_EXPERTS_MAX, MOE_ZERO_PAIRS

        var = self._lane.scope.find_var(ZERO_PAIRS_VAR)
        if var is None:
            return None
        tally = np.asarray(var)
        for layer, (pairs, most) in enumerate(tally):
            MOE_ZERO_PAIRS.labels(layer=str(layer)).set(int(pairs))
            MOE_REAL_EXPERTS_MAX.labels(layer=str(layer)).set(int(most))
        return tally

    def experts_touched(self) -> Optional[np.ndarray]:
        """``[n_layer, n_expert_local]``: the decode steps in which each
        expert THIS chip holds was given at least one pair, for a cfg
        with ``n_expert_local`` (None otherwise). Counted on the device
        beside the routed pairs; this call (and ``routed_pairs()``) is
        the one transfer and refreshes ``paddle_moe_experts_touched``."""
        from ..models.gpt import EXPERTS_TOUCHED_VAR
        from ..observe.families import MOE_EXPERTS_TOUCHED

        return self._refresh_tally(EXPERTS_TOUCHED_VAR, MOE_EXPERTS_TOUCHED)

    def compact_calls(self) -> Optional[np.ndarray]:
        """``[n_layer, 2]``: the prefills' expert calls long enough to
        carry a bound on the pairs THIS chip's experts hold
        (``ops/moe_ops.py::compact_rows``), by the branch they took —
        column 0 cut their sorted rows at the bound, column 1 found more
        held pairs than it and ran the full length — for a cfg with
        ``n_expert_local`` that has prefilled a prompt that long (None
        otherwise). Those prefill programs add to it on the device; this call (and
        ``routed_pairs()``) is the one transfer and refreshes
        ``paddle_moe_compact_calls``."""
        from ..models.gpt import COMPACT_CALLS_VAR
        from ..observe.families import MOE_COMPACT_CALLS

        var = self._lane.prefill_var(COMPACT_CALLS_VAR)
        if var is None:
            return None
        tally = np.asarray(var)
        for layer, row in enumerate(tally):
            for path, n in zip(("compact", "full"), row):
                MOE_COMPACT_CALLS.labels(layer=str(layer),
                                         path=path).set(int(n))
        return tally

    def mhc_res_deviation(self) -> Optional[float]:
        """The largest ``|row sum - 1|`` or ``|column sum - 1|`` any
        residual mapping ``H_res`` has shown in a decode step since the
        engine was built, for a cfg with ``residual='mhc'`` (None
        otherwise). The step keeps the running maximum on the device;
        this call is the one transfer and refreshes
        ``paddle_mhc_res_deviation``."""
        from ..models.gpt import MHC_RES_DEV_VAR
        from ..observe.families import MHC_RES_DEVIATION

        var = self._lane.scope.find_var(MHC_RES_DEV_VAR)
        if var is None:
            return None
        dev = float(np.asarray(var).reshape(-1)[0])
        MHC_RES_DEVIATION.set(dev)
        return dev

    def _refresh_tally(self, name, family) -> Optional[np.ndarray]:
        var = self._lane.scope.find_var(name)
        if var is None:
            return None
        tally = np.asarray(var)
        for layer, row in enumerate(tally):
            for expert, n in enumerate(row):
                family.labels(layer=str(layer),
                              expert=str(expert)).set(int(n))
        return tally

    def predicted_resident_bytes(self) -> Optional[int]:
        """Static estimate of this engine's resident device bytes
        (target + draft weights, the caches at their own shapes, one
        decode step's activations) — None when the byte model could not
        be built."""
        return None if self._mem is None else int(self._mem["resident"])

    def predicted_bytes(self, prompt_len: int) -> Optional[int]:
        """Predicted peak while admitting a prompt of ``prompt_len``:
        resident bytes plus the prefill's non-shared extra,
        interpolated on the chord between the two analyzed endpoint
        lengths (prefill cost is convex in P, so the chord brackets
        every P from above). The admission guard's quantity."""
        if self._mem is None:
            return None
        m = self._mem
        p = min(max(int(prompt_len), m["p_lo"]), m["p_hi"])
        span = max(1, m["p_hi"] - m["p_lo"])
        extra = (m["prefill_extra_lo"]
                 + (m["prefill_extra_hi"] - m["prefill_extra_lo"])
                 * (p - m["p_lo"]) / span)
        return int(m["resident"] + max(extra, 0))

    def alive(self) -> bool:
        """Health probe for replica supervision: started, scheduler
        thread running, no terminal error."""
        return (self._started and self._error is None
                and self._thread.is_alive())

    @contextlib.contextmanager
    def _busy_mark(self, site, compiling):
        self._busy_frames.append((site, bool(compiling),
                                  time.monotonic()))
        try:
            yield
        finally:
            self._busy_frames.pop()
            self.last_progress = time.monotonic()

    def busy_compiling(self) -> bool:
        """True while the scheduler thread is inside work that may
        legitimately take seconds (program build, first-signature
        dispatch, splice jit) — the router judges a stale progress
        stamp against its compile grace instead of the stall deadline
        then (serving/router.py)."""
        frames = list(self._busy_frames)
        return any(f[1] for f in frames)

    def start(self) -> "DecodeEngine":
        if not self._started:
            self._started = True
            self._thread.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        """Stop the scheduler. Queued requests fail with ``Cancelled``;
        sequences mid-generation fail with ``Cancelled`` too (their
        partial output is dropped). Idempotent. A shorter ``timeout``
        is the router's drain knob: a wedged scheduler thread is
        abandoned after it (daemon — it dies with the process) and its
        slot requests are failed here so the router can re-admit them
        immediately."""
        from .queue import Cancelled

        self._stop.set()
        self.queue.close()
        if self._started:
            self._thread.join(timeout=timeout)
        self._fail_slots(Cancelled("engine stopped mid-generation"))

    def __enter__(self) -> "DecodeEngine":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False

    # --------------------------------------------------------- scheduler
    def _loop(self) -> None:
        from .queue import Cancelled

        # one trace identity for the scheduler loop: every decode-step
        # span groups under it (requests keep their own traces; the
        # step spans reference them via the "traces" attr)
        self._loop_trace = _tr.new_trace() if _tr.trace_enabled() else None
        try:
            while not self._stop.is_set():
                self.last_progress = time.monotonic()
                # admit into free slots at the step boundary; block on
                # the queue only when the whole batch is idle and no
                # step is in flight (its riders wait for their tokens)
                self._admit(block=not self._busy())
                if self._stop.is_set():
                    return
                if self._busy():
                    self._step()
        except BaseException as exc:  # noqa: BLE001 — fail every caller loudly
            self._error = exc
            self._drain()
            self._fail_slots(exc)  # a dead engine holds no live slots
            self.queue.close()  # pending requests fail as Cancelled
            if not isinstance(exc, Cancelled) and not self._stop.is_set():
                # a stop-requested teardown (router drain) already
                # failed the slots; re-raising into a thread nobody
                # joins would only spray a traceback
                raise
        finally:
            if self._stop.is_set():
                from .queue import Cancelled as _C

                # a slot admitted WHILE stop() was sweeping (this
                # thread was mid-_admit_one past the join timeout)
                # would otherwise strand its caller: nobody steps it
                # and stop's sweep already ran. The admitting thread
                # sweeps once more on its way out, so every admitted
                # request reaches a terminal state no matter how the
                # teardown interleaves.
                self._drain()
                self._fail_slots(_C("engine stopped mid-generation"))

    def _busy(self) -> bool:
        """A slot is held or a dispatched step has not been read."""
        return self._n_active > 0 or bool(self._flights)

    def _drain(self) -> None:
        """Wait for every step still in flight and drop what it chose:
        this thread leaves nothing queued on the device behind it, so
        the donated caches and tallies in the scope are settled arrays
        when ``stop()`` returns. A step that failed fails here again,
        quietly: its riders are failed by ``_fail_slots`` either way."""
        for out, _riders, _greedy in list(self._flights):
            try:
                self._lane.read(out)
            except Exception:  # noqa: BLE001 — the caller holds the cause
                pass

    def _fail_slots(self, exc: BaseException) -> None:
        # a rider of a step in flight may have left its slot already
        # (its budget ends with that step): it is failed here too
        flights = list(self._flights)
        self._flights.clear()
        riders = [slot for _out, aboard, _g in flights
                  for slot in aboard.values()]
        for slot in riders + [s for s in self._slots if s is not None]:
            slot.request.set_exception(exc)    # a no-op once terminal
        self._slots[:] = [None] * self.b_max
        self._n_active = 0
        self._set_active_gauge()

    def _admit(self, block: bool) -> None:
        while self._n_active < self.b_max and not self._stop.is_set():
            req = self.queue.get(timeout=0.05 if block else 0)
            if req is None:
                return
            slot_idx = self._slots.index(None)
            try:
                self._admit_one(slot_idx, req)
            except BaseException as exc:  # noqa: BLE001
                # the pop already admitted req (queue.close can't cancel
                # it) but it isn't in a slot yet — fail it HERE or its
                # caller blocks in result() forever, then let the loop's
                # error path fail everyone else. _error is set BEFORE
                # the request fails: its done-callback may be the
                # router's, which must see a dead engine (alive() False)
                # to re-admit instead of surfacing the replica's fault
                # to the caller
                self._error = exc
                req.set_exception(exc)
                raise
            block = False  # drain without blocking once something runs

    def _admit_one(self, slot_idx: int, req) -> None:
        from ..observe.families import (SERVING_ADMITTED, SERVING_FETCHES,
                                        SERVING_TOKENS,
                                        SERVING_TTFT_SECONDS)

        p = req.payload
        slot = _Slot(req, p["prompt"], p["n_new"], p["eos_id"],
                     p["temperature"], p["top_k"], p["seed"],
                     spec=self._draft is not None)
        # admission runs under the REQUEST's trace (explicit hand-off
        # from the caller thread via req.trace): prefill + splice child
        # spans attribute the one-time admission cost to this request.
        # The busy frame is compiling-class: admission may build and
        # compile new prefill/suffix programs and jit splices — the
        # router must judge it against its compile grace
        with self._busy_mark("admit", True):
            with _tr.trace_span("serving.engine.admit", ctx=req.trace,
                                slot=slot_idx,
                                prompt_len=len(p["prompt"])):
                # a greedy request's first token is chosen by the
                # prefill program and one id comes back; a sampled one
                # gets the last position's row for its own sampler
                fetch, last = self._lane.prefill_insert(
                    slot_idx, p["prompt"],
                    prefix_store=self.prefix_store,
                    prefix_len=p.get("prefix_len"),
                    greedy=slot.temperature == 0)
                SERVING_FETCHES.labels(site="admit", fetch=fetch).inc()
                with _tr.trace_span("serving.engine.sample", active=1):
                    first = (int(last) if fetch == "tokens"
                             else slot.sample(last))
                if slot.spec and not slot.finished(first):
                    # mirror the prompt into the draft lane's slot so
                    # drafting starts cache-aligned with the target
                    # (the draft never consults the prefix store: its
                    # rows would be a different model's); only the
                    # cache rows matter, so it fetches the least
                    self._draft.prefill_insert(slot_idx, p["prompt"],
                                               greedy=True)
        # the first token exists on the host: time to first token, from
        # submit, under the request's own trace (a retroactive span, so
        # it is in the ring and not among the profiler's annotations)
        self._last_emit = time.perf_counter()
        ttft = self._last_emit - req.submitted_perf
        SERVING_TTFT_SECONDS.observe(ttft)
        if req.trace is not None:
            _tr.record_span("serving.request.first_token",
                            req.submitted_perf, ttft, ctx=req.trace,
                            prompt_len=len(p["prompt"]),
                            queued_s=req.queued_s)
        SERVING_ADMITTED.inc()
        SERVING_TOKENS.inc()
        slot.tokens.append(first)
        if slot.finished(first):
            self._retire(slot_idx, slot)
            return
        self._slots[slot_idx] = slot
        self._n_active += 1
        self._set_active_gauge()

    # ------------------------------------------------------------- steps
    def _step(self) -> None:
        active = [i for i, s in enumerate(self._slots) if s is not None]
        self.last_progress = time.monotonic()
        spec_slots = [i for i in active if self._slots[i].spec]
        # a speculative iteration writes k+1 cache rows per slot; any
        # row that would clamp past max_len (corrupting valid rows —
        # dynamic_update_slice shifts an overflowing window DOWN) forces
        # the whole batch onto a plain step for this iteration
        if spec_slots and all(
                len(self._slots[i].tokens) + self.spec_k <= self.max_len
                for i in active):
            self._spec_step(active, spec_slots)
        else:
            self._plain_step(active,
                             advance_draft=bool(spec_slots))

    def _emitted(self) -> None:
        """A step's tokens reached the host: the gap since the last
        emission (a decode step's or an admission's first token) is the
        token gap its riders saw."""
        from ..observe.families import SERVING_TOKEN_GAP_SECONDS

        now = time.perf_counter()
        if self._last_emit is not None:
            SERVING_TOKEN_GAP_SECONDS.observe(now - self._last_emit)
        self._last_emit = now

    def _feeds(self, active):
        """(token, pos) [b_max, 1] of the next step. A rider still
        aboard the step in flight stands one position further than its
        host tokens say, and its token is the one that step leaves on
        the device; every other slot's comes from the host."""
        with _tr.trace_span("serving.engine.feeds"):
            token = np.zeros((self.b_max, 1), dtype="int64")
            pos = np.zeros((self.b_max, 1), dtype="int64")
            for i in active:
                slot = self._slots[i]
                token[i, 0] = -1 if slot.aboard else slot.tokens[-1]
                pos[i, 0] = len(slot.tokens) - 1 + slot.aboard
            if self._flights:
                token = self._lane.carry(self._flights[-1][0],
                                         token.astype("int32"))
            return token, pos

    def _step_span(self, site, n_active, reads, **attrs):
        # one span per loop iteration under the engine thread, the
        # admission apart; "traces" lists the trace id of every rider
        # whose token this span hands out (``reads``: the riders of the
        # step it waits for), so a request's share of the batched decode
        # time is attributable post-hoc (the span is shared — B slots
        # advance in ONE dispatch by design). Attrs are attached BEFORE
        # entering: the ring copies attrs per event, so only enter-time
        # keys ride the B event (and an unfinished step in a wedge dump
        # must still name the riders it waits for)
        sp = _tr.trace_span(site, ctx=getattr(self, "_loop_trace", None))
        if sp.attrs is not None:
            sp.attrs["active"] = n_active
            sp.attrs["traces"] = [
                slot.request.trace.trace_id for slot in reads
                if slot.request.trace is not None]
            sp.attrs.update(attrs)
        return sp

    def _plain_step(self, active, advance_draft=False) -> None:
        """One loop iteration but its admissions: dispatch the next
        plain step, then read the oldest step in flight. While every
        rider is greedy and no draft lane is attached the step just
        dispatched STAYS in flight: the one read is the previous
        step's, and the host's work for both ran while the chip
        computed. Otherwise the loop is synchronous, from what it can
        observe: a sampled rider's token does not exist before its
        logits are read, and a draft lane is fed from the host. On the
        way there an iteration only reads (nothing goes out before the
        ids the host now needs are known), as does the last one of a
        burst."""
        from ..kernels.mla_decode import blocks_of
        from ..observe.families import (MLA_DECODE_BLOCKS,
                                        SERVING_DECODE_STEPS,
                                        SERVING_FETCHES,
                                        SERVING_OCCUPANCY,
                                        SERVING_POSITIONS,
                                        SERVING_SPEC_DRAFT_STEPS,
                                        SERVING_STEP_DISPATCHES)

        # what a step brings to the host follows from its riders: all
        # at temperature 0, the program's own argmax a slot (b_max
        # ids); one sampled rider, the [b_max, 1, vocab] logits for
        # every rider's host sampler. The logits plan compiles when the
        # first sampled rider rides a step
        greedy = all(self._slots[i].temperature == 0 for i in active)
        stays = greedy and self._draft is None
        if self._flights and not stays:
            active = []       # read first: the next iteration dispatches
        riders = {i: self._slots[i] for i in active}
        ahead = bool(riders and self._flights)
        reads = (self._flights[0][1] if self._flights
                 else {} if stays else riders)
        # the step span holds all the host does in one iteration: feeds,
        # the Executor's phases (gather, h2d, dispatch, write_back nest
        # in executor.call by the thread's context), the wait
        # (executor.complete) and sampling. With a step in flight the
        # device work inside the span is the PREVIOUS step's. Free slots
        # keep token 0 at pos 0: the write lands in a row nobody reads
        # (masked, and the next prefill-insert overwrites)
        with self._step_span("serving.engine.step", len(riders),
                             reads.values(), ahead=ahead):
            if riders:
                SERVING_OCCUPANCY.observe(len(riders) / float(self.b_max))
                token, pos = self._feeds(active)
                out = self._lane.decode(token, pos, greedy=greedy)
                SERVING_FETCHES.labels(
                    site="step",
                    fetch="tokens" if greedy else "logits").inc()
                SERVING_STEP_DISPATCHES.labels(
                    dispatch="ahead" if ahead else "sync").inc()
                SERVING_DECODE_STEPS.inc()
                # a rider at position p may see rows 0 .. p
                SERVING_POSITIONS.labels(kind="live").inc(
                    int(pos.sum()) + len(riders))
                SERVING_POSITIONS.labels(kind="held").inc(
                    self.b_max * self.max_len)
                if self._lane.latent_walk is not None:
                    layers, bs = self._lane.latent_walk
                    live, grid = blocks_of(pos, bs, self.max_len)
                    MLA_DECODE_BLOCKS.labels(kind="live").inc(layers * live)
                    MLA_DECODE_BLOCKS.labels(kind="grid").inc(layers * grid)
                if advance_draft and self._draft is not None:
                    # keep the draft lane's caches mirror-aligned through
                    # plain iterations: a skipped position would leave a
                    # never-written garbage row in every later draft's
                    # visible window, silently cratering acceptance
                    self._draft.decode(token, pos)
                    SERVING_SPEC_DRAFT_STEPS.inc()
                self._flights.append((out, riders, greedy))
                for i, slot in riders.items():
                    slot.aboard += 1
                    if slot.ends_by_length():
                        # this step fills the rider's budget: it takes
                        # no row of the next, and its slot is free for
                        # the next admission while its token is awaited
                        self._slots[i] = None
                        self._n_active -= 1
            while len(self._flights) > (1 if riders and stays else 0):
                self._land()
            self._set_active_gauge()

    def _land(self) -> None:
        """Read the oldest step in flight (the one block of an
        iteration) and do its bookkeeping: append, finished, retire,
        resolve the request."""
        from ..observe.families import (SERVING_OVERRUN_ROWS,
                                        SERVING_TOKENS)

        dev, riders, greedy = self._flights[0]
        out = self._lane.read(dev)
        with _tr.trace_span("serving.engine.sample", active=len(riders)):
            chosen = out.tolist() if greedy else None
            for i, slot in riders.items():
                slot.aboard -= 1
                tok = chosen[i] if greedy else slot.sample(out[i, 0])
                slot.tokens.append(tok)
                if not slot.finished(tok):
                    continue
                if self._slots[i] is slot:   # not freed at its dispatch
                    self._slots[i] = None
                    self._n_active -= 1
                if slot.aboard:
                    # found finished (eos_id) with the next step already
                    # out and this rider aboard: that row is computed
                    # for nobody, at pos + 1 of a slot now free (masked
                    # for every neighbour, overwritten by the next
                    # prefill-insert), and its id is dropped
                    del self._flights[1][1][i]
                    SERVING_OVERRUN_ROWS.inc()
                self._retire(i, slot)
        self._flights.popleft()
        SERVING_TOKENS.inc(len(riders))
        self._emitted()

    def _spec_step(self, active, spec_slots) -> None:
        """One speculative iteration: k greedy draft steps through the
        draft lane's fixed-shape decode executable, then ONE target
        verify dispatch scoring k+1 positions per slot. Greedy
        verification accepts the longest draft prefix that matches the
        target's own argmax chain — every emitted token equals what the
        plain step would have produced, bit for bit (the verify
        program's per-position attention IS the plain step's). Plain
        (sampled) slots ride the verify dispatch and use only its first
        position; their extra rows are masked garbage the next real
        write overwrites."""
        from ..models.gpt import sample_token
        from ..observe.families import (SERVING_OCCUPANCY,
                                        SERVING_SPEC_ACCEPTED,
                                        SERVING_SPEC_DRAFT_STEPS,
                                        SERVING_SPEC_PROPOSED,
                                        SERVING_SPEC_VERIFY_STEPS,
                                        SERVING_TOKENS)

        k = self.spec_k
        SERVING_OCCUPANCY.observe(len(active) / float(self.b_max))
        with self._step_span("serving.engine.spec", len(active),
                             [self._slots[i] for i in active],
                             spec_slots=len(spec_slots), k=k):
            # --- draft phase: k lockstep draft-lane steps; non-spec
            # rows re-feed their real (token, pos) every round — the
            # repeated write is idempotent and keeps the feeds simple
            token, pos = self._feeds(active)
            drafts: Dict[int, List[int]] = {i: [] for i in spec_slots}
            greedy = np.random.RandomState(0)  # unused at temperature 0
            for _ in range(k):
                logits = self._draft.read(self._draft.decode(token, pos))
                SERVING_SPEC_DRAFT_STEPS.inc()
                for i in spec_slots:
                    d = sample_token(logits[i, 0], greedy)
                    drafts[i].append(d)
                    token[i, 0] = d
                    pos[i, 0] += 1
            # --- verify phase: one multi-token target dispatch
            vtok = np.zeros((self.b_max, k + 1), dtype="int64")
            vpos = np.stack([np.arange(k + 1, dtype="int64")]
                            * self.b_max)
            for i in active:
                slot = self._slots[i]
                p0 = len(slot.tokens) - 1
                vpos[i] += p0
                vtok[i, 0] = slot.tokens[-1]
                if i in drafts:
                    vtok[i, 1:] = drafts[i]
            logits = self._lane.multi_decode(vtok, vpos)
            SERVING_SPEC_VERIFY_STEPS.inc()
            SERVING_SPEC_PROPOSED.inc(k * len(spec_slots))
            appended = 0
            with _tr.trace_span("serving.engine.sample",
                                active=len(active)):
                for i in active:
                    slot = self._slots[i]
                    if i not in drafts:
                        # plain rider: position 0 IS its plain step
                        tok = slot.sample(logits[i, 0])
                        slot.tokens.append(tok)
                        appended += 1
                        if slot.finished(tok):
                            self._slots[i] = None
                            self._n_active -= 1
                            self._retire(i, slot)
                        continue
                    accepted = 0
                    for s in range(k + 1):
                        # row s is valid iff every draft before it matched
                        # the target's argmax chain — walked in order, so
                        # reaching s proves it
                        tok = slot.sample(logits[i, s])
                        slot.tokens.append(tok)
                        appended += 1
                        matched = s < k and tok == drafts[i][s]
                        if matched:
                            # count BEFORE the finished-break: a drafted
                            # EOS / final-budget token the verification
                            # confirmed is an acceptance, not a drop —
                            # accept_rate is THE switch-the-draft-off
                            # signal and must not systematically undercount
                            # request tails
                            accepted += 1
                        if slot.finished(tok):
                            self._slots[i] = None
                            self._n_active -= 1
                            self._retire(i, slot)
                            break
                        if s < k and not matched:
                            break  # mismatch: the draft chain is dead
                    SERVING_SPEC_ACCEPTED.inc(accepted)
            SERVING_TOKENS.inc(appended)
            self._set_active_gauge()
        self._emitted()

    def _retire(self, slot_idx: int, slot: _Slot) -> None:
        from ..observe.families import SERVING_RETIRED

        SERVING_RETIRED.inc()
        if slot.request.trace is not None:
            _tr.trace_event("serving.engine.retire", ctx=slot.request.trace,
                            slot=slot_idx, tokens=len(slot.tokens))
        slot.request.set_result(np.asarray(slot.tokens, dtype="int64"))

    def _set_active_gauge(self) -> None:
        from ..observe.families import SERVING_SLOTS_ACTIVE

        # additive, not set(): N router replicas share the process-wide
        # gauge, so each engine contributes its delta and the gauge
        # reads the fleet total
        change = self._n_active - self._gauge_contrib
        if change:
            SERVING_SLOTS_ACTIVE.inc(change)
            self._gauge_contrib = self._n_active
