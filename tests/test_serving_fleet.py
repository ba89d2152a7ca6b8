"""The serving fleet tier (ISSUE 10): prefix/KV-cache reuse,
speculative decoding, and SLO-aware multi-replica routing.

Contracts pinned here:

* PrefixStore — longest-exact-prefix lookup, byte-capped LRU eviction,
  hit/saved-token telemetry.
* gpt.build_multi_token_decode_step — S tokens in one dispatch, logits
  AND resulting cache state bitwise the single-token step's.
* Prefix-cached admission — outputs bitwise the uncached path's (and
  ``generate``'s); hits splice + suffix-prefill instead of full
  prefill, visible in the counters; no store attached = zero movement
  across every prefix family.
* Speculative decode — greedy outputs bitwise ``generate``'s with an
  arbitrary (even disagreeing) draft; speculative and sampled rows
  coexist in one batch; an agreeing draft accepts k tokens per verify
  dispatch; near the cache end the engine degrades to plain steps and
  stays bitwise.
* ReplicaRouter — tenant quotas and the tenant label on
  ``paddle_serving_requests_total``; SLO reject-early against projected
  wait; the chaos criterion: a replica wedged via FaultPlan is
  detected, drained, restarted, and every one of its requests still
  reports exactly one terminal outcome, completing on survivors.
* (slow) the two perf criteria: shared-prefix workload >= 1.3x
  tokens/sec vs prefix-cache-off, draft-friendly workload >= 1.2x vs
  spec-off — calibrated best-of-5 ratios, no absolute-ms asserts.
"""

import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observe
from paddle_tpu.core.scope import Scope, scope_guard
from paddle_tpu.models import gpt
from paddle_tpu.serving import (Cancelled, DeadlineExpired, DecodeEngine,
                                PrefixStore, ReplicaRouter,
                                TenantQuotaExceeded)

CFG = dict(d_model=32, d_ff=64, n_head=2, n_layer=2, vocab=64,
           max_length=48, dropout=0.0)
MAX_LEN = 48
DRAFT_CFG = dict(d_model=16, d_ff=32, n_head=2, n_layer=1, vocab=64,
                 max_length=48, dropout=0.0)


def _value(name, **labels):
    for s in observe.snapshot()["metrics"][name]["samples"]:
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s.get("value", s.get("count"))
    return 0.0


class _SeqRef:
    """B=1 decode-loop reference (the parity oracle) + the parameter
    set every engine in this module shares."""

    def __init__(self):
        self.prog, start = fluid.Program(), fluid.Program()
        self.scope = Scope()
        with scope_guard(self.scope):
            with fluid.program_guard(self.prog, start):
                self.logits, self.cache_names = gpt.build_decode_step(
                    CFG, batch=1, max_len=MAX_LEN)
            self.exe = fluid.Executor(fluid.TPUPlace())
            self.exe.run(start, scope=self.scope)
        self.params = {n: np.asarray(self.scope.find_var(n))
                       for n in self.prog.global_block().vars
                       if n.startswith("gpt_")
                       and n not in self.cache_names
                       and self.scope.find_var(n) is not None}

    def generate(self, prompt, n_new, **kw):
        with scope_guard(self.scope):
            return gpt.generate(self.exe, self.prog, self.logits,
                                prompt[None, :], n_new, self.scope,
                                **kw)[0]


@pytest.fixture(scope="module")
def seq_ref():
    return _SeqRef()


# ------------------------------------------------------------ prefix store
def test_prefix_store_longest_match_lru_and_caps():
    store = PrefixStore(max_bytes=4096)
    rows = lambda L: [np.zeros((1, 2, L, 4), "float32")]  # noqa: E731
    a = np.arange(1, 9, dtype="int64")          # 8 tokens
    assert store.insert(a[:4], rows(4))
    assert not store.insert(a[:4], rows(4))     # first write wins
    assert store.insert(a[:6], rows(6))
    # longest match wins; a full-length prompt match is capped at P-1
    L, got = store.lookup(a)
    assert L == 6 and got[0].shape[2] == 6
    L, _ = store.lookup(a[:5])                  # only the 4-prefix fits
    assert L == 4
    assert store.lookup(np.array([9, 9, 9], "int64")) is None
    # key/rows length mismatch is a hard error
    with pytest.raises(ValueError, match="disagree"):
        store.insert(a[:3], rows(4))
    with pytest.raises(ValueError):
        PrefixStore(max_bytes=0)
    # LRU eviction under the byte cap: touch the 4-prefix (recency),
    # then insert until the 6-prefix (now coldest) evicts
    e0 = _value("paddle_serving_prefix_evictions_total")
    store.lookup(a[:5])
    b = np.arange(20, 40, dtype="int64")
    # 3968-byte entry: held 320 bytes + 3968 > 4096 forces exactly one
    # eviction, and the LRU victim is the untouched 6-prefix
    store.insert(b[:8], [np.zeros((1, 2, 8, 62), "float32")])
    assert _value("paddle_serving_prefix_evictions_total") > e0
    assert store.contains(a[:4])                # recently used survived
    assert not store.contains(a[:6])            # LRU victim
    # an entry bigger than the whole cap is refused, not thrashed
    assert not store.insert(b[:10],
                            [np.zeros((1, 2, 10, 64), "float32")])
    assert store.bytes_used <= 4096


# ------------------------------------------------- multi-token decode step
def test_multi_token_step_bitwise_matches_single_steps():
    """Logits of a 3-token dispatch == three single-token dispatches,
    bit for bit, and the cache state it leaves behind drives identical
    later steps — the foundation both fleet levers rest on."""
    B, S = 2, 3
    ref_scope, scope = Scope(), Scope()
    rs = np.random.RandomState(0)
    toks = rs.randint(1, 64, (B, 8)).astype("int64")

    with scope_guard(ref_scope):
        dec, dstart = fluid.Program(), fluid.Program()
        with fluid.program_guard(dec, dstart):
            lg, _ = gpt.build_serving_decode_step(CFG, batch=B,
                                                  max_len=16)
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(dstart, scope=ref_scope)
        ref = []
        for t in range(7):
            (lv,) = exe.run(dec, feed={
                "token": toks[:, t:t + 1],
                "pos": np.full((B, 1), t, "int64")},
                fetch_list=[lg], scope=ref_scope)
            ref.append(lv.copy())

    with scope_guard(scope):
        dec2, dstart2 = fluid.Program(), fluid.Program()
        multi, mstart = fluid.Program(), fluid.Program()
        with fluid.program_guard(dec2, dstart2):
            lg2, _ = gpt.build_serving_decode_step(CFG, batch=B,
                                                   max_len=16)
        with fluid.program_guard(multi, mstart):
            mlg, _ = gpt.build_multi_token_decode_step(
                CFG, batch=B, steps=S, max_len=16)
        exe2 = fluid.Executor(fluid.TPUPlace())
        exe2.run(dstart2, scope=scope)
        for n in dec.global_block().vars:
            if n.endswith(("_cache_k", "_cache_v")) or n in ("token",
                                                             "pos"):
                continue
            v = ref_scope.find_var(n)
            if v is not None:
                scope.set_var(n, v)
        # program-private vars (unnamed fc biases) come from a scratch
        # startup — running mstart in `scope` would re-init live state
        scratch = Scope()
        with scope_guard(scratch):
            exe2.run(mstart, scope=scratch)
        for n in multi.global_block().vars:
            if scope.find_var(n) is None \
                    and scratch.find_var(n) is not None:
                scope.set_var(n, np.asarray(scratch.find_var(n)))
        for t in range(3):
            exe2.run(dec2, feed={"token": toks[:, t:t + 1],
                                 "pos": np.full((B, 1), t, "int64")},
                     fetch_list=[lg2], scope=scope)
        (mv,) = exe2.run(multi, feed={
            "token": toks[:, 3:6],
            "pos": np.stack([np.arange(3, 6)] * B).astype("int64")},
            fetch_list=[mlg], scope=scope)
        for s in range(S):
            np.testing.assert_array_equal(mv[:, s], ref[3 + s][:, 0])
        # cache-state parity: the next single step matches too
        (lv6,) = exe2.run(dec2, feed={"token": toks[:, 6:7],
                                      "pos": np.full((B, 1), 6, "int64")},
                          fetch_list=[lg2], scope=scope)
        np.testing.assert_array_equal(lv6, ref[6])


# ----------------------------------------------------- prefix-cached engine
def test_prefix_cache_bitwise_outputs_and_telemetry(seq_ref):
    rs = np.random.RandomState(3)
    shared = rs.randint(1, 64, (10,)).astype("int64")
    prompts = [np.concatenate([shared,
                               rs.randint(1, 64, (4,)).astype("int64")])
               for _ in range(4)]
    store = PrefixStore(64 << 20)
    eng = DecodeEngine(CFG, params=seq_ref.params, b_max=2,
                       max_len=MAX_LEN, prefix_store=store).start()
    try:
        h0 = _value("paddle_serving_prefix_hits_total")
        m0 = _value("paddle_serving_prefix_misses_total")
        s0 = _value("paddle_serving_prefix_tokens_saved_total")
        outs = [eng.submit(p, 6, prefix_len=10).result(timeout=120)
                for p in prompts]
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o, seq_ref.generate(p, 6))
        # first admission misses and stores; the other three splice the
        # stored 10-token head and prefill only their 4-token suffix
        assert _value("paddle_serving_prefix_misses_total") == m0 + 1
        assert _value("paddle_serving_prefix_hits_total") == h0 + 3
        assert _value("paddle_serving_prefix_tokens_saved_total") == \
            s0 + 3 * 10
        assert len(store) == 1 and store.bytes_used > 0
        # a sampled request through the same cache stays bitwise too
        got = eng.submit(prompts[0], 6, prefix_len=10, temperature=0.9,
                         top_k=8, seed=11).result(timeout=120)
        np.testing.assert_array_equal(
            got, seq_ref.generate(prompts[0], 6, temperature=0.9,
                                  top_k=8, seed=11))
    finally:
        eng.stop()


def test_prefix_store_shared_across_fresh_engine_stays_bitwise(seq_ref):
    """Review regression (confirmed by repro): a FRESH engine whose
    FIRST admission hits a shared store has never built a full-prefill
    program, so nothing had shared the engine's weights into its
    prefill scope — the suffix program ran with scratch-initialized
    weights and broke parity. Params are deliberately scaled AWAY from
    startup init so the scratch weights cannot coincidentally match
    (the hole the original tests fell into)."""
    params = {n: v * 1.5 for n, v in seq_ref.params.items()}
    ref = _SeqRef.__new__(_SeqRef)  # a B=1 oracle with the SAME params
    ref.prog, start = fluid.Program(), fluid.Program()
    ref.scope = Scope()
    with scope_guard(ref.scope):
        with fluid.program_guard(ref.prog, start):
            ref.logits, cache_names = gpt.build_decode_step(
                CFG, batch=1, max_len=MAX_LEN)
        ref.exe = fluid.Executor(fluid.TPUPlace())
        ref.exe.run(start, scope=ref.scope)
        for n, v in params.items():
            if ref.scope.find_var(n) is not None:
                ref.scope.set_var(n, v)

    rs = np.random.RandomState(14)
    shared = rs.randint(1, 64, (8,)).astype("int64")
    p1 = np.concatenate([shared, rs.randint(1, 64, (3,)).astype("int64")])
    p2 = np.concatenate([shared, rs.randint(1, 64, (3,)).astype("int64")])
    store = PrefixStore(16 << 20)
    # replica A prefills + stores the shared head
    eng_a = DecodeEngine(CFG, params=params, b_max=1, max_len=MAX_LEN,
                         prefix_store=store).start()
    try:
        out1 = eng_a.submit(p1, 5, prefix_len=8).result(timeout=120)
        np.testing.assert_array_equal(out1, ref.generate(p1, 5))
    finally:
        eng_a.stop()
    assert store.contains(shared)
    # replica B (fresh engine, same store): its first admission is a
    # HIT — the suffix path must still decode with the engine's params
    eng_b = DecodeEngine(CFG, params=params, b_max=1, max_len=MAX_LEN,
                         prefix_store=store).start()
    try:
        h0 = _value("paddle_serving_prefix_hits_total")
        out2 = eng_b.submit(p2, 5, prefix_len=8).result(timeout=120)
        assert _value("paddle_serving_prefix_hits_total") == h0 + 1
        np.testing.assert_array_equal(out2, ref.generate(p2, 5))
    finally:
        eng_b.stop()


def test_prefix_families_zero_without_store(seq_ref):
    eng = DecodeEngine(CFG, params=seq_ref.params, b_max=1,
                       max_len=MAX_LEN).start()
    fams = ("paddle_serving_prefix_hits_total",
            "paddle_serving_prefix_misses_total",
            "paddle_serving_prefix_tokens_saved_total",
            "paddle_serving_prefix_inserts_total")
    try:
        before = {f: _value(f) for f in fams}
        p = np.arange(1, 9, dtype="int64")
        # prefix_len without a store is explicitly inert
        eng.submit(p, 4, prefix_len=4).result(timeout=120)
        for f in fams:
            assert _value(f) == before[f], f
    finally:
        eng.stop()


def test_prefix_len_validation(seq_ref):
    eng = DecodeEngine(CFG, params=seq_ref.params, b_max=1,
                       max_len=MAX_LEN,
                       prefix_cache_bytes=1 << 20)
    p = np.arange(1, 9, dtype="int64")
    with pytest.raises(ValueError, match="prefix_len"):
        eng.submit(p, 4, prefix_len=0)
    with pytest.raises(ValueError, match="prefix_len"):
        eng.submit(p, 4, prefix_len=9)
    eng.stop()


# ------------------------------------------------------- speculative decode
def test_spec_decode_bitwise_with_disagreeing_draft(seq_ref):
    """A random draft (near-zero acceptance) must cost only wasted
    drafts, never correctness: greedy AND sampled requests in one
    batch stay bitwise ``generate``'s."""
    rs = np.random.RandomState(4)
    p1 = rs.randint(1, 64, (5,)).astype("int64")
    p2 = rs.randint(1, 64, (4,)).astype("int64")
    eng = DecodeEngine(CFG, params=seq_ref.params, b_max=2,
                       max_len=MAX_LEN, draft_cfg=DRAFT_CFG,
                       spec_k=3).start()
    try:
        pr0 = _value("paddle_serving_spec_proposed_tokens_total")
        v0 = _value("paddle_serving_spec_verify_steps_total")
        r1 = eng.submit(p1, 10)                       # greedy -> spec
        r2 = eng.submit(p2, 8, temperature=0.9, top_k=8, seed=13)
        np.testing.assert_array_equal(r1.result(timeout=120),
                                      seq_ref.generate(p1, 10))
        np.testing.assert_array_equal(
            r2.result(timeout=120),
            seq_ref.generate(p2, 8, temperature=0.9, top_k=8, seed=13))
        assert _value("paddle_serving_spec_proposed_tokens_total") > pr0
        assert _value("paddle_serving_spec_verify_steps_total") > v0
    finally:
        eng.stop()


def test_spec_decode_agreeing_draft_accepts_k_per_dispatch(seq_ref):
    """Draft == target: every draft token matches the target's argmax
    chain, so each verify dispatch advances k+1 tokens — the whole
    speculative win, pinned via the acceptance counters."""
    rs = np.random.RandomState(5)
    p = rs.randint(1, 64, (4,)).astype("int64")
    eng = DecodeEngine(CFG, params=seq_ref.params, b_max=1,
                       max_len=MAX_LEN, draft_cfg=CFG,
                       draft_params=seq_ref.params, spec_k=3).start()
    try:
        pr0 = _value("paddle_serving_spec_proposed_tokens_total")
        a0 = _value("paddle_serving_spec_accepted_tokens_total")
        v0 = _value("paddle_serving_spec_verify_steps_total")
        n_new = 13
        out = eng.submit(p, n_new).result(timeout=120)
        np.testing.assert_array_equal(out, seq_ref.generate(p, n_new))
        proposed = _value("paddle_serving_spec_proposed_tokens_total") - pr0
        accepted = _value("paddle_serving_spec_accepted_tokens_total") - a0
        verifies = _value("paddle_serving_spec_verify_steps_total") - v0
        assert accepted == proposed > 0        # perfect agreement
        # 12 post-admission tokens in ceil(12 / (k+1)) = 3 dispatches,
        # not 12 — the (k+1)-tokens-per-dispatch mechanism itself
        assert verifies == 3, (verifies, accepted, proposed)
    finally:
        eng.stop()


def test_spec_decode_plain_fallback_near_cache_end(seq_ref):
    """A budget running to the cache edge forces plain iterations at
    the tail (a speculative slab would clamp and corrupt); outputs
    stay bitwise and the plain-step counter proves the fallback ran."""
    max_len = 16
    rs = np.random.RandomState(6)
    p = rs.randint(1, 64, (4,)).astype("int64")
    eng = DecodeEngine(CFG, params=seq_ref.params, b_max=1,
                       max_len=max_len, draft_cfg=CFG,
                       draft_params=seq_ref.params, spec_k=3).start()
    try:
        d0 = _value("paddle_serving_decode_steps_total")
        out = eng.submit(p, 12).result(timeout=120)   # 4 + 12 == max_len
        np.testing.assert_array_equal(out, seq_ref.generate(p, 12))
        # the final iterations could not fit pos + k + 1 and took the
        # plain path
        assert _value("paddle_serving_decode_steps_total") > d0
    finally:
        eng.stop()


# ------------------------------------------------------------------ router
def _mk_factory(seq_ref, store=None, b_max=2, queue_capacity=16):
    def factory(idx):
        return DecodeEngine(CFG, params=seq_ref.params, b_max=b_max,
                            max_len=MAX_LEN, prefix_store=store,
                            queue_capacity=queue_capacity)
    return factory


def test_router_routes_quota_and_tenant_label(seq_ref):
    rs = np.random.RandomState(7)
    router = ReplicaRouter(_mk_factory(seq_ref), n_replicas=2,
                           tenant_quotas={"burst": 1})
    try:
        ok0 = _value("paddle_serving_requests_total", outcome="ok",
                     tenant="burst")
        prompts = [rs.randint(1, 64, (4,)).astype("int64")
                   for _ in range(6)]
        reqs = [router.submit(p, 6) for p in prompts]
        # burst tenant: one in flight allowed, the second rejects NOW
        b1 = router.submit(prompts[0], 6, tenant="burst")
        with pytest.raises(TenantQuotaExceeded):
            router.submit(prompts[1], 6, tenant="burst")
        for p, r in zip(prompts, reqs):
            np.testing.assert_array_equal(r.result(timeout=120),
                                          seq_ref.generate(p, 6))
        np.testing.assert_array_equal(b1.result(timeout=120),
                                      seq_ref.generate(prompts[0], 6))
        # quota released at completion: burst admits again
        router.submit(prompts[2], 6, tenant="burst").result(timeout=120)
        # tenant label landed on the terminal outcomes
        assert _value("paddle_serving_requests_total", outcome="ok",
                      tenant="burst") == ok0 + 2
        assert _value("paddle_serving_requests_total",
                      outcome="rejected", tenant="burst") >= 1
        # 6 + the admitted burst pair = 8 dispatches (the quota
        # rejection never routes)
        routed = sum(
            _value("paddle_serving_router_routed_total",
                   replica=str(i)) for i in (0, 1))
        assert routed >= 8
    finally:
        router.close()


def test_router_slo_reject_early(seq_ref):
    """With a known (tiny) service-rate estimate and a loaded replica,
    a deadlined submit is rejected AT ADMISSION — projected wait beats
    the deadline — and counted/outcome'd as such."""
    router = ReplicaRouter(_mk_factory(seq_ref, b_max=1), n_replicas=1,
                           service_rate_tps=0.5)
    try:
        rs = np.random.RandomState(8)
        slow = [router.submit(rs.randint(1, 64, (4,)).astype("int64"),
                              20) for _ in range(3)]
        # 60 outstanding tokens at 0.5 tok/s/stream -> ~120s projected
        s0 = _value("paddle_serving_router_rejected_total",
                    reason="slo")
        with pytest.raises(DeadlineExpired, match="projected"):
            router.submit(rs.randint(1, 64, (4,)).astype("int64"), 4,
                          deadline_s=0.5)
        assert _value("paddle_serving_router_rejected_total",
                      reason="slo") == s0 + 1
        # a deadline the projection clears admits fine
        ok = router.submit(rs.randint(1, 64, (4,)).astype("int64"), 4,
                           deadline_s=1e6)
        for r in slow + [ok]:
            r.result(timeout=240)
    finally:
        router.close()


def _wait_until(cond, timeout_s=120.0, poll_s=0.05, what="condition"):
    """Poll a telemetry/health condition to its deadline — the
    counter-poll pattern: recovery (drain → factory rebuild → start)
    runs on the monitor thread and may still be mid-rebuild when the
    re-admitted requests complete on the survivor, so 'restarted and
    alive' is an EVENTUAL property, never an instant assert."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(poll_s)
    assert cond(), "timed out waiting for %s" % what


def test_router_chaos_wedge_drain_readmit_restart(seq_ref):
    """THE acceptance criterion: a replica wedged via FaultPlan is
    detected (stall deadline), drained (its in-flight requests
    re-admitted elsewhere), and restarted — and every request still
    reports exactly one terminal outcome, completing on survivors.

    Deflaked (PR 11's known timing flake): the stall deadline is
    CALIBRATED from measured warm-request latency instead of a fixed
    0.3s — on a loaded 2-share CI box a healthy request can take
    longer than any fixed guess, and a too-small deadline drains
    HEALTHY replicas until the re-admission budget is exhausted (the
    flake's mechanism). Detection arms only after warmup
    (router.set_stall_deadline), the wedge is sized off the same
    calibration, and the restarted-and-alive postcondition is polled
    (counter pattern), not asserted instantly — recovery runs on the
    monitor thread and legitimately trails request completion."""
    from paddle_tpu.resilience.faults import FaultPlan

    store = PrefixStore(16 << 20)
    # stall detection DISARMED during warmup: first-admission compiles
    # under load can exceed any steady-state deadline
    router = ReplicaRouter(_mk_factory(seq_ref, store=store, b_max=2),
                           n_replicas=2, stall_deadline_s=None,
                           poll_s=0.05, max_readmissions=3)
    try:
        rs = np.random.RandomState(9)
        shared = rs.randint(1, 64, (8,)).astype("int64")
        prompts = [np.concatenate(
            [shared, rs.randint(1, 64, (3,)).astype("int64")])
            for _ in range(8)]
        # warm both replicas end to end so every program is compiled
        # BEFORE the fault arms: the wedge must strike steady-state
        # decode, where stall detection (not compile grace) judges it —
        # and the warm pass doubles as the latency calibration
        per_req = 0.0
        for p in prompts[:4]:
            t0 = time.monotonic()
            router.submit(p, 6, prefix_len=8).result(timeout=240)
            per_req = max(per_req, time.monotonic() - t0)
        for rep in router.replicas:
            assert rep.engine.alive()
        # deadline: comfortably above a whole healthy request (progress
        # stamps land per decode STEP, so healthy age stays far below
        # this even when the box is slow); wedge: comfortably above the
        # deadline so detection fires mid-wedge
        stall_s = min(max(0.3, 2.0 * per_req), 10.0)
        wedge_s = 3.0 * stall_s + 1.0
        router.set_stall_deadline(stall_s)
        ok0 = _value("paddle_serving_requests_total", outcome="ok",
                     tenant="default")
        re0 = _value("paddle_serving_router_readmitted_total")
        rs0 = sum(_value("paddle_serving_router_replica_restarts_total",
                         replica=str(i)) for i in (0, 1))
        w0 = _value("paddle_resilience_faults_injected_total",
                    site="executor.dispatch", mode="wedge")
        plan = FaultPlan().arm("executor.dispatch", mode="wedge",
                               seconds=wedge_s, steps=(4,))
        with plan:
            done = []
            reqs = [router.submit(p, 6, prefix_len=8) for p in prompts]
            for r in reqs:
                r.add_done_callback(lambda _r: done.append(_r))
            outs = [r.result(timeout=240) for r in reqs]
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o, seq_ref.generate(p, 6))
        # the fault genuinely fired ...
        assert _value("paddle_resilience_faults_injected_total",
                      site="executor.dispatch", mode="wedge") == w0 + 1
        # ... the wedged replica was drained and its work re-admitted
        # (durable by the time results returned: done callbacks run
        # before result() wakes) ...
        assert _value("paddle_serving_router_readmitted_total") > re0
        # ... every request reports exactly ONE terminal outcome ...
        assert len(done) == len(reqs)
        assert {id(r) for r in done} == {id(r) for r in reqs}
        assert _value("paddle_serving_requests_total", outcome="ok",
                      tenant="default") == ok0 + len(reqs)
        # ... and the wedged replica is EVENTUALLY restarted and alive
        # (the rebuild may trail request completion — polled, not
        # instant)
        _wait_until(
            lambda: sum(_value(
                "paddle_serving_router_replica_restarts_total",
                replica=str(i)) for i in (0, 1)) > rs0,
            what="replica restart counter")
        _wait_until(
            lambda: all(rep.engine.alive() for rep in router.replicas),
            what="both replicas alive after restart")
    finally:
        router.close()


def test_requests_total_tenant_schema_pinned():
    """The per-tenant label satellite: schema (outcome, tenant) with
    every outcome pre-materialized for the default tenant."""
    snap = observe.snapshot()["metrics"]["paddle_serving_requests_total"]
    seen = {(s["labels"]["outcome"], s["labels"]["tenant"])
            for s in snap["samples"]}
    for o in ("ok", "rejected", "expired", "cancelled", "error"):
        assert (o, "default") in seen, (o, seen)
    for s in snap["samples"]:
        assert set(s["labels"]) == {"outcome", "tenant"}, s


# ------------------------------------------------------- perf acceptance
def _collect_params(c, max_len):
    scope = Scope()
    with scope_guard(scope):
        prog, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, start):
            _, cache_names = gpt.build_decode_step(c, batch=1,
                                                   max_len=max_len)
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(start, scope=scope)
        return {n: np.asarray(scope.find_var(n))
                for n in prog.global_block().vars
                if n.startswith("gpt_") and n not in cache_names
                and scope.find_var(n) is not None}


@pytest.mark.slow
def test_prefix_cache_throughput_on_shared_prefix_workload():
    """Acceptance: on a shared-prefix arrival mix the prefix cache
    drops prefill work proportionally to the hit rate and sustains
    >= 1.3x aggregate tokens/sec vs prefix-cache-off, outputs bitwise
    identical. The model/prompt are sized so prefill COMPUTE dominates
    dispatch overhead (a 192-token shared head on a d256/l4 model) —
    at toy scale the suffix path's extra splice dispatch wins nothing,
    which is exactly what the hit telemetry is for. Engines are built
    once (compiles out of the timed segments); calibrated best-of-5
    ratio, no absolute-ms asserts."""
    cfg = dict(d_model=256, d_ff=1024, n_head=4, n_layer=4, vocab=512,
               max_length=224, dropout=0.0)
    max_len, pre_len, n_new = 224, 192, 2
    params = _collect_params(cfg, max_len)
    rs = np.random.RandomState(11)
    shared = rs.randint(1, 512, (pre_len,)).astype("int64")
    prompts = [np.concatenate(
        [shared, rs.randint(1, 512, (8,)).astype("int64")])
        for _ in range(10)]

    eng_off = DecodeEngine(cfg, params=params, b_max=4, max_len=max_len,
                           queue_capacity=64).start()
    eng_on = DecodeEngine(cfg, params=params, b_max=4, max_len=max_len,
                          prefix_store=PrefixStore(256 << 20),
                          queue_capacity=64).start()

    def run(eng):
        reqs = [eng.submit(p, n_new, prefix_len=pre_len)
                for p in prompts]
        return [r.result(timeout=600) for r in reqs]

    try:
        # warm both paths once: compiles (prefill P, suffix S, decode,
        # splices) and the store's one miss stay out of the timing
        run(eng_off), run(eng_on)
        h0 = _value("paddle_serving_prefix_hits_total")
        for attempt in range(5):
            if attempt:
                time.sleep(1.0)
            t0 = time.perf_counter()
            outs_off = run(eng_off)
            dt_off = time.perf_counter() - t0
            t0 = time.perf_counter()
            outs_on = run(eng_on)
            dt_on = time.perf_counter() - t0
            for a, b in zip(outs_on, outs_off):
                np.testing.assert_array_equal(a, b)
            speedup = dt_off / dt_on
            print("prefix-cache off %.3fs  on %.3fs  speedup %.2fx"
                  % (dt_off, dt_on, speedup))
            if speedup >= 1.3:
                break
        # work avoidance proportional to hits: every cached-path
        # admission in the timed attempts hit the stored prefix
        assert _value("paddle_serving_prefix_hits_total") >= \
            h0 + len(prompts)
        assert speedup >= 1.3, (dt_off, dt_on)
    finally:
        eng_off.stop()
        eng_on.stop()


@pytest.mark.slow
def test_spec_decode_throughput_on_draft_friendly_workload():
    """Acceptance: >= 1.2x tokens/sec on a draft-friendly workload,
    acceptance rate visible in telemetry, outputs bitwise the
    spec-off engine's. Draft-friendly means two things here: the
    models AGREE (both output heads zeroed -> identical greedy
    chains), and the target is big enough (d1024/l3) that its step is
    weight-streaming-bound — so the k+1-position verify dispatch
    costs ~2 steps, not k+1, while the d32/l1 draft steps are cheap.
    (d512 was enough while the spec-off engine paid its host time
    every step; with one step in flight it no longer does, and the
    draft lane's iterations are still synchronous.)
    That is the same regime that makes speculative decoding pay on a
    memory-bound accelerator. Engines built once; calibrated
    best-of-5 ratio, no absolute-ms asserts."""
    cfg = dict(d_model=1024, d_ff=4096, n_head=8, n_layer=3, vocab=512,
               max_length=96, dropout=0.0)
    draft = dict(d_model=32, d_ff=64, n_head=2, n_layer=1, vocab=512,
                 max_length=96, dropout=0.0)
    rs = np.random.RandomState(12)
    prompts = [rs.randint(1, 512, (6,)).astype("int64")
               for _ in range(4)]
    n_new = 36

    # zero both models' output heads: logits identically 0, argmax
    # token 0 — the draft agrees with the target on every step
    def zero_heads(params):
        return {n: (np.zeros_like(v) if "out_proj" in n else v)
                for n, v in params.items()}

    params = zero_heads(_collect_params(cfg, 96))
    draft_params = zero_heads(_collect_params(draft, 96))

    eng_off = DecodeEngine(cfg, params=params, b_max=2, max_len=96,
                           queue_capacity=16).start()
    eng_on = DecodeEngine(cfg, params=params, b_max=2, max_len=96,
                          draft_cfg=draft, draft_params=draft_params,
                          spec_k=5, queue_capacity=16).start()

    def run(eng):
        reqs = [eng.submit(p, n_new) for p in prompts]
        return [r.result(timeout=600) for r in reqs]

    try:
        run(eng_off), run(eng_on)     # compiles out of the timing
        a0 = _value("paddle_serving_spec_accepted_tokens_total")
        p0 = _value("paddle_serving_spec_proposed_tokens_total")
        for attempt in range(5):
            if attempt:
                time.sleep(1.0)
            t0 = time.perf_counter()
            outs_off = run(eng_off)
            dt_off = time.perf_counter() - t0
            t0 = time.perf_counter()
            outs_on = run(eng_on)
            dt_on = time.perf_counter() - t0
            for a, b in zip(outs_on, outs_off):
                np.testing.assert_array_equal(a, b)
            speedup = dt_off / dt_on
            accepted = _value(
                "paddle_serving_spec_accepted_tokens_total") - a0
            proposed = _value(
                "paddle_serving_spec_proposed_tokens_total") - p0
            print("spec off %.3fs  on %.3fs  speedup %.2fx  "
                  "accept %.0f/%.0f"
                  % (dt_off, dt_on, speedup, accepted, proposed))
            if speedup >= 1.2:
                break
        assert proposed > 0 and accepted / proposed > 0.9
        assert speedup >= 1.2, (dt_off, dt_on)
    finally:
        eng_off.stop()
        eng_on.stop()
