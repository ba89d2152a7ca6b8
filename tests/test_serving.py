"""Serving scheduler (paddle_tpu/serving/): request queue, dynamic
micro-batching, continuous batching for autoregressive decode.

Contracts pinned here:

* RequestQueue — bounded admission (reject-when-full, counted),
  deadlines over queue time, cancellation racing the pop, close()
  stranding nobody.
* MicroBatcher — a backlog coalesces into ONE Predictor dispatch whose
  per-request slices are bitwise what a solo run returns; validation
  and error propagation fail futures, never the batcher thread.
* DecodeEngine — per-request outputs bitwise-identical to
  ``gpt.generate`` (greedy AND seeded sampling), EOS/budget retirement
  frees the slot immediately, admission mid-flight, occupancy/
  admission/retirement telemetry.
* (slow) with staggered arrivals the engine sustains >= 1.5x aggregate
  tokens/sec over serving the same requests sequentially through
  ``generate()`` — the PR's acceptance criterion. The assertion is a
  RATIO of two measured segments with the calibrated re-try pattern of
  test_device_pipeline (this box has 20-60 ms scheduler noise; no
  absolute-ms asserts).
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
                                MicroBatcher, QueueFull, RequestQueue)

CFG = dict(d_model=32, d_ff=64, n_head=2, n_layer=2, vocab=64,
           max_length=16, dropout=0.0)


def _value(name, **labels):
    for s in observe.snapshot()["metrics"][name]["samples"]:
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s.get("value", s.get("count"))
    return 0.0


def _hist(name):
    s = observe.snapshot()["metrics"][name]["samples"][0]
    return s["count"], s["sum"]


# ------------------------------------------------------------------ queue
def test_queue_fifo_roundtrip_and_wait_telemetry():
    q = RequestQueue(capacity=4)
    w0 = _hist("paddle_serving_queue_wait_seconds")[0]
    a = q.submit("a")
    b = q.submit("b")
    assert len(q) == 2
    assert q.get().payload == "a"       # FIFO
    assert q.get().payload == "b"
    assert q.get(timeout=0.01) is None  # empty: timeout, not block
    assert _hist("paddle_serving_queue_wait_seconds")[0] == w0 + 2
    a.set_result(1)
    b.set_exception(RuntimeError("boom"))
    assert a.result(timeout=1) == 1
    assert a.result(timeout=1) == 1     # idempotent
    with pytest.raises(RuntimeError, match="boom"):
        b.result(timeout=1)
    assert isinstance(b.exception(timeout=1), RuntimeError)


def test_queue_backpressure_rejects_when_full():
    q = RequestQueue(capacity=2)
    r0 = _value("paddle_serving_queue_rejected_total")
    q.submit(1)
    q.submit(2)
    with pytest.raises(QueueFull, match="capacity 2"):
        q.submit(3)
    assert _value("paddle_serving_queue_rejected_total") == r0 + 1
    assert _value("paddle_serving_requests_total", outcome="rejected") >= 1
    # popping frees capacity again
    q.get()
    q.submit(3)
    with pytest.raises(ValueError):
        RequestQueue(capacity=0)


def test_queue_deadline_expires_at_pop_never_dispatches():
    q = RequestQueue(capacity=4)
    e0 = _value("paddle_serving_deadline_expirations_total")
    dead = q.submit("stale", deadline_s=0.0)   # expired on arrival
    live = q.submit("fresh")
    got = q.get(timeout=1)                     # skips+fails the expired one
    assert got.payload == "fresh"
    with pytest.raises(DeadlineExpired):
        dead.result(timeout=1)
    assert _value("paddle_serving_deadline_expirations_total") == e0 + 1
    # deadlines cover QUEUE time only: an admitted request can't expire
    got.set_result("ok")
    assert got.result(timeout=1) == "ok"
    with pytest.raises(ValueError):
        q.submit("x", deadline_s=-1)


def test_queue_cancel_wins_only_while_pending():
    q = RequestQueue(capacity=4)
    r = q.submit("x")
    assert r.cancel()
    assert not r.cancel()                      # second cancel lost
    with pytest.raises(Cancelled):
        r.result(timeout=1)
    assert q.get(timeout=0.01) is None         # cancelled: skipped at pop
    admitted = q.submit("y")
    assert q.get(timeout=1) is admitted
    assert not admitted.cancel()               # too late: already running
    admitted.set_result(5)
    assert admitted.result(timeout=1) == 5


def test_queue_close_fails_pending_and_refuses_submits():
    q = RequestQueue(capacity=4)
    pending = [q.submit(i) for i in range(3)]
    q.close()
    for r in pending:
        with pytest.raises(Cancelled):
            r.result(timeout=1)
    with pytest.raises(RuntimeError, match="closed"):
        q.submit("late")
    assert q.get(timeout=0.01) is None
    q.close()  # idempotent
    assert _value("paddle_serving_queue_depth") == 0


def test_admitted_request_cancelled_by_scheduler_counts_cancelled():
    # engine.stop()/batcher shutdown fail ADMITTED work with
    # Cancelled via set_exception — that must land in
    # outcome=cancelled, not read as an error-rate spike
    q = RequestQueue(capacity=2)
    c0 = _value("paddle_serving_requests_total", outcome="cancelled")
    e0 = _value("paddle_serving_requests_total", outcome="error")
    r = q.submit("x")
    assert q.get(timeout=1) is r          # admitted: cancel() is too late
    r.set_exception(Cancelled("scheduler stopped"))
    with pytest.raises(Cancelled):
        r.result(timeout=1)
    assert _value("paddle_serving_requests_total",
                  outcome="cancelled") == c0 + 1
    assert _value("paddle_serving_requests_total", outcome="error") == e0


def test_queue_get_unblocks_on_concurrent_submit():
    q = RequestQueue(capacity=4)
    got = []
    t = threading.Thread(target=lambda: got.append(q.get(timeout=5)),
                         daemon=True)
    t.start()
    time.sleep(0.05)
    q.submit("wake")
    t.join(timeout=5)
    assert not t.is_alive()
    assert got and got[0].payload == "wake"


# ---------------------------------------------------------------- batcher
@pytest.fixture(scope="module")
def predictor(tmp_path_factory):
    """Tiny saved model with warmup buckets [1, 4] — the batcher's
    coalesced batches ride the bucket router."""
    from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor

    model_dir = str(tmp_path_factory.mktemp("serving_pred"))
    scope = Scope()
    main, startup = fluid.Program(), fluid.Program()
    with scope_guard(scope):
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", [8], dtype="float32")
            pred = fluid.layers.fc(x, 4, act="softmax")
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        fluid.io.save_inference_model(model_dir, ["x"], [pred], exe,
                                      main_program=main)
    config = AnalysisConfig(model_dir=model_dir)
    config.warmup_batch_sizes = [1, 4]
    return create_paddle_predictor(config)


@pytest.mark.parametrize("rows", [(1, 1, 1), (2, 1), (4,), (3, 1)],
                         ids=["1+1+1", "2+1", "4", "3+1"])
def test_batcher_rows_equal_the_same_dispatch_run_directly(predictor, rows):
    """The contract ``MicroBatcher`` keeps on every backend: a backlog
    rides ONE dispatch, and each request gets back exactly its rows of
    it — bitwise what ``predictor.run`` returns for the concatenated
    backlog (which pads to the same [1, 4] bucket) sliced at the
    requests' boundaries.

    A request run ALONE is not the reference: on a CPU a ``[1,8]·[8,4]``
    product and a ``[4,8]·[8,4]`` product reduce in different orders and
    differ in the last bit, so alone-versus-batched is equal only within
    rounding. The benchmark's row-locality probes hold that stronger
    property where it matters, on the chip's decode step."""
    rs = np.random.RandomState(sum(rows) * 10 + len(rows))
    feeds = [{"x": rs.randn(n, 8).astype("float32")} for n in rows]
    whole = predictor.run(
        {"x": np.concatenate([f["x"] for f in feeds])})[0]
    assert whole.shape == (sum(rows), 4)

    b0 = _value("paddle_serving_batches_total")
    rows0 = _hist("paddle_serving_batch_rows")
    mb = MicroBatcher(predictor, max_rows=4, max_wait_s=0.2,
                      autostart=False)
    try:
        reqs = [mb.submit(f) for f in feeds]   # deterministic backlog
        mb.start()
        outs = [r.result(timeout=30) for r in reqs]
    finally:
        mb.close()
    # ONE dispatch carried the whole backlog (its rows pre-padding)...
    assert _value("paddle_serving_batches_total") == b0 + 1
    rows1 = _hist("paddle_serving_batch_rows")
    assert rows1[0] == rows0[0] + 1 and rows1[1] == rows0[1] + sum(rows)
    # ...and each request got bitwise its own rows of it back
    lo = 0
    for got, n in zip(outs, rows):
        assert len(got) == 1 and got[0].shape == (n, 4)
        np.testing.assert_array_equal(got[0], whole[lo:lo + n])
        lo += n


def test_batcher_validates_feeds(predictor):
    with MicroBatcher(predictor, autostart=False) as mb:
        with pytest.raises(ValueError, match="do not match"):
            mb.submit({"wrong": np.zeros((1, 8), "float32")})
        with pytest.raises(ValueError, match="row count"):
            mb.submit({"x": np.zeros((0, 8), "float32")})
    with pytest.raises(ValueError):
        MicroBatcher(predictor, max_rows=0)
    with pytest.raises(ValueError):
        MicroBatcher(predictor, max_wait_s=-1)


def test_batcher_never_exceeds_max_rows(predictor):
    """A request that would overflow max_rows seeds the NEXT batch
    instead of riding along: an overflowing batch would overflow the
    largest warmup bucket too — the exact steady-state recompile the
    batcher exists to prevent."""
    rs = np.random.RandomState(5)
    feeds = [{"x": rs.randn(2, 8).astype("float32")} for _ in range(3)]
    b0 = _value("paddle_serving_batches_total")
    with MicroBatcher(predictor, max_rows=3, max_wait_s=0.2,
                      autostart=False) as mb:
        reqs = [mb.submit(f) for f in feeds]   # 2+2+2 rows, cap 3
        mb.start()
        for f, r in zip(feeds, reqs):
            np.testing.assert_array_equal(r.result(timeout=30)[0],
                                          predictor.run(f)[0])
    # 2+2 > 3 at every coalesce attempt: three 2-row dispatches, and
    # every observed batch stayed within the cap
    assert _value("paddle_serving_batches_total") == b0 + 3


def test_batcher_rejects_non_batch_major_fetch_and_feed():
    class _StaticVar:
        name, shape = "static", (4, 4)       # no dynamic batch axis

    class _RowVar:
        name, shape = "rows", (None, 4)

    class _Block:
        vars = {"static": _StaticVar(), "rows": _RowVar()}

    class _Prog:
        def global_block(self):
            return _Block()

    class _Stub:
        program = _Prog()

        def __init__(self, fetch, feeds):
            self.fetch_vars = fetch
            self._feeds = feeds

        def get_input_names(self):
            return list(self._feeds)

    with pytest.raises(ValueError, match="batch-major fetches"):
        MicroBatcher(_Stub([_StaticVar()], ["rows"]))
    # a fixed-shape FEED works solo but breaks the first time two
    # requests coalesce — rejected at construction, not under load
    with pytest.raises(ValueError, match="batch-major feeds"):
        MicroBatcher(_Stub([_RowVar()], ["static"]))


def test_batcher_run_error_fails_the_batch_futures(predictor):
    # wrong inner dim: predictor.run raises inside the batcher thread —
    # every future in the batch gets the exception, the thread survives
    with MicroBatcher(predictor, max_rows=4, max_wait_s=0.1) as mb:
        bad = mb.submit({"x": np.zeros((1, 5), "float32")})
        with pytest.raises(Exception):
            bad.result(timeout=30)
        # the batcher is still serving after the failed batch
        ok = mb.submit({"x": np.zeros((1, 8), "float32")})
        assert ok.result(timeout=30)[0].shape == (1, 4)


def test_batcher_close_cancels_pending(predictor):
    mb = MicroBatcher(predictor, autostart=False)
    r = mb.submit({"x": np.zeros((1, 8), "float32")})
    mb.close()
    with pytest.raises(Cancelled):
        r.result(timeout=1)
    with pytest.raises(RuntimeError, match="closed"):
        mb.submit({"x": np.zeros((1, 8), "float32")})


# ----------------------------------------------------------------- engine
class _SeqRef:
    """The classic B=1 decode loop — the engine's parity reference. One
    program/executor/scope for the whole module (the KV caches are
    reusable across generates: the visibility mask hides stale rows
    past the current position); weights are startup-initialized with
    the same deterministic per-name seeds as the engine's scope."""

    def __init__(self):
        self.prog, start = fluid.Program(), fluid.Program()
        self.scope = Scope()
        with scope_guard(self.scope):
            with fluid.program_guard(self.prog, start):
                self.logits, _ = gpt.build_decode_step(CFG, batch=1,
                                                       max_len=16)
            self.exe = fluid.Executor(fluid.TPUPlace())
            self.exe.run(start, scope=self.scope)

    def generate(self, prompt, n_new, temperature=0.0, top_k=0, seed=0):
        with scope_guard(self.scope):
            return gpt.generate(self.exe, self.prog, self.logits,
                                prompt[None, :], n_new, self.scope,
                                temperature=temperature, top_k=top_k,
                                seed=seed)[0]


@pytest.fixture(scope="module")
def seq_ref():
    return _SeqRef()


@pytest.fixture(scope="module")
def engine():
    eng = DecodeEngine(CFG, b_max=2, max_len=16, queue_capacity=16)
    eng.start()
    yield eng
    eng.stop()


def test_engine_output_matches_generate_greedy_and_sampled(engine,
                                                           seq_ref):
    rs = np.random.RandomState(2)
    p1 = rs.randint(1, 64, (3,)).astype("int64")
    p2 = rs.randint(1, 64, (4,)).astype("int64")
    # greedy + seeded-sampling requests IN FLIGHT TOGETHER: each slot's
    # host-side sampler is private, so outputs are bitwise the B=1 path
    r1 = engine.submit(p1, 5)
    r2 = engine.submit(p2, 6, temperature=0.9, top_k=8, seed=13)
    np.testing.assert_array_equal(r1.result(timeout=120),
                                  seq_ref.generate(p1, 5))
    np.testing.assert_array_equal(
        r2.result(timeout=120),
        seq_ref.generate(p2, 6, temperature=0.9, top_k=8, seed=13))


def test_engine_admits_beyond_b_max_and_retires_slots(engine, seq_ref):
    rs = np.random.RandomState(3)
    a0 = _value("paddle_serving_slots_admitted_total")
    t0 = _value("paddle_serving_slots_retired_total")
    occ0 = _hist("paddle_serving_slot_occupancy_ratio")[0]
    # 4 requests over 2 slots with different budgets: the 3rd and 4th
    # are admitted into slots freed by retirement, not a fresh batch
    prompts = [rs.randint(1, 64, (3,)).astype("int64") for _ in range(4)]
    budgets = [5, 3, 4, 2]
    reqs = [engine.submit(p, n) for p, n in zip(prompts, budgets)]
    for p, n, r in zip(prompts, budgets, reqs):
        got = r.result(timeout=120)
        np.testing.assert_array_equal(got, seq_ref.generate(p, n))
    assert _value("paddle_serving_slots_admitted_total") == a0 + 4
    assert _value("paddle_serving_slots_retired_total") == t0 + 4
    assert _hist("paddle_serving_slot_occupancy_ratio")[0] > occ0
    assert _value("paddle_serving_slots_active") == 0  # drained


def test_engine_eos_retires_early(engine, seq_ref):
    rs = np.random.RandomState(4)
    p = rs.randint(1, 64, (3,)).astype("int64")
    ref = seq_ref.generate(p, 8)
    gen = [int(t) for t in ref[3:]]
    eos = gen[2]  # retire at the 3rd generated token (or earlier dup)
    want = gen[:gen.index(eos) + 1]
    got = engine.submit(p, 8, eos_id=eos).result(timeout=120)
    np.testing.assert_array_equal(got, np.concatenate([p, want]))


def test_engine_submit_validation(engine):
    p = np.array([1, 2, 3], dtype="int64")
    with pytest.raises(ValueError, match="max_len"):
        engine.submit(p, 99)
    with pytest.raises(ValueError, match="empty"):
        engine.submit(np.zeros((0,), "int64"), 2)
    with pytest.raises(ValueError, match="n_new"):
        engine.submit(p, 0)
    with pytest.raises(ValueError, match="temperature"):
        engine.submit(p, 2, temperature=-0.5)
    with pytest.raises(ValueError):
        DecodeEngine(CFG, b_max=0)


def test_engine_admission_failure_fails_the_popped_request():
    """A request that dies DURING admission (prefill compile error,
    bad params) was already popped — queue.close can't cancel it, so
    the scheduler must fail it explicitly or its caller hangs in
    result() forever. The engine then shuts down loudly: error state,
    queued requests cancelled, slots_active gauge at 0."""
    eng = DecodeEngine(CFG, b_max=2, max_len=16, queue_capacity=4)

    def boom(P):
        raise RuntimeError("prefill exploded")

    eng._lane._prefill_program = boom
    eng.start()
    r = eng.submit(np.array([1, 2, 3], dtype="int64"), 4)
    with pytest.raises(RuntimeError, match="prefill exploded"):
        r.result(timeout=30)              # terminal outcome, no hang
    eng._thread.join(timeout=10)
    assert _value("paddle_serving_slots_active") == 0
    with pytest.raises(RuntimeError, match="DecodeEngine failed"):
        eng.submit(np.array([1], dtype="int64"), 2)
    eng.stop()


def test_engine_stop_cancels_queued_requests():
    eng = DecodeEngine(CFG, b_max=1, max_len=16, queue_capacity=4)
    # never started: the queued request deterministically never runs
    r = eng.submit(np.array([1, 2], dtype="int64"), 3)
    eng.stop()
    with pytest.raises(Cancelled):
        r.result(timeout=1)
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(np.array([1], dtype="int64"), 2)


def test_engine_tokens_are_the_same_with_the_cache_write_kernel(
        monkeypatch):
    """The serving decode step with its per-slot cache writes as the
    in-place Pallas kernel (interpret mode here: the dispatch is told
    Pallas compiles, the kernel that it does not) answers with the same
    tokens as the composed form: 10 requests of mixed prompt and answer
    lengths over 4 slots, so slots retire and are written again by a
    later request at other positions."""
    from paddle_tpu.kernels import kv_cache_write as kvw
    from paddle_tpu.observe.families import KV_CACHE_WRITE_PLANS

    cfg = dict(CFG, max_length=128)      # 2 layers, 2 heads of 16
    assert kvw.write_plan((4, 2, 128, 16), "float32") == (
        "cols", (1, 2, 16, 128))
    rs = np.random.RandomState(7)
    prompts = [rs.randint(1, 64, (n,)).astype("int64")
               for n in (3, 9, 5, 3, 9, 5, 9, 3, 5, 9)]
    budgets = [4, 12, 2, 7, 3, 9, 5, 11, 6, 2]

    def serve():
        eng = DecodeEngine(cfg, b_max=4, max_len=128, queue_capacity=16)
        eng.start()
        try:
            reqs = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
            return [r.result(timeout=300) for r in reqs]
        finally:
            eng.stop()

    composed = serve()
    kernel = KV_CACHE_WRITE_PLANS.labels(form="pallas", rows="1")
    before = kernel.value
    run = kvw.kv_cache_write_pallas
    monkeypatch.setattr(kvw, "use_interpret", lambda: False)
    monkeypatch.setattr(
        kvw, "kv_cache_write_pallas",
        lambda cache, upd, pos, interpret=None:
        run(cache, upd, pos, interpret=True))
    with_kernel = serve()
    assert kernel.value - before == 2 * cfg["n_layer"]   # K and V a layer
    for got, want, p, n in zip(with_kernel, composed, prompts, budgets):
        assert len(want) == len(p) + n
        np.testing.assert_array_equal(got, want)


# ------------------------------------------------------ the occupancy proof
@pytest.mark.slow
def test_continuous_batching_beats_sequential_generate():
    """Acceptance criterion: staggered arrivals through the engine
    sustain >= 1.5x the aggregate tokens/sec of serving the same
    requests one after another through ``generate()`` (its best config:
    one-dispatch prefill), with bitwise-identical per-request outputs
    and the admission/retirement churn visible in the occupancy
    histogram. Ratio of two measured segments, re-tried up to 5 times —
    the box's 20-60 ms scheduler noise can eat one attempt's margin,
    but a genuine regression fails all 5."""
    b_max, P, max_len = 8, 4, 24
    cfg = dict(CFG, max_length=max_len)
    budgets = [10, 12, 14, 16] * 4              # staggered retirements
    rs = np.random.RandomState(5)
    prompts = [rs.randint(1, 64, (P,)).astype("int64") for _ in budgets]
    total_new = sum(budgets)

    # sequential path: ONE warm executor/scope, prefill + decode programs
    dec_prog, dec_start = fluid.Program(), fluid.Program()
    pre_prog, pre_start = fluid.Program(), fluid.Program()
    seq_scope = Scope()
    with scope_guard(seq_scope):
        with fluid.program_guard(dec_prog, dec_start):
            logits, cache_names = gpt.build_decode_step(cfg, batch=1,
                                                        max_len=max_len)
        with fluid.program_guard(pre_prog, pre_start):
            pl, _ = gpt.build_prefill_step(cfg, batch=1, prompt_len=P,
                                           max_len=max_len)
        seq_exe = fluid.Executor(fluid.TPUPlace())
        seq_exe.run(dec_start, scope=seq_scope)
        seq_exe.run(pre_start, scope=seq_scope)
        # the engine must decode with the SAME weights this reference
        # uses: collect the named gpt_* parameters (startup inits are
        # stream-ordered, not name-seeded, so two scopes' draws differ)
        # and hand them to the engine below. Caches stay out — their
        # batch dim is the engine's b_max, not 1.
        params = {n: np.asarray(seq_scope.find_var(n))
                  for n in dec_prog.global_block().vars
                  if n.startswith("gpt_") and n not in cache_names
                  and seq_scope.find_var(n) is not None}

    def run_sequential():
        outs = []
        with scope_guard(seq_scope):
            t0 = time.perf_counter()
            for p, n in zip(prompts, budgets):
                outs.append(gpt.generate(
                    seq_exe, dec_prog, logits, p[None, :], n, seq_scope,
                    prefill_prog=pre_prog, prefill_logits=pl)[0])
            return time.perf_counter() - t0, outs

    engine = DecodeEngine(cfg, params=params, b_max=b_max,
                          max_len=max_len, queue_capacity=64)
    engine.start()

    def run_engine(seq_dt):
        """Staggered open-loop drive: the submit span stays well inside
        the engine's expected service time, so later requests genuinely
        arrive while earlier ones hold slots (and 16 requests over 8
        slots force mid-flight admission regardless of timing)."""
        gap = seq_dt / (12 * len(prompts))
        reqs = [None] * len(prompts)

        def drive():
            for i, (p, n) in enumerate(zip(prompts, budgets)):
                if i:
                    time.sleep(gap)
                reqs[i] = engine.submit(p, n)

        t0 = time.perf_counter()
        drv = threading.Thread(target=drive, daemon=True)
        drv.start()
        drv.join()
        outs = [r.result(timeout=600) for r in reqs]
        return time.perf_counter() - t0, outs

    try:
        # warm both paths with one FULL untimed round each: the first
        # concurrent engine pass pays one-time jit/compile costs (splice,
        # prefill, the b_max decode step) that must stay out of the
        # timed segments
        seq_dt, seq_outs = run_sequential()
        run_engine(seq_dt)

        for attempt in range(5):
            if attempt:
                time.sleep(1.0)
            seq_dt, seq_now = run_sequential()
            for a, b in zip(seq_now, seq_outs):
                np.testing.assert_array_equal(a, b)  # stable reference

            a0 = _value("paddle_serving_slots_admitted_total")
            r0 = _value("paddle_serving_slots_retired_total")
            occ0 = _hist("paddle_serving_slot_occupancy_ratio")

            eng_dt, eng_outs = run_engine(seq_dt)

            # bitwise parity with the sequential path, request by request
            for got, ref in zip(eng_outs, seq_outs):
                np.testing.assert_array_equal(got, ref)

            # admission/retirement visible in the occupancy telemetry
            assert _value("paddle_serving_slots_admitted_total") == \
                a0 + len(prompts)
            assert _value("paddle_serving_slots_retired_total") == \
                r0 + len(prompts)
            occ1 = _hist("paddle_serving_slot_occupancy_ratio")
            steps = occ1[0] - occ0[0]
            mean_occ = (occ1[1] - occ0[1]) / steps
            assert steps > 0
            # staggered budgets + tail drain: occupancy moved below full
            # batch at least sometimes, and the batch was genuinely shared
            assert 0.25 < mean_occ < 1.0, mean_occ
            assert _value("paddle_serving_slots_active") == 0

            speedup = seq_dt / eng_dt
            print("sequential %.3fs (%.0f tok/s)  engine %.3fs "
                  "(%.0f tok/s)  speedup %.2fx  mean occupancy %.2f"
                  % (seq_dt, total_new / seq_dt, eng_dt,
                     total_new / eng_dt, speedup, mean_occ))
            if speedup >= 1.5:
                break
        assert speedup >= 1.5, (seq_dt, eng_dt)
    finally:
        engine.stop()
