"""One decode step in flight (ISSUE 31).

While every rider is greedy and no draft lane is attached,
``DecodeEngine`` dispatches step n+1 from the ids step n left on the
device and only then reads step n (docs/SERVING.md "One step in
flight"). Contracts pinned here:

* tokens are bitwise ``generate()``'s, and the synchronous loop's, with
  riders of different budgets retiring on different steps and their
  slots re-admitted at once; a rider that ends by length takes no row of
  the step after its last;
* a rider that ends by ``eos_id`` is found one step late: its extra row
  harms neither its neighbours nor the slot's next occupant (a slab, and
  a ring where it lands at ``pos mod window``), and
  ``paddle_serving_overrun_rows_total`` counts it;
* a sampled rider joining drops the loop to ``sync`` and its leaving
  returns it to ``ahead``; a draft lane keeps it ``sync`` throughout;
* ``stop()``, a failing step and an empty queue, each with a step in
  flight: every request terminal, every dispatched step awaited, the
  device-side tallies readable.
"""

import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observe
from paddle_tpu.core.scope import Scope, scope_guard
from paddle_tpu.models import gpt
from paddle_tpu.observe import trace
from paddle_tpu.serving import Cancelled, DecodeEngine

MAX_LEN = 80
CFG = dict(d_model=32, d_ff=64, n_head=2, n_layer=2, vocab=64,
           max_length=MAX_LEN, dropout=0.0)
DRAFT_CFG = dict(d_model=16, d_ff=32, n_head=2, n_layer=1, vocab=64,
                 max_length=MAX_LEN, dropout=0.0)
WINDOW = 8
# two window layers (rings of 8 rows) and a full one, the last two with
# sparse experts: the tallies of ``routed_pairs()`` are donated state of
# the decode step, like the caches
RING_CFG = dict(d_model=32, n_head=4, n_kv_head=2, d_head=8, n_layer=3,
                vocab=64, max_length=MAX_LEN, dropout=0.0, pos_emb="rope",
                rope_theta=10000.0, rope_layers="sliding",
                layer_types=["sliding", "sliding", "full"], window=WINDOW,
                norm="rms", norm_eps=1e-5, ffn_act="swiglu", d_ff=64,
                n_dense_layer=1, n_expert=8, expert_top_k=2, d_expert=16,
                router_score="sigmoid", norm_topk=True)


def _counts():
    # one snapshot: the engine's thread moves these a few lines apart
    metrics = observe.snapshot()["metrics"]

    def value(name, **labels):
        for s in metrics[name]["samples"]:
            if s["labels"] == labels:
                return s["value"]
        return 0.0

    return {"ahead": value("paddle_serving_step_dispatches_total",
                           dispatch="ahead"),
            "sync": value("paddle_serving_step_dispatches_total",
                          dispatch="sync"),
            "steps": value("paddle_serving_decode_steps_total"),
            "overrun": value("paddle_serving_overrun_rows_total"),
            "logits": value("paddle_serving_fetches_total", site="step",
                            fetch="logits")}


def _moved(before):
    """What the counters gained. Two readings that agree were taken
    between two steps of an engine that is still running (the chaperone
    of the synchronous case keeps it so)."""
    now = _counts()
    for _ in range(100):
        again = _counts()
        if again == now:
            break
        now = again
    return {k: v - before[k] for k, v in now.items()}


class _SeqRef:
    """The B=1 lockstep loop, ``generate()``: the parity oracle, and the
    parameters every dense engine of this module is given."""

    def __init__(self):
        self.prog, start = fluid.Program(), fluid.Program()
        self.scope = Scope()
        with scope_guard(self.scope):
            with fluid.program_guard(self.prog, start):
                self.logits, cache_names = gpt.build_decode_step(
                    CFG, batch=1, max_len=MAX_LEN)
            self.exe = fluid.Executor(fluid.TPUPlace())
            self.exe.run(start, scope=self.scope)
        self.params = {n: np.asarray(self.scope.find_var(n))
                       for n in self.prog.global_block().vars
                       if n.startswith("gpt_") and n not in cache_names
                       and self.scope.find_var(n) is not None}

    def generate(self, prompt, n_new, **kw):
        with scope_guard(self.scope):
            return gpt.generate(self.exe, self.prog, self.logits,
                                prompt[None, :], n_new, self.scope,
                                **kw)[0]


@pytest.fixture(scope="module")
def seq_ref():
    return _SeqRef()


def _ring_params():
    """Every parameter of RING_CFG drawn from one seed."""
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        gpt.build_serving_decode_step(RING_CFG, batch=1, max_len=16)
    rng = np.random.default_rng(31)
    out = {}
    for p in sorted(prog.global_block().all_parameters(),
                    key=lambda p: p.name):
        shape = tuple(p.shape)
        if len(shape) == 1:
            out[p.name] = rng.uniform(0.5, 1.5, shape).astype("float32")
        else:
            lim = (6.0 / (shape[-2] + shape[-1])) ** 0.5
            out[p.name] = rng.uniform(-lim, lim, shape).astype("float32")
    return out


class _AloneRef:
    """The oracle for RING_CFG, which ``generate()`` does not build: a
    fresh engine of the same ``b_max`` that serves each request alone,
    so no step of it carries a neighbour or a previous tenant's row."""

    def __init__(self, b_max):
        self.params = _ring_params()
        self.b_max = b_max

    def generate(self, prompt, n_new):
        with DecodeEngine(RING_CFG, params=self.params, b_max=self.b_max,
                          max_len=MAX_LEN) as eng:
            return eng.submit(prompt, n_new).result(timeout=300)


@pytest.fixture(scope="module")
def ring_ref():
    return _AloneRef(b_max=2)


def _engine(cfg, params, b_max, **kw):
    return DecodeEngine(cfg, params=params, b_max=b_max, max_len=MAX_LEN,
                        queue_capacity=32, **kw)


def _prompts(seed, lengths):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, 64, (n,)).astype("int64") for n in lengths]


def _idle(eng, timeout=30.0):
    """Wait until the scheduler holds no slot and no step in flight."""
    deadline = time.monotonic() + timeout
    while eng._busy() and time.monotonic() < deadline:
        time.sleep(0.002)
    assert not eng._busy()


# ------------------------- (a) budgets that retire on different steps
BUDGETS = {
    # more requests than slots: a slot freed at the dispatch of its
    # rider's last step is re-admitted while that step is in flight;
    # n_new 1 ends at the admission, 2 on the first step of the slot
    "b2": (2, [9, 5, 7, 1, 11, 2]),
    "b3": (3, [4, 12, 6, 6, 2, 9, 1, 3]),
    "b4": (4, [2, 3, 30, 5, 8, 13, 4]),
}


@pytest.mark.parametrize("case", sorted(BUDGETS))
def test_staggered_budgets_give_generates_tokens(seq_ref, case):
    b_max, budgets = BUDGETS[case]
    prompts = _prompts(len(case) + b_max, [3 + i % 5 for i in
                                           range(len(budgets))])
    eng = _engine(CFG, seq_ref.params, b_max)
    before = _counts()
    with eng:
        outs = [r.result(timeout=300) for r in
                [eng.submit(p, n) for p, n in zip(prompts, budgets)]]
        _idle(eng)
    for p, n, got in zip(prompts, budgets, outs):
        np.testing.assert_array_equal(got, seq_ref.generate(p, n))
    moved = _moved(before)
    # every step went out ahead but those that found nothing in flight
    assert moved["ahead"] + moved["sync"] == moved["steps"] > 0
    assert moved["ahead"] > moved["sync"] >= 1
    assert moved["overrun"] == 0 and moved["logits"] == 0
    assert not eng._flights


@pytest.mark.parametrize("case", sorted(BUDGETS))
def test_staggered_budgets_give_the_synchronous_loops_tokens(seq_ref, case):
    """The same requests beside a sampled chaperone that outlasts them:
    every step is read before the next goes out, as before this loop ran
    ahead, and each greedy rider's tokens are the same."""
    b_max, budgets = BUDGETS[case]
    prompts = _prompts(len(case) + b_max, [3 + i % 5 for i in
                                           range(len(budgets))])
    ahead = _engine(CFG, seq_ref.params, b_max + 1)
    with ahead:
        want = [r.result(timeout=300) for r in
                [ahead.submit(p, n) for p, n in zip(prompts, budgets)]]
    sync = _engine(CFG, seq_ref.params, b_max + 1)
    before = _counts()
    with sync:
        chaperone = sync.submit(prompts[0], MAX_LEN - len(prompts[0]),
                                temperature=0.7, seed=3)
        outs = [r.result(timeout=300) for r in
                [sync.submit(p, n) for p, n in zip(prompts, budgets)]]
        moved = _moved(before)
        assert not chaperone.done()
    assert moved["ahead"] == 0 and moved["sync"] == moved["steps"] > 0
    for a, b in zip(outs, want):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("budgets", [(3, 9), (2, 6), (7, 4)])
def test_a_rider_that_ends_by_length_takes_no_row_of_the_next_step(
        seq_ref, budgets):
    """Two riders, no queue: the ``active`` of the step spans says how
    many rows each dispatched step advanced. The shorter rider is known
    to finish when its last step goes out, so the step after holds one."""
    prompts = _prompts(5, [4, 6])
    eng = _engine(CFG, seq_ref.params, 2)
    # both admitted before the first step: one slot each, one queue pop
    hold = threading.Event()
    admit = eng._admit

    def admit_both(block):
        hold.wait(60)
        admit(block)

    eng._admit = admit_both
    observe.reset()
    with eng:
        reqs = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
        hold.set()
        outs = [r.result(timeout=300) for r in reqs]
        _idle(eng)
    for p, n, got in zip(prompts, budgets, outs):
        np.testing.assert_array_equal(got, seq_ref.generate(p, n))
    rows = [e["attrs"]["active"] for e in trace.recorder().events()
            if e["ph"] == "E" and e["site"] == "serving.engine.step"]
    short, long_ = sorted(n - 1 for n in budgets)
    # the last iteration of the burst only reads
    assert rows == [2] * short + [1] * (long_ - short) + [0]


# --------------------------------- (b) an eos rider found one step late
def _eos_case(generate, prompt, n_new):
    """(eos id, the tokens the request must return): the first generated
    token from the third on that no earlier generated token equals."""
    full = generate(prompt, n_new)
    made = full[len(prompt):].tolist()
    for j in range(2, n_new - 2):
        if made[j] not in made[:j]:
            return made[j], full[:len(prompt) + j + 1]
    raise AssertionError("no usable eos in %r" % (made,))


@pytest.mark.parametrize("kind", ["slab", "ring"])
def test_an_eos_rider_is_found_one_step_late_and_harms_nobody(
        seq_ref, ring_ref, kind):
    cfg, ref = ((CFG, seq_ref) if kind == "slab" else (RING_CFG, ring_ref))
    # the eos rider stands past the window when it ends, so in a ring its
    # extra row lands at pos mod window, on a row its successor's prompt
    # (shorter than the window) does not fill
    p_eos, p_near, p_next = _prompts(17, [WINDOW + 3, 5, 4])
    eos, want_eos = _eos_case(ref.generate, p_eos, 16)
    eng = _engine(cfg, ref.params, 2)
    before = _counts()
    with eng:
        r_eos = eng.submit(p_eos, 16, eos_id=eos)
        r_near = eng.submit(p_near, 30)
        r_next = eng.submit(p_next, 20)      # takes the eos rider's slot
        got_eos, got_near, got_next = (
            r.result(timeout=300) for r in (r_eos, r_near, r_next))
        _idle(eng)
    np.testing.assert_array_equal(got_eos, want_eos)
    assert len(want_eos) < len(p_eos) + 16 and got_eos[-1] == eos
    np.testing.assert_array_equal(got_near, ref.generate(p_near, 30))
    np.testing.assert_array_equal(got_next, ref.generate(p_next, 20))
    moved = _moved(before)
    assert moved["overrun"] == 1
    assert moved["ahead"] > 0 and not eng._flights


# ------------------------------- (c) a sampled rider joins and leaves
def test_a_sampled_rider_drops_the_loop_to_sync_and_back(seq_ref):
    pa, pb, pc = _prompts(7, [5, 3, 4])
    eng = _engine(CFG, seq_ref.params, 3)
    decode = eng._lane.decode
    seen, gate = [], threading.Event()

    def gated(token, pos, greedy=False):
        # what the loop picked: a step goes out ahead when one is in
        # flight, and then its tokens are a device array
        seen.append(("tokens" if greedy else "logits",
                     "ahead" if eng._flights else "sync",
                     isinstance(token, np.ndarray)))
        if len(seen) == 3:
            # hold the third all-greedy step until the sampled request
            # is queued: the next admission takes it
            assert gate.wait(60)
        return decode(token, pos, greedy=greedy)

    eng._lane.decode = gated
    before = _counts()
    with eng:
        ra = eng.submit(pa, 40)                       # greedy, long
        rc = eng.submit(pc, 30)                       # greedy, long
        while len(seen) < 3:
            time.sleep(0.002)
        rb = eng.submit(pb, 6, temperature=0.8, top_k=5, seed=21)
        gate.set()
        a, b, c = (r.result(timeout=300) for r in (ra, rb, rc))
        _idle(eng)
    np.testing.assert_array_equal(a, seq_ref.generate(pa, 40))
    np.testing.assert_array_equal(c, seq_ref.generate(pc, 30))
    np.testing.assert_array_equal(
        b, seq_ref.generate(pb, 6, temperature=0.8, top_k=5, seed=21))
    # the sampled rider rides its budget less the admission's token; the
    # step after it finds nothing in flight, and the loop runs ahead again
    assert seen == ([("tokens", "sync", True)]
                    + [("tokens", "ahead", False)] * 2
                    + [("logits", "sync", True)] * 5
                    + [("tokens", "sync", True)]
                    + [("tokens", "ahead", False)] * 30)
    moved = _moved(before)
    assert (moved["sync"], moved["ahead"], moved["logits"]) == (7, 32, 5)
    assert moved["overrun"] == 0


# --------------------------------------------- (d) a draft lane attached
def test_a_draft_lane_keeps_every_step_synchronous(seq_ref):
    p1, p2 = _prompts(4, [5, 4])
    eng = _engine(CFG, seq_ref.params, 2, draft_cfg=DRAFT_CFG, spec_k=3)
    decode = eng._lane.decode

    def watched(token, pos, greedy=False):
        assert not eng._flights and isinstance(token, np.ndarray)
        return decode(token, pos, greedy=greedy)

    eng._lane.decode = watched
    before = _counts()
    with eng:
        r1 = eng.submit(p1, MAX_LEN - 5)    # greedy, to the cache's end:
        r2 = eng.submit(p2, 8, temperature=0.9, top_k=8, seed=13)
        a, b = r1.result(timeout=300), r2.result(timeout=300)
        _idle(eng)
    np.testing.assert_array_equal(a, seq_ref.generate(p1, MAX_LEN - 5))
    np.testing.assert_array_equal(
        b, seq_ref.generate(p2, 8, temperature=0.9, top_k=8, seed=13))
    moved = _moved(before)
    # the tail that cannot fit k + 1 more rows takes plain steps
    assert moved["ahead"] == 0 and moved["sync"] == moved["steps"] >= 1


# ------------------- (e) teardown and idleness with a step in flight
def _tally_rows(eng):
    """Rows the decode step routed, from the device-side tally: every
    dispatched step routes all ``b_max`` rows on each expert layer."""
    tally = eng.routed_pairs()
    per_layer = tally.sum(axis=1).tolist()
    assert per_layer[0] == 0 and len(set(per_layer[1:])) == 1
    return per_layer[1] // RING_CFG["expert_top_k"]


def _watch(eng):
    """Count dispatches and reads of the engine's lane."""
    n = {"dispatched": 0, "read": 0}
    decode, read = eng._lane.decode, eng._lane.read

    def counting_decode(token, pos, greedy=False):
        out = decode(token, pos, greedy=greedy)
        n["dispatched"] += 1
        return out

    def counting_read(out):
        n["read"] += 1
        return read(out)

    eng._lane.decode, eng._lane.read = counting_decode, counting_read
    return n


@pytest.mark.parametrize("how", ["empty_queue", "stop", "failing_step"])
def test_nothing_stays_in_flight(ring_ref, how):
    prompts = _prompts(23, [6, 4, 9])
    eng = _engine(RING_CFG, ring_ref.params, 2)
    n = _watch(eng)
    reading, go = threading.Event(), threading.Event()
    if how == "stop":
        read = eng._lane.read

        def held_read(out):
            if n["read"] == 3:
                # step 4 is out, step 3 about to be read: stop() now
                reading.set()
                assert go.wait(60)
            return read(out)

        eng._lane.read = held_read
    if how == "failing_step":
        decode = eng._lane.decode

        def failing(token, pos, greedy=False):
            if n["dispatched"] == 4:
                assert eng._flights        # a step is in flight
                raise RuntimeError("step exploded")
            return decode(token, pos, greedy=greedy)

        eng._lane.decode = failing
    eng.start()
    reqs = [eng.submit(p, 12) for p in prompts]
    if how == "empty_queue":
        outs = [r.result(timeout=300) for r in reqs]
        _idle(eng)
        assert all(len(o) == len(p) + 12 for o, p in zip(outs, prompts))
        assert _tally_rows(eng) == n["dispatched"] * 2
        # and the idle engine takes the next request up from the host
        before = _counts()
        again = eng.submit(prompts[0], 12).result(timeout=300)
        np.testing.assert_array_equal(again, outs[0])
        assert _moved(before)["sync"] == 1
        _idle(eng)
        eng.stop()
    elif how == "stop":
        assert reading.wait(120)
        assert len(eng._flights) == 2
        threading.Timer(0.2, go.set).start()
        eng.stop()
        for r in reqs:
            assert r.done()
            with pytest.raises(Cancelled):
                r.result(timeout=1)
    else:
        for r in reqs[:2]:                 # the riders of both steps
            with pytest.raises(RuntimeError, match="step exploded"):
                r.result(timeout=300)
        with pytest.raises(Cancelled):     # still queued: no slot yet
            reqs[2].result(timeout=300)
        eng._thread.join(timeout=30)
        assert not eng.alive()
        eng.stop()
    # every step that went out was awaited, by the loop or by its drain,
    # and the donated tallies are settled arrays
    assert not eng._flights and not eng._busy()
    assert n["read"] == n["dispatched"] > 0
    assert _tally_rows(eng) == n["dispatched"] * 2
