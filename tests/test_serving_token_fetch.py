"""What a serving step and an admission bring to the host (ISSUE 29).

The decode step and the prefill choose the greedy token on the device
(``gpt.NEXT_TOKEN_VAR``); ``DecodeEngine`` fetches the ids when every
rider of a step is at temperature 0, the logits when one samples, and
for an admission one id or the last prompt position's one row.

Contracts pinned here:

* the in-program choice is ``sample_token(row, rng, 0.0)``'s index on
  rows built to part two argmax rules (ties, all-equal, -inf, NaN);
* an all-greedy run fetches nothing wider than ``b_max`` ids a step and
  one id an admission, and its tokens are bitwise ``generate()``'s;
* a sampled rider joining and leaving flips the step's fetch
  tokens -> logits -> tokens, and every rider decodes as it does alone;
* a sampled admission samples its first token from the one row;
* the speculative path and a prefix-store hit give the tokens they gave.
"""

import itertools
import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observe
from paddle_tpu.core.scope import Scope, scope_guard
from paddle_tpu.models import gpt
from paddle_tpu.serving import DecodeEngine, PrefixStore

MAX_LEN = 80
CFG = dict(d_model=32, d_ff=64, n_head=2, n_layer=2, vocab=64,
           max_length=MAX_LEN, dropout=0.0)
DRAFT_CFG = dict(d_model=16, d_ff=32, n_head=2, n_layer=1, vocab=64,
                 max_length=MAX_LEN, dropout=0.0)


def _fetches(site, fetch):
    for s in observe.snapshot()["metrics"][
            "paddle_serving_fetches_total"]["samples"]:
        if s["labels"] == {"site": site, "fetch": fetch}:
            return s["value"]
    return 0.0


def _fetch_counts():
    return {(site, fetch): _fetches(site, fetch)
            for site in ("step", "admit") for fetch in ("tokens", "logits")}


def _moved(before):
    return {k: v - before[k] for k, v in _fetch_counts().items()
            if v != before[k]}


class _SeqRef:
    """The B=1 lockstep loop, ``generate()``: the parity oracle, and
    the parameters every engine of this module is given."""

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


def _engine(seq_ref, **kw):
    kw.setdefault("b_max", 2)
    return DecodeEngine(CFG, params=seq_ref.params, max_len=MAX_LEN,
                        queue_capacity=16, **kw)


def _record_fetches(eng):
    """Every ``Executor.run`` of the engine from here on, as (feed
    names, shapes of what came back)."""
    seen, run = [], eng._exe.run

    def recording(program, feed=None, fetch_list=None, **kw):
        out = run(program, feed=feed, fetch_list=fetch_list, **kw)
        if fetch_list:
            seen.append((tuple(sorted(feed)),
                         [np.asarray(v).shape for v in out]))
        return out

    eng._exe.run = recording
    return seen


# ------------------------------------------------ (a) the choice itself
_NEG = -np.inf
ROWS = {
    "tie_at_two_indices": [0.5, 3.0, -1.0, 3.0, 2.0, 0.0, 1.0, 2.5],
    "tie_with_the_last_index": [0.0, 1.0, 7.0, 2.0, 3.0, 4.0, 5.0, 7.0],
    "maximum_at_the_last_index": [0.0, 1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.5],
    "all_equal": [1.25] * 8,
    "all_minus_inf": [_NEG] * 8,
    "minus_inf_padding": [_NEG, _NEG, -3.0, _NEG, -3.0, _NEG, _NEG, _NEG],
    "nan_row": [0.0, 9.0, np.nan, 1.0, np.nan, 9.5, 2.0, 3.0],
    "all_nan": [np.nan] * 8,
    "signed_zeros": [-0.0, 0.0, -0.0, 0.0, -1.0, -2.0, -3.0, -4.0],
    "float32_neighbours": [1.0, np.float32(1.0) + np.float32(2 ** -23),
                           1.0, np.float32(1.0) + np.float32(2 ** -23),
                           0.0, 0.0, 0.0, 0.0],
}


@pytest.fixture(scope="module")
def choose():
    """``gpt._greedy_token``, the op both builders append, over a fed
    [N, 8] float32 batch of rows."""
    prog, start = fluid.Program(), fluid.Program()
    scope = Scope()
    with scope_guard(scope):
        with fluid.program_guard(prog, start):
            rows = fluid.layers.data("rows", [8], dtype="float32")
            gpt._greedy_token(rows)
        exe = fluid.Executor(fluid.TPUPlace())

    def run(batch):
        with scope_guard(scope):
            (ids,) = exe.run(prog, feed={"rows": batch},
                             fetch_list=[gpt.NEXT_TOKEN_VAR], scope=scope)
        return ids

    return run


@pytest.mark.parametrize("case", sorted(ROWS))
def test_program_choice_is_sample_tokens_greedy_choice(choose, case):
    row = np.asarray(ROWS[case], dtype="float32")
    # in company too: the other rows of the batch are other cases
    batch = np.stack([row] + [np.asarray(ROWS[c], dtype="float32")
                              for c in sorted(ROWS) if c != case])
    want = [gpt.sample_token(r, np.random.RandomState(0), 0.0)
            for r in batch]
    got = choose(batch)
    assert got.shape == (len(batch),) and got.dtype == np.int32
    assert int(got[0]) == want[0] == gpt.sample_token(
        row, np.random.RandomState(0), 0.0, top_k=3)
    assert got.tolist() == want


# -------------------------------------- (b) an all-greedy run's fetches
@pytest.mark.parametrize("P", [1, 2, 64])
def test_greedy_run_fetches_ids_only_and_matches_generate(seq_ref, P):
    rs = np.random.RandomState(100 + P)
    prompts = [rs.randint(1, 64, (P,)).astype("int64") for _ in range(3)]
    budgets = [9, 5, 7]
    eng = _engine(seq_ref)
    seen = _record_fetches(eng)
    before = _fetch_counts()
    steps0 = observe.snapshot()["metrics"][
        "paddle_serving_decode_steps_total"]["samples"][0]["value"]
    eng.start()
    try:
        outs = [r.result(timeout=300) for r in
                [eng.submit(p, n) for p, n in zip(prompts, budgets)]]
    finally:
        eng.stop()
    for p, n, got in zip(prompts, budgets, outs):
        np.testing.assert_array_equal(got, seq_ref.generate(p, n))
    steps = observe.snapshot()["metrics"][
        "paddle_serving_decode_steps_total"]["samples"][0]["value"] - steps0
    assert steps > 0
    assert _moved(before) == {("step", "tokens"): steps,
                              ("admit", "tokens"): 3}
    # and by what actually crossed: b_max ids a step, one id an admission
    by_feed = {}
    for feeds, shapes in seen:
        by_feed.setdefault(feeds, set()).update(shapes)
    assert by_feed == {("pos", "token"): {(eng.b_max,)},
                       ("tokens",): {(1,)}}


# ------------------------- (c) a sampled rider joins and leaves a batch
def test_sampled_rider_flips_the_step_fetch_and_every_rider_is_solo(
        seq_ref):
    rs = np.random.RandomState(7)
    pa = rs.randint(1, 64, (5,)).astype("int64")
    pb = rs.randint(1, 64, (3,)).astype("int64")
    pc = rs.randint(1, 64, (4,)).astype("int64")
    eng = _engine(seq_ref, b_max=3)
    seen = _record_fetches(eng)
    decode = eng._lane.decode
    kinds, gate = [], threading.Event()

    def gated(token, pos, greedy=False):
        kinds.append("tokens" if greedy else "logits")
        if len(kinds) == 3:
            # hold the third all-greedy step until the sampled request
            # is queued: the next step boundary admits it
            assert gate.wait(60)
        return decode(token, pos, greedy=greedy)

    eng._lane.decode = gated
    before = _fetch_counts()
    eng.start()
    try:
        ra = eng.submit(pa, 40)                       # greedy, long
        rc = eng.submit(pc, 30)                       # greedy, long
        while len(kinds) < 3:
            threading.Event().wait(0.002)
        rb = eng.submit(pb, 6, temperature=0.8, top_k=5, seed=21)
        gate.set()
        a, b, c = (r.result(timeout=300) for r in (ra, rb, rc))
    finally:
        eng.stop()
    np.testing.assert_array_equal(a, seq_ref.generate(pa, 40))
    np.testing.assert_array_equal(c, seq_ref.generate(pc, 30))
    np.testing.assert_array_equal(
        b, seq_ref.generate(pb, 6, temperature=0.8, top_k=5, seed=21))
    runs = [(k, len(list(g))) for k, g in itertools.groupby(kinds)]
    # the sampled rider rides its budget less the admission's token
    assert runs == [("tokens", 3), ("logits", 5), ("tokens", 39 - 8)]
    assert _moved(before) == {("step", "tokens"): 34, ("step", "logits"): 5,
                              ("admit", "tokens"): 2, ("admit", "logits"): 1}
    step_shapes = [s[0] for f, s in seen if f == ("pos", "token")]
    assert [k for k, _ in itertools.groupby(step_shapes)] == [
        (3,), (3, 1, 64), (3,)]


# ---------------------------------------------- (d) a sampled admission
@pytest.mark.parametrize("P,seed", [(1, 3), (6, 11), (33, 5)])
def test_sampled_admission_first_token_is_generates(seq_ref, P, seed):
    prompt = np.random.RandomState(P).randint(1, 64, (P,)).astype("int64")
    eng = _engine(seq_ref, b_max=1)
    seen = _record_fetches(eng)
    before = _fetch_counts()
    eng.start()
    try:
        got = eng.submit(prompt, 1, temperature=1.3, top_k=7,
                         seed=seed).result(timeout=300)
    finally:
        eng.stop()
    np.testing.assert_array_equal(
        got, seq_ref.generate(prompt, 1, temperature=1.3, top_k=7,
                              seed=seed))
    # one row came back, the other P - 1 positions' logits did not
    assert _moved(before) == {("admit", "logits"): 1}
    assert seen == [(("tokens",), [(1, CFG["vocab"])])]


# ------------------- (e) the paths this change leaves fetching logits
def test_speculative_path_gives_the_tokens_it_gave(seq_ref):
    rs = np.random.RandomState(4)
    p1 = rs.randint(1, 64, (5,)).astype("int64")
    p2 = rs.randint(1, 64, (4,)).astype("int64")
    eng = _engine(seq_ref, draft_cfg=DRAFT_CFG, spec_k=3)
    before = _fetch_counts()
    eng.start()
    try:
        r1 = eng.submit(p1, MAX_LEN - 5)    # greedy, to the cache's end:
        r2 = eng.submit(p2, 8, temperature=0.9, top_k=8, seed=13)
        a, b = r1.result(timeout=300), r2.result(timeout=300)
    finally:
        eng.stop()
    np.testing.assert_array_equal(a, seq_ref.generate(p1, MAX_LEN - 5))
    np.testing.assert_array_equal(
        b, seq_ref.generate(p2, 8, temperature=0.9, top_k=8, seed=13))
    moved = _moved(before)
    # the admissions choose their fetch like any other; the tail that
    # cannot fit k + 1 more rows takes plain all-greedy steps
    assert moved[("admit", "tokens")] == 1
    assert moved[("admit", "logits")] == 1
    assert moved.get(("step", "tokens"), 0) >= 1


def test_prefix_store_hit_gives_the_tokens_it_gave(seq_ref):
    rs = np.random.RandomState(3)
    shared = rs.randint(1, 64, (10,)).astype("int64")
    prompts = [np.concatenate([shared,
                               rs.randint(1, 64, (4,)).astype("int64")])
               for _ in range(3)]
    eng = _engine(seq_ref, prefix_store=PrefixStore(64 << 20))
    before = _fetch_counts()
    eng.start()
    try:
        outs = [eng.submit(p, 6, prefix_len=10).result(timeout=300)
                for p in prompts]
    finally:
        eng.stop()
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(o, seq_ref.generate(p, 6))
    moved = _moved(before)
    # the miss prefills whole and fetches its id; the two hits run the
    # suffix dispatch, which still hands back logits
    assert moved[("admit", "tokens")] == 1
    assert moved[("admit", "logits")] == 2
    assert ("step", "logits") not in moved
