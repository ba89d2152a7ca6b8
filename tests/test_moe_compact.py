"""A share's expert call over enough pair rows cuts the sorted rows at a
static bound on the held pairs (``ops/moe_ops.py::compact_rows``) before
the gather, and takes the full length where the held pairs do not fit.

The full-length program (what every call ran before the bound existed,
and what a call without a bound still is) is had by lifting the
threshold out of reach: the tests compare the two on the same input.
The fallback IS the full-length code and agrees with it to the bit. So
does the cut branch of a dense share (1 expert in 4 held: each of a
token's choices gathers its row of the cut rows and they add in choice
order, as at full length). The cut branch of a sparse share (1 in 32)
sums the cut rows a token in a fixed tree over its experts' numbers and
agrees to float32 rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import moe_ops
from paddle_tpu.ops.moe_ops import _experts, compact_rows

K = 4
SHARES = {"1of32": (64, 2, 6), "1of4": (16, 4, 8)}   # E, n_local, first


def _weights(rng, act, E, n_local, D, F, with_xe):
    Dx = 24 if with_xe else D

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.2, jnp.float32)

    biased = act == "relu"
    return dict(
        w1=draw(n_local, Dx, F),
        w1v=draw(n_local, Dx, F) if act == "swiglu" else None,
        b1=draw(n_local, F) if biased else None,
        w2=draw(n_local, F, Dx),
        b2=draw(n_local, Dx) if biased else None,
        gate_w=draw(D, E))


def _call(x, w, E, share, act, xe=None):
    return _experts(x, w["w1"], w["w1v"], w["b1"], w["w2"], w["b2"],
                    w["gate_w"], E, K, None, act, True, 0.0, None, share,
                    xe)


def _run(x, w, E, share, act, xe=None):
    """One compiled call (the arrays as arguments: a closed-over one is
    a constant XLA folds the router's sort over)."""
    return jax.jit(lambda x, w, xe: _call(x, w, E, share, act, xe))(
        x, w, xe)


def _full_length(monkeypatch, *args):
    """The same call with the bound out of reach: the program before it."""
    with monkeypatch.context() as m:
        m.setattr(moe_ops, "_COMPACT_MIN_PAIRS", 1 << 62)
        out = _run(*args)
    assert out[3] is None
    return out


def _tokens_over_threshold():
    return -(-moe_ops._COMPACT_MIN_PAIRS // K)


@pytest.mark.parametrize("with_xe", [False, True], ids=["x", "xe"])
@pytest.mark.parametrize("act", ["swiglu", "relu2", "relu"])
@pytest.mark.parametrize("share", sorted(SHARES))
def test_cut_rows_equal_the_full_length(share, act, with_xe, monkeypatch):
    """(i) a share at 1/32 and at 1/4 under a seeded (near-even) router:
    the held pairs fit, the cut branch runs, and the layer's output is
    the full length's — to float32 rounding where the way back sums by
    token (1/32: a token's at most two terms in another order), to the
    bit where it gathers by choice (1/4)."""
    E, n_local, first = SHARES[share]
    T, D, F = _tokens_over_threshold(), 16, 40
    rng = np.random.default_rng(3)
    w = _weights(rng, act, E, n_local, D, F, with_xe)
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    xe = jnp.asarray(rng.standard_normal((T, 24)), jnp.float32) \
        if with_xe else None
    cap = compact_rows(K * T, E, n_local)
    assert cap is not None and cap % 128 == 0 and cap < K * T
    got = _run(x, w, E, (first, n_local), act, xe)
    want = _full_length(monkeypatch, x, w, E, (first, n_local), act, xe)
    assert int(got[3]) == 1
    assert int(jnp.sum(got[2][first:first + n_local])) <= cap
    assert got[0].shape == want[0].shape == (T, 24 if with_xe else D)
    if share == "1of4":
        np.testing.assert_array_equal(got[0], want[0])
    else:
        scale = float(jnp.max(jnp.abs(want[0])))
        np.testing.assert_allclose(got[0], want[0], rtol=0,
                                   atol=4e-7 * scale)
    np.testing.assert_array_equal(got[2], want[2])
    np.testing.assert_array_equal(got[1], want[1])


def _classed_input(n_a, n_b, E, picks_a, picks_b, D=16, seed=5):
    """``n_a`` tokens whose four experts are ``picks_a`` and ``n_b``
    whose four are ``picks_b``, interleaved: the class sits in the first
    two features, which alone the router reads."""
    rng = np.random.default_rng(seed)
    T = n_a + n_b
    x = rng.standard_normal((T, D)).astype("float32")
    is_a = np.zeros(T, bool)
    is_a[rng.permutation(T)[:n_a]] = True
    x[:, 0], x[:, 1] = is_a, ~is_a
    gate_w = np.zeros((D, E), "float32")
    gate_w[0, picks_a] = 8.0 + np.arange(4)
    gate_w[1, picks_b] = 8.0 + np.arange(4)
    return jnp.asarray(x), jnp.asarray(gate_w)


@pytest.mark.parametrize("held_pairs,took", [
    ("none", 1), ("at_the_bound", 1), ("one_past", 0), ("every_token", 0)])
def test_the_bound_is_met_exactly_and_overflow_drops_no_pair(
        held_pairs, took, monkeypatch):
    """(ii), (iii): held pairs counted to the row. None and exactly
    ``cap`` of them run the cut branch; two more (a token brings two) or
    a share every token chose run the full length — and then the output
    is the full-length program's to the bit: no pair is dropped."""
    E, n_local, first = 64, 2, 6
    T = _tokens_over_threshold()
    cap = compact_rows(K * T, E, n_local)
    n_a = {"none": 0, "at_the_bound": cap // 2, "one_past": cap // 2 + 1,
           "every_token": T}[held_pairs]
    # class a picks both held experts and two absent ones, b four absent
    x, gate_w = _classed_input(n_a, T - n_a, E, [6, 7, 30, 31],
                               [40, 41, 42, 43])
    w = _weights(np.random.default_rng(9), "swiglu", E, n_local, 16, 40,
                 False)
    w["gate_w"] = gate_w
    got = _run(x, w, E, (first, n_local), "swiglu")
    want = _full_length(monkeypatch, x, w, E, (first, n_local), "swiglu")
    assert int(jnp.sum(got[2][first:first + n_local])) == 2 * n_a
    assert int(got[3]) == took
    if took:
        np.testing.assert_allclose(got[0], want[0], rtol=0, atol=1e-6)
        assert bool(jnp.any(got[0] != 0)) == (n_a > 0)
    else:
        np.testing.assert_array_equal(got[0], want[0])


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


# the three share cells' decode steps (b_max tokens) and one call that
# holds every expert at a prefill's length
NO_BOUND = {
    "trinity_step_M64": (16, 256, 8, 4),
    "pangu_step_M512": (64, 256, 8, 8),
    "nemotron_step_M2112": (96, 512, 128, 22),
    "all_held_M8192": (2048, 64, 64, 4),
}


@pytest.mark.parametrize("case", sorted(NO_BOUND) + ["share_M8192"])
def test_only_a_long_share_call_holds_a_cond(case):
    """(iv) a decode step's call and a call that holds all its experts
    trace to the program they were: no ``cond``. (A share's long call
    holds exactly one.)"""
    T, E, n_local, k = NO_BOUND.get(case, (2048, 64, 2, 4))
    share = None if n_local == E else (0, n_local)
    D, F = 8, 16
    sds = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (T, D), (n_local, D, F), (n_local, F, D), (D, E))]

    def layer(x, w1, w2, gate_w):
        return _experts(x, w1, None, None, w2, None, gate_w, E, k, None,
                        "relu2", True, 0.0, None, share)[0]

    conds = list(_primitives(jax.make_jaxpr(layer)(*sds).jaxpr)) \
        .count("cond")
    assert conds == (1 if case == "share_M8192" else 0)
    assert (compact_rows(T * k, E, n_local) is None) == (conds == 0)


@pytest.mark.parametrize("wrt", ["x", "w1", "w2", "gate_w"])
def test_gradients_through_the_cut_branch_match_the_full_length(
        wrt, monkeypatch):
    """(v) ``jax.grad`` through a long share call."""
    E, n_local, first = SHARES["1of4"]
    T = _tokens_over_threshold()
    rng = np.random.default_rng(13)
    w = _weights(rng, "swiglu", E, n_local, 16, 40, False)
    x = jnp.asarray(rng.standard_normal((T, 16)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((T, 16)), jnp.float32)

    def loss(v):
        ws = dict(w, **({} if wrt == "x" else {wrt: v}))
        out = _call(v if wrt == "x" else x, ws, E, (first, n_local),
                    "swiglu")
        return jnp.sum(out[0] * r)

    at = x if wrt == "x" else w[wrt]
    got = jax.jit(jax.grad(loss))(at)
    with monkeypatch.context() as m:
        m.setattr(moe_ops, "_COMPACT_MIN_PAIRS", 1 << 62)
        want = jax.jit(jax.grad(loss))(at)
    assert float(jnp.max(jnp.abs(want))) > 0
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * float(
        jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("M,E,n_local,want", [
    (8 * 3328, 256, 8, 1664),       # Pangu's longest prompt: 13 tiles of 208
    (4 * 8192, 256, 8, 2048),       # Trinity's: 16 of 256
    (22 * 2048, 512, 128, 22528),   # Nemotron's: half
    (8 * 128, 256, 8, None),        # under the threshold
    (4 * 8192, 64, 64, None),       # all held
    (4096, 8, 4, None),             # twice the even part is everything
])
def test_compact_rows(M, E, n_local, want):
    assert compact_rows(M, E, n_local) == want


# ------------------------------------------------------------ the tally
def _tiny_share(monkeypatch):
    """Trinity's tiny cfg holding experts 8-9 of 16, prompts to 96
    tokens, with the threshold brought down to 256 pair rows so that an
    80-token prompt (320 rows, bound 128) is over it and a 12-token one
    (48 rows) under."""
    from tests.test_afmoe import _share_of, seeded_params, tiny_cfg

    monkeypatch.setattr(moe_ops, "_COMPACT_MIN_PAIRS", 256)
    cfg = tiny_cfg(n_expert_local=2, expert_first=8, max_length=128)
    params = dict(seeded_params(tiny_cfg(max_length=128), 43))
    for layer in range(1, 5):
        params = _share_of(params, 8, 2, layer)
    return cfg, params


@pytest.mark.parametrize("router", ["seeded", "hot_share"])
def test_the_engine_tallies_which_length_its_prefills_ran_at(
        router, monkeypatch):
    """Admissions over and under the threshold: ``routed_pairs()``
    refreshes ``paddle_moe_compact_calls`` with one call a long prompt
    and expert layer — ``compact`` under the seeded router, ``full``
    where a selection bias sends every token to both held experts (160
    held pairs against a bound of 128) — and none for the short prompt;
    a later prefill program's startup keeps what the earlier ones
    counted."""
    from paddle_tpu.observe.families import MOE_COMPACT_CALLS
    from paddle_tpu.serving import DecodeEngine
    from tests.test_afmoe import (_assert_matches_reference,
                                  _decode_in_company)

    cfg, params = _tiny_share(monkeypatch)
    if router == "hot_share":
        for layer in range(1, 5):
            bias = np.zeros(16, "float32")
            bias[8:10] = 50.0
            params["gpt_%d_moe_router_bias" % layer] = bias
    eng = DecodeEngine(cfg, params=params, b_max=3, max_len=100)
    assert eng.compact_calls() is None          # nothing has prefilled
    rng = np.random.default_rng(53)
    prompts = [rng.integers(1, 97, n) for n in (80, 12, 81)]
    toks, rows = _decode_in_company(eng, prompts, 3)
    _assert_matches_reference(cfg, params, prompts, toks, rows)
    eng.routed_pairs()
    tally = eng.compact_calls()
    col = {"seeded": 0, "hot_share": 1}[router]
    want = np.zeros((5, 2), "int32")
    want[1:, col] = 2                   # two long prompts, layer 0 dense
    np.testing.assert_array_equal(tally, want)
    for path, n in zip(("compact", "full"), want[3]):
        assert MOE_COMPACT_CALLS.labels(layer="3", path=path).value == n
