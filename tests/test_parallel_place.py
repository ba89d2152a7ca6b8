"""The mesh dispatch places what moved, not what it holds (ISSUE 33).

``ParallelEngine._execute`` hands an array that is already committed to
the sharding the plan asks for to the executable as it is; only what
fails that test goes through ``jax.device_put``:

* a second window moves the host feeds and nothing else, and the step's
  arguments are the scope's own objects;
* whatever comes into the scope from elsewhere (numpy, one device,
  another mesh) is placed again, once;
* the arithmetic is the parent's, bit for bit (``jax.device_put`` for
  every argument, kept here as ``_parent_window``);
* the int64 range check and the donation survive.
"""

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as fluid
from paddle_tpu import layers, observe
from paddle_tpu.core.executor import RNG_VAR, Executor, _feed_to_device
from paddle_tpu.core.scope import Scope, scope_guard
from paddle_tpu.observe import trace
from paddle_tpu.parallel import ParallelEngine, ShardingRules
from paddle_tpu.parallel.engine import make_mesh

STEPS = 2
PLANS = {
    "dp4": ((4, 1), lambda: ShardingRules()),
    "dp2_tp2": ((2, 2), lambda: ShardingRules(
        [(r"fc_.*\.w_0", P(None, "model"))], zero1=True)),
}


@pytest.fixture(autouse=True)
def _fresh_ring():
    observe.reset()
    yield
    observe.reset()


def _place_spans():
    return [e for e in trace.recorder().events()
            if e["ph"] == "E" and e["site"] == "executor.place"]


def _build(dropout=0.0):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        x = layers.data("x", [16])
        y = layers.data("y", [1], dtype="int64")
        h = layers.fc(x, size=32, act="relu")
        if dropout:
            h = layers.dropout(h, dropout_prob=dropout)
        probs = layers.fc(h, size=8, act="softmax")
        loss = layers.mean(layers.cross_entropy(probs, y))
        fluid.optimizer.Adam(learning_rate=0.05).minimize(loss)
    return main, startup, loss


def _started(startup):
    scope = Scope()
    with scope_guard(scope):
        fluid.Executor(fluid.TPUPlace()).run(startup, scope=scope)
    return scope


def _engine(main, loss, plan="dp4", devices=4):
    shape, rules = PLANS[plan]
    if devices != 4:
        shape = (devices, 1)
    return ParallelEngine(
        main, loss_name=loss.name, rules=rules(),
        mesh=make_mesh(jax.devices()[:devices], ("data", "model"), shape))


def _windows(n, seed=0):
    rs = np.random.RandomState(seed)
    return [{"x": rs.rand(STEPS, 8, 16).astype("float32"),
             "y": rs.randint(0, 8, size=(STEPS, 8, 1)).astype("int64")}
            for _ in range(n)]


def _window(engine, feed, loss, scope):
    (out,) = engine.run_repeated(feed, [loss], scope, steps=STEPS,
                                 feed_stacked=True)
    return out


def _state(scope):
    return {n: np.asarray(scope.find_var(n))
            for n in sorted(scope.local_var_names())}


def _parent_window(engine, feed, loss, scope):
    """The dispatch as the parent commit made it: every host feed to
    chip 0 first, then one ``jax.device_put`` for every argument,
    resident or not. Same plan, same executable."""
    block = engine.program.global_block()
    on_chip0 = {n: _feed_to_device(n, v, block.vars.get(n))
                for n, v in feed.items()}
    plan, feeds, const, mut, rng = engine._gather(on_chip0, [loss], scope)
    fn, feed_in = engine._multi_fn(plan, STEPS, True, "last")
    at = plan.state_shardings
    out = fn([jax.device_put(v, s) for v, s in zip(feeds, feed_in)],
             [jax.device_put(v, at[n])
              for n, v in zip(plan.const_state, const)],
             [jax.device_put(v, at[n])
              for n, v in zip(plan.mut_state, mut)],
             jax.device_put(rng, NamedSharding(engine.mesh, P())))
    (loss_val,) = Executor._finish(plan, scope, *out, True, "")
    return loss_val


# ------------------------------------------------ (a) resident = handed on
def test_second_window_moves_the_host_feeds_and_nothing_else():
    main, startup, loss = _build()
    scope = _started(startup)
    engine = _engine(main, loss)
    first, second = _windows(2)
    _window(engine, first, loss, scope)
    (plan,) = engine._cache.values()
    key = (STEPS, True, "last")
    fn, feed_in = plan.multi[key]
    seen = []

    def spy(*args):
        seen.append(args)
        return fn(*args)

    plan.multi[key] = (spy, feed_in)
    held = {n: scope.find_var(n)
            for n in plan.const_state + plan.mut_state + [RNG_VAR]}
    observe.reset()
    _window(engine, second, loss, scope)
    (place,) = _place_spans()
    n_state = len(plan.const_state) + len(plan.mut_state)
    assert place["attrs"]["arrays"] == len(second) == 2
    assert place["attrs"]["bytes"] == \
        second["x"].nbytes + second["y"].nbytes // 2   # int64 -> int32
    assert place["attrs"]["resident"] == n_state + 1
    # the step's arguments ARE the scope's objects, the key included
    ((feeds, const, mut, rng),) = seen
    for n, v in zip(plan.const_state, const):
        assert v is held[n], n
    for n, v in zip(plan.mut_state, mut):
        assert v is held[n], n
    assert rng is held[RNG_VAR]
    # and a host feed went to its sharding as it is: one array a feed,
    # split over the data axis below the window axis
    for v, s in zip(feeds, feed_in):
        assert isinstance(v, jax.Array) and v.sharding == s
        assert s.spec[:2] == (None, "data")


def test_what_no_step_writes_is_placed_once_and_kept_in_the_scope():
    main, startup, loss = _build()
    scope = _started(startup)
    engine = _engine(main, loss)
    (feed,) = _windows(1)
    lr = _named(scope, "learning_rate")
    before = scope.find_var(lr), scope.find_var(RNG_VAR)
    assert len(before[0].sharding.device_set) == 1
    _window(engine, feed, loss, scope)
    (plan,) = engine._cache.values()
    assert lr in plan.const_state and not plan.needs_rng
    repl = NamedSharding(engine.mesh, P())
    for name, old in zip((lr, RNG_VAR), before):
        now = scope.find_var(name)
        assert now is not old and now.sharding == repl
        np.testing.assert_array_equal(np.asarray(now), np.asarray(old))
    _window(engine, feed, loss, scope)
    assert scope.find_var(lr).sharding == repl
    first, second = _place_spans()
    assert first["attrs"]["resident"] == 0
    assert first["attrs"]["arrays"] == \
        2 + len(plan.const_state) + len(plan.mut_state) + 1
    assert second["attrs"]["arrays"] == 2


def test_a_feed_that_is_placed_already_counts_as_resident():
    main, startup, loss = _build()
    scope = _started(startup)
    engine = _engine(main, loss)
    (feed,) = _windows(1)
    _window(engine, feed, loss, scope)
    (plan,) = engine._cache.values()
    _fn, feed_in = plan.multi[(STEPS, True, "last")]
    placed = {n: jax.device_put(feed[n].astype(
        "int32" if n == "y" else "float32"), s)
        for n, s in zip(plan.feed_names, feed_in)}
    observe.reset()
    _window(engine, placed, loss, scope)
    (place,) = _place_spans()
    assert place["attrs"]["arrays"] == 0 and place["attrs"]["bytes"] == 0
    assert place["attrs"]["resident"] == \
        2 + len(plan.const_state) + len(plan.mut_state) + 1
    assert len(engine._cache) == 1   # the same plan as the host feed's


# ------------------------------------- (b) what comes from elsewhere moves
def _named(scope, prefix="", suffix=""):
    """The first variable of that kind (the name counters are the
    process's, so a test cannot spell them)."""
    return sorted(n for n in scope.local_var_names()
                  if n.startswith(prefix) and n.endswith(suffix))[0]


def _set_lr_numpy(scope, main, loss):
    scope.set_var(_named(scope, "learning_rate"),
                  np.asarray([0.01], "float32"))
    return 1


def _commit_weight_to_one_device(scope, main, loss):
    name = _named(scope, "fc_", ".w_0")
    scope.set_var(name, jax.device_put(np.asarray(scope.find_var(name)),
                                       jax.devices()[1]))
    return 1


def _restore_checkpoint_as_numpy(scope, main, loss):
    for n, v in _state(scope).items():
        scope.set_var(n, v)
    return len(scope.local_var_names())


def _run_another_mesh(scope, main, loss):
    other = _engine(main, loss, devices=2)
    _window(other, _windows(1, seed=5)[0], loss, scope)
    return len(scope.local_var_names())


@pytest.mark.parametrize("disturb", [
    _set_lr_numpy, _commit_weight_to_one_device,
    _restore_checkpoint_as_numpy, _run_another_mesh],
    ids=lambda f: f.__name__.strip("_"))
def test_state_from_elsewhere_is_placed_again(disturb):
    main, startup, loss = _build()
    scope = _started(startup)
    engine = _engine(main, loss)
    first, second = _windows(2)
    _window(engine, first, loss, scope)
    n_moved = disturb(scope, main, loss)
    start = _state(scope)
    observe.reset()
    got = _window(engine, second, loss, scope)
    place = _place_spans()[-1]
    (plan,) = engine._cache.values()
    n_args = 2 + len(plan.const_state) + len(plan.mut_state) + 1
    assert place["attrs"]["arrays"] == 2 + n_moved
    assert place["attrs"]["resident"] == n_args - 2 - n_moved
    # a fresh engine over the same values, all of them placed
    fresh = Scope()
    for n, v in start.items():
        fresh.set_var(n, v)
    want = _window(_engine(main, loss), second, loss, fresh)
    np.testing.assert_array_equal(got, want)
    end, fresh_end = _state(scope), _state(fresh)
    assert sorted(end) == sorted(fresh_end)
    for n in end:
        np.testing.assert_array_equal(end[n], fresh_end[n], err_msg=n)
    # and the window after it finds everything resident again
    observe.reset()
    _window(engine, first, loss, scope)
    (place,) = _place_spans()
    assert place["attrs"]["arrays"] == 2


# ------------------------------------------ (c) the parent's arithmetic
@pytest.mark.parametrize("plan_name", sorted(PLANS))
def test_three_windows_are_bit_for_bit_the_parents(plan_name):
    main, startup, loss = _build(dropout=0.3)
    feeds = _windows(3, seed=11)
    losses, finals = [], []
    for window in (_window, _parent_window):
        scope = _started(startup)
        engine = _engine(main, loss, plan_name)
        losses.append([window(engine, f, loss, scope) for f in feeds])
        (plan,) = engine._cache.values()
        assert plan.needs_rng
        if plan_name == "dp2_tp2":
            w = _named(scope, "fc_", ".w_0")
            assert plan.state_shardings[w].spec == P(None, "model")
            assert scope.find_var(w).sharding == plan.state_shardings[w]
        finals.append(_state(scope))
    ours, parents = losses
    assert len({float(v) for v in ours}) == 3
    for a, b in zip(ours, parents):
        assert a.tobytes() == b.tobytes()
    assert sorted(finals[0]) == sorted(finals[1])
    for n in finals[0]:
        assert finals[0][n].tobytes() == finals[1][n].tobytes(), n


# -------------------------------------------------- (d) the range check
@pytest.mark.parametrize("site", ["run", "run_repeated"])
def test_int64_feed_out_of_range_still_raises_on_the_mesh_path(site):
    main, startup, loss = _build()
    scope = _started(startup)
    engine = _engine(main, loss)
    (feed,) = _windows(1)
    feed["y"][0, 0, 0] = 2 ** 31
    with pytest.raises(OverflowError, match="feed 'y'"):
        if site == "run":
            engine.run({n: v[0] for n, v in feed.items()}, [loss], scope)
        else:
            _window(engine, feed, loss, scope)


def test_host_feed_of_64_bits_keys_the_plan_of_its_device_dtype():
    """A float64 / int64 host feed narrows as ``jnp.asarray`` narrowed
    it: one plan for both spellings of a feed."""
    main, startup, loss = _build()
    scope = _started(startup)
    engine = _engine(main, loss)
    (feed,) = _windows(1)
    a = _window(engine, feed, loss, scope)
    _window(engine, {"x": feed["x"].astype("float64"),
                     "y": feed["y"].astype("int32")}, loss, scope)
    assert len(engine._cache) == 1
    assert np.isfinite(a)


# ------------------------------------------------------- (e) donation
def test_donation_still_frees_the_previous_windows_state():
    main, startup, loss = _build()
    scope = _started(startup)
    engine = _engine(main, loss)
    first, second = _windows(2)
    _window(engine, first, loss, scope)
    (plan,) = engine._cache.values()
    old = {n: scope.find_var(n) for n in plan.mut_state}
    kept = {n: scope.find_var(n) for n in plan.const_state + [RNG_VAR]}
    _window(engine, second, loss, scope)
    for n, v in old.items():
        assert v.is_deleted(), n
        assert not scope.find_var(n).is_deleted(), n
    # what is not donated is the same live object still
    for n, v in kept.items():
        assert scope.find_var(n) is v and not v.is_deleted(), n
