"""A plan loads its program once (ISSUE 46).

A step given a committed argument returns committed state, so state that
reaches a plan's FIRST dispatch loose (what a startup program wrote, a
caller's own arrays) used to make the second dispatch another argument
signature, and ``jax.jit`` lowered and loaded (cold: compiled) the same
program again. ``core/executor.py::_commit_loose`` commits the loose
state arrays to the executor's place at the first dispatch of a plan
signature and puts them back in the scope:

* every plan signature has ONE backend load (``plan.loads``,
  ``paddle_executor_program_loads_total{again="1"}`` stays 0) through
  ``run``, ``run_repeated``, ``train_loop`` and a ``DecodeEngine``, and
  the first load's ``executor.dispatch`` span says how many arrays it
  committed (``committed``);
* the numbers are the uncommitted path's, bit for bit;
* an array committed to another device stays there, an executor without
  a place commits nothing, a startup program's own outputs stay loose,
  and a loose array put under a live plan is still counted as a load
  AGAIN.
"""

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import observe
from paddle_tpu.core import executor as executor_mod
from paddle_tpu.core.scope import Scope, scope_guard
from paddle_tpu.observe import families, trace
from paddle_tpu.serving import DecodeEngine

CFG = dict(d_model=32, d_ff=64, n_head=2, n_layer=2, vocab=64,
           max_length=32, dropout=0.0)
FEED = {"x": np.ones((8, 4), "float32")}
BACKEND = "executor.load.backend"


@pytest.fixture(autouse=True)
def _fresh_ring():
    observe.reset()
    yield
    observe.reset()


def _ended(site):
    return [e for e in trace.recorder().events()
            if e["ph"] == "E" and e["site"] == site]


def _again():
    return sum(families.PROGRAM_LOADS.labels(cache=c, again="1").value
               for c in ("hit", "miss", "off"))


def _train(place, dropout=0.0):
    """A two-layer train program over a scope its startup has filled."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    scope = Scope()
    with scope_guard(scope):
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", [4], dtype="float32")
            h = fluid.layers.fc(x, 8, act="relu")
            if dropout:
                h = fluid.layers.dropout(h, dropout)
            loss = fluid.layers.mean(fluid.layers.fc(h, 2))
            fluid.optimizer.Adam(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor(place)
        exe.run(startup, scope=scope)
    return exe, main, scope, loss


def _state(scope):
    return {n: scope.find_var(n) for n in scope.local_var_names()
            if isinstance(scope.find_var(n), jax.Array)}


def _drive_run(exe, main, scope, loss):
    return [exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)[0]
            for _ in range(3)]


def _drive_repeated(exe, main, scope, loss):
    return [exe.run_repeated(main, feed=FEED, fetch_list=[loss],
                             scope=scope, steps=2)[0] for _ in range(3)]


def _drive_loop(exe, main, scope, loss):
    out = []
    exe.train_loop(main, reader=lambda: iter([FEED] * 3),
                   fetch_list=[loss], scope=scope,
                   on_step=lambda i, vals: out.append(vals[0]))
    return out


DRIVES = {"run": _drive_run, "repeated": _drive_repeated,
          "loop": _drive_loop}


def _serve():
    """Two requests of one prompt length, then their decode steps."""
    eng = DecodeEngine(CFG, b_max=2, max_len=32, queue_capacity=8,
                       place=fluid.TPUPlace())
    observe.reset()
    with eng:
        for _ in range(2):
            eng.submit(np.arange(1, 6, dtype="int64"), 4).result(
                timeout=300)
    return eng._exe


# -------------------------------------------------- one load a signature
@pytest.mark.parametrize("how", ["run", "repeated", "loop", "engine"])
def test_a_plan_signature_loads_once(how):
    if how == "engine":
        exe = _serve()
    else:
        exe, main, scope, loss = _train(fluid.TPUPlace(), dropout=0.5)
        assert not any(a.committed for a in _state(scope).values())
        observe.reset()
        with scope_guard(scope):
            DRIVES[how](exe, main, scope, loss)
    plans = [p for p in exe._cache.values() if p.loads]
    assert plans and all(set(p.loads.values()) == {1} for p in plans)
    assert _again() == 0
    assert all(e["attrs"]["nth"] == 1 for e in _ended(BACKEND)
               if "nth" in e["attrs"])
    # the dispatches that loaded a program and committed what they were
    # handed loose: the train step its parameters, Adam's slots and the
    # key; each prefill length the caches its scratch startup drew
    firsts = {e["parent"] for e in _ended(BACKEND)}
    committed = [e["attrs"]["committed"]
                 for e in _ended("executor.dispatch")
                 if "committed" in e["attrs"]]
    assert committed and all(n > 0 for n in committed)
    assert all(e["span"] in firsts for e in _ended("executor.dispatch")
               if "committed" in e["attrs"])
    assert all("nth" not in e["attrs"]
               for e in _ended("executor.dispatch"))
    if how != "engine":
        held = _state(scope)
        assert held and all(a.committed for a in held.values())
        assert committed == [len(held)]


# ----------------------------------------------------- the same numbers
@pytest.mark.parametrize("how", ["run", "repeated", "loop"])
def test_the_numbers_are_the_uncommitted_paths(how, monkeypatch):
    def drive():
        exe, main, scope, loss = _train(fluid.TPUPlace(), dropout=0.5)
        with scope_guard(scope):
            losses = DRIVES[how](exe, main, scope, loss)
        # the two builds name their variables apart, in the same order
        return losses, [np.asarray(a) for _, a in
                        sorted(_state(scope).items())]

    losses, state = drive()
    with monkeypatch.context() as m:
        m.setattr(executor_mod, "_commit_loose",
                  lambda plan, scope, args, device: (args, 0))
        losses0, state0 = drive()
    assert len(losses) == 3
    for a, b in zip(losses, losses0):
        assert np.array_equal(a, b)
    assert len(state) == len(state0) > 0
    for a, b in zip(state, state0):
        assert np.array_equal(a, b)


# ------------------------------------------- what is left where it is
def test_the_scope_holds_what_was_dispatched():
    """The committed arrays are the scope's: what no step writes (the
    learning rate) stays committed there, so another plan over the same
    state has nothing to commit."""
    exe, main, scope, loss = _train(fluid.TPUPlace(), dropout=0.5)
    test_prog = main.clone(for_test=True)
    observe.reset()
    with scope_guard(scope):
        exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
        assert all(a.committed for a in _state(scope).values())
        exe.run(test_prog, feed=FEED, fetch_list=[loss], scope=scope)
    first, second = _ended("executor.dispatch")
    assert first["attrs"]["committed"] > 0
    assert "committed" not in second["attrs"]


def test_an_array_committed_to_another_device_stays_there():
    """Somebody put the parameters on a second device and feeds them
    there: they stay, and what is loose goes on following them."""
    other = jax.devices("cpu")[1]
    exe, main, scope, loss = _train(fluid.CPUPlace())
    assert exe._jax_device() != other
    params = [p.name for p in main.global_block().all_parameters()]
    for n in params:
        scope.set_var(n, jax.device_put(scope.find_var(n), other))
    observe.reset()
    feed = {"x": jax.device_put(FEED["x"], other)}
    with scope_guard(scope):
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    (dispatch,) = _ended("executor.dispatch")
    assert "committed" not in dispatch["attrs"]
    (plan,) = [p for p in exe._cache.values() if p.mut_state]
    assert plan.const_state and not any(
        scope.find_var(n).committed for n in plan.const_state)
    for n in params:
        assert scope.find_var(n).devices() == {other}


def test_an_executor_without_a_place_commits_nothing():
    """No place, nothing to commit to: a caller who feeds committed
    arrays sees the second load as before, and the counter says so."""
    exe, main, scope, loss = _train(None)
    observe.reset()
    feed = {"x": jax.device_put(FEED["x"], jax.devices()[0])}
    with scope_guard(scope):
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert all("committed" not in e["attrs"]
               for e in _ended("executor.dispatch"))
    (plan,) = [p for p in exe._cache.values() if p.mut_state]
    assert plan.loads == {"run": 2} and _again() == 1


def test_a_startup_programs_outputs_stay_loose():
    """A dispatch with no committed argument returns loose arrays and
    flips nothing: it is left alone, so what a startup program writes
    reaches a mesh engine as it always did."""
    exe, main, scope, loss = _train(fluid.TPUPlace())
    held = _state(scope)
    assert held and not any(a.committed for a in held.values())
    assert all("committed" not in e["attrs"]
               for e in _ended("executor.dispatch"))


def test_host_arrays_in_the_scope_are_committed_too():
    """A checkpoint restored as numpy arrays is loose like a startup
    program's output: the first step commits it, the second loads
    nothing."""
    exe, main, scope, loss = _train(fluid.TPUPlace())
    for n, a in _state(scope).items():
        scope.set_var(n, np.asarray(a))
    observe.reset()
    with scope_guard(scope):
        _drive_run(exe, main, scope, loss)
    assert _again() == 0
    (plan,) = [p for p in exe._cache.values() if p.mut_state]
    assert plan.loads == {"run": 1}
    assert all(a.committed for a in _state(scope).values())


def test_a_plan_that_draws_nothing_never_sees_the_scopes_key():
    """Its key argument is one constant: the scope's key turning from
    loose to committed under it (another program drew) is no new load."""
    exe, main, scope, loss = _train(fluid.TPUPlace())
    observe.reset()
    with scope_guard(scope):
        exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
        key = scope.find_var(executor_mod.RNG_VAR)
        assert not key.committed   # the startup's, never handed over
        scope.set_var(executor_mod.RNG_VAR,
                      jax.device_put(key, exe._jax_device()))
        exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    assert _again() == 0
    (plan,) = [p for p in exe._cache.values() if p.mut_state]
    assert not plan.needs_rng and plan.loads == {"run": 1}
