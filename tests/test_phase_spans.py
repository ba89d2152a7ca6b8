"""The one span type where the host works (ISSUE 24).

* a ``Span`` under a live ``jax.profiler`` trace is in the host plane of
  the ``.xplane.pb`` under its site name, nested as in the ring; with
  ``PADDLE_TPU_TRACE=0`` nothing is recorded and no annotation made;
* ``Executor`` and ``ParallelEngine`` record ``executor.call`` round the
  phases of a call (gather, place, dispatch, complete, write_back), and
  the mesh dispatch goes through the Executor's guard;
* a ``DecodeEngine`` stamps one ``serving.request.first_token`` a request
  and its token gaps are rebuilt from the step spans' ``traces``;
* every Pallas call site has a name the program chose, and the names
  reach the lowered HLO.
"""

import contextlib
import glob
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import observe
from paddle_tpu.core.scope import Scope, scope_guard
from paddle_tpu.observe import trace
from paddle_tpu.serving import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CFG = dict(d_model=32, d_ff=64, n_head=2, n_layer=2, vocab=64,
           max_length=32, dropout=0.0)
PHASES = {"executor.gather", "executor.dispatch", "executor.complete",
          "executor.write_back"}


@pytest.fixture(autouse=True)
def _fresh_ring():
    observe.reset()
    yield
    observe.reset()


def _ended(site=None):
    return [e for e in trace.recorder().events() if e["ph"] == "E"
            and (site is None or e["site"] == site)]


def _mlp(batch=8):
    main, startup = fluid.Program(), fluid.Program()
    scope = Scope()
    with scope_guard(scope):
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", [4], dtype="float32")
            loss = fluid.layers.mean(fluid.layers.fc(x, 2))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
    observe.reset()   # the startup run's spans are not the test's
    return exe, main, scope, loss


# ------------------------------------------------- the profiler's clock
def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    found = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                found.append((ev.name, ev.start_ns,
                              ev.start_ns + ev.duration_ns))
    return found


def test_span_is_an_annotation_in_the_host_plane_nested_as_in_the_ring(
        tmp_path):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with trace.trace_span("executor.call", site="run", steps=1):
            with trace.trace_span("executor.gather"):
                jnp.ones((8, 8)).block_until_ready()
            with trace.trace_span("executor.dispatch", plan="p"):
                pass
    finally:
        jax.profiler.stop_trace()
    by_name = {}
    for name, lo, hi in _host_events(str(tmp_path)):
        by_name.setdefault(name, []).append((lo, hi))
    for site in ("executor.call", "executor.gather", "executor.dispatch"):
        assert len(by_name.get(site, ())) == 1, (site, sorted(by_name))
    (call,), (gather,), (dispatch,) = (by_name["executor.call"],
                                       by_name["executor.gather"],
                                       by_name["executor.dispatch"])
    assert call[0] <= gather[0] <= gather[1] <= dispatch[0] \
        <= dispatch[1] <= call[1]
    # the ring says the same
    ring = {e["site"]: e for e in _ended()}
    assert ring["executor.gather"]["parent"] == \
        ring["executor.call"]["span"] == \
        ring["executor.dispatch"]["parent"]


def test_trace_off_records_nothing_and_makes_no_annotation(monkeypatch):
    made = []

    class Counting:
        def __init__(self, name):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace, "_ANNOTATION", Counting)
    exe, main, scope, loss = _mlp()
    feed = {"x": np.ones((8, 4), "float32")}
    assert "executor.call" in made     # the startup run, tracing on
    del made[:]
    prior = trace.set_trace_enabled(False)
    try:
        with scope_guard(scope):
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        assert made == [] and len(trace.recorder()) == 0
        assert trace.recorder().recorded == 0
    finally:
        trace.set_trace_enabled(prior)
    with scope_guard(scope):
        exe.run(main, feed={"x": np.ones((4, 4), "float32")},
                fetch_list=[loss], scope=scope)
    # on again: one annotation a span, none for retroactive spans (a
    # batch of another size is another plan, whose first dispatch loads
    # its program: executor.load.*)
    assert sorted(made) == sorted(
        e["site"] for e in _ended()
        if not e["site"].startswith("executor.load."))
    assert any(e["site"] == "executor.load.backend" for e in _ended())
    assert "executor.call" in made


# ------------------------------------------------------ train path phases
@pytest.mark.parametrize("steps", [1, 3])
def test_executor_call_holds_the_phases(steps):
    exe, main, scope, loss = _mlp()
    feed = {"x": np.ones((8, 4), "float32")}
    with scope_guard(scope):
        for _ in range(2):
            if steps == 1:
                exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
            else:
                exe.run_repeated(main, feed=feed, fetch_list=[loss],
                                 scope=scope, steps=steps)
    calls = [e for e in _ended("executor.call")
             if e["attrs"]["steps"] == steps]
    assert len(calls) == 2
    assert calls[-1]["attrs"]["site"] == \
        ("run" if steps == 1 else "run_repeated")
    kids = [e for e in _ended() if e["parent"] == calls[-1]["span"]]
    assert {e["site"] for e in kids} == PHASES
    # h2d nests in gather, and the children fit inside the call
    (gather,) = [e for e in kids if e["site"] == "executor.gather"]
    assert any(e["parent"] == gather["span"]
               for e in _ended("executor.h2d"))
    assert sum(e["dur"] for e in kids) <= calls[-1]["dur"]


def _engine(scope, main, loss, n=4):
    from paddle_tpu.parallel import ParallelEngine
    from paddle_tpu.parallel.engine import make_mesh

    return ParallelEngine(main, loss_name=loss.name,
                          mesh=make_mesh(jax.devices()[:n]))


@pytest.mark.parametrize("path", ["run", "run_repeated", "engine.run",
                                  "engine.run_repeated"])
def test_one_dispatch_body(path, capsys):
    """A ``profiler.profiler()`` session is a reader of the ring, not a
    second path through the call: the span sequence and the fetches of
    two calls inside a session equal those of two calls outside one."""
    from paddle_tpu import profiler

    def two_calls(in_session):
        exe, main, scope, loss = _mlp()
        feed = {"x": np.arange(32, dtype="float32").reshape(8, 4)}
        on_mesh = path.startswith("engine.")
        target = _engine(scope, main, loss) if on_mesh else exe
        args = (feed, [loss], scope) if on_mesh else (main, feed, [loss],
                                                      scope)
        kw = {} if path.endswith("run") else {"steps": 3}
        call = target.run_repeated if kw else target.run
        session = profiler.profiler(state="CPU") if in_session \
            else contextlib.nullcontext()
        with scope_guard(scope), session:
            fetched = [call(*args, **kw) for _ in range(2)]
        ids = {e["span"]: e["site"] for e in _ended()}
        spans = [(e["site"], ids.get(e["parent"]),
                  e["attrs"] if e["site"] == "executor.call" else None)
                 for e in trace.recorder().events() if e["ph"] == "B"
                 # which stages JAX runs again depends on what its
                 # in-memory caches hold from the other pair of calls
                 and not e["site"].startswith("executor.load.")]
        return spans, fetched

    plain, plain_out = two_calls(False)
    observe.reset()
    profiled, profiled_out = two_calls(True)
    assert "executor.call" in capsys.readouterr().out  # the session's table
    assert [s for s, _p, _a in plain].count("executor.call") == 2
    assert profiled == plain
    np.testing.assert_array_equal(np.asarray(profiled_out),
                                  np.asarray(plain_out))


def test_parallel_engine_call_holds_the_phases_and_counts_what_moves():
    exe, main, scope, loss = _mlp()
    engine = _engine(scope, main, loss)
    feed = {"x": np.ones((8, 4), "float32")}
    with scope_guard(scope):
        engine.run_repeated(feed, [loss], scope, steps=2)
        (first,) = _ended("executor.place")
        # feeds already where the plan wants them: nothing left to move
        (plan,) = engine._cache.values()
        _fn, feed_in = engine._multi_fn(plan, 2, False)
        placed = {n: jax.device_put(jnp.asarray(feed[n]), s)
                  for n, s in zip(plan.feed_names, feed_in)}
        observe.reset()
        engine.run_repeated(placed, [loss], scope, steps=2)
    (call,) = _ended("executor.call")
    assert call["attrs"] == {"site": "run_repeated", "steps": 2}
    kids = [e for e in _ended() if e["parent"] == call["span"]]
    assert {e["site"] for e in kids} == PHASES | {"executor.place"}
    (place,) = [e for e in kids if e["site"] == "executor.place"]
    # the feeds, the state the step wrote, and what no step ever writes
    # (the learning rate, the RNG key of a program that draws none: the
    # first call left them in the scope as placed) are where they
    # belong: nothing moves, every argument is handed through
    assert first["attrs"]["bytes"] >= 8 * 4 * 4 + (4 * 2 + 2) * 4
    assert first["attrs"]["resident"] == 0
    assert place["attrs"]["arrays"] == 0 and place["attrs"]["bytes"] == 0
    assert place["attrs"]["resident"] == \
        len(feed) + len(plan.const_state) + len(plan.mut_state) + 1
    (dispatch,) = [e for e in kids if e["site"] == "executor.dispatch"]
    assert dispatch["attrs"]["plan"] == plan.sig
    order = [e["site"] for e in sorted(kids, key=lambda e: e["t"])]
    assert order == ["executor.gather", "executor.place",
                     "executor.dispatch", "executor.write_back",
                     "executor.complete"]


def test_parallel_engine_first_call_moves_the_startup_state():
    exe, main, scope, loss = _mlp()
    engine = _engine(scope, main, loss)
    with scope_guard(scope):
        engine.run({"x": np.ones((8, 4), "float32")}, [loss], scope)
    (place,) = _ended("executor.place")
    # the feed (8 x 4 float32) and the startup program's single-device
    # parameters are not yet on the mesh
    assert place["attrs"]["arrays"] >= 3
    assert place["attrs"]["bytes"] >= 8 * 4 * 4 + (4 * 2 + 2) * 4


def test_parallel_engine_dispatch_goes_through_the_guard():
    """The heartbeat and the ``executor.dispatch`` fault point: a mesh
    dispatch that wedges or fails is seen like a one-chip one."""
    from paddle_tpu.resilience.faults import FaultPlan, InjectedFault
    from paddle_tpu.resilience.watchdog import heartbeat

    exe, main, scope, loss = _mlp()
    engine = _engine(scope, main, loss)
    feed = {"x": np.ones((8, 4), "float32")}
    with scope_guard(scope):
        engine.run(feed, [loss], scope)
        (plan,) = engine._cache.values()
        assert len(plan.compiled_sigs) == 1
        before = heartbeat().snapshot()["seq"]
        with FaultPlan().arm("executor.dispatch", every=True):
            with pytest.raises(InjectedFault):
                engine.run(feed, [loss], scope)
        # the failed dispatch closed its span (the guard's finally)
        assert len(_ended("executor.dispatch")) == 2
        engine.run(feed, [loss], scope)   # and the engine still runs
        # a begin and an end stamp a dispatch, the failed one included
        assert heartbeat().snapshot()["seq"] >= before + 4
        assert heartbeat().snapshot()["phase"] == "idle"


# ------------------------------------------------------- serving stamps
def test_first_token_stamps_and_token_gaps_rebuilt_from_the_steps():
    from benchmarks.lib import program_spans

    eng = DecodeEngine(CFG, b_max=2, max_len=32, queue_capacity=16)
    asked = [(5, 4), (7, 6), (4, 3)]   # (prompt length, new tokens)
    with eng:
        handles = [eng.submit(np.arange(1, 1 + p, dtype="int64"), n)
                   for p, n in asked]
        for h in handles:
            h.result(timeout=300)
    events = trace.recorder().events()
    ended = [e for e in events if e["ph"] == "E"]
    traces = [h.trace.trace_id for h in handles]
    stamps = program_spans.token_times({"program_spans": events})
    for tid, (plen, n_new) in zip(traces, asked):
        mine = [e for e in ended if e["trace"] == tid]
        (first,) = [e for e in mine
                    if e["site"] == "serving.request.first_token"]
        (wait,) = [e for e in mine if e["site"] == "serving.queue.wait"]
        (admit,) = [e for e in mine if e["site"] == "serving.engine.admit"]
        assert first["attrs"]["prompt_len"] == plen
        assert first["attrs"]["queued_s"] == pytest.approx(wait["dur"])
        # both start at the submit, on the recorder's clock
        assert first["t"] - first["dur"] == \
            pytest.approx(wait["t"] - wait["dur"], abs=1e-9)
        assert first["dur"] >= wait["dur"] + admit["dur"]
        assert first["t"] >= admit["t"]
        # admission = prefill + splice + the first-token sample + self
        kids = {e["site"] for e in ended if e["parent"] == admit["span"]}
        assert {"serving.engine.prefill", "serving.engine.splice",
                "serving.engine.sample"} <= kids
        # one emission a token: the admission's, then one a step
        times = stamps[tid]
        assert len(times) == n_new and times[0] == admit["t"]
        gaps = [b - a for a, b in zip(times, times[1:])]
        assert all(g > 0 for g in gaps)
        (done,) = [e for e in events if e["trace"] == tid
                   and e["site"] == "serving.request.done"]
        assert sum(gaps) == pytest.approx(times[-1] - times[0])
        # the request is retired inside its last step, before the step ends
        assert times[-2] < done["t"] <= times[-1]
    # a token gap for every iteration that handed tokens out (one that
    # only dispatched a step ahead names no rider)
    steps = [e for e in ended if e["site"] == "serving.engine.step"
             and e["attrs"]["traces"]]
    snap = observe.snapshot()["metrics"]
    assert snap["paddle_serving_ttft_seconds"]["samples"][0]["count"] == 3
    assert snap["paddle_serving_token_gap_seconds"]["samples"][0][
        "count"] == len(steps)


def test_a_decode_step_decomposes_into_its_phases():
    """One greedy request, four new tokens: the admission's, then three
    steps. With one step in flight (docs/SERVING.md) that is four loop
    iterations: the first only dispatches, two dispatch the next step
    and read the one before, the last only reads."""
    eng = DecodeEngine(CFG, b_max=2, max_len=32, queue_capacity=4)
    with eng:
        eng.submit(np.arange(1, 6, dtype="int64"), 4).result(timeout=300)
    ended = _ended()
    steps = [e for e in ended if e["site"] == "serving.engine.step"]

    def kids_of(step):
        return sorted((e for e in ended if e["parent"] == step["span"]),
                      key=lambda e: e["t"])

    assert [[e["site"] for e in kids_of(s)] for s in steps] == [
        ["serving.engine.feeds", "executor.call"],
        ["serving.engine.feeds", "executor.call", "executor.complete",
         "serving.engine.sample"],
        ["serving.engine.feeds", "executor.call", "executor.complete",
         "serving.engine.sample"],
        ["executor.complete", "serving.engine.sample"]]
    assert [(s["attrs"]["active"], s["attrs"]["ahead"],
             len(s["attrs"]["traces"])) for s in steps] == [
        (1, False, 0), (1, True, 1), (1, True, 1), (0, False, 1)]
    # the dispatch returns without waiting: the call holds every phase
    # but the wait, which is the step span's own child
    for step in steps[:3]:
        (call,) = [e for e in kids_of(step)
                   if e["site"] == "executor.call"]
        assert {e["site"] for e in ended if e["parent"] == call["span"]} \
            == PHASES - {"executor.complete"}
    (sample,) = [e for e in kids_of(steps[-1])
                 if e["site"] == "serving.engine.sample"]
    assert sample["attrs"]["active"] == 1


# --------------------------------------------------------- kernel names
def test_a_pallas_call_without_a_name_is_a_type_error():
    from paddle_tpu.kernels.common import checked_pallas_call

    with pytest.raises(TypeError, match="name"):
        checked_pallas_call(lambda *refs: None, grid=(1,), in_specs=[],
                            operands=[], out_specs=[], out_shape=[],
                            scratch_shapes=[], interpret=True)


def _flash_text():
    from paddle_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, None, 0.125)   # the forward op's
        again = flash_attention(q, k, v, None, 0.125)
        return jnp.sum(out.astype(jnp.float32)), jnp.sum(
            again.astype(jnp.float32) ** 2)

    def both(q, k, v):
        first = loss(q, k, v)[0]
        grads = jax.grad(lambda *a: loss(*a)[1], argnums=(0, 1, 2))(q, k, v)
        return first, grads

    x = jnp.ones((1, 2, 256, 64), jnp.float32)
    return jax.jit(both).lower(x, x, x).as_text(debug_info=True)


def _lowered(fn, *shapes):
    args = [jnp.zeros(sh, dt) for sh, dt in shapes]
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def _kv_cache_write_text():
    from paddle_tpu.kernels import kv_cache_write as kvw

    return _lowered(
        lambda c, u, p: kvw.kv_cache_write_pallas(c, u, p, interpret=True),
        ((4, 2, 256, 64), jnp.float32), ((4, 2, 1, 64), jnp.float32),
        ((4, 1), jnp.int32))


def _gmm_text():
    from paddle_tpu.kernels import moe_gmm

    def both(lhs, up, down, gs):
        h = moe_gmm.gmm_pallas(lhs, (up,), gs, name=moe_gmm.KERNEL_UP,
                               interpret=True)
        return moe_gmm.gmm_pallas(h, (down,), gs, name=moe_gmm.KERNEL_DOWN,
                                  interpret=True)

    return _lowered(both, ((40, 64), jnp.float32),
                    ((6, 64, 128), jnp.float32), ((6, 128, 64), jnp.float32),
                    ((6,), jnp.int32))


def _mla_decode_text():
    from paddle_tpu.kernels import mla_decode as K

    return _lowered(
        lambda q, c, p: K.mla_decode_pallas(q, c, p, d_c=32, scale=0.1,
                                            interpret=True),
        ((4, 8, 40), jnp.float32), ((4, 1, 128, 40), jnp.float32),
        ((4,), jnp.int32))


def _mhc_text():
    from paddle_tpu.kernels import mhc

    def both(x, phi, alpha, b, y):
        _h, coef, _dev = mhc.mhc_pre_pallas(
            x, phi, alpha, b, n=4, eps=1e-6, iters=20, hc_eps=1e-6,
            clamp=(-30.0, 30.0), interpret=True)
        return mhc.mhc_post_pallas(x, y, coef, n=4, interpret=True)

    return _lowered(both, ((37, 512), jnp.float32), ((512, 24), jnp.float32),
                    ((3,), jnp.float32), ((24,), jnp.float32),
                    ((37, 128), jnp.float32))


def _ssm_text():
    from paddle_tpu.kernels import ssm

    def both(state, x, dt, a, bm, cm):
        y, _ = ssm.ssm_scan_pallas(x, dt, a, bm, cm, chunk=128,
                                   interpret=True)
        return y, ssm.ssm_update_pallas(state, x[:, 0], dt[:, 0], a,
                                        bm[:, 0], cm[:, 0], interpret=True)

    f32 = jnp.float32
    return _lowered(both, ((2, 2, 128, 128), f32), ((2, 128, 256), f32),
                    ((2, 128, 4), f32), ((4,), f32), ((2, 128, 2, 128), f32),
                    ((2, 128, 2, 128), f32))


@pytest.mark.parametrize("lower,names", [
    (_flash_text, ["flash_fwd", "flash_refwd", "flash_bwd_dkv",
                   "flash_bwd_dq"]),
    (_kv_cache_write_text, ["kv_cache_write"]),
    (_gmm_text, ["moe_gmm_up", "moe_gmm_down"]),
    (_mla_decode_text, ["mla_decode"]),
    (_mhc_text, ["mhc_pre", "mhc_post"]),
    (_ssm_text, ["ssm_scan", "ssm_update"]),
], ids=["flash", "kv_cache_write", "moe_gmm", "mla_decode", "mhc", "ssm"])
def test_kernel_names_reach_the_lowered_stablehlo(lower, names):
    text = lower()
    for name in names:
        assert "/%s/" % name in text or "(%s)" % name in text, name


def test_every_pallas_call_site_in_the_package_passes_a_name():
    """No call site reaches ``pl.pallas_call`` but through
    ``checked_pallas_call``, which takes its name."""
    import re

    offenders = []
    for base, _dirs, files in os.walk(os.path.join(ROOT, "paddle_tpu")):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(base, f)
            text = open(path).read()
            if re.search(r"\bpl\.pallas_call\(", text) \
                    and not path.endswith(os.path.join("kernels",
                                                       "common.py")):
                offenders.append(path)
            for m in re.finditer(r"checked_pallas_call\(\n", text):
                call = text[m.end():m.end() + 200]
                if "name=" not in call:
                    offenders.append("%s: %s" % (path, call[:40]))
    assert offenders == []


def test_a_grad_op_lowers_inside_its_own_named_scope():
    """``core/autodiff.py``: what a grad op runs again of its forward is
    told from the forward op's own run by ``<op>_grad`` in the op name."""
    from paddle_tpu.core.lowering import LowerContext
    from paddle_tpu.core.registry import get_op

    grad = get_op("tanh_grad")
    assert grad.synthesized

    def f(x, g):
        return grad.lowering(
            LowerContext(), {"X": [x], "Out@GRAD": [g]},
            {"__fwd_in_slots__": {"X": 1}, "__fwd_out_slots__": {"Out": 1},
             "__diff__": [("X", 0)]})["X@GRAD"][0]

    x = jnp.ones((4,), jnp.float32)
    assert "tanh_grad" in jax.jit(f).lower(x, x).as_text(debug_info=True)


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no TPU compiler here: skip
        pytest.skip("get_topology_desc cannot describe a v5e here: %s" % exc)
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def test_the_four_flash_runs_are_four_instruction_names_on_a_v5e(
        v5e, monkeypatch):
    """What the device profile shows: XLA names a Pallas custom call
    after the innermost name scope, so the forward op's kernel, the grad
    op's rerun of it and the two backward kernels are each found by the
    string ``ops/attention.py`` wrote. Compiled for a described chip;
    nothing runs."""
    import re

    from paddle_tpu.ops import attention

    monkeypatch.setenv("PADDLE_TPU_FLASH_INTERPRET", "0")

    def step(q, k, v):
        out = attention.flash_attention(q, k, v, None, 0.125)
        with jax.named_scope("fused_attention_grad"):
            o2, vjp = jax.vjp(lambda a, b, c: attention.flash_attention(
                a, b, c, None, 0.125), q, k, v)
            return out, vjp(jnp.ones_like(o2))

    sds = [jax.ShapeDtypeStruct((2, 4, 512, 64), jnp.bfloat16,
                                sharding=v5e)] * 3
    text = jax.jit(step).lower(*sds).compile().as_text()
    names = [re.search(r"%(\S+) = ", line).group(1)
             for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(names) == 4
    for kernel in (attention.KERNEL_FWD, attention.KERNEL_REFWD,
                   attention.KERNEL_BWD_DKV, attention.KERNEL_BWD_DQ):
        assert sum(kernel in n for n in names) == 1, (kernel, names)
