"""Data/model-parallel engine tests on the 8-device virtual CPU mesh.

Reference analog: test_parallel_executor_mnist.py convergence parity —
single-device vs multi-device runs of the same program must match
(unittests/parallel_executor_test_base.py). Here the parity is exact
(same global batch, deterministic program), not loss-delta based.
"""

import numpy as np

import jax
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.parallel import ParallelEngine, ShardingRules
from paddle_tpu.parallel.engine import make_mesh
from paddle_tpu.parallel.sharding import P


def _build_mlp_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [32])
        y = layers.data("y", [1], dtype="int64")
        h = layers.fc(x, size=64, act="relu")
        probs = layers.fc(h, size=10, act="softmax")
        loss = layers.mean(layers.cross_entropy(probs, y))
        opt = fluid.optimizer.SGD(learning_rate=0.1)
        opt.minimize(loss)
    return main, startup, loss


def _batches(n, bs=16, seed=0):
    rs = np.random.RandomState(seed)
    for _ in range(n):
        yield (rs.rand(bs, 32).astype("float32"),
               rs.randint(0, 10, size=(bs, 1)).astype("int64"))


def _run(n_steps, parallel, rules=None, mesh=None):
    main, startup, loss = _build_mlp_program()
    scope = fluid.core.scope.Scope()
    with fluid.core.scope.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        losses = []
        if parallel:
            engine = ParallelEngine(main, loss_name=loss.name, mesh=mesh,
                                    rules=rules)
            run = lambda feed: engine.run(feed, [loss], scope)
        else:
            run = lambda feed: exe.run(main, feed=feed, fetch_list=[loss],
                                       scope=scope)
        for x, y in _batches(n_steps):
            (l,) = run({"x": x, "y": y})
            losses.append(float(l))
    return losses


def test_data_parallel_parity():
    single = _run(6, parallel=False)
    multi = _run(6, parallel=True)
    np.testing.assert_allclose(single, multi, rtol=1e-4, atol=1e-5)
    assert single[-1] < single[0]  # actually training


def test_feed_is_batch_sharded():
    main, startup, loss = _build_mlp_program()
    scope = fluid.core.scope.Scope()
    with fluid.core.scope.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        engine = ParallelEngine(main, loss_name=loss.name)
        x, y = next(iter(_batches(1)))
        engine.run({"x": x, "y": y}, [loss], scope)
        plan = next(iter(engine._cache.values()))
        assert plan.feed_shardings["x"].spec == P("data")


def test_tensor_parallel_fc():
    """fc weights column-sharded over a model axis: numeric parity with
    the replicated run (TP beyond reference parity, SURVEY §2.9)."""
    devs = jax.devices()
    mesh = make_mesh(devs, ("data", "model"), (2, 4))
    rules = ShardingRules([(r"fc_.*\.w_0", P(None, "model"))])
    single = _run(4, parallel=False)
    tp = _run(4, parallel=True, rules=rules, mesh=mesh)
    np.testing.assert_allclose(single, tp, rtol=1e-4, atol=1e-5)


def test_compiled_program_path():
    main, startup, loss = _build_mlp_program()
    scope = fluid.core.scope.Scope()
    with fluid.core.scope.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        prog = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
        for x, y in _batches(3):
            (l,) = exe.run(prog, feed={"x": x, "y": y}, fetch_list=[loss],
                           scope=scope)
        assert np.isfinite(l)


def test_sequence_parallel_feed_rules():
    """Sequence/context parallelism: a [B, T] id feed shards batch AND
    time via feed_rules; numeric parity with the single-device run."""
    V, E, B, T = 40, 16, 8, 8

    def build():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            ids = layers.data("ids", [B, T], dtype="int64",
                              append_batch_size=False)
            lbl = layers.data("lbl", [B, 1], dtype="int64",
                              append_batch_size=False)
            emb = layers.embedding(ids, size=[V, E])
            pooled = layers.reduce_mean(emb, dim=1)
            probs = layers.fc(pooled, size=10, act="softmax")
            loss = layers.mean(layers.cross_entropy(probs, lbl))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        return main, startup, loss

    def run(parallel):
        main, startup, loss = build()
        scope = fluid.core.scope.Scope()
        with fluid.core.scope.scope_guard(scope):
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup, scope=scope)
            if parallel:
                mesh = make_mesh(jax.devices(), ("data", "seq"), (4, 2))
                rules = ShardingRules(
                    feed_rules=[(r"^ids$", P("data", "seq"))])
                engine = ParallelEngine(main, loss_name=loss.name,
                                        mesh=mesh, rules=rules)
                runner = lambda feed: engine.run(feed, [loss], scope)
            else:
                runner = lambda feed: exe.run(main, feed=feed,
                                              fetch_list=[loss], scope=scope)
            rs = np.random.RandomState(0)
            losses = []
            for _ in range(5):
                feed = {
                    "ids": rs.randint(0, V, (B, T)).astype("int64"),
                    "lbl": rs.randint(0, 10, (B, 1)).astype("int64"),
                }
                (l,) = runner(feed)
                losses.append(float(np.asarray(l).reshape(-1)[0]))
        return losses

    single = run(False)
    sp = run(True)
    np.testing.assert_allclose(single, sp, rtol=1e-4, atol=1e-5)
    assert single[-1] < single[0]


def test_parallel_executor_api():
    """fluid.ParallelExecutor parity wrapper (reference
    parallel_executor.py:81): dict feeds split over the mesh; a list of
    per-device dicts concatenates back to the global batch."""
    main, startup, loss = _build_mlp_program()
    scope = fluid.core.scope.Scope()
    with fluid.core.scope.scope_guard(scope):
        fluid.Executor(fluid.TPUPlace()).run(startup, scope=scope)
        pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                    main_program=main, scope=scope)
        assert pe.device_count == len(jax.devices())
        x, y = next(iter(_batches(1)))
        (l1,) = pe.run([loss.name], feed={"x": x, "y": y})
        per = len(x) // pe.device_count
        split = [{"x": x[i * per:(i + 1) * per],
                  "y": y[i * per:(i + 1) * per]}
                 for i in range(pe.device_count)]
        (l2,) = pe.run([loss.name], feed=split)
        assert np.isfinite(float(np.asarray(l1).reshape(-1)[0]))
        assert np.isfinite(float(np.asarray(l2).reshape(-1)[0]))
        # reference contract: list length must equal device_count
        import pytest

        with pytest.raises(ValueError, match="same size as places"):
            pe.run([loss.name], feed=split[:2])
        # share_vars_from adopts the training executor's scope
        pe2 = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                     main_program=main, share_vars_from=pe)
        assert pe2._scope is scope


def test_sp_fused_attention_rides_ring():
    """Under a (data, seq) mesh the fused-attention op must ride ring
    attention — sequence stays sharded, K/V blocks hop via ppermute —
    and the losses must match the single-device run through training.
    (VERDICT-r3-style promotion: sp is a framework path, not a library
    function.)"""
    import paddle_tpu as fluid
    from paddle_tpu.core.scope import Scope, scope_guard
    from paddle_tpu.models import transformer
    from paddle_tpu.parallel.engine import ParallelEngine, make_mesh
    from paddle_tpu.parallel.sharding import ShardingRules, P

    cfg = dict(d_model=32, d_ff=64, n_head=2, n_layer=1, src_vocab=64,
               trg_vocab=64, max_length=16, dropout=0.0)
    rs = np.random.RandomState(0)
    feed = {n: rs.randint(1, 64, (4, 16)).astype("int64")
            for n in ("src_ids", "trg_ids", "lbl_ids")}

    losses = {}
    for mode in ("single", "sp"):
        main, startup = fluid.Program(), fluid.Program()
        scope = Scope()
        with scope_guard(scope):
            with fluid.program_guard(main, startup):
                loss, _ = transformer.build(cfg, seq_len=16,
                                            use_fused_attention=True,
                                            label_smooth_eps=0.0)
                fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup, scope=scope)
            if mode == "single":
                run = lambda: exe.run(  # noqa: E731
                    main, feed=feed, fetch_list=[loss], scope=scope)[0]
            else:
                mesh = make_mesh(jax.devices(), ("data", "seq"), (2, 4))
                rules = ShardingRules(
                    feed_rules=[(r"^(src|trg|lbl)_ids$", P("data", "seq"))])
                eng = ParallelEngine(main, loss_name=loss.name, mesh=mesh,
                                     rules=rules)
                run = lambda: eng.run(feed, [loss], scope)[0]  # noqa: E731
                txt = eng.lowered_hlo(feed=feed, fetch_list=[loss],
                                      scope=scope)
                # the ring's signature collective
                assert "collective-permute" in txt
            vals = [float(np.asarray(run()).reshape(-1)[0])
                    for _ in range(4)]
            losses[mode] = vals
    np.testing.assert_allclose(losses["sp"], losses["single"],
                               rtol=2e-4, atol=2e-5)


def test_run_repeated_sharded_matches_sequential():
    """Engine K-step scan (constant feed) == K sequential engine.run
    calls: the sharded scan must thread donated state identically."""
    x, y = next(iter(_batches(1)))
    feed = {"x": x, "y": y}

    def final_loss(mode):
        main, startup, loss = _build_mlp_program()
        scope = fluid.core.scope.Scope()
        with fluid.core.scope.scope_guard(scope):
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup, scope=scope)
            engine = ParallelEngine(main, loss_name=loss.name)
            if mode == "seq":
                for _ in range(5):
                    (l,) = engine.run(feed, [loss], scope)
            else:
                (l,) = engine.run_repeated(feed, [loss], scope, steps=5)
        return float(np.asarray(l).reshape(-1)[0])

    l_seq, l_rep = final_loss("seq"), final_loss("rep")
    assert abs(l_seq - l_rep) < 1e-5, (l_seq, l_rep)


def test_run_repeated_stacked_feeds_shard_and_match():
    """feed_stacked windows through the mesh engine: K different
    minibatches per dispatch, per-step slices data-sharded, numerics
    equal to the sequential engine loop over the same batches."""
    from paddle_tpu import reader as rd

    batches = [{"x": x, "y": y} for x, y in _batches(4, seed=3)]

    main, startup, loss = _build_mlp_program()
    scope = fluid.core.scope.Scope()
    with fluid.core.scope.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        engine = ParallelEngine(main, loss_name=loss.name)
        for b in batches:
            (l_seq,) = engine.run(b, [loss], scope)

    main, startup, loss = _build_mlp_program()
    scope = fluid.core.scope.Scope()
    with fluid.core.scope.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        engine = ParallelEngine(main, loss_name=loss.name)
        window = rd.stack_feed_window(batches)
        (l_rep,) = engine.run_repeated(window, [loss], scope, steps=4,
                                       feed_stacked=True)
        # the stacked feed's sharding: leading K axis unsharded, batch
        # axis (dim 1) split over 'data' — a regression that replicates
        # the window (the sharding-from-stacked-shape bug) fails HERE
        plan = next(iter(engine._cache.values()))
        _, feed_in = plan.multi[(4, True, "last")]
        x_idx = plan.feed_names.index("x")
        assert feed_in[x_idx].spec == P(None, "data"), feed_in[x_idx].spec

    assert abs(float(l_seq) - float(l_rep)) < 1e-5, (l_seq, l_rep)


def test_engine_check_nan_inf_fires_on_mesh_path():
    """FLAGS_check_nan_inf must guard the sharded path too (run and the
    K-step scan) — the mesh engine shares the Executor epilogue."""
    import pytest

    from paddle_tpu import flags

    main, startup, loss = _build_mlp_program()
    scope = fluid.core.scope.Scope()
    with fluid.core.scope.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        engine = ParallelEngine(main, loss_name=loss.name)
        x, y = next(iter(_batches(1)))
        x = np.full_like(x, np.nan)
        old = flags.get_flag("check_nan_inf")
        flags.set_flag("check_nan_inf", True)
        try:
            with pytest.raises(FloatingPointError):
                engine.run({"x": x, "y": y}, [loss], scope)
            with pytest.raises(FloatingPointError, match="scanned"):
                engine.run_repeated({"x": x, "y": y}, [loss], scope,
                                    steps=3)
        finally:
            flags.set_flag("check_nan_inf", old)


def test_engine_lowered_hlo_rejects_stacked_single_step():
    import pytest

    main, startup, loss = _build_mlp_program()
    scope = fluid.core.scope.Scope()
    with fluid.core.scope.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        engine = ParallelEngine(main, loss_name=loss.name)
        x, y = next(iter(_batches(1)))
        with pytest.raises(ValueError, match="unstack"):
            engine.lowered_hlo({"x": x[None], "y": y[None]}, [loss],
                               scope, steps=1, feed_stacked=True)


def test_engine_lowered_hlo_validates_stacked_window():
    """lowered_hlo must give the same contract error run_repeated does
    when the window's leading axis disagrees with steps — not a deep
    lax.scan length error."""
    import pytest

    main, startup, loss = _build_mlp_program()
    scope = fluid.core.scope.Scope()
    with fluid.core.scope.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        engine = ParallelEngine(main, loss_name=loss.name)
        x, y = next(iter(_batches(1)))
        window = {"x": np.stack([x] * 4), "y": np.stack([y] * 4)}
        with pytest.raises(ValueError, match="leading steps axis of 3"):
            engine.lowered_hlo(window, [loss], scope, steps=3,
                               feed_stacked=True)


def test_engine_reduce_fetches_mean_on_mesh():
    """reduce_fetches='mean' through the SHARDED scan: window mean of
    the global-batch losses equals the sequential per-batch mean."""
    from paddle_tpu import reader as rd

    batches = [{"x": x, "y": y} for x, y in _batches(3, seed=9)]

    main, startup, loss = _build_mlp_program()
    scope = fluid.core.scope.Scope()
    with fluid.core.scope.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        engine = ParallelEngine(main, loss_name=loss.name)
        per = [float(np.asarray(engine.run(b, [loss], scope)[0])
                     .reshape(-1)[0]) for b in batches]

    main, startup, loss = _build_mlp_program()
    scope = fluid.core.scope.Scope()
    with fluid.core.scope.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        engine = ParallelEngine(main, loss_name=loss.name)
        (m,) = engine.run_repeated(rd.stack_feed_window(batches), [loss],
                                   scope, steps=3, feed_stacked=True,
                                   reduce_fetches="mean")
    np.testing.assert_allclose(float(np.asarray(m).reshape(-1)[0]),
                               np.mean(per), rtol=1e-5)


def test_packed_gpt_sp_rides_ring_with_segment_ids():
    """Packed causal LM training under a (data, seq) mesh: the fused op
    receives segment IDS (never the [S,S] pack bias), they ride the
    zigzag ring as travelling id vectors, and the training losses match
    the single-device packed run exactly — the long-context packed-sp
    composition (round-5 perf configuration)."""
    import paddle_tpu as fluid
    from paddle_tpu.core.scope import Scope, scope_guard
    from paddle_tpu.models import gpt
    from paddle_tpu import reader

    cfg = dict(d_model=32, d_ff=64, n_head=2, n_layer=2, vocab=64,
               max_length=32, dropout=0.0, pos_emb="rope")
    S = 32
    rs = np.random.RandomState(3)
    docs = [list(rs.randint(1, 64, rs.randint(5, 14))) for _ in range(10)]
    feed = reader.pack_sequences(docs, seq_len=S, n_rows=4)

    losses = {}
    for mode in ("single", "sp"):
        main, startup = fluid.Program(), fluid.Program()
        scope = Scope()
        with scope_guard(scope):
            with fluid.program_guard(main, startup):
                loss, _ = gpt.build(cfg, seq_len=S, packed=True,
                                    use_fused_attention=True)
                fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup, scope=scope)
            if mode == "single":
                run = lambda: exe.run(  # noqa: E731
                    main, feed=feed, fetch_list=[loss], scope=scope)[0]
            else:
                mesh = make_mesh(jax.devices(), ("data", "seq"), (2, 4))
                rules = ShardingRules(feed_rules=[
                    (r"^(ids|segment_ids|pos_ids)$", P("data", "seq"))])
                eng = ParallelEngine(main, loss_name=loss.name, mesh=mesh,
                                     rules=rules)
                run = lambda: eng.run(feed, [loss], scope)[0]  # noqa: E731
                txt = eng.lowered_hlo(feed=feed, fetch_list=[loss],
                                      scope=scope)
                assert "collective-permute" in txt  # the ring engaged
            vals = [float(np.asarray(run()).reshape(-1)[0])
                    for _ in range(4)]
            losses[mode] = vals
    np.testing.assert_allclose(losses["sp"], losses["single"],
                               rtol=3e-4, atol=3e-5)
