"""Executor.run_repeated: K train steps as ONE device-side executable
(lax.scan over the whole-block step). Must be semantically identical to
K sequential Executor.run calls with the same feed — params, optimizer
slots, the RNG chain (dropout differs per iteration), and the last
step's fetches all match the unrolled sequence.
"""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.scope import Scope, scope_guard


def _build(seed=7, dropout=0.0):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        h = layers.fc(x, 16, act="relu")
        if dropout:
            h = layers.dropout(h, dropout_prob=dropout)
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square(pred - y))
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(loss)
    return main, startup, loss


def _feed():
    rs = np.random.RandomState(0)
    return {"x": rs.randn(16, 8).astype("float32"),
            "y": rs.randn(16, 1).astype("float32")}


def _param_names(scope):
    """fc layer numbering is a process-global counter, so two _build()
    calls name the same params fc_0/fc_1 then fc_2/fc_3 — normalize the
    layer index to its ordinal within this scope."""
    names = sorted(n for n in scope.local_var_names()
                   if n.startswith("fc_") and not n.endswith("@GRAD"))
    prefixes = sorted({n.split(".", 1)[0] for n in names},
                      key=lambda p: int(p.split("_")[1]))
    ordinal = {p: i for i, p in enumerate(prefixes)}
    return {n: "fc#%d.%s" % (ordinal[n.split(".", 1)[0]],
                             n.split(".", 1)[1]) for n in names}


def _run(mode, steps, dropout=0.0, build=None):
    """Shared harness: train `steps` iterations via sequential run() or
    one run_repeated() scan, return (last loss, params). `build`
    overrides the model (returns (main, startup, loss))."""
    main, startup, loss = (build or (lambda: _build(dropout=dropout)))()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        feed = _feed()
        if mode == "sequential":
            for _ in range(steps):
                vals = exe.run(main, feed=feed, fetch_list=[loss],
                               scope=scope)
        else:
            vals = exe.run_repeated(main, feed=feed, fetch_list=[loss],
                                    scope=scope, steps=steps)
        params = {norm: np.asarray(scope.find_var(n))
                  for n, norm in _param_names(scope).items()}
    return float(np.asarray(vals[0]).reshape(-1)[0]), params


def test_run_repeated_matches_sequential():
    l_seq, p_seq = _run("sequential", 4)
    l_rep, p_rep = _run("repeated", 4)
    assert abs(l_seq - l_rep) < 1e-5, (l_seq, l_rep)
    assert p_seq.keys() == p_rep.keys() and p_seq
    for n in p_seq:
        np.testing.assert_allclose(p_seq[n], p_rep[n], atol=1e-5,
                                   err_msg=n)


def test_run_repeated_rng_chain_matches_with_dropout():
    """The scan carries the RNG key exactly as the sequential chain
    does — with dropout on, step t's mask must match the unrolled
    run's, so final params agree."""
    l_seq, p_seq = _run("sequential", 3, dropout=0.3)
    l_rep, p_rep = _run("repeated", 3, dropout=0.3)
    assert abs(l_seq - l_rep) < 1e-5, (l_seq, l_rep)
    for n in p_seq:
        np.testing.assert_allclose(p_seq[n], p_rep[n], atol=1e-5,
                                   err_msg=n)


def test_run_repeated_steps_one_delegates():
    main, startup, loss = _build()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        vals = exe.run_repeated(main, feed=_feed(), fetch_list=[loss],
                                scope=scope, steps=1)
    assert np.isfinite(np.asarray(vals[0])).all()


def test_run_repeated_advances_training():
    """K scanned steps actually train: loss after run_repeated(8) is
    well below the first step's loss."""
    main, startup, loss = _build()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        feed = _feed()
        first = float(np.asarray(
            exe.run(main, feed=feed, fetch_list=[loss],
                    scope=scope)[0]).reshape(-1)[0])
        vals = exe.run_repeated(main, feed=feed, fetch_list=[loss],
                                scope=scope, steps=30)
        last = float(np.asarray(vals[0]).reshape(-1)[0])
    assert last < first * 0.7, (first, last)


def test_run_repeated_compiled_program_delegates_to_engine():
    """A data-parallel CompiledProgram routes run_repeated through the
    mesh engine's sharded K-step scan — same result as the plain
    Executor path on the same (deterministic) program."""
    from paddle_tpu.compiler import CompiledProgram

    l_plain, p_plain = _run("repeated", 4)

    main, startup, loss = _build()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        compiled = CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
        vals = exe.run_repeated(compiled, feed=_feed(), fetch_list=[loss],
                                scope=scope, steps=4)
        l_dp = float(np.asarray(vals[0]).reshape(-1)[0])
        p_dp = {norm: np.asarray(scope.find_var(n))
                for n, norm in _param_names(scope).items()}
    assert abs(l_plain - l_dp) < 1e-4, (l_plain, l_dp)
    for n in p_plain:
        np.testing.assert_allclose(p_plain[n], p_dp[n], atol=1e-4,
                                   err_msg=n)


def test_run_repeated_check_nan_inf():
    import pytest

    from paddle_tpu import flags

    main, startup, loss = _build()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = Scope()
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        feed = _feed()
        feed["x"] = np.full_like(feed["x"], np.nan)
        old = flags.get_flag("check_nan_inf")
        flags.set_flag("check_nan_inf", True)
        try:
            with pytest.raises(FloatingPointError, match="scanned"):
                exe.run_repeated(main, feed=feed, fetch_list=[loss],
                                 scope=scope, steps=3)
        finally:
            flags.set_flag("check_nan_inf", old)

def _feeds_k(k):
    rs = np.random.RandomState(3)
    return [{"x": rs.randn(16, 8).astype("float32"),
             "y": rs.randn(16, 1).astype("float32")} for _ in range(k)]


def test_run_repeated_feed_stacked_matches_sequential():
    """feed_stacked=True consumes one stacked slice per scanned step —
    K DIFFERENT minibatches per dispatch must train identically to K
    sequential run() calls over those minibatches."""
    from paddle_tpu import reader as rd

    k = 4
    feeds = _feeds_k(k)

    main, startup, loss = _build()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        for f in feeds:
            vals = exe.run(main, feed=f, fetch_list=[loss], scope=scope)
        l_seq = float(np.asarray(vals[0]).reshape(-1)[0])
        p_seq = {norm: np.asarray(scope.find_var(n))
                 for n, norm in _param_names(scope).items()}

    main, startup, loss = _build()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        stacked = rd.stack_feed_window(feeds)
        assert stacked["x"].shape == (k, 16, 8)
        vals = exe.run_repeated(main, feed=stacked, fetch_list=[loss],
                                scope=scope, steps=k, feed_stacked=True)
        l_rep = float(np.asarray(vals[0]).reshape(-1)[0])
        p_rep = {norm: np.asarray(scope.find_var(n))
                 for n, norm in _param_names(scope).items()}

    assert abs(l_seq - l_rep) < 1e-5, (l_seq, l_rep)
    assert p_seq.keys() == p_rep.keys() and p_seq
    for n in p_seq:
        np.testing.assert_allclose(p_seq[n], p_rep[n], atol=1e-5,
                                   err_msg=n)


def test_run_repeated_feed_stacked_wrong_leading_axis():
    import pytest

    main, startup, loss = _build()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        stacked = {k: np.stack([v, v]) for k, v in _feed().items()}  # K=2
        with pytest.raises(ValueError, match="leading"):
            exe.run_repeated(main, feed=stacked, fetch_list=[loss],
                             scope=scope, steps=3, feed_stacked=True)


def test_stack_feed_window_validates_keys():
    import pytest

    from paddle_tpu import reader as rd

    with pytest.raises(ValueError, match="keys"):
        rd.stack_feed_window([{"a": np.zeros(2)}, {"b": np.zeros(2)}])
    with pytest.raises(ValueError, match="at least one"):
        rd.stack_feed_window([])


def test_run_repeated_feed_stacked_steps_one_unstacks():
    """A window of length 1 must unstack (drop the leading axis) before
    delegating to the single-step path — not trace the program with a
    wrong-rank batch."""
    main, startup, loss = _build()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        f = _feed()
        stacked = {k: v[None] for k, v in f.items()}  # K=1 leading axis
        v_stacked = exe.run_repeated(main, feed=stacked, fetch_list=[loss],
                                     scope=scope, steps=1,
                                     feed_stacked=True)
    main, startup, loss = _build()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        v_plain = exe.run(main, feed=f, fetch_list=[loss], scope=scope)
    np.testing.assert_allclose(np.asarray(v_stacked[0]),
                               np.asarray(v_plain[0]), atol=1e-6)


def test_run_repeated_feed_stacked_steps_one_rejects_wider_window():
    """steps=1 with a K>1 window is a caller bug — must raise, never
    silently train on slice 0 and drop the rest of the data."""
    import pytest

    main, startup, loss = _build()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        stacked = {k: np.stack([v, v, v]) for k, v in _feed().items()}
        with pytest.raises(ValueError, match="leading axis of 1"):
            exe.run_repeated(main, feed=stacked, fetch_list=[loss],
                             scope=scope, steps=1, feed_stacked=True)


def test_run_repeated_lr_schedule_advances_per_scanned_step():
    """The decay step counter is program state, so LR schedules advance
    INSIDE the scan — K scanned steps must land on the same learning
    rate and params as K sequential steps (a frozen counter would decay
    K times slower and silently overtrain early steps)."""
    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 11
        startup.random_seed = 11
        with fluid.program_guard(main, startup):
            x = layers.data("x", [8], dtype="float32")
            y = layers.data("y", [1], dtype="float32")
            pred = layers.fc(layers.fc(x, 16, act="relu"), 1)
            loss = layers.mean(layers.square(pred - y))
            lr = layers.exponential_decay(learning_rate=0.1,
                                          decay_steps=2, decay_rate=0.5)
            fluid.optimizer.SGD(learning_rate=lr).minimize(loss)
        return main, startup, loss

    def run(mode, steps=6):
        main, startup, loss = build()
        scope = Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(scope):
            exe.run(startup, scope=scope)
            feed = _feed()
            if mode == "sequential":
                for _ in range(steps):
                    vals = exe.run(main, feed=feed, fetch_list=[loss],
                                   scope=scope)
            else:
                vals = exe.run_repeated(main, feed=feed,
                                        fetch_list=[loss], scope=scope,
                                        steps=steps)
            counter = np.asarray(scope.find_var("@LR_DECAY_COUNTER@")) \
                if scope.find_var("@LR_DECAY_COUNTER@") is not None else None
            params = {norm: np.asarray(scope.find_var(n))
                      for n, norm in _param_names(scope).items()}
        return float(np.asarray(vals[0]).reshape(-1)[0]), params, counter

    l_seq, p_seq, c_seq = run("sequential")
    l_rep, p_rep, c_rep = run("repeated")
    assert abs(l_seq - l_rep) < 1e-6, (l_seq, l_rep)
    if c_seq is not None:
        np.testing.assert_array_equal(c_seq, c_rep)
    for n in p_seq:
        np.testing.assert_allclose(p_seq[n], p_rep[n], atol=1e-6,
                                   err_msg=n)


def test_pyreader_windows_drive_run_repeated():
    """The full steady-state loop: PyReader prefetches, windows(K)
    stacks, run_repeated consumes — identical params to the per-batch
    exe.run loop over the same data, including a 10-batch epoch with
    K=4 (two full windows + a tail of 2) and a short final batch that
    flushes its window early."""
    batches = _feeds_k(9)
    # a final partial batch (8 rows instead of 16): must form its own
    # window, never stacked with the full-size ones
    batches.append({"x": batches[0]["x"][:8], "y": batches[0]["y"][:8]})

    def gen():
        for b in batches:
            yield (b["x"], b["y"])

    def final_params(mode):
        main, startup, loss = _build()
        x_var = main.global_block().var("x")
        y_var = main.global_block().var("y")
        scope = Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(scope):
            exe.run(startup, scope=scope)
            reader = layers.PyReader(feed_list=[x_var, y_var])
            reader.decorate_batch_generator(gen)
            if mode == "windows":
                seen = []
                for window, steps in reader.windows(4):
                    seen.append(steps)
                    exe.run_repeated(main, feed=window, fetch_list=[loss],
                                     scope=scope, steps=steps,
                                     feed_stacked=True)
                assert seen == [4, 4, 1, 1], seen  # tail + flushed short
            else:
                for feed in reader():
                    exe.run(main, feed=feed, fetch_list=[loss],
                            scope=scope)
            return {norm: np.asarray(scope.find_var(n))
                    for n, norm in _param_names(scope).items()}

    p_win = final_params("windows")
    p_seq = final_params("sequential")
    for n in p_seq:
        np.testing.assert_allclose(p_seq[n], p_win[n], atol=1e-5,
                                   err_msg=n)


def test_run_repeated_composes_with_grad_accum():
    """Grad accumulation already lowers to a scan inside the step;
    run_repeated wraps it in an outer scan. K scanned accum-steps must
    equal K sequential accum-steps exactly (scan-of-scan)."""
    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 13
        startup.random_seed = 13
        with fluid.program_guard(main, startup):
            x = layers.data("x", [8], dtype="float32")
            y = layers.data("y", [1], dtype="float32")
            pred = layers.fc(layers.fc(x, 16, act="relu"), 1)
            loss = layers.mean(layers.square(pred - y))
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
        main.set_gradient_accumulation(4)
        return main, startup, loss

    # full batch; set_gradient_accumulation(4) splits it into 4
    # microbatches inside the step's own scan
    l_seq, p_seq = _run("sequential", 3, build=build)
    l_rep, p_rep = _run("repeated", 3, build=build)
    assert abs(l_seq - l_rep) < 1e-6, (l_seq, l_rep)
    for n in p_seq:
        np.testing.assert_allclose(p_seq[n], p_rep[n], atol=1e-6,
                                   err_msg=n)


def test_run_repeated_composes_with_recompute():
    """RecomputeOptimizer puts forward segments behind an
    optimization_barrier with RngKey replay; the outer scan must thread
    the same RNG chain — params after K scanned recompute-steps equal
    the sequential run's (dropout inside the recomputed segment)."""
    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 17
        startup.random_seed = 17
        with fluid.program_guard(main, startup):
            x = layers.data("x", [8], dtype="float32")
            y = layers.data("y", [1], dtype="float32")
            h = layers.fc(x, 16, act="relu")
            h = layers.dropout(h, dropout_prob=0.2)
            ckpt = h
            pred = layers.fc(h, 1)
            loss = layers.mean(layers.square(pred - y))
            opt = fluid.optimizer.RecomputeOptimizer(
                fluid.optimizer.SGD(learning_rate=0.05))
            opt._set_checkpoints([ckpt])
            opt.minimize(loss)
        return main, startup, loss

    l_seq, p_seq = _run("sequential", 3, build=build)
    l_rep, p_rep = _run("repeated", 3, build=build)
    assert abs(l_seq - l_rep) < 1e-6, (l_seq, l_rep)
    for n in p_seq:
        np.testing.assert_allclose(p_seq[n], p_rep[n], atol=1e-6,
                                   err_msg=n)


def test_warmup_cosine_composition_in_scan():
    """linear_lr_warmup(cosine_decay(...)) — the standard modern
    schedule — composes, and advances correctly inside run_repeated
    (both schedules share the step counter carried by the scan)."""
    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 29
        startup.random_seed = 29
        with fluid.program_guard(main, startup):
            x = layers.data("x", [8], dtype="float32")
            y = layers.data("y", [1], dtype="float32")
            pred = layers.fc(layers.fc(x, 16, act="relu"), 1)
            loss = layers.mean(layers.square(pred - y))
            lr = layers.linear_lr_warmup(
                layers.cosine_decay(0.1, step_each_epoch=8, epochs=1),
                warmup_steps=3, start_lr=0.0, end_lr=0.1)
            fluid.optimizer.SGD(learning_rate=lr).minimize(loss)
        return main, startup, loss

    l_seq, p_seq = _run("sequential", 6, build=build)
    l_rep, p_rep = _run("repeated", 6, build=build)
    assert abs(l_seq - l_rep) < 1e-6, (l_seq, l_rep)
    for n in p_seq:
        np.testing.assert_allclose(p_seq[n], p_rep[n], atol=1e-6,
                                   err_msg=n)


def test_reduce_fetches_mean_and_sum():
    """reduce_fetches aggregates float fetches across the scanned
    steps: 'mean' equals the average of the sequential per-step losses,
    'sum' their total; state advance is unchanged."""
    feeds = _feeds_k(3)
    from paddle_tpu import reader as rd

    main, startup, loss = _build()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        seq_losses = []
        for f in feeds:
            (l,) = exe.run(main, feed=f, fetch_list=[loss], scope=scope)
            seq_losses.append(float(np.asarray(l).reshape(-1)[0]))
        p_seq = {norm: np.asarray(scope.find_var(n))
                 for n, norm in _param_names(scope).items()}

    for mode, expect in (("mean", np.mean(seq_losses)),
                         ("sum", np.sum(seq_losses))):
        main, startup, loss = _build()
        scope = Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(scope):
            exe.run(startup, scope=scope)
            window = rd.stack_feed_window(feeds)
            (l,) = exe.run_repeated(main, feed=window, fetch_list=[loss],
                                    scope=scope, steps=3,
                                    feed_stacked=True,
                                    reduce_fetches=mode)
            np.testing.assert_allclose(
                float(np.asarray(l).reshape(-1)[0]), expect, rtol=1e-5,
                err_msg=mode)
            p_rep = {norm: np.asarray(scope.find_var(n))
                     for n, norm in _param_names(scope).items()}
        for n in p_seq:
            np.testing.assert_allclose(p_seq[n], p_rep[n], atol=1e-5,
                                       err_msg="%s/%s" % (mode, n))


def test_reduce_fetches_rejects_unknown():
    import pytest

    main, startup, loss = _build()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        with pytest.raises(ValueError, match="last|mean|sum"):
            exe.run_repeated(main, feed=_feed(), fetch_list=[loss],
                             scope=scope, steps=2, reduce_fetches="avg")


def test_reduce_fetches_validated_even_at_steps_one():
    import pytest

    main, startup, loss = _build()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(scope):
        exe.run(startup, scope=scope)
        with pytest.raises(ValueError, match="last|mean|sum"):
            exe.run_repeated(main, feed=_feed(), fetch_list=[loss],
                             scope=scope, steps=1, reduce_fetches="avg")


# ------------------------------------------------ the zoo through a window
def _zoo_case(name):
    """(build, one fixed batch, lr) at the tiny sizes tests/test_models.py
    trains each model at."""
    from paddle_tpu.models import bert, ctr, resnet, transformer, vgg

    rs = np.random.RandomState(0)
    if name in ("resnet50", "vgg16"):
        mod = resnet if name == "resnet50" else vgg
        return (lambda: mod.build(class_dim=10, image_shape=(3, 32, 32)),
                {"img": rs.rand(2, 3, 32, 32).astype("float32"),
                 "label": rs.randint(0, 10, (2, 1)).astype("int64")}, 1e-4)
    if name == "deepfm":
        return (lambda: ctr.build("deepfm", vocab=1000, emb_dim=8),
                {"sparse_ids": rs.randint(0, 1000, (8, 26)).astype("int64"),
                 "dense": rs.rand(8, 13).astype("float32"),
                 "label": rs.randint(0, 2, (8, 1)).astype("int64")}, 1e-3)
    if name == "transformer":
        cfg = dict(d_model=32, d_ff=64, n_head=4, n_layer=2, src_vocab=100,
                   trg_vocab=100, max_length=16, dropout=0.1)
        return (lambda: transformer.build(cfg, seq_len=16),
                {k: rs.randint(1, 100, (4, 16)).astype("int64")
                 for k in ("src_ids", "trg_ids", "lbl_ids")}, 1e-3)
    cfg = dict(d_model=32, d_ff=64, n_head=4, n_layer=2, vocab=100,
               type_vocab=2, max_length=64, dropout=0.1)
    B, S, M = 4, 16, 4
    return (lambda: bert.build(cfg, seq_len=S, max_mask=M),
            {"src_ids": rs.randint(1, 100, (B, S)).astype("int64"),
             "sent_ids": rs.randint(0, 2, (B, S)).astype("int64"),
             "input_mask": np.ones((B, S), "float32"),
             "mask_pos": rs.randint(0, B * S, (B, M)).astype("int64"),
             "mask_label": rs.randint(1, 100, (B, M)).astype("int64"),
             "mask_weight": np.ones((B, M), "float32")}, 1e-3)


@pytest.mark.parametrize("name", ["resnet50", "vgg16", "deepfm",
                                  "transformer", "bert"])
def test_zoo_model_trains_through_a_window(name):
    """The zoo's builders through ``run_repeated(steps=2,
    feed_stacked=True)``, the path the benchmark's train cells take:
    on one fixed batch every window's loss is finite and the loss
    falls (the judgement tests/test_models.py makes over ``run``)."""
    build, batch, lr = _zoo_case(name)
    main, startup = fluid.Program(), fluid.Program()
    scope = Scope()
    with scope_guard(scope):
        with fluid.program_guard(main, startup):
            loss = build()[0]
            fluid.optimizer.Adam(learning_rate=lr).minimize(loss)
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        window = {k: np.stack([v, v]) for k, v in batch.items()}
        losses = [np.asarray(exe.run_repeated(
            main, feed=window, fetch_list=[loss], scope=scope, steps=2,
            feed_stacked=True)[0]).item() for _ in range(3)]
    assert np.all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
