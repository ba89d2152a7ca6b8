"""StaticRNN / DynamicRNN / IfElse tests.

Reference analogs: unittests/test_recurrent_op.py (StaticRNN numeric +
grad), test_dyn_rnn.py (DynamicRNN over ragged sequences trains), and
the IfElse usage in test_mnist_if_else_op.py.
"""

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers


def test_static_rnn_matches_numpy(fresh_programs):
    """Param-free recurrence mem' = mem*0.5 + x_t checked exactly."""
    main, startup, scope = fresh_programs
    T, B, D = 5, 3, 4
    with fluid.program_guard(main, startup):
        x3 = layers.data("x3", [T, B, D], append_batch_size=False)
        rnn = layers.StaticRNN()
        with rnn.step():
            word = rnn.step_input(x3)
            prev = rnn.memory(shape=[-1, D], batch_ref=word,
                              ref_batch_dim_idx=1)
            half = layers.scale(prev, scale=0.5)
            new = layers.elementwise_add(half, word)
            rnn.update_memory(prev, new)
            rnn.step_output(new)
        out = rnn()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup, scope=scope)
    xs = np.random.randn(T, B, D).astype("float32")
    (got,) = exe.run(main, feed={"x3": xs}, fetch_list=[out], scope=scope)
    mem = np.zeros((B, D), "float32")
    want = []
    for t in range(T):
        mem = mem * 0.5 + xs[t]
        want.append(mem)
    np.testing.assert_allclose(got, np.stack(want), rtol=1e-5, atol=1e-5)


def test_static_rnn_trains_fc_memory(fresh_programs):
    """fc inside the step block: gradients must reach its weights."""
    main, startup, scope = fresh_programs
    T, B, D, H = 6, 8, 5, 7
    with fluid.program_guard(main, startup):
        x = layers.data("x", [T, B, D], append_batch_size=False)
        y = layers.data("y", [B, H], append_batch_size=False)
        rnn = layers.StaticRNN()
        with rnn.step():
            word = rnn.step_input(x)
            prev = rnn.memory(shape=[-1, H], batch_ref=word,
                              ref_batch_dim_idx=1)
            hidden = layers.fc([word, prev], size=H, act="tanh")
            rnn.update_memory(prev, hidden)
            rnn.step_output(hidden)
        seq = rnn()
        last = layers.slice(seq, axes=[0], starts=[T - 1], ends=[T])
        last = layers.reshape(last, shape=[B, H])
        loss = layers.mean(layers.square(layers.elementwise_sub(last, y)))
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup, scope=scope)
    rs = np.random.RandomState(0)
    xs = rs.randn(T, B, D).astype("float32")
    ys = np.tanh(rs.randn(B, H)).astype("float32")
    losses = []
    for _ in range(25):
        (lv,) = exe.run(main, feed={"x": xs, "y": ys}, fetch_list=[loss],
                        scope=scope)
        losses.append(float(lv))
    assert np.isfinite(losses).all()
    assert losses[-1] < 0.5 * losses[0], losses


def test_static_rnn_with_dropout_trains(fresh_programs):
    """RNG ops inside the step body: the custom recurrent grad replays
    the saved forward rng (dropout-mask pattern), so training works."""
    main, startup, scope = fresh_programs
    T, B, D, H = 4, 8, 5, 6
    with fluid.program_guard(main, startup):
        x = layers.data("x", [T, B, D], append_batch_size=False)
        rnn = layers.StaticRNN()
        with rnn.step():
            word = rnn.step_input(x)
            prev = rnn.memory(shape=[-1, H], batch_ref=word,
                              ref_batch_dim_idx=1)
            hidden = layers.fc([word, prev], size=H, act="tanh")
            hidden = layers.dropout(hidden, dropout_prob=0.3)
            rnn.update_memory(prev, hidden)
            rnn.step_output(hidden)
        seq = rnn()
        loss = layers.mean(layers.square(seq))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup, scope=scope)
    xs = np.random.RandomState(4).randn(T, B, D).astype("float32")
    losses = [float(exe.run(main, feed={"x": xs}, fetch_list=[loss],
                            scope=scope)[0]) for _ in range(40)]
    assert np.isfinite(losses).all()
    # every step's loss carries its own mask's noise (96 hidden values at
    # p = 0.3), so the trend is read over five steps at either end and not
    # off two single draws, whatever stream the masks come from
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses


def test_ifelse_one_sided_raises(fresh_programs):
    main, startup, scope = fresh_programs
    import pytest

    with fluid.program_guard(main, startup):
        x = layers.data("x", [4])
        lab = layers.data("lab", [1], dtype="int64")
        cond = layers.less_than(lab, layers.fill_constant([1], "int64", 1))
        ie = layers.IfElse(cond)
        with ie.true_block():
            ie.output(layers.fc(ie.input(x), size=2))
        with pytest.raises(ValueError, match="both branches"):
            ie()


def test_dynamic_rnn_masked_semantics(fresh_programs):
    """Rows past their length freeze memory and emit zeros."""
    main, startup, scope = fresh_programs
    B, T, D = 4, 6, 3
    with fluid.program_guard(main, startup):
        x = layers.data("x", [B, T, D], append_batch_size=False)
        length = layers.data("len", [B], dtype="int64",
                             append_batch_size=False)
        drnn = layers.DynamicRNN()
        with drnn.block():
            word = drnn.step_input(x, length=length)
            prev = drnn.memory(shape=[D], value=0.0, dtype="float32")
            new = layers.elementwise_add(prev, word)  # running sum
            drnn.update_memory(prev, new)
            drnn.output(new)
        out = drnn()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup, scope=scope)
    rs = np.random.RandomState(1)
    xs = rs.randn(B, T, D).astype("float32")
    lens = np.array([6, 3, 1, 4], "int64")
    (got,) = exe.run(main, feed={"x": xs, "len": lens}, fetch_list=[out],
                     scope=scope)
    want = np.zeros((B, T, D), "float32")
    for b in range(B):
        acc = np.zeros(D, "float32")
        for t in range(int(lens[b])):
            acc = acc + xs[b, t]
            want[b, t] = acc
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_ifelse_merges_and_trains(fresh_programs):
    main, startup, scope = fresh_programs
    B, D = 16, 8
    with fluid.program_guard(main, startup):
        x = layers.data("x", [D])
        lab = layers.data("lab", [1], dtype="int64")
        limit = layers.fill_constant([1], "int64", 1)
        cond = layers.less_than(lab, limit)  # [B,1] bool
        ie = layers.IfElse(cond)
        with ie.true_block():
            xt = ie.input(x)
            ie.output(layers.fc(xt, size=4, act="tanh",
                                param_attr=fluid.ParamAttr(name="w_true")))
        with ie.false_block():
            xf = ie.input(x)
            ie.output(layers.fc(xf, size=4, act="tanh",
                                param_attr=fluid.ParamAttr(name="w_false")))
        merged, = ie()
        loss = layers.mean(layers.square(merged))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup, scope=scope)
    rs = np.random.RandomState(2)
    xs = rs.randn(B, D).astype("float32")
    labs = (rs.rand(B, 1) < 0.5).astype("int64")
    # snapshot weights before the first run (it includes the SGD update)
    w_t = np.array(scope.find_var("w_true"))
    w_f = np.array(scope.find_var("w_false"))
    assert w_t.shape == (D, 4)
    (m0, l0) = exe.run(main, feed={"x": xs, "lab": labs},
                       fetch_list=[merged, loss], scope=scope)
    # biases are fresh-initialized to 0
    t_out = np.tanh(xs @ w_t)
    f_out = np.tanh(xs @ w_f)
    want = np.where(labs < 1, t_out, f_out)
    np.testing.assert_allclose(m0, want, rtol=1e-4, atol=1e-4)
    # training moves both branch weights (each selected by some rows)
    for _ in range(3):
        exe.run(main, feed={"x": xs, "lab": labs}, fetch_list=[loss],
                scope=scope)
    assert not np.allclose(np.asarray(scope.find_var("w_true")), w_t)
    assert not np.allclose(np.asarray(scope.find_var("w_false")), w_f)


def test_machine_translation_dynamic_rnn_trains(fresh_programs):
    """Book-style MT: DynamicRNN encoder + StaticRNN decoder trains
    (reference book test test_machine_translation.py uses the
    programmable-RNN family the same way)."""
    main, startup, scope = fresh_programs
    B, Ts, Tt, V, E, H = 8, 7, 5, 40, 16, 24
    with fluid.program_guard(main, startup):
        src = layers.data("src", [B, Ts], dtype="int64",
                          append_batch_size=False)
        src_len = layers.data("src_len", [B], dtype="int64",
                              append_batch_size=False)
        trg = layers.data("trg", [B, Tt], dtype="int64",
                          append_batch_size=False)

        emb = layers.embedding(src, size=[V, E])
        drnn = layers.DynamicRNN()
        with drnn.block():
            word = drnn.step_input(emb, length=src_len)
            prev = drnn.memory(shape=[H], value=0.0, dtype="float32")
            hidden = layers.fc([word, prev], size=H, act="tanh")
            drnn.update_memory(prev, hidden)
            drnn.output(hidden)
        enc_seq = drnn()                      # [B, Ts, H], zero-padded
        context = layers.sequence_last_step(enc_seq, src_len)  # [B, H]

        trg_emb = layers.embedding(trg, size=[V, E])
        trg_tm = layers.transpose(trg_emb, perm=[1, 0, 2])  # [Tt, B, E]
        dec = layers.StaticRNN()
        with dec.step():
            w = dec.step_input(trg_tm)
            st = dec.memory(init=context)
            new_st = layers.fc([w, st], size=H, act="tanh")
            dec.update_memory(st, new_st)
            dec.step_output(new_st)
        dec_seq = dec()                       # [Tt, B, H]
        logits = layers.fc(dec_seq, size=V, act=None, num_flatten_dims=2)
        lbl = layers.transpose(trg, perm=[1, 0])
        lbl = layers.reshape(lbl, shape=[Tt * B, 1])
        flat = layers.reshape(logits, shape=[Tt * B, V])
        loss = layers.mean(
            layers.softmax_with_cross_entropy(flat, lbl))
        fluid.optimizer.Adam(learning_rate=5e-3).minimize(loss)

    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup, scope=scope)
    rs = np.random.RandomState(3)
    feed = {
        "src": rs.randint(1, V, (B, Ts)).astype("int64"),
        "src_len": rs.randint(2, Ts + 1, (B,)).astype("int64"),
        "trg": rs.randint(1, V, (B, Tt)).astype("int64"),
    }
    losses = []
    for _ in range(30):
        (lv,) = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        losses.append(float(lv))
    assert np.isfinite(losses).all()
    assert losses[-1] < 0.5 * losses[0], (losses[0], losses[-1])
