"""Gradient accumulation (Program.set_gradient_accumulation).

Parity contract (reference ir/multi_batch_merge_pass.cc analog): training on
batch k*b with k microbatches must match training on batch k*b in one shot,
because mean-of-microbatch-mean-grads == full-batch mean grad for mean
losses. Also covers LR-schedule stepping (once per applied step, not per
microbatch) and batch-norm stat updates under the scan.
"""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.scope import Scope, scope_guard


def _build(lr_sched=False, bn=False):
    from paddle_tpu.core.program import unique_name

    main, startup = fluid.Program(), fluid.Program()
    with unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[16], dtype="float32")
        y = fluid.layers.data("y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, size=32, act="relu")
        if bn:
            h = fluid.layers.batch_norm(h)
        pred = fluid.layers.fc(h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        lr = (fluid.layers.exponential_decay(0.1, decay_steps=2,
                                             decay_rate=0.5)
              if lr_sched else 0.1)
        fluid.optimizer.SGD(learning_rate=lr).minimize(loss)
    return main, startup, loss


def _train(main, startup, loss, steps, batch, seed=3):
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        rs = np.random.RandomState(seed)
        X = rs.rand(batch, 16).astype("float32")
        Y = X.sum(1, keepdims=True).astype("float32") * 0.1
        losses = []
        for _ in range(steps):
            (v,) = exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss],
                           scope=scope)
            losses.append(float(v))
        params = {
            p.name: np.asarray(scope.find_var(p.name))
            for p in main.global_block().all_parameters()
        }
    return losses, params


class TestGradAccum:
    @pytest.mark.parametrize("k", [2, 4])
    def test_parity_with_full_batch(self, k):
        ref_main, ref_startup, ref_loss = _build()
        ref_losses, ref_params = _train(ref_main, ref_startup, ref_loss,
                                        steps=5, batch=16)

        acc_main, acc_startup, acc_loss = _build()
        acc_main.set_gradient_accumulation(k)
        acc_losses, acc_params = _train(acc_main, acc_startup, acc_loss,
                                        steps=5, batch=16)

        np.testing.assert_allclose(acc_losses, ref_losses, rtol=1e-4,
                                   atol=1e-5)
        for name, ref in ref_params.items():
            np.testing.assert_allclose(acc_params[name], ref, rtol=1e-4,
                                       atol=1e-5, err_msg=name)

    def test_lr_schedule_steps_once_per_applied_step(self):
        # decay halves lr every 2 *applied* steps; with k=4 microbatches the
        # counter must still advance once per run, so trajectories match
        ref = _train(*_build(lr_sched=True), steps=4, batch=8)
        acc_main, acc_startup, acc_loss = _build(lr_sched=True)
        acc_main.set_gradient_accumulation(4)
        got = _train(acc_main, acc_startup, acc_loss, steps=4, batch=8)
        np.testing.assert_allclose(got[0], ref[0], rtol=1e-4, atol=1e-5)

    def test_batch_norm_stats_update_per_microbatch(self):
        # BN moving stats are mut_state inside the scan: they must carry
        # across microbatches (k updates per step), and training still works
        main, startup, loss = _build(bn=True)
        main.set_gradient_accumulation(2)
        losses, _ = _train(main, startup, loss, steps=6, batch=16)
        assert losses[-1] < losses[0]

    def test_indivisible_batch_rejected(self):
        main, startup, loss = _build()
        main.set_gradient_accumulation(3)
        with pytest.raises(Exception, match="divisible"):
            _train(main, startup, loss, steps=1, batch=16)

    def test_with_amp(self):
        main, startup, loss = _build()
        main.set_amp(True).set_gradient_accumulation(2)
        losses, params = _train(main, startup, loss, steps=6, batch=16)
        assert losses[-1] < losses[0]
        assert all(p.dtype == np.float32 for p in params.values())

    def test_global_norm_clip_chain_runs_in_apply_phase(self):
        # the clip-by-global-norm chain (squared_l2_norm -> sum -> sqrt ->
        # max -> div -> mul) spans several helper ops; all must land in the
        # apply phase or the scan body reads values that don't exist yet
        from paddle_tpu.core.program import unique_name

        main, startup = fluid.Program(), fluid.Program()
        with unique_name.guard(), fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[16], dtype="float32")
            y = fluid.layers.data("y", shape=[1], dtype="float32")
            pred = fluid.layers.fc(x, size=1)
            loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
            fluid.clip.set_gradient_clip(
                fluid.clip.GradientClipByGlobalNorm(1.0))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        main.set_gradient_accumulation(2)
        losses, _ = _train(main, startup, loss, steps=4, batch=8)
        assert losses[-1] < losses[0]

    def test_per_example_fetch_concatenates(self):
        # fetching a [B, C] activation under accumulation must return the
        # full batch in feed order, not a cross-microbatch average
        from paddle_tpu.core.program import unique_name

        main, startup = fluid.Program(), fluid.Program()
        with unique_name.guard(), fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[4], dtype="float32")
            y = fluid.layers.data("y", shape=[1], dtype="float32")
            pred = fluid.layers.fc(x, size=3)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(
                    fluid.layers.fc(pred, size=1), y))
            fluid.optimizer.SGD(learning_rate=0.0).minimize(loss)
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup, scope=scope)
            rs = np.random.RandomState(0)
            X = rs.rand(8, 4).astype("float32")
            Y = np.zeros((8, 1), dtype="float32")
            (ref,) = exe.run(main, feed={"x": X, "y": Y},
                             fetch_list=[pred], scope=scope)
            main.set_gradient_accumulation(2)
            (got,) = exe.run(main, feed={"x": X, "y": Y},
                             fetch_list=[pred], scope=scope)
        assert got.shape == (8, 3)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)

    def test_version_bump_invalidates_cache(self):
        main, startup, loss = _build()
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup, scope=scope)
            rs = np.random.RandomState(0)
            X = rs.rand(8, 16).astype("float32")
            Y = X.sum(1, keepdims=True).astype("float32")
            exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss],
                    scope=scope)
            main.set_gradient_accumulation(2)  # same shapes, new plan
            (v,) = exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss],
                           scope=scope)
            assert np.isfinite(float(v))


def test_a_programs_gradient_clip_is_no_other_programs():
    """``set_gradient_clip`` keeps the clip on the parameters of the
    program it is called under, as the reference does. It used to keep it
    in a module global as well, so every later ``minimize`` of the process
    clipped by it: the clip this file sets grew the trainer program of
    ``test_dist_ps.py``'s sparse table with a global norm over
    ``emb_w@GRAD``, which that program never makes (red under six
    workers whenever the two files shared one, green alone)."""
    def minimized(clip):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[16], dtype="float32")
            y = fluid.layers.data("y", shape=[1], dtype="float32")
            loss = fluid.layers.mean(fluid.layers.square_error_cost(
                fluid.layers.fc(x, size=1), y))
            if clip is not None:
                fluid.clip.set_gradient_clip(clip)
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        return [op.type for op in main.global_block().ops]

    plain = minimized(None)
    clipped = minimized(fluid.clip.GradientClipByGlobalNorm(1.0))
    assert len(clipped) > len(plain) and "sqrt" in clipped
    assert minimized(None) == plain
