"""Kernel tier (paddle_tpu/kernels/): registry contract, Mosaic
legality of every candidate grid, forward+backward parity of the new
fused kernels vs their composed fallbacks (interpret mode on CPU —
tolerances per kernel docstring), dispatch semantics (bypass / default-
composed / tuned-pallas), and the fuse_kernel_tier_pass rewrites
(bitwise with the unfused program on the default dispatch path).
"""

import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import kernels
from paddle_tpu.core.scope import Scope, scope_guard
from paddle_tpu.kernels import tune


@pytest.fixture(autouse=True)
def _clean_tuner(monkeypatch, tmp_path):
    """Every test runs with an isolated (empty) winner cache and a clean
    decision ledger — tuned entries must never leak between tests."""
    monkeypatch.setenv("PADDLE_TPU_KERNEL_CACHE_DIR", str(tmp_path / "kc"))
    monkeypatch.delenv("PADDLE_TPU_KERNELS", raising=False)
    monkeypatch.delenv("PADDLE_TPU_KERNEL_TUNE", raising=False)
    tune.reset()
    kernels.reset_decisions()
    yield
    tune.reset()
    kernels.reset_decisions()


# ------------------------------------------------------------- registry
def test_registry_catalog_contract():
    names = kernels.all_kernels()
    assert names == ["adam_update", "attention", "kv_cache_write",
                     "layernorm_residual", "sgd_update", "ssm_scan",
                     "ssm_update"]
    for name in names:
        kdef = kernels.get_kernel(name)
        assert callable(kdef.fallback), name
        assert kdef.doc, "%s: registry entries carry docstrings" % name
        assert kdef.tol, name


def test_registry_rejects_incomplete_entries():
    from paddle_tpu.kernels.registry import register_kernel

    with pytest.raises(ValueError, match="fallback"):
        register_kernel("bogus_k1", fallback=None, signature=None,
                        candidates=None, check=None, make_inputs=None)(
            lambda cfg: None)

    def undocumented(cfg):
        return None

    with pytest.raises(ValueError, match="docstring"):
        register_kernel("bogus_k2", fallback=lambda: None, signature=None,
                        candidates=None, check=None,
                        make_inputs=None)(undocumented)
    assert not kernels.has_kernel("bogus_k1")
    assert not kernels.has_kernel("bogus_k2")


# ------------------------------------------------------- Mosaic legality
@pytest.mark.parametrize("op,sigs", [
    ("layernorm_residual", [("float32", 7, 48), ("float32", 4096, 512),
                            ("float32", 130, 128)]),
    ("adam_update", [("float32", 100, 4), ("float32", 70000, 16)]),
    ("sgd_update", [("float32", 100, 4), ("float32", 70000, 16)]),
    ("attention", [(128, 128), (1024, 1024), (64, 512)]),
])
def test_every_candidate_is_mosaic_legal(op, sigs):
    """KernelDef.check passes for EVERY grid candidate at representative
    signatures — the autotuner asserts exactly this before measuring."""
    kdef = kernels.get_kernel(op)
    for sig in sigs:
        cands = list(kdef.candidates(sig))
        assert cands, (op, sig)
        for cfg in cands:
            kdef.check(cfg, sig)


def test_illegal_candidates_raise():
    with pytest.raises(ValueError, match="Mosaic-illegal"):
        kernels.get_kernel("layernorm_residual").check(
            (9,), ("float32", 64, 32))
    with pytest.raises(ValueError, match="Mosaic"):
        kernels.get_kernel("adam_update").check((9,), ("float32", 4096, 4))
    with pytest.raises(ValueError, match="Mosaic"):
        kernels.get_kernel("attention").check((100, 128), (256, 256))
    with pytest.raises(ValueError, match="Mosaic"):
        kernels.get_kernel("attention").check((128, 100), (256, 256))


# ---------------------------------------------------------------- parity
def _ln_args(n=37, d=96, seed=0):
    import jax.numpy as jnp

    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(n, d).astype("float32"))
    r = jnp.asarray(rs.randn(n, d).astype("float32"))
    sc = jnp.asarray((rs.rand(d) + 0.5).astype("float32"))
    b = jnp.asarray(rs.randn(d).astype("float32"))
    return x, r, sc, b


@pytest.mark.parametrize("cfg", [(8,), (16,), (64,)])
def test_layernorm_residual_forward_parity(cfg):
    """Kernel vs composed fallback, interpret mode: fwd atol 1e-5 (the
    tolerance stated in the kernel docstring); the residual stream is
    bitwise (a pure f32 add)."""
    from paddle_tpu.kernels import layernorm as L

    x, r, sc, b = _ln_args()
    yk, sk, mk, vk = L.layernorm_residual(cfg, x, r, sc, b, eps=1e-5)
    yc, scmp, mc, vc = L.composed_layernorm_residual(x, r, sc, b, eps=1e-5)
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(scmp))
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yc), atol=1e-5)
    np.testing.assert_allclose(np.asarray(mk), np.asarray(mc), atol=1e-5)
    np.testing.assert_allclose(np.asarray(vk), np.asarray(vc), atol=1e-5)


def test_layernorm_residual_backward_parity():
    """Backward kernel vs autodiff of the composed fallback: atol 5e-5
    on all four input grads, INCLUDING the residual stream's own
    cotangent (s is consumed downstream in real programs) and the
    mean/variance cotangents (exactness of the jnp correction terms)."""
    import jax

    from paddle_tpu.kernels import layernorm as L

    x, r, sc, b = _ln_args(n=26, d=64, seed=3)

    def loss(fn):
        def inner(x, r, sc, b):
            y, s, m, v = fn(x, r, sc, b)
            return (y ** 2).sum() + (s * 1.5).sum() \
                + (m * 0.3).sum() + (v * 0.2).sum()
        return inner

    gk = jax.grad(loss(lambda *a: L.layernorm_residual((8,), *a)),
                  argnums=(0, 1, 2, 3))(x, r, sc, b)
    gc = jax.grad(loss(lambda *a: L.composed_layernorm_residual(*a)),
                  argnums=(0, 1, 2, 3))(x, r, sc, b)
    for a, c, name in zip(gk, gc, ("x", "r", "scale", "bias")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=5e-5, err_msg=name)


@pytest.mark.parametrize("wd", [0.0, 0.01])
def test_adam_update_parity(wd):
    """Flattened Adam sweep vs the composed fallback: atol 2e-6 (1-2 ULP
    from FMA contraction — the kernel docstring's stated tolerance),
    both weight-decay branches."""
    import jax.numpy as jnp

    from paddle_tpu.kernels import optimizer_update as O

    rs = np.random.RandomState(1)
    n = 3001  # deliberately not a multiple of 128: padding is exercised
    p, g, m, v, lrt, lrwd = (
        jnp.asarray((rs.rand(n) + 0.1).astype("float32"))
        for _ in range(6))
    for cfg in ((8,), (64,)):
        ok = O.adam_update(cfg, p, g, m, v, lrt, lrwd, weight_decay=wd)
        oc = O.composed_adam_update(p, g, m, v, lrt, lrwd,
                                    weight_decay=wd)
        for a, c, name in zip(ok, oc, ("p", "m", "v")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       atol=2e-6, err_msg=name)


def test_sgd_update_parity():
    import jax.numpy as jnp

    from paddle_tpu.kernels import optimizer_update as O

    rs = np.random.RandomState(2)
    n = 515
    p, g, lrv = (jnp.asarray(rs.rand(n).astype("float32"))
                 for _ in range(3))
    (pk,) = O.sgd_update((16,), p, g, lrv)
    (pc,) = O.composed_sgd_update(p, g, lrv)
    np.testing.assert_allclose(np.asarray(pk), np.asarray(pc), atol=2e-6)


@pytest.mark.parametrize("op", ["adam_update", "sgd_update"])
def test_optimizer_group_entry_parity(op):
    """The REGISTERED surface (what the tuner measures) is the whole
    group wrapper — concat + scalar broadcasts + kernel + K splits —
    vs the per-param composed replay shape: atol 2e-6 per param, on the
    registry's own make_inputs at an uneven K-way split."""
    kdef = kernels.get_kernel(op)
    sig = ("float32", 2000, 3)  # 3-way uneven split, padded sweep
    (ins,) = kdef.make_inputs(sig, np.random.RandomState(7))
    got = kdef.pallas((8,), ins)
    want = kdef.fallback(ins)
    for g_list, w_list in zip(got, want):
        assert len(g_list) == 3
        for a, c in zip(g_list, w_list):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       atol=2e-6)


# -------------------------------------------------------------- dispatch
def test_bypass_moves_zero_kernel_counters(monkeypatch):
    """PADDLE_TPU_KERNELS=0: run_kernel returns the composed fallback
    and NO paddle_kernel_* family moves — the A/B bypass is provable."""
    from paddle_tpu.observe.families import REGISTRY

    def kernel_counters():
        snap = REGISTRY.snapshot()["metrics"]
        return {k: v["samples"] for k, v in snap.items()
                if k.startswith("paddle_kernel")}

    monkeypatch.setenv("PADDLE_TPU_KERNELS", "0")
    before = kernel_counters()
    assert before, "paddle_kernel_* families must be declared"
    x, r, sc, b = _ln_args(n=8, d=32)
    out = kernels.run_kernel("layernorm_residual", (x, r, sc, b),
                             {"eps": 1e-5})
    assert len(out) == 4
    assert kernel_counters() == before
    assert kernels.decisions_seen()["layernorm_residual"]["choice"] \
        == "bypass"


def test_default_dispatch_is_composed_and_counts_miss():
    from paddle_tpu.observe.families import (KERNEL_DISPATCHES,
                                             KERNEL_TUNER_MISSES)

    m0 = KERNEL_TUNER_MISSES.value
    d0 = KERNEL_DISPATCHES.labels(op="sgd_update", impl="composed").value
    import jax.numpy as jnp

    p = jnp.ones(40)
    lr = jnp.ones(1)
    ([out],) = kernels.run_kernel(
        "sgd_update", ({"Param": [p], "Grad": [p],
                        "LearningRate": [lr]},))
    np.testing.assert_array_equal(np.asarray(out), np.zeros(40))
    assert KERNEL_TUNER_MISSES.value == m0 + 1
    assert KERNEL_DISPATCHES.labels(op="sgd_update",
                                    impl="composed").value == d0 + 1
    dec = kernels.decisions_seen()["sgd_update"]
    assert dec == {"choice": "composed", "tuned": False}


def test_tuned_entry_routes_to_pallas():
    """An injected pallas winner flips dispatch to the kernel (the
    decision map marks it tuned), and a composed winner pins composed."""
    from paddle_tpu.kernels import optimizer_update as O

    sig = O.signature_for(40, "float32", 1)
    tune.set_entry("sgd_update", sig, {"choice": "pallas", "cfg": [8]})
    import jax.numpy as jnp

    p = jnp.ones(40)
    lr = jnp.ones(1)
    ([out],) = kernels.run_kernel(
        "sgd_update", ({"Param": [p], "Grad": [p],
                        "LearningRate": [lr]},))
    np.testing.assert_allclose(np.asarray(out), np.zeros(40), atol=2e-6)
    dec = kernels.decisions_seen()["sgd_update"]
    assert dec["choice"] == "pallas:8" and dec["tuned"] is True


# --------------------------------------------- fuse_kernel_tier_pass
def _ln_heavy_program(n_blocks=3, with_adam=True, seed=11):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[6, 32],
                                  dtype="float32")
            h = x
            for _ in range(n_blocks):
                branch = fluid.layers.fc(h, size=32, num_flatten_dims=2,
                                         act="relu")
                s = fluid.layers.elementwise_add(h, branch)
                h = fluid.layers.layer_norm(s, begin_norm_axis=2)
            loss = fluid.layers.reduce_mean(h)
            opt = fluid.optimizer.Adam(1e-3) if with_adam \
                else fluid.optimizer.SGD(0.1)
            opt.minimize(loss)
    return main, startup, loss


def test_pass_rewrites_ln_pairs_and_optimizer_runs():
    from paddle_tpu.core.passes import optimize_program

    main, _s, loss = _ln_heavy_program()
    opt, stats = optimize_program(main, fetch_list=[loss], level=2)
    types = [op.type for op in opt.global_block().ops]
    assert types.count("fused_layernorm_residual") == 3
    assert types.count("fused_optimizer_update") == 1
    assert "adam" not in types
    row = next(r for r in stats if r["pass"] == "fuse_kernel_tier_pass")
    assert row["ln_residual_fused"] == 3
    assert row["optimizer_groups"] == 1


def test_pass_is_noop_with_kernels_off(monkeypatch):
    from paddle_tpu.core.passes import optimize_program

    monkeypatch.setenv("PADDLE_TPU_KERNELS", "0")
    main, _s, loss = _ln_heavy_program()
    opt, stats = optimize_program(main, fetch_list=[loss], level=2)
    types = [op.type for op in opt.global_block().ops]
    assert "fused_layernorm_residual" not in types
    assert "fused_optimizer_update" not in types
    row = next(r for r in stats if r["pass"] == "fuse_kernel_tier_pass")
    assert row["ops_before"] == row["ops_after"]


def test_pass_skips_broadcast_add_and_multi_write():
    """A broadcasting bias-add feeding a layer_norm is NOT the residual
    seam; the pattern must not fire on it."""
    from paddle_tpu.core.passes import optimize_program

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[6, 32],
                                  dtype="float32")
            bvec = fluid.layers.create_parameter([32], "float32",
                                                 name="bcast_b")
            s = fluid.layers.elementwise_add(x, bvec)  # broadcast add
            h = fluid.layers.layer_norm(s, begin_norm_axis=2)
            loss = fluid.layers.reduce_mean(h)
    opt, _ = optimize_program(main, fetch_list=[loss], level=2)
    assert "fused_layernorm_residual" not in [
        op.type for op in opt.global_block().ops]


def test_optimizer_run_splits_on_amp_override_and_stays_bitwise(
        monkeypatch):
    """A per-op __amp__ user override is part of the optimizer group
    key: the overridden op must not share a fused replay with its
    neighbors (one cast tag per group), and bf16-AMP training with the
    override stays bitwise level 2 vs level 0."""
    from paddle_tpu.core.passes import optimize_program

    def build():
        main, startup, loss = _ln_heavy_program()
        adams = [op for op in main.global_block().ops
                 if op.type == "adam"]
        assert len(adams) >= 3
        adams[1].attrs["__amp__"] = "keep"  # user override on ONE op
        return main, startup, loss

    main, _s, loss = build()
    opt, _ = optimize_program(main, fetch_list=[loss], level=2)
    types = [op.type for op in opt.global_block().ops]
    # the override op and its lone predecessor cannot group (runs of 1
    # never fuse); the remaining >= 2 consecutive adams still do — and
    # the fused group must carry the plain (no-override) tag
    assert types.count("adam") == 2
    assert types.count("fused_optimizer_update") == 1
    fused = next(op for op in opt.global_block().ops
                 if op.type == "fused_optimizer_update")
    assert "amp_override" not in fused.attrs

    def steps(level):
        monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", str(level))
        main, startup, loss = build()
        main.set_amp(True)
        scope = Scope()
        X = np.random.RandomState(0).randn(4, 6, 32).astype(np.float32)
        with scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup, scope=scope)
            return [exe.run(main, feed={"x": X}, fetch_list=[loss.name],
                            scope=scope)[0] for _ in range(2)]

    for a, b in zip(steps(0), steps(2)):
        assert np.array_equal(a, b)


def test_optimizer_ops_split_by_program_ops_never_fuse(monkeypatch):
    """Two same-hyperparameter sgd ops SEPARATED in program order by an
    add->layer_norm pair (which the ln rewrite fuses away) must not
    become 'consecutive' and group: the fused update would anchor at
    the second sgd's slot, moving the first param update past the
    fused layer_norm that reads it (review-confirmed ordering hazard).
    Runs are judged on ORIGINAL program adjacency."""
    from paddle_tpu.core.passes import optimize_program

    def build():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                x = fluid.layers.data(name="x", shape=[4, 32],
                                      dtype="float32")
                g = fluid.layers.fill_constant([32], "float32", 0.5)
                lr = fluid.layers.fill_constant([1], "float32", 0.1)
                pz = fluid.layers.create_parameter(
                    [32], "float32", name="pz",
                    default_initializer=fluid.initializer.Constant(4.0))
                blk = main.global_block()
                n_before = len(blk.ops)
                s = fluid.layers.elementwise_add(x, x)
                h = fluid.layers.layer_norm(
                    s, begin_norm_axis=2,
                    param_attr=fluid.ParamAttr(name="lns"),
                    bias_attr=fluid.ParamAttr(name="lnb"))
                loss = fluid.layers.reduce_mean(h)
                role = {"__op_role__": "optimize"}
                # sgd(lns) BEFORE the add->ln pair that reads lns ...
                blk.insert_op(n_before, "sgd",
                              {"Param": [blk.vars["lns"]], "Grad": [g],
                               "LearningRate": [lr]},
                              {"ParamOut": [blk.vars["lns"]]},
                              dict(role))
                # ... and sgd(pz) after it: same key, NOT adjacent
                blk.append_op("sgd", {"Param": [pz], "Grad": [g],
                                      "LearningRate": [lr]},
                              {"ParamOut": [pz]}, dict(role))
        return main, startup, loss

    main, _s, loss = build()
    opt, _ = optimize_program(main, fetch_list=[loss], level=2)
    types = [op.type for op in opt.global_block().ops]
    assert "fused_optimizer_update" not in types  # NOT adjacent
    assert types.count("sgd") == 2
    assert "fused_layernorm_residual" in types    # the ln pair fused

    def run(level):
        monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", str(level))
        main, startup, loss = build()
        scope = Scope()
        X = np.random.RandomState(0).randn(2, 4, 32) \
            .astype(np.float32)
        with scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup, scope=scope)
            out = exe.run(main, feed={"x": X}, fetch_list=[loss.name],
                          scope=scope)[0]
            return np.asarray(out), np.asarray(scope.find_var("lns"))

    (l0, s0), (l2, s2) = run(0), run(2)
    assert np.array_equal(l0, l2) and np.array_equal(s0, s2)
    """sgd(Param=a, Grad=a); sgd(Param=b, Grad=a): unfused, the second
    op reads the UPDATED a — the fused lowering fetches every input at
    op entry, so fusing would hand it the stale pre-update value. The
    pass must skip the run (and the program must stay bitwise level 2
    vs 0 — the review-confirmed hazard-direction guard)."""
    from paddle_tpu.core.passes import optimize_program

    def build():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                a = fluid.layers.create_parameter(
                    [16], "float32", name="pa",
                    default_initializer=fluid.initializer.Constant(2.0))
                b = fluid.layers.create_parameter(
                    [16], "float32", name="pb",
                    default_initializer=fluid.initializer.Constant(3.0))
                lr = fluid.layers.fill_constant([1], "float32", 0.1)
                blk = main.global_block()
                role = {"__op_role__": "optimize"}
                blk.append_op("sgd", {"Param": [a], "Grad": [a],
                                      "LearningRate": [lr]},
                              {"ParamOut": [a]}, dict(role))
                blk.append_op("sgd", {"Param": [b], "Grad": [a],
                                      "LearningRate": [lr]},
                              {"ParamOut": [b]}, dict(role))
        return main, startup

    main, _startup = build()
    opt, _ = optimize_program(main, fetch_list=[], level=2)
    assert "fused_optimizer_update" not in [
        op.type for op in opt.global_block().ops]

    def run(level):
        monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", str(level))
        main, startup = build()
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup, scope=scope)
            exe.run(main, scope=scope)
            return (np.asarray(scope.find_var("pa")),
                    np.asarray(scope.find_var("pb")))

    a0, b0 = run(0)
    a2, b2 = run(2)
    assert np.array_equal(a0, a2) and np.array_equal(b0, b2)
    # and the unfused semantics really are read-after-write: pb update
    # uses the UPDATED pa (2.0 -> 1.8; pb = 3.0 - 0.1*1.8 = 2.82)
    np.testing.assert_allclose(b0, np.full(16, 2.82, np.float32),
                               atol=1e-6)


def _train(level, monkeypatch, optimizer="adam", steps=3, amp=False,
           kernels_env=None):
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", str(level))
    if kernels_env is not None:
        monkeypatch.setenv("PADDLE_TPU_KERNELS", kernels_env)
    main, startup, loss = _ln_heavy_program(
        with_adam=(optimizer == "adam"))
    if amp:
        main.set_amp(True)
    scope = Scope()
    X = np.random.RandomState(0).randn(4, 6, 32).astype(np.float32)
    with scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        losses = [exe.run(main, feed={"x": X}, fetch_list=[loss.name],
                          scope=scope)[0] for _ in range(steps)]
        params = {n: np.asarray(scope.find_var(n))
                  for n in ("fc_0.w_0", "fc_1.w_0")}
    return losses, params


@pytest.mark.parametrize("optimizer", ["adam", "sgd"])
def test_fused_training_is_bitwise_identical(monkeypatch, optimizer):
    """Level 2 (fused_layernorm_residual + fused_optimizer_update on the
    composed dispatch path) vs level 0: losses and params bitwise —
    the kernel-tier rewrites preserve the optimizer pipeline's core
    contract through BOTH new fused ops."""
    l0, p0 = _train(0, monkeypatch, optimizer)
    l2, p2 = _train(2, monkeypatch, optimizer)
    for a, b in zip(l0, l2):
        assert np.array_equal(a, b)
    for n in p0:
        assert np.array_equal(p0[n], p2[n]), n


def test_fused_training_amp_bitwise(monkeypatch):
    """Under AMP the fused layernorm op REPLAYS per-constituent casts
    (add in bf16, norm in f32) and the optimizer sweep upcasts like the
    unfused f32-policy ops: level 2 == level 0 bitwise with amp on."""
    l0, p0 = _train(0, monkeypatch, amp=True)
    l2, p2 = _train(2, monkeypatch, amp=True)
    for a, b in zip(l0, l2):
        assert np.array_equal(a, b)
    for n in p0:
        assert np.array_equal(p0[n], p2[n]), n


def test_kernels_off_training_matches_and_moves_no_counters(monkeypatch):
    """PADDLE_TPU_KERNELS=0 end to end: the same training trajectory
    (bitwise) and zero movement across every paddle_kernel_* family."""
    from paddle_tpu.observe.families import REGISTRY

    def kernel_counters():
        return {k: v["samples"]
                for k, v in REGISTRY.snapshot()["metrics"].items()
                if k.startswith("paddle_kernel")}

    l2, p2 = _train(2, monkeypatch)
    before = kernel_counters()
    assert before, "paddle_kernel_* families must be declared"
    loff, poff = _train(2, monkeypatch, kernels_env="0")
    assert kernel_counters() == before
    for a, b in zip(l2, loff):
        assert np.array_equal(a, b)
    for n in p2:
        assert np.array_equal(p2[n], poff[n]), n


def test_tuned_pallas_training_close_and_keyed(monkeypatch):
    """With tuned pallas winners injected for the program's signatures,
    training still converges to the composed trajectory within kernel
    tolerance, the decision map shows pallas, and flipping the table
    re-prepares (the kernels config keys the plan cache)."""
    from paddle_tpu.kernels import layernorm as L
    from paddle_tpu.kernels import optimizer_update as O
    from paddle_tpu.observe.families import EXECUTOR_CACHE_MISSES

    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "2")
    l0, _ = _train(2, monkeypatch, steps=2)

    # inject winners for every signature the program will dispatch
    tune.set_entry("layernorm_residual",
                   L.signature_for(4 * 6, 32, "float32"),
                   {"choice": "pallas", "cfg": [8]})
    # adam group: 3 x (32x32 W + 32 b + 32 ln scale + 32 ln bias)
    n_total = 3 * (32 * 32 + 32 + 32 + 32)
    tune.set_entry("adam_update",
                   O.signature_for(n_total, "float32", 12),
                   {"choice": "pallas", "cfg": [8]})
    kernels.reset_decisions()
    m0 = EXECUTOR_CACHE_MISSES.value
    lt, _ = _train(2, monkeypatch, steps=2)
    assert EXECUTOR_CACHE_MISSES.value > m0  # epoch keyed a re-prepare
    seen = kernels.decisions_seen()
    assert seen["layernorm_residual"]["choice"].startswith("pallas")
    assert seen["adam_update"]["choice"].startswith("pallas")
    for a, b in zip(l0, lt):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4)
