"""Expert-parallel MoE tests: top-1 switch routing over the 8-device
mesh must match a dense single-device evaluation of the same router and
experts, forward and backward, including capacity-overflow drops."""

import numpy as np

import jax
import jax.numpy as jnp
import pytest

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.parallel.moe import moe_apply


def _setup(E=8, T=32, D=8, H=16, seed=0):
    rs = np.random.RandomState(seed)
    w1 = jnp.asarray(rs.randn(E, D, H).astype("float32") * 0.3)
    b1 = jnp.asarray(rs.randn(E, H).astype("float32") * 0.1)
    w2 = jnp.asarray(rs.randn(E, H, D).astype("float32") * 0.3)
    b2 = jnp.asarray(rs.randn(E, D).astype("float32") * 0.1)
    gw = jnp.asarray(rs.randn(D, E).astype("float32"))
    x = jnp.asarray(rs.randn(T, D).astype("float32"))
    return (w1, b1, w2, b2), gw, x


def _dense_reference(params, gw, x, capacity=None):
    """Single-device transcription of the routed computation."""
    w1, b1, w2, b2 = params
    E = w1.shape[0]
    probs = jax.nn.softmax(x @ gw, axis=-1)
    eidx = jnp.argmax(probs, axis=-1)
    gate = jnp.max(probs, axis=-1)
    onehot = jax.nn.one_hot(eidx, E)
    pos = (jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1)
           - 1).astype(jnp.int32)
    keep = (pos < capacity) if capacity else jnp.ones_like(pos, bool)

    def expert(e, v):
        return jax.nn.relu(v @ w1[e] + b1[e]) @ w2[e] + b2[e]

    outs = jax.vmap(lambda v, e: expert(e, v))(x, eidx)
    outs = jnp.where(keep[:, None], outs, 0.0)
    aux = E * jnp.sum(jnp.mean(onehot, axis=0) * jnp.mean(probs, axis=0))
    return outs * gate[:, None], aux


def _sharded(params, gw, x, capacity=None):
    mesh = Mesh(np.array(jax.devices()), ("expert",))
    fn = shard_map(
        lambda w1, b1, w2, b2, g, xx: moe_apply(
            (w1, b1, w2, b2), g, xx, "expert", capacity=capacity),
        mesh=mesh,
        in_specs=(P("expert"),) * 4 + (P(), P()),
        out_specs=(P(), P()),
        check_vma=False)
    return jax.jit(fn)(*params, gw, x)


def test_moe_matches_dense():
    params, gw, x = _setup()
    got, aux = _sharded(params, gw, x, capacity=32)  # no drops
    want, aux_ref = _dense_reference(params, gw, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-5)


def test_moe_capacity_drops():
    params, gw, x = _setup(seed=3)
    cap = 2
    got, _ = _sharded(params, gw, x, capacity=cap)
    want, _ = _dense_reference(params, gw, x, capacity=cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    # overflow rows really are zeroed
    assert (np.abs(np.asarray(got)).sum(axis=1) == 0).any()


def test_moe_gradients_match():
    params, gw, x = _setup(T=16)
    mesh = Mesh(np.array(jax.devices()), ("expert",))
    fn = shard_map(
        lambda w1, b1, w2, b2, g, xx: moe_apply(
            (w1, b1, w2, b2), g, xx, "expert", capacity=16),
        mesh=mesh, in_specs=(P("expert"),) * 4 + (P(), P()),
        out_specs=(P(), P()), check_vma=False)

    def loss_sharded(params, g):
        out, aux = fn(*params, g, x)
        return jnp.sum(out ** 2) + 0.01 * aux

    def loss_dense(params, g):
        out, aux = _dense_reference(params, g, x)
        return jnp.sum(out ** 2) + 0.01 * aux

    gp = jax.jit(jax.grad(loss_sharded, (0, 1)))(params, gw)
    gd = jax.grad(loss_dense, (0, 1))(params, gw)
    for a, r in zip(jax.tree.leaves(gp), jax.tree.leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=1e-4, rtol=1e-4)


def test_moe_apply_top2_matches_dense():
    """moe_apply(top_k=2): the all_to_all path equals an independent
    dense transcription of GShard top-2 (renormalized gates, both
    experts' outputs mixed), forward and backward."""
    params, gw, x = _setup()
    E = params[0].shape[0]

    def dense2(params, gw, x):
        w1, b1, w2, b2 = params
        probs = jax.nn.softmax(x @ gw, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, 2)
        gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

        def expert(e, v):
            return jax.nn.relu(v @ w1[e] + b1[e]) @ w2[e] + b2[e]

        o1 = jax.vmap(lambda v, e: expert(e, v))(x, top_e[:, 0])
        o2 = jax.vmap(lambda v, e: expert(e, v))(x, top_e[:, 1])
        out = o1 * gates[:, 0:1] + o2 * gates[:, 1:2]
        onehot1 = jax.nn.one_hot(top_e[:, 0], E)
        aux = E * jnp.sum(jnp.mean(onehot1, axis=0)
                          * jnp.mean(probs, axis=0))
        return out, aux

    mesh = Mesh(np.array(jax.devices()), ("expert",))
    fn = shard_map(
        lambda w1, b1, w2, b2, g, xx: moe_apply(
            (w1, b1, w2, b2), g, xx, "expert", capacity=64, top_k=2),
        mesh=mesh,
        in_specs=(P("expert"),) * 4 + (P(), P()),
        out_specs=(P(), P()),
        check_vma=False)
    out, aux = jax.jit(fn)(*params, gw, x)
    ref, aux_ref = dense2(params, gw, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-6)

    # gradients flow through both experts and the renormalized gates
    g1 = jax.grad(lambda g: jnp.sum(jax.jit(fn)(*params, g, x)[0] ** 2))(gw)
    g2 = jax.grad(lambda g: jnp.sum(dense2(params, g, x)[0] ** 2))(gw)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               atol=2e-4, rtol=2e-4)


def test_moe_z_loss_exact_and_differentiable():
    """aux with z_loss equals aux without plus
    z * mean(logsumexp(logits)^2) exactly, on both the all_to_all path
    and the dense route_tokens; its gradient shrinks router logits."""
    from paddle_tpu.parallel.moe import route_tokens

    params, gw, x = _setup()
    E = params[0].shape[0]
    z = 1e-2

    *_, aux0 = route_tokens(x, gw, E, capacity=64)
    *_, auxz = route_tokens(x, gw, E, capacity=64, z_loss=z)
    expect = z * jnp.mean(
        jax.nn.logsumexp((x @ gw).astype(jnp.float32), axis=-1) ** 2)
    np.testing.assert_allclose(float(auxz - aux0), float(expect),
                               rtol=1e-5)

    # the distributed path folds the identical term
    mesh = Mesh(np.array(jax.devices()), ("expert",))
    fn = shard_map(
        lambda w1, b1, w2, b2, g, xx: moe_apply(
            (w1, b1, w2, b2), g, xx, "expert", capacity=64, z_loss=z),
        mesh=mesh, in_specs=(P("expert"),) * 4 + (P(), P()),
        out_specs=(P(), P()), check_vma=False)
    _, aux_dist = jax.jit(fn)(*params, gw, x)
    np.testing.assert_allclose(float(aux_dist), float(auxz), rtol=1e-5)

    # gradient steps on z-loss alone shrink the router logit scale
    def zterm(g):
        *_, a = route_tokens(x, g, E, capacity=64, z_loss=1.0)
        *_, a0 = route_tokens(x, g, E, capacity=64)
        return a - a0

    g = gw
    before = float(zterm(g))
    dg = jax.grad(zterm)(g)
    assert np.abs(np.asarray(dg)).max() > 0
    g = g - 0.5 * dg
    assert float(zterm(g)) < before


def test_moe_apply_top3_matches_dense():
    """top_k=3 sweep: the routed path equals a dense transcription of
    GShard top-3 (renormalized gates over the chosen three)."""
    params, gw, x = _setup(T=40)
    E = params[0].shape[0]

    def dense3(params, gw, x):
        w1, b1, w2, b2 = params
        probs = jax.nn.softmax(x @ gw, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, 3)
        gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

        def expert(e, v):
            return jax.nn.relu(v @ w1[e] + b1[e]) @ w2[e] + b2[e]

        out = 0
        for kk in range(3):
            ok = jax.vmap(lambda v, e: expert(e, v))(x, top_e[:, kk])
            out = out + ok * gates[:, kk:kk + 1]
        return out

    mesh = Mesh(np.array(jax.devices()), ("expert",))
    fn = shard_map(
        lambda w1, b1, w2, b2, g, xx: moe_apply(
            (w1, b1, w2, b2), g, xx, "expert", capacity=120, top_k=3),
        mesh=mesh, in_specs=(P("expert"),) * 4 + (P(), P()),
        out_specs=(P(), P()), check_vma=False)
    out, _ = jax.jit(fn)(*params, gw, x)
    ref = dense3(params, gw, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_moe_top3_choice_major_capacity():
    """With capacity 0 (degenerate: nothing fits) every contribution
    drops; with tiny capacity, 1st choices claim slots before ANY 2nd
    or 3rd choice — verified against the shared route_tokens on the
    all_to_all path staying exact."""
    from paddle_tpu.parallel.moe import route_tokens

    params, gw, x = _setup(T=24)
    E = params[0].shape[0]
    # tiny capacity: drops must match the shared routing exactly
    cap = 2
    eidx, gate, pos, keep, _ = route_tokens(x, gw, E, cap, top_k=3)
    # choice-major invariant: a kept 2nd/3rd choice never displaces a
    # dropped 1st choice of the same expert
    eidx, pos, keep = map(np.asarray, (eidx, pos, keep))
    for e in range(E):
        first_dropped = ((eidx[0] == e) & ~keep[0]).any()
        later_kept = (((eidx[1:] == e) & keep[1:]).any()
                      if first_dropped else False)
        assert not (first_dropped and later_kept), e

    mesh = Mesh(np.array(jax.devices()), ("expert",))
    fn = shard_map(
        lambda w1, b1, w2, b2, g, xx: moe_apply(
            (w1, b1, w2, b2), g, xx, "expert", capacity=cap, top_k=3),
        mesh=mesh, in_specs=(P("expert"),) * 4 + (P(), P()),
        out_specs=(P(), P()), check_vma=False)
    out, _ = jax.jit(fn)(*params, gw, x)

    # dense reconstruction honoring the same keep/drop set
    w1, b1, w2, b2 = params

    def expert(e, v):
        return jax.nn.relu(v @ w1[e] + b1[e]) @ w2[e] + b2[e]

    ref = np.zeros_like(np.asarray(x))
    gate = np.asarray(gate)
    for kk in range(3):
        ok = np.asarray(jax.vmap(lambda v, e: expert(e, v))(x, eidx[kk]))
        ref += np.where(keep[kk][:, None], ok * gate[kk][:, None], 0.0)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5,
                               rtol=1e-5)
