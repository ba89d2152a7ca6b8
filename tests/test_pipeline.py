"""Pipeline parallelism (collective-permute GPipe schedule) tests:
8 stages over the 8-device mesh must match the sequential composition
exactly, forward and backward."""

import numpy as np

import jax
import jax.numpy as jnp
import pytest

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.parallel.pipeline import pipeline_apply


def _setup(n_stages=8, d=16, mb=4, M=4, seed=0):
    rs = np.random.RandomState(seed)
    Ws = jnp.asarray(rs.randn(n_stages, d, d).astype("float32") * 0.3)
    bs = jnp.asarray(rs.randn(n_stages, d).astype("float32") * 0.1)
    x = jnp.asarray(rs.randn(M, mb, d).astype("float32"))
    return Ws, bs, x


def _stage(params, x):
    W, b = params
    return jnp.tanh(x @ W + b)


def _sequential(Ws, bs, x_mb):
    out = x_mb
    for i in range(Ws.shape[0]):
        out = jax.vmap(lambda x: _stage((Ws[i], bs[i]), x))(out)
    return out


def _pipelined(Ws, bs, x):
    mesh = Mesh(np.array(jax.devices()), ("pipe",))
    fn = shard_map(
        lambda W, b, xx: pipeline_apply(
            lambda p, a: _stage(p, a), (W, b), xx, "pipe"),
        mesh=mesh,
        in_specs=(P("pipe"), P("pipe"), P()),
        out_specs=P(),
        check_vma=False)
    return jax.jit(fn)(Ws, bs, x)


def test_pipeline_matches_sequential():
    Ws, bs, x = _setup()
    got = _pipelined(Ws, bs, x)
    want = _sequential(Ws, bs, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_gradients_match():
    """Autodiff transposes the ppermute schedule into the reverse-order
    backward pipeline; grads must equal the sequential model's."""
    Ws, bs, x = _setup(M=3, mb=2, d=8)
    mesh = Mesh(np.array(jax.devices()), ("pipe",))
    fn = shard_map(
        lambda W, b, xx: pipeline_apply(
            lambda p, a: _stage(p, a), (W, b), xx, "pipe"),
        mesh=mesh, in_specs=(P("pipe"), P("pipe"), P()), out_specs=P(),
        check_vma=False)

    def loss_pipe(W, b):
        return jnp.sum(fn(W, b, x) ** 2)

    def loss_seq(W, b):
        return jnp.sum(_sequential(W, b, x) ** 2)

    gp = jax.jit(jax.grad(loss_pipe, (0, 1)))(Ws, bs)
    gs = jax.grad(loss_seq, (0, 1))(Ws, bs)
    for a, r in zip(gp, gs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=1e-4, rtol=1e-4)
