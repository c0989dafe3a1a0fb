"""Sequence-parallel attention tests: ring + Ulysses vs dense reference.

Model: survey §4/3 (multi-device tests on a virtual mesh). The reference has no
sequence parallelism (survey §5.7) — these validate our TPU-native extension.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.distributed.sequence_parallel import (
    ring_attention,
    ulysses_attention,
    split_sequence,
    gather_sequence,
)

B, H, S, D = 2, 8, 64, 16
SP = 4


def dense_ref(q, k, v, causal):
    scale = 1.0 / np.sqrt(D)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _mesh():
    return Mesh(np.array(jax.devices()[:SP]), ("sp",))


def _qkv(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    mk = lambda k: jax.random.normal(k, (B, H, S, D), jnp.float32)
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_forward(causal):
    q, k, v = _qkv()
    mesh = _mesh()
    spec = P(None, None, "sp", None)
    f = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    out = jax.jit(f)(q, k, v)
    ref = dense_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_grads(causal):
    q, k, v = _qkv(1)
    mesh = _mesh()
    spec = P(None, None, "sp", None)

    def loss_ring(q, k, v):
        f = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "sp", causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        )
        w = jnp.sin(jnp.arange(D) / D)
        return jnp.sum(f(q, k, v) * w)

    def loss_ref(q, k, v):
        w = jnp.sin(jnp.arange(D) / D)
        return jnp.sum(dense_ref(q, k, v, causal) * w)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention(causal):
    q, k, v = _qkv(2)
    mesh = _mesh()
    spec = P(None, None, "sp", None)
    f = jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "sp", causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    out = jax.jit(f)(q, k, v)
    ref = dense_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_split_gather_sequence():
    x = jnp.arange(2 * S * 4, dtype=jnp.float32).reshape(2, S, 4)
    mesh = _mesh()

    def body(x):
        loc = split_sequence(x, "sp", seq_dim=1)
        assert loc.shape == (2, S // SP, 4)
        return gather_sequence(loc, "sp", seq_dim=1)

    f = jax.shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                      check_vma=False)
    out = jax.jit(f)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))


def test_ring_attention_bf16():
    q, k, v = (t.astype(jnp.bfloat16) for t in _qkv(3))
    mesh = _mesh()
    spec = P(None, None, "sp", None)
    f = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    out = jax.jit(f)(q, k, v)
    ref = dense_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=3e-2, rtol=3e-2
    )
