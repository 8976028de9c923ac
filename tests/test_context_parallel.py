"""Context-parallel attention tests (ring + Ulysses) on the CPU mesh.

Parity: sharded CP attention must equal full attention over the global
sequence (fwd + grads)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.distributed.context_parallel import (ring_attention,
                                                     ulysses_attention)
from paddle_tpu.distributed.topology import (create_hybrid_mesh,
                                             set_hybrid_mesh)
from paddle_tpu.ops.flash_attention import reference_attention


def _qkv(b=2, s=64, h=4, d=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    mesh = create_hybrid_mesh(sep=4, dp=2)
    q, k, v = _qkv()
    out = ring_attention(q, k, v, mesh=mesh, causal=causal)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_grads_match(causal):
    mesh = create_hybrid_mesh(sep=4, dp=2)
    q, k, v = _qkv(b=1, s=32, h=2, d=8)

    f = lambda q, k, v: jnp.sum(
        jnp.sin(ring_attention(q, k, v, mesh=mesh, causal=causal)))
    g = lambda q, k, v: jnp.sum(
        jnp.sin(reference_attention(q, k, v, causal=causal)))
    gp = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(a, b, atol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(causal):
    mesh = create_hybrid_mesh(sep=4, dp=2)
    q, k, v = _qkv()
    out = ulysses_attention(q, k, v, mesh=mesh, causal=causal)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_ring_attention_sep8():
    mesh = create_hybrid_mesh(sep=8)
    q, k, v = _qkv(s=128)
    out = ring_attention(q, k, v, mesh=mesh, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_ring_attention_sep1_falls_back():
    mesh = create_hybrid_mesh(dp=8)
    set_hybrid_mesh(mesh)
    q, k, v = _qkv()
    out = ring_attention(q, k, v, mesh=mesh, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5)
