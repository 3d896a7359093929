"""Long self-attention in training runs in query blocks, each over its
keys in chunks and recomputed in the backward (``layers.attention_core``):
values and gradients equal the dense attention's, for one KV head (MQA)
and for grouped heads."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L


@pytest.mark.parametrize("kv_heads", [1, 2])
def test_query_blocks_equal_dense_attention(kv_heads, monkeypatch):
    k0 = jax.random.PRNGKey(0)
    q = jax.random.normal(k0, (1, 64, 4, 8))
    k = jax.random.normal(jax.random.fold_in(k0, 1), (1, 64, kv_heads, 8))
    v = jax.random.normal(jax.random.fold_in(k0, 2), (1, 64, kv_heads, 8))

    def f(q, k, v, chunk):
        return jnp.sum(jnp.sin(L.attention_core(q, k, v, chunk=chunk)))
    want = L.attention_core(q, k, v, chunk=None)
    g_want = jax.grad(f, argnums=(0, 1, 2))(q, k, v, None)
    monkeypatch.setattr(L, "LONG_SEQ", 16)
    monkeypatch.setattr(L, "Q_BLOCK", 16)
    jaxpr = str(jax.make_jaxpr(lambda q, k, v: L.attention_core(
        q, k, v, chunk=8))(q, k, v))
    assert jaxpr.count("prevent_cse") >= 4       # one remat per query block
    got = L.attention_core(q, k, v, chunk=8)
    g_got = jax.grad(f, argnums=(0, 1, 2))(q, k, v, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
