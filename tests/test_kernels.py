"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.embedding_bag import embedding_bag
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan

KEY = jax.random.PRNGKey(42)


class TestFlashAttention:
    @pytest.mark.parametrize("b,h,hkv,s,d,causal,bq,bk", [
        (2, 4, 2, 256, 64, True, 128, 128),
        (1, 8, 8, 130, 32, True, 64, 64),        # ragged seq
        (2, 2, 1, 64, 128, False, 32, 32),       # MQA, non-causal
        (1, 4, 4, 100, 64, True, 64, 32),        # uneven blocks
        (1, 6, 2, 96, 16, True, 32, 32),         # GQA group=3
    ])
    def test_matches_reference(self, b, h, hkv, s, d, causal, bq, bk):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32)
        out = flash_attention_fwd(q, k, v, causal=causal, block_q=bq,
                                  block_k=bk, interpret=True)
        want = ref.attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)

    def test_bf16(self):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (1, 2, 128, 64)).astype(jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, 2, 128, 64)).astype(jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, 2, 128, 64)).astype(jnp.bfloat16)
        out = flash_attention_fwd(q, k, v, interpret=True)
        want = ref.attention_ref(q, k, v)
        np.testing.assert_allclose(out.astype(np.float32),
                                   want.astype(np.float32), atol=3e-2)

    def test_blockwise_jnp_oracle_matches_naive(self):
        """models.common.blockwise_attention is itself verified vs naive."""
        from repro.models.common import blockwise_attention, naive_attention
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (2, 300, 4, 32), jnp.float32)
        k = jax.random.normal(ks[1], (2, 300, 2, 32), jnp.float32)
        v = jax.random.normal(ks[2], (2, 300, 2, 32), jnp.float32)
        out = blockwise_attention(q, k, v, causal=True, q_block=128,
                                  kv_block=64)
        want = naive_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


class TestSsdScan:
    @pytest.mark.parametrize("b,h,s,p,n,chunk", [
        (2, 3, 128, 16, 32, 32),
        (1, 2, 100, 8, 16, 32),     # ragged chunks
        (2, 4, 64, 32, 64, 64),
        (1, 1, 256, 64, 128, 128),  # production-like dims
    ])
    def test_matches_recurrence(self, b, h, s, p, n, chunk):
        ks = jax.random.split(KEY, 5)
        x = jax.random.normal(ks[0], (b, h, s, p), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, h, s)))
        a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
        bm = jax.random.normal(ks[3], (b, h, s, n), jnp.float32)
        cm = jax.random.normal(ks[4], (b, h, s, n), jnp.float32)
        y, st = ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=True)
        want_y, want_st = ref.ssd_ref(x, dt, a, bm, cm)
        np.testing.assert_allclose(y, want_y, atol=5e-4, rtol=1e-3)
        np.testing.assert_allclose(st, want_st, atol=5e-4, rtol=1e-3)

    def test_chunked_jnp_oracle_matches_recurrence(self):
        """models.mamba.ssd_chunked (the model path) vs the recurrence."""
        from repro.models.mamba import ssd_chunked
        ks = jax.random.split(KEY, 5)
        b, h, s, p, n = 2, 4, 96, 16, 32
        x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
        bm = jax.random.normal(ks[3], (b, s, 1, n), jnp.float32)
        cm = jax.random.normal(ks[4], (b, s, 1, n), jnp.float32)
        y, st = ssd_chunked(x, dt, a, bm, cm, chunk=32)
        bm_h = jnp.repeat(bm, h, axis=2).transpose(0, 2, 1, 3)
        cm_h = jnp.repeat(cm, h, axis=2).transpose(0, 2, 1, 3)
        want_y, want_st = ref.ssd_ref(
            x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1), a, bm_h, cm_h)
        np.testing.assert_allclose(y.transpose(0, 2, 1, 3), want_y,
                                   atol=5e-4, rtol=1e-3)
        np.testing.assert_allclose(st, want_st, atol=5e-4, rtol=1e-3)


class TestRmsNorm:
    @pytest.mark.parametrize("shape,dtype", [
        ((4, 64), jnp.float32),
        ((3, 17, 128), jnp.float32),
        ((2, 100, 256), jnp.bfloat16),
    ])
    def test_matches(self, shape, dtype):
        x = jax.random.normal(KEY, shape).astype(dtype)
        g = jax.random.normal(KEY, shape[-1:], jnp.float32)
        out = rmsnorm(x, g, interpret=True)
        want = ref.rmsnorm_ref(x, g)
        np.testing.assert_allclose(out.astype(np.float32),
                                   want.astype(np.float32),
                                   atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


class TestEmbeddingBag:
    @pytest.mark.parametrize("t,r,e,b,n", [
        (4, 50, 16, 3, 7),
        (2, 128, 32, 8, 1),
        (8, 16, 8, 2, 16),
    ])
    def test_matches(self, t, r, e, b, n):
        tbl = jax.random.normal(KEY, (t, r, e), jnp.float32)
        idx = jax.random.randint(KEY, (b, t, n), 0, r)
        out = embedding_bag(tbl, idx, interpret=True)
        want = ref.embedding_bag_ref(tbl, idx)
        np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)


def test_ops_dispatch():
    """ops.py wrappers run the implementation the caller names."""
    from repro.kernels import ops
    q = jax.random.normal(KEY, (1, 2, 64, 32))
    out = ops.flash_attention(q, q, q, impl="ref")
    assert out.shape == q.shape
    g = jnp.ones((32,))
    assert ops.rmsnorm(q, g, impl="ref").shape == q.shape
    with pytest.raises(ValueError):
        ops.rmsnorm(q, g, impl="auto")
