"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.embedding_bag import embedding_bag
from repro.kernels.flash_attention import (Blocks, flash_attention,
                                          flash_attention_fwd)
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan

KEY = jax.random.PRNGKey(42)


FLASH_CASES = [
    (2, 4, 2, 256, 64, True, 128, 128),
    (1, 8, 8, 130, 32, True, 64, 64),        # ragged seq
    (2, 2, 1, 64, 128, False, 32, 32),       # MQA, non-causal
    (1, 4, 4, 100, 64, True, 64, 32),        # uneven blocks
    (1, 6, 2, 96, 16, True, 32, 32),         # GQA group=3
]


def _qkv(b, h, hkv, s, d, dtype=jnp.float32):
    ks = jax.random.split(KEY, 4)
    return (jax.random.normal(ks[0], (b, h, s, d), jnp.float32).astype(dtype),
            jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32).astype(dtype),
            jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32).astype(dtype),
            jax.random.normal(ks[3], (b, h, s, d), jnp.float32).astype(dtype))


class TestFlashAttention:
    @pytest.mark.parametrize("b,h,hkv,s,d,causal,bq,bk", FLASH_CASES)
    def test_matches_reference(self, b, h, hkv, s, d, causal, bq, bk):
        """Output, and the logsumexp of each query row that the backward
        reads, against the reference's scores."""
        q, k, v, _ = _qkv(b, h, hkv, s, d)
        out, lse = flash_attention_fwd(q, k, v, causal=causal, block_q=bq,
                                       block_k=bk, interpret=True)
        want = ref.attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q,
                            jnp.repeat(k, h // hkv, axis=1)) / np.sqrt(d)
        if causal:
            scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores,
                               -jnp.inf)
        np.testing.assert_allclose(lse, jax.nn.logsumexp(scores, axis=-1),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("b,h,hkv,s,d,causal,bq,bk", FLASH_CASES)
    def test_gradients_match_reference(self, b, h, hkv, s, d, causal, bq,
                                       bk):
        """The custom VJP's (dq, dk, dv) against autodiff of the
        reference."""
        q, k, v, dout = _qkv(b, h, hkv, s, d)
        blocks = Blocks(bq, bk, bq, bk, bq, bk)
        out, back = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal, blocks, True), q, k, v)
        want, want_back = jax.vjp(lambda q, k, v: ref.attention_ref(
            q, k, v, causal=causal), q, k, v)
        np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
        for got, exp in zip(back(dout), want_back(dout)):
            np.testing.assert_allclose(got, exp, atol=2e-5, rtol=2e-5)

    def test_bf16(self):
        q, k, v, _ = _qkv(1, 2, 2, 128, 64, jnp.bfloat16)
        out = flash_attention(q, k, v, interpret=True)
        want = ref.attention_ref(q, k, v)
        np.testing.assert_allclose(out.astype(np.float32),
                                   want.astype(np.float32), atol=3e-2)

    def test_bf16_gradients(self):
        """bf16 operands: the kernel's gradients are as close to the
        float32 reference's as autodiff of the bf16 reference is."""
        q, k, v, dout = _qkv(1, 4, 2, 128, 64, jnp.bfloat16)
        f32 = [x.astype(jnp.float32) for x in (q, k, v, dout)]
        _, back32 = jax.vjp(ref.attention_ref, *f32[:3])
        want = back32(f32[3])
        _, back = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, True, Blocks(64, 64, 64, 64, 64, 64), True), q, k, v)
        _, back16 = jax.vjp(ref.attention_ref, q, k, v)
        for got, bf, exp in zip(back(dout), back16(dout), want):
            err = jnp.linalg.norm(got.astype(jnp.float32) - exp)
            err16 = jnp.linalg.norm(bf.astype(jnp.float32) - exp)
            assert got.dtype == jnp.bfloat16
            assert err <= 1.5 * err16 + 1e-6, (float(err), float(err16))

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

    def test_model_attention_off_tpu_is_xla(self):
        """Off a TPU, models.common.attention is naive_attention, forward
        and gradient, bit for bit."""
        from repro.models.common import attention, naive_attention
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (2, 64, 4, 64), jnp.float32)
        k = jax.random.normal(ks[1], (2, 64, 2, 64), jnp.float32)
        v = jax.random.normal(ks[2], (2, 64, 2, 64), jnp.float32)

        def loss(f):
            return jax.jit(jax.value_and_grad(
                lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=(0, 1, 2)))

        (got, got_g), (want, want_g) = (loss(attention)(q, k, v),
                                        loss(naive_attention)(q, k, v))
        np.testing.assert_array_equal(got, want)
        for a, b in zip(got_g, want_g):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("sq,skv,head_dim,q_offset,mesh,kernel", [
        (8, 8, 64, 0, None, True),
        (8, 8, 128, 0, None, True),
        (8, 8, 160, 0, None, False),        # a head dim the kernel skips
        (8, 16, 64, 0, None, False),        # cross-attention
        (8, 8, 64, 4, None, False),         # offset queries
        (8, 8, 64, 0, (2, 2), False),       # a batch of 1 over 2 data shards
    ])
    def test_model_attention_stages_the_kernel(self, sq, skv, head_dim,
                                               q_offset, mesh, kernel):
        """Where models.common.attention stages the kernel (for a TPU to
        lower): self-attention at the head dims it tiles, on one device or
        per shard of a mesh that its shapes divide."""
        from jax.sharding import AbstractMesh, AxisType

        from repro.models.common import attention
        q = jnp.zeros((1, sq, 4, head_dim))
        kv = jnp.zeros((1, skv, 2, head_dim))

        def staged():
            return str(jax.make_jaxpr(lambda q, k, v: attention(
                q, k, v, q_offset=q_offset))(q, kv, kv))

        if mesh is None:
            text = staged()
        else:
            with jax.sharding.use_abstract_mesh(AbstractMesh(
                    mesh, ("data", "model"),
                    axis_types=(AxisType.Auto,) * 2)):
                text = staged()
        assert ("pallas_call" in text) == kernel

    @pytest.mark.parametrize("shape,names,b,h,hkv,kind,spec", [
        ((2, 2), ("data", "model"), 4, 12, 4, "TPU v5 lite",
         ("data", None, "model", None)),
        ((2, 2), ("data", "model"), 4, 12, 4, None,       # kind unknown
         ("data", None, "model", None)),
        ((2, 2), ("data", "model"), 4, 12, 4, "cpu", None),
        ((2, 2), ("data", "model"), 3, 12, 4, "TPU v5 lite", None),
        ((2, 2), ("data", "model"), 4, 12, 3, "TPU v5 lite", None),
        ((2, 2, 2), ("pod", "data", "model"), 4, 12, 4, "TPU v5 lite",
         (("pod", "data"), None, "model", None)),
        ((4,), ("data",), 4, 9, 3, "TPU v5 lite", ("data", None, None, None)),
        ((4,), ("pipe",), 4, 12, 4, "TPU v5 lite", None),
    ])
    def test_model_attention_per_shard_spec(self, shape, names, b, h, hkv,
                                            kind, spec):
        """Under a mesh, models.common.attention stages the kernel inside
        ``shard_map`` exactly where a per-shard spec exists: TPU devices (or
        an unnamed kind), no axis but the data and model axes spanning
        devices, and a batch and head counts that divide them."""
        from jax._src.mesh import AbstractDevice
        from jax.sharding import AbstractMesh, AxisType, PartitionSpec as P

        from repro.models.common import _shard_spec, attention
        mesh = AbstractMesh(shape, names, axis_types=(AxisType.Auto,) *
                            len(names), abstract_device=kind and
                            AbstractDevice(kind, None))
        q = jnp.zeros((b, 128, h, 64), jnp.bfloat16)
        kv = jnp.zeros((b, 128, hkv, 64), jnp.bfloat16)
        assert _shard_spec(mesh, q, kv) == (spec and P(*spec))
        with jax.sharding.use_abstract_mesh(mesh):
            text = str(jax.make_jaxpr(attention)(q, kv, kv))
        assert ("pallas_call" in text) == ("shard_map" in text) == bool(spec)

    def test_model_attention_inside_shard_map_is_xla(self):
        """Where the mesh's axes are already manual (inside a
        ``shard_map``, as in the GPipe pipeline), attention keeps XLA's
        path."""
        from jax.sharding import AbstractMesh, AxisType, PartitionSpec as P

        from repro.models.common import attention
        mesh = AbstractMesh((2, 2), ("data", "model"),
                            axis_types=(AxisType.Auto,) * 2)
        q = jnp.zeros((4, 128, 12, 64), jnp.bfloat16)
        kv = jnp.zeros((4, 128, 4, 64), jnp.bfloat16)
        body = jax.shard_map(lambda q, k, v: attention(q, k, v), mesh=mesh,
                             in_specs=P("data"), out_specs=P("data"))
        with jax.sharding.use_abstract_mesh(mesh):
            text = str(jax.make_jaxpr(body)(q, kv, kv))
        assert "pallas_call" not in text


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
