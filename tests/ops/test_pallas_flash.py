"""Pallas flash attention kernel (interpret mode on CPU): parity + gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlenlp_tpu.ops.flash_attention import dot_product_attention
from paddlenlp_tpu.ops.pallas.flash_attention import flash_attention


def qkv(B=2, T=128, N=4, K=2, H=64, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((B, T, N, H)), dtype),
            jnp.asarray(rng.standard_normal((B, T, K, H)), dtype),
            jnp.asarray(rng.standard_normal((B, T, K, H)), dtype))


class TestPallasFlash:
    def test_causal_parity(self):
        q, k, v = qkv()
        ref = dot_product_attention(q, k, v, causal=True, use_pallas=False)
        out = flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_non_causal_parity(self):
        q, k, v = qkv(T=256)
        ref = dot_product_attention(q, k, v, causal=False, use_pallas=False)
        out = flash_attention(q, k, v, causal=False, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_gqa_no_repeat(self):
        q, k, v = qkv(N=8, K=2)
        ref = dot_product_attention(q, k, v, causal=True, use_pallas=False)
        out = flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_multi_kv_blocks(self):
        """T > block sizes: the online-softmax accumulation across kv blocks."""
        q, k, v = qkv(B=1, T=512)
        ref = dot_product_attention(q, k, v, causal=True, use_pallas=False)
        out = flash_attention(q, k, v, block_q=128, block_kv=128, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_bf16(self):
        q, k, v = qkv(dtype=jnp.bfloat16)
        ref = dot_product_attention(q, k, v, causal=True, use_pallas=False)
        out = flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(np.asarray(out, dtype=np.float32), np.asarray(ref, dtype=np.float32),
                                   atol=3e-2)

    def test_gradients_match_math_path(self):
        q, k, v = qkv(B=1, T=128, N=2, K=2, H=64)

        def f_pallas(q, k, v):
            return flash_attention(q, k, v, interpret=True).sum()

        def f_ref(q, k, v):
            return dot_product_attention(q, k, v, causal=True, use_pallas=False).astype(jnp.float32).sum()

        gp = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)

    def test_dispatcher_forced(self):
        """use_pallas=True routes through the kernel (interpret off-TPU) and matches."""
        q, k, v = qkv()
        ref = dot_product_attention(q, k, v, causal=True, use_pallas=False)
        out = dot_product_attention(q, k, v, causal=True, use_pallas=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_ragged_kv_length_masked(self):
        """S not a multiple of block_kv: padding columns must not leak into softmax."""
        q, k, v = qkv(B=1, T=160, N=2, K=2)  # 160 = 128 + 32
        ref = dot_product_attention(q, k, v, causal=False, use_pallas=False)
        out = flash_attention(q, k, v, causal=False, block_kv=128, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_segment_ids_parity(self):
        """Packed-batch (ZeroPadding/flashmask) masking inside the kernel."""
        q, k, v = qkv(B=2, T=128)
        seg = jnp.asarray(np.repeat([[0, 1, 2, 3]], 2, axis=0).repeat(32, axis=1))  # 4 segments of 32
        ref = dot_product_attention(q, k, v, causal=True, segment_ids=seg, use_pallas=False)
        out = flash_attention(q, k, v, segment_ids=seg, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_sliding_window_parity(self):
        q, k, v = qkv(B=1, T=256)
        ref = dot_product_attention(q, k, v, causal=True, window=64, use_pallas=False)
        out = flash_attention(q, k, v, window=64, block_q=64, block_kv=64, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_gradients_gqa_segments(self):
        """Pallas bwd kernels: GQA group-sum + segment masking, vs math-path grads."""
        q, k, v = qkv(B=1, T=128, N=4, K=2, H=64, seed=3)
        seg = jnp.asarray(np.repeat([[0, 1]], 1, axis=0).repeat(64, axis=1))

        def f_pallas(q, k, v):
            return (flash_attention(q, k, v, segment_ids=seg, interpret=True) ** 2).sum()

        def f_ref(q, k, v):
            return (dot_product_attention(q, k, v, causal=True, segment_ids=seg,
                                          use_pallas=False).astype(jnp.float32) ** 2).sum()

        gp = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=1e-3)

    def test_gradients_window(self):
        q, k, v = qkv(B=1, T=128, N=2, K=2, H=64, seed=5)

        def f_pallas(q, k, v):
            return flash_attention(q, k, v, window=32, interpret=True).sum()

        def f_ref(q, k, v):
            return dot_product_attention(q, k, v, causal=True, window=32,
                                         use_pallas=False).astype(jnp.float32).sum()

        gp = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=1e-3)

    def test_sharded_dispatch_parity(self, eight_devices):
        """use_pallas under a dp x tp mesh: the shard_map wrapper must reproduce
        the unsharded kernel output (values AND grads)."""
        from paddlenlp_tpu.parallel import MeshConfig, create_mesh, use_mesh

        q, k, v = qkv(B=2, T=128, N=4, K=4)
        ref = dot_product_attention(q, k, v, causal=True, use_pallas=False)
        mesh = create_mesh(MeshConfig(dp=2, tp=4))
        with use_mesh(mesh):
            out = jax.jit(lambda q, k, v: dot_product_attention(q, k, v, causal=True, use_pallas=True))(q, k, v)
            g = jax.jit(jax.grad(lambda q, k, v: dot_product_attention(
                q, k, v, causal=True, use_pallas=True).astype(jnp.float32).sum(), argnums=0))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
        g_ref = jax.grad(lambda q, k, v: dot_product_attention(
            q, k, v, causal=True, use_pallas=False).astype(jnp.float32).sum(), argnums=0)(q, k, v)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=5e-4, rtol=1e-3)

    def test_kernel_failure_is_not_swallowed(self, monkeypatch):
        """A failure of the Pallas kernel raises out of the dispatcher; it no
        longer gives way to the XLA path behind a warning."""
        from paddlenlp_tpu.ops.pallas import flash_attention as kernel_module

        def broken(*args, **kwargs):
            raise RuntimeError("mosaic said no")

        monkeypatch.setattr(kernel_module, "flash_attention", broken)
        q, k, v = qkv()
        with pytest.raises(RuntimeError, match="mosaic said no"):
            dot_product_attention(q, k, v, causal=True, use_pallas=True)

    def test_untileable_shape_is_turned_away_in_the_open(self, monkeypatch):
        """On a TPU a shape Mosaic cannot tile goes to the XLA path by the
        explicit gate, and says so once, with the shape."""
        from paddlenlp_tpu.utils.log import logger

        said = []
        monkeypatch.setattr(logger, "warning_once", said.append)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        q, k, v = qkv(T=72)  # not a multiple of 128
        out = dot_product_attention(q, k, v, causal=True)  # default: pallas on a TPU
        ref = dot_product_attention(q, k, v, causal=True, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        assert len(said) == 1 and "(2, 72, 4, 64)" in said[0] and "T % 128" in said[0]

    def test_causal_cross_length_rejected(self):
        q, _, _ = qkv(T=64)
        _, k, v = qkv(T=128)
        with pytest.raises(ValueError, match="requires T == S"):
            flash_attention(q, k, v, causal=True, interpret=True)
