"""Pallas flash attention kernel (interpret mode on CPU): parity + gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlenlp_tpu.ops.flash_attention import dot_product_attention
from paddlenlp_tpu.ops.pallas import flash_attention as kernel_file
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


def packed_segments(B, T, seed=0):
    """Three documents a row, cut at seeded places (not at tile edges)."""
    cuts = np.sort(np.random.default_rng(seed).integers(1, T, (B, 2)), axis=1)
    pos = np.arange(T)[None]
    return jnp.asarray((pos >= cuts[:, :1]).astype(np.int32) + (pos >= cuts[:, 1:]))


# (T, query heads, kv heads, head_dim, dtype, segments, window): the tiles are the rule's own
# (block_q / block_kv not passed), so the rule and the clamped index maps are what is under
# test. 1024 x 1024 tiles at these T:
DEFAULT_TILE_CASES = {
    "one-tile-h64-gqa7": (128, 7, 1, 64, jnp.float32, False, None),
    "one-tile-h128-mha-bf16": (128, 2, 2, 128, jnp.bfloat16, False, None),
    "several-tiles-h64-gqa7": (1536, 7, 1, 64, jnp.float32, False, None),  # 1024 + 512, a partial last block
    "cell-2048-h64-gqa7-bf16": (2048, 7, 1, 64, jnp.bfloat16, False, None),
    "cell-2048-h128-mha": (2048, 2, 2, 128, jnp.float32, False, None),
    "segments-h64-gqa7": (1536, 7, 1, 64, jnp.float32, True, None),
    "segments-h128-mha-bf16": (2048, 2, 2, 128, jnp.bfloat16, True, None),
    "window-h64-gqa7": (2048, 7, 1, 64, jnp.float32, False, 300),
    "window-skips-tiles-h64": (3072, 2, 1, 64, jnp.float32, False, 300),  # the last rows' first tile lies below it
    "window-h128-mha-bf16": (2048, 2, 2, 128, jnp.bfloat16, False, 640),
    "segments-and-window": (1536, 2, 1, 64, jnp.float32, True, 200),
    "ragged-every-kernel-h128": (1152, 2, 1, 128, jnp.float32, False, None),  # 1024 + 128
    "ragged-h64-bf16": (1664, 2, 2, 64, jnp.bfloat16, False, None),
}


@pytest.mark.parametrize("case", DEFAULT_TILE_CASES)
def test_default_tiles_match_the_xla_path(case):
    """Forward and all three gradients at the tiles the rule picks, every row of
    tiles (the first, whose later steps clamp to its one block, and the last)."""
    T, N, K, H, dtype, segmented, window = DEFAULT_TILE_CASES[case]
    q, k, v = qkv(B=1, T=T, N=N, K=K, H=H, seed=T + N, dtype=dtype)
    w = qkv(B=1, T=T, N=N, K=K, H=H, seed=1)[0]  # float32 weights of the scalar that is differentiated
    seg = packed_segments(1, T) if segmented else None

    def f_pallas(q, k, v):
        out = flash_attention(q, k, v, seg, None, True, window, interpret=True)
        return (out.astype(jnp.float32) * w).sum(), out

    def f_ref(q, k, v):
        out = dot_product_attention(q, k, v, causal=True, segment_ids=seg, window=window, use_pallas=False)
        return (out.astype(jnp.float32) * w).sum(), out

    (_, out), grads = jax.value_and_grad(f_pallas, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, ref), ref_grads = jax.value_and_grad(f_ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    f32 = lambda a: np.asarray(a, dtype=np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(f32(out), f32(ref), atol=2e-5)
        for name, a, b in zip("qkv", grads, ref_grads):
            np.testing.assert_allclose(f32(a), f32(b), atol=5e-4, rtol=1e-3, err_msg=f"d{name}")
    else:  # both sides round to bf16, in different places: compare in the norm
        rel = lambda a, b: np.linalg.norm(f32(a) - f32(b)) / np.linalg.norm(f32(b))
        np.testing.assert_allclose(f32(out), f32(ref), atol=3e-2)
        for name, a, b in zip("qkv", grads, ref_grads):
            assert np.isfinite(f32(a)).all() and rel(a, b) < 1.5e-2, f"d{name}: {rel(a, b)}"


def test_non_causal_default_tiles():
    """Nothing to clamp and no diagonal: the mask is the ragged kv end alone."""
    q, k, v = qkv(B=1, T=1536, N=2, K=1)
    ref = dot_product_attention(q, k, v, causal=False, use_pallas=False)
    out = flash_attention(q, k, v, causal=False, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("block_q,block_kv,window", [
    (128, 128, None), (512, 1024, None), (1024, 1024, None), (384, 256, None),
    (128, 128, 100), (512, 1024, 300), (1024, 1024, 640), (256, 384, 1), (512, 512, 5000),
])
def test_tile_spans_against_the_mask(block_q, block_kv, window):
    """``_kv_blocks`` / ``_q_blocks`` (which steps run, and what a skipped step
    names) against the element mask itself, for every tile of a ragged sequence."""
    T = 2048 + 128
    rows, cols = np.arange(T)[:, None], np.arange(T)[None, :]
    visible = cols <= rows
    if window is not None:
        visible &= cols > rows - window
    n_q, n_k = -(-T // block_q), -(-T // block_kv)
    for qi in range(n_q):
        lo, hi = (int(x) for x in kernel_file._kv_blocks(qi, block_q, block_kv, n_k, True, window))
        for ki in range(n_k):
            tile = visible[qi * block_q:(qi + 1) * block_q, ki * block_kv:(ki + 1) * block_kv]
            assert (lo <= ki <= hi) == bool(tile.any()), (qi, ki)
            q_lo, q_hi = (int(x) for x in kernel_file._q_blocks(ki, block_q, block_kv, n_q, True, window))
            assert (q_lo <= qi <= q_hi) == bool(tile.any()), (qi, ki)


REMAT_CASES = {  # query heads, kv heads, packed segments
    "mha": (2, 2, False),
    "gqa": (4, 2, False),
    "gqa-segments": (4, 1, True),
}


@pytest.mark.parametrize("case", REMAT_CASES)
def test_saved_residuals_are_the_recomputed_ones(case):
    """Under ``jax.checkpoint`` with a policy that saves the two names the
    forward rule gives (``flash_out``, ``flash_lse``) the backward reads what the
    forward left; with no policy it runs the forward kernel again. Both give,
    bit for bit, the gradients of the call without remat, and the jaxpr says
    which of the two happened."""
    N, K, segmented = REMAT_CASES[case]
    q, k, v = qkv(B=1, T=256, N=N, K=K, H=64, seed=7)
    w = qkv(B=1, T=256, N=N, K=K, H=64, seed=1)[0]
    seg = packed_segments(1, 256) if segmented else None

    def f(q, k, v):
        return (flash_attention(q, k, v, seg, None, True, None, 128, 128, True) * w).sum()

    saving = jax.checkpoint_policies.save_only_these_names("flash_out", "flash_lse")
    grad = lambda fn: jax.grad(fn, argnums=(0, 1, 2))
    plain = grad(f)(q, k, v)
    for policy, forward_kernels in ((saving, 1), (None, 2)):
        remat = grad(jax.checkpoint(f, policy=policy))
        for name, a, b in zip("qkv", remat(q, k, v), plain):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=f"d{name}")
        assert str(jax.make_jaxpr(remat)(q, k, v)).count("name=flash_attention_fwd") == forward_kernels


# ---------------------------------------------------------------- a value head of its own width
def _qkv_two_widths(B, T, N, K, H, Hv, seed=0):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return draw(B, T, N, H), draw(B, T, K, H), draw(B, T, K, Hv), draw(B, T, N, Hv)


@pytest.mark.parametrize("case", ["192-128", "192-128-segments", "64-128-gqa"])
def test_value_head_of_its_own_width_forward_and_all_three_gradients(case):
    """Latent attention's head sizes (query/key 192, value 128: two tiles of 128 a side) and a value head wider
    than the key's under GQA, interpret mode, against the plain attention: the output and dQ, dK (key width), dV
    (value width)."""
    from paddlenlp_tpu.ops.flash_attention import _math_attention, make_causal_mask, make_segment_mask

    H, Hv = (64, 128) if case.startswith("64") else (192, 128)
    N, K = (4, 2) if case.endswith("gqa") else (2, 2)
    B, T = 1, 256
    q, k, v, probe = _qkv_two_widths(B, T, N, K, H, Hv)
    seg = packed_segments(B, T) if case.endswith("segments") else None
    mask = make_causal_mask(T, T)
    if seg is not None:
        mask = jnp.logical_and(mask, make_segment_mask(seg, seg))

    def kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, seg, H ** -0.5, True, None, 128, 128, True) * probe)

    def plain(q, k, v):
        return jnp.sum(_math_attention(q, k, v, mask, H ** -0.5) * probe)

    (a, ga), (b, gb) = jax.value_and_grad(kernel, (0, 1, 2))(q, k, v), jax.value_and_grad(plain, (0, 1, 2))(q, k, v)
    assert flash_attention(q, k, v, seg, H ** -0.5, True, None, 128, 128, True).shape == (B, T, N, Hv)
    assert abs(float(a) - float(b)) < 1e-3
    assert [g.shape for g in ga] == [q.shape, k.shape, v.shape]
    for got, want in zip(ga, gb):
        assert float(jnp.abs(got - want).max()) < 2e-5


def test_the_dispatcher_takes_a_value_head_of_its_own_width_on_both_paths():
    """With the kernel (forced: interpret mode off the chip) and through the XLA path, which pads and slices."""
    q, k, v, _ = _qkv_two_widths(1, 128, 2, 2, 192, 128, seed=3)
    with_kernel = dot_product_attention(q, k, v, causal=True, scale=192 ** -0.5, use_pallas=True)
    through_xla = dot_product_attention(q, k, v, causal=True, scale=192 ** -0.5, use_pallas=False)
    assert with_kernel.shape == through_xla.shape == (1, 128, 2, 128)
    assert float(jnp.abs(with_kernel - through_xla).max()) < 2e-5
    wide = dot_product_attention(v, v, q, causal=True, use_pallas=False)  # a value head wider than the key's
    assert wide.shape == (1, 128, 2, 192)


def test_at_equal_head_sizes_the_kernel_calls_are_what_they_were():
    """The forward and both backward calls at one head size, as a jaxpr with source positions cut out, against the
    digest of the same text made on the commit before the kernels took a second width (PR 39's tree:
    ``python3 -c`` of this test's body there gives the digest; it changes only when the calls do)."""
    import hashlib
    import re

    q = jnp.zeros((2, 256, 4, 64), jnp.bfloat16)
    kv = jnp.zeros((2, 256, 2, 64), jnp.bfloat16)
    loss = lambda q, k, v: flash_attention(q, k, v, None, None, True, None, 128, 128, True).astype(jnp.float32).sum()
    text = re.sub(r" at [^\s\]]+:\d+", "", str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv)))
    assert hashlib.sha256(text.encode()).hexdigest() == "8251a34c54ac40e19280c70f32106e9b18917da15e276209348419a37ea0f68e"
