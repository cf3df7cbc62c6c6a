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


def case(T, N, K, H, dtype=jnp.float32, Hv=None, cuts=None, window=None, blocks=(None, None), strip=None):
    """``cuts``: "seeded" (three documents a row, cut at seeded places) or the two places themselves;
    ``strip``: the height of the strips the kernels must walk a crossed tile in, None for the whole-tile body."""
    return dict(T=T, N=N, K=K, H=H, dtype=dtype, Hv=Hv or H, cuts=cuts, window=window, blocks=blocks, strip=strip)


# The tiles are the rule's own (no block argument but in the last two), so the rule, the clamped
# index maps and the strip walk are what is under test. 1024 x 1024 tiles at these T:
DEFAULT_TILE_CASES = {
    "one-tile-h64-gqa7": case(128, 7, 1, 64),
    "one-tile-h128-mha-bf16": case(128, 2, 2, 128, jnp.bfloat16),
    "several-tiles-h64-gqa7": case(1536, 7, 1, 64, strip=256),  # 1024 + 512: the crossed last tile is partial
    "cell-2048-h64-gqa7-bf16": case(2048, 7, 1, 64, jnp.bfloat16, strip=256),
    "cell-2048-h128-mha": case(2048, 2, 2, 128, strip=256),
    "segments-h64-gqa7": case(1536, 7, 1, 64, cuts="seeded", strip=256),
    "segments-h128-mha-bf16": case(2048, 2, 2, 128, jnp.bfloat16, cuts="seeded", strip=256),
    "window-h64-gqa7": case(2048, 7, 1, 64, window=300, strip=256),  # the window cuts into both diagonal tiles
    "window-skips-tiles-h64": case(3072, 2, 1, 64, window=300, strip=256),  # the last rows' first tile lies below it
    "window-h128-mha-bf16": case(2048, 2, 2, 128, jnp.bfloat16, window=640, strip=256),
    "segments-and-window": case(1536, 2, 1, 64, cuts="seeded", window=200, strip=256),
    "ragged-every-kernel-h128": case(1152, 2, 1, 128, strip=256),  # 1024 + 128: three strips past the sequence
    "ragged-h64-bf16": case(1664, 2, 2, 64, jnp.bfloat16, strip=256),  # the sequence ends inside a strip
    "ragged-h192-v128-segments-blocks-of-512": case(1280, 2, 2, 192, Hv=128, cuts=(300, 1100), blocks=(512, 512),
                                                    strip=256),  # 3 x 3 tiles, the last a strip long
    # the strip walk: one diagonal tile and 2 x 2 tiles, the head sizes of both training cells and 128
    "strips-one-tile-h64-gqa7": case(1024, 7, 1, 64, strip=256),
    "strips-one-tile-h64-mha-bf16": case(1024, 2, 2, 64, jnp.bfloat16, strip=256),
    "strips-one-tile-h128-mha": case(1024, 2, 2, 128, strip=256),
    "strips-one-tile-h192-v128": case(1024, 2, 2, 192, Hv=128, strip=256),
    "strips-2x2-h192-v128-bf16": case(2048, 2, 2, 192, jnp.bfloat16, Hv=128, strip=256),
    "strips-2x2-h64-mha": case(2048, 2, 2, 64, strip=256),
    "strips-segments-cut-inside-strips": case(2048, 2, 1, 64, cuts=(300, 1500), strip=256),
    "strips-segments-cut-on-strip-edges": case(2048, 2, 1, 64, cuts=(256, 1280), strip=256),
    "strips-segments-h192-v128-bf16": case(1024, 2, 2, 192, jnp.bfloat16, Hv=128, cuts=(512, 700), strip=256),
    "strips-window-inside-a-strip-h192-v128": case(1024, 2, 2, 192, Hv=128, window=100, strip=256),
    "strips-segments-and-window": case(2048, 2, 1, 64, cuts=(256, 1100), window=400, strip=256),
    "strips-of-128-a-tile-of-384": case(384, 2, 1, 64, strip=128),
    "strips-of-128-callers-blocks-of-256": case(512, 2, 2, 128, jnp.bfloat16, blocks=(256, 256), strip=128),
    "unequal-blocks-take-the-whole-tile-body": case(2048, 2, 1, 64, blocks=(512, 1024)),
    "unequal-blocks-segments-bf16": case(2048, 2, 2, 64, jnp.bfloat16, cuts=(300, 1500), blocks=(1024, 512)),
}


@pytest.mark.parametrize("name", DEFAULT_TILE_CASES)
def test_default_tiles_match_the_xla_path(name):
    """Forward and all three gradients at the tiles the rule picks, every row of
    tiles (the first, whose later steps clamp to its one block, and the last), with
    the body the case names: strips in the tiles the diagonal crosses, or the whole tile."""
    c = DEFAULT_TILE_CASES[name]
    T, N, K, H, Hv, dtype, window, blocks = (c[key] for key in ("T", "N", "K", "H", "Hv", "dtype", "window", "blocks"))
    assert kernel_file._strip(*kernel_file._blocks(*blocks, T, T), True) == c["strip"]
    q, k, _ = qkv(B=1, T=T, N=N, K=K, H=H, seed=T + N, dtype=dtype)
    v = qkv(B=1, T=T, N=N, K=K, H=Hv, seed=T + N + 1, dtype=dtype)[2]
    w = qkv(B=1, T=T, N=N, K=K, H=Hv, seed=1)[0]  # float32 weights of the scalar that is differentiated
    if c["cuts"] is None or c["cuts"] == "seeded":
        seg = packed_segments(1, T) if c["cuts"] else None
    else:
        seg = jnp.asarray(sum((np.arange(T)[None] >= cut).astype(np.int32) for cut in c["cuts"]))

    def f_pallas(q, k, v):
        out = flash_attention(q, k, v, seg, None, True, window, *blocks, interpret=True)
        return (out.astype(jnp.float32) * w).sum(), out

    def f_ref(q, k, v):
        out = dot_product_attention(q, k, v, causal=True, segment_ids=seg, window=window, use_pallas=False)
        return (out.astype(jnp.float32) * w).sum(), out

    (_, out), grads = jax.value_and_grad(f_pallas, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, ref), ref_grads = jax.value_and_grad(f_ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    assert out.shape == (1, T, N, Hv) and [g.shape for g in grads] == [q.shape, k.shape, v.shape]
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


# ---------------------------------------------------------------- strips inside a tile
@pytest.mark.parametrize("block,strip,run,of", [(1024, 256, 10, 16), (1024, 128, 36, 64), (512, 256, 3, 4),
                                                (384, 128, 6, 9), (256, 128, 3, 4)])
@pytest.mark.parametrize("transposed", [False, True], ids=["q-by-kv", "kv-by-q"])
def test_strips_cover_what_is_visible_of_a_crossed_tile(block, strip, run, of, transposed):
    """Every visible element of a tile the diagonal crosses lies in a sub-block that runs, no element in two, the
    sub-blocks are ``run`` of the tile's ``of`` squares of ``strip``, and their corners lie on whole strips (sublanes
    of the operands, lanes of the rows of statistics). Of a tile below the diagonal the strips cover everything."""
    q, kv = np.arange(block)[:, None], np.arange(block)[None, :]
    visible = (kv <= q).T if transposed else kv <= q  # [kv, q] or [q, kv]
    covered = np.zeros((block, block), np.int32)
    for rows, cols in kernel_file._strips(block, strip, True, transposed):
        covered[rows, cols] += 1
        assert visible[rows, cols].any()
        assert all(edge % strip == 0 for edge in (rows.start, rows.stop, cols.start, cols.stop))
    assert covered.max() == 1 and (covered[visible] == 1).all()
    assert covered.sum() * of == run * block * block
    below = np.zeros((block, block), np.int32)
    for rows, cols in kernel_file._strips(block, strip, False, transposed):
        below[rows, cols] += 1
    assert (below == 1).all()


@pytest.mark.parametrize("T,block,strip,window,tiles", [
    (2048, 1024, None, None, 3), (2048, 1024, 256, None, 2.25), (2048, 1024, 128, None, 2.125),
    (8192, 1024, None, None, 36), (8192, 1024, 256, None, 33), (8192, 1024, 128, None, 32.5),
    (3072, 1024, 256, 300, 2 + 3 * 10 / 16),  # rows of tiles 1 and 2 each reach one tile back
    (1024, 1024, 256, None, 10 / 16), (2176, 1024, None, None, 6), (1536, 512, 256, None, 3 + 3 * 3 / 4),
])
def test_computed_share_of_the_square_against_the_mask(T, block, strip, window, tiles):
    """What a call computes (``computed_elements``, from shapes alone) in tiles' worth, and against the element
    mask: a tile is counted iff it holds a visible element, whole without strips; with them, of a crossed tile the
    sub-blocks ``_strips`` gives, each of which holds a visible element. At the rule's own tile and strip: 56.25% of
    the square at 2,048 where it was 75%, 51.6% at 8,192 where it was 56.25% (the kernel file's docstring)."""
    assert kernel_file.computed_elements(T, T, block, block, strip, True, window) == tiles * block * block
    assert kernel_file.computed_elements(T, T, block, block, None, False) == (-(-T // block) * block) ** 2  # not causal
    rows, cols = np.arange(T)[:, None], np.arange(T)[None, :]
    visible = cols <= rows
    if window is not None:
        visible &= cols > rows - window
    n = -(-T // block)
    counted = 0
    for qi in range(n):
        for ki in range(n):
            tile = visible[qi * block:(qi + 1) * block, ki * block:(ki + 1) * block]
            if not tile.any():
                continue
            if strip is None or qi != ki:
                counted += block * block
            else:
                counted += sum((r.stop - r.start) * (c.stop - c.start)
                               for r, c in kernel_file._strips(block, strip, True) if tile[r, c].any())
    assert counted == tiles * block * block
    if window is None and strip == kernel_file._strip(*kernel_file._blocks(None, None, T, T), True):
        assert {2048: 100 * tiles / 4 == 56.25, 8192: 100 * tiles / 64 == 51.5625}.get(T, True)


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation under ``jaxpr``, those inside a ``jit`` or a rule's own jaxpr too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


def _score_products(jaxpr, found):
    """Sizes of the [rows, columns] score products (``_nt``: both operands contracted over their minor axis) of
    every branch of every ``pl.when`` in a kernel's jaxpr, a list a branch."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            for branch in eqn.params["branches"]:
                sizes = []
                _collect_products(branch.jaxpr, sizes)
                if sizes:
                    found.append(sizes)
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _score_products(sub, found)
    return found


def _collect_products(jaxpr, sizes):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and eqn.params["dimension_numbers"] == (((1,), (1,)), ((), ())):
            sizes.append(int(np.prod(eqn.outvars[0].aval.shape)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _collect_products(sub, sizes)


@pytest.mark.parametrize("head_dim,value_dim", [(64, 64), (192, 128)])
def test_the_kernels_compute_what_the_count_says(head_dim, value_dim):
    """The three kernels' own jaxprs at 2 x 2 tiles: the body of a tile the diagonal crosses makes score products of
    10/16 of a tile (one a sub-block in the forward, two in dq and in dkv: QK^T and dO V^T), the body of a tile
    below it of the whole tile: in four strips in the forward, in one piece (the body it was) in dq and dkv; and
    each call's cost estimate is ``computed_elements`` times its 2 / 3 / 4 products."""
    T, N, block = 2048, 2, 1024
    q = jnp.zeros((1, T, N, head_dim), jnp.bfloat16)
    v = jnp.zeros((1, T, N, value_dim), jnp.bfloat16)
    loss = lambda q, k, v: flash_attention(q, k, v, interpret=True).astype(jnp.float32).sum()
    calls = {eqn.params["name"]: eqn.params
             for eqn in _pallas_calls(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, v).jaxpr)}
    strip = kernel_file._strip(block, block, True)
    elements = kernel_file.computed_elements(T, T, block, block, strip, True)
    assert strip == 256 and elements == 2.25 * block * block
    crossed = kernel_file.computed_elements(block, block, block, block, strip, True)
    for name, score_products, below, at_v in (("flash_attention_fwd", 1, 4, 1), ("flash_attention_bwd_dq", 2, 1, 1),
                                              ("flash_attention_bwd_dkv", 2, 1, 2)):
        bodies = sorted(_score_products(calls[name]["jaxpr"], []), key=sum)
        assert [sum(sizes) for sizes in bodies] == [score_products * crossed, score_products * block * block], name
        assert [len(sizes) for sizes in bodies] == [score_products * 4, score_products * below], name
        cost = calls[name]["cost_estimate"]
        assert cost.flops == 2 * N * elements * (score_products * head_dim + at_v * value_dim), name
        assert cost.transcendentals == N * elements


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


def _kernel_call_text(eqn):
    """A ``pallas_call`` equation (its grid, block maps and the kernel's own jaxpr) as text, with source positions
    cut out and the cost estimate, which PR 42 added for XLA's schedule round the kernel."""
    import re

    text, estimates = re.subn(r"cost_estimate=CostEstimate\([^)]*\)", "cost_estimate=None", str(eqn))
    assert estimates == 1
    return re.sub(r" at [^\s\]]+:\d+", "", text)


KERNEL_CALL_DIGESTS = {  # tokens, causal, block: the digest of the three calls
    # as on PR 40's tree: a call that is not causal, and a causal one whose tile is one strip, trace to the kernels
    # they were (``_run`` gives them the whole-tile body, whose jaxpr is what ``_tile`` made), the estimate apart
    "not-causal": (256, False, 128, "a0f3e8cb7a5c5388a84f4bbf2b83609be50ed8a8e76276bfe34a31e2f011dfe4"),
    "not-causal-default-tiles": (2048, False, None, "c2506107f6d4d22c27b0790cbd6a9a46fbd33fc82682b4d494c4f6029e94e0e1"),
    "causal-a-tile-of-one-strip": (256, True, 128, "73a65422bc72b57a3a80f0519b83b4a788858cc9c6731934b828fd98712e9aff"),
    # re-made at PR 42, which changed it on purpose: two strips of 128 in a tile of 256 (on PR 40's tree b5f4b133...)
    "causal-strips": (512, True, 256, "c561b622d99936d2440e5a5456cef1874dea6b3012badfba1f851a2cdc4d340f"),
}


@pytest.mark.parametrize("case", KERNEL_CALL_DIGESTS)
def test_at_equal_head_sizes_the_kernel_calls_are_what_they_were(case):
    """The forward and both backward kernel calls at one head size against the digest of the same text made on an
    earlier tree (the lines below, run there, give it; it changes only when a call does). Until PR 42 the digest
    was of the whole jaxpr of a causal call at blocks of 128, pinned on PR 39's tree, before the kernels took a second
    width; the calls now sit inside a ``jit`` (once a program: the kernel file), so it is taken over the calls alone
    and was made anew on PR 40's tree, where the old digest still held. A causal call whose tile holds several
    strips changed at PR 42 on purpose, and its digest is that PR's; the other three must not change."""
    import hashlib

    T, causal, block, digest = KERNEL_CALL_DIGESTS[case]
    q = jnp.zeros((2, T, 4, 64), jnp.bfloat16)
    kv = jnp.zeros((2, T, 2, 64), jnp.bfloat16)
    out = lambda q, k, v: flash_attention(q, k, v, None, None, causal, None, block, block, True)
    loss = lambda q, k, v: out(q, k, v).astype(jnp.float32).sum()
    calls = [_kernel_call_text(eqn)
             for eqn in _pallas_calls(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv).jaxpr)]
    assert len(calls) == 3
    assert hashlib.sha256("\n".join(calls).encode()).hexdigest() == digest
