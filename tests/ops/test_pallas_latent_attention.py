"""The chunk form of full-layer latent attention as one Pallas kernel (interpret
mode on the CPU) against a plain float32 softmax under the selection mask."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlenlp_tpu.ops.pallas import latent_attention as kernel_file
from paddlenlp_tpu.ops.pallas.latent_attention import latent_chunk_attention

# the full layers' sizes cut down 8 times: 128 heads of 128 + 64 over a 512 + 64 latent, values 128 wide
SIZES = dict(heads=4, nope=16, rope=8, v=16, kv_lora=64)
TILE = 8


def plain(q_nope, q_pe, rows, w_k, w_v, keep, scale):
    """Every key expanded at once, float32 at ``highest`` precision, one softmax a query and head."""
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    q_nope, q_pe, rows, w_k, w_v = f32(q_nope), f32(q_pe), f32(rows), f32(w_k), f32(w_v)
    kv_lora = w_k.shape[0]
    hi = jax.lax.Precision.HIGHEST
    c_kv, k_pe = rows[..., :kv_lora], rows[..., kv_lora:]
    k_nope = jnp.einsum("bsc,chn->bshn", c_kv, w_k, precision=hi)
    sc = (jnp.einsum("bthn,bshn->bhts", q_nope, k_nope, precision=hi)
          + jnp.einsum("bthr,bsr->bhts", q_pe, k_pe, precision=hi)) * scale
    p = jax.nn.softmax(jnp.where(keep[:, None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bshv->bthv", p, jnp.einsum("bsc,chv->bshv", c_kv, w_v, precision=hi), precision=hi)


def inputs(seed, b, t, tiles, dtype=jnp.float32, **sizes):
    d = dict(SIZES, **sizes)
    rng = np.random.default_rng(seed)
    normal = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    s = tiles * TILE
    q = (jnp.asarray(normal(b, t, d["heads"], d["nope"]), dtype), jnp.asarray(normal(b, t, d["heads"], d["rope"]), dtype))
    rows = jnp.asarray(normal(b, s, d["kv_lora"] + d["rope"]), dtype)
    w_k = jnp.asarray(normal(d["kv_lora"], d["heads"], d["nope"]) * d["kv_lora"] ** -0.5, dtype)
    w_v = jnp.asarray(normal(d["kv_lora"], d["heads"], d["v"]) * d["kv_lora"] ** -0.5, dtype)
    return q, rows, w_k, w_v, (d["nope"] + d["rope"]) ** -0.5, rng


def selection(rng, b, t, s, first, share=0.5):
    """A chunk whose first query sits at position ``first``: a query sees the
    positions up to its own and keeps ``share`` of them at random, its own among them."""
    pos = first + np.arange(t)
    kpos = np.arange(s)
    keep = (kpos[None, None, :] <= pos[None, :, None]) & (rng.random((b, t, s)) < share)
    keep |= kpos[None, None, :] == pos[None, :, None]
    return keep, int(pos[-1]) // TILE + 1


CASES = {
    # name: (rows, chunk, tiles of the table, first position, sizes, head block)
    "one-row-whole-table": (1, 8, 3, 16, {}, 4),
    "ends-inside-a-key-tile": (1, 8, 4, 15, {}, 4),  # last position 22: three tiles, the third to its seventh row
    "values-wider-than-nope": (1, 8, 3, 16, dict(v=24), 4),
    "two-head-blocks": (1, 8, 3, 16, {}, 2),
    "head-block-that-does-not-divide": (1, 8, 3, 16, dict(heads=6), 4),  # steps of 3 heads
    "two-rows": (2, 8, 3, 16, {}, 4),
    "chunk-of-16-over-four-tiles": (1, 16, 5, 24, {}, 2),
}


@pytest.mark.parametrize("name", CASES)
def test_kernel_equals_the_plain_softmax_under_the_mask(name):
    b, t, tiles, first, sizes, block = CASES[name]
    q, rows, w_k, w_v, scale, rng = inputs(1, b, t, tiles, **sizes)
    keep, n_tiles = selection(rng, b, t, tiles * TILE, first)
    got = latent_chunk_attention(*q, rows, w_k, w_v, jnp.asarray(keep), n_tiles, scale=scale, tile=TILE,
                                 head_block=block)
    assert got.shape == q[0].shape[:3] + (w_v.shape[-1],)
    want = plain(*q, rows, w_k, w_v, jnp.asarray(keep), scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_rows_past_the_tiles_visited_are_never_read():
    """``n_tiles`` smaller than the table allows, every row past it poisoned:
    the result is finite and the one the whole table gives."""
    q, rows, w_k, w_v, scale, rng = inputs(2, 1, 8, 6)
    keep, n_tiles = selection(rng, 1, 8, 6 * TILE, 9)
    assert n_tiles == 3
    call = lambda r, n: np.asarray(latent_chunk_attention(*q, r, w_k, w_v, jnp.asarray(keep), n, scale=scale,
                                                         tile=TILE, head_block=2))
    got = call(rows.at[:, n_tiles * TILE:].set(jnp.nan), jnp.int32(n_tiles))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, call(rows, 6))
    np.testing.assert_allclose(got, np.asarray(plain(*q, rows, w_k, w_v, jnp.asarray(keep), scale)), atol=2e-6)


def test_a_query_that_keeps_nothing_in_its_first_tiles():
    """What it summed at weight 1 before its first kept position fades to nothing."""
    q, rows, w_k, w_v, scale, rng = inputs(3, 1, 8, 4)
    keep, n_tiles = selection(rng, 1, 8, 4 * TILE, 24)
    keep[0, 2, : 2 * TILE] = False  # nothing in tiles 0 and 1
    keep[0, 5, : 3 * TILE] = False  # nothing before the tile of its own position
    got = latent_chunk_attention(*q, rows, w_k, w_v, jnp.asarray(keep), n_tiles, scale=scale, tile=TILE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain(*q, rows, w_k, w_v, jnp.asarray(keep), scale)),
                               atol=2e-6)


def test_a_padded_query_row_is_finite_and_moves_no_other():
    """A row past the chunk's valid tokens keeps nothing anywhere: finite rubbish for the caller to drop."""
    q, rows, w_k, w_v, scale, rng = inputs(4, 2, 8, 3)
    keep, n_tiles = selection(rng, 2, 8, 3 * TILE, 16)
    keep[1, 5:] = False  # the second row feeds five tokens
    got = np.asarray(latent_chunk_attention(*q, rows, w_k, w_v, jnp.asarray(keep), n_tiles, scale=scale, tile=TILE))
    assert np.isfinite(got).all()
    want = np.asarray(plain(*q, rows, w_k, w_v, jnp.asarray(keep), scale))
    live = keep.any(-1)
    np.testing.assert_allclose(got[live], want[live], atol=2e-6)


def test_bfloat16_inputs_keep_float32_statistics():
    """Products of bfloat16 inputs accumulated in float32, the softmax in float32:
    against the plain float32 result of the same (rounded) inputs."""
    q, rows, w_k, w_v, scale, rng = inputs(5, 1, 16, 4, dtype=jnp.bfloat16)
    keep, n_tiles = selection(rng, 1, 16, 4 * TILE, 16)
    got = latent_chunk_attention(*q, rows, w_k, w_v, jnp.asarray(keep), n_tiles, scale=scale, tile=TILE)
    assert got.dtype == jnp.bfloat16
    want = plain(*q, rows, w_k, w_v, jnp.asarray(keep), scale)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=3e-2)


def test_shapes_that_do_not_belong_together_are_refused():
    q, rows, w_k, w_v, scale, rng = inputs(6, 1, 8, 3)
    keep = jnp.ones((1, 8, 3 * TILE), bool)
    with pytest.raises(ValueError, match="latent_chunk_attention"):
        latent_chunk_attention(*q, rows[:, :-1], w_k, w_v, keep[..., :-1], 1, scale=scale, tile=TILE)  # S not tiles
    with pytest.raises(ValueError, match="latent_chunk_attention"):
        latent_chunk_attention(*q, rows[..., :-1], w_k, w_v, keep, 1, scale=scale, tile=TILE)  # no room for k_pe


def test_head_block_and_vmem_request():
    assert [kernel_file._head_block(h, 4) for h in (128, 64, 6, 3, 1)] == [4, 4, 3, 3, 1]
    # the full layers' step at the default head block: what the chip's compiler was found to need, with room
    need = kernel_file.vmem_bytes(1024, 512, kernel_file.HEAD_BLOCK, 512, 128, 64, 128, 2)
    assert 28 << 20 < need <= 64 << 20
