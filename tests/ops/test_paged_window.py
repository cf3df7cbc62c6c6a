"""The ragged paged kernel's window call (``ops/pallas/paged_attention.py``, ``window=``) in interpret mode: against
plain attention under the window mask over a window table (blocks only at the logical blocks inside the window, the
sentinel elsewhere), at GQA groups 8 and 6; the grid sized by the window and not by the table; and the call without a
window left as it was (a window that covers every position walks the same blocks in the same order: bit for bit)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlenlp_tpu.ops.pallas.paged_attention import ragged_paged_attention

BS, H = 4, 16


def case(group, window, rng):
    """Four rows of a launch T = 8 wide: a chunk that crosses the window inside itself, a decode row still under the
    window, a decode row far past it, a dead row. The pool holds noise everywhere, so a block read that should not be
    shows; each row's window table names blocks only from ``(start - (window - 1)) // BS`` to its last fed position."""
    kv_heads, t, m, blocks = 2, 8, 12, 40
    n = kv_heads * group
    q_start = np.asarray([5, 3, 37, 0], np.int32)
    q_lens = np.asarray([8, 1, 1, 0], np.int32)
    pool = rng.standard_normal((3, 2, blocks, BS, kv_heads * H)).astype(np.float32)
    q = rng.standard_normal((4, t, n, H)).astype(np.float32)
    free = list(rng.permutation(np.arange(1, blocks)))
    full = np.zeros((4, m), np.int32)
    win = np.zeros((4, m), np.int32)
    for b in range(3):
        last = (q_start[b] + q_lens[b] - 1) // BS
        for j in range(last + 1):
            full[b, j] = free.pop()
        for j in range(max(q_start[b] - (window - 1), 0) // BS, last + 1):
            win[b, j] = full[b, j]
    return q, pool, full, win, q_start, q_lens


def plain(q, pool, layer, table, q_start, q_lens, window):
    """Gather the table's blocks, attend under the causal and window masks in float32, zero the dead rows."""
    b, t, n, h = q.shape
    k, v = (pool[layer, side][table].reshape(b, -1, pool.shape[-1] // h, h) for side in (0, 1))
    group = n // k.shape[2]
    k, v = np.repeat(k, group, axis=2), np.repeat(v, group, axis=2)
    s = np.einsum("btnh,bsnh->bnts", q, k) * h ** -0.5
    q_pos = q_start[:, None] + np.arange(t)[None, :]
    k_pos = np.arange(k.shape[1])[None, None, :]
    seen = (k_pos <= q_pos[:, :, None]) & (k_pos > q_pos[:, :, None] - window)
    s = np.where(seen[:, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    out = np.einsum("bnts,bsnh->btnh", p / p.sum(-1, keepdims=True), v)
    return np.where((np.arange(t)[None, :] < q_lens[:, None])[:, :, None, None], out, 0.0)


@pytest.mark.parametrize("window", [8, 7, 9, 1], ids=lambda w: f"window{w}")
@pytest.mark.parametrize("group", [8, 6], ids=["group8", "group6"])
def test_a_window_call_is_plain_attention_under_the_window_mask(group, window):
    q, pool, full, win, q_start, q_lens = case(group, window, np.random.default_rng(group))
    want = plain(q, pool, 1, full, q_start, q_lens, window)
    for table in (win, full):  # the window table is enough; blocks behind the window are never read
        got = ragged_paged_attention(jnp.asarray(q), jnp.asarray(pool), jnp.asarray(table), jnp.asarray(q_start),
                                     jnp.asarray(q_lens), 1, interpret=True, window=window)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    assert not np.asarray(got)[3].any() and not np.asarray(got)[1, 1:].any()  # dead rows and padding: exact zeros
    # a window one off reads one key more or fewer for the rows past it: the comparison tells them apart
    other = plain(q, pool, 1, full, q_start, q_lens, window + 1)
    assert np.abs(other - want).max() > 1e-3


def test_without_a_window_nothing_changed_and_a_window_over_everything_is_the_same_walk():
    q, pool, full, _, q_start, q_lens = case(8, 8, np.random.default_rng(0))
    args = (jnp.asarray(q), jnp.asarray(pool), jnp.asarray(full), jnp.asarray(q_start), jnp.asarray(q_lens), 2)
    whole = ragged_paged_attention(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(whole), plain(q, pool, 2, full, q_start, q_lens, 10**6), atol=2e-5)
    # 48 positions in the table: a window of 48 + 8 sees them all, starts at block 0 and takes all 12 steps
    covering = ragged_paged_attention(*args, interpret=True, window=56)
    assert np.array_equal(np.asarray(whole), np.asarray(covering))


def grid_of(fn, *args):
    eqns = [e for e in jax.make_jaxpr(fn)(*args).eqns if e.primitive.name == "pallas_call"]
    assert len(eqns) == 1
    return tuple(eqns[0].params["grid_mapping"].grid)


@pytest.mark.parametrize("rows, tokens, steps, tiles", [(16, 1, 9, 1), (1, 1024, 25, 4)], ids=["decode", "chunk1024"])
def test_the_grid_is_sized_by_the_window_and_not_by_the_table(rows, tokens, steps, tiles):
    """At the benchmark cell's sizes (64 query / 8 KV heads of 128, blocks of 16, tables of 1,088, window 128): a
    decode row walks ceil(127 + 1 / 16) + 1 = 9 blocks, a chunk of 1,024 in four query tiles of 256 walks
    ceil((127 + 256) / 16) + 1 = 25 a tile, where the table has 1,088 entries; without a window the grid is the table's."""
    q = jax.ShapeDtypeStruct((rows, tokens, 64, 128), jnp.bfloat16)
    plane = jax.ShapeDtypeStruct((6, 2, 1185, 16, 1024), jnp.bfloat16)
    table = jax.ShapeDtypeStruct((rows, 1088), jnp.int32)
    vec = jax.ShapeDtypeStruct((rows,), jnp.int32)
    call = lambda window: (lambda q, kv, t, s, n: ragged_paged_attention(q, kv, t, s, n, 3, interpret=True, window=window))
    assert grid_of(call(128), q, plane, table, vec, vec) == (rows, 8, tiles, steps)
    assert grid_of(call(None), q, plane, table, vec, vec) == (rows, 8, tiles, 1088)
