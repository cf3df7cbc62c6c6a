"""The ragged paged kernel's table walk by runs (``ops/pallas/paged_run_attention.py``: what the windowed kinds' full
layers call) in interpret mode: against plain float32 attention and against the walk by blocks on one pool of noise,
at 8 KV heads x group 8 and at 2 x 6; decode rows at a run's edges, a dead row beside live ones, chunk rows at the
start of their context, deep in it and ending inside a run, in one query tile and in several; table entries past a
row's last live block poisoned; and the arithmetic of the walk: a grid without an axis of the table's length, runs a
row, heads a step.

Blocks of 4 and heads of 16 here, and a run of 4 blocks = 16 keys (``_RUN_KEYS`` patched: at its own 512 keys every
table of this size would be one run)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlenlp_tpu.ops.pallas import paged_attention, paged_run_attention
from paddlenlp_tpu.ops.pallas.paged_attention import ragged_paged_attention
from paddlenlp_tpu.ops.pallas.paged_run_attention import (_heads_a_step, ragged_paged_run_attention, run_blocks,
                                                          runs_visited)

BS, H, RUN_KEYS = 4, 16, 16
GROUPINGS = {"8x8": (8, 8), "2x6": (2, 6)}  # KV heads x group


@pytest.fixture(autouse=True)
def short_runs(monkeypatch):
    monkeypatch.setattr(paged_run_attention, "_RUN_KEYS", RUN_KEYS)


def launch(kv_heads, group, t, q_start, q_lens, rng, m=14, blocks=64, poison=False):
    """Rows of a launch ``t`` wide over one pool of noise. Each row's table names blocks up to its last live position;
    past that the sentinel 0, or with ``poison`` a block of NaN (in both planes of every layer)."""
    q_start, q_lens = np.asarray(q_start, np.int32), np.asarray(q_lens, np.int32)
    b = len(q_start)
    pool = rng.standard_normal((2, 2, blocks, BS, kv_heads * H)).astype(np.float32)
    free = list(rng.permutation(np.arange(1, blocks - 1)))
    table = np.zeros((b, m), np.int32)
    if poison:
        pool[:, :, blocks - 1] = np.nan
        table[:] = blocks - 1
    for row in range(b):
        if q_lens[row]:
            for j in range((q_start[row] + q_lens[row] - 1) // BS + 1):
                table[row, j] = free.pop()
    q = rng.standard_normal((b, t, kv_heads * group, H)).astype(np.float32)
    return q, pool, table, q_start, q_lens


def plain(q, pool, layer, table, q_start, q_lens):
    """Gather each live row's blocks up to its last live position, attend under the causal mask in float32, zero the
    dead rows (a dead row's table is never read)."""
    b, t, n, h = q.shape
    out = np.zeros_like(q)
    for row in range(b):
        if not q_lens[row]:
            continue
        n_blocks = (q_start[row] + q_lens[row] - 1) // BS + 1
        k, v = (pool[layer, side][table[row, :n_blocks]].reshape(n_blocks * BS, -1, h) for side in (0, 1))
        group = n // k.shape[1]
        k, v = np.repeat(k, group, axis=1), np.repeat(v, group, axis=1)
        s = np.einsum("tnh,snh->nts", q[row], k) * h ** -0.5
        seen = np.arange(k.shape[0])[None, :] <= (q_start[row] + np.arange(t))[:, None]
        s = np.where(seen[None], s, -1e30)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[row] = np.einsum("nts,snh->tnh", p / p.sum(-1, keepdims=True), v)
        out[row, q_lens[row]:] = 0.0
    return out


def by_runs(q, pool, table, q_start, q_lens, layer=1):
    return np.asarray(ragged_paged_run_attention(jnp.asarray(q), jnp.asarray(pool), jnp.asarray(table),
                                                 jnp.asarray(q_start), jnp.asarray(q_lens), layer, interpret=True))


def by_blocks(q, pool, table, q_start, q_lens, layer=1):
    return np.asarray(ragged_paged_attention(jnp.asarray(q), jnp.asarray(pool), jnp.asarray(table),
                                             jnp.asarray(q_start), jnp.asarray(q_lens), layer, interpret=True))


# decode rows by their length (the one token fed sits at length - 1): 1, one short of a run's edge, on it, one past
# it, two runs and a block, the whole table; a dead row among them
DECODE_LENGTHS = [1, RUN_KEYS - 1, RUN_KEYS, RUN_KEYS + 1, 0, 2 * RUN_KEYS + BS + 1, 14 * BS]


@pytest.mark.parametrize("grouping", GROUPINGS)
def test_decode_rows_at_a_runs_edges_are_plain_attention_and_the_walk_by_blocks(grouping):
    kv_heads, group = GROUPINGS[grouping]
    lengths = np.asarray(DECODE_LENGTHS)
    case = launch(kv_heads, group, 1, np.maximum(lengths - 1, 0), (lengths > 0).astype(np.int32),
                  np.random.default_rng(kv_heads))
    got = by_runs(*case)
    np.testing.assert_allclose(got, plain(case[0], case[1], 1, *case[2:]), atol=2e-5)
    np.testing.assert_allclose(got, by_blocks(*case), atol=2e-5)  # only the order of summation moved
    assert not got[4].any()  # the dead row: exact zeros


# chunk rows of a launch 8 wide, by (q_start, q_lens): at the start of its context; deep in it and ending on a run's
# edge; ending inside a run, with padding behind; one token of a chunk; a dead row
CHUNK_ROWS = [(0, 8), (3 * RUN_KEYS - 8, 8), (RUN_KEYS + 3, 6), (37, 1), (0, 0)]


@pytest.mark.parametrize("max_q_rows", [3072, 32, 16], ids=["one-tile", "tiles-of-4", "tiles-of-2"])
@pytest.mark.parametrize("grouping", GROUPINGS)
def test_chunk_rows_are_plain_attention_and_the_walk_by_blocks(grouping, max_q_rows, monkeypatch):
    """In one query tile with every KV head a step, and with ``_MAX_Q_ROWS`` cut so that a chunk of 8 goes in tiles of
    4 or 2 tokens and a step takes fewer heads (at 8 x 8: one head a step; at 2 x 6: tiles of 4 with one head a step,
    since 24 rows a head leave room for no second one under 32)."""
    kv_heads, group = GROUPINGS[grouping]
    monkeypatch.setattr(paged_attention, "_MAX_Q_ROWS", max_q_rows)
    monkeypatch.setattr(paged_run_attention, "_MAX_Q_ROWS", max_q_rows)
    case = launch(kv_heads, group, 8, [s for s, _ in CHUNK_ROWS], [n for _, n in CHUNK_ROWS],
                  np.random.default_rng(group))
    got = by_runs(*case)
    np.testing.assert_allclose(got, plain(case[0], case[1], 1, *case[2:]), atol=2e-5)
    np.testing.assert_allclose(got, by_blocks(*case), atol=2e-5)
    assert not got[4].any() and not got[2, 6:].any() and not got[3, 1:].any()  # dead row and padding: exact zeros


@pytest.mark.parametrize("tokens", [1, 8], ids=["decode", "chunk"])
def test_entries_past_a_rows_last_live_block_are_never_read(tokens):
    """Every table entry past a row's last live block names a block of NaN, in the row's last run and in the runs
    behind it: one key read from there would make the row NaN. (The walk by blocks skips those blocks too.)"""
    rows = [(s, n) for s, n in CHUNK_ROWS] if tokens == 8 else [(max(n - 1, 0), int(n > 0)) for n in DECODE_LENGTHS]
    case = launch(8, 8, tokens, [s for s, _ in rows], [n for _, n in rows], np.random.default_rng(5), poison=True)
    got = by_runs(*case)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, plain(case[0], case[1], 1, *case[2:]), atol=2e-5)


def test_both_layers_and_a_pool_in_bfloat16():
    """The layer index reaches the copies, and a bfloat16 pool under bfloat16 queries (the cell's precisions: the
    scores' products are exact in float32) agrees with the walk by blocks, which casts both to float32 first."""
    q, pool, table, q_start, q_lens = launch(8, 8, 8, [0, 40, 19], [8, 8, 5], np.random.default_rng(9))
    assert np.abs(by_runs(q, pool, table, q_start, q_lens, layer=0) - by_runs(q, pool, table, q_start, q_lens, layer=1)).max() > 0.1
    np.testing.assert_allclose(by_runs(q, pool, table, q_start, q_lens, layer=0), plain(q, pool, 0, table, q_start, q_lens), atol=2e-5)
    half = lambda a: jnp.asarray(a, jnp.bfloat16)
    args = (half(q), half(pool), jnp.asarray(table), jnp.asarray(q_start), jnp.asarray(q_lens), 1)
    got = ragged_paged_run_attention(*args, interpret=True)
    want = ragged_paged_attention(*args, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), atol=2e-2)


def grid_of(fn, *args):
    eqns = [e for e in jax.make_jaxpr(fn)(*args).eqns if e.primitive.name == "pallas_call"]
    assert len(eqns) == 1
    return tuple(eqns[0].params["grid_mapping"].grid)


@pytest.mark.parametrize("rows, tokens, grid", [(16, 1, (16, 1, 1)), (1, 1024, (1, 8, 4))], ids=["decode", "chunk1024"])
def test_the_grid_has_no_axis_of_the_tables_length(rows, tokens, grid, monkeypatch):
    """At the benchmark cell's sizes (64 query / 8 KV heads of 128, blocks of 16, tables of 1,088): 16 decode rows are
    16 grid steps of all 8 KV heads, a chunk of 1,024 is 8 heads x 4 query tiles of 256 tokens, and the steps of the
    walk are turns of the kernel's loop, a row's own runs of 32 blocks; the walk by blocks has the table's 1,088
    entries as its innermost axis whatever the rows hold."""
    monkeypatch.undo()  # the file's own run: 512 keys
    q = jax.ShapeDtypeStruct((rows, tokens, 64, 128), jnp.bfloat16)
    pool = jax.ShapeDtypeStruct((2, 2, 17408, 16, 1024), jnp.bfloat16)
    table = jax.ShapeDtypeStruct((rows, 1088), jnp.int32)
    vec = jax.ShapeDtypeStruct((rows,), jnp.int32)
    runs = lambda q, kv, t, s, n: ragged_paged_run_attention(q, kv, t, s, n, 1, interpret=True)
    blocks = lambda q, kv, t, s, n: ragged_paged_attention(q, kv, t, s, n, 1, interpret=True)
    assert grid_of(runs, q, pool, table, vec, vec) == grid
    assert grid_of(blocks, q, pool, table, vec, vec) == (rows, 8, grid[2], 1088)
    assert run_blocks(16, 1088) == 32


@pytest.mark.parametrize("block_size, table, run", [(16, 1088, 32), (16, 8, 8), (4, 14, 4), (32, 1088, 16), (1024, 64, 1)])
def test_a_run_is_512_keys_of_blocks_or_the_table(block_size, table, run, monkeypatch):
    if block_size != 4:  # the file's own 512 keys; blocks of 4 under this file's runs of 16
        monkeypatch.undo()
    assert run_blocks(block_size, table) == run


def test_runs_visited_is_the_rows_own_and_the_loops_trip_count():
    """A live row that feeds n tokens from s walks ceil((s + n) / run positions) runs and a dead row none: at the
    cell's sizes 16 decode rows of a hundred to 16,000 positions take 113 turns a layer where
    the walk by blocks took 16 x 8 x 1,088 grid steps."""
    start = jnp.asarray([0, 510, 511, 512, 5000, 16383, 0, 7000])
    lens = jnp.asarray([1, 1, 1, 1, 1, 1, 0, 1024])
    assert runs_visited(start, lens, 512).tolist() == [1, 1, 1, 2, 10, 32, 0, 16]
    typical = jnp.asarray([200, 600, 1500, 1500, 3000, 800, 16000, 7000, 400, 2500, 1200, 100, 5000, 900, 1700, 12000])
    assert int(runs_visited(typical - 1, jnp.ones(16, jnp.int32), 512).sum()) == 113 < 16 * 8 * 1088 // 1000


@pytest.mark.parametrize("rows, n_kv, heads", [(8, 8, 8), (2048, 8, 1), (1024, 8, 2), (512, 8, 4), (600, 8, 4),
                                               (6, 2, 2), (3072, 2, 1), (1100, 6, 2)])
def test_heads_a_step_come_from_the_query_rows(rows, n_kv, heads):
    """As many KV heads as keep the step's query rows inside ``_MAX_Q_ROWS`` (3,072), and a divisor of the head count."""
    assert _heads_a_step(rows, n_kv) == heads
