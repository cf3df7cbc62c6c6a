"""The block mask of generation by diffusion over blocks (``block=B``: a query at position p sees kv positions
``<= p | (B - 1)``, causal over blocks of B counted from position 0, its own block whole) in the two ragged paged
kernels (interpret mode) and their XLA forms: each against plain float32 attention under a dense mask written out by
hand, and against each other; decode rows of one block (a pass), chunk rows of whole blocks that start deep in their
context and cross a run's edge, a dead row beside live ones; and **with ``block=None`` the results of both kernels are
bit for bit those of the parent commit** (digests of their outputs on seeded inputs, taken on commit 3fd3e23).

Blocks (pages) of 4 and heads of 16 here, and a run of 4 pages = 16 keys (``_RUN_KEYS`` patched)."""

import hashlib

import jax.numpy as jnp
import numpy as np
import pytest

from paddlenlp_tpu.ops.pallas import paged_run_attention
from paddlenlp_tpu.ops.pallas.paged_attention import ragged_paged_attention
from paddlenlp_tpu.ops.pallas.paged_run_attention import ragged_paged_run_attention
from paddlenlp_tpu.transformers import window_layers as W

BS, H, KV, GROUP = 4, 16, 2, 2
TOL = 2e-6  # float32 on both sides, another order of summation (pages or runs against one softmax)


@pytest.fixture(autouse=True)
def short_runs(monkeypatch):
    monkeypatch.setattr(paged_run_attention, "_RUN_KEYS", 16)


def launch(t, q_start, q_lens, seed, m=12, blocks=48):
    """Rows of a launch ``t`` wide over one pool of noise; a row's table names pages up to its last fed position and
    a page of NaN past it: under a block mask nothing past the block's last position may be read."""
    rng = np.random.default_rng(seed)
    q_start, q_lens = np.asarray(q_start, np.int32), np.asarray(q_lens, np.int32)
    pool = rng.standard_normal((2, 2, blocks, BS, KV * H)).astype(np.float32)
    pool[:, :, blocks - 1] = np.nan
    free = list(rng.permutation(np.arange(1, blocks - 1)))
    table = np.full((len(q_start), m), blocks - 1, np.int32)
    for row in range(len(q_start)):
        for j in range((q_start[row] + q_lens[row] - 1) // BS + 1 if q_lens[row] else 0):
            table[row, j] = free.pop()
    q = rng.standard_normal((len(q_start), t, KV * GROUP, H)).astype(np.float32)
    return q, pool, table, q_start, q_lens


def dense_mask(q_pos, n_keys, block):
    """[T, S] written out by hand: key j is visible to the query at position i iff j // B <= i // B."""
    return np.asarray([[j // block <= i // block for j in range(n_keys)] for i in q_pos])


def plain(q, pool, layer, table, q_start, q_lens, block):
    b, t, n, h = q.shape
    out = np.zeros_like(q)
    for row in range(b):
        if not q_lens[row]:
            continue
        n_pages = (q_start[row] + q_lens[row] - 1) // BS + 1
        k, v = (np.repeat(pool[layer, side][table[row, :n_pages]].reshape(n_pages * BS, -1, h), GROUP, axis=1)
                for side in (0, 1))
        s = np.einsum("tnh,snh->nts", q[row], k) * h ** -0.5
        s = np.where(dense_mask(q_start[row] + np.arange(t), k.shape[0], block)[None], s, -1e30)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[row] = np.einsum("nts,snh->tnh", p / p.sum(-1, keepdims=True), v)
        out[row, q_lens[row]:] = 0.0
    return out


def call(fn, args, **kw):
    q, pool, table, q_start, q_lens = (jnp.asarray(a) for a in args)
    return np.asarray(fn(q, pool, table, q_start, q_lens, 1, interpret=True, **kw))


CASES = {
    "a-pass": (4, [8, 0, 36, 4], [4, 0, 4, 4]),             # decode rows of one block; a dead row; one past two runs
    "chunks": (16, [0, 20, 12], [16, 8, 12]),               # whole blocks from the start, deep in the context, short
    "block-of-8": (8, [16, 8], [8, 8]),
}


@pytest.mark.parametrize("case", CASES, ids=list(CASES))
@pytest.mark.parametrize("kernel", [ragged_paged_run_attention, ragged_paged_attention], ids=["runs", "blocks"])
def test_the_kernels_compute_the_block_mask(kernel, case):
    t, q_start, q_lens = CASES[case]
    block = 8 if case == "block-of-8" else 4
    args = launch(t, q_start, q_lens, seed=7)
    want = plain(*args[:2], 1, *args[2:], block)
    got = call(kernel, args, block=block)
    assert np.isfinite(got).all()  # nothing past a block's last position was read
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    # and not the causal rule: a block's first position sees the three after it
    assert np.abs(call(kernel, args) - want).max() > 1e-3


def test_the_two_kernels_agree_under_the_block_mask():
    args = launch(16, [0, 20, 12], [16, 8, 12], seed=11)
    np.testing.assert_allclose(call(ragged_paged_run_attention, args, block=4),
                               call(ragged_paged_attention, args, block=4), atol=TOL, rtol=0)


def test_the_xla_forms_compute_the_block_mask():
    """``window_layers.window_mask(block=)`` is the dense mask; ``attend`` under it agrees with the kernel."""
    q_pos, k_pos = np.arange(8, 16)[None], np.arange(24)[None]
    got = np.asarray(W.window_mask(jnp.asarray(q_pos), jnp.asarray(k_pos), None, 4))[0]
    assert (got == dense_mask(q_pos[0], 24, 4)).all()
    assert (np.asarray(W.window_mask(jnp.asarray(q_pos), jnp.asarray(k_pos), None))[0]
            == (k_pos[0][None, :] <= q_pos[0][:, None])).all()  # None: causal, as ever
    q, pool, table, q_start, q_lens = launch(8, [16], [8], seed=3)
    k, v = (jnp.asarray(pool[1, side][table[0, :6]].reshape(1, 24, KV, H)) for side in (0, 1))
    positions = jnp.asarray(16 + np.arange(8)[None])
    xla = W.attend(jnp.asarray(q), k, v, W.window_mask(positions, jnp.asarray(k_pos), None, 4))
    np.testing.assert_allclose(np.asarray(xla), call(ragged_paged_run_attention, (q, pool, table, q_start, q_lens),
                                                     block=4), atol=TOL, rtol=0)


def test_the_llama_kinds_gather_form_takes_the_block_mask():
    """``PagedInferenceModel._attend(block=)``: the same dense mask beside the length mask; None is causal."""
    from paddlenlp_tpu.experimental.inference_model import PagedInferenceModel

    class Bare:
        n_kv = KV

    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((1, 4, KV * GROUP, H)).astype(np.float32))
    k, v = (jnp.asarray(rng.standard_normal((1, 16, KV, H)).astype(np.float32)) for _ in range(2))
    positions, alive = jnp.asarray(8 + np.arange(4)[None]), jnp.ones((1, 16), bool)
    blocked = PagedInferenceModel._attend(Bare(), q, k, v, positions, alive, block=4)
    want = W.attend(q, k, v, jnp.asarray(dense_mask(8 + np.arange(4), 16, 4))[None])
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(want), atol=TOL, rtol=0)
    causal = PagedInferenceModel._attend(Bare(), q, k, v, positions, alive)
    want = W.attend(q, k, v, jnp.asarray(np.arange(16)[None, :] <= (8 + np.arange(4))[:, None])[None])
    np.testing.assert_allclose(np.asarray(causal), np.asarray(want), atol=TOL, rtol=0)


def test_a_block_that_is_no_power_of_two_is_refused():
    args = launch(4, [8], [4], seed=1)
    for kernel in (ragged_paged_run_attention, ragged_paged_attention):
        with pytest.raises(ValueError, match="power of two"):
            call(kernel, args, block=6)
    with pytest.raises(ValueError, match="no window beside it"):
        call(ragged_paged_attention, args, block=4, window=8)


# sha256 of each kernel's float32 output on the seeded launch below, taken on the parent commit (3fd3e23, before either
# kernel had the parameter): with block unset the kernels compute what they computed, bit for bit
PARENT = {
    "blocks": ("f8fef782c7ae0b1eec0a1e8a2e014de4f99b48d82179a91ae86666acd90cd157", ragged_paged_attention, {}),
    "window": ("41b29b7cf6a1e372aba200621239d8e973ce89c7bbef29eb6554a36e8637ed02", ragged_paged_attention, {"window": 6}),
    "runs": ("2ea2ee48e9cf1609ea3f56847b0e3139dffeb9ef4aed4ba179dbd213b15997ae", ragged_paged_run_attention, {}),
}


@pytest.mark.parametrize("name", PARENT, ids=list(PARENT))
@pytest.mark.parametrize("unset", [{}, {"block": None}], ids=["left-out", "none"])
def test_block_unset_gives_the_parents_result_bit_for_bit(monkeypatch, name, unset):
    monkeypatch.setattr(paged_run_attention, "_RUN_KEYS", 512)  # as the digests were taken
    rng = np.random.default_rng(44)
    pool = rng.standard_normal((2, 2, 40, 4, 2 * 16)).astype(np.float32)
    table = rng.permutation(np.arange(1, 40))[:3 * 12].reshape(3, 12).astype(np.int32)
    q = rng.standard_normal((3, 8, 4, 16)).astype(np.float32)
    start, lens = np.asarray([12, 0, 5], np.int32), np.asarray([8, 0, 3], np.int32)
    digest, kernel, kw = PARENT[name]
    out = call(kernel, (q, pool, table, start, lens), **kw, **unset)
    assert hashlib.sha256(out.tobytes()).hexdigest() == digest
