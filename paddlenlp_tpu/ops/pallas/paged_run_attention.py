"""The ragged paged kernel's table walk by runs: what the windowed kinds'
``gqa_full`` layers call (``experimental/window_model.py:_attention_kind``).

The same attention as ``paged_attention.py``'s call without a window (query
token t of row b sees kv positions ``[0, q_start[b] + t]`` of one pool layer
through the row's block table; rows past ``q_lens[b]`` are exact zeros; scores,
running maximum, sum and accumulator float32; no gathered copy of the cache),
with another answer to what one step of the walk covers. There a step is one
block of one KV head and the grid's block axis is as long as the table: 16 rows
x 8 KV heads x 1,088 entries = 139,264 grid steps a layer a decode sub-step,
nearly all of them past a row's last block and skipped one by one. Here

- a step covers **a run of consecutive table entries** (``run_blocks``: as many
  blocks as hold ``_RUN_KEYS`` = 512 keys, 32 blocks of 16, so a score tile is
  four full 128-lane widths) **for as many KV heads as the query tile allows**
  (``_heads_a_step``: all of them for a decode row, whose queries are ``group``
  rows a head; one for a chunk tile of ``_q_tile_tokens`` tokens, whose rows
  fill ``_MAX_Q_ROWS``);
- the steps are turns of a loop inside the kernel, and **the loop's trip count
  is the row's own number of runs** (``ceil((highest live position + 1) /
  run positions)``, from the prefetched ``q_start`` / ``q_lens``), so there is
  no step past a row's last run to skip: the grid is ``(B, K / heads a step,
  T / tq)`` and has no axis of the table's length. A dead row takes no turn;
- the pool stays in HBM (``pl.ANY``), passed once, and a run's blocks are
  copied by hand, one DMA a block and plane (``[bs, heads a step * H]``: the
  whole 2 KB row of a block where every head is taken, so a block is one
  contiguous 32 KB read), into one of two VMEM slots: run ``r + 1`` is on its
  way while run ``r`` is computed. A run's entries past the row's last live
  block name that block again (fetched, masked: nothing past it is read, so a
  table's stale entries never are).

On one v5e chip at ``mixedlen``'s shapes (64 query / 8 KV heads of 128, bf16,
tables of 1,088; PERF.md section 6, PR 36): 16 decode rows of 100 to 16,000
positions 22.9 ms by blocks and 0.53 ms by runs (52% of the time their bytes
take at the HBM's peak), a chunk of 1,024 at 8,192 cached 76.0 ms and 4.7 ms.
Runs of 128 and 256 keys read 0.71 ms and 10.3 / 7.2 ms there: a turn's fixed
work (64 copies started and awaited, the accumulator rescaled) wants long runs,
and at 512 keys a chunk tile's float32 scores ([2048, 512]) still fit the 16
MiB of scoped VMEM a kernel gets without asking.

Only the order of summation moves, by runs instead of blocks. What the call
may assume, because the windowed kinds refuse the rest at the door: no
``kv_scale``, one chip, ``head_dim`` a multiple of 128 on the chip. The
``pallas_call`` keeps the name ``ragged_paged_attention``: the benchmark's
readers find the kernel by it. ``runs_visited`` is the arithmetic of the walk,
for the counter ``attn_kv_fetched``.

Off-TPU (tests), the kernel runs in Pallas interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import _MAX_Q_ROWS, NEG_INF, _q_tile_tokens

__all__ = ["ragged_paged_run_attention", "run_blocks", "runs_visited"]

# keys a step of the walk attends: four 128-lane widths of scores (module docstring: measured against 128 and 256)
_RUN_KEYS = 512


def run_blocks(block_size: int, max_blocks: int) -> int:
    """Table entries a step of the walk covers: ``_RUN_KEYS`` keys' worth (32 blocks of 16), the table itself
    where that is shorter."""
    return max(1, min(_RUN_KEYS // block_size, max_blocks))


def _heads_a_step(rows: int, n_kv: int) -> int:
    """KV heads one step attends: the most whose query rows (``rows`` a head) fit ``_MAX_Q_ROWS``, a divisor of
    ``n_kv`` (all 8 at a decode row's 8 rows a head, one at a chunk tile's 2,048)."""
    heads = max(1, min(n_kv, _MAX_Q_ROWS // rows))
    while n_kv % heads:
        heads -= 1
    return heads


def runs_visited(q_start, q_lens, run_positions: int):
    """Runs of ``run_positions`` cached positions the walk visits for each row [B]: a live row that feeds ``n``
    tokens from ``s`` reads positions ``[0, s + n)``, whole runs of them; a dead row none. The trip count of the
    kernel's loop (there for one query tile) and the arithmetic of the counter ``attn_kv_fetched``."""
    return jnp.where(q_lens > 0, (q_start + q_lens + run_positions - 1) // run_positions, 0)


def _kernel(tables_ref, start_ref, len_ref, layer_ref, q_ref, kv_ref, o_ref, k_buf, v_buf, sem, m_s, l_s, acc_s,
            *, bs, scale, group, tq, run, heads, H, block=None):
    b = pl.program_id(0)
    hb = pl.program_id(1)
    start = start_ref[b]
    qlen = len_ref[b]
    layer = layer_ref[0]
    t0 = pl.program_id(2) * tq  # first query token of this tile
    live = jnp.minimum(qlen - t0, tq)  # live tokens in this tile (<= 0: none)
    hi = start + t0 + live - 1  # highest live query position: nothing past it is read
    if block is not None:  # ... its block's last position under a block mask (the caller feeds whole blocks)
        hi = hi | (block - 1)
    last_block = jnp.maximum(hi, 0) // bs
    n_runs = runs_visited(start + t0, live, run * bs)
    if block is not None:  # the runs up to that position
        n_runs = jnp.where(live > 0, hi // (run * bs) + 1, 0)
    lanes = pl.ds(pl.multiple_of(hb * (heads * H), heads * H), heads * H)

    def copy(block, i, plane, slot):
        """The DMA of one block's rows of one plane into place ``i`` of ``slot``."""
        return pltpu.make_async_copy(kv_ref.at[layer, plane, block, :, lanes],
                                     (k_buf, v_buf)[plane].at[slot, pl.ds(pl.multiple_of(i * bs, bs), bs), :],
                                     sem.at[plane, slot])

    # the copies of a run are started and awaited in rolled loops: unrolled, 64 of each a turn made the kernel three
    # times as long to lower, once a full layer and program, in every process that starts (setup_s)
    def fetch(r, slot):
        def issue(i, _):  # entries past the row's last live block name it again
            block = tables_ref[b, jnp.minimum(r * run + i, last_block)]
            for plane in (0, 1):
                copy(block, i, plane, slot).start()

        jax.lax.fori_loop(0, run, issue, None)

    def land(slot):
        def wait(i, _):  # a wait takes a copy's shape, not its source
            for plane in (0, 1):
                copy(0, i, plane, slot).wait()

        jax.lax.fori_loop(0, run, wait, None)

    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(n_runs > 0)
    def _first():
        fetch(0, 0)

    def turn(r, _):
        slot = r % 2

        @pl.when(r + 1 < n_runs)
        def _next():
            fetch(r + 1, 1 - slot)

        land(slot)
        rows = tq * group
        kv_pos = r * (run * bs) + jax.lax.broadcasted_iota(jnp.int32, (rows, run * bs), 1)
        t = t0 + jax.lax.broadcasted_iota(jnp.int32, (rows, run * bs), 0) // group  # query token idx
        valid = (kv_pos <= (start + t if block is None else (start + t) | (block - 1))) & (t < qlen)
        for h in range(heads):
            q = q_ref[0, h]  # [tq*group, H]
            k = k_buf[slot, :, h * H:(h + 1) * H]  # [run*bs, H]
            if q.dtype != k.dtype:
                q, k = q.astype(jnp.float32), k.astype(jnp.float32)
            v = v_buf[slot, :, h * H:(h + 1) * H].astype(jnp.float32)
            # pool and queries in one precision: their products are exact in float32 either way
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_s[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_s[h] = alpha * l_s[h] + jnp.sum(p, axis=-1, keepdims=True)
            acc_s[h] = acc_s[h] * alpha + jax.lax.dot(p, v)
            m_s[h] = m_new

    jax.lax.fori_loop(0, n_runs, turn, None)
    # dead rows (t >= q_lens, or q_lens == 0) kept l == 0 -> exact zeros
    o_ref[0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-37)).astype(o_ref.dtype)


def ragged_paged_run_attention(
    q: jnp.ndarray,  # [B, T, N, H] new-token queries (rows past q_lens ignored)
    kv: jnp.ndarray,  # [L, 2, num_blocks, bs, K*H] the whole pool
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    q_start: jnp.ndarray,  # [B] absolute position of q[:, 0]
    q_lens: jnp.ndarray,  # [B] valid new tokens per sequence (0 = inactive row)
    layer,  # int32 scalar: the pool layer to read
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    block: Optional[int] = None,  # static, a power of two: a query sees its whole block of this many positions
) -> jnp.ndarray:
    """``ragged_paged_attention`` without a window, walking the table by runs (module docstring).
    With ``block`` (generation by diffusion over blocks) query token t of row b sees kv positions
    ``[0, (q_start[b] + t) | (block - 1)]``: causal over blocks counted from position 0, its own whole; the
    caller feeds whole blocks, so nothing past what this launch wrote is read. ``None`` traces nothing new.
    Returns ``[B, T, N, H]`` with rows ``t >= q_lens[b]`` zeroed."""
    if block is not None and block & (block - 1):
        raise ValueError(f"block={block} is not a power of two")
    B, T, N, H = q.shape
    bs, K = kv.shape[3], kv.shape[4] // H
    group = N // K
    scale = scale if scale is not None else H**-0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    tq = _q_tile_tokens(T, group)
    rows = tq * group
    heads = _heads_a_step(rows, K)
    run = run_blocks(bs, block_tables.shape[1])

    # [B, T, N, H] -> [B, K, T*group, H]: head n = kh*group + g, so T and group
    # interleave as rows (t, g) -> row t*group + g of kv head kh
    qf = q.reshape(B, T, K, group, H).transpose(0, 2, 1, 3, 4).reshape(B, K, T * group, H)
    q_spec = pl.BlockSpec((1, heads, rows, H), lambda b, hb, qt, t, s, n, l: (b, hb, qt, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, K // heads, T // tq),
        in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, run * bs, heads * H), kv.dtype),  # a run of K, two slots
            pltpu.VMEM((2, run * bs, heads * H), kv.dtype),  # a run of V
            pltpu.SemaphoreType.DMA((2, 2)),  # plane x slot
            pltpu.VMEM((heads, rows, 1), jnp.float32),  # m
            pltpu.VMEM((heads, rows, 1), jnp.float32),  # l
            pltpu.VMEM((heads, rows, H), jnp.float32),  # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, scale=scale, group=group, tq=tq, run=run, heads=heads, H=H,
                          **({} if block is None else {"block": block})),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, T * group, H), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="ragged_paged_attention",
    )(block_tables.astype(jnp.int32), q_start.astype(jnp.int32),
      q_lens.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1), qf, kv)
    return out.reshape(B, K, T, group, H).transpose(0, 2, 1, 3, 4).reshape(B, T, N, H)
