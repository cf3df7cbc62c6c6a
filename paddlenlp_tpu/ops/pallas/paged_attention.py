"""Pallas TPU ragged paged-attention kernel (mixed prefill-chunk + decode).

Counterpart of the reference's block-attention ops
(``csrc/gpu/append_attention.cu:801`` + ``csrc/gpu/append_attn/*.cuh``) in the
shape the *Ragged Paged Attention* TPU kernel paper describes: ONE launch
computes attention for a ragged batch where each sequence contributes a
different number of new query tokens — a prefill chunk (tens to hundreds of
tokens picking up at ``q_start`` = its already-prefilled length), a decode step
(one token), or nothing (padded slot) — against its own paged KV, walking each
sequence's block table and streaming the addressed KV blocks HBM->VMEM with an
online-softmax accumulator. No ``[B, max_blocks*bs, K, H]`` gathered copy of
the cache ever materializes (the XLA fallback's cost).

Design:
- grid = (B, K, q_tiles, max_blocks); the block axis is innermost and
  sequential, carrying (m, l, acc) VMEM scratch per (tq*group, H) query tile.
  The query rows are tiled (``_MAX_Q_ROWS``) because the chip's compiler gives
  a kernel 16 MiB of scoped VMEM: a whole 2048-token prefill bucket at GQA
  group 6 (12288 rows x 128) needed 18 MiB and was refused;
- the operand is the WHOLE pool ``[L, 2, num_blocks, bs, K*H]`` (token-major
  rows, passed once for K and once for V), never a layer or a plane cut out
  of it: the block table, per-sequence ``q_start``/``q_lens`` and the layer
  index ride scalar prefetch (``pltpu.PrefetchScalarGridSpec``), and the KV
  BlockSpec index map aims the DMA of one head's ``(bs, H)`` tile at
  ``(layer, plane, tables[b, j], 0, kh)`` — the table gather IS the address
  computation, exactly like the CUDA kernel's block walk. The tile is ``H``
  lanes out of a ``K*H``-lane row, so on the chip ``H`` must be a multiple
  of 128 (or the row itself);
- causal masking is per query ROW: query token t of sequence b sits at
  absolute position ``q_start[b] + t`` and sees kv positions ``<= q_start+t``
  — correct across chunk boundaries (a chunk's first token attends over the
  whole prefilled span, its last over prefilled+chunk-1);
- rows past ``q_lens[b]`` (padding) and fully-masked rows produce exact zeros
  (their softmax denominator stays 0); blocks past the tile's highest live
  query position are skipped entirely (@pl.when). A skipped block is exactly
  what a fully-masked one computes (m, l, acc unchanged), so the tiling does
  not change any row's result;
- GQA: queries fold to [B, K, T*group, H]; each grid cell attends its kv
  head's whole query group for every token of its query tile at once;
- ``q_lens = 1`` everywhere reduces to the classic paged decode kernel;
- **a window call** (``window=w``, static: a query sees itself and the ``w - 1``
  positions before it) walks a window, not a table: the block axis of the grid
  is as long as the window plus a query tile's tokens
  (``ceil((w - 1 + tq) / bs) + 1`` steps: 9 for a decode row at ``w`` 128 and
  blocks of 16, where the table may have a thousand entries) and is counted
  from the tile's first block, ``max(q_start[b] + t0 - (w - 1), 0) // bs``,
  which the index map and the body both derive from the prefetched
  ``q_start``. The table may be a window table, with blocks only at the logical
  blocks inside the window (``BlockManager.window_span``), the pool a window
  plane. Without ``window``: the table's walk, a block of one head a step (the
  llama and state kinds'; the windowed kinds' is ``paged_run_attention.py``'s);
- **a block call** (``block=B``, static, a power of two: generation by diffusion
  over blocks) replaces the causal rule by causal over blocks of ``B`` positions
  counted from position 0: a query at ``p`` sees kv positions ``<= p | (B - 1)``,
  its whole block included. The caller feeds whole blocks (``q_start`` and
  ``q_lens`` multiples of ``B``), so a block's last position was written by this
  launch at the latest. ``None``, the default, traces nothing new.

Off-TPU (tests), the kernel runs in Pallas interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ragged_paged_attention"]

NEG_INF = -1e30

# most query rows (tokens x GQA group) one grid cell holds in VMEM. 3072 rows
# x 128 (a 512-token chunk at group 6) is the largest tile the v5e compiler
# accepted inside its 16 MiB scoped limit; see tests/ops/test_tpu_compile.py.
_MAX_Q_ROWS = 3072


def _q_tile_tokens(T: int, group: int) -> int:
    """Largest halving of ``T`` whose rows fit ``_MAX_Q_ROWS`` (``T`` itself
    when it already fits — the engine's buckets are powers of two)."""
    tq = T
    while tq * group > _MAX_Q_ROWS and tq % 2 == 0:
        tq //= 2
    return tq


def _kernel(tables_ref, start_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, *rest,
            bs, scale, use_kv_scale, group, tq, window=None, block=None):
    if use_kv_scale:
        ks_ref, vs_ref, o_ref, m_s, l_s, acc_s = rest
    else:
        o_ref, m_s, l_s, acc_s = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    kh = pl.program_id(1)
    j = pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    start = start_ref[b]
    qlen = len_ref[b]
    t0 = pl.program_id(2) * tq  # first query token of this tile
    live = jnp.minimum(qlen - t0, tq)  # live tokens in this tile (<= 0: none)
    # highest live query position: blocks past it contribute nothing to any row
    hi = start + t0 + live - 1
    if block is not None:  # its block's last position
        hi = hi | (block - 1)
    # the logical block this step reads: the table's j-th, or with a window the j-th from the tile's first
    jb = j if window is None else jnp.maximum(start + t0 - (window - 1), 0) // bs + j

    @pl.when((live > 0) & (jb * bs <= hi))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [tq*group, H]
        k = k_ref[...].astype(jnp.float32)  # [bs, H]
        v = v_ref[...].astype(jnp.float32)
        if use_kv_scale:  # int8/fp8 cache: dequant the streamed block in VMEM
            # the scale tile is the block's [bs, K] rows: keep this head's column
            ks, vs = ks_ref[...], vs_ref[...]
            mine = jax.lax.broadcasted_iota(jnp.int32, ks.shape, 1) == kh
            k = k * jnp.sum(jnp.where(mine, ks, 0.0), axis=-1, keepdims=True)
            v = v * jnp.sum(jnp.where(mine, vs, 0.0), axis=-1, keepdims=True)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # [tq*group, bs]
        kv_pos = jb * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        t = t0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group  # query token idx
        valid = (kv_pos <= (start + t if block is None else (start + t) | (block - 1))) & (t < qlen)
        if window is not None:
            valid &= kv_pos > start + t - window
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot(p, v)
        m_s[...] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        # dead rows (t >= q_lens, or q_lens == 0) kept l == 0 -> exact zeros
        o_ref[0, 0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-37)).astype(o_ref.dtype)


def ragged_paged_attention(
    q: jnp.ndarray,  # [B, T, N, H] new-token queries (rows past q_lens ignored)
    kv: jnp.ndarray,  # [L, 2, num_blocks, bs, K*H] the whole pool
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    q_start: jnp.ndarray,  # [B] absolute position of q[:, 0]
    q_lens: jnp.ndarray,  # [B] valid new tokens per sequence (0 = inactive row)
    layer,  # int32 scalar: the pool layer to read
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    kv_scale: Optional[jnp.ndarray] = None,  # [L, 2, num_blocks, bs, K] quantized-pool scales
    window: Optional[int] = None,  # static: positions a query sees, itself included (None: all before it)
    block: Optional[int] = None,  # static, a power of two: a query sees its whole block of this many positions
) -> jnp.ndarray:
    """One-launch attention for a ragged mixed prefill/decode batch.

    Query token t of row b attends kv positions ``[0, q_start[b] + t]`` of
    pool layer ``layer``, read through ``block_tables[b]`` — the KV for
    positions ``< q_start`` was written by earlier chunks/steps, the chunk's
    own KV by this step's scatter (ordered before the kernel by jit data
    dependence on the pool). With ``window`` it attends positions
    ``(q_start[b] + t - window, q_start[b] + t]`` only and the grid's block
    axis is sized by the window (module docstring). With ``block`` it attends
    positions ``[0, (q_start[b] + t) | (block - 1)]``. Returns ``[B, T, N, H]``
    with rows ``t >= q_lens[b]`` zeroed.
    """
    if block is not None and (window is not None or block & (block - 1)):
        raise ValueError(f"block={block}: a power of two, and no window beside it")
    B, T, N, H = q.shape
    bs, K = kv.shape[3], kv.shape[4] // H
    group = N // K
    max_blocks = block_tables.shape[1]
    scale = scale if scale is not None else H**-0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    use_kv_scale = kv_scale is not None
    tq = _q_tile_tokens(T, group)
    rows = tq * group

    # [B, T, N, H] -> [B, K, T*group, H]: head n = kh*group + g, so T and group
    # interleave as rows (t, g) -> row t*group + g of kv head kh
    qf = q.reshape(B, T, K, group, H).transpose(0, 2, 1, 3, 4).reshape(B, K, T * group, H)
    q_spec = pl.BlockSpec((1, 1, rows, H), lambda b, kh, qt, j, t, s, n, l: (b, kh, qt, 0))

    n_steps = max_blocks if window is None else min(-(-(window - 1 + tq) // bs) + 1, max_blocks)

    def logical(b, qt, j, s):
        """The table entry grid step j reads: the j-th, or with a window the j-th from the tile's first block
        (a step past the table names its last entry again: no new fetch, and the body skips it)."""
        if window is None:
            return j
        return jnp.minimum(jnp.maximum(s[b] + qt * tq - (window - 1), 0) // bs + j, max_blocks - 1)

    def pool_spec(plane, width, per_head):
        # rows of block tables[b, j] in one plane of layer l[0], in place in the
        # pool: kv head kh's H lanes of the KV rows, or the whole scale row
        return pl.BlockSpec(
            (None, None, None, bs, width),
            lambda b, kh, qt, j, t, s, n, l: (l[0], plane, t[b, logical(b, qt, j, s)], 0, kh if per_head else 0))

    in_specs = [q_spec, pool_spec(0, H, True), pool_spec(1, H, True)]
    operands = [qf, kv, kv]
    if use_kv_scale:
        in_specs += [pool_spec(0, K, False), pool_spec(1, K, False)]
        operands += [kv_scale, kv_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, K, T // tq, n_steps),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),  # m
            pltpu.VMEM((rows, 1), jnp.float32),  # l
            pltpu.VMEM((rows, H), jnp.float32),  # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, scale=scale, use_kv_scale=use_kv_scale,
                          group=group, tq=tq, window=window, **({} if block is None else {"block": block})),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, T * group, H), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ragged_paged_attention",
    )(block_tables.astype(jnp.int32), q_start.astype(jnp.int32),
      q_lens.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1), *operands)
    return out.reshape(B, K, T, group, H).transpose(0, 2, 1, 3, 4).reshape(B, T, N, H)
