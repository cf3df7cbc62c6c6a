"""Pallas TPU flash attention (causal, GQA-aware), forward AND backward.

Counterpart of the reference's attention custom ops (csrc/gpu/append_attention.cu,
FlashAttention-2 dispatch in llama/fusion_ops.py:147, flash_attn_bwd.cc) and of
FlashMask packed-batch semantics (fusion_ops.py:223-238) via ``segment_ids``:
an O(T)-memory fused attention kernel family tiled for the MXU.

Structure (classic flash-attention-2 schedule):
- forward: grid = (batch*heads, T/block_q, S/block_kv); the kv axis is innermost
  and sequential ("arbitrary"), carrying VMEM scratch accumulators (m, l, acc);
  emits the per-row logsumexp L = m + log(l) as a residual for the backward;
- GQA maps query-head blocks onto shared kv heads in the BlockSpec index maps —
  no materialized repeat;
- backward: two kernels re-streaming K/V — dq (kv innermost) and dk/dv
  (q innermost), with p recomputed from the saved logsumexp and
  delta = rowsum(dO*O) precomputed by XLA. dk/dv accumulate per KV head INSIDE
  the kernel: the grid batch axis is B*K and the innermost sequential axis
  walks (query-head-in-group, q-block) pairs, so for an N/K = g GQA model the
  dk/dv output traffic and K/V re-streaming drop by g× versus the per-query-head
  scheme. The dk/dv kernel works on the transposed score tile [block_kv, block_q],
  so P^T dO and dS^T Q are plain matmuls and no tile is transposed;
- ``segment_ids`` restricts attention to same-segment tokens (ZeroPadding packed
  batches); ``window`` adds the mistral sliding-window lower bound;
- the value head may be narrower or wider than the query/key head (latent
  attention: 192 and 128): v, dO, the output, its accumulator and dV carry the
  value width, q, k, dQ and dK the key width. Alike, the calls are what they were.

What one grid step does. A step costs about 0.35 us whatever it does, so it has to do a tile's worth of work:
- the tile comes from the shape (``_blocks``): ``_TILE`` on both axes, cut to the sequence; half the VMEM asked for;
- a step the causal mask (or the window) skips names the block already resident: the index maps clip to the
  first / last block the row of tiles needs (``_kv_blocks``, ``_q_blocks``), so Pallas issues no copy for it;
- the tile the diagonal crosses (the last of a causal row) has a body of its own, walked in strips of ``_strip``
  rows of the accumulators (q rows in the forward and dq, kv rows in dkv; static slices): strip ``i`` takes the
  columns its rows see, so the rectangles that ``cols <= rows`` masks whole are not computed (``_strips``: 10 of a
  tile's 16 sub-blocks at strips of 256: 56.25% of the score square at 2,048 tokens where whole tiles were 75%,
  51.6% at 8,192 where they were 56.25%) and a row's sums keep their order: the results are the whole tile's bit for
  bit. Below the diagonal dq and dkv run the whole-tile body; the forward walks every tile in full strips (one
  strip's products beside the last one's softmax: 5-7% of a call). Not causal, a caller's unequal blocks, a tile of
  one strip: the whole-tile body, the kernels as they were. XLA is told what a call computes (``_cost``);
- every sub-block that runs builds the element mask (``_visible`` drops the tests the call rules out, and below
  the diagonal the causal one): segments, a window and a partial last tile mask inside it and skip nothing more;
- the MXU takes the operands in the inputs' precision with float32 accumulation (bf16 q, k, v, dO as they are; p
  and dS cast to the inputs' dtype); running maximum, sum, logsumexp, delta and every accumulator stay float32;
- per-row statistics never have the shape [rows, 1]: logsumexp and delta cross HBM as lane-dense [1, T] rows (a
  [T, 1] array is padded to 128 lanes there) and live in VMEM as [rows, 128] with every lane alike (``_across``).

Once a program: ``_flash_fwd`` and ``_flash_bwd`` sit behind ``jax.jit``, so a kernel's body is traced and lowered to
Mosaic once for each distinct call, not once a call site (an unrolled model paid both a layer on every start, warm
compile cache or not: ``setup_s``); the compiled program is what it was. Under remat the forward rule names the two
residuals its backward reads (``flash_out``, ``flash_lse``: ``_fwd``); a policy that saves both runs the forward once.

Left for later (PERF.md section 7, ROADMAP S3): two 64-wide heads in one 128-lane tile (at 64 the kernels are bound
by MXU passes half empty); the lower edge of a window (the diagonal's mirror image); one backward kernel for two.
Off-TPU (tests), the kernels run in Pallas interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

NEG_INF = -1e30

_NT = (((1,), (1,)), ((), ()))  # [m, h] x [n, h] -> [m, n]


def _nt(a, b):
    return jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)


def _dot(a, b):
    return jax.lax.dot(a, b, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------- tiles
# Edge of the tile a grid step aims for, on both axes and in all three kernels
# (chip sweeps on v5e), and the most scoped VMEM the chip's compiler needs for
# such a step (head_dim 256, float32, segments and a window, compiled for v5p;
# v5e and v6e fit every case into their defaults). PERF.md section 6, PR 28.
_TILE = 1024
_VMEM_STEP = 24 << 20


def _vmem_share():
    """Half the chip's VMEM, which a step with a tile over 512 x 512 asks for:
    64 MiB on v5e, where the training step around the kernels is 5% faster for
    it than with 32 MiB or the compiler's default of 16 (the kernels alone are
    3% slower). None in a process without a chip (interpret mode, a compile for
    a described chip): the compiler's default."""
    try:
        return pltpu.get_tpu_info().vmem_capacity_bytes // 2
    except ValueError:
        return None


def _blocks(block_q, block_kv, q_len, kv_len):
    """(block_q, block_kv): the caller's where given, else ``_TILE``, halved on a
    chip whose share of VMEM does not hold such a step (v2 to v4 have 16 MiB in
    all, a 1024 x 1024 step needs 17 to 21 MB there and a 512 x 512 one compiles
    in every case); cut to the sequence. A block is then the whole axis or a
    multiple of 128 for every shape the dispatcher's gate (``T % 128 == 0``)
    lets through; an axis it does not divide ends in a partial block, which
    the mask covers."""
    share = _vmem_share()
    tile = _TILE if share is None or share >= _VMEM_STEP else _TILE // 2
    return min(block_q or tile, q_len), min(block_kv or tile, kv_len)


def _compiler_params(block_q, block_kv):
    large = block_q * block_kv > (_TILE // 2) ** 2
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                vmem_limit_bytes=_vmem_share() if large else None)


# ---------------------------------------------------------------- which tiles run
def _kv_blocks(qi, block_q, block_kv, n_k, causal, window):
    """First and last kv block the q block ``qi`` attends to."""
    lo, hi = 0, n_k - 1
    if causal:
        hi = jnp.minimum((qi * block_q + block_q - 1) // block_kv, hi)
    if window is not None:
        lo = jnp.maximum(qi * block_q - window + 1, 0) // block_kv
    return lo, hi


def _q_blocks(ki, block_q, block_kv, n_q, causal, window):
    """First and last q block that attends to the kv block ``ki``."""
    lo, hi = 0, n_q - 1
    if causal:
        lo = ki * block_kv // block_q
    if window is not None:
        hi = jnp.minimum((ki * block_kv + block_kv + window - 2) // block_q, hi)
    return lo, hi


# A tile's geometry, as ``_visible`` takes it:
# (q_start, k_start, block_q, block_kv, causal, window, q_len, kv_len)
def _visible(q_start, k_start, block_q, block_kv, causal, window, q_len, kv_len,
             seg_q, seg_k, q_axis=0):
    """Element-level visibility mask for one score tile, [block_q, block_kv] or,
    with ``q_axis=1``, its transpose. ``seg_q`` / ``seg_k`` broadcast against
    each other along the tile ([block_q, 1] and [1, block_kv], or transposed).
    None where nothing masks."""
    s_shape = (block_q, block_kv) if q_axis == 0 else (block_kv, block_q)
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s_shape, q_axis)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s_shape, 1 - q_axis)
    tests = []
    if kv_len % block_kv:
        tests.append(cols < kv_len)
    if q_len % block_q:
        tests.append(rows < q_len)
    if causal:
        tests.append(cols <= rows)
    if window is not None:
        tests.append(cols > rows - window)
    if seg_q is not None:
        tests.append(seg_q == seg_k)
    return functools.reduce(jnp.logical_and, tests) if tests else None


# Per-row statistics (running maximum and sum, logsumexp, delta) live in VMEM as
# [rows, 128] with every lane alike: arithmetic on them is dense, and meeting a
# [rows, width] tile is a repeat of whole registers, not a broadcast across lanes.
_LANES = 128


def _across(stat, width):
    """[rows, 128] lane-replicated statistic against a [rows, width] tile."""
    if width % _LANES == 0:
        return jnp.tile(stat, (1, width // _LANES))
    if width < _LANES:
        return stat[:, :width]
    return jnp.broadcast_to(stat[:, :1], (stat.shape[0], width))


def _replicated(row):
    """[1, n] lane-dense row of statistics -> [n, 128], through the XLU.
    Logsumexp and delta cross HBM as rows: a [T, 1] array there is padded to
    128 lanes, 128 times what the numbers take."""
    return jnp.transpose(jnp.broadcast_to(row, (_LANES, row.shape[1])))


def _row(stat):
    """[n, 128] lane-replicated statistic -> [1, n] lane-dense row."""
    return jnp.transpose(stat)[0:1, :]


def _zero_oob(x, start, limit, block, axis=0):
    """Zero what lies past ``limit`` along ``axis`` (Pallas pads partial edge
    blocks with garbage — even p=0 coefficients turn garbage into NaN via 0*NaN)."""
    if limit % block == 0:
        return x
    idx = start + jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    return jnp.where(idx < limit, x.astype(jnp.float32), 0.0).astype(x.dtype)


# ---------------------------------------------------------------- strips inside a tile
# Height of the strips a grid step walks its tile in (chip sweep on v5e at 64/64 x 2,048 and
# 192/128 x 8,192: 512 is slower everywhere, 128 within 1%. PERF.md section 6, PRs 41 and 42).
_STRIP = 256


def _strip(block_q, block_kv, causal):
    """Height of the strips a step walks its tile in, from the shape: ``_STRIP`` where it
    divides the tile into several, else 128 (whole sublanes of the operands, whole lanes of
    the rows of statistics and segment ids). None where the whole-tile body runs: a call that
    is not causal, unequal blocks (a caller's own), a tile of one strip."""
    if not causal or block_q != block_kv:
        return None
    return next((strip for strip in (_STRIP, _LANES) if block_q % strip == 0 and strip < block_q), None)


def _strips(block, strip, crossed, transposed=False):
    """(rows, columns) of the sub-blocks a step computes of its tile: strip ``i`` of the
    tile's rows, which are the accumulators' rows, so each is updated once a tile and a
    row's sums keep their order. Of a tile the diagonal crosses a strip takes the columns
    its rows see: ``[0, (i + 1) strip)`` of the [q, kv] tile, ``[i strip, block)`` of the
    transposed one; what is left out is what ``cols <= rows`` masks whole."""
    for start in range(0, block, strip):
        rows = slice(start, start + strip)
        yield rows, slice(0, block) if not crossed else slice(start, block) if transposed else slice(0, rows.stop)


def computed_elements(q_len, kv_len, block_q, block_kv, strip, causal, window=None):
    """Score elements one head of a call computes: a tile that runs, whole; of the tile the
    diagonal crosses, with strips, the sub-blocks that hold a visible element."""
    n_q, n_k = pl.cdiv(q_len, block_q), pl.cdiv(kv_len, block_kv)
    tiles = 0
    for qi in range(n_q):  # ``_kv_blocks`` on plain integers
        hi = min((qi * block_q + block_q - 1) // block_kv, n_k - 1) if causal else n_k - 1
        lo = max(qi * block_q - window + 1, 0) // block_kv if window is not None else 0
        tiles += hi - lo + 1
    if strip is None:
        return tiles * block_q * block_kv
    crossed = sum((r.stop - r.start) * (c.stop - c.start) for r, c in _strips(block_q, strip, True))
    return (tiles - n_q) * block_q * block_kv + n_q * crossed


def _cost(elements, heads, qk_products, v_products, head_dim, value_dim, arrays):
    """What a call does, for XLA's schedule round the kernel (with it ``seq8k``'s step is 3.5 ms shorter on the
    chip: PERF.md section 6, PR 42) and the trace's operation statistics: ``elements`` scores a head
    (``computed_elements``), each an exponential and a row of every product."""
    return pl.CostEstimate(
        flops=2 * heads * elements * (qk_products * head_dim + v_products * value_dim),
        transcendentals=heads * elements,
        bytes_accessed=sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize for a in arrays))


def _run(strip, tile, lo, hi, crossed, whole, walk, below_in_strips):
    """The body of the step at ``tile`` of its row of tiles ``lo..hi``. Without strips ``whole``. With them (causal,
    equal blocks) the diagonal crosses the tile ``crossed``, an end of the row, which runs ``walk(True)``; a tile
    below it runs ``whole``, as it was, or ``walk(False)``: full strips, where the chip is faster for them."""
    runs = jnp.logical_and(lo <= tile, tile <= hi)
    if strip is None:
        pl.when(runs)(whole)
    else:
        pl.when(jnp.logical_and(runs, tile != crossed))(functools.partial(walk, False) if below_in_strips else whole)
        pl.when(tile == crossed)(functools.partial(walk, True))


def _sub_visible(geometry, q_rows, k_rows, below, seg_q, seg_k, q_axis=0):
    """``_visible`` for the sub-block ``q_rows`` x ``k_rows`` of a tile that ``_run`` walks in strips. ``below``:
    the tile lies wholly below the diagonal, so the causal test is left out. Of a ragged last tile the rows
    past the sequence are masked (its columns past it are ``cols > rows`` of the rows that are left)."""
    q_start, k_start, block_q, _, causal, window, q_len, _ = geometry
    n_q, n_k = q_rows.stop - q_rows.start, k_rows.stop - k_rows.start
    valid = _visible(q_start + q_rows.start, k_start + k_rows.start, n_q, n_k, causal and not below, window,
                     n_q, n_k, seg_q, seg_k, q_axis)
    if q_len % block_q:
        rows = jax.lax.broadcasted_iota(jnp.int32, (n_q, n_k) if q_axis == 0 else (n_k, n_q), q_axis)
        inside = q_start + q_rows.start + rows < q_len
        valid = inside if valid is None else jnp.logical_and(valid, inside)
    return valid


def _sub(ref, rows, start, limit, block, axis=0):
    """Rows ``rows`` (columns, with ``axis=1``) of the block in ``ref``, which starts at ``start`` of ``limit``."""
    x = ref[0, rows, :] if axis == 0 else ref[0, :, rows]
    return _zero_oob(x, start + rows.start, limit, block, axis)


# ---------------------------------------------------------------- forward
def _fa_kernel(q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, lse_ref,
               m_scratch, l_scratch, acc_scratch, *,
               scale, block_q, block_kv, causal, window, q_len, kv_len, use_segments, strip):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    q_start = qi * block_q
    k_start = ki * block_kv
    geometry = (q_start, k_start, block_q, block_kv, causal, window, q_len, kv_len)
    lo, hi = _kv_blocks(qi, block_q, block_kv, n_k, causal, window)

    def update(rows, q, k, v, valid):
        """Online softmax of the accumulators' rows ``rows`` (``...``: all) over the scores of ``q`` against ``k``."""
        s = _nt(q, k) * scale
        if valid is not None:
            s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scratch[rows]  # [rows, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _across(m_new, s.shape[1]))
        if valid is not None:
            p = jnp.where(valid, p, 0.0)  # exp(NEG-NEG)=1 on fully-masked rows
        alpha = jnp.exp(m_prev - m_new)
        l_scratch[rows] = alpha * l_scratch[rows] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scratch[rows] = acc_scratch[rows] * _across(alpha, acc_scratch.shape[1]) + _dot(p.astype(v.dtype), v)
        m_scratch[rows] = m_new

    def whole():
        q = _zero_oob(q_ref[0], q_start, q_len, block_q)
        k = _zero_oob(k_ref[0], k_start, kv_len, block_kv)
        v = _zero_oob(v_ref[0], k_start, kv_len, block_kv)
        valid = _visible(*geometry, sq_ref[0] if use_segments else None,
                         sk_ref[0] if use_segments else None)
        update(..., q, k, v, valid)

    def walk(crossed):
        for rows, cols in _strips(block_q, strip, crossed):
            valid = _sub_visible(geometry, rows, cols, not crossed,
                                 sq_ref[0, rows, :] if use_segments else None,
                                 sk_ref[0, :, cols] if use_segments else None)
            update(rows, _sub(q_ref, rows, q_start, q_len, block_q), _sub(k_ref, cols, k_start, kv_len, block_kv),
                   _sub(v_ref, cols, k_start, kv_len, block_kv), valid)

    # full strips below the diagonal too: one strip's products run beside the last strip's softmax
    _run(strip, ki, lo, hi, qi, whole, walk, below_in_strips=True)

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_scratch[...], 1e-37)
        o_ref[0] = (acc_scratch[...] / _across(l, acc_scratch.shape[1])).astype(o_ref.dtype)
        lse_ref[0] = _row(m_scratch[...] + jnp.log(l))  # [1, block_q]


def _fold(x):  # [B, T, N, H] -> [B*N, T, H]
    B, T, N, H = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * N, T, H)


def _segments(segments, B, T):
    """[B, T, 1] and [B, 1, T] layouts of the segment ids (zeros without): TPU
    tiling requires the last two block dims divisible by (8, 128) or equal to the
    array dims, so per-position data rides a trailing or a middle unit dim."""
    seg = segments if segments is not None else jnp.zeros((B, T), jnp.int32)
    return seg[:, :, None], seg[:, None, :]


# A program traces and lowers a kernel once for every (shapes, these arguments) it calls it with, not once a call
# site: unrolled layers, and a layer's recomputed forward, share the jaxpr, and the module holds one function for it.
_once_a_program = functools.partial(
    jax.jit, static_argnames=("scale", "causal", "window", "block_q", "block_kv", "interpret"))


@_once_a_program
def _flash_fwd(q, k, v, segments, scale, causal, window, block_q, block_kv, interpret):
    B, T, N, H = q.shape
    Hv = v.shape[-1]  # the value head: the accumulator's and the output's width, H where the heads are alike
    if causal and T != k.shape[1]:
        raise ValueError(
            f"causal flash_attention requires T == S (got T={T}, S={k.shape[1]}); "
            "cross-length causal (KV cache) goes through the XLA dispatcher path"
        )
    if window is not None and not causal:
        raise ValueError("sliding window requires causal=True (matches the XLA dispatcher)")
    S, K = k.shape[1], k.shape[2]
    group = N // K
    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    use_seg = segments is not None
    seg_col, seg_row = _segments(segments, B, T)
    block_q, block_kv = _blocks(block_q, block_kv, T, S)
    n_q, n_k = pl.cdiv(T, block_q), pl.cdiv(S, block_kv)

    def kv_block(qi, ki):  # a skipped step names the nearest block its row of tiles needs: no copy
        return jnp.clip(ki, *_kv_blocks(qi, block_q, block_kv, n_k, causal, window))

    strip = _strip(block_q, block_kv, causal)
    kernel = functools.partial(
        _fa_kernel, scale=scale, block_q=block_q, block_kv=block_kv,
        causal=causal, window=window, q_len=T, kv_len=S, use_segments=use_seg, strip=strip,
    )
    out_shape = [
        jax.ShapeDtypeStruct((B * N, T, Hv), q.dtype),
        jax.ShapeDtypeStruct((B * N, 1, T), jnp.float32),
    ]
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * N, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, H), lambda bn, qi, ki: (bn, qi, 0)),
            pl.BlockSpec((1, block_kv, H), lambda bn, qi, ki: (bn // group, kv_block(qi, ki), 0)),
            pl.BlockSpec((1, block_kv, Hv), lambda bn, qi, ki: (bn // group, kv_block(qi, ki), 0)),
            pl.BlockSpec((1, block_q, 1), lambda bn, qi, ki: (bn // N, qi, 0)),
            pl.BlockSpec((1, 1, block_kv), lambda bn, qi, ki: (bn // N, 0, kv_block(qi, ki))),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Hv), lambda bn, qi, ki: (bn, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bn, qi, ki: (bn, 0, qi)),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # m
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # l
            pltpu.VMEM((block_q, Hv), jnp.float32),  # acc
        ],
        compiler_params=_compiler_params(block_q, block_kv),
        cost_estimate=_cost(computed_elements(T, S, block_q, block_kv, strip, causal, window), B * N, 1, 1, H, Hv,
                            [qf, kf, vf, *out_shape]),
        interpret=interpret,
        name="flash_attention_fwd",
    )(qf, kf, vf, seg_col, seg_row)
    return out.reshape(B, N, T, Hv).transpose(0, 2, 1, 3), lse  # lse: [B*N, 1, T]


# ---------------------------------------------------------------- backward
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
                   dq_ref, dq_scratch, lse_scratch, delta_scratch, *,
                   scale, block_q, block_kv, causal, window, q_len, kv_len, use_segments, strip):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scratch[...] = jnp.zeros_like(dq_scratch)
        lse_scratch[...] = _replicated(lse_ref[0])  # once a row of tiles
        delta_scratch[...] = _replicated(delta_ref[0])

    q_start = qi * block_q
    k_start = ki * block_kv
    geometry = (q_start, k_start, block_q, block_kv, causal, window, q_len, kv_len)
    lo, hi = _kv_blocks(qi, block_q, block_kv, n_k, causal, window)

    def update(rows, q, k, v, do, lse, delta, valid):
        """dQ of the rows ``rows`` (``...``: all) from the scores of ``q`` against ``k``."""  # lse, delta: [rows, 128]
        p = jnp.exp(_nt(q, k) * scale - _across(lse, k.shape[0]))
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        ds = p * (_nt(do, v) - _across(delta, k.shape[0]))  # times scale, once, in _finalize
        dq_scratch[rows] += _dot(ds.astype(k.dtype), k)

    def whole():
        q = _zero_oob(q_ref[0], q_start, q_len, block_q)
        k = _zero_oob(k_ref[0], k_start, kv_len, block_kv)
        v = _zero_oob(v_ref[0], k_start, kv_len, block_kv)
        do = _zero_oob(do_ref[0], q_start, q_len, block_q)
        lse = lse_scratch[...]  # [block_q, 128]
        # delta rows past q_len are Pallas edge-block garbage; p=0 there cannot
        # save ds (0 * NaN = NaN)
        delta = _zero_oob(delta_scratch[...], q_start, q_len, block_q)
        valid = _visible(*geometry, sq_ref[0] if use_segments else None,
                         sk_ref[0] if use_segments else None)
        update(..., q, k, v, do, lse, delta, valid)

    def walk(crossed):
        for rows, cols in _strips(block_q, strip, crossed):
            valid = _sub_visible(geometry, rows, cols, not crossed,
                                 sq_ref[0, rows, :] if use_segments else None,
                                 sk_ref[0, :, cols] if use_segments else None)
            update(rows, _sub(q_ref, rows, q_start, q_len, block_q), _sub(k_ref, cols, k_start, kv_len, block_kv),
                   _sub(v_ref, cols, k_start, kv_len, block_kv), _sub(do_ref, rows, q_start, q_len, block_q),
                   lse_scratch[rows, :], _zero_oob(delta_scratch[rows, :], q_start + rows.start, q_len, block_q),
                   valid)

    _run(strip, ki, lo, hi, qi, whole, walk, below_in_strips=False)

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[0] = (dq_scratch[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
                    dk_ref, dv_ref, dk_scratch, dv_scratch, *,
                    scale, block_q, block_kv, causal, window, q_len, kv_len, use_segments,
                    n_q, strip):
    """Works on the transposed tile: scores [block_kv, block_q], logsumexp,
    delta and the q-side segment ids as [1, block_q] rows."""
    ki = pl.program_id(1)
    j = pl.program_id(2)  # walks (query-head-in-group, q-block) pairs
    n_j = pl.num_programs(2)
    qi = j % n_q

    @pl.when(j == 0)
    def _init():
        dk_scratch[...] = jnp.zeros_like(dk_scratch)
        dv_scratch[...] = jnp.zeros_like(dv_scratch)

    q_start = qi * block_q
    k_start = ki * block_kv
    geometry = (q_start, k_start, block_q, block_kv, causal, window, q_len, kv_len)
    lo, hi = _q_blocks(ki, block_q, block_kv, n_q, causal, window)

    def update(rows, q, k, v, do, lse, delta, valid):
        """dK, dV of the kv rows ``rows`` from the scores of ``k`` against ``q``."""  # lse, delta: [1, q]
        pt = jnp.exp(_nt(k, q) * scale - lse)  # p^T
        if valid is not None:
            pt = jnp.where(valid, pt, 0.0)
        dv_scratch[rows] += _dot(pt.astype(do.dtype), do)
        dst = pt * (_nt(v, do) - delta)  # dS^T; times scale, once, in _finalize
        dk_scratch[rows] += _dot(dst.astype(q.dtype), q)

    def whole():
        q = _zero_oob(q_ref[0], q_start, q_len, block_q)
        k = _zero_oob(k_ref[0], k_start, kv_len, block_kv)
        v = _zero_oob(v_ref[0], k_start, kv_len, block_kv)
        do = _zero_oob(do_ref[0], q_start, q_len, block_q)
        lse = lse_ref[0]  # [1, block_q]
        # as in dq; here the reduction over q would spread the NaN over dk
        delta = _zero_oob(delta_ref[0], q_start, q_len, block_q, axis=1)
        valid = _visible(*geometry, sq_ref[0] if use_segments else None,
                         sk_ref[0] if use_segments else None, q_axis=1)
        update(..., q, k, v, do, lse, delta, valid)

    def walk(crossed):
        for rows, cols in _strips(block_kv, strip, crossed, transposed=True):  # kv rows, q columns
            valid = _sub_visible(geometry, cols, rows, not crossed,
                                 sq_ref[0, :, cols] if use_segments else None,
                                 sk_ref[0, rows, :] if use_segments else None, q_axis=1)
            update(rows, _sub(q_ref, cols, q_start, q_len, block_q), _sub(k_ref, rows, k_start, kv_len, block_kv),
                   _sub(v_ref, rows, k_start, kv_len, block_kv), _sub(do_ref, cols, q_start, q_len, block_q),
                   lse_ref[0, :, cols], _sub(delta_ref, cols, q_start, q_len, block_q, axis=1), valid)

    _run(strip, qi, lo, hi, ki, whole, walk, below_in_strips=False)

    @pl.when(j == n_j - 1)
    def _finalize():
        dk_ref[0] = (dk_scratch[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scratch[...].astype(dv_ref.dtype)


@_once_a_program
def _flash_bwd(q, k, v, segments, out, lse, g, scale, causal, window, block_q, block_kv, interpret):
    B, T, N, H = q.shape
    S, K, Hv = k.shape[1], k.shape[2], v.shape[-1]  # dO and dV at the value head's width, dQ and dK at the key's
    group = N // K
    qf, kf, vf, dof = _fold(q), _fold(k), _fold(v), _fold(g)
    # from ``out`` and ``g`` as they come, the small result folded: ``out`` is read
    # nowhere else, so a saved ``out`` (remat) is never relaid for the kernels
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # [B, T, N]
    delta = delta.transpose(0, 2, 1).reshape(B * N, 1, T)
    use_seg = segments is not None
    seg_col, seg_row = _segments(segments, B, T)
    # dq: as the forward. Logsumexp and delta are [B*N, 1, T] rows in both kernels
    bq, bkv = _blocks(block_q, block_kv, T, S)
    n_q, n_k = pl.cdiv(T, bq), pl.cdiv(S, bkv)
    strip = _strip(bq, bkv, causal)
    elements = computed_elements(T, S, bq, bkv, strip, causal, window)
    common = dict(scale=scale, causal=causal, window=window, q_len=T, kv_len=S, use_segments=use_seg, strip=strip)

    def kv_block(qi, ki):
        return jnp.clip(ki, *_kv_blocks(qi, bq, bkv, n_k, causal, window))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common, block_q=bq, block_kv=bkv),
        grid=(B * N, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, H), lambda bn, qi, ki: (bn, qi, 0)),
            pl.BlockSpec((1, bkv, H), lambda bn, qi, ki: (bn // group, kv_block(qi, ki), 0)),
            pl.BlockSpec((1, bkv, Hv), lambda bn, qi, ki: (bn // group, kv_block(qi, ki), 0)),
            pl.BlockSpec((1, bq, Hv), lambda bn, qi, ki: (bn, qi, 0)),
            pl.BlockSpec((1, 1, bq), lambda bn, qi, ki: (bn, 0, qi)),
            pl.BlockSpec((1, 1, bq), lambda bn, qi, ki: (bn, 0, qi)),
            pl.BlockSpec((1, bq, 1), lambda bn, qi, ki: (bn // N, qi, 0)),
            pl.BlockSpec((1, 1, bkv), lambda bn, qi, ki: (bn // N, 0, kv_block(qi, ki))),
        ],
        out_specs=pl.BlockSpec((1, bq, H), lambda bn, qi, ki: (bn, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * N, T, H), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, H), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32), pltpu.VMEM((bq, _LANES), jnp.float32)],
        compiler_params=_compiler_params(bq, bkv),
        cost_estimate=_cost(elements, B * N, 2, 1, H, Hv, [qf, kf, vf, dof, lse, delta, qf]),  # dq: q's shape
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(qf, kf, vf, dof, lse, delta, seg_col, seg_row)

    # dk/dv: grid batch axis is the B*K kv heads; the sequential axis walks the
    # group*n_q (query-head-in-group, q-block) pairs so dk/dv for a kv head
    # accumulate in VMEM across its whole query group (no outside group-sum).
    # The tile is transposed: q-side segment ids a [.., 1, T] row, k-side a column.
    def q_head(bk, j):
        return bk * group + j // n_q

    def q_block(ki, j):
        return jnp.clip(j % n_q, *_q_blocks(ki, bq, bkv, n_q, causal, window))

    dkv_shape = [
        jax.ShapeDtypeStruct((B * K, S, H), jnp.float32),
        jax.ShapeDtypeStruct((B * K, S, Hv), jnp.float32),
    ]
    dk_p, dv_p = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common, block_q=bq, block_kv=bkv, n_q=n_q),
        grid=(B * K, n_k, group * n_q),
        in_specs=[
            pl.BlockSpec((1, bq, H), lambda bk, ki, j: (q_head(bk, j), q_block(ki, j), 0)),
            pl.BlockSpec((1, bkv, H), lambda bk, ki, j: (bk, ki, 0)),
            pl.BlockSpec((1, bkv, Hv), lambda bk, ki, j: (bk, ki, 0)),
            pl.BlockSpec((1, bq, Hv), lambda bk, ki, j: (q_head(bk, j), q_block(ki, j), 0)),
            pl.BlockSpec((1, 1, bq), lambda bk, ki, j: (q_head(bk, j), 0, q_block(ki, j))),
            pl.BlockSpec((1, 1, bq), lambda bk, ki, j: (q_head(bk, j), 0, q_block(ki, j))),
            pl.BlockSpec((1, 1, bq), lambda bk, ki, j: (bk // K, 0, q_block(ki, j))),
            pl.BlockSpec((1, bkv, 1), lambda bk, ki, j: (bk // K, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bkv, H), lambda bk, ki, j: (bk, ki, 0)),
            pl.BlockSpec((1, bkv, Hv), lambda bk, ki, j: (bk, ki, 0)),
        ],
        out_shape=dkv_shape,
        scratch_shapes=[
            pltpu.VMEM((bkv, H), jnp.float32),
            pltpu.VMEM((bkv, Hv), jnp.float32),
        ],
        compiler_params=_compiler_params(bq, bkv),
        cost_estimate=_cost(elements, B * N, 2, 2, H, Hv, [qf, kf, vf, dof, lse, delta, *dkv_shape]),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(qf, kf, vf, dof, lse, delta, seg_row, seg_col)

    dq = dq.reshape(B, N, T, H).transpose(0, 2, 1, 3)
    dk = dk_p.reshape(B, K, S, H).transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dv_p.reshape(B, K, S, Hv).transpose(0, 2, 1, 3).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------- public api
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def flash_attention(
    q: jnp.ndarray,  # [B, T, N, H]
    k: jnp.ndarray,  # [B, S, K, H]
    v: jnp.ndarray,  # [B, S, K, Hv]: the value head may differ from the key's (latent attention: 192 and 128)
    segment_ids: Optional[jnp.ndarray] = None,  # [B, T] packed-batch segments
    scale: Optional[float] = None,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: Optional[int] = None,  # None: the tile comes from the shape (_blocks)
    block_kv: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out, _ = _flash_fwd(q, k, v, segment_ids, scale, causal, window, block_q, block_kv, interpret)
    return out


def _fwd(q, k, v, segment_ids, scale, causal, window, block_q, block_kv, interpret):
    scale_v = scale if scale is not None else q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out, lse = _flash_fwd(q, k, v, segment_ids, scale_v, causal, window, block_q, block_kv, interpret)
    # What the backward reads and no caller can name: the residual ``out`` is the
    # value before the caller's name for the result ("core_attn"), and ``lse``
    # never leaves the rule. A remat policy that saves both names keeps the
    # backward from running this kernel again (llama/modeling.py:_remat_policy).
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, segment_ids, out, lse)


def _bwd(scale, causal, window, block_q, block_kv, interpret, residuals, g):
    q, k, v, segment_ids, out, lse = residuals
    scale_v = scale if scale is not None else q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    dq, dk, dv = _flash_bwd(q, k, v, segment_ids, out, lse, g,
                            scale_v, causal, window, block_q, block_kv, interpret)
    dseg = None if segment_ids is None else np.zeros(segment_ids.shape, jax.dtypes.float0)
    return dq, dk, dv, dseg


flash_attention.defvjp(_fwd, _bwd)
