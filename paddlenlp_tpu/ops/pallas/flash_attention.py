"""Pallas TPU flash attention (causal, GQA-aware), forward AND backward.

Counterpart of the reference's attention custom ops (csrc/gpu/append_attention.cu,
FlashAttention-2 dispatch in llama/fusion_ops.py:147, flash_attn_bwd.cc) and of
FlashMask packed-batch semantics (fusion_ops.py:223-238) via ``segment_ids``:
an O(T)-memory fused attention kernel family tiled for the MXU.

Structure (classic flash-attention-2 schedule):
- forward: grid = (batch*heads, T/block_q, S/block_kv); the kv axis is innermost
  and sequential ("arbitrary"), carrying VMEM scratch accumulators (m, l, acc);
  emits the per-row logsumexp L = m + log(l) as a residual for the backward;
- fully-invisible blocks are skipped under causal/window masking (@pl.when);
- GQA maps query-head blocks onto shared kv heads in the BlockSpec index maps —
  no materialized repeat;
- backward: two kernels re-streaming K/V — dq (kv innermost) and dk/dv
  (q innermost), with p recomputed from the saved logsumexp and
  delta = rowsum(dO*O) precomputed by XLA. dk/dv accumulate per KV head INSIDE
  the kernel: the grid batch axis is B*K and the innermost sequential axis
  walks (query-head-in-group, q-block) pairs, so for an N/K = g GQA model the
  dk/dv output traffic and K/V re-streaming drop by g× versus the per-query-head
  scheme (outputs were [B*N, S, H] + an XLA group-sum pass; now [B*K, S, H]);
- ``segment_ids`` restricts attention to same-segment tokens (ZeroPadding packed
  batches); ``window`` adds the mistral sliding-window lower bound.

Off-TPU (tests), the kernels run in Pallas interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _visible(s_shape, q_start, k_start, causal, window, q_len, kv_len, seg_q, seg_k):
    """Element-level visibility mask for one [block_q, block_kv] tile.

    ``seg_q`` is [block_q, 1] and ``seg_k`` is [1, block_kv] (the trailing/leading
    unit dims come from the TPU-tileable [B, T, 1] / [B, 1, S] segment layouts).
    """
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s_shape, 0)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s_shape, 1)
    valid = (cols < kv_len) & (rows < q_len)
    if causal:
        valid &= cols <= rows
    if window is not None:
        valid &= cols > rows - window
    if seg_q is not None:
        valid &= seg_q == seg_k
    return valid


def _zero_oob(x, start, limit):
    """Zero rows past ``limit`` (Pallas pads partial edge blocks with garbage —
    even p=0 coefficients turn garbage into NaN via 0*NaN)."""
    idx = start + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(idx < limit, x, 0.0)


def _block_runs(q_start, k_start, block_q, block_kv, causal, window):
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
    if window is not None:
        run = jnp.logical_and(run, k_start + block_kv - 1 > q_start - window) if causal else run
    return run


# ---------------------------------------------------------------- forward
def _fa_kernel(q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, lse_ref,
               m_scratch, l_scratch, acc_scratch, *,
               scale, block_q, block_kv, causal, window, q_len, kv_len, use_segments):
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
    run = _block_runs(q_start, k_start, block_q, block_kv, causal, window)

    @pl.when(run)
    def _compute():
        q = _zero_oob(q_ref[0].astype(jnp.float32), q_start, q_len)
        k = _zero_oob(k_ref[0].astype(jnp.float32), k_start, kv_len)
        v = _zero_oob(v_ref[0].astype(jnp.float32), k_start, kv_len)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        seg_q = sq_ref[0] if use_segments else None
        seg_k = sk_ref[0] if use_segments else None
        valid = _visible(s.shape, q_start, k_start, causal, window, q_len, kv_len, seg_q, seg_k)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scratch[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)  # exp(NEG-NEG)=1 on fully-masked rows
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scratch[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scratch[...] = acc_scratch[...] * alpha + jax.lax.dot(p, v)
        m_scratch[...] = m_new
        l_scratch[...] = l_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_scratch[...], 1e-37)
        o_ref[0] = (acc_scratch[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scratch[...] + jnp.log(l)  # [block_q, 1]


def _fold(x):  # [B, T, N, H] -> [B*N, T, H]
    B, T, N, H = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * N, T, H)


def _flash_fwd(q, k, v, segments, scale, causal, window, block_q, block_kv, interpret):
    B, T, N, H = q.shape
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
    seg = segments if use_seg else jnp.zeros((B, T), jnp.int32)
    # TPU tiling requires the last two block dims divisible by (8, 128) or equal
    # to the array dims — per-row 1D data rides a trailing/middle unit dim.
    seg_q3 = seg[:, :, None]  # [B, T, 1] -> block (1, block_q, 1)
    seg_k3 = seg[:, None, :]  # [B, 1, S] -> block (1, 1, block_kv)
    block_q = min(block_q, T)
    block_kv = min(block_kv, S)
    grid = (B * N, pl.cdiv(T, block_q), pl.cdiv(S, block_kv))

    kernel = functools.partial(
        _fa_kernel, scale=scale, block_q=block_q, block_kv=block_kv,
        causal=causal, window=window, q_len=T, kv_len=S, use_segments=use_seg,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, H), lambda bn, qi, ki: (bn, qi, 0)),
            pl.BlockSpec((1, block_kv, H), lambda bn, qi, ki, g=group: (bn // g, ki, 0)),
            pl.BlockSpec((1, block_kv, H), lambda bn, qi, ki, g=group: (bn // g, ki, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bn, qi, ki, n=N: (bn // n, qi, 0)),
            pl.BlockSpec((1, 1, block_kv), lambda bn, qi, ki, n=N: (bn // n, 0, ki)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, H), lambda bn, qi, ki: (bn, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bn, qi, ki: (bn, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * N, T, H), q.dtype),
            jax.ShapeDtypeStruct((B * N, T, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),  # m
            pltpu.VMEM((block_q, 1), jnp.float32),  # l
            pltpu.VMEM((block_q, H), jnp.float32),  # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(qf, kf, vf, seg_q3, seg_k3)
    return out.reshape(B, N, T, H).transpose(0, 2, 1, 3), lse[..., 0]


# ---------------------------------------------------------------- backward
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
                   dq_ref, dq_scratch, *,
                   scale, block_q, block_kv, causal, window, q_len, kv_len, use_segments):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scratch[...] = jnp.zeros_like(dq_scratch)

    q_start = qi * block_q
    k_start = ki * block_kv
    run = _block_runs(q_start, k_start, block_q, block_kv, causal, window)

    @pl.when(run)
    def _compute():
        q = _zero_oob(q_ref[0].astype(jnp.float32), q_start, q_len)
        k = _zero_oob(k_ref[0].astype(jnp.float32), k_start, kv_len)
        v = _zero_oob(v_ref[0].astype(jnp.float32), k_start, kv_len)
        do = _zero_oob(do_ref[0].astype(jnp.float32), q_start, q_len)
        lse = lse_ref[0]  # [block_q, 1]
        # delta rows past q_len are Pallas edge-block garbage; p=0 there cannot
        # save ds (0 * NaN = NaN), and dkv's column reduction would spread it
        row_idx = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        delta = jnp.where(row_idx < q_len, delta_ref[0], 0.0)  # [block_q, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        seg_q = sq_ref[0] if use_segments else None
        seg_k = sk_ref[0] if use_segments else None
        valid = _visible(s.shape, q_start, k_start, causal, window, q_len, kv_len, seg_q, seg_k)
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))  # [bq, bkv]
        ds = p * (dp - delta) * scale
        dq_scratch[...] += jax.lax.dot(ds, k)

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[0] = dq_scratch[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
                    dk_ref, dv_ref, dk_scratch, dv_scratch, *,
                    scale, block_q, block_kv, causal, window, q_len, kv_len, use_segments,
                    n_q):
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
    run = _block_runs(q_start, k_start, block_q, block_kv, causal, window)

    @pl.when(run)
    def _compute():
        q = _zero_oob(q_ref[0].astype(jnp.float32), q_start, q_len)
        k = _zero_oob(k_ref[0].astype(jnp.float32), k_start, kv_len)
        v = _zero_oob(v_ref[0].astype(jnp.float32), k_start, kv_len)
        do = _zero_oob(do_ref[0].astype(jnp.float32), q_start, q_len)
        lse = lse_ref[0]  # [block_q, 1]
        # delta rows past q_len are Pallas edge-block garbage; p=0 there cannot
        # save ds (0 * NaN = NaN), and dkv's column reduction would spread it
        row_idx = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        delta = jnp.where(row_idx < q_len, delta_ref[0], 0.0)  # [block_q, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        seg_q = sq_ref[0] if use_segments else None
        seg_k = sk_ref[0] if use_segments else None
        valid = _visible(s.shape, q_start, k_start, causal, window, q_len, kv_len, seg_q, seg_k)
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)  # [bq, bkv]
        dv_scratch[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))  # p^T @ do
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta) * scale
        dk_scratch[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))  # ds^T @ q

    @pl.when(j == n_j - 1)
    def _finalize():
        dk_ref[0] = dk_scratch[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scratch[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, segments, out, lse, g, scale, causal, window, block_q, block_kv, interpret):
    B, T, N, H = q.shape
    S, K = k.shape[1], k.shape[2]
    group = N // K
    qf, kf, vf, dof = _fold(q), _fold(k), _fold(v), _fold(g)
    of = _fold(out)
    # [B*N, T, 1]: trailing unit dim keeps the block TPU-tileable (see _flash_fwd)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1, keepdims=True)
    lse3 = lse[..., None]
    use_seg = segments is not None
    seg = segments if use_seg else jnp.zeros((B, T), jnp.int32)
    seg_q3 = seg[:, :, None]  # [B, T, 1]
    seg_k3 = seg[:, None, :]  # [B, 1, S]
    block_q = min(block_q, T)
    block_kv = min(block_kv, S)
    n_q, n_k = pl.cdiv(T, block_q), pl.cdiv(S, block_kv)

    common = dict(scale=scale, block_q=block_q, block_kv=block_kv, causal=causal,
                  window=window, q_len=T, kv_len=S, use_segments=use_seg)
    params = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(B * N, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, H), lambda bn, qi, ki: (bn, qi, 0)),
            pl.BlockSpec((1, block_kv, H), lambda bn, qi, ki, g_=group: (bn // g_, ki, 0)),
            pl.BlockSpec((1, block_kv, H), lambda bn, qi, ki, g_=group: (bn // g_, ki, 0)),
            pl.BlockSpec((1, block_q, H), lambda bn, qi, ki: (bn, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bn, qi, ki: (bn, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bn, qi, ki: (bn, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bn, qi, ki, n=N: (bn // n, qi, 0)),
            pl.BlockSpec((1, 1, block_kv), lambda bn, qi, ki, n=N: (bn // n, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, block_q, H), lambda bn, qi, ki: (bn, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * N, T, H), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, H), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(qf, kf, vf, dof, lse3, delta, seg_q3, seg_k3)

    # dk/dv: grid batch axis is the B*K kv heads; the sequential axis walks the
    # group*n_q (query-head-in-group, q-block) pairs so dk/dv for a kv head
    # accumulate in VMEM across its whole query group (no outside group-sum).
    qhead = lambda bk, j, g_=group, nq=n_q: bk * g_ + j // nq
    dk_p, dv_p = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common, n_q=n_q),
        grid=(B * K, n_k, group * n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, H), lambda bk, ki, j, nq=n_q: (qhead(bk, j), j % nq, 0)),
            pl.BlockSpec((1, block_kv, H), lambda bk, ki, j: (bk, ki, 0)),
            pl.BlockSpec((1, block_kv, H), lambda bk, ki, j: (bk, ki, 0)),
            pl.BlockSpec((1, block_q, H), lambda bk, ki, j, nq=n_q: (qhead(bk, j), j % nq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bk, ki, j, nq=n_q: (qhead(bk, j), j % nq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bk, ki, j, nq=n_q: (qhead(bk, j), j % nq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bk, ki, j, kk=K, nq=n_q: (bk // kk, j % nq, 0)),
            pl.BlockSpec((1, 1, block_kv), lambda bk, ki, j, kk=K: (bk // kk, 0, ki)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_kv, H), lambda bk, ki, j: (bk, ki, 0)),
            pl.BlockSpec((1, block_kv, H), lambda bk, ki, j: (bk, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * K, S, H), jnp.float32),
            jax.ShapeDtypeStruct((B * K, S, H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, H), jnp.float32),
            pltpu.VMEM((block_kv, H), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(qf, kf, vf, dof, lse3, delta, seg_q3, seg_k3)

    dq = dq.reshape(B, N, T, H).transpose(0, 2, 1, 3)
    dk = dk_p.reshape(B, K, S, H).transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dv_p.reshape(B, K, S, H).transpose(0, 2, 1, 3).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------- public api
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def flash_attention(
    q: jnp.ndarray,  # [B, T, N, H]
    k: jnp.ndarray,  # [B, S, K, H]
    v: jnp.ndarray,
    segment_ids: Optional[jnp.ndarray] = None,  # [B, T] packed-batch segments
    scale: Optional[float] = None,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_kv: int = 128,
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
