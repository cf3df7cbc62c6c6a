"""Ring attention: exact attention over sequence chunks sharded on the ``cp`` axis.

Counterpart of ``paddlenlp/transformers/ring_flash_attention.py`` (``RingCommunicator``
P2P :24, ``balanced_ring_flash_attention_fwd_func`` :97 with log-sum-exp merge :69,
custom backward) and ``context_parallel_utils.py``. TPU-native redesign:

- the NCCL isend/irecv ring becomes ``lax.ppermute`` over the ``cp`` mesh axis
  inside ``shard_map`` — XLA schedules the collective-permute to overlap with the
  per-chunk attention compute on ICI;
- the hand-written backward disappears: the ring is a ``lax.scan`` of traceable
  ops, so reverse-mode AD derives it (ppermute's transpose is the reverse ring);
  the scan body is ``jax.checkpoint``-ed so K/V chunks are re-permuted, not stored;
- causal masking uses absolute positions, so any chunk layout works; the zigzag
  load-balanced split of the reference (:32) is provided for contiguous causal
  runs.

Per-device memory is O(S/cp) for K/V — the point of ring attention vs letting
GSPMD all-gather the sequence axis.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["ring_attention_local", "ring_self_attention", "zigzag_split", "zigzag_unsplit"]


def _chunk_attention(q, k, v, q_pos, kv_pos, scale):
    """Masked attention contribution of one kv chunk: returns UNNORMALIZED
    (num [B,Tq,N,H], den [B,N,Tq], m [B,N,Tq]) in fp32 — the flash-attention
    accumulator triple. ``m`` is -inf for fully-masked rows. Positions are
    per-row [B, Tq]/[B, Tk] (absolute), so heterogeneous batches mask correctly."""
    B, Tq, N, H = q.shape
    K = k.shape[2]
    if K != N:
        rep = N // K
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("btnh,bsnh->bnts", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    mask = kv_pos[:, None, None, :] <= q_pos[:, None, :, None]  # [B,1,Tq,Tk] causal by abs position
    logits = jnp.where(mask, logits, -jnp.inf)
    m = jnp.max(logits, axis=-1)  # [B,N,Tq], -inf when fully masked
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    probs = jnp.where(mask, jnp.exp(logits - safe_m[..., None]), 0.0)
    den = probs.sum(axis=-1)
    num = jnp.einsum("bnts,bsnh->btnh", probs, v.astype(jnp.float32))
    return num, den, m


def _merge(num_a, den_a, m_a, num_b, den_b, m_b):
    """Numerically-stable merge of two unnormalized partials (the reference's
    update_out_and_lse, ring_flash_attention.py:69, in (num, den, max) form)."""
    m = jnp.maximum(m_a, m_b)
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    wa = jnp.where(jnp.isfinite(m_a), jnp.exp(m_a - safe_m), 0.0)
    wb = jnp.where(jnp.isfinite(m_b), jnp.exp(m_b - safe_m), 0.0)
    num = num_a * wa.transpose(0, 2, 1)[..., None] + num_b * wb.transpose(0, 2, 1)[..., None]
    den = den_a * wa + den_b * wb
    return num, den, m


def ring_attention_local(
    q: jnp.ndarray,  # [B, Tq, N, H] — this device's query chunk
    k: jnp.ndarray,  # [B, Tk, K, H] — this device's kv chunk
    v: jnp.ndarray,
    q_positions: jnp.ndarray,  # [B, Tq] absolute positions of the q chunk
    kv_positions: jnp.ndarray,  # [B, Tk] absolute positions of the kv chunk
    axis_name: str = "cp",
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Runs INSIDE shard_map: each step attends to the resident kv chunk, then
    ppermutes (k, v, kv_positions) one hop around the ring."""
    H = q.shape[-1]
    scale = scale if scale is not None else H**-0.5
    cp = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    @jax.checkpoint
    def step(carry, _):
        num_acc, den_acc, m_acc, k_c, v_c, kv_pos = carry
        num_c, den_c, m_c = _chunk_attention(q, k_c, v_c, q_positions, kv_pos, scale)
        num_acc, den_acc, m_acc = _merge(num_acc, den_acc, m_acc, num_c, den_c, m_c)
        k_n = jax.lax.ppermute(k_c, axis_name, perm)
        v_n = jax.lax.ppermute(v_c, axis_name, perm)
        p_n = jax.lax.ppermute(kv_pos, axis_name, perm)
        return (num_acc, den_acc, m_acc, k_n, v_n, p_n), None

    B, Tq, N, _ = q.shape
    num0 = jnp.zeros((B, Tq, N, H), jnp.float32)
    den0 = jnp.zeros((B, N, Tq), jnp.float32)
    m0 = jnp.full((B, N, Tq), -jnp.inf, jnp.float32)
    (num, den, _, _, _, _), _ = jax.lax.scan(step, (num0, den0, m0, k, v, kv_positions), None, length=cp)
    out = num / jnp.maximum(den, 1e-37).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_self_attention(
    q: jnp.ndarray,  # [B, S, N, H] — logical (global) arrays, seq sharded over cp
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    positions: Optional[jnp.ndarray] = None,  # [S] or [B, S] absolute positions (zigzag layouts)
    axis_name: str = "cp",
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """shard_map wrapper: manual over ``cp`` only — batch/heads axes stay under
    GSPMD (the reference needs a dedicated cp process group; here it's one axis)."""
    B, S = q.shape[:2]
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions[None, :], (B, S))

    def local(q_c, k_c, v_c, pos_c):
        return ring_attention_local(q_c, k_c, v_c, pos_c, pos_c, axis_name, scale)

    qspec = P(None, axis_name, None, None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(qspec, qspec, qspec, P(None, axis_name)),
        out_specs=qspec,
        axis_names={axis_name},
        check_vma=False,
    )(q, k, v, positions)


def zigzag_split(x: jnp.ndarray, cp: int, axis: int = 1) -> jnp.ndarray:
    """Reorder the sequence axis into the load-balanced zigzag layout (reference
    context_parallel_utils.py:32): rank r gets chunks (r, 2*cp-1-r) so every rank
    sees a balanced mix of early (cheap) and late (expensive) causal positions.
    Returns the permuted array (same shape); pair with position ids from
    ``zigzag_positions`` so ring attention masks by absolute position."""
    S = x.shape[axis]
    idx = zigzag_positions(S, cp)
    return jnp.take(x, idx, axis=axis)


@functools.lru_cache(maxsize=64)
def zigzag_positions(S: int, cp: int) -> "np.ndarray":
    """Absolute positions, zigzag order: concat over r of chunk r and chunk 2cp-1-r.
    Pure NumPy + cached: this sits on the per-batch host data path."""
    import numpy as np

    if S % (2 * cp) != 0:
        raise ValueError(
            f"context parallel requires seq_len divisible by 2*cp for the zigzag "
            f"load-balanced split: got seq_len={S}, cp={cp} (need a multiple of {2 * cp})"
        )
    chunk = S // (2 * cp)
    order = []
    for r in range(cp):
        order.extend(range(r * chunk, (r + 1) * chunk))
        order.extend(range((2 * cp - 1 - r) * chunk, (2 * cp - r) * chunk))
    return np.asarray(order, dtype=np.int32)


def zigzag_unsplit(x: jnp.ndarray, cp: int, axis: int = 1) -> jnp.ndarray:
    import numpy as np

    S = x.shape[axis]
    idx = np.asarray(zigzag_positions(S, cp))
    inv = np.zeros_like(idx)
    inv[idx] = np.arange(S, dtype=np.int32)
    return jnp.take(x, inv, axis=axis)
