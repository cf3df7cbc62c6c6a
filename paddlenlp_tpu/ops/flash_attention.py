"""Attention dispatch: Pallas flash attention on TPU, fused XLA math elsewhere.

Counterpart of the reference's ``llama/fusion_ops.py:147-238``
(``fusion_flash_attention``: FlashAttention-2 / flashmask / ring / vendor-op dispatch).
TPU-native structure:

- default path: ``jax.nn.dot_product_attention`` — XLA fuses the softmax chain onto
  the MXU and handles GQA natively; on TPU this already hits the fused attention path;
- ``segment_ids`` support for packed (ZeroPadding) batches — the FlashMask
  ``startend_row_indices`` equivalent: tokens attend only within their segment,
  causally (reference fusion_ops.py:223-238);
- context-parallel path: ring attention over the ``cp`` mesh axis
  (``ops/ring_attention.py``), selected by the caller when cp > 1;
- a Pallas splash/flash kernel path for long sequences (`use_pallas=True`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

__all__ = ["dot_product_attention", "make_causal_mask", "make_segment_mask"]


def make_causal_mask(q_len: int, kv_len: int, offset=0, dtype=jnp.bool_, window: Optional[int] = None) -> jnp.ndarray:
    """[1, 1, q_len, kv_len] causal mask; ``offset`` = absolute position of q row 0.
    ``window`` adds a sliding-window lower bound (mistral-style local attention)."""
    rows = jnp.arange(q_len)[:, None] + offset
    cols = jnp.arange(kv_len)[None, :]
    mask = cols <= rows
    if window is not None:
        mask = mask & (cols > rows - window)
    return mask.astype(dtype)[None, None]


def make_segment_mask(q_segments: jnp.ndarray, kv_segments: jnp.ndarray) -> jnp.ndarray:
    """[B, 1, T, S] same-segment mask for packed batches (flashmask equivalent)."""
    return (q_segments[:, None, :, None] == kv_segments[:, None, None, :])


def alibi_slopes(n_heads: int) -> jnp.ndarray:
    """Closed-form ALiBi slopes (reference attention_strategies.py
    AttentionWithLinearBias :24 / the bloom convention)."""
    import math

    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start**i) for i in range(n)]

    if math.log2(n_heads).is_integer():
        return jnp.asarray(pow2_slopes(n_heads), jnp.float32)
    closest = 2 ** int(math.floor(math.log2(n_heads)))
    slopes = pow2_slopes(closest)
    extra = pow2_slopes(2 * closest)[0::2][: n_heads - closest]
    return jnp.asarray(slopes + extra, jnp.float32)


def alibi_bias(n_heads: int, q_len: int, kv_len: int, offset=0) -> jnp.ndarray:
    """[1, n_heads, q_len, kv_len] additive bias: -slope * (q_pos - k_pos)."""
    slopes = alibi_slopes(n_heads)
    rows = jnp.arange(q_len)[:, None] + offset
    cols = jnp.arange(kv_len)[None, :]
    dist = (rows - cols).astype(jnp.float32)  # >= 0 within the causal region
    return (-slopes[:, None, None] * dist)[None]


def dot_product_attention(
    query: jnp.ndarray,  # [B, T, n_heads, head_dim]
    key: jnp.ndarray,  # [B, S, n_kv, head_dim]
    value: jnp.ndarray,  # [B, S, n_kv, head_dim], or a value head of its own width (latent attention: 192 and 128)
    *,
    attention_mask: Optional[jnp.ndarray] = None,  # [B, S] padding mask (1 = keep)
    segment_ids: Optional[jnp.ndarray] = None,  # [B, S] packed-batch segments
    causal: bool = True,
    q_offset=0,  # absolute pos of query row 0 (decode with KV cache)
    scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    window: Optional[int] = None,
    positions: Optional[jnp.ndarray] = None,  # [B, T] or [T] ABSOLUTE positions (permuted layouts)
    use_pallas: Optional[bool] = None,
    use_alibi: bool = False,  # additive -slope*(q_pos-k_pos) bias (bloom/baichuan-13b)
    bias: Optional[jnp.ndarray] = None,  # [B|1, N|1, T, S] additive bias (t5 relative positions)
) -> jnp.ndarray:
    """Fused attention; returns [B, T, n_heads, value head_dim] in query dtype.

    A value head narrower or wider than the query/key head goes to the Pallas
    kernels as it is (they carry both widths); the XLA path, which wants one
    width, runs on zero-padded heads and slices the result back.

    ``positions``: when the sequence axis is physically permuted (context-parallel
    zigzag layout), index order != causal order; pass absolute positions and the
    causal/window mask is built from them instead of array indices.

    ``use_pallas``: None (default) enables the Pallas flash kernel automatically
    on TPU for eligible shapes (causal self-attention, optional segment_ids /
    sliding window, no dropout/padding-mask/cache). Inside a sharded jit the
    kernel runs under a ``shard_map`` over the batch/head mesh axes so it
    composes with GSPMD (pallas_call alone is opaque to the partitioner).
    Pass False to force the XLA path, True to force Pallas (interpret off-TPU).
    A shape or sharding the kernel cannot take is turned away by
    :func:`_pallas_dispatch`'s explicit gates (logged once each); a failure
    of the kernel itself raises — it never gives way to the XLA path.
    """
    B, T, N, H = query.shape
    S = key.shape[1]
    K = key.shape[2]
    scale = scale if scale is not None else H**-0.5

    pallas_eligible = (
        causal
        and bias is None
        and attention_mask is None
        and positions is None
        and dropout_rate == 0.0
        and not use_alibi
        and T == S  # self-attention, no KV cache
        and (isinstance(q_offset, int) and q_offset == 0)
        and N % K == 0
    )
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"  # default ON for TPU
    if use_pallas and pallas_eligible:
        out = _pallas_dispatch(query, key, value, segment_ids, scale, window)
        if out is not None:
            return out

    Hv = value.shape[-1]
    if Hv != H:  # the XLA path wants one head width: zeros add nothing to a score or to a value
        pad = lambda x, to: jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, to - x.shape[-1])))
        query, key, value = pad(query, max(H, Hv)), pad(key, max(H, Hv)), pad(value, max(H, Hv))

    mask = None
    if causal and positions is not None:
        pos = positions if positions.ndim == 2 else positions[None, :]
        pos = jnp.broadcast_to(pos, (B, S))
        q_pos = pos[:, -T:] if T != S else pos
        m = pos[:, None, None, :] <= q_pos[:, None, :, None]
        if window is not None:
            m = m & (pos[:, None, None, :] > q_pos[:, None, :, None] - window)
        mask = m
    elif causal:
        mask = jnp.broadcast_to(make_causal_mask(T, S, q_offset, window=window), (B, 1, T, S))
    if segment_ids is not None:
        q_seg = segment_ids[:, -T:] if T != S else segment_ids
        seg_mask = make_segment_mask(q_seg, segment_ids)
        mask = seg_mask if mask is None else jnp.logical_and(mask, seg_mask)
    if attention_mask is not None:
        pad = attention_mask[:, None, None, :].astype(jnp.bool_)
        mask = pad if mask is None else jnp.logical_and(mask, pad)

    if use_alibi:
        if positions is not None:
            # permuted layouts (cp zigzag): distances from ABSOLUTE positions
            pos = positions if positions.ndim == 2 else positions[None, :]
            pos = jnp.broadcast_to(pos, (B, S)).astype(jnp.float32)
            q_pos = pos[:, -T:] if T != S else pos
            dist = q_pos[:, None, :, None] - pos[:, None, None, :]
            ab = -alibi_slopes(N)[None, :, None, None] * dist
            bias = ab if bias is None else bias + ab
        else:
            ab = jnp.broadcast_to(alibi_bias(N, T, S, q_offset), (B, N, T, S))
            bias = ab if bias is None else bias + ab

    if dropout_rate == 0.0:
        out = jax.nn.dot_product_attention(query, key, value, bias=bias, mask=mask, scale=scale)
    else:
        out = _math_attention(query, key, value, mask, scale, dropout_rate, dropout_rng, bias=bias)
    # one value, one name: the kernel names its own output where its forward rule
    # makes it ("flash_out"), so the callers name nothing. A second name on the
    # same array is a second saved copy a layer under a scanned remat.
    return checkpoint_name(out[..., :Hv] if Hv != H else out, "core_attn")


def _pallas_dispatch(query, key, value, segment_ids, scale, window):
    """Run the Pallas kernel directly (off-mesh) or under a shard_map over the
    batch/head mesh axes (the GSPMD composition the reference gets from fleet's
    per-rank kernel launches). Returns None — logged once with the shape —
    when the shape or the active sharding cannot be expressed; the caller
    then takes the XLA path."""
    from jax.sharding import PartitionSpec as PS

    from ..parallel.partition import _current_mesh
    from ..utils.log import logger
    from .pallas.flash_attention import flash_attention as pallas_flash

    B, T, N, H = query.shape
    K = key.shape[2]

    def turned_away(why: str):
        logger.warning_once(
            f"pallas flash attention not used for q{tuple(query.shape)} kv{tuple(key.shape)}: "
            f"{why}; using the XLA attention path")
        return None

    if jax.default_backend() == "tpu" and not (T % 128 == 0 and H % 64 == 0 and value.shape[-1] % 64 == 0):
        # Mosaic tiling gate: a shape the kernel cannot tile is turned away
        # here, by rule, and not discovered as a compile error of the
        # enclosing jit. The value head may differ from the query/key head.
        return turned_away("needs T % 128 == 0 and head_dim % 64 == 0 (query/key and value)")
    mesh = _current_mesh()
    if mesh is None:
        return pallas_flash(query, key, value, segment_ids, scale, True, window)
    live = lambda axes: tuple(a for a in axes if mesh.shape.get(a, 1) > 1)
    if not live(("dp", "fsdp", "tp", "sep", "cp")):
        return pallas_flash(query, key, value, segment_ids, scale, True, window)
    if live(("cp",)):  # seq would be sharded; ring/XLA paths own that case
        return turned_away("sequence sharded over cp")
    batch_ax = live(("dp", "fsdp"))
    head_ax = live(("tp", "sep"))
    nb, nh = 1, 1
    for a in batch_ax:
        nb *= mesh.shape[a]
    for a in head_ax:
        nh *= mesh.shape[a]
    if B % nb or N % nh or K % nh or (N // nh) % max(K // nh, 1):
        return turned_away(f"batch/heads do not divide the mesh (batch x{nb}, heads x{nh})")
    qkv_spec = PS(batch_ax or None, None, head_ax or None, None)
    fn = functools.partial(pallas_flash, scale=scale, causal=True, window=window)
    if segment_ids is None:
        return jax.shard_map(
            lambda q, k, v: fn(q, k, v, None),
            mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec),
            out_specs=qkv_spec,
            check_vma=False,
        )(query, key, value)
    seg_spec = PS(batch_ax or None, None)
    return jax.shard_map(
        lambda q, k, v, s: fn(q, k, v, s),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, seg_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )(query, key, value, segment_ids)


def _math_attention(query, key, value, mask, scale, dropout_rate=0.0, dropout_rng=None, bias=None):
    B, T, N, H = query.shape
    S = key.shape[1]
    K = key.shape[2]
    if K != N:  # GQA: broadcast kv heads over query groups
        rep = N // K
        key = jnp.repeat(key, rep, axis=2)
        value = jnp.repeat(value, rep, axis=2)
    logits = jnp.einsum("btnh,bsnh->bnts", query.astype(jnp.float32), key.astype(jnp.float32)) * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    out = jnp.einsum("bnts,bsnh->btnh", probs, value.astype(jnp.float32))
    return out.astype(query.dtype)
