"""Pallas TPU latent attention for a chunk of a prompt: every head's queries
against cached latent rows ``(c_kv | k_pe)``, keys and values expanded from the
latent inside the kernel, dense under a selection mask that all heads share.

What the two other kernels do not need and this one does: a mask chosen per
query and key but alike for every head (one int8 tile a step, made an additive
float32 tile once and used by every head of the block), keys ``[nope | rope]``
wide whose ``nope`` part and whose values come out of ``c_kv`` through W^K and
W^V, scores wider than values, no backward.

Structure. grid = (row, head block, key tile), the key tile innermost and
sequential; the whole chunk's queries of a head block stay resident while the
tiles go by, with the running maximum, sum and accumulator of each head in
VMEM scratch (the online softmax of ``flash_attention.py``, whose helpers
for lane-replicated row statistics this file uses). One step:
- at a head block's first tile, lays each head's queries ``[nope | rope]`` side
  by side in scratch, once for all its tiles;
- expands the tile's keys and values for the head block: two products of the
  tile's ``c_kv`` with the block's columns of W^K and W^V;
- per head scores ``[q_nope | q_pe] . [k_nope | k_pe]`` in one product, scales,
  adds the mask tile, updates maximum, sum and accumulator. The score tile
  lives in VMEM only;
- a step past ``n_tiles`` (known on the device only: scalar prefetch) names the
  tile already resident, so nothing is copied, and its body is under
  ``pl.when``: a chunk with 2,000 positions cached does not pay for a table of
  16,896 (0.24 us a skipped step on v5e). The last visited tile divides by the
  sum and writes the block out.

Layout. Heads lie along lanes: the queries cross as [B, T, H * nope] and
[B, T, H * rope], W^K as [kv_lora, H * nope], W^V as [kv_lora, H * v], the
result as [B, T, H * v], so a head's part is a static lane slice and nothing
is padded or concatenated in HBM. Inside, the rope parts of queries and keys
get zeros up to whole 128-lane tiles (zeros add nothing to a score). On the
chip ``nope``, ``v`` and ``kv_lora`` have to be multiples of 128 and a head
block's ``rope`` lanes too (interpret mode does not care).

Precision: products of the inputs' dtype accumulated in float32, the expanded
keys and values rounded to the inputs' dtype (as XLA's einsum gives them), the
scale, mask, maximum, exponential and sums in float32, the probabilities cast
to the inputs' dtype for the product with V, one cast at the end.

A query that keeps nothing in the tiles so far sums rubbish at weight 1; its
first kept position fades that to nothing (exp(NEG - score) = 0). A query that
keeps nothing at all (a padded row) returns that rubbish, finite: the caller
drops such rows.

Off-TPU (tests), the kernel runs in Pallas interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _LANES, NEG_INF as NEG, _across, _dot, _nt

__all__ = ["latent_chunk_attention"]

HEAD_BLOCK = 4  # heads a grid step takes, where the head count allows (chip sweep on v5e: PERF.md section 6, PR 34)


def _kernel(n_ref, q_nope_ref, q_pe_ref, rows_ref, wk_ref, wv_ref, keep_ref, o_ref,
            q_scratch, m_scratch, l_scratch, acc_scratch, bias_scratch, *,
            scale, heads, kv_lora, nope, rope, v_dim):
    j = pl.program_id(2)
    n_tiles = n_ref[0]
    pad = q_scratch.shape[-1] - nope - rope  # zeros on both sides of the score product, up to whole lane tiles

    def padded(x):  # [rows, rope] -> [rows, rope + pad]
        return x if not pad else jnp.concatenate([x, jnp.zeros((x.shape[0], pad), x.dtype)], axis=1)

    @pl.when(j == 0)
    def _init():
        for h in range(heads):  # a head's [nope | rope | 0] queries side by side, once for all its key tiles
            q_scratch[h] = jnp.concatenate([q_nope_ref[0, :, h * nope:(h + 1) * nope],
                                            padded(q_pe_ref[0, :, h * rope:(h + 1) * rope])], axis=1)
        m_scratch[...] = jnp.full_like(m_scratch, NEG)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    @pl.when(j < n_tiles)
    def _tile():
        rows = rows_ref[0]  # [tile, kv_lora + rope]
        dtype = rows.dtype
        tile = rows.shape[0]
        c_kv, k_pe = rows[:, :kv_lora], padded(rows[:, kv_lora:])
        k_nope = _dot(c_kv, wk_ref[...]).astype(dtype)
        v = _dot(c_kv, wv_ref[...]).astype(dtype)
        bias_scratch[...] = jnp.where(keep_ref[0].astype(jnp.int32) != 0, 0.0, NEG)
        for h in range(heads):
            k_cat = jnp.concatenate([k_nope[:, h * nope:(h + 1) * nope], k_pe], axis=1)
            s = _nt(q_scratch[h], k_cat) * scale + bias_scratch[...]
            m_prev = m_scratch[h]  # [T, 128]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - _across(m_new, tile))
            alpha = jnp.exp(m_prev - m_new)
            l_scratch[h] = alpha * l_scratch[h] + jnp.sum(p, axis=-1, keepdims=True)
            acc_scratch[h] = acc_scratch[h] * _across(alpha, v_dim) + _dot(p.astype(dtype), v[:, h * v_dim:(h + 1) * v_dim])
            m_scratch[h] = m_new

    @pl.when(j == n_tiles - 1)
    def _finalize():
        for h in range(heads):
            norm = jnp.maximum(l_scratch[h], 1e-30)
            o_ref[0, :, h * v_dim:(h + 1) * v_dim] = (acc_scratch[h] / _across(norm, v_dim)).astype(o_ref.dtype)


def _head_block(heads, head_block):
    """The most heads a step takes: ``head_block`` or, where the head count is
    not a multiple of it, the largest divisor below."""
    block = min(head_block, heads)
    while heads % block:
        block -= 1
    return block


def _lanes(n):
    """``n`` rounded up to whole 128-lane tiles."""
    return -(-n // _LANES) * _LANES


def vmem_bytes(t, tile, heads, kv_lora, nope, rope, v_dim, itemsize):
    """What one grid step holds in VMEM, by hand: the operands' blocks twice
    (Pallas double-buffers them), the scratch, and the values a head's pass
    leaves live (the tile's expanded keys and values in float32 and rounded,
    every head's score tile in float32 twice and its probabilities rounded:
    the scheduler runs one head's softmax over the next one's products). The
    chip's compiler needs 16 / 28 / 56 MiB at most for 2 / 4 / 8 heads a step
    at the full layers' sizes, where this gives 23 / 42 / 80."""
    blocks = (t * heads * (nope + rope) + tile * _lanes(kv_lora + rope) + kv_lora * heads * (nope + v_dim)
              + t * heads * v_dim) * itemsize + t * tile
    scratch = heads * t * _lanes(nope + rope) * itemsize + 4 * (heads * t * (2 * _LANES + _lanes(v_dim)) + t * tile)
    live = tile * heads * (nope + v_dim) * (4 + itemsize) + heads * t * tile * (4 + 4 + itemsize)
    return 2 * blocks + scratch + live


def latent_chunk_attention(
    q_nope: jnp.ndarray,  # [B, T, H, nope] the chunk's queries
    q_pe: jnp.ndarray,  # [B, T, H, rope] their roped part
    rows: jnp.ndarray,  # [B, S, kv_lora + rope] the row's cached latent rows (c_kv | roped k_pe), S = tiles x tile
    w_k: jnp.ndarray,  # [kv_lora, H, nope]
    w_v: jnp.ndarray,  # [kv_lora, H, v]
    keep: jnp.ndarray,  # [B, T, S] bool: what a query attends, alike for every head
    n_tiles,  # int32 scalar on the device: key tiles to visit, 1 .. S // tile
    *,
    scale: float,
    tile: int,
    head_block: int = HEAD_BLOCK,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """-> [B, T, H, v]: softmax(scale * [q_nope | q_pe] . [c_kv W^K | k_pe] under
    ``keep``) times c_kv W^V, over the first ``n_tiles`` tiles of ``tile``
    cached positions; rows of later tiles are never read."""
    b, t, h, nope = q_nope.shape
    rope = q_pe.shape[-1]
    s = rows.shape[1]
    kv_lora, v_dim = w_v.shape[0], w_v.shape[-1]
    if (s % tile or rows.shape[2] != kv_lora + rope or keep.shape != (b, t, s)
            or w_k.shape != (kv_lora, h, nope) or q_pe.shape[:3] != (b, t, h)):
        raise ValueError(f"latent_chunk_attention: rows {rows.shape} and keep {keep.shape} against q_nope "
                         f"{q_nope.shape}, q_pe {q_pe.shape}, W^K {w_k.shape}, W^V {w_v.shape}, tile {tile}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    dtype = q_nope.dtype
    width = _lanes(nope + rope)
    heads = _head_block(h, head_block)

    def key_tile(j, n):  # a step past the last visited tile names that tile again: no copy
        return jnp.minimum(j, n[0] - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h // heads, s // tile),
        in_specs=[
            pl.BlockSpec((1, t, heads * nope), lambda bi, hi, j, n: (bi, 0, hi)),
            pl.BlockSpec((1, t, heads * rope), lambda bi, hi, j, n: (bi, 0, hi)),
            pl.BlockSpec((1, tile, kv_lora + rope), lambda bi, hi, j, n: (bi, key_tile(j, n), 0)),
            pl.BlockSpec((kv_lora, heads * nope), lambda bi, hi, j, n: (0, hi)),
            pl.BlockSpec((kv_lora, heads * v_dim), lambda bi, hi, j, n: (0, hi)),
            pl.BlockSpec((1, t, tile), lambda bi, hi, j, n: (bi, 0, key_tile(j, n))),
        ],
        out_specs=pl.BlockSpec((1, t, heads * v_dim), lambda bi, hi, j, n: (bi, 0, hi)),
        scratch_shapes=[
            pltpu.VMEM((heads, t, width), dtype),  # a head's queries [nope | rope | 0]
            pltpu.VMEM((heads, t, _LANES), jnp.float32),  # running maximum, every lane alike
            pltpu.VMEM((heads, t, _LANES), jnp.float32),  # running sum
            pltpu.VMEM((heads, t, v_dim), jnp.float32),  # accumulator
            pltpu.VMEM((t, tile), jnp.float32),  # the mask tile as what is added to a score
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, heads=heads, kv_lora=kv_lora, nope=nope, rope=rope, v_dim=v_dim),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, h * v_dim), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(vmem_bytes(t, tile, heads, kv_lora, nope, rope, v_dim, dtype.itemsize), 16 << 20)),
        interpret=interpret,
        name="latent_chunk_attention",
    )(jnp.asarray(n_tiles, jnp.int32).reshape(1), q_nope.reshape(b, t, h * nope), q_pe.reshape(b, t, h * rope),
      rows.astype(dtype), w_k.astype(dtype).reshape(kv_lora, h * nope), w_v.astype(dtype).reshape(kv_lora, h * v_dim),
      keep.astype(jnp.int8))
    return out.reshape(b, t, h, v_dim)
