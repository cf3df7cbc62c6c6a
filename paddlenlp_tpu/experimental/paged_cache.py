"""Paged (block) KV cache + host-side block manager.

Counterpart of the reference's block-attention machinery: the CUDA block pool in
``csrc/gpu/append_attn/*`` (write_cache_with_rope, c16 cache) and the in-kernel
allocator ``csrc/gpu/step.cu`` (op ``step_paddle`` :316 — free/dispatch blocks,
preempt + recover). TPU-native split:

- device side: ONE pool tensor ``[L, 2, num_blocks, block_size, n_kv * H]``:
  token-major rows, a token's K (or V) for all its kv heads is one contiguous
  row. Every consumer addresses that one donated buffer in place, by layer
  index: prefill/decode scatter whole rows at ``[l, plane, block, offset]``
  and the Pallas kernel DMAs one head's ``[block_size, H]`` tile out of the
  rows of block ``tables[b, j]``. Writer and reader agree on the layout, so
  no step program slices, stacks or re-lays out a pool-sized array (the pool rides
  the layer scan's carry, never its xs/ys). The block axis is axis 2 for
  whole-block copies (prefix-cache COW, host tier, stage migration);
- beside it, for layer kinds that keep something else (``latent_model.py``):
  ``LatentKVPool``, planes of one latent row a token for full-attention
  layers, their indexer keys, and window layers' rows under a second table
  that holds the window only; ``StatePool`` (``state_model.py``): the
  per-head planes of the attention layers, addressed by block table as
  above, beside **state rows addressed by slot** for the scan layers, whose
  whole past is one recurrent state and a few convolution inputs a sequence,
  in no block of tokens; and ``WindowKVPool`` (``window_model.py``): per-head
  K and V in **two planes of the layout above**, ``kv`` for the layers that
  attend the whole context, under a sequence's block table, and ``win`` for
  the layers that attend a window, under its second table
  (``BlockManager.window_span`` / ``table_array`` row 1), which holds blocks
  at the logical blocks inside the window only, so that plane's block count
  is slots x (window + the tokens a launch feeds) whatever the context;
- host side: ``BlockManager`` does the step.cu bookkeeping (free list, per-seq
  tables, allocate/extend/free, preemption candidates) in plain Python — the
  allocator runs between device steps, so there is no launch-latency reason to
  put it in-kernel as CUDA must.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["PagedKVPool", "LatentKVPool", "StatePool", "WindowKVPool", "BlockManager", "init_paged_pool",
           "init_latent_pool", "init_state_pool", "init_window_pool", "write_kv_block", "write_rows", "read_state_rows", "write_state_rows", "gather_kv",
           "copy_blocks"]

@dataclasses.dataclass
class PagedKVPool:
    """Device-side pool: kv [L, 2, num_blocks, block_size, n_kv * head_dim].

    Quantized caches (the reference's c8/fp8 cache, ``csrc/gpu/append_attn/``
    c8 impls + ``predictor.py:775-791`` cachekv_int8) store ``kv`` as int8 /
    float8_e4m3 plus per-token-per-head ``scale`` [L, 2, nb, bs, n_kv], laid
    out and addressed like ``kv`` — dequant happens at the attention read
    (in-kernel for the Pallas path)."""

    kv: jnp.ndarray
    scale: Optional[jnp.ndarray] = None

    @property
    def num_blocks(self) -> int:
        return self.kv.shape[2]

    @property
    def block_size(self) -> int:
        return self.kv.shape[3]

    @property
    def quantized(self) -> bool:
        return self.scale is not None


jax.tree_util.register_dataclass(PagedKVPool, data_fields=["kv", "scale"], meta_fields=[])


@dataclasses.dataclass
class LatentKVPool:
    """Three planes side by side, each ``[layers of its kind, blocks, block_size,
    width]`` with a token's row minor, donated and carried whole like
    :class:`PagedKVPool`:

    - ``kv``   the full-attention layers' latent row ``(c_kv | roped k_pe)``,
    - ``idx``  the same layers' indexer key,
      both addressed by a sequence's block table (``tables[:, 0]``);
    - ``win``  the window layers' latent row, under a second table
      (``tables[:, 1]``) that holds blocks for the window only: the
      ``BlockManager`` gives back what falls behind it, so this plane has
      its own, much smaller block count.

    ``stats`` int32 [n] rides along: what the last launch's layers counted on
    the device (``LatentInferenceModel.STATS``), read at the sync point."""

    kv: jnp.ndarray
    idx: jnp.ndarray
    win: jnp.ndarray
    stats: jnp.ndarray
    scale = None  # no quantized form of the latent planes

    @property
    def num_blocks(self) -> int:
        return self.kv.shape[1]

    @property
    def block_size(self) -> int:
        return self.kv.shape[2]

    @property
    def quantized(self) -> bool:
        return False


jax.tree_util.register_dataclass(LatentKVPool, data_fields=["kv", "idx", "win", "stats"], meta_fields=[])


def init_latent_pool(n_full: int, n_window: int, num_blocks: int, num_window_blocks: int, block_size: int,
                     widths: Dict[str, int], n_stats: int, dtype=jnp.bfloat16) -> LatentKVPool:
    plane = lambda layers, blocks, width: jnp.zeros((max(layers, 1), blocks, block_size, width), dtype)
    return LatentKVPool(kv=plane(n_full, num_blocks, widths["kv"]), idx=plane(n_full, num_blocks, widths["idx"]),
                        win=plane(n_window, num_window_blocks, widths["win"]), stats=jnp.zeros((n_stats,), jnp.int32))


def write_rows(plane: jnp.ndarray, rows: jnp.ndarray, table: jnp.ndarray, positions: jnp.ndarray,
               valid: jnp.ndarray, layer) -> jnp.ndarray:
    """Scatter a batch's new rows into ``plane[layer]``: rows [B, T, width] at
    absolute ``positions`` [B, T] through ``table`` [B, max_blocks]. One
    scatter of whole rows, in place on a donated plane. Rows that are not
    ``valid`` [B, T] (padding of a chunk or of the batch) land in the sentinel
    block 0 and never in a block some sequence owns."""
    bs = plane.shape[2]
    slot = jnp.minimum(positions // bs, table.shape[1] - 1)
    blocks = jnp.where(valid, jnp.take_along_axis(table, slot, axis=1), 0)
    return plane.at[layer, blocks, positions % bs].set(rows.astype(plane.dtype))


@dataclasses.dataclass
class StatePool:
    """Paged per-head K and V for the attention layers beside recurrent state
    rows for the scan layers, donated and carried whole like :class:`PagedKVPool`:

    - ``kv``    [attention layers, 2, blocks, block_size, n_kv * head_dim]:
      :class:`PagedKVPool`'s layout, written by ``write_kv_block`` and read by
      the ragged paged kernel, addressed by a sequence's block table;
    - ``ssm``   [scan layers, slots + 1, groups, heads a group, head_dim,
      state] **float32**: a sequence's recurrent state, one row an engine slot;
    - ``conv``  [scan layers, slots + 1, conv_kernel - 1, conv_dim]: the last
      inputs of its causal convolution.

    A row belongs to the slot, not to a block: whoever feeds a slot's position
    0 starts from zeros (an admission, a re-prefill after preemption), so a
    slot is never cleared on the host and a preempted sequence rebuilds its
    state by recompute, as its KV. Row ``slots`` is the sentinel that rows of
    a launch that feed nothing write to. ``stats`` int32 [n] rides along:
    what the last launch's layers counted on the device."""

    kv: jnp.ndarray
    ssm: jnp.ndarray
    conv: jnp.ndarray
    stats: jnp.ndarray
    scale = None  # no quantized form

    @property
    def num_blocks(self) -> int:
        return self.kv.shape[2]

    @property
    def block_size(self) -> int:
        return self.kv.shape[3]

    @property
    def quantized(self) -> bool:
        return False


jax.tree_util.register_dataclass(StatePool, data_fields=["kv", "ssm", "conv", "stats"], meta_fields=[])


def init_state_pool(n_attention: int, num_blocks: int, block_size: int, kv_width: int, n_scan: int, slots: int,
                    state_shape: Tuple[int, ...], conv_shape: Tuple[int, ...], n_stats: int,
                    dtype=jnp.bfloat16) -> StatePool:
    return StatePool(kv=jnp.zeros((max(n_attention, 1), 2, num_blocks, block_size, kv_width), dtype),
                     ssm=jnp.zeros((max(n_scan, 1), slots + 1) + tuple(state_shape), jnp.float32),
                     conv=jnp.zeros((max(n_scan, 1), slots + 1) + tuple(conv_shape), dtype),
                     stats=jnp.zeros((n_stats,), jnp.int32))


def read_state_rows(plane: jnp.ndarray, layer: int, slots: Optional[jnp.ndarray], n: int) -> jnp.ndarray:
    """The rows of ``plane[layer]`` a launch's ``n`` rows own: those of
    ``slots`` [n], or rows ``0 .. n - 1`` where the launch's rows are the
    slots in order (``slots`` None: a static slice, nothing gathered)."""
    return plane[layer, :n] if slots is None else plane[layer, slots]


def write_state_rows(plane: jnp.ndarray, layer: int, slots: Optional[jnp.ndarray], old: jnp.ndarray,
                     new: jnp.ndarray, live: jnp.ndarray) -> jnp.ndarray:
    """Put ``new`` [n, ...] back where ``read_state_rows`` took ``old`` from, in
    place on the donated plane. A row that is not ``live`` [n] keeps what it
    held: in slot order it writes ``old`` back, under ``slots`` it lands in the
    sentinel row (padding rows all name slot 0, which may be someone's)."""
    new = new.astype(plane.dtype)
    if slots is None:
        keep = live.reshape((-1,) + (1,) * (new.ndim - 1))
        return plane.at[layer, : new.shape[0]].set(jnp.where(keep, new, old))
    return plane.at[layer, jnp.where(live, slots, plane.shape[1] - 1)].set(new)


@dataclasses.dataclass
class WindowKVPool:
    """Per-head K and V in two planes, each in :class:`PagedKVPool`'s layout
    (written by ``write_kv_block``, read by the ragged paged kernel through a
    ``PagedKVPool`` view of the plane), donated and carried whole:

    - ``kv``   [full layers, 2, blocks, block_size, n_kv * head_dim]: the layers
      that attend the whole context, under a sequence's block table
      (``tables[:, 0]``);
    - ``win``  [window layers, 2, window_blocks, block_size, n_kv * head_dim]:
      the layers that attend a window, under the second table
      (``tables[:, 1]``), whose blocks the ``BlockManager`` gives back once
      they lie wholly behind the window: its block count does not grow with
      the context.

    A layer addresses its plane by its index among the layers of its kind.
    ``stats`` int32 [n] rides along: what the last launch's layers counted on
    the device (``WindowedInferenceModel.STATS``)."""

    kv: jnp.ndarray
    win: jnp.ndarray
    stats: jnp.ndarray
    scale = None  # no quantized form

    @property
    def num_blocks(self) -> int:
        return self.kv.shape[2]

    @property
    def block_size(self) -> int:
        return self.kv.shape[3]

    @property
    def quantized(self) -> bool:
        return False


jax.tree_util.register_dataclass(WindowKVPool, data_fields=["kv", "win", "stats"], meta_fields=[])


def init_window_pool(n_full: int, n_window: int, num_blocks: int, num_window_blocks: int, block_size: int,
                     kv_width: int, n_stats: int, dtype=jnp.bfloat16) -> WindowKVPool:
    plane = lambda layers, blocks: jnp.zeros((max(layers, 1), 2, blocks, block_size, kv_width), dtype)
    return WindowKVPool(kv=plane(n_full, num_blocks), win=plane(n_window, num_window_blocks),
                        stats=jnp.zeros((n_stats,), jnp.int32))


_QMAX = {"int8": 127.0, "fp8": 448.0}  # float8_e4m3 max normal


def init_paged_pool(config, num_blocks: int, block_size: int = 16, dtype=jnp.bfloat16,
                    quant: Optional[str] = None) -> PagedKVPool:
    n_kv = getattr(config, "num_key_value_heads", config.num_attention_heads)
    head_dim = getattr(config, "head_dim", config.hidden_size // config.num_attention_heads)
    shape = (config.num_hidden_layers, 2, num_blocks, block_size, n_kv * head_dim)
    if quant is None:
        return PagedKVPool(kv=jnp.zeros(shape, dtype=dtype))
    if quant not in _QMAX:
        raise ValueError(f"kv cache quant must be int8/fp8, got {quant!r}")
    qdtype = jnp.int8 if quant == "int8" else jnp.float8_e4m3fn
    return PagedKVPool(
        kv=jnp.zeros(shape, dtype=qdtype),
        scale=jnp.zeros(shape[:-1] + (n_kv,), dtype=jnp.float32),
    )


def quantize_kv(x: jnp.ndarray, qdtype) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-token-per-head symmetric quant over the head dim.

    x [..., H] -> (q [..., H] in qdtype, scale [..., 1] fp32)."""
    qmax = _QMAX["int8" if qdtype == jnp.int8 else "fp8"]
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / qmax
    q = x.astype(jnp.float32) / scale
    if qdtype == jnp.int8:
        q = jnp.clip(jnp.round(q), -127, 127)
    return q.astype(qdtype), scale


def write_kv_block(pool: PagedKVPool, k: jnp.ndarray, v: jnp.ndarray,
                   block_tables: jnp.ndarray, start_pos: jnp.ndarray, layer) -> PagedKVPool:
    """Scatter a batch's new K/V rows into layer ``layer`` of the whole pool.

    pool.kv [L, 2, num_blocks, bs, K*H] (updated in place when the caller
    donated it); k/v [B, T, K, H]; block_tables [B, max_blocks]; start_pos [B]
    — token i of row b lands at logical position start_pos[b]+i ->
    (block_tables[b, (start_pos[b]+i)//bs], (start_pos[b]+i)%bs).
    One scatter per plane for the whole batch: each update is a token's whole
    row, minor in the pool, so XLA keeps the pool's layout and aliases the
    operand to the output. Padded rows land in the sentinel block.
    A quantized pool (``pool.scale`` [L, 2, num_blocks, bs, K]) range-compresses
    K/V per token+head on write and scatters the scales the same way."""
    B, T, K, H = k.shape
    kv, scale = pool.kv, pool.scale
    pos = start_pos[:, None] + jnp.arange(T)[None, :]  # [B, T]
    blocks = block_tables[jnp.arange(B)[:, None], pos // pool.block_size]
    offs = pos % pool.block_size
    if scale is not None:
        k, ks = quantize_kv(k, kv.dtype)
        v, vs = quantize_kv(v, kv.dtype)
        scale = scale.at[layer, 0, blocks, offs].set(ks[..., 0])
        scale = scale.at[layer, 1, blocks, offs].set(vs[..., 0])
    kv = kv.at[layer, 0, blocks, offs].set(k.reshape(B, T, K * H).astype(kv.dtype))
    kv = kv.at[layer, 1, blocks, offs].set(v.reshape(B, T, K * H).astype(kv.dtype))
    return PagedKVPool(kv=kv, scale=scale)


def gather_kv(pool: PagedKVPool, block_tables: jnp.ndarray, layer,
              n_kv: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Gather per-sequence K/V views of layer ``layer`` from the whole pool.

    pool.kv [L, 2, num_blocks, bs, K*H] with K = ``n_kv``; block_tables
    [B, max_blocks] -> (k, v) each [B, max_blocks*bs, K, H]. Out-of-range
    table entries must point at a zeroed sentinel block; masking by context
    length happens in attention. The layer is one more gather index: nothing
    is sliced out of the pool first. Quantized pools (``pool.scale``
    [L, 2, num_blocks, bs, K]) dequantize on the gathered view."""
    B, M = block_tables.shape

    def view(plane):
        rows = pool.kv[layer, plane, block_tables]  # [B, max_blocks, bs, K*H]
        rows = rows.reshape(B, M * pool.block_size, n_kv, -1)
        if pool.scale is None:
            return rows
        scales = pool.scale[layer, plane, block_tables].reshape(B, M * pool.block_size, n_kv, 1)
        # dequantize to bf16: the quantized cache must not carry a LARGER
        # working set than the bf16 pool it replaces
        return (rows.astype(jnp.float32) * scales).astype(jnp.bfloat16)

    return view(0), view(1)


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_blocks_plane(plane: jnp.ndarray, src: jnp.ndarray, dst: jnp.ndarray) -> jnp.ndarray:
    return plane.at[:, :, dst].set(plane[:, :, src])


def copy_blocks(pool: PagedKVPool, pairs: Sequence[Tuple[int, int]]) -> PagedKVPool:
    """Copy whole KV blocks src -> dst across every layer (K and V planes).

    The copy-on-write primitive behind prefix caching: when a request's prompt
    is fully covered by cached blocks, the tail block must still absorb the
    re-prefilled last token — so it is duplicated into a private block first.
    Jitted with the pool donated so XLA scatters in place — an eager ``.at[]``
    would materialize a second full pool (transient 2x HBM) to copy one block.
    Functional semantics still order the copy before any later prefill/decode
    write that might recycle ``src``.

    The pair list is padded to the next power of two with ``(0, 0)`` identity
    copies of the zero sentinel block (real dsts are never block 0), so the
    full-pool scatter compiles for at most log2(max pairs) shapes instead of
    once per distinct COW count seen in the admission hot path."""
    if not pairs:
        return pool
    padded = 1
    while padded < len(pairs):
        padded *= 2
    pairs = list(pairs) + [(0, 0)] * (padded - len(pairs))
    src = jnp.asarray([s for s, _ in pairs], jnp.int32)
    dst = jnp.asarray([d for _, d in pairs], jnp.int32)
    kv = _copy_blocks_plane(pool.kv, src, dst)
    scale = None if pool.scale is None else _copy_blocks_plane(pool.scale, src, dst)
    return PagedKVPool(kv=kv, scale=scale)


class BlockManager:
    """Host-side allocator (the step.cu bookkeeping in Python).

    Block 0 is reserved as the zero sentinel for unused table slots.

    **Prefix caching** (``enable_prefix_cache=True``): every owned block carries
    a refcount, and full blocks of finished prompts are registered in a
    chained-hash index (``h_i = sha256(h_{i-1} || block_i tokens)`` — block-
    granular, content-addressed). ``allocate(..., token_ids=...)`` walks the
    chain and reuses the longest cached prefix of FULL blocks; the caller skips
    prefill for those tokens. Zero-ref cached blocks sit on an LRU list and are
    evicted only under allocation pressure, so the cache can never cause an
    admission failure the uncached allocator wouldn't have had: ``num_free``
    counts them as available.

    **Concurrency model**: lock-free by thread confinement — the manager is
    owned by the engine, which the serving stack drives from ONE loop thread
    (engine_loop.py); ``generate()`` callers are single-threaded by contract.
    Metrics/stats readers on HTTP threads only touch scalar counters
    (``cache_hits``/``num_free``/...), where a stale read is harmless. Do not
    add cross-thread mutation here; route it through the engine loop's
    command queue instead.
    """

    def __init__(self, num_blocks: int, block_size: int, max_blocks_per_seq: int,
                 enable_prefix_cache: bool = False, window_back: Optional[int] = None,
                 num_window_blocks: int = 0):
        # a second table a sequence for layer kinds that keep a window only
        # (``LatentKVPool.win``, ``WindowKVPool.win``): ``window_back`` = how many
        # positions behind a query its layers still read; logical block ->
        # block of the window plane, given back once wholly behind the window
        self.window_back = window_back
        self.window_free: List[int] = list(range(1, num_window_blocks))  # block 0 = sentinel
        self.window_tables: Dict[int, Dict[int, int]] = {}
        if window_back is not None and enable_prefix_cache:
            raise ValueError("prefix caching is refused beside a window cache: a shared prefix's window "
                             "planes are gone by the time a second request could reuse its blocks")
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        self.total_usable_blocks = num_blocks - 1
        self.free: List[int] = list(range(1, num_blocks))  # block 0 = sentinel
        self.tables: Dict[int, List[int]] = {}
        self.lengths: Dict[int, int] = {}
        self.enable_prefix_cache = enable_prefix_cache
        self.ref: Dict[int, int] = {}  # block -> #sequences referencing it
        self._index: Dict[int, int] = {}  # chained prefix hash -> block
        self._block_hash: Dict[int, int] = {}  # registered block -> its hash
        self._lru: "OrderedDict[int, None]" = OrderedDict()  # zero-ref cached blocks
        self._cow_pairs: List[Tuple[int, int]] = []  # (src, dst) device copies owed
        self._cache_epoch = 0  # bumped by clear_prefix_cache()
        self._seq_epoch: Dict[int, int] = {}  # seq -> epoch it was allocated in
        self.cache_hits = 0  # allocations that reused >=1 cached block
        self.cached_tokens_total = 0  # prompt tokens whose prefill was skipped
        self.evictions = 0  # cached blocks recycled under pressure
        # hierarchical cache (kv_host_tier.py): with a tier attached, LRU
        # evictions queue (hash, block) pairs here instead of dropping the
        # content; the engine drains them into one batched D2H spill BEFORE
        # any device launch can overwrite the recycled blocks
        self.host_tier = None
        self._pending_spills: List[Tuple[bytes, int]] = []

    @property
    def num_free(self) -> int:
        """Blocks available to an allocation: the free list plus zero-ref
        cached blocks (evictable on demand, so they ARE capacity)."""
        return len(self.free) + len(self._lru)

    @property
    def num_cached_blocks(self) -> int:
        """Blocks currently registered in the prefix index (shared or idle)."""
        return len(self._block_hash)

    def blocks_needed(self, n_tokens: int) -> int:
        return (n_tokens + self.block_size - 1) // self.block_size

    def can_allocate(self, n_tokens: int) -> bool:
        return self.blocks_needed(n_tokens) <= self.num_free

    def can_admit(self, n_tokens: int, token_ids=None, match=None,
                  salt: Optional[str] = None) -> bool:
        """Like :meth:`can_allocate`, but cached prefix blocks don't need fresh
        capacity — the scheduler admits a warm request a cold one must wait for.

        Pass a precomputed ``match`` (from :meth:`match_prefix`) to skip
        re-hashing the prompt; matched blocks that are idle on the LRU are
        subtracted from available capacity — they can't be both "no fresh
        block needed" AND "evictable free capacity" at once."""
        if match is None and token_ids is not None:
            match = self.match_prefix(token_ids, min(len(token_ids), n_tokens),
                                      salt=salt)
        matched = match[0] if match is not None else []
        need = self.blocks_needed(n_tokens) - len(matched)
        return need <= self.num_free - self._idle_count(matched)

    # ------------------------------------------------------------- prefix cache
    def _idle_count(self, blocks) -> int:
        """How many of ``blocks`` currently sit on the (counted-as-free) LRU."""
        return sum(1 for b in blocks if b in self._lru)

    def _chain_hashes(self, token_ids, nb_full: int, salt: Optional[str] = None):
        """Chained sha256 content digests of the first ``nb_full`` full blocks.

        Cryptographic on purpose: the index serves another prompt's KV on a
        key collision with no further check, so a non-collision-resistant
        hash would be a silent-wrong-output (and cross-request leak) channel.

        ``salt`` seeds the chain (multi-LoRA: the adapter_id) so two tenants
        with identical prompts but different adapters never share KV — a LoRA
        delta changes every hidden state, so cross-adapter cache hits would be
        silently wrong. ``salt=None`` keeps the historical hash values: the
        no-adapter cache population is untouched."""
        h = hashlib.sha256(salt.encode()).digest() if salt else b""
        bs = self.block_size
        arr = np.ascontiguousarray(
            np.asarray(token_ids[: nb_full * bs], dtype=np.int64))
        out = []
        for i in range(nb_full):
            h = hashlib.sha256(h + arr[i * bs: (i + 1) * bs].tobytes()).digest()
            out.append(h)
        return out

    def match_prefix(self, token_ids, n_tokens: int, salt: Optional[str] = None):
        """Longest cached full-block prefix of ``token_ids``.

        Returns ``(shared_blocks, n_cached_tokens, cow_src)``: blocks to attach
        by reference, tokens covered, and — when the match would cover the whole
        prompt (leaving nothing to prefill) — the tail block to copy-on-write
        instead of sharing, so the re-prefilled last token never mutates a
        shared block. Pure lookup: acquires nothing. ``salt`` must match the
        salt the blocks were registered under (see :meth:`_chain_hashes`)."""
        if not self.enable_prefix_cache:
            return [], 0, None
        bs = self.block_size
        nb_full = min(len(token_ids), n_tokens) // bs
        matched: List[int] = []
        for h in self._chain_hashes(token_ids, nb_full, salt=salt):
            b = self._index.get(h)
            if b is None:
                break
            matched.append(b)
        if not matched:
            return [], 0, None
        if len(matched) * bs == n_tokens:
            # full cover: keep >=1 token to prefill (the sampler needs logits
            # at the last prompt position) — COW the tail block
            return matched[:-1], n_tokens - 1, matched[-1]
        return matched, len(matched) * bs, None

    def _acquire(self, block: int):
        """Take a reference on a cached block (removing it from the LRU if idle)."""
        self.ref[block] = self.ref.get(block, 0) + 1
        self._lru.pop(block, None)

    def _release_block(self, block: int):
        r = self.ref.get(block, 0) - 1
        if r > 0:
            self.ref[block] = r
            return
        self.ref.pop(block, None)
        if block in self._block_hash:
            # zero-ref but cached: evictable, not free — most-recently-used last
            self._lru[block] = None
            self._lru.move_to_end(block)
        else:
            self.free.append(block)

    def _pop_block(self) -> int:
        """A fresh private block: free list first, else evict the LRU cached
        block (allocation pressure is the ONLY thing that shrinks the cache).
        With a host tier attached the evicted block's hash demotes instead of
        dying: it is queued for the engine's batched D2H spill and the tier
        keeps serving it to future prefix matches (:meth:`host_match`)."""
        if self.free:
            b = self.free.pop()
        else:
            b, _ = self._lru.popitem(last=False)
            h = self._block_hash.pop(b)
            self._index.pop(h, None)
            self.evictions += 1
            if self.host_tier is not None and self.host_tier.accepting:
                self._pending_spills.append((h, b))
        self.ref[b] = 1
        return b

    # ------------------------------------------------------------- host tier
    def attach_host_tier(self, tier):
        """Hang a :class:`~.kv_host_tier.HostKVTier` under the LRU: evictions
        demote to it, :meth:`host_match` extends prefix matches into it."""
        self.host_tier = tier

    def drain_pending_spills(self) -> List[Tuple[bytes, int]]:
        """(hash, block) pairs evicted since the last drain; cleared on read.
        The engine MUST consume these before dispatching any device work that
        writes the recycled blocks — the spill gather reads them in dispatch
        order (exactly the COW-pairs contract one method up)."""
        out, self._pending_spills = self._pending_spills, []
        return out

    def host_match(self, token_ids, n_tokens: int, salt: Optional[str] = None,
                   skip: int = 0) -> List[bytes]:
        """Chain hashes of the full-block prefix run that continues past the
        device match (``skip`` = blocks the device index already covered)
        and is resident in the host tier. Pure lookup: pops nothing — the
        engine calls :meth:`HostKVTier.take` only once it has device blocks
        allocated to promote into."""
        if (not self.enable_prefix_cache or self.host_tier is None
                or not self.host_tier.accepting):
            return []
        bs = self.block_size
        nb_full = min(len(token_ids), n_tokens) // bs
        if nb_full <= skip:
            return []
        out: List[bytes] = []
        for h in self._chain_hashes(token_ids, nb_full, salt=salt)[skip:]:
            if not self.host_tier.contains(h):
                break
            out.append(h)
        return out

    def register_promoted(self, blocks: Sequence[int], hashes: Sequence[bytes]):
        """Re-register just-promoted blocks in the device index (the other
        half of the resident-XOR move that :meth:`HostKVTier.take` started).
        Content-addressed exactly like :meth:`finish_seq_cached`: a hash or
        block already claimed is simply skipped."""
        for b, h in zip(blocks, hashes):
            if h not in self._index and b not in self._block_hash:
                self._index[h] = b
                self._block_hash[b] = h

    def drain_cow_pairs(self) -> List[Tuple[int, int]]:
        """(src, dst) block copies the caller owes the device pool (see
        :func:`copy_blocks`); cleared on read."""
        pairs, self._cow_pairs = self._cow_pairs, []
        return pairs

    # ------------------------------------------------------------- allocation
    def allocate(self, seq_id: int, n_tokens: int, token_ids=None, match=None,
                 salt: Optional[str] = None):
        """Allocate a sequence's blocks.

        Plain call (``token_ids=None``): the uncached path — returns the block
        list, exactly the historical contract.

        With ``token_ids`` and prefix caching enabled: matches the longest
        cached full-block prefix and returns ``(cached_blocks,
        n_cached_tokens, new_blocks)``; the sequence's table is
        ``cached_blocks [+ cow dst] + new_blocks`` and the caller only
        prefills tokens ``[n_cached_tokens:]``. Pass the ``match`` a prior
        :meth:`match_prefix`/:meth:`can_admit` computed (no mutation may
        happen in between) to avoid re-hashing the prompt."""
        need = self.blocks_needed(n_tokens)
        if need > self.max_blocks_per_seq:
            raise ValueError(f"sequence needs {need} blocks > max_blocks_per_seq {self.max_blocks_per_seq}")
        if match is None and token_ids is not None:
            match = self.match_prefix(token_ids, n_tokens, salt=salt)
        shared, n_cached, cow_src = match if match is not None else ([], 0, None)
        n_fresh = need - len(shared)
        # matched idle blocks are about to leave the LRU: they can't double as
        # evictable capacity for this same allocation's fresh blocks
        available = self.num_free - self._idle_count(shared)
        if n_fresh > available:
            raise RuntimeError(f"out of KV blocks: need {n_fresh}, free {available}")
        # acquire shared refs BEFORE popping fresh blocks: a matched idle block
        # must leave the LRU first or the eviction path could recycle it
        for b in shared:
            self._acquire(b)
        if cow_src is not None and cow_src in self._lru:
            self._lru.move_to_end(cow_src)  # just used: keep it warm
        new_blocks = [self._pop_block() for _ in range(n_fresh)]
        if cow_src is not None:
            # new_blocks[0] becomes the private copy of the shared tail block
            self._cow_pairs.append((cow_src, new_blocks[0]))
        self.tables[seq_id] = shared + new_blocks
        self.lengths[seq_id] = n_tokens
        self._seq_epoch[seq_id] = self._cache_epoch
        if n_cached > 0:
            self.cache_hits += 1
            self.cached_tokens_total += n_cached
        if token_ids is not None:
            return shared, n_cached, new_blocks
        return self.tables[seq_id]

    def extend(self, seq_id: int, n_new_tokens: int = 1) -> Optional[List[int]]:
        """Grow a sequence; returns newly-allocated blocks (None if OOM -> preempt)."""
        new_len = self.lengths[seq_id] + n_new_tokens
        need = self.blocks_needed(new_len) - len(self.tables[seq_id])
        if need > 0:
            if need > self.num_free:
                return None
            if self.blocks_needed(new_len) > self.max_blocks_per_seq:
                return None
            new_blocks = [self._pop_block() for _ in range(need)]
            self.tables[seq_id].extend(new_blocks)
        else:
            new_blocks = []
        self.lengths[seq_id] = new_len
        return new_blocks

    # ------------------------------------------------------------- window table
    @property
    def table_shape(self) -> Tuple[int, ...]:
        """Shape of one sequence's :meth:`table_array`."""
        return (self.max_blocks_per_seq,) if self.window_back is None else (2, self.max_blocks_per_seq)

    def window_span(self, seq_id: int, start: int, n: int) -> int:
        """Before a launch feeds positions ``[start, start + n)`` of ``seq_id``:
        give back the window blocks that lie wholly behind ``start -
        window_back`` and take blocks up to the last position fed. Returns how
        many came back. A no-op (0) without a window cache. The window plane is
        sized so that every slot can hold its window plus one launch's tokens
        (``SingleDeviceBackend``), so running out is a fault, not pressure."""
        if self.window_back is None:
            return 0
        table = self.window_tables.setdefault(seq_id, {})
        bs = self.block_size
        first = max(0, start - self.window_back) // bs
        behind = [b for b in table if b < first]
        for b in behind:
            self.window_free.append(table.pop(b))
        for b in range(first, (start + n - 1) // bs + 1):
            if b not in table:
                if not self.window_free:
                    raise RuntimeError("out of window-cache blocks: the window plane is smaller than "
                                       "slots x (window + tokens a launch feeds)")
                table[b] = self.window_free.pop()
        return len(behind)

    def _drop_window(self, seq_id: int, keep_below: int = 0):
        table = self.window_tables.get(seq_id)
        if table is None:
            return
        for b in [b for b in table if b >= keep_below]:
            self.window_free.append(table.pop(b))
        if not keep_below:
            del self.window_tables[seq_id]

    def shrink(self, seq_id: int, new_len: int):
        """Release blocks beyond ``new_len`` tokens (undo speculative multi-step
        extension after a sequence finished early). Refcount-aware: a shared
        block dropped from this table survives for its other holders."""
        if seq_id not in self.tables:
            return
        keep = max(self.blocks_needed(new_len), 1)
        self._drop_window(seq_id, keep_below=keep)
        blocks = self.tables[seq_id]
        if keep < len(blocks):
            for b in blocks[keep:]:
                self._release_block(b)
            del blocks[keep:]
        self.lengths[seq_id] = new_len

    def free_seq(self, seq_id: int):
        """Release a sequence WITHOUT registering its blocks (abort/preempt)."""
        blocks = self.tables.pop(seq_id, [])
        self.lengths.pop(seq_id, None)
        self._seq_epoch.pop(seq_id, None)
        self._drop_window(seq_id)
        for b in blocks:
            self._release_block(b)

    def finish_seq_cached(self, seq_id: int, token_ids, salt: Optional[str] = None):
        """Release a finished sequence, registering its full prompt blocks in
        the prefix index so later requests skip their prefill.

        Chain registration is content-addressed: a block whose hash is already
        claimed by another block is simply not registered (deeper blocks still
        are — a future match mixes providers freely, content is identical).

        A sequence allocated before the last :meth:`clear_prefix_cache` holds
        KV computed under superseded params — it releases without registering
        (the epoch check), otherwise it would re-poison the cleared index."""
        blocks = self.tables.pop(seq_id, None)
        self.lengths.pop(seq_id, None)
        epoch = self._seq_epoch.pop(seq_id, None)
        self._drop_window(seq_id)
        if blocks is None:
            return
        if self.enable_prefix_cache and token_ids is not None and epoch == self._cache_epoch:
            bs = self.block_size
            nb_full = min(len(token_ids) // bs, len(blocks))
            for i, h in enumerate(self._chain_hashes(token_ids, nb_full, salt=salt)):
                b = blocks[i]
                if h not in self._index and b not in self._block_hash:
                    self._index[h] = b
                    self._block_hash[b] = h
                    # resident-XOR: a cold re-prefill of a spilled span just
                    # re-registered device-side — the (identical-content) host
                    # copy is displaced, and any still-queued spill of it dies
                    # before the drain would double-register it
                    if self.host_tier is not None:
                        self.host_tier.discard(h)
                        if self._pending_spills:
                            self._pending_spills = [
                                p for p in self._pending_spills if p[0] != h]
        for b in blocks:
            self._release_block(b)

    def clear_prefix_cache(self):
        """Drop every idle cached block back to the free list (index reset)."""
        for b in list(self._lru):
            self._index.pop(self._block_hash.pop(b), None)
            self.free.append(b)
        self._lru.clear()
        # blocks still referenced by running sequences stay out of the index
        # from now on: unregister them so they free normally on release
        for b in list(self._block_hash):
            self._index.pop(self._block_hash.pop(b), None)
        # in-flight sequences hold KV from before the clear: the epoch bump
        # stops finish_seq_cached from re-registering it into the fresh index
        self._cache_epoch += 1
        # the host tier is the same cache one level down: a promoted pre-swap
        # block serving post-swap traffic would splice KV across weight
        # generations, so queued spills die and resident entries invalidate
        self._pending_spills.clear()
        if self.host_tier is not None:
            self.host_tier.clear()

    def table_array(self, seq_id: int) -> np.ndarray:
        """Padded table row (sentinel block 0 for unused slots); with a window
        cache, two rows: the block table and the window table, the latter
        holding blocks at the logical blocks inside the window and 0 elsewhere."""
        out = np.zeros(self.table_shape, dtype=np.int32)
        blocks = self.tables.get(seq_id, [])
        if self.window_back is None:
            out[: len(blocks)] = blocks
            return out
        out[0, : len(blocks)] = blocks
        for logical, block in self.window_tables.get(seq_id, {}).items():
            out[1, logical] = block
        return out

    def longest_seq(self) -> Optional[int]:
        """Preemption candidate (reference step.cu preempts the longest)."""
        if not self.lengths:
            return None
        return max(self.lengths, key=lambda s: self.lengths[s])
