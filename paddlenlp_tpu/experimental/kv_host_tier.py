"""Host-RAM KV spill tier: the second level of the hierarchical prefix cache.

HBM bounds the prefix cache today — when allocation pressure pops a zero-ref
cached block off the :class:`~.paged_cache.BlockManager` LRU, its KV bytes
are simply recycled and a later identical prompt re-prefills from scratch.
This module keeps those bytes alive one level down: the engine gathers the
evicted blocks out of the device pool (one batched async D2H per step, the
:mod:`~.disagg_backend` migration gather pointed at the host) and registers
them here under the SAME chained content hashes the device index used. A
later prefix match that runs past the device index and lands on host-tier
entries promotes them back with an async H2D scatter dispatched ahead of
prefill — the PR 12 migration machinery verbatim: a data-dependent marker
scalar gates *scheduling* (``kv_stage == "promoting"`` until it lands,
overlapped with other slots' decode steps) while the pool's functional
threading already guarantees *correctness* ordering.

Invariants the tests pin:

- a chain hash is resident in the device index XOR the host tier — spill
  moves it down (``_pop_block`` unregisters, the engine ``put``s here),
  promote moves it back up (``take`` pops here, ``register_promoted``
  re-registers there). Leaks in either direction show up as double-resident
  or vanished hashes under churn.
- promoted bytes are bitwise-identical to the bytes spilled: the tier never
  touches content, so an evict-to-host-then-promote run streams the exact
  tokens a never-evicted run does.
- weight swaps invalidate the tier with the device cache
  (``clear_prefix_cache`` → :meth:`HostKVTier.clear`): a pre-swap block must
  never splice old-weights KV into post-swap traffic.

Spill batches hold the gathered device array until the *next* spill (or
their own ``take``) settles them to numpy — ``copy_to_host_async`` is
dispatched at gather time, so the eventual ``np.asarray`` finds the copy
already landed instead of blocking a hot path on D2H.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax  # noqa: F401  (jnp is the real dependency; kept for parity with siblings)
import jax.numpy as jnp
import numpy as np

__all__ = ["HostKVTier", "HostPromoteTicket", "gather_blocks", "scatter_blocks",
           "pool_block_bytes"]


def gather_blocks(src, ids):
    """Pull whole blocks (all layers, K and V planes) out of a stage pool —
    the migration gather (disagg_backend) reused for the D2H spill read."""
    return src[:, :, ids]


def scatter_blocks(dst, data, ids):
    """Land promoted blocks in the device pool. The second output is a tiny
    marker scalar data-dependent on the scatter result: it completes exactly
    when the copy has landed and — unlike the (donated-away-next-step) pool
    tensor itself — stays safe to poll with ``is_ready()``."""
    out = dst.at[:, :, ids].set(data)
    marker = (out[0, 0, 0, 0, 0] * 0).astype(jnp.int32) + ids.shape[0]
    return out, marker


def pool_block_bytes(pool) -> int:
    """Bytes one block carries across the host boundary: [L, 2, bs, K*H]
    (+ the [L, 2, bs, K] scale plane for quantized pools)."""
    planes = [pool.kv] if pool.scale is None else [pool.kv, pool.scale]
    return sum(int(a.dtype.itemsize * a.shape[0] * 2 * a.shape[3] * a.shape[4]) for a in planes)


@dataclasses.dataclass
class HostPromoteTicket:
    """One in-flight host→device block promotion (engine-held). Shape-
    compatible with :class:`~.disagg_backend.MigrationTicket` so the engine's
    marker-poll scheduling gate (``migration_ready``) serves both."""

    seq_id: int
    n_blocks: int
    markers: tuple  # device scalars completing when each plane's copy lands
    polls: int = 0  # force-land fallback counter (engine-side scheduling)


@dataclasses.dataclass
class _SpillBatch:
    """One batched spill's payload: gathered [L, 2, n, bs, K*H] planes,
    device-resident until settled (D2H already in flight), then numpy."""

    kv: object
    scale: object  # None for unquantized pools
    live: int  # resident tier entries still pointing into this batch
    settled: bool = False

    def settle(self):
        if not self.settled:
            # the async D2H was dispatched at gather time; this materializes
            # the landed copy and drops the device buffers
            self.kv = np.asarray(self.kv)  # sync-ok: copy_to_host_async dispatched at spill time — this reads the landed host copy
            if self.scale is not None:
                self.scale = np.asarray(self.scale)  # sync-ok: same landed D2H copy, scale plane
            self.settled = True


class HostKVTier:
    """Host-side LRU of spilled prefix-cache blocks, keyed by chain hash.

    Owned by the engine loop thread exactly like the :class:`BlockManager`
    it sits under (same lock-free-by-confinement concurrency model); the
    metrics plane only reads the scalar ``stats`` counters, where a stale
    read is harmless. ``max_blocks == 0`` disables the tier (``accepting``
    False) so the manager's spill hook stays dormant.
    """

    def __init__(self, max_blocks: int, block_bytes: int = 0):
        self.max_blocks = int(max_blocks)
        self.block_bytes = int(block_bytes)
        # hash -> (batch, row index along the gathered blocks axis)
        self._entries: "OrderedDict[bytes, Tuple[_SpillBatch, int]]" = OrderedDict()
        #: monotone counters (the metrics plane deltas these) + the live size
        self.stats: Dict[str, int] = {
            "spills": 0,          # spilled blocks, total
            "spill_batches": 0,   # batched D2H dispatches, total
            "promotes": 0,        # promote (take) calls, total
            "promoted_blocks": 0,
            "promote_bytes": 0,
            "evictions": 0,       # host-LRU evictions under tier pressure
        }

    # ------------------------------------------------------------- queries
    @property
    def accepting(self) -> bool:
        return self.max_blocks > 0

    @property
    def num_blocks(self) -> int:
        """Blocks currently resident in the tier."""
        return len(self._entries)

    def contains(self, h: bytes) -> bool:
        return h in self._entries

    def snapshot(self) -> Dict[str, int]:
        out = dict(self.stats)
        out["blocks"] = len(self._entries)
        out["capacity"] = self.max_blocks
        return out

    # ------------------------------------------------------------- mutation
    def _drop_entry(self, h: bytes):
        batch, _row = self._entries.pop(h)
        batch.live -= 1

    def put(self, hashes: List[bytes], kv, scale=None):
        """Register one spill batch: ``kv``/``scale`` are the gathered
        [L, 2, n, bs, K*H] planes (rows beyond ``len(hashes)`` are pow2
        padding and never referenced) with their D2H copies already in
        flight. Earlier batches settle to numpy here — one batch of deferral
        means the async copy has had a full engine step to land."""
        if not self.accepting or not hashes:
            return
        for _h, (batch, _row) in list(self._entries.items()):
            batch.settle()
        new = _SpillBatch(kv=kv, scale=scale, live=0)
        for row, h in enumerate(hashes):
            if h in self._entries:
                # re-spill of a hash already resident: newest content wins
                # (identical bytes by content-addressing, but the old batch
                # must drop its reference either way)
                self._drop_entry(h)
            self._entries[h] = (new, row)
            self._entries.move_to_end(h)
            new.live += 1
        self.stats["spills"] += len(hashes)
        self.stats["spill_batches"] += 1
        while len(self._entries) > self.max_blocks:
            oldest = next(iter(self._entries))
            self._drop_entry(oldest)
            self.stats["evictions"] += 1

    def take(self, hashes: List[bytes]):
        """Pop ``hashes`` (resident-XOR invariant: a promoted hash leaves the
        tier — the engine re-registers it in the device index) and return
        their stacked planes ``(kv [L, 2, m, bs, K*H], scale | None,
        nbytes)`` ready for the H2D scatter."""
        kv_rows, scale_rows = [], []
        for h in hashes:
            batch, row = self._entries[h]
            batch.settle()
            kv_rows.append(batch.kv[:, :, row])
            if batch.scale is not None:
                scale_rows.append(batch.scale[:, :, row])
            self._drop_entry(h)
        kv = np.stack(kv_rows, axis=2)
        scale = np.stack(scale_rows, axis=2) if scale_rows else None
        nbytes = len(hashes) * self.block_bytes
        self.stats["promotes"] += 1
        self.stats["promoted_blocks"] += len(hashes)
        self.stats["promote_bytes"] += nbytes
        return kv, scale, nbytes

    def discard(self, h: bytes):
        """Drop one hash if resident — the device index just (re-)claimed it
        (cold re-prefill of a spilled span), and resident-XOR says the tier
        copy yields. Content-addressing makes the two copies identical, so
        this is bookkeeping, not invalidation."""
        if h in self._entries:
            self._drop_entry(h)

    def clear(self):
        """Invalidate every resident block (weight swap / cache-epoch bump:
        pre-swap KV must never serve post-swap traffic)."""
        for h in list(self._entries):
            self._drop_entry(h)
