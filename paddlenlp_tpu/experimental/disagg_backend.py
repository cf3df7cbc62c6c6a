"""Disaggregated prefill/decode serving backend: MPMD two-stage execution.

The third :class:`~.backend.ModelBackend` implementation — the stage-split
PR 8 reserved the seam for (backend.py's "MPMD stage-split seam" note, per
*Scaling Deep Learning Training with MPMD Pipeline Parallelism*). Chunked
prefill time-slices the TTFT-vs-inter-token contention on one device group;
this backend *removes* it: prompt processing (monolithic prefill and the
chunk rows of mixed steps) executes on a **prefill stage** and decode rows on
a **decode stage**, each its own device group with its own tp layout, sized
independently (``stages=(P, D)`` device counts).

Layout — two disjoint sub-meshes of the ``(dp, tp)`` mesh:

- each stage is a :class:`~.sharded_backend.ShardedBackend` pinned to an
  explicit device slice (``devices[:P]`` / ``devices[P:P+D]``), so each stage
  keeps the all-gather column-parallel layout that is bitwise token-identical
  to :class:`~.backend.SingleDeviceBackend` — the disagg engine inherits the
  token-identity contract stage by stage;
- both stages allocate a **full-size paged pool** over ONE shared block-id
  space (the engine's single ``BlockManager``): a block id addresses the same
  logical block in either pool, so the engine's block tables stay valid on
  both stages and migration never rewrites a table — only the pool tensor
  behind it moves.

**KV-block migration.** A sequence's prompt KV is written on the prefill
stage; decode reads it on the decode stage. When the last prefill chunk lands
(first token sampled), the engine calls :meth:`DisaggBackend.kv_migrate`: the
sequence's table blocks are gathered on the prefill mesh, ``device_put``
across meshes, and scattered into the decode pool — all async dispatches the
host never blocks on, so the copy stream overlaps subsequent decode steps.
Correctness needs no gate at all (the decode pool tensor is threaded
functionally, so XLA orders the scatter before any later decode read); the
``migration_ready`` poll is the *scheduling* gate — a sequence becomes
decode-eligible only once its blocks have landed, so a decode step never
stalls on an in-flight copy. Per-sequence penalty counts migrate as a
host-truth re-seed (bincount of ``prompt + emitted``, exactly the engine's
quarantine ``resync_counts`` rule) — the same integers the prefill stage
accumulated, so penalty sampling stays token-exact across the handoff.

Shared prefix-cache blocks live in BOTH pools: their content is written once
on the prefill stage (chunk attention reads them there) and copied to the
decode pool by every migration that references them — identical bytes, so
concurrent re-copies are idempotent. COW copies run on the prefill pool only
(the re-prefilled tail is prefill-stage work); migration carries the result
across.

Testable anywhere: ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
backs both stages with virtual CPU devices, and the parity suite
(tests/experimental/test_disagg_backend.py) asserts bitwise token identity
against the single-device engine.
"""

from __future__ import annotations

import dataclasses
import itertools
from collections import deque
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..observability.goodput import LAUNCH_GEOMETRY
from ..utils.log import logger
from .backend import MixedRow, ModelBackend
from .kv_host_tier import gather_blocks, pool_block_bytes, scatter_blocks
from .paged_cache import PagedKVPool
from .sharded_backend import ShardedBackend

__all__ = ["DisaggBackend", "MigrationTicket"]


def _normalize_stages(stages) -> Tuple[int, int]:
    """``(P, D)`` device counts for the prefill / decode stages."""
    if isinstance(stages, (tuple, list)) and len(stages) == 2:
        p, d = int(stages[0]), int(stages[1])
        if p >= 1 and d >= 1:
            return p, d
    raise ValueError(
        f"disagg stages must be a (prefill_devices, decode_devices) pair of "
        f"positive ints; got {stages!r}")


@dataclasses.dataclass
class MigrationTicket:
    """One in-flight prefill→decode block migration (engine-held)."""

    seq_id: int
    n_blocks: int
    markers: tuple  # device scalars completing when each plane's copy lands
    polls: int = 0  # force-land fallback counter (engine-side scheduling)


class DisaggBackend(ModelBackend):
    """Two-stage MPMD backend: prefill rows on one device group, decode rows
    on another, paged KV blocks migrating between the stage pools.

    ``InferenceEngine(disagg_stages=(P, D))`` selects it. The engine's
    scheduler stays device-free: it sees the ordinary backend interface plus
    the three migration hooks (:meth:`kv_migrate`, :meth:`migration_ready`,
    ``migration_stats``) and owns all migration *scheduling* (stage-aware
    admission, the decode-pressure gate, the in-flight bound)."""

    #: engines check this to enable migration scheduling
    staged = True

    def __init__(self, model, *, stages, **kw):
        p_devs, d_devs = _normalize_stages(stages)
        devices = jax.devices()
        if p_devs + d_devs > len(devices):
            raise ValueError(
                f"disagg stages {stages!r} need {p_devs + d_devs} devices, "
                f"{len(devices)} available (CPU runs: set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={p_devs + d_devs})")
        self.model = model
        self.max_batch_size = kw["max_batch_size"]
        self.step_accounting = {"fed": 0, "shape": ()}
        # two disjoint sub-meshes: each stage is a full ShardedBackend over its
        # own device slice (engine.shard_init fires once per stage, so a
        # supervisor rebuild of either stage is chaos-coverable)
        self.prefill_stage = ShardedBackend(
            model, mesh_shape=(1, p_devs), devices=devices[:p_devs],
            stage="prefill", **kw)
        self.decode_stage = ShardedBackend(
            model, mesh_shape=(1, d_devs), devices=devices[p_devs:p_devs + d_devs],
            stage="decode", **kw)
        self._build_migration_jits()
        # bytes one block carries across the wire (+ scale)
        self._block_bytes = pool_block_bytes(self.decode_stage.pool)
        # monotone migration accounting + a bounded (seq, blocks, bytes) event
        # ring the metrics plane drains by sequence number (same contract as
        # the engine's chunk rings: stats() reads never consume events)
        self.migration_stats = {"migrations": 0, "blocks": 0, "bytes": 0}
        self.recent_migrations: deque = deque(maxlen=256)
        self._mig_seq = itertools.count(1)
        if p_devs != d_devs:
            logger.info(
                f"disagg backend: asymmetric stages prefill={p_devs} decode={d_devs} "
                "(independent tp layouts; migration reshards in flight)")

    def _build_migration_jits(self):
        """Migration copy programs, compiled with the same explicit-placement
        contract as every other step program (sharding-contract checker):
        gather on the prefill mesh, scatter (pool donated) on the decode
        mesh. The cross-mesh hop itself is a ``device_put`` at call time."""
        p_inf, d_inf = self.prefill_stage.infer, self.decode_stage.infer
        p_kv_s = p_inf.pool_shardings.kv
        d_kv_s = d_inf.pool_shardings.kv
        self._kv_data_sharding = d_kv_s  # block-slice layout == pool layout
        self._gather_kv = jax.jit(
            gather_blocks, donate_argnums=(),
            in_shardings=(p_kv_s, p_inf._repl), out_shardings=p_kv_s)
        self._scatter_kv = jax.jit(
            scatter_blocks, donate_argnums=(0,),
            in_shardings=(d_kv_s, d_kv_s, d_inf._repl),
            out_shardings=(d_kv_s, d_inf._repl))
        # the reverse direction (decode→prefill) serves kv_writeback:
        # generated-token KV exists only in the decode pool, but prefix-cache
        # reads (chunk attention, host-tier spills) happen on the prefill
        # stage — registering generated blocks requires carrying them back
        self._gather_kv_back = jax.jit(
            gather_blocks, donate_argnums=(),
            in_shardings=(d_kv_s, d_inf._repl), out_shardings=d_kv_s)
        self._scatter_kv_back = jax.jit(
            scatter_blocks, donate_argnums=(0,),
            in_shardings=(p_kv_s, p_kv_s, p_inf._repl),
            out_shardings=(p_kv_s, p_inf._repl))
        self._kv_back_sharding = p_kv_s
        if self.decode_stage.pool.scale is not None:
            p_s = p_inf.pool_shardings.scale
            d_s = d_inf.pool_shardings.scale
            self._scale_data_sharding = d_s
            self._gather_scale = jax.jit(
                gather_blocks, donate_argnums=(),
                in_shardings=(p_s, p_inf._repl), out_shardings=p_s)
            self._scatter_scale = jax.jit(
                scatter_blocks, donate_argnums=(0,),
                in_shardings=(d_s, d_s, d_inf._repl),
                out_shardings=(d_s, d_inf._repl))
            self._gather_scale_back = jax.jit(
                gather_blocks, donate_argnums=(),
                in_shardings=(d_s, d_inf._repl), out_shardings=d_s)
            self._scatter_scale_back = jax.jit(
                scatter_blocks, donate_argnums=(0,),
                in_shardings=(p_s, p_s, p_inf._repl),
                out_shardings=(p_s, p_inf._repl))
            self._scale_back_sharding = p_s

    # ------------------------------------------------------------- device state
    # the decode stage is "the" pool/counts/infer for read paths that predate
    # the stage split (tests, tools, the metrics plane): decode is where
    # sequences live for most of their lifetime
    @property
    def infer(self):
        return self.decode_stage.infer

    @property
    def pool(self):
        return self.decode_stage.pool

    @property
    def counts(self):
        return self.decode_stage.counts

    @property
    def params(self):
        return self.decode_stage.params

    def sync_params(self, new_params):
        """Atomic two-stage resync: both stage placements are staged BEFORE
        either stage's binding moves, so no step can ever launch prefill rows
        on one weight version and decode rows on the other — if the second
        ``device_put`` raises, neither stage changed. Each stage keeps its own
        mesh/NamedSharding layout; pools, counts and in-flight migrations are
        untouched (KV is invalidated one level up via the prefix-cache
        epoch)."""
        p_placed = jax.device_put(new_params, self.prefill_stage.infer.param_shardings)
        d_placed = jax.device_put(new_params, self.decode_stage.infer.param_shardings)
        self.model.params = new_params
        self.prefill_stage._params_src = new_params
        self.prefill_stage._params = p_placed
        self.decode_stage._params_src = new_params
        self.decode_stage._params = d_placed

    # ------------------------------------------------------------- steps
    def prefill(self, input_ids, block_tables, suffix_lens, cached_entries,
                sampling, slot_idx, adapter_table=None):
        out = self.prefill_stage.prefill(
            input_ids, block_tables, suffix_lens, cached_entries, sampling, slot_idx,
            adapter_table=adapter_table)
        self.step_accounting = self.prefill_stage.step_accounting
        return out

    def decode(self, last_tokens, block_tables, context_lens, done0, remaining,
               sampling, adapter_table=None):
        out = self.decode_stage.decode(
            last_tokens, block_tables, context_lens, done0, remaining, sampling,
            adapter_table=adapter_table)
        self.step_accounting = self.decode_stage.step_accounting
        return out

    def verify(self, tokens, block_tables, start_pos, need_logits: bool,
               adapter_table=None):
        out = self.decode_stage.verify(tokens, block_tables, start_pos, need_logits,
                                       adapter_table=adapter_table)
        self.step_accounting = self.decode_stage.step_accounting
        return out

    def mixed_step(self, chunk_rows: List[MixedRow], decode_rows: List[MixedRow]):
        """One engine mixed step = up to TWO stage programs: chunk rows on the
        prefill stage, decode rows on the decode stage — distinct programs on
        distinct device groups (the MPMD split). BOTH programs are dispatched
        before either is collected, so the stages compute concurrently: a
        decode row never waits on the host serializing it behind a chunk
        forward (the whole point of disaggregation, preserved off-TPU too).
        Returns tokens in ``[*chunk_rows, *decode_rows]`` order, the
        single-backend contract."""
        collectors = []
        acct = {"fed": 0, **{g: 0 for g in LAUNCH_GEOMETRY}}
        shapes = []
        for tag, stage, chunks, decodes in (("stage_prefill", self.prefill_stage, chunk_rows, []),
                                            ("stage_decode", self.decode_stage, [], decode_rows)):
            if chunks or decodes:
                collectors.append(stage.mixed_step_begin(chunks, decodes))
                for k in acct:
                    acct[k] += stage.step_accounting[k]
                shapes.append((tag,) + stage.step_accounting["shape"])
        # one engine mixed step = the SUM of both stage launches: the goodput
        # ledger accounts device positions burnt fleet-of-stages-wide
        self.step_accounting = dict(acct, shape=tuple(shapes))
        if not collectors:
            return np.zeros(0, np.int32)
        return np.concatenate([collect() for collect in collectors])

    def apply_cow(self, pairs):
        # COW serves the re-prefill of the tail token — prefill-stage work;
        # migration carries the private copy into the decode pool later
        self.prefill_stage.apply_cow(pairs)

    def seed_counts(self, slot_idx, cached_entries):
        # chunk rows accumulate onto the prefill counts; the decode row is
        # re-seeded at migration. Seeding BOTH keeps either stage's row exact
        # for whichever program touches the slot next (quarantine resyncs
        # land here too, where live slots may sit on either stage).
        self.prefill_stage.seed_counts(slot_idx, cached_entries)
        self.decode_stage.seed_counts(slot_idx, cached_entries)

    def reset_counts(self):
        self.prefill_stage.reset_counts()
        self.decode_stage.reset_counts()

    # ------------------------------------------------------------- migration
    def kv_migrate(self, seq_id: int, blocks: Sequence[int], slot: int,
                   token_hist) -> MigrationTicket:
        """Start moving one sequence's KV blocks prefill→decode.

        Everything here is an async dispatch: gather on the prefill mesh,
        cross-mesh ``device_put``, scatter into the (donated) decode pool.
        The new decode pool is bound immediately — later decode steps are
        functionally ordered after the copy — and the returned ticket's
        markers tell the engine when the blocks have physically landed.
        ``token_hist`` (host ids: prefilled prompt + emitted tokens) re-seeds
        the slot's decode-stage penalty counts exactly."""
        ids = [int(b) for b in blocks]
        n = len(ids)
        # pad to pow2 with sentinel self-copies (block 0 is never a live dst),
        # bounding the gather/scatter to log2(max_blocks_per_seq) compiles
        padded = 1
        while padded < max(n, 1):
            padded *= 2
        ids_arr = jnp.asarray(ids + [0] * (padded - n), jnp.int32)
        src = self._gather_kv(self.prefill_stage.pool.kv, ids_arr)
        moved = jax.device_put(src, self._kv_data_sharding)
        new_kv, marker = self._scatter_kv(self.decode_stage.pool.kv, moved, ids_arr)
        markers = [marker]
        scale = self.decode_stage.pool.scale
        if scale is not None:
            s_src = self._gather_scale(self.prefill_stage.pool.scale, ids_arr)
            s_moved = jax.device_put(s_src, self._scale_data_sharding)
            scale, s_marker = self._scatter_scale(scale, s_moved, ids_arr)
            markers.append(s_marker)
        self.decode_stage.pool = PagedKVPool(kv=new_kv, scale=scale)
        self.decode_stage.seed_counts([slot], [(0, token_hist, len(token_hist))])
        moved_bytes = n * self._block_bytes
        self.migration_stats["migrations"] += 1
        self.migration_stats["blocks"] += n
        self.migration_stats["bytes"] += moved_bytes
        self.recent_migrations.append((next(self._mig_seq), n, moved_bytes))
        return MigrationTicket(seq_id=seq_id, n_blocks=n, markers=tuple(markers))

    # migration_ready: inherited from ModelBackend — the marker poll is the
    # same non-blocking scheduling signal for stage migrations and host-tier
    # promotions (correctness never needs it; functional threading orders
    # every pool read after the copy).

    # ------------------------------------------------------------- host tier
    # Registered prefix blocks live canonically in the PREFILL pool (written
    # there by chunk/prefill work, carried to decode by migrations), so the
    # hierarchical tier spills from and promotes into the prefill stage; a
    # promoted sequence's ordinary prefill→decode migration then carries the
    # promoted blocks across like any other prefix hit.
    def kv_spill(self, block_ids):
        return self.prefill_stage.kv_spill(block_ids)

    def kv_promote(self, seq_id, block_ids, host_kv, host_scale=None):
        return self.prefill_stage.kv_promote(seq_id, block_ids, host_kv,
                                             host_scale=host_scale)

    def kv_writeback(self, block_ids):
        """Carry generated-token KV decode→prefill so the blocks can join the
        prefix index: async gather on the decode mesh, cross-mesh
        ``device_put``, scatter into the (donated) prefill pool — kv_migrate
        run in reverse, with the same sentinel padding. No ticket: nothing
        gates on the landing (future prefill reads are functionally ordered
        after the scatter)."""
        ids = [int(b) for b in block_ids]
        n = len(ids)
        padded = 1
        while padded < max(n, 1):
            padded *= 2
        ids_arr = jnp.asarray(ids + [0] * (padded - n), jnp.int32)
        src = self._gather_kv_back(self.decode_stage.pool.kv, ids_arr)
        moved = jax.device_put(src, self._kv_back_sharding)
        new_kv, _ = self._scatter_kv_back(self.prefill_stage.pool.kv, moved, ids_arr)
        scale = self.prefill_stage.pool.scale
        if scale is not None:
            s_src = self._gather_scale_back(self.decode_stage.pool.scale, ids_arr)
            s_moved = jax.device_put(s_src, self._scale_back_sharding)
            scale, _ = self._scatter_scale_back(scale, s_moved, ids_arr)
        self.prefill_stage.pool = PagedKVPool(kv=new_kv, scale=scale)
        return None

    # ------------------------------------------------------------- misc
    def describe(self) -> dict:
        p, d = self.prefill_stage.describe(), self.decode_stage.describe()
        return {
            "kind": "disagg",
            "devices": p["devices"] + d["devices"],
            "tp_degree": d["tp_degree"],  # decode is the steady-state stage
            "mesh": {"prefill_tp": p["tp_degree"], "decode_tp": d["tp_degree"]},
            "stages": {"prefill": p, "decode": d},
            "kv_pool_sharded": d["kv_pool_sharded"],
        }
