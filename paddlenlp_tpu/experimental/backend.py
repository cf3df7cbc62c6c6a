"""Pluggable model backend: the seam between the scheduler and the device.

``InferenceEngine`` (engine.py) owns *scheduling* — the waiting queue, slot
binding, the ``BlockManager``, preemption, chunk budgets, prefix-cache
bookkeeping, speculative acceptance. Everything that touches the device —
model params, the paged KV pool, the per-slot penalty-count tensor, and the
jitted step programs — lives behind a :class:`ModelBackend`. The engine talks
to it in host numpy and plain Python; the backend decides placement, layout
and compilation.

Contract (one backend == one way to run the forward + lay out KV):

- ``prefill(...)``       batched monolithic prompt prefill, samples token 0;
- ``decode(...)``        multi-token decode for every running slot;
- ``mixed_step(...)``    one ragged step of prefill chunks + decode tokens;
- ``verify(...)``        speculative-decoding verify forward;
- ``seed_counts``/``reset_counts``  per-slot penalty-count maintenance;
- ``apply_cow(pairs)``   prefix-cache copy-on-write block copies in the pool;
- ``describe()``         placement metadata for ``stats()``/the metrics plane.

Every step entry point additionally stamps ``self.step_accounting`` —
``{"fed": <token positions the launch processed>, "shape": <launch-geometry
key>, **LAUNCH_GEOMETRY}`` — immediately before dispatch. The engine reads it
right after the call to feed the goodput ledger (observability/goodput.py):
``fed`` is the *padded* geometry (``n_rows * bucket_width``), which is what
the device actually burnt cycles on, ``shape`` keys the live shape-bucket
cardinality gauge, and the ``LAUNCH_GEOMETRY`` counts (``rows_live``,
``rows``, ``kv_positions``: what the launch was asked to do, see
:func:`launch_geometry`) ride the launch span and the ledger's per-program
totals. A decode launch only knows its attended positions once ``valid`` is
back, so it restamps after its sync. Backends never decompose fed into
useful/padding/rework — that split needs scheduler knowledge (prefix hits,
preemption history, speculative acceptance) the backend deliberately does
not have.

Each entry point records two child spans of the engine's launch span:
``dispatch`` and ``wait`` (the ``np.asarray`` sync point), mirrored to the
profiler like every live engine span. ``dispatch`` holds ONE host-to-device
transfer and the jit call returning: every host array the launch takes (ids,
block tables, lengths, flags, the rows' sampling parameters, adapter rows)
rides one packed buffer (launch_pack.py) that the step program takes apart, so
a launch pays one transfer where it paid 13 to 19. The span's ``h2d_arrays`` /
``h2d_bytes`` args say what crossed inside it: 1 and the buffer's bytes, one
more array where a prefix hit ships its cached counts.

External weight updates (serving epochs, PPO rollouts) flow through the
``params`` property: callers rebind ``model.params`` and the backend picks it
up on the next step (the sharded backend re-places the tree on its mesh via
an id check).

Implementations:

- :class:`SingleDeviceBackend` — the historical engine layout: everything on
  the default device, ``PagedInferenceModel`` jits with no sharding
  annotations.
- ``ShardedBackend`` (sharded_backend.py) — weights + KV pool laid out with
  ``jax.sharding.NamedSharding`` over a ``parallel.mesh`` Mesh; the same
  scheduler runs unchanged on top.

**MPMD stage-split seam.** A two-stage disaggregated prefill/decode backend
(per *Scaling Deep Learning Training with MPMD Pipeline Parallelism*) is a
THIRD implementation of this interface, not an engine rewrite: ``prefill`` /
the chunk rows of ``mixed_step`` run on the prefill stage's mesh, ``decode`` /
the decode rows on the decode stage's mesh, and the backend migrates a
sequence's KV blocks between the two pools when its last chunk lands (the
block-table indirection means the engine's tables stay valid — only the pool
tensor behind them moves). Nothing in the engine assumes the four entry
points share a device, a pool tensor, or even a process; the only cross-call
state the engine relies on is that KV written by one call is readable by the
next call *for the same sequence*. ``DisaggBackend`` (disagg_backend.py)
implements it: backends that set ``staged = True`` additionally expose
``kv_migrate(seq_id, blocks, slot, token_hist)`` → ticket and
``migration_ready(ticket)``, and the engine gates a sequence's
decode-eligibility on the landed migration (the scheduler still never
touches the device — it only polls tickets).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..observability.tracer import TRACER
from .inference_model import SAMP_FIELDS, PagedInferenceModel, inference_model_class
from .kv_host_tier import HostPromoteTicket, gather_blocks, scatter_blocks
from .launch_pack import pack
from .paged_cache import PagedKVPool, copy_blocks

__all__ = ["ModelBackend", "SingleDeviceBackend", "MixedRow", "BlockRow", "samp_arrays",
           "launch_geometry"]


def launch_geometry(rows: int, q_lens, kv_lens) -> dict:
    """The LAUNCH_GEOMETRY counts of one launch (observability/goodput.py).
    ``q_lens`` holds the real query tokens of each live row (a dead or padding
    row has none), ``kv_lens`` the KV positions each live row's attention had
    to read; ``rows`` is the padded row count."""
    live = np.asarray(q_lens, np.int64).reshape(-1) > 0  # sync-ok: host row counts
    kv_lens = np.asarray(kv_lens, np.int64).reshape(-1)  # sync-ok: host row counts
    return {"rows_live": int(live.sum()),  # sync-ok: host numpy
            "rows": int(rows),
            "kv_positions": int(kv_lens[live].sum())}  # sync-ok: host numpy


#: SamplingParams attribute, padding-row default and dtype of each of ``SAMP_FIELDS``
_SAMP_SOURCE = dict(zip(SAMP_FIELDS, (
    ("seed", 0, np.int32), ("temperature", 1.0, np.float32), ("top_k", 0, np.int32), ("top_p", 1.0, np.float32),
    ("do_sample", False, np.bool_), ("repetition_penalty", 1.0, np.float32), ("presence_penalty", 0.0, np.float32),
    ("frequency_penalty", 0.0, np.float32))))


def samp_arrays(sampling: Sequence, n: Optional[int] = None):
    """Per-row sampling-parameter arrays for the device kernels, as host
    arrays: they ride the launch's packed buffer (``SAMP_FIELDS`` by name).

    ``sampling`` holds SamplingParams-shaped objects (duck-typed) or None for
    padding rows; ``n`` pads/truncates to a fixed row count."""
    rows = list(sampling)
    if n is not None:
        rows = (rows + [None] * n)[:n]
    # astype, not a dtype argument: a seed past 31 bits wraps, as it did when the device converted it
    return {name: np.asarray([getattr(s, attr) if s is not None else default for s in rows]).astype(dtype)  # sync-ok: a list of host scalars
            for name, (attr, default, dtype) in _SAMP_SOURCE.items()}


@dataclasses.dataclass
class MixedRow:
    """One row of a ragged mixed step, as the scheduler sees it.

    A prefill-chunk row feeds ``tokens`` (the next chunk of the prompt)
    starting at absolute position ``start``; a decode row feeds exactly one
    token (the slot's last sampled id). ``emit=True`` means the sampler's
    token at position ``start + len(tokens)`` is kept (final chunks and
    decode rows); non-final chunks discard it."""

    slot: int
    tokens: np.ndarray
    start: int
    table: np.ndarray
    emit: bool
    sampling: object
    #: adapter-pool slot this row's LoRA delta gathers from (0 = identity —
    #: the no-adapter row); the engine fills it from Request.adapter_slot
    adapter: int = 0


@dataclasses.dataclass
class BlockRow:
    """One decode row of a kind that generates by diffusion over blocks
    (``block_model.py``): the block the slot is at. ``tokens`` / ``masked``
    [block_length] are its positions' tokens and which are still masked,
    ``start`` its first position, ``fixed`` how many of its leading positions
    are the prompt's, ``remaining`` the tokens the request is still owed."""

    slot: int
    tokens: np.ndarray
    masked: np.ndarray
    start: int
    table: np.ndarray
    fixed: int
    remaining: int


def _bucket(n: int, minimum: int = 16) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


class ModelBackend:
    """Interface base (see module docstring). Subclasses own params, the KV
    pool, the penalty-count tensor and the compiled step functions."""

    #: the PagedInferenceModel (or subclass) holding the jitted programs —
    #: exposed because tests and tools flip ``infer.use_paged_kernel``
    infer: PagedInferenceModel
    #: how many prefill-chunk rows one mixed step may carry (None: as many as the budget feeds)
    max_chunk_rows: Optional[int] = None

    #: True for stage-split (disaggregated) backends: the engine then routes
    #: finished prefills through kv_migrate/migration_ready before treating
    #: the sequence as decode-eligible
    staged = False

    #: the last launch's padded token geometry for the goodput ledger (see
    #: module docstring) — stamped (REASSIGNED, never mutated in place: the
    #: engine may hold a reference across its accounting read) by every step
    #: entry point before dispatch. Instance state — initialized per backend
    #: in __init__ so fleets of in-process engines never share one dict.
    step_accounting: dict

    def prefill(self, input_ids, block_tables, suffix_lens, cached_entries,
                sampling, slot_idx, adapter_table=None) -> np.ndarray:
        raise NotImplementedError

    def decode(self, last_tokens, block_tables, context_lens, done0, remaining,
               sampling, adapter_table=None) -> Tuple[np.ndarray, np.ndarray]:
        raise NotImplementedError

    def mixed_step(self, chunk_rows: List[MixedRow], decode_rows: List[MixedRow]) -> np.ndarray:
        raise NotImplementedError

    def verify(self, tokens, block_tables, start_pos, need_logits: bool,
               adapter_table=None):
        raise NotImplementedError

    def seed_counts(self, slot_idx, cached_entries):
        raise NotImplementedError

    def reset_counts(self):
        raise NotImplementedError

    def apply_cow(self, pairs):
        raise NotImplementedError

    def kv_spill(self, block_ids):
        """Gather ``block_ids`` out of the pool and start their D2H copy
        (hierarchical prefix cache, kv_host_tier.py). Returns ``(kv, scale)``
        gathered [L, 2, n_padded, bs, K*H] planes with
        ``copy_to_host_async`` dispatched — the engine hands them straight to
        :meth:`HostKVTier.put`. Must be called BEFORE any launch that writes
        the (just-recycled) blocks; dispatch order then guarantees the gather
        reads the pre-overwrite bytes."""
        raise NotImplementedError

    def kv_promote(self, seq_id, block_ids, host_kv, host_scale=None):
        """Scatter host-tier KV back into freshly-allocated pool blocks (the
        async H2D dispatched ahead of prefill). Returns a
        :class:`HostPromoteTicket` whose markers feed
        :meth:`migration_ready` — the engine keeps the sequence in
        ``kv_stage == "promoting"`` until the copy lands."""
        raise NotImplementedError

    def kv_writeback(self, block_ids):
        """Make ``block_ids``' KV readable by future *prefill* work. A no-op
        everywhere except staged backends: generated-token KV is written in
        the decode pool, so registering generated blocks in the prefix index
        needs their bytes copied back into the prefill pool first."""
        return None

    def migration_ready(self, ticket) -> bool:
        """Non-blocking landed check for any marker-carrying copy ticket
        (stage migrations and host-tier promotions share it). Purely a
        scheduling signal — the pool's functional threading already orders
        every read after the copy — so a runtime without ``is_ready``
        introspection just reports landed."""
        for m in ticket.markers:
            probe = getattr(m, "is_ready", None)
            if probe is not None and not probe():
                return False
        return True

    def sync_params(self, new_params):
        """Install a new base-weight tree as THE params for every subsequent
        step. The explicit sibling of the lazy params-property rebind: callers
        that need the placement to happen NOW (a serving weight swap that
        wants device OOM / layout failures to surface inside its rollback
        window, not on the next request's step) go through here. Backends
        must keep their existing device layout — same NamedShardings, same
        mesh — and must not touch the KV pool or penalty counts."""
        raise NotImplementedError

    def describe(self) -> dict:
        raise NotImplementedError


class SingleDeviceBackend(ModelBackend):
    """The historical engine layout: params/pool/counts on the default device,
    no sharding annotations on the jitted steps."""

    def __init__(self, model, *, max_batch_size: int, block_size: int, num_blocks: int,
                 max_blocks_per_seq: int, dtype, decode_steps: int, eos_ids,
                 kv_cache_quant: Optional[str] = None,
                 adapter_registry=None,
                 prefill_chunk_tokens: Optional[int] = None):
        self.model = model
        self.max_batch_size = max_batch_size
        # read by a kind whose mixed program has one fixed shape (see _build_infer)
        self.prefill_chunk_tokens = prefill_chunk_tokens
        self.step_accounting = {"fed": 0, "shape": ()}
        # host arrays handed to the device since the last dispatch began (_to_device)
        self._h2d_arrays = self._h2d_bytes = 0
        # multi-LoRA: with a registry attached, EVERY step passes the device
        # adapter pool + a per-row slot index (identity rows gather slot 0's
        # zeros) — one program serves mixed adapter/no-adapter batches. No
        # registry -> lora=None everywhere: the historical programs, untouched.
        # Set BEFORE _build_infer: the sharded infer reads it to decide the
        # lora leg of its in_shardings at jit-build time.
        self.adapter_registry = adapter_registry
        self._lora_dev = None
        self._lora_version = None
        self.infer = self._build_infer(model, block_size, num_blocks, max_blocks_per_seq,
                                       dtype, decode_steps, eos_ids)
        self.pool = self._init_pool(model.config, num_blocks, block_size, dtype, kv_cache_quant)
        self.counts = self._init_counts()

    # ---------------------------------------------------------------- setup
    def _build_infer(self, model, block_size, num_blocks, max_blocks_per_seq,
                     dtype, decode_steps, eos_ids) -> PagedInferenceModel:
        """The inference model that computes the configuration's layer kinds
        (``inference_model.inference_model_class``); the llama kind's is built as ever."""
        return inference_model_class(model.config)(
            model, block_size, num_blocks, max_blocks_per_seq, dtype=dtype,
            decode_steps=decode_steps, eos_ids=eos_ids,
            max_batch_size=self.max_batch_size, prefill_chunk_tokens=self.prefill_chunk_tokens)

    def _init_pool(self, config, num_blocks, block_size, dtype, quant):
        return self.infer.init_pool(num_blocks, block_size,
                                    jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32, quant)

    @property
    def max_chunk_rows(self) -> Optional[int]:
        shape = self.infer.fixed_mixed_shape
        return shape[0] if shape else None

    def _init_counts(self):
        return jnp.zeros((self.max_batch_size, self.model.config.vocab_size), jnp.int32)

    @property
    def params(self):
        return self.model.params

    def sync_params(self, new_params):
        # single device: the params property reads model.params directly, so
        # the rebind IS the install (jit retraces nothing — same avals)
        self.model.params = new_params

    # ---------------------------------------------------------------- lora
    def _place_lora(self, host_pool):
        """Place the host adapter pool on device (the sharded backend overrides
        this with NamedSharding placement)."""
        return jax.tree_util.tree_map(jnp.asarray, host_pool)

    def _lora_tree(self):
        """Device copy of the registry's adapter pool, re-placed ONLY when the
        registry's content version moved (adapter load/evict) — the sharded
        params-rebind id-check pattern applied to the adapter pool."""
        reg = self.adapter_registry
        if reg is None:
            return None
        host, version = reg.pool_arrays()
        if version != self._lora_version:
            self._lora_dev = self._place_lora(host)
            self._lora_version = version
        return self._lora_dev

    def _adapter_idx(self, adapter_table, n: int):
        """Per-row pool-slot indices for an n-row launch (None -> identity), a
        host array for the launch's buffer; None without a registry.
        Raises when adapters are requested without a registry attached — a
        scheduler bug that must not silently serve base-model tokens."""
        if adapter_table is None:
            idx = np.zeros(n, np.int32)
        else:
            idx = np.zeros(n, np.int32)
            idx[: len(adapter_table)] = np.asarray(adapter_table, np.int32)
        if self.adapter_registry is None:
            if idx.any():
                raise ValueError("adapter_table has non-identity rows but the "
                                 "backend has no adapter_registry")
            return None
        return idx

    # ---------------------------------------------------------------- counts
    def _cached_counts(self, cached_entries, n_rows: int) -> jnp.ndarray:
        """Penalty counts for prefix-cache-hit prompt spans: the fed suffix is
        counted on device, the cached span here via host bincount. Clipped: an
        out-of-vocab id from a direct caller must degrade to a garbage count
        (the old one_hot behavior), not crash the step. All-miss (or
        cache-off) batches materialize the zeros on device instead of shipping
        an n*vocab host buffer. ``cached_entries`` = [(row, prompt_ids,
        n_cached)]; returns [n_rows, vocab] int32."""
        vocab = self.model.config.vocab_size
        counts_in = None
        for row, prompt_ids, n_cached in cached_entries:
            if n_cached > 0:
                if counts_in is None:
                    counts_in = np.zeros((n_rows, vocab), np.int32)
                counts_in[row] = np.bincount(  # sync-ok: bincount of HOST prompt ids over the cached span only (documented in the docstring)
                    np.clip(prompt_ids[:n_cached], 0, vocab - 1),
                    minlength=vocab)[:vocab]
        if counts_in is None:
            return jnp.zeros((n_rows, vocab), jnp.int32)
        return self._to_device(counts_in)

    def seed_counts(self, slot_idx, cached_entries):
        # one shape whatever the group's size: the index is padded past the last slot and those rows are dropped,
        # so an admission group of a size no warm-up saw compiles nothing
        n = self.counts.shape[0]
        idx = np.full(n, n, np.int32)
        idx[: len(slot_idx)] = slot_idx
        self.counts = self.counts.at[jnp.asarray(idx)].set(self._cached_counts(cached_entries, n), mode="drop")

    def reset_counts(self):
        self.counts = jnp.zeros_like(self.counts)

    # ---------------------------------------------------------------- steps
    def _place_launch(self, host):
        """Start the H2D transfer of a launch's host input (the sharded
        backend lands it replicated on its mesh)."""
        return jax.device_put(host)

    def _to_device(self, host):
        """THE transfer call of the step entry points: every host array a
        launch hands to the device goes through here and is counted on the
        launch's ``dispatch`` span."""
        self._h2d_arrays += 1
        self._h2d_bytes += host.nbytes
        return self._place_launch(host)

    @contextlib.contextmanager
    def _dispatch(self, program: str):
        """A launch's ``dispatch`` span, stamped with what crossed to the
        device inside it (``h2d_arrays``, ``h2d_bytes``)."""
        self._h2d_arrays = self._h2d_bytes = 0
        with TRACER.span("dispatch", cat="engine", program=program) as span:
            yield
            span.set(h2d_arrays=self._h2d_arrays, h2d_bytes=self._h2d_bytes)

    def _send(self, **fields):
        """One launch's host inputs as one packed buffer on the device, and its
        layout. A field that is None is left out: the adapter rows without a
        registry attached (``_adapter_idx``)."""
        buf, layout = pack({k: v for k, v in fields.items() if v is not None})
        return self._to_device(buf), layout

    def prefill(self, input_ids, block_tables, suffix_lens, cached_entries,
                sampling, slot_idx, adapter_table=None) -> np.ndarray:
        n = input_ids.shape[0]
        cached_lens = np.zeros(n, np.int32)
        for row, _ids, n_cached in cached_entries:
            cached_lens[row] = n_cached
        suffix_lens = np.asarray(suffix_lens, np.int32)  # sync-ok: suffix_lens is host numpy
        self.step_accounting = dict(
            {"fed": n * input_ids.shape[1], "shape": ("prefill", n, input_ids.shape[1])},
            **launch_geometry(n, suffix_lens, cached_lens + suffix_lens))
        # the batch's count rows land at their slots inside the program: the
        # index is padded past the last slot, and those rows are dropped
        slots = np.full(n, self.counts.shape[0], np.int32)
        slots[: len(slot_idx)] = slot_idx
        with self._dispatch("prefill"):
            counts_dev = self._cached_counts(cached_entries, n)
            packed, layout = self._send(
                input_ids=np.asarray(input_ids, np.int32), block_tables=np.asarray(block_tables, np.int32),  # sync-ok: host numpy
                suffix_lens=suffix_lens, cached_lens=cached_lens, slot_idx=slots,
                **samp_arrays(sampling, n), adapter_idx=self._adapter_idx(adapter_table, n))
            tokens, self.counts, self.pool = self.infer.prefill(
                self.params, self.pool, packed, layout, counts_dev, self.counts, lora=self._lora_tree())
        with TRACER.span("wait", cat="engine", program="prefill"):
            return np.asarray(tokens)  # sync-ok: THE prefill sync point — sampled int32 ids only

    def decode(self, last_tokens, block_tables, context_lens, done0, remaining,
               sampling, adapter_table=None) -> Tuple[np.ndarray, np.ndarray]:
        B, steps = last_tokens.shape[0], self.infer.decode_steps
        live = ~np.asarray(done0, bool)  # sync-ok: done0 is host numpy
        ctx = np.asarray(context_lens, np.int64)  # sync-ok: context_lens is host numpy
        acct = dict({"fed": B * steps, "shape": ("decode", B, steps)},
                    **launch_geometry(B, live, live * (ctx + 1)))
        self.step_accounting = acct
        with self._dispatch("decode"):
            packed, layout = self._send(
                tokens=np.asarray(last_tokens, np.int32), block_tables=np.asarray(block_tables, np.int32),  # sync-ok: host numpy
                context_lens=ctx.astype(np.int32), done0=~live,
                remaining=np.asarray(remaining, np.int32),  # sync-ok: remaining is host numpy
                **samp_arrays(sampling, len(sampling)), adapter_idx=self._adapter_idx(adapter_table, B))
            toks, valid, _, _, self.counts, self.pool = self.infer.decode(
                self.params, self.pool, packed, layout, self.counts, lora=self._lora_tree())
        with TRACER.span("wait", cat="engine", program="decode"):
            toks, valid = np.asarray(toks), np.asarray(valid)  # sync-ok: THE decode sync point — int32 ids + validity flags only
        # what the launch really read is known only now: a row still emitting
        # in sub-step s read ctx + s + 1 positions there (restamped, not
        # mutated: see ModelBackend.step_accounting)
        sub = np.arange(1, valid.shape[0] + 1, dtype=np.int64)[:, None]
        self.step_accounting = dict(
            acct, kv_positions=int(((ctx[None, :] + sub) * valid).sum()))  # sync-ok: valid already host
        if self.infer.launch_counts:
            self.step_accounting.update(self.infer.launch_counts(self.pool))
        return toks, valid

    # ---------------------------------------------------------------- diffusion over blocks
    def _block_results(self, program: str, packed, passes: int, rows: int) -> dict:
        """The sync point of a block launch: its packed results as host arrays (``block_model.unpack_results``),
        and the launch's device counts on ``step_accounting`` (``kv_positions`` is what they say a layer read)."""
        from .block_model import unpack_results

        with TRACER.span("wait", cat="engine", program=program):
            out = unpack_results(np.asarray(packed), passes, rows, self.infer.block_length)  # sync-ok: THE sync point of a block launch — ids and flags only
            counted = self.infer.launch_counts(self.pool)
        self.step_accounting = dict(self.step_accounting, **counted,
                                    kv_positions=counted["attn_kv_visible"] // self.infer.n_layers)
        return out

    def decode_blocks(self, block_tokens, block_masked, block_tables, start, fixed, done0, remaining) -> dict:
        """``decode_steps`` passes of every live slot over its blocks (``block_model._decode_body``). Everything
        is [slots, ...] host arrays; returns ``block_model.unpack_results``' dict."""
        B, bk = block_tokens.shape
        steps = self.infer.decode_steps
        live = ~np.asarray(done0, bool)  # sync-ok: done0 is host numpy
        self.step_accounting = dict({"fed": B * steps * bk, "shape": ("decode", B, steps, bk)},
                                    **launch_geometry(B, live, live * (np.asarray(start, np.int64) + bk)))  # sync-ok: start is host numpy
        with self._dispatch("decode"):
            packed, layout = self._send(
                block_tokens=np.asarray(block_tokens, np.int32), block_masked=np.asarray(block_masked, bool),  # sync-ok: host numpy
                block_tables=np.asarray(block_tables, np.int32), start=np.asarray(start, np.int32),  # sync-ok: host numpy
                fixed=np.asarray(fixed, np.int32), done0=~live, remaining=np.asarray(remaining, np.int32))  # sync-ok: host numpy
            results, self.counts, self.pool = self.infer.decode(self.params, self.pool, packed, layout, self.counts)
        return self._block_results("decode", results, steps, B)

    def mixed_step_blocks(self, chunk_rows: List[MixedRow], decode_rows: List[BlockRow]) -> dict:
        """One mixed step of a kind that generates by diffusion over blocks: the chunk rows' prompt blocks and one
        pass of every decode row, at the kind's one fixed shape. Returns ``unpack_results``' dict, its rows in
        ``decode_rows``' order (one pass)."""
        C, T, D = self.infer.fixed_mixed_shape
        bk = self.infer.block_length
        M = (chunk_rows[0].table if chunk_rows else decode_rows[0].table).shape
        self.step_accounting = dict(
            {"fed": C * T + D * bk, "shape": ("mixed_flat", C, T, D, bk)},
            **launch_geometry(C + D, [len(r.tokens) for r in chunk_rows] + [bk] * len(decode_rows),
                              [r.start + len(r.tokens) for r in chunk_rows] + [r.start + bk for r in decode_rows]))
        f = {"chunk_ids": np.zeros((C, T), np.int32), "chunk_tables": np.zeros((C,) + M, np.int32),
             "chunk_qlens": np.zeros(C, np.int32), "chunk_start": np.zeros(C, np.int32),
             "dec_tokens": np.zeros((D, bk), np.int32), "dec_masked": np.zeros((D, bk), bool),
             "dec_tables": np.zeros((D,) + M, np.int32), "dec_start": np.zeros(D, np.int32),
             "dec_fixed": np.zeros(D, np.int32), "dec_live": np.zeros(D, bool), "dec_remaining": np.zeros(D, np.int32)}
        for j, r in enumerate(chunk_rows):
            f["chunk_ids"][j, :len(r.tokens)] = r.tokens
            f["chunk_tables"][j], f["chunk_qlens"][j], f["chunk_start"][j] = r.table, len(r.tokens), r.start
        for j, r in enumerate(decode_rows):
            f["dec_tokens"][j], f["dec_masked"][j], f["dec_tables"][j] = r.tokens, r.masked, r.table
            f["dec_start"][j], f["dec_fixed"][j], f["dec_live"][j], f["dec_remaining"][j] = r.start, r.fixed, True, r.remaining
        with self._dispatch("mixed"):
            packed, layout = self._send(**f)
            results, self.counts, self.pool = self.infer.mixed_step_flat(self.params, self.pool, packed, layout,
                                                                         self.counts)
        return self._block_results("mixed", results, 1, D)

    def verify(self, tokens, block_tables, start_pos, need_logits: bool,
               adapter_table=None):
        B, T = tokens.shape
        # a live row's table starts at a real block (block 0 is the sentinel);
        # every live row feeds all K + 1 positions, drafted or zero-padded
        live = np.asarray(block_tables)[:, 0] != 0  # sync-ok: block_tables is host numpy
        self.step_accounting = dict(
            {"fed": B * T, "shape": ("verify", B, T)},
            **launch_geometry(B, live, live * (np.asarray(start_pos, np.int64) + T)))  # sync-ok: start_pos is host numpy
        with self._dispatch("verify"):
            packed, layout = self._send(
                tokens=np.asarray(tokens, np.int32), block_tables=np.asarray(block_tables, np.int32),  # sync-ok: host numpy
                start_pos=np.asarray(start_pos, np.int32),  # sync-ok: start_pos is host numpy
                adapter_idx=self._adapter_idx(adapter_table, B))
            argmax, logits, self.pool = self.infer.verify(
                self.params, self.pool, packed, layout, lora=self._lora_tree(), need_logits=need_logits)
        with TRACER.span("wait", cat="engine", program="verify"):
            return np.asarray(argmax), (np.asarray(logits) if need_logits else None)  # sync-ok: THE verify sync point (logits only when rejection sampling asks)

    def apply_cow(self, pairs):
        self.pool = copy_blocks(self.pool, pairs)

    # ---------------------------------------------------------------- host tier
    def _build_host_tier_jits(self):
        """(gather, scatter) programs for spill/promote. The sharded backend
        overrides this to compile them with explicit shardings; the jits are
        dtype-polymorphic so one pair serves the kv and scale planes."""
        return (jax.jit(gather_blocks, donate_argnums=()),
                jax.jit(scatter_blocks, donate_argnums=(0,)))

    def _host_tier_jits(self):
        jits = getattr(self, "_host_jits", None)
        if jits is None:
            jits = self._build_host_tier_jits()
            self._host_jits = jits
        return jits

    def _place_host_blocks(self, data):
        """Start the H2D transfer of a promoted block slice (the sharded
        backend lands it with the pool's NamedSharding)."""
        return jnp.asarray(data)

    @staticmethod
    def _pad_block_ids(block_ids):
        """pow2-pad with sentinel self-references (block 0 is never a live
        dst), bounding gather/scatter to log2(max_blocks_per_seq) compiles —
        the migration padding rule."""
        ids = [int(b) for b in block_ids]
        padded = 1
        while padded < max(len(ids), 1):
            padded *= 2
        return ids, jnp.asarray(ids + [0] * (padded - len(ids)), jnp.int32), padded

    def kv_spill(self, block_ids):
        ids, ids_arr, _ = self._pad_block_ids(block_ids)
        gather, _ = self._host_tier_jits()
        kv = gather(self.pool.kv, ids_arr)
        kv.copy_to_host_async()
        scale = None
        if self.pool.scale is not None:
            scale = gather(self.pool.scale, ids_arr)
            scale.copy_to_host_async()
        return kv, scale

    def kv_promote(self, seq_id, block_ids, host_kv, host_scale=None):
        ids, ids_arr, padded = self._pad_block_ids(block_ids)
        n = len(ids)
        if padded != n:
            # pad with ZERO rows, not gathered bytes: the sentinel ids point
            # the extra scatter rows at block 0, which must stay all-zeros
            pad = np.zeros(host_kv.shape[:2] + (padded - n,) + host_kv.shape[3:],
                           host_kv.dtype)
            host_kv = np.concatenate([host_kv, pad], axis=2)
            if host_scale is not None:
                spad = np.zeros(host_scale.shape[:2] + (padded - n,) + host_scale.shape[3:],
                                host_scale.dtype)
                host_scale = np.concatenate([host_scale, spad], axis=2)
        _, scatter = self._host_tier_jits()
        new_kv, marker = scatter(self.pool.kv, self._place_host_blocks(host_kv), ids_arr)
        markers = [marker]
        scale = self.pool.scale
        if scale is not None:
            if host_scale is None:
                raise ValueError("quantized pool promote needs the spilled scale plane")
            scale, s_marker = scatter(scale, self._place_host_blocks(host_scale), ids_arr)
            markers.append(s_marker)
        self.pool = PagedKVPool(kv=new_kv, scale=scale)
        return HostPromoteTicket(seq_id=seq_id, n_blocks=n, markers=tuple(markers))

    # ---------------------------------------------------------------- mixed
    def mixed_step(self, chunk_rows: List[MixedRow], decode_rows: List[MixedRow]) -> np.ndarray:
        """One ragged mixed step. Returns sampled tokens in row order
        ``[*chunk_rows, *decode_rows]`` (the scheduler keeps them only where
        ``emit``)."""
        return self.mixed_step_begin(chunk_rows, decode_rows)()

    def mixed_step_begin(self, chunk_rows: List[MixedRow],
                         decode_rows: List[MixedRow]) -> Callable[[], np.ndarray]:
        """Dispatch the ragged step WITHOUT syncing; returns a zero-arg
        collector yielding the sampled ids in ``[*chunk_rows, *decode_rows]``
        order. The split exists for staged (MPMD) backends: they dispatch the
        prefill-stage and decode-stage programs back to back and only then
        collect, so the two device groups compute concurrently instead of the
        host serializing them at the first sync."""
        with self._dispatch("mixed"):
            tokens_dev, mapper = self._mixed_flat_launch(chunk_rows, decode_rows)

        def collect() -> np.ndarray:
            with TRACER.span("wait", cat="engine", program="mixed"):
                out = mapper(np.asarray(tokens_dev))  # sync-ok: THE mixed-step sync point — sampled int32 ids only
                if self.infer.launch_counts:
                    self.step_accounting = dict(self.step_accounting, **self.infer.launch_counts(self.pool))
                return out

        return collect

    def _mixed_flat_launch(self, chunk_rows, decode_rows):
        """Build and dispatch the mixed launch: chunk rows keep their [C, T]
        matrix, decode rows collapse to a [D, 1] segment, each bucketed on its
        own (or ``fixed_mixed_shape``), so per-step cost scales with the
        tokens actually fed. Both segments run in ONE jit. Returns (device
        tokens, host-order mapper)."""
        C, T, D = self.infer.fixed_mixed_shape or (
            _bucket(len(chunk_rows), minimum=1),
            _bucket(max([len(r.tokens) for r in chunk_rows], default=1), minimum=1),
            _bucket(len(decode_rows), minimum=1))
        M = (chunk_rows[0].table.shape if chunk_rows else decode_rows[0].table.shape)
        rows = chunk_rows + decode_rows
        self.step_accounting = dict(
            {"fed": C * T + D, "shape": ("mixed_flat", C, T, D)},
            **launch_geometry(C + D, [len(r.tokens) for r in rows],
                              [r.start + len(r.tokens) for r in rows]))
        c_ids = np.zeros((C, T), np.int32)
        c_tables = np.zeros((C,) + M, np.int32)
        c_qlens = np.zeros(C, np.int32)
        c_start = np.zeros(C, np.int32)
        c_slots = np.zeros(C, np.int32)
        c_emit = np.zeros(C, bool)
        c_adapter = np.zeros(C, np.int32)
        d_tokens = np.zeros(D, np.int32)
        d_tables = np.zeros((D,) + M, np.int32)
        d_start = np.zeros(D, np.int32)
        d_slots = np.zeros(D, np.int32)
        d_live = np.zeros(D, bool)
        d_adapter = np.zeros(D, np.int32)
        for j, r in enumerate(chunk_rows):
            n = len(r.tokens)
            c_ids[j, :n] = r.tokens
            c_tables[j] = r.table
            c_qlens[j] = n
            c_start[j] = r.start
            c_slots[j] = r.slot
            c_emit[j] = r.emit
            c_adapter[j] = r.adapter
        for j, r in enumerate(decode_rows):
            d_tokens[j] = r.tokens[0]
            d_tables[j] = r.table
            d_start[j] = r.start
            d_slots[j] = r.slot
            d_live[j] = True
            d_adapter[j] = r.adapter
        sampling = ([r.sampling for r in chunk_rows] + [None] * (C - len(chunk_rows))
                    + [r.sampling for r in decode_rows] + [None] * (D - len(decode_rows)))
        packed, layout = self._send(
            chunk_ids=c_ids, chunk_tables=c_tables, chunk_qlens=c_qlens, chunk_start=c_start,
            chunk_slots=c_slots, chunk_emit=c_emit, dec_tokens=d_tokens, dec_tables=d_tables,
            dec_start=d_start, dec_slots=d_slots, dec_live=d_live, **samp_arrays(sampling, C + D),
            chunk_adapter=self._adapter_idx(c_adapter, C), dec_adapter=self._adapter_idx(d_adapter, D))
        tokens, self.counts, self.pool = self.infer.mixed_step_flat(
            self.params, self.pool, packed, layout, self.counts, lora=self._lora_tree())
        n_c, n_d = len(chunk_rows), len(decode_rows)
        return tokens, lambda host: np.concatenate([host[:n_c], host[C : C + n_d]])

    # ---------------------------------------------------------------- misc
    def describe(self) -> dict:
        return {"kind": "single_device", "devices": 1, "tp_degree": 1, "mesh": None}
