"""Continuous-batching inference engine.

Counterpart of the reference's dynamic-insertion serving loop:
``GenerationBlockInferenceModel.sample`` per-token loop
(experimental/transformers/generation_utils.py:403) + the ``step_paddle`` block
scheduler (csrc/gpu/step.cu:316 — dispatch/free/preempt/recover) + the on-GPU
sampling/penalty/stop ops (top_p_sampling_reject.cu, token_penalty_multi_scores.cu,
stop_generation_multi_ends.cu, update_inputs.cu). Host-side scheduler + two jitted
device programs:

- admission: waiting requests prefill in BATCHES grouped by power-of-two padded
  prompt length; the first token is sampled on device inside the prefill jit;
- chunked prefill (``prefill_chunk_tokens=N``): prompt processing is split into
  fixed-size chunks interleaved with decode tokens — each engine step feeds at
  most N prompt tokens (tracked per slot via ``Request.prefilled_len``) plus one
  decode token per running sequence through ONE ragged mixed forward, so a
  long-prompt admission never stalls running decodes for the whole prompt; the
  sampler fires only when a request's last chunk lands (the *Ragged Paged
  Attention* TPU-serving design);
- decode: ALL slots advance up to ``decode_steps`` tokens in ONE jit —
  sampling, repetition/presence/frequency penalties, eos and length stops all
  run on device; the host round-trip carries int32 ids + flags only (the
  reference avoids per-token host sync the same way, with CUDA ops);
- preemption: on block exhaustion the youngest sequence is evicted and requeued
  with prompt+generated as its new prompt (recompute-style recovery, the
  ``is_block_step``/recover list of step.cu). Sampling keys are
  (seed, absolute position), so a recomputed sequence resamples identically;
- streaming: per-request callbacks fire as tokens land (the reference pushes
  tokens over a SysV message queue to the serving process; in-process callbacks
  replace the IPC hop).
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import time
from collections import deque
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..observability.flight_recorder import RECORDER
from ..observability.goodput import (
    KIND_COUNTERS,
    LAUNCH_GEOMETRY,
    GoodputLedger,
    compile_attribution,
    device_peak_flops,
    efficiency_doc,
    estimate_model_flops_per_token,
    install_compile_listener,
)
from ..observability.tracer import TRACER
from ..serving.tenancy.adapters import AdapterPressure, UnknownAdapterError
from ..serving.tenancy.quotas import DEFAULT_TENANT, TenantQuotas, tenant_goodput_fold
from ..utils.faults import FaultPoint
from ..utils.log import logger
from .backend import BlockRow, MixedRow, ModelBackend, SingleDeviceBackend, _bucket
from .inference_model import inference_model_class
from .kv_host_tier import HostKVTier, pool_block_bytes
from .paged_cache import BlockManager

__all__ = ["InferenceEngine", "Request", "SamplingParams"]

# one call site per phase records on both clocks: every live engine / engine-loop
# span also opens a profiler annotation of the same name and args (a no-op
# TraceMe while no capture runs); see observability/tracer.py
TRACER.mirror_spans(jax.profiler.TraceAnnotation, cats=("engine", "engine_loop"))


_F_STEP = FaultPoint("engine.step")
_F_CHUNK = FaultPoint("engine.prefill_chunk")
_F_MIGRATE = FaultPoint("engine.kv_migrate")
_F_SPILL = FaultPoint("engine.kv_spill")
_F_PROMOTE = FaultPoint("engine.kv_promote")


@dataclasses.dataclass
class SamplingParams:
    max_new_tokens: int = 64
    do_sample: bool = False
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0
    seed: int = 0
    repetition_penalty: float = 1.0
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0


#: admission preference rank per serving priority class (lower admits first);
#: unknown strings rank as interactive so a bare engine user can ignore this
_PRIORITY_RANK = {"interactive": 0, "batch": 1, "best_effort": 2}


@dataclasses.dataclass
class Request:
    req_id: int
    prompt_ids: np.ndarray
    sampling: SamplingParams
    output_ids: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    stream_cb: Optional[Callable[[int, bool], None]] = None
    # the request clock: arrival_t is when the client's submission was taken
    # (the serving loop passes its handle's submitted_t, stamped on the HTTP
    # thread), enqueued_t when add_request put it on the waiting queue — on the
    # loop thread, so after whatever step was running when it came in. A bare
    # engine user gets both at once.
    arrival_t: float = 0.0
    enqueued_t: Optional[float] = None
    sched_t: Optional[float] = None  # first admitted to a slot (prefill launch)
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    finish_reason: Optional[str] = None  # stop | length | abort | capacity
    aborted: bool = False
    base_prompt_len: int = 0  # original prompt length (preemption grows prompt_ids)
    trace: Optional[str] = None  # observability trace id (serving request context)
    # serving request priority ("interactive" | "batch" | "best_effort"):
    # orders the waiting queue under load — interactive admits ahead of batch,
    # batch ahead of best_effort; FIFO within a class (0/1/2 rank, see
    # InferenceEngine.add_request)
    priority: str = "interactive"
    # multi-tenant serving: which tenant the request bills to (quotas, the
    # per-tenant goodput fold, shed/served metric labels) ...
    tenant: str = DEFAULT_TENANT
    # ... which registered LoRA adapter its rows gather (None = base model;
    # also the prefix-cache salt, so adapter outputs never share KV) ...
    adapter_id: Optional[str] = None
    # ... and the adapter-pool slot held while admitted (0 = identity slot;
    # a real slot carries a registry refcount, released with the KV blocks
    # in _free_kv and re-acquired on re-admission)
    adapter_slot: int = 0
    prefilled_len: int = 0  # prompt tokens whose KV is in the pool (chunked prefill)
    # which stage's pool holds this sequence's KV (disaggregated backends):
    # "prefill" while chunks run, "migrating" while blocks move between stage
    # pools, "decode" once landed (single-pool backends stay "decode" always)
    kv_stage: str = "decode"
    # latency-attribution bookkeeping (engine_loop.request_attribution):
    # first time the request was head-of-queue but deferred by an admission
    # gate (splits queue_wait into pure-queue vs admission-gate) ...
    gated_t: Optional[float] = None
    # ... decode-window seconds spent riding mixed steps that also carried
    # other requests' prefill chunks (the per-request decode-stall share) ...
    chunk_stall_s: float = 0.0
    # ... the mirror of it before the first token (_launch books both at the
    # end of every launch): launches that carried this request's prompt tokens
    # and their seconds, and the seconds of launches it sat in a slot behind
    # while they carried none of its prompt ...
    prefill_steps: int = 0
    prefill_own_s: float = 0.0
    prefill_behind_s: float = 0.0
    # ... and seconds spent waiting for prefill->decode block migration
    # (accumulated on land; migrate_start_t marks an episode still open)
    migration_wait_s: float = 0.0
    migrate_start_t: Optional[float] = None
    # ... and seconds waiting for a host-tier KV promotion (H2D copy of
    # spilled prefix blocks) to land before prefill could proceed
    # (accumulated on land; promote_start_t marks an episode still open)
    promote_wait_s: float = 0.0
    promote_start_t: Optional[float] = None
    # goodput-ledger bookkeeping: highest absolute position ever fed through
    # a forward for this request (prompt+output indexing survives the
    # preemption fold) — re-feeding below the mark is rework, not useful ...
    fed_hwm: int = 0
    # ... COW tail tokens owed by a full-cover prefix-cache admission (they
    # re-prefill KV another request already built: rework kind "cow_token") ...
    cow_pending: int = 0
    # ... and which rework bucket this request's re-fed positions land in
    # (preemption recompute vs a supervisor requeue across a rebuild)
    rework_src: str = "preempt_refill"
    # usage-metering bookkeeping (serving.tenancy.metering.UsageMeter reads
    # these at finish): prefix-cache tokens credited at FIRST admission only
    # (None until admitted — a preemption re-admission must not re-credit) ...
    cached_tokens: Optional[int] = None
    # ... engine-attributed useful fed positions, mirroring the per-tenant
    # goodput fold token for token so summed finished-request usage
    # reconciles exactly against the ledger's useful total ...
    useful_tokens: int = 0
    # ... speculative work billed to this request ...
    spec_drafted: int = 0
    spec_accepted: int = 0
    # ... the block·seconds integral of KV residency (advanced by a per-step
    # checkpoint while kv_occ_t holds the open episode's start; finalized in
    # _free_kv, so it accumulates across preemption episodes) ...
    kv_block_seconds: float = 0.0
    kv_occ_t: Optional[float] = None
    # ... and wall seconds holding a real adapter-pool slot (refcount
    # bracket: acquire in _admit_slots, release in _free_kv)
    adapter_slot_seconds: float = 0.0
    adapter_acq_t: Optional[float] = None
    # generation by diffusion over blocks (block_model.py): the block length the
    # sequence advances by (1: a token a step, every other kind), and the passes
    # its blocks took so far, by kind
    block_length: int = 1
    denoise_passes: int = 0
    commit_passes: int = 0

    @property
    def prefill_len(self) -> int:
        """Prompt tokens that enter through prefill: all of them, or under
        diffusion over blocks the prompt's whole blocks (the ``len mod B`` left
        over open the first generated block in the decode program)."""
        n = len(self.prompt_ids)
        return n - n % self.block_length

    @property
    def needs_prefill(self) -> bool:
        """True while part of the prompt still awaits a prefill chunk."""
        return self.prefilled_len < self.prefill_len

    @property
    def total_len(self) -> int:
        return len(self.prompt_ids) + len(self.output_ids)

    @property
    def queue_wait(self) -> Optional[float]:
        """Seconds from submission to first admission, the wait for the loop
        thread to take the request in included (TTFT = queue_wait + prefill)."""
        if self.sched_t is None:
            return None
        return self.sched_t - self.arrival_t

    @property
    def ttft(self) -> Optional[float]:
        if self.first_token_t is None:
            return None
        return self.first_token_t - self.arrival_t

    @property
    def decode_time(self) -> Optional[float]:
        """Seconds from first token to completion (0 for single-token results)."""
        if self.finish_t is None or self.first_token_t is None:
            return None
        return self.finish_t - self.first_token_t

    @property
    def gen_offset(self) -> int:
        """Tokens already regenerated into prompt_ids by a preemption-requeue."""
        return len(self.prompt_ids) - self.base_prompt_len

    @property
    def remaining_new(self) -> int:
        return self.sampling.max_new_tokens - self.gen_offset - len(self.output_ids)


class InferenceEngine:
    # migration scheduling (staged backends only): at most this many block
    # migrations in flight at once ...
    migration_inflight_limit = 4
    # ... and new migrations are deferred while the decode stage's share of KV
    # blocks exceeds this fraction (decode pressure gates handoff)
    decode_pressure_gate = 0.92
    # stage-aware admission: new prompts stop admitting while the prefill
    # stage's share of KV blocks (mid-prefill + migrating sequences) would
    # exceed this fraction
    prefill_pressure_gate = 0.95
    # n-gram (prompt-lookup) proposer: length of the matched suffix
    spec_ngram = 2
    # engine leg of the per-request rejection-sampling seed (_req_rng)
    spec_seed = 0

    def __init__(
        self,
        model,
        tokenizer=None,
        max_batch_size: int = 8,
        block_size: int = 16,
        num_blocks: int = 512,
        max_blocks_per_seq: int = 64,
        eos_token_id: Optional[int] = None,
        dtype=jnp.float32,
        decode_steps: int = 8,
        kv_cache_quant: Optional[str] = None,  # None | "int8" | "fp8" (cachekv_int8 knob)
        use_speculative: bool = False,
        spec_draft_len: int = 4,
        draft_model=None,  # small causal LM proposer (reference speculate_method=draft_model)
        # share KV blocks across common prompt prefixes. Content-addressed:
        # only valid while params are frozen — callers that update weights
        # between requests must disable this or call clear_prefix_cache()
        enable_prefix_cache: bool = True,
        # split prompt processing into chunks of at most this many tokens,
        # interleaved with decode tokens (one ragged mixed step per chunk) so
        # no engine step does unbounded prefill. None/0 = monolithic prefill.
        prefill_chunk_tokens: Optional[int] = None,
        # shard the forward + KV pool over a device mesh: int tp degree,
        # (dp, tp) tuple, or a parallel.mesh.MeshConfig. None = single device.
        mesh_shape=None,
        # disaggregated prefill/decode serving: (P, D) device counts — prompt
        # work runs on a P-device prefill stage, decode on a D-device decode
        # stage, KV blocks migrating between the stage pools. Overrides
        # mesh_shape. None = single-stage.
        disagg_stages=None,
        # a prebuilt ModelBackend instance overrides mesh_shape (tests /
        # future MPMD stage-split backends plug in here)
        backend: Optional[ModelBackend] = None,
        # multi-LoRA serving: a tenancy.AdapterRegistry whose device pool the
        # backend gathers per-row deltas from. None = base model only (the
        # historical jit programs, untouched).
        adapter_registry=None,
        # per-tenant KV-block share limits: a tenancy.TenantQuotas (or its
        # dict form). The max_inflight leg is enforced upstream by the
        # serving scheduler; the engine owns the block-share admission gate.
        tenant_quotas=None,
        # hierarchical KV cache: host-RAM spill tier capacity in BLOCKS
        # (0 = off). Zero-ref prefix blocks popped off the cache LRU demote
        # to pinned host memory (batched async D2H) instead of being
        # destroyed; a prefix match landing on them promotes back with an
        # async H2D copy overlapped with decode. Requires enable_prefix_cache.
        host_kv_blocks: int = 0,
    ):
        self.model = model
        self.tokenizer = tokenizer
        eos = eos_token_id if eos_token_id is not None else getattr(model.config, "eos_token_id", None)
        self.eos_ids = set(eos) if isinstance(eos, (list, tuple)) else ({eos} if eos is not None else set())
        backend_kw = dict(
            max_batch_size=max_batch_size, block_size=block_size, num_blocks=num_blocks,
            max_blocks_per_seq=max_blocks_per_seq, dtype=dtype, decode_steps=decode_steps,
            eos_ids=self.eos_ids, kv_cache_quant=kv_cache_quant,
            adapter_registry=adapter_registry, prefill_chunk_tokens=prefill_chunk_tokens,
        )
        # every kind's door: the class that computes the configuration's layer
        # kinds says what of the engine it does not serve, here, by name
        inference_model_class(model.config).refuse_engine_features(
            kv_cache_quant=kv_cache_quant, adapter_registry=adapter_registry,
            use_speculative=use_speculative or draft_model is not None, mesh_shape=mesh_shape,
            disagg_stages=disagg_stages, host_kv_blocks=host_kv_blocks,
            enable_prefix_cache=enable_prefix_cache, prefill_chunk_tokens=prefill_chunk_tokens)
        if disagg_stages is not None and mesh_shape is not None:
            raise ValueError(
                "mesh_shape and disagg_stages are mutually exclusive: a disagg "
                "stage is itself a sharded device group (sized by disagg_stages)")
        if backend is not None:
            self.backend = backend
        elif disagg_stages is not None:
            from .disagg_backend import DisaggBackend

            self.backend = DisaggBackend(model, stages=disagg_stages, **backend_kw)
        elif mesh_shape is not None:
            from .sharded_backend import ShardedBackend

            self.backend = ShardedBackend(model, mesh_shape=mesh_shape, **backend_kw)
        else:
            self.backend = SingleDeviceBackend(model, **backend_kw)
        # the backend's registry is authoritative (a prebuilt backend carries
        # its own); the engine uses it for slot acquire/release at admission
        self.adapter_registry = (getattr(self.backend, "adapter_registry", None)
                                 or adapter_registry)
        self.tenant_quotas = (
            tenant_quotas
            if tenant_quotas is None or isinstance(tenant_quotas, TenantQuotas)
            else TenantQuotas(tenant_quotas))
        # per-tenant attributable-token accounting (the tenancy fold over the
        # PR 15 ledger): monotone engine totals, surviving reset() like the
        # ledger's — the metrics plane rebaselines on rebind
        self.tenant_goodput: Dict[str, Dict[str, int]] = {}
        # stage-split scheduling state (engine-owned; the backend only copies
        # blocks): req_id -> in-flight MigrationTicket, plus the deferred
        # queue migrations wait on while the decode stage is under pressure
        self.staged = bool(getattr(self.backend, "staged", False))
        # is_ready-less runtimes: force-land a migration after this many polls
        # (the functional pool threading already guarantees correctness)
        self.migration_force_land_polls = 8
        self._migrating: Dict[int, object] = {}
        self._migrate_pending: deque = deque()
        # req_ids whose migration deferral was already recorded this episode
        # (one migrate.defer event per wait, not one per engine step)
        self._migrate_defer_noted: set = set()
        self.enable_prefix_cache = enable_prefix_cache
        self.mgr = self._new_block_manager(num_blocks, block_size, max_blocks_per_seq)
        # hierarchical KV: the optional host-RAM tier under the BlockManager,
        # plus the engine-held in-flight promotion tickets (req_id -> ticket;
        # the same marker-poll scheduling gate as stage migrations)
        self.host_kv_blocks = int(host_kv_blocks or 0)
        self._host_tier: Optional[HostKVTier] = None
        if self.host_kv_blocks > 0:
            if not enable_prefix_cache:
                raise ValueError(
                    "host_kv_blocks requires enable_prefix_cache=True: the "
                    "tier is the prefix cache's second level")
            self._host_tier = HostKVTier(
                self.host_kv_blocks,
                block_bytes=pool_block_bytes(self.backend.pool))
            self.mgr.attach_host_tier(self._host_tier)
        self._promoting: Dict[int, object] = {}
        self.max_batch_size = max_batch_size
        self.decode_steps = decode_steps
        self.waiting: deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * max_batch_size
        self._next_id = itertools.count()
        self._last_token = np.zeros(max_batch_size, np.int32)
        # generation by diffusion over blocks: the block length the kind's step
        # programs advance a sequence by (None: a token a step), and between
        # launches the block every slot is at: its tokens and which of its
        # positions are still masked (the launch's carry, _open_block)
        self.block: Optional[int] = getattr(self.backend.infer, "block_length", None)
        if self.block:
            self._block_tokens = np.zeros((max_batch_size, self.block), np.int32)
            self._block_masked = np.ones((max_batch_size, self.block), bool)
        # speculative decoding: n-gram prompt-lookup OR draft-model proposer,
        # batched verify; greedy acceptance or rejection sampling
        self.use_speculative = use_speculative or draft_model is not None
        self.spec_draft_len = spec_draft_len
        self.draft_model = draft_model
        self._spec_rngs: Dict[int, np.random.Generator] = {}
        self.spec_stats = {"verify_steps": 0, "tokens_emitted": 0, "drafted": 0, "accepted": 0}
        self.num_preemptions = 0
        if prefill_chunk_tokens is not None and prefill_chunk_tokens < 0:
            raise ValueError(f"prefill_chunk_tokens must be >= 0, got {prefill_chunk_tokens}")
        self.prefill_chunk_tokens = prefill_chunk_tokens or None
        # chunked-prefill accounting: monotone totals (stats()) plus bounded
        # event rings the metrics plane drains by sequence number — a stats()
        # read from an HTTP thread must never consume a histogram observation
        self.chunk_stats = {"chunks": 0, "chunk_tokens": 0}
        self._chunk_seq = itertools.count(1)
        self.recent_chunk_sizes: deque = deque(maxlen=256)  # (seq, n_tokens)
        self.recent_decode_stalls: deque = deque(maxlen=256)  # (seq, seconds)
        # monotone step id: stamped on host spans AND on the device timeline
        # via jax.profiler.StepTraceAnnotation, so a span in /debug/trace and
        # an XLA op in a device profile join on the same number
        self._step_seq = itertools.count()
        self._cur_step = -1
        # goodput ledger: per-step token-conservation accounting
        # (fed == useful + padding + spec_rejected + rework, exact) + compile
        # telemetry + step anatomy. Loop-thread-confined like chunk_stats;
        # totals survive reset() (monotone engine totals, rebaselined by the
        # metrics plane on rebind exactly like the chunk counters)
        self.ledger = GoodputLedger(
            flops_per_token=estimate_model_flops_per_token(model.config),
            peak_flops=device_peak_flops()
            * max(self.backend.describe().get("devices", 1), 1))
        install_compile_listener()
        # step-time anatomy event ring, drained by seq like the chunk rings:
        # (seq, gap_s, device_s, host_s); gap_s < 0 = unmeasured (post-idle)
        self.recent_step_times: deque = deque(maxlen=512)
        self._step_time_seq = itertools.count(1)
        self._last_step_end: Optional[float] = None
        self._prev_step_busy = False
        self._step_device_s = 0.0

    def _new_block_manager(self, num_blocks: int, block_size: int, max_blocks_per_seq: int) -> BlockManager:
        """The allocator for this engine's backend: with a second table a
        sequence where the model's cache kinds keep a window."""
        return BlockManager(num_blocks, block_size, max_blocks_per_seq,
                            enable_prefix_cache=self.enable_prefix_cache,
                            **(getattr(self.backend.infer, "window_spec", None) or {}))

    # device state lives in the backend; these stay as read paths for tests,
    # tools and the metrics plane that predate the backend split
    @property
    def infer(self):
        return self.backend.infer

    @property
    def pool(self):
        return self.backend.pool

    @property
    def counts(self):
        return self.backend.counts

    @property
    def cur_step(self) -> int:
        """Number of the last ``step()`` started (-1 before the first): the
        ``step=`` arg of its spans and the ``step_num`` of its annotation."""
        return self._cur_step

    @property
    def last_step_device_s(self) -> float:
        """Seconds the last ``step()`` spent inside backend launches, as its
        launch spans measured them (0.0: it launched nothing)."""
        return self._step_device_s

    # ------------------------------------------------------------------ api
    def add_request(self, prompt_ids, sampling: Optional[SamplingParams] = None,
                    stream_cb: Optional[Callable] = None, trace: Optional[str] = None,
                    priority: str = "interactive", rework_hwm: int = 0,
                    adapter_id: Optional[str] = None,
                    tenant: str = DEFAULT_TENANT,
                    arrival_t: Optional[float] = None) -> int:
        """``arrival_t`` (``time.time()`` clock) is when the submission was
        taken, if that was earlier than this call: the serving loop passes its
        handle's ``submitted_t``, so queue wait, TTFT and e2e cover what the
        client waited, the time on the loop's inbox included.

        ``rework_hwm`` marks the first ``rework_hwm`` prompt positions as
        already-fed-once (a supervisor requeue resubmitting a folded prompt
        after an engine rebuild): the goodput ledger then books their
        re-prefill as ``requeue_refill`` rework instead of useful work.

        ``adapter_id`` selects a LoRA adapter registered with the engine's
        :class:`~..serving.tenancy.AdapterRegistry` (validated HERE so an
        unknown id fails at submit, not mid-batch); ``tenant`` names the
        billing/quota identity the request's work is attributed to."""
        sampling = sampling or SamplingParams()
        if self.block:
            self.backend.infer.refuse_sampling(sampling)  # greedy only, by name
        if adapter_id is not None:
            if self.adapter_registry is None:
                raise UnknownAdapterError(
                    f"adapter {adapter_id!r} requested but the engine has no "
                    "adapter_registry")
            if adapter_id not in self.adapter_registry:
                raise UnknownAdapterError(
                    f"adapter {adapter_id!r} is not registered "
                    f"(known: {sorted(self.adapter_registry.ids())})")
        req = Request(
            req_id=next(self._next_id),
            prompt_ids=np.asarray(prompt_ids, dtype=np.int32).reshape(-1),
            sampling=sampling,
            stream_cb=stream_cb,
            trace=trace,
            priority=priority,
            tenant=tenant,
            adapter_id=adapter_id,
            block_length=self.block or 1,
        )
        req.enqueued_t = time.time()
        req.arrival_t = req.enqueued_t if arrival_t is None else min(arrival_t, req.enqueued_t)
        req.base_prompt_len = len(req.prompt_ids)
        self._tenant_counts(tenant)["requests"] += 1
        if rework_hwm > 0:
            req.fed_hwm = min(int(rework_hwm), len(req.prompt_ids))
            req.rework_src = "requeue_refill"
        # priority-ordered admission: insert before the first waiting request
        # of a STRICTLY lower class so interactive work overtakes queued batch/
        # best-effort prompts under load, while same-class order stays FIFO
        # (the default "interactive"-everywhere case degenerates to append).
        # Preemption-requeues keep their appendleft fast path untouched.
        rank = _PRIORITY_RANK.get(priority, 0)
        if not self.waiting or _PRIORITY_RANK.get(self.waiting[-1].priority, 0) <= rank:
            self.waiting.append(req)
        else:
            for i, queued in enumerate(self.waiting):
                if _PRIORITY_RANK.get(queued.priority, 0) > rank:
                    self.waiting.insert(i, req)
                    break
        return req.req_id

    def has_work(self) -> bool:
        return bool(self.waiting) or any(r is not None for r in self.slots)

    def abort(self, req_id: int) -> Optional[Request]:
        """Cancel a request wherever it is (waiting queue or a running slot).

        Counterpart of the reference's stop-flag write into the running batch
        (step.cu clears the slot; here the host owns scheduling so it is a
        plain dict/slot edit). Frees the request's KV blocks, marks it
        ``aborted`` with ``finish_reason='abort'`` and returns it; returns
        None for ids that are unknown or already finished. The stream callback
        is NOT fired — cancellation notification is the caller's job (the
        serving loop resolves the handle)."""
        for i, req in enumerate(self.waiting):
            if req.req_id == req_id:
                del self.waiting[i]
                self._finish_abort(req)
                return req
        for slot, req in enumerate(self.slots):
            if req is not None and req.req_id == req_id:
                self._free_kv(req)
                self.slots[slot] = None
                self._drop_migration(req_id)
                self._drop_promotion(req_id)
                self._finish_abort(req)
                return req
        return None

    def _free_kv(self, req: Request, cache: bool = False):
        """Release a request's KV blocks (+ an alloc/free trace marker).

        ``cache=True`` (normal finishes) registers the request's full prompt
        blocks in the prefix index instead of freeing them, so the next
        request sharing the prefix skips their prefill; aborts and
        preemptions release by refcount without registering."""
        freed = self.mgr.lengths.get(req.req_id)
        if req.kv_occ_t is not None:
            # close the open KV-occupancy episode while the block table still
            # exists: the block·seconds integral is what usage metering bills
            # for cache residency
            req.kv_block_seconds += (time.perf_counter() - req.kv_occ_t) \
                * len(self.mgr.tables.get(req.req_id, ()))
            req.kv_occ_t = None
        if cache and self.enable_prefix_cache and req.finish_reason in ("stop", "length"):
            # salt = adapter_id: an adapter's KV is the product of base+delta
            # forwards, so cached prefixes are only shareable within the SAME
            # adapter (base-model requests keep the historical unsalted hashes).
            # GENERATED blocks register too (conversation-lifetime caching: a
            # chat turn's completion is the next turn's prompt prefix) — the
            # last sampled token is excluded because it was emitted, never fed,
            # so its KV position was never written
            token_ids = req.prompt_ids
            if len(req.output_ids) > 1:
                gen = np.asarray(req.output_ids[:-1], np.int32)  # sync-ok: host int list, no device sync
                token_ids = np.concatenate([req.prompt_ids, gen])
            bs = self.mgr.block_size
            nb_full = len(token_ids) // bs
            wb = nb_full - len(req.prompt_ids) // bs
            if self.staged and wb > 0 and req.req_id in self.mgr.tables:
                # staged backends: decode wrote the generated positions into
                # the DECODE pool, but cached prefixes serve prefill from the
                # PREFILL pool — copy the generation-bearing full blocks back
                # before registering them. The prompt/generation boundary
                # block is complete in the decode pool (migration moved it
                # whole before decode appended), so the write-back slice
                # starts there, not one block later.
                table = self.mgr.tables[req.req_id]
                self.backend.kv_writeback(
                    list(table[len(req.prompt_ids) // bs : nb_full]))
            self.mgr.finish_seq_cached(req.req_id, token_ids, salt=req.adapter_id)
        else:
            self.mgr.free_seq(req.req_id)
        if req.adapter_slot:
            # the adapter-pool refcount travels with the KV blocks: finish,
            # abort, preemption and quarantine all pass through here, and
            # re-admission re-acquires (content-addressed => token-exact)
            self.adapter_registry.release(req.adapter_id)
            req.adapter_slot = 0
            if req.adapter_acq_t is not None:
                req.adapter_slot_seconds += time.perf_counter() - req.adapter_acq_t
                req.adapter_acq_t = None
        TRACER.instant("kv_free", cat="engine", trace=req.trace,
                       req_id=req.req_id, tokens_held=freed,
                       free_blocks=self.mgr.num_free,
                       cached_blocks=self.mgr.num_cached_blocks)

    def _finish_abort(self, req: Request):
        req.done = True
        req.aborted = True
        req.finish_reason = "abort"
        req.finish_t = time.time()
        self._spec_rngs.pop(req.req_id, None)

    def release_request(self, req_id: int) -> bool:
        """Drop a request from the scheduler (waiting queue or its slot) and
        free its KV blocks WITHOUT touching its finish fields — the serving
        supervisor's slot-level quarantine, where the supervisor (not the
        engine) owns the request's resolution. Unlike :meth:`abort` this never
        fabricates a ``finish_reason`` and fires no callback; unlike
        :meth:`reset` it leaves every other slot untouched, so unaffected
        streams keep decoding. Returns True iff the engine held the request."""
        for i, req in enumerate(self.waiting):
            if req.req_id == req_id:
                # waiting requests hold no KV blocks (allocation happens at
                # admission; preemption frees before requeue)
                del self.waiting[i]
                self._spec_rngs.pop(req_id, None)
                return True
        for slot, req in enumerate(self.slots):
            if req is not None and req.req_id == req_id:
                self._free_kv(req)
                self.slots[slot] = None
                self._drop_migration(req_id)
                self._drop_promotion(req_id)
                self._spec_rngs.pop(req_id, None)
                return True
        self._spec_rngs.pop(req_id, None)
        if req_id in self.mgr.lengths:
            # allocated but bound to no slot yet: the failure escaped mid-
            # admission, between KV allocation and the slot write — the
            # blocks are real even though the scheduler never saw the request
            self.mgr.free_seq(req_id)
            return True
        # already retired (finish raced the failure): nothing held
        return False

    def resync_counts(self):
        """Re-seed the device-side penalty counts of every live slot from
        host-known token history (``prompt[:prefilled_len] + output_ids``).
        The supervisor's slot quarantine calls this after releasing a
        poisoned request: the failed step may have committed count updates
        on device for tokens whose host-side emit never ran — those tokens
        will be regenerated from host state, and without the resync a
        penalty-sampling neighbor would see them double-counted."""
        entries, slot_idx = [], []
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            hist = np.concatenate([req.prompt_ids[: req.prefilled_len],
                                   np.asarray(req.output_ids, np.int32)])
            entries.append((len(slot_idx), hist, len(hist)))
            slot_idx.append(slot)
        if slot_idx:
            self.backend.seed_counts(slot_idx, entries)

    def clear_prefix_cache(self):
        """Invalidate every cached prefix block (idle ones return to the free
        list). Required after a weight update: cached KV is only valid under
        the params that produced it."""
        self.mgr.clear_prefix_cache()

    def sync_params(self, new_params):
        """Install a new base-weight tree through the backend seam (eager
        placement on the backend's existing device layout — see
        :meth:`ModelBackend.sync_params`). Callers own the rest of the swap
        protocol: quiesce, :meth:`clear_prefix_cache` (cached KV is only
        valid under the params that produced it), and
        :meth:`resync_counts` for any slots kept live across the swap."""
        self.backend.sync_params(new_params)

    # ------------------------------------------------------------------ stage migration
    def _slot_of(self, req_id: int) -> Optional[int]:
        for slot, r in enumerate(self.slots):
            if r is not None and r.req_id == req_id:
                return slot
        return None

    def _stage_blocks(self) -> Dict[str, int]:
        """KV blocks held per stage (host bookkeeping off the single shared
        block-id space): ``prefill`` = sequences mid-prefill or migrating,
        ``decode`` = decode-eligible sequences. The pressure inputs for
        stage-aware admission and the migration gate."""
        held = {"prefill": 0, "decode": 0}
        for r in self.slots:
            if r is None or r.req_id not in self.mgr.tables:
                continue
            key = "decode" if r.kv_stage == "decode" else "prefill"
            held[key] += len(self.mgr.tables[r.req_id])
        return held

    def _drop_migration(self, req_id: int):
        """Forget a request's migration state (abort / preempt / quarantine).
        An already-dispatched copy needs no cancellation: it only wrote the
        request's own blocks, which are about to be freed — any future owner
        re-prefills and re-migrates over them."""
        self._migrating.pop(req_id, None)
        self._migrate_defer_noted.discard(req_id)
        try:
            self._migrate_pending.remove(req_id)
        except ValueError:
            pass

    def _advance_migrations(self):
        """Poll in-flight prefill→decode block migrations and start deferred
        ones. Landing flips the sequence to ``kv_stage="decode"`` — the only
        thing that makes it decode-eligible. Starts are gated by the in-flight
        bound and by decode-stage KV pressure (a saturated decode pool must
        drain before it accepts more handoffs — the backpressure that keeps
        the two SLOs decoupled instead of re-coupling them through the pool)."""
        for req_id, ticket in list(self._migrating.items()):
            ticket.polls += 1
            if not (self.backend.migration_ready(ticket)
                    or ticket.polls >= self.migration_force_land_polls):
                continue
            del self._migrating[req_id]
            slot = self._slot_of(req_id)
            if slot is None:
                continue  # aborted/preempted while the blocks were in flight
            req = self.slots[slot]
            req.kv_stage = "decode"
            if req.migrate_start_t is not None:
                # the migration-wait episode closes: bank it for attribution
                req.migration_wait_s += time.time() - req.migrate_start_t
                req.migrate_start_t = None
            RECORDER.record("migrate.land", req_id=req_id, trace=req.trace,
                            blocks=ticket.n_blocks, polls=ticket.polls)
            TRACER.instant("kv_migrated", cat="engine", trace=req.trace,
                           req_id=req_id, blocks=ticket.n_blocks,
                           polls=ticket.polls)
        total = max(self.mgr.total_usable_blocks, 1)
        if self._migrate_pending and len(self._migrating) >= self.migration_inflight_limit:
            self._note_migrate_deferred(self._migrate_pending[0], "inflight_limit")
        while self._migrate_pending and len(self._migrating) < self.migration_inflight_limit:
            if self._stage_blocks()["decode"] / total > self.decode_pressure_gate:
                self._note_migrate_deferred(self._migrate_pending[0], "decode_pressure")
                break  # decode pressure gates handoff; finishing seqs free it
            req_id = self._migrate_pending[0]
            slot = self._slot_of(req_id)
            if slot is None or self.slots[slot].kv_stage != "migrating":
                self._migrate_pending.popleft()
                continue  # retired/preempted while deferred
            req = self.slots[slot]
            # fired BEFORE the queue pop: an injected failure leaves the
            # handoff queued, so recovery (or a bare retry) finds it intact
            _F_MIGRATE.fire(req_id=req_id)
            self._migrate_pending.popleft()
            blocks = self.mgr.tables[req_id]
            hist = np.concatenate([req.prompt_ids[: req.prefilled_len],
                                   np.asarray(req.output_ids, np.int32)])  # sync-ok: host-side id lists (decode-stage count seed)
            t0 = time.perf_counter()
            self._migrating[req_id] = self.backend.kv_migrate(
                req_id, list(blocks), slot, hist)
            # goodput: the decode-stage penalty-count re-seed re-processes the
            # sequence's whole token history — pure rework, zero useful
            self.ledger.record("reseed", len(hist), 0, rework=len(hist),
                               rework_by={"migration_reseed": len(hist)})
            self._migrate_defer_noted.discard(req_id)
            RECORDER.record("migrate.start", req_id=req_id, trace=req.trace,
                            blocks=len(blocks), inflight=len(self._migrating))
            TRACER.add_span("kv_migrate", TRACER.epoch_time(t0),
                            time.perf_counter() - t0, cat="engine",
                            trace=req.trace, req_id=req_id, blocks=len(blocks),
                            inflight=len(self._migrating))

    def _note_migrate_deferred(self, req_id: int, reason: str):
        """One migrate.defer event per wait episode for the head pending
        handoff (the gate re-evaluates every step; the recorder must not)."""
        if req_id in self._migrate_defer_noted:
            return
        self._migrate_defer_noted.add(req_id)
        slot = self._slot_of(req_id)
        trace = self.slots[slot].trace if slot is not None else None
        RECORDER.record("migrate.defer", req_id=req_id, trace=trace,
                        reason=reason, inflight=len(self._migrating),
                        pending=len(self._migrate_pending))

    # ------------------------------------------------------------------ host KV tier
    def _drop_promotion(self, req_id: int):
        """Forget a request's in-flight promotion (abort / preempt /
        quarantine). A dispatched H2D copy needs no cancellation: functional
        pool threading orders it before any later read, and it only wrote
        the request's own blocks, which are about to be freed."""
        self._promoting.pop(req_id, None)

    def _drain_spills(self):
        """Flush prefix blocks the allocator popped off the cache LRU since
        the last drain into the host tier: ONE batched D2H gather, dispatched
        BEFORE any launch that could overwrite the recycled blocks (JAX
        dispatch order makes the gather read the pre-write values, and
        ``copy_to_host_async`` overlaps the transfer with the step's real
        work). A failure drops the spill — the blocks were already recycled,
        which is exactly the pre-tier behavior — and leaks nothing."""
        if self._host_tier is None:
            return
        pairs = self.mgr.drain_pending_spills()
        if not pairs:
            return
        try:
            _F_SPILL.fire(blocks=len(pairs))
            kv, scale = self.backend.kv_spill([b for _h, b in pairs])
            self._host_tier.put([h for h, _b in pairs], kv, scale)
        except Exception as e:
            RECORDER.record("spill.drop", blocks=len(pairs),
                            error=type(e).__name__)
            logger.warning(f"host-tier spill of {len(pairs)} blocks dropped: {e}")
            return
        RECORDER.record("spill.batch", blocks=len(pairs),
                        resident=self._host_tier.num_blocks)

    def _advance_promotions(self, finished: List[Request]):
        """Poll in-flight host→device KV promotions (same marker-poll gate as
        stage migrations). Landing re-opens the request's prefill path:
        chunked engines start feeding its remaining suffix next
        ``_mixed_step``; monolithic engines launch the deferred prefill batch
        right here, in the same step the copy landed."""
        to_prefill: List[tuple] = []
        for req_id, ticket in list(self._promoting.items()):
            ticket.polls += 1
            if not (self.backend.migration_ready(ticket)
                    or ticket.polls >= self.migration_force_land_polls):
                continue
            del self._promoting[req_id]
            slot = self._slot_of(req_id)
            if slot is None:
                continue  # aborted/preempted while the copy was in flight
            req = self.slots[slot]
            # staged backends resume the ordinary prefill→migrate→decode walk
            # (promoted blocks landed in the prefill-stage pool); single-pool
            # backends just become row-eligible again
            req.kv_stage = "prefill" if self.staged else "decode"
            if req.promote_start_t is not None:
                # the promote-wait episode closes: bank it for attribution
                req.promote_wait_s += time.time() - req.promote_start_t
                req.promote_start_t = None
            RECORDER.record("promote.land", req_id=req_id, trace=req.trace,
                            blocks=ticket.n_blocks, polls=ticket.polls)
            TRACER.instant("kv_promoted", cat="engine", trace=req.trace,
                           req_id=req_id, blocks=ticket.n_blocks,
                           polls=ticket.polls)
            if not self.prefill_chunk_tokens and req.needs_prefill:
                to_prefill.append((slot, req, req.prefilled_len))
        if to_prefill:
            self._prefill_batch(to_prefill, finished)

    def reset(self):
        """Drop ALL scheduler/allocator state after a failed step — the
        in-place recovery the serving supervisor uses when it has no
        ``engine_factory``. The device pool tensor is kept (stale KV is
        unreachable once the block tables are rebuilt; prefill overwrites
        live slots), so reset is O(host state), not O(HBM).

        In-flight requests are NOT resolved here: the supervisor owns their
        retry/abort disposition and must triage before calling reset."""
        self.waiting.clear()
        self.slots = [None] * self.max_batch_size
        self.mgr = self._new_block_manager(self.mgr.total_usable_blocks + 1, self.mgr.block_size,
                                           self.mgr.max_blocks_per_seq)
        self._last_token[:] = 0
        self.backend.reset_counts()
        if self.adapter_registry is not None:
            # dropped requests can no longer release their pool refcounts;
            # adapters stay RESIDENT (content intact for re-acquisition)
            self.adapter_registry.reset_refs()
        self._spec_rngs.clear()
        self._migrating.clear()
        self._migrate_pending.clear()
        self._migrate_defer_noted.clear()
        self._promoting.clear()
        if self._host_tier is not None:
            # tier content stays valid across reset (content-addressed KV
            # under unchanged params) — only the device-side index dropped
            # with the manager; re-attach so spills keep flowing. Pending
            # spills died with the old manager: their block ids are stale.
            self.mgr.attach_host_tier(self._host_tier)
        # the failed step never ran its anatomy tail: without this, the first
        # post-recovery step would book the whole outage (triage + reset) as
        # a "step gap" and pollute the histogram the bench gate reads
        self._last_step_end = None
        self._prev_step_busy = False
        logger.warning("inference engine reset: scheduler + KV allocator state dropped")

    def stats(self) -> Dict:
        """Point-in-time scheduler/allocator stats (what the serving loop
        hands to ``ServingMetrics.on_step`` after every step)."""
        out = {
            "queue_depth": len(self.waiting),
            "running": sum(1 for r in self.slots if r is not None),
            "max_batch_size": self.max_batch_size,
            "free_blocks": self.mgr.num_free,
            "total_blocks": self.mgr.total_usable_blocks,
            "num_preemptions": self.num_preemptions,
            "spec_stats": dict(self.spec_stats),
            "prefix_cache": {
                "enabled": self.enable_prefix_cache,
                "hits": self.mgr.cache_hits,
                "cached_tokens": self.mgr.cached_tokens_total,
                "evictions": self.mgr.evictions,
                "cached_blocks": self.mgr.num_cached_blocks,
                # the host-RAM spill tier under the device cache: always
                # present (zeros when off) so the metrics plane reads one shape
                "host": dict(
                    {"enabled": self._host_tier is not None,
                     "promotes_inflight": len(self._promoting)},
                    **(self._host_tier.snapshot() if self._host_tier is not None
                       else {"blocks": 0, "capacity": 0, "spills": 0,
                             "spill_batches": 0, "promotes": 0,
                             "promoted_blocks": 0, "promote_bytes": 0,
                             "evictions": 0}),
                ),
            },
            "chunked_prefill": {
                "enabled": bool(self.prefill_chunk_tokens),
                "chunk_tokens": self.prefill_chunk_tokens or 0,
                "chunks": self.chunk_stats["chunks"],
                "chunk_tokens_total": self.chunk_stats["chunk_tokens"],
            },
            "backend": self.backend.describe(),
            # the goodput ledger rides stats() so the metrics plane, /health
            # and postmortem bundles all carry the waste accounting
            "goodput": self.ledger.snapshot(),
        }
        if self.adapter_registry is not None or self.tenant_goodput:
            out["tenancy"] = {
                # per-tenant goodput fold over the engine's attributable-token
                # accounting (the tenancy leg of the PR 15 ledger)
                "tenants": tenant_goodput_fold(self.tenant_goodput),
                "adapters": (self.adapter_registry.stats()
                             if self.adapter_registry is not None else None),
                "quotas": (self.tenant_quotas.describe()
                           if self.tenant_quotas is not None else None),
            }
        if self.staged:
            held = self._stage_blocks()
            total = max(self.mgr.total_usable_blocks, 1)
            n_prefilling = sum(1 for r in self.slots
                               if r is not None and r.needs_prefill)
            n_migrating = sum(1 for r in self.slots
                              if r is not None and r.kv_stage == "migrating")
            out["disagg"] = {
                # TTFT comes from this pool ...
                "prefill_stage": {
                    "kv_blocks": held["prefill"],
                    "kv_utilization": held["prefill"] / total,
                    "queue_depth": len(self.waiting) + n_prefilling,
                },
                # ... inter-token latency from this one
                "decode_stage": {
                    "kv_blocks": held["decode"],
                    "kv_utilization": held["decode"] / total,
                    "queue_depth": n_migrating,
                },
                "migrations": dict(getattr(self.backend, "migration_stats",
                                           {"migrations": 0, "blocks": 0, "bytes": 0})),
                "migrations_inflight": len(self._migrating),
                "migrations_pending": len(self._migrate_pending),
            }
        return out

    def kv_fragmentation(self) -> float:
        """Internal fragmentation of allocated KV blocks: 1 - held tokens /
        (held blocks * block_size). 0.0 when nothing is allocated. Block-
        granular allocation always strands the tail of the last block; this
        gauge is how much of the allocated pool that amounts to right now.

        Called from HTTP scrape threads while the loop thread mutates the
        BlockManager: the dict snapshots are taken via ``list()`` (atomic in
        CPython) and a mid-resize race degrades to one stale scrape, never a
        500."""
        try:
            tables = list(self.mgr.tables.values())
            lengths = list(self.mgr.lengths.values())
        except RuntimeError:  # dict resized mid-copy by the loop thread
            return 0.0
        blocks = sum(len(t) for t in tables)
        if not blocks:
            return 0.0
        return max(0.0, 1.0 - sum(lengths) / (blocks * self.mgr.block_size))

    def efficiency(self) -> Dict:
        """The ``GET /debug/efficiency`` document: ledger snapshot, MFU /
        FLOPs model, percentiled step anatomy, occupancy and KV
        fragmentation. Readable from any thread (plain attribute reads; at
        worst one step stale — the stats() contract)."""
        running = sum(1 for r in self.slots if r is not None)
        try:
            step_times = list(self.recent_step_times)
        except RuntimeError:  # loop thread appended mid-copy: drop one window
            step_times = []
        return efficiency_doc(
            self.ledger, step_times, tier="serving",
            extra={
                "occupancy": {
                    "running": running,
                    "max_batch_size": self.max_batch_size,
                    "slot_occupancy": running / max(self.max_batch_size, 1),
                },
                "kv_fragmentation": round(self.kv_fragmentation(), 6),
                "spec": {
                    "drafted": self.spec_stats["drafted"],
                    "accepted": self.spec_stats["accepted"],
                    "acceptance_rate": self.spec_stats["accepted"]
                    / max(self.spec_stats["drafted"], 1),
                },
                "backend": self.backend.describe(),
            })

    def generate(self, prompts: List, sampling: Optional[SamplingParams] = None) -> List[List[int]]:
        """Submit a batch and run to completion (convenience API)."""
        ids = [self.add_request(p, sampling) for p in prompts]
        results: Dict[int, Request] = {}
        while self.has_work():
            for req in self.step():
                results[req.req_id] = req
        return [results[i].output_ids for i in ids]

    # ------------------------------------------------------------------ scheduling
    def step(self) -> List[Request]:
        """One engine iteration: admit + decode. Returns requests finished this step."""
        _F_STEP.fire()
        self._cur_step = next(self._step_seq)
        # step anatomy: host gap since the previous BUSY step ended (loop
        # overhead between steps) vs device time inside backend calls (the
        # launch spans' own durations: anatomy and span are one measurement)
        # vs the step's own host scheduling time. Post-idle steps have no meaningful
        # gap (the loop slept on purpose) — marked unmeasured (-1)
        t_step0 = time.perf_counter()
        gap_s = (t_step0 - self._last_step_end
                 if self._last_step_end is not None and self._prev_step_busy
                 else -1.0)
        self._step_device_s = 0.0
        finished: List[Request] = []
        # StepTraceAnnotation brackets this step on the device timeline: a
        # jax.profiler capture (POST /debug/profile) shows per-step lanes
        # whose step_num matches the step= arg on the host prefill/decode
        # spans — host stall or device stall is one cross-reference away
        with jax.profiler.StepTraceAnnotation("engine_step", step_num=self._cur_step):
            if self._promoting:
                # land finished host→device promotions FIRST, so a landed
                # request prefills (or chunks) in this very step
                self._advance_promotions(finished)
            if self.staged:
                # land finished prefill→decode block copies and start deferred
                # ones BEFORE row selection, so a landed sequence decodes in
                # this very step
                self._advance_migrations()
            if self.prefill_chunk_tokens:
                self._admit_chunked(finished)
                if any(r is not None and r.needs_prefill for r in self.slots):
                    # >=1 slot mid-prefill: one ragged mixed step (chunks +
                    # one decode token per running sequence)
                    self._mixed_step(finished)
                elif self.block:
                    # diffusion over blocks: decode_steps passes of every slot
                    self._decode_blocks(finished)
                else:
                    # steady state: the multi-token decode jit as usual
                    self._decode_running(finished)
            else:
                self._admit(finished)
                self._decode_running(finished)
            with TRACER.span("step_tail", cat="engine", step=self._cur_step) as tail:
                # usage metering: advance each admitted request's
                # kv_block_seconds integral piecewise per step (block counts
                # grow during decode, so a single count-at-free rectangle
                # would misbill long requests)
                t_occ = time.perf_counter()
                for req in self.slots:
                    if req is not None and req.kv_occ_t is not None:
                        req.kv_block_seconds += (t_occ - req.kv_occ_t) \
                            * len(self.mgr.tables.get(req.req_id, ()))
                        req.kv_occ_t = t_occ
                t_end = time.perf_counter()
                host_s = max(t_end - t_step0 - self._step_device_s, 0.0)
                self.ledger.note_step(max(gap_s, 0.0), self._step_device_s, host_s)
                self.recent_step_times.append(
                    (next(self._step_time_seq), gap_s, self._step_device_s, host_s))
                self._last_step_end = t_end
                self._prev_step_busy = self.has_work()
                if not self._step_device_s:
                    # a step that launched nothing (every slot waiting on a
                    # copy) re-polls at once: keep the spin out of the ring
                    tail.discard()
        return finished

    @contextlib.contextmanager
    def _launch(self, name: str, program: str, carried=(), pending=(), **args):
        """One backend call: the launch span (mirrored to the profiler; what
        the launch was asked to do joins its args once the backend has stamped
        it) under compile attribution. The span's own duration is the step
        anatomy's device time: anatomy and span are one measurement.

        ``carried`` are the requests whose prompt tokens ride in the launch,
        ``pending`` those admitted with them that no slot holds yet (the later
        buckets of a monolithic prefill batch). The span says whose prompt it
        carried (``carried``: their req_ids) and how many admitted requests
        still need prefill and are not in it (``prefill_waiting``: what a
        second chunk row would have served); at its end every admitted request
        without a first token is booked the launch's duration, as its own or
        as another's (``Request.prefill_own_s`` / ``prefill_behind_s``)."""
        ids = [r.req_id for r in carried]
        # a promotion copy in flight is promote_wait's: no launch could carry it
        others = [r for r in (*self.slots, *pending)
                  if r is not None and r.req_id not in ids and r.kv_stage != "promoting"]
        span = TRACER.span(name, cat="engine", step=self._cur_step,  # span-names: prefill decode mixed_step spec_verify
                           carried=ids,
                           prefill_waiting=sum(1 for r in others if r.needs_prefill),
                           **args)
        try:
            with span, compile_attribution(self.ledger, program):
                yield span
                acct = self.backend.step_accounting
                span.set(**{g: acct[g] for g in LAUNCH_GEOMETRY})
                span.set(**{g: acct[g] for g in KIND_COUNTERS if g in acct})
        finally:
            self._step_device_s += span.dur
            # a request preempted after its first token is carried again and
            # books nothing: its time to first token is over
            for req in carried:
                if req.first_token_t is None:
                    req.prefill_steps += 1
                    req.prefill_own_s += span.dur
            for req in others:
                if req.first_token_t is None:
                    req.prefill_behind_s += span.dur

    def _free_slot_indices(self) -> List[int]:
        return [i for i, r in enumerate(self.slots) if r is None]

    def _tenant_counts(self, tenant: str) -> Dict[str, int]:
        tg = self.tenant_goodput.get(tenant)
        if tg is None:
            tg = self.tenant_goodput[tenant] = {
                "useful": 0, "rework": 0, "requests": 0, "tokens_out": 0}
        return tg

    def _tenant_held_blocks(self, tenant: str) -> int:
        """KV blocks currently held by a tenant's admitted requests (the
        engine-side input to the per-tenant block-share gate)."""
        return sum(len(self.mgr.tables[r.req_id]) for r in self.slots
                   if r is not None and r.tenant == tenant
                   and r.req_id in self.mgr.tables)

    def _note_fed_span(self, req: Request, start: int, n: int):
        """Goodput split of one fed span ``[start, start+n)``: positions below
        the request's fed high-water mark (re-prefill after preemption or a
        supervisor requeue) plus owed COW tail tokens are rework; the rest is
        useful. Advances the mark. Returns ``(rework, rework_by|None)``."""
        if n <= 0:
            return 0, None
        overlap = min(max(req.fed_hwm - start, 0), n)
        by = {}
        if overlap:
            by[req.rework_src] = overlap
        cow = min(req.cow_pending, n - overlap)
        if cow:
            by["cow_token"] = cow
            req.cow_pending -= cow
        req.fed_hwm = max(req.fed_hwm, start + n)
        rework = overlap + cow
        # the per-tenant fold: this request's attributable positions (padding
        # and speculative rejection are step-global, deliberately not here)
        tg = self._tenant_counts(req.tenant)
        tg["useful"] += n - rework
        tg["rework"] += rework
        # per-request mirror of the same attribution: the usage record's
        # useful_tokens must reconcile against the ledger token for token
        req.useful_tokens += n - rework
        return rework, (by or None)

    @staticmethod
    def _merge_rework(total_by: Dict[str, int], by: Optional[Dict[str, int]]):
        if by:
            for k, v in by.items():
                total_by[k] = total_by.get(k, 0) + v

    def _note_gated(self, req: Request, reason: str):
        """Mark the head-of-queue request as gate-deferred, ONCE per wait
        episode: the timestamp splits its eventual queue_wait into pure-queue
        vs admission-gate time (latency attribution), and the single decision
        event keeps a blocked queue from flooding the flight recorder with
        one identical record per engine step."""
        if req.gated_t is not None:
            return
        # stamped even on a preemption-requeue (sched_t already set) so the
        # event fires once, not per step; attribution only *uses* the stamp
        # when it falls inside the arrival -> first-admission window
        req.gated_t = time.time()
        RECORDER.record("admit.defer", req_id=req.req_id, trace=req.trace,
                        reason=reason, queue_depth=len(self.waiting),
                        free_blocks=self.mgr.num_free)

    def _admit_slots(self, finished: List[Request]) -> List[tuple]:
        """Shared admission front half: bind waiting requests to free slots and
        allocate their KV blocks (prefix-cache match + COW included). Returns
        ``[(slot, req, n_cached), ...]``; the caller owns the prefill launch —
        monolithic (:meth:`_admit`) or chunked (:meth:`_admit_chunked`)."""
        free = self._free_slot_indices()
        if not self.waiting or not free:
            return []
        n_finished0 = len(finished)
        cache_on = self.enable_prefix_cache
        hits0, cached0 = self.mgr.cache_hits, self.mgr.cached_tokens_total
        # admission closes BEFORE prefill (sibling phases, not nested) and is
        # kept in the ring only when something happened — a blocked queue
        # spinning admitted=0 every step must not flood it (the profiler
        # annotation brackets the attempt either way)
        with TRACER.span("admission", cat="engine", step=self._cur_step,
                         queue_depth=len(self.waiting)) as span:
            admitted = self._bind_waiting(finished, free, cache_on)
            span.set(admitted=len(admitted),
                     rejected_capacity=len(finished) - n_finished0)
            if not admitted and len(finished) == n_finished0:
                span.discard()
        # spill drain BEFORE the COW copies: a pending spill's D2H gather must
        # be enqueued before any device write can touch the recycled blocks
        # (apply_cow may write into freshly popped LRU blocks)
        self._drain_spills()
        if cache_on and admitted:
            # prefix_cache phase: match/COW bookkeeping + the owed block copies
            with TRACER.span("prefix_cache", cat="engine", step=self._cur_step,
                             hits=self.mgr.cache_hits - hits0,
                             cached_tokens=self.mgr.cached_tokens_total - cached0) as span:
                cow = self.mgr.drain_cow_pairs()
                if cow:
                    self.backend.apply_cow(cow)
                span.set(cow_copies=len(cow))
        return admitted

    def _bind_waiting(self, finished: List[Request], free: List[int],
                      cache_on: bool) -> List[tuple]:
        """The body of the ``admission`` phase: walk the waiting queue while
        slots are free. Returns ``[(slot, req, n_cached), ...]``."""
        admitted: List[tuple] = []  # (slot, req, n_cached)
        # stage-aware admission (staged backends): new prompts are prefill-
        # stage work, so their gate is PREFILL-stage KV pressure — blocks held
        # by mid-prefill + migrating sequences — not the shared total alone
        held_prefill = self._stage_blocks()["prefill"] if self.staged else 0
        total_blocks = max(self.mgr.total_usable_blocks, 1)
        # requests deferred by their TENANT's block-share cap step aside for
        # the rest of this pass (re-queued in order afterwards): one capped
        # tenant must not head-of-line block every other tenant's admissions
        tenant_deferred: List[Request] = []
        while self.waiting and free:
            req = self.waiting[0]
            prompt_len = len(req.prompt_ids)
            # a request that can NEVER fit must fail fast, not spin has_work()
            # forever. remaining_new (not max_new_tokens) so a preempted request
            # whose generated tokens were folded into the prompt is not
            # over-counted and spuriously rejected on re-admission.
            need = self.mgr.blocks_needed(prompt_len + req.remaining_new)
            if need > self.mgr.max_blocks_per_seq or need > self.mgr.total_usable_blocks:
                self.waiting.popleft()
                req.done = True
                req.finish_reason = "capacity"
                req.finish_t = time.time()
                RECORDER.record("admit.reject", req_id=req.req_id, trace=req.trace,
                                reason="capacity", blocks_needed=need,
                                prompt_len=prompt_len)
                logger.warning(f"req {req.req_id}: needs {need} KV blocks (> capacity); rejected")
                finished.append(req)
                continue
            # the gate charges only what admission actually reserves
            # (prompt + 1; decode growth happens on the decode stage), and an
            # IDLE prefill stage always admits at least one request — a lone
            # prompt larger than the gate fraction must run, not head-of-line
            # block the queue forever
            admit_need = self.mgr.blocks_needed(prompt_len + 1)
            if self.staged and held_prefill > 0 \
                    and held_prefill + admit_need > self.prefill_pressure_gate * total_blocks:
                self._note_gated(req, "prefill_gate")
                break  # prefill stage saturated: admitting would starve handoff
            if self.tenant_quotas is not None:
                cap = self.tenant_quotas.kv_block_cap(req.tenant,
                                                      self.mgr.total_usable_blocks)
                if cap is not None \
                        and self._tenant_held_blocks(req.tenant) + admit_need > cap:
                    # the tenant waits for its own requests to finish; it is
                    # deferred (not shed) and other tenants keep admitting
                    self._note_gated(req, "tenant_kv_share")
                    self.waiting.popleft()
                    tenant_deferred.append(req)
                    continue
            # reserve prompt + 1 so the first decode never immediately preempts;
            # cached prefix blocks need no fresh capacity, so a warm request
            # can be admitted where a cold one of the same length must wait.
            # The prefix match is computed ONCE and shared with allocate
            match = None
            if cache_on:
                # bound check before hashing: if even a perfect full-block
                # match can't fit, a blocked head-of-queue request must not
                # chain-hash its whole prompt again every engine step
                best_need = self.mgr.blocks_needed(prompt_len + 1) \
                    - prompt_len // self.mgr.block_size
                if best_need > self.mgr.num_free:
                    self._note_gated(req, "kv_pressure")
                    break
                match = self.mgr.match_prefix(req.prompt_ids, prompt_len,
                                              salt=req.adapter_id)
            if not self.mgr.can_admit(prompt_len + 1, match=match):
                self._note_gated(req, "kv_pressure")
                break
            adapter_slot = 0
            if req.adapter_id is not None:
                # acquire BEFORE the queue pop and KV allocation: a failed
                # hot-load leaves queue and allocator untouched (no KV or
                # pool-slot leak), and AdapterPressure just waits like
                # kv_pressure for a running adapter's refcount to drop
                try:
                    adapter_slot = self.adapter_registry.acquire(req.adapter_id)
                except AdapterPressure:
                    self._note_gated(req, "adapter_pressure")
                    break
                except Exception as e:
                    # a poisoned load (the engine.adapter_load fault point, a
                    # corrupt source): attribute it so the serving supervisor
                    # quarantines ONLY this request (engine_error/retry) while
                    # every other tenant's stream keeps decoding
                    if getattr(e, "req_id", None) is None:
                        try:
                            e.req_id = req.req_id
                        except Exception:
                            pass
                    raise
            self.waiting.popleft()
            req.adapter_slot = adapter_slot
            if adapter_slot:
                # adapter_slot_seconds episode opens with the refcount; the
                # release in _free_kv closes it (accumulates across preemptions)
                req.adapter_acq_t = time.perf_counter()
            if req.sched_t is None:  # preserved across preemption-requeues
                req.sched_t = time.time()
            if cache_on:
                _cached_blocks, n_cached, _new = self.mgr.allocate(
                    req.req_id, prompt_len, token_ids=req.prompt_ids, match=match)
            else:
                self.mgr.allocate(req.req_id, prompt_len)
                n_cached = 0
            # full-cover COW admissions owe a tail re-prefill of KV another
            # request already built: the ledger books it as cow_token rework.
            # Set, not accumulated — a preemption re-admission must not leak
            # a stale pending count into later spans
            req.cow_pending = (prompt_len - n_cached
                               if (match is not None and match[2] is not None) else 0)
            # hierarchical KV: the device-index match may continue into the
            # host tier — promote those blocks back with an async H2D copy
            # instead of re-prefilling them. The copy is dispatched NOW
            # (ahead of any prefill) and the request sits in kv_stage
            # "promoting" until the marker lands, overlapped with other
            # slots' decode steps. A full-cover COW admission skips this:
            # its whole prompt is already device-resident.
            if cache_on and self._host_tier is not None \
                    and not (match is not None and match[2] is not None):
                bs = self.mgr.block_size
                host_hashes = self.mgr.host_match(
                    req.prompt_ids, prompt_len, salt=req.adapter_id,
                    skip=n_cached // bs)
                # at least one prompt token must remain uncached: the first
                # output token is sampled by the final prompt forward
                while host_hashes and n_cached + len(host_hashes) * bs >= prompt_len:
                    host_hashes = host_hashes[:-1]
                if host_hashes:
                    # drain pending spills FIRST: this very allocate() may
                    # have popped LRU blocks that are about to be promote
                    # targets — their D2H gather must be enqueued before the
                    # promote scatter overwrites them. The drain's put() can
                    # LRU-evict tier entries, so re-truncate the match to the
                    # still-resident prefix afterwards.
                    self._drain_spills()
                    resident: List[bytes] = []
                    for h in host_hashes:
                        if not self._host_tier.contains(h):
                            break
                        resident.append(h)
                    host_hashes = resident
                if host_hashes:
                    promote_blocks = list(_new[: len(host_hashes)])
                    t_pr = time.perf_counter()
                    nbytes = len(host_hashes) * self._host_tier.block_bytes
                    try:
                        _F_PROMOTE.fire(req_id=req.req_id,
                                        blocks=len(host_hashes))
                        host_kv, host_scale, nbytes = \
                            self._host_tier.take(host_hashes)
                        ticket = self.backend.kv_promote(
                            req.req_id, promote_blocks, host_kv, host_scale)
                    except Exception as e:
                        # token-exact fallback: a pre-take failure leaves the
                        # entries tier-resident; a post-take one already
                        # popped them — either way the request keeps its
                        # allocated blocks, prefill just recomputes the span
                        # cold and the finish re-registers it. No host- or
                        # device-tier entry leaks, no stream is lost.
                        RECORDER.record("promote.fail", req_id=req.req_id,
                                        trace=req.trace,
                                        blocks=len(host_hashes),
                                        error=type(e).__name__)
                        logger.warning(
                            f"req {req.req_id}: host-tier promote failed "
                            f"({e}); falling back to cold prefill")
                    else:
                        self.mgr.register_promoted(promote_blocks, host_hashes)
                        if n_cached == 0:
                            self.mgr.cache_hits += 1
                        self.mgr.cached_tokens_total += len(host_hashes) * bs
                        n_cached += len(host_hashes) * bs
                        req.kv_stage = "promoting"
                        req.promote_start_t = time.time()
                        self._promoting[req.req_id] = ticket
                        RECORDER.record("promote.start", req_id=req.req_id,
                                        trace=req.trace,
                                        blocks=len(host_hashes), bytes=nbytes)
                        TRACER.add_span("kv_promote", TRACER.epoch_time(t_pr),
                                        time.perf_counter() - t_pr,
                                        cat="engine", trace=req.trace,
                                        req_id=req.req_id,
                                        blocks=len(host_hashes), bytes=nbytes)
            # usage metering: the KV-occupancy episode opens with the blocks;
            # the cache credit bills ONCE, at first admission — re-admission
            # hits after a preemption are rework economics, not a discount
            req.kv_occ_t = time.perf_counter()
            if req.cached_tokens is None:
                req.cached_tokens = n_cached
            TRACER.instant("kv_alloc", cat="engine", trace=req.trace,
                           req_id=req.req_id, tokens=prompt_len,
                           cached_tokens=n_cached,
                           free_blocks=self.mgr.num_free)
            if self.staged:
                # the sequence's KV is prefill-stage-resident until its last
                # chunk lands and the blocks migrate to the decode pool
                # ("promoting" is prefill-stage too — _stage_blocks agrees —
                # and flips to "prefill" when the H2D copy lands)
                if req.kv_stage != "promoting":
                    req.kv_stage = "prefill"
                held_prefill += len(self.mgr.tables[req.req_id])
            slot = free.pop(0)
            RECORDER.record("admit.accept", req_id=req.req_id, trace=req.trace,
                            slot=slot, prompt_len=prompt_len,
                            cached_tokens=n_cached)
            admitted.append((slot, req, n_cached))
        # capped-tenant requests return to the FRONT in their original order
        # (they were popped from the head before anything behind them)
        for r in reversed(tenant_deferred):
            self.waiting.appendleft(r)
        return admitted

    def _admit(self, finished: List[Request]):
        admitted = self._admit_slots(finished)
        if not admitted:
            return
        launch: List[tuple] = []
        for slot, req, n_cached in admitted:
            if req.kv_stage == "promoting":
                # promoted KV is still in flight: the request holds its slot
                # (prefilled_len = device + promoted cache credit) and its
                # prefill launches from _advance_promotions when the copy
                # lands — never against un-landed blocks
                req.prefilled_len = n_cached
                self.slots[slot] = req
            else:
                launch.append((slot, req, n_cached))
        self._prefill_batch(launch, finished)

    def _prefill_batch(self, admitted: List[tuple], finished: List[Request]):
        """Launch monolithic prefill for ``[(slot, req, n_cached), ...]`` —
        the back half of :meth:`_admit`, also invoked from
        :meth:`_advance_promotions` for requests whose prefill was deferred
        behind a host-tier promotion."""
        if not admitted:
            return
        # batch prefills, grouped by padded UNCACHED suffix length (bounded
        # retraces; a cache hit shortens the fed sequence, not just the FLOPs)
        by_bucket: Dict[int, List[tuple]] = {}
        for slot, req, n_cached in admitted:
            by_bucket.setdefault(_bucket(len(req.prompt_ids) - n_cached),
                                 []).append((slot, req, n_cached))
        groups = list(by_bucket.values())
        for g, (padded, group) in enumerate(by_bucket.items()):
            carried = [req for _, req, _ in group]
            # the later buckets' requests wait out this launch in no slot yet
            pending = [req for later in groups[g + 1:] for _, req, _ in later]
            with TRACER.span("launch_build", cat="engine", step=self._cur_step,
                             program="prefill"):
                n = _bucket(len(group), minimum=1)
                ids = np.zeros((n, padded), np.int32)
                tables = np.zeros((n, self.mgr.max_blocks_per_seq), np.int32)
                suffix_lens = np.zeros(n, np.int32)
                cached_lens = np.zeros(n, np.int32)
                sampling: List = [None] * n
                for j, (slot, req, n_cached) in enumerate(group):
                    suffix = req.prompt_ids[n_cached:]
                    ids[j, : len(suffix)] = suffix
                    tables[j] = self.mgr.table_array(req.req_id)
                    suffix_lens[j] = len(suffix)
                    cached_lens[j] = n_cached
                    sampling[j] = req.sampling
                entries = [(j, req.prompt_ids, c) for j, (_, req, c) in enumerate(group)]
                cached_total = int(cached_lens.sum())  # sync-ok: cached_lens is host numpy
                # adapter_table only with a registry attached: prebuilt test
                # backends predating the kwarg keep working registry-off
                extra = ({"adapter_table": [r.adapter_slot for _, r, _ in group]}
                         if self.adapter_registry is not None else {})
            # the per-request prefill spans join this launch on step=
            with self._launch("prefill", "prefill", carried=carried, pending=pending,
                              bucket=padded, batch=len(group), cached_tokens=cached_total):
                tokens = self.backend.prefill(
                    ids, tables, suffix_lens, entries, sampling,
                    [slot for slot, _, _ in group], **extra)
            acct = self.backend.step_accounting
            with TRACER.span("emit", cat="engine", step=self._cur_step, program="prefill"):
                # goodput: fed = the padded launch geometry; useful = the
                # uncached suffixes minus any re-fed (post-preemption/requeue/
                # COW) positions
                g_useful = g_rework = 0
                g_by: Dict[str, int] = {}
                for slot, req, n_cached in group:
                    n_fed = len(req.prompt_ids) - n_cached
                    rw, by = self._note_fed_span(req, n_cached, n_fed)
                    g_useful += n_fed - rw
                    g_rework += rw
                    self._merge_rework(g_by, by)
                self.ledger.note_shape(acct["shape"])
                self.ledger.record(
                    "prefill", acct["fed"], g_useful,
                    padding=acct["fed"] - g_useful - g_rework,
                    rework=g_rework, rework_by=g_by or None, geometry=acct)
                for j, (slot, req, _) in enumerate(group):
                    req.prefilled_len = len(req.prompt_ids)
                    self._settle_sampled(slot, req, int(tokens[j]), finished)  # sync-ok: tokens already host (backend.prefill synced)

    def _settle_sampled(self, slot: int, req: Request, tok: int, finished: List[Request]):
        """Post-sample bookkeeping shared by every sampling site (monolithic
        prefill, mixed-step final chunks, mixed-step decode rows): emit, then
        either retire the request (KV freed / prefix-cache registered, slot
        vacated) or keep it decoding in its slot."""
        self._emit(req, tok)
        if req.done:
            self._free_kv(req, cache=True)
            self.slots[slot] = None
            finished.append(req)
        else:
            self.slots[slot] = req
            self._last_token[slot] = tok
            if self.staged and req.kv_stage == "prefill" and not req.needs_prefill:
                # prefill done (first token sampled on the prefill stage):
                # the sequence decodes only after its blocks land in the
                # decode pool — queue the migration, don't block the step
                req.kv_stage = "migrating"
                req.migrate_start_t = time.time()  # migration-wait episode opens
                self._migrate_pending.append(req.req_id)

    # ------------------------------------------------------------------ chunked prefill
    def _admit_chunked(self, finished: List[Request]):
        """Chunked admission: bind slots + allocate KV, but launch NO prefill —
        the request sits in its slot with ``prefilled_len`` = its prefix-cache
        hit and :meth:`_mixed_step` feeds the rest chunk by chunk."""
        admitted = self._admit_slots(finished)
        if not admitted:
            return
        slot_idx = []
        for slot, req, n_cached in admitted:
            req.prefilled_len = n_cached
            self.slots[slot] = req
            slot_idx.append(slot)
            if self.block:
                self._open_block(slot, req)
        if self.block:
            return  # greedy only: no penalty counts to seed
        # seed the device-side penalty counts: the cached span never rides
        # through a chunk forward, so its counts come from a host bincount
        # (zeros rows still land — the slot's previous occupant is stale)
        self.backend.seed_counts(
            slot_idx, [(i, req.prompt_ids, c) for i, (_, req, c) in enumerate(admitted)])

    def _mixed_rows(self):
        """The ``launch_build`` phase of a mixed step: the capacity pass, the
        chunk budget and the row payloads. Returns ``(chunk_rows, decode_rows,
        chunk_payload, dec_payload)``, or None when no row can ride."""
        # capacity pass: every decoding slot needs a block covering this step's
        # KV write. Oldest slots secure theirs first; exhaustion preempts the
        # YOUNGEST active slot — which may be a mid-prefill request (its chunk
        # progress resets on requeue; mid-prefill rows themselves never grow,
        # their full-prompt blocks were reserved at admission).
        for slot in sorted(
                [s for s, r in enumerate(self.slots)
                 if r is not None and not r.needs_prefill
                 and r.kv_stage == "decode"],
                key=lambda s: self.slots[s].req_id):
            req = self.slots[slot]
            if req is None or req.needs_prefill:
                continue  # victim of an earlier iteration's preemption
            while True:
                # the positions this step writes: the token fed, or its whole block
                grow = (self._block_start(req) + self.block if self.block else req.total_len) \
                    - self.mgr.lengths[req.req_id]
                if grow <= 0 or self.mgr.extend(req.req_id, grow) is not None:
                    break
                active = [s for s, r in enumerate(self.slots) if r is not None]
                victim = max(active, key=lambda s: self.slots[s].req_id)
                self._preempt(victim, cause="mixed_capacity")
                if victim == slot:
                    break
        # the capacity pass may have popped LRU blocks: enqueue their D2H
        # gather before the mixed forward can overwrite them
        self._drain_spills()
        budget = self.prefill_chunk_tokens
        chunk_rows: List[tuple] = []  # (slot, req, n_new)
        decode_rows: List[tuple] = []  # (slot, req)
        prefilling: List[int] = []
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            if req.kv_stage == "promoting":
                continue  # promoted KV still in flight: no row until it lands
            if req.needs_prefill:
                prefilling.append(slot)
            elif req.kv_stage == "decode":
                decode_rows.append((slot, req))
            # else: migrating — contributes no row until its blocks land
        # the OLDEST mid-prefill request drinks the chunk budget first: slot
        # order would let a newly-admitted prompt landing in a lower slot
        # starve an older one indefinitely under sustained admissions
        most_rows = self.backend.max_chunk_rows
        for slot in sorted(prefilling, key=lambda s: self.slots[s].req_id):
            if budget <= 0 or (most_rows and len(chunk_rows) >= most_rows):
                break
            req = self.slots[slot]
            n = min(budget, req.prefill_len - req.prefilled_len)
            chunk_rows.append((slot, req, n))
            budget -= n
            RECORDER.record("chunk.grant", req_id=req.req_id, trace=req.trace,
                            tokens=n, budget_left=budget, step=self._cur_step)
        if not chunk_rows and not decode_rows:
            return None
        chunk_payload = []
        for slot, req, n in chunk_rows:
            p0 = req.prefilled_len
            self.mgr.window_span(req.req_id, p0, n)
            chunk_payload.append(MixedRow(
                slot=slot, tokens=req.prompt_ids[p0 : p0 + n], start=p0,
                table=self.mgr.table_array(req.req_id),
                # sampler on last chunk (never under diffusion over blocks: the
                # first generated block starts from masks)
                emit=not self.block and p0 + n == len(req.prompt_ids),
                sampling=req.sampling, adapter=req.adapter_slot))
        if self.block:
            return chunk_rows, decode_rows, chunk_payload, [self._block_row(slot, req) for slot, req in decode_rows]
        for slot, req in decode_rows:
            self.mgr.window_span(req.req_id, req.total_len - 1, 1)
        dec_payload = [
            MixedRow(slot=slot, tokens=np.asarray([self._last_token[slot]], np.int32),  # sync-ok: _last_token is a host array
                     start=req.total_len - 1,  # position of the token being fed
                     table=self.mgr.table_array(req.req_id), emit=True,
                     sampling=req.sampling, adapter=req.adapter_slot)
            for slot, req in decode_rows]
        return chunk_rows, decode_rows, chunk_payload, dec_payload

    def _mixed_step(self, finished: List[Request]):
        """One ragged mixed step: up to ``prefill_chunk_tokens`` prompt tokens
        (split across mid-prefill slots, oldest request first) plus ONE decode token
        for every running sequence, in a single forward. Decode keeps flowing
        while a long prompt fills — the per-step stall is bounded by the chunk
        budget, not the prompt length."""
        _F_CHUNK.fire(
            prefilling=sum(1 for r in self.slots if r is not None and r.needs_prefill))
        with TRACER.span("launch_build", cat="engine", step=self._cur_step,
                         program="mixed") as build:
            rows = self._mixed_rows()
            if rows is None:
                build.discard()  # nothing to launch: every slot waits on a copy
                return
            chunk_rows, decode_rows, chunk_payload, dec_payload = rows
        t0 = time.perf_counter()
        with self._launch("mixed_step", "mixed", carried=[req for _, req, _ in chunk_rows],
                          chunks=len(chunk_rows), decodes=len(decode_rows),
                          chunk_tokens=int(sum(n for _, _, n in chunk_rows))):
            tokens = (self.backend.mixed_step_blocks if self.block else self.backend.mixed_step)(
                chunk_payload, dec_payload)
        acct = self.backend.step_accounting
        dur = time.perf_counter() - t0
        with TRACER.span("emit", cat="engine", step=self._cur_step, program="mixed"):
            self._mixed_settle(chunk_rows, decode_rows, tokens, acct, dur, finished)

    def _mixed_settle(self, chunk_rows, decode_rows, tokens, acct, dur: float,
                      finished: List[Request]):
        """The ``emit`` phase of a mixed step: accounting, then settle."""
        # goodput accounting BEFORE settle mutates prefilled_len/total_len:
        # chunk tokens + the one fed token per decode row are useful (minus
        # re-fed positions); the padded launch remainder is padding
        g_useful = g_rework = 0
        g_by: Dict[str, int] = {}
        for _slot, req, n in chunk_rows:
            rw, by = self._note_fed_span(req, req.prefilled_len, n)
            g_useful += n - rw
            g_rework += rw
            self._merge_rework(g_by, by)
        # rows that fed one token and sampled the next; under diffusion over blocks none does
        token_rows = [] if self.block else decode_rows
        for _slot, req in token_rows:
            rw, by = self._note_fed_span(req, req.total_len - 1, 1)
            g_useful += 1 - rw
            g_rework += rw
            self._merge_rework(g_by, by)
        if self.block:
            # a pass's useful positions are the tokens it hands on; they are emitted first, so that
            # the ledger's entry holds them (``tokens`` is the launch's ``unpack_results``)
            g_useful += self._settle_blocks([(j, slot, req) for j, (slot, req) in enumerate(decode_rows)],
                                            tokens, finished)
        self.ledger.note_shape(acct["shape"])
        self.ledger.record(
            "mixed", acct["fed"], g_useful,
            padding=acct["fed"] - g_useful - g_rework,
            rework=g_rework, rework_by=g_by or None, geometry=acct)
        if chunk_rows:
            # every decode token in this step waited out the chunk work: the
            # step duration is each riding request's decode-stall share
            # (accumulated BEFORE settle so a request finishing this very
            # step still carries it into its attribution)
            for _slot, req in decode_rows:
                req.chunk_stall_s += dur
        for j, (slot, req, n) in enumerate(chunk_rows):
            req.prefilled_len += n
            self.chunk_stats["chunks"] += 1
            self.chunk_stats["chunk_tokens"] += n
            self.recent_chunk_sizes.append((next(self._chunk_seq), n))
            if not req.needs_prefill and not self.block:
                self._settle_sampled(slot, req, int(tokens[j]), finished)  # sync-ok: tokens already host (backend.mixed_step synced)
        for j, (slot, req) in enumerate(token_rows):
            self._settle_sampled(slot, req, int(tokens[len(chunk_rows) + j]), finished)  # sync-ok: tokens already host (backend.mixed_step synced)
        if chunk_rows and decode_rows:
            # every decode token in this step waited out the chunk work: the
            # step duration IS the decode stall attributable to prefill
            self.recent_decode_stalls.append((next(self._chunk_seq), dur))

    # ------------------------------------------------------------------ speculative
    def _spec_mode(self) -> Optional[str]:
        """'greedy' when every active request decodes greedily with penalties
        off (deterministic acceptance); 'sample' when a draft model is attached
        and every request does plain temperature sampling (top-k/top-p and
        penalties off) — that path accepts drafts by REJECTION SAMPLING, which
        preserves the target distribution exactly (the generalization the
        reference implements in top_p_sampling_reject.cu); None otherwise."""
        greedy = sample = True
        for r in self.slots:
            if r is None:
                continue
            s = r.sampling
            if s.repetition_penalty != 1.0 or s.presence_penalty != 0.0 \
                    or s.frequency_penalty != 0.0:
                return None
            if s.do_sample:
                greedy = False
                if s.top_k or (s.top_p < 1.0):
                    sample = False
            else:
                sample = False
        if greedy:
            return "greedy"
        if sample and self.draft_model is not None:
            return "sample"
        return None

    def _propose_drafts(self, req: Request) -> np.ndarray:
        """Prompt-lookup (n-gram) proposer: find the most recent earlier
        occurrence of the sequence's final n-gram and propose the tokens that
        followed it. Draft-model-free — the proposer the reference pairs with
        its speculative write ops for repetitive/extractive workloads."""
        k = min(self.spec_draft_len, max(req.remaining_new - 1, 0))
        n = self.spec_ngram
        if k == 0:
            return np.zeros(0, np.int32)
        hist = np.concatenate([req.prompt_ids, np.asarray(req.output_ids, np.int32)])
        if len(hist) <= n:
            return np.zeros(0, np.int32)
        pat = hist[-n:]
        windows = np.lib.stride_tricks.sliding_window_view(hist, n)
        starts = np.nonzero((windows == pat).all(axis=1))[0]
        starts = starts[starts < len(hist) - n]  # exclude the suffix itself
        if len(starts) == 0:
            return np.zeros(0, np.int32)
        s = int(starts[-1])
        return hist[s + n : s + n + k].astype(np.int32)

    def _propose_drafts_draft_model(self, mode: str):
        """Autoregressive draft-model proposer: K greedy/sampled steps of the
        small model over a FIXED padded buffer (one compile per length bucket;
        the draft is orders of magnitude cheaper than the target so the full
        recompute per step is noise). Returns (drafts per slot, draft probs per
        slot — [k, V] fp32 temperature-applied, None in greedy mode)."""
        active = [i for i, r in enumerate(self.slots) if r is not None]
        K = self.spec_draft_len
        ctxs = {i: np.concatenate([self.slots[i].prompt_ids,
                                   np.asarray(self.slots[i].output_ids, np.int32)])
                for i in active}
        ks = {i: min(K, max(self.slots[i].remaining_new - 1, 0)) for i in active}
        if not active or all(ks[i] == 0 for i in active):
            return [np.zeros(0, np.int32)] * len(self.slots), [None] * len(self.slots)
        max_len = max(len(c) for c in ctxs.values())
        L = 1 << max(6, (max_len + K - 1).bit_length())  # pow2 bucket caps recompiles
        B = len(active)
        ids = np.zeros((B, L), np.int32)
        lens = np.zeros(B, np.int32)
        for j, i in enumerate(active):
            ids[j, : len(ctxs[i])] = ctxs[i]
            lens[j] = len(ctxs[i])
        drafts = {i: [] for i in active}
        qprobs = {i: [] for i in active}
        for t in range(K):
            mask = (np.arange(L)[None, :] < (lens + t)[:, None]).astype(np.int32)
            out = self.draft_model(input_ids=jnp.asarray(ids), attention_mask=jnp.asarray(mask))
            # gather each sequence's next-token row ON DEVICE: only [B, V]
            # crosses to host, not the [B, L, V] tensor
            rows = np.asarray(jnp.take_along_axis(
                out.logits, jnp.asarray(lens + t - 1)[:, None, None], axis=1)[:, 0],
                dtype=np.float32)
            for j, i in enumerate(active):
                if t >= ks[i]:
                    continue
                row = rows[j]
                temp = max(self.slots[i].sampling.temperature, 1e-6)
                if mode == "sample":
                    row = row / temp
                    p = np.exp(row - row.max())
                    p /= p.sum()
                    nxt = int(self._req_rng(self.slots[i]).choice(len(p), p=p))
                    qprobs[i].append(p)
                else:
                    nxt = int(np.argmax(row))
                drafts[i].append(nxt)
                ids[j, lens[j] + t] = nxt
        out_d = [np.asarray(drafts.get(i, []), np.int32) for i in range(len(self.slots))]
        out_q = [np.asarray(qprobs[i], np.float32) if i in qprobs and qprobs[i] else None
                 for i in range(len(self.slots))]
        return out_d, out_q

    def _preempt(self, slot: int, cause: str = "decode_growth"):
        """Evict + requeue with prompt+generated as the new prompt (recompute
        recovery, the step.cu is_block_step/recover list). ``cause`` names
        which capacity pass chose the victim (decode table growth, a mixed
        step's capacity pass, or the speculative K+1 reservation)."""
        req = self.slots[slot]
        logger.warning(f"req {req.req_id}: KV blocks exhausted; preempting (recompute)")
        self.num_preemptions += 1
        RECORDER.record("preempt", req_id=req.req_id, trace=req.trace,
                        reason=cause, generated=len(req.output_ids),
                        free_blocks=self.mgr.num_free)
        TRACER.instant("preempt", cat="engine", trace=req.trace, req_id=req.req_id,
                       generated=len(req.output_ids), free_blocks=self.mgr.num_free)
        if req.migrate_start_t is not None:
            # an open migration-wait episode ends here (the blocks are gone;
            # re-admission restarts the walk) — bank the wait for attribution
            req.migration_wait_s += time.time() - req.migrate_start_t
            req.migrate_start_t = None
        if req.promote_start_t is not None:
            # same for an open promote-wait episode: the in-flight H2D copy
            # targets blocks being freed; re-admission re-matches the tier
            req.promote_wait_s += time.time() - req.promote_start_t
            req.promote_start_t = None
        self._drop_promotion(req.req_id)
        if not self.staged and req.kv_stage == "promoting":
            req.kv_stage = "decode"  # the single-pool default
        self._free_kv(req)
        self.slots[slot] = None
        req.prompt_ids = np.concatenate([req.prompt_ids, np.asarray(req.output_ids, np.int32)])  # sync-ok: host-side id lists
        req.output_ids = []
        # a half-prefilled request's KV is gone with its blocks: re-admission
        # starts the chunk walk over (prefix-cache hits re-credit what they can)
        req.prefilled_len = 0
        # from here on, re-fed positions are THIS preemption's recompute —
        # even for a request that originally arrived as a supervisor requeue
        req.rework_src = "preempt_refill"
        if self.staged:
            # any in-flight/deferred migration is moot: re-admission
            # re-prefills on the prefill stage and re-migrates
            self._drop_migration(req.req_id)
            req.kv_stage = "prefill"
        self.waiting.appendleft(req)

    def _req_rng(self, req) -> np.random.Generator:
        """Per-request generator seeded by (engine seed, SamplingParams.seed,
        req_id) — a request's rejection-sampling draws reproduce under re-runs
        with the same seed, matching the device sampler's per-request contract."""
        if req.req_id not in self._spec_rngs:
            self._spec_rngs[req.req_id] = np.random.default_rng(
                (self.spec_seed, req.sampling.seed, req.req_id))
        return self._spec_rngs[req.req_id]

    def _decode_spec(self, finished: List[Request], drafts: List[np.ndarray],
                     qprobs=None, mode: str = "greedy"):
        """One speculative iteration: verify the proposed drafts for the whole
        batch in ONE [B, K+1] forward, then accept on the host — greedy mode
        takes the longest argmax-matching prefix plus the model's bonus token;
        sample mode runs Leviathan rejection sampling against the draft probs
        (accept x_i w.p. min(1, p_i(x_i)/q_i(x_i)); on reject draw from
        normalize(max(p_i - q_i, 0))), which emits EXACT target-distribution
        samples. 1..K+1 tokens per sequence per forward either way."""
        K = self.spec_draft_len
        with TRACER.span("launch_build", cat="engine", step=self._cur_step,
                         program="verify") as build:
            # reserve capacity for all K+1 optimistic KV writes; preempt on OOM
            active = [s for s in range(len(self.slots)) if self.slots[s] is not None]
            for slot in sorted(active, key=lambda s: -self.slots[s].req_id):
                req = self.slots[slot]
                grow = req.total_len + K - self.mgr.lengths[req.req_id]
                if grow > 0 and self.mgr.extend(req.req_id, grow) is None:
                    self._preempt(slot, cause="spec_reserve")
            # the reservation pass may have popped LRU blocks: enqueue their D2H
            # gather before the verify forward can overwrite them
            self._drain_spills()
            if not any(r is not None for r in self.slots):
                build.discard()
                return

            B = self.max_batch_size
            tokens = np.zeros((B, K + 1), np.int32)
            tables = np.zeros((B, self.mgr.max_blocks_per_seq), np.int32)
            start = np.zeros(B, np.int32)
            for i, req in enumerate(self.slots):
                if req is None:
                    drafts[i] = np.zeros(0, np.int32)
                    continue
                d = drafts[i]
                tokens[i, 0] = self._last_token[i]
                tokens[i, 1 : 1 + len(d)] = d
                tables[i] = self.mgr.table_array(req.req_id)
                start[i] = req.total_len - 1  # position of the token being fed
            extra = ({"adapter_table": [0 if r is None else r.adapter_slot
                                        for r in self.slots]}
                     if self.adapter_registry is not None else {})
        with self._launch("spec_verify", "verify", mode=mode,
                          drafted=int(sum(len(d) for d in drafts))):
            # greedy acceptance never reads the logits: need_logits=False keeps
            # the [B, K+1, V] fp32 buffer from materializing at all
            argmax, logits = self.backend.verify(
                tokens, tables, start, need_logits=mode == "sample", **extra)
        acct = self.backend.step_accounting
        with TRACER.span("emit", cat="engine", step=self._cur_step, program="verify"):
            self._spec_accept(finished, drafts, qprobs, mode, argmax, logits, acct)

    def _spec_accept(self, finished: List[Request], drafts, qprobs, mode: str,
                     argmax, logits, acct):
        """The ``emit`` phase of a speculative step: host-side acceptance,
        emission and the ledger entry."""
        self.spec_stats["verify_steps"] += 1
        # goodput: drafted-but-rejected positions are the spec_rejected waste
        # bucket; emitted (accepted + correction/bonus) positions are useful
        g_acc0 = self.spec_stats["accepted"]
        g_drafted = g_emitted = 0
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            d = drafts[i]
            g_drafted += len(d)
            self.spec_stats["drafted"] += len(d)
            req.spec_drafted += len(d)
            if mode == "sample":
                with TRACER.span("sampling", cat="engine", trace=req.trace,
                                 req_id=req.req_id, kind="rejection", drafted=len(d)):
                    emitted = self._accept_rejection(i, req, d, logits[i], qprobs[i])
            else:
                targets = argmax[i]
                n_acc = 0
                while n_acc < len(d) and targets[n_acc] == d[n_acc]:
                    n_acc += 1
                emitted = list(d[:n_acc]) + [int(targets[n_acc])]  # sync-ok: argmax already host (backend.verify synced)
                self.spec_stats["accepted"] += n_acc
                req.spec_accepted += n_acc
            for tok in emitted:
                self._emit(req, int(tok))
                self._last_token[i] = int(tok)
                self.spec_stats["tokens_emitted"] += 1
                g_emitted += 1
                # per-tenant fold: accepted/bonus tokens are the useful verify
                # positions (rejected drafts are step-global spec waste)
                self._tenant_counts(req.tenant)["useful"] += 1
                req.useful_tokens += 1
                if req.done:
                    break
            # the last emitted token was sampled, not fed: mark to total-1
            req.fed_hwm = max(req.fed_hwm, req.total_len - 1)
            if req.done:
                self._free_kv(req, cache=True)
                self.slots[i] = None
                finished.append(req)
            else:
                # release the optimistic blocks past the accepted tokens
                self.mgr.shrink(req.req_id, req.total_len)
        g_rejected = g_drafted - (self.spec_stats["accepted"] - g_acc0)
        self.ledger.note_shape(acct["shape"])
        self.ledger.record(
            "verify", acct["fed"], g_emitted,
            padding=acct["fed"] - g_emitted - g_rejected,
            spec_rejected=g_rejected, geometry=acct)

    def _accept_rejection(self, slot: int, req, d: np.ndarray, logits_row: np.ndarray,
                          q: Optional[np.ndarray]) -> List[int]:
        """Leviathan et al. rejection sampling over one row: returns the tokens
        to emit (accepted prefix + correction-or-bonus sample)."""
        temp = max(req.sampling.temperature, 1e-6)
        rng = self._req_rng(req)
        emitted: List[int] = []
        for t in range(len(d)):
            row = logits_row[t] / temp
            p = np.exp(row - row.max())
            p /= p.sum()
            x = int(d[t])
            qv = float(q[t][x]) if q is not None else 1.0
            if rng.uniform() < min(1.0, float(p[x]) / max(qv, 1e-20)):
                emitted.append(x)
                self.spec_stats["accepted"] += 1
                req.spec_accepted += 1
                continue
            residual = np.maximum(p - (q[t] if q is not None else 0.0), 0.0)
            s = residual.sum()
            residual = residual / s if s > 0 else p
            emitted.append(int(rng.choice(len(residual), p=residual)))
            return emitted
        # every draft accepted: bonus token from the position after the last draft
        row = logits_row[len(d)] / temp
        p = np.exp(row - row.max())
        p /= p.sum()
        emitted.append(int(rng.choice(len(p), p=p)))
        return emitted

    # ------------------------------------------------------------------ diffusion over blocks
    def _block_start(self, req: Request) -> int:
        """First position of the block ``req`` is at: everything before it is
        prompt or handed on (a block's tokens leave together, so ``total_len``
        lies inside it only by the prompt's partial block)."""
        return req.total_len - req.total_len % self.block

    def _open_block(self, slot: int, req: Request):
        """The first generated block of a (re)admitted request: the ``len mod
        B`` prompt tokens left over as fixed positions, the rest masked."""
        r = len(req.prompt_ids) % self.block
        self._block_tokens[slot] = 0
        self._block_tokens[slot, :r] = req.prompt_ids[len(req.prompt_ids) - r:]
        self._block_masked[slot] = np.arange(self.block) >= r

    def _block_row(self, slot: int, req: Request) -> BlockRow:
        return BlockRow(slot=slot, tokens=self._block_tokens[slot].copy(), masked=self._block_masked[slot].copy(),
                        start=self._block_start(req), table=self.mgr.table_array(req.req_id),
                        fixed=req.total_len % self.block, remaining=req.remaining_new)

    def _settle_blocks(self, rows, out, finished: List[Request]) -> int:
        """The ``emit`` phase of a block launch: ``rows`` are (row of ``out``,
        slot, request); every pass's handed-on block streams out in order, the
        slot keeps the block it is now at, a finished request retires. Returns
        the tokens emitted."""
        n_emitted = 0
        of_row = {j: req for j, _slot, req in rows}
        # the (pass, row) pairs that handed a block on, pass by pass
        for s, j in zip(*np.nonzero(out["valid"].any(-1))):  # sync-ok: out is host numpy (the launch synced)
            req = of_row.get(j)
            if req is None or req.done:
                continue
            for tok in out["tokens"][s, j][out["valid"][s, j]]:
                self._emit(req, int(tok))  # sync-ok: out is host numpy (the launch synced)
                n_emitted += 1
                self._tenant_counts(req.tenant)["useful"] += 1
                req.useful_tokens += 1
                if req.done:
                    break
        for j, slot, req in rows:
            req.denoise_passes += int(out["denoise"][j])  # sync-ok: host numpy
            req.commit_passes += int(out["commit"][j])  # sync-ok: host numpy
            # every position before the block it is at has been fed
            req.fed_hwm = max(req.fed_hwm, req.total_len)
            if req.done:
                self._free_kv(req, cache=True)
                self.slots[slot] = None
                finished.append(req)
            else:
                self._block_tokens[slot] = out["block_tokens"][j]
                self._block_masked[slot] = out["block_masked"][j]
                self.mgr.shrink(req.req_id, req.total_len)  # pages reserved ahead and not reached
        return n_emitted

    def _decode_blocks(self, finished: List[Request]):
        """``decode_steps`` passes of every slot in one launch (diffusion over
        blocks): pages reserved for the blocks the passes can reach, as the
        speculative K + 1 reservation does."""
        steps, bk = self.decode_steps, self.block
        with TRACER.span("launch_build", cat="engine", step=self._cur_step, program="decode") as build:
            ahead = self.backend.infer.blocks_a_launch(steps)
            active = [s for s in range(len(self.slots)) if self.slots[s] is not None]
            for slot in sorted(active, key=lambda s: -self.slots[s].req_id):
                req = self.slots[slot]
                start = self._block_start(req)
                owed = -(-(req.total_len - start + req.remaining_new) // bk)  # blocks until max_tokens
                grow = start + min(ahead, owed) * bk - self.mgr.lengths[req.req_id]
                if grow > 0 and self.mgr.extend(req.req_id, grow) is None:
                    self._preempt(slot)
            if not any(r is not None for r in self.slots):
                build.discard()
                return
            B = self.max_batch_size
            tables = np.zeros((B,) + self.mgr.table_shape, np.int32)
            start, fixed, remaining = (np.zeros(B, np.int32) for _ in range(3))
            done0 = np.ones(B, bool)
            rows = []
            for i, req in enumerate(self.slots):
                if req is None:
                    continue
                tables[i] = self.mgr.table_array(req.req_id)
                start[i], fixed[i] = self._block_start(req), req.total_len % bk
                remaining[i], done0[i] = req.remaining_new, False
                rows.append((i, i, req))
        with self._launch("decode", "decode", steps=steps, active=len(rows)):
            out = self.backend.decode_blocks(self._block_tokens, self._block_masked, tables, start, fixed,
                                             done0, remaining)
        acct = self.backend.step_accounting
        with TRACER.span("emit", cat="engine", step=self._cur_step, program="decode"):
            n_emitted = self._settle_blocks(rows, out, finished)
            # every pass feeds its block again: the positions a launch hands on are its useful ones
            self.ledger.note_shape(acct["shape"])
            self.ledger.record("decode", acct["fed"], n_emitted, padding=acct["fed"] - n_emitted, geometry=acct)

    def _decode_running(self, finished: List[Request]):
        # migrating slots (staged backends) hold KV that has not landed in the
        # decode pool yet: they ride no decode row this step — a step with
        # ONLY migrating slots launches nothing and just re-polls next step
        if not any(r is not None and r.kv_stage == "decode" for r in self.slots):
            return
        # speculative decoding needs every active slot advancing in lockstep;
        # a mid-migration slot would verify against un-landed KV, so the spec
        # path waits for an all-decode-ready batch (the chunked-prefill
        # carve-out, extended to the stage handoff window)
        all_ready = all(r is None or r.kv_stage == "decode" for r in self.slots)
        mode = self._spec_mode() if (self.use_speculative and all_ready) else None
        if mode is not None:
            # propose first: when NO slot has a draft, a verify forward would
            # emit 1 token/seq for (K+1)x the compute — use the multi-step
            # decode instead and only pay for verification when drafts exist
            with TRACER.span("spec_propose", cat="engine", mode=mode,
                             step=self._cur_step,
                             proposer="draft_model" if self.draft_model is not None else "ngram"):
                if self.draft_model is not None:
                    drafts, qprobs = self._propose_drafts_draft_model(mode)
                else:
                    drafts = [np.zeros(0, np.int32) if r is None else self._propose_drafts(r)
                              for r in self.slots]
                    qprobs = [None] * len(self.slots)
            if any(len(d) for d in drafts):
                return self._decode_spec(finished, drafts, qprobs, mode)
        steps = self.decode_steps
        with TRACER.span("launch_build", cat="engine", step=self._cur_step,
                         program="decode") as build:
            # grow tables for up to `steps` tokens; preempt (recompute-requeue)
            # youngest on exhaustion. Surplus is shrunk back after the device call.
            start_len: Dict[int, int] = {}
            active = [s for s in range(len(self.slots))
                      if self.slots[s] is not None and self.slots[s].kv_stage == "decode"]
            for slot in sorted(active, key=lambda s: -self.slots[s].req_id):
                req = self.slots[slot]
                needed = min(steps, req.remaining_new)
                start_len[req.req_id] = self.mgr.lengths[req.req_id]
                if self.mgr.extend(req.req_id, max(needed, 1)) is None:
                    start_len.pop(req.req_id, None)
                    self._preempt(slot)
            # extends may have popped LRU blocks: enqueue their D2H gather before
            # the decode forward can overwrite them
            self._drain_spills()

            if not any(r is not None and r.kv_stage == "decode" for r in self.slots):
                build.discard()
                return
            B = self.max_batch_size
            tokens = np.array(self._last_token, np.int32)  # sync-ok: _last_token is a host array
            tables = np.zeros((B,) + self.mgr.table_shape, np.int32)
            ctx = np.zeros(B, np.int32)
            done0 = np.ones(B, bool)
            remaining = np.zeros(B, np.int32)
            for i, req in enumerate(self.slots):
                if req is None or req.kv_stage != "decode":
                    continue  # migrating rows stay frozen (done0) like empty slots
                self.mgr.window_span(req.req_id, req.total_len - 1, steps)
                tables[i] = self.mgr.table_array(req.req_id)
                ctx[i] = req.total_len - 1  # position of the token being fed
                done0[i] = False
                remaining[i] = req.remaining_new
            extra = ({"adapter_table": [0 if r is None else r.adapter_slot
                                        for r in self.slots]}
                     if self.adapter_registry is not None else {})
        with self._launch("decode", "decode", steps=steps,
                          active=int(sum(1 for r in self.slots if r is not None))):
            # ONE host transfer of ids + validity flags (no logits)
            toks, valid = self.backend.decode(
                tokens, tables, ctx, done0, remaining,
                [None if r is None else r.sampling for r in self.slots], **extra)
        acct = self.backend.step_accounting
        with TRACER.span("emit", cat="engine", step=self._cur_step, program="decode"):
            self._decode_settle(toks, valid, acct, start_len, finished)

    def _decode_settle(self, toks, valid, acct, start_len: Dict[int, int],
                       finished: List[Request]):
        """The ``emit`` phase of a decode launch: stream the tokens out, book
        the launch, retire or shrink."""
        n_emitted = 0
        for s in range(toks.shape[0]):
            for i, req in enumerate(self.slots):
                if req is None or req.done or not valid[s, i]:
                    continue
                self._emit(req, int(toks[s, i]))  # sync-ok: toks already host (backend.decode synced)
                self._last_token[i] = int(toks[s, i])  # sync-ok: toks already host (backend.decode synced)
                n_emitted += 1
                # per-tenant fold: each emitted decode token consumed one fed
                # position (this path bypasses _note_fed_span)
                self._tenant_counts(req.tenant)["useful"] += 1
                req.useful_tokens += 1
        # goodput: the decode jit always burns B x decode_steps positions;
        # every emitted token is one useful fed position, the rest (idle
        # slots, post-EOS sub-steps, unconsumed budget) is padding
        for req in self.slots:
            if req is not None and req.kv_stage == "decode":
                # the last emitted token was sampled, not fed: mark to total-1
                req.fed_hwm = max(req.fed_hwm, req.total_len - 1)
        self.ledger.note_shape(acct["shape"])
        self.ledger.record("decode", acct["fed"], n_emitted,
                           padding=acct["fed"] - n_emitted, geometry=acct)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            if req.done:
                self._free_kv(req, cache=True)
                self.slots[i] = None
                finished.append(req)
            elif req.req_id in start_len:
                # return speculative blocks past the tokens actually produced
                self.mgr.shrink(req.req_id, req.total_len)

    def _emit(self, req: Request, tok: int):
        try:
            if req.first_token_t is None:
                req.first_token_t = time.time()
            req.output_ids.append(tok)
            self._tenant_counts(req.tenant)["tokens_out"] += 1
            is_eos = tok in self.eos_ids
            hit_max = req.gen_offset + len(req.output_ids) >= req.sampling.max_new_tokens
            req.done = is_eos or hit_max
            if req.done:
                req.finish_t = time.time()
                req.finish_reason = "stop" if is_eos else "length"
            if req.stream_cb is not None:
                req.stream_cb(tok, req.done)
        except Exception as e:
            # per-request host failure (a poisoned stream callback, broken
            # sampling bookkeeping): attribute it so the serving supervisor
            # can quarantine THIS slot instead of rebuilding the whole engine
            if getattr(e, "req_id", None) is None:
                try:
                    e.req_id = req.req_id
                except Exception:
                    pass
            raise
