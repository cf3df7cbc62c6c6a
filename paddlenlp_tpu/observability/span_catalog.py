"""Trace span/instant name catalog — the stable vocabulary of the tracer.

Span names are string API the same way metric names and fault-point names
are: Perfetto queries, ``/debug/trace?trace=`` tooling, the README's
observability tables and the SLO runbooks all refer to spans by name, so a
rename or an undocumented addition is a silent break for every saved query.
``tools/analyze`` (the ``span-catalog`` checker) enforces both directions:
every literal name passed to ``TRACER.span/instant/add_span`` in
``paddlenlp_tpu/`` must have an entry here, and every entry must have a call
site (a dynamic-name call site declares its names with an inline
``# span-names: a b c`` comment).

Grouped by emitting tier. Keep docs to one line — they are the catalog, not
the design doc (that lives in the emitting module's docstring).

This module must stay stdlib-only (no jax, no package-relative imports): the
static-analysis suite loads it by file path without executing
``paddlenlp_tpu.__init__``.
"""

from __future__ import annotations

__all__ = ["SPAN_CATALOG"]

SPAN_CATALOG = {
    # ------------------------------------------------------------- engine (cat="engine")
    "admission": "waiting->slot binding + KV allocation for one engine step, kept only when something was admitted or rejected (also the scheduler-side admission span, cat=scheduler)",
    "prefix_cache": "prefix-cache match/COW bookkeeping + owed device block copies during admission",
    "launch_build": "host work before a backend call: capacity pass, numpy tables and inputs of the launch (program=prefill|decode|mixed|verify)",
    "prefill": "the backend call of one batched monolithic prompt prefill, one span per padded suffix-length bucket, launch geometry in its args (also the retrospective per-request prefill phase)",
    "mixed_step": "the backend call of one ragged mixed prefill-chunk + decode forward (chunked prefill), launch geometry in its args",
    "decode": "the backend call of the multi-token decode jit over all running slots, launch geometry in its args (also the retrospective per-request decode phase)",
    "dispatch": "child of a launch span: host arrays to the device and the jit call returning (program=...)",
    "wait": "child of a launch span: the np.asarray sync point, the host waiting for the device (program=...)",
    "emit": "host work after a backend call: ledger entry, settle, stream callbacks, free/shrink (program=...)",
    "step_tail": "end of an engine step: usage metering and the step-anatomy record",
    "spec_propose": "speculative-decoding draft proposal (ngram or draft model)",
    "spec_verify": "the backend call of the speculative-decoding batched verify forward, launch geometry in its args",
    "sampling": "host-side rejection-sampling acceptance for one request (spec sample mode)",
    "kv_alloc": "instant: KV blocks allocated for an admitted request (cached_tokens = prefix-cache hit)",
    "kv_free": "instant: a request's KV blocks released (finish/abort/preempt)",
    "preempt": "instant: KV exhaustion evicted the youngest sequence for recompute-requeue",
    "kv_migrate": "dispatch of one sequence's prefill->decode KV-block migration (disaggregated backend)",
    "kv_migrated": "instant: a sequence's migrated blocks landed in the decode pool; it is now decode-eligible",
    "kv_promote": "dispatch of one request's host->device KV promotion copy ahead of its prefill",
    "kv_promoted": "instant: a request's promoted blocks landed in the device pool; its deferred prefill proceeds",
    # ------------------------------------------------------------- engine loop / supervisor
    "engine_degraded": "one DEGRADED window: triage -> backoff -> rebuild -> requeue",
    "slot_quarantine": "one slot-level partial recovery: poisoned request released + failed, engine kept running",
    "loop_intake": "loop iteration head: command drain (submissions, aborts) and deadline enforcement, before the engine step",
    "loop_finish": "loop iteration tail: per-request finish (metrics, usage record, handle resolution) and the metrics plane's on_step",
    "loop_idle": "retrospective: one idle episode of the loop (no work, no commands), closed when work arrives",
    "request": "retrospective whole-request span (submission -> finish) under the request's trace id",
    "inbox": "retrospective per-request wait from submission on the HTTP thread to the loop thread enqueuing it (step= the engine step it waited out)",
    "queue": "retrospective per-request wait from enqueue to slot admission",
    # ------------------------------------------------------------- scheduler
    # ------------------------------------------------------------- router
    "route": "routing decision for one request (snapshot + policy ordering)",
    "router_request": "whole router-side request span (forward + stream relay)",
    "reroute": "instant: attempt moved to the next candidate before anything was relayed",
    "failover": "accepted-then-failed pre-token resubmission onto another replica",
    "hedge": "instant: hedged-stream lifecycle event (outcome=fired/capped/primary_won/hedge_won/failed)",
    # ------------------------------------------------------------- serving api
    "trace_adopted": "instant: replica adopted an inbound router traceparent instead of minting req-N",
    # ------------------------------------------------------------- trainer
    "train_step": "one optimizer step (forward/backward/update) on the trainer loop",
    "evaluate": "one evaluation pass over the eval dataset",
    "checkpoint": "checkpoint save (stage + manifest + commit rename)",
    "block_until_ready": "device sync inside a trainer timer stop (host waited on the device here)",
    # ------------------------------------------------------------- profiler
    "profiler_window_start": "instant: jax.profiler capture window opened",
    "profiler_window_stop": "instant: jax.profiler capture window closed",
}
