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

__all__ = ["SPAN_CATALOG", "DEVICE_SCOPES", "LAUNCH_ARGS"]

SPAN_CATALOG = {
    # ------------------------------------------------------------- engine (cat="engine")
    "admission": "waiting->slot binding + KV allocation for one engine step, kept only when something was admitted or rejected (also the scheduler-side admission span, cat=scheduler)",
    "prefix_cache": "prefix-cache match/COW bookkeeping + owed device block copies during admission",
    "launch_build": "host work before a backend call: capacity pass, numpy tables and inputs of the launch (program=prefill|decode|mixed|verify)",
    "prefill": "the backend call of one batched monolithic prompt prefill, one span per padded suffix-length bucket, launch geometry in its args (also the retrospective per-request prefill phase, admission -> first token, whose args steps / own_ms / behind_ms split it into the request's own launches, the launches it sat behind and, the span less both, host time)",
    "mixed_step": "the backend call of one ragged mixed prefill-chunk + decode forward (chunked prefill), launch geometry in its args",
    "decode": "the backend call of the multi-token decode jit over all running slots, launch geometry in its args (also the retrospective per-request decode phase)",
    "dispatch": "child of a launch span: the launch's one packed host-to-device transfer and the jit call returning (program=..., h2d_arrays, h2d_bytes)",
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

#: ``jax.named_scope`` names the serving step programs put on their operations
#: (a device profile shows them in each operation's ``tf_op``): the llama kind's
#: (``experimental/inference_model.py``), the latent kinds'
#: (``experimental/latent_model.py``, ``transformers/latent_layers.py``) and the
#: state-space kinds' (``experimental/state_model.py``, ``transformers/state_layers.py``),
#: and generation by diffusion over blocks' (``experimental/block_model.py``).
#: ``bench/harness/program_spans.py``, ``bench/harness/latent_scopes.py``,
#: ``bench/harness/state_scopes.py``, ``window_scopes.py`` and ``diffusion_scopes.py`` read them.
DEVICE_SCOPES = {
    "embed": "token embedding lookup", "attn_norm": "the layer's input RMS norm",
    "qkv": "llama kind: q/k/v projections", "rope": "llama kind: rotary embedding of q and k",
    "kv_write": "scatter of the fed tokens' rows into the pool (latent kinds: nested latent_plane / index_plane / window_plane; gqa_window: nested window_plane)",
    "paged_attn": "llama kind, the windowed kinds' full layers and gqa_block (under the block mask): the Pallas ragged paged attention kernel walking the block table",
    "paged_attn_window": "gqa_window: the same kernel walking a window (a grid sized by the window, over the window plane)",
    "attn_gather": "llama and windowed kinds: XLA gather + attend (no kernel)",
    "qk_norm": "windowed kinds and gqa_block: RMS norm of q and k over each head's dims, before any rotation",
    "o_proj": "attention output projection", "mlp_norm": "post-attention RMS norm", "mlp": "dense SwiGLU MLP",
    "final_norm": "final RMS norm", "lm_head": "output head", "sample": "on-device sampler", "bookkeeping": "counts, stops, positions",
    "mla_proj": "latent kinds: low-rank q and kv chains (norm, rescale, RoPE of the pe slices)",
    "indexer": "latent_full: indexer projections and the scores of cached positions against a query",
    "index_topk": "latent_full: the k-th largest score a query (counting passes) or top_k of a decode row",
    "latent_gather": "latent_full decode: gather of the kept positions' latent rows through the block table",
    "mla_attn": "latent_full: attention over the kept positions (absorbed for one token; for a chunk the gather of the table's rows and the latent_chunk_attention kernel)",
    "window_attn": "latent_window: gather of the window's blocks and attention over them",
    "attn_gate": "latent kinds: headwise sigmoid gate on the attention output",
    "router": "expert layer: float32 sigmoid scores, top-k of score + bias (or softmax scores and their top-k), per-expert counts",
    "experts": "expert layer: the held experts' tiles (rows ranked by expert, one tile of one expert a loop turn)",
    "shared_expert": "expert layer: the shared expert on every token",
    "ssm_proj": "scan layer: the in-projection (z | xBC | dt) and the out-projection",
    "ssm_conv": "scan layer: causal depthwise convolution over xBC from the row's cached inputs, bias, SiLU",
    "ssm_scan": "scan layer: the recurrence, chunk (SSD) form for a prompt chunk and one-step form for one token, and the D term",
    "ssm_gate_norm": "scan layer: gate by SiLU(z), then RMS norm in groups",
    "state_rw": "scan layer: read of the slots' state rows (zeros for a row at position 0) and the write back",
    "denoise": "diffusion over blocks: a pass's forward over every live slot's block (the mask id laid over masked positions; the layers' scopes nest inside), its confidences and the unmasking",
    "confidence": "diffusion over blocks: the best token and its softmax probability at every position of the block: one argmax and one logsumexp a row, the mask id's logit left out",
    "unmask": "diffusion over blocks: the unmasking rule (low_confidence_static: the block_length / denoising_steps most confident masked positions; low_confidence_dynamic: every one over the threshold besides)",
    "commit": "diffusion over blocks: a row with no masked position hands its block on (what is emitted, what max_tokens or an EOS discards) and opens the next, all masked; the pass's device counts",
}

#: args a launch span (``prefill`` / ``decode`` / ``mixed_step`` / ``spec_verify``) carries once the
#: launch has returned: the geometry (``goodput.LAUNCH_GEOMETRY``) and, from programs whose layers
#: count them, ``goodput.KIND_COUNTERS``; ``carried`` and ``prefill_waiting`` are there from its start
LAUNCH_ARGS = {
    "carried": "req_ids of the requests whose prompt tokens ride in the launch: a prefill's group, a mixed step's chunk rows, none on decode and spec_verify",
    "prefill_waiting": "admitted requests that still need prefill and are not carried: the backlog a second chunk row would have served",
    "rows_live": "rows that fed at least one real token", "rows": "padded rows of the launch",
    "kv_positions": "cached positions the live rows' attention had to cover",
    "index_candidates": "cached positions the sparse indexer scored for live queries, over full layers (device count)",
    "index_selected": "positions its selection kept for attention: the top index_topk a query, ties with the last included (device count)",
    "expert_assignments_local": "routed choices of live tokens that landed on experts held here (device count)",
    "expert_assignments": "all routed choices of live tokens: tokens x experts a token x expert layers",
    "expert_tokens_max": "the busiest held expert's tokens, summed over expert layers and decode sub-steps",
    "state_rows": "rows whose recurrent state the scan layers read and wrote: rows x decode sub-steps, dead ones too (device count)",
    "state_rows_live": "those of them that fed a token (device count)",
    "state_resets": "those that fed a sequence's position 0 and so started from zeros: admissions and re-prefills (device count)",
    "attn_key_tiles": "key tiles of cached positions the full layers' chunk-form attention kernel visited: rows x tiles, over full layers (device count)",
    "attn_kv_full": "windowed kinds: cached positions visible to the live rows, summed over the layers that attend the whole context and over decode sub-steps (device count)",
    "attn_kv_window": "the same over the layers that attend a window: at most the window a fed token (device count)",
    "attn_kv_fetched": "windowed kinds: cached positions the full layers' table walk fetched for the live rows: runs visited x positions a run with the kernel, the whole table a row through the gather; attn_kv_full / attn_kv_fetched is the share of what was read that a row could see (device count)",
    "attn_kv_visible": "diffusion over blocks: cached positions visible to the live rows (a row that feeds n positions from s: s + n, its own block whole), summed over layers and passes (device count)",
    "denoise_passes": "diffusion over blocks: rows x passes that fed a block with masked positions and unmasked some (device count)",
    "commit_passes": "diffusion over blocks: rows x passes that fed a block's final tokens once more and handed it on (device count)",
    "tokens_unmasked": "diffusion over blocks: positions the denoising passes unmasked (device count)",
    "tokens_emitted": "diffusion over blocks: tokens of committed blocks handed on to their requests (device count)",
    "tokens_discarded": "diffusion over blocks: tokens of a committed block past the request's max_tokens or behind an EOS: denoised, never emitted (device count)",
}
