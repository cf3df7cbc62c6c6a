"""Chunked prefill: mixed ragged prefill/decode steps must be token-identical
to monolithic prefill (greedy AND seeded sampling, with and without the prefix
cache), bound per-step prefill work, keep decode flowing while a long prompt
fills, and fold preempted half-prefilled requests correctly on re-admission.

The monolithic and chunked engines are module-scoped and REUSED across parity
tests (each fresh engine pays several jit compiles); every test uses distinct
prompts so runs stay independent — and any cross-test prefix-cache hit must
leave outputs identical anyway, which is exactly the property under test."""

import contextlib

import numpy as np
import pytest

from paddlenlp_tpu.experimental import InferenceEngine, SamplingParams
from paddlenlp_tpu.transformers import LlamaConfig, LlamaForCausalLM


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=112, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=256,
                      eos_token_id=None, pad_token_id=0, use_scan_layers=True)
    return LlamaForCausalLM.from_config(cfg, seed=0)


KW = dict(max_batch_size=4, block_size=4, num_blocks=128, max_blocks_per_seq=32)
PROMPTS = [list(range(5, 30)), [40, 41, 42], list(range(50, 67))]


@pytest.fixture(scope="module")
def eng_mono(model):
    return InferenceEngine(model, **KW)


@pytest.fixture(scope="module")
def eng_chunk(model):
    return InferenceEngine(model, prefill_chunk_tokens=8, **KW)


@pytest.fixture(scope="module")
def eng_chunk_kernel(model):
    """eng_chunk's twin through the Pallas ragged kernel (interpret mode on the
    CPU). The flag is read at trace time, so it is set before the first step."""
    eng = InferenceEngine(model, prefill_chunk_tokens=8, **KW)
    eng.infer.use_paged_kernel = True
    return eng


@contextlib.contextmanager
def spy_mixed_launches(eng):
    """Records (chunk rows, decode rows, stamped launch shape) of every mixed
    launch of ``eng`` while the block runs; the backend's method is restored."""
    seen, orig = [], eng.backend._mixed_flat_launch

    def spy(chunk_rows, decode_rows):
        out = orig(chunk_rows, decode_rows)
        seen.append((len(chunk_rows), len(decode_rows), eng.backend.step_accounting["shape"]))
        return out

    eng.backend._mixed_flat_launch = spy
    try:
        yield seen
    finally:
        eng.backend._mixed_flat_launch = orig


@pytest.fixture(scope="module")
def eng_chunk4(model):
    """Shared chunk-budget-4 engine for the interleave/bounding tests; each
    test uses prompts with unique leading blocks so cross-test prefix-cache
    hits can't change the chunk walk under test."""
    return InferenceEngine(model, prefill_chunk_tokens=4, **KW)


class TestChunkedParity:
    def test_greedy_token_identical(self, eng_mono, eng_chunk):
        want = eng_mono.generate(PROMPTS, SamplingParams(max_new_tokens=8))
        c0 = dict(eng_chunk.chunk_stats)
        got = eng_chunk.generate(PROMPTS, SamplingParams(max_new_tokens=8))
        assert got == want
        # 25+3+17 prompt tokens in chunks of <=8 across several mixed steps
        assert eng_chunk.chunk_stats["chunk_tokens"] - c0["chunk_tokens"] \
            == sum(len(p) for p in PROMPTS)
        assert eng_chunk.chunk_stats["chunks"] - c0["chunks"] >= 7

    def test_seeded_sampling_token_identical(self, eng_mono, eng_chunk):
        prompts = [list(range(60, 85)), [33, 34, 35]]
        sp = SamplingParams(max_new_tokens=8, do_sample=True, temperature=0.8,
                            top_p=0.9, seed=11)
        assert eng_chunk.generate(prompts, sp) == eng_mono.generate(prompts, sp)

    def test_penalties_accumulate_across_chunks(self, eng_mono, eng_chunk):
        """Penalty counts must cover every earlier chunk of the prompt, not
        just the chunk that samples."""
        prompts = [list(range(20, 45)), [70, 71, 72, 73]]
        sp = SamplingParams(max_new_tokens=8, repetition_penalty=1.3,
                            presence_penalty=0.2, frequency_penalty=0.1)
        assert eng_chunk.generate(prompts, sp) == eng_mono.generate(prompts, sp)

    def test_prompt_smaller_than_chunk(self, eng_mono, model):
        eng = InferenceEngine(model, prefill_chunk_tokens=64, **KW)
        want = eng_mono.generate([[7, 8, 9]], SamplingParams(max_new_tokens=6))
        assert eng.generate([[7, 8, 9]], SamplingParams(max_new_tokens=6)) == want
        assert eng.chunk_stats["chunks"] == 1  # one (short) chunk, sampler fired

    def test_chunk_boundary_on_block_boundary(self, eng_mono, eng_chunk):
        """A chunk boundary landing exactly on a KV block boundary (chunk=8,
        block_size=4, prompt lengths 16 and 17) must not corrupt the walk."""
        prompts = [list(range(5, 21)), list(range(30, 47))]
        want = eng_mono.generate(prompts, SamplingParams(max_new_tokens=6))
        assert eng_chunk.generate(prompts, SamplingParams(max_new_tokens=6)) == want

    def test_chunked_with_ragged_kernel(self, eng_mono, eng_chunk_kernel):
        """Whole-engine chunked decode through the Pallas ragged kernel
        (interpret) must equal the XLA gather path."""
        want = eng_mono.generate(PROMPTS, SamplingParams(max_new_tokens=6))
        assert eng_chunk_kernel.generate(PROMPTS, SamplingParams(max_new_tokens=6)) == want

    def test_prefix_cache_fed_suffix_chunked(self, model, eng_mono):
        """Warm admissions start chunking at the cached length; outputs match
        monolithic with the cache AND chunked without it. The chunked arms use
        fresh engines (the test asserts exact hit counts, so their caches must
        start empty); the monolithic arm rides the shared engine — a warm
        cache must not change its outputs, which is the invariant itself."""
        shared = list(range(5, 21))  # 16 tokens = 4 full blocks
        first = [shared + [50]]
        warm = [shared + [60, 61, 62]]
        eng_mono.generate(first, SamplingParams(max_new_tokens=4))
        results = {"mono_cache": eng_mono.generate(warm, SamplingParams(max_new_tokens=6))}
        for key, cache in (("chunk_cache", True), ("chunk_nocache", False)):
            eng = InferenceEngine(model, prefill_chunk_tokens=8,
                                  enable_prefix_cache=cache, **KW)
            eng.generate(first, SamplingParams(max_new_tokens=4))
            results[key] = eng.generate(warm, SamplingParams(max_new_tokens=6))
            if key == "chunk_cache":
                assert eng.mgr.cache_hits == 1  # the warm admission
                # the cached span never re-fed: only the suffix was chunked
                assert eng.chunk_stats["chunk_tokens"] < len(first[0]) + len(warm[0])
        assert results["chunk_cache"] == results["mono_cache"]
        assert results["chunk_nocache"] == results["mono_cache"]

    def test_cached_admission_groups_seed_each_rows_own_counts(self, model):
        """Groups of one, two and three prompts whose prefix is cached, so their penalty counts come from the host
        through ``seed_counts``' one padded scatter: each row's counts are its own prompt's, so a repetition penalty
        samples what the cache-off engine samples."""
        shared = list(range(5, 21))  # 4 full blocks
        warm = [[shared + [70 + i, 71 + i] for i in range(n)] for n in (1, 2, 3)]
        penal = SamplingParams(max_new_tokens=5, repetition_penalty=1.3)
        plain = InferenceEngine(model, prefill_chunk_tokens=8, enable_prefix_cache=False, **KW)
        eng = InferenceEngine(model, prefill_chunk_tokens=8, enable_prefix_cache=True, **KW)
        eng.generate([shared + [50]], penal)
        assert [eng.generate(g, penal) for g in warm] == [plain.generate(g, penal) for g in warm]
        assert eng.mgr.cache_hits == 6

    def test_per_step_prefill_bounded(self, eng_chunk4):
        """No engine step feeds more prompt tokens than the chunk budget."""
        eng = eng_chunk4
        eng.add_request(list(range(5, 35)), SamplingParams(max_new_tokens=2))
        fed_per_step = []
        while eng.has_work():
            before = eng.chunk_stats["chunk_tokens"]
            eng.step()
            fed_per_step.append(eng.chunk_stats["chunk_tokens"] - before)
        assert max(fed_per_step) <= 4
        assert sum(fed_per_step) == 30


class TestChunkedInterleave:
    def test_decode_flows_during_long_prefill(self, eng_mono, eng_chunk4):
        """The serving property itself: a running request keeps emitting
        tokens on the very steps a long prompt is chunk-prefilling."""
        want = eng_mono.generate([[5, 6, 7, 8]], SamplingParams(max_new_tokens=12))[0]

        eng = eng_chunk4
        stalls0 = len(eng.recent_decode_stalls)
        short = eng.add_request([5, 6, 7, 8], SamplingParams(max_new_tokens=12))
        done = list(eng.step())  # prefill chunk(s) + first token
        chunks0 = eng.chunk_stats["chunks"]  # long-prompt chunking not started
        eng.add_request(list(range(10, 40)), SamplingParams(max_new_tokens=4))
        interleaved = 0
        while eng.has_work():
            running = next((r for r in eng.slots
                            if r is not None and r.req_id == short), None)
            n_before = len(running.output_ids) if running is not None else None
            done += eng.step()
            if n_before is not None and len(running.output_ids) > n_before \
                    and eng.chunk_stats["chunks"] > chunks0:
                interleaved += 1
        res = {r.req_id: r.output_ids for r in done}
        assert res[short] == list(want)
        assert interleaved > 0  # decode advanced while the long prompt filled
        assert len(eng.recent_decode_stalls) > stalls0  # stall events recorded

    @pytest.mark.parametrize("kw,prompts,max_new", [
        (dict(prefill_chunk_tokens=4, max_batch_size=2, num_blocks=11), [[5, 6, 7], list(range(10, 34))], 10),
        (dict(prefill_chunk_tokens=8, max_batch_size=4, num_blocks=18), [list(range(5, 25)), list(range(30, 50))], 20),
    ], ids=["youngest_mid_prefill", "two_side_by_side"])
    def test_preempt_half_prefilled_folds_state(self, model, eng_mono, kw, prompts, max_new):
        """Pool pressure evicts the youngest slot mid-flight (a 24-token prompt
        mid-prefill beside a short one; two 20-token prompts chunking side by
        side, 10 blocks each where 17 are usable); after requeue + re-admission
        the streams are token-exact and no KV block leaks. The reference run
        rides the shared monolithic engine — the requests fit its batch at
        once, so the outputs are batch-capacity-independent."""
        want = eng_mono.generate(prompts, SamplingParams(max_new_tokens=max_new))
        eng = InferenceEngine(model, block_size=4, max_blocks_per_seq=32, **kw)
        streams = [[] for _ in prompts]
        for prompt, stream in zip(prompts, streams):
            eng.add_request(prompt, SamplingParams(max_new_tokens=max_new),
                            stream_cb=lambda t, d, stream=stream: stream.append(t))
        while eng.has_work():
            eng.step()
        assert eng.num_preemptions > 0
        assert streams == [list(w) for w in want]
        assert eng.mgr.num_free == eng.mgr.total_usable_blocks  # no leak

    def test_oldest_prefill_gets_budget_first(self, eng_chunk4):
        """A newly-admitted prompt landing in a lower slot index must not
        starve an older mid-prefill request: the chunk budget is handed out
        oldest-request-first, not in slot order."""
        eng = eng_chunk4
        eng.add_request([65, 66, 67], SamplingParams(max_new_tokens=2))  # slot 0
        eng.step()  # chunk + first token
        a = eng.add_request(list(range(36, 66)), SamplingParams(max_new_tokens=2))
        eng.step()  # A -> slot 1, first chunk; the short request finishes
        assert eng.slots[0] is None  # a free slot BELOW mid-prefill A
        b = eng.add_request(list(range(48, 78)), SamplingParams(max_new_tokens=2))
        eng.step()  # B admitted into slot 0, younger than A
        req_a = next(r for r in eng.slots if r is not None and r.req_id == a)
        req_b = next(r for r in eng.slots if r is not None and r.req_id == b)
        assert eng.slots.index(req_b) < eng.slots.index(req_a)
        assert req_a.prefilled_len == 8  # A drank the whole budget...
        assert req_b.prefilled_len == 0  # ...B waited its turn
        while eng.has_work():
            eng.step()

    def test_abort_mid_prefill_frees_blocks(self, eng_chunk4):
        eng = eng_chunk4
        rid = eng.add_request(list(range(2, 32)), SamplingParams(max_new_tokens=4))
        eng.step()  # admitted, one chunk in
        req = next(r for r in eng.slots if r is not None)
        assert req.needs_prefill and req.prefilled_len > 0
        out = eng.abort(rid)
        assert out is not None and out.finish_reason == "abort"
        assert eng.mgr.num_free == eng.mgr.total_usable_blocks
        assert not eng.has_work()


class TestChunkedMetrics:
    def test_serving_metrics_chunk_series(self, model):
        """ServingMetrics consumes the engine's chunk totals + event rings:
        chunks counter, chunk-size histogram, decode-stall histogram."""
        from paddlenlp_tpu.serving.engine_loop import ServingMetrics
        from paddlenlp_tpu.serving.metrics import MetricsRegistry

        registry = MetricsRegistry()
        eng = InferenceEngine(model, prefill_chunk_tokens=4, **KW)
        metrics = ServingMetrics(eng, registry=registry)
        eng.add_request([5, 6, 7, 8], SamplingParams(max_new_tokens=10))
        eng.step()
        eng.add_request(list(range(10, 30)), SamplingParams(max_new_tokens=2))
        while eng.has_work():
            pre = eng.num_preemptions
            eng.step()
            metrics.on_step(eng.stats(), eng.num_preemptions - pre)
        # deltas off monotone totals: the pre-on_step first step is swept up
        # by the next on_step, so the counter converges on the engine total
        chunks = metrics.prefill_chunks.value()
        assert chunks == eng.chunk_stats["chunks"]
        assert metrics.prefill_chunk_tokens.count() == chunks
        assert metrics.prefill_chunk_tokens.sum() == eng.chunk_stats["chunk_tokens"]
        assert metrics.decode_stall.count() == len(
            [1 for s, _ in eng.recent_decode_stalls])
        # re-running on_step with unchanged stats must not double-observe
        before = metrics.prefill_chunk_tokens.count()
        metrics.on_step(eng.stats(), 0)
        assert metrics.prefill_chunk_tokens.count() == before

        # rebind (the supervisor's rebuild path) must rebaseline, not replay
        registry2 = MetricsRegistry()
        metrics2 = ServingMetrics(eng, registry=registry2)
        metrics2.rebind(eng)
        metrics2.on_step(eng.stats(), 0)
        assert metrics2.prefill_chunks.value() == 0
        assert metrics2.prefill_chunk_tokens.count() == 0


class TestMixedLaunch:
    """What the mixed step launches: two packed segments, chunk rows [C, T]
    and decode rows [D, 1], each bucketed on its own, with the ragged kernel
    as without it."""

    @pytest.mark.parametrize("sp,prompts", [
        (SamplingParams(max_new_tokens=7),
         [list(range(8, 31)), [88, 89], list(range(61, 74))]),
        (SamplingParams(max_new_tokens=7, do_sample=True, temperature=0.8, top_p=0.9, seed=3,
                        repetition_penalty=1.2, presence_penalty=0.1, frequency_penalty=0.05),
         [list(range(9, 32)), [90, 91], list(range(62, 75))]),
    ], ids=["greedy", "sampled_with_penalties"])
    def test_mixed_step_with_the_kernel_equals_without(self, eng_chunk, eng_chunk_kernel, sp, prompts):
        """The mixed step through the Pallas ragged kernel (interpret) against
        the XLA gather path, row for row: chunk rows whose counts accumulate
        across chunks beside decode rows, seeded sampling under penalties."""
        assert eng_chunk_kernel.generate(prompts, sp) == eng_chunk.generate(prompts, sp)

    def test_launch_shape_is_the_same_with_the_kernel(self, eng_chunk, eng_chunk_kernel):
        """The kernel does not pick the layout: with it on as with it off a
        mixed step is stamped ``("mixed_flat", C, T, D)``, one chunk row of 8
        beside the decode rows' own bucket."""
        shapes = {}
        for name, eng in (("xla", eng_chunk), ("kernel", eng_chunk_kernel)):
            with spy_mixed_launches(eng) as seen:
                for i in range(3):
                    eng.add_request([80 + i, 3, 4], SamplingParams(max_new_tokens=16))
                for _ in range(2):
                    eng.step()
                eng.add_request(list(range(72, 92)), SamplingParams(max_new_tokens=2))
                while eng.has_work():
                    eng.step()
            shapes[name] = [shape for _c, _d, shape in seen]
        assert shapes["kernel"] == shapes["xla"]
        assert ("mixed_flat", 1, 8, 4) in shapes["xla"]  # a full chunk beside 3 decode rows
        assert all(s[0] == "mixed_flat" and len(s) == 4 for s in shapes["xla"])

    def test_flat_feeds_fewer_padded_rows(self, eng_chunk):
        """With one long prompt chunking while three short requests decode,
        the step's chunk segment holds 1 row, not max_batch_size — assert via
        the backend's segment shapes. Rides the shared chunk engine; the long
        prompt's leading block is unique to this test so no cache hit shortens
        the chunk walk."""
        eng = eng_chunk
        with spy_mixed_launches(eng) as seen:
            for p in ([40 + i] for i in range(3)):
                eng.add_request(list(p) + [7, 8], SamplingParams(max_new_tokens=24))
            for _ in range(3):
                eng.step()  # the shorties admit + start decoding
            eng.add_request(list(range(41, 73)), SamplingParams(max_new_tokens=4))
            while eng.has_work():
                eng.step()
        mixed = [(c, d) for c, d, _shape in seen if c and d]
        assert mixed, "no step carried chunks and decodes together"
        # every mixed step fed exactly the live rows: 1 chunk row + <=3 decodes
        assert all(c == 1 and 1 <= d <= 3 for c, d in mixed), mixed
