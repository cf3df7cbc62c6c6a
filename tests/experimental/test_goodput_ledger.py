"""Goodput-ledger conservation parity across every engine step path.

The invariant under test: ``fed == useful + padding + spec_rejected + rework``
holds EXACTLY on monolithic, chunked, sharded and disaggregated steps — and
``useful`` is identical across all of them for
the same greedy workload (token identity implies work identity; only the
padding/rework decomposition may differ per layout). Plus the rework
accounting: preemption recompute, supervisor-requeue hints, prefix-cache COW
tails and disagg migration re-seeds all land in their named buckets.

Engines are module-scoped and reused (compiles are the cost); tests use
distinct prompt streams so runs stay independent."""

import numpy as np
import pytest

from paddlenlp_tpu.experimental import InferenceEngine, SamplingParams
from paddlenlp_tpu.transformers import LlamaConfig, LlamaForCausalLM


@pytest.fixture(scope="module")
def model(eight_devices):
    cfg = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=112,
                      num_hidden_layers=2, num_attention_heads=8,
                      num_key_value_heads=8, max_position_embeddings=256,
                      eos_token_id=None, pad_token_id=0, use_scan_layers=True)
    return LlamaForCausalLM.from_config(cfg, seed=0)


KW = dict(max_batch_size=4, block_size=4, num_blocks=128, max_blocks_per_seq=32,
          decode_steps=4)


@pytest.fixture(scope="module")
def engines(model):
    return {
        "mono": InferenceEngine(model, **KW),
        "chunked": InferenceEngine(model, prefill_chunk_tokens=4, **KW),
        "sharded": InferenceEngine(model, mesh_shape=(1, 2), **KW),
        "disagg": InferenceEngine(model, disagg_stages=(1, 1),
                                  prefill_chunk_tokens=4, **KW),
    }


def run(eng, prompts, max_new=6):
    led0 = dict(eng.ledger.totals)
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=max_new))
    delta = {k: eng.ledger.totals[k] - led0[k] for k in led0}
    assert eng.ledger.verify_conservation()
    assert delta["fed"] == delta["useful"] + delta["padding"] \
        + delta["spec_rejected"] + delta["rework"]
    return outs, delta


class TestConservationParity:
    def test_useful_identical_across_all_step_paths(self, engines):
        # distinct leading block per engine family is NOT needed here: each
        # engine owns its BlockManager, so caches never cross engines
        prompts = [[11, 12, 13, 14, 15], [21, 22, 23], [31, 32, 33, 34, 35, 36, 37]]
        results = {name: run(eng, [list(p) for p in prompts])
                   for name, eng in engines.items()}
        outs0, delta0 = results["mono"]
        # greedy token identity across every backend/layout
        for name, (outs, _d) in results.items():
            assert outs == outs0, name
        # useful = prompt tokens + (emitted - 1) per request, exactly
        expect_useful = sum(len(p) for p in prompts) \
            + sum(len(o) - 1 for o in outs0)
        for name, (_outs, d) in results.items():
            if name == "disagg":
                # the migration re-seed re-processes prompt + first token per
                # sequence: pure rework on top of the same useful work
                assert d["useful"] == expect_useful, name
                assert d["rework"] == sum(len(p) + 1 for p in prompts)
            else:
                assert d["useful"] == expect_useful, name
                assert d["rework"] == 0, name
            assert d["spec_rejected"] == 0, name
            assert d["fed"] >= d["useful"], name

    def test_disagg_rework_is_migration_reseed(self, engines):
        eng = engines["disagg"]
        before = dict(eng.ledger.rework_by)
        run(eng, [[41, 42, 43, 44]])
        assert eng.ledger.rework_by["migration_reseed"] - before.get(
            "migration_reseed", 0) == 5  # 4 prompt + 1 emitted at handoff
        assert eng.ledger.rework_by.get("preempt_refill", 0) == before.get(
            "preempt_refill", 0)

    def test_shape_buckets_and_stats_surface(self, engines):
        eng = engines["mono"]
        run(eng, [[51, 52, 53]])
        snap = eng.stats()["goodput"]
        assert snap["shape_buckets"] >= 1
        assert snap["totals"] == dict(eng.ledger.totals)
        eff = eng.efficiency()
        assert eff["goodput_ratio"] == pytest.approx(eng.ledger.ratio())
        assert eff["mfu"] is None  # CPU: NaN -> null, never a fake number
        assert "step_anatomy" in eff and eff["step_anatomy"]["window_steps"] >= 1


class TestReworkAccounting:
    def test_preemption_books_preempt_refill(self, model):
        # tiny pool: decode growth forces preemption; the recompute re-prefill
        # of already-fed positions must land in rework, token-identically
        # (identity is asserted on the STREAMED tokens — a preempted request's
        # engine-side output_ids restart at the fold, the stream does not)
        def streamed_run(eng, prompts, max_new=8):
            streams = {}
            for p in prompts:
                toks = []
                rid = eng.add_request(list(p), SamplingParams(max_new_tokens=max_new),
                                      stream_cb=lambda t, d, _l=toks: _l.append(t))
                streams[rid] = toks
            while eng.has_work():
                eng.step()
            return [streams[r] for r in sorted(streams)]

        ref = InferenceEngine(model, **KW)
        tiny = InferenceEngine(model, max_batch_size=4, block_size=4,
                               num_blocks=8, max_blocks_per_seq=32,
                               decode_steps=4)
        prompts = [[61, 62, 63, 64], [71, 72, 73, 74], [81, 82, 83, 84]]
        outs_ref = streamed_run(ref, prompts)
        led0 = dict(tiny.ledger.totals)
        outs = streamed_run(tiny, prompts)
        delta = {k: tiny.ledger.totals[k] - led0[k] for k in led0}
        assert tiny.num_preemptions > 0
        # recompute identity: pre-preemption stream + resampled continuation
        # must equal the unconstrained run token for token
        assert outs == outs_ref
        assert tiny.ledger.verify_conservation()
        assert delta["rework"] > 0
        assert tiny.ledger.rework_by["preempt_refill"] == delta["rework"]
        # useful counts true work ONCE: the recompute's re-prefill of
        # already-fed positions is all rework, so useful equals the
        # no-preemption run's exactly (prompts + emits - 1 per request)
        base_useful = sum(len(p) for p in prompts) + sum(len(o) - 1 for o in outs)
        assert delta["useful"] == base_useful

    def test_requeue_hint_books_requeue_refill(self, model):
        eng = InferenceEngine(model, **KW)
        rid = eng.add_request([91, 92, 93, 94, 95],
                              SamplingParams(max_new_tokens=3), rework_hwm=4)
        while eng.has_work():
            eng.step()
        assert eng.ledger.rework_by["requeue_refill"] == 4
        assert eng.ledger.verify_conservation()
        assert rid >= 0

    def test_full_cover_cow_books_cow_token(self, model):
        eng = InferenceEngine(model, **KW)
        prompt = [1, 2, 3, 4, 5, 6, 7, 8]  # exactly 2 full blocks at bs=4
        run(eng, [list(prompt)])  # registers the prompt's full blocks
        before = eng.ledger.rework_by.get("cow_token", 0)
        _outs, delta = run(eng, [list(prompt)])  # full-cover hit -> COW tail
        assert eng.ledger.rework_by.get("cow_token", 0) - before == 1
        assert delta["rework"] == 1
        assert delta["useful"] == 0 + (len(_outs[0]) - 1)  # suffix was all COW


class TestSpeculative:
    def test_spec_rejected_matches_engine_stats(self, model):
        eng = InferenceEngine(model, use_speculative=True, spec_draft_len=3, **KW)
        # constant prompt: the model repeats, the n-gram proposer drafts,
        # greedy verify accepts some and rejects the rest — the ledger's
        # spec_rejected bucket must equal the engine's drafted - accepted
        prompt = [30] * 12
        _outs, delta = run(eng, [prompt], max_new=24)
        st = eng.spec_stats
        assert st["drafted"] > 0
        assert delta["spec_rejected"] == st["drafted"] - st["accepted"]
        assert eng.ledger.verify_conservation()


class TestChaosConservation:
    def test_conservation_across_engine_step_fault_and_reset(self, model):
        # a mid-run step fault + in-place reset must leave the ledger's
        # monotone totals conserved (reset keeps them, like chunk_stats)
        from paddlenlp_tpu.utils.faults import FAULTS

        eng = InferenceEngine(model, **KW)
        eng.add_request([15, 16, 17], SamplingParams(max_new_tokens=6))
        eng.step()  # prefill lands
        FAULTS.arm("engine.step", nth=1)
        try:
            with pytest.raises(Exception):
                while eng.has_work():
                    eng.step()
        finally:
            FAULTS.disarm("engine.step")
        totals_mid = dict(eng.ledger.totals)
        assert eng.ledger.verify_conservation()
        eng.reset()
        assert eng.ledger.totals == totals_mid  # reset never rewinds totals
        # the anatomy anchors must reset too, or the first post-recovery step
        # books the whole outage (triage + reset) as a "step gap"
        assert eng._last_step_end is None and eng._prev_step_busy is False
        _outs, delta = run(eng, [[25, 26, 27]])
        assert delta["useful"] > 0
        assert eng.ledger.verify_conservation()
