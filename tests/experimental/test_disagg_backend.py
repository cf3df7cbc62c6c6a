"""Disaggregated prefill/decode backend: token identity + migration behavior.

``InferenceEngine(disagg_stages=(P, D))`` runs prompt work on a P-device
prefill stage and decode on a D-device decode stage with paged KV blocks
migrating between the stage pools. Each stage is a ShardedBackend (all-gather
layout), so the disagg engine must be BITWISE token-identical to the
single-device one — greedy, seeded sampling with penalties, and the chunked
× prefix-cache matrix. The conftest forces 8 virtual CPU devices.

Engines are module-scoped and reused aggressively (every fresh engine
compiles BOTH stages' jit sets): the identity engines run distinct prompts
per test, and the scheduling/robustness tests share one (1,1) engine whose
gating knobs are plain attributes saved/restored by the ``eng_11`` fixture —
each test drains fully, and any cross-test prefix-cache hit must leave
behavior identical anyway (the cached-block invariant under test elsewhere).
The module fixture is deliberately ASYMMETRIC (2 prefill devices, 1 decode)
so every identity test also exercises the in-flight tp-resharding migration
path."""

import numpy as np
import pytest

from paddlenlp_tpu.experimental import InferenceEngine, SamplingParams
from paddlenlp_tpu.transformers import LlamaConfig, LlamaForCausalLM


@pytest.fixture(scope="module")
def model(eight_devices):
    cfg = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=112,
                      num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=8,
                      max_position_embeddings=256, eos_token_id=None, pad_token_id=0,
                      use_scan_layers=True)
    return LlamaForCausalLM.from_config(cfg, seed=0)


KW = dict(max_batch_size=4, block_size=4, num_blocks=128, max_blocks_per_seq=32,
          decode_steps=4)


@pytest.fixture(scope="module")
def eng_ref(model):
    return InferenceEngine(model, **KW)


@pytest.fixture(scope="module")
def eng_disagg(model):
    # asymmetric on purpose: prefill-heavy 2:1 — migration reshards across
    # different tp degrees in flight on every handoff
    return InferenceEngine(model, disagg_stages=(2, 1), **KW)


@pytest.fixture(scope="module")
def eng_disagg_chunked(model):
    return InferenceEngine(model, disagg_stages=(1, 1), prefill_chunk_tokens=8, **KW)


@pytest.fixture(scope="module")
def _eng_11(model):
    return InferenceEngine(model, disagg_stages=(1, 1), **KW)


@pytest.fixture
def eng_11(_eng_11):
    """The shared scheduling/robustness engine, with gating knobs restored
    after each test (they are plain attributes — the backend is untouched)."""
    saved = (_eng_11.migration_inflight_limit, _eng_11.decode_pressure_gate,
             _eng_11.prefill_pressure_gate)
    yield _eng_11
    (_eng_11.migration_inflight_limit, _eng_11.decode_pressure_gate,
     _eng_11.prefill_pressure_gate) = saved


class TestLayout:
    def test_describe_two_stages(self, eng_disagg):
        desc = eng_disagg.stats()["backend"]
        assert desc["kind"] == "disagg" and desc["devices"] == 3
        assert desc["stages"]["prefill"]["stage"] == "prefill"
        assert desc["stages"]["decode"]["stage"] == "decode"
        assert desc["mesh"] == {"prefill_tp": 2, "decode_tp": 1}

    def test_disjoint_device_groups_and_pools(self, eng_disagg):
        b = eng_disagg.backend
        p_devs = set(b.prefill_stage.pool.kv.devices())
        d_devs = set(b.decode_stage.pool.kv.devices())
        assert p_devs and d_devs and not (p_devs & d_devs)
        # one shared block-id space: both pools are full-size
        assert b.prefill_stage.pool.kv.shape == b.decode_stage.pool.kv.shape
        # each stage's pool is laid out on its own tp axis
        assert tuple(b.prefill_stage.pool.kv.sharding.spec) == (
            None, None, None, None, "tp")

    def test_insufficient_devices_raises(self, model):
        with pytest.raises(ValueError, match="devices"):
            InferenceEngine(model, disagg_stages=(8, 8), **KW)

    def test_bad_stage_spec_raises(self, model):
        with pytest.raises(ValueError, match="stages"):
            InferenceEngine(model, disagg_stages=(0, 2), **KW)

    def test_mesh_shape_and_disagg_mutually_exclusive(self, model):
        with pytest.raises(ValueError, match="mutually exclusive"):
            InferenceEngine(model, disagg_stages=(1, 1), mesh_shape=(1, 2), **KW)

    def test_stats_disagg_section(self, eng_disagg):
        dg = eng_disagg.stats()["disagg"]
        assert set(dg) >= {"prefill_stage", "decode_stage", "migrations",
                           "migrations_inflight", "migrations_pending"}
        for stage in ("prefill_stage", "decode_stage"):
            assert set(dg[stage]) == {"kv_blocks", "kv_utilization", "queue_depth"}


class TestTokenIdentity:
    def test_greedy(self, eng_ref, eng_disagg):
        prompts = [list(range(5, 30)), [40, 41, 42], list(range(50, 67))]
        want = eng_ref.generate(prompts, SamplingParams(max_new_tokens=8))
        got = eng_disagg.generate(prompts, SamplingParams(max_new_tokens=8))
        assert got == want
        # the handoff actually happened: one migration per sequence
        assert eng_disagg.backend.migration_stats["migrations"] >= 3

    def test_seeded_sampling_with_penalties(self, eng_ref, eng_disagg):
        sp = SamplingParams(max_new_tokens=8, do_sample=True, temperature=0.9,
                            top_p=0.8, top_k=12, seed=7, repetition_penalty=1.3,
                            presence_penalty=0.1, frequency_penalty=0.1)
        prompts = [[9, 8, 7, 6, 5], list(range(20, 41)), [60, 61]]
        want = eng_ref.generate(prompts, sp)
        got = eng_disagg.generate(prompts, sp)
        assert got == want

    def test_chunked_prefill_and_prefix_cache(self, eng_ref, eng_disagg_chunked):
        # chunk rows run on the prefill stage while decode rows flow on the
        # decode stage; the second pass hits the prefix cache (shared blocks
        # + COW on the exact repeat) whose blocks live in the PREFILL pool
        prompts = [list(range(30, 55)), [70, 71, 72], list(range(10, 27))]
        want = eng_ref.generate(prompts, SamplingParams(max_new_tokens=8))
        got_cold = eng_disagg_chunked.generate(prompts, SamplingParams(max_new_tokens=8))
        assert got_cold == want
        hits0 = eng_disagg_chunked.mgr.cache_hits
        got_warm = eng_disagg_chunked.generate(prompts, SamplingParams(max_new_tokens=8))
        assert got_warm == want
        assert eng_disagg_chunked.mgr.cache_hits > hits0  # cache actually engaged

    def test_seeded_sampling_chunked(self, eng_ref, eng_disagg_chunked):
        sp = SamplingParams(max_new_tokens=6, do_sample=True, temperature=1.1,
                            top_p=0.9, seed=13)
        prompts = [list(range(33, 52)), [80, 81, 82, 83]]
        assert eng_disagg_chunked.generate(prompts, sp) == eng_ref.generate(prompts, sp)


class TestMigrationScheduling:
    def test_decode_eligibility_gated_on_landing(self, eng_11):
        """After prefill the sequence is 'migrating' (no decode row) and only
        a later step's poll flips it to 'decode'."""
        eng = eng_11
        m0 = eng.backend.migration_stats["migrations"]
        eng.add_request([75, 76, 77, 78, 79], SamplingParams(max_new_tokens=6))
        eng.step()  # admit + prefill: first token sampled on the prefill stage
        req = next(r for r in eng.slots if r is not None)
        assert len(req.output_ids) == 1
        assert req.kv_stage == "migrating"
        assert eng._migrate_pending or eng._migrating
        while eng.has_work():
            eng.step()
        assert req.kv_stage == "decode"
        assert len(req.output_ids) == 6
        assert eng.backend.migration_stats["migrations"] == m0 + 1
        assert eng.mgr.num_free == eng.mgr.total_usable_blocks

    def test_migration_inflight_limit(self, eng_11):
        eng = eng_11
        eng.migration_inflight_limit = 1
        m0 = eng.backend.migration_stats["migrations"]
        for i in range(3):
            eng.add_request([61 + i, 2, 3, 4], SamplingParams(max_new_tokens=4))
        saw_pending = False
        while eng.has_work():
            eng.step()
            assert len(eng._migrating) <= 1
            saw_pending = saw_pending or len(eng._migrate_pending) > 0
        assert saw_pending  # the bound actually deferred a handoff
        assert eng.backend.migration_stats["migrations"] == m0 + 3

    def test_decode_pressure_defers_migration(self, eng_11):
        """decode_pressure_gate=0: while ANY decode-stage sequence holds
        blocks, new handoffs defer — and resume once it finishes."""
        eng = eng_11
        eng.decode_pressure_gate = 0.0
        m0 = eng.backend.migration_stats["migrations"]
        # A long enough to keep decoding for several steps (decode_steps=4),
        # so B's deferral window is observable — a short request could land
        # its migration AND finish inside one step
        a = eng.add_request([91, 92, 93], SamplingParams(max_new_tokens=13))
        while eng.has_work() and not any(
                r is not None and r.kv_stage == "decode" for r in eng.slots):
            eng.step()
        assert any(r is not None and r.req_id == a for r in eng.slots)
        b = eng.add_request([86, 87, 88, 89], SamplingParams(max_new_tokens=3))
        deferred = False
        while eng.has_work():
            eng.step()
            b_req = next((r for r in eng.slots
                          if r is not None and r.req_id == b), None)
            if (b_req is not None and b_req.kv_stage == "migrating"
                    and any(r is not None and r.req_id == a for r in eng.slots)):
                deferred = True  # B held back while A still decodes
        assert deferred
        assert eng.backend.migration_stats["migrations"] == m0 + 2
        assert eng.mgr.num_free == eng.mgr.total_usable_blocks

    def test_lone_request_admits_despite_gate(self, eng_11):
        """An IDLE prefill stage always admits: a single request whose
        reservation exceeds the gate fraction must run, not head-of-line
        block the queue forever (the gate throttles contention, it is not an
        absolute cap)."""
        eng = eng_11
        eng.prefill_pressure_gate = 0.01  # ~1 block: any prompt exceeds it
        out = eng.generate([list(range(11, 31))], SamplingParams(max_new_tokens=3))
        assert len(out[0]) == 3

    def test_prefill_pressure_gates_admission(self, eng_11):
        """Stage-aware admission: with a tight prefill gate only part of the
        queue admits per wave; everything still completes."""
        eng = eng_11
        eng.prefill_pressure_gate = 0.04  # ~5 of 127 blocks
        ids = [eng.add_request([55 + i, 6, 7, 8, 9, 10, 11, 12],
                               SamplingParams(max_new_tokens=3))
               for i in range(3)]
        eng.step()
        admitted = sum(1 for r in eng.slots if r is not None)
        assert admitted < 3  # the gate held some of the queue back
        out = {}
        while eng.has_work():
            for req in eng.step():
                out[req.req_id] = req
        assert sorted(out) == sorted(ids)
        assert all(len(out[i].output_ids) == 3 for i in ids)


class TestRobustness:
    def test_abort_mid_migration_leak_free(self, eng_11):
        eng = eng_11
        rid = eng.add_request([15, 16, 17, 18, 19], SamplingParams(max_new_tokens=8))
        eng.step()  # prefill done, request now migrating-pending
        req = next(r for r in eng.slots if r is not None)
        assert req.kv_stage == "migrating"
        assert eng.abort(rid) is not None
        assert not eng._migrating and not eng._migrate_pending
        assert eng.mgr.num_free == eng.mgr.total_usable_blocks

    def test_release_request_drops_migration(self, eng_11):
        eng = eng_11
        rid = eng.add_request([25, 26, 27, 28], SamplingParams(max_new_tokens=8))
        eng.step()
        assert eng.release_request(rid) is True
        assert not eng._migrating and not eng._migrate_pending
        assert eng.mgr.num_free == eng.mgr.total_usable_blocks

    def test_preempt_and_abort_leak_free(self, model):
        """KV-pressure preemption with the stage handoff in the loop releases
        every block (a preempted mid-migration request re-prefills and
        re-migrates on re-admission). Small pool: needs its own engine."""
        eng = InferenceEngine(model, disagg_stages=(1, 1), max_batch_size=2,
                              block_size=4, num_blocks=12, max_blocks_per_seq=16,
                              decode_steps=4, enable_prefix_cache=False)
        ids = [eng.add_request(list(range(5, 13)), SamplingParams(max_new_tokens=16))
               for _ in range(3)]
        # enough steps to ride past the 2-step migration latency so two
        # sequences actually decode concurrently and exhaust the pool
        for _ in range(5):
            eng.step()
        eng.abort(ids[1])
        while eng.has_work():
            eng.step()
        assert eng.mgr.num_free == eng.mgr.total_usable_blocks
        assert eng.num_preemptions >= 1  # pressure actually hit

    def test_single_device_engine_has_no_staging(self, eng_ref):
        assert eng_ref.staged is False
        assert "disagg" not in eng_ref.stats()
        out = eng_ref.generate([[77, 78]], SamplingParams(max_new_tokens=3))
        assert len(out[0]) == 3

    def test_reset_clears_migration_state(self, eng_11):
        # LAST on the shared engine on purpose: reset drops scheduler state
        eng = eng_11
        eng.add_request([35, 36, 37], SamplingParams(max_new_tokens=4))
        eng.step()
        eng.reset()
        assert not eng._migrating and not eng._migrate_pending
        out = eng.generate([[44, 45, 46]], SamplingParams(max_new_tokens=4))
        assert len(out[0]) == 4


class TestSyncParams:
    def test_two_stage_resync_keeps_layouts_and_counts(self, model, eng_disagg):
        """``DisaggBackend.sync_params`` (the weight-swap install seam):

        - both stage placements keep their EXISTING mesh/NamedSharding layout
          (no resharding, device groups stay disjoint);
        - both bindings move together — after the resync every launch runs on
          the new tree, and a penalty-sampling generation (whose logits READ
          the device-side counts through ``resync_counts``-seeded state) is
          token-exact against a fresh single-device engine built on the new
          weights, across the prefill->migrate->decode handoff."""
        import jax

        b = eng_disagg.backend
        old_params = model.params
        before = {}
        for name, stage in (("prefill", b.prefill_stage), ("decode", b.decode_stage)):
            leaves = jax.tree_util.tree_leaves(stage.params)
            before[name] = [leaf.sharding for leaf in leaves]

        new_model = type(model).from_config(model.config, seed=1)
        b.sync_params(new_model.params)
        try:
            for name, stage in (("prefill", b.prefill_stage),
                                ("decode", b.decode_stage)):
                leaves = jax.tree_util.tree_leaves(stage.params)
                assert len(leaves) == len(before[name])
                for leaf, old_sharding in zip(leaves, before[name]):
                    assert leaf.sharding == old_sharding, \
                        f"{name} stage resharded during sync_params"
            p_devs = set(b.prefill_stage.params and jax.tree_util.tree_leaves(
                b.prefill_stage.params)[0].devices())
            d_devs = set(jax.tree_util.tree_leaves(
                b.decode_stage.params)[0].devices())
            assert p_devs and d_devs and not (p_devs & d_devs)
            # the engine-level resync_counts contract survives the swap: a
            # no-op here (no live slots), then penalty decoding must match a
            # fresh engine on the new weights bit-for-bit
            eng_disagg.resync_counts()
            sp = SamplingParams(max_new_tokens=8, frequency_penalty=0.6)
            prompts = [[81, 82, 83, 84, 85]]
            ref = InferenceEngine(new_model, **KW)
            assert eng_disagg.generate(prompts, sp) == ref.generate(prompts, sp)
        finally:
            # the module model/engines are shared: restore the old binding
            b.sync_params(old_params)
        out = eng_disagg.generate([[86, 87, 88]], SamplingParams(max_new_tokens=4))
        assert len(out[0]) == 4
