"""Sharded serving backend: token-identity + layout on a host-device CPU mesh.

``InferenceEngine(mesh_shape=...)`` lays weights and the paged KV pool out
with NamedSharding over the parallel/mesh ``tp`` axis and compiles every step
with explicit in/out shardings. The all-gather column-parallel layout makes
every floating-point reduction read replicated operands, so the sharded
engine must be BITWISE token-identical to the single-device one — greedy,
seeded sampling with penalties, with the prefix cache and chunked prefill on.
The conftest forces 8 virtual CPU devices, so the 8-way mesh runs in tier-1.

Engines are module-scoped and reused (each fresh engine pays several jit
compiles x 8 devices); tests use distinct prompts so runs stay independent —
and any cross-test prefix-cache hit must leave outputs identical anyway,
which is the property under test."""

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from paddlenlp_tpu.experimental import InferenceEngine, SamplingParams
from paddlenlp_tpu.transformers import LlamaConfig, LlamaForCausalLM


@pytest.fixture(scope="module")
def model(eight_devices):
    # 8 heads / 8 kv heads (head_dim 8): the tp=8 axis divides both, so the
    # KV pool and attention actually shard instead of falling back replicated
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
def eng_tp8(model):
    return InferenceEngine(model, mesh_shape=(1, 8), **KW)


@pytest.fixture(scope="module")
def eng_tp8_chunked(model):
    return InferenceEngine(model, mesh_shape=(1, 8), prefill_chunk_tokens=8, **KW)


class TestLayout:
    def test_kv_pool_sharded_on_tp(self, eng_tp8):
        spec = eng_tp8.pool.kv.sharding.spec
        assert tuple(spec) == (None, None, None, None, "tp")
        assert len(eng_tp8.pool.kv.devices()) == 8

    def test_params_sharded(self, eng_tp8):
        layers = eng_tp8.backend.params["model"]["layers"]
        q_spec = layers["self_attn"]["q_proj"]["kernel"].sharding.spec
        assert "tp" in tuple(q_spec), q_spec  # column-parallel heads
        emb = eng_tp8.backend.params["model"]["embed_tokens"]["embedding"]
        assert tuple(emb.sharding.spec)[0] == "tp"  # vocab rows sharded
        norm = layers["input_layernorm"]["scale"]
        assert all(s is None for s in tuple(norm.sharding.spec))  # replicated

    def test_jits_carry_explicit_shardings(self, eng_tp8):
        infer = eng_tp8.infer
        # the sharding trees the jits were compiled with are non-trivial
        assert infer.pool_shardings.kv.spec == P(None, None, None, None, "tp")
        import jax
        leaves = jax.tree.leaves(infer.param_shardings)
        assert any("tp" in tuple(ns.spec) for ns in leaves)

    def test_describe_and_stats(self, eng_tp8):
        desc = eng_tp8.stats()["backend"]
        assert desc["kind"] == "sharded"
        assert desc["tp_degree"] == 8 and desc["devices"] == 8
        assert desc["kv_pool_sharded"] is True

    def test_single_device_describe(self, eng_ref):
        desc = eng_ref.stats()["backend"]
        assert desc["kind"] == "single_device" and desc["tp_degree"] == 1


class TestTokenIdentity:
    def test_greedy(self, eng_ref, eng_tp8):
        prompts = [list(range(5, 30)), [40, 41, 42], list(range(50, 67))]
        want = eng_ref.generate(prompts, SamplingParams(max_new_tokens=8))
        got = eng_tp8.generate(prompts, SamplingParams(max_new_tokens=8))
        assert got == want

    def test_seeded_sampling_with_penalties(self, eng_ref, eng_tp8):
        sp = SamplingParams(max_new_tokens=8, do_sample=True, temperature=0.9,
                            top_p=0.8, top_k=12, seed=7, repetition_penalty=1.3,
                            presence_penalty=0.1, frequency_penalty=0.1)
        prompts = [[9, 8, 7, 6, 5], list(range(20, 41)), [60, 61]]
        want = eng_ref.generate(prompts, sp)
        got = eng_tp8.generate(prompts, sp)
        assert got == want

    def test_chunked_prefill_and_prefix_cache(self, eng_ref, eng_tp8_chunked):
        # two passes: the second hits the prefix cache (shared blocks + COW on
        # the exact repeat) while chunks interleave with decode — the full
        # feature matrix on the sharded pool
        prompts = [list(range(30, 55)), [70, 71, 72], list(range(10, 27))]
        want = eng_ref.generate(prompts, SamplingParams(max_new_tokens=8))
        got_cold = eng_tp8_chunked.generate(prompts, SamplingParams(max_new_tokens=8))
        assert got_cold == want
        hits0 = eng_tp8_chunked.mgr.cache_hits
        got_warm = eng_tp8_chunked.generate(prompts, SamplingParams(max_new_tokens=8))
        assert got_warm == want
        assert eng_tp8_chunked.mgr.cache_hits > hits0  # cache actually engaged
        # the jitted steps' out_shardings hold: after real prefill/mixed/decode
        # traffic (and COW copies) the pool is still laid out on tp
        assert tuple(eng_tp8_chunked.pool.kv.sharding.spec) == (
            None, None, None, None, "tp")

    def test_seeded_sampling_chunked(self, eng_ref, eng_tp8_chunked):
        sp = SamplingParams(max_new_tokens=6, do_sample=True, temperature=1.1,
                            top_p=0.9, seed=13)
        prompts = [list(range(33, 52)), [80, 81, 82, 83]]
        assert eng_tp8_chunked.generate(prompts, sp) == eng_ref.generate(prompts, sp)

    def test_dp_tp_mesh(self, model, eng_ref):
        eng = InferenceEngine(model, mesh_shape=(2, 4), **KW)
        assert eng.stats()["backend"]["mesh"]["dp"] == 2
        prompts = [[11, 12, 13, 14], list(range(44, 60))]
        want = eng_ref.generate(prompts, SamplingParams(max_new_tokens=6))
        assert eng.generate(prompts, SamplingParams(max_new_tokens=6)) == want

    @pytest.mark.parametrize("kv_heads,chunk,quant", [(8, None, None), (8, 8, "int8"), (2, None, None)])
    def test_paged_kernel_under_shard_map(self, eight_devices, kv_heads, chunk, quant):
        """The Pallas ragged kernel on a mesh (what a TPU runs by default):
        GSPMD cannot partition a Mosaic kernel, so the sharded model runs it
        under shard_map — per tp shard with a sharded pool (kv_heads=8), on
        every shard with a replicated one (kv_heads=2 on tp=4). Tokens must
        equal the single-device kernel's."""
        cfg = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=112,
                          num_hidden_layers=2, num_attention_heads=8,
                          num_key_value_heads=kv_heads, max_position_embeddings=256,
                          eos_token_id=None, pad_token_id=0, use_scan_layers=True)
        m = LlamaForCausalLM.from_config(cfg, seed=0)
        kw = dict(KW, prefill_chunk_tokens=chunk, kv_cache_quant=quant)
        ref = InferenceEngine(m, **kw)
        eng = InferenceEngine(m, mesh_shape=(2, 4), **kw)
        # the kernel choice is read at trace time: flip it before any step
        ref.infer.use_paged_kernel = eng.infer.use_paged_kernel = True
        assert eng.stats()["backend"]["kv_pool_sharded"] is (kv_heads == 8)
        prompts = [[11, 12, 13, 14], list(range(44, 63))]
        sp = SamplingParams(max_new_tokens=6)
        assert eng.generate(prompts, sp) == ref.generate(prompts, sp)

    def test_weight_update_resync(self, model, eng_ref, eng_tp8):
        """Rebinding model.params re-places them on the mesh (id check), and
        the updated sharded engine still matches the updated single-device
        one."""
        import jax

        old = model.params
        try:
            model.params = jax.tree.map(lambda x: x * 1.01, old)
            prompts = [[21, 22, 23]]
            want = eng_ref.generate(prompts, SamplingParams(max_new_tokens=6))
            got = eng_tp8.generate(prompts, SamplingParams(max_new_tokens=6))
            assert got == want
        finally:
            model.params = old


class TestRobustness:
    def test_preempt_and_abort_leak_free(self, model):
        """KV-pressure preemption and mid-flight aborts on the SHARDED pool
        release every block (the sharded pool tensor must never strand host
        allocator state)."""
        eng = InferenceEngine(model, mesh_shape=(1, 8), max_batch_size=2,
                              block_size=4, num_blocks=12, max_blocks_per_seq=16,
                              decode_steps=4, enable_prefix_cache=False)
        ids = [eng.add_request(list(range(5, 13)), SamplingParams(max_new_tokens=16))
               for _ in range(3)]
        for _ in range(3):
            eng.step()
        eng.abort(ids[1])
        while eng.has_work():
            eng.step()
        assert eng.mgr.num_free == eng.mgr.total_usable_blocks
        assert eng.num_preemptions >= 1  # pressure actually hit

    def test_reset_keeps_sharded_pool(self, model):
        eng = InferenceEngine(model, mesh_shape=(1, 8), **KW)
        pool_before = eng.pool.kv
        eng.add_request([5, 6, 7], SamplingParams(max_new_tokens=4))
        eng.step()
        eng.reset()
        # reset drops host state but keeps the device pool tensor (and its
        # sharding) — the supervisor's in-place recovery contract
        assert eng.pool.kv.sharding.spec == pool_before.sharding.spec
        out = eng.generate([[8, 9, 10]], SamplingParams(max_new_tokens=4))
        assert len(out[0]) == 4

    def test_insufficient_devices_raises(self, model):
        with pytest.raises(ValueError, match="devices"):
            InferenceEngine(model, mesh_shape=(4, 4), **KW)

    def test_gqa_indivisible_falls_back(self, eight_devices, eng_ref):
        """num_key_value_heads % tp != 0: pool replicates, outputs still
        token-identical (rules degrade, never crash)."""
        cfg = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=112,
                          num_hidden_layers=2, num_attention_heads=4,
                          num_key_value_heads=2, max_position_embeddings=256,
                          eos_token_id=None, pad_token_id=0, use_scan_layers=True)
        m = LlamaForCausalLM.from_config(cfg, seed=0)
        ref = InferenceEngine(m, **KW)
        eng = InferenceEngine(m, mesh_shape=(1, 8), **KW)
        assert eng.stats()["backend"]["kv_pool_sharded"] is False
        prompts = [[5, 6, 7, 8]]
        want = ref.generate(prompts, SamplingParams(max_new_tokens=6))
        assert eng.generate(prompts, SamplingParams(max_new_tokens=6)) == want
