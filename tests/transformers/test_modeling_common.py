"""Cross-model harness — the reference's ModelTesterMixin pattern
(tests/transformers/test_modeling_common.py): tiny configs for EVERY family,
forward shape checks, save/load round trip, greedy generate smoke, tp-sharded
placement. One parametrized suite instead of per-model copies."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlenlp_tpu.parallel import MeshConfig, create_mesh, use_mesh
from paddlenlp_tpu.transformers import (
    AlbertConfig,
    AlbertForMaskedLM,
    AlbertForSequenceClassification,
    ElectraConfig,
    ElectraForSequenceClassification,
    RobertaConfig,
    RobertaForMaskedLM,
    RobertaForSequenceClassification,
    BaichuanConfig,
    DeepseekV2Config,
    DeepseekV2ForCausalLM,
    DeepseekV3Config,
    DeepseekV3ForCausalLM,
    MambaConfig,
    MambaForCausalLM,
    BaichuanForCausalLM,
    BertConfig,
    BloomConfig,
    BloomForCausalLM,
    ChatGLMv2Config,
    ChatGLMv2ForCausalLM,
    OPTConfig,
    OPTForCausalLM,
    QWenConfig,
    QWenForCausalLM,
    BertForMaskedLM,
    BertForSequenceClassification,
    ErnieConfig,
    ErnieForSequenceClassification,
    GemmaConfig,
    GemmaForCausalLM,
    GPTConfig,
    GPTForCausalLM,
    LlamaConfig,
    LlamaForCausalLM,
    MistralConfig,
    MistralForCausalLM,
    MixtralConfig,
    MixtralForCausalLM,
    Qwen2Config,
    Qwen2ForCausalLM,
    Qwen2MoeConfig,
    Qwen2MoeForCausalLM,
    RWConfig,
    RWForCausalLM,
    ChatGLMConfig,
    ChatGLMForCausalLM,
    YuanConfig,
    YuanForCausalLM,
    JambaConfig,
    JambaForCausalLM,
)

TINY = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4, max_position_embeddings=64,
            initializer_range=0.02)

CAUSAL_CASES = {
    "llama": (LlamaForCausalLM, lambda: LlamaConfig(vocab_size=96, intermediate_size=112,
                                                    num_key_value_heads=2, **TINY)),
    "qwen2": (Qwen2ForCausalLM, lambda: Qwen2Config(vocab_size=96, intermediate_size=112,
                                                    num_key_value_heads=2, **TINY)),
    "mistral": (MistralForCausalLM, lambda: MistralConfig(vocab_size=96, intermediate_size=112,
                                                          num_key_value_heads=2, sliding_window=8, **TINY)),
    "gemma": (GemmaForCausalLM, lambda: GemmaConfig(vocab_size=96, intermediate_size=112,
                                                    num_key_value_heads=2, head_dim=16, **TINY)),
    "gpt": (GPTForCausalLM, lambda: GPTConfig(vocab_size=96, **TINY)),
    "baichuan": (BaichuanForCausalLM, lambda: BaichuanConfig(vocab_size=96, intermediate_size=112, **TINY)),
    "baichuan_alibi": (BaichuanForCausalLM, lambda: BaichuanConfig(vocab_size=96, intermediate_size=112,
                                                                   use_alibi=True, **TINY)),
    "qwen": (QWenForCausalLM, lambda: QWenConfig(vocab_size=96, intermediate_size=224, **TINY)),
    "bloom": (BloomForCausalLM, lambda: BloomConfig(vocab_size=96, **TINY)),
    "opt": (OPTForCausalLM, lambda: OPTConfig(vocab_size=96, intermediate_size=128, **TINY)),
    "chatglm_v2": (ChatGLMv2ForCausalLM, lambda: ChatGLMv2Config(vocab_size=96, intermediate_size=112,
                                                                 multi_query_group_num=2, kv_channels=16,
                                                                 **TINY)),
    "mixtral": (MixtralForCausalLM, lambda: MixtralConfig(vocab_size=96, intermediate_size=80,
                                                          num_key_value_heads=2, num_local_experts=4,
                                                          num_experts_per_tok=2, **TINY)),
    "qwen2_moe": (Qwen2MoeForCausalLM, lambda: Qwen2MoeConfig(vocab_size=96, intermediate_size=112,
                                                              num_key_value_heads=2, num_experts=4,
                                                              num_experts_per_tok=2, moe_intermediate_size=48,
                                                              shared_expert_intermediate_size=64, **TINY)),
    # MLA: low-rank q/kv, rope on a 8-dim slice, dense layer 0 + grouped MoE after
    "deepseek_v2": (DeepseekV2ForCausalLM, lambda: DeepseekV2Config(
        vocab_size=96, intermediate_size=112, moe_intermediate_size=48,
        q_lora_rank=24, kv_lora_rank=16, qk_rope_head_dim=8, qk_nope_head_dim=8, v_head_dim=16,
        n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=2,
        topk_method="group_limited_greedy", n_group=2, topk_group=1,
        first_k_dense_replace=1, routed_scaling_factor=1.0, norm_topk_prob=True,
        rope_scaling={"type": "yarn", "factor": 2.0, "original_max_position_embeddings": 32,
                      "mscale": 0.707, "mscale_all_dim": 0.707,
                      "beta_fast": 32, "beta_slow": 1},
        **TINY)),
    # MLA without a q latent at two head sizes, dense layer 0, then sigmoid bias-corrected routing over 8 experts
    # of which this process holds 4 (a share: the router stays 8 wide), grouped products with a backward
    "deepseek_v3": (DeepseekV3ForCausalLM, lambda: DeepseekV3Config(
        vocab_size=96, intermediate_size=112, moe_intermediate_size=48,
        q_lora_rank=None, kv_lora_rank=16, qk_rope_head_dim=8, qk_nope_head_dim=8, v_head_dim=16,
        n_routed_experts=4, n_routed_experts_total=8, first_held_expert=2, n_shared_experts=2, num_experts_per_tok=3,
        n_group=1, topk_group=1, first_k_dense_replace=1, routed_scaling_factor=2.448, **TINY)),
    # hybrid: NoPE attention at layer 1, mamba elsewhere; MoE ffn on odd layers
    "jamba": (JambaForCausalLM, lambda: JambaConfig(
        vocab_size=96, hidden_size=64, intermediate_size=112, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=64,
        initializer_range=0.02, num_experts=4, num_experts_per_tok=2,
        attn_layer_period=4, attn_layer_offset=1, expert_layer_period=2, expert_layer_offset=1,
        mamba_d_state=8, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8)),
    # localized-filtering gate (two causal convs) ahead of q/k; v from raw hiddens
    "yuan": (YuanForCausalLM, lambda: YuanConfig(vocab_size=96, intermediate_size=112,
                                                 num_key_value_heads=2, **TINY)),
    # GLM v1: 2D rotary halves, alpha-scaled post-LN residuals, per-head-thirds fused qkv
    "chatglm": (ChatGLMForCausalLM, lambda: ChatGLMConfig(vocab_size=96, intermediate_size=128,
                                                          bos_token_id=None, eos_token_id=None,
                                                          generation_2d_positions=False, **TINY)),
    # falcon-7b shape: MQ fused qkv + parallel_attn + rotary; rw-1b shape: MHA + alibi
    "rw_falcon": (RWForCausalLM, lambda: RWConfig(vocab_size=96, multi_query=True,
                                                  parallel_attn=True, bias=False, **TINY)),
    "rw_alibi": (RWForCausalLM, lambda: RWConfig(vocab_size=96, multi_query=False,
                                                 parallel_attn=False, bias=True, alibi=True, **TINY)),
    # falcon-40b shape: grouped-kv fused qkv ([n_kv, group+2, hd] layout)
    "rw_gqa": (RWForCausalLM, lambda: RWConfig(vocab_size=96, multi_query=False,
                                               n_head_kv=2, parallel_attn=True, bias=False, **TINY)),
    # attention-free SSM: associative-scan recurrence + conv/ssm state cache
    "mamba": (MambaForCausalLM, lambda: MambaConfig(
        vocab_size=96, hidden_size=64, num_hidden_layers=2, state_size=8,
        conv_kernel=4, expand=2, time_step_rank=8, initializer_range=0.02,
        max_position_embeddings=64)),
}

ENCODER_CASES = {
    "bert_mlm": (BertForMaskedLM, lambda: BertConfig(vocab_size=96, intermediate_size=128, **TINY)),
    "roberta_mlm": (RobertaForMaskedLM, lambda: RobertaConfig(vocab_size=96, intermediate_size=128,
                                                              pad_token_id=1, **TINY)),
    "roberta_cls": (RobertaForSequenceClassification, lambda: RobertaConfig(
        vocab_size=96, intermediate_size=128, pad_token_id=1, num_labels=3, **TINY)),
    "electra_cls": (ElectraForSequenceClassification, lambda: ElectraConfig(
        vocab_size=96, embedding_size=32, intermediate_size=128, num_labels=3, **TINY)),
    "albert_mlm": (AlbertForMaskedLM, lambda: AlbertConfig(vocab_size=96, embedding_size=32,
                                                           intermediate_size=128, **TINY)),
    "albert_cls": (AlbertForSequenceClassification, lambda: AlbertConfig(
        vocab_size=96, embedding_size=32, intermediate_size=128, num_labels=3, **TINY)),
    "bert_cls": (BertForSequenceClassification, lambda: BertConfig(vocab_size=96, intermediate_size=128,
                                                                   num_labels=3, **TINY)),
    "ernie_cls": (ErnieForSequenceClassification, lambda: ErnieConfig(vocab_size=96, intermediate_size=128,
                                                                      num_labels=3, **TINY)),
}


@pytest.mark.parametrize("name", list(CAUSAL_CASES))
class TestCausalCommon:
    def test_forward_and_roundtrip(self, name, tmp_path):
        cls, cfg_fn = CAUSAL_CASES[name]
        model = cls.from_config(cfg_fn(), seed=0)
        ids = jnp.asarray(np.arange(10)[None, :] % 90 + 3, dtype=jnp.int32)
        out = model(input_ids=ids)
        assert out.logits.shape == (1, 10, 96)
        assert np.isfinite(np.asarray(out.logits)).all()
        model.save_pretrained(str(tmp_path))
        reloaded = cls.from_pretrained(str(tmp_path))
        np.testing.assert_allclose(
            np.asarray(out.logits), np.asarray(reloaded(input_ids=ids).logits), atol=1e-5
        )

    def test_greedy_generate_cache_parity(self, name, tmp_path):
        """Cached greedy decode == argmax over repeated full forwards."""
        cls, cfg_fn = CAUSAL_CASES[name]
        model = cls.from_config(cfg_fn(), seed=0)
        prompt = jnp.asarray([[5, 6, 7]], dtype=jnp.int32)
        gen, _ = model.generate(prompt, max_new_tokens=4, do_sample=False, eos_token_id=None)
        ids = np.asarray(prompt)
        for _ in range(4):
            logits = model(input_ids=jnp.asarray(ids)).logits
            ids = np.concatenate([ids, [[int(jnp.argmax(logits[0, -1]))]]], axis=1)
        np.testing.assert_array_equal(np.asarray(gen[0]), ids[0, 3:])


@pytest.mark.parametrize("name", list(ENCODER_CASES))
class TestEncoderCommon:
    def test_forward_and_roundtrip(self, name, tmp_path):
        cls, cfg_fn = ENCODER_CASES[name]
        model = cls.from_config(cfg_fn(), seed=0)
        ids = jnp.asarray(np.arange(8)[None, :] % 90 + 3, dtype=jnp.int32)
        mask = jnp.ones_like(ids)
        out = model(input_ids=ids, attention_mask=mask)
        logits = np.asarray(out.logits)
        assert np.isfinite(logits).all()
        model.save_pretrained(str(tmp_path))
        reloaded = cls.from_pretrained(str(tmp_path))
        np.testing.assert_allclose(
            logits, np.asarray(reloaded(input_ids=ids, attention_mask=mask).logits), atol=1e-5
        )


class TestMoESpecifics:
    def test_aux_loss_flows(self):
        cls, cfg_fn = CAUSAL_CASES["mixtral"]
        model = cls.from_config(cfg_fn(), seed=0)
        ids = jnp.asarray([[4, 5, 6, 7]], dtype=jnp.int32)
        out = model(input_ids=ids)
        aux = np.asarray(out.aux_loss)
        assert np.isfinite(aux) and aux > 0  # coef 0.02 * balanced ~ E*sum(f*P) ~ 1

    def test_expert_checkpoint_keys(self, tmp_path):
        from paddlenlp_tpu.utils.safetensors_io import safe_keys

        cls, cfg_fn = CAUSAL_CASES["mixtral"]
        model = cls.from_config(cfg_fn(), seed=0)
        model.save_pretrained(str(tmp_path))
        keys = set(safe_keys(str(tmp_path / "model.safetensors")))
        assert "model.layers.0.block_sparse_moe.experts.0.w1.weight" in keys
        assert "model.layers.1.block_sparse_moe.experts.3.w2.weight" in keys
        assert "model.layers.0.block_sparse_moe.gate.weight" in keys

    def test_qwen2moe_shared_expert_keys(self, tmp_path):
        from paddlenlp_tpu.utils.safetensors_io import safe_keys

        cls, cfg_fn = CAUSAL_CASES["qwen2_moe"]
        model = cls.from_config(cfg_fn(), seed=0)
        model.save_pretrained(str(tmp_path))
        keys = set(safe_keys(str(tmp_path / "model.safetensors")))
        assert "model.layers.0.mlp.experts.0.gate_proj.weight" in keys
        assert "model.layers.0.mlp.shared_expert.gate_proj.weight" in keys
        assert "model.layers.0.mlp.shared_expert_gate.weight" in keys

    def test_moe_expert_sharding(self, eight_devices):
        cls, cfg_fn = CAUSAL_CASES["mixtral"]
        mesh = create_mesh(MeshConfig(dp=4, tp=2))
        model = cls.from_config(cfg_fn(), seed=0, mesh=mesh)
        w1 = model.params["model"]["layers"]["block_sparse_moe"]["w1"]
        spec = str(w1.sharding.spec)
        assert "dp" in spec  # experts sharded over the data axes (EP)


class TestGPTSpecifics:
    def test_hf_gpt2_key_format(self, tmp_path):
        from paddlenlp_tpu.utils.safetensors_io import SafeFile, safe_keys

        model = GPTForCausalLM.from_config(GPTConfig(vocab_size=96, use_scan_layers=False, **TINY), seed=0)
        model.save_pretrained(str(tmp_path))
        keys = set(safe_keys(str(tmp_path / "model.safetensors")))
        assert "transformer.wte.weight" in keys
        assert "transformer.wpe.weight" in keys
        assert "transformer.h.0.attn.c_attn.weight" in keys
        assert "transformer.h.0.mlp.c_fc.weight" in keys
        assert "transformer.ln_f.weight" in keys
        # Conv1D layout: c_attn stored [in, 3*out] (not transposed)
        with SafeFile(str(tmp_path / "model.safetensors")) as sf:
            assert sf.get_slice("transformer.h.0.attn.c_attn.weight").shape == (64, 192)


class TestBertSpecifics:
    def test_hf_bert_key_format(self, tmp_path):
        from paddlenlp_tpu.utils.safetensors_io import safe_keys

        model = BertForSequenceClassification.from_config(
            BertConfig(vocab_size=96, intermediate_size=128, num_labels=3, **TINY), seed=0
        )
        model.save_pretrained(str(tmp_path))
        keys = set(safe_keys(str(tmp_path / "model.safetensors")))
        assert "bert.embeddings.word_embeddings.weight" in keys
        assert "bert.encoder.layer.0.attention.self.query.weight" in keys
        assert "bert.encoder.layer.0.attention.output.LayerNorm.weight" in keys
        assert "bert.encoder.layer.1.intermediate.dense.weight" in keys
        assert "bert.pooler.dense.weight" in keys
        assert "classifier.weight" in keys

    def test_padding_invariance(self):
        model = BertForSequenceClassification.from_config(
            BertConfig(vocab_size=96, intermediate_size=128, num_labels=3, **TINY), seed=0
        )
        ids = jnp.asarray([[5, 6, 7, 8]], dtype=jnp.int32)
        full = model(input_ids=ids, attention_mask=jnp.ones_like(ids)).logits
        padded = jnp.asarray([[5, 6, 7, 8, 0, 0]], dtype=jnp.int32)
        mask = jnp.asarray([[1, 1, 1, 1, 0, 0]], dtype=jnp.int32)
        out = model(input_ids=padded, attention_mask=mask).logits
        np.testing.assert_allclose(np.asarray(full), np.asarray(out), atol=2e-5)


class TestAutoClasses:
    def test_auto_roundtrip(self, tmp_path):
        from paddlenlp_tpu.transformers import AutoConfig, AutoModelForCausalLM

        model = LlamaForCausalLM.from_config(
            LlamaConfig(vocab_size=96, intermediate_size=112, num_key_value_heads=2, **TINY), seed=0
        )
        model.save_pretrained(str(tmp_path))
        cfg = AutoConfig.from_pretrained(str(tmp_path))
        assert cfg.model_type == "llama"
        auto = AutoModelForCausalLM.from_pretrained(str(tmp_path))
        assert type(auto).__name__ == "LlamaForCausalLM"

    def test_auto_unknown_type(self, tmp_path):
        import json

        (tmp_path / "config.json").write_text(json.dumps({"model_type": "not_a_model"}))
        from paddlenlp_tpu.transformers import AutoConfig

        with pytest.raises(ValueError, match="unrecognized model_type"):
            AutoConfig.from_pretrained(str(tmp_path))
