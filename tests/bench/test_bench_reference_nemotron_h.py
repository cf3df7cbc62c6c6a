"""The nemotron_h yardstick is itself tested: the plain reference against the repo's own NemotronHForCausalLM at a
tiny size in float32 (tree, forward, the serving comparison with sequences padded together), its int8 control, that
it imports nothing of the program, and the configuration file against the catalog's published keys."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import loader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CFG = dict(
    vocab_size=97, hidden_size=48, num_hidden_layers=9, hybrid_override_pattern="MEM*EMEM*", num_attention_heads=4,
    num_key_value_heads=2, head_dim=8, mamba_num_heads=8, mamba_head_dim=4, n_groups=2, ssm_state_size=8,
    conv_kernel=4, chunk_size=4, moe_intermediate_size=24, moe_shared_expert_intermediate_size=40,
    n_routed_experts=4, n_routed_experts_total=16, first_held_expert=4, num_experts_per_tok=3,
    routed_scaling_factor=2.5, norm_eps=1e-5, initializer_range=0.14)
SEED = 7


@pytest.fixture(scope="module")
def ref():
    return loader.module_from("reference", "nemotron_h")


@pytest.fixture(scope="module")
def model(ref):
    from paddlenlp_tpu.transformers import NemotronHConfig, NemotronHForCausalLM

    m = NemotronHForCausalLM(NemotronHConfig(**CFG), dtype=jnp.float32, param_dtype=jnp.float32)
    m.params = jax.jit(lambda s: ref.program_params(CFG, s, jnp.float32))(ref.seed_array(SEED))
    return m


def test_parameter_tree_is_the_programs_and_holds_the_references_numbers(ref, model):
    want = jax.tree.map(lambda s: (s.shape, s.dtype), model.param_shapes)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), model.params) == want
    one = ref.layer_weights(CFG, SEED, 0, jnp.float32)  # a scan block: its in_proj lies in three column blocks
    mixer = model.params["model"]["layers_0"]["mixer"]
    parts = jnp.concatenate([mixer["in_proj"][k]["kernel"] for k in ("z", "xbc", "dt")], axis=1)
    assert np.allclose(np.asarray(parts), np.asarray(one["in_proj"]), rtol=1e-6, atol=0)
    held = np.asarray(model.params["model"]["layers_1"]["mixer"]["experts"]["up_proj"])
    sixth = ref.expert_weights(CFG, SEED, 1, 6, jnp.float32)["up"]  # the model's expert 6 is the third held (4..7)
    assert np.allclose(held[2], np.asarray(sixth).T, rtol=1e-6, atol=0)  # out x in in the program's tree


def test_the_seeded_scan_constants_span_a_realistic_range(ref):
    w = ref.layer_weights(CFG, SEED, 0, jnp.float32)
    a, dt = -np.exp(np.asarray(w["A_log"])), np.log1p(np.exp(np.asarray(w["dt_bias"])))
    assert (-16 <= a).all() and (a <= -1).all() and (1e-3 <= dt * 1.001).all() and (dt <= 0.1 * 1.001).all()


def test_forward_agrees_with_the_program_and_padding_changes_nothing(ref, model):
    ids = np.random.default_rng(0).integers(0, CFG["vocab_size"], (1, 48)).astype(np.int32)
    logits = np.asarray(model(jnp.asarray(ids)))[0]
    assert np.abs(np.asarray(ref.forward(CFG, SEED, ids[0])) - logits).max() < 5e-5
    # two sequences of unlike length in one padded batch (48 -> 64 and 21 -> 64)
    seqs = [(ids[0, :40].tolist(), ids[0, 40:48].tolist()), (ids[0, :15].tolist(), ids[0, 15:22].tolist())]
    rows = ref.served_gaps(CFG, SEED, seqs, "float32")
    want = logits[39:47]
    gaps = want.max(-1) - want[np.arange(8), ids[0, 40:48]]
    assert np.allclose(rows[0]["gaps"], gaps, atol=5e-5)
    short = np.asarray(ref.forward(CFG, SEED, ids[0, :22]))[14:21]
    assert np.allclose(rows[1]["gaps"], short.max(-1) - short[np.arange(7), ids[0, 15:22]], atol=5e-5)
    assert gaps.max() > 0.1  # random tokens are not the best ones: the number moves when a token is altered


def test_int8_control_moves_the_logits_and_is_told_apart(ref):
    ids = np.random.default_rng(2).integers(0, CFG["vocab_size"], 40).astype(np.int32)
    sound = np.asarray(ref.forward(CFG, SEED, ids))
    low = np.asarray(ref.forward(CFG, SEED, ids, precision="int8"))
    assert 1e-3 < np.abs(low - sound).max() < 3.0
    rows = ref.served_gaps(CFG, SEED, [(ids[:30].tolist(), ids[30:40].tolist())], "float32", control="int8")
    at = sound[29:39]
    assert np.allclose(rows[0]["gaps"], at.max(-1) - at[np.arange(10), ids[30:40]], atol=5e-5)
    assert (rows[0]["control_gaps"] >= 0).all() and rows[0]["control_gaps"].shape == (10,)


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "bench", "reference", "nemotron_h.py")).read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w\.]+)", src, re.M)
    assert imports and all(not m.startswith(("paddlenlp_tpu", "bench", ".")) for m in imports), imports
    assert "state_layers" not in src and "latent_layers" not in src


def test_the_configuration_file_holds_every_published_number():
    doc = json.load(open(os.path.join(ROOT, "bench", "configs", "nemotron3-nano-serve-ep8.json")))
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
        "hidden_size": 2688, "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False, "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h", "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8, "n_routed_experts": 128,
        "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 52, "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "partial_rotary_factor": 1, "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "sliding_window": None, "ssm_state_size": 128, "tie_word_embeddings": False,
        "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
        "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072}
    differs = sorted(k for k, v in published.items() if doc.get(k, "absent") != v)
    assert differs == sorted(doc["bench"]["reduced"]) == ["n_routed_experts", "vocab_size"]
    assert (doc["n_routed_experts"], doc["n_routed_experts_total"], doc["first_held_expert"]) == (16, 128, 0)
    assert doc["vocab_size"] * 8 == published["vocab_size"] and len(doc["hybrid_override_pattern"]) == 52
    b = doc["bench"]
    assert b["assumed"][0].startswith("NO rotary embedding") and "8 TPU v5e chips of one host" in b["deployment"]
    assert "no pipeline" in b["deployment"] and "12.7 GB" in b["deployment"]
    assert b["require_paged_kernel"] is True and b["engine"]["enable_prefix_cache"] is False
    assert (b["engine"]["max_batch_size"], b["engine"]["block_size"], b["engine"]["decode_steps"],
            b["engine"]["prefill_chunk_tokens"]) == (32, 16, 8, 1024)


def test_the_parameter_count_is_the_configuration_files():
    """5.26 B parameters, 10.5 GB in bfloat16: the byte count the file states, from the program's own tree."""
    from paddlenlp_tpu.transformers import NemotronHConfig
    from paddlenlp_tpu.transformers.nemotron_h.modeling import param_tree_shapes

    doc = json.load(open(os.path.join(ROOT, "bench", "configs", "nemotron3-nano-serve-ep8.json")))
    cfg = NemotronHConfig(**{k: v for k, v in doc.items() if k not in ("bench", "architectures", "torch_dtype", "model_type")})
    leaves = jax.tree.leaves(param_tree_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    total = sum(int(np.prod(s)) for s in leaves)
    assert 5.25e9 < total < 5.27e9
    by_kind = {k: 0 for k in "ME*"}
    for i, c in enumerate(cfg.hybrid_override_pattern):
        by_kind[c] += sum(int(np.prod(s)) for s in jax.tree.leaves(param_tree_shapes(cfg)["model"][f"layers_{i}"],
                                                                   is_leaf=lambda x: isinstance(x, tuple)))
    # ISSUE 33's table, to the million (each block's own norm is counted with it here)
    assert [round(by_kind[k] / 1e6) for k in "EM*"] == [4139, 891, 140]
