"""The dots3_note yardstick is itself tested: the plain reference against the
repo's own Dots3NoteForCausalLM at a tiny size in float32 (tree, forward, the
serving comparison), its int8 control, and the share of guide section 4: what
the 8 shares of an expert layer compute adds up to the uncut layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import loader

CFG = dict(
    vocab_size=97, hidden_size=64, intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=5,
    layer_types=["full_attention", "full_attention", "sliding_attention", "sliding_attention", "sliding_attention"],
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4, index_head_dim=16, index_topk=8, sliding_window_size=5,
    swa_num_attention_heads=2, swa_num_key_value_heads=2, swa_q_lora_rank=32, swa_kv_lora_rank=32,
    swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16, n_routed_experts=4,
    n_routed_experts_total=16, first_held_expert=4, num_experts_per_tok=4, first_k_dense_replace=1,
    n_shared_experts=1, routed_scaling_factor=1.0, rms_norm_eps=1e-5, initializer_range=0.02, rope_theta=8e7,
    swa_rope_theta=5e4, apply_mla_qkv_lora_rescale=True)
SEED = 7


@pytest.fixture(scope="module")
def ref():
    return loader.module_from("reference", "dots3_note")


def program(ref, cfg):
    from paddlenlp_tpu.transformers import Dots3NoteConfig, Dots3NoteForCausalLM

    m = Dots3NoteForCausalLM(Dots3NoteConfig(**cfg), dtype=jnp.float32, param_dtype=jnp.float32)
    m.params = jax.jit(lambda s: ref.program_params(cfg, s, jnp.float32))(ref.seed_array(SEED))
    return m


@pytest.fixture(scope="module")
def model(ref):
    return program(ref, CFG)


def test_parameter_tree_is_the_programs(model):
    want = jax.tree.map(lambda s: (s.shape, s.dtype), model.param_shapes)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), model.params) == want


def test_layer_leaves_are_the_trees(ref, model):
    one = ref.layer_weights(CFG, SEED, 1, jnp.float32)
    leaves = ref.program_leaves(model.params, 1)
    assert set(leaves) == set(one)
    # the same draws; a draw made inside another jit may differ in its last float32 bit
    for name, value in one.items():
        assert np.allclose(np.asarray(leaves[name]), np.asarray(value), rtol=1e-6, atol=0), name
    held = np.asarray(model.params["model"]["layers_1"]["mlp"]["experts"]["up_proj"])
    sixth = ref.expert_weights(CFG, SEED, 1, 6, jnp.float32)["up"]  # the model's expert 6 is the third held (4..7)
    assert np.allclose(held[2], np.asarray(sixth), rtol=1e-6, atol=0)


def test_forward_agrees_with_the_program(ref, model):
    ids = np.random.default_rng(0).integers(0, CFG["vocab_size"], (1, 48)).astype(np.int32)
    logits = np.asarray(model(jnp.asarray(ids)))[0]
    assert np.abs(np.asarray(ref.forward(CFG, SEED, ids[0])) - logits).max() < 2e-5
    rows = ref.served_gaps(CFG, SEED, [(ids[0, :40].tolist(), ids[0, 40:48].tolist())], "float32")
    want = logits[39:47]
    gaps = want.max(-1) - want[np.arange(8), ids[0, 40:48]]
    # tight: both sides float32; only the order of sums differs. The sequence is padded to 64 on the
    # reference's side: what lies behind a position cannot move it
    assert np.allclose(rows[0]["gaps"], gaps, atol=2e-5)
    assert gaps.max() > 0.1  # random tokens are not the best ones: the number moves when a token is altered


def test_int8_control_moves_the_logits_and_is_told_apart(ref):
    ids = np.random.default_rng(2).integers(0, CFG["vocab_size"], 40).astype(np.int32)
    sound = np.asarray(ref.forward(CFG, SEED, ids))
    low = np.asarray(ref.forward(CFG, SEED, ids, precision="int8"))
    assert 1e-3 < np.abs(low - sound).max() < 1.0
    rows = ref.served_gaps(CFG, SEED, [(ids[:30].tolist(), ids[30:40].tolist())], "float32", control="int8")
    at = sound[29:39]
    assert np.allclose(rows[0]["gaps"], at.max(-1) - at[np.arange(10), ids[30:40]], atol=2e-5)
    # the control's first choices, scored by the reference: never better than the reference's own
    assert np.allclose(rows[0]["control_gaps"], at.max(-1) - at[np.arange(10), low[29:39].argmax(-1)], atol=2e-5)


def test_the_eight_shares_add_up_to_the_uncut_expert_layer(ref):
    """Layer 1's expert layer on 40 tokens: the uncut reference (all 16 experts
    held) against the sum of the routed parts the 8 shares of 2 experts give,
    by the reference and by the program's layer given each share, with the
    shared expert counted once."""
    from paddlenlp_tpu.transformers import Dots3NoteConfig
    from paddlenlp_tpu.transformers import latent_layers as M

    uncut = dict(CFG, n_routed_experts=16, first_held_expert=0)
    x = jax.random.normal(jax.random.key(1), (40, CFG["hidden_size"]), jnp.float32)
    w = {k: jnp.asarray(v, jnp.float32) for k, v in ref.layer_weights(uncut, SEED, 1, jnp.float32).items()}
    whole = np.asarray(ref.mlp(uncut, SEED, 1, w, x, "float32"))
    shared = np.asarray(ref._swiglu(x, w["sh_gate"], w["sh_up"], w["sh_down"], "float32"))
    idx, wts = ref.route(uncut, w, x)
    by_ref, by_program, seen = shared.copy(), shared.copy(), 0
    for first in range(0, 16, 2):
        share = dict(CFG, n_routed_experts=2, first_held_expert=first)
        by_ref += np.asarray(ref.routed_part(share, SEED, 1, idx, wts, x, "float32"))
        p = program(ref, share).params["model"]["layers_1"]["mlp"]
        cfg = Dots3NoteConfig(**share)
        chosen, weights = M.route(p, x, cfg)
        assert np.array_equal(np.asarray(chosen), np.asarray(idx))  # every share routes over all 16 alike
        by_program += np.asarray(M.experts_held(p["experts"], x, chosen, weights, first, 2))
        seen += int(M.held_counts(chosen, first, 2).sum())
    assert seen == 40 * CFG["num_experts_per_tok"]  # every routed choice landed on exactly one share
    assert np.abs(by_ref - whole).max() < 1e-5 and np.abs(by_program - whole).max() < 1e-5
    assert np.abs(whole - shared).max() > 1e-3  # the routed part is not nothing
