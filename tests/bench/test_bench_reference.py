"""The yardstick is itself tested: the plain reference against the repo's own
Qwen2ForCausalLM at a tiny size in float32, forward, loss and gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import loader

CFG = dict(vocab_size=512, hidden_size=64, intermediate_size=160, num_hidden_layers=3, num_attention_heads=4,
           num_key_value_heads=2, max_position_embeddings=512, initializer_range=0.02, rope_theta=1e6, rms_norm_eps=1e-6,
           tie_word_embeddings=True)
OPTIM = dict(adam_beta1=0.9, adam_beta2=0.999, adam_epsilon=1e-8, learning_rate=3e-4, weight_decay=0.01,
             max_grad_norm=1.0)


@pytest.fixture(scope="module")
def ref():
    return loader.module_from("reference", "dense_decoder")


@pytest.fixture(scope="module")
def model(ref):
    from paddlenlp_tpu.transformers import Qwen2Config, Qwen2ForCausalLM

    m = Qwen2ForCausalLM(Qwen2Config(**CFG), dtype=jnp.float32, param_dtype=jnp.float32)
    m.params = jax.jit(lambda: ref.program_params(CFG, 7, jnp.float32))()
    return m


def test_parameter_tree_is_the_programs(ref, model):
    want = jax.tree.map(lambda s: s.shape, model.param_shapes)
    assert jax.tree.map(lambda a: a.shape, model.params) == want


def test_layer_by_layer_weights_equal_the_stacked(ref):
    stacked = jax.jit(lambda: ref.stacked_weights(CFG, 7, jnp.float32))()
    one = jax.jit(lambda k: ref.layer_weights(CFG, k, jnp.float32))(ref.layer_key(7, 2))
    for name in ref.LAYER_LEAVES:
        assert np.array_equal(np.asarray(stacked["layers"][name][2]), np.asarray(one[name])), name


def test_forward_agrees_with_the_program(ref, model):
    ids = np.random.default_rng(0).integers(0, 512, (1, 48)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(model(input_ids=jnp.asarray(ids)).logits[0])
    rows = ref.served_gaps(CFG, 7, [(ids[0, :40].tolist(), ids[0, 40:48].tolist())], "float32")
    want = logits[39:47]
    gaps = want.max(-1) - want[np.arange(8), ids[0, 40:48]]
    # tight: both sides are float32 at highest precision; only summation order differs
    assert np.allclose(rows[0]["gaps"], gaps, atol=2e-5)
    assert gaps.max() > 0.1  # random tokens are not the best ones: the number moves when a token is altered


def test_loss_and_gradients_agree_with_the_program(ref, model):
    from paddlenlp_tpu.ops.cross_entropy import causal_lm_loss

    batch = np.random.default_rng(1).integers(0, 512, (2, 32)).astype(np.int32)

    def loss_fn(p):
        out = model.module.apply({"params": p}, input_ids=jnp.asarray(batch))
        return causal_lm_loss(out.logits, jnp.asarray(batch), shift=True)

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_fn)(model.params)
    want = ref.train_trajectory(CFG, 7, [batch], OPTIM)
    assert abs(float(loss) - want["losses"][0]) < 1e-5
    norms = {k: float(jnp.linalg.norm(v.ravel())) for k, v in ref.program_leaves(grads).items()}
    total = np.sqrt(sum(v * v for v in norms.values()))
    clipped = {k: v * min(1.0, OPTIM["max_grad_norm"] / total) for k, v in norms.items()}
    assert ref.worst_leaf_gap(clipped, want["first_grad_norm"]) < 1e-4


def test_lower_precision_moves_the_training_numbers(ref):
    batch = np.random.default_rng(1).integers(0, 512, (2, 32)).astype(np.int32)
    want = ref.train_trajectory(CFG, 7, [batch] * 3, OPTIM)
    low = ref.train_trajectory(CFG, 7, [batch] * 3, OPTIM, precision="bfloat16")
    # norm scales near 1.0 cannot take a 3e-4 step in bfloat16: their change vanishes
    assert low["param_delta_norm"]["ln1"] == 0.0 < want["param_delta_norm"]["ln1"]
    assert ref.worst_leaf_gap(low["param_delta_norm"], want["param_delta_norm"]) > 0.05
    assert ref.worst_leaf_gap(want["param_delta_norm"], want["param_delta_norm"]) == 0.0


def test_int8_control_changes_the_logits(ref):
    ids = np.random.default_rng(2).integers(0, 512, 64).astype(np.int32)
    h = jax.jit(lambda: ref.global_weights(CFG, 7, jnp.float32))()["embed"][jnp.asarray(ids)]
    w = jax.jit(lambda k: ref.layer_weights(CFG, k, jnp.float32))(ref.layer_key(7, 0))
    a = np.asarray(ref.layer_forward(CFG, w, h, "float32"))
    b = np.asarray(ref.layer_forward(CFG, w, h, "int8"))
    rel = np.sqrt(np.mean((a - b) ** 2) / np.mean((a - h) ** 2))
    assert 1e-3 < rel < 0.2
