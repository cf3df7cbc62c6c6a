"""Remat granularities (reference recompute_granularity, training_args.py):
every policy must trace, train, and produce the same loss/grads — remat is a
memory/compute tradeoff, never a numerics change."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlenlp_tpu.transformers import LlamaConfig, LlamaForCausalLM

GRANULARITIES = [
    "full", "full_attn", "core_attn",
    "save_core_attn", "save_qkv_attn", "save_attn_mlp", "save_dots", "offload_attn",
]


def _skip_without_offload(gran):
    if gran == "offload_attn" and not hasattr(
        jax.checkpoint_policies, "save_and_offload_only_these_names"
    ):
        pytest.skip("jax build lacks save_and_offload_only_these_names")


def _loss_and_grad(gran, use_scan):
    _skip_without_offload(gran)
    cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=128,
        recompute=True, recompute_granularity=gran, use_flash_attention=False,
        use_scan_layers=use_scan,
    )
    m = LlamaForCausalLM(cfg, dtype=jnp.float32, param_dtype=jnp.float32)
    params = m.init_weights(seed=0)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 32)), jnp.int32)

    def loss_fn(p):
        logits = m.apply(p, input_ids=ids).logits
        return jnp.mean(logits.astype(jnp.float32) ** 2)

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    gnorm = jax.jit(lambda g: jax.tree.reduce(jnp.add, jax.tree.map(lambda x: jnp.sum(x**2), g)))(grads)
    return float(loss), float(gnorm)


@pytest.mark.parametrize("use_scan", [True, False], ids=["scan", "unrolled"])
def test_all_granularities_numerically_identical(use_scan):
    base_loss, base_gnorm = _loss_and_grad("full", use_scan)
    for gran in GRANULARITIES[1:]:
        loss, gnorm = _loss_and_grad(gran, use_scan)
        np.testing.assert_allclose(loss, base_loss, rtol=1e-6, err_msg=gran)
        np.testing.assert_allclose(gnorm, base_gnorm, rtol=1e-4, err_msg=gran)


def _equations(jaxpr, inside_remat=False):
    """``(equation, is it inside a remat body)`` for a jaxpr and every jaxpr under
    it (scan bodies, pjit, custom_vjp, shard_map)."""
    for eqn in jaxpr.eqns:
        yield eqn, inside_remat
        inside = inside_remat or eqn.primitive.name == "remat2"  # jax.checkpoint's equation
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)  # a ClosedJaxpr holds one
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, inside)


LAYERS = 2
# the tiers that promise to keep the attention core keep what the kernel's
# backward reads, so their backward runs no forward kernel. ``full`` and
# ``save_dots`` (a kernel call is no dot) run it again, once a layer.
# ``full_attn`` and ``core_attn`` never ran it again, before the kernel named
# its residuals as after: jax's save_anything_except_these_names keeps every
# value without a name, the kernel's raw outputs among them, and recomputes
# only the names, which are identities.
RERUN_FORWARD = {"full": 1, "save_dots": 1, "full_attn": 0, "core_attn": 0,
                 "save_core_attn": 0, "save_qkv_attn": 0, "save_attn_mlp": 0, "offload_attn": 0}


# arrays of q's shape [batch, tokens, heads, head_dim] that the forward scan keeps
# for the backward, a layer: the kernel's output, and q where the tier saves it
SAVED_LIKE_Q = {"save_core_attn": 1, "save_qkv_attn": 2, "save_attn_mlp": 2}


@pytest.mark.parametrize("use_scan", [True, False], ids=["scan", "unrolled"])
@pytest.mark.parametrize("gran", GRANULARITIES)
def test_forward_kernels_in_the_backward(gran, use_scan, monkeypatch):
    """On the Pallas flash path the differentiated step holds one forward kernel
    a layer outside the remat bodies (the forward pass) and, inside them, as
    many as the tier says it recomputes. Nothing runs: the jaxpr is read."""
    _skip_without_offload(gran)
    cfg = LlamaConfig(
        vocab_size=128, hidden_size=128, intermediate_size=128, num_hidden_layers=LAYERS,
        num_attention_heads=2, num_key_value_heads=1, max_position_embeddings=128,
        recompute=True, recompute_granularity=gran, use_scan_layers=use_scan,
    )  # 2 / 1 heads of 64 over 128 tokens: a shape the kernel takes
    m = LlamaForCausalLM(cfg, dtype=jnp.float32, param_dtype=jnp.float32)
    params = m.init_weights(seed=0)
    ids = jnp.zeros((2, 128), jnp.int32)

    def loss_fn(p):
        return jnp.mean(m.apply(p, input_ids=ids).logits.astype(jnp.float32) ** 2)

    # the dispatcher asks jax.default_backend() whether to take the kernel
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    equations = list(_equations(jax.make_jaxpr(jax.grad(loss_fn))(params).jaxpr))
    counts = collections.Counter((eqn.params["name"], inside) for eqn, inside in equations
                                 if eqn.primitive.name == "pallas_call")
    if use_scan and gran in SAVED_LIKE_Q:
        # one saved copy of the kernel's output a layer: a second name on it
        # (the layer's own, say) would stack a second
        stacked = [v.aval.shape for eqn, _ in equations if eqn.primitive.name == "scan" for v in eqn.outvars]
        assert stacked.count((LAYERS, 2, 128, 2, 64)) == SAVED_LIKE_Q[gran]
    per_jaxpr = 1 if use_scan else LAYERS  # a scan's body stands once for every layer
    assert counts.pop(("flash_attention_fwd", False)) == per_jaxpr
    assert counts.pop(("flash_attention_fwd", True), 0) == RERUN_FORWARD[gran] * per_jaxpr
    assert counts == {("flash_attention_bwd_dq", True): per_jaxpr,
                      ("flash_attention_bwd_dkv", True): per_jaxpr}


def test_unknown_granularity_raises():
    with pytest.raises(ValueError, match="recompute_granularity"):
        _loss_and_grad("bogus", True)
