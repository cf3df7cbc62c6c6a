"""``latent_layers.route`` by softmax (``cfg.scoring_func == "softmax"``: the router of ``sdar_moe``) and the expert
layer behind it (``sdar_moe.modeling.sparse_mlp``: ``route`` -> ``experts_held_dense``, no shared expert) against
``moe_layers.MoEMLP`` (mixtral / qwen2-moe's layer: softmax over all experts, the k largest, normalised) on seeded
inputs, every expert held; and the sigmoid scoring beside it untouched."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlenlp_tpu.transformers import latent_layers as M
from paddlenlp_tpu.transformers.moe_layers import MoEMLP
from paddlenlp_tpu.transformers.sdar_moe.modeling import sparse_mlp

E, K, D, F = 8, 3, 32, 16


def layer(seed, norm=True):
    rng = np.random.RandomState(seed)
    params = {"gate": {"kernel": jnp.asarray(rng.standard_normal((D, E)) * 0.5, jnp.float32)},
              # the held experts' three matrices all [width, hidden] (latent_layers.experts_held_dense)
              "experts": {name: jnp.asarray(rng.standard_normal((E, F, D)) * 0.2, jnp.float32)
                          for name in ("gate_proj", "up_proj", "down_proj")}}
    cfg = types.SimpleNamespace(scoring_func="softmax", num_experts_per_tok=K, norm_topk_prob=norm, experts_held=(0, E),
                                num_local_experts=E, hidden_size=D, moe_intermediate_size=F, initializer_range=0.02)
    return params, cfg, jnp.asarray(rng.standard_normal((2, 9, D)), jnp.float32)


@pytest.mark.parametrize("norm", [True, False], ids=["norm_topk_prob", "raw"])
def test_softmax_routing_is_moe_mlps(norm):
    params, cfg, x = layer(0, norm)
    idx, w = M.route(params, x.reshape(-1, D), cfg)
    probs = jax.nn.softmax(x.reshape(-1, D) @ params["gate"]["kernel"], axis=-1)
    top, top_idx = jax.lax.top_k(probs, K)
    assert (np.asarray(idx) == np.asarray(top_idx)).all() and idx.dtype == jnp.int32
    want = top / top.sum(-1, keepdims=True) if norm else top
    np.testing.assert_allclose(np.asarray(w), np.asarray(want), atol=1e-6, rtol=0)
    # the whole layer with every expert held: MoEMLP's dense dispatch on the same weights
    moe = MoEMLP(cfg)
    theirs = {"params": {"gate": params["gate"], "w1": params["experts"]["gate_proj"].swapaxes(1, 2),
                         "w3": params["experts"]["up_proj"].swapaxes(1, 2), "w2": params["experts"]["down_proj"]}}
    out = moe.apply(theirs, x)
    out = out[0] if isinstance(out, tuple) else out
    got, chosen = sparse_mlp(params, x, cfg)  # 18 rows: every held expert on every row
    assert (np.asarray(chosen) == np.asarray(top_idx)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(out), atol=2e-6, rtol=0)


def test_sigmoid_routing_is_what_it_was():
    """A configuration that says nothing, or sigmoid, still routes by sigmoid scores plus the selection bias."""
    params, cfg, x = layer(1)
    params["e_score_correction_bias"] = jnp.asarray(np.random.RandomState(2).standard_normal(E) * 0.05, jnp.float32)
    x2d = x.reshape(-1, D)
    s = jax.nn.sigmoid(x2d @ params["gate"]["kernel"])
    _, want_idx = jax.lax.top_k(s + params["e_score_correction_bias"], K)
    chosen = jnp.take_along_axis(s, want_idx, -1)
    for cfg in (types.SimpleNamespace(num_experts_per_tok=K, routed_scaling_factor=2.5),
                types.SimpleNamespace(num_experts_per_tok=K, routed_scaling_factor=2.5, scoring_func="sigmoid")):
        idx, w = M.route(params, x2d, cfg)
        assert (np.asarray(idx) == np.asarray(want_idx)).all()
        np.testing.assert_allclose(np.asarray(w), np.asarray(chosen / chosen.sum(-1, keepdims=True) * 2.5), atol=1e-6)
