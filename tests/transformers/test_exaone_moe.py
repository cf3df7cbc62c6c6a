"""The windowed grouped-query layer kinds' mathematics (``transformers/window_layers.py``), the expert layer on the
sixteen shares of a sparse layer (``transformers/latent_layers.py``) and the exaone_moe whole-sequence module, at small
sizes on the CPU, float32."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import loader
from paddlenlp_tpu.transformers import ExaoneMoeConfig, ExaoneMoeForCausalLM
from paddlenlp_tpu.transformers import latent_layers as L
from paddlenlp_tpu.transformers import window_layers as W
from tests.experimental.test_window_serving import SMALL  # the small preset the engine tests serve

SEED = 5


@pytest.fixture(scope="module")
def ref():
    return loader.module_from("reference", "exaone_moe")


def test_the_module_is_the_reference(ref):
    m = ExaoneMoeForCausalLM(ExaoneMoeConfig(**SMALL))
    m.params = jax.jit(lambda s: ref.program_params(SMALL, s, jnp.float32))(ref.seed_array(SEED))
    want = jax.tree.map(lambda s: (s.shape, s.dtype), m.param_shapes)
    assert want == jax.tree.map(lambda a: (a.shape, a.dtype), m.params)  # the reference lays out the program's tree
    ids = np.random.RandomState(1).randint(0, 97, (2, 19))
    got = np.asarray(m(jnp.asarray(ids)))
    for row in range(2):
        assert np.abs(got[row] - np.asarray(ref.forward(SMALL, SEED, ids[row]))).max() < 5e-5


def test_the_sixteen_shares_of_a_sparse_layer_sum_to_the_uncut_layer(ref):
    """A layer of 16 routed experts and one shared: the program's expert layer on the shares (first, 1) for first =
    0 .. 15 gives sixteen partial results; their routed parts and the shared expert counted once add up to the
    reference's uncut layer (all 16 held)."""
    whole = dict(SMALL, num_experts=16, num_experts_total=16, first_held_expert=0)
    layer = 2  # a sparse layer
    w = {k: jnp.asarray(v, jnp.float32) for k, v in ref.layer_weights(whole, SEED, layer, jnp.float32).items()}
    u = jax.random.normal(jax.random.key(2), (40, SMALL["hidden_size"]), jnp.float32)
    uncut = ref.mlp(whole, SEED, layer, w, u, "float32")
    idx, _ = ref.route(whole, w, u)
    shared = {"gate_proj": {"kernel": w["sh_gate"]}, "up_proj": {"kernel": w["sh_up"]}, "down_proj": {"kernel": w["sh_down"]}}
    total = L.swiglu(shared, u)
    for first in range(16):
        share = dict(SMALL, num_experts=1, num_experts_total=16, first_held_expert=first)
        p = {"gate": {"kernel": w["router"]}, "e_score_correction_bias": w["router_bias"], "shared_experts": shared,
             "experts": ref.program_params(share, SEED, jnp.float32)["model"][f"layers_{layer}"]["mlp"]["experts"]}
        y, chosen = L.mlp(p, u, ExaoneMoeConfig(**share), layer)
        assert np.array_equal(np.asarray(chosen), np.asarray(idx))  # every share routes over all 16 alike
        total = total + (y - L.swiglu(shared, u))
    assert np.abs(total - uncut).max() < 2e-5


def test_window_layers_rotate_and_full_layers_do_not():
    cfg = ExaoneMoeConfig(**SMALL)
    d = cfg.attention_dims()
    assert d == dict(heads=8, kv_heads=2, head_dim=8, theta=1e6, window=8)
    key = jax.random.split(jax.random.key(0), 7)
    hidden = SMALL["hidden_size"]
    p = {"q_proj": {"kernel": jax.random.normal(key[0], (hidden, 64)) * 0.14},
         "k_proj": {"kernel": jax.random.normal(key[1], (hidden, 16)) * 0.14},
         "v_proj": {"kernel": jax.random.normal(key[2], (hidden, 16)) * 0.14},
         "o_proj": {"kernel": jax.random.normal(key[3], (64, hidden)) * 0.14},
         "q_norm": {"scale": 1 + jax.random.normal(key[4], (8,)) / 8}, "k_norm": {"scale": 1 + jax.random.normal(key[5], (8,)) / 8}}
    x = jax.random.normal(key[6], (1, 12, hidden))
    at = lambda shift: jnp.arange(12)[None, :] + shift
    full = lambda shift: W.project_qkv(p, x, at(shift), d, W.GQA_FULL, 1e-5)
    window = lambda shift: W.project_qkv(p, x, at(shift), d, W.GQA_WINDOW, 1e-5)
    # a full layer's q and k do not know their positions; a window layer's do, and only through their difference
    assert all(np.array_equal(a, b) for a, b in zip(full(0), full(5)))
    assert np.abs(window(0)[0] - window(5)[0]).max() > 0.1 and np.array_equal(window(0)[2], window(5)[2])
    scores = lambda qkv: np.einsum("btnh,bsnh->bnts", qkv[0], np.repeat(qkv[1], 4, axis=2))
    assert np.abs(scores(window(0)) - scores(window(5))).max() < 1e-4
    # the norm is over each head's dims with one scale for all heads: unit mean square before the scale
    q = np.asarray(full(0)[0]) / np.asarray(p["q_norm"]["scale"])
    assert np.allclose((q ** 2).mean(-1), 1.0, atol=1e-3)
    # the mask: causal, and a query sees itself and the 7 positions before it
    seen = np.asarray(W.window_mask(jnp.arange(12), jnp.arange(12), 8))
    assert seen[11].tolist() == [False] * 4 + [True] * 8 and seen[3].tolist() == [True] * 4 + [False] * 8
    assert np.array_equal(np.asarray(W.window_mask(jnp.arange(12), jnp.arange(12), None)), np.tril(np.ones((12, 12), bool)))


def test_the_configuration_yields_its_kinds_and_refuses_by_name():
    cfg = ExaoneMoeConfig()
    kinds = cfg.layer_kinds()
    assert kinds[:8] == ["gqa_window"] * 3 + ["gqa_full"] + ["gqa_window"] * 3 + ["gqa_full"] and len(kinds) == 48
    assert (kinds.count("gqa_window"), kinds.count("gqa_full")) == (36, 12)
    assert cfg.experts_held == (0, 128) and cfg.rope_theta == 1e6 and cfg.mlp_layer_types[:2] == ["dense", "sparse"]
    assert cfg.inference_model.endswith("window_model.WindowedInferenceModel")
    for bad, named in (({"layer_types": ["linear_attention"] * 48}, "no layer kind computes"),
                       ({"scoring_func": "softmax"}, "sigmoid"), ({"n_group": 2}, "group-limited"),
                       ({"num_shared_experts": 2}, "one shared expert"), ({"hidden_act": "gelu"}, "SwiGLU"),
                       ({"layer_types": ["full_attention"] * 47}, "47 entries for 48 layers"),
                       ({"sliding_windows": [64] * 48}, "one window for every sliding_attention layer"),
                       ({"mlp_layer_types": ["sparse"] * 48}, "first_k_dense_replace"),
                       ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}}, "default rotary"),
                       ({"num_experts": 8, "num_experts_total": 128, "first_held_expert": 124}, "lie outside")):
        with pytest.raises(ValueError, match=named):
            ExaoneMoeConfig(**bad)


def test_auto_classes_build_the_model_from_published_keys(tmp_path):
    from paddlenlp_tpu.transformers import AutoConfig
    from paddlenlp_tpu.transformers.auto.modeling import AutoModelForCausalLM

    path = os.path.join(os.path.dirname(__file__), "..", "..", "bench", "configs", "k-exaone-serve-ep16.json")
    with open(path) as f:
        published = {k: v for k, v in json.load(f).items() if k != "bench"}
    with open(tmp_path / "config.json", "w") as f:
        json.dump(published, f)
    cfg = AutoConfig.from_pretrained(str(tmp_path))
    assert type(cfg) is ExaoneMoeConfig and cfg.experts_held == (0, 8) and cfg.num_experts_total == 128
    assert cfg.layer_kinds() == ["gqa_window"] * 3 + ["gqa_full"] + ["gqa_window"] * 3 + ["gqa_full"]
    assert cfg.attention_dims() == dict(heads=64, kv_heads=8, head_dim=128, theta=1e6, window=128)
    assert cfg.num_nextn_predict_layers == 1 and cfg.mtp_layer_types == ["full_attention"]  # accepted, not loaded
    small = AutoModelForCausalLM.from_config(ExaoneMoeConfig(**SMALL))
    assert type(small) is ExaoneMoeForCausalLM
