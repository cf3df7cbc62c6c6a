"""The exaone_moe yardstick is itself tested: the plain reference against the repo's own ExaoneMoeForCausalLM at a tiny
size in float32 (tree, forward, the serving comparison), its int8 control, that it imports nothing of the program, and
the configuration file against the catalog's published keys."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import loader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
L, G = "sliding_attention", "full_attention"
CFG = dict(
    vocab_size=97, hidden_size=48, intermediate_size=96, moe_intermediate_size=24, num_hidden_layers=8,
    layer_types=[L, L, L, G, L, L, L, G], mlp_layer_types=["dense"] + ["sparse"] * 7,
    sliding_windows=[8, 8, 8, 0, 8, 8, 8, 0], sliding_window=8, num_attention_heads=8, num_key_value_heads=2, head_dim=8,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"}, first_k_dense_replace=1, num_experts=4,
    num_experts_total=16, first_held_expert=4, num_shared_experts=1, num_experts_per_tok=3,
    routed_scaling_factor=2.5, rms_norm_eps=1e-5, initializer_range=0.14)
SEED = 7
FILE = os.path.join(ROOT, "bench", "configs", "k-exaone-serve-ep16.json")


@pytest.fixture(scope="module")
def ref():
    return loader.module_from("reference", "exaone_moe")


@pytest.fixture(scope="module")
def model(ref):
    from paddlenlp_tpu.transformers import ExaoneMoeConfig, ExaoneMoeForCausalLM

    m = ExaoneMoeForCausalLM(ExaoneMoeConfig(**CFG), dtype=jnp.float32, param_dtype=jnp.float32)
    m.params = jax.jit(lambda s: ref.program_params(CFG, s, jnp.float32))(ref.seed_array(SEED))
    return m


def test_parameter_tree_is_the_programs_and_holds_the_references_numbers(ref, model):
    want = jax.tree.map(lambda s: (s.shape, s.dtype), model.param_shapes)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), model.params) == want
    one = ref.layer_weights(CFG, SEED, 3, jnp.float32)
    attn = model.params["model"]["layers_3"]["self_attn"]
    assert np.array_equal(np.asarray(attn["k_norm"]["scale"]), np.asarray(one["k_norm"])) and one["k_norm"].shape == (8,)
    held = np.asarray(model.params["model"]["layers_1"]["mlp"]["experts"]["up_proj"])
    sixth = ref.expert_weights(CFG, SEED, 1, 6, jnp.float32)["up"]  # the model's expert 6 is the third held (4..7)
    assert np.allclose(held[2], np.asarray(sixth), rtol=1e-6, atol=0)
    assert "experts" not in model.params["model"]["layers_0"]["mlp"]  # the leading layer is dense


def test_forward_agrees_with_the_program_and_the_serving_comparison_reads_it(ref, model):
    ids = np.random.default_rng(0).integers(0, CFG["vocab_size"], (1, 48)).astype(np.int32)
    logits = np.asarray(model(jnp.asarray(ids)))[0]
    assert np.abs(np.asarray(ref.forward(CFG, SEED, ids[0])) - logits).max() < 5e-5
    seqs = [(ids[0, :40].tolist(), ids[0, 40:48].tolist()), (ids[0, :15].tolist(), ids[0, 15:22].tolist())]
    rows = ref.served_gaps(CFG, SEED, seqs, "float32")
    want = logits[39:47]
    gaps = want.max(-1) - want[np.arange(8), ids[0, 40:48]]
    assert np.allclose(rows[0]["gaps"], gaps, atol=5e-5)
    short = np.asarray(ref.forward(CFG, SEED, ids[0, :22]))[14:21]
    assert np.allclose(rows[1]["gaps"], short.max(-1) - short[np.arange(7), ids[0, 15:22]], atol=5e-5)
    assert gaps.max() > 0.1  # random tokens are not the best ones: the number moves when a token is altered


def test_the_window_is_an_explicit_mask_and_only_window_layers_rotate(ref):
    w = {k: jnp.asarray(v, jnp.float32) for k, v in ref.layer_weights(CFG, SEED, 1, jnp.float32).items()}
    x = jax.random.normal(jax.random.key(1), (24, CFG["hidden_size"]), jnp.float32)
    window = ref.attention(CFG, L, w, x)
    # the first 8 queries see everything before them: the window changes nothing there, and everything after
    causal = ref.attention(CFG, L, w, x, window=24)
    assert np.abs(window[:8] - causal[:8]).max() < 1e-6 and np.abs(window[8:] - causal[8:]).max() > 1e-3
    assert np.abs(window - ref.attention(CFG, L, w, x, window=7)).max() > 1e-3
    # a full layer of the same weights: no window and no rotation, and rotation alone moves the result
    full = ref.attention(CFG, G, w, x)
    assert np.abs(full - ref.attention(CFG, G, w, x, rotate=True)).max() > 1e-3
    assert np.abs(ref.attention(CFG, L, w, x, window=24) - ref.attention(CFG, G, w, x, rotate=True)).max() < 1e-6
    # queries in blocks of 5 and of 24 give the same sums in another order
    assert np.abs(ref.attention(CFG, L, w, x, q_block=5)[:20] - window[:20]).max() < 1e-5


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
    src = open(os.path.join(ROOT, "bench", "reference", "exaone_moe.py")).read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w\.]+)", src, re.M)
    assert imports and all(not m.startswith(("paddlenlp_tpu", "bench", ".")) for m in imports), imports
    assert "window_layers" not in src and "latent_layers" not in src


def test_the_configuration_file_holds_every_published_number():
    doc = json.load(open(FILE))
    period = [L, L, L, G]
    published = {
        "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 6144,
        "intermediate_size": 18432, "layer_types": period * 12, "max_position_embeddings": 262144,
        "mlp_layer_types": ["dense"] + ["sparse"] * 47, "model_type": "exaone_moe", "moe_intermediate_size": 2048,
        "mtp_layer_types": [G], "mtp_sliding_windows": [0], "n_group": 1, "norm_topk_prob": True,
        "num_attention_heads": 64, "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 8, "num_nextn_predict_layers": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "sliding_window": 128, "sliding_window_pattern": "LLLG",
        "sliding_windows": [128, 128, 128, 0] * 12, "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 153600}
    differs = sorted(k for k, v in published.items() if doc.get(k, "absent") != v)
    assert differs == sorted(doc["bench"]["reduced"]) == sorted(
        ["num_hidden_layers", "layer_types", "mlp_layer_types", "sliding_windows", "num_experts", "vocab_size"])
    entry = next(c for c in loader.manifest()["configs"] if c["name"] == "k-exaone-serve-ep16")
    assert sorted(entry["reduced"]) == differs and entry["source"] == doc["bench"]["source"]
    for key in ("layer_types", "mlp_layer_types", "sliding_windows"):
        assert doc[key] == published[key][:8]
    assert (doc["num_experts"], doc["num_experts_total"], doc["first_held_expert"]) == (8, 128, 0)
    assert doc["vocab_size"] * 8 == published["vocab_size"] and doc["num_hidden_layers"] == 8
    b = doc["bench"]
    assert b["assumed"][0].startswith("THE RESIDUAL FORM IS PRE-NORM") and "16 chips share each stage's layers" in b["deployment"]
    assert "1/16 of its deployment's tokens" in b["deployment"] and "10.5 GB" in b["deployment"]
    assert b["require_paged_kernel"] is True and b["precision"]["control"] == "int8" and b["precision"]["control_engine"] == {}
    assert b["engine"] == {"max_batch_size": 16, "block_size": 16, "num_blocks": 17408, "max_blocks_per_seq": 1088,
                           "decode_steps": 8, "prefill_chunk_tokens": 1024, "enable_prefix_cache": False,
                           "eos_token_id": []}


def test_the_parameter_and_cache_bytes_are_the_configuration_files():
    """3.87 B parameters (7.73 GB in bfloat16), 2.28 GB of full-layer K/V and 0.47 GB of window planes: the byte
    counts the file states, from the program's own tree and pool."""
    from paddlenlp_tpu.experimental.paged_cache import init_window_pool
    from paddlenlp_tpu.transformers import ExaoneMoeConfig
    from paddlenlp_tpu.transformers.exaone_moe.modeling import param_tree_shapes

    doc = json.load(open(FILE))
    cfg = ExaoneMoeConfig(**{k: v for k, v in doc.items() if k not in ("bench", "architectures", "torch_dtype", "model_type")})
    count = lambda tree: sum(int(np.prod(s)) for s in jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, tuple)))
    shapes = param_tree_shapes(cfg)
    assert 3.865e9 < count(shapes) < 3.875e9
    norms = 2 * 6144 + 2 * 128  # each layer's two block norms and its q and k norms are counted with it here
    assert count(shapes["model"]["layers_0"]) == 452_984_832 + norms  # ISSUE 35: 453.0 M
    assert count(shapes["model"]["layers_1"]) == 151_781_376 + 128 + 8 * 37_748_736 + norms  # 151.8 M + the bias + 8 experts
    e = doc["bench"]["engine"]
    pool = jax.eval_shape(lambda: init_window_pool(2, 6, e["num_blocks"], 16 * 74 + 1, e["block_size"], 8 * 128, 5))
    size = lambda a: a.size * a.dtype.itemsize
    assert size(pool.kv) == 2_281_701_376 and size(pool.win) == 465_960_960
