"""The plain reference of sdar_moe (``bench/reference/sdar_moe.py``) at a small size on the CPU: its block mask against
a dense mask written out by hand, both copies of a served row; ``served_gaps`` on the reference's own ``generate``
output and on a corrupted token; **the share test** (the parts that all 8 shares of the experts give add up to the
uncut layer's result); the program's parameter tree and module forward against it; and the configuration file's bytes
recomputed from its keys."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import loader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CFG = json.load(open(os.path.join(ROOT, "bench", "configs", "sdar-30b-a3b-serve-ep8.json")))
SMALL = dict(
    vocab_size=96, hidden_size=64, intermediate_size=128, moe_intermediate_size=32, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, num_experts=2, num_experts_total=16, first_held_expert=6,
    num_experts_per_tok=3, norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1000000.0, initializer_range=0.125,
    block_length=4, denoising_steps=4, remasking="low_confidence_static", confidence_threshold=0.9, mask_token_id=95)
SEED = 5
TOL = 5e-5  # float32 on both sides in another order of summation; logits of std 1 agree to a few 1e-6


@pytest.fixture(scope="module")
def ref():
    return loader.module_from("reference", "sdar_moe")


def ids(n, seed=0):
    return np.random.RandomState(seed).randint(0, SMALL["mask_token_id"], n).astype(np.int32)


# ------------------------------------------------------------------ the mask
def test_the_block_mask_against_a_dense_mask_written_out_by_hand(ref):
    """One clean sequence of 10 positions, blocks of 4: 0-3 see 0-3, 4-7 see 0-7, 8-9 see 0-9 (the last block is
    short: the sequence ends inside it)."""
    want = np.zeros((10, 10), bool)
    want[0:4, 0:4] = want[4:8, 0:8] = want[8:10, 0:10] = True
    assert (np.asarray(ref.visible(ref.rows_of(10, 4))) == want).all()
    # from another start the blocks are still counted from position 0
    assert (np.asarray(ref.visible(ref.rows_of(4, 4, start=6))) == np.asarray(
        [[1, 1, 0, 0], [1, 1, 0, 0], [1, 1, 1, 1], [1, 1, 1, 1]], bool)).all()


def test_the_two_copy_mask_written_out_by_hand(ref):
    """A prompt of 5 and 6 served tokens: clean copy 12 rows (5 + 6 and one of padding to a whole block), noised
    copy the generated blocks [4, 8) and [8, 12); a noised row of block b sees the clean rows of the blocks before b
    and the noised rows of b; a clean row sees clean rows only; nobody sees the bucket's padding."""
    clean, rows, n_clean, blocks = ref._replay_rows(SMALL, list(range(10, 15)), list(range(20, 26)))
    assert clean == [10, 11, 12, 13, 14, 20, 21, 22, 23, 24, 25, 0] and n_clean == 12
    assert blocks == [(4, 1, [20, 21, 22]), (8, 0, [23, 24, 25])]  # first position, fixed, served tokens of the block
    seen = np.asarray(ref.visible(rows))
    assert rows["pos"][12:20].tolist() == [4, 5, 6, 7, 8, 9, 10, 11] and len(rows["pos"]) == 512
    want = np.zeros((20, 20), bool)
    for i in range(12):  # the clean copy among itself: causal over blocks
        want[i, : (i // 4 + 1) * 4] = True
    want[12:16, 0:4] = want[12:16, 12:16] = True   # noised block 1: clean block 0 and itself
    want[16:20, 0:8] = want[16:20, 16:20] = True   # noised block 2: clean blocks 0 and 1 and itself
    assert (seen[:20, :20] == want).all() and not seen[:, 20:].any()


# ------------------------------------------------------------------ generate and served_gaps
@pytest.fixture(scope="module")
def generated(ref):
    prompts = [ids(9, 1).tolist(), ids(6, 2).tolist(), ids(8, 3).tolist()]
    return prompts, [ref.generate(SMALL, SEED, p, n)[0] for p, n in zip(prompts, (10, 7, 8))]


def test_served_gaps_reads_zero_on_the_references_own_output(ref, generated):
    prompts, outs = generated
    rows = ref.served_gaps(SMALL, SEED, list(zip(prompts, outs)), "float32", control="int8")
    assert [len(r["gaps"]) for r in rows] == [10, 7, 8] == [len(r["control_gaps"]) for r in rows]
    assert all((r["gaps"] == 0).all() for r in rows)
    assert all((r["control_gaps"] >= 0).all() for r in rows)


def test_served_gaps_reads_more_on_a_corrupted_token(ref, generated):
    prompts, outs = generated
    bad = list(outs[0])
    bad[4] = (bad[4] + 1) % SMALL["mask_token_id"]
    (row,) = ref.served_gaps(SMALL, SEED, [(prompts[0], bad)], "float32")
    assert row["gaps"][4] > 1e-3 and (row["gaps"] >= 0).all()


def test_generate_counts_its_passes(ref):
    """Prompt 9 (1 fixed position), 10 tokens: blocks of 3, 4 and 4 new tokens of which 3 are emitted; 3 + 4 + 4
    denoising passes and a commit pass a block; the mask id is fed where masked and never emitted."""
    out, passes = ref.generate(SMALL, SEED, ids(9, 1).tolist(), 10)
    assert len(out) == 10 and SMALL["mask_token_id"] not in out
    assert [p["kind"] for p in passes] == (["denoise"] * 3 + ["commit"] + (["denoise"] * 4 + ["commit"]) * 2)
    assert passes[0]["start"] == 8 and passes[0]["fed"][1:] == [95] * 3 and passes[0]["fed"][0] == ids(9, 1)[8]
    assert [int(p["masked"].sum()) for p in passes[:4]] == [3, 2, 1, 0]
    # under the dynamic rule a threshold nothing passes is the static rule
    assert ref.generate(dict(SMALL, remasking="low_confidence_dynamic"), SEED, ids(9, 1).tolist(), 10)[0] == out


def test_to_unmask_rules(ref):
    conf, masked = [0.5, 0.95, 0.2, 0.97], np.asarray([True, True, True, False])
    assert ref.to_unmask(SMALL, conf, masked).tolist() == [False, True, False, False]  # the most confident masked
    dyn = dict(SMALL, remasking="low_confidence_dynamic")
    assert ref.to_unmask(dyn, [0.93, 0.95, 0.2, 0.97], masked).tolist() == [True, True, False, False]  # all over 0.9
    assert ref.to_unmask(dyn, [0.5, 0.6, 0.2, 0.97], masked).tolist() == [False, True, False, False]  # never fewer
    assert ref.to_unmask(SMALL, [0.5, 0.5, 0.5, 0.5], np.ones(4, bool)).tolist() == [True, False, False, False]  # the earlier


# ------------------------------------------------------------------ the share
def test_the_parts_all_eight_shares_give_add_up_to_the_uncut_layer(ref):
    """Guide section 4's test: 16 experts 8 ways, 2 a share. Routing and the normalising sum are over all 16 in every
    share; the shares' parts of the expert layer add up to what the uncut layer (all 16 held) gives; and a share's
    part is what the program's expert layer computes for that share."""
    from paddlenlp_tpu.transformers import SdarMoeConfig
    from paddlenlp_tpu.transformers.sdar_moe.modeling import sparse_mlp

    x = jnp.asarray(np.random.RandomState(7).standard_normal((24, SMALL["hidden_size"])), jnp.float32)
    w = ref.layer_weights(SMALL, SEED, 1, jnp.float32)
    idx, wts = ref.route(SMALL, w, x)
    assert idx.shape == (24, 3) and np.allclose(np.asarray(wts).sum(-1), 1.0, atol=1e-6)
    whole = ref.routed_part(SMALL, SEED, 1, idx, wts, x, "float32", experts=(0, 16))
    parts = [ref.routed_part(SMALL, SEED, 1, idx, wts, x, "float32", experts=(first, 2)) for first in range(0, 16, 2)]
    assert np.abs(np.asarray(sum(parts) - whole)).max() < 2e-6 and np.abs(np.asarray(whole)).max() > 0.1
    assert all(np.abs(np.asarray(p)).max() > 1e-3 for p in parts)  # every share has tokens of its own
    # the configured share (experts 6 and 7) through the program's expert layer
    cfg = SdarMoeConfig(**SMALL)
    params = jax.tree.map(lambda a: a[1], ref.program_params(SMALL, ref.seed_array(SEED), jnp.float32)["model"]["layers"])
    got, chosen = sparse_mlp(params["mlp"], x, cfg)
    assert (np.asarray(chosen) == np.asarray(idx)).all()
    assert np.abs(np.asarray(got - parts[3])).max() < 2e-6


# ------------------------------------------------------------------ the program's tree and module
def test_the_modules_forward_agrees_with_the_reference(ref):
    """``program_params`` is the module's own tree, and the whole-sequence module under the block mask (no cache)
    gives the reference's logits: 11 positions, the last block short."""
    from paddlenlp_tpu.transformers import SdarMoeConfig, SdarMoeForCausalLM

    model = SdarMoeForCausalLM(SdarMoeConfig(**SMALL))
    params = jax.jit(lambda s: ref.program_params(SMALL, s, jnp.float32))(ref.seed_array(SEED))
    assert jax.tree.map(lambda s: (s.shape, s.dtype), model.param_shapes) == jax.tree.map(
        lambda a: (a.shape, a.dtype), params)
    model.params = params
    tokens = ids(11, 4)
    want = np.asarray(ref.forward(SMALL, SEED, tokens))
    got = np.asarray(model(jnp.asarray(tokens[None]))[0])
    assert want.std() > 0.5 and np.abs(got - want).max() < TOL
    # not the causal rule: position 0 sees positions 1-3, so its logits move with the token at 3
    other = tokens.copy()
    other[3] = (other[3] + 1) % 95
    assert np.abs(np.asarray(ref.forward(SMALL, SEED, other))[0] - want[0]).max() > 1e-2


# ------------------------------------------------------------------ the configuration file
def test_the_configuration_files_bytes_recomputed_from_its_keys(ref):
    """9.24 GB of bf16 weights and 98,304 B of K and V a token (ISSUE 44 writes 196,608: its product 48 x 2 x 4 x 128
    x 2 B is 98,304), the published widths, the reduced keys, the assumed sizes."""
    assert ref.weight_bytes(CFG) == 2 * (48 * (18_874_368 + 262_144 + 16 * 4_718_592) + 2 * 18_992 * 2048) == 9_240_444_928
    assert ref.kv_bytes_a_token(CFG) == 48 * 2 * 4 * 128 * 2 == 98_304
    e = CFG["bench"]["engine"]
    pool = (e["num_blocks"] - 1) * e["block_size"] * ref.kv_bytes_a_token(CFG)
    assert 4.0e9 < pool < 4.1e9 and 13.2e9 < ref.weight_bytes(CFG) + pool < 13.4e9  # over 25% of the chip's 16 GB
    for text in ("9.24 GB", "98,304 B a token", "8 chips"):
        assert text in CFG["bench"]["deployment"]
    catalog = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
               "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768, "max_window_layers": 48,
               "mlp_only_layers": [], "model_type": "sdar_moe", "moe_intermediate_size": 768, "norm_topk_prob": True,
               "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
               "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
               "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936}
    assert sorted(k for k, v in catalog.items() if CFG[k] != v) == ["num_experts", "vocab_size"] == sorted(CFG["bench"]["reduced"])
    assert (CFG["num_experts"], CFG["num_experts_total"], CFG["vocab_size"] * 8) == (16, 128, 151936)
    assert (CFG["block_length"], CFG["denoising_steps"], CFG["remasking"], CFG["mask_token_id"]) == (
        4, 4, "low_confidence_static", CFG["vocab_size"] - 1)
    assumed = " ".join(CFG["bench"]["assumed"])
    for text in ("block_length 4", "denoising_steps 4", "low_confidence_static", "151,669", "RMSNorm over each head",
                 "softmax", "block mask in prefill", "commit pass kept", "initializer_range 0.02", "no EOS", "text only"):
        assert text in assumed, text
