"""The plain reference of the held-expert latent-attention decoder (``bench/reference/deepseek_v3_moe.py``) against
hand-written arithmetic on a tiny case, the share against the whole, and its training trajectory against an AdamW
written out here. The program against this reference: tests/transformers/test_deepseek_v3.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import loader

TINY = dict(vocab_size=40, hidden_size=16, intermediate_size=24, moe_intermediate_size=8, num_hidden_layers=2,
            num_attention_heads=2, n_shared_experts=2, n_routed_experts=8, routed_scaling_factor=2.448, kv_lora_rank=8,
            qk_rope_head_dim=4, v_head_dim=6, qk_nope_head_dim=8, num_experts_per_tok=3, first_k_dense_replace=1,
            rope_theta=1e4, rms_norm_eps=1e-6, initializer_range=0.4)
SEED = 11


@pytest.fixture(scope="module")
def ref():
    return loader.module_from("reference", "deepseek_v3_moe")


def test_the_router_by_hand(ref):
    """Three tokens, four experts, top 2: sigmoid scores, the choice by score + bias, weights from the scores alone."""
    cfg = dict(TINY, num_experts_per_tok=2, n_routed_experts=4, routed_scaling_factor=2.0)
    logits = np.array([[2.0, 1.0, 0.0, -1.0], [0.0, 0.0, 3.0, 0.1], [-2.0, -1.0, -3.0, -4.0]], np.float32)
    x = jnp.eye(3, 16, dtype=jnp.float32)
    w = {"router_w": jnp.zeros((16, 4)).at[:3].set(logits), "router_b": jnp.asarray([0.0, 0.0, 0.0, 0.5])}
    idx, weights = ref.route(cfg, w, x)
    s = 1 / (1 + np.exp(-logits))
    # token 0: scores .88 .73 .5 .27, + bias -> .88 .73 .5 .77: experts 0 and 3, weighted by .88 and .27 (no bias)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 3] and sorted(np.asarray(idx[1]).tolist()) == [2, 3]
    assert sorted(np.asarray(idx[2]).tolist()) == [1, 3]  # .12 .27 .05 .02 + bias -> .12 .27 .05 .52
    for t, pair in enumerate(([0, 3], [2, 3], [1, 3])):
        got = dict(zip(np.asarray(idx[t]).tolist(), np.asarray(weights[t]).tolist()))
        for e in pair:
            assert got[e] == pytest.approx(s[t, e] / s[t, pair].sum() * 2.0, rel=1e-6)


def test_the_rotation_brings_interleaved_pairs_to_the_half_layout(ref):
    x = jnp.asarray(np.arange(2 * 1 * 4, dtype=np.float32).reshape(2, 1, 4))  # position 1: (4, 5), (6, 7)
    got = np.asarray(ref._rope_interleaved(x, 100.0))
    assert np.allclose(got[0, 0], [0, 2, 1, 3])  # position 0: the permutation alone
    a = np.array([1.0, 100.0 ** -0.5])  # the two pairs' angles at position 1
    want = np.concatenate([np.array([4, 6]) * np.cos(a) - np.array([5, 7]) * np.sin(a),
                           np.array([5, 7]) * np.cos(a) + np.array([4, 6]) * np.sin(a)])
    assert np.allclose(got[1, 0], want, atol=1e-6)


def test_attention_in_query_blocks_is_attention_at_once(ref, monkeypatch):
    w = ref.layer_weights(TINY, ref.layer_key(SEED, 0), jnp.float32, False)
    x = jax.random.normal(jax.random.key(1), (12, 16), jnp.float32)
    whole = ref.attention(TINY, w, x, "float32")
    monkeypatch.setattr(ref, "QUERY_BLOCK", 4)
    assert np.abs(np.asarray(ref.attention(TINY, w, x, "float32")) - np.asarray(whole)).max() < 1e-6
    # causal: a later token does not move an earlier one
    moved = ref.attention(TINY, w, x.at[9].add(1.0), "float32")
    assert np.abs(np.asarray(moved[:9]) - np.asarray(whole[:9])).max() < 1e-6
    assert np.abs(np.asarray(moved[9:]) - np.asarray(whole[9:])).max() > 1e-3


def test_the_expert_layer_is_the_sum_written_out_and_the_shares_tie_to_the_whole(ref):
    w = ref.layer_weights(TINY, ref.layer_key(SEED, 1), jnp.float32, True)
    x = jax.random.normal(jax.random.key(2), (10, 16), jnp.float32)
    idx, weights = ref.route(TINY, w, x)
    silu = lambda a: a / (1 + np.exp(-a))
    xn = np.asarray(x, np.float64)
    want = silu(xn @ np.asarray(w["s_gate"])) * (xn @ np.asarray(w["s_up"])) @ np.asarray(w["s_down"])
    for t in range(10):
        for e, wt in zip(np.asarray(idx[t]), np.asarray(weights[t])):
            act = silu(xn[t] @ np.asarray(w["e_gate"][e])) * (xn[t] @ np.asarray(w["e_up"][e]))
            want[t] += wt * (act @ np.asarray(w["e_down"][e]))
    uncut = np.asarray(ref.experts(TINY, w, x, "float32"))
    assert np.abs(uncut - want).max() < 1e-5
    shared = np.asarray(ref._swiglu(x, w["s_gate"], w["s_up"], w["s_down"], "float32"))
    total = shared.copy()
    for first in (0, 2, 4, 6):  # four shares of two experts: the router stays 8 wide
        share = dict(TINY, n_routed_experts=2, n_routed_experts_total=8, first_held_expert=first)
        ws = dict(w, **{k: w[k][first:first + 2] for k in ("e_gate", "e_up", "e_down")})
        total += np.asarray(ref.experts(share, ws, x, "float32")) - shared
    assert np.abs(total - uncut).max() < 1e-5


def test_the_parameter_tree_and_its_leaves(ref):
    share = dict(TINY, n_routed_experts=2, n_routed_experts_total=8, first_held_expert=4)
    tree = ref.program_params(share, ref.seed_array(SEED), jnp.float32)
    assert tree["model"]["layers_1"]["mlp"]["experts"]["gate_proj"].shape == (2, 16, 8)
    assert tree["model"]["layers_1"]["mlp"]["gate"]["kernel"].shape == (16, 8)
    assert tree["model"]["layers_0"]["mlp"]["gate_proj"]["kernel"].shape == (16, 24)
    assert tree["lm_head"]["kernel"].shape == (16, 40)
    leaves = ref.program_leaves(tree)
    assert len(leaves) == 3 + 10 + 15 and len(jax.tree.leaves(tree)) == len(leaves)
    own = ref._leafwise(ref.all_weights(share, ref.seed_array(SEED), jnp.float32))
    assert set(own) == set(leaves) and all(np.array_equal(np.asarray(own[k]), np.asarray(leaves[k])) for k in own)
    assert np.any(np.asarray(leaves["L1.router_b"])) and leaves["L1.router_b"].dtype == jnp.float32


def test_the_trajectory_is_adamw_written_out_and_the_control_moves_it(ref):
    optim = dict(adam_beta1=0.9, adam_beta2=0.999, adam_epsilon=1e-8, learning_rate=1e-2, weight_decay=0.1,
                 max_grad_norm=0.05)
    batches = [np.random.RandomState(s).randint(0, 40, (2, 8)) for s in range(2)]
    got = ref.train_trajectory(TINY, SEED, batches, optim)
    params = ref.all_weights(TINY, ref.seed_array(SEED), jnp.float32)
    start = ref._leafwise(params)
    flat = {k: np.asarray(v, np.float64) for k, v in start.items()}
    mu = {k: np.zeros_like(v) for k, v in flat.items()}
    nu = {k: np.zeros_like(v) for k, v in flat.items()}
    losses = []
    mean_loss = lambda p, batch: sum(ref._loss_sum(TINY, p, r, "float32") for r in batch) / (2 * 7)
    loss_and_grads = jax.jit(jax.value_and_grad(mean_loss))  # one compile for both steps, not an operation at a time
    for t, batch in enumerate(batches, 1):
        loss, grads = loss_and_grads(params, jnp.asarray(batch))
        losses.append(float(loss))
        g = {k: np.asarray(v, np.float64) for k, v in ref._leafwise(grads).items()}
        norm = np.sqrt(sum((v ** 2).sum() for v in g.values()))
        assert norm > 0.05  # the clip engages
        g = {k: v / norm * 0.05 for k, v in g.items()}
        if t == 1:
            first = {k: np.sqrt((v ** 2).sum()) for k, v in g.items()}
        for k in flat:
            mu[k] = 0.9 * mu[k] + 0.1 * g[k]
            nu[k] = 0.999 * nu[k] + 0.001 * g[k] ** 2
            step = (mu[k] / (1 - 0.9 ** t)) / (np.sqrt(nu[k] / (1 - 0.999 ** t)) + 1e-8)
            decay = 0.0 if k.split(".")[-1] in ("ln1", "ln2", "kv_ln", "norm", "router_b") else 0.1
            flat[k] = flat[k] - 1e-2 * (step + decay * flat[k])
        leaves = {k: jnp.asarray(v, jnp.float32) for k, v in flat.items()}
        params = {"layers": [{n: leaves[f"L{i}.{n}"] for n in lw} for i, lw in enumerate(params["layers"])],
                  **{k: leaves[k] for k in ref.GLOBAL_LEAVES}}
    assert np.allclose(got["losses"], losses, rtol=1e-5)
    for k in flat:
        assert got["first_grad_norm"][k] == pytest.approx(first[k], rel=1e-3, abs=1e-9), k
        delta = np.sqrt(((flat[k] - np.asarray(start[k], np.float64)) ** 2).sum())
        assert got["param_delta_norm"][k] == pytest.approx(delta, rel=2e-3, abs=1e-9), k
    assert got["first_grad_norm"]["L1.router_b"] == 0.0 and got["param_delta_norm"]["L1.router_b"] == 0.0
    low = ref.train_trajectory(TINY, SEED, batches, optim, precision="bfloat16")
    assert ref.worst_leaf_gap(low["param_delta_norm"], got["param_delta_norm"]) > 0.02  # bfloat16 state is not this
    assert ref.worst_leaf_gap(got["param_delta_norm"], got["param_delta_norm"]) == 0.0
