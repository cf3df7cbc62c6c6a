"""deepseek_v3 on the training path, at small sizes on the CPU, float32: the module against the plain reference
(``bench/reference/deepseek_v3_moe.py``), the held-expert layer with a backward (``latent_layers.experts_grouped``) on
shares of a layer and under a rigged imbalance, the router's bias under AdamW, and three ``Trainer`` steps against the
reference's trajectory (the check the benchmark's cell makes on the chip).

Tolerances. Both sides are float32 on the CPU; the reference asks for ``highest`` precision, which on the CPU is what
float32 already is. They differ in the order of sums (attention in query blocks there, one softmax here; an expert's
rows grouped here, every token times a zero-or-weight there), so logits agree to a few 1e-6 of values of order 1 and a
gradient to 1e-5 of its leaf's largest entry; 5e-5 leaves room and is far under any wrong term (a dropped assignment
moves a token's output by its expert's whole contribution, order 1e-1 at these weights)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import loader
from paddlenlp_tpu.transformers import AutoConfig, AutoModelForCausalLM, DeepseekV3Config, DeepseekV3ForCausalLM
from paddlenlp_tpu.transformers import latent_layers as L

SEED = 7
TINY = dict(vocab_size=96, hidden_size=32, intermediate_size=48, moe_intermediate_size=16, num_hidden_layers=2,
            num_attention_heads=2, num_key_value_heads=2, n_shared_experts=2, n_routed_experts=16, routed_scaling_factor=2.448,
            kv_lora_rank=16, q_lora_rank=None, qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16, qk_head_dim=24,
            head_dim=8, n_group=1, topk_group=1, num_experts_per_tok=3, first_k_dense_replace=1, moe_layer_freq=1,
            norm_topk_prob=True, scoring_func="sigmoid", topk_method="noaux_tc", rope_interleave=True, rope_theta=1e6,
            rope_scaling=None, rms_norm_eps=1e-6, hidden_act="silu", attention_bias=False, tie_word_embeddings=False,
            max_position_embeddings=64, initializer_range=0.3)  # 0.3: the layers, not the embedding, decide a logit
TOL = 5e-5


@pytest.fixture(scope="module")
def ref():
    return loader.module_from("reference", "deepseek_v3_moe")


def _model(ref, cfg_dict, **kw):
    m = DeepseekV3ForCausalLM(DeepseekV3Config(**cfg_dict, **kw))
    m.params = jax.jit(lambda s: ref.program_params(cfg_dict, s, jnp.float32))(ref.seed_array(SEED))
    want = jax.tree.map(lambda s: (s.shape, s.dtype), m.param_shapes)
    assert want == jax.tree.map(lambda a: (a.shape, a.dtype), m.params)  # the reference lays out the program's tree
    return m


def _rel(got, want):
    return float(np.abs(np.asarray(got) - np.asarray(want)).max() / max(float(np.abs(np.asarray(want)).max()), 1e-12))


def test_module_is_the_reference_logits_loss_and_every_gradient(ref):
    m = _model(ref, TINY)
    ids = np.random.RandomState(1).randint(0, TINY["vocab_size"], (2, 16)).astype(np.int32)
    params = jax.jit(lambda s: ref.all_weights(TINY, s, jnp.float32))(ref.seed_array(SEED))
    logits = np.asarray(m(jnp.asarray(ids)).logits)
    forward = jax.jit(lambda p, row: ref.forward(TINY, p, row))
    for row in range(2):
        assert np.abs(logits[row] - np.asarray(forward(params, jnp.asarray(ids[row])))).max() < TOL

    def program_loss(p):
        lg = m.module.apply({"params": p}, jnp.asarray(ids)).logits.astype(jnp.float32)[:, :-1]
        picked = jnp.take_along_axis(lg, jnp.asarray(ids)[:, 1:, None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(lg, -1) - picked)

    loss, grads = jax.jit(jax.value_and_grad(program_loss))(m.params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: sum(ref._loss_sum(TINY, p, jnp.asarray(r), "float32") for r in ids)))(params)
    assert abs(float(loss) - float(want_loss)) / float(want_loss) < 1e-6
    got, want = ref.program_leaves(grads), ref._leafwise(want)
    assert set(got) == set(want) and len(got) == 3 + 10 + 15
    for name in want:
        if name.endswith("router_b"):  # the bias enters the choice only: no gradient on either side
            assert not np.any(np.asarray(got[name])) and not np.any(np.asarray(want[name]))
        else:
            assert _rel(got[name], want[name]) < TOL, name


def _moe_params(ref, cfg_dict, layer=1):
    w = ref.layer_weights(cfg_dict, ref.layer_key(SEED, layer), jnp.float32, True)
    p = {"gate": {"kernel": w["router_w"]}, "e_score_correction_bias": w["router_b"]}
    return w, p, {"gate_proj": w["e_gate"], "up_proj": w["e_up"], "down_proj": w["e_down"]}


def test_four_shares_of_four_experts_add_up_to_the_uncut_layer_and_its_input_gradient(ref):
    """The held experts' part on the shares (first, 4), first = 0, 4, 8, 12, added up, plus the shared expert once,
    is the reference's uncut expert layer (all 16 held); the same for the gradient with respect to the layer's input."""
    w, p, experts = _moe_params(ref, TINY)
    cfg = DeepseekV3Config(**TINY)
    x = jax.random.normal(jax.random.key(3), (40, TINY["hidden_size"]), jnp.float32)
    probe = jax.random.normal(jax.random.key(4), x.shape, jnp.float32)

    def uncut(x):
        return jnp.sum(ref.experts(TINY, w, x, "float32") * probe)

    def by_shares(x):
        idx, wts = L.route(p, x, cfg)
        total = ref._swiglu(x, w["s_gate"], w["s_up"], w["s_down"], "float32")
        for first in range(0, 16, 4):
            held = {k: v[first:first + 4] for k, v in experts.items()}
            y, counted = L.experts_grouped(held, x, idx, wts, first, 4, 16)
            total = total + y
        return jnp.sum(total * probe)

    (a, ga), (b, gb) = jax.jit(jax.value_and_grad(by_shares))(x), jax.jit(jax.value_and_grad(uncut))(x)
    assert abs(float(a) - float(b)) < TOL * max(1.0, abs(float(b)))
    assert _rel(ga, gb) < TOL


def test_no_assignment_is_dropped_when_one_held_expert_receives_them_all(ref):
    """A router rigged so that every token's first choice is held expert 5 and its other two are not held: expert 5
    receives as many rows as there are tokens (8 times an even load, 4 chunks of the layer's walk), and the result
    and the gradients of the input and of the expert's matrices are the reference's."""
    share = dict(TINY, n_routed_experts=4, n_routed_experts_total=16, first_held_expert=4)
    w, p, experts = _moe_params(ref, share)
    bias = np.full(16, -10.0, np.float32)
    bias[[5, 0, 1]] = [30.0, 20.0, 10.0]  # score + bias picks 5, 0, 1 whatever the scores are; 0 and 1 are not held
    w = dict(w, router_b=jnp.asarray(bias))
    p = dict(p, e_score_correction_bias=w["router_b"])
    cfg = DeepseekV3Config(**share)
    n = 64
    x = jax.random.normal(jax.random.key(5), (n, TINY["hidden_size"]), jnp.float32)
    probe = jax.random.normal(jax.random.key(6), x.shape, jnp.float32)

    def program(x, experts):
        idx, wts = L.route(p, x, cfg)
        y, counted = L.experts_grouped(experts, x, idx, wts, 4, 4, 16)
        return jnp.sum(y * probe), counted

    def reference(x, experts):
        ww = dict(w, e_gate=experts["gate_proj"], e_up=experts["up_proj"], e_down=experts["down_proj"])
        y = ref.experts(share, ww, x, "float32") - ref._swiglu(x, w["s_gate"], w["s_up"], w["s_down"], "float32")
        return jnp.sum(y * probe)

    (a, counted), ga = jax.jit(jax.value_and_grad(program, argnums=(0, 1), has_aux=True))(x, experts)
    b, gb = jax.jit(jax.value_and_grad(reference, argnums=(0, 1)))(x, experts)
    assert float(counted["expert_assignments_local"]) == n and float(counted["expert_tokens_max"]) == n
    assert abs(float(a) - float(b)) < TOL * max(1.0, abs(float(b)))
    assert _rel(ga[0], gb[0]) < TOL
    for k in experts:
        assert _rel(ga[1][k], gb[1][k]) < TOL, k
        assert not np.any(np.asarray(ga[1][k])[[0, 2, 3]])  # an expert nobody chose has no gradient


@pytest.mark.parametrize("first,count", [(0, 16), (4, 4), (12, 4)])
def test_product_rows_are_the_held_assignments_plus_tile_padding(ref, first, count):
    """No product for a pair the router did not choose, and none left out: the rows the grouped products cover are
    counted as the kernel's grid counts them, and lie between the held assignments and those plus one row tile a held
    expert (a group's first and last tiles may be partial)."""
    import importlib

    share = dict(TINY, n_routed_experts=count, n_routed_experts_total=16, first_held_expert=first)
    _, p, experts = _moe_params(ref, share)
    x = jax.random.normal(jax.random.key(8), (56, TINY["hidden_size"]), jnp.float32)
    idx, wts = L.route(p, x, DeepseekV3Config(**share))
    _, counted = L.experts_grouped(experts, x, idx, wts, first, count, 16)
    local = int(np.sum((np.asarray(idx) >= first) & (np.asarray(idx) < first + count)))
    rows, tile = int(counted["expert_product_rows"]), 8
    assert int(counted["expert_assignments_local"]) == local
    assert local <= rows <= local + 2 * count * tile and rows % tile == 0
    # the count is the kernel's own: megablox sizes its grid by the same arithmetic
    backend = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")
    sizes = jnp.asarray(np.bincount(np.sort(np.where((np.asarray(idx) >= first) & (np.asarray(idx) < first + count),
                                                     np.asarray(idx) - first, count).ravel()), minlength=count + 1),
                        jnp.int32)
    m = -(-sizes.sum() // tile) * tile
    sizes = sizes.at[count].add(m - sizes.sum())
    _, steps = backend.make_group_metadata(group_sizes=sizes, m=int(m), tm=tile, start_group=jnp.zeros((), jnp.int32),
                                           num_nonzero_groups=count, visit_empty_groups=False)
    assert int(L._product_rows(sizes, int(m), count)) == int(steps) * tile


def _trainer(ref, cfg_dict, tmp_path, rows, steps, **targs):
    from paddlenlp_tpu.trainer import Trainer, TrainingArguments
    from paddlenlp_tpu.transformers import LlmMetaConfig

    optim = dict(adam_beta1=0.9, adam_beta2=0.999, adam_epsilon=1e-8, learning_rate=1e-3, weight_decay=0.01,
                 max_grad_norm=1.0)
    args = TrainingArguments(output_dir=str(tmp_path), max_steps=steps, seed=SEED, save_strategy="no", disable_tqdm=True,
                             per_device_train_batch_size=1, gradient_accumulation_steps=1, lr_scheduler_type="constant",
                             warmup_steps=0, logging_steps=1, use_scan_layers=False, **optim, **targs)
    cfg = DeepseekV3Config(**cfg_dict)
    LlmMetaConfig.set_llm_config(cfg, args)
    cfg.use_cache = False
    m = DeepseekV3ForCausalLM(cfg)
    m.params = jax.jit(lambda s: ref.program_params(cfg_dict, s, jnp.float32))(ref.seed_array(SEED))
    data = [{"input_ids": r, "labels": r.copy()} for r in rows]
    return Trainer(model=m, args=args, train_dataset=data), optim


def test_three_trainer_steps_are_the_references_trajectory_and_leave_the_bias_as_it_was(ref, tmp_path):
    """What the benchmark's cell checks on the chip, here at a tiny size with a share (4 of 16 experts held) and
    recomputation on: the losses of three optimizer steps, and every leaf's change over them, against the reference's
    AdamW trajectory. The router's bias is bit-identical after the three steps (no gradient, no decay), the step's
    metrics carry the layers' counters, and the Trainer puts them on its ``train_step`` spans and into its logs."""
    from paddlenlp_tpu.observability.tracer import TRACER
    from paddlenlp_tpu.trainer import TrainerCallback

    share = dict(TINY, n_routed_experts=4, n_routed_experts_total=16, first_held_expert=8)
    rng = np.random.RandomState(2)
    rows = [rng.randint(0, TINY["vocab_size"], 16).astype(np.int32) for _ in range(32)]  # 8 devices x 1 row a step
    trainer, optim = _trainer(ref, share, tmp_path, rows, 3, recompute=True, recompute_granularity="full")
    bias0 = {i: np.asarray(trainer.model.params["model"][f"layers_{i}"]["mlp"]["gate"]["e_score_correction_bias"]).copy()
             for i in (1,)}
    assert np.any(bias0[1])  # seeded, not zeros
    asked, logged = [], []

    class Watch(TrainerCallback):
        def on_log(self, args, state, control, logs=None, **kw):
            if logs and "loss" in logs:
                logged.append(dict(logs))

    class Rows(list):
        def __getitem__(self, i):
            asked.append(int(i))
            return list.__getitem__(self, i)

    trainer.train_dataset = Rows(trainer.train_dataset)
    trainer.add_callback(Watch())
    TRACER.clear()
    trainer.train()
    params = trainer.train_state.params
    for i in (1,):
        assert np.array_equal(np.asarray(params["model"][f"layers_{i}"]["mlp"]["gate"]["e_score_correction_bias"]), bias0[i])
    batches = [np.stack([rows[i] for i in asked[s * 8:(s + 1) * 8]]) for s in range(3)]
    want = ref.train_trajectory(share, SEED, batches, optim)
    assert max(abs(a["loss"] - b) / b for a, b in zip(logged, want["losses"])) < 1e-5
    start = ref.program_leaves(jax.jit(lambda s: ref.program_params(share, s, jnp.float32))(ref.seed_array(SEED)))
    delta = {k: float(jnp.linalg.norm((v - start[k]).ravel())) for k, v in ref.program_leaves(params).items()}
    assert ref.worst_leaf_gap(delta, want["param_delta_norm"]) < 2e-3  # Adam divides by sqrt(nu): 1e-6 of a gradient is more of a step
    assert delta["L1.router_b"] == 0.0 and want["param_delta_norm"]["L1.router_b"] == 0.0
    # counters: one expert layer, 8 x 16 tokens x 3 choices, a step
    for logs in logged:
        assert logs["expert_assignments"] == 128 * 3
        assert 0 < logs["expert_assignments_local"] < logs["expert_assignments"]
        assert logs["expert_assignments_local"] <= logs["expert_product_rows"]
        assert logs["expert_tokens_max"] >= logs["expert_assignments_local"] / 4
    spans = [s for s in TRACER.snapshot() if s.name == "train_step"]
    assert len(spans) == 3 and all(s.args["expert_assignments"] == 128 * 3 for s in spans)
    assert [s.args["expert_assignments_local"] for s in spans] == [l["expert_assignments_local"] for l in logged]


def test_auto_classes_resolve_the_model_type_and_the_configuration_refuses_what_it_does_not_compute(tmp_path):
    cfg = DeepseekV3Config(**TINY)
    cfg.save_pretrained(str(tmp_path))
    back = AutoConfig.from_pretrained(str(tmp_path))
    assert type(back) is DeepseekV3Config and back.experts_held == (0, 16) and back.n_routed_experts_total == 16
    assert type(AutoModelForCausalLM.from_config(back)) is DeepseekV3ForCausalLM
    share = DeepseekV3Config(**dict(TINY, n_routed_experts=4, n_routed_experts_total=16, first_held_expert=12))
    assert share.experts_held == (12, 4)
    for bad, word in [(dict(scoring_func="softmax"), "sigmoid"), (dict(n_group=4, topk_group=2), "group-limited"),
                      (dict(use_scan_layers=True), "use_scan_layers"), (dict(rope_interleave=False), "rope_interleave"),
                      (dict(topk_method="greedy"), "noaux_tc"), (dict(qk_head_dim=32), "qk_head_dim"),
                      (dict(n_routed_experts=4, n_routed_experts_total=16, first_held_expert=13), "outside")]:
        with pytest.raises(ValueError, match=word):
            DeepseekV3Config(**dict(TINY, **bad))
    cfg.use_scan_layers = True  # as the trainer's arguments would set it after the configuration is made
    with pytest.raises(ValueError, match="use_scan_layers"):
        DeepseekV3ForCausalLM(cfg)
