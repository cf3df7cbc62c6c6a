"""The state-space layer kinds behind the engine, at a small size on the CPU
(hidden 48, nine blocks ``MEM*EMEM*``, 8 scan heads of 4 in 2 groups, state 8,
sub-chunks of 4 in chunks of 8, 4 experts held of 16): program against the
plain reference (``bench/reference/nemotron_h.py``) through the state rows and
the paged KV, float32 on both sides, logits and not tokens; slots reused,
dead rows, preemption, and the doors that refuse what no layer kind computes.

Weights are drawn at std 0.14 = 1 / sqrt(hidden), so that projections of a
normed input have the spread they have at the published widths (0.02 x
sqrt(2688) = 1.04) and the logits a std of 1: at 0.02 a 48-wide model's experts
and states would add next to nothing and no rounding would show.

Tolerances. Program and reference compute the same float32 mathematics in
another order (the chunk form's masked products against a token-by-token scan;
tiles of experts against gathered rows), so logits of std 1.0 agree to 3e-6;
``TOL`` = 5e-5 leaves that fifteen times of room and is fourteen times under
what half precision in the wrong place gives: bfloat16 state rows read 7e-4
here, and a bfloat16 router flips a choice, 1.0 (both tested below)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import loader
from paddlenlp_tpu.experimental import InferenceEngine
from paddlenlp_tpu.experimental.engine import SamplingParams
from paddlenlp_tpu.transformers import NemotronHConfig, NemotronHForCausalLM

SMALL = dict(
    vocab_size=97, hidden_size=48, num_hidden_layers=9, hybrid_override_pattern="MEM*EMEM*", num_attention_heads=4,
    num_key_value_heads=2, head_dim=8, mamba_num_heads=8, mamba_head_dim=4, n_groups=2, ssm_state_size=8,
    conv_kernel=4, chunk_size=4, moe_intermediate_size=24, moe_shared_expert_intermediate_size=40,
    n_routed_experts=4, n_routed_experts_total=16, first_held_expert=4, num_experts_per_tok=3,
    routed_scaling_factor=2.5, norm_eps=1e-5, initializer_range=0.14)
SEED = 3
ENGINE = dict(max_batch_size=4, block_size=4, num_blocks=64, max_blocks_per_seq=16, dtype=jnp.float32,
              decode_steps=4, enable_prefix_cache=False, prefill_chunk_tokens=8, eos_token_id=[])
TOL = 5e-5


@pytest.fixture(scope="module")
def ref():
    return loader.module_from("reference", "nemotron_h")


@pytest.fixture(scope="module")
def model(ref):
    m = NemotronHForCausalLM(NemotronHConfig(**SMALL))
    m.params = jax.jit(lambda s: ref.program_params(SMALL, s, jnp.float32))(ref.seed_array(SEED))
    return m


def prompts(*lengths):
    rng = np.random.RandomState(0)
    return [rng.randint(0, SMALL["vocab_size"], n).tolist() for n in lengths]


def gap(ref, prompt, out):
    """The widest gap by which a served token's reference logit lies under the reference's best."""
    logits = np.asarray(ref.forward(SMALL, SEED, np.asarray(prompt + out)))[len(prompt) - 1: len(prompt) + len(out) - 1]
    return (logits.max(-1) - logits[np.arange(len(out)), out]).max()


@pytest.fixture(scope="module")
def served(model):
    eng = InferenceEngine(model, **ENGINE)
    ps = prompts(30, 21, 13)
    return eng, ps, eng.generate(ps, SamplingParams(max_new_tokens=10))


@pytest.fixture(scope="module")
def bare(model):
    """An engine whose step programs' forward the tests below drive by hand, each on a pool of its own."""
    return InferenceEngine(model, **ENGINE)


def feed(eng, params, pool, ids, slot, start, n, width):
    """One row through the step programs' forward: ``n`` tokens from ``start`` in a row ``width`` wide."""
    tok = np.zeros((1, width), np.int32)
    tok[0, :n] = ids[start:start + n]
    pos = start + np.arange(width)[None, :]
    size = eng.mgr.max_blocks_per_seq * eng.mgr.block_size
    logits, pool = eng.infer._forward(
        params, pool, jnp.asarray(tok), jnp.asarray(eng.mgr.table_array(0)[None]), jnp.asarray(pos),
        jnp.arange(size)[None, :] < start + n, jnp.asarray([start]), None, q_lens=jnp.asarray([n]),
        slots=jnp.asarray([slot]))
    return np.asarray(logits[0, :n], np.float32), pool


def through_the_rows(eng, params, pool, ids, feeds, slot=2):
    eng.mgr.allocate(0, len(ids))
    got = []
    for start, n in feeds:
        logits, pool = feed(eng, params, pool, ids, slot, start, n, 8 if n > 1 else 1)
        got.append(logits)
    eng.mgr.free_seq(0)
    return np.concatenate(got), pool


# a 25-token sequence: two whole chunks, a third that ends inside a chunk and inside a sub-chunk of 4
# (5 = 4 + 1 tokens, padded to 8), then five single-token steps
FEEDS = [(0, 8), (8, 8), (16, 5)] + [(p, 1) for p in range(21, 25)]


def test_logits_through_state_rows_and_paged_kv_agree_with_the_reference(ref, model, bare):
    eng = bare
    ids = np.asarray(prompts(25)[0], np.int32)
    want = np.asarray(ref.forward(SMALL, SEED, ids))
    got, pool = through_the_rows(eng, model.params, eng.pool, ids, FEEDS)
    assert np.abs(got - want).max() < TOL
    # only slot 2's rows were written: every other slot's, and the sentinel's, are as they were made
    ssm = np.asarray(pool.ssm)
    assert np.abs(ssm[:, 2]).max() > 0 and not ssm[:, [0, 1, 3, 4]].any()


@pytest.mark.parametrize("n", [1, 2, 3])
def test_prompts_shorter_than_the_convolution(ref, model, bare, n):
    """1 to 3 tokens: the convolution's window is mostly the zeros before the sequence, and the cached inputs
    after the chunk are part zeros, part tokens."""
    eng = bare
    ids = np.asarray(prompts(n + 4)[0], np.int32)
    want = np.asarray(ref.forward(SMALL, SEED, ids))
    got, _ = through_the_rows(eng, model.params, eng.pool, ids, [(0, n)] + [(p, 1) for p in range(n, n + 4)])
    assert np.abs(got - want).max() < TOL


def test_a_used_slot_starts_from_zero_state(ref, model, bare):
    """Two sequences through one slot, one after the other: the second feeds position 0 and so reads zeros,
    whatever the first left in the slot's rows."""
    eng = bare
    first, second = (np.asarray(p, np.int32) for p in prompts(25, 11))
    _, pool = through_the_rows(eng, model.params, eng.pool, first, FEEDS)
    assert np.abs(np.asarray(pool.ssm)[:, 2]).max() > 0
    got, _ = through_the_rows(eng, model.params, pool, second, [(0, 8), (8, 1), (9, 1), (10, 1)])
    assert np.abs(got - np.asarray(ref.forward(SMALL, SEED, second))).max() < TOL
    # the engine's own path: one slot, two requests, the second admitted into the slot the first used
    one = InferenceEngine(model, **dict(ENGINE, max_batch_size=1))
    for p in prompts(19, 9):
        assert gap(ref, p, one.generate([p], SamplingParams(max_new_tokens=6))[0]) < TOL
    assert one.ledger.totals["state_resets"] == 2


def test_served_tokens_are_the_references_first_choice(ref, served):
    _, ps, outs = served
    for p, o in zip(ps, outs):
        assert gap(ref, p, o) < TOL


def test_dead_rows_beside_live_ones(ref, model):
    """Requests of 3, 9 and 14 new tokens: rows finish inside decode launches and sit dead beside live ones, a
    fourth slot stays empty throughout; a dead row changes no state and no live row's tokens."""
    eng = InferenceEngine(model, **ENGINE)
    ps, streams = prompts(12, 17, 7), [[], [], []]
    for p, n, stream in zip(ps, (3, 9, 14), streams):
        eng.add_request(p, SamplingParams(max_new_tokens=n), stream_cb=lambda t, d, s=stream: s.append(t))
    while eng.has_work():
        eng.step()
    assert [len(s) for s in streams] == [3, 9, 14]
    for p, o in zip(ps, streams):
        assert gap(ref, p, o) < TOL
    t = eng.ledger.totals
    assert t["state_rows_live"] < t["state_rows"] and t["state_resets"] == 3
    assert not np.asarray(eng.pool.ssm)[:, 3].any()  # the slot no request used


def test_a_preempted_request_resamples_the_same_tokens(model, served):
    """A pool too small for three sequences at once: the youngest is evicted, its slot given back, and its
    re-prefill rebuilds the state from its first token (recompute, as KV: no snapshot)."""
    _, ps, want = served
    eng = InferenceEngine(model, **dict(ENGINE, num_blocks=18))
    streams = [[] for _ in ps]
    for p, stream in zip(ps, streams):
        eng.add_request(p, SamplingParams(max_new_tokens=10), stream_cb=lambda t, d, s=stream: s.append(t))
    while eng.has_work():
        eng.step()
    assert streams == want
    assert eng.num_preemptions > 0 and eng.ledger.totals["state_resets"] == 3 + eng.num_preemptions
    assert eng.mgr.num_free == eng.mgr.total_usable_blocks


def test_an_admission_group_of_a_new_size_compiles_nothing(model):
    """One request at a time warms the engine; two and then three arriving in one step add no program: the step
    programs have one shape each and the penalty counts are zeroed at one shape (PR 33: a group size the warm-up
    had not seen compiled eight small programs inside the measured window)."""
    eng = InferenceEngine(model, **ENGINE)
    built = []
    listener = lambda event, duration, **_: built.append(event) if event.endswith("backend_compile_duration") else None
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        eng.generate(prompts(9), SamplingParams(max_new_tokens=6))
        assert built
        del built[:]
        eng.generate(prompts(5, 11), SamplingParams(max_new_tokens=6))
        eng.generate(prompts(5, 11, 3), SamplingParams(max_new_tokens=6))
        assert not built
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def test_launch_counts_and_ledger_totals(served):
    eng, ps, outs = served
    t = eng.ledger.totals
    fed_tokens = sum(len(p) for p in ps) + sum(len(o) - 1 for o in outs)
    assert t["expert_assignments"] == fed_tokens * 3 * 3  # live tokens x top-3 x 3 expert blocks
    assert 0 < t["expert_assignments_local"] < t["expert_assignments"]
    assert t["expert_tokens_max"] * 4 >= t["expert_assignments_local"]  # max >= mean over 4 held
    # rows the scan layers computed: 1 + 4 a mixed step, 4 x 4 sub-steps a decode launch
    by = eng.ledger.by_kind
    assert t["state_rows"] == 5 * by["mixed"]["steps"] + 16 * by["decode"]["steps"]
    assert 0 < t["state_rows_live"] < t["state_rows"] and t["state_resets"] == 3


def test_bfloat16_state_rows_fail_the_tolerance(ref, model, bare):
    """The tolerance holds the state rows to float32: the same feeds through a pool whose rows are bfloat16."""
    eng = bare
    ids = np.asarray(prompts(25)[0], np.int32)
    pool = dataclasses.replace(eng.pool, ssm=eng.pool.ssm.astype(jnp.bfloat16))
    got, _ = through_the_rows(eng, model.params, pool, ids, FEEDS)
    assert np.abs(got - np.asarray(ref.forward(SMALL, SEED, ids))).max() > 10 * TOL


def test_a_bfloat16_router_fails_the_tolerance(ref, model, bare):
    """... and the router to float32 scores: with its weight and input rounded to bfloat16 a choice flips."""
    from paddlenlp_tpu.transformers import latent_layers

    eng = bare
    ids = np.asarray(prompts(25)[0], np.int32)
    route = latent_layers.route

    def rounded(p, x2d, cfg):
        half = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
        return route(dict(p, gate={"kernel": half(p["gate"]["kernel"])}), half(x2d), cfg)

    latent_layers.route = rounded
    try:
        got, _ = through_the_rows(eng, model.params, eng.pool, ids, FEEDS)
    finally:
        latent_layers.route = route
    assert np.abs(got - np.asarray(ref.forward(SMALL, SEED, ids))).max() > 10 * TOL


@pytest.mark.parametrize("feature, value, named", [
    ("kv_cache_quant", "int8", "kv_cache_quant"),
    ("use_speculative", True, "speculative verify"),
    ("mesh_shape", 2, "mesh_shape"),
    ("disagg_stages", (1, 1), "disagg_stages"),
    ("host_kv_blocks", 8, "host_kv_blocks"),
    ("enable_prefix_cache", True, "prefix cache"),
    ("prefill_chunk_tokens", None, "prefill_chunk_tokens"),
])
def test_state_space_kinds_refuse_engine_features_by_name(model, feature, value, named):
    with pytest.raises(ValueError, match=named):
        InferenceEngine(model, **dict(ENGINE, **{feature: value}))


def test_lora_pools_are_refused_by_name(model):
    with pytest.raises(ValueError, match="adapter_registry"):
        InferenceEngine(model, **dict(ENGINE, adapter_registry=object()))


def test_the_llama_door_refuses_state_space_layers_by_mechanism(model):
    from paddlenlp_tpu.experimental.inference_model import (PagedInferenceModel, inference_model_class,
                                                            refuse_unserved)
    from paddlenlp_tpu.experimental.state_model import StateSpaceInferenceModel
    from paddlenlp_tpu.transformers import MambaConfig

    with pytest.raises(ValueError, match="state-space layers"):
        refuse_unserved(model.config, max_context=64)
    with pytest.raises(ValueError, match="state-space layers"):  # the Mamba-1 families: whole-sequence only
        refuse_unserved(MambaConfig(vocab_size=64, hidden_size=32, num_hidden_layers=2), max_context=64)
    with pytest.raises(ValueError, match="state-space layers"):
        PagedInferenceModel(model, block_size=4, num_blocks=16, max_blocks_per_seq=4)
    assert inference_model_class(model.config) is StateSpaceInferenceModel


def test_the_attention_blocks_rotate_nothing_and_the_llama_kind_still_does(bare):
    from paddlenlp_tpu.transformers import LlamaConfig, LlamaForCausalLM

    eng = bare
    assert eng.infer.rotary is False and not hasattr(eng.infer, "inv_freq")
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=2, num_key_value_heads=2)
    llama = InferenceEngine(LlamaForCausalLM.from_config(cfg, seed=0), max_batch_size=2, block_size=4, num_blocks=16,
                            max_blocks_per_seq=4)
    assert llama.infer.rotary is True and llama.infer.inv_freq.shape == (8,)


def test_auto_classes_build_the_model_from_published_keys(tmp_path):
    import json
    import os

    from paddlenlp_tpu.transformers import AutoConfig
    from paddlenlp_tpu.transformers.auto.modeling import AutoModelForCausalLM

    path = os.path.join(os.path.dirname(__file__), "..", "..", "bench", "configs", "nemotron3-nano-serve-ep8.json")
    with open(path) as f:
        published = {k: v for k, v in json.load(f).items() if k != "bench"}
    with open(tmp_path / "config.json", "w") as f:
        json.dump(published, f)
    cfg = AutoConfig.from_pretrained(str(tmp_path))
    assert type(cfg) is NemotronHConfig and cfg.experts_held == (0, 16) and cfg.n_routed_experts_total == 128
    kinds = cfg.layer_kinds()
    assert len(kinds) == 52 and (kinds.count("ssm"), kinds.count("experts"), kinds.count("attention")) == (23, 23, 6)
    assert cfg.ssm_dims()["conv_dim"] == 6144 and cfg.ssm_dims()["d_in"] == 4096
    small = AutoModelForCausalLM.from_config(NemotronHConfig(**SMALL))
    assert type(small) is NemotronHForCausalLM
