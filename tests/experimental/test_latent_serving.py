"""The latent layer kinds behind the engine, at a small size on the CPU (hidden
64, 4 experts held of 16, index top-8 of a 40-token context, window 5):
program against the plain reference (``bench/reference/dots3_note.py``) through
the caches, the indexer's selection, the window cache's blocks, preemption,
and the doors that refuse what no layer kind computes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import loader
from paddlenlp_tpu.experimental import InferenceEngine
from paddlenlp_tpu.experimental.engine import SamplingParams
from paddlenlp_tpu.experimental.paged_cache import BlockManager
from paddlenlp_tpu.transformers import Dots3NoteConfig, Dots3NoteForCausalLM

SMALL = dict(
    vocab_size=97, hidden_size=64, intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=5,
    layer_types=["full_attention", "full_attention", "sliding_attention", "sliding_attention", "sliding_attention"],
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4, index_head_dim=16, index_topk=8, sliding_window_size=5,
    swa_num_attention_heads=2, swa_num_key_value_heads=2, swa_q_lora_rank=32, swa_kv_lora_rank=32,
    swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16, n_routed_experts=4,
    n_routed_experts_total=16, first_held_expert=4, num_experts_per_tok=4, first_k_dense_replace=1,
    n_shared_experts=1, routed_scaling_factor=1.0, rms_norm_eps=1e-5, initializer_range=0.02, rope_theta=8e7,
    swa_rope_theta=5e4, apply_mla_qkv_lora_rescale=True)
SEED = 3
ENGINE = dict(max_batch_size=4, block_size=4, num_blocks=64, max_blocks_per_seq=16, dtype=jnp.float32,
              decode_steps=4, enable_prefix_cache=False, prefill_chunk_tokens=8, eos_token_id=[])


@pytest.fixture(scope="module")
def ref():
    return loader.module_from("reference", "dots3_note")


@pytest.fixture(scope="module")
def model(ref):
    m = Dots3NoteForCausalLM(Dots3NoteConfig(**SMALL))
    m.params = jax.jit(lambda s: ref.program_params(SMALL, s, jnp.float32))(ref.seed_array(SEED))
    return m


def prompts(*lengths):
    rng = np.random.RandomState(0)
    return [rng.randint(0, SMALL["vocab_size"], n).tolist() for n in lengths]


@pytest.fixture(scope="module")
def served(model):
    eng = InferenceEngine(model, **ENGINE)
    ps = prompts(30, 21, 13)
    return eng, ps, eng.generate(ps, SamplingParams(max_new_tokens=10))


def test_logits_through_the_caches_agree_with_the_reference(ref, model):
    """One sequence through the step programs' forward: three prefill chunks
    (8 + 8 + 4 tokens, the last padded), then five single-token steps, every
    step reading what the earlier ones wrote into the three planes. float32 on
    both sides; the tolerance covers summation order only (absorbed against
    expanded products, tiles against whole rows): 2e-5 on logits of std 0.16."""
    eng = InferenceEngine(model, **ENGINE)
    infer, mgr = eng.infer, eng.mgr
    ids = np.asarray(prompts(25)[0], np.int32)
    want = np.asarray(ref.forward(SMALL, SEED, ids))
    mgr.allocate(0, len(ids))
    pool, got = eng.pool, []
    feeds = [(0, 8), (8, 8), (16, 4)] + [(p, 1) for p in range(20, 25)]
    for start, n in feeds:
        width = 8 if n > 1 else 1
        mgr.window_span(0, start, n)
        tok = np.zeros((1, width), np.int32)
        tok[0, :n] = ids[start:start + n]
        pos = start + np.arange(width)[None, :]
        logits, pool = infer._forward(model.params, pool, jnp.asarray(tok), jnp.asarray(mgr.table_array(0)[None]),
                                      jnp.asarray(pos), None, None, None, q_lens=jnp.asarray([n]))
        got.append(np.asarray(logits[0, :n], np.float32))
    assert np.abs(np.concatenate(got) - want).max() < 2e-5


def test_served_tokens_are_the_references_first_choice(ref, served):
    _, ps, outs = served
    for p, o in zip(ps, outs):
        logits = np.asarray(ref.forward(SMALL, SEED, np.asarray(p + o)))[len(p) - 1: len(p) + len(o) - 1]
        assert (logits.max(-1) - logits[np.arange(len(o)), o]).max() < 1e-5


def test_a_stack_without_window_layers_is_served_on_the_block_table_alone(ref):
    """Two full layers and no window layer: no second table, no window plane in use, the same step programs."""
    small = dict(SMALL, num_hidden_layers=2, layer_types=SMALL["layer_types"][:2])
    m = Dots3NoteForCausalLM(Dots3NoteConfig(**small))
    m.params = jax.jit(lambda s: ref.program_params(small, s, jnp.float32))(ref.seed_array(SEED))
    eng = InferenceEngine(m, **ENGINE)
    assert eng.infer.window_spec is None and eng.mgr.table_shape == (ENGINE["max_blocks_per_seq"],)
    p = prompts(19)[0]
    o = eng.generate([p], SamplingParams(max_new_tokens=6))[0]
    logits = np.asarray(ref.forward(small, SEED, np.asarray(p + o)))[len(p) - 1: len(p) + len(o) - 1]
    assert (logits.max(-1) - logits[np.arange(len(o)), o]).max() < 1e-5


def test_a_prompt_over_three_key_tiles_and_five_chunks(ref, model, monkeypatch):
    """Key tiles of 16 positions (4 of this table's 16 blocks) where the engine's
    table is one tile of 64: a 40-token prompt enters in five chunks of 8 whose
    attention kernel visits 1, 1, 2, 2 and 3 tiles, the running softmax carried
    from tile to tile; the tiles past those are in the table and never visited."""
    from paddlenlp_tpu.experimental import latent_model

    monkeypatch.setattr(latent_model, "KEY_TILE", 16)
    eng = InferenceEngine(model, **ENGINE)
    p = prompts(40)[0]
    o = eng.generate([p], SamplingParams(max_new_tokens=6))[0]
    logits = np.asarray(ref.forward(SMALL, SEED, np.asarray(p + o)))[len(p) - 1: len(p) + len(o) - 1]
    assert (logits.max(-1) - logits[np.arange(len(o)), o]).max() < 1e-5
    assert eng.ledger.totals["attn_key_tiles"] == (1 + 1 + 2 + 2 + 3) * 2  # both full layers


def test_indexer_selection_equals_the_references(ref, model):
    """Layer 1 (a full layer past the dense one) on a 40-token input: the set of
    positions each query may attend, program against reference, exactly."""
    from paddlenlp_tpu.transformers import latent_layers as M

    cfg = model.config
    x = jax.random.normal(jax.random.key(0), (1, 40, SMALL["hidden_size"]), jnp.float32)
    pos = jnp.arange(40)[None, :]
    _, allowed = M.attention_dense(model.params["model"]["layers_1"]["self_attn"], x, pos, cfg, "latent_full")
    w = {k: jnp.asarray(v, jnp.float32) for k, v in ref.layer_weights(SMALL, SEED, 1, jnp.float32).items()}
    out, want = ref.attention(SMALL, "full_attention", w, x[0])
    allowed, want = np.asarray(allowed[0]), np.asarray(want)
    assert np.array_equal(allowed, want)
    kept = allowed.sum(-1)
    # top-8 once 8 are there; a tie with the 8th is kept too (four ReLU heads can all be 0 at this size)
    assert all(k >= min(t + 1, 8) for t, k in enumerate(kept)) and (kept[8:] == 8).mean() > 0.8


def test_kth_largest_by_counting_is_the_sorted_one():
    from paddlenlp_tpu.transformers.latent_layers import kth_largest

    rng = np.random.default_rng(1)
    scores = rng.normal(size=(5, 64)).astype(np.float32)
    scores[1, :10] = 0.0  # ties, zero and negatives
    valid = rng.random((5, 64)) < 0.7
    valid[4] = False
    valid[4, :3] = True  # fewer than k valid: all kept
    keys, thr = kth_largest(jnp.asarray(scores), jnp.asarray(valid), 8)
    kept = np.asarray((keys >= thr) & valid)
    for r in range(5):
        vals = np.sort(scores[r][valid[r]])[::-1]
        cut = vals[7] if len(vals) >= 8 else -np.inf
        assert np.array_equal(kept[r], valid[r] & (scores[r] >= cut)), r


def test_window_blocks_come_back_and_nothing_behind_the_window_is_read(model, served):
    """Blocks the manager took back are poisoned at once in the window plane:
    were any row behind the window still read, the tokens would change."""
    eng0, ps, want = served
    assert len(eng0.mgr.window_free) == eng0.infer.window_spec["num_window_blocks"] - 1  # all back
    assert eng0.mgr.window_tables == {}
    eng = InferenceEngine(model, **ENGINE)
    span, came_back = BlockManager.window_span, []

    def poisoning(self, seq_id, start, n):
        before = set(self.window_free)
        freed = span(self, seq_id, start, n)
        gone = sorted(set(self.window_free) - before)
        came_back.append(freed)  # some are taken again at once, for the positions this launch feeds
        if gone:
            pool = eng.backend.pool
            eng.backend.pool = type(pool)(kv=pool.kv, idx=pool.idx, stats=pool.stats,
                                          win=pool.win.at[:, jnp.asarray(gone)].set(1e4))
        return freed

    eng.mgr.window_span = poisoning.__get__(eng.mgr)
    assert eng.generate(ps, SamplingParams(max_new_tokens=10)) == want
    assert sum(came_back) > 0  # blocks did fall behind the window while the sequences ran
    # a sequence never holds more than the window and what one launch feeds
    most = (4 + 8 + 4 - 1) // 4 + 2
    assert eng.infer.window_spec["num_window_blocks"] == 4 * most + 1


def test_preemption_and_resume_with_all_three_planes(model, served):
    """A pool too small for three sequences at once: the youngest is evicted,
    its blocks of all planes freed, and its recomputed stream is token-exact."""
    _, ps, want = served
    eng = InferenceEngine(model, **dict(ENGINE, num_blocks=18))
    streams = [[] for _ in ps]
    for p, stream in zip(ps, streams):
        eng.add_request(p, SamplingParams(max_new_tokens=10), stream_cb=lambda t, d, s=stream: s.append(t))
    while eng.has_work():
        eng.step()
    assert streams == want
    assert eng.num_preemptions > 0
    assert eng.mgr.num_free == eng.mgr.total_usable_blocks
    assert len(eng.mgr.window_free) == eng.infer.window_spec["num_window_blocks"] - 1


def test_launch_counts_and_ledger_totals(served):
    eng = served[0]
    t = eng.ledger.totals
    fed_tokens = sum(len(p) for p in served[1]) + sum(len(o) - 1 for o in served[2])
    assert t["expert_assignments"] == fed_tokens * 4 * 4  # live tokens x top-4 x 4 expert layers
    assert 0 < t["expert_assignments_local"] < t["expert_assignments"]
    # counted on the device: every live query scores positions 0..p in both full layers and keeps
    # the top 8 (a tie with the 8th is kept too, so at least min(p + 1, 8))
    seen = np.concatenate([np.arange(len(p) + len(o) - 1) + 1 for p, o in zip(served[1], served[2])])
    assert t["index_candidates"] == 2 * seen.sum()
    assert 2 * np.minimum(seen, 8).sum() <= t["index_selected"] < 1.1 * 2 * np.minimum(seen, 8).sum()
    assert t["expert_tokens_max"] * 4 >= t["expert_assignments_local"]  # max >= mean over 4 held
    # the chunk form's kernel: one key tile (the whole table of 64 positions) x 2 full layers x the chunks of 8
    # that prompts of 30, 21 and 13 tokens enter in; the decode form visits none
    assert t["attn_key_tiles"] == 1 * 2 * (4 + 3 + 2)


@pytest.mark.parametrize("feature, value, named", [
    ("kv_cache_quant", "int8", "kv_cache_quant"),
    ("use_speculative", True, "speculative verify"),
    ("mesh_shape", 2, "mesh_shape"),
    ("disagg_stages", (1, 1), "disagg_stages"),
    ("host_kv_blocks", 8, "host_kv_blocks"),
    ("enable_prefix_cache", True, "prefix cache"),
    ("prefill_chunk_tokens", None, "prefill_chunk_tokens"),
])
def test_latent_kinds_refuse_engine_features_by_name(model, feature, value, named):
    with pytest.raises(ValueError, match=named):
        InferenceEngine(model, **dict(ENGINE, **{feature: value}))


def _llama_config(**extra):
    from paddlenlp_tpu.transformers import LlamaConfig

    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=2, num_key_value_heads=2)
    for k, v in extra.items():
        setattr(cfg, k, v)
    return cfg


@pytest.mark.parametrize("extra, named", [
    ({"sliding_window": 32}, "sliding_window"),
    ({"kv_lora_rank": 16}, "latent attention"),
    ({"n_routed_experts": 8}, "routed experts"),
    ({"num_local_experts": 8}, "routed experts"),
])
def test_llama_kind_refuses_what_it_does_not_compute(extra, named):
    from paddlenlp_tpu.experimental.inference_model import refuse_unserved

    with pytest.raises(ValueError, match=named):
        refuse_unserved(_llama_config(**extra), max_context=64)


def test_a_window_no_sequence_can_reach_is_not_in_use():
    from paddlenlp_tpu.experimental.inference_model import layer_kinds, refuse_unserved

    refuse_unserved(_llama_config(sliding_window=4096), max_context=64)
    assert layer_kinds(_llama_config()) == ["llama", "llama"]


def test_auto_classes_build_the_model_from_published_keys(tmp_path):
    import json
    import os

    from paddlenlp_tpu.transformers import AutoConfig
    from paddlenlp_tpu.transformers.auto.modeling import AutoModelForCausalLM

    path = os.path.join(os.path.dirname(__file__), "..", "..", "bench", "configs", "dots3-note-serve-ep8.json")
    with open(path) as f:
        published = {k: v for k, v in json.load(f).items() if k != "bench"}
    with open(tmp_path / "config.json", "w") as f:
        json.dump(published, f)
    cfg = AutoConfig.from_pretrained(str(tmp_path))
    assert type(cfg) is Dots3NoteConfig and cfg.experts_held == (0, 32) and cfg.n_routed_experts_total == 256
    assert cfg.attention_dims("latent_window")["kv_lora"] == 1024
    small = AutoModelForCausalLM.from_config(Dots3NoteConfig(**SMALL))
    assert type(small) is Dots3NoteForCausalLM
