"""The windowed grouped-query layer kinds behind the engine, at a small size on the CPU (hidden 48, eight layers
``LLLG LLLG``, 8 query / 2 KV heads of 8, a window of 8, blocks of 4, chunks of 8, 4 experts held of 16): program
against the plain reference (``bench/reference/exaone_moe.py``) through the two planes, float32 on both sides, logits
and not tokens; the kernel's path beside the gathered one; slots reused, dead rows, preemption, and the doors; what the
full layers' walk by runs fetched, counted; and which layer kinds' step programs hold which walk of the table.

Weights are drawn at std 0.14 = 1 / sqrt(hidden), so that projections of a normed input have the spread they have at
the published widths (0.02 x sqrt(6144) = 1.57) and the logits a std of 1.

Tolerances. Program and reference compute the same float32 mathematics in another order (paged blocks against whole
sequences, tiles of experts against gathered rows), so logits of std 1.0 agree to 4e-6; ``TOL`` = 5e-5 leaves that
twelve times of room and is far under what a mechanism in the wrong place gives: a window one off reads 1e-2 or more,
rotation on the full layers 0.1 or more, a bfloat16 router flips a choice, 1.0 (all tested below)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hashlib

from bench.harness import loader
from paddlenlp_tpu.experimental import InferenceEngine
from paddlenlp_tpu.experimental.engine import SamplingParams
from paddlenlp_tpu.observability.tracer import TRACER
from paddlenlp_tpu.ops.pallas import paged_run_attention
from paddlenlp_tpu.transformers import ExaoneMoeConfig, ExaoneMoeForCausalLM

L, G = "sliding_attention", "full_attention"
SMALL = dict(
    vocab_size=97, hidden_size=48, intermediate_size=96, moe_intermediate_size=24, num_hidden_layers=8,
    layer_types=[L, L, L, G, L, L, L, G], mlp_layer_types=["dense"] + ["sparse"] * 7,
    sliding_windows=[8, 8, 8, 0, 8, 8, 8, 0], sliding_window=8, num_attention_heads=8, num_key_value_heads=2, head_dim=8,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"}, first_k_dense_replace=1, num_experts=4,
    num_experts_total=16, first_held_expert=4, num_shared_experts=1, num_experts_per_tok=3,
    routed_scaling_factor=2.5, rms_norm_eps=1e-5, initializer_range=0.14)
SEED = 3
ENGINE = dict(max_batch_size=4, block_size=4, num_blocks=64, max_blocks_per_seq=16, dtype=jnp.float32,
              decode_steps=4, enable_prefix_cache=False, prefill_chunk_tokens=8, eos_token_id=[])
TOL = 5e-5


@pytest.fixture(scope="module")
def ref():
    return loader.module_from("reference", "exaone_moe")


@pytest.fixture(scope="module")
def model(ref):
    m = ExaoneMoeForCausalLM(ExaoneMoeConfig(**SMALL))
    m.params = jax.jit(lambda s: ref.program_params(SMALL, s, jnp.float32))(ref.seed_array(SEED))
    return m


def prompts(*lengths):
    rng = np.random.RandomState(0)
    return [rng.randint(0, SMALL["vocab_size"], n).tolist() for n in lengths]


LONGEST = 48  # every sequence here is padded to this for the reference (causal: padding changes nothing before it),


def reference_logits(ref, ids):  # so that its layers compile once
    padded = np.zeros(LONGEST, np.int32)
    padded[: len(ids)] = ids
    return np.asarray(ref.forward(SMALL, SEED, padded))[: len(ids)]


def gap(ref, prompt, out):
    """The widest gap by which a served token's reference logit lies under the reference's best."""
    logits = reference_logits(ref, prompt + out)[len(prompt) - 1: len(prompt) + len(out) - 1]
    return (logits.max(-1) - logits[np.arange(len(out)), out]).max()


def served_by(model, kernel, ps, new=10, **engine):
    eng = InferenceEngine(model, **dict(ENGINE, **engine))
    eng.infer.use_paged_kernel = kernel  # read when the step programs are first traced; interpret mode off the chip
    return eng, eng.generate(ps, SamplingParams(max_new_tokens=new))


@pytest.fixture(scope="module")
def served(model):
    ps = prompts(30, 21, 5)
    eng, outs = served_by(model, False, ps)
    return eng, ps, outs


@pytest.fixture(scope="module")
def bare(model):
    """An engine whose step programs' forward the tests below drive by hand, each on a pool of its own."""
    return InferenceEngine(model, **ENGINE)


def through_the_planes(eng, params, pool, ids, feeds):
    """One row through the step programs' forward, feed by feed: ``n`` tokens from ``start`` in a row 8 wide (a
    chunk) or 1 wide (a step), after the block manager moved the row's window (``window_span``), as the engine does
    before every launch. Traced anew for every call of this function, so that what a test patched is what runs."""
    forward = jax.jit(lambda params, pool, tok, table, pos, start, n: eng.infer._forward(
        params, pool, tok, table, pos, None, start, None, q_lens=n))
    eng.mgr.allocate(0, len(ids))
    got = []
    for start, n in feeds:
        eng.mgr.window_span(0, start, n)
        width = 8 if n > 1 else 1
        tok = np.zeros((1, width), np.int32)
        tok[0, :n] = ids[start:start + n]
        logits, pool = forward(params, pool, jnp.asarray(tok), jnp.asarray(eng.mgr.table_array(0)[None]),
                               jnp.asarray(start + np.arange(width)[None, :]), jnp.asarray([start]), jnp.asarray([n]))
        got.append(np.asarray(logits[0, :n], np.float32))
    eng.mgr.free_seq(0)
    return np.concatenate(got), pool


def chunks_then_steps(prompt, total):
    """A prompt in chunks of 8 (the last one short), then single-token steps up to ``total`` positions."""
    feeds = [(s, min(8, prompt - s)) for s in range(0, prompt, 8)]
    return feeds + [(p, 1) for p in range(prompt, total)]


@pytest.fixture(scope="module")
def long_ids():
    return np.asarray(prompts(47)[0], np.int32)


@pytest.fixture(scope="module")
def long_logits(ref, long_ids):
    return reference_logits(ref, long_ids)


@pytest.mark.parametrize("kernel, prompt, total", [
    (False, 3, 7),     # a prompt and a decode that stay under the window of 8
    (False, 6, 14),    # decode that runs from under the window to past it
    (False, 13, 17),   # a prompt that crosses the window inside its second chunk
    (False, 16, 20),   # ... and one that ends at a chunk's edge, its first decode step a block's first position
    (False, 21, 47),   # a context of twelve blocks: window blocks are given back and taken again, several times over
    (True, 6, 14), (True, 21, 47),  # the kernel's walks (interpret mode) in place of the gathers
], ids=["under", "decode-crosses", "chunk-crosses", "chunk-edge", "blocks-recycled", "kernel-decode-crosses",
        "kernel-blocks-recycled"])
def test_logits_through_both_planes_agree_with_the_reference(model, bare, long_ids, long_logits, kernel, prompt, total):
    eng = bare
    eng.infer.use_paged_kernel = kernel
    free_before = len(eng.mgr.window_free)
    try:
        got, _ = through_the_planes(eng, model.params, eng.pool, long_ids[:total], chunks_then_steps(prompt, total))
    finally:
        eng.infer.use_paged_kernel = False
    assert np.abs(got - long_logits[:total]).max() < TOL
    assert len(eng.mgr.window_free) == free_before  # every window block came back


def test_window_blocks_are_given_back_behind_the_window(bare):
    """47 positions through a window of 8 in blocks of 4: the row never holds more than the window's blocks plus
    a launch's, and the plane it writes is the small one (25 blocks for 4 slots), not the pool (64)."""
    eng = bare
    assert eng.mgr.window_back == 7 and eng.pool.win.shape[:3] == (6, 2, 4 * 6 + 1) and eng.pool.kv.shape[:3] == (2, 2, 64)
    eng.mgr.allocate(0, 47)
    held = []
    for start, n in chunks_then_steps(21, 47):
        eng.mgr.window_span(0, start, n)
        held.append(len(eng.mgr.window_tables[0]))
        table = eng.mgr.table_array(0)
        first = max(start - 7, 0) // 4
        assert not table[1, :first].any() and table[1, first:(start + n - 1) // 4 + 1].all()
    eng.mgr.free_seq(0)
    assert max(held) <= 5 and len(set(held)) > 1


def test_served_tokens_are_the_references_first_choice(ref, served):
    _, ps, outs = served
    for p, o in zip(ps, outs):
        assert gap(ref, p, o) < TOL


def test_the_kernels_path_serves_the_same_tokens(model, served):
    _, ps, want = served
    eng, got = served_by(model, True, ps)
    assert eng.infer.use_paged_kernel is True and [list(o) for o in got] == [list(o) for o in want]


def test_two_requests_through_one_slot(ref, model):
    """One slot, two requests, the second admitted into the slot and the window blocks the first used."""
    one = InferenceEngine(model, **dict(ENGINE, max_batch_size=1))
    for p in prompts(19, 9):
        assert gap(ref, p, one.generate([p], SamplingParams(max_new_tokens=6))[0]) < TOL
    assert len(one.mgr.window_free) == one.pool.win.shape[2] - 1


def test_dead_rows_beside_live_ones(ref, model):
    """Requests of 3, 9 and 14 new tokens: rows finish inside decode launches and sit dead beside live ones, a
    fourth slot stays empty throughout; a dead row changes no live row's tokens."""
    eng = InferenceEngine(model, **ENGINE)
    ps, streams = prompts(12, 17, 7), [[], [], []]
    for p, n, stream in zip(ps, (3, 9, 14), streams):
        eng.add_request(p, SamplingParams(max_new_tokens=n), stream_cb=lambda t, d, s=stream: s.append(t))
    while eng.has_work():
        eng.step()
    assert [len(s) for s in streams] == [3, 9, 14]
    for p, o in zip(ps, streams):
        assert gap(ref, p, o) < TOL
    assert eng.mgr.num_free == eng.mgr.total_usable_blocks and len(eng.mgr.window_free) == eng.pool.win.shape[2] - 1


def test_a_preempted_request_resamples_the_same_tokens(model, served):
    """A pool too small for three sequences at once: the youngest is evicted, its blocks of both tables given
    back, and its re-prefill rebuilds both planes from its first token."""
    _, ps, want = served
    eng = InferenceEngine(model, **dict(ENGINE, num_blocks=18))
    streams = [[] for _ in ps]
    for p, stream in zip(ps, streams):
        eng.add_request(p, SamplingParams(max_new_tokens=10), stream_cb=lambda t, d, s=stream: s.append(t))
    while eng.has_work():
        eng.step()
    assert streams == [list(o) for o in want]
    assert eng.num_preemptions > 0
    assert eng.mgr.num_free == eng.mgr.total_usable_blocks and len(eng.mgr.window_free) == eng.pool.win.shape[2] - 1


def test_launch_counts_and_ledger_totals(served):
    eng, ps, outs = served
    t = eng.ledger.totals
    fed_tokens = sum(len(p) for p in ps) + sum(len(o) - 1 for o in outs)
    assert t["expert_assignments"] == fed_tokens * 3 * 7  # live tokens x top-3 x 7 expert layers
    assert 0 < t["expert_assignments_local"] < t["expert_assignments"]
    # positions visible, by hand: a row that feeds n tokens from s sees s + n in each of the 2 full layers and
    # min(s, 7) + n in each of the 6 window layers; prompts go in chunks of 8, every later token alone
    full = window = 0
    for p, o in zip(ps, outs):
        feeds = chunks_then_steps(len(p), len(p) + len(o) - 1)
        full += sum(s + n for s, n in feeds)
        window += sum(min(s, 7) + n for s, n in feeds)
    assert (t["attn_kv_full"], t["attn_kv_window"]) == (2 * full, 6 * window)


RUN = 16  # positions a run of the walk below: four blocks of 4 (at the file's own 512 the table of 64 is one run)


@pytest.fixture(scope="module")
def served_by_runs(model):
    """``served``'s requests through the kernels, the full layers walking runs of 16 positions (read as the step
    programs are traced), with the launch spans the engine left."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(paged_run_attention, "_RUN_KEYS", RUN)
        TRACER.clear()
        ps = prompts(30, 21, 5)
        eng, outs = served_by(model, True, ps)
        launches = [s for s in TRACER.snapshot() if s.cat == "engine" and s.name in ("decode", "mixed_step")]
    return eng, ps, outs, launches


def test_the_walk_by_runs_serves_the_same_tokens_and_counts_what_it_fetched(served, served_by_runs):
    """Mixed lengths (30, 21 and 5 prompt tokens, ten new each) through the walk by runs: the gathered path's tokens;
    ``attn_kv_fetched`` is the arithmetic of the rows' lengths (a row that feeds n tokens from s fetches
    ceil((s + n) / 16) runs of 16 positions in each of the 2 full layers), in the ledger's totals and, summed, in the
    launch spans' args, and never below ``attn_kv_full`` in any launch: whole runs hold what a row may see."""
    _, _, want = served
    eng, ps, outs, launches = served_by_runs
    assert [list(o) for o in outs] == [list(o) for o in want]
    fetched = full = 0
    for p, o in zip(ps, outs):
        feeds = chunks_then_steps(len(p), len(p) + len(o) - 1)
        fetched += sum(-(-(s + n) // RUN) * RUN for s, n in feeds)
        full += sum(s + n for s, n in feeds)
    t = eng.ledger.totals
    assert (t["attn_kv_fetched"], t["attn_kv_full"]) == (2 * fetched, 2 * full)
    assert launches and sum(s.args["attn_kv_fetched"] for s in launches) == t["attn_kv_fetched"]
    assert all(s.args["attn_kv_fetched"] >= s.args["attn_kv_full"] > 0 for s in launches)
    assert 1.0 < t["attn_kv_fetched"] / t["attn_kv_full"] < 1.5  # near what a row may see, not the table's 64 a feed
    assert eng.efficiency()["ledger"]["totals"]["attn_kv_fetched"] == t["attn_kv_fetched"]  # /debug/efficiency


def test_through_the_gather_the_whole_table_is_fetched(served):
    """Without the kernel a full layer gathers its row's whole table, 16 blocks of 4, whatever the row holds."""
    eng, ps, outs = served
    feeds = sum(len(chunks_then_steps(len(p), len(p) + len(o) - 1)) for p, o in zip(ps, outs))
    assert eng.ledger.totals["attn_kv_fetched"] == 2 * 64 * feeds > 2 * eng.ledger.totals["attn_kv_full"]


# ------------------------------------------------------------------ which kinds hold which walk
def kernel_calls(jaxpr, found):
    """Every ``pallas_call`` of a traced program, through its scans and inner jits, in order."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    kernel_calls(inner, found)
    return found


def small_of(kind):
    from paddlenlp_tpu.transformers import LlamaConfig, LlamaForCausalLM, NemotronHConfig, NemotronHForCausalLM
    from test_state_serving import SMALL as STATE_SMALL  # beside this file: scan and expert blocks round two of attention

    if kind == "llama":
        return LlamaForCausalLM, LlamaConfig(vocab_size=97, hidden_size=48, intermediate_size=96, num_hidden_layers=2,
                                             num_attention_heads=6, num_key_value_heads=2, head_dim=8)
    if kind == "state":
        return NemotronHForCausalLM, NemotronHConfig(**STATE_SMALL)
    return ExaoneMoeForCausalLM, ExaoneMoeConfig(**SMALL)


def traced(kind, program):
    """A kind's step program traced with the kernels on, abstract weights: 4 slots, blocks of 4, tables of 16, one
    chunk row of 8 in the mixed step."""
    from paddlenlp_tpu.experimental.backend import samp_arrays
    from paddlenlp_tpu.experimental.inference_model import inference_model_class
    from paddlenlp_tpu.experimental.launch_pack import layout_of, packed_size

    cls, cfg = small_of(kind)
    m = cls(cfg, dtype=jnp.float32, param_dtype=jnp.float32)
    m.params = m.param_shapes
    infer = inference_model_class(cfg)(m, 4, 64, 16, dtype=jnp.float32, decode_steps=4, prefill_chunk_tokens=8,
                                       max_batch_size=4, use_paged_kernel=True)
    pool = jax.eval_shape(lambda: infer.init_pool(64, 4, jnp.float32))
    table = (2, 16) if kind == "windowed" else (16,)
    aval = jax.ShapeDtypeStruct
    rows = lambda *shape: aval((4,) + shape, jnp.int32)
    chunk = lambda *shape, dtype=jnp.int32: aval((1,) + shape, dtype)
    flag = lambda n: aval((n,), jnp.bool_)

    def trace(step, counts, **fields):  # the launch's host inputs ride one packed buffer, its layout static
        layout = layout_of(fields)
        return jax.make_jaxpr(step, static_argnums=(5,))(
            m.params, pool, aval((packed_size(layout),), jnp.int32), counts, None, layout)

    if program == "decode":
        return trace(infer._decode_impl, rows(97), tokens=rows(), block_tables=rows(*table), context_lens=rows(),
                     done0=flag(4), remaining=rows(), **samp_arrays([None] * 4, 4))
    return trace(infer._mixed_flat_impl, rows(97), chunk_ids=chunk(8), chunk_tables=chunk(*table), chunk_qlens=chunk(),
                 chunk_start=chunk(), chunk_slots=chunk(), chunk_emit=chunk(dtype=jnp.bool_), dec_tokens=rows(),
                 dec_tables=rows(*table), dec_start=rows(), dec_slots=rows(), dec_live=flag(4),
                 **samp_arrays([None] * 5, 5))


# sha256 of the text of the programs' walk-by-blocks kernel calls (kernel jaxpr, grid and index maps inside it) as the
# tree before PR 36 traces them, from the same function run there. A change to ``ops/pallas/paged_attention.py`` or to
# how the llama and state kinds (or the window layers) call it moves these: say so in PERF.md and pin anew
WALK_BY_BLOCKS = {
    ("llama", "decode"): "7f38493074bac93cfcf453c4e4b822512e09ca419e64aa5396d0773fb0a0b0ff",
    ("llama", "mixed"): "07ac55077af46544576222f3fbcbb4de59631f9841354d2ba25c5ac2b3cf7ea3",
    ("state", "decode"): "53e43a7466929ffeff73e1fea8144508447639cdf6e402e48aaf21408f506512",
    ("state", "mixed"): "7f2d04e5507b3464cbbc3e5ad9ca1f2ea75f687e8af36778ab73c9f13e24a391",
    ("windowed", "decode"): "7e017497791bfbceef1a19df663f3410a7306c166546b896bedbb40b4d71d1aa",  # its six window calls
    ("windowed", "mixed"): "81ee4f3166a08ccb5e1e966a0b3a5d4efb37b4ac7699f1c226255791cd70140a",  # its twelve
}


@pytest.mark.parametrize("program", ["decode", "mixed"])
@pytest.mark.parametrize("kind", ["llama", "state", "windowed"])
def test_the_layer_kind_chooses_the_walk_and_the_other_kinds_programs_are_the_parents(kind, program):
    """The llama and state kinds' step programs hold the walk by blocks and nothing else: a grid whose innermost axis is
    the table's width (16), and the very kernel calls the tree before PR 36 traced (``chat`` and ``shortchat`` run
    these programs, so no metric of theirs can move). The windowed kinds' window layers hold it too, over a window's
    steps (3 for a decode row, 5 for a chunk of 8), unchanged; their two full layers hold the walk by runs, a grid of
    rows x head groups x query tiles without an axis of the table."""
    calls = kernel_calls(traced(kind, program).jaxpr, [])
    grids = [tuple(c.params["grid_mapping"].grid) for c in calls]
    by_blocks = [c for c, g in zip(calls, grids) if len(g) == 4]
    by_runs = [g for g in grids if len(g) == 3]
    assert all(c.params["name"] == "ragged_paged_attention" for c in calls)
    segments = 1 if program == "decode" else 2  # the mixed step: its chunk row, then its decode rows
    if kind == "windowed":
        assert len(by_blocks) == 6 * segments and {g[-1] for g in grids if len(g) == 4} <= {3, 5}
        assert by_runs == ([(4, 1, 1)] * 2 if program == "decode" else [(1, 1, 1)] * 2 + [(4, 1, 1)] * 2)
    else:
        assert not by_runs and {g[-1] for g in grids} == {16}
        assert len(by_blocks) == segments * (1 if kind == "llama" else 2)  # a scan's body, or two attention blocks
    text = "\n".join(str(c) for c in by_blocks)
    assert hashlib.sha256(text.encode()).hexdigest() == WALK_BY_BLOCKS[kind, program]


@pytest.mark.parametrize("window", [7, 9])
def test_a_window_one_off_fails_the_tolerance(model, long_ids, long_logits, window):
    eng = InferenceEngine(model, **ENGINE)
    eng.infer.window = window
    got, _ = through_the_planes(eng, model.params, eng.pool, long_ids[:30], chunks_then_steps(21, 30))
    assert np.abs(got - long_logits[:30]).max() > 10 * TOL


def test_rotation_on_the_full_layers_fails_the_tolerance(model, bare, long_ids, long_logits, monkeypatch):
    from paddlenlp_tpu.transformers import window_layers

    project = window_layers.project_qkv
    monkeypatch.setattr(window_layers, "project_qkv",
                        lambda p, x, positions, d, kind, eps: project(p, x, positions, d, window_layers.GQA_WINDOW, eps))
    got, _ = through_the_planes(bare, model.params, bare.pool, long_ids[:30], chunks_then_steps(21, 30))
    assert np.abs(got - long_logits[:30]).max() > 10 * TOL


def test_a_bfloat16_router_fails_the_tolerance(model, bare, long_ids, long_logits, monkeypatch):
    from paddlenlp_tpu.transformers import latent_layers

    route = latent_layers.route
    half = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    monkeypatch.setattr(latent_layers, "route",
                        lambda p, x2d, cfg: route(dict(p, gate={"kernel": half(p["gate"]["kernel"])}), half(x2d), cfg))
    got, _ = through_the_planes(bare, model.params, bare.pool, long_ids[:30], chunks_then_steps(21, 30))
    assert np.abs(got - long_logits[:30]).max() > 10 * TOL


@pytest.mark.parametrize("feature, value, named", [
    ("kv_cache_quant", "int8", "kv_cache_quant"),
    ("use_speculative", True, "speculative verify"),
    ("mesh_shape", 2, "mesh_shape"),
    ("disagg_stages", (1, 1), "disagg_stages"),
    ("host_kv_blocks", 8, "host_kv_blocks"),
    ("enable_prefix_cache", True, "prefix cache"),
    ("prefill_chunk_tokens", None, "prefill_chunk_tokens"),
])
def test_windowed_kinds_refuse_engine_features_by_name(model, feature, value, named):
    with pytest.raises(ValueError, match=named):
        InferenceEngine(model, **dict(ENGINE, **{feature: value}))


def test_lora_pools_are_refused_by_name(model):
    with pytest.raises(ValueError, match="adapter_registry"):
        InferenceEngine(model, **dict(ENGINE, adapter_registry=object()))


def test_the_llama_door_refuses_this_configuration_by_mechanism(model):
    from paddlenlp_tpu.experimental.inference_model import (PagedInferenceModel, inference_model_class,
                                                            refuse_unserved)
    from paddlenlp_tpu.experimental.window_model import WindowedInferenceModel
    from paddlenlp_tpu.transformers import LlamaConfig

    with pytest.raises(ValueError, match="layer kinds .*gqa_full.*gqa_window"):
        refuse_unserved(model.config, max_context=64)
    with pytest.raises(ValueError, match="layer kinds"):
        PagedInferenceModel(model, block_size=4, num_blocks=16, max_blocks_per_seq=4)
    # a llama configuration with a window in use is still refused, and told which kinds have one
    windowed = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
                           num_attention_heads=2, num_key_value_heads=2)
    windowed.sliding_window = 8
    with pytest.raises(ValueError, match="sliding_window=8 is in use.*WindowedInferenceModel"):
        refuse_unserved(windowed, max_context=64)
    refuse_unserved(windowed, max_context=8)  # a window no sequence can outgrow is not in use
    assert inference_model_class(model.config) is WindowedInferenceModel


def test_per_layer_choices_come_from_the_configuration(bare):
    infer = bare.infer
    assert infer.kinds == ["gqa_window"] * 3 + ["gqa_full"] + ["gqa_window"] * 3 + ["gqa_full"]
    assert infer.plane_index == [0, 1, 2, 0, 3, 4, 5, 1] and (infer.n_full, infer.n_window, infer.window) == (2, 6, 8)
    assert infer.fixed_mixed_shape == (1, 8, 4) and infer.window_spec == {"window_back": 7, "num_window_blocks": 25}
