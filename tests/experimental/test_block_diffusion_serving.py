"""Generation by diffusion over blocks behind the engine, at a small size on the CPU (hidden 64, four layers, 4 query /
2 KV heads of 16, 8 experts of which 4 are held, top 2, blocks of B = 4 positions, pages of 4, chunks of 8): the
engine against the plain reference's ``generate`` (``bench/reference/sdar_moe.py``) under both unmasking rules, float32
on both sides, **logits and not only tokens**; prompts of every length mod 4, ``max_tokens`` inside a block, chunks
that end mid-prompt, slots reused, a preempted request; the kernel's walk beside the gathers; the commit pass; the
counters; the stream a block at a time through ``/v1/completions``; and every door.

Weights are drawn at std 0.125 = 1 / sqrt(hidden), so that projections of a normed input have the spread they have
at the published widths (0.02 x sqrt(2048) = 0.9) and the logits a std near 1.

Tolerances. Program and reference compute the same float32 mathematics in another order (a block over a paged cache
whose K and V were written pass by pass against whole sequences recomputed from their tokens, tiles of experts against
gathered rows), so logits of std 1 agree to a few 1e-6; ``TOL`` = 5e-5 leaves ten times of room and is far under what
a mechanism in the wrong place gives: the causal rule inside a block, or a block left uncommitted (its last unmasked
position's K and V still those of the mask id), reads 1e-2 or more (both tested below)."""

import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import loader
from paddlenlp_tpu.experimental import InferenceEngine
from paddlenlp_tpu.experimental import block_model
from paddlenlp_tpu.experimental.engine import SamplingParams
from paddlenlp_tpu.transformers import SdarMoeConfig, SdarMoeForCausalLM

SMALL = dict(
    vocab_size=96, hidden_size=64, intermediate_size=128, moe_intermediate_size=32, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, num_experts=4, num_experts_total=8, first_held_expert=4,
    num_experts_per_tok=2, norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1000000.0, initializer_range=0.125,
    block_length=4, denoising_steps=4, remasking="low_confidence_static", confidence_threshold=0.9, mask_token_id=95)
#: the dynamic rule at a threshold the small model's confidences straddle: some passes unmask several positions
DYNAMIC = dict(SMALL, remasking="low_confidence_dynamic", confidence_threshold=0.15)
SEED = 3
ENGINE = dict(max_batch_size=2, block_size=4, num_blocks=64, max_blocks_per_seq=16, dtype=jnp.float32,
              decode_steps=5, enable_prefix_cache=False, prefill_chunk_tokens=8, eos_token_id=[])
TOL = 5e-5
LENGTHS = (9, 10, 11, 12, 3, 21)  # every length mod 4; one under a block; one whose chunks of 8 end mid-prompt


@pytest.fixture(scope="module")
def ref():
    return loader.module_from("reference", "sdar_moe")


def build(ref, cfg, **engine):
    m = SdarMoeForCausalLM(SdarMoeConfig(**cfg))
    m.params = jax.jit(lambda s: ref.program_params(cfg, s, jnp.float32))(ref.seed_array(SEED))
    return InferenceEngine(m, **dict(ENGINE, **engine))


@pytest.fixture(scope="module")
def eng(ref):
    """The one built engine most of the file shares (static rule, the gathers)."""
    return build(ref, SMALL)


def prompts(*lengths):
    rng = np.random.RandomState(0)
    return [rng.randint(0, SMALL["mask_token_id"], n).tolist() for n in lengths]


def through_the_pool(eng, prompt, passes):
    """The reference's passes fed through the program's forward by hand, over a pool of its own: the prompt's whole
    blocks as chunks of 8, then every pass's block at its position (the commit passes too: they write K and V).
    -> the logits of every denoising pass [B, vocab]. Traced anew a call, so that what a test patched is what runs."""
    infer, bk = eng.infer, SMALL["block_length"]
    hidden = jax.jit(lambda params, pool, ids, table, start, n: infer._hidden(params, pool, ids, table, start, n))
    pool = infer.init_pool(64, 4, jnp.float32)
    eng.mgr.allocate(0, len(prompt) + 16)
    table = jnp.asarray(eng.mgr.table_array(0)[None])
    eng.mgr.free_seq(0)
    whole = len(prompt) - len(prompt) % bk
    for s in range(0, whole, 8):
        ids = np.zeros((1, 8), np.int32)
        n = min(8, whole - s)
        ids[0, :n] = prompt[s:s + n]
        _, pool = hidden(eng.backend.params, pool, jnp.asarray(ids), table, jnp.asarray([s]), jnp.asarray([n]))
    got = []
    for p in passes:
        h, pool = hidden(eng.backend.params, pool, jnp.asarray([p["fed"]], jnp.int32), table,
                         jnp.asarray([p["start"]]), jnp.asarray([bk]))
        if p["kind"] == "denoise":
            got.append(np.asarray(infer._logits(eng.backend.params, h)[0], np.float32))
    return got


# ------------------------------------------------------------------ engine against the reference's generate
@pytest.fixture(scope="module")
def served(eng):
    ps = prompts(*LENGTHS)
    return ps, eng.generate(ps, SamplingParams(max_new_tokens=10))  # six requests through two slots: slots reused


@pytest.mark.parametrize("i", range(len(LENGTHS)), ids=[f"prompt-{n}" for n in LENGTHS])
def test_the_engine_serves_what_the_reference_generates(ref, eng, served, i):
    """Tokens, and the logits of every denoising pass through the paged cache against the reference's whole-sequence
    recomputation: prompts of every length mod 4, 10 tokens (a ``max_tokens`` that ends inside a block)."""
    ps, outs = served
    want, passes = ref.generate(SMALL, SEED, ps[i], 10)
    assert outs[i] == want and len(want) == 10
    got = through_the_pool(eng, ps[i], passes)
    wanted = [p["logits"] for p in passes if p["kind"] == "denoise"]
    assert len(got) == len(wanted) > 0
    assert max(np.abs(g - w).max() for g, w in zip(got, wanted)) < TOL
    # a full block is denoising_steps passes and a commit pass; the first has as many as it has masked positions
    kinds = [p["kind"] for p in passes]
    first = 4 - len(ps[i]) % 4
    assert kinds[:first + 1] == ["denoise"] * first + ["commit"]
    assert kinds[first + 1:first + 6] == ["denoise"] * 4 + ["commit"]


def test_a_block_left_uncommitted_is_seen(ref, eng):
    """The commit pass is not bookkeeping: without it the K and V of a block's last unmasked position are those of
    the mask id, and the next block's logits move by far more than ``TOL``."""
    prompt = prompts(12)[0]
    _, passes = ref.generate(SMALL, SEED, prompt, 8)
    without = [p for p in passes if p["kind"] == "denoise"]
    got = through_the_pool(eng, prompt, without)
    wanted = [p["logits"] for p in without]
    assert max(np.abs(g - w).max() for g, w in zip(got[:4], wanted[:4])) < TOL  # the first block needs none before it
    assert max(np.abs(g - w).max() for g, w in zip(got[4:], wanted[4:])) > 1e-2


def test_the_causal_rule_inside_a_block_is_seen(ref, eng, monkeypatch):
    """The block mask is not the causal rule: with it in the gathers' place the logits move by far more than ``TOL``."""
    prompt = prompts(12)[0]
    _, passes = ref.generate(SMALL, SEED, prompt, 4)
    real = block_model.W.window_mask
    monkeypatch.setattr(block_model.W, "window_mask", lambda q, k, window, block=None: real(q, k, window))
    got = through_the_pool(eng, prompt, passes)
    assert np.abs(got[0] - passes[0]["logits"]).max() > 1e-2


def test_the_dynamic_rule(ref):
    """``low_confidence_dynamic`` at a threshold some confidences pass: tokens as the reference's, some pass unmasks
    more than one position, and never fewer than the static rule would (a block takes at most 4 denoising passes)."""
    eng = build(ref, DYNAMIC)
    ps = prompts(9, 12, 6)
    outs = eng.generate(ps, SamplingParams(max_new_tokens=12))
    several = 0
    for prompt, out in zip(ps, outs):
        want, passes = ref.generate(DYNAMIC, SEED, prompt, 12)
        assert out == want
        got = through_the_pool(eng, prompt, passes)
        wanted = [p["logits"] for p in passes if p["kind"] == "denoise"]
        assert max(np.abs(g - w).max() for g, w in zip(got, wanted)) < TOL
        several += sum(1 for p in passes if p["kind"] == "denoise" and p["unmasked"].sum() > 1)
        run = 0
        for p in passes:
            run = run + 1 if p["kind"] == "denoise" else 0
            assert run <= 4
    assert several > 0
    t = eng.ledger.totals
    assert t["tokens_unmasked"] > t["denoise_passes"]  # counted on the device too


def test_the_kernels_walk_agrees_with_the_gathers(ref, served):
    """The ragged paged kernel's walk by runs under ``block=4`` (interpret mode) in the gathers' place: the same
    tokens through the engine, and the same logits to ``TOL`` through the pool by hand."""
    ps, outs = served
    eng = build(ref, SMALL)
    eng.infer.use_paged_kernel = True  # read when the step programs are first traced
    assert eng.generate(ps[:3], SamplingParams(max_new_tokens=10)) == outs[:3]
    _, passes = ref.generate(SMALL, SEED, ps[0], 10)
    got = through_the_pool(eng, ps[0], passes)
    wanted = [p["logits"] for p in passes if p["kind"] == "denoise"]
    assert max(np.abs(g - w).max() for g, w in zip(got, wanted)) < TOL


# ------------------------------------------------------------------ scheduling in blocks
def test_counters_and_passes_a_token(ref):
    """Whole-block prompts and answers: 5 passes a block of 4 tokens, 1.25 a token, nothing discarded; a request
    knows the passes its blocks took; pages reserved ahead of a launch are given back."""
    eng = build(ref, SMALL)
    reqs = {}
    for p in prompts(8, 16):
        eng.add_request(p, SamplingParams(max_new_tokens=8))
    while eng.has_work():
        for r in eng.step():
            reqs[r.req_id] = r
    t = eng.ledger.totals
    assert (t["tokens_emitted"], t["tokens_discarded"], t["tokens_unmasked"]) == (16, 0, 16)
    assert (t["denoise_passes"], t["commit_passes"]) == (16, 4)
    assert (t["denoise_passes"] + t["commit_passes"]) / t["tokens_emitted"] == 1.25
    assert all((r.denoise_passes, r.commit_passes, r.finish_reason) == (8, 2, "length") for r in reqs.values())
    # a pass of a row at block start s sees s + 4 positions in each of the 4 layers; the chunks their own
    passes = sum(4 * (s + 4) for base in (8, 16) for s in (base, base + 4) for _ in range(5))
    assert t["attn_kv_visible"] == passes + 4 * (8 + 8 + 16)
    assert t["expert_assignments"] == (8 + 16 + 2 * 2 * 5 * 4) * 2 * 4  # live tokens x top 2 x 4 layers
    assert 0 < t["expert_assignments_local"] < t["expert_assignments"]
    assert t["fed"] == t["useful"] + t["padding"] and t["useful"] == 8 + 16 + 16
    assert eng.mgr.tables == {} and eng.mgr.num_free == eng.mgr.total_usable_blocks


def test_max_tokens_inside_a_block_discards_the_rest(ref):
    eng = build(ref, SMALL)
    (out,) = eng.generate(prompts(10), SamplingParams(max_new_tokens=5))
    want, _ = ref.generate(SMALL, SEED, prompts(10)[0], 5)
    assert out == want and len(out) == 5
    t = eng.ledger.totals
    # prompt 10: the first block has 2 fixed positions and 2 new tokens; the second is cut after 3 of its 4
    assert (t["tokens_emitted"], t["tokens_discarded"]) == (5, 1)


def test_a_preempted_request_streams_on_token_exact(ref):
    """A pool too small for both requests' answers: the younger is preempted inside its answer, re-prefills prompt and
    handed-on blocks (whole blocks: a block in progress is denoised anew) and its stream is the reference's."""
    eng = build(ref, SMALL, num_blocks=14)  # 13 usable pages of 4: two prompts of 12 and 9, answers of 24
    ps = prompts(12, 9)
    streams = {0: [], 1: []}
    for i, p in enumerate(ps):
        eng.add_request(p, SamplingParams(max_new_tokens=24), stream_cb=lambda tok, done, i=i: streams[i].append(tok))
    while eng.has_work():
        eng.step()
    assert eng.num_preemptions >= 1
    for i, p in enumerate(ps):
        assert streams[i] == ref.generate(SMALL, SEED, p, 24)[0]
    assert eng.mgr.tables == {} and eng.mgr.num_free == eng.mgr.total_usable_blocks


def test_blocks_a_launch():
    """Pages are reserved for the blocks a launch's passes can reach: the block a row is at, and one more for every
    5 passes after its first under the static rule (2 under the dynamic one)."""
    class Bare(block_model.BlockDiffusionInferenceModel):
        def __init__(self, dynamic):
            self.dynamic, self.config = dynamic, SdarMoeConfig(**SMALL)

    assert [Bare(False).blocks_a_launch(p) for p in (1, 2, 5, 6, 10, 11)] == [1, 2, 2, 2, 3, 3]
    assert [Bare(True).blocks_a_launch(p) for p in (1, 2, 3, 5, 10)] == [1, 2, 2, 3, 6]


# ------------------------------------------------------------------ the served path
def test_completions_stream_a_block_at_a_time(ref, eng):
    """``ServingServer`` ``/v1/completions`` streamed: the engine's tokens in order; ``/metrics`` carries the device
    counts; the request's ``decode`` span in ``/debug/trace`` says how many passes its blocks took; a request that asks
    for sampling inside a block is refused at the door with 400, by name."""
    from paddlenlp_tpu.observability.tracer import TRACER
    from paddlenlp_tpu.serving import SchedulerConfig, ServingServer
    from paddlenlp_tpu.serving.metrics import MetricsRegistry

    TRACER.clear()
    server = ServingServer(eng, registry=MetricsRegistry(), scheduler_config=SchedulerConfig(max_inflight=4))
    port = server.start_in_thread()
    try:
        prompt = prompts(10)[0]

        def post(body):
            req = urllib.request.Request(f"http://127.0.0.1:{port}/v1/completions", json.dumps(body).encode(),
                                         {"Content-Type": "application/json"})
            return urllib.request.urlopen(req, timeout=120)

        toks = []
        for raw in post({"prompt": prompt, "max_tokens": 10, "stream": True}):
            if raw.startswith(b"data: ") and not raw.startswith(b"data: [DONE]"):
                choice = json.loads(raw[6:])["choices"][0]
                if "token" in choice:
                    toks.append(choice["token"])
        assert toks == ref.generate(SMALL, SEED, prompt, 10)[0]
        with pytest.raises(urllib.error.HTTPError) as refused:
            post({"prompt": prompt, "max_tokens": 4, "top_k": 5, "temperature": 0.7, "do_sample": True})
        assert refused.value.code == 400 and b"do_sample" in refused.value.read()
        text = urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=60).read().decode()
        for sample in ('paddlenlp_serving_diffusion_passes_total{kind="denoise"}',
                       'paddlenlp_serving_diffusion_passes_total{kind="commit"}',
                       'paddlenlp_serving_diffusion_tokens_total{kind="emitted"}',
                       'paddlenlp_serving_attn_kv_positions_total{layers="block"}'):
            assert any(line.startswith(sample) and float(line.rsplit(" ", 1)[1]) > 0 for line in text.splitlines()), sample
        trace = json.loads(urllib.request.urlopen(f"http://127.0.0.1:{port}/debug/trace", timeout=60).read())
        decode = [e for e in trace["traceEvents"] if e.get("name") == "decode" and e.get("cat") == "request"]
        assert decode and decode[-1]["args"]["denoise_passes"] == 10 and decode[-1]["args"]["commit_passes"] == 3
        launches = [e for e in trace["traceEvents"] if e.get("name") == "decode" and e.get("cat") == "engine"]
        assert launches and all(k in launches[-1]["args"] for k in ("denoise_passes", "commit_passes", "tokens_emitted",
                                                                     "attn_kv_visible", "expert_assignments_local"))
    finally:
        server.shutdown(drain_timeout_s=10)


# ------------------------------------------------------------------ the doors
DOORS = {
    "kv_cache_quant": (dict(kv_cache_quant="int8"), "a quantized KV cache"),
    "adapter_registry": (dict(adapter_registry=object()), "LoRA adapter pools"),
    "use_speculative": (dict(use_speculative=True), "speculative verify"),
    "mesh_shape": (dict(mesh_shape=(1, 2)), "a sharded backend"),
    "disagg_stages": (dict(disagg_stages=(1, 1)), "a disaggregated backend"),
    "host_kv_blocks": (dict(host_kv_blocks=8, enable_prefix_cache=True), "the host KV tier"),
    "enable_prefix_cache": (dict(enable_prefix_cache=True), "the prefix cache"),
    "monolithic_prefill": (dict(prefill_chunk_tokens=None), "prefills in chunks only"),
    "chunk_not_whole_blocks": (dict(prefill_chunk_tokens=6), "must be multiples of block_length"),
    "page_not_whole_blocks": (dict(block_size=2), "must be multiples of block_length"),
}


@pytest.mark.parametrize("door", DOORS, ids=list(DOORS))
def test_the_engines_door_refuses_by_name(door):
    kwargs, name = DOORS[door]
    model = SdarMoeForCausalLM(SdarMoeConfig(**SMALL))
    with pytest.raises(ValueError, match=name):
        InferenceEngine(model, **dict(ENGINE, **kwargs))


SAMPLING = dict(do_sample=dict(do_sample=True), top_k=dict(top_k=4), top_p=dict(top_p=0.9),
                repetition_penalty=dict(repetition_penalty=1.2), presence_penalty=dict(presence_penalty=0.5),
                frequency_penalty=dict(frequency_penalty=0.5))


@pytest.mark.parametrize("key", SAMPLING, ids=list(SAMPLING))
def test_sampling_inside_a_block_is_refused_by_name(eng, key):
    with pytest.raises(ValueError, match=f"does not serve {key}="):
        eng.add_request([1, 2, 3, 4, 5], SamplingParams(max_new_tokens=4, **SAMPLING[key]))
    assert not eng.has_work()


CONFIG_DOORS = {
    "sigmoid": (dict(scoring_func="sigmoid"), "softmax scores"),
    "dense_layers": (dict(mlp_only_layers=[0]), "routed experts"),
    "window": (dict(use_sliding_window=True, sliding_window=128), "sliding window"),
    "attention_bias": (dict(attention_bias=True), "attention_bias"),
    "rope_scaling": (dict(rope_scaling={"type": "yarn"}), "plain rotary embedding"),
    "block_of_six": (dict(block_length=6), "power of two"),
    "steps": (dict(denoising_steps=3), "must divide block_length"),
    "remasking": (dict(remasking="random"), "is not computed"),
    "mask_id": (dict(mask_token_id=151669), "outside the vocabulary"),
    "held_range": (dict(first_held_expert=6), "lie outside the router"),
}


@pytest.mark.parametrize("door", CONFIG_DOORS, ids=list(CONFIG_DOORS))
def test_the_configurations_door_refuses_by_name(door):
    kwargs, name = CONFIG_DOORS[door]
    with pytest.raises(ValueError, match=name):
        SdarMoeConfig(**dict(SMALL, **kwargs))


def test_the_llama_kind_refuses_the_configuration():
    """The configuration names its class; the llama kind's step programs refuse its layer kind by name."""
    from paddlenlp_tpu.experimental.inference_model import inference_model_class, refuse_unserved

    cfg = SdarMoeConfig(**SMALL)
    assert inference_model_class(cfg) is block_model.BlockDiffusionInferenceModel
    with pytest.raises(ValueError, match="gqa_block"):
        refuse_unserved(cfg, 64)


def test_auto_classes_find_the_family(tmp_path):
    from paddlenlp_tpu.transformers import AutoConfig, AutoModelForCausalLM

    SdarMoeConfig(**SMALL).save_pretrained(str(tmp_path))
    cfg = AutoConfig.from_pretrained(str(tmp_path))
    assert type(cfg) is SdarMoeConfig and cfg.mask_token_id == 95 and cfg.block_length == 4
    assert type(AutoModelForCausalLM.from_config(cfg)) is SdarMoeForCausalLM
