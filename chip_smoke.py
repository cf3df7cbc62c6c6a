"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the serving and the training main path once, through the entry points a
user calls, at the published width of a supported model, with seeded random
weights, no network and no tokenizer (prompts are token-id lists), and checks
what comes out. One process, which alone holds the chip; it starts no other.

    python chip_smoke.py             # one chip: phases 1-4 (what the driver runs)
    python chip_smoke.py --chips 4   # one four-chip host: the two sharded paths
                                     # and what each is compared with, nothing else

Every phase prints one JSON object on a line of its own. The LAST line of
standard output is ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": N}}`` and the exit code 0 only when every phase passed. With no TPU
(``JAX_PLATFORMS=cpu``, or no accelerator at all) it builds nothing, prints
``"ok": false`` and exits 2. A failing phase is never caught and carried past:
its exception ends the run with ``"ok": false`` and exit code 1.

Models (widths exactly as published; depth is full too):

- serving: Qwen2-1.5B — https://huggingface.co/Qwen/Qwen2-1.5B/blob/main/config.json
- training: Qwen2-0.5B — https://huggingface.co/Qwen/Qwen2-0.5B/blob/main/config.json
"""

from __future__ import annotations

import argparse
import gc
import http.client
import json
import sys
import tempfile
import threading
import time
import traceback
from importlib import metadata

import numpy as np

# ------------------------------------------------------------------ sizes
QWEN2_1P5B = dict(  # Qwen/Qwen2-1.5B config.json
    vocab_size=151936, hidden_size=1536, intermediate_size=8960, num_hidden_layers=28,
    num_attention_heads=12, num_key_value_heads=2, max_position_embeddings=131072,
    rope_theta=1e6, rms_norm_eps=1e-6, tie_word_embeddings=True,
    bos_token_id=151643, eos_token_id=151643)
QWEN2_0P5B = dict(  # Qwen/Qwen2-0.5B config.json
    vocab_size=151936, hidden_size=896, intermediate_size=4864, num_hidden_layers=24,
    num_attention_heads=14, num_key_value_heads=2, max_position_embeddings=131072,
    rope_theta=1e6, rms_norm_eps=1e-6, tie_word_embeddings=True,
    bos_token_id=151643, eos_token_id=151643)

# KV pool sized like a deployment: 9600 blocks x 16 tokens x 28 layers x 2 (k,v)
# x 2 kv heads x 128 x bf16 = 4.1 GiB (153,600 tokens of cache)
BLOCK_SIZE = 16
NUM_BLOCKS = 9600
MAX_BLOCKS_PER_SEQ = 160  # 2560 tokens: the longest request is 2048 + 64
MIN_POOL_BYTES = 4 * 2**30
MIN_SHARED_PREFIX = 512  # tokens the second-wave requests must find cached
MAX_BATCH = 8
MAX_TOKENS = 64
CHUNK_TOKENS = 512

# Phase 3 / --chips 4 logits bars. Both sides take the same bf16 q/k/v and
# accumulate in fp32; they differ in summation order (online softmax per KV
# block against one full softmax) and so in how the attention output rounds to
# bf16 (2^-8 relative). 28 layers of bf16 matmuls carry that forward, and the
# logits themselves are bf16 (spacing 2^-6..2^-5 near the largest, ~4). First
# chip run: largest difference 0.028 of the largest logit. The bars leave
# about twice that; a wrong mask, block walk or head mapping moves logits by
# their own size.
LOGITS_MAX_RTOL = 2.0 ** -4  # max |a - b| over max |b|
LOGITS_RMS_RTOL = 2.0 ** -4  # rms(a - b) over rms(b)
# The kernel alone, one call against plain fp32 attention on the same bf16
# inputs: nothing amplifies here, so the bar is the bf16 rounding of the
# output (2^-9 of a value) plus the kernel's bf16 passes over fp32
# probabilities — 2^-7 of the largest output.
KERNEL_MAX_RTOL = 2.0 ** -7

# Phase 4 batch. The published pretrain config asks 8 sequences of 2048 a
# step; the chip's compiler refuses the step at 8 (17.8 GiB against 15.75 GiB
# of HBM: the fp32 [B, T, 151936] logits alone are 9.3 GiB) and takes it at 4.
# The batch is lowered, not a width.
TRAIN_BATCH = 4
TRAIN_SEQ = 2048
TRAIN_STEPS = 5
# --chips 4 loss-parity bar against the one-device trajectory: the bar
# __graft_entry__.DRYRUN_ATOL holds virtual-device meshes to (bf16 compute and
# another collective reduction order land ~1e-3 apart; a sharding bug moves
# the loss by far more)
TRAIN_PARITY_ATOL = 2e-2


def emit(**obj):
    print(json.dumps(obj), flush=True)


class CompileCounter:
    """Counts what jax built: programs (cache hit or not), the seconds that
    took, and the persistent-cache hits among them."""

    def __init__(self):
        import jax

        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.programs += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    def snapshot(self):
        return {"programs": self.programs, "seconds": round(self.seconds, 1),
                "cache_hits": self.cache_hits}

    def since(self, before):
        now = self.snapshot()
        return {k: round(now[k] - before[k], 1) for k in now}


def device_memory(devices):
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append({"id": d.id, "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return out


def devices_of(tree):
    """Sorted ids of the devices the arrays of ``tree`` live on."""
    import jax

    ids = set()
    for leaf in jax.tree.leaves(tree):
        ids.update(d.id for d in leaf.sharding.device_set)
    return sorted(ids)


# ------------------------------------------------------------------ serving
def make_prompts(seed, vocab):
    """Seeded prompts. First wave: eight, concurrent, in three of the engine's
    pow2 length buckets (256, 1024, 2048). Second wave: two that each repeat
    768 / 1024 leading tokens of a first-wave prompt (the prefix cache only
    knows a prompt once its request has finished)."""
    rng = np.random.default_rng(seed)
    lengths = ([int(rng.integers(129, 257)) for _ in range(2)]
               + [int(rng.integers(800, 1025)) for _ in range(4)]
               + [int(rng.integers(1100, 2049)) for _ in range(2)])
    wave1 = [rng.integers(0, vocab, n).tolist() for n in lengths]
    wave2 = [wave1[2][:768] + rng.integers(0, vocab, 150).tolist(),
             wave1[7][:1024] + rng.integers(0, vocab, 200).tolist()]
    return wave1, wave2


def post_completion(port, prompt, index, results, first_token=None):
    """One real HTTP request; every third samples, the rest are greedy; the
    first of the wave streams."""
    body = {"prompt": prompt, "max_tokens": MAX_TOKENS, "timeout": 1100,
            "stream": first_token is not None}
    if index % 3 == 2:
        body.update(do_sample=True, temperature=0.8, top_p=0.9, top_k=50, seed=index)
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=1150)
    try:
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        out = {"status": resp.status, "prompt_tokens": len(prompt)}
        if not body["stream"]:
            doc = json.loads(resp.read())
            out.update(finish_reason=doc["choices"][0]["finish_reason"],
                       token_ids=doc["choices"][0]["token_ids"],
                       cached_tokens=doc["usage"]["cached_tokens"])
        else:
            toks, final = [], None
            for raw in resp:
                line = raw.decode().strip()
                if not line.startswith("data: ") or line == "data: [DONE]":
                    continue
                doc = json.loads(line[6:])
                if doc.get("object") == "error":
                    raise RuntimeError(f"stream error: {doc}")
                choice = doc["choices"][0]
                if "token" in choice:
                    toks.append(choice["token"])
                    first_token.set()
                if choice["finish_reason"] is not None:
                    final = doc
            out.update(finish_reason=final["choices"][0]["finish_reason"], token_ids=toks,
                       cached_tokens=final["usage"]["cached_tokens"], streamed=True)
        results[index] = out
    except BaseException as e:  # surfaced by the caller: no request is lost silently
        results[index] = {"status": None, "error": repr(e)}
        if first_token is not None:
            first_token.set()
    finally:
        conn.close()


def scrape(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        body = resp.read().decode()
        assert resp.status == 200, (path, resp.status, body[:200])
        return body
    finally:
        conn.close()


def serve_phase(model, prefill_chunk_tokens, seed, compiles, **engine_kw):
    """Phase 2, one prefill mode: InferenceEngine -> ServingServer on a local
    port -> ten HTTP completions -> the asserts of the issue."""
    import jax
    import jax.numpy as jnp

    from paddlenlp_tpu.experimental import InferenceEngine
    from paddlenlp_tpu.observability.prometheus import parse_prometheus_text
    from paddlenlp_tpu.serving import SchedulerConfig, ServingServer
    from paddlenlp_tpu.serving.metrics import MetricsRegistry

    mode = f"chunked_{prefill_chunk_tokens}" if prefill_chunk_tokens else "monolithic"
    before = compiles.snapshot()
    t0 = time.time()
    engine = InferenceEngine(
        model, max_batch_size=MAX_BATCH, block_size=BLOCK_SIZE, num_blocks=NUM_BLOCKS,
        max_blocks_per_seq=MAX_BLOCKS_PER_SEQ, dtype=jnp.bfloat16,
        prefill_chunk_tokens=prefill_chunk_tokens, **engine_kw)
    assert engine.infer.use_paged_kernel is True, "paged attention kernel is off on the TPU"
    pool_bytes = engine.pool.kv.size * engine.pool.kv.dtype.itemsize
    assert pool_bytes >= MIN_POOL_BYTES, pool_bytes
    server = ServingServer(engine, registry=MetricsRegistry(),
                           scheduler_config=SchedulerConfig(max_inflight=32, default_timeout_s=None))
    port = server.start_in_thread()
    try:
        wave1, wave2 = make_prompts(seed, model.config.vocab_size)
        results = [None] * (len(wave1) + len(wave2))
        # the first request streams; the other seven go out together on its
        # first token, so all eight are in flight at once and the engine
        # admits the seven in one step (three prefill shapes, not seven)
        first_token = threading.Event()
        threads = [threading.Thread(target=post_completion,
                                    args=(port, wave1[0], 0, results, first_token))]
        threads[0].start()
        assert first_token.wait(1100), "no first token within 1100 s"
        for i in range(1, len(wave1)):
            threads.append(threading.Thread(target=post_completion,
                                            args=(port, wave1[i], i, results)))
            threads[-1].start()
        for t in threads:
            t.join(1150)
            assert not t.is_alive(), "request thread still running"
        for j, prompt in enumerate(wave2):  # one after the other: shared prefixes
            post_completion(port, prompt, len(wave1) + j, results)
        metrics = parse_prometheus_text(scrape(port, "/metrics"))
        efficiency = json.loads(scrape(port, "/debug/efficiency"))
    finally:
        server.shutdown(drain_timeout_s=30)
    jax.block_until_ready(engine.pool.kv)
    wall = time.time() - t0

    for i, r in enumerate(results):
        assert r is not None and r.get("status") == 200, (i, r)
        assert r["finish_reason"] in ("length", "stop"), (i, r["finish_reason"])
        n = len(r["token_ids"])
        if r["finish_reason"] == "length":
            assert n == MAX_TOKENS, (i, n)
        else:
            assert 1 <= n <= MAX_TOKENS and r["token_ids"][-1] in engine.eos_ids, (i, n)
        assert all(0 <= t < model.config.vocab_size for t in r["token_ids"]), i
    assert any(r.get("streamed") for r in results)
    total = lambda name: sum(metrics[f"paddlenlp_serving_{name}"].samples.values())
    restarts, quarantines = total("engine_restarts_total"), total("slot_quarantines_total")
    prefix_hits = total("prefix_cache_hits_total")
    assert restarts == 0 and quarantines == 0, (restarts, quarantines)
    assert prefix_hits > 0, "no prefix-cache hit"
    assert min(r["cached_tokens"] for r in results[len(wave1):]) >= MIN_SHARED_PREFIX, \
        [r["cached_tokens"] for r in results]
    backend = engine.backend
    line = dict(
        phase="serving", mode=mode, model="Qwen2-1.5B", ok=True,
        requests=len(results), concurrent=len(wave1), streamed=1,
        prompt_tokens=sum(r["prompt_tokens"] for r in results),
        completion_tokens=sum(len(r["token_ids"]) for r in results),
        finish_reasons=sorted({r["finish_reason"] for r in results}),
        cached_tokens=[r["cached_tokens"] for r in results[len(wave1):]],
        prefix_hits=int(prefix_hits), engine_restarts=int(restarts),
        use_paged_kernel=engine.infer.use_paged_kernel,
        kv_pool_gib=round(pool_bytes / 2**30, 2),
        shape_buckets=efficiency["ledger"]["shape_buckets"],
        engine_compiles=efficiency["ledger"]["compiles"],
        compile=compiles.since(before), wall_seconds=round(wall, 1),
        params_devices=devices_of(backend.params), pool_devices=devices_of(backend.pool.kv),
        memory=device_memory(jax.devices()))
    return line, [r["token_ids"] for r in results], engine


def reference_logits(backend, seed, vocab):
    """Prefill-then-decode through ``backend.verify`` (the entry that returns
    logits): 4 rows of 256 tokens from position 0, then one more token per
    row with the rows at different context lengths. Returns the fp32 logits of
    the last prefill position and of the decode step, on the host."""
    rng = np.random.default_rng(seed + 1)
    rows, width = 4, 256
    tokens = rng.integers(0, vocab, (rows, width)).astype(np.int32)
    tables = (1 + np.arange(rows)[:, None] * MAX_BLOCKS_PER_SEQ
              + np.arange(MAX_BLOCKS_PER_SEQ)[None, :]).astype(np.int32)
    _, logits = backend.verify(tokens, tables, np.zeros(rows, np.int32), need_logits=True)
    prefill_last = logits[:, -1].copy()
    del logits
    ctx = np.asarray([width, width - 16, width - 56, width // 2 + 2], np.int32)  # ragged
    nxt = rng.integers(0, vocab, (rows, 1)).astype(np.int32)
    _, logits = backend.verify(nxt, tables, ctx, need_logits=True)
    return prefill_last, logits[:, 0]


def compare_logits(name, got, want):
    assert np.isfinite(got).all() and np.isfinite(want).all(), name
    max_ratio = float(np.abs(got - want).max() / np.abs(want).max())
    rms_ratio = float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))
    assert max_ratio <= LOGITS_MAX_RTOL and rms_ratio <= LOGITS_RMS_RTOL, \
        (name, max_ratio, rms_ratio)
    return {"max_diff_over_max_logit": round(max_ratio, 5), "rms_diff_over_rms_logit": round(rms_ratio, 5),
            "argmax_equal": f"{int((got.argmax(-1) == want.argmax(-1)).sum())}/{len(want)}"}


def kernel_alone(seed, tokens):
    """The ragged kernel at Qwen2-1.5B's attention shape, aimed at layer 1 of a
    three-layer pool of token-major rows, against plain fp32 jax.numpy
    attention over that layer's gathered blocks, same bf16 inputs. Rows: a
    full one, one that ends early, one dead, one at a late start. 1024 tokens
    are two query tiles of the kernel, 256 one, 1 a decode step."""
    import jax
    import jax.numpy as jnp

    from paddlenlp_tpu.ops.pallas.paged_attention import ragged_paged_attention

    rng = np.random.default_rng(seed + 2)
    rows, heads, kv_heads, head_dim, blocks = 4, 12, 2, 128, MAX_BLOCKS_PER_SEQ
    bf16 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    q = bf16(rows, tokens, heads, head_dim)
    layers, layer = 3, 1
    pool = bf16(layers, 2, rows * blocks + 1, BLOCK_SIZE, kv_heads * head_dim)
    tables = jnp.asarray(1 + rng.permutation(rows * blocks).reshape(rows, blocks), jnp.int32)
    span = blocks * BLOCK_SIZE
    start = jnp.asarray([0, 37, 5, span - tokens], jnp.int32)
    lens = jnp.asarray([tokens, (tokens + 1) // 2, 0, tokens], jnp.int32)

    def reference(q, pool, tables, start, lens):
        flat = lambda plane: plane[tables].reshape(
            rows, span, kv_heads, head_dim).astype(jnp.float32)
        k = jnp.repeat(flat(pool[layer, 0]), heads // kv_heads, axis=2)
        v = jnp.repeat(flat(pool[layer, 1]), heads // kv_heads, axis=2)
        s = jnp.einsum("btnh,bsnh->bnts", q.astype(jnp.float32), k,
                       precision="highest") * head_dim ** -0.5
        q_pos = start[:, None] + jnp.arange(tokens)[None, :]
        seen = jnp.arange(span)[None, None, :] <= q_pos[:, :, None]
        p = jax.nn.softmax(jnp.where(seen[:, None], s, -1e30), axis=-1)
        out = jnp.einsum("bnts,bsnh->btnh", p, v, precision="highest")
        live = jnp.arange(tokens)[None, :] < lens[:, None]
        return jnp.where(live[:, :, None, None], out, 0.0)

    got = np.asarray(jax.jit(ragged_paged_attention)(q, pool, tables, start, lens, layer),
                     np.float32)
    want = np.asarray(jax.jit(reference)(q, pool, tables, start, lens))
    assert np.isfinite(got).all()
    assert (got[2] == 0).all() and (got[1, (tokens + 1) // 2:] == 0).all(), "dead rows not zero"
    ratio = float(np.abs(got - want).max() / np.abs(want).max())
    assert ratio <= KERNEL_MAX_RTOL, (tokens, ratio)
    return round(ratio, 6)


def kernel_agreement_phase(model, seed, compiles):
    """Phase 3: the Pallas ragged kernel against the XLA gather path, both on
    the chip, same weights, same pool shape, compared on logits."""
    import jax.numpy as jnp

    from paddlenlp_tpu.experimental.backend import SingleDeviceBackend

    before = compiles.snapshot()
    out = {}
    for name in ("pallas", "xla"):
        backend = SingleDeviceBackend(
            model, max_batch_size=MAX_BATCH, block_size=BLOCK_SIZE, num_blocks=NUM_BLOCKS,
            max_blocks_per_seq=MAX_BLOCKS_PER_SEQ, dtype=jnp.bfloat16, decode_steps=8,
            eos_ids=())
        assert backend.infer.use_paged_kernel is True, "paged attention kernel is off on the TPU"
        # the kernel choice is read when a step is traced: nothing is yet
        backend.infer.use_paged_kernel = name == "pallas"
        out[name] = reference_logits(backend, seed, model.config.vocab_size)
        del backend
        gc.collect()
    return dict(
        phase="kernel_agreement", model="Qwen2-1.5B", ok=True,
        kernel_alone={"max_rtol": KERNEL_MAX_RTOL,
                      "max_diff_over_max_out": {f"T={t}": kernel_alone(seed, t) for t in (1, 256, 1024)}},
        max_rtol=LOGITS_MAX_RTOL, rms_rtol=LOGITS_RMS_RTOL,
        prefill=compare_logits("prefill", out["pallas"][0], out["xla"][0]),
        decode=compare_logits("decode", out["pallas"][1], out["xla"][1]),
        compile=compiles.since(before))


# ------------------------------------------------------------------ training
class SyntheticLM:
    """Seeded, learnable token sequences: each row walks a small slice of the
    vocabulary with a fixed stride, so the loss can fall within a few steps
    (uniform random tokens sit at ln(vocab) for ever)."""

    def __init__(self, seed, n, seq_len, vocab):
        rng = np.random.default_rng(seed)
        span = min(4096, vocab)
        self.starts = rng.integers(0, span, n)
        self.strides = rng.integers(1, 8, n)
        self.seq_len, self.span = seq_len, span

    def __len__(self):
        return len(self.starts)

    def __getitem__(self, i):
        ids = ((self.starts[i] + self.strides[i] * np.arange(self.seq_len)) % self.span)
        ids = ids.astype(np.int32)
        return {"input_ids": ids, "labels": ids.copy()}


def run_trainer(seed, layout, one_device=False):
    """Trainer + TrainingArguments on Qwen2-0.5B, as llm/run_pretrain.py drives
    them. ``layout`` holds the parallel degrees; the global batch stays
    TRAIN_BATCH. Returns (losses, trainer)."""
    import jax

    from paddlenlp_tpu.trainer import Trainer, TrainingArguments
    from paddlenlp_tpu.transformers import LlmMetaConfig, Qwen2Config, Qwen2ForCausalLM

    data_shards = layout.get("sharding_parallel_degree", 1)
    args = TrainingArguments(
        output_dir=tempfile.mkdtemp(prefix="chip_smoke_train_"), max_steps=TRAIN_STEPS,
        per_device_train_batch_size=TRAIN_BATCH // data_shards, gradient_accumulation_steps=1,
        learning_rate=3e-4, lr_scheduler_type="constant", warmup_steps=0, weight_decay=0.01,
        max_grad_norm=1.0, bf16=True, seed=seed, logging_steps=1, save_strategy="no",
        disable_tqdm=True, use_flash_attention=True,
        # llm/config/qwen2/pretrain_argument.json
        recompute=True, recompute_granularity="save_qkv_attn", **layout)
    if one_device:
        # the comparison run of --chips 4: a mesh of the first chip alone.
        # TrainingArguments sizes its mesh from every device jax has, and has
        # no option for fewer, so the mesh is handed to it.
        from paddlenlp_tpu.parallel import MeshConfig, create_mesh

        args._mesh = create_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
        args.data_parallel_degree = 1
    config = Qwen2Config(**QWEN2_0P5B)
    LlmMetaConfig.set_llm_config(config, args)
    config.use_cache = False
    model = Qwen2ForCausalLM.from_config(config, dtype="bfloat16", param_dtype="float32", seed=seed)
    trainer = Trainer(model=model, args=args,
                      train_dataset=SyntheticLM(seed, TRAIN_BATCH * TRAIN_STEPS, TRAIN_SEQ,
                                                config.vocab_size))
    out = trainer.train()
    jax.block_until_ready(trainer.train_state.params)
    assert out.global_step == TRAIN_STEPS, out.global_step
    losses = [h["loss"] for h in trainer.state.log_history if "loss" in h][:TRAIN_STEPS]
    assert len(losses) == TRAIN_STEPS and all(np.isfinite(x) for x in losses), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    return losses, trainer


def train_step_kernels(trainer):
    """The kernels in the train step, read from the text of the step the
    trainer ran (lowered again from the same function and the state it holds)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddlenlp_tpu.parallel import use_mesh

    shape_of = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    state = jax.tree.map(shape_of, trainer.train_state)
    rows = trainer.args.global_train_batch_size
    ids = jax.ShapeDtypeStruct((rows, TRAIN_SEQ), np.int32,
                               sharding=NamedSharding(trainer.mesh, P(("dp", "fsdp"))))
    with use_mesh(trainer.mesh):
        text = trainer._train_step_fn.lower(
            state, {"input_ids": ids, "labels": ids}, jax.random.key(0)).as_text()
    found = {name: text.count(f'kernel_name = "{name}"') for name in
             ("flash_attention_fwd", "flash_attention_bwd_dq", "flash_attention_bwd_dkv")}
    assert "tpu_custom_call" in text and all(found.values()), \
        f"Pallas flash forward and backward are not both in the train step: {found}"
    return found


def train_phase(seed, compiles):
    import jax

    before = compiles.snapshot()
    t0 = time.time()
    losses, trainer = run_trainer(seed, {})
    line = dict(
        phase="training", model="Qwen2-0.5B", ok=True, steps=TRAIN_STEPS,
        batch=TRAIN_BATCH, batch_asked=8, seq_len=TRAIN_SEQ,
        batch_note="lowered from 8: the chip's compiler refuses the step at 8 for HBM",
        recompute="save_qkv_attn", losses=[round(x, 4) for x in losses],
        kernels=train_step_kernels(trainer), mesh=dict(trainer.mesh.shape),
        compile=compiles.since(before), wall_seconds=round(time.time() - t0, 1),
        memory=device_memory(jax.devices()))
    return line


# ------------------------------------------------------------------ four chips
def four_chip_serving(seed, compiles):
    """(a) InferenceEngine(mesh_shape=(2, 2)) against the one-device backend:
    the same HTTP requests, and prefill-then-decode logits compared."""
    import jax.numpy as jnp

    from paddlenlp_tpu.transformers import Qwen2Config, Qwen2ForCausalLM

    model = Qwen2ForCausalLM.from_config(Qwen2Config(**QWEN2_1P5B), dtype=jnp.bfloat16,
                                         param_dtype=jnp.bfloat16, seed=seed)
    lines, logits, tokens = [], {}, {}
    for name, kw in (("mesh_2x2", dict(mesh_shape=(2, 2))), ("one_device", {})):
        line, tokens[name], engine = serve_phase(model, None, seed, compiles, **kw)
        # the server is shut down; its backend still answers
        logits[name] = reference_logits(engine.backend, seed, model.config.vocab_size)
        line["layout"] = name
        line["backend"] = engine.backend.describe()
        lines.append(line)
        del engine
        gc.collect()
    for line in lines:
        emit(**line)
    sharded, single = lines
    assert sharded["params_devices"] == sharded["pool_devices"] == [0, 1, 2, 3], sharded
    assert single["params_devices"] == single["pool_devices"] == [0], single
    same = sum(a == b for a, b in zip(tokens["mesh_2x2"], tokens["one_device"]))
    emit(phase="serving_parity", ok=True, max_rtol=LOGITS_MAX_RTOL, rms_rtol=LOGITS_RMS_RTOL,
         prefill=compare_logits("prefill", logits["mesh_2x2"][0], logits["one_device"][0]),
         decode=compare_logits("decode", logits["mesh_2x2"][1], logits["one_device"][1]),
         requests_with_equal_tokens=f"{same}/{len(tokens['one_device'])}")


def four_chip_training(seed, compiles):
    """(b) Trainer under tp 2 x fsdp 2 (the layout __graft_entry__._topologies(4)
    uses on virtual devices) against the one-device loss trajectory."""
    import jax

    runs = {}
    for name, layout, one in (
            ("one_device", {}, True),
            ("tp2_fsdp2", dict(tensor_parallel_degree=2, sharding_parallel_degree=2,
                               sharding="stage3"), False)):
        before = compiles.snapshot()
        losses, trainer = run_trainer(seed, layout, one_device=one)
        runs[name] = losses
        emit(phase="training", layout=name, model="Qwen2-0.5B", ok=True,
             losses=[round(x, 4) for x in losses], mesh=dict(trainer.mesh.shape),
             kernels=train_step_kernels(trainer),
             params_devices=devices_of(trainer.train_state.params),
             compile=compiles.since(before), memory=device_memory(jax.devices()))
        if name == "tp2_fsdp2":
            assert devices_of(trainer.train_state.params) == [0, 1, 2, 3]
        del trainer
        gc.collect()
    delta = max(abs(a - b) for a, b in zip(runs["tp2_fsdp2"], runs["one_device"]))
    assert delta < TRAIN_PARITY_ATOL, (delta, runs)
    emit(phase="training_parity", ok=True, max_loss_delta=round(delta, 5), atol=TRAIN_PARITY_ATOL)


# ------------------------------------------------------------------ main
def run(args, device):
    import jax
    import jax.numpy as jnp

    from paddlenlp_tpu.utils.env import device_peak_flops, enable_compile_cache

    cache_dir = enable_compile_cache()
    compiles = CompileCounter()
    emit(phase="device", ok=True, **device,
         versions={"jax": jax.__version__, "jaxlib": metadata.version("jaxlib"),
                   "libtpu": metadata.version("libtpu")},
         peak_bf16_flops=device_peak_flops(), compile_cache_dir=cache_dir,
         hbm_bytes=(jax.devices()[0].memory_stats() or {}).get("bytes_limit"))

    if args.chips == 4:
        four_chip_serving(args.seed, compiles)
        gc.collect()
        four_chip_training(args.seed, compiles)
    else:
        from paddlenlp_tpu.transformers import Qwen2Config, Qwen2ForCausalLM

        model = Qwen2ForCausalLM.from_config(Qwen2Config(**QWEN2_1P5B), dtype=jnp.bfloat16,
                                             param_dtype=jnp.bfloat16, seed=args.seed)
        for chunk in (None, CHUNK_TOKENS):
            emit(**serve_phase(model, chunk, args.seed, compiles)[0])
            gc.collect()
        emit(**kernel_agreement_phase(model, args.seed, compiles))
        del model
        gc.collect()
        emit(**train_phase(args.seed, compiles))
    emit(phase="compile_cache", dir=cache_dir, **compiles.snapshot())


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the two sharded paths and what each is compared with")
    parser.add_argument("--seed", type=int, default=0, help="weights, prompts and data")
    args = parser.parse_args()

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if device["platform"] != "tpu" or device["count"] != args.chips:
        # no accelerator (or not the number asked for): build nothing
        print(json.dumps({"ok": False, "device": device,
                          "error": f"needs {args.chips} TPU chip(s)"}), flush=True)
        return 2
    try:
        run(args, device)
    except BaseException:
        traceback.print_exc()
        print(json.dumps({"ok": False, "device": device}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
