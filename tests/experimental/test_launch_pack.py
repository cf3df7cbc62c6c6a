"""A launch's host inputs cross to the device in one transfer (``launch_pack.py``):
the step programs take one packed ``int32`` buffer with a static layout, take it
apart and run the step on the fields.

(a) pack -> unpack under ``jit`` is bit for bit, for every launch kind's field set
and every dtype a buffer holds; (b) for each of the four model classes a launch
through the backend returns what the step's body returns on the unpacked arrays;
(c) a launch of each kind makes exactly one host-to-device transfer, and its
``dispatch`` span says so; (d) the sharded backend lands the buffer replicated
and serves the single device's tokens."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import loader
from paddlenlp_tpu.experimental import InferenceEngine, SamplingParams
from paddlenlp_tpu.experimental import backend as backend_mod
from paddlenlp_tpu.experimental.backend import MixedRow, SingleDeviceBackend, samp_arrays
from paddlenlp_tpu.experimental.inference_model import SAMP_FIELDS
from paddlenlp_tpu.experimental.launch_pack import layout_of, pack, packed_size, unpack
from paddlenlp_tpu.observability.tracer import TRACER
from paddlenlp_tpu.transformers import LlamaConfig, LlamaForCausalLM

ROWS, TABLE, VOCAB = 4, 16, 96


def bits(tree):
    """Every leaf of ``tree`` as its bytes, so that -0.0, a NaN's payload and a denormal count."""
    return [(np.asarray(x).shape, np.asarray(x).dtype.name, np.asarray(x).tobytes()) for x in jax.tree.leaves(tree)]


# ------------------------------------------------------------------ (a) pack -> unpack
def launch_fields(kind, rng):
    """The field set a launch of ``kind`` packs (backend.py), random values, no adapters."""
    ints = lambda *shape: rng.integers(-2**31, 2**31, shape, dtype=np.int64).astype(np.int32)
    flags = lambda n: rng.integers(0, 2, n).astype(bool)
    sampling = [SamplingParams(seed=int(s), temperature=float(t), top_k=int(k), top_p=0.9, do_sample=bool(d),
                               repetition_penalty=1.3, presence_penalty=-0.25, frequency_penalty=0.5)
                for s, t, k, d in zip(rng.integers(0, 2**31 - 1, ROWS), rng.random(ROWS), rng.integers(0, 50, ROWS),
                                      flags(ROWS))]
    samp = samp_arrays(sampling + [None], ROWS + 1 if kind == "mixed" else ROWS)
    if kind == "prefill":
        return dict(input_ids=ints(ROWS, 8), block_tables=ints(ROWS, TABLE), suffix_lens=ints(ROWS),
                    cached_lens=ints(ROWS), slot_idx=ints(ROWS), **samp)
    if kind == "decode":
        return dict(tokens=ints(ROWS), block_tables=ints(ROWS, 2, TABLE), context_lens=ints(ROWS), done0=flags(ROWS),
                    remaining=ints(ROWS), **samp, adapter_idx=ints(ROWS))
    if kind == "verify":
        return dict(tokens=ints(ROWS, 3), block_tables=ints(ROWS, TABLE), start_pos=ints(ROWS))
    return dict(chunk_ids=ints(1, 8), chunk_tables=ints(1, TABLE), chunk_qlens=ints(1), chunk_start=ints(1),
                chunk_slots=ints(1), chunk_emit=flags(1), dec_tokens=ints(ROWS), dec_tables=ints(ROWS, TABLE),
                dec_start=ints(ROWS), dec_slots=ints(ROWS), dec_live=flags(ROWS), **samp)


DTYPE_CASES = {
    "int32": np.array([0, 1, -1, 2**31 - 1, -2**31, 0x7FC00001], np.int32),
    # -0.0, both infinities, the smallest denormal and a NaN with a payload: a conversion by value loses each
    "float32": np.array([0.0, -0.0, np.inf, -np.inf, 1e-45, -1e-45, 1.0, 3.4028235e38], np.float32),
    "float32_nan_payload": np.array([0x7FC00001, 0xFFC12345 - 2**32, 0x7F800001], np.int32).view(np.float32),
    "bool": np.array([True, False, False, True, True], bool),
}


@pytest.mark.parametrize("case", ["prefill", "decode", "verify", "mixed", *DTYPE_CASES])
def test_pack_then_unpack_under_jit_is_bit_for_bit(case):
    if case in DTYPE_CASES:
        fields = {"lead": np.arange(3, dtype=np.int32), case: DTYPE_CASES[case].reshape(1, -1), "empty": np.zeros((0, 4), np.float32),
                  "tail": np.float32([2.5])}
    else:
        fields = launch_fields(case, np.random.default_rng(7))
    buf, layout = pack(fields)
    assert buf.dtype == np.int32 and buf.shape == (packed_size(layout),) and layout == layout_of(fields)
    out = jax.jit(unpack, static_argnums=(1,))(buf, layout)
    assert set(out) == set(fields)
    for name, want in fields.items():
        got = np.asarray(out[name])
        assert (got.shape, got.dtype) == (want.shape, want.dtype), name
        assert got.tobytes() == want.tobytes(), name


def test_a_buffer_holds_three_dtypes_and_says_which_field_is_another():
    with pytest.raises(TypeError, match="'context_lens' is int64"):
        pack(dict(tokens=np.zeros(4, np.int32), context_lens=np.zeros(4, np.int64)))
    # the layout is what a jit keys on: the same shapes give an equal, hashable layout, other shapes another
    a, b = (pack(dict(x=np.zeros((2, n), np.float32), y=np.ones(2, bool)))[1] for n in (3, 3))
    assert a == b and hash(a) == hash(b) and a != layout_of(dict(x=np.zeros((2, 4), np.float32), y=np.ones(2, bool)))


def test_the_sampling_rows_are_host_arrays_with_the_devices_dtypes():
    samp = samp_arrays([SamplingParams(seed=5, temperature=0.5, top_k=3, do_sample=True), None], 3)
    assert tuple(samp) == SAMP_FIELDS and all(type(v) is np.ndarray and v.shape == (3,) for v in samp.values())
    assert {k: v.dtype.name for k, v in samp.items()} == dict(
        seeds="int32", temperature="float32", top_k="int32", top_p="float32", do_sample="bool",
        repetition_penalty="float32", presence_penalty="float32", frequency_penalty="float32")
    assert samp["seeds"].tolist() == [5, 0, 0] and samp["do_sample"].tolist() == [True, False, False]
    assert samp_arrays([SamplingParams(seed=2**31 + 5)])["seeds"].tolist() == [-2**31 + 5]  # wraps, as the device's conversion did
    assert samp["temperature"].tolist() == [0.5, 1.0, 1.0] and samp["top_p"].tolist() == [1.0, 1.0, 1.0]


# ------------------------------------------------------------------ (b) the program is the body on the unpacked arrays
def host_unpack(buf, layout):
    """The inverse of ``pack`` on the host, written out here: what the body is called with."""
    out, off = {}, 0
    for name, shape, dtype in layout:
        part = buf[off:off + int(np.prod(shape))]
        out[name] = {"int32": part, "float32": part.view(np.float32), "bool": part != 0}[dtype].reshape(shape)
        off += part.size
    return out


def llama_model():
    cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=64, intermediate_size=112, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=256,
                      eos_token_id=None, pad_token_id=0, use_scan_layers=True)
    return LlamaForCausalLM.from_config(cfg, seed=0)


def kind_engine(kind):
    """A tiny engine of each model class, as the kinds' own test files build theirs."""
    if kind == "llama":
        return InferenceEngine(llama_model(), max_batch_size=4, block_size=4, num_blocks=64, max_blocks_per_seq=16,
                               decode_steps=4, dtype=jnp.float32)
    if kind == "latent":
        import test_latent_serving as t
        from paddlenlp_tpu.transformers import Dots3NoteConfig as Config, Dots3NoteForCausalLM as Model
        ref = loader.module_from("reference", "dots3_note")
    elif kind == "state":
        import test_state_serving as t
        from paddlenlp_tpu.transformers import NemotronHConfig as Config, NemotronHForCausalLM as Model
        ref = loader.module_from("reference", "nemotron_h")
    else:
        import test_window_serving as t
        from paddlenlp_tpu.transformers import ExaoneMoeConfig as Config, ExaoneMoeForCausalLM as Model
        ref = loader.module_from("reference", "exaone_moe")
    model = Model(Config(**t.SMALL))
    model.params = jax.jit(lambda s: ref.program_params(t.SMALL, s, jnp.float32))(ref.seed_array(t.SEED))
    return InferenceEngine(model, **t.ENGINE)


KIND_CLASS = {"llama": "PagedInferenceModel", "latent": "LatentInferenceModel", "state": "StateSpaceInferenceModel",
              "windowed": "WindowedInferenceModel"}


@pytest.fixture(scope="module", params=list(KIND_CLASS))
def recorded(request):
    """A kind's engine driven through two requests with penalties and seeded sampling; the first launch of each
    program as (pool before, packed buffer, layout, the arguments already on the device, what it returned)."""
    eng = kind_engine(request.param)
    infer = eng.backend.infer
    assert type(infer).__name__ == KIND_CLASS[request.param]
    first = {}

    def spy(name):
        real = getattr(infer, name)

        def call(params, pool, packed, layout, *on_device, **kw):
            if name in first:
                return real(params, pool, packed, layout, *on_device, **kw)
            before = jax.tree.map(jnp.copy, pool)  # the launch donates its pool, and the next one what it returned
            out = real(params, pool, packed, layout, *on_device, **kw)
            first[name] = (params, before, np.asarray(packed), layout, on_device, jax.tree.map(jnp.copy, out))
            return out
        return call

    for name in ("prefill", "decode", "mixed_step_flat"):
        setattr(infer, name, spy(name))
    rng = np.random.RandomState(1)
    eng.generate([rng.randint(0, VOCAB, n).tolist() for n in (11, 6)],
                 SamplingParams(max_new_tokens=6, do_sample=True, seed=11, temperature=0.7, top_k=20,
                                repetition_penalty=1.2, frequency_penalty=0.1))
    return request.param, infer, first


@pytest.mark.parametrize("program", ["decode", "prompt"])
def test_a_launch_through_the_backend_is_the_body_on_the_unpacked_arrays(recorded, program):
    kind, infer, first = recorded
    name = "decode" if program == "decode" else "prefill" if kind == "llama" else "mixed_step_flat"
    params, pool, buf, layout, on_device, out = first[name]
    f = host_unpack(buf, layout)
    samp = {k: f[k] for k in SAMP_FIELDS}
    if name == "decode":
        (counts,) = on_device
        want = jax.jit(infer._decode_body)(params, pool, f["tokens"], f["block_tables"], f["context_lens"], f["done0"],
                                           f["remaining"], counts, samp)
    elif name == "mixed_step_flat":
        (counts,) = on_device
        want = jax.jit(infer._mixed_flat_body)(
            params, pool, f["chunk_ids"], f["chunk_tables"], f["chunk_qlens"], f["chunk_start"], f["chunk_slots"],
            f["chunk_emit"], f["dec_tokens"], f["dec_tables"], f["dec_start"], f["dec_slots"], f["dec_live"], counts, samp)
    else:
        cached_counts, counts = on_device
        tokens, rows, new_pool = jax.jit(infer._prefill_body)(
            params, pool, f["input_ids"], f["block_tables"], f["suffix_lens"], f["cached_lens"], cached_counts, samp)
        landed = np.array(counts)  # the batch's rows at their slots; the padding rows' index lies past the last slot
        for row, slot in zip(np.asarray(rows), f["slot_idx"]):
            if slot < landed.shape[0]:
                landed[slot] = row
        assert (f["slot_idx"] < landed.shape[0]).any() and (landed != np.asarray(counts)).any()
        want = (tokens, landed, new_pool)
    assert bits(out) == bits(want)


# ------------------------------------------------------------------ (c) one transfer a launch
@pytest.fixture(scope="module")
def llama_backend():
    return SingleDeviceBackend(llama_model(), max_batch_size=ROWS, block_size=4, num_blocks=64, max_blocks_per_seq=TABLE,
                               dtype=jnp.float32, decode_steps=2, eos_ids=())


def table_of(*blocks):
    return np.array(list(blocks) + [0] * (TABLE - len(blocks)), np.int32)


def launch(be, kind):
    greedy = SamplingParams(max_new_tokens=4)
    if kind in ("prefill", "prefill_prefix_hit"):
        ids = np.arange(2 * 8, dtype=np.int32).reshape(2, 8) % VOCAB
        cached = [(0, list(range(20)), 4)] if kind == "prefill_prefix_hit" else []
        return be.prefill(ids, np.stack([table_of(1, 2, 3), table_of(4, 5, 6)]), np.array([8, 5], np.int32), cached,
                          [greedy, greedy], [2, 0])
    if kind == "decode":
        tables = np.stack([table_of(1, 2, 3), table_of(4, 5, 6), table_of(), table_of()])
        return be.decode(np.array([3, 4, 0, 0], np.int32), tables, np.array([8, 5, 0, 0], np.int32),
                         np.array([False, False, True, True]), np.array([4, 4, 0, 0], np.int32), [greedy, greedy, None, None])
    if kind == "verify":
        tables = np.stack([table_of(1, 2, 3), table_of(4, 5, 6), table_of(), table_of()])
        return be.verify(np.zeros((ROWS, 3), np.int32), tables, np.array([8, 5, 0, 0], np.int32), need_logits=False)
    chunk = MixedRow(slot=1, tokens=np.arange(5, dtype=np.int32), start=0, table=table_of(7, 8), emit=False, sampling=greedy)
    dec = MixedRow(slot=0, tokens=np.array([9], np.int32), start=8, table=table_of(1, 2, 3), emit=True, sampling=greedy)
    return be.mixed_step([chunk], [dec])


@pytest.mark.parametrize("kind", ["prefill", "decode", "verify", "mixed", "prefill_prefix_hit"])
def test_a_launch_makes_one_host_to_device_transfer_and_its_dispatch_span_says_so(llama_backend, monkeypatch, kind):
    launch(llama_backend, kind)  # compiled, so that the counted launch traces nothing
    handed = []

    def counting(real):
        def call(x, *args, **kw):
            if isinstance(x, np.ndarray):  # a host array handed to the device
                handed.append(x.nbytes)
            return real(x, *args, **kw)
        return call

    for module, name in ((jax, "device_put"), (jnp, "asarray"), (jnp, "array")):
        monkeypatch.setattr(module, name, counting(getattr(module, name)))
    TRACER.clear()
    t0 = TRACER.now()
    launch(llama_backend, kind)
    (span,) = [s for s in TRACER.snapshot(since_ts=t0) if s.name == "dispatch"]
    # a prefix hit ships its cached span's counts besides (n x vocab, bytes and not a small array)
    extra = [2 * VOCAB * 4] if kind == "prefill_prefix_hit" else []
    assert span.args["h2d_arrays"] == len(handed) == 1 + len(extra)
    assert span.args["h2d_bytes"] == sum(handed) and sorted(handed)[1:] == extra
    assert span.args["program"] == kind.split("_")[0]


def test_adapter_rows_ride_the_buffer_when_a_registry_is_attached(llama_backend, monkeypatch):
    sent = []
    monkeypatch.setattr(backend_mod, "pack", lambda fields: sent.append(list(fields)) or pack(fields))
    launch(llama_backend, "decode")
    assert "adapter_idx" not in sent[-1]  # no registry: the program carries no adapter operand at all
    monkeypatch.setattr(llama_backend, "adapter_registry", object())
    assert llama_backend._adapter_idx([0, 2], 4).tolist() == [0, 2, 0, 0]
    monkeypatch.setattr(llama_backend, "_to_device", lambda buf: buf)
    buf, layout = llama_backend._send(tokens=np.zeros(4, np.int32), adapter_idx=llama_backend._adapter_idx([0, 2], 4),
                                      dec_adapter=None)
    assert [name for name, _, _ in layout] == ["tokens", "adapter_idx"] and buf.tolist() == [0, 0, 0, 0, 0, 2, 0, 0]


# ------------------------------------------------------------------ (d) the sharded backend
def test_the_sharded_backend_lands_the_buffer_replicated_and_serves_the_same_tokens(eight_devices):
    model = llama_model()
    kw = dict(max_batch_size=4, block_size=4, num_blocks=64, max_blocks_per_seq=16, decode_steps=4)
    prompts = [list(range(3, 14)), [7, 9, 2, 40, 41]]
    sampling = SamplingParams(max_new_tokens=8, do_sample=True, seed=5, temperature=0.8, top_k=30, repetition_penalty=1.1)
    want = InferenceEngine(model, **kw).generate(prompts, sampling)
    for extra in (dict(), dict(prefill_chunk_tokens=8)):
        eng = InferenceEngine(model, mesh_shape=(1, 2), **kw, **extra)
        landed, real = [], eng.backend._place_launch
        eng.backend._place_launch = lambda host: landed.append(real(host)) or landed[-1]
        TRACER.clear()
        assert eng.generate(prompts, sampling) == want
        dispatches = [s for s in TRACER.snapshot() if s.name == "dispatch"]
        assert dispatches and all(s.args["h2d_arrays"] == 1 for s in dispatches) and len(landed) == len(dispatches)
        assert all(x.sharding.is_fully_replicated and len(x.devices()) == 2 and x.dtype == jnp.int32 for x in landed)
