"""The Pallas kernels of the main path, compiled by the chip's own compiler.

No chip is attached here, but the TPU compiler is installed and compiles for a
chip that is *described* (``/opt/skills/guides/on-chip-measurement`` section
2). Interpret mode cannot show what this does: these kernels passed every
interpret-mode test while the compiler refused the paged kernel's whole-prompt
query tile (18 MiB of scoped VMEM against a limit of 16) and GSPMD refused to
partition it at all. The shapes are the ones ``chip_smoke.py`` runs: Qwen2-1.5B
serving (12 query / 2 kv heads, head_dim 128, 9600-block pool of 16-token
blocks, 160-block tables) and Qwen2-0.5B training (14 / 2 heads, head_dim 64,
4 rows of 2048: the benchmark's training cell); the step programs are the benchmark cell's (28 layers, 16
slots, 192-block tables); the latent kernel's are ``longdoc``'s full layers
(one chunk of 1,024, 128 heads, a 512 + 64 latent, a table of 1,056 blocks of
16); the walk by runs and the windowed kinds' decode program are ``mixedlen``'s
(64 query / 8 KV heads of 128, 16 slots, tables of 1,088 over 17,408 blocks of
16). Nothing runs; a compile that passes says nothing about results or times.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddlenlp_tpu.ops.pallas.flash_attention import flash_attention
from paddlenlp_tpu.ops.pallas.paged_attention import ragged_paged_attention


@pytest.fixture(scope="module")
def topology():
    """A described v5e 2x2, persistent compile cache off while it is in use (a
    compile for a described device is written to the cache but cannot be read
    back without a chip: the next one only warns)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def tell_vmem(monkeypatch, mib):
    """The flash kernel asks ``pltpu.get_tpu_info`` for the chip's VMEM; a
    described chip does not answer, so the test does (v5e: 128 MiB)."""
    import types

    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(pltpu, "get_tpu_info", lambda: types.SimpleNamespace(vmem_capacity_bytes=mib << 20))


@pytest.fixture
def chip(topology, monkeypatch):
    tell_vmem(monkeypatch, 128)
    return SingleDeviceSharding(topology.devices[0])


def compiled_text(fn, *avals):
    return jax.jit(fn).lower(*avals).compile().as_text()


NUM_BLOCKS, BLOCK, TABLE = 9600, 16, 160
LAYERS = 28


@pytest.mark.parametrize("batch,tokens,kv_heads,pool_dtype", [
    (8, 1, 2, jnp.bfloat16),  # decode: a 6-row query tile per kv head
    (8, 1, 12, jnp.bfloat16),  # decode without GQA: a 1-row query tile
    (8, 512, 2, jnp.bfloat16),  # one prefill chunk: 3072 rows, the largest single tile
    (2, 2048, 2, jnp.bfloat16),  # largest monolithic prefill bucket: 12288 rows in 4 tiles
    (8, 1, 2, jnp.int8),  # quantized pools: (16, 128) int8/fp8 tiles + (16, kv_heads) scale rows
    (8, 512, 2, jnp.float8_e4m3fn),
], ids=["decode", "decode-mha", "chunk512", "prefill2048", "int8-decode", "fp8-chunk512"])
def test_ragged_paged_attention_compiles(chip, batch, tokens, kv_heads, pool_dtype):
    """One head's (16, 128) tile cut out of the pool's token-major rows of
    ``kv_heads * 128`` lanes, at ``(layer, plane, table[b, j], 0, kv head)``."""
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    heads, head_dim, layers = 12, 128, 4  # 28 layers of 12 kv heads would not fit the chip
    avals = [aval((batch, tokens, heads, head_dim), jnp.bfloat16),
             aval((layers, 2, NUM_BLOCKS, BLOCK, kv_heads * head_dim), pool_dtype),
             aval((batch, TABLE), jnp.int32), aval((batch,), jnp.int32), aval((batch,), jnp.int32),
             aval((), jnp.int32)]
    if pool_dtype != jnp.bfloat16:
        avals.append(aval((layers, 2, NUM_BLOCKS, BLOCK, kv_heads), jnp.float32))

    def attend(q, kv, tables, start, lens, layer, kv_scale=None):
        return ragged_paged_attention(q, kv, tables, start, lens, layer,
                                      interpret=False, kv_scale=kv_scale)

    assert "tpu_custom_call" in compiled_text(attend, *avals)


@pytest.mark.parametrize("rows,tokens", [(16, 1), (1, 1024)], ids=["decode16", "chunk1024"])
def test_ragged_paged_attention_window_call_compiles(chip, rows, tokens):
    """The window call at the windowed kinds' cell sizes (64 query / 8 KV heads of 128, window 128, tables of 1,088 over
    a window plane of 6 layers x 1,185 blocks): the first block of a tile comes from the prefetched ``q_start`` inside
    the index map, and a chunk of 1,024 runs in four query tiles of 2,048 rows."""
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    avals = [aval((rows, tokens, 64, 128), jnp.bfloat16), aval((6, 2, 1185, 16, 8 * 128), jnp.bfloat16),
             aval((rows, 1088), jnp.int32), aval((rows,), jnp.int32), aval((rows,), jnp.int32), aval((), jnp.int32)]

    def attend(q, kv, tables, start, lens, layer):
        return ragged_paged_attention(q, kv, tables, start, lens, layer, interpret=False, window=128)

    assert "tpu_custom_call" in compiled_text(attend, *avals)


@pytest.mark.parametrize("rows,tokens", [(16, 1), (1, 1024)], ids=["decode16", "chunk1024"])
def test_ragged_paged_run_attention_compiles_within_the_default_vmem(chip, rows, tokens):
    """The full layers' walk by runs at the windowed kinds' cell sizes (64 query / 8 KV heads of 128, tables of 1,088
    over the full layers' plane of 2 x 17,408 blocks, bf16): 16 decode rows take every KV head a step (two slots of a
    run of 512 keys x 1,024 lanes of K and of V: 4 MiB), a chunk of 1,024 one head and 256 tokens a step (scores of
    [2048, 512] float32); both inside the 16 MiB of scoped VMEM a kernel gets without asking, so the call asks for none."""
    from paddlenlp_tpu.ops.pallas.paged_run_attention import ragged_paged_run_attention

    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    avals = [aval((rows, tokens, 64, 128), jnp.bfloat16), aval((2, 2, 17408, 16, 8 * 128), jnp.bfloat16),
             aval((rows, 1088), jnp.int32), aval((rows,), jnp.int32), aval((rows,), jnp.int32), aval((), jnp.int32)]

    def attend(q, kv, tables, start, lens, layer):
        return ragged_paged_run_attention(q, kv, tables, start, lens, layer, interpret=False)

    text = compiled_text(attend, *avals)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert '"scoped_memory_configs":[]' in text  # no vmem_limit_bytes was asked for


FLASH_SHAPES = {  # batch, tokens, query heads, kv heads, head_dim (query/key, then the value head where it differs)
    "cell-4x2048-h64-group7": (4, 2048, 14, 2, 64),  # qwen2-0.5b-pretrain.seq2k, exactly
    "h128-group6-4096": (2, 4096, 12, 2, 128),  # Qwen2-1.5B's heads
    "cell-2x8192-h192-v128": (2, 8192, 32, 32, 192, 128),  # kanana2-30b-a3b-pretrain-ep8.seq8k, exactly
    "partial-last-tile-2x2688-h64-group7": (2, 2688, 14, 2, 64),  # 2 x 1024 + 640: the last tile ends inside a strip
}


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("masking", ["causal", "segments", "window", "segments-window", "segments-128x128"])
@pytest.mark.parametrize("shape", FLASH_SHAPES)
def test_flash_attention_compiles(chip, shape, masking, backward):
    """At the tiles the kernel file's rule picks (no block argument: the tile the
    diagonal crosses walked in strips, under a window of a whole tile and one of 300
    that ends inside a strip) and with the 64 MiB of scoped VMEM it asks for on this
    chip, and with a caller's own 128 x 128 blocks under segments, as before the rule."""
    batch, seq, heads, kv_heads, head_dim, *value_dim = FLASH_SHAPES[shape]
    blocks = (128, 128) if masking.endswith("128x128") else (None, None)
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    q = aval((batch, seq, heads, head_dim), jnp.bfloat16)
    kv = aval((batch, seq, kv_heads, head_dim), jnp.bfloat16)
    value = aval((batch, seq, kv_heads, *(value_dim or [head_dim])), jnp.bfloat16)
    segments = aval((batch, seq), jnp.int32)  # packed batches: [B,T,1] / [B,1,S] tiles

    def forward(q, k, v, seg):
        return flash_attention(q, k, v, seg if masking.startswith("segments") else None, None, True,
                               {"window": 1024, "segments-window": 300}.get(masking), *blocks, interpret=False)

    def loss(q, k, v, seg):
        return forward(q, k, v, seg).astype(jnp.float32).sum()

    text = compiled_text(jax.grad(loss, argnums=(0, 1, 2)) if backward else forward,
                         q, kv, value, segments)
    # forward alone is one kernel; the gradient adds dq and dk/dv
    assert text.count('custom_call_target="tpu_custom_call"') == (3 if backward else 1)
    # half the chip's VMEM for a 1024 x 1024 step, the compiler's default for the caller's small blocks
    assert text.count('"size":"67108864"}],"custom_call_config"') == (0 if blocks[0] else 3 if backward else 1)


@pytest.mark.parametrize("recomputed", [False, True], ids=["plain", "each-layer-recomputed"])
def test_unrolled_layers_lower_each_distinct_kernel_once(chip, recomputed):
    """The guard on ``setup_s``: five attention calls at ``seq8k``'s shape, one after the other as an unrolled
    model's layers are, differentiated. Tracing a kernel's body and lowering it to Mosaic is paid on every run, warm
    compile cache or not, so the lowered module holds one ``tpu_custom_call`` a distinct kernel (forward, dq, dkv;
    under ``jax.checkpoint`` the recomputed forward's jaxpr is a second one) where it held one a call site (15 and
    19), and the compiled program still runs a kernel a call site. Nothing is compiled but the last count's program."""
    B, T, N, H, Hv = 2, 8192, 32, 192, 128
    aval = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)

    def layer(x, w):
        out = flash_attention(x, x, x[..., :Hv], None, None, True, None, None, None, False)
        return x + jnp.einsum("btnv,vh->btnh", out, w)

    def loss(x, w):
        for i in range(5):
            x = (jax.checkpoint(layer) if recomputed else layer)(x, w[i])
        return x.astype(jnp.float32).sum()

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(aval(B, T, N, H), aval(5, Hv, H))
    assert lowered.as_text().count("stablehlo.custom_call @tpu_custom_call") == (4 if recomputed else 3)
    if not recomputed:
        assert lowered.compile().as_text().count('custom_call_target="tpu_custom_call"') == 15


def test_latent_chunk_attention_compiles_within_its_vmem_request(chip):
    """The full layers' chunk form at ``longdoc``'s shapes: one kernel, holding
    the scoped VMEM the kernel file sizes by hand (the compiler refuses a step
    that needs more than it was given: 28 MiB at four heads a step)."""
    from paddlenlp_tpu.ops.pallas import latent_attention as kernel_file

    chunk, heads, nope, rope, v, kv_lora, tile = 1024, 128, 128, 64, 128, 512, 512
    cached = 1056 * 16  # the longest table: 33 key tiles
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def attend(q_nope, q_pe, rows, w_k, w_v, keep, n_tiles):
        return kernel_file.latent_chunk_attention(q_nope, q_pe, rows, w_k, w_v, keep, n_tiles,
                                                  scale=(nope + rope) ** -0.5, tile=tile, interpret=False)

    text = compiled_text(attend, aval((1, chunk, heads, nope), jnp.bfloat16), aval((1, chunk, heads, rope), jnp.bfloat16),
                         aval((1, cached, kv_lora + rope), jnp.bfloat16), aval((kv_lora, heads, nope), jnp.bfloat16),
                         aval((kv_lora, heads, v), jnp.bfloat16), aval((1, chunk, cached), jnp.bool_),
                         aval((), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    request = kernel_file.vmem_bytes(chunk, tile, kernel_file.HEAD_BLOCK, kv_lora, nope, rope, v, 2)
    assert request <= 64 << 20  # half the chip's VMEM at most
    assert f'"size":"{request}"}}],"custom_call_config"' in text


def test_flash_attention_on_a_mesh_compiles(topology, monkeypatch):
    """dp 2 x tp 2 training: the dispatcher runs the kernel under shard_map, each
    shard on 2 of 4 rows and on 7 query heads over one kv head, tiles by the rule."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddlenlp_tpu.ops.flash_attention import dot_product_attention
    from paddlenlp_tpu.parallel import MeshConfig, create_mesh, use_mesh

    mesh = create_mesh(MeshConfig(dp=2, tp=2), devices=topology.devices)
    spec = NamedSharding(mesh, P("dp", None, "tp", None))
    q = jax.ShapeDtypeStruct((4, 2048, 14, 64), jnp.bfloat16, sharding=spec)
    kv = jax.ShapeDtypeStruct((4, 2048, 2, 64), jnp.bfloat16, sharding=spec)

    def loss(q, k, v):
        return dot_product_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    # the dispatcher and the kernel ask jax.default_backend(); a described chip
    # does not change that answer, so the test gives it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tell_vmem(monkeypatch, 128)
    with use_mesh(mesh):
        text = compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert "bf16[2,2048,7,64]" in text  # per shard


@pytest.mark.parametrize("placement", ["one-chip", "dp2-tp2"])
def test_remat_step_runs_the_forward_kernel_once_a_layer(topology, monkeypatch, placement):
    """The training cell's layer (14 / 2 heads of 64, 4 rows of 2048, remat
    ``save_qkv_attn``, scanned) twice, differentiated: three flash kernels in the
    program (forward, dq, dk/dv; a scan's body is compiled once for both layers),
    the backward reading the output and logsumexp the forward left. Four meant
    remat ran the forward kernel again. On the mesh the kernel sits under
    shard_map. The unrolled stack: tests/transformers/test_remat_policies.py."""
    import contextlib

    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddlenlp_tpu.parallel import MeshConfig, create_mesh, use_mesh
    from paddlenlp_tpu.parallel.partition import sharding_tree
    from paddlenlp_tpu.transformers import Qwen2Config, Qwen2ForCausalLM

    config = Qwen2Config(vocab_size=1024, hidden_size=896, intermediate_size=4864, num_hidden_layers=2,
                         num_attention_heads=14, num_key_value_heads=2, tie_word_embeddings=True,
                         recompute=True, recompute_granularity="save_qkv_attn", use_scan_layers=True)
    model = Qwen2ForCausalLM(config, dtype=jnp.bfloat16, param_dtype=jnp.float32)
    shapes = model.param_shapes  # shapes only: there is no device to hold arrays
    if placement == "one-chip":
        mesh, rows = contextlib.nullcontext(), SingleDeviceSharding(topology.devices[0])
        placed = jax.tree.map(lambda a: rows, shapes)
    else:
        mesh = create_mesh(MeshConfig(dp=2, tp=2), devices=topology.devices)
        rows = NamedSharding(mesh, P("dp", None))
        placed = sharding_tree(shapes, model.get_partition_rules(config), mesh)
        mesh = use_mesh(mesh)
    params = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), shapes, placed)
    ids = jax.ShapeDtypeStruct((4, 2048), jnp.int32, sharding=rows)

    def loss(params, ids):
        return jnp.mean(model.apply(params, input_ids=ids).logits.astype(jnp.float32) ** 2)

    # the dispatcher and the kernel ask jax.default_backend(); a described chip
    # does not change that answer, so the test gives it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tell_vmem(monkeypatch, 128)
    with mesh:
        text = compiled_text(jax.grad(loss), params, ids)
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def test_flash_attention_compiles_for_16_mib_of_vmem(topology, monkeypatch):
    """A chip of 16 MiB VMEM (v4) gets 512 x 512 tiles and the compiler's default:
    a 1024 x 1024 step of this call needs 17.2 MB there."""
    from jax.experimental import topologies

    from paddlenlp_tpu.ops.pallas import flash_attention as kernel_file

    try:
        v4 = topologies.get_topology_desc(platform="tpu", topology_name="v4:2x2x1")
    except Exception as e:
        pytest.skip(f"cannot describe a v4 topology here: {e!r}")
    tell_vmem(monkeypatch, 16)
    assert kernel_file._blocks(None, None, 2048, 2048) == (512, 512)
    assert kernel_file._compiler_params(512, 512).vmem_limit_bytes is None
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=SingleDeviceSharding(v4.devices[0]))
    q, kv = aval((2, 2048, 12, 128), jnp.bfloat16), aval((2, 2048, 2, 128), jnp.bfloat16)

    def loss(q, k, v, seg):
        return flash_attention(q, k, v, seg, interpret=False).astype(jnp.float32).sum()

    text = compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv, aval((2, 2048), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert "f32[24,1,2048]" in text  # the logsumexp rows: the kernels are these


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
def test_flash_tile_rule(kernel):
    """The rule alone, no compiler: at the cell's shape a call is under 1,000 grid
    steps (it was 14,336), and for every ``T`` the dispatcher's gate lets through
    (``T % 128 == 0``, up to 131,072) a block is the whole axis or a multiple of 128
    (sublanes of the [block, head_dim] operands, lanes of the [1, block] rows) and
    the strips inside a crossed tile are legal too."""
    from paddlenlp_tpu.ops.pallas import flash_attention as kernel_file

    block_q, block_kv = kernel_file._blocks(None, None, 2048, 2048)
    rows, q_tiles, kv_tiles = 4 * (2 if kernel == "dkv" else 14), 2048 // block_q, 2048 // block_kv
    steps = rows * kv_tiles * q_tiles * (7 if kernel == "dkv" else 1)
    assert 100 <= steps < 1000, (block_q, block_kv, steps)

    for tokens in range(128, 131072 + 1, 128):
        blocks = kernel_file._blocks(None, None, tokens, tokens)
        for block in blocks:
            assert block == tokens or (block % 128 == 0 and 0 < block < tokens), (tokens, block)
        # the strips a step walks a crossed tile in: none where a tile is one strip, else a divisor of the
        # tile of 128 to 512 whose sub-blocks start and end on whole strips, so every slice is whole
        # sublanes of the [block, head_dim] operands and whole lanes of the [1, block] rows
        strip = kernel_file._strip(*blocks, True)
        if blocks[0] == 128:
            assert strip is None, (tokens, blocks, strip)
            continue
        assert strip in (128, 256, 512) and blocks[0] % strip == 0 and strip < blocks[0], (tokens, blocks, strip)
        for rows, cols in kernel_file._strips(blocks[0], strip, True, transposed=kernel == "dkv"):
            assert all(edge % strip == 0 and 0 <= edge <= blocks[0]
                       for edge in (rows.start, rows.stop, cols.start, cols.stop)), (tokens, rows, cols)
    assert kernel_file._strip(1024, 1024, False) is None  # not causal: the whole-tile body
    assert kernel_file._strip(512, 1024, True) is None  # unequal blocks
    assert kernel_file._compiler_params(block_q, block_kv).vmem_limit_bytes is None  # no chip here: the default
    assert kernel_file._blocks(128, 256, 2048, 2048) == (128, 256)  # the caller's are honoured
    assert kernel_file._blocks(512, 512, 256, 384) == (256, 384)  # and cut to the sequence


def test_paged_kernel_on_a_mesh_compiles(topology, monkeypatch):
    """dp 2 x tp 2 serving: GSPMD refuses a bare Mosaic kernel ("cannot be
    automatically partitioned"), so the sharded model runs it under shard_map,
    each tp shard on its own kv head: its 128 lanes of every pool row."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddlenlp_tpu.experimental.sharded_backend import ShardedPagedInferenceModel
    from paddlenlp_tpu.parallel import MeshConfig, create_mesh
    from paddlenlp_tpu.transformers import Qwen2Config, Qwen2ForCausalLM

    mesh = create_mesh(MeshConfig(dp=2, tp=2), devices=topology.devices)
    config = Qwen2Config(vocab_size=1024, hidden_size=1536, intermediate_size=8960,
                         num_hidden_layers=1, num_attention_heads=12, num_key_value_heads=2)
    model = Qwen2ForCausalLM(config, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    model.params = model.param_shapes  # shapes only: there is no device to hold arrays
    infer = ShardedPagedInferenceModel(model, BLOCK, NUM_BLOCKS, TABLE, dtype=jnp.bfloat16,
                                       mesh=mesh, use_paged_kernel=True)
    aval = lambda shape, dtype, spec: jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, spec))
    batch = 8
    avals = (aval((batch, 1, 12, 128), jnp.bfloat16, P(None, None, "tp", None)),
             aval((1, 2, NUM_BLOCKS, BLOCK, 2 * 128), jnp.bfloat16, infer.pool_spec),
             aval((batch, TABLE), jnp.int32, P()), aval((batch,), jnp.int32, P()),
             aval((batch,), jnp.int32, P()), aval((), jnp.int32, P()))

    def attend(q, kv, tables, start, lens, layer):
        return infer._paged_attention(q, kv, None, tables, start, lens, layer)

    # the kernel asks jax.default_backend() whether to interpret; a described
    # chip does not change that answer, so the test gives it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = compiled_text(attend, *avals)
    # per shard: one kv head, its 6 query heads
    assert "tpu_custom_call" in text and "bf16[8,1,6,128]" in text


@pytest.mark.parametrize("program", ["decode", "prefill16x512", "mixed_flat1x512+16"])
def test_step_programs_copy_no_pool(chip, monkeypatch, program):
    """The serving step programs at the benchmark cell's shapes (Qwen2-1.5B,
    28 layers, 9600 blocks of 16, 16 slots, 192-block tables, abstract weights)
    address the donated pool in place. While the pool rode the layer scan as
    xs / ys in a kv-head-major layout, the same compiles held 4.53 GiB (decode)
    and 4.79 GiB (prefill) of temporaries: each layer's pool sliced out, relaid
    for the scatter, relaid for the kernel and stacked back. The mixed step (one
    chunk row of 512 tokens beside 16 decode rows: what a chunked engine of this
    geometry launches) runs two forwards in series over the one donated pool."""
    from paddlenlp_tpu.experimental.backend import samp_arrays
    from paddlenlp_tpu.experimental.inference_model import PagedInferenceModel
    from paddlenlp_tpu.experimental.launch_pack import layout_of, packed_size
    from paddlenlp_tpu.experimental.paged_cache import init_paged_pool
    from paddlenlp_tpu.transformers import Qwen2Config, Qwen2ForCausalLM

    slots, table, vocab = 16, 192, 151936
    config = Qwen2Config(vocab_size=vocab, hidden_size=1536, intermediate_size=8960,
                         num_hidden_layers=LAYERS, num_attention_heads=12,
                         num_key_value_heads=2, tie_word_embeddings=True)
    model = Qwen2ForCausalLM(config, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    model.params = model.param_shapes  # shapes only: there is no device to hold arrays
    # the kernel asks jax.default_backend() whether to interpret; a described
    # chip does not change that answer, so the test gives it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    infer = PagedInferenceModel(model, BLOCK, NUM_BLOCKS, table, dtype=jnp.bfloat16, decode_steps=8)
    assert infer.use_paged_kernel
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    params = on_chip(model.params)
    pool = on_chip(jax.eval_shape(lambda: init_paged_pool(config, NUM_BLOCKS, BLOCK)))
    rows = lambda *shape: aval((slots,) + shape, jnp.int32)
    if program == "decode":
        step, fields, counts = infer._decode_impl, dict(
            tokens=rows(), block_tables=rows(table), context_lens=rows(), done0=aval((slots,), jnp.bool_),
            remaining=rows(), **samp_arrays([None] * slots, slots)), (rows(vocab),)
    elif program == "prefill16x512":
        step, fields, counts = infer._prefill_impl, dict(
            input_ids=rows(512), block_tables=rows(table), suffix_lens=rows(), cached_lens=rows(), slot_idx=rows(),
            **samp_arrays([None] * slots, slots)), (rows(vocab), rows(vocab))
    else:
        chunk = lambda *shape, dtype=jnp.int32: aval((1,) + shape, dtype)
        step, fields, counts = infer._mixed_flat_impl, dict(
            chunk_ids=chunk(512), chunk_tables=chunk(table), chunk_qlens=chunk(), chunk_start=chunk(),
            chunk_slots=chunk(), chunk_emit=chunk(dtype=jnp.bool_), dec_tokens=rows(), dec_tables=rows(table),
            dec_start=rows(), dec_slots=rows(), dec_live=aval((slots,), jnp.bool_),
            **samp_arrays([None] * (1 + slots), 1 + slots)), (rows(vocab),)
    # the launch's host inputs ride one packed buffer; its layout is the program's last, static argument
    layout = layout_of(fields)
    args = (params, pool, aval((packed_size(layout),), jnp.int32), *counts, None, layout)
    compiled = jax.jit(step, donate_argnums=(1,), static_argnums=(len(args) - 1,)).lower(*args).compile()

    pool_bytes = pool.kv.size * pool.kv.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < 0.25 * pool_bytes
    weights = {a.shape for a in jax.tree.leaves(model.params)}  # only ever read
    assert_pool_sized_values_stay_in_place(compiled.as_text(), pool.kv.size // LAYERS, weights)


def assert_pool_sized_values_stay_in_place(text, layer_elements, weights):
    """Every instruction of the compiled program ``text`` with a bf16 output of one layer of pool or more
    (``layer_elements``) either passes a buffer on or updates the donated pool in place (a bound on temporaries is
    what says the update's operand is aliased); ``weights`` are shapes only ever read."""
    fusion_roots = dict(re.findall(
        r"^%?(fused_computation[\w.\-]*) [^\n]*\{\n(?:[^}][^\n]*\n)*?\s*ROOT %?[\w.\-]+ = \S+ ([a-z\-]+)\(",
        text, flags=re.M))
    in_place = {"scatter", "dynamic-update-slice"}
    passed_on = {"parameter", "get-tuple-element", "bitcast"}
    found = set()
    for name, dims, op, callee in re.findall(
            r"^\s*(?:ROOT )?%?([\w.\-]+) = bf16\[([\d,]+)\]\S* ([a-z\-]+)\((?:[^\n]*calls=%?([\w.\-]+))?",
            text, flags=re.M):
        shape = tuple(int(d) for d in dims.split(","))
        if shape in weights or math.prod(shape) < layer_elements:
            continue
        op = fusion_roots.get(callee, "fusion") if op == "fusion" else op
        assert op in in_place | passed_on, f"{name}: a pool-sized {op} {shape}"
        found.add(op)
    assert found & in_place, "no write into the pool was found: the scan reads nothing"


def test_the_windowed_kinds_decode_program_copies_no_plane(chip, monkeypatch):
    """``mixedlen``'s decode program (K-EXAONE's eight layers at the cell's geometry, abstract weights, 16 slots,
    tables of 1,088): the full layers' walk by runs takes the donated plane as it lies in HBM (``pl.ANY``, copied
    from by hand), so nothing of a plane's size is made round the call: the program's temporaries stay under half
    the full layers' plane (886 MiB of 2,176, to the MiB what the walk by blocks left), and every value of a
    layer of that plane or more is the plane passed on or updated in place."""
    import json
    import os

    from bench.harness import common
    from paddlenlp_tpu.experimental.backend import samp_arrays
    from paddlenlp_tpu.experimental.inference_model import inference_model_class
    from paddlenlp_tpu.experimental.launch_pack import layout_of, packed_size

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "bench", "configs", "k-exaone-serve-ep16.json")) as f:
        config = json.load(f)
    engine = config["bench"]["engine"]
    cfg, make = common.build_model(config, jnp.bfloat16, jnp.bfloat16)
    model = make()
    model.params = model.param_shapes  # shapes only: there is no device to hold arrays
    # the kernels ask jax.default_backend() whether to interpret; a described chip does not change that answer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, table = engine["max_batch_size"], engine["max_blocks_per_seq"]
    infer = inference_model_class(cfg)(model, engine["block_size"], engine["num_blocks"], table, dtype=jnp.bfloat16,
                                       decode_steps=engine["decode_steps"], max_batch_size=slots,
                                       prefill_chunk_tokens=engine["prefill_chunk_tokens"])
    assert infer.use_paged_kernel and (infer.n_full, infer.n_window) == (2, 6)
    on_chip = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    pool = on_chip(jax.eval_shape(lambda: infer.init_pool(engine["num_blocks"], engine["block_size"], jnp.bfloat16)))
    rows = lambda *shape: aval((slots,) + shape, jnp.int32)
    layout = layout_of(dict(tokens=rows(), block_tables=rows(2, table), context_lens=rows(),
                            done0=aval((slots,), jnp.bool_), remaining=rows(), **samp_arrays([None] * slots, slots)))
    args = (on_chip(model.params), pool, aval((packed_size(layout),), jnp.int32), rows(cfg.vocab_size), None, layout)
    compiled = jax.jit(infer._decode_impl, donate_argnums=(1,), static_argnums=(5,)).lower(*args).compile()

    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 8  # one kernel a layer (the stack is unrolled; the sub-steps' scan compiles its body once)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * pool.kv.size * pool.kv.dtype.itemsize
    weights = {a.shape for a in jax.tree.leaves(model.params)}
    assert_pool_sized_values_stay_in_place(text, pool.kv.size // infer.n_full, weights)


def test_the_diffusion_kinds_two_programs_fit_the_chip(chip, monkeypatch):
    """``cot``'s two step programs (SDAR-30B-A3B's 48 layers at the cell's geometry, abstract weights, 32 slots of a
    block of 4, tables of 128, one chunk row of 256): the chip's compiler takes the walk by runs under ``block=4`` at a
    pass's 4 query tokens and at a chunk's 256; the stack is one scan, so each program holds the kernel once; and
    weights, pool and temporaries together stay under the chip's 16 GB with room for the reference check."""
    import json
    import os

    from bench.harness import common
    from paddlenlp_tpu.experimental.inference_model import inference_model_class
    from paddlenlp_tpu.experimental.launch_pack import layout_of, packed_size

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "bench", "configs", "sdar-30b-a3b-serve-ep8.json")) as f:
        config = json.load(f)
    engine = config["bench"]["engine"]
    cfg, make = common.build_model(config, jnp.bfloat16, jnp.bfloat16)
    model = make()
    model.params = model.param_shapes  # shapes only: there is no device to hold arrays
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, table, bk = engine["max_batch_size"], engine["max_blocks_per_seq"], cfg.block_length
    infer = inference_model_class(cfg)(model, engine["block_size"], engine["num_blocks"], table, dtype=jnp.bfloat16,
                                       decode_steps=engine["decode_steps"], max_batch_size=slots,
                                       prefill_chunk_tokens=engine["prefill_chunk_tokens"])
    assert infer.use_paged_kernel and infer.fixed_mixed_shape == (1, 256, 32)
    on_chip = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)
    aval = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    pool = on_chip(jax.eval_shape(lambda: infer.init_pool(engine["num_blocks"], engine["block_size"], jnp.bfloat16)))
    rows = lambda *shape: aval((slots,) + shape)
    flags = lambda *shape: aval(shape, jnp.bool_)
    layouts = {
        "_decode_impl": layout_of(dict(block_tokens=rows(bk), block_masked=flags(slots, bk), block_tables=rows(table),
                                       start=rows(), fixed=rows(), done0=flags(slots), remaining=rows())),
        "_mixed_flat_impl": layout_of(dict(
            chunk_ids=aval((1, 256)), chunk_tables=aval((1, table)), chunk_qlens=aval((1,)), chunk_start=aval((1,)),
            dec_tokens=rows(bk), dec_masked=flags(slots, bk), dec_tables=rows(table), dec_start=rows(), dec_fixed=rows(),
            dec_live=flags(slots), dec_remaining=rows())),
    }
    for name, layout in layouts.items():
        args = (on_chip(model.params), pool, aval((packed_size(layout),)), rows(cfg.vocab_size), None, layout)
        compiled = jax.jit(getattr(infer, name), donate_argnums=(1,), static_argnums=(5,)).lower(*args).compile()
        kernels = compiled.as_text().count('custom_call_target="tpu_custom_call"')
        assert kernels == (1 if name == "_decode_impl" else 2), name  # the mixed step: the chunk row's call and the pass's
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= pool.kv.size * pool.kv.dtype.itemsize  # the donated pool is updated in place
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9, name
