"""The Pallas kernels of the main path, compiled by the chip's own compiler.

No chip is attached here, but the TPU compiler is installed and compiles for a
chip that is *described* (``/opt/skills/guides/on-chip-measurement`` section
2). Interpret mode cannot show what this does: these kernels passed every
interpret-mode test while the compiler refused the paged kernel's whole-prompt
query tile (18 MiB of scoped VMEM against a limit of 16) and GSPMD refused to
partition it at all. The shapes are the ones ``chip_smoke.py`` runs: Qwen2-1.5B
serving (12 query / 2 kv heads, head_dim 128, 9600-block pool of 16-token
blocks, 160-block tables) and Qwen2-0.5B training (14 / 2 heads, head_dim 64)
at sequence 2048. Nothing runs; a compile that passes says nothing about
results or times.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

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


@pytest.fixture
def chip(topology):
    return SingleDeviceSharding(topology.devices[0])


def compiled_text(fn, *avals):
    return jax.jit(fn).lower(*avals).compile().as_text()


NUM_BLOCKS, BLOCK, TABLE = 9600, 16, 160


@pytest.mark.parametrize("batch,tokens,kv_heads,pool_dtype", [
    (8, 1, 2, jnp.bfloat16),  # decode: a 6-row query tile per kv head
    (8, 1, 12, jnp.bfloat16),  # decode without GQA: a 1-row query tile
    (8, 512, 2, jnp.bfloat16),  # one prefill chunk: 3072 rows, the largest single tile
    (2, 2048, 2, jnp.bfloat16),  # largest monolithic prefill bucket: 12288 rows in 4 tiles
    (8, 1, 2, jnp.int8),  # quantized pools: (16, 128) int8/fp8 blocks + (16, 1) scales
    (8, 512, 2, jnp.float8_e4m3fn),
], ids=["decode", "decode-mha", "chunk512", "prefill2048", "int8-decode", "fp8-chunk512"])
def test_ragged_paged_attention_compiles(chip, batch, tokens, kv_heads, pool_dtype):
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    heads, head_dim = 12, 128
    pool = aval((NUM_BLOCKS, kv_heads, BLOCK, head_dim), pool_dtype)
    avals = [aval((batch, tokens, heads, head_dim), jnp.bfloat16), pool, pool,
             aval((batch, TABLE), jnp.int32), aval((batch,), jnp.int32), aval((batch,), jnp.int32)]
    if pool_dtype != jnp.bfloat16:
        avals += [aval((NUM_BLOCKS, kv_heads, BLOCK, 1), jnp.float32)] * 2

    def attend(q, pool_k, pool_v, tables, start, lens, k_scale=None, v_scale=None):
        return ragged_paged_attention(q, pool_k, pool_v, tables, start, lens,
                                      interpret=False, k_scale=k_scale, v_scale=v_scale)

    assert "tpu_custom_call" in compiled_text(attend, *avals)


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("heads,kv_heads,head_dim", [(14, 2, 64), (12, 2, 128)],
                         ids=["h64-group7", "h128-group6"])
def test_flash_attention_compiles(chip, heads, kv_heads, head_dim, backward):
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    batch, seq = 2, 2048
    q = aval((batch, seq, heads, head_dim), jnp.bfloat16)
    kv = aval((batch, seq, kv_heads, head_dim), jnp.bfloat16)
    segments = aval((batch, seq), jnp.int32)  # packed batches: [B,T,1] / [B,1,S] tiles

    def forward(q, k, v, seg):
        return flash_attention(q, k, v, seg, None, True, None, 128, 128, False)

    def loss(q, k, v, seg):
        return forward(q, k, v, seg).astype(jnp.float32).sum()

    text = compiled_text(jax.grad(loss, argnums=(0, 1, 2)) if backward else forward,
                         q, kv, kv, segments)
    # forward alone is one kernel; the gradient adds dq and dk/dv
    assert text.count('custom_call_target="tpu_custom_call"') == (3 if backward else 1)


def test_paged_kernel_on_a_mesh_compiles(topology, monkeypatch):
    """dp 2 x tp 2 serving: GSPMD refuses a bare Mosaic kernel ("cannot be
    automatically partitioned"), so the sharded model runs it under shard_map,
    each tp shard on its own kv head."""
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
             aval((2, NUM_BLOCKS, 2, BLOCK, 128), jnp.bfloat16, P(*infer.pool_spec[1:])),
             aval((batch, TABLE), jnp.int32, P()), aval((batch,), jnp.int32, P()),
             aval((batch,), jnp.int32, P()))

    def attend(q, pool_layer, tables, start, lens):
        return infer._paged_attention(q, pool_layer, None, tables, start, lens)

    # the kernel asks jax.default_backend() whether to interpret; a described
    # chip does not change that answer, so the test gives it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = compiled_text(attend, *avals)
    # per shard: one kv head, its 6 query heads
    assert "tpu_custom_call" in text and "bf16[8,1,6,128]" in text
