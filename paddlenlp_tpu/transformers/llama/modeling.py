"""LLaMA / LLaMA-2 / LLaMA-3, TPU-native.

Counterpart of ``paddlenlp/transformers/llama/modeling.py`` (2071 LoC):
``LlamaRMSNorm`` :352, rotary classes :402-556, ``LlamaMLP`` :580, ``LlamaAttention``
:655 (TP head split, GQA, fused qkv, SP swaps), ``LlamaDecoderLayer`` :1122,
``LlamaModel`` :1440, ``LlamaPretrainingCriterion`` :1777, ``LlamaLMHead`` :1849,
``LlamaForCausalLM`` :1924.

TPU-first redesign:
- ONE network definition for every parallelism strategy. The reference swaps modules
  per strategy (ColumnParallelLinear / RowSequenceParallelLinear / ReshardLayer /
  modeling_pp.py / modeling_auto.py — four parallel copies of the net). Here the
  linen module is strategy-free; ``get_partition_rules`` + activation sharding
  constraints tell GSPMD where tensors live, and XLA inserts the collectives
  (TP all-reduce, Megatron-SP reduce-scatter/all-gather, Ulysses all-to-all).
- decoder layers run UNROLLED (``layers_<i>``) or SCANNED over a stacked [L, ...]
  param axis (``config.use_scan_layers``, the MaxText idiom): L-times smaller HLO,
  near-constant compile time in depth, and the natural substrate for pipeline
  parallelism. Checkpoints are identical either way (HF per-layer keys).
- bf16 compute / fp32 params+norms; RoPE tables in fp32.
- rematerialization via ``flax.linen.remat`` with named-checkpoint policies
  (full / full_attn / core_attn) instead of the reference's recompute wrappers.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from ...ops.cross_entropy import causal_lm_loss, cross_entropy_with_ignore
from ...ops.flash_attention import dot_product_attention
from ...ops.rope import apply_rotary_pos_emb, rope_frequencies, rope_tables
from ...parallel.partition import P, logical_axis_size, shard_constraint
from ..cache_utils import KVCache, update_layer_kv
from ..model_outputs import BaseModelOutputWithPast, CausalLMOutputWithPast, SequenceClassifierOutput
from ..model_utils import PretrainedModel
from .configuration import LlamaConfig

__all__ = [
    "LlamaRMSNorm",
    "LlamaMLP",
    "LlamaAttention",
    "LlamaDecoderLayer",
    "LlamaModule",
    "LlamaModel",
    "LlamaForCausalLM",
    "LlamaForSequenceClassification",
    "LlamaPretrainingCriterion",
    "LlamaPretrainedModel",
]

ACT2FN = {
    "silu": nn.silu,
    "gelu": partial(nn.gelu, approximate=False),
    "relu": nn.relu,
    "gelu_new": partial(nn.gelu, approximate=True),
    "gelu_pytorch_tanh": partial(nn.gelu, approximate=True),
    "tanh": jnp.tanh,
    "quick_gelu": lambda x: x * jax.nn.sigmoid(1.702 * x),  # openai clip
}


def tied_mlm_head(module, h, *, table, vocab_size, hidden_size, act, layer_norm_eps,
                  dtype, param_dtype, dense_name: str, ln_name: str, bias_name: str):
    """BERT-style MLM head with the decoder TIED to the word-embedding table:
    dense -> act -> LayerNorm -> h @ table.T + standalone bias. Shared by the
    encoder zoo (bert/distilbert/nezha/mpnet/deberta/blip) so dtype and sharding
    handling of the tied projection lives in one place. Param names are passed
    in because each family keeps its HF checkpoint naming."""
    from ...parallel.partition import P, shard_constraint

    x = nn.Dense(hidden_size, dtype=dtype, param_dtype=param_dtype, name=dense_name)(h)
    x = ACT2FN[act](x)
    x = nn.LayerNorm(epsilon=layer_norm_eps, dtype=dtype, param_dtype=param_dtype,
                     name=ln_name)(x)
    bias = module.param(bias_name, nn.initializers.zeros, (vocab_size,), param_dtype)
    logits = x @ table.T.astype(dtype) + bias.astype(dtype)
    return shard_constraint(logits, P("batch", "act_seq", "act_vocab"))


class LlamaRMSNorm(nn.Module):
    """RMSNorm in fp32 (reference llama/modeling.py:352; the fused rms_norm custom op
    fusion_ops.py:119 is unnecessary — XLA fuses this chain natively).
    ``unit_offset`` selects the gemma convention ((1 + scale) with zeros-init)."""

    dim: int
    eps: float = 1e-6
    param_dtype: jnp.dtype = jnp.float32
    unit_offset: bool = False

    @nn.compact
    def __call__(self, x):
        dtype = x.dtype
        init = nn.initializers.zeros if self.unit_offset else nn.initializers.ones
        scale = self.param("scale", init, (self.dim,), self.param_dtype)
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        x32 = x32 * jax.lax.rsqrt(var + self.eps)
        scale32 = scale.astype(jnp.float32)
        if self.unit_offset:
            scale32 = scale32 + 1.0
        return (x32 * scale32).astype(dtype)


def _dense(features, use_bias, config, dtype, param_dtype, name):
    return nn.Dense(
        features,
        use_bias=use_bias,
        dtype=dtype,
        param_dtype=param_dtype,
        kernel_init=nn.initializers.normal(config.initializer_range),
        name=name,
    )


class VocabEmbed(nn.Module):
    """Token embedding with a vocab-parallel lookup.

    When the ``vocab`` logical axis is sharded (tp>1), a plain gather makes GSPMD
    all-gather the full table every step ("involuntary full rematerialization" in
    the compile log). Instead, contract a one-hot of the ids against the table:
    the iota-compare one-hot fuses into the dot operand (never materialized in
    HBM), the contraction stays vocab-sharded (local matmul + psum over tp), and
    the backward is the matching scatter-matmul. This is the TPU analogue of the
    reference's fleet ``VocabParallelEmbedding`` (llama/modeling.py:1440 embed
    path) — masked local lookup + all-reduce, here expressed MXU-natively.
    """

    num_embeddings: int
    features: int
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    embedding_init: Any = nn.initializers.normal(0.02)

    @nn.compact
    def __call__(self, ids):
        table = self.param(
            "embedding", self.embedding_init, (self.num_embeddings, self.features), self.param_dtype
        )
        # one-hot path only when the table is actually vocab-sharded (divisible);
        # otherwise resolve_spec replicates it and a gather is strictly cheaper
        if logical_axis_size("vocab") > 1 and self.num_embeddings % logical_axis_size("vocab") == 0:
            onehot = jax.nn.one_hot(ids, self.num_embeddings, dtype=self.dtype)
            onehot = shard_constraint(onehot, P("batch", "act_seq", "act_vocab"))
            return onehot @ table.astype(self.dtype)
        return jnp.take(table.astype(self.dtype), ids, axis=0)


class LlamaMLP(nn.Module):
    """SwiGLU MLP (reference :580). gate/up column-parallel, down row-parallel —
    expressed purely via partition rules on the kernels."""

    config: LlamaConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        act = ACT2FN[cfg.hidden_act]
        gate = _dense(cfg.intermediate_size, cfg.mlp_bias, cfg, self.dtype, self.param_dtype, "gate_proj")(x)
        up = _dense(cfg.intermediate_size, cfg.mlp_bias, cfg, self.dtype, self.param_dtype, "up_proj")(x)
        h = act(gate) * up
        h = checkpoint_name(h, "mlp_act")
        h = shard_constraint(h, P("batch", "seq", "act_mlp"))
        return _dense(cfg.hidden_size, cfg.mlp_bias, cfg, self.dtype, self.param_dtype, "down_proj")(h)


class LlamaAttention(nn.Module):
    """GQA attention with RoPE (reference :655-1120).

    The reference's TP machinery (head-split bookkeeping, ``assign_kv_heads``, fused
    qkv weights, ReshardQKV for sep parallel) reduces to: project, constrain the
    heads dim onto the ``tp``(+``sep``) axes, call the attention dispatcher.
    ``kv`` is one layer's cache slice (k, v) [B, S_max, n_kv, H]; ``offset`` is the
    global cache write index.
    """

    config: LlamaConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self,
        hidden_states,
        attention_mask=None,
        position_ids=None,
        segment_ids=None,
        kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
        offset=0,
        deterministic: bool = True,
    ):
        cfg = self.config
        B, T, _ = hidden_states.shape
        n_heads, n_kv, head_dim = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim

        q = _dense(n_heads * head_dim, cfg.attention_bias, cfg, self.dtype, self.param_dtype, "q_proj")(hidden_states)
        k = _dense(n_kv * head_dim, cfg.attention_bias, cfg, self.dtype, self.param_dtype, "k_proj")(hidden_states)
        v = _dense(n_kv * head_dim, cfg.attention_bias, cfg, self.dtype, self.param_dtype, "v_proj")(hidden_states)
        q = q.reshape(B, T, n_heads, head_dim)
        k = k.reshape(B, T, n_kv, head_dim)
        v = v.reshape(B, T, n_kv, head_dim)
        # heads onto tp(+sep): with an active sep axis this constraint IS the Ulysses
        # seq<->heads all-to-all (reference segment_parallel_utils.py ReshardQKV).
        q = shard_constraint(q, P("batch", "act_seq_attn", "act_heads", None))
        k = shard_constraint(k, P("batch", "act_seq_attn", "act_kv_heads", None))
        v = shard_constraint(v, P("batch", "act_seq_attn", "act_kv_heads", None))

        if position_ids is None:
            position_ids = jnp.arange(T)[None, :] + (offset if kv is not None else 0)
        use_alibi = bool(getattr(cfg, "use_alibi", False))
        if not use_alibi:
            inv_freq = jnp.asarray(rope_frequencies(head_dim, cfg.rope_theta, cfg.rope_scaling))
            cos, sin = rope_tables(position_ids, inv_freq)
            q, k = apply_rotary_pos_emb(q, k, cos, sin)

        q_offset = 0
        new_kv = None
        if kv is not None:
            q_offset = offset
            k, v = update_layer_kv(kv[0], kv[1], k, v, offset)
            new_kv = (k, v)

        # variant configs (qwen/baichuan) don't declare the field; no dropout then
        dropout_rate = getattr(cfg, "attention_dropout", 0.0) if not deterministic else 0.0
        dropout_rng = self.make_rng("dropout") if dropout_rate > 0.0 else None
        q = checkpoint_name(q, "attn_qkv")
        k = checkpoint_name(k, "attn_qkv")
        v = checkpoint_name(v, "attn_qkv")

        # context parallel: ring attention over the cp axis (reference
        # fusion_ops.py:209-216 dispatches RingFlashAttention when cp>1) —
        # O(S/cp) K/V per chip instead of the GSPMD all-gather. When masks or
        # dropout make the ring kernel inapplicable, the fallback still masks by
        # ABSOLUTE positions (the cp input layout is zigzag-permuted, so index
        # order != causal order).
        from ...parallel.partition import _current_mesh

        mesh = _current_mesh()
        cp_active = mesh is not None and getattr(mesh, "shape", {}).get("cp", 1) > 1
        if (
            cp_active
            and kv is None
            and attention_mask is None
            and segment_ids is None
            and dropout_rate == 0.0
            and not use_alibi
            and getattr(cfg, "sliding_window", None) is None
        ):
            from ...ops.ring_attention import ring_self_attention

            attn_out = checkpoint_name(ring_self_attention(q, k, v, mesh, positions=position_ids), "core_attn")
        else:  # names its result itself: "core_attn", or the kernel's own "flash_out"
            attn_out = dot_product_attention(
                q,
                k,
                v,
                attention_mask=attention_mask,
                segment_ids=segment_ids,
                causal=True,
                q_offset=q_offset,
                dropout_rate=dropout_rate,
                dropout_rng=dropout_rng,
                window=getattr(cfg, "sliding_window", None),
                positions=position_ids if (cp_active and kv is None) else None,
                use_alibi=use_alibi,
            )
        attn_out = attn_out.reshape(B, T, n_heads * head_dim)
        out_bias = getattr(cfg, "attention_out_bias", cfg.attention_bias)
        out = _dense(cfg.hidden_size, out_bias, cfg, self.dtype, self.param_dtype, "o_proj")(attn_out)
        return out, new_kv


class LlamaDecoderLayer(nn.Module):
    """Pre-norm residual block (reference :1122) with a scan-compatible signature:
    ``(carry=(h, offset, aux), layer_kv, ...) -> ((h, offset, aux), new_layer_kv)``.
    ``aux`` accumulates MoE load-balancing loss across layers (0.0 for dense MLP).

    Variant architectures override the class attributes: ``mlp_cls``/``mlp_name``
    (mixtral's block_sparse_moe, qwen2-moe) — the attention/norm skeleton is shared.
    """

    config: LlamaConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    mlp_cls = LlamaMLP  # class attrs, not dataclass fields (subclass-overridable)
    mlp_name = "mlp"
    attn_cls = LlamaAttention

    def _mlp_module(self):
        """Build this layer's MLP; deepseek-style archs override to pick dense
        vs MoE per layer index (first_k_dense_replace / moe_layer_freq)."""
        return type(self).mlp_cls(self.config, self.dtype, self.param_dtype, name=type(self).mlp_name)

    @nn.compact
    def __call__(
        self,
        carry,
        layer_kv,
        attention_mask=None,
        position_ids=None,
        segment_ids=None,
        deterministic: bool = True,
    ):
        cfg = self.config
        hidden_states, offset, aux = carry
        unit_offset = bool(getattr(cfg, "rms_norm_add_unit_offset", False))
        residual = hidden_states
        h = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps, unit_offset=unit_offset,
                         name="input_layernorm")(hidden_states)
        attn_out, new_kv = type(self).attn_cls(cfg, self.dtype, self.param_dtype, name="self_attn")(
            h, attention_mask, position_ids, segment_ids, layer_kv, offset, deterministic
        )
        h = residual + attn_out
        h = shard_constraint(h, P("batch", "act_seq", "act_embed"))
        residual = h
        h2 = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps, unit_offset=unit_offset,
                          name="post_attention_layernorm")(h)
        h2 = self._mlp_module()(h2)
        if isinstance(h2, tuple):  # MoE MLPs return (out, aux_loss)
            h2, layer_aux = h2
            aux = aux + layer_aux
        h = residual + h2
        h = shard_constraint(h, P("batch", "act_seq", "act_embed"))
        return (h, offset, aux), new_kv


def _remat_policy(granularity: str):
    """Map the reference's recompute_granularity (training_args) onto jax.checkpoint
    policies via named checkpoints ("attn_qkv" post-rope q/k/v and "mlp_act" the
    silu(gate)*up product, tagged inside the decoder layer; the attention core's
    output, tagged where it is made, see below):

    - ``full``          recompute the whole decoder layer (save nothing)
    - ``full_attn``     save everything except attention internals (qkv + core)
    - ``core_attn``     save everything except the attention core (softmax(qk)v)
    - ``save_core_attn``  save ONLY the attention core output (cheap memory;
                          the backward skips the attention-core recompute)
    - ``save_qkv_attn``   save only q/k/v + attention core output
    - ``save_attn_mlp``   save q/k/v + attention core + mlp activation
    - ``save_dots``       XLA classic: save all non-batch matmul outputs
    - ``offload_attn``    save q/k/v + core to HOST memory (device HBM stays
                          at layer-boundary footprint)

    The save_only_* tiers are the 16 GB-HBM middle ground between ``full``
    (recomputes the whole layer) and ``core_attn`` (save-everything-except,
    which does not fit).

    "The attention core" has one name a path, given where the value is made: the
    XLA path's result is "core_attn" (ops/flash_attention.py:dot_product_attention;
    ring attention's is named so by the layer), the Pallas kernel's forward rule
    names the two residuals its backward reads, "flash_out" and "flash_lse" (the
    fp32 logsumexp, 4 B a query head a token: ops/pallas/flash_attention.py:_fwd).
    Every tier keeps or drops the three together, so a tier that saves the core
    runs the forward kernel once a layer (tests/transformers/test_remat_policies.py
    counts it). No caller names the result again: under a scanned remat a second
    name on one array is a second saved copy a layer. The two "except" tiers keep
    the kernel's raw outputs whatever they list, since jax's
    save_anything_except_these_names saves every value that has no name.
    """
    core = ("core_attn", "flash_out", "flash_lse")
    if granularity == "full":
        return None
    if granularity == "full_attn":
        return jax.checkpoint_policies.save_anything_except_these_names("attn_qkv", *core)
    if granularity == "core_attn":
        return jax.checkpoint_policies.save_anything_except_these_names(*core)
    if granularity == "save_core_attn":
        return jax.checkpoint_policies.save_only_these_names(*core)
    if granularity == "save_qkv_attn":
        return jax.checkpoint_policies.save_only_these_names("attn_qkv", *core)
    if granularity == "save_attn_mlp":
        return jax.checkpoint_policies.save_only_these_names("attn_qkv", *core, "mlp_act")
    if granularity == "save_dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if granularity == "offload_attn":
        # the logsumexp is small and stays on the device
        return jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=["flash_lse"],
            names_which_can_be_offloaded=["attn_qkv", "core_attn", "flash_out"],
            offload_src="device",
            offload_dst="pinned_host",
        )
    raise ValueError(f"unknown recompute_granularity {granularity!r}")


def _maybe_remat(layer_cls, config):
    if not getattr(config, "recompute", False):
        return layer_cls
    policy = _remat_policy(getattr(config, "recompute_granularity", "full"))
    # static_argnums counts the bound module as arg 0 -> `deterministic` is arg 6
    return nn.remat(layer_cls, policy=policy, static_argnums=(6,))


class LlamaModule(nn.Module):
    """Embedding -> N decoder layers (unrolled or scanned) -> final norm
    (reference ``LlamaModel`` :1440)."""

    config: LlamaConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    decoder_layer_cls = LlamaDecoderLayer  # class attr (subclass-overridable)

    @nn.compact
    def __call__(
        self,
        input_ids=None,
        attention_mask=None,
        position_ids=None,
        segment_ids=None,
        cache: Optional[KVCache] = None,
        inputs_embeds=None,
        deterministic: bool = True,
        output_hidden_states: bool = False,
        return_dict: bool = True,
    ):
        cfg = self.config
        if inputs_embeds is None:
            inputs_embeds = VocabEmbed(
                cfg.vocab_size,
                cfg.hidden_size,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                embedding_init=nn.initializers.normal(cfg.initializer_range),
                name="embed_tokens",
            )(input_ids)
        if getattr(cfg, "scale_embeddings", False):  # gemma: h *= sqrt(hidden)
            inputs_embeds = inputs_embeds * jnp.asarray(cfg.hidden_size**0.5, dtype=inputs_embeds.dtype)
        h = shard_constraint(inputs_embeds, P("batch", "act_seq", "act_embed"))
        offset = cache.offset if cache is not None else jnp.zeros((), jnp.int32)

        layer_cls = _maybe_remat(type(self).decoder_layer_cls, cfg)
        all_hidden = [] if output_hidden_states else None
        use_scan = getattr(cfg, "use_scan_layers", False) and not output_hidden_states

        if use_scan:
            scan_kv = (cache.keys, cache.values) if cache is not None else None
            ScanStack = nn.scan(
                layer_cls,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(0 if cache is not None else nn.broadcast,) + (nn.broadcast,) * 4,
                length=cfg.num_hidden_layers,
            )
            aux0 = jnp.zeros((), jnp.float32)
            (h, _, aux), new_kv = ScanStack(cfg, self.dtype, self.param_dtype, name="layers")(
                (h, offset, aux0), scan_kv, attention_mask, position_ids, segment_ids, deterministic
            )
            if cache is not None:
                cache = KVCache(keys=new_kv[0], values=new_kv[1],
                                offset=offset + (input_ids.shape[1] if input_ids is not None else inputs_embeds.shape[1]))
        else:
            new_keys, new_values = [], []
            aux = jnp.zeros((), jnp.float32)
            for i in range(cfg.num_hidden_layers):
                if output_hidden_states:
                    all_hidden.append(h)
                layer_kv = cache.layer(i) if cache is not None else None
                (h, _, aux), kv_i = layer_cls(cfg, self.dtype, self.param_dtype, name=f"layers_{i}")(
                    (h, offset, aux), layer_kv, attention_mask, position_ids, segment_ids, deterministic
                )
                if kv_i is not None:
                    new_keys.append(kv_i[0])
                    new_values.append(kv_i[1])
            if cache is not None:
                T = input_ids.shape[1] if input_ids is not None else inputs_embeds.shape[1]
                cache = KVCache(keys=jnp.stack(new_keys), values=jnp.stack(new_values), offset=offset + T)

        # normalize the layer-summed MoE aux loss to the HF convention (computed
        # once over all layers' router logits, not summed per layer)
        aux = aux / cfg.num_hidden_layers
        h = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                         unit_offset=bool(getattr(cfg, "rms_norm_add_unit_offset", False)), name="norm")(h)
        if output_hidden_states:
            all_hidden.append(h)
        if not return_dict:
            return (h, cache, all_hidden)
        return BaseModelOutputWithPast(
            last_hidden_state=h,
            past_key_values=cache,
            hidden_states=tuple(all_hidden) if all_hidden else None,
            aux_loss=aux,
        )


class LlamaForCausalLMModule(nn.Module):
    config: LlamaConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    base_module_cls = LlamaModule  # class attr (subclass-overridable)

    @nn.compact
    def __call__(
        self,
        input_ids=None,
        attention_mask=None,
        position_ids=None,
        segment_ids=None,
        cache: Optional[KVCache] = None,
        inputs_embeds=None,
        deterministic: bool = True,
        output_hidden_states: bool = False,
        return_dict: bool = True,
    ):
        cfg = self.config
        outputs = type(self).base_module_cls(cfg, self.dtype, self.param_dtype, name="model")(
            input_ids,
            attention_mask,
            position_ids,
            segment_ids,
            cache,
            inputs_embeds,
            deterministic,
            output_hidden_states,
            True,
        )
        h = outputs.last_hidden_state
        if cfg.tie_word_embeddings:
            # reference LlamaLMHead with shared weight (modeling_pp.py:361-377)
            embedding = self.get_variable("params", "model")["embed_tokens"]["embedding"]
            logits = h @ embedding.T.astype(self.dtype)
        else:
            logits = _dense(cfg.vocab_size, False, cfg, self.dtype, self.param_dtype, "lm_head")(h)
        # keep logits tp-sharded on vocab: the loss computes on shards
        # (reference `parallel_matmul` + tensor_parallel_output, modeling.py:176)
        logits = shard_constraint(logits, P("batch", "act_seq", "act_vocab"))
        if not return_dict:
            return (logits, outputs.past_key_values)
        return CausalLMOutputWithPast(
            logits=logits,
            past_key_values=outputs.past_key_values,
            hidden_states=outputs.hidden_states,
            aux_loss=outputs.aux_loss,
        )


class LlamaForSequenceClassificationModule(nn.Module):
    config: LlamaConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, input_ids=None, attention_mask=None, position_ids=None, segment_ids=None,
                 cache=None, inputs_embeds=None, deterministic=True, output_hidden_states=False, return_dict=True):
        cfg = self.config
        outputs = LlamaModule(cfg, self.dtype, self.param_dtype, name="model")(
            input_ids, attention_mask, position_ids, segment_ids, cache, inputs_embeds, deterministic, False, True
        )
        h = outputs.last_hidden_state
        # pool at the last non-pad token (reference pools the sequence end)
        if attention_mask is not None:
            last = jnp.maximum(attention_mask.sum(axis=-1).astype(jnp.int32) - 1, 0)
        else:
            last = jnp.full((h.shape[0],), h.shape[1] - 1, dtype=jnp.int32)
        pooled = h[jnp.arange(h.shape[0]), last]
        logits = _dense(cfg.num_labels, False, cfg, self.dtype, self.param_dtype, "score")(pooled)
        if not return_dict:
            return (logits,)
        return SequenceClassifierOutput(logits=logits)


class LlamaPretrainedModel(PretrainedModel):
    config_class = LlamaConfig
    base_model_prefix = "model"

    @classmethod
    def get_partition_rules(cls, config=None):
        """Logical partition specs per param (reference `_get_tensor_parallel_mappings`
        llama/modeling.py:1267-1330 — here one table covers tp AND fsdp AND the rest;
        scanned layers get a leading `layers` axis prepended automatically)."""
        return [
            (r"embed_tokens/embedding$", P("vocab", "embed")),
            (r"self_attn/(q_proj|k_proj|v_proj)/kernel$", P("embed", "heads")),
            (r"self_attn/(q_proj|k_proj|v_proj)/bias$", P("heads")),
            (r"self_attn/o_proj/kernel$", P("heads", "embed")),
            (r"mlp/(gate_proj|up_proj)/kernel$", P("embed", "mlp")),
            (r"mlp/(gate_proj|up_proj)/bias$", P("mlp")),
            (r"mlp/down_proj/kernel$", P("mlp", "embed")),
            (r"(lm_head|score)/kernel$", P("embed", "vocab")),
            (r"(input_layernorm|post_attention_layernorm|norm)/scale$", P()),
        ]


class LlamaModel(LlamaPretrainedModel):
    module_class = LlamaModule


class LlamaForCausalLM(LlamaPretrainedModel):
    module_class = LlamaForCausalLMModule
    _keys_to_ignore_on_load_missing = [r"lm_head"]

    def pipelined_loss(self, params, batch, *, n_stages: int, criterion=None, shift: bool = True,
                       dropout_rng=None):
        """Causal-LM loss with the decoder trunk run as a pp-stage pipeline.

        The Trainer calls this instead of ``compute_loss`` when the mesh has
        pp>1 (reference ``training_pipeline_step`` trainer.py:2246 +
        ``LlamaForCausalLMPipe`` modeling_pp.py:296 — here the SAME network/
        params pipeline themselves; no second model class). ``batch`` tensors
        are [M, mb, ...] with M = microbatch count (the grad-accum axis).
        Embedding/head run outside the pipeline; under the Trainer they are
        vocab-sharded over (tp, pp) — see Trainer._logical_overrides — (they are
        a small fraction of trunk FLOPs); shared-embedding gradients therefore
        need no special handling — AD sums both uses.
        """
        from ...parallel.pipeline import spatial_pipeline

        cfg = self.config
        module = self.module
        if not getattr(cfg, "use_scan_layers", False):
            raise ValueError("pipeline parallelism requires use_scan_layers=True (stacked [L] params)")
        dtype, pdtype = module.dtype, module.param_dtype
        ids = batch["input_ids"]
        labels = batch["labels"]
        M, mb, T = ids.shape
        mp = params["model"]

        h = VocabEmbed(
            cfg.vocab_size, cfg.hidden_size, dtype=dtype, param_dtype=pdtype,
        ).apply({"params": mp["embed_tokens"]}, ids.reshape(M * mb, T))
        if getattr(cfg, "scale_embeddings", False):
            h = h * jnp.asarray(cfg.hidden_size**0.5, dtype=h.dtype)
        h = h.reshape(M, mb, T, cfg.hidden_size)
        h = shard_constraint(h, P(None, "batch", "act_seq", None))

        mask = batch.get("attention_mask")
        pos = batch.get("position_ids")
        seg = batch.get("segment_ids")
        layer_cls = type(module).base_module_cls.decoder_layer_cls
        base_layer = layer_cls(cfg, dtype, pdtype)

        def layer_fn(lp, state):
            hh, m_, p_, s_, aux, mb_i, layer_i = state
            if dropout_rng is None:
                rngs, det = {}, True
            else:
                # unique stream per (microbatch, layer): the microbatch id rides
                # the pipeline state, the layer counter increments per tick
                rngs = {"dropout": jax.random.fold_in(jax.random.fold_in(dropout_rng, mb_i), layer_i)}
                det = False
            (hh, _, aux), _ = base_layer.apply(
                {"params": lp}, (hh, jnp.zeros((), jnp.int32), aux), None, m_, p_, s_, det,
                rngs=rngs,
            )
            return (hh, m_, p_, s_, aux, mb_i, layer_i + 1)

        if getattr(cfg, "recompute", False):
            layer_fn = jax.checkpoint(
                layer_fn, policy=_remat_policy(getattr(cfg, "recompute_granularity", "full"))
            )
        stream = (h, mask, pos, seg, jnp.zeros((M,), jnp.float32),
                  jnp.arange(M, dtype=jnp.int32), jnp.zeros((M,), jnp.int32))
        h_out, _, _, _, aux, _, _ = spatial_pipeline(layer_fn, mp["layers"], stream, n_stages)
        aux = aux / cfg.num_hidden_layers  # HF convention (LlamaModule does the same)

        norm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                            unit_offset=bool(getattr(cfg, "rms_norm_add_unit_offset", False)))

        def head_loss(total, xs):
            h_mb, labels_mb, aux_mb = xs
            hn = norm.apply({"params": mp["norm"]}, h_mb)
            if cfg.tie_word_embeddings:
                logits = hn @ mp["embed_tokens"]["embedding"].T.astype(dtype)
            else:
                import flax.linen as fnn

                logits = fnn.Dense(cfg.vocab_size, use_bias=False, dtype=dtype, param_dtype=pdtype).apply(
                    {"params": params["lm_head"]}, hn
                )
            logits = shard_constraint(logits, P("batch", "act_seq", "act_vocab"))
            if criterion is not None:
                loss = criterion(logits, labels_mb)
            else:
                loss = causal_lm_loss(logits, labels_mb, shift=shift)
            return total + loss + aux_mb, None

        total, _ = jax.lax.scan(head_loss, jnp.zeros((), jnp.float32), (h_out, labels, aux))
        return total / M

    def get_model_flops(self, batch_size: int, seq_length: int) -> float:
        cfg = self.config
        n = self.num_parameters()
        # 6ND for matmuls + causal attention term (fwd+bwd)
        return 6.0 * n * batch_size * seq_length + 6.0 * cfg.num_hidden_layers * cfg.head_dim * \
            cfg.num_attention_heads * (seq_length**2) * batch_size


class LlamaForSequenceClassification(LlamaPretrainedModel):
    module_class = LlamaForSequenceClassificationModule
    _keys_to_ignore_on_load_missing = [r"score"]


class LlamaPretrainingCriterion:
    """Parallel-CE pretraining loss (reference :1777). Logits stay vocab-sharded;
    XLA's partitioner builds the reduce across tp shards."""

    def __init__(self, config: LlamaConfig, ignore_index: int = -100):
        self.config = config
        self.ignore_index = ignore_index

    def __call__(self, logits, labels):
        loss, _ = cross_entropy_with_ignore(logits, labels, self.ignore_index)
        return loss
