"""Jamba (mamba + attention hybrid MoE), TPU-native.

Counterpart of ``paddlenlp/transformers/jamba/modeling.py``
(``JambaAttentionDecoderLayer`` :981, ``JambaMambaDecoderLayer`` :1066,
``JambaMambaMixer`` :586 with the dt/B/C RMSNorm stabilization :643-699,
``JambaSparseMoeBlock``). Distinctives:

- layer i is an ATTENTION block when ``i % attn_layer_period ==
  attn_layer_offset``, else a MAMBA block (config.layers_block_type);
- attention is GQA with NO positional encoding (Jamba is NoPE — position
  comes from the mamba recurrences);
- the feed-forward of layer i is a top-k routed MoE when ``i %
  expert_layer_period == expert_layer_offset`` (reusing the shared
  stacked-expert ``MoEMLP``), a plain SwiGLU MLP otherwise;
- the mamba mixer REUSES this framework's ``MambaMixer`` (associative-scan
  selective scan) with ``norm_selection=True``;
- decode carries a hybrid ``JambaCache``: KV rows only for attention layers,
  conv/ssm state rows only for mamba layers (no memory wasted on the other
  kind).

Layer heterogeneity rules out lax.scan over layers; the stack is unrolled
(``use_scan_layers`` raises).

Whole-sequence only: ``JambaCache`` serves ``model.generate()`` and lives
outside the serving engine, whose door refuses this family (Mamba-1 mixers).
The hybrid the engine serves is ``nemotron_h`` (``experimental/state_model.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from ...ops.flash_attention import dot_product_attention
from ...parallel.partition import P, shard_constraint
from ..cache_utils import update_layer_kv
from ..llama.modeling import LlamaRMSNorm, VocabEmbed, _maybe_remat
from ..llama.modeling import LlamaPretrainingCriterion as JambaPretrainingCriterion
from ..mamba.configuration import MambaConfig
from ..mamba.modeling import MambaMixer
from ..model_outputs import BaseModelOutputWithPast, CausalLMOutputWithPast
from ..model_utils import PretrainedModel
from ..moe_layers import MoEMLP
from .configuration import JambaConfig

__all__ = ["JambaModel", "JambaForCausalLM", "JambaPretrainedModel", "JambaCache",
           "JambaPretrainingCriterion"]


@dataclasses.dataclass
class JambaCache:
    """Hybrid decode cache: keys/values [L_attn, B, S, K, H] for the attention
    layers (in layer order), conv_states [L_mamba, B, Kc, Di] + ssm_states
    [L_mamba, B, N, Di] for the mamba layers; offset scalar."""

    keys: jnp.ndarray
    values: jnp.ndarray
    conv_states: jnp.ndarray
    ssm_states: jnp.ndarray
    offset: jnp.ndarray


jax.tree_util.register_dataclass(
    JambaCache,
    data_fields=["keys", "values", "conv_states", "ssm_states", "offset"],
    meta_fields=[],
)


def _mamba_cfg(cfg: JambaConfig) -> MambaConfig:
    """Adapter: the shared MambaMixer reads MambaConfig field names."""
    return MambaConfig(
        vocab_size=1, hidden_size=cfg.hidden_size, state_size=cfg.mamba_d_state,
        num_hidden_layers=1, expand=cfg.mamba_expand, conv_kernel=cfg.mamba_d_conv,
        use_bias=cfg.mamba_proj_bias, use_conv_bias=cfg.mamba_conv_bias,
        time_step_rank=cfg.mamba_dt_rank, layer_norm_epsilon=cfg.rms_norm_eps,
        initializer_range=cfg.initializer_range,
    )


def _dense(features, cfg, dtype, param_dtype, name, use_bias=False):
    return nn.Dense(features, use_bias=use_bias, dtype=dtype, param_dtype=param_dtype,
                    kernel_init=nn.initializers.normal(cfg.initializer_range), name=name)


class JambaMoEBlock(MoEMLP):
    """Router linear named ``router``; expert stacks named gate/up/down_proj
    (the HF jamba convention)."""

    gate_name = "router"
    names = ("gate_proj", "up_proj", "down_proj")


class JambaAttention(nn.Module):
    config: JambaConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, attention_mask, segment_ids, layer_kv, offset, deterministic):
        cfg = self.config
        B, T, D = x.shape
        n, kvn, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        q = _dense(n * hd, cfg, self.dtype, self.param_dtype, "q_proj")(x).reshape(B, T, n, hd)
        k = _dense(kvn * hd, cfg, self.dtype, self.param_dtype, "k_proj")(x).reshape(B, T, kvn, hd)
        v = _dense(kvn * hd, cfg, self.dtype, self.param_dtype, "v_proj")(x).reshape(B, T, kvn, hd)
        q = shard_constraint(q, P("batch", "act_seq_attn", "act_heads", None))
        k = shard_constraint(k, P("batch", "act_seq_attn", "act_kv_heads", None))
        v = shard_constraint(v, P("batch", "act_seq_attn", "act_kv_heads", None))
        # NoPE: no rotary/alibi — order is carried by the mamba layers
        q_offset = 0
        new_kv = None
        if layer_kv is not None:
            q_offset = offset
            k, v = update_layer_kv(layer_kv[0], layer_kv[1], k, v, offset)
            new_kv = (k, v)
        drop = cfg.attention_dropout if not deterministic else 0.0
        rng = self.make_rng("dropout") if drop > 0 else None
        out = dot_product_attention(
            q, k, v, attention_mask=attention_mask, segment_ids=segment_ids, causal=True,
            q_offset=q_offset, dropout_rate=drop, dropout_rng=rng,
        ).reshape(B, T, n * hd)
        return _dense(D, cfg, self.dtype, self.param_dtype, "o_proj")(out), new_kv


class JambaModule(nn.Module):
    config: JambaConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, input_ids=None, attention_mask=None, position_ids=None, segment_ids=None,
                 cache: Optional[JambaCache] = None, inputs_embeds=None, deterministic: bool = True,
                 output_hidden_states: bool = False, return_dict: bool = True):
        cfg = self.config
        if getattr(cfg, "use_scan_layers", False):
            raise ValueError("jamba's heterogeneous layer stack does not support use_scan_layers")
        if inputs_embeds is None:
            inputs_embeds = VocabEmbed(cfg.vocab_size, cfg.hidden_size, dtype=self.dtype,
                                       param_dtype=self.param_dtype,
                                       embedding_init=nn.initializers.normal(cfg.initializer_range),
                                       name="embed_tokens")(input_ids)
        h = shard_constraint(inputs_embeds, P("batch", "act_seq", "act_embed"))
        offset = cache.offset if cache is not None else jnp.zeros((), jnp.int32)
        T_in = h.shape[1]
        pad_mask = None
        if attention_mask is not None and T_in > 1 and attention_mask.shape[1] >= T_in:
            pad_mask = attention_mask[:, :T_in]

        block_types = cfg.layers_block_type
        num_experts = cfg.layers_num_experts
        mcfg = _mamba_cfg(cfg)
        all_hidden = [] if output_hidden_states else None
        aux = jnp.zeros((), jnp.float32)
        new_k, new_v, new_conv, new_ssm = [], [], [], []
        attn_i = mamba_i = 0
        for i in range(cfg.num_hidden_layers):
            if output_hidden_states:
                all_hidden.append(h)
            residual = h
            x = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps, name=f"layers_{i}_input_layernorm")(h)
            if block_types[i] == "attention":
                layer_kv = (cache.keys[attn_i], cache.values[attn_i]) if cache is not None else None
                out, kv_i = JambaAttention(cfg, self.dtype, self.param_dtype,
                                           name=f"layers_{i}_self_attn")(
                    x, attention_mask, segment_ids, layer_kv, offset, deterministic)
                if kv_i is not None:
                    new_k.append(kv_i[0])
                    new_v.append(kv_i[1])
                attn_i += 1
            else:
                layer_cache = (cache.conv_states[mamba_i], cache.ssm_states[mamba_i]) \
                    if cache is not None else None
                out, (c_i, s_i) = MambaMixer(mcfg, self.dtype, self.param_dtype,
                                             norm_selection=True, name=f"layers_{i}_mamba")(
                    x, layer_cache, pad_mask)
                if c_i is not None:
                    new_conv.append(c_i)
                    new_ssm.append(s_i)
                mamba_i += 1
            h = residual + out
            h = shard_constraint(h, P("batch", "act_seq", "act_embed"))

            residual = h
            x = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps, name=f"layers_{i}_pre_ff_layernorm")(h)
            if num_experts[i] > 1:
                ff, aux_i = JambaMoEBlock(cfg, self.dtype, self.param_dtype,
                                          name=f"layers_{i}_feed_forward")(x)
                aux = aux + aux_i
            else:
                gate = _dense(cfg.intermediate_size, cfg, self.dtype, self.param_dtype,
                              f"layers_{i}_ff_gate_proj")(x)
                up = _dense(cfg.intermediate_size, cfg, self.dtype, self.param_dtype,
                            f"layers_{i}_ff_up_proj")(x)
                y = nn.silu(gate) * up
                y = shard_constraint(y, P("batch", "seq", "act_mlp"))
                ff = _dense(cfg.hidden_size, cfg, self.dtype, self.param_dtype,
                            f"layers_{i}_ff_down_proj")(y)
            h = residual + ff
            h = shard_constraint(h, P("batch", "act_seq", "act_embed"))

        if cache is not None:
            T = input_ids.shape[1] if input_ids is not None else inputs_embeds.shape[1]
            stack = lambda xs, like: jnp.stack(xs) if xs else jnp.zeros_like(like)
            cache = JambaCache(
                keys=stack(new_k, cache.keys), values=stack(new_v, cache.values),
                conv_states=stack(new_conv, cache.conv_states),
                ssm_states=stack(new_ssm, cache.ssm_states),
                offset=offset + T,
            )
        h = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps, name="final_layernorm")(h)
        if output_hidden_states:
            all_hidden.append(h)
        if not return_dict:
            return (h, cache, all_hidden)
        return BaseModelOutputWithPast(last_hidden_state=h, past_key_values=cache,
                                       hidden_states=tuple(all_hidden) if all_hidden else None,
                                       aux_loss=aux)


class JambaForCausalLMModule(nn.Module):
    config: JambaConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, input_ids=None, attention_mask=None, position_ids=None, segment_ids=None,
                 cache=None, inputs_embeds=None, deterministic=True,
                 output_hidden_states=False, return_dict=True):
        cfg = self.config
        outputs = JambaModule(cfg, self.dtype, self.param_dtype, name="model")(
            input_ids, attention_mask, position_ids, segment_ids, cache, inputs_embeds,
            deterministic, output_hidden_states, True,
        )
        h = outputs.last_hidden_state
        if cfg.tie_word_embeddings:
            embedding = self.get_variable("params", "model")["embed_tokens"]["embedding"]
            logits = h @ embedding.T.astype(self.dtype)
        else:
            logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=self.dtype,
                              param_dtype=self.param_dtype,
                              kernel_init=nn.initializers.normal(cfg.initializer_range),
                              name="lm_head")(h)
        logits = shard_constraint(logits, P("batch", "act_seq", "act_vocab"))
        if not return_dict:
            return (logits, outputs.past_key_values)
        return CausalLMOutputWithPast(logits=logits, past_key_values=outputs.past_key_values,
                                      hidden_states=outputs.hidden_states, aux_loss=outputs.aux_loss)


class JambaPretrainedModel(PretrainedModel):
    config_class = JambaConfig
    base_model_prefix = "model"

    @classmethod
    def get_partition_rules(cls, config=None):
        return [
            (r"embed_tokens/embedding$", P("vocab", "embed")),
            (r"(q_proj|k_proj|v_proj)/kernel$", P("embed", "heads")),
            (r"o_proj/kernel$", P("heads", "embed")),
            (r"mamba/in_proj/kernel$", P("embed", "mlp")),
            (r"mamba/(x_proj|out_proj)/kernel$", P("mlp", None)),
            (r"mamba/dt_proj/kernel$", P(None, "mlp")),
            (r"feed_forward/(gate_proj|up_proj)$", P("expert", "embed", "mlp")),
            (r"feed_forward/down_proj$", P("expert", "mlp", "embed")),
            (r"ff_(gate|up)_proj/kernel$", P("embed", "mlp")),
            (r"ff_down_proj/kernel$", P("mlp", "embed")),
            (r"(layernorm|final_layernorm)/scale$", P()),
            (r"lm_head/kernel$", P("embed", "vocab")),
        ]

    @classmethod
    def _get_name_mappings(cls, config, flat_shapes):
        """Flat underscore scopes -> HF dotted scopes; mamba conv1d like the
        mamba family; per-expert stacks handled as single stacked tensors."""
        import re

        import numpy as np

        from ..conversion_utils import StateDictNameMapping

        mappings = super()._get_name_mappings(config, flat_shapes)
        for m in mappings:
            key = m.source_template if hasattr(m, "source_template") else m.source_name
            key = re.sub(r"layers_(\d+)_ff_(gate|up|down)_proj", r"layers.\1.feed_forward.\2_proj", key)
            key = re.sub(r"layers_(\d+)_(input_layernorm|pre_ff_layernorm|self_attn|mamba|feed_forward)",
                         r"layers.\1.\2", key)
            key = key.replace("conv1d_weight", "conv1d.weight").replace("conv1d_bias", "conv1d.bias")
            if hasattr(m, "source_template"):
                m.source_template = key
            else:
                m.source_name = key
            if m.target_name.endswith("conv1d_weight"):
                m.action = None
                m.fn = lambda a: np.ascontiguousarray(np.squeeze(np.asarray(a), 1).T)
                m.fn_reverse = lambda a: np.ascontiguousarray(np.asarray(a).T[:, None, :])
        return mappings


class JambaModel(JambaPretrainedModel):
    module_class = JambaModule


class JambaForCausalLM(JambaPretrainedModel):
    module_class = JambaForCausalLMModule
    _keys_to_ignore_on_load_missing = [r"lm_head"]

    def _init_decode_cache(self, batch_size: int, max_length: int):
        cfg = self.config
        dtype = jnp.bfloat16 if self.module.dtype == jnp.bfloat16 else jnp.float32
        n_attn = sum(1 for t in cfg.layers_block_type if t == "attention")
        n_mamba = cfg.num_hidden_layers - n_attn
        Di = cfg.mamba_expand * cfg.hidden_size
        return JambaCache(
            keys=jnp.zeros((max(n_attn, 1), batch_size, max_length,
                            cfg.num_key_value_heads, cfg.head_dim), dtype),
            values=jnp.zeros((max(n_attn, 1), batch_size, max_length,
                              cfg.num_key_value_heads, cfg.head_dim), dtype),
            conv_states=jnp.zeros((max(n_mamba, 1), batch_size, cfg.mamba_d_conv, Di), jnp.float32),
            ssm_states=jnp.zeros((max(n_mamba, 1), batch_size, cfg.mamba_d_state, Di), jnp.float32),
            offset=jnp.zeros((), jnp.int32),
        )
