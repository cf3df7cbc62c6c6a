"""DeepSeek-V2, TPU-native: Multi-head Latent Attention (MLA) + grouped MoE.

Counterpart of ``paddlenlp/transformers/deepseek_v2/modeling.py``
(``DeepseekV2Attention`` :775, ``MoEGate`` :605, ``DeepseekV2MoE`` :715).
TPU-first shape of the port:

- MLA is two low-rank projection chains (q: hidden->q_lora->heads, kv:
  hidden->kv_lora(+shared rope head)->heads) feeding the SAME fused attention
  dispatcher as every other family — the decompressed per-head K/V stay
  ephemeral inside the jit, XLA fuses the b-proj matmuls into the attention
  chain. V (128) rides padded inside the K-dim (192) cache so the shared
  KVCache/generation machinery applies unchanged.
- DeepSeek's rope convention: interleaved pairs permuted to half layout before
  the rotate (reference :539-556), applied only to the rope slice of q and the
  single shared k_pe head; YaRN mscale multiplies the tables and the softmax
  scale (reference :846-855).
- MoE: stacked-expert einsums ([E, D, F] — one MXU pass, no per-expert loop)
  with softmax routing, optional group-limited top-k (n_group/topk_group,
  reference :648-655), routed_scaling_factor, always-on shared experts, and the
  sequence-level aux loss (seq_aux, reference :674-691) threaded through the
  layer carry (summed over layers, normalized by L in LlamaModule).
- first_k_dense_replace / moe_layer_freq pick dense vs MoE per layer index
  (reference DeepseekV2DecoderLayer :1122) — unrolled layers only.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ...ops.flash_attention import dot_product_attention
from ...ops.rope import rope_frequencies, rope_tables, rotate_half
from ...parallel.partition import P, shard_constraint
from ..cache_utils import update_layer_kv
from ..conversion_utils import StackedLayerMapping, auto_name_mappings
from ..llama.modeling import (
    LlamaDecoderLayer,
    LlamaForCausalLMModule,
    LlamaMLP,
    LlamaModule,
    LlamaPretrainedModel,
    LlamaPretrainingCriterion,
    LlamaRMSNorm,
    _dense,
    checkpoint_name,
)
from .configuration import DeepseekV2Config

__all__ = ["DeepseekV2Model", "DeepseekV2ForCausalLM", "DeepseekV2PretrainedModel"]


def _yarn_mscale(scale: float, mscale: float) -> float:
    if scale <= 1 or mscale == 0:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def _interleave_to_half(x: jnp.ndarray) -> jnp.ndarray:
    """[..., d] pairs (x0,x1,x2,x3,..) -> (x0,x2,..,x1,x3,..): deepseek stores
    rope dims interleaved; permute to the half-rotate layout (reference :550-553)."""
    d = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (d // 2, 2))
    return jnp.moveaxis(x, -1, -2).reshape(x.shape[:-2] + (d,))


class DeepseekV2Attention(nn.Module):
    """MLA (reference DeepseekV2Attention :775): low-rank q/kv projections, rope
    on a small shared-head slice, softmax scale with the YaRN mscale correction."""

    config: DeepseekV2Config
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
        n_heads = cfg.num_attention_heads
        d_nope, d_rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        q_head_dim = d_nope + d_rope
        d_v = cfg.v_head_dim

        # scopes (forward and backward operations carry them): mla_proj, rope, mla_attn, o_proj
        with jax.named_scope("mla_proj"):
            # ---- q path (optionally low-rank: hidden -> q_lora -> heads)
            if cfg.q_lora_rank is None:
                q = _dense(n_heads * q_head_dim, False, cfg, self.dtype, self.param_dtype, "q_proj")(hidden_states)
            else:
                qa = _dense(cfg.q_lora_rank, cfg.attention_bias, cfg, self.dtype, self.param_dtype, "q_a_proj")(hidden_states)
                qa = LlamaRMSNorm(cfg.q_lora_rank, cfg.rms_norm_eps, name="q_a_layernorm")(qa)
                q = _dense(n_heads * q_head_dim, False, cfg, self.dtype, self.param_dtype, "q_b_proj")(qa)
            q = q.reshape(B, T, n_heads, q_head_dim)

            # ---- kv path: compressed latent + a single shared rope head (MQA-style)
            ckv = _dense(cfg.kv_lora_rank + d_rope, cfg.attention_bias, cfg, self.dtype, self.param_dtype,
                         "kv_a_proj_with_mqa")(hidden_states)
            c_kv, k_pe = ckv[..., : cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank :]
            c_kv = LlamaRMSNorm(cfg.kv_lora_rank, cfg.rms_norm_eps, name="kv_a_layernorm")(c_kv)
            kvb = _dense(n_heads * (d_nope + d_v), False, cfg, self.dtype, self.param_dtype, "kv_b_proj")(c_kv)
            kvb = kvb.reshape(B, T, n_heads, d_nope + d_v)
            k_nope, v = kvb[..., :d_nope], kvb[..., d_nope:]
            k_pe = k_pe.reshape(B, T, 1, d_rope)

        q = shard_constraint(q, P("batch", "act_seq_attn", "act_heads", None))
        k_nope = shard_constraint(k_nope, P("batch", "act_seq_attn", "act_heads", None))
        v = shard_constraint(v, P("batch", "act_seq_attn", "act_heads", None))

        # ---- rope on the pe slices only (deepseek interleaved convention)
        if position_ids is None:
            position_ids = jnp.arange(T)[None, :] + (offset if kv is not None else 0)
        inv_freq = jnp.asarray(rope_frequencies(d_rope, cfg.rope_theta, cfg.rope_scaling))
        cos, sin = rope_tables(position_ids, inv_freq)
        softmax_scale = q_head_dim**-0.5
        scaling = cfg.rope_scaling or {}
        if scaling.get("type", scaling.get("rope_type")) == "yarn":
            factor = float(scaling.get("factor", 1.0))
            m = _yarn_mscale(factor, scaling.get("mscale", 1)) / _yarn_mscale(
                factor, scaling.get("mscale_all_dim", 0)
            )
            cos, sin = cos * m, sin * m
            if scaling.get("mscale_all_dim", 0):
                ms = _yarn_mscale(factor, scaling["mscale_all_dim"])
                softmax_scale = softmax_scale * ms * ms

        def rope(x):
            x = _interleave_to_half(x)
            x32 = x.astype(jnp.float32)
            return (x32 * cos[:, :, None, :] + rotate_half(x32) * sin[:, :, None, :]).astype(x.dtype)

        with jax.named_scope("rope"):
            q_pe = rope(q[..., d_nope:])
            k_pe = rope(k_pe)
            q = jnp.concatenate([q[..., :d_nope], q_pe], axis=-1)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (B, T, n_heads, d_rope))], axis=-1)

        q_offset = 0
        new_kv = None
        if kv is not None:
            # shared cache layout is [B, S, n_heads, q_head_dim]: V (d_v) rides
            # zero-padded inside the K head dim, sliced back after the gather
            v_padded = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, q_head_dim - d_v)))
            q_offset = offset
            k, v_padded = update_layer_kv(kv[0], kv[1], k, v_padded, offset)
            new_kv = (k, v_padded)
            v = v_padded

        dropout_rate = cfg.attention_dropout if not deterministic else 0.0
        dropout_rng = self.make_rng("dropout") if dropout_rate > 0.0 else None
        q = checkpoint_name(q, "attn_qkv")
        k = checkpoint_name(k, "attn_qkv")
        # Without a cache V goes to the dispatcher at its own width: the flash kernels
        # carry a value head beside the query/key head (192 and 128), and the XLA
        # path pads it there. The cached-decode path is already padded.
        v_run = checkpoint_name(v, "attn_qkv")
        with jax.named_scope("mla_attn"):
            attn_out = dot_product_attention(
                q, k, v_run,
                attention_mask=attention_mask,
                segment_ids=segment_ids,
                causal=True,
                q_offset=q_offset,
                scale=softmax_scale,
                dropout_rate=dropout_rate,
                dropout_rng=dropout_rng,
            )
        attn_out = attn_out[..., :d_v].reshape(B, T, n_heads * d_v)  # named "core_attn" by the call
        with jax.named_scope("o_proj"):
            out = _dense(cfg.hidden_size, cfg.attention_bias, cfg, self.dtype, self.param_dtype, "o_proj")(attn_out)
        return out, new_kv


class _SharedExpertsMLP(nn.Module):
    """Always-on shared experts: one SwiGLU with n_shared * moe_intermediate
    width (reference DeepseekV2MoE :736)."""

    config: DeepseekV2Config
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        F = cfg.moe_intermediate_size * cfg.n_shared_experts
        gate = _dense(F, False, cfg, self.dtype, self.param_dtype, "gate_proj")(x)
        up = _dense(F, False, cfg, self.dtype, self.param_dtype, "up_proj")(x)
        return _dense(cfg.hidden_size, False, cfg, self.dtype, self.param_dtype, "down_proj")(nn.silu(gate) * up)


class DeepseekV2MoE(nn.Module):
    """Routed experts with softmax scoring, optional group-limited top-k, and
    the seq-aux balance loss (reference MoEGate :605 + DeepseekV2MoE :715)."""

    config: DeepseekV2Config
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
        cfg = self.config
        E, K = cfg.n_routed_experts, cfg.num_experts_per_tok
        D, F = cfg.hidden_size, cfg.moe_intermediate_size
        B, T, _ = x.shape
        init = nn.initializers.normal(cfg.initializer_range)

        router = nn.Dense(E, use_bias=False, dtype=jnp.float32, param_dtype=self.param_dtype,
                          kernel_init=init, name="gate")
        logits = router(x.astype(jnp.float32)).reshape(-1, E)
        probs = jax.nn.softmax(logits, axis=-1)  # scoring_func == softmax
        N = probs.shape[0]

        if cfg.topk_method == "group_limited_greedy":
            G = cfg.n_group
            group_scores = probs.reshape(N, G, E // G).max(axis=-1)  # [N, G]
            _, gidx = jax.lax.top_k(group_scores, cfg.topk_group)
            gmask = jax.vmap(lambda m, i: m.at[i].set(1.0))(jnp.zeros((N, G)), gidx)
            sel_probs = jnp.where(jnp.repeat(gmask, E // G, axis=-1) > 0, probs, 0.0)
        else:
            sel_probs = probs
        topk_probs, topk_idx = jax.lax.top_k(sel_probs, K)
        if K > 1 and cfg.norm_topk_prob:
            topk_probs = topk_probs / jnp.clip(topk_probs.sum(-1, keepdims=True), 1e-20)
        topk_probs = topk_probs * cfg.routed_scaling_factor
        combine = jax.vmap(lambda c, i, p: c.at[i].set(p))(jnp.zeros_like(probs), topk_idx, topk_probs)

        w_gate = self.param("gate_proj", init, (E, D, F), self.param_dtype)
        w_up = self.param("up_proj", init, (E, D, F), self.param_dtype)
        w_down = self.param("down_proj", init, (E, F, D), self.param_dtype)
        w_gate_ = shard_constraint(w_gate.astype(self.dtype), P("expert", "embed", "mlp"))
        w_up_ = shard_constraint(w_up.astype(self.dtype), P("expert", "embed", "mlp"))
        w_down_ = shard_constraint(w_down.astype(self.dtype), P("expert", "mlp", "embed"))

        xf = x.reshape(-1, D)
        g = jnp.einsum("nd,edf->nef", xf, w_gate_)
        u = jnp.einsum("nd,edf->nef", xf, w_up_)
        expert_out = jnp.einsum("nef,efd->ned", nn.silu(g) * u, w_down_)
        out = jnp.einsum("ned,ne->nd", expert_out, combine.astype(expert_out.dtype))

        if cfg.n_shared_experts:
            out = out + _SharedExpertsMLP(cfg, self.dtype, self.param_dtype,
                                          name="shared_experts")(x).reshape(-1, D)

        # aux balance loss (per-sequence when seq_aux — reference :674-691)
        aux = jnp.zeros((), jnp.float32)
        if cfg.aux_loss_alpha and cfg.aux_loss_alpha > 0:
            sel = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32).sum(axis=1)  # [N, E]
            if cfg.seq_aux:
                ce = sel.reshape(B, T, E).sum(axis=1) / (T * K / E)  # [B, E]
                aux = (ce * probs.reshape(B, T, E).mean(axis=1)).sum(axis=1).mean()
            else:
                fi = sel.mean(axis=0) * E / K
                aux = (fi * probs.mean(axis=0)).sum()
            aux = aux * cfg.aux_loss_alpha
        return out.reshape(B, T, D), aux


class DeepseekV2DecoderLayer(LlamaDecoderLayer):
    attn_cls = DeepseekV2Attention

    def _mlp_module(self):
        cfg = self.config
        # unrolled layers are named "layers_<i>"; scan ("layers") is rejected at
        # config time for heterogeneous stacks
        name = self.name or ""
        idx = int(name.rsplit("_", 1)[1]) if "_" in name and name.rsplit("_", 1)[1].isdigit() else 0
        moe_here = (
            cfg.n_routed_experts is not None
            and idx >= cfg.first_k_dense_replace
            and idx % cfg.moe_layer_freq == 0
        )
        if moe_here:
            return DeepseekV2MoE(cfg, self.dtype, self.param_dtype, name="mlp")
        return LlamaMLP(cfg, self.dtype, self.param_dtype, name="mlp")


class DeepseekV2Module(LlamaModule):
    decoder_layer_cls = DeepseekV2DecoderLayer


class DeepseekV2ForCausalLMModule(LlamaForCausalLMModule):
    base_module_cls = DeepseekV2Module


class DeepseekV2PretrainedModel(LlamaPretrainedModel):
    config_class = DeepseekV2Config

    @classmethod
    def get_partition_rules(cls, config=None):
        return list(LlamaPretrainedModel.get_partition_rules(config)) + [
            (r"self_attn/(q_a_proj|kv_a_proj_with_mqa)/kernel$", P("embed", None)),
            (r"self_attn/(q_b_proj|kv_b_proj)/kernel$", P(None, "heads")),
            (r"mlp/gate/kernel$", P("embed", None)),
            (r"mlp/(gate_proj|up_proj)$", P("expert", "embed", "mlp")),
            (r"mlp/down_proj$", P("expert", "mlp", "embed")),
            (r"shared_experts/(gate_proj|up_proj)/kernel$", P("embed", "mlp")),
            (r"shared_experts/down_proj/kernel$", P("mlp", "embed")),
        ]

    @classmethod
    def _get_name_mappings(cls, config, flat_shapes):
        mappings = []
        plain = {}
        n_experts = config.n_routed_experts or 0
        for path, leaf in flat_shapes.items():
            tail = path.rsplit("/", 1)[-1]
            stacked_expert = (
                "/mlp/" in path
                and "/shared_experts/" not in path
                and tail in ("gate_proj", "up_proj", "down_proj")
                and len(getattr(leaf, "shape", ())) == 3
            )
            if stacked_expert:
                layer_idx = path.split("/layers_")[1].split("/")[0]
                tpl = f"model.layers.{layer_idx}.mlp.experts.{{}}.{tail}.weight"
                mappings.append(StackedLayerMapping(tpl, path, action="transpose", dims=(n_experts,)))
            else:
                plain[path] = leaf
        mappings.extend(auto_name_mappings(plain))
        return mappings


class DeepseekV2Model(DeepseekV2PretrainedModel):
    module_class = DeepseekV2Module


class DeepseekV2ForCausalLM(DeepseekV2PretrainedModel):
    module_class = DeepseekV2ForCausalLMModule
    _keys_to_ignore_on_load_missing = [r"lm_head"]


DeepseekV2PretrainingCriterion = LlamaPretrainingCriterion
