"""Mamba (selective state space model), TPU-native.

Counterpart of ``paddlenlp/transformers/mamba/modeling.py`` (``MambaMixer``
:121, ``MambaCache`` :76, ``MambaBlock`` :371, ``MambaModel`` :595). The
reference's fast path is a fused CUDA kernel (``mamba_inner_fn`` /
``selective_scan_fn``); its fallback is a Python for-loop over time (:322-329).
TPU-first shape of the port:

- the selective-scan recurrence ``s_t = dA_t * s_{t-1} + dBu_t`` is a
  first-order linear recurrence — expressed as ``jax.lax.associative_scan``
  (O(log T) depth on the VPU, the TPU-native answer to the CUDA scan kernel);
- the depthwise causal conv (kernel 4) is K shifted adds — no conv primitive,
  fuses into the surrounding elementwise chain;
- decode carries a ``MambaCache`` pytree (conv tail [K, Di] + SSM state
  [N, Di] per layer) through the SAME static ``lax.while_loop`` decode as the
  attention families, via the ``_init_decode_cache`` hook;
- params keep HF mamba names (``backbone.layers.{i}.mixer.*``) for checkpoint
  interop; ``A_log``/``D``/``conv1d.weight`` get explicit mappings.

Whole-sequence only: ``MambaCache`` serves ``model.generate()`` and lives
outside the serving engine, whose door refuses this family. The state-space
family ``InferenceEngine`` serves is ``nemotron_h`` (Mamba-2: recurrent state
rows beside the paged KV pool, ``experimental/state_model.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from ...parallel.partition import P, shard_constraint
from ..conversion_utils import StateDictNameMapping, auto_name_mappings
from ..model_outputs import BaseModelOutputWithPast, CausalLMOutputWithPast
from ..model_utils import PretrainedModel
from ...ops.cross_entropy import causal_lm_loss
from .configuration import MambaConfig

__all__ = ["MambaModel", "MambaForCausalLM", "MambaPretrainedModel", "MambaCache"]


@dataclasses.dataclass
class MambaCache:
    """conv_states [L, B, K, Di] (last K inputs per channel), ssm_states
    [L, B, N, Di] fp32, offset scalar (tokens already consumed)."""

    conv_states: jnp.ndarray
    ssm_states: jnp.ndarray
    offset: jnp.ndarray

    def layer(self, idx):
        return self.conv_states[idx], self.ssm_states[idx]


jax.tree_util.register_dataclass(
    MambaCache, data_fields=["conv_states", "ssm_states", "offset"], meta_fields=[]
)


def init_mamba_cache(config, batch_size: int, dtype=jnp.float32) -> MambaCache:
    L, K = config.num_hidden_layers, config.conv_kernel
    Di, N = config.intermediate_size, config.state_size
    return MambaCache(
        conv_states=jnp.zeros((L, batch_size, K, Di), dtype),
        ssm_states=jnp.zeros((L, batch_size, N, Di), jnp.float32),
        offset=jnp.zeros((), jnp.int32),
    )


def selective_scan(dA: jnp.ndarray, dBu: jnp.ndarray, s0: Optional[jnp.ndarray] = None):
    """All states of ``s_t = dA_t * s_{t-1} + dBu_t`` (t along axis 1).

    dA/dBu [B, T, Di, N]; s0 [B, Di, N] initial state (decode resume).
    associative combine for first-order recurrences: (a2·a1, a2·b1 + b2).
    """
    if s0 is not None:
        dBu = dBu.at[:, 0].add(dA[:, 0] * s0)

    def combine(left, right):
        a1, b1 = left
        a2, b2 = right
        return a2 * a1, a2 * b1 + b2

    _, states = jax.lax.associative_scan(combine, (dA, dBu), axis=1)
    return states  # [B, T, Di, N]


class MambaMixer(nn.Module):
    """The S6 block (reference MambaMixer :121): gated in_proj, depthwise causal
    conv, input-dependent (dt, B, C) selection, selective scan, gated out_proj."""

    config: MambaConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    norm_selection: bool = False  # jamba: RMSNorm on dt/B/C before dt_proj

    @nn.compact
    def __call__(self, x, layer_cache=None, pad_mask=None):
        cfg = self.config
        B_, T, _ = x.shape
        Di, N, K, R = cfg.intermediate_size, cfg.state_size, cfg.conv_kernel, cfg.time_step_rank
        act = nn.silu
        dense = lambda f, b, name: nn.Dense(
            f, use_bias=b, dtype=self.dtype, param_dtype=self.param_dtype,
            kernel_init=nn.initializers.normal(cfg.initializer_range), name=name)

        proj = dense(2 * Di, cfg.use_bias, "in_proj")(x)  # [B, T, 2Di]
        u, gate = proj[..., :Di], proj[..., Di:]
        if pad_mask is not None:
            # pad tokens (left-padded batched generate) must be invisible to the
            # recurrence: zero the conv input here, and zero dt below so the
            # SSM update at pads is the identity (dA=1, dBu=0)
            u = u * pad_mask[:, :, None].astype(u.dtype)

        conv_w = self.param("conv1d_weight", nn.initializers.normal(cfg.initializer_range),
                            (K, Di), self.param_dtype).astype(self.dtype)
        conv_b = (self.param("conv1d_bias", nn.initializers.zeros, (Di,), self.param_dtype)
                  .astype(self.dtype) if cfg.use_conv_bias else None)

        new_conv = new_ssm = None
        decode_step = layer_cache is not None and T == 1
        if decode_step:
            conv_state, ssm_state = layer_cache  # [B, K, Di], [B, N, Di]
            conv_state = jnp.concatenate([conv_state[:, 1:], u], axis=1)  # roll in the new token
            new_conv = conv_state
            u = jnp.einsum("bkd,kd->bd", conv_state.astype(self.dtype), conv_w)[:, None]
            if conv_b is not None:
                u = u + conv_b
            u = act(u)
        else:
            # depthwise causal conv as K shifted adds (kernel is tiny)
            conv_in = u
            pad = jnp.pad(conv_in, ((0, 0), (K - 1, 0), (0, 0)))
            u = sum(pad[:, k : k + T] * conv_w[k] for k in range(K))
            if conv_b is not None:
                u = u + conv_b
            u = act(u)
            if layer_cache is not None:  # prefill: save the last K pre-conv inputs
                new_conv = jnp.pad(conv_in, ((0, 0), (K, 0), (0, 0)))[:, -K:]

        sel = dense(R + 2 * N, False, "x_proj")(u)  # [B, T, R + 2N]
        dt, Bsel, Csel = sel[..., :R], sel[..., R : R + N], sel[..., R + N :]
        if self.norm_selection:
            # jamba stabilization (reference jamba/modeling.py:643-699)
            eps = cfg.layer_norm_epsilon
            dt = MambaRMSNorm(R, eps, name="dt_layernorm")(dt)
            Bsel = MambaRMSNorm(N, eps, name="b_layernorm")(Bsel)
            Csel = MambaRMSNorm(N, eps, name="c_layernorm")(Csel)
        dt = dense(Di, True, "dt_proj")(dt)  # [B, T, Di]
        dt = jax.nn.softplus(dt.astype(jnp.float32))
        if pad_mask is not None:
            dt = dt * pad_mask[:, :, None].astype(jnp.float32)

        A_log = self.param("A_log", lambda key: jnp.log(jnp.broadcast_to(
            jnp.arange(1, N + 1, dtype=jnp.float32)[None, :], (Di, N)).copy()))
        D = self.param("D", nn.initializers.ones, (Di,), jnp.float32)
        A = -jnp.exp(A_log.astype(jnp.float32))  # [Di, N]

        # state layout [.., N, Di]: dt [B,T,Di] -> [B,T,1,Di]; Bsel [B,T,N] ->
        # [B,T,N,1]; u [B,T,1,Di]
        u32 = u.astype(jnp.float32)
        dA = jnp.exp(dt[:, :, None, :] * A.T[None, None])  # [B, T, N, Di]
        dBu = dt[:, :, None, :] * Bsel.astype(jnp.float32)[..., None] * u32[:, :, None, :]

        if decode_step:
            s = dA[:, 0] * ssm_state + dBu[:, 0]  # [B, N, Di]
            new_ssm = s
            y = jnp.einsum("bnd,bn->bd", s, Csel[:, 0].astype(jnp.float32))[:, None]
        else:
            states = selective_scan(dA, dBu)  # [B, T, N, Di]
            if layer_cache is not None:
                new_ssm = states[:, -1]
            y = jnp.einsum("btnd,btn->btd", states, Csel.astype(jnp.float32))
        y = y + u32 * D[None, None]
        y = y * act(gate.astype(jnp.float32))
        out = dense(cfg.hidden_size, cfg.use_bias, "out_proj")(y.astype(self.dtype))
        return out, (new_conv, new_ssm)


class MambaRMSNorm(nn.Module):
    dim: int
    eps: float = 1e-5
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (self.dim,), self.param_dtype)
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + self.eps)
        return (x32 * scale.astype(jnp.float32)).astype(x.dtype)


class MambaModule(nn.Module):
    config: MambaConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self,
        input_ids=None,
        attention_mask=None,  # accepted for API parity; SSM state has no pad masking
        position_ids=None,
        segment_ids=None,
        cache: Optional[MambaCache] = None,
        inputs_embeds=None,
        deterministic: bool = True,
        output_hidden_states: bool = False,
        return_dict: bool = True,
    ):
        cfg = self.config
        if inputs_embeds is None:
            table = self.param("embeddings", nn.initializers.normal(cfg.initializer_range),
                               (cfg.vocab_size, cfg.hidden_size), self.param_dtype)
            inputs_embeds = jnp.take(table.astype(self.dtype), input_ids, axis=0)
        h = shard_constraint(inputs_embeds, P("batch", "act_seq", "act_embed"))
        offset = cache.offset if cache is not None else jnp.zeros((), jnp.int32)
        T_in = h.shape[1]
        # left-pad masking for batched prefill; single decode tokens are real
        pad_mask = None
        if attention_mask is not None and T_in > 1 and attention_mask.shape[1] >= T_in:
            pad_mask = attention_mask[:, :T_in]

        all_hidden = [] if output_hidden_states else None
        new_conv, new_ssm = [], []
        for i in range(cfg.num_hidden_layers):
            if output_hidden_states:
                all_hidden.append(h)
            residual = h
            x = MambaRMSNorm(cfg.hidden_size, cfg.layer_norm_epsilon,
                             name=f"layers_{i}_norm")(h)
            out, (c_i, s_i) = MambaMixer(cfg, self.dtype, self.param_dtype,
                                         name=f"layers_{i}_mixer")(
                x, cache.layer(i) if cache is not None else None, pad_mask)
            h = residual + out
            if c_i is not None:
                new_conv.append(c_i)
                new_ssm.append(s_i)
        if cache is not None:
            T = input_ids.shape[1] if input_ids is not None else inputs_embeds.shape[1]
            cache = MambaCache(conv_states=jnp.stack(new_conv), ssm_states=jnp.stack(new_ssm),
                               offset=offset + T)
        h = MambaRMSNorm(cfg.hidden_size, cfg.layer_norm_epsilon, name="norm_f")(h)
        if output_hidden_states:
            all_hidden.append(h)
        if not return_dict:
            return (h, cache, all_hidden)
        return BaseModelOutputWithPast(
            last_hidden_state=h, past_key_values=cache,
            hidden_states=tuple(all_hidden) if all_hidden else None,
        )


class MambaForCausalLMModule(nn.Module):
    config: MambaConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, input_ids=None, attention_mask=None, position_ids=None,
                 segment_ids=None, cache: Optional[MambaCache] = None, inputs_embeds=None,
                 deterministic: bool = True, output_hidden_states: bool = False,
                 return_dict: bool = True):
        cfg = self.config
        outputs = MambaModule(cfg, self.dtype, self.param_dtype, name="backbone")(
            input_ids, attention_mask, position_ids, segment_ids, cache, inputs_embeds,
            deterministic, output_hidden_states, True,
        )
        h = outputs.last_hidden_state
        # HF mamba ties lm_head to the embedding table
        table = self.get_variable("params", "backbone")["embeddings"]
        logits = h @ table.T.astype(self.dtype)
        if not return_dict:
            return (logits, outputs.past_key_values)
        return CausalLMOutputWithPast(
            logits=logits, past_key_values=outputs.past_key_values,
            hidden_states=outputs.hidden_states,
        )


class MambaPretrainedModel(PretrainedModel):
    config_class = MambaConfig
    base_model_prefix = "backbone"

    def _init_decode_cache(self, batch_size: int, max_length: int):
        return init_mamba_cache(self.config, batch_size)

    @classmethod
    def get_partition_rules(cls, config=None):
        return [
            (r"embeddings$", P("vocab", "embed")),
            (r"mixer/in_proj/kernel$", P("embed", "mlp")),
            (r"mixer/(x_proj|out_proj)/kernel$", P("mlp", None)),
            (r"mixer/dt_proj/kernel$", P(None, "mlp")),
            (r"mixer/(A_log|conv1d_weight)$", P(None, None)),
            (r"mixer/(D|conv1d_bias|dt_proj/bias)$", P(None)),
            (r"(norm|norm_f)/scale$", P()),
        ]

    @classmethod
    def _get_name_mappings(cls, config, flat_shapes):
        import re

        mappings = []
        for path, leaf in flat_shapes.items():
            # layers_{i}_norm / layers_{i}_mixer -> layers.{i}.norm / .mixer
            hf = re.sub(r"layers_(\d+)_(norm|mixer)", r"layers.\1.\2", path)
            hf = hf.replace("/", ".")
            if hf.endswith(".conv1d_weight"):
                # HF conv1d.weight is [Di, 1, K]; ours is [K, Di]
                mappings.append(StateDictNameMapping(
                    hf.replace(".conv1d_weight", ".conv1d.weight"), path,
                    fn=lambda a: np.ascontiguousarray(np.squeeze(a, 1).T),
                    fn_reverse=lambda a: np.ascontiguousarray(a.T[:, None, :])))
            elif hf.endswith(".conv1d_bias"):
                mappings.append(StateDictNameMapping(
                    hf.replace(".conv1d_bias", ".conv1d.bias"), path))
            elif hf.endswith(".kernel"):
                mappings.append(StateDictNameMapping(hf.replace(".kernel", ".weight"), path, "transpose"))
            elif hf.endswith(".scale"):
                mappings.append(StateDictNameMapping(hf.replace(".scale", ".weight"), path))
            elif hf.endswith("backbone.embeddings"):
                mappings.append(StateDictNameMapping("backbone.embeddings.weight", path))
            else:  # A_log, D, biases: name-identical
                mappings.append(StateDictNameMapping(hf, path))
        return mappings


class MambaModel(MambaPretrainedModel):
    module_class = MambaModule


class MambaForCausalLM(MambaPretrainedModel):
    module_class = MambaForCausalLMModule

    def compute_loss(self, params, batch):
        logits = self.module.apply({"params": params}, input_ids=batch["input_ids"],
                                   deterministic=True).logits
        return causal_lm_loss(logits, batch["labels"], shift=True)
