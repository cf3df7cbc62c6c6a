"""exaone_moe decoder: grouped-query attention over a window (rotated) or the
whole context (not rotated) with a per-head norm of q and k, a dense SwiGLU MLP
in the leading layer and sigmoid-routed SwiGLU experts that know which they
hold in the rest.

The layer mathematics is ``transformers/window_layers.py``'s (the attention)
and ``transformers/latent_layers.py``'s (the MLP and the expert layer): plain
functions over one layer's parameter tree, so that the whole-sequence module
below (``AutoModel``, no cache) and the serving step programs the configuration
names (``ExaoneMoeConfig.inference_model``) compute the same thing from the same
code. Here: the parameter tree, the whole-sequence forward, the flax modules,
checkpoint names and partition rules.

Left out: the multi-token-prediction block."""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
from flax import linen as nn

from ...parallel.partition import P
from ..conversion_utils import StackedLayerMapping, auto_name_mappings
from ..latent_layers import mlp, rms_norm
from ..model_utils import PretrainedModel
from ..param_tree import ParamTree
from ..window_layers import attention_dense
from .configuration import ExaoneMoeConfig

__all__ = ["ExaoneMoeModel", "ExaoneMoeForCausalLM", "ExaoneMoePretrainedModel", "param_tree_shapes"]

FLOAT32_LEAVES = ("scale", "e_score_correction_bias")  # kept float32 whatever the weights' dtype


# ------------------------------------------------------------------ the parameter tree
def _swiglu_shapes(hidden, width):
    return {"gate_proj": {"kernel": (hidden, width)}, "up_proj": {"kernel": (hidden, width)},
            "down_proj": {"kernel": (width, hidden)}}


def _mlp_shapes(cfg, layer):
    hidden = cfg.hidden_size
    if layer < cfg.first_k_dense_replace:
        return _swiglu_shapes(hidden, cfg.intermediate_size)
    held, width = cfg.num_experts, cfg.moe_intermediate_size
    return {
        "gate": {"kernel": (hidden, cfg.num_experts_total)},
        "e_score_correction_bias": (cfg.num_experts_total,),
        "experts": {"gate_proj": (held, hidden, width), "up_proj": (held, hidden, width),
                    "down_proj": (held, width, hidden)},
        "shared_experts": _swiglu_shapes(hidden, width * cfg.num_shared_experts),
    }


def param_tree_shapes(cfg, causal_lm: bool = True) -> Dict:
    """{path: shape} nested as the module's parameters are."""
    hidden, hd = cfg.hidden_size, cfg.head_dim
    q, kv = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd
    model = {"embed_tokens": {"embedding": (cfg.vocab_size, hidden)}, "norm": {"scale": (hidden,)}}
    for i in range(cfg.num_hidden_layers):
        model[f"layers_{i}"] = {
            "input_layernorm": {"scale": (hidden,)},
            "post_attention_layernorm": {"scale": (hidden,)},
            "self_attn": {"q_proj": {"kernel": (hidden, q)}, "k_proj": {"kernel": (hidden, kv)},
                          "v_proj": {"kernel": (hidden, kv)}, "o_proj": {"kernel": (q, hidden)},
                          "q_norm": {"scale": (hd,)}, "k_norm": {"scale": (hd,)}},
            "mlp": _mlp_shapes(cfg, i),
        }
    out = {"model": model}
    if causal_lm:
        out["lm_head"] = {"kernel": (hidden, cfg.vocab_size)}
    return out


# ------------------------------------------------------------------ whole-sequence forward (no cache)
def decoder_forward(cfg, params, input_ids, positions=None, dtype=jnp.float32):
    """Whole-sequence forward: hidden states [B, T, hidden] after the final norm.
    The residual form (pre-norm) lives here and in ``window_model._run_layers``."""
    m = params["model"] if "model" in params else params
    b, t = input_ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None, :], (b, t))
    h = m["embed_tokens"]["embedding"][input_ids].astype(dtype)
    for i, kind in enumerate(cfg.layer_kinds()):
        lp = m[f"layers_{i}"]
        x = rms_norm(h, lp["input_layernorm"]["scale"], cfg.rms_norm_eps)
        h = h + attention_dense(lp["self_attn"], x, positions, cfg, kind)
        x = rms_norm(h, lp["post_attention_layernorm"]["scale"], cfg.rms_norm_eps)
        h = h + mlp(lp["mlp"], x, cfg, i)[0]
    return rms_norm(h, m["norm"]["scale"], cfg.rms_norm_eps)


# ------------------------------------------------------------------ flax modules
def _float32_init(name):
    """Norm scales start at 1, the router's selection bias at 0; None: not a float32 leaf."""
    if name not in FLOAT32_LEAVES:
        return None
    return nn.initializers.ones if name == "scale" else nn.initializers.zeros


class ExaoneMoeModule(nn.Module):
    config: ExaoneMoeConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    causal_lm = False

    @nn.compact
    def __call__(self, input_ids, position_ids=None, deterministic: bool = True):
        cfg = self.config
        shapes = param_tree_shapes(cfg, self.causal_lm)
        params = {k: ParamTree(v, cfg.initializer_range, _float32_init, self.param_dtype, name=k)()
                  for k, v in shapes.items()}
        h = decoder_forward(cfg, params, input_ids, position_ids, self.dtype)
        if not self.causal_lm:
            return h
        return (h @ params["lm_head"]["kernel"].astype(self.dtype)).astype(jnp.float32)


class ExaoneMoeForCausalLMModule(ExaoneMoeModule):
    causal_lm = True


class ExaoneMoePretrainedModel(PretrainedModel):
    config_class = ExaoneMoeConfig
    base_model_prefix = "model"

    @classmethod
    def get_partition_rules(cls, config=None):
        return [
            (r"embed_tokens/embedding$", P("vocab", "embed")),
            (r"self_attn/(q_proj|k_proj|v_proj)/kernel$", P("embed", "heads")),
            (r"self_attn/o_proj/kernel$", P("heads", "embed")),
            (r"mlp/gate/kernel$", P("embed", None)),
            (r"mlp/experts/(gate_proj|up_proj)$", P("expert", "embed", "mlp")),
            (r"mlp/experts/down_proj$", P("expert", "mlp", "embed")),
            (r"(mlp|shared_experts)/(gate_proj|up_proj)/kernel$", P("embed", "mlp")),
            (r"(mlp|shared_experts)/down_proj/kernel$", P("mlp", "embed")),
            (r"lm_head/kernel$", P("embed", "vocab")),
            (r"(scale|e_score_correction_bias)$", P()),
        ]

    @classmethod
    def _get_name_mappings(cls, config, flat_shapes):
        """Checkpoint names: the stacked experts are ``mlp.experts.<n>.<proj>.weight``
        of the held range; everything else maps by its own path."""
        mappings, plain = [], {}
        for path, leaf in flat_shapes.items():
            tail = path.rsplit("/", 1)[-1]
            if "/mlp/experts/" in path and tail in ("gate_proj", "up_proj", "down_proj"):
                layer = path.split("/layers_")[1].split("/")[0]
                tpl = f"model.layers.{layer}.mlp.experts.{{}}.{tail}.weight"
                mappings.append(StackedLayerMapping(tpl, path, action="transpose", dims=(config.num_experts,)))
            else:
                plain[path] = leaf
        mappings.extend(auto_name_mappings(plain))
        return mappings


class ExaoneMoeModel(ExaoneMoePretrainedModel):
    module_class = ExaoneMoeModule


class ExaoneMoeForCausalLM(ExaoneMoePretrainedModel):
    module_class = ExaoneMoeForCausalLMModule
