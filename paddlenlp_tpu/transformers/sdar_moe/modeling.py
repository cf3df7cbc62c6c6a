"""sdar_moe decoder: grouped-query attention under a block mask (causal over
blocks of ``block_length`` positions, a query seeing its own block whole) with a
per-head norm of q and k and RoPE, and softmax-routed SwiGLU experts that know
which they hold, no shared expert. Every layer alike.

The attention's mathematics is ``transformers/window_layers.py``'s (kind
``gqa_block``) and the expert layer's ``transformers/latent_layers.py``'s
(``route`` by softmax, ``experts_held_dense``): plain functions over one layer's
parameter tree, so that the whole-sequence module below (``AutoModel``, no cache)
and the serving step programs the configuration names
(``SdarMoeConfig.inference_model``) compute the same thing from the same code.
Here: the parameter tree (the layers stacked on a leading axis: they are alike,
so both forwards scan them), the whole-sequence forward, the flax modules,
checkpoint names and partition rules.

The module's forward is one pass of the denoiser over whole sequences under the
block mask: what prefill computes, and what training would (its two-copy
diffusion loss is not here). Generation (``block_diffusion_generate``) is the
serving path's, ``experimental/block_model.py``."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from flax import linen as nn

from ...parallel.partition import P
from ..conversion_utils import StackedLayerMapping, auto_name_mappings
from ..latent_layers import experts_held_dense, rms_norm, route
from ..model_utils import PretrainedModel
from ..param_tree import ParamTree
from ..window_layers import GQA_BLOCK, attention_dense
from .configuration import SdarMoeConfig

__all__ = ["SdarMoeModel", "SdarMoeForCausalLM", "SdarMoePretrainedModel", "param_tree_shapes", "sparse_mlp"]


# ------------------------------------------------------------------ the parameter tree
def param_tree_shapes(cfg, causal_lm: bool = True) -> Dict:
    """{path: shape} nested as the module's parameters are; ``model/layers`` holds every layer's leaves stacked."""
    n, hidden, hd = cfg.num_hidden_layers, cfg.hidden_size, cfg.head_dim
    q, kv = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd
    held, width = cfg.num_experts, cfg.moe_intermediate_size
    layers = {
        "input_layernorm": {"scale": (n, hidden)},
        "post_attention_layernorm": {"scale": (n, hidden)},
        "self_attn": {"q_proj": {"kernel": (n, hidden, q)}, "k_proj": {"kernel": (n, hidden, kv)},
                      "v_proj": {"kernel": (n, hidden, kv)}, "o_proj": {"kernel": (n, q, hidden)},
                      "q_norm": {"scale": (n, hd)}, "k_norm": {"scale": (n, hd)}},
        "mlp": {"gate": {"kernel": (n, hidden, cfg.num_experts_total)},
                # the held experts' three matrices all [width, hidden]: gate and up as the checkpoint has them
                # (out x in), down transposed (latent_layers.experts_held_dense)
                "experts": {"gate_proj": (n, held, width, hidden), "up_proj": (n, held, width, hidden),
                            "down_proj": (n, held, width, hidden)}},
    }
    out = {"model": {"embed_tokens": {"embedding": (cfg.vocab_size, hidden)}, "norm": {"scale": (hidden,)},
                     "layers": layers}}
    if causal_lm:
        out["lm_head"] = {"kernel": (hidden, cfg.vocab_size)}
    return out


# ------------------------------------------------------------------ the expert layer
def sparse_mlp(p, x, cfg, live=None):
    """The expert layer on x [..., hidden]: the held experts' part of the
    softmax-routed sum, nothing shared: every held expert on every row
    (``experts_held_dense``: a pass is 128 rows and a chunk 256, where every
    held expert's weights are read anyway). -> (y, chosen [N, k])."""
    x2d = x.reshape(-1, x.shape[-1])
    with jax.named_scope("router"):
        idx, w = route(p, x2d, cfg)
    first, count = cfg.experts_held
    with jax.named_scope("experts"):
        y = experts_held_dense(p["experts"], x2d, idx, w, first, count, live)
    return y.reshape(x.shape), idx


# ------------------------------------------------------------------ whole-sequence forward (no cache)
def decoder_forward(cfg, params, input_ids, positions=None, dtype=jnp.float32):
    """One pass over whole sequences under the block mask: hidden states
    [B, T, hidden] after the final norm. The residual form (pre-norm) lives here
    and in ``block_model._layer``."""
    m = params["model"] if "model" in params else params
    b, t = input_ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None, :], (b, t))

    def layer(h, lp):
        x = rms_norm(h, lp["input_layernorm"]["scale"], cfg.rms_norm_eps)
        h = h + attention_dense(lp["self_attn"], x, positions, cfg, GQA_BLOCK)
        x = rms_norm(h, lp["post_attention_layernorm"]["scale"], cfg.rms_norm_eps)
        return h + sparse_mlp(lp["mlp"], x, cfg)[0], None

    h, _ = jax.lax.scan(layer, m["embed_tokens"]["embedding"][input_ids].astype(dtype), m["layers"])
    return rms_norm(h, m["norm"]["scale"], cfg.rms_norm_eps)


# ------------------------------------------------------------------ flax modules
def _float32_init(name):
    """Norm scales start at 1 and stay float32; None: not a float32 leaf."""
    return nn.initializers.ones if name == "scale" else None


class SdarMoeModule(nn.Module):
    config: SdarMoeConfig
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


class SdarMoeForCausalLMModule(SdarMoeModule):
    causal_lm = True


class SdarMoePretrainedModel(PretrainedModel):
    config_class = SdarMoeConfig
    base_model_prefix = "model"

    @classmethod
    def get_partition_rules(cls, config=None):
        return [
            (r"embed_tokens/embedding$", P("vocab", "embed")),
            (r"self_attn/(q_proj|k_proj|v_proj)/kernel$", P(None, "embed", "heads")),
            (r"self_attn/o_proj/kernel$", P(None, "heads", "embed")),
            (r"mlp/gate/kernel$", P(None, "embed", None)),
            (r"mlp/experts/(gate_proj|up_proj|down_proj)$", P(None, "expert", "mlp", "embed")),
            (r"lm_head/kernel$", P("embed", "vocab")),
            (r"scale$", P()),
        ]

    @classmethod
    def _get_name_mappings(cls, config, flat_shapes):
        """Checkpoint names: the held experts of every layer are
        ``model.layers.<l>.mlp.experts.<n>.<proj>.weight`` of the held range; every
        other leaf maps by its own path (``auto_name_mappings`` unstacks ``model/layers``)."""
        mappings, plain = [], {}
        for path, leaf in flat_shapes.items():
            if "/mlp/experts/" in path:
                tpl = f"model.layers.{{}}.mlp.experts.{{}}.{path.rsplit('/', 1)[-1]}.weight"
                mappings.append(StackedLayerMapping(tpl, path, action="transpose" if path.endswith("down_proj") else None,
                                                    dims=(config.num_hidden_layers, config.num_experts)))
            else:
                plain[path] = leaf
        mappings.extend(auto_name_mappings(plain))
        return mappings


class SdarMoeModel(SdarMoePretrainedModel):
    module_class = SdarMoeModule


class SdarMoeForCausalLM(SdarMoePretrainedModel):
    module_class = SdarMoeForCausalLMModule
