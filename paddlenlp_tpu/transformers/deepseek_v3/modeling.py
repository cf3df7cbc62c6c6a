"""deepseek_v3 for training: ``deepseek_v2``'s latent attention (its class, with
the flash kernels at a value head of its own width) and an expert layer that is
told which experts it holds and has a backward.

The expert layer (``DeepseekV3MoE``, every layer from ``first_k_dense_replace``
on): router scores in float32 through ``latent_layers.route`` (the serving kinds'
router: sigmoid, top-k of score + ``e_score_correction_bias``, the chosen scores
normalised and scaled), the held experts' part of the sum through
``latent_layers.experts_grouped`` (assignments sorted by expert, grouped products
at exactly the rows each held expert received, no capacity and no drop), the
shared expert for every token. ``e_score_correction_bias`` enters the choice
only, so its gradient is zero, and by its name it takes no weight decay
(``trainer.py:_no_decay_mask``): AdamW leaves it as it is. The update of that
bias from the experts' load is not computed here (ROADMAP R6).

What the layers count goes out through the ``counters`` collection
(``latent_layers.GROUPED_COUNTERS``, summed over layers by whoever applies the
module with ``mutable=["counters"]``: the Trainer). Scopes a trace can be read
by: ``mla_proj``, ``rope``, ``mla_attn``, ``o_proj`` (the attention), ``router``,
``expert_dispatch``, ``expert_mm``, ``expert_combine``, ``shared_expert``, and
flax's own module names ``mlp`` (the dense layer) and ``lm_head``.

Not computed, and refused by ``DeepseekV3Config.check``: group-limited routing,
softmax scoring, scanned layers, rope without interleaving. No generation cache
of this family's own: ``generate`` goes through ``deepseek_v2``'s padded cache."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from ...parallel.partition import P
from ..deepseek_v2.modeling import (
    DeepseekV2DecoderLayer,
    DeepseekV2PretrainedModel,
    _SharedExpertsMLP,
)
from ..latent_layers import experts_grouped, route
from ..llama.modeling import LlamaForCausalLMModule, LlamaMLP, LlamaModule, LlamaPretrainingCriterion
from ..param_tree import ParamTree
from .configuration import DeepseekV3Config

__all__ = ["DeepseekV3Model", "DeepseekV3ForCausalLM", "DeepseekV3PretrainedModel"]


def _float32_init(name):
    """The router's selection bias starts at zero and stays float32; every other leaf here draws normal."""
    return nn.initializers.zeros if name == "e_score_correction_bias" else None


class DeepseekV3MoE(nn.Module):
    """Sigmoid bias-corrected routing over the router's whole width; of the routed
    sum the part of the experts held here; the shared expert for every token."""

    config: DeepseekV3Config
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        (first, count), total = cfg.experts_held, cfg.n_routed_experts_total
        hidden, width = cfg.hidden_size, cfg.moe_intermediate_size
        tree = lambda name, shapes: ParamTree(shapes, cfg.initializer_range, _float32_init, self.param_dtype,
                                              name=name)()
        # the checkpoint keeps the bias with the router (``mlp.gate.e_score_correction_bias``); ``route`` reads it beside it
        gate = tree("gate", {"kernel": (hidden, total), "e_score_correction_bias": (total,)})
        p = {"gate": {"kernel": gate["kernel"]}, "e_score_correction_bias": gate["e_score_correction_bias"]}
        experts = tree("experts", {"gate_proj": (count, hidden, width), "up_proj": (count, hidden, width),
                                   "down_proj": (count, width, hidden)})
        x2d = x.reshape(-1, hidden)
        with jax.named_scope("router"):
            idx, w = route(p, x2d, cfg)
        y, counters = experts_grouped(experts, x2d, idx, w, first, count, total)
        for name, value in counters.items():
            self.sow("counters", name, value, reduce_fn=jnp.add, init_fn=lambda: jnp.zeros((), jnp.float32))
        with jax.named_scope("shared_expert"):
            y = y.reshape(x.shape) + _SharedExpertsMLP(cfg, self.dtype, self.param_dtype, name="shared_experts")(x)
        return y


class DeepseekV3DecoderLayer(DeepseekV2DecoderLayer):
    def _mlp_module(self):
        cfg = self.config
        layer = int(self.name.rsplit("_", 1)[1])  # unrolled layers are named "layers_<i>"
        if layer >= cfg.first_k_dense_replace:
            return DeepseekV3MoE(cfg, self.dtype, self.param_dtype, name="mlp")
        return LlamaMLP(cfg, self.dtype, self.param_dtype, name="mlp")


class DeepseekV3Module(LlamaModule):
    decoder_layer_cls = DeepseekV3DecoderLayer


class DeepseekV3ForCausalLMModule(LlamaForCausalLMModule):
    base_module_cls = DeepseekV3Module


class DeepseekV3PretrainedModel(DeepseekV2PretrainedModel):
    config_class = DeepseekV3Config

    def __init__(self, config, *args, **kwargs):
        config.check()  # the trainer's arguments reach a configuration after it is made
        super().__init__(config, *args, **kwargs)

    @classmethod
    def get_partition_rules(cls, config=None):
        return [
            (r"mlp/experts/(gate_proj|up_proj)$", P("expert", "embed", "mlp")),
            (r"mlp/experts/down_proj$", P("expert", "mlp", "embed")),
            (r"e_score_correction_bias$", P()),
        ] + list(DeepseekV2PretrainedModel.get_partition_rules(config))


class DeepseekV3Model(DeepseekV3PretrainedModel):
    module_class = DeepseekV3Module


class DeepseekV3ForCausalLM(DeepseekV3PretrainedModel):
    module_class = DeepseekV3ForCausalLMModule
    _keys_to_ignore_on_load_missing = [r"lm_head"]


DeepseekV3PretrainingCriterion = LlamaPretrainingCriterion
