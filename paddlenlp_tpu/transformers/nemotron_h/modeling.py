"""nemotron_h hybrid decoder: Mamba-2 mixers, sigmoid-routed relu² experts that
know which they hold, and grouped-query attention without a position embedding,
one mixer a block under a pre-norm residual.

The layer mathematics is ``transformers/state_layers.py``'s (the scan layer, the
attention) and ``transformers/latent_layers.py``'s (the expert layer, told the
relu² body): plain functions over one block's parameter tree, so that the
whole-sequence module below (``AutoModel``, no cache) and the serving step
programs the configuration names (``NemotronHConfig.inference_model``) compute
the same thing from the same code. Here: the parameter tree, the
whole-sequence forward, the flax modules, checkpoint names and partition rules.

Whole-sequence only, like every module: the engine serves this family through
``experimental/state_model.py`` (recurrent state rows beside the paged KV pool)."""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
from flax import linen as nn

from ...parallel.partition import P
from ..conversion_utils import StackedLayerMapping, StateDictNameMapping
from ..latent_layers import RELU2, moe
from ..model_utils import PretrainedModel
from ..param_tree import ParamTree
from ..state_layers import EXPERTS, SSM, attention_dense, rms_norm, ssm_mixer
from .configuration import NemotronHConfig

__all__ = ["NemotronHModel", "NemotronHForCausalLM", "NemotronHPretrainedModel", "param_tree_shapes"]

#: kept float32 whatever the weights' dtype: norm scales, the router's selection bias, the scan layers' A_log, D, dt_bias and conv bias
FLOAT32_LEAVES = ("scale", "bias", "e_score_correction_bias", "A_log", "D", "dt_bias")


# ------------------------------------------------------------------ the parameter tree
def _mixer_shapes(cfg, kind):
    hidden = cfg.hidden_size
    if kind == SSM:
        d = cfg.ssm_dims()
        # the checkpoint's in_proj [z | xBC | dt] as its three column blocks (state_layers.in_project says why)
        return {"in_proj": {"z": {"kernel": (hidden, d["d_in"])}, "xbc": {"kernel": (hidden, d["conv_dim"])},
                            "dt": {"kernel": (hidden, d["heads"])}},
                "conv1d": {"kernel": (d["conv"], d["conv_dim"]), "bias": (d["conv_dim"],)},
                "A_log": (d["heads"],), "D": (d["heads"],), "dt_bias": (d["heads"],),
                "norm": {"scale": (d["d_in"],)}, "out_proj": {"kernel": (d["d_in"], hidden)}}
    if kind == EXPERTS:
        held, width, shared = cfg.n_routed_experts, cfg.moe_intermediate_size, cfg.moe_shared_expert_intermediate_size
        return {"gate": {"kernel": (hidden, cfg.n_routed_experts_total)},
                "e_score_correction_bias": (cfg.n_routed_experts_total,),
                # up_proj out x in as the checkpoint has it, down_proj in x out (latent_layers.RELU2)
                "experts": {"up_proj": (held, width, hidden), "down_proj": (held, width, hidden)},
                "shared_experts": {"up_proj": {"kernel": (hidden, shared)}, "down_proj": {"kernel": (shared, hidden)}}}
    q, kv = cfg.num_attention_heads * cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim
    return {"q_proj": {"kernel": (hidden, q)}, "k_proj": {"kernel": (hidden, kv)}, "v_proj": {"kernel": (hidden, kv)},
            "o_proj": {"kernel": (q, hidden)}}


def param_tree_shapes(cfg, causal_lm: bool = True) -> Dict:
    """{path: shape} nested as the module's parameters are."""
    hidden = cfg.hidden_size
    model = {"embed_tokens": {"embedding": (cfg.vocab_size, hidden)}, "norm": {"scale": (hidden,)}}
    for i, kind in enumerate(cfg.layer_kinds()):
        model[f"layers_{i}"] = {"norm": {"scale": (hidden,)}, "mixer": _mixer_shapes(cfg, kind)}
    out = {"model": model}
    if causal_lm:
        out["lm_head"] = {"kernel": (hidden, cfg.vocab_size)}
    return out


# ------------------------------------------------------------------ whole-sequence forward (no cache)
def decoder_forward(cfg, params, input_ids, dtype=jnp.float32):
    """Whole-sequence forward: hidden states [B, T, hidden] after the final
    norm. Every scan layer starts from a zero state and runs the chunk form."""
    m = params["model"] if "model" in params else params
    b, t = input_ids.shape
    d, eps = cfg.ssm_dims(), cfg.norm_eps
    h = m["embed_tokens"]["embedding"][input_ids].astype(dtype)
    valid = jnp.ones((b, t), bool)
    for i, kind in enumerate(cfg.layer_kinds()):
        lp = m[f"layers_{i}"]
        u, mixer = rms_norm(h, lp["norm"]["scale"], eps), lp["mixer"]
        if kind == SSM:
            before = jnp.zeros((b, d["conv"] - 1, d["conv_dim"]), dtype)
            h0 = jnp.zeros((b, d["groups"], d["heads"] // d["groups"], d["head_dim"], d["state"]), jnp.float32)
            y = ssm_mixer(mixer, u, valid, before, h0, d, eps)[0]
        elif kind == EXPERTS:
            y = moe(mixer, u, cfg, body=RELU2)[0]
        else:
            y = attention_dense(mixer, u, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim)
        h = h + y
    return rms_norm(h, m["norm"]["scale"], eps)


# ------------------------------------------------------------------ flax modules
def _float32_init(name):
    """Norm scales and D start at 1, A_log at log(1..16) by head, dt_bias and biases at 0: a checkpoint or a
    benchmark's seeded draw overwrites them. None: not a float32 leaf."""
    if name not in FLOAT32_LEAVES:
        return None
    if name in ("scale", "D"):
        return nn.initializers.ones
    if name == "A_log":
        return lambda key, shape, dtype: jnp.log(jnp.linspace(1.0, 16.0, shape[0], dtype=dtype))
    return nn.initializers.zeros


class NemotronHModule(nn.Module):
    config: NemotronHConfig
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    causal_lm = False

    @nn.compact
    def __call__(self, input_ids, position_ids=None, deterministic: bool = True):
        cfg = self.config
        shapes = param_tree_shapes(cfg, self.causal_lm)
        params = {k: ParamTree(v, cfg.initializer_range, _float32_init, self.param_dtype, name=k)()
                  for k, v in shapes.items()}
        h = decoder_forward(cfg, params, input_ids, self.dtype)  # no position enters: the scan layers carry it
        if not self.causal_lm:
            return h
        return (h @ params["lm_head"]["kernel"].astype(self.dtype)).astype(jnp.float32)


class NemotronHForCausalLMModule(NemotronHModule):
    causal_lm = True


class NemotronHPretrainedModel(PretrainedModel):
    config_class = NemotronHConfig
    base_model_prefix = "model"

    @classmethod
    def get_partition_rules(cls, config=None):
        return [
            (r"embed_tokens/embedding$", P("vocab", "embed")),
            (r"mixer/in_proj/(z|xbc|dt)/kernel$", P("embed", "heads")),
            (r"mixer/(q_proj|k_proj|v_proj)/kernel$", P("embed", "heads")),
            (r"mixer/(out_proj|o_proj)/kernel$", P("heads", "embed")),
            (r"mixer/gate/kernel$", P("embed", None)),
            (r"mixer/experts/up_proj$", P("expert", "mlp", "embed")),
            (r"mixer/experts/down_proj$", P("expert", "mlp", "embed")),
            (r"shared_experts/up_proj/kernel$", P("embed", "mlp")),
            (r"shared_experts/down_proj/kernel$", P("mlp", "embed")),
            (r"lm_head/kernel$", P("embed", "vocab")),
            (r"(scale|bias|e_score_correction_bias|A_log|D|dt_bias|conv1d/kernel)$", P()),
        ]

    @classmethod
    def _get_name_mappings(cls, config, flat_shapes):
        """Checkpoint names (``backbone.layers.<i>.mixer.*``): a block's norm is
        ``norm.weight``, the convolution ``conv1d.weight`` [C, 1, K] against
        this tree's [K, C], the held experts ``mixer.experts.<n>.<proj>.weight``."""
        mappings = []
        for path in flat_shapes:
            parts = path.split("/")
            tail = parts[-1]
            if parts[:2] == ["model", "embed_tokens"]:
                mappings.append(StateDictNameMapping("backbone.embeddings.weight", path))
            elif parts[:2] == ["model", "norm"]:
                mappings.append(StateDictNameMapping("backbone.norm_f.weight", path))
            elif parts[0] == "lm_head":
                mappings.append(StateDictNameMapping("lm_head.weight", path, action="transpose"))
            else:
                layer = parts[1].split("_")[1]
                stem = f"backbone.layers.{layer}." + ".".join(parts[2:-1])
                if "/experts/" in path:  # up_proj stays out x in, down_proj becomes in x out
                    tpl = f"backbone.layers.{layer}.mixer.experts.{{}}.{tail}.weight"
                    mappings.append(StackedLayerMapping(tpl, path, action="transpose" if tail == "down_proj" else None,
                                                        dims=(config.n_routed_experts,)))
                elif "/in_proj/" in path:  # one checkpoint matrix [z | xBC | dt] x hidden, three column blocks here
                    d = config.ssm_dims()
                    lo = {"z": 0, "xbc": d["d_in"], "dt": d["d_in"] + d["conv_dim"]}[parts[-2]]
                    hi = lo + {"z": d["d_in"], "xbc": d["conv_dim"], "dt": d["heads"]}[parts[-2]]
                    mappings.append(StateDictNameMapping(f"backbone.layers.{layer}.mixer.in_proj.weight", path,
                                                         fn=lambda a, lo=lo, hi=hi: a[lo:hi].T.copy()))
                elif parts[-2] == "conv1d" and tail == "kernel":
                    mappings.append(StateDictNameMapping(stem + ".weight", path, fn=lambda a: a[:, 0, :].T.copy(),
                                                         fn_reverse=lambda a: a.T[:, None, :].copy()))
                elif tail == "kernel":
                    mappings.append(StateDictNameMapping(stem + ".weight", path, action="transpose"))
                elif tail == "scale":
                    mappings.append(StateDictNameMapping(stem + ".weight", path))
                elif tail == "bias":
                    mappings.append(StateDictNameMapping(stem + ".bias", path))
                else:  # A_log, D, dt_bias, e_score_correction_bias: parameters of the mixer itself
                    mappings.append(StateDictNameMapping(f"backbone.layers.{layer}.mixer.{tail}", path))
        return mappings


class NemotronHModel(NemotronHPretrainedModel):
    module_class = NemotronHModule


class NemotronHForCausalLM(NemotronHPretrainedModel):
    module_class = NemotronHForCausalLMModule
