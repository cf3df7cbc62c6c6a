"""PromptTrainer + soft prompt tuning.

Counterpart of ``paddlenlp/prompt/`` (2.4k LoC: PromptTrainer, PromptModel,
templates/verbalizers). Two pieces:

- ``PromptModelForClassification``: masked-LM model + template + verbalizer;
  classification logits are the verbalized vocab logits at the mask position.
- ``SoftPromptModelForCausalLM``: p-tuning-style trainable virtual-token
  embeddings prepended via ``inputs_embeds``; only the prompt matrix trains
  (facade design like peft/prefix).
- ``PromptTrainer``: Trainer whose loss is CE over verbalized class scores.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..trainer.trainer import Trainer
from ..transformers.conversion_utils import flatten_params, unflatten_params
from ..utils.log import logger
from ..utils.safetensors_io import SafeFile, save_file

__all__ = ["PromptModelForClassification", "SoftPromptModelForCausalLM", "PromptTrainer"]

SOFT_PROMPT_WEIGHTS_NAME = "soft_prompt.safetensors"


class PromptModelForClassification:
    """Masked-LM + verbalizer head (frozen or full finetune both work)."""

    def __init__(self, model, template, verbalizer):
        self.model = model
        self.template = template
        self.verbalizer = verbalizer
        self.config = model.config
        self.params = model.params
        self.module = model.module

    def class_logits(self, params, input_ids, attention_mask, mask_position):
        out = self.model.module.apply({"params": params}, input_ids=input_ids,
                                      attention_mask=attention_mask, deterministic=True)
        logits = out.logits if hasattr(out, "logits") else out[0]
        mask_logits = jnp.take_along_axis(
            logits, mask_position[:, None, None].astype(jnp.int32), axis=1
        )[:, 0]
        return self.verbalizer.process_logits(mask_logits.astype(jnp.float32))

    def num_parameters(self, params=None):
        return self.model.num_parameters(params)


class SoftPromptModelForCausalLM:
    """Prepends ``n_prompt_tokens`` trainable embeddings to the input embedding
    sequence; labels/attention are host-extended by the caller (the Trainer's
    built-in loss sees -100 over the virtual span via compute_loss below)."""

    def __init__(self, model, n_prompt_tokens: int = 16, init_std: float = 0.02,
                 params: Optional[dict] = None):
        self.model = model
        self.config = model.config
        self.dtype = model.dtype
        self.n_prompt_tokens = n_prompt_tokens
        if params is not None:
            self.params = params
        else:
            rng = np.random.default_rng(0)
            prompt = rng.normal(0.0, init_std,
                                (n_prompt_tokens, model.config.hidden_size)).astype(np.float32)
            self.params = dict(model.params)
            self.params["soft_prompt"] = jnp.asarray(prompt)
        self.module = self
        self.mesh = model.mesh
        self.generation_config = model.generation_config

    # duck-typed module.apply used by the Trainer loss
    def apply(self, variables, input_ids=None, attention_mask=None, deterministic=True, **kw):
        params = variables["params"] if "params" in variables else variables
        prompt = params["soft_prompt"]
        base = {k: v for k, v in params.items() if k != "soft_prompt"}
        B, T = input_ids.shape
        embed = self._embedding(base)
        tok = jnp.take(embed, input_ids, axis=0).astype(self.model.module.dtype)
        virt = jnp.broadcast_to(prompt[None], (B,) + prompt.shape).astype(tok.dtype)
        inputs_embeds = jnp.concatenate([virt, tok], axis=1)
        if attention_mask is not None:
            attention_mask = jnp.concatenate(
                [jnp.ones((B, self.n_prompt_tokens), attention_mask.dtype), attention_mask], axis=1
            )
        out = self.model.module.apply({"params": base}, inputs_embeds=inputs_embeds,
                                      attention_mask=attention_mask,
                                      deterministic=deterministic, **kw)
        # with ``mutable`` (the Trainer asks for the layers' counters) flax returns (outputs, collections)
        out, *collections = out if kw.get("mutable") else (out,)
        # slice the virtual-token span off so logits align with the caller's
        # [B, T] labels (the built-in causal-LM loss shifts against them)
        if hasattr(out, "logits"):
            import dataclasses as _dc

            out = _dc.replace(out, logits=out.logits[:, self.n_prompt_tokens:])
        return (out, *collections) if collections else out

    def _embedding(self, params):
        prefix = type(self.model).base_model_prefix
        node = params.get(prefix, params)
        for key in ("embed_tokens", "wte", "word_embeddings"):
            if key in node:
                return node[key]["embedding"]
        raise ValueError("could not locate the token embedding table for soft prompts")

    def trainable_mask(self) -> dict:
        flat = flatten_params(self.params)
        return unflatten_params({p: p == "soft_prompt" for p in flat})

    def get_partition_rules_instance(self):
        from ..parallel.partition import P

        base = list(type(self.model).get_partition_rules(self.config))
        return base + [(r"^soft_prompt$", P(None, "embed"))]

    def __call__(self, *args, params=None, **kwargs):
        return self.apply({"params": params if params is not None else self.params}, *args, **kwargs)

    def num_parameters(self, params=None):
        return self.model.num_parameters()

    def get_model_flops(self, *a, **kw):
        return self.model.get_model_flops(*a, **kw)

    def save_pretrained(self, save_directory: str, **kw):
        os.makedirs(save_directory, exist_ok=True)
        save_file({"soft_prompt": np.asarray(jax.device_get(self.params["soft_prompt"]))},
                  os.path.join(save_directory, SOFT_PROMPT_WEIGHTS_NAME), metadata={"format": "np"})
        logger.info(f"soft prompt saved to {save_directory}")

    @classmethod
    def from_pretrained(cls, model, path: str, n_prompt_tokens: int = 16) -> "SoftPromptModelForCausalLM":
        obj = cls(model, n_prompt_tokens=n_prompt_tokens)
        with SafeFile(os.path.join(path, SOFT_PROMPT_WEIGHTS_NAME)) as sf:
            obj.params["soft_prompt"] = jnp.asarray(sf.get_tensor("soft_prompt"))
        return obj


class PromptTrainer(Trainer):
    """Trains a PromptModelForClassification with CE over verbalized scores
    (reference PromptTrainer). Batches carry input_ids/attention_mask/
    mask_position/labels(int class index)."""

    def __init__(self, model: PromptModelForClassification = None, **kwargs):
        self.prompt_model = model
        super().__init__(model=model.model, **kwargs)

    def compute_loss(self, params, inputs: Dict[str, Any], dropout_rng=None):
        labels = inputs["labels"]
        scores = self.prompt_model.class_logits(
            params, inputs["input_ids"], inputs.get("attention_mask"), inputs["mask_position"]
        )
        logp = jax.nn.log_softmax(scores, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=-1).mean()
