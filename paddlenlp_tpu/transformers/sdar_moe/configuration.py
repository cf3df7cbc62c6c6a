"""sdar_moe configuration: the decoder of ``JetLM/SDAR-30B-A3B-Chat``, which
generates by diffusion over blocks.

The published keys are accepted as they are. Every layer is grouped-query
attention (q and k pass an RMS norm over each head's dims, then rotate-half
RoPE over the whole head) and softmax-routed SwiGLU experts without a shared
one, under pre-norm residuals. **Attention is causal over blocks** of
``block_length`` positions counted from position 0: position i sees position j
iff ``j // B <= i // B``, in prompts and in generated text alike.

Keys of this class's own, not the checkpoint's:

- ``num_experts_total`` / ``first_held_expert``: the router's width (the
  published ``num_experts``) and the first expert held when this process holds
  only a share of the experts; ``num_experts`` then counts the experts held here
  (as ``ExaoneMoeConfig``);
- ``block_length`` (4), ``denoising_steps`` (4), ``remasking``
  (``low_confidence_static`` / ``low_confidence_dynamic``),
  ``confidence_threshold`` (0.9), ``mask_token_id``: how the family's
  ``block_diffusion_generate`` is run (the checkpoint's generation script's
  arguments; the published config carries none of them).

Inert here: ``max_position_embeddings``, ``max_window_layers``,
``intermediate_size`` (no layer is dense: ``mlp_only_layers`` must be empty and
``decoder_sparse_step`` 1)."""

from __future__ import annotations

from ..configuration_utils import PretrainedConfig
from ..window_layers import GQA_BLOCK

__all__ = ["SdarMoeConfig"]

REMASKING = ("low_confidence_static", "low_confidence_dynamic")


class SdarMoeConfig(PretrainedConfig):
    model_type = "sdar_moe"
    #: the class whose serving step programs compute this configuration's layer
    #: kind (``experimental/inference_model.py:inference_model_class`` imports it)
    inference_model = "paddlenlp_tpu.experimental.block_model.BlockDiffusionInferenceModel"

    def __init__(
        self,
        vocab_size: int = 151936,
        hidden_size: int = 2048,
        intermediate_size: int = 6144,
        moe_intermediate_size: int = 768,
        num_hidden_layers: int = 48,
        num_attention_heads: int = 32,
        num_key_value_heads: int = 4,
        head_dim: int = 128,
        attention_bias: bool = False,
        decoder_sparse_step: int = 1,
        mlp_only_layers=None,
        num_experts: int = 128,
        num_experts_total: int = None,
        first_held_expert: int = 0,
        num_experts_per_tok: int = 8,
        norm_topk_prob: bool = True,
        scoring_func: str = "softmax",
        hidden_act: str = "silu",
        rope_theta: float = 1000000.0,
        rope_scaling=None,
        sliding_window=None,
        use_sliding_window: bool = False,
        max_window_layers: int = 48,
        max_position_embeddings: int = 32768,
        initializer_range: float = 0.02,
        rms_norm_eps: float = 1e-6,
        block_length: int = 4,
        denoising_steps: int = 4,
        remasking: str = "low_confidence_static",
        confidence_threshold: float = 0.9,
        mask_token_id: int = 151669,
        **kwargs,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.attention_bias = attention_bias
        self.decoder_sparse_step = decoder_sparse_step
        self.mlp_only_layers = list(mlp_only_layers or [])
        self.num_experts = num_experts
        self.num_experts_total = num_experts if num_experts_total is None else num_experts_total
        self.first_held_expert = first_held_expert
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.scoring_func = scoring_func
        self.hidden_act = hidden_act
        self.rope_theta = float(rope_theta)
        self.rope_scaling = rope_scaling
        self.sliding_window = sliding_window
        self.use_sliding_window = use_sliding_window
        self.max_window_layers = max_window_layers
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.rms_norm_eps = rms_norm_eps
        self.block_length = block_length
        self.denoising_steps = denoising_steps
        self.remasking = remasking
        self.confidence_threshold = confidence_threshold
        kwargs.setdefault("tie_word_embeddings", False)
        super().__init__(mask_token_id=mask_token_id, **kwargs)  # one of the base class's token ids
        self.check()

    def check(self):
        """Refuse what no layer of this port computes, by the mechanism's name."""
        if self.mlp_only_layers or self.decoder_sparse_step != 1:
            raise ValueError("sdar_moe: every layer's MLP is the routed experts (mlp_only_layers [] and "
                             f"decoder_sparse_step 1); got {self.mlp_only_layers}, {self.decoder_sparse_step}")
        if self.scoring_func != "softmax":
            raise ValueError(f"sdar_moe routes with softmax scores and no selection bias; scoring_func={self.scoring_func!r}")
        if self.attention_bias:
            raise ValueError("sdar_moe: attention_bias (biases on q, k, v) is not computed")
        if self.use_sliding_window or self.sliding_window:
            raise ValueError("sdar_moe: a sliding window beside the block mask is not computed")
        if self.rope_scaling is not None:
            raise ValueError(f"sdar_moe: only the plain rotary embedding is computed; rope_scaling={self.rope_scaling}")
        if self.hidden_act != "silu":
            raise ValueError(f"sdar_moe: the experts are SwiGLU; hidden_act={self.hidden_act!r}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("sdar_moe: num_key_value_heads must divide num_attention_heads")
        if not 0 <= self.first_held_expert <= self.num_experts_total - self.num_experts:
            raise ValueError(f"experts held {self.first_held_expert}..+{self.num_experts} lie outside the "
                             f"router's {self.num_experts_total}")
        b = self.block_length
        if b < 1 or b & (b - 1):
            raise ValueError(f"sdar_moe: block_length={b} is not a power of two (the block mask is q | (B - 1))")
        if not 1 <= self.denoising_steps <= b or b % self.denoising_steps:
            raise ValueError(f"sdar_moe: denoising_steps={self.denoising_steps} must divide block_length={b}: every "
                             "denoising pass unmasks block_length / denoising_steps positions")
        if self.remasking not in REMASKING:
            raise ValueError(f"sdar_moe: remasking={self.remasking!r} is not computed; one of {REMASKING}")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"sdar_moe: mask_token_id={self.mask_token_id} lies outside the vocabulary of "
                             f"{self.vocab_size} (a sliced vocabulary names one of its own ids)")

    def layer_kinds(self):
        """The kind of every layer, first to last: all ``gqa_block``."""
        return [GQA_BLOCK] * self.num_hidden_layers

    @property
    def experts_held(self):
        """(first, count) of the routed experts this process holds."""
        return self.first_held_expert, self.num_experts

    def attention_dims(self) -> dict:
        """The sizes of the attention, under the names ``transformers/window_layers.py`` reads."""
        return dict(heads=self.num_attention_heads, kv_heads=self.num_key_value_heads, head_dim=self.head_dim,
                    theta=self.rope_theta, window=None, block=self.block_length)
