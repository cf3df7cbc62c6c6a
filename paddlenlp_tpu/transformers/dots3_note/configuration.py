"""dots3_note configuration: the text decoder of ``dots-studio/dots3-note-prev``.

The published keys are accepted as they are. A decoder layer is one of two
attention kinds, picked by ``layer_types[i]``: ``full_attention`` (latent
attention with a learned top-k indexer) and ``sliding_attention`` (latent
attention with its own ``swa_*`` sizes over a window). Layers before
``first_k_dense_replace`` have a dense MLP, the rest sigmoid-routed experts.

Three keys are this class's own and not the checkpoint's:

- ``n_routed_experts_total``  the router's width (the published
  ``n_routed_experts``) when this process holds only a share of the experts;
- ``first_held_expert``       the first expert of that share: the layer holds
  experts ``first_held_expert .. first_held_expert + n_routed_experts - 1``;
- ``n_routed_experts`` then counts the experts held here.

Left out of this port: the vision and audio towers and the MTP module."""

from __future__ import annotations

from ..configuration_utils import PretrainedConfig

__all__ = ["Dots3NoteConfig"]

FULL, WINDOW = "full_attention", "sliding_attention"  # the published ``layer_types``
KIND_OF = {FULL: "latent_full", WINDOW: "latent_window"}  # the layer kinds of ``transformers/latent_layers.py``


class Dots3NoteConfig(PretrainedConfig):
    model_type = "dots3_note"
    #: the class whose serving step programs compute this configuration's layer
    #: kinds (``experimental/inference_model.py:inference_model_class`` imports it)
    inference_model = "paddlenlp_tpu.experimental.latent_model.LatentInferenceModel"

    def __init__(
        self,
        vocab_size: int = 152064,
        hidden_size: int = 5120,
        intermediate_size: int = 13824,
        moe_intermediate_size: int = 1536,
        num_hidden_layers: int = 46,
        layer_types=None,
        num_attention_heads: int = 128,
        num_key_value_heads: int = 128,
        q_lora_rank: int = 1024,
        kv_lora_rank: int = 512,
        qk_nope_head_dim: int = 128,
        qk_rope_head_dim: int = 64,
        v_head_dim: int = 128,
        rope_theta: float = 80000000.0,
        rope_scaling=None,
        attention_gate_type: str = "headwise",
        apply_mla_qkv_lora_rescale: bool = True,
        attention_bias: bool = False,
        index_n_heads: int = 64,
        index_head_dim: int = 128,
        index_topk: int = 2048,
        sliding_window_size: int = 513,
        swa_num_attention_heads: int = 64,
        swa_num_key_value_heads: int = 64,
        swa_q_lora_rank: int = 1024,
        swa_kv_lora_rank: int = 1024,
        swa_qk_nope_head_dim: int = 192,
        swa_qk_rope_head_dim: int = 64,
        swa_v_head_dim: int = 128,
        swa_rope_theta: float = 50000.0,
        swa_attention_gate_type: str = "headwise",
        first_k_dense_replace: int = 1,
        moe_layer_freq: int = 1,
        n_routed_experts: int = 256,
        n_routed_experts_total: int = None,
        first_held_expert: int = 0,
        n_shared_experts: int = 1,
        num_experts_per_tok: int = 8,
        norm_topk_prob: bool = True,
        routed_scaling_factor: float = 1.0,
        scoring_func: str = "sigmoid",
        topk_method: str = "noaux_tc",
        hidden_act: str = "silu",
        max_position_embeddings: int = 524288,
        initializer_range: float = 0.02,
        rms_norm_eps: float = 1e-5,
        **kwargs,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        if layer_types is None:  # the published pattern: a leading full layer, then full + 3 window
            layer_types = [FULL if i == 0 or i % 4 == 1 else WINDOW for i in range(num_hidden_layers)]
        self.layer_types = list(layer_types)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope_theta = rope_theta
        self.rope_scaling = rope_scaling
        self.attention_gate_type = attention_gate_type
        self.apply_mla_qkv_lora_rescale = apply_mla_qkv_lora_rescale
        self.attention_bias = attention_bias
        self.index_n_heads = index_n_heads
        self.index_head_dim = index_head_dim
        self.index_topk = index_topk
        self.sliding_window_size = sliding_window_size
        self.swa_num_attention_heads = swa_num_attention_heads
        self.swa_num_key_value_heads = swa_num_key_value_heads
        self.swa_q_lora_rank = swa_q_lora_rank
        self.swa_kv_lora_rank = swa_kv_lora_rank
        self.swa_qk_nope_head_dim = swa_qk_nope_head_dim
        self.swa_qk_rope_head_dim = swa_qk_rope_head_dim
        self.swa_v_head_dim = swa_v_head_dim
        self.swa_rope_theta = swa_rope_theta
        self.swa_attention_gate_type = swa_attention_gate_type
        self.first_k_dense_replace = first_k_dense_replace
        self.moe_layer_freq = moe_layer_freq
        self.n_routed_experts = n_routed_experts
        self.n_routed_experts_total = n_routed_experts if n_routed_experts_total is None else n_routed_experts_total
        self.first_held_expert = first_held_expert
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.scoring_func = scoring_func
        self.topk_method = topk_method
        self.hidden_act = hidden_act
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.rms_norm_eps = rms_norm_eps
        kwargs.setdefault("tie_word_embeddings", False)
        super().__init__(**kwargs)
        self.check()

    def check(self):
        """Refuse what no layer of this port computes, by the mechanism's name."""
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(f"layer_types has {len(self.layer_types)} entries for "
                             f"{self.num_hidden_layers} layers")
        unknown = sorted(set(self.layer_types) - {FULL, WINDOW})
        if unknown:
            raise ValueError(f"dots3_note: no layer kind computes layer_types {unknown}")
        if self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc":
            raise ValueError("dots3_note routes with sigmoid scores and a selection bias (noaux_tc); got "
                             f"scoring_func={self.scoring_func!r}, topk_method={self.topk_method!r}")
        if self.rope_scaling is not None:
            raise ValueError("dots3_note: rope_scaling is not computed (the published value is null)")
        if "headwise" != self.attention_gate_type or "headwise" != self.swa_attention_gate_type:
            raise ValueError("dots3_note: only the headwise attention gate is computed")
        if self.attention_bias:
            raise ValueError("dots3_note: attention_bias is not computed (the published value is false)")
        if self.moe_layer_freq != 1 or self.n_shared_experts != 1 or not self.norm_topk_prob:
            raise ValueError("dots3_note: moe_layer_freq 1, one shared expert and norm_topk_prob are what "
                             "the expert layer computes")
        if not 0 <= self.first_held_expert <= self.n_routed_experts_total - self.n_routed_experts:
            raise ValueError(f"experts held {self.first_held_expert}..+{self.n_routed_experts} lie outside the "
                             f"router's {self.n_routed_experts_total}")

    def layer_kinds(self):
        """The kind of every layer, first to last, under the names the serving
        step programs and ``latent_layers`` know (``latent_full`` / ``latent_window``)."""
        return [KIND_OF[t] for t in self.layer_types]

    @property
    def experts_held(self):
        """(first, count) of the routed experts this process holds."""
        return self.first_held_expert, self.n_routed_experts

    def attention_dims(self, kind: str) -> dict:
        """The sizes of one attention kind (``latent_full`` / ``latent_window``), under common names."""
        hidden = self.hidden_size
        if kind == KIND_OF[FULL]:
            d = dict(heads=self.num_attention_heads, q_lora=self.q_lora_rank, kv_lora=self.kv_lora_rank,
                     nope=self.qk_nope_head_dim, rope=self.qk_rope_head_dim, v=self.v_head_dim,
                     theta=float(self.rope_theta), window=None)
        elif kind == KIND_OF[WINDOW]:
            d = dict(heads=self.swa_num_attention_heads, q_lora=self.swa_q_lora_rank,
                     kv_lora=self.swa_kv_lora_rank, nope=self.swa_qk_nope_head_dim,
                     rope=self.swa_qk_rope_head_dim, v=self.swa_v_head_dim,
                     theta=float(self.swa_rope_theta), window=self.sliding_window_size)
        else:
            raise ValueError(kind)
        rescale = self.apply_mla_qkv_lora_rescale
        d["s_q"] = (hidden / d["q_lora"]) ** 0.5 if rescale else 1.0
        d["s_kv"] = (hidden / d["kv_lora"]) ** 0.5 if rescale else 1.0
        return d
