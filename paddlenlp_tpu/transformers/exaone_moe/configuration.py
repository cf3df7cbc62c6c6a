"""exaone_moe configuration: the decoder of ``LGAI-EXAONE/K-EXAONE-236B-A23B``.

The published keys are accepted as they are. Every layer is grouped-query
attention and an MLP under pre-norm residuals; ``layer_types`` says which
layers attend a window (``sliding_attention``: ``sliding_window`` positions, the
query itself counted, q and k rotated at ``rope_parameters.rope_theta``) and
which the whole context (``full_attention``: **nothing rotated**); q and k pass
an RMS norm over each head's dims in both. ``mlp_layer_types`` says which MLPs
are one dense SwiGLU (the first ``first_k_dense_replace``) and which are
sigmoid-routed SwiGLU experts with a selection bias and shared experts.

Three keys are this class's own and not the checkpoint's, as ``Dots3NoteConfig``'s:

- ``num_experts_total``  the router's width (the published ``num_experts``)
  when this process holds only a share of the experts;
- ``first_held_expert``  the first expert of that share;
- ``num_experts`` then counts the experts held here.

Not loaded: the multi-token-prediction block (``num_nextn_predict_layers``,
``mtp_layer_types``, ``mtp_sliding_windows``): one more layer that drafts the
token after next and does not enter the main model's logits. Inert here:
``max_position_embeddings``, ``sliding_window_pattern`` (``layer_types`` is what
is read)."""

from __future__ import annotations

from ..configuration_utils import PretrainedConfig
from ..window_layers import GQA_FULL, GQA_WINDOW

__all__ = ["ExaoneMoeConfig"]

FULL, WINDOW = "full_attention", "sliding_attention"
KIND_OF = {FULL: GQA_FULL, WINDOW: GQA_WINDOW}  # the layer kinds of ``transformers/window_layers.py``


class ExaoneMoeConfig(PretrainedConfig):
    model_type = "exaone_moe"
    #: the class whose serving step programs compute this configuration's layer
    #: kinds (``experimental/inference_model.py:inference_model_class`` imports it)
    inference_model = "paddlenlp_tpu.experimental.window_model.WindowedInferenceModel"
    rope_scaling = None  # rope_parameters.rope_type "default": the plain rotary embedding (``check`` refuses any other)

    def __init__(
        self,
        vocab_size: int = 153600,
        hidden_size: int = 6144,
        intermediate_size: int = 18432,
        moe_intermediate_size: int = 2048,
        num_hidden_layers: int = 48,
        layer_types=None,
        mlp_layer_types=None,
        sliding_windows=None,
        sliding_window: int = 128,
        sliding_window_pattern: str = "LLLG",
        num_attention_heads: int = 64,
        num_key_value_heads: int = 8,
        head_dim: int = 128,
        rope_parameters=None,
        first_k_dense_replace: int = 1,
        num_experts: int = 128,
        num_experts_total: int = None,
        first_held_expert: int = 0,
        num_shared_experts: int = 1,
        num_experts_per_tok: int = 8,
        n_group: int = 1,
        topk_group: int = 1,
        norm_topk_prob: bool = True,
        routed_scaling_factor: float = 2.5,
        scoring_func: str = "sigmoid",
        hidden_act: str = "silu",
        num_nextn_predict_layers: int = 1,
        mtp_layer_types=None,
        mtp_sliding_windows=None,
        max_position_embeddings: int = 262144,
        initializer_range: float = 0.02,
        rms_norm_eps: float = 1e-5,
        **kwargs,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        if layer_types is None:  # the published pattern: three window layers, then a full one
            layer_types = [FULL if i % 4 == 3 else WINDOW for i in range(num_hidden_layers)]
        self.layer_types = list(layer_types)
        if mlp_layer_types is None:
            mlp_layer_types = ["dense" if i < first_k_dense_replace else "sparse" for i in range(num_hidden_layers)]
        self.mlp_layer_types = list(mlp_layer_types)
        if sliding_windows is None:
            sliding_windows = [sliding_window if t == WINDOW else 0 for t in self.layer_types]
        self.sliding_windows = list(sliding_windows)
        self.sliding_window = sliding_window
        self.sliding_window_pattern = sliding_window_pattern
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.rope_parameters = dict(rope_parameters or {"rope_theta": 1000000, "rope_type": "default"})
        self.first_k_dense_replace = first_k_dense_replace
        self.num_experts = num_experts
        self.num_experts_total = num_experts if num_experts_total is None else num_experts_total
        self.first_held_expert = first_held_expert
        self.num_shared_experts = num_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_group = n_group
        self.topk_group = topk_group
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.scoring_func = scoring_func
        self.hidden_act = hidden_act
        self.num_nextn_predict_layers = num_nextn_predict_layers
        self.mtp_layer_types = list(mtp_layer_types or [])
        self.mtp_sliding_windows = list(mtp_sliding_windows or [])
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.rms_norm_eps = rms_norm_eps
        kwargs.setdefault("tie_word_embeddings", False)
        super().__init__(**kwargs)
        self.check()

    def check(self):
        """Refuse what no layer of this port computes, by the mechanism's name."""
        n = self.num_hidden_layers
        for key in ("layer_types", "mlp_layer_types", "sliding_windows"):
            if len(getattr(self, key)) != n:
                raise ValueError(f"{key} has {len(getattr(self, key))} entries for {n} layers")
        unknown = sorted(set(self.layer_types) - set(KIND_OF))
        if unknown:
            raise ValueError(f"exaone_moe: no layer kind computes layer_types {unknown}")
        want = [self.sliding_window if t == WINDOW else 0 for t in self.layer_types]
        if self.sliding_windows != want:
            raise ValueError("exaone_moe: one window for every sliding_attention layer and none for a "
                             f"full_attention layer is what is computed; sliding_windows={self.sliding_windows}")
        if self.mlp_layer_types != ["dense" if i < self.first_k_dense_replace else "sparse" for i in range(n)]:
            raise ValueError("exaone_moe: dense MLPs in the first first_k_dense_replace layers and experts in the "
                             f"rest is what is computed; mlp_layer_types={self.mlp_layer_types}")
        if self.scoring_func != "sigmoid":
            raise ValueError(f"exaone_moe routes with sigmoid scores and a selection bias; scoring_func={self.scoring_func!r}")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError("exaone_moe: group-limited routing (n_group / topk_group > 1) is not computed")
        if self.num_shared_experts != 1 or not self.norm_topk_prob:
            raise ValueError("exaone_moe: one shared expert and norm_topk_prob are what the expert layer computes")
        if self.hidden_act != "silu":
            raise ValueError(f"exaone_moe: the MLPs are SwiGLU; hidden_act={self.hidden_act!r}")
        if self.rope_parameters.get("rope_type", "default") != "default":
            raise ValueError("exaone_moe: only the default rotary embedding is computed; "
                             f"rope_parameters={self.rope_parameters}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("exaone_moe: num_key_value_heads must divide num_attention_heads")
        if not 0 <= self.first_held_expert <= self.num_experts_total - self.num_experts:
            raise ValueError(f"experts held {self.first_held_expert}..+{self.num_experts} lie outside the "
                             f"router's {self.num_experts_total}")

    def layer_kinds(self):
        """The kind of every layer, first to last (``gqa_window`` / ``gqa_full``)."""
        return [KIND_OF[t] for t in self.layer_types]

    @property
    def rope_theta(self):
        return float(self.rope_parameters["rope_theta"])

    @property
    def experts_held(self):
        """(first, count) of the routed experts this process holds."""
        return self.first_held_expert, self.num_experts

    def attention_dims(self) -> dict:
        """The sizes of the attention, under the names ``transformers/window_layers.py`` reads."""
        return dict(heads=self.num_attention_heads, kv_heads=self.num_key_value_heads, head_dim=self.head_dim,
                    theta=self.rope_theta, window=self.sliding_window)
