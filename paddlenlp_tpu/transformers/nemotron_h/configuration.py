"""nemotron_h configuration: the hybrid decoder of ``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B``.

The published keys are accepted as they are. Every block is one mixer under a
pre-norm residual, picked by its character of ``hybrid_override_pattern``:
``M`` a Mamba-2 mixer, ``E`` sigmoid-routed relu² experts with one shared
expert, ``*`` grouped-query attention **without a position embedding** (the
family's attention blocks carry none: the scan layers carry position, and the
published ``rope_theta`` / ``partial_rotary_factor`` are defaults its modelling
code does not read). ``-`` (a dense MLP block, other members of the family) is
not computed here and refused by name.

Three keys are this class's own and not the checkpoint's, as ``Dots3NoteConfig``'s:

- ``n_routed_experts_total``  the router's width (the published
  ``n_routed_experts``) when this process holds only a share of the experts;
- ``first_held_expert``       the first expert of that share;
- ``n_routed_experts`` then counts the experts held here.

Inert here (they shape the checkpoint's initialisation or an API, not the
forward pass): ``time_step_min`` / ``max`` / ``floor``, ``rescale_prenorm_residual``,
``expand`` (``mamba_num_heads * mamba_head_dim`` is the inner width),
``num_logits_to_keep``, ``use_mamba_kernels``, ``residual_in_fp32``."""

from __future__ import annotations

from ..configuration_utils import PretrainedConfig
from ..state_layers import ATTENTION, EXPERTS, SSM

__all__ = ["NemotronHConfig"]

KIND_OF = {"M": SSM, "E": EXPERTS, "*": ATTENTION}  # the layer kinds of ``transformers/state_layers.py``
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


class NemotronHConfig(PretrainedConfig):
    model_type = "nemotron_h"
    #: the class whose serving step programs compute this configuration's layer
    #: kinds (``experimental/inference_model.py:inference_model_class`` imports it)
    inference_model = "paddlenlp_tpu.experimental.state_model.StateSpaceInferenceModel"
    #: the attention blocks rotate nothing: read by the step programs' attention
    #: path (``experimental/inference_model.py``), which is the llama kind's own
    rotary_attention = False

    def __init__(
        self,
        vocab_size: int = 131072,
        hidden_size: int = 2688,
        num_hidden_layers: int = 52,
        hybrid_override_pattern: str = PATTERN,
        num_attention_heads: int = 32,
        num_key_value_heads: int = 2,
        head_dim: int = 128,
        attention_bias: bool = False,
        sliding_window=None,
        mamba_num_heads: int = 64,
        mamba_head_dim: int = 64,
        n_groups: int = 8,
        ssm_state_size: int = 128,
        conv_kernel: int = 4,
        chunk_size: int = 128,
        use_conv_bias: bool = True,
        mamba_proj_bias: bool = False,
        mamba_hidden_act: str = "silu",
        intermediate_size: int = 1856,
        moe_intermediate_size: int = 1856,
        moe_shared_expert_intermediate_size: int = 3712,
        mlp_hidden_act: str = "relu2",
        mlp_bias: bool = False,
        use_bias: bool = False,
        n_routed_experts: int = 128,
        n_routed_experts_total: int = None,
        first_held_expert: int = 0,
        n_shared_experts: int = 1,
        num_experts_per_tok: int = 6,
        n_group: int = 1,
        topk_group: int = 1,
        norm_topk_prob: bool = True,
        routed_scaling_factor: float = 2.5,
        norm_eps: float = 1e-5,
        layer_norm_epsilon: float = 1e-5,
        max_position_embeddings: int = 262144,
        initializer_range: float = 0.02,
        rope_theta: float = 10000.0,
        partial_rotary_factor: float = 1.0,
        **kwargs,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.hybrid_override_pattern = hybrid_override_pattern
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.attention_bias = attention_bias
        self.sliding_window = sliding_window
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.n_groups = n_groups
        self.ssm_state_size = ssm_state_size
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.use_conv_bias = use_conv_bias
        self.mamba_proj_bias = mamba_proj_bias
        self.mamba_hidden_act = mamba_hidden_act
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.moe_shared_expert_intermediate_size = moe_shared_expert_intermediate_size
        self.mlp_hidden_act = mlp_hidden_act
        self.mlp_bias = mlp_bias
        self.use_bias = use_bias
        self.n_routed_experts = n_routed_experts
        self.n_routed_experts_total = n_routed_experts if n_routed_experts_total is None else n_routed_experts_total
        self.first_held_expert = first_held_expert
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_group = n_group
        self.topk_group = topk_group
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_eps = norm_eps
        self.layer_norm_epsilon = layer_norm_epsilon
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.rope_theta = rope_theta
        self.partial_rotary_factor = partial_rotary_factor
        kwargs.setdefault("tie_word_embeddings", False)
        super().__init__(**kwargs)
        self.check()

    def check(self):
        """Refuse what no layer of this port computes, by the mechanism's name."""
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.num_hidden_layers:
            raise ValueError(f"hybrid_override_pattern has {len(pattern)} blocks for {self.num_hidden_layers} layers")
        unknown = sorted(set(pattern) - set(KIND_OF))
        if unknown:
            raise ValueError(f"nemotron_h: no layer kind computes blocks {unknown} of hybrid_override_pattern "
                             "('-' is a dense MLP block: not computed here)")
        if self.mlp_hidden_act != "relu2" or self.mamba_hidden_act != "silu":
            raise ValueError("nemotron_h: the experts compute relu(x W_up)^2 W_down and the scan layers SiLU; got "
                             f"mlp_hidden_act={self.mlp_hidden_act!r}, mamba_hidden_act={self.mamba_hidden_act!r}")
        if self.attention_bias or self.mlp_bias or self.use_bias or self.mamba_proj_bias or not self.use_conv_bias:
            raise ValueError("nemotron_h: projections without bias and a convolution with bias are what is "
                             "computed (the published values)")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError("nemotron_h: group-limited routing (n_group / topk_group > 1) is not computed")
        if self.n_shared_experts != 1 or not self.norm_topk_prob:
            raise ValueError("nemotron_h: one shared expert and norm_topk_prob are what the expert layer computes")
        if self.sliding_window is not None:
            raise ValueError("nemotron_h: sliding_window is not computed (the published value is null)")
        if self.norm_eps != self.layer_norm_epsilon:
            raise ValueError("nemotron_h: norm_eps and layer_norm_epsilon differ; one epsilon serves every norm here")
        if self.mamba_num_heads % self.n_groups or (self.mamba_num_heads * self.mamba_head_dim) % self.n_groups:
            raise ValueError("nemotron_h: n_groups must divide mamba_num_heads")
        if not 0 <= self.first_held_expert <= self.n_routed_experts_total - self.n_routed_experts:
            raise ValueError(f"experts held {self.first_held_expert}..+{self.n_routed_experts} lie outside the "
                             f"router's {self.n_routed_experts_total}")

    def layer_kinds(self):
        """The kind of every block, first to last (``ssm`` / ``experts`` / ``attention``)."""
        return [KIND_OF[c] for c in self.hybrid_override_pattern]

    @property
    def rms_norm_eps(self):
        return self.norm_eps

    @property
    def experts_held(self):
        """(first, count) of the routed experts this process holds."""
        return self.first_held_expert, self.n_routed_experts

    def ssm_dims(self) -> dict:
        """The sizes of a scan layer, under the names ``transformers/state_layers.py`` reads."""
        d_in = self.mamba_num_heads * self.mamba_head_dim
        return dict(heads=self.mamba_num_heads, head_dim=self.mamba_head_dim, d_in=d_in, groups=self.n_groups,
                    state=self.ssm_state_size, conv=self.conv_kernel,
                    conv_dim=d_in + 2 * self.n_groups * self.ssm_state_size, chunk=self.chunk_size)
