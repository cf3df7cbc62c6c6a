"""deepseek_v3 configuration (HF ``DeepseekV3Config``): ``deepseek_v2``'s latent
attention with a sigmoid router whose choice is corrected by a bias
(``noaux_tc``), and an expert layer that can be told which experts it holds.

The published keys are accepted as they are. Three keys are this class's own and
not the checkpoint's, the ones ``Dots3NoteConfig`` uses for a share:

- ``n_routed_experts_total``  the router's width (the published
  ``n_routed_experts``) when this process holds only a share of the experts;
- ``first_held_expert``       the first expert of that share: the layer holds
  experts ``first_held_expert .. first_held_expert + n_routed_experts - 1``;
- ``n_routed_experts`` then counts the experts held here (default: all).

What the modules do not compute is refused here, by the mechanism's name."""

from __future__ import annotations

from ..deepseek_v2.configuration import DeepseekV2Config

__all__ = ["DeepseekV3Config"]


class DeepseekV3Config(DeepseekV2Config):
    model_type = "deepseek_v3"

    def __init__(
        self,
        vocab_size: int = 129280,
        hidden_size: int = 7168,
        intermediate_size: int = 18432,
        moe_intermediate_size: int = 2048,
        num_hidden_layers: int = 61,
        num_attention_heads: int = 128,
        n_shared_experts: int = 1,
        n_routed_experts: int = 256,
        n_routed_experts_total: int = None,
        first_held_expert: int = 0,
        routed_scaling_factor: float = 2.5,
        kv_lora_rank: int = 512,
        q_lora_rank: int = 1536,
        qk_rope_head_dim: int = 64,
        v_head_dim: int = 128,
        qk_nope_head_dim: int = 128,
        qk_head_dim: int = None,
        head_dim: int = None,
        topk_method: str = "noaux_tc",
        n_group: int = 8,
        topk_group: int = 4,
        num_experts_per_tok: int = 8,
        first_k_dense_replace: int = 3,
        norm_topk_prob: bool = True,
        scoring_func: str = "sigmoid",
        rope_interleave: bool = True,
        rope_theta: float = 10000.0,
        max_position_embeddings: int = 4096,
        **kwargs,
    ):
        # the published file repeats two derived sizes: qk_head_dim (nope + rope) and head_dim, which HF's class
        # sets to the rope width; here ``head_dim`` stays the cache's contract (deepseek_v2: nope + rope)
        if qk_head_dim not in (None, qk_nope_head_dim + qk_rope_head_dim):
            raise ValueError(f"deepseek_v3: qk_head_dim {qk_head_dim} is not qk_nope_head_dim + qk_rope_head_dim")
        if head_dim not in (None, qk_rope_head_dim, qk_nope_head_dim + qk_rope_head_dim):
            raise ValueError(f"deepseek_v3: head_dim {head_dim} is neither the rope width nor nope + rope")
        kwargs.setdefault("use_scan_layers", False)  # the global default is true; ``check`` refuses a true one
        kwargs.setdefault("aux_loss_alpha", 0.0)  # the bias balances the load; the published file has no loss for it
        self.n_routed_experts_total = n_routed_experts if n_routed_experts_total is None else n_routed_experts_total
        self.first_held_expert = first_held_expert
        self.rope_interleave = rope_interleave
        self.qk_head_dim = qk_nope_head_dim + qk_rope_head_dim
        super().__init__(
            vocab_size=vocab_size, hidden_size=hidden_size, intermediate_size=intermediate_size,
            moe_intermediate_size=moe_intermediate_size, num_hidden_layers=num_hidden_layers,
            num_attention_heads=num_attention_heads, n_shared_experts=n_shared_experts,
            n_routed_experts=n_routed_experts, routed_scaling_factor=routed_scaling_factor,
            kv_lora_rank=kv_lora_rank, q_lora_rank=q_lora_rank, qk_rope_head_dim=qk_rope_head_dim,
            v_head_dim=v_head_dim, qk_nope_head_dim=qk_nope_head_dim, topk_method=topk_method, n_group=n_group,
            topk_group=topk_group, num_experts_per_tok=num_experts_per_tok,
            first_k_dense_replace=first_k_dense_replace, norm_topk_prob=norm_topk_prob, scoring_func=scoring_func,
            rope_theta=rope_theta, max_position_embeddings=max_position_embeddings, **kwargs,
        )
        self.check()

    def check(self):
        """Refuse what no layer of this port computes, by the mechanism's name. Also called when a model is
        built: the trainer's arguments reach the configuration after it is made (``LlmMetaConfig``)."""
        if self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc":
            raise ValueError("deepseek_v3 routes with sigmoid scores and a selection bias (noaux_tc); got "
                             f"scoring_func={self.scoring_func!r}, topk_method={self.topk_method!r}")
        if (self.n_group or 1) > 1:
            raise ValueError(f"deepseek_v3: group-limited routing (n_group={self.n_group}, topk_group="
                             f"{self.topk_group}) is not computed: the router chooses over one group")
        if not self.norm_topk_prob or self.num_experts_per_tok < 2:
            raise ValueError("deepseek_v3: the router normalises the chosen scores (norm_topk_prob) over two or "
                             "more experts a token")
        if not self.rope_interleave:
            raise ValueError("deepseek_v3: rope_interleave=false is not computed (the attention brings "
                             "interleaved pairs to the half layout, as the published files store them)")
        if self.moe_layer_freq != 1 or not self.n_shared_experts:
            raise ValueError("deepseek_v3: moe_layer_freq 1 and a shared expert are what the expert layer computes")
        if self.use_scan_layers:
            raise ValueError("deepseek_v3: use_scan_layers is not computed (a dense layer, then expert layers, "
                             "each named by its index): train with --use_scan_layers false")
        if not 0 <= self.first_held_expert <= self.n_routed_experts_total - self.n_routed_experts:
            raise ValueError(f"experts held {self.first_held_expert}..+{self.n_routed_experts} lie outside the "
                             f"router's {self.n_routed_experts_total}")

    @property
    def experts_held(self):
        """(first, count) of the routed experts this process holds."""
        return self.first_held_expert, self.n_routed_experts
