"""FLOPs a token of a latent-attention decoder with held experts (``deepseek_v3`` as the
configuration file states it), for ``moe_train_mfu``: the published mathematics on this
chip's share, recomputation not counted.

A layer's attention: q (hidden x heads x (nope + rope), no q latent), kv_a (hidden x
(kv_lora + rope)), kv_b (kv_lora x heads x (nope + v)), o (heads x v x hidden); scores at
the query/key head (nope + rope) and values at the value head, causal, so on average half
the sequence: 2 * T/2 * heads * (qk + v) forward. Layers before ``first_k_dense_replace``:
SwiGLU at ``intermediate_size``. The others: the router over all experts, of the
``num_experts_per_tok`` routed SwiGLU experts a token the held share (held / total: what
this chip computes of its own batch), the shared expert. The head over the vocabulary
slice counts, the embedding lookup does not. Backward is twice forward."""


def _total(config):
    return config.get("n_routed_experts_total") or config["n_routed_experts"]


def attention_params(config):
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope, v, lora = (config[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank"))
    return hidden * heads * (nope + rope) + hidden * (lora + rope) + lora * heads * (nope + v) + heads * v * hidden


def expert_layer_params(config):
    """Matmul parameters a token meets in an expert layer's MLP on this chip: router, held share of its routed experts, shared."""
    hidden, width = config["hidden_size"], config["moe_intermediate_size"]
    routed = config["num_experts_per_tok"] * config["n_routed_experts"] / _total(config)
    return hidden * _total(config) + (routed + config["n_shared_experts"]) * 3 * hidden * width


def matmul_params(config):
    layers, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    hidden = config["hidden_size"]
    return (layers * attention_params(config) + dense * 3 * hidden * config["intermediate_size"]
            + (layers - dense) * expert_layer_params(config) + hidden * config["vocab_size"])


def attention_flops_per_token(config, seq_len):
    """Scores and values of one layer, forward, causal."""
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return seq_len * config["num_attention_heads"] * (qk + config["v_head_dim"])


def forward_flops_per_token(config, seq_len):
    return 2 * matmul_params(config) + config["num_hidden_layers"] * attention_flops_per_token(config, seq_len)


def train_flops_per_token(config, seq_len):
    return 3 * forward_flops_per_token(config, seq_len)
