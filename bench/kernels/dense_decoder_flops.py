"""FLOPs a token of a dense decoder (Qwen2 / Llama shape), for MFU.

Matmul parameters count twice a token forward (multiply and add); the output
head counts, the embedding lookup does not. Causal attention adds, a layer,
QK^T and PV over on average half the sequence: 2 * 2 * T/2 * hidden = 2*T*hidden
forward. Backward is twice forward. Recomputation is not counted."""


def matmul_params(config):
    hidden, ffn = config["hidden_size"], config["intermediate_size"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = hidden // heads
    layer = hidden * heads * hd + 2 * hidden * kv * hd + heads * hd * hidden + 3 * hidden * ffn
    return config["num_hidden_layers"] * layer + config["vocab_size"] * hidden


def forward_flops_per_token(config, seq_len):
    attention = 2 * seq_len * config["hidden_size"] * config["num_hidden_layers"]
    return 2 * matmul_params(config) + attention


def train_flops_per_token(config, seq_len):
    return 3 * forward_flops_per_token(config, seq_len)
