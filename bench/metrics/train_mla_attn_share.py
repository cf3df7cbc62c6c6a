"""Device time of the training step program under the scopes ``mla_proj``, ``rope`` and ``mla_attn``
(``transformers/deepseek_v2/modeling.py:DeepseekV2Attention``: the projection chain, the rotation, and the flash
kernels' calls; forward, recomputed and backward operations) over the program's device time in the traced span."""

NAME = "train_mla_attn_share"
UNIT = "%"
LAYER = "Model step, training (transformers/deepseek_v3, latent_layers.py)"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    from bench.harness.train_scopes import MLA_SCOPES, share

    return share(run, MLA_SCOPES)
