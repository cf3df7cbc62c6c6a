"""Device time of the training step program under the scopes ``router``, ``expert_dispatch``, ``expert_mm``,
``expert_combine`` and ``shared_expert`` (``transformers/deepseek_v3/modeling.py:DeepseekV3MoE``,
``latent_layers.experts_grouped``; forward, recomputed and backward operations) over the program's device time in
the traced span."""

NAME = "train_experts_share"
UNIT = "%"
LAYER = "Model step, training (transformers/deepseek_v3, latent_layers.py)"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    from bench.harness.train_scopes import EXPERT_SCOPES, share

    return share(run, EXPERT_SCOPES)
