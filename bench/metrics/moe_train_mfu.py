"""Model FLOP/s utilisation of the held-expert training step: tokens/s/chip x FLOPs a token
(``bench/kernels/mla_moe_flops.py``: the published mathematics on this chip's share, forward + backward,
recomputation not counted) over the chip's bf16 peak (``bench/peaks.json``). The cell's share of the whole step."""

NAME = "moe_train_mfu"
UNIT = "%"
LAYER = "Trainer (trainer/trainer.py, parallel)"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def reduce(run):
    from bench.harness import loader

    if run.get("kind") != "train" or "moe_intermediate_size" not in run.get("config", {}):
        return None
    flops = loader.module_from("kernels", "mla_moe_flops").train_flops_per_token(run["config"], run["seq_len"])
    return run["train_tokens_per_s"] * flops / run["peaks"]["bf16_flops"] * 100.0
