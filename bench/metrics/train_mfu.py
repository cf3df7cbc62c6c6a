"""Model FLOP/s utilisation: tokens/s/chip x FLOPs a token (``bench/kernels/dense_decoder_flops.py``:
forward + backward, recomputation not counted) over the chip's bf16 peak (``bench/peaks.json``)."""

NAME = "train_mfu"
UNIT = "%"
LAYER = "Trainer (trainer/trainer.py, parallel)"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def reduce(run):
    from bench.harness import loader

    if run.get("kind") != "train":
        return None
    flops = loader.module_from("kernels", "dense_decoder_flops").train_flops_per_token(
        run["config"], run["seq_len"])
    return run["train_tokens_per_s"] * flops / run["peaks"]["bf16_flops"] * 100.0
