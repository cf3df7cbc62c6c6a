"""Median time between the ends of consecutive optimizer steps in the window, on the
benchmark's own callback clock (the Trainer blocks on each step's loss to log it)."""

NAME = "train_step_ms"
UNIT = "ms"
LAYER = "Trainer (trainer/trainer.py, parallel)"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def reduce(run):
    import statistics

    if run.get("kind") != "train" or len(run["step_ends"]) < 2:
        return None
    ends = run["step_ends"]
    return statistics.median(b - a for a, b in zip(ends, ends[1:])) * 1e3
