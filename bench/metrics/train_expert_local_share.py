"""Routed choices that landed on experts held here over all routed choices of the window's steps: the
``train_step`` spans' ``expert_assignments_local`` / ``expert_assignments`` (the program's own ``TRACER`` ring; counted
on the device, summed over expert layers). 16 of 128 held: near 12.5."""

NAME = "train_expert_local_share"
UNIT = "%"
LAYER = "Model step, training (transformers/deepseek_v3, latent_layers.py)"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def reduce(run):
    from bench.harness.train_scopes import table

    w = table(run)["window"]
    return w["expert_assignments_local"] / w["expert_assignments"] * 100.0 if w and w["expert_assignments"] else None
