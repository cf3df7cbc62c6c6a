"""The busiest held expert's rows over the mean of the held experts', an expert layer, summed over the window's
steps: ``expert_tokens_max`` x experts held / ``expert_assignments_local`` of the ``train_step`` spans (the program's
own ``TRACER`` ring; the experts held are the configuration's ``n_routed_experts``). 1 is an even load."""

NAME = "train_expert_load_max_over_mean"
UNIT = "ratio"
LAYER = "Model step, training (transformers/deepseek_v3, latent_layers.py)"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def reduce(run):
    from bench.harness.train_scopes import table

    w = table(run)["window"]
    held = run.get("config", {}).get("n_routed_experts")
    return w["expert_tokens_max"] * held / w["expert_assignments_local"] if w and held and w["expert_assignments_local"] else None
