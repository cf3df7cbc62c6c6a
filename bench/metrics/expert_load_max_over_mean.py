"""The busiest held expert's tokens over the mean of the held experts', per expert layer and launch sub-step, summed
over the window: ``expert_tokens_max`` x experts held / ``expert_assignments_local`` (ledger totals, two scrapes; the
experts held are the run's configuration's ``n_routed_experts``)."""

NAME = "expert_load_max_over_mean"
UNIT = "ratio"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "ttft_p90_ms"
SOURCE = "program_counter"


def reduce(run):
    from bench.harness.latent_scopes import config_of, counter_delta

    most, local = counter_delta(run, "expert_tokens_max"), counter_delta(run, "expert_assignments_local")
    held = (config_of(run) or {}).get("n_routed_experts")
    return most * held / local if local and held else None
