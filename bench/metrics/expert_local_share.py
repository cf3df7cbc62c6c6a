"""Routed choices that landed on experts held here over all routed choices of live tokens in the window: the ledger
totals ``expert_assignments_local`` / ``expert_assignments`` (``/debug/efficiency``, two scrapes). 32 of 256 held: near 12.5."""

NAME = "expert_local_share"
UNIT = "%"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "ttft_p90_ms"
SOURCE = "program_counter"


def reduce(run):
    from bench.harness.latent_scopes import counter_delta

    local, routed = counter_delta(run, "expert_assignments_local"), counter_delta(run, "expert_assignments")
    return local / routed * 100.0 if routed else None
