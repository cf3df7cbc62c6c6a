"""Roofline share of the held experts' grouped products: the least time the chip could take for the held
assignments the traced steps counted (``expert_assignments_local`` of the ``train_step`` spans inside the
profiler's span; ``bench/kernels/expert_mm.py``: three matrices, forward and both backward products, the larger of
FLOPs over peak and bytes over bandwidth; recomputation and tile padding not counted) over the step program's
device time under the scope ``expert_mm`` (``latent_layers.experts_grouped``)."""

NAME = "expert_mm_roofline"
UNIT = "%"
LAYER = "Model step, training (transformers/deepseek_v3, latent_layers.py)"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    from bench.harness import loader, train_scopes

    t = train_scopes.table(run)
    scopes, traced = t["scopes"], t["traced"]
    if not scopes or not traced or not scopes["ns_by_scope"].get("expert_mm") or not scopes["runs"]:
        return None
    config = run["config"]
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    rows = traced["expert_assignments_local"] / traced["steps"] * scopes["runs"]  # the step runs the trace holds
    least = loader.module_from("kernels", "expert_mm").least_seconds(config, rows, layers * scopes["runs"], run["peaks"])
    return least / (scopes["ns_by_scope"]["expert_mm"] / 1e9) * 100.0
