"""Device time of the serving step programs (decode and mixed) under the scopes ``router``, ``experts`` and
``shared_expert`` (``transformers/latent_layers.py:moe``) over the programs' device time in the traced span."""

NAME = "experts_share"
UNIT = "%"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "ttft_p90_ms"
SOURCE = "device_trace"


def reduce(run):
    from bench.harness.latent_scopes import share

    return share(run, ("router", "experts", "shared_expert"))
