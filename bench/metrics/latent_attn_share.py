"""Device time of the serving step programs under the scopes ``latent_gather``, ``mla_attn`` and ``window_attn``
(``experimental/latent_model.py``) over the programs' device time in the traced span."""

NAME = "latent_attn_share"
UNIT = "%"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "ttft_p90_ms"
SOURCE = "device_trace"


def reduce(run):
    from bench.harness.latent_scopes import share

    return share(run, ("latent_gather", "mla_attn", "window_attn"))
