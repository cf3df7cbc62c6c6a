"""Device time of the serving step programs under the scopes ``indexer`` and ``index_topk``
(``experimental/latent_model.py``: scoring cached positions, finding the k-th largest) over the programs' device time."""

NAME = "indexer_share"
UNIT = "%"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "ttft_p90_ms"
SOURCE = "device_trace"


def reduce(run):
    from bench.harness.latent_scopes import share

    return share(run, ("indexer", "index_topk"))
