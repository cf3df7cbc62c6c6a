"""Cached positions the indexer's selection kept for attention over the positions it scored, both counted on the
device by the full layers of every launch in the window: the ledger totals ``index_selected`` / ``index_candidates``
(``/debug/efficiency``, two scrapes)."""

NAME = "index_kept_share"
UNIT = "%"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "ttft_p90_ms"
SOURCE = "program_counter"


def reduce(run):
    from bench.harness.latent_scopes import counter_delta

    kept, scored = counter_delta(run, "index_selected"), counter_delta(run, "index_candidates")
    return kept / scored * 100.0 if scored else None
