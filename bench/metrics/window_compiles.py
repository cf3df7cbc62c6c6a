"""Programs jax built inside the window (``jax.monitoring`` compile events). Should read 0:
anything else means the cell's warm-up list is short."""

NAME = "window_compiles"
UNIT = "count"
LAYER = "Scheduler (experimental/engine.py, paged_cache.py)"
MOVES = "ttft_p90_ms"
SOURCE = "program_counter"


def reduce(run):
    if run.get("kind") != "serve":
        return None
    return float(run["window_compiles"])
