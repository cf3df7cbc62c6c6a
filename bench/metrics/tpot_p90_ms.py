"""90th percentile, over the window's requests with more than ``tpot_min_tokens`` output
tokens, of (last token - first token) / (tokens - 1), client side. With some thirty requests
a window it is the fourth worst request: it stands beside ``tpot_mean_ms`` and is not judged."""

NAME = "tpot_p90_ms"
UNIT = "ms"
LAYER = "Scheduler (experimental/engine.py, paged_cache.py)"
MOVES = "tpot_mean_ms"
SOURCE = "host_clock"


def reduce(run):
    return run.get("tpot_p90_ms")
