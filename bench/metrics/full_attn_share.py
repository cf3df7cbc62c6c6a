"""Device time of the serving step programs (decode and mixed) under ``paged_attn`` in a program of the windowed
grouped-query kinds (``experimental/window_model.py``): the ragged paged kernel walking the block table for the layers
that attend the whole context, over the programs' device time in the traced span.
In the program it moves the time of a decode sub-step and of a mixed step alike; the cell reports TTFT alone
(PERF.md section 3), so ``MOVES`` names that, as ``longdoc``'s and ``shortchat``'s metrics do."""

NAME = "full_attn_share"
UNIT = "%"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "ttft_p90_ms"
SOURCE = "device_trace"


def reduce(run):
    from bench.harness.window_scopes import FULL_ATTN, share

    return share(run, FULL_ATTN)
