"""Device time of the serving step programs (decode and mixed) under ``paged_attn_window`` (the ragged paged kernel
walking a window: a grid sized by the window, over the window plane) and ``kv_write/window_plane`` (the scatter of the
fed tokens' K and V into that plane), over the programs' device time in the traced span
(``experimental/window_model.py``). Beside ``attn_kv_window_share`` it says whether the window layers cost more time than
their share of the keys.
In the program it moves the time of a decode sub-step and of a mixed step alike; the cell reports TTFT alone
(PERF.md section 3), so ``MOVES`` names that."""

NAME = "window_attn_share"
UNIT = "%"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "ttft_p90_ms"
SOURCE = "device_trace"


def reduce(run):
    from bench.harness.window_scopes import WINDOW_ATTN, share

    return share(run, WINDOW_ATTN)
