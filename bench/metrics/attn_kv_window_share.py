"""Cached positions visible to the launches' live rows in the layers that attend a window over those visible in all
layers, over the window: the ledger totals ``attn_kv_window`` / (``attn_kv_window`` + ``attn_kv_full``)
(``/debug/efficiency``, two scrapes; counted on the device, ``WindowedInferenceModel.STATS``). With six window layers of
128 keys to two full layers of thousands it is small; beside ``window_attn_share`` it says whether the window layers
cost more time than their share of the keys.
In the program it moves the attention's part of a step; the cell reports TTFT alone (PERF.md section 3), so ``MOVES``
names that."""

NAME = "attn_kv_window_share"
UNIT = "%"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "ttft_p90_ms"
SOURCE = "program_counter"


def reduce(run):
    from bench.harness.window_scopes import counter_delta

    window, full = counter_delta(run, "attn_kv_window"), counter_delta(run, "attn_kv_full")
    if window is None or full is None or not window + full:
        return None
    return window / (window + full) * 100.0
