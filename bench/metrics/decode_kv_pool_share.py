"""Device time of the decode program's operations under the scope ``kv_write``
(``experimental/inference_model.py:_layer``) over the program's device time.
Read by ``bench/harness/program_spans.py``; nothing where the program has no such span or scope."""

NAME = "decode_kv_pool_share"
UNIT = "%"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "tpot_mean_ms"
SOURCE = "device_trace"


def reduce(run):
    from bench.harness.program_spans import metric

    return metric(run, NAME)
