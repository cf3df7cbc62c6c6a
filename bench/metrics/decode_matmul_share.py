"""Device time of the decode program's operations under the scopes ``qkv``, ``o_proj``, ``mlp``,
``lm_head`` and ``embed`` (``experimental/inference_model.py``) over the program's device time.
Read by ``bench/harness/program_spans.py``; nothing where the program has no such span or scope."""

NAME = "decode_matmul_share"
UNIT = "%"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "tpot_mean_ms"
SOURCE = "device_trace"


def reduce(run):
    from bench.harness.program_spans import metric

    return metric(run, NAME)
