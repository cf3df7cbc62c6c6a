"""Device time of the decode program's operations that carry none of the program's scopes
(copies the compiler inserted) over the program's device time: the guard that the scopes stay.
Read by ``bench/harness/program_spans.py``; nothing where the program has no such span or scope."""

NAME = "decode_unscoped_share"
UNIT = "%"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "tpot_mean_ms"
SOURCE = "device_trace"


def reduce(run):
    from bench.harness.program_spans import metric

    return metric(run, NAME)
