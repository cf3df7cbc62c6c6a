"""Share of the traced span in which the device was idle while the loop thread was inside a
backend call: the launch spans and their children ``dispatch`` and ``wait``
(``experimental/backend.py``).
Read by ``bench/harness/program_spans.py``; nothing where the program has no such span or scope."""

NAME = "idle_launch_share"
UNIT = "%"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "tpot_mean_ms"
SOURCE = "program_span"


def reduce(run):
    from bench.harness.program_spans import metric

    return metric(run, NAME)
