"""Share of the traced span in which the device was idle while the loop thread was outside
an engine step: ``loop_intake``, ``loop_finish``, ``loop_idle`` (``serving/engine_loop.py:
_run_iteration``). Each gap between device operations goes to the innermost phase open at it.
Read by ``bench/harness/program_spans.py``; nothing where the program has no such span or scope."""

NAME = "idle_loop_share"
UNIT = "%"
LAYER = "HTTP and admission (serving/api.py, scheduler.py, engine_loop.py)"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def reduce(run):
    from bench.harness.program_spans import metric

    return metric(run, NAME)
