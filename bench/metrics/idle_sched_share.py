"""Share of the traced span in which the device was idle while the loop thread was inside an
engine step and outside a backend call: ``admission``, ``prefix_cache``, ``launch_build``, ``emit``,
``step_tail`` (``experimental/engine.py``).
Read by ``bench/harness/program_spans.py``; nothing where the program has no such span or scope."""

NAME = "idle_sched_share"
UNIT = "%"
LAYER = "Scheduler (experimental/engine.py, paged_cache.py)"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def reduce(run):
    from bench.harness.program_spans import metric

    return metric(run, NAME)
