"""Mean ``inbox`` span of the requests that finished inside the window (the population of
``queue_wait_mean_ms``, whose histogram is observed at finish): the time a submission lay on
the loop's command inbox while the loop was inside an engine step, before ``add_request``
(``serving/engine_loop.py:_trace_finished``, from ``RequestHandle.submitted_t`` and ``enqueued_t``).
Read by ``bench/harness/program_spans.py``; nothing where the program has no such span or scope."""

NAME = "inbox_wait_mean_ms"
UNIT = "ms"
LAYER = "HTTP and admission (serving/api.py, scheduler.py, engine_loop.py)"
MOVES = "ttft_p90_ms"
SOURCE = "program_span"


def reduce(run):
    from bench.harness.program_spans import metric

    return metric(run, NAME)
