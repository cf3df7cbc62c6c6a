"""Live rows over padded rows of the decode launches inside the traced span: the sums of the
``rows_live`` and ``rows`` args of their ``decode`` spans (``experimental/backend.py:launch_geometry``).
Read by ``bench/harness/program_spans.py``; nothing where the program has no such span or scope."""

NAME = "batch_occupancy"
UNIT = "%"
LAYER = "Scheduler (experimental/engine.py, paged_cache.py)"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def reduce(run):
    from bench.harness.program_spans import metric

    return metric(run, NAME)
