"""Mean wait between submission and admission over the window: the difference of two
scrapes of ``paddlenlp_serving_queue_wait_seconds`` (sum over count), which is exact."""

NAME = "queue_wait_mean_ms"
UNIT = "ms"
LAYER = "HTTP and admission (serving/api.py, scheduler.py, engine_loop.py)"
MOVES = "ttft_p90_ms"
SOURCE = "program_counter"


def reduce(run):
    if run.get("kind") != "serve":
        return None
    n = run["after"]["queue_wait_count"] - run["before"]["queue_wait_count"]
    if n <= 0:
        return None
    return (run["after"]["queue_wait_sum"] - run["before"]["queue_wait_sum"]) / n * 1e3
