"""Useful token positions over positions fed to the device in the window, from the
goodput ledger's exact counts (``/debug/efficiency``, difference of two scrapes)."""

NAME = "goodput_ratio"
UNIT = "%"
LAYER = "Scheduler (experimental/engine.py, paged_cache.py)"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def reduce(run):
    if run.get("kind") != "serve":
        return None
    fed = run["after"]["ledger"]["fed"] - run["before"]["ledger"]["fed"]
    if fed <= 0:
        return None
    return (run["after"]["ledger"]["useful"] - run["before"]["ledger"]["useful"]) / fed * 100.0
