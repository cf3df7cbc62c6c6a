"""Rows of the launches that fed a token over the rows whose recurrent state the scan layers read and wrote (rows x
decode sub-steps, dead ones too), over the window: the ledger totals ``state_rows_live`` / ``state_rows``
(``/debug/efficiency``, two scrapes). What of the state traffic was for a sequence.
In the program it moves the decode sub-step's useful share; the cell is judged on TTFT alone (PERF.md section 7, PR 33),
so ``MOVES`` names that."""

NAME = "state_rows_live_share"
UNIT = "%"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "ttft_p90_ms"
SOURCE = "program_counter"


def reduce(run):
    from bench.harness.state_scopes import counter_delta

    live, rows = counter_delta(run, "state_rows_live"), counter_delta(run, "state_rows")
    return live / rows * 100.0 if rows else None
