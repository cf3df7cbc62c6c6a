"""Device time of the serving step programs (decode and mixed) under the scan layers' scopes ``ssm_proj``, ``ssm_conv``,
``ssm_scan``, ``ssm_gate_norm`` and ``state_rw`` (``transformers/state_layers.py``, ``experimental/state_model.py``) over the
programs' device time in the traced span.
In the program it moves the time of a decode sub-step and of a mixed step alike; the cell is judged on TTFT alone
(PERF.md section 7, PR 33), so ``MOVES`` names that, as ``longdoc``'s metrics do."""

NAME = "ssm_share"
UNIT = "%"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "ttft_p90_ms"
SOURCE = "device_trace"


def reduce(run):
    from bench.harness.state_scopes import STATE_SCOPES, share

    return share(run, STATE_SCOPES)
