"""Roofline share of the scan layers' state traffic in decode: the least time the chip could take to read and write
the recurrent state of the rows that the decode launches inside the traced span computed (their ``state_rows`` args:
rows x sub-steps, dead ones too; bytes from ``bench/kernels/ssm_state.py`` over the HBM bandwidth of ``bench/peaks.json``)
over the device time under ``ssm_scan`` and ``state_rw`` inside those launches. Bound by bytes.
In the program it moves the decode sub-step (``tpot_mean_ms`` where a cell reports it); the cell is judged on TTFT alone
(PERF.md section 7, PR 33), so ``MOVES`` names that."""

NAME = "ssm_state_roofline"
UNIT = "%"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "ttft_p90_ms"
SOURCE = "device_trace"


def reduce(run):
    from bench.harness import loader
    from bench.harness.state_scopes import config_of, table

    t = table(run)
    if not t or not t["decode"] or not t["decode"]["traffic_ns"]:
        return None
    k = loader.module_from("kernels", "ssm_state")
    least = k.least_seconds(t["decode"]["state_rows"], k.shape_of(config_of(run)), run["peaks"])
    return least / (t["decode"]["traffic_ns"] / 1e9) * 100.0
