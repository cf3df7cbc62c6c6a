"""Bytes the scan layers' one-step recurrence has to move: every row's recurrent
state is read and written once a scan layer and sub-step (``h <- exp(dt A) h +
dt x (x) B``, float32). ``state_rows`` is what the program counts (launch-span
arg and ledger total): rows x sub-steps, dead rows too, since the program
computes them. The recurrence is bound by these bytes, not by FLOPs: a row's
step in one layer is 3 flops an element of state on 8 bytes moved.

shape = {"layers" (scan layers), "heads", "head_dim", "state", "bytes" (of one state element: 4, float32)}"""


def shape_of(config):
    return {"layers": config["hybrid_override_pattern"].count("M"), "heads": config["mamba_num_heads"],
            "head_dim": config["mamba_head_dim"], "state": config["ssm_state_size"], "bytes": 4}


def row_bytes(s):
    """One row's state in one scan layer."""
    return s["heads"] * s["head_dim"] * s["state"] * s["bytes"]


def bytes_moved(state_rows, s):
    """Read and write of every counted row's state, in every scan layer."""
    return state_rows * s["layers"] * row_bytes(s) * 2


def least_seconds(state_rows, s, peaks):
    return bytes_moved(state_rows, s) / peaks["hbm_bytes_per_s"]
