"""Median device duration of the mixed step program's executions in the traced span (one launch feeds one prefill
chunk and one token a decoding slot). Found by XLA module name."""

NAME = "mixed_launch_ms"
UNIT = "ms"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "ttft_p90_ms"
SOURCE = "device_trace"


def reduce(run):
    runs = [d for name in MODULES for d in run.get("trace", {}).get("module_runs_s", {}).get(name, [])]
    if not runs:
        return None
    return sorted(runs)[len(runs) // 2] * 1e3


MODULES = ("jit__mixed_flat_impl",)
