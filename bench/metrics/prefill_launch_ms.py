"""Mean device duration of the prefill step programs' executions in the traced span, all
length and row buckets pooled (device seconds over launches). Found by XLA module name."""

NAME = "prefill_launch_ms"
UNIT = "ms"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "ttft_p90_ms"
SOURCE = "device_trace"


def reduce(run):
    runs = [d for name in MODULES for d in run.get("trace", {}).get("module_runs_s", {}).get(name, [])]
    if not runs:
        return None
    return sum(runs) / len(runs) * 1e3


MODULES = ("jit__prefill_impl",)
