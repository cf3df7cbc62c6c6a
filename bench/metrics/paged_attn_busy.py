"""Device time inside ``ragged_paged_attention`` over the device's busy time in the traced span."""

NAME = "paged_attn_busy"
UNIT = "%"
LAYER = "Kernels (ops/pallas/paged_attention.py)"
MOVES = "tpot_mean_ms"
SOURCE = "device_trace"


def reduce(run):
    from bench.harness.trace_reduce import kernel_seconds

    trace = run.get("trace")
    if not trace or run.get("kind") != "serve":
        return None
    seconds, calls = kernel_seconds(trace, KERNELS)
    return seconds / trace["busy_s"] * 100.0 if calls else None


KERNELS = ("ragged_paged_attention",)
