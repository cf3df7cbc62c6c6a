"""Roofline share of the flash attention bwd kernels (dq; dk and dv) at two head sizes (query/key 192, value
128): the least time the chip could take for the calls seen in the trace (larger of FLOPs over peak and bytes over
bandwidth, from ``bench/kernels/flash_attention_mla.py``: the unpadded mathematics at the step's static shapes)
over their device time."""

from bench.harness.trace_reduce import roofline_share

NAME = "mla_flash_bwd_roofline"
UNIT = "%"
LAYER = "Kernels (ops/pallas/flash_attention.py)"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
KERNELS = ('flash_attention_bwd_dq', 'flash_attention_bwd_dkv')


def reduce(run):
    if "v_head_dim" not in run.get("config", {}):
        return None
    return roofline_share(run, "flash_attention_mla", KERNELS)
