"""Roofline share of the flash attention fwd kernel(s): the least time the chip could take for the
calls seen in the trace (larger of FLOPs over peak and bytes over bandwidth, from
``bench/kernels/flash_attention.py`` at the step's static shapes) over their device time."""

from bench.harness.trace_reduce import roofline_share

NAME = "flash_fwd_roofline"
UNIT = "%"
LAYER = "Kernels (ops/pallas/flash_attention.py)"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
KERNELS = ('flash_attention_fwd',)


def reduce(run):
    return roofline_share(run, "flash_attention", KERNELS)
