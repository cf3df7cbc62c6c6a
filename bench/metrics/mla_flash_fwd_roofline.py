"""Roofline share of the flash attention fwd kernel at two head sizes (query/key 192, value 128): the least
time the chip could take for the calls seen in the trace (larger of FLOPs over peak and bytes over bandwidth, from
``bench/kernels/flash_attention_mla.py``: the unpadded mathematics at the step's static shapes) over their device
time. Under full-layer recomputation the forward kernel runs twice a layer; every call seen counts on both sides."""

from bench.harness.trace_reduce import roofline_share

NAME = "mla_flash_fwd_roofline"
UNIT = "%"
LAYER = "Kernels (ops/pallas/flash_attention.py)"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
KERNELS = ('flash_attention_fwd',)


def reduce(run):
    if "v_head_dim" not in run.get("config", {}):
        return None
    return roofline_share(run, "flash_attention_mla", KERNELS)
