"""Share of the traced span in which no operation ran on the device (1 - union of the
device's operation intervals over the span), mean over the chips used."""

from bench.harness.trace_reduce import idle_share as reduce  # noqa: F401

NAME = "device_idle.train"
UNIT = "%"
LAYER = "Device"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
