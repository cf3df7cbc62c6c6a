"""Bytes the ragged paged attention kernel (``ops/pallas/paged_attention.py``) has to
read for the KV positions its launches attended, from the launch geometry the program
records (``kv_positions``: for a decode launch the sum over sub-steps and rows still
emitting of context + 1). Decode attention is bound by those bytes, not by FLOPs: a
query row of 12 heads against one position is 6 KFLOP on 1 KiB.

shape = {"layers", "kv_heads", "head_dim", "bytes" (per pool element, 2 for bf16)}"""

import numpy as np


def pool_itemsize(config):
    """Bytes of one element of the KV pool the cell's engine is built with
    (``harness/serve.py:build_engine``): one where the configuration's engine
    quantises the pool (``kv_cache_quant`` int8 or fp8; the scales, one per
    position and head, are left out, so the least time reads low rather than
    high), the weights' precision otherwise."""
    b = config["bench"]
    return 1 if b["engine"].get("kv_cache_quant") else np.dtype(b["precision"]["weights"]).itemsize


def shape_of(config):
    heads = config["num_attention_heads"]
    return {"layers": config["num_hidden_layers"], "kv_heads": config["num_key_value_heads"],
            "head_dim": config["hidden_size"] // heads, "bytes": pool_itemsize(config)}


def bytes_read(kv_positions, s):
    """K and V of every attended position, in every layer."""
    return kv_positions * s["layers"] * 2 * s["kv_heads"] * s["head_dim"] * s["bytes"]


def least_seconds(kv_positions, s, peaks):
    return bytes_read(kv_positions, s) / peaks["hbm_bytes_per_s"]
