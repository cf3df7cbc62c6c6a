"""Bytes the ragged paged attention kernel under a block mask (``ops/pallas/paged_run_attention.py``, ``block=B``:
generation by diffusion over blocks, ``experimental/block_model.py``) has to read for the cached positions its launches'
rows could see. ``attn_kv_visible`` is what the program counts on the device (launch-span args and ledger totals),
already summed over layers and passes, so a counted position is one token's K and V in one layer: a row that feeds its
block of B positions from ``s`` counts ``s + B`` a layer a pass (its own block whole: the block mask). It counts what
a row may see, not what the walk fetched (whole runs of 512 keys), so the share of the roofline it gives cannot pass
100%. A pass's attention is bound by these bytes, not by FLOPs: 4 queries a KV head's group of 8 against every key.

shape = {"kv_heads", "head_dim", "bytes" (of one pool element: the weights' precision; the kind refuses a quantized pool)}"""

import numpy as np


def shape_of(config):
    return {"kv_heads": config["num_key_value_heads"], "head_dim": config["head_dim"],
            "bytes": np.dtype(config["bench"]["precision"]["weights"]).itemsize}


def position_bytes(s):
    """K and V of one cached position in one layer."""
    return 2 * s["kv_heads"] * s["head_dim"] * s["bytes"]


def bytes_read(attn_kv_visible, s):
    return attn_kv_visible * position_bytes(s)


def least_seconds(attn_kv_visible, s, peaks):
    return bytes_read(attn_kv_visible, s) / peaks["hbm_bytes_per_s"]
