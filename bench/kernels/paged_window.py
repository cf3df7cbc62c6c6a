"""Bytes the ragged paged attention kernel (``ops/pallas/paged_attention.py``) has to
read for the cached positions its launches' rows could see, where the layers are of
two kinds (``experimental/window_model.py``): ``attn_kv_full`` and ``attn_kv_window``
are what the program counts on the device (launch-span args and ledger totals), each
already summed over the layers of its kind and over decode sub-steps, so a counted
position is one token's K and V in one layer. It counts what a row may see (a decode
row at context c: c in a full layer, min(c, window) in a window layer), not what the
grid fetched (whole blocks, and the window's first block from its start), so the
share of the roofline it gives cannot pass 100%. Decode attention is bound by these
bytes, not by FLOPs.

shape = {"kv_heads", "head_dim", "bytes" (of one pool element: the weights' precision; the kinds refuse a quantized pool)}"""

import numpy as np


def shape_of(config):
    return {"kv_heads": config["num_key_value_heads"], "head_dim": config["head_dim"],
            "bytes": np.dtype(config["bench"]["precision"]["weights"]).itemsize}


def position_bytes(s):
    """K and V of one cached position in one layer."""
    return 2 * s["kv_heads"] * s["head_dim"] * s["bytes"]


def bytes_read(attn_kv_full, attn_kv_window, s):
    return (attn_kv_full + attn_kv_window) * position_bytes(s)


def least_seconds(attn_kv_full, attn_kv_window, s, peaks):
    return bytes_read(attn_kv_full, attn_kv_window, s) / peaks["hbm_bytes_per_s"]
