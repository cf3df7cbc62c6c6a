"""Operations and bytes of the flash attention kernels (``ops/pallas/flash_attention.py``)
from their static shapes. Causal: half the score matrix is computed.

shape = {"batch", "seq", "heads", "kv_heads", "head_dim", "bytes" (per element, 2 for bf16)}"""


def shape_of(config, rows, seq_len):
    heads = config["num_attention_heads"]
    return {"batch": rows, "seq": seq_len, "heads": heads, "kv_heads": config["num_key_value_heads"],
            "head_dim": config["hidden_size"] // heads, "bytes": 2}


def _score_flops(s):
    """One [T, T] x head_dim matmul over all heads, causal half: 2*B*H*T*T*D/2."""
    return s["batch"] * s["heads"] * s["seq"] * s["seq"] * s["head_dim"]


def flops(kernel, s):
    # forward: QK^T and PV. dq: recompute QK^T, dP = dO V^T, dQ = dS K. dkv: QK^T, dV = P^T dO,
    # dP = dO V^T, dK = dS^T Q.
    matmuls = {"flash_attention_fwd": 2, "flash_attention_bwd_dq": 3, "flash_attention_bwd_dkv": 4}[kernel]
    return matmuls * _score_flops(s)


def bytes_moved(kernel, s):
    q = s["batch"] * s["seq"] * s["heads"] * s["head_dim"] * s["bytes"]
    kv = s["batch"] * s["seq"] * s["kv_heads"] * s["head_dim"] * s["bytes"]
    stats = s["batch"] * s["seq"] * s["heads"] * 4  # float32 log-sum-exp / delta rows
    if kernel == "flash_attention_fwd":
        return q + 2 * kv + q + stats                      # read q, k, v; write o, lse
    if kernel == "flash_attention_bwd_dq":
        return q + 2 * kv + q + 2 * stats + q              # read q, k, v, do, lse, delta; write dq
    if kernel == "flash_attention_bwd_dkv":
        return q + 2 * kv + q + 2 * stats + 2 * kv         # read q, k, v, do, lse, delta; write dk, dv
    raise KeyError(kernel)


def least_seconds(kernel, s, peaks):
    """The roofline bound of one call: the larger of FLOPs over peak and bytes over bandwidth."""
    return max(flops(kernel, s) / peaks["bf16_flops"], bytes_moved(kernel, s) / peaks["hbm_bytes_per_s"])
