"""Operations and bytes of the flash attention kernels (``ops/pallas/flash_attention.py``)
at two head sizes: a query/key head of ``qk_nope_head_dim + qk_rope_head_dim`` and a value
head of ``v_head_dim`` (latent attention: 192 and 128), every query head with a key head
of its own. The unpadded mathematics, whatever a kernel pads. Causal: half the score
matrix is computed.

shape = {"batch", "seq", "heads", "qk_dim", "v_dim", "bytes" (per element, 2 for bf16)}"""


def shape_of(config, rows, seq_len):
    return {"batch": rows, "seq": seq_len, "heads": config["num_attention_heads"],
            "qk_dim": config["qk_nope_head_dim"] + config["qk_rope_head_dim"], "v_dim": config["v_head_dim"], "bytes": 2}


def _product_flops(s, dim):
    """One [T, T] x dim matmul over all heads, causal half: 2*B*H*T*T*dim/2."""
    return s["batch"] * s["heads"] * s["seq"] * s["seq"] * dim


# products at the query/key head and at the value head:
# forward: QK^T | PV. dq: QK^T again, dQ = dS K | dP = dO V^T. dkv: QK^T, dK = dS^T Q | dV = P^T dO, dP = dO V^T.
PRODUCTS = {"flash_attention_fwd": (1, 1), "flash_attention_bwd_dq": (2, 1), "flash_attention_bwd_dkv": (2, 2)}


def flops(kernel, s):
    at_qk, at_v = PRODUCTS[kernel]
    return at_qk * _product_flops(s, s["qk_dim"]) + at_v * _product_flops(s, s["v_dim"])


def bytes_moved(kernel, s):
    tokens = s["batch"] * s["seq"] * s["heads"]
    qk, v = tokens * s["qk_dim"] * s["bytes"], tokens * s["v_dim"] * s["bytes"]
    stats = tokens * 4  # float32 log-sum-exp / delta rows
    if kernel == "flash_attention_fwd":
        return 2 * qk + v + v + stats                      # read q, k, v; write o, lse
    if kernel == "flash_attention_bwd_dq":
        return 2 * qk + v + v + 2 * stats + qk             # read q, k, v, do, lse, delta; write dq
    if kernel == "flash_attention_bwd_dkv":
        return 2 * qk + v + v + 2 * stats + 2 * (qk + v)   # read q, k, v, do, lse, delta; write dk, dv in float32
    raise KeyError(kernel)


def least_seconds(kernel, s, peaks):
    """The roofline bound of one call: the larger of FLOPs over peak and bytes over bandwidth."""
    return max(flops(kernel, s) / peaks["bf16_flops"], bytes_moved(kernel, s) / peaks["hbm_bytes_per_s"])
