"""Roofline share of the ragged paged attention kernel in decode where the layers are of two kinds: the least time the
chip could take to read the cached positions that the rows of the decode launches inside the traced span could see
(their ``attn_kv_full`` + ``attn_kv_window`` args, already summed over the layers of each kind and over sub-steps; bytes
from ``bench/kernels/paged_window.py`` over the HBM bandwidth of ``bench/peaks.json``) over the device time of
``ragged_paged_attention`` in the decode program's runs inside those launches. Bound by bytes. It counts what a row may
see, not what the grid fetched, so it cannot pass 100%.
In the program it moves the decode sub-step (``tpot_mean_ms`` where a cell reports it); the cell reports TTFT alone
(PERF.md section 3), so ``MOVES`` names that."""

NAME = "paged_kv_roofline"
UNIT = "%"
LAYER = "Kernels (ops/pallas/paged_attention.py)"
MOVES = "ttft_p90_ms"
SOURCE = "device_trace"


def reduce(run):
    from bench.harness import loader
    from bench.harness.window_scopes import config_of, table

    t = table(run)
    if not t or not t["decode"] or not t["decode"]["kernel_ns"]:
        return None
    k = loader.module_from("kernels", "paged_window")
    d = t["decode"]
    least = k.least_seconds(d["attn_kv_full"], d["attn_kv_window"], k.shape_of(config_of(run)), run["peaks"])
    return least / (d["kernel_ns"] / 1e9) * 100.0
