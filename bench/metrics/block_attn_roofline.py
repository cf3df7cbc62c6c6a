"""Roofline share of the ragged paged attention kernel under the block mask (generation by diffusion over blocks): the
least time the chip could take to read the cached positions that the rows of the decode launches inside the traced span
could see (their ``attn_kv_visible`` args, already summed over layers and passes; bytes from
``bench/kernels/paged_block_attention.py`` over the HBM bandwidth of ``bench/peaks.json``) over the device time of
``ragged_paged_attention`` in the decode program's runs inside those launches. Bound by bytes. It counts what a row may
see, not what the walk fetched (whole runs of 512 keys), so it cannot pass 100%."""

NAME = "block_attn_roofline"
UNIT = "%"
LAYER = "Kernels (ops/pallas/paged_attention.py)"
MOVES = "tpot_mean_ms"
SOURCE = "device_trace"


def reduce(run):
    from bench.harness import loader
    from bench.harness.diffusion_scopes import config_of, table

    t = table(run)
    if not t or not t["decode"] or not t["decode"]["kernel_ns"]:
        return None
    k = loader.module_from("kernels", "paged_block_attention")
    least = k.least_seconds(t["decode"]["attn_kv_visible"], k.shape_of(config_of(run)), run["peaks"])
    return least / (t["decode"]["kernel_ns"] / 1e9) * 100.0
