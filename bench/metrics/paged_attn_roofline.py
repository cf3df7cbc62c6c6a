"""Roofline share of the paged attention kernel in decode: the least time the chip could take
to read the KV positions that the decode launches inside the traced span attended (their
``kv_positions`` args; bytes from ``bench/kernels/paged_attention.py`` over the HBM bandwidth of
``bench/peaks.json``) over the device time of ``ragged_paged_attention`` inside those launches."""

NAME = "paged_attn_roofline"
UNIT = "%"
LAYER = "Kernels (ops/pallas/paged_attention.py)"
MOVES = "tpot_mean_ms"
SOURCE = "device_trace"


def reduce(run):
    import os

    from bench.harness import loader
    from bench.harness.program_spans import tables

    out = tables(run)
    if out is None or not out["kv_positions"] or not out["paged_kernel_s"]:
        return None
    # a serving run carries neither its cell nor its configuration (PERF.md, Open questions):
    # the cell is the one this trace was written for, bench_trace/<workload>
    config = loader.cell(os.path.basename(run["tracer"].dir))["config"]
    k = loader.module_from("kernels", "paged_attention")
    return k.least_seconds(out["kv_positions"], k.shape_of(config), run["peaks"]) / out["paged_kernel_s"] * 100.0
